"""Faults of a correlated slashing, for the cell halfway through one:
planted as ``faults.py``'s are, through a patcher with pytest's
``monkeypatch.setattr`` interface, underneath a whole run or a chain of
crossings. Each is a way to get ``process_slashings`` or
``get_eligible_validator_indices`` wrong that a node could ship; the plain
reference (``reference/deneb_epoch_registry.py``) has to call each one
wrong. ``CONTROL`` is the control the cell shares with the others."""

from __future__ import annotations

from benchmark.tests.faults import rounded_balances


def penalty_skipped(monkeypatch):
    """``process_slashings`` left out: nobody at the halfway point pays."""
    from ethereum_consensus_tpu.models import epoch_vector

    monkeypatch.setattr(epoch_vector, "_slashings", lambda ec: None)


def altair_multiplier(monkeypatch):
    """altair's ``PROPORTIONAL_SLASHING_MULTIPLIER_ALTAIR`` (2) in place of
    bellatrix's 3: a row at 31 ETH effective pays 0 where it owes 1 ETH."""
    from ethereum_consensus_tpu.models import epoch_vector

    table = dict(epoch_vector._FORK_CFG)
    table["deneb"] = dict(
        table["deneb"], slash_mult="PROPORTIONAL_SLASHING_MULTIPLIER_ALTAIR"
    )
    monkeypatch.setattr(epoch_vector, "_FORK_CFG", table)


class _WholeVector:
    """A context whose ``EPOCHS_PER_SLASHINGS_VECTOR`` is twice its own, so
    that ``current + vector // 2`` is ``current + vector``."""

    def __init__(self, context):
        self._context = context
        self.EPOCHS_PER_SLASHINGS_VECTOR = 2 * int(context.EPOCHS_PER_SLASHINGS_VECTOR)

    def __getattr__(self, name):
        return getattr(self._context, name)


def penalty_at_withdrawable(monkeypatch):
    """The penalty taken from the rows whose ``withdrawable_epoch`` is the
    current epoch plus the whole vector instead of half of it: the rows at
    the halfway point do not pay."""
    from ethereum_consensus_tpu.models import epoch_vector

    served = epoch_vector._slashings

    def faulty(ec):
        context = ec.context
        ec.context = _WholeVector(context)
        try:
            served(ec)
        finally:
            ec.context = context

    monkeypatch.setattr(epoch_vector, "_slashings", faulty)


def slashed_not_eligible(monkeypatch):
    """``get_eligible_validator_indices`` without its second branch: the
    slashed, exited, not yet withdrawable rows take no flag penalty."""
    from ethereum_consensus_tpu.models import epoch_vector

    served = epoch_vector._sync

    def faulty(state, context, fork):
        ec = served(state, context, fork)
        if ec is not None:
            ec.eligible = ec.active_prev
        return ec

    monkeypatch.setattr(epoch_vector, "_sync", faulty)


FAULTS = [penalty_skipped, altair_multiplier, penalty_at_withdrawable, slashed_not_eligible]
CONTROL = rounded_balances
