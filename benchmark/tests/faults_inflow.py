"""Faults of a registry that grows, for the cell whose chains append
validators: planted as ``faults.py``'s are, through a patcher with pytest's
``monkeypatch.setattr`` interface, underneath a whole run or a chain of
crossings. Each is a way to get a deposit's new row wrong that a node could
ship; the plain reference (``reference/deneb_epoch_inflow.py``) has to call
each one wrong. ``CONTROL`` is the control the cell shares with the others.

Two are planted under the program's ``add_validator_to_registry``, the
function the driver delivers an epoch's deposits by; two after every
blockless slot advance of the served path."""

from __future__ import annotations

from benchmark.tests.faults import after_every_transition, rounded_balances

FAR_FUTURE_EPOCH = (1 << 64) - 1
SLOTS_PER_EPOCH = 32


def _after_every_new_validator(monkeypatch, alter) -> None:
    """``alter(state)`` after every ``add_validator_to_registry``."""
    from ethereum_consensus_tpu.models.altair import block_processing

    served = block_processing.add_validator_to_registry

    def altered(state, *args, **kwargs):
        served(state, *args, **kwargs)
        alter(state)

    monkeypatch.setattr(block_processing, "add_validator_to_registry", altered)


def new_row_flagged(monkeypatch):
    """A new row given a flag: the newest validator, which has never been
    active, holds the source flag in the list the refill became."""
    def flag(state):
        if int(state.validators[-1].activation_epoch) == FAR_FUTURE_EPOCH:
            state.previous_epoch_participation[-1] |= 0b001

    after_every_transition(monkeypatch, flag)


def stamped_an_epoch_early(monkeypatch):
    """The rows a boundary has just made eligible for the activation queue
    (stamped with the epoch after the one that ended) stamped an epoch
    early."""
    def early(state):
        if int(state.slot) % SLOTS_PER_EPOCH:
            return
        current = int(state.slot) // SLOTS_PER_EPOCH
        for validator in state.validators:
            if int(validator.activation_eligibility_epoch) == current:
                validator.activation_eligibility_epoch = current - 1

    after_every_transition(monkeypatch, early)


def score_entry_not_zero(monkeypatch):
    """A new validator's ``inactivity_scores`` entry starts at
    ``INACTIVITY_SCORE_BIAS`` and not at 0 (no boundary touches the score of
    a row that is not eligible, so it stays)."""
    def biased(state):
        state.inactivity_scores[-1] = 4

    _after_every_new_validator(monkeypatch, biased)


def dropped_score_entry(monkeypatch):
    """Every other new validator gets no ``inactivity_scores`` entry. The
    five lists are then out of step, which the program's epoch pass refuses
    (the columnar pass declines and the literal one raises): a raise inside
    a timed crossing is counted wrong, but the harness's next untimed
    advance raises on that state too, outside the ledger, so this one is
    for a test that walks the chain itself."""
    def drop(state):
        if len(state.validators) % 2:
            state.inactivity_scores.pop()

    _after_every_new_validator(monkeypatch, drop)


def balance_at_the_wrong_index(monkeypatch):
    """A new validator's balance put at the front of ``balances`` instead of
    at its own index: every balance is its neighbour's."""
    def misplace(state):
        state.balances.insert(0, state.balances.pop())

    _after_every_new_validator(monkeypatch, misplace)


FAULTS = [
    new_row_flagged, stamped_an_epoch_early, score_entry_not_zero,
    balance_at_the_wrong_index,
]
CONTROL = rounded_balances
