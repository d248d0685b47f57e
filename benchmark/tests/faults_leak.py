"""Faults of an inactivity leak, for the cell on a chain that does not
finalize: planted as ``faults.py``'s are, through a patcher with pytest's
``monkeypatch.setattr`` interface, underneath a whole run or a chain of
crossings. Each is a way to get a leak wrong that a node could ship; the
plain reference (``reference/deneb_epoch_leak.py``) has to call each one
wrong. ``CONTROL`` is the control the cell shares with the other two.

Four of the five are planted in the fused epoch kernel itself:
``fused_epoch_kernel`` is wrapped and the jitted programs are built anew
from the wrapped body (the route, the upload, the planes and the commit are
the served ones)."""

from __future__ import annotations

from benchmark.tests.faults import rounded_balances


def _wrap_fused_kernel(monkeypatch, make_faulty) -> None:
    """``fused_epoch_kernel`` replaced by ``make_faulty(served kernel)``,
    on the host run and, through freshly built jits, on the device route."""
    from ethereum_consensus_tpu.models import epoch_vector

    monkeypatch.setattr(
        epoch_vector, "fused_epoch_kernel",
        make_faulty(epoch_vector.fused_epoch_kernel),
    )
    monkeypatch.setattr(epoch_vector, "_JITTED_KERNELS", {})


# positions in ``fused_epoch_kernel``'s arguments (after ``xp``)
_EFF, _PREV_PART, _SLASHED, _ACTIVE_PREV, _ELIGIBLE, _SCORES = 1, 2, 3, 4, 5, 6
_DENOMINATOR, _RECOVERY_RATE, _WEIGHTS, _LEAKING = 10, 12, 13, 15
_HEAD_FLAG, _TARGET_FLAG = 16, 17


def recovery_in_a_leak(monkeypatch):
    """The recovery rate applied inside a leak: every eligible row's score
    comes out ``min(INACTIVITY_SCORE_RECOVERY_RATE, score)`` lower."""
    def make(served):
        def faulty(xp, *args, **kwargs):
            scores, balances, wrapped = served(xp, *args, **kwargs)
            rate = xp.uint64(args[_RECOVERY_RATE])
            recovered = xp.where(
                args[_ELIGIBLE], scores - xp.minimum(rate, scores), scores
            )
            return recovered, balances, wrapped
        return faulty

    _wrap_fused_kernel(monkeypatch, make)


def flag_rewards_in_a_leak(monkeypatch):
    """Flag rewards paid inside a leak: the kernel is told the chain
    finalizes, with a recovery rate of 0 so that the scores stay right."""
    def make(served):
        def faulty(xp, *args, **kwargs):
            args = list(args)
            args[_LEAKING], args[_RECOVERY_RATE] = False, 0
            return served(xp, *args, **kwargs)
        return faulty

    _wrap_fused_kernel(monkeypatch, make)


def penalty_before_the_update(monkeypatch):
    """The inactivity penalty taken off the scores as they stood before
    ``process_inactivity_updates``: a row that missed the target is handed
    back the difference."""
    def make(served):
        def faulty(xp, *args, **kwargs):
            scores, balances, wrapped = served(xp, *args, **kwargs)
            eff, old = args[_EFF], args[_SCORES]
            target_bit = (
                (args[_PREV_PART] >> xp.uint8(args[_TARGET_FLAG])) & xp.uint8(1)
            ).astype(bool)
            missed = args[_ELIGIBLE] & ~(
                args[_ACTIVE_PREV] & ~args[_SLASHED] & target_bit
            )
            denominator = args[_DENOMINATOR]
            back = eff * scores // denominator - eff * old // denominator
            return scores, xp.where(missed, balances + back, balances), wrapped
        return faulty

    _wrap_fused_kernel(monkeypatch, make)


def head_flag_penalty(monkeypatch):
    """A missed head flag penalised like a missed source or target."""
    def make(served):
        def faulty(xp, *args, **kwargs):
            args = list(args)
            args[_HEAD_FLAG] = len(args[_WEIGHTS])  # no flag is the head flag
            return served(xp, *args, **kwargs)
        return faulty

    _wrap_fused_kernel(monkeypatch, make)


def hysteresis_skipped(monkeypatch):
    """``process_effective_balance_updates`` left out: no effective balance
    steps down."""
    from ethereum_consensus_tpu.models import epoch_vector

    monkeypatch.setattr(epoch_vector, "_effective_balance_updates", lambda ec: None)


FAULTS = [
    recovery_in_a_leak, flag_rewards_in_a_leak, penalty_before_the_update,
    head_flag_penalty, hysteresis_skipped,
]
CONTROL = rounded_balances
