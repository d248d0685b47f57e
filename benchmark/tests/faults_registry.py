"""Faults of the registry's churn, for the cells on a registry that has
one: planted as ``faults.py``'s are, through a patcher with pytest's
``monkeypatch.setattr`` interface, underneath a whole run."""

from __future__ import annotations

from benchmark.tests.faults import after_every_transition

FAR_FUTURE_EPOCH = (1 << 64) - 1


def dropped_activation(monkeypatch):
    """One activation dropped: of the rows the last boundary dequeued, the
    one with the highest index waits on."""
    def drop(state):
        written = int(state.slot) // 32 + 4  # compute_activation_exit_epoch - 1
        hit = [
            i for i, v in enumerate(state.validators)
            if int(v.activation_epoch) == written
        ]
        if hit:
            state.validators[hit[-1]].activation_epoch = FAR_FUTURE_EPOCH

    after_every_transition(monkeypatch, drop)


def queue_in_index_order(monkeypatch):
    """The activation queue taken in index order instead of (eligibility
    epoch, index): the pass's sort of the queue leaves it as it came."""
    import numpy

    from ethereum_consensus_tpu.models import epoch_vector

    class IndexOrder:
        def __getattr__(self, name):
            return getattr(numpy, name)

        @staticmethod
        def argsort(keys, kind=None):
            return numpy.arange(len(keys))

    monkeypatch.setattr(epoch_vector, "_np", IndexOrder)


def exited_row_rewarded(monkeypatch):
    """One exited row paid a reward at every transition."""
    def pay(state):
        index = next(
            i for i, v in enumerate(state.validators) if int(v.exit_epoch) == 0
        )
        state.balances[index] += 12_345

    after_every_transition(monkeypatch, pay)

