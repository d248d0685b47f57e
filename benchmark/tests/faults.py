"""The faults and the controls: ways to break the served path underneath a
run. Each takes a patcher with pytest's ``monkeypatch.setattr(obj, name,
value)`` interface, so the tests (on the CPU) and ``control_on_chip.py`` (at
the cells' own size) plant the same thing."""

from __future__ import annotations


class Patcher:
    """``monkeypatch.setattr`` without pytest, for the chip script."""

    def __init__(self):
        self._undo = []

    def setattr(self, obj, name, value) -> None:
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self) -> None:
        while self._undo:
            obj, name, value = self._undo.pop()
            setattr(obj, name, value)


def after_every_transition(monkeypatch, alter) -> None:
    """``alter(state)`` after every blockless slot advance of the served
    path."""
    from ethereum_consensus_tpu.models.deneb import slot_processing

    served = slot_processing.process_slots

    def altered(state, *args, **kwargs):
        served(state, *args, **kwargs)
        alter(state)

    monkeypatch.setattr(slot_processing, "process_slots", altered)


def unchanged_state(monkeypatch):
    """A step that returns its state unchanged."""
    from ethereum_consensus_tpu.models.deneb import slot_processing

    monkeypatch.setattr(slot_processing, "process_slots", lambda *a, **k: None)


def altered_answer(monkeypatch):
    """An answer altered where it is produced: one balance off by one gwei
    after every transition."""
    def off_by_one(state):
        state.balances[0] += 1

    after_every_transition(monkeypatch, off_by_one)


def rounded_balances(monkeypatch):
    """The control: the post-epoch balances approximate (rounded down to 2 gwei) where the configuration says exact."""
    def approximate(state):
        state.balances = [b & ~1 for b in state.balances]

    after_every_transition(monkeypatch, approximate)


BY_NAME = {
    f.__name__: f
    for f in (unchanged_state, altered_answer, rounded_balances)
}
