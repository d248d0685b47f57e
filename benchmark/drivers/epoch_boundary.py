"""Traffic kind ``epoch_boundary``: closed loop, one client. Each operation
is timed from ``process_slots(state, first slot of the next epoch)``, on a
state at an epoch's last slot, to the end of ``hash_tree_root(state)`` with
the device drained.

The operations come in chains of ``world.chain_epochs`` crossings, as a node
meets them. A chain starts from a copy of the world's state (untimed);
between two crossings the driver advances the 31 empty slots of the new
epoch and sets its participation to the world's next refill (untimed: what
that epoch's blocks would have done). Every chain of a window is the same
work, so the plain reference follows one chain once.

Plain reference: ``benchmark/reference/<traffic "reference">.py``, the same
chain worked out again from the state's plain values with numpy and
hashlib; it imports nothing of the program. It runs once the window has
closed. Compared: every operation's root against the reference's root at
its place in the chain, bit for bit."""

from __future__ import annotations

import time

from benchmark import harness, meters, worlds
from benchmark.driverkit import DriverBase, OpLedger, compared, state_root
from benchmark.worlds import registry

SLOTS_PER_EPOCH = 32


class Driver(DriverBase):
    def prepare(self) -> None:
        self.world = worlds.build(self.cell.config, self.traffic["world"], self.seed)
        self.refills = [flags.tolist() for flags in self.world.refills]

    def process_slots(self, state, slot: int) -> None:
        registry.fork_module(self.world.fork).slot_processing.process_slots(
            state, slot, self.world.context
        )

    def facts(self) -> dict:
        return {
            "validators": len(self.world.pre.validators),
            "target_slot": self.world.target_slot,
            "chain_epochs": len(self.refills) + 1,
            "miss_shares": self.world.miss_shares,
        }

    def cross(self, state, place: int) -> bytes:
        """The timed path: the boundary, then the root."""
        with harness.span("process_slots"):
            self.process_slots(state, self.world.target_slot + place * SLOTS_PER_EPOCH)
        self._t_mid = time.perf_counter()
        with harness.span("root"):
            root = state_root(state)
            meters.device_sync()
        return root

    def advance(self, state, place: int) -> None:
        """From crossing ``place - 1`` to the last slot of its epoch, that
        epoch's participation filled in."""
        with harness.span("advance"):
            self.process_slots(
                state, self.world.target_slot + place * SLOTS_PER_EPOCH - 1
            )
            state.current_epoch_participation = self.refills[place - 1]
            state_root(state)
            meters.device_sync()

    def chains(self):
        """(state, place in its chain) for ever: the untimed part."""
        while True:
            with harness.span("copy"):
                state = self.world.pre.copy()
            for place in range(len(self.refills) + 1):
                if place:
                    self.advance(state, place)
                yield state, place

    def warm_up(self) -> None:
        for _, (state, place) in zip(range(int(self.traffic["warmup_ops"])), self.chains()):
            self.cross(state, place)

    def measure(self, seconds: float) -> dict:
        ledger = OpLedger()
        series = {"boundary_s": [], "transition_s": [], "root_s": []}
        roots = []
        t_open = time.perf_counter()
        deadline = t_open + seconds
        for state, place in self.chains():
            ledger.begin()
            t0 = time.perf_counter()
            try:
                roots.append((place, self.cross(state, place)))
            except Exception as error:  # a crossing that raises is an answer
                roots.append((place, None))
                ledger.end(error=error)
            else:
                t1 = time.perf_counter()
                series["boundary_s"].append(t1 - t0)
                series["transition_s"].append(self._t_mid - t0)
                series["root_s"].append(t1 - self._t_mid)
                ledger.end()
            if time.perf_counter() >= deadline:
                break
        return {
            "window_s": time.perf_counter() - t_open,
            "series": series,
            "counts": {"boundaries": len(roots), "rows": len(self.world.pre.validators)},
            "roots": roots,
            "ops_failed": ledger.failed,
        }

    def verify(self, observations: dict) -> list:
        reference = harness.load_module(
            self.cell.root, self.cell.paths, "reference", self.traffic["reference"]
        )
        roots = observations.pop("roots")
        reached = max(place for place, _ in roots)
        t0 = time.perf_counter()
        want = reference.chain_roots(
            self.world.pre, self.world.target_slot, self.world.refills[:reached]
        )
        harness.log("reference", {
            "s": time.perf_counter() - t0, "roots": [r.hex() for r in want],
            "crossings_compared": len(roots),
        })
        wrong = [(place, root) for place, root in roots if root != want[place]]
        if wrong:
            harness.log("roots_wrong", {
                "first": [(place, root.hex() if root else None) for place, root in wrong[:8]],
            })
        return [compared("boundary_roots_wrong", len(wrong), 0)]
