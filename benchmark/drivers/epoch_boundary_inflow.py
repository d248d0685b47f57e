"""Traffic kind ``epoch_boundary_inflow``: ``epoch_boundary``'s chains on a
registry that grows. The timed path is that driver's, unchanged
(``process_slots`` over the boundary, then ``hash_tree_root``, device
drained). The untimed advance differs: after the 31 empty slots of the new
epoch and before its participation is filled in, the driver delivers that
epoch's deposits (``world.deposits``: what that epoch's blocks would have
done, as the refill stands for their attestations) by the program's own
``add_validator_to_registry``, the function ``apply_deposit`` calls once the
proof and the signature have passed, with ``eth1_deposit_index`` moved as
``process_deposit`` moves it. So every timed crossing meets a registry that
is ``inflow.per_epoch`` rows longer than the last one met.

The program's ``fused_dispatch_rows`` is imported where this module is
loaded: a program whose fused kernel is dispatched at the registry's exact
length compiles it anew inside every crossing of such a chain, and has no
such function, so it fails here at once instead."""

from __future__ import annotations

import time

from benchmark import harness, meters
from benchmark.driverkit import compared, state_root
from benchmark.drivers.epoch_boundary import SLOTS_PER_EPOCH
from benchmark.drivers.epoch_boundary import Driver as EpochBoundaryDriver
from ethereum_consensus_tpu.models.altair import block_processing
from ethereum_consensus_tpu.models.epoch_vector import fused_dispatch_rows


def deliver(state, deposits: list, context) -> None:
    """What the blocks that carried ``deposits`` leave of them in the state:
    each a new validator (no key among them is in the registry)."""
    for public_key, withdrawal_credentials, amount in deposits:
        state.eth1_deposit_index += 1
        block_processing.add_validator_to_registry(
            state, public_key, withdrawal_credentials, amount, context
        )


class Driver(EpochBoundaryDriver):
    def facts(self) -> dict:
        first = len(self.world.pre.validators)
        last = first + sum(len(batch) for batch in self.world.deposits)
        return {
            **super().facts(),
            "validators_at_last_crossing": last,
            "deposits_per_epoch": len(self.world.deposits[0]),
            "fused_dispatch_rows": [fused_dispatch_rows(first), fused_dispatch_rows(last)],
        }

    def advance(self, state, place: int) -> None:
        """From crossing ``place - 1`` to the last slot of its epoch, that
        epoch's deposits delivered and its participation filled in."""
        with harness.span("advance"):
            self.process_slots(
                state, self.world.target_slot + place * SLOTS_PER_EPOCH - 1
            )
            deliver(state, self.world.deposits[place - 1], self.world.context)
            state.current_epoch_participation = self.refills[place - 1]
            state_root(state)
            meters.device_sync()

    def verify(self, observations: dict) -> list:
        reference = harness.load_module(
            self.cell.root, self.cell.paths, "reference", self.traffic["reference"]
        )
        roots = observations.pop("roots")
        reached = max(place for place, _ in roots)
        t0 = time.perf_counter()
        want = reference.chain_roots(
            self.world.pre, self.world.target_slot,
            self.world.refills[:reached], self.world.deposits[:reached],
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
