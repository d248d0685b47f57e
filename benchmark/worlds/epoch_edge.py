"""World kind ``epoch_edge``: a state on the last slot of an epoch, ready to
cross the boundary, with seeded participation.

The registry of ``registry.py`` is advanced blockless (the plain host path)
to the last slot of epoch ``epoch``; then both participation lists are
filled: every validator holds all three flags except a seeded share,
drawn per flag and per list between ``miss_share`` = [low, high], that
misses it. No block, so no signature: BLS is bypassed entirely.

``chain_epochs`` > 1 makes the world a chain of that many crossings: after
a crossing the driver advances the 31 empty slots of the new epoch and sets
its ``current_epoch_participation`` to ``refills[k]``, what that epoch's
blocks would have left there, drawn as above; the next crossing then pays
that epoch's rewards. The reference follows the same chain."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import registry


@dataclass
class EpochEdgeWorld:
    fork: str
    context: object
    pre: object          # at the last slot of the epoch (never mutated)
    target_slot: int     # first slot of the next epoch
    miss_shares: dict    # what the seed drew, for the record
    refills: list        # uint8 flags of every validator, one per later crossing


def participation(seed: int, stream: str, count: int, low: float, high: float):
    rng = registry.rng_for(seed, stream)
    flags = np.full(count, 0b111, dtype=np.uint8)
    shares = []
    for bit in range(3):
        share = float(rng.uniform(low, high))
        shares.append(share)
        missing = rng.random(count) < share
        flags[missing] &= np.uint8(~(1 << bit) & 0xFF)
    return flags, shares


def build(config: dict, world: dict, seed: int) -> EpochEdgeWorld:
    state, context = registry.build_registry_state(config, seed)
    mod = registry.fork_module(config["fork"])
    spe = int(context.SLOTS_PER_EPOCH)
    last_slot = (int(world["epoch"]) + 1) * spe - 1
    mod.slot_processing.process_slots(state, last_slot, context)
    low, high = world["miss_share"]
    count = len(state.validators)
    previous, prev_shares = participation(seed, "previous", count, low, high)
    current, cur_shares = participation(seed, "current", count, low, high)
    state.previous_epoch_participation = previous.tolist()
    state.current_epoch_participation = current.tolist()
    type(state).hash_tree_root(state)  # the root memo travels with copies
    refills = [
        participation(seed, f"refill-{k}", count, low, high)[0]
        for k in range(1, int(world.get("chain_epochs", 1)))
    ]
    return EpochEdgeWorld(
        fork=config["fork"],
        context=context,
        pre=state,
        target_slot=last_slot + 1,
        miss_shares={"previous": prev_shares, "current": cur_shares},
        refills=refills,
    )
