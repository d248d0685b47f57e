#!/usr/bin/env python3
"""A probe outside any cell, on the chip: a node that has only ever seen
finality loses it. How long is its first leaking boundary, and what does it
compile there?

    python3 benchmark/tests/leak_entry_on_chip.py --seed 11 [--crossings 4]

The world is ``mainnet-deneb-1m-leak``'s registry at its own size with the
leak not yet begun: the finalized checkpoint four epochs behind the chain's
first crossing, every score 0, no balance bled, 35 % of the rows offline from
now on. The first crossing still finalizes nothing and is not yet leaking
(finality delay 4); the second is the first leaking one (delay 5). Each
crossing is timed as the cells time theirs (``process_slots`` over the
boundary, then the root, device drained), through ``ops.install()``
defaults, and printed as one JSON line with what jax compiled inside it.
Not part of any benchmark run; PERF.md section 6 records what it read."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/mainnet-deneb-1m-leak.json"
MIN_EPOCHS_TO_INACTIVITY_PENALTY = 4


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--crossings", type=int, default=4)
    parser.add_argument("--validators", type=int, default=None)
    args = parser.parse_args()

    from benchmark import harness, meters, worlds
    from benchmark.driverkit import state_root
    from benchmark.worlds import registry

    device = harness.device_record()
    if device["platform"] != "tpu":
        print(f"leak_entry_on_chip: no TPU, jax found {device}", file=sys.stderr)
        return 3
    with open(os.path.join(ROOT, CONFIG)) as handle:
        config = json.load(handle)
    if args.validators:
        config["validators"] = args.validators
    fin = config["finality"]
    first_epoch = (int(fin["at_slot"]) + 1) // 32 - 1  # the chain's first crossing
    fin["finalized_epoch"] = first_epoch - 1 - MIN_EPOCHS_TO_INACTIVITY_PENALTY
    fin["offline_epochs"] = 0
    fin["online_walk"]["steps"] = 0
    world = worlds.build(
        config,
        {"kind": "leak_edge", "miss_share": [0.01, 0.03],
         "chain_epochs": args.crossings},
        args.seed,
    )
    meter = meters.CompileMeter()
    harness.default_install()
    process_slots = registry.fork_module(world.fork).slot_processing.process_slots
    state = world.pre.copy()
    for place in range(args.crossings):
        slot = world.target_slot + 32 * place
        if place:
            process_slots(state, slot - 1, world.context)
            state.current_epoch_participation = world.refills[place - 1].tolist()
            state_root(state)
        delay = slot // 32 - 2 - int(state.finalized_checkpoint.epoch)
        before = meter.read()
        t0 = time.perf_counter()
        process_slots(state, slot, world.context)
        t1 = time.perf_counter()
        state_root(state)
        meters.device_sync()
        t2 = time.perf_counter()
        after = meter.read()
        print(json.dumps({
            "probe": "leak_entry", "seed": args.seed, "crossing": place,
            "validators": len(state.validators), "finality_delay": delay,
            "leaking": delay > MIN_EPOCHS_TO_INACTIVITY_PENALTY,
            "boundary_s": t2 - t0, "transition_s": t1 - t0, "root_s": t2 - t1,
            "compiled": {k: after[k] - before[k] for k in after},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
