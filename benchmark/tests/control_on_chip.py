#!/usr/bin/env python3
"""The control and the faults at a cell's own size, on the chip:

    python3 benchmark/tests/control_on_chip.py --workload <cell> \\
        --plant rounded_balances --seed 11 [--seconds 5]

One whole run of the cell (``harness.execute``, through ``ops.install()``
defaults) with the fault planted after the plain reference has been taken;
``correct`` has to come out false: exit code 0 when it does, 1 when the
fault went unseen. One seed a process, as the command, so that every world
is generated before any routing is installed. Not part of any benchmark
run; PERF.md section 2 records what it read."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--plant", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args()

    from benchmark import harness
    from benchmark.tests import faults

    device = harness.device_record()
    if device["platform"] != "tpu":
        print(f"control_on_chip: no TPU, jax found {device}", file=sys.stderr)
        return 3
    patcher = faults.Patcher()

    def install():
        harness.default_install()
        faults.BY_NAME[args.plant](patcher)

    cell = harness.load_cell(args.workload, ROOT)
    result = harness.execute(
        cell, args.seed, args.seconds, False, time.perf_counter(), install
    )
    print(json.dumps({
        "plant": args.plant, "workload": args.workload, "seed": args.seed,
        "correct": result["correct"], "compared": result["compared"],
    }), flush=True)
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main())
