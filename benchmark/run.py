#!/usr/bin/env python3
"""One cell, once, in a new process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero and prints no result line where JAX finds no TPU or fewer
chips than the cell asks for; it never falls to the CPU. The last line of
standard output is the result object; everything else is on earlier lines.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload, ROOT)

    import jax

    device = harness.device_record()
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(
            f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); jax "
            f"found {device}", file=sys.stderr,
        )
        return 3
    del jax
    result = harness.execute(
        cell, args.seed, args.seconds, bool(args.trace), T_START
    )
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
