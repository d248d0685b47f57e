"""What the tests share: the cut to a size the CPU holds."""

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_VALIDATORS = 1 << 12


def tiny(cell):
    """The cell cut to a size the CPU holds: 4,096 validators."""
    cell.config["validators"] = TINY_VALIDATORS
    return cell


def read_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as handle:
        return json.load(handle)
