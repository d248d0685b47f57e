"""A kernel's share (%) of its memory roofline: the least time the chip
could take to move the kernel's bytes (benchmark/roofline.py, from its
shapes) over the device time the trace shows for it.

params: {"program": <substring of its name>, "bytes_fn": <name in
roofline.BYTES_FNS>, "rows": <count name>}"""

from __future__ import annotations

from benchmark import peaks, roofline
from benchmark.readers.trace_program_time import program_seconds


def read(params: dict, run):
    if not run.trace:
        return None
    found = program_seconds(run.trace, params["program"])
    rows = run.observations.get("counts", {}).get(params["rows"])
    if not found or not found[0] or not rows:
        return None
    seconds, calls = found
    bytes_per_call = roofline.BYTES_FNS[params["bytes_fn"]](rows)
    floor_s = bytes_per_call / peaks.peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (seconds / calls)
