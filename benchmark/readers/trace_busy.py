"""The device's idle share, from the profiler trace: 100 x idle seconds /
seconds, over the harness spans the metric names. The spans are the timed
work of the cell (``bench:process_slots`` and ``bench:root``), so what the
harness does between operations (a copy, a reset) is in neither term and
the share moves when the layer's host time does. Time is split by the
innermost span that covers it, so no second is counted twice. The whole
window's figure is the result line's ``device.busy_s`` and ``device.window_s``.

params: {"spans": [<span name>, ...]}"""

from __future__ import annotations


def read(params: dict, run):
    trace = run.trace
    if not trace:
        return None
    found = [trace["spans"][n] for n in params["spans"] if n in trace["spans"]]
    seconds = sum(s["seconds"] for s in found)
    idle = sum(s["idle_s"] for s in found)
    if not seconds:
        return None
    return 100.0 * idle / seconds
