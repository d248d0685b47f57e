"""Integer counters of the program's metrics registry, as they moved over
the window (``run.counters``: the harness snapshots them after warm-up and
again when the window has closed). This is how a metric reads a span or a
ledger inside the program: ``span.<name>.ns`` is the nanoseconds the facade
span ``<name>`` was open (the program counts them while a profiler session
is live, so under ``--trace 1``), ``device.transfer.h2d_bytes`` the bytes
the transfer seams uploaded.

params: {"counter": <name, or a list of names: summed>,
         "minus": [<names whose sum is taken off>],
         "scale": 1e-6, "per": <count name of the window>}: the difference
x scale per unit of the count. None where the first counter named did not
move: on a program that has no such span, and where the sink was off."""

from __future__ import annotations


def read(params: dict, run):
    names = params["counter"]
    names = [names] if isinstance(names, str) else list(names)
    moved = run.counters
    if not moved.get(names[0]):
        return None
    total = sum(moved.get(n, 0) for n in names)
    total -= sum(moved.get(n, 0) for n in params.get("minus", ()))
    per = params.get("per")
    divisor = run.observations.get("counts", {}).get(per) if per else 1
    if not divisor:
        return None
    return total * float(params.get("scale", 1.0)) / divisor
