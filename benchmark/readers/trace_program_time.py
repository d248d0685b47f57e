"""Device time of one program, found by its name in the trace.

params: {"program": <substring of the program's name, or a list of them>,
         "scale": 1e3, "per": "call" | <count name>}: seconds x scale per
executed call of the program, or per unit of a count of the window. A list
names the same program under the names it has had: the fused epoch kernel
is jitted from a ``functools.partial``, so the trace prints it as
``jit__unknown`` until the program gives it a name."""

from __future__ import annotations


def program_seconds(trace: dict, needle: str):
    """(seconds, calls) over the programs whose name holds ``needle``."""
    needles = [needle] if isinstance(needle, str) else list(needle)
    hits = [
        v for k, v in trace["programs"].items() if any(n in k for n in needles)
    ]
    if not hits:
        return None
    return sum(h["seconds"] for h in hits), sum(h["count"] for h in hits)


def read(params: dict, run):
    if not run.trace:
        return None
    found = program_seconds(run.trace, params["program"])
    if not found or not found[0]:
        return None
    seconds, calls = found
    per = params.get("per", "call")
    divisor = calls if per == "call" else run.observations.get("counts", {}).get(per)
    if not divisor:
        return None
    return seconds * float(params.get("scale", 1.0)) / divisor
