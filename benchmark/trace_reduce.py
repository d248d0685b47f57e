"""From a profiler trace (xplane) to device busy/idle time, device time by
program and by operation, and idle gaps attributed to the harness's spans.

The yardstick's only reading of the device clock. ``load`` reads an
``.xplane.pb`` with nothing but JAX (``jax.profiler.ProfileData``);
``reduce`` is plain arithmetic over the loaded events, so it can be checked
on a small recorded trace (benchmark/tests/) without a chip.

What a TPU trace holds (seen by hand on the v5e, PERF.md section 6): one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` carries one
event per executed HLO operation and whose line ``XLA Modules`` one event
per executed program (``jit_<name>(<fingerprint>)``); the host's threads
are lines of the plane ``/host:CPU``, where ``jax.profiler.TraceAnnotation``
spans appear under the names given to them. All on one clock, nanoseconds.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
NO_SPAN = "(no harness span)"
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclass
class Events:
    """One line's events: parallel arrays, names interned (a pairing flush
    is millions of events under a few hundred names)."""

    table: list            # distinct names
    index: np.ndarray      # per event, its name's place in ``table``
    start_ns: np.ndarray
    end_ns: np.ndarray

    @classmethod
    def of(cls, events, rename=None) -> "Events":
        """From (name, start_ns, duration_ns) triples or profiler events."""
        table, place, index, start, duration = [], {}, [], [], []
        for event in events:
            if isinstance(event, tuple):
                name, s, d = event
            else:
                name, s, d = event.name, event.start_ns, event.duration_ns
            at = place.get(name)
            if at is None:
                at = place[name] = len(table)
                table.append(rename(name) if rename else name)
            index.append(at)
            start.append(s)
            duration.append(d)
        start = np.array(start, dtype=np.float64)
        return cls(
            table, np.array(index, dtype=np.int64), start,
            start + np.array(duration, dtype=np.float64),
        )

    @property
    def names(self) -> list:
        return [self.table[i] for i in self.index]

    def __len__(self) -> int:
        return len(self.index)

    def seconds_by_name(self, lo: float, hi: float) -> dict:
        """{name: [nanoseconds inside [lo, hi], events inside]}."""
        inside = np.clip(self.end_ns, lo, hi) - np.clip(self.start_ns, lo, hi)
        size = len(self.table)
        total = np.bincount(self.index, weights=inside, minlength=size)
        count = np.bincount(self.index, weights=inside > 0, minlength=size)
        out: dict = {}
        for name, ns, n in zip(self.table, total, count):
            if n:
                entry = out.setdefault(name, [0.0, 0])
                entry[0] += float(ns)
                entry[1] += int(n)
        return out


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)  # plane -> {line -> Events}
    spans: Events = None                         # harness spans, all threads
    inventory: list = field(default_factory=list)  # (plane, line, events)


def find_xplane(log_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    if path.endswith(".xz"):  # the recorded trace of benchmark/tests/
        import lzma

        with lzma.open(path) as handle:
            data = ProfileData.from_serialized_xspace(handle.read())
    else:
        data = ProfileData.from_file(path)
    trace = Trace()
    spans = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if is_device and line.name in (OPS_LINE, MODULES_LINE):
                events = Events.of(
                    line.events,
                    rename=op_name if line.name == OPS_LINE else program_name,
                )
                trace.devices.setdefault(plane.name, {})[line.name] = events
                trace.inventory.append((plane.name, line.name, len(events)))
                continue
            count = 0
            for e in line.events:
                count += 1
                if plane.name == HOST_PLANE and e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, e.start_ns, e.duration_ns))
            trace.inventory.append((plane.name, line.name, count))
    trace.spans = Events.of(spans)
    return trace


def _union(start: np.ndarray, end: np.ndarray):
    """The union of intervals as sorted disjoint (starts, ends)."""
    if len(start) == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    reach = np.maximum.accumulate(end)
    opens = np.concatenate(([True], start[1:] > reach[:-1]))
    closes = np.concatenate((opens[1:], [True]))
    return start[opens], reach[closes]


def _clip(events: Events, lo: float, hi: float):
    start = np.clip(events.start_ns, lo, hi)
    end = np.clip(events.end_ns, lo, hi)
    keep = end > start
    return start[keep], end[keep]


def op_name(event_name: str) -> str:
    """``%fusion.12 = (u32[...]) fusion(...)`` -> ``%fusion.12``: the trace
    prints the whole HLO instruction as the operation's name."""
    return event_name.split(" = ", 1)[0][:80]


def program_name(event_name: str) -> str:
    """``jit_fused(123456)`` -> ``jit_fused``: the fingerprint changes with
    every change to the program, the name does not."""
    return _FINGERPRINT.sub("", event_name)


def _innermost_spans(spans: Events, lo: float, hi: float):
    """Elementary segments of [lo, hi] with the innermost harness span that
    covers each (the covering span that started last), or None."""
    cuts = {lo, hi}
    for s, e in zip(spans.start_ns, spans.end_ns):
        if e > lo and s < hi:
            cuts.add(min(max(s, lo), hi))
            cuts.add(min(max(e, lo), hi))
    cuts = sorted(cuts)
    segments = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        covering = (spans.start_ns <= mid) & (spans.end_ns >= mid)
        name = None
        if covering.any():
            idx = np.flatnonzero(covering)
            name = spans.table[spans.index[idx[np.argmax(spans.start_ns[idx])]]]
        segments.append((a, b, name))
    return segments


def reduce(trace: Trace, top: int = 10) -> dict:
    """Busy and idle seconds of the traced window, averaged over the device
    planes; device seconds by program and by operation; the window's and the
    idle seconds by the innermost harness span that covered them. The window is the ``bench:window`` span
    where the harness wrote one, else the extent of the device events."""
    if not trace.devices:
        raise ValueError(
            "the trace holds no device plane: no operation ran on a device"
        )
    window = [
        (s, e) for n, s, e in zip(
            trace.spans.names, trace.spans.start_ns, trace.spans.end_ns
        ) if n == WINDOW_SPAN
    ]
    if window:
        lo, hi = min(s for s, _ in window), max(e for _, e in window)
    else:
        every = [ev for lines in trace.devices.values() for ev in lines.values()]
        lo = min(float(ev.start_ns.min()) for ev in every if len(ev))
        hi = max(float(ev.end_ns.max()) for ev in every if len(ev))
    busy_ns, programs, ops, gaps = [], {}, {}, {}
    span_ns: dict = {}
    names = trace.spans.names
    not_window = [i for i, n in enumerate(names) if n != WINDOW_SPAN]
    inner = Events.of([
        (names[i], trace.spans.start_ns[i],
         trace.spans.end_ns[i] - trace.spans.start_ns[i])
        for i in not_window
    ])
    segments = _innermost_spans(inner, lo, hi)
    for a, b, name in segments:
        key = name or NO_SPAN
        span_ns[key] = span_ns.get(key, 0.0) + (b - a)

    def add(table: dict, more: dict) -> None:
        for name, (ns, count) in more.items():
            entry = table.setdefault(name, [0.0, 0])
            entry[0] += ns
            entry[1] += count

    for lines in trace.devices.values():
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        u_start, u_end = _union(*_clip(op_events, lo, hi))
        busy_ns.append(float((u_end - u_start).sum()))
        add(ops, op_events.seconds_by_name(lo, hi))
        if MODULES_LINE in lines:
            add(programs, lines[MODULES_LINE].seconds_by_name(lo, hi))
        # idle = the window minus the busy union, split by harness span
        g_start = np.concatenate(([lo], u_end))
        g_end = np.concatenate((u_start, [hi]))
        for a, b, name in segments:
            overlap = np.minimum(g_end, b) - np.maximum(g_start, a)
            idle = float(overlap[overlap > 0].sum())
            if idle > 0:
                key = name or NO_SPAN
                gaps[key] = gaps.get(key, 0.0) + idle
    n = len(trace.devices)

    def ranked(table: dict):
        rows = sorted(
            ((k, v[0] if isinstance(v, list) else v) for k, v in table.items()),
            key=lambda kv: -kv[1],
        )
        return [[k, ns / n / 1e9] for k, ns in rows[:top]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "devices": n,
        "programs": {
            k: {"seconds": v[0] / n / 1e9, "count": v[1]}
            for k, v in programs.items()
        },
        # the window split by innermost harness span: its seconds and the
        # device's idle seconds inside them (a metric names the spans that
        # are its denominator: readers/trace_busy.py)
        "spans": {
            k: {"seconds": float(ns) / 1e9, "idle_s": gaps.get(k, 0.0) / n / 1e9}
            for k, ns in span_ns.items()
        },
        "device_ops": ranked(ops),
        "idle_gaps": ranked(gaps),
    }
