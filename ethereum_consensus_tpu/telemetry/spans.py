"""Structured span recorder: thread-aware ring buffer + Chrome-trace export.

The tracing facade (``utils/trace.py``) stays the only API call sites
use; this module is the recording sink behind it. When recording is off
(the default) the facade never calls in here beyond one attribute read,
so the disabled path costs nothing measurable (guarded by
tests/test_telemetry.py's overhead test).

When recording is on, every ``trace.span`` exit appends one fixed-size
record — name, thread lane, parent span, start/end ``perf_counter``
stamps, the call site's fields, the error repr if the body raised — into
a bounded ``deque`` (oldest spans drop first; spans-in-progress live
only on a per-thread stack). ``chrome_trace()`` renders the buffer as
Chrome trace-event JSON (the ``{"traceEvents": [...]}`` flavor), loadable
in Perfetto / ``chrome://tracing``: each recording thread becomes one
``tid`` lane with its Python thread name as metadata, spans are ``"X"``
complete events in microseconds, point events are ``"i"`` instants. A
pipelined replay therefore renders stage A (the submitting thread) and
the background verifier as separate tracks, with flush dispatch/settle/
verify windows and rollbacks visible.

Thread lanes are small sequential ints (0 = first thread to record, in
practice the main thread) rather than raw ``threading.get_ident()``
values, so the Perfetto track list stays readable; the real ident is
kept in the thread-name metadata.

Besides thread lanes there are **named virtual lanes**
(``named_lane``): tid tracks that belong to no Python thread —
the device observatory (``telemetry/device.py``) renders XLA compiles
and host<->device transfers on a dedicated ``device`` track alongside
the pipeline/verifier thread tracks, via ``add_complete``/
``add_instant`` (pre-timed records appended without touching any
thread's span stack).

Lock discipline (speclint-checked): every write to the recorder's shared
structures holds ``self._lock``; the hot ``enabled`` read and the
per-thread span stack (``threading.local``) stay lock-free.

**Causal trace plane.** Spans only parent within a thread (the TLS
stack), so causality used to die at every cross-thread handoff — pool
admission → flush-window dispatch → verify lane → settle. A
``TraceContext`` is the explicit handoff token across those seams:
``SpanRecorder.context()`` captures the current span as
``(trace_id, span_id, lane, ts)``, the receiving thread brackets its
work in ``adopt(ctx)``, and every top-of-stack span begun under an
adopted context parents to ``ctx.span_id`` and inherits
``ctx.trace_id`` — one flush window becomes one connected tree no
matter how many threads it crossed. A span with no parent and no
adopted context roots its own trace (``trace_id == span_id``).
Cross-lane adoptions additionally record a flow source, rendered by
``chrome_trace()`` as Chrome flow events (``ph:"s"``/``"f"`` arrows
across ``tid`` lanes in Perfetto). The ring drops oldest records when
full as before, but no longer silently: ``dropped`` counts evictions
and mirrors to the ``spans.dropped`` counter. Completed traces noted
via ``note_trace`` feed a bounded worst-N slow-trace ring — the
``/trace`` endpoint's index (docs/OBSERVABILITY.md).

**The profiler sink.** ``profiler_annotation()`` is the facade's second
timing sink: while a ``jax.profiler`` session is live it returns
``jax.profiler.TraceAnnotation`` and the facade opens ``"ect:" + name``
on the calling thread, so program spans land in the xplane on the
device events' clock. The session is the switch; this module never
imports jax (it looks in ``sys.modules``), so a host-only process pays
one dict lookup a span.

**Per-name totals.** While either timing sink is on, ``end`` adds the
span to three integer counters of the metrics registry:
``span.<name>.n``, ``span.<name>.ns`` and ``span.<name>.self_ns``
(``ns`` minus what child spans on the same thread covered: the
per-thread stack that parents spans also carries each open span's
children total). They do not move while no sink is on.

**Counter scopes.** A name declared a scope (``declare_scope``, which
``utils/trace.scope`` calls) qualifies the totals of every span that
ends inside it on the same thread: besides ``span.<name>.*`` such a span
adds to ``span.<scope>/<name>.{n,ns,self_ns}``. The per-thread stack
carries the innermost scope the way it carries ``child_ns``, so a scope
is no second recorder, only a second name for totals that exist anyway.

**Garbage collection.** ``gc.callbacks`` holds ``GcWatch.on_gc`` from
import on: it always counts ``gc.collections.gen{0,1,2}`` and
``gc.pause_ns``, and while a timing sink is on it makes each collection
a ``gc.collect`` span (fields ``generation``, ``collected``) on the
thread that ran it, so a trace shows a collection where it fell. A
collection can start inside any allocation, this module's own locked
sections included, so the hook takes no lock a caller may hold: it
touches the thread's stack, counters made before it runs, and leaves
what needs a lock (the ring, a qualified total named for the first
time) to the next span that ends.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager

from . import metrics as _metrics

__all__ = [
    "SpanRecord",
    "SpanRecorder",
    "TraceContext",
    "RECORDER",
    "PROFILER_PREFIX",
    "profiler_annotation",
    "DEFAULT_CAPACITY",
    "SLOW_TRACE_RING",
    "is_recording",
    "start_recording",
    "stop_recording",
    "recording",
    "write_chrome_trace",
    "GC_WATCH",
]

DEFAULT_CAPACITY = 1 << 16

# worst-N slow-trace ring size (completed traces, by duration)
SLOW_TRACE_RING = 32

# what a reduction of the xplane filters program spans on: the host plane
# also holds jax's own TraceMes and the benchmark's ``bench:`` spans
PROFILER_PREFIX = "ect:"


def profiler_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session is live,
    else None. Never imports jax: a session can only be live where
    ``jax.profiler`` is already in ``sys.modules``."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    annotation = profiler.TraceAnnotation
    return annotation if annotation.is_enabled() else None


class TraceContext:
    """Immutable cross-thread handoff token: ``trace_id`` names the
    causal tree, ``span_id`` the parent span the receiving side should
    link under, ``lane``/``ts`` the handoff origin (the flow-arrow
    source in the Chrome trace). Captured with ``context()`` on the
    sending thread, passed explicitly (a ticket field, a closure arg —
    never ambient), adopted with ``adopt(ctx)`` on the receiving
    thread."""

    __slots__ = ("trace_id", "span_id", "lane", "ts")

    def __init__(self, trace_id: int, span_id: int, lane: int, ts: float):
        self.trace_id = trace_id
        self.span_id = span_id
        self.lane = lane
        self.ts = ts

    def __repr__(self) -> str:
        return (
            f"TraceContext(trace={self.trace_id}, span={self.span_id}, "
            f"lane={self.lane})"
        )


class SpanRecord:
    """One completed span (or, transiently, one in progress on its
    thread's stack). ``parent_id`` is 0 for top-level spans; parents are
    resolved per thread at begin time, so cross-thread work (the
    verifier) starts its own tree."""

    __slots__ = (
        "span_id",
        "parent_id",
        "trace_id",
        "name",
        "lane",
        "t0",
        "t1",
        "fields",
        "error",
        "flow_src",
        "child_ns",
        "ring",
        "annotation",
        "scope",
    )

    def __init__(self, span_id: int, parent_id: int, name: str, lane: int,
                 t0: float, fields: dict, trace_id: int = 0):
        self.span_id = span_id
        self.parent_id = parent_id
        # the causal tree this span belongs to: its own span_id when it
        # roots a fresh trace, the adopted/inherited trace_id otherwise
        self.trace_id = trace_id or span_id
        self.name = name
        self.lane = lane
        self.t0 = t0
        self.t1 = t0
        self.fields = fields
        self.error = None
        # (src_span_id, src_lane, src_ts) when this span was begun under
        # a context adopted from another lane — the flow-arrow source
        self.flow_src = None
        # the nanoseconds this span's ended children (same thread)
        # covered: what ``span.<name>.self_ns`` subtracts
        self.child_ns = 0
        # whether the ring keeps it (recording on at begin), or only the
        # totals do (a profiler session alone)
        self.ring = True
        # the profiler's open annotation of this span, while a session is
        # live: where ``note`` sends the fields the body learns late
        self.annotation = None
        # the scope whose qualified totals this span's children add to:
        # its own name if it is a declared scope, else its parent's
        self.scope = None

    @property
    def duration_s(self) -> float:
        return max(0.0, self.t1 - self.t0)


class _EventRecord:
    __slots__ = ("name", "lane", "ts", "fields")

    def __init__(self, name: str, lane: int, ts: float, fields: dict):
        self.name = name
        self.lane = lane
        self.ts = ts
        self.fields = fields


def _json_safe(value):
    if isinstance(value, (bool, int, float, str)) or value is None:
        return value
    return str(value)


class SpanRecorder:
    """In-process ring-buffer recorder; one module-level instance
    (``RECORDER``) serves the whole process."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._capacity = capacity
        self._spans: deque = deque(maxlen=capacity)
        self._events: deque = deque(maxlen=capacity)
        self._lanes: dict = {}        # thread ident -> small lane int
        self._lane_names: dict = {}   # lane int -> thread name
        self._tls = threading.local()
        self._ids = itertools.count(1)
        self._t0 = 0.0                # perf_counter origin of the recording
        self._wall0 = 0.0             # wall-clock at start (metadata only)
        self._slow: list = []         # worst-N completed traces, ascending
        self._totals: dict = {}       # span name -> its three counters
        self._scoped: dict = {}       # (scope, span name) -> three counters
        self._scopes: set = set()     # names declared scopes
        self.dropped = 0              # ring evictions (spans + events)
        self.enabled = False

    # -- lifecycle -----------------------------------------------------------
    def start(self, capacity: "int | None" = None) -> None:
        """Begin a fresh recording (drops any previous buffer)."""
        with self._lock:
            if capacity is not None and capacity != self._capacity:
                self._capacity = capacity
                self._spans = deque(maxlen=capacity)
                self._events = deque(maxlen=capacity)
            else:
                self._spans.clear()
                self._events.clear()
            self._lanes.clear()
            self._lane_names.clear()
            self._slow = []
            self.dropped = 0
            self._t0 = time.perf_counter()
            self._wall0 = time.time()
            self.enabled = True

    def stop(self) -> None:
        with self._lock:
            self.enabled = False

    # -- recording (called from the trace facade) ---------------------------
    def _lane(self) -> int:
        ident = threading.get_ident()
        lane = self._lanes.get(ident)
        if lane is None:
            with self._lock:
                lane = self._lanes.get(ident)
                if lane is None:
                    lane = len(self._lanes)
                    self._lanes[ident] = lane
                    self._lane_names[lane] = (
                        f"{threading.current_thread().name} ({ident})"
                    )
        return lane

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def begin(self, name: str, fields: dict) -> SpanRecord:
        """Open a span on this thread's stack. The facade calls this
        while either timing sink is on; the ring keeps the record only
        if recording was on here, the per-name totals count it either
        way."""
        stack = self._stack()
        lane = self._lane()
        flow_src = None
        parent = stack[-1] if stack else None
        if parent is not None:
            # in-thread nesting wins: parent is the enclosing span
            parent_id = parent.span_id
            trace_id = parent.trace_id
        else:
            ctx = getattr(self._tls, "adopted", None)
            if ctx is not None:
                # cross-seam handoff: link under the sender's span
                parent_id = ctx.span_id
                trace_id = ctx.trace_id
                if ctx.lane != lane:
                    flow_src = (ctx.span_id, ctx.lane, ctx.ts)
            else:
                parent_id = 0
                trace_id = 0  # self-rooted: SpanRecord uses its span_id
        rec = SpanRecord(
            span_id=next(self._ids),
            parent_id=parent_id,
            name=name,
            lane=lane,
            t0=time.perf_counter(),
            fields=fields,
            trace_id=trace_id,
        )
        rec.flow_src = flow_src
        rec.ring = self.enabled
        if name in self._scopes:
            rec.scope = name
        elif parent is not None:
            rec.scope = parent.scope
        stack.append(rec)
        return rec

    def declare_scope(self, name: str) -> None:
        """Make ``name`` a counter scope: every span that ends inside a
        span of that name, on its thread, also adds to
        ``span.<name>/<its name>.*``."""
        with self._lock:
            self._scopes.add(name)

    def is_scope(self, name: str) -> bool:
        return name in self._scopes

    def note(self, fields: dict) -> None:
        """Add ``fields`` to the innermost span open on this thread: the
        ring's record and, while a profiler session is live, the xplane's
        event. Nothing is open where no timing sink is on."""
        stack = getattr(self._tls, "stack", None)
        if not stack:
            return
        rec = stack[-1]
        rec.fields.update(fields)
        if rec.annotation is not None:
            rec.annotation.set_metadata(**fields)

    def end(self, rec: SpanRecord, error: "str | None" = None) -> None:
        rec.t1 = time.perf_counter()
        rec.error = error
        if GC_WATCH.pending:
            self._settle_collections()
        stack = self._stack()
        # the facade pairs begin/end via try/finally, so rec is the top;
        # remove by identity anyway in case a caller misnests
        if stack and stack[-1] is rec:
            stack.pop()
        else:  # pragma: no cover - defensive
            try:
                stack.remove(rec)
            except ValueError:
                pass
        ns = max(0, round((rec.t1 - rec.t0) * 1e9))
        own_ns = max(0, ns - rec.child_ns)
        scope = None
        if stack:  # what is now on top encloses this span
            top = stack[-1]
            top.child_ns += ns
            scope = top.scope
        _add_totals(self._span_totals(rec.name), ns, own_ns)
        if scope is not None:
            _add_totals(self._scoped_totals(scope, rec.name), ns, own_ns)
        if rec.ring:
            self._append_span(rec)

    def _span_totals(self, name: str) -> tuple:
        """The ``span.<name>.{n,ns,self_ns}`` counters, looked up once a
        name."""
        totals = self._totals.get(name)
        if totals is None:
            totals = _make_totals(name)
            with self._lock:
                self._totals[name] = totals
        return totals

    def _scoped_totals(self, scope: str, name: str) -> tuple:
        """The ``span.<scope>/<name>.{n,ns,self_ns}`` counters."""
        key = (scope, name)
        totals = self._scoped.get(key)
        if totals is None:
            totals = _make_totals(f"{scope}/{name}")
            with self._lock:
                self._scoped[key] = totals
        return totals

    # -- garbage collection (GcWatch calls these inside its hook) -----------
    def gc_begin(self, generation: int, annotate) -> "SpanRecord | None":
        """Open a ``gc.collect`` span on this thread's stack without a
        lock: None on a thread that never opened a span (its lane would
        need the lock)."""
        lane = self._lanes.get(threading.get_ident())
        if lane is None:
            return None
        stack = self._stack()
        parent = stack[-1] if stack else None
        rec = SpanRecord(
            span_id=next(self._ids),
            parent_id=parent.span_id if parent is not None else 0,
            name=GC_SPAN,
            lane=lane,
            t0=time.perf_counter(),
            fields={"generation": generation},
            trace_id=parent.trace_id if parent is not None else 0,
        )
        rec.ring = self.enabled
        if parent is not None:
            rec.scope = parent.scope
        if annotate is not None:
            rec.annotation = annotate(PROFILER_PREFIX + GC_SPAN,
                                      generation=generation)
            rec.annotation.__enter__()
        stack.append(rec)
        return rec

    def gc_end(self, rec: SpanRecord, collected: int) -> None:
        """Close the span ``gc_begin`` opened: the totals made before the
        hook ran move now; the ring's record and a qualified total named
        for the first time wait in ``GC_WATCH.pending`` for the next span
        that ends (``_settle_collections``)."""
        rec.t1 = time.perf_counter()
        rec.fields["collected"] = collected
        if rec.annotation is not None:
            rec.annotation.set_metadata(collected=collected)
            rec.annotation.__exit__(None, None, None)
        stack = self._stack()
        if stack and stack[-1] is rec:
            stack.pop()
        else:  # pragma: no cover - a finalizer left a span open
            try:
                stack.remove(rec)
            except ValueError:
                pass
        ns = max(0, round((rec.t1 - rec.t0) * 1e9))
        own_ns = max(0, ns - rec.child_ns)
        scope = None
        if stack:
            top = stack[-1]
            top.child_ns += ns
            scope = top.scope
        _add_totals(GC_WATCH.totals, ns, own_ns)
        if scope is not None:
            totals = self._scoped.get((scope, GC_SPAN))
            if totals is not None:
                _add_totals(totals, ns, own_ns)
                scope = None
        if scope is not None or rec.ring:
            GC_WATCH.pending.append((rec, scope, ns, own_ns))

    def _settle_collections(self) -> None:
        pending = GC_WATCH.pending
        while pending:
            try:
                rec, scope, ns, own_ns = pending.popleft()
            except IndexError:  # pragma: no cover - another thread took it
                return
            if scope is not None:
                _add_totals(self._scoped_totals(scope, GC_SPAN), ns, own_ns)
            if rec.ring:
                self._append_span(rec)

    def _append_span(self, rec: SpanRecord) -> None:
        dropped = False
        with self._lock:
            if len(self._spans) == self._capacity:
                self.dropped += 1
                dropped = True
            self._spans.append(rec)
        if dropped:
            _metrics.counter("spans.dropped").inc()

    def event(self, name: str, fields: dict) -> None:
        rec = _EventRecord(name, self._lane(), time.perf_counter(), fields)
        self._append_event(rec)

    def _append_event(self, rec: _EventRecord) -> None:
        dropped = False
        with self._lock:
            if len(self._events) == self._capacity:
                self.dropped += 1
                dropped = True
            self._events.append(rec)
        if dropped:
            _metrics.counter("spans.dropped").inc()

    # -- causal trace plane --------------------------------------------------
    def context(self) -> "TraceContext | None":
        """The current causal position as a handoff token: the top of
        this thread's span stack if one is open (the common case — call
        inside the span that should parent the downstream work), else
        the context this thread itself adopted, else None."""
        stack = getattr(self._tls, "stack", None)
        if stack:
            top = stack[-1]
            return TraceContext(
                top.trace_id, top.span_id, top.lane, time.perf_counter()
            )
        return getattr(self._tls, "adopted", None)

    @contextmanager
    def adopt(self, ctx: "TraceContext | None"):
        """Bracket the receiving side of a handoff: top-of-stack spans
        begun inside the block parent to ``ctx.span_id`` and inherit its
        trace. Nests (the previous adoption is restored on exit); TLS
        only, so it is lock-free."""
        prev = getattr(self._tls, "adopted", None)
        self._tls.adopted = ctx
        try:
            yield ctx
        finally:
            self._tls.adopted = prev

    def note_trace(self, trace_id: int, name: str, duration_s: float,
                   fields: "dict | None" = None) -> None:
        """Feed the worst-N slow-trace ring: called once per completed
        trace (the pipeline notes each settled window, the pool each
        settled flush) with its end-to-end duration."""
        entry = {
            "trace_id": trace_id,
            "name": name,
            "duration_s": duration_s,
        }
        if fields:
            entry.update({k: _json_safe(v) for k, v in fields.items()})
        with self._lock:
            slow = self._slow
            if len(slow) < SLOW_TRACE_RING:
                slow.append(entry)
                slow.sort(key=lambda e: e["duration_s"])
            elif duration_s > slow[0]["duration_s"]:
                slow[0] = entry
                slow.sort(key=lambda e: e["duration_s"])

    def slow_traces(self) -> "list[dict]":
        """The worst-N completed traces, slowest first (consistent
        copy)."""
        with self._lock:
            return [dict(e) for e in reversed(self._slow)]

    def trace_records(self, trace_id: int) -> "list[SpanRecord]":
        """Completed spans belonging to ``trace_id`` (consistent copy,
        sorted by start time)."""
        with self._lock:
            spans = [r for r in self._spans if r.trace_id == trace_id]
        spans.sort(key=lambda r: r.t0)
        return spans

    def trace_tree(self, trace_id: int) -> dict:
        """One trace assembled as a JSON-ready causal tree: its spans
        (start-ordered), root/orphan accounting, and the wall window it
        covered. ``connected`` is the gate the tests and the ``/trace``
        endpoint assert: at least one span, exactly one root, zero
        orphans (an orphan parents to a span id absent from the
        trace)."""
        spans = self.trace_records(trace_id)
        ids = {r.span_id for r in spans}
        roots = sum(1 for r in spans if r.parent_id == 0)
        orphans = sum(
            1 for r in spans if r.parent_id and r.parent_id not in ids
        )
        t0 = self._t0
        out_spans = []
        for rec in spans:
            d = {
                "span_id": rec.span_id,
                "parent_id": rec.parent_id,
                "name": rec.name,
                "lane": rec.lane,
                "t0_s": max(0.0, rec.t0 - t0),
                "duration_s": rec.duration_s,
                "fields": {k: _json_safe(v) for k, v in rec.fields.items()},
            }
            if rec.error is not None:
                d["error"] = rec.error
            if rec.flow_src is not None:
                d["flow_from"] = {
                    "span_id": rec.flow_src[0],
                    "lane": rec.flow_src[1],
                }
            out_spans.append(d)
        return {
            "trace_id": trace_id,
            "spans": out_spans,
            "span_count": len(spans),
            "roots": roots,
            "orphans": orphans,
            "connected": bool(spans) and roots == 1 and orphans == 0,
            "t0_s": out_spans[0]["t0_s"] if out_spans else None,
            "duration_s": (
                max(r.t1 for r in spans) - min(r.t0 for r in spans)
                if spans
                else None
            ),
            "lanes": sorted({r.lane for r in spans}),
        }

    def audit(self) -> dict:
        """Whole-buffer trace health (the bench's evidence block):
        distinct traces, spans that parent to an id absent from the
        buffer (orphans), and ring evictions."""
        with self._lock:
            spans = list(self._spans)
            dropped = self.dropped
        ids = {r.span_id for r in spans}
        orphans = sum(
            1 for r in spans if r.parent_id and r.parent_id not in ids
        )
        return {
            "spans": len(spans),
            "traces": len({r.trace_id for r in spans}),
            "orphans": orphans,
            "dropped": dropped,
        }

    # -- named virtual lanes (non-thread tid tracks) -------------------------
    def named_lane(self, name: str) -> int:
        """The lane int for the virtual track ``name`` (allocated on
        first use). Virtual lanes share the tid namespace with thread
        lanes but belong to no thread — the device observatory's
        ``device`` track."""
        key = ("virtual", name)
        lane = self._lanes.get(key)
        if lane is None:
            with self._lock:
                lane = self._lanes.get(key)
                if lane is None:
                    lane = len(self._lanes)
                    self._lanes[key] = lane
                    self._lane_names[lane] = name
        return lane

    def add_complete(self, name: str, t0: float, t1: float, fields: dict,
                     lane: "int | None" = None) -> SpanRecord:
        """Append a pre-timed completed span (``perf_counter`` stamps)
        without touching any thread's span stack — the virtual-lane
        writer's API."""
        rec = SpanRecord(
            span_id=next(self._ids),
            parent_id=0,
            name=name,
            lane=self._lane() if lane is None else lane,
            t0=t0,
            fields=fields,
        )
        rec.t1 = t1
        self._append_span(rec)
        return rec

    def add_instant(self, name: str, ts: float, fields: dict,
                    lane: "int | None" = None) -> None:
        """Append a pre-timed instant event, optionally on a virtual
        lane."""
        rec = _EventRecord(
            name, self._lane() if lane is None else lane, ts, fields
        )
        self._append_event(rec)

    # -- reading -------------------------------------------------------------
    @property
    def origin(self) -> float:
        """``perf_counter`` stamp of the recording start — the zero
        point of every relative ``t0_s`` this recorder emits
        (``trace_tree``, ``chrome_trace``). Readers holding absolute
        ``perf_counter`` stamps (``records()``/``event_records()``)
        rebase with ``t - origin`` before comparing against them."""
        return self._t0

    def records(self) -> "list[SpanRecord]":
        """Completed spans, consistent copy (any order; sort by ``t0``)."""
        with self._lock:
            return list(self._spans)

    def event_records(self) -> "list[_EventRecord]":
        """Instant events (the ``event``/``add_instant`` ring),
        consistent copy (any order; sort by ``ts``)."""
        with self._lock:
            return list(self._events)

    def mark(self) -> int:
        """A watermark for ``records_since``: consumes one span id, so
        every span begun after the mark has ``span_id > mark``. Cheap
        (no lock) — the pipeline's per-block phase-split probe."""
        return next(self._ids)

    def records_since(self, mark: int) -> "list[SpanRecord]":
        """Completed spans begun after ``mark`` (consistent copy)."""
        with self._lock:
            return [r for r in self._spans if r.span_id > mark]

    def chrome_trace(self) -> dict:
        """The buffer as a Chrome trace-event JSON document
        (Perfetto / ``chrome://tracing`` loadable). Timestamps are
        microseconds relative to the recording start, strictly
        non-negative and monotonic per the ``perf_counter`` clock."""
        with self._lock:
            spans = sorted(self._spans, key=lambda r: r.t0)
            events = sorted(self._events, key=lambda r: r.ts)
            lane_names = dict(self._lane_names)
            t0 = self._t0
            wall0 = self._wall0
        pid = os.getpid()
        out = [
            {
                "ph": "M",
                "pid": pid,
                "name": "process_name",
                "args": {"name": "ethereum_consensus_tpu"},
            }
        ]
        for lane in sorted(lane_names):
            out.append(
                {
                    "ph": "M",
                    "pid": pid,
                    "tid": lane,
                    "name": "thread_name",
                    "args": {"name": lane_names[lane]},
                }
            )
        for rec in spans:
            args = {k: _json_safe(v) for k, v in rec.fields.items()}
            args["span_id"] = rec.span_id
            args["trace_id"] = rec.trace_id
            if rec.parent_id:
                args["parent_id"] = rec.parent_id
            if rec.error is not None:
                args["error"] = rec.error
            out.append(
                {
                    "ph": "X",
                    "pid": pid,
                    "tid": rec.lane,
                    "name": rec.name,
                    "cat": rec.name.split(".", 1)[0],
                    "ts": max(0.0, (rec.t0 - t0) * 1e6),
                    "dur": max(0.0, (rec.t1 - rec.t0) * 1e6),
                    "args": args,
                }
            )
            if rec.flow_src is not None:
                # cross-lane handoff: a flow-start at the sender's
                # capture point, a binding flow-finish at this span's
                # start — Perfetto draws the arrow between tid lanes
                src_span, src_lane, src_ts = rec.flow_src
                flow = {
                    "pid": pid,
                    "name": "trace.flow",
                    "cat": "flow",
                    "id": rec.span_id,
                }
                out.append(
                    dict(
                        flow,
                        ph="s",
                        tid=src_lane,
                        ts=max(0.0, (src_ts - t0) * 1e6),
                        args={"from_span": src_span},
                    )
                )
                out.append(
                    dict(
                        flow,
                        ph="f",
                        bp="e",
                        tid=rec.lane,
                        ts=max(0.0, (rec.t0 - t0) * 1e6),
                        args={"to_span": rec.span_id},
                    )
                )
        for rec in events:
            out.append(
                {
                    "ph": "i",
                    "s": "t",
                    "pid": pid,
                    "tid": rec.lane,
                    "name": rec.name,
                    "cat": rec.name.split(".", 1)[0],
                    "ts": max(0.0, (rec.ts - t0) * 1e6),
                    "args": {k: _json_safe(v) for k, v in rec.fields.items()},
                }
            )
        return {
            "traceEvents": out,
            "displayTimeUnit": "ms",
            "otherData": {"recordingStartUnixTime": wall0},
        }


def _make_totals(name: str) -> tuple:
    return tuple(
        _metrics.counter(f"span.{name}.{what}")
        for what in ("n", "ns", "self_ns")
    )


def _add_totals(totals: tuple, ns: int, own_ns: int) -> None:
    count, total, own = totals
    count.inc()
    total.inc(ns)
    own.inc(own_ns)


GC_SPAN = "gc.collect"


class GcWatch:
    """The ``gc.callbacks`` hook. Collections never overlap (the
    interpreter runs one at a time and none inside a callback), so the
    open collection's start and span live here without a lock."""

    def __init__(self):
        self.generations = tuple(
            _metrics.counter(f"gc.collections.gen{g}") for g in range(3)
        )
        self.pause_ns = _metrics.counter("gc.pause_ns")
        # made before the hook can run: the hook never creates a counter
        self.totals = _make_totals(GC_SPAN)
        # (record, scope, ns, self ns) the hook could not finish without
        # a lock
        self.pending: deque = deque()
        self.t0 = 0
        self.rec = None

    def on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t0 = time.perf_counter_ns()
            annotate = profiler_annotation()
            if annotate is not None or RECORDER.enabled:
                self.rec = RECORDER.gc_begin(info["generation"], annotate)
            return
        self.pause_ns.inc(time.perf_counter_ns() - self.t0)
        self.generations[info["generation"]].inc()
        rec = self.rec
        if rec is not None:
            self.rec = None
            RECORDER.gc_end(rec, info["collected"])

    def install(self) -> None:
        if self.on_gc not in gc.callbacks:
            gc.callbacks.append(self.on_gc)


RECORDER = SpanRecorder()
GC_WATCH = GcWatch()
GC_WATCH.install()


def is_recording() -> bool:
    return RECORDER.enabled


def start_recording(capacity: "int | None" = None) -> None:
    RECORDER.start(capacity)


def stop_recording() -> None:
    RECORDER.stop()


@contextmanager
def recording(capacity: "int | None" = None):
    """Record spans for the duration of the block; yields ``RECORDER``."""
    RECORDER.start(capacity)
    try:
        yield RECORDER
    finally:
        RECORDER.stop()


def write_chrome_trace(path: str) -> None:
    """Serialize the current buffer as Chrome trace JSON at ``path``."""
    doc = RECORDER.chrome_trace()
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
