"""Device execution observatory: JAX/XLA compile, transfer, and routing
telemetry.

The host paths are instrumented exhaustively (spans, metrics, flight
lineage) but the JAX/XLA side was a black box: a TPU run would come home
with ``bls.pairing_route.{device,host}`` tallies and nothing else — no
visibility into compiles (up to a minute per distinct shape for the
emulated-u64 programs), silent per-shape RE-compiles (the classic TPU perf
killer: one drifting dtype and every "warm" call re-traces), host<->
device transfer volume (the epoch columns and signature batches are the
payloads that matter), or why a given call routed device vs host. This
module closes that: one process-wide ``DeviceObservatory`` recording

* a **compile ledger** — every traced-function compile observed through
  the repo's jit seams (``ops/``, ``parallel/``,
  ``models/epoch_vector.py`` kernels), with the call's shape/dtype
  signature, elapsed seconds (the compiling call's wall time — on an
  accelerator trace+compile dominates it), and a **recompile sentinel**:
  a counter plus a ONE-SHOT trace event per function naming the old and
  new signatures whenever an already-compiled kernel is re-traced for a
  drifted signature;
* a **transfer ledger** — host→device and device→host transfer counts
  and bytes aggregated per call site (``device.transfer.{h2d,d2h}_
  {count,bytes}`` registry counters + per-site totals). The time of a
  copy is not the ledger's: the seams open a facade span
  ``<site>.h2d`` / ``<site>.d2h`` (``bytes=``) around it on the thread
  that pays it, whatever the observatory's state, so it reaches every
  sink of ``utils/trace.span`` (the recorder, the profiler's trace, the
  ``span.<name>.*`` totals) nested under the caller's span;
* a **routing journal** — every device-vs-host decision (the
  ``_device_flags`` threshold gates, the BLS pairing route, the
  ``epoch_vector`` engage/decline) with its choice, reason, and
  threshold inputs, queryable live via the introspection server's
  ``/device`` endpoint and summarized per flush window in
  ``BlockLineage.verify_route``.

Cost discipline (the spans/commit-hook contract): ``OBSERVATORY.active``
is a plain bool read — instrumented call sites check it FIRST and pay
nothing else while the observatory is off (guarded by the overhead test
in tests/test_device_observatory.py); the transfer seams besides pay one
disabled facade span a copy. Everything here is stdlib-only;
jax is never imported by this module (the instrumented seams already
have it).

Lock discipline (speclint-checked): every write to the observatory's
shared structures holds ``self._lock``; the hot ``active`` read and the
metrics-registry increments (locked per metric) stay outside it.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque
from contextlib import contextmanager

from .. import _env
from . import metrics as _metrics
from . import spans as _spans

__all__ = [
    "DeviceObservatory",
    "OBSERVATORY",
    "DEFAULT_CAPACITY",
    "observe_jit",
    "h2d",
    "d2h",
    "route",
    "signature_of",
    "start",
    "stop",
    "is_observing",
    "observing",
    "snapshot",
]

DEFAULT_CAPACITY = 1 << 12

_DEVICE_LANE = "device"


def signature_of(args: tuple, kwargs: dict) -> str:
    """A stable shape/dtype signature for one jitted call: arrays render
    as ``dtype[d0,d1]``, static scalars by value, everything else by
    type name — the same drift axes XLA re-traces on."""
    parts = []
    for a in args:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
        elif isinstance(a, (bool, int, float, str, bytes)):
            parts.append(repr(a))
        else:
            parts.append(type(a).__name__)
    for k in sorted(kwargs):
        v = kwargs[k]
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{k}={dtype}[{','.join(str(d) for d in shape)}]")
        elif isinstance(v, (bool, int, float, str, bytes)):
            parts.append(f"{k}={v!r}")
        else:
            parts.append(f"{k}={type(v).__name__}")
    return "(" + ", ".join(parts) + ")"


class DeviceObservatory:
    """Process-wide ledger of device-side execution facts; one instance
    (``OBSERVATORY``) serves the whole process, started/stopped like the
    span recorder."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._capacity = capacity
        self._compiles: deque = deque(maxlen=capacity)
        self._routes: deque = deque(maxlen=capacity)
        self._route_tally: dict = {}      # (kind, choice) -> count
        self._transfers: dict = {}        # site -> {h2d/d2h count/bytes}
        self._signatures: dict = {}       # fn -> set of compiled signatures
        self._device_span: dict = {}      # fn -> [max arg span, max out span]
        self._sentinel_seen: set = set()  # fn names whose sentinel fired
        self.active = False

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> None:
        """Begin a fresh observation (drops previous ledgers)."""
        with self._lock:
            self._compiles.clear()
            self._routes.clear()
            self._route_tally.clear()
            self._transfers.clear()
            self._signatures.clear()
            self._device_span.clear()
            self._sentinel_seen.clear()
            self.active = True

    def stop(self) -> None:
        """Stop observing (ledgers stay readable)."""
        with self._lock:
            self.active = False

    # -- compile ledger ------------------------------------------------------
    def record_call(self, name: str, signature: str, t0: float, t1: float,
                    compiled: bool, cache_size: int,
                    span: "tuple[int, int]" = (0, 0)) -> None:
        """One observed jitted call. ``compiled`` is the jit-cache
        verdict: the call grew the jitted function's executable cache.
        ``span`` is how many devices the call's widest array argument and
        widest output were laid out over (0 = no device array)."""
        seconds = max(0.0, t1 - t0)
        recompile_from = None
        with self._lock:
            widest = self._device_span.setdefault(name, [0, 0])
            widest[0] = max(widest[0], span[0])
            widest[1] = max(widest[1], span[1])
            known = self._signatures.get(name)
            if known is None:
                known = self._signatures[name] = set()
            if compiled:
                if known and signature not in known:
                    # the sentinel case: this kernel had compiled before
                    # and a drifted signature re-traced it
                    recompile_from = sorted(known)[-1]
                known.add(signature)
                self._compiles.append(
                    {
                        "fn": name,
                        "signature": signature,
                        "compile_s": seconds,
                        "recompile": recompile_from is not None,
                        "prev_signature": recompile_from,
                        "cache_size": cache_size,
                        "at": time.time(),
                    }
                )
            fire_sentinel = (
                recompile_from is not None
                and name not in self._sentinel_seen
            )
            if fire_sentinel:
                self._sentinel_seen.add(name)
        if compiled:
            _metrics.counter("device.compiles").inc()
            _metrics.histogram("device.compile_s").observe(seconds)
            _metrics.counter("device.jit_cache.misses").inc()
        else:
            _metrics.counter("device.jit_cache.hits").inc()
        if recompile_from is not None:
            _metrics.counter("device.recompiles").inc()
        if fire_sentinel:
            # one-shot per function per process (the ops_vector.fallback
            # idiom): the counter counts every recompile, the event names
            # the drift once so a trace isn't flooded by a pathological
            # shape churn
            from ..utils import trace

            trace.event(
                "device.recompile",
                fn=name,
                old_signature=recompile_from,
                new_signature=signature,
            )
        rec = _spans.RECORDER
        if rec.enabled and compiled:
            rec.add_complete(
                "device.compile",
                t0,
                t1,
                {"fn": name, "signature": signature,
                 "recompile": recompile_from is not None},
                lane=rec.named_lane(_DEVICE_LANE),
            )

    # -- transfer ledger -----------------------------------------------------
    def record_transfer(self, site: str, direction: str, count: int,
                        nbytes: int) -> None:
        """One host<->device transfer at ``site`` (``direction`` is
        ``h2d`` or ``d2h``): counts and bytes; the seam's facade span
        has the time."""
        with self._lock:
            agg = self._transfers.get(site)
            if agg is None:
                agg = self._transfers[site] = {
                    "h2d_count": 0, "h2d_bytes": 0,
                    "d2h_count": 0, "d2h_bytes": 0,
                }
            agg[f"{direction}_count"] += count
            agg[f"{direction}_bytes"] += nbytes
        _metrics.counter(f"device.transfer.{direction}_count").inc(count)
        _metrics.counter(f"device.transfer.{direction}_bytes").inc(nbytes)

    # -- routing journal -----------------------------------------------------
    def record_route(self, kind: str, choice: str, reason: str,
                     inputs: dict) -> None:
        """One device-vs-host decision: ``kind`` names the gate
        (``pairing``, ``sweeps`` (the epoch pass's: ``device`` = the
        fused kernel was selected), ``shuffle``, ``bls_agg``,
        ``epoch_vector``), ``choice`` where it went (``device`` /
        ``host`` / ``columnar`` / ``literal``), ``reason`` why, and
        ``inputs`` the threshold arithmetic behind it."""
        with self._lock:
            key = (kind, choice)
            self._route_tally[key] = self._route_tally.get(key, 0) + 1
            self._routes.append(
                {
                    "kind": kind,
                    "choice": choice,
                    "reason": reason,
                    "inputs": dict(inputs),
                    "at": time.time(),
                }
            )
        _metrics.counter(f"device.route.{kind}.{choice}").inc()
        rec = _spans.RECORDER
        if rec.enabled:
            rec.add_instant(
                "device.route",
                time.perf_counter(),
                {"kind": kind, "choice": choice, "reason": reason},
                lane=rec.named_lane(_DEVICE_LANE),
            )

    # -- reading -------------------------------------------------------------
    def compiles(self) -> list:
        """Compile-ledger records, oldest first (consistent copy)."""
        with self._lock:
            return [dict(r) for r in self._compiles]

    def routes(self, n: "int | None" = None) -> list:
        """Routing-journal records, oldest first; newest ``n`` if
        given."""
        with self._lock:
            records = [dict(r) for r in self._routes]
        return records if n is None else records[-n:]

    def route_tallies(self) -> dict:
        """Cumulative ``{kind: {choice: count}}`` over the whole
        observation (unbounded, unlike the journal ring)."""
        with self._lock:
            items = list(self._route_tally.items())
        out: dict = {}
        for (kind, choice), count in items:
            out.setdefault(kind, {})[choice] = count
        return out

    def transfer_summary(self) -> dict:
        """Per-site transfer aggregates plus process totals."""
        with self._lock:
            sites = {site: dict(agg) for site, agg in self._transfers.items()}
        totals = {"h2d_count": 0, "h2d_bytes": 0, "d2h_count": 0,
                  "d2h_bytes": 0}
        for agg in sites.values():
            for key in totals:
                totals[key] += agg[key]
        return {"sites": sites, "totals": totals}

    def signatures(self) -> dict:
        """``{fn: sorted compiled signatures}`` — the shape census."""
        with self._lock:
            return {name: sorted(sigs)
                    for name, sigs in self._signatures.items()}

    def device_span(self) -> dict:
        """``{fn: {"args": n, "outs": n}}`` — the most devices any array
        argument / output of each observed function was laid out over. A
        mesh-sharded kernel whose span reads 1 had everything on one
        device."""
        with self._lock:
            return {name: {"args": span[0], "outs": span[1]}
                    for name, span in self._device_span.items()}

    def snapshot(self, journal_n: int = 128) -> dict:
        """The /device endpoint document: every ledger, JSON-ready."""
        from .._jax_cache import status as _jax_cache_status

        # mesh runtime state (parallel/runtime.py): imported ONLY when
        # ECT_MESH is switched on — this module stays jax-free otherwise
        mesh_env = _env.raw("ECT_MESH").strip()
        mesh_state = {
            "requested": False,
            "env": mesh_env or "off",
            "devices": 0,
        }
        if mesh_env.lower() not in ("", "off", "0", "none", "host"):
            try:
                from ..parallel import runtime as _mesh_runtime

                mesh_state = _mesh_runtime.status()
            except Exception as exc:  # noqa: BLE001 — report, not raise
                mesh_state["error"] = repr(exc)[:160]
        compiles = self.compiles()
        return {
            "observing": self.active,
            "compile_ledger": {
                "compiles": len(compiles),
                "recompiles": sum(1 for c in compiles if c["recompile"]),
                "total_compile_s": sum(c["compile_s"] for c in compiles),
                "signatures": self.signatures(),
                "device_span": self.device_span(),
                "recent": compiles[-journal_n:],
            },
            "transfer_ledger": self.transfer_summary(),
            "routing_journal": {
                "tallies": self.route_tallies(),
                "recent": self.routes(journal_n),
            },
            "jit_cache": {
                "hits": _metrics.counter("device.jit_cache.hits").value(),
                "misses": _metrics.counter("device.jit_cache.misses").value(),
            },
            "persistent_cache": _jax_cache_status(),
            "mesh": mesh_state,
        }


OBSERVATORY = DeviceObservatory()


# ---------------------------------------------------------------------------
# the instrumentation seams (called from ops/, parallel/, models/, crypto/)
# ---------------------------------------------------------------------------


def _widest_span(values) -> int:
    """The most devices any array among ``values`` (one value, or a flat
    tuple/list of them) is laid out over; 0 when none is a device array."""
    if not isinstance(values, (tuple, list)):
        values = (values,)
    widest = 0
    for v in values:
        sharding = getattr(v, "sharding", None)
        if sharding is not None:
            widest = max(widest, len(sharding.device_set))
    return widest


def observe_jit(jitted, name: str):
    """Wrap an already-jitted callable so every call through it feeds
    the compile ledger while the observatory is active. The inactive
    path is one bool read + one indirection (overhead-test guarded);
    the active path derives the call's shape signature, times the call,
    and classifies it compile / cache-hit / RECOMPILE by whether the call
    grew the jitted function's executable cache (``_cache_size``)."""

    def observed(*args, **kwargs):
        obs = OBSERVATORY
        if not obs.active:
            return jitted(*args, **kwargs)
        signature = signature_of(args, kwargs)
        before = jitted._cache_size()
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        t1 = time.perf_counter()
        after = jitted._cache_size()
        obs.record_call(
            name, signature, t0, t1, after > before, after,
            span=(_widest_span(args), _widest_span(out)),
        )
        return out

    observed.__name__ = name.rsplit(".", 1)[-1]
    observed.__qualname__ = name
    observed.__wrapped__ = jitted
    return observed


@functools.lru_cache(maxsize=1)
def _jnp():
    """The jax.numpy module, resolved once (thread-safe via lru_cache —
    no unlocked module-global write). Call sites of ``h2d`` are device
    entry points that already imported jax, so this never triggers a
    cold jax import on a host-only process."""
    import jax.numpy

    return jax.numpy


@functools.lru_cache(maxsize=1)
def _np():
    import numpy

    return numpy


@functools.lru_cache(maxsize=1)
def _span():
    """The facade's ``span``, resolved once: ``utils/trace.py`` imports
    this package, so the import cannot stand at the top."""
    from ..utils.trace import span

    return span


def _nbytes(a) -> int:
    n = getattr(a, "nbytes", None)
    if n is not None:
        return int(n)
    try:
        return len(a)
    except TypeError:
        return 0


def _ledger(site: str, direction: str, count: int, nbytes: int) -> None:
    obs = OBSERVATORY
    if obs.active:
        obs.record_transfer(site, direction, count, nbytes)


def h2d(site: str, *arrays):
    """``jnp.asarray`` every argument (the repo's host→device seam)
    inside a facade span ``<site>.h2d``, recording count/bytes against
    ``site`` while observing. Returns a single array for a single
    argument, a tuple otherwise. On the CPU backend the "transfer" may
    be a zero-copy view, and on an accelerator the copy may outlive the
    call — the span times the dispatch seam; whoever reads the result
    first pays the rest."""
    jnp = _jnp()
    nbytes = sum(_nbytes(a) for a in arrays)
    with _span()(site + ".h2d", bytes=nbytes):
        out = tuple(jnp.asarray(a) for a in arrays)
    _ledger(site, "h2d", len(out), nbytes)
    return out[0] if len(out) == 1 else out


def h2d_put(site: str, arrays, sharding=None):
    """``jax.device_put`` with an explicit sharding — the sharded-mesh
    twin of ``h2d``, and the ONLY sanctioned way to place host buffers
    onto a mesh (speclint's transfer-seam rule points every raw
    ``device_put`` here). Takes an iterable so one span and one ledger
    entry cover the whole staged argument tuple; returns the placed
    tuple."""
    import jax

    arrays = tuple(arrays)
    nbytes = sum(_nbytes(a) for a in arrays)
    with _span()(site + ".h2d", bytes=nbytes):
        out = tuple(jax.device_put(a, sharding) for a in arrays)
    _ledger(site, "h2d", len(out), nbytes)
    return out


def d2h_start(array) -> None:
    """Queue ``array``'s copy to the host behind whatever computes it
    and return at once, so the copy overlaps what the caller does next.
    No span and no ledger entry: the ``d2h`` that collects the copy
    owns both."""
    array.copy_to_host_async()


def d2h(site: str, array):
    """``np.asarray`` the device value (the device→host seam) inside a
    facade span ``<site>.d2h``, recording against ``site`` while
    observing. The copy waits for whatever computes ``array``."""
    np = _np()
    with _span()(site + ".d2h", bytes=_nbytes(array)):
        out = np.asarray(array)
    _ledger(site, "d2h", 1, _nbytes(out))
    return out


def route(kind: str, choice: str, reason: str, **inputs) -> None:
    """Journal one device-vs-host decision (no-op while not observing;
    hot call sites pre-guard with ``OBSERVATORY.active`` so the off
    path is a single bool read)."""
    obs = OBSERVATORY
    if not obs.active:
        return
    obs.record_route(kind, choice, reason, inputs)


# -- module-level lifecycle ---------------------------------------------------


def start() -> DeviceObservatory:
    OBSERVATORY.start()
    return OBSERVATORY


def stop() -> None:
    OBSERVATORY.stop()


def is_observing() -> bool:
    return OBSERVATORY.active


@contextmanager
def observing():
    """Observe for the duration of the block; yields ``OBSERVATORY``
    (the ``spans.recording`` idiom)."""
    start()
    try:
        yield OBSERVATORY
    finally:
        stop()


def snapshot(journal_n: int = 128) -> dict:
    return OBSERVATORY.snapshot(journal_n)
