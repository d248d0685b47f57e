"""Telemetry core: span recording round-trips through Chrome-trace
export (valid JSON, monotonic timestamps, correct thread lanes, parent
nesting), the metrics registry counts exactly under concurrent
increments, the trace facade's disabled path stays near-free, and
``basic_setup`` no longer stacks duplicate handlers.
"""

import json
import logging
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))

from ethereum_consensus_tpu.telemetry import metrics, phases, spans  # noqa: E402
from ethereum_consensus_tpu.utils import trace  # noqa: E402


# ---------------------------------------------------------------------------
# span recorder -> Chrome trace export
# ---------------------------------------------------------------------------


def test_span_nesting_and_threads_roundtrip_chrome_export(tmp_path):
    def worker_job():
        with trace.span("worker.outer", role="verifier"):
            with trace.span("worker.inner"):
                time.sleep(0.001)

    with spans.recording():
        with trace.span("main.outer", slot=7):
            with trace.span("main.inner", step="a"):
                time.sleep(0.001)
        trace.event("main.marker", detail="x")
        with ThreadPoolExecutor(max_workers=1) as pool:
            pool.submit(worker_job).result()
        out_path = tmp_path / "trace.json"
        spans.write_chrome_trace(str(out_path))

    doc = json.loads(out_path.read_text())  # valid JSON by construction
    events = doc["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    by_name = {e["name"]: e for e in complete}

    # every expected span exported, with non-negative monotonic ts
    for name in ("main.outer", "main.inner", "worker.outer", "worker.inner"):
        assert name in by_name, sorted(by_name)
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in complete)
    ts_order = [e["ts"] for e in sorted(complete, key=lambda e: e["ts"])]
    assert ts_order == sorted(ts_order)

    # nesting: inner's parent is outer, and inner fits inside outer
    outer, inner = by_name["main.outer"], by_name["main.inner"]
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0
    assert outer["args"]["slot"] == 7

    # thread attribution: worker spans on their own tid lane, and the
    # worker's parent chain does NOT cross into the main thread
    assert by_name["worker.outer"]["tid"] != outer["tid"]
    assert by_name["worker.inner"]["tid"] == by_name["worker.outer"]["tid"]
    assert "parent_id" not in by_name["worker.outer"]["args"]

    # lane metadata present for both threads
    lane_meta = [e for e in events if e["ph"] == "M" and e["name"] == "thread_name"]
    assert {e["tid"] for e in lane_meta} >= {outer["tid"], by_name["worker.outer"]["tid"]}

    # the instant event rides along
    instants = [e for e in events if e["ph"] == "i"]
    assert any(e["name"] == "main.marker" for e in instants)


def test_span_error_recorded_and_reraised():
    with spans.recording():
        with pytest.raises(ValueError):
            with trace.span("failing.span"):
                raise ValueError("boom")
        records = spans.RECORDER.records()
    rec = next(r for r in records if r.name == "failing.span")
    assert "boom" in rec.error


def test_recording_off_records_nothing():
    spans.RECORDER.stop()
    before = len(spans.RECORDER.records())
    with trace.span("not.recorded"):
        pass
    assert len(spans.RECORDER.records()) == before


def test_ring_buffer_bounds_memory():
    try:
        with spans.recording(capacity=16):
            for i in range(64):
                with trace.span("spin", i=i):
                    pass
            records = spans.RECORDER.records()
    finally:
        # a capacity outlives its recording: give the worker's later
        # tests the default ring back
        spans.start_recording(spans.DEFAULT_CAPACITY)
        spans.stop_recording()
    assert len(records) == 16
    # newest survive, oldest dropped
    assert max(r.fields["i"] for r in records) == 63


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_exact_under_concurrent_increments():
    c = metrics.counter("test.concurrent_counter")
    before = c.value()
    n_threads, per_thread = 8, 5000

    def bump():
        for _ in range(per_thread):
            c.inc()

    threads_done = []
    with ThreadPoolExecutor(max_workers=n_threads) as pool:
        threads_done = [pool.submit(bump) for _ in range(n_threads)]
    for f in threads_done:
        f.result()
    assert c.value() - before == n_threads * per_thread


def test_registry_get_or_create_identity_and_kind_guard():
    a = metrics.counter("test.identity")
    b = metrics.counter("test.identity")
    assert a is b
    with pytest.raises(TypeError):
        metrics.gauge("test.identity")


def test_snapshot_delta_semantics():
    c = metrics.counter("test.delta_counter")
    g = metrics.gauge("test.delta_gauge")
    h = metrics.histogram("test.delta_hist")
    before = metrics.snapshot()
    c.inc(5)
    c.inc(2)
    g.set(3)
    g.update_max(9)
    g.update_max(4)  # smaller: no change
    h.observe(10)
    h.observe(30)
    d = metrics.delta(before)
    assert d["test.delta_counter"] == 7
    assert d["test.delta_gauge"] == 9  # gauges are levels: after-value
    assert d["test.delta_hist"]["count"] == 2
    assert d["test.delta_hist"]["sum"] == 40
    assert d["test.delta_hist"]["mean"] == 20
    # snapshot is JSON-ready
    json.dumps(metrics.snapshot())


def test_digest_counter_shims_still_serve_deltas():
    """PR 1's hash-count contract: digest_count()/add_digests() read and
    write the registry-backed counter, including cross-thread."""
    from ethereum_consensus_tpu.ssz import hash as ssz_hash

    before = ssz_hash.digest_count()
    ssz_hash.hash_bytes(b"x")
    ssz_hash.hash_pair(b"\x00" * 32, b"\x11" * 32)
    ssz_hash.add_digests(10)
    with ThreadPoolExecutor(max_workers=4) as pool:
        for f in [pool.submit(ssz_hash.add_digests, 1) for _ in range(100)]:
            f.result()
    assert ssz_hash.digest_count() - before == 112
    assert metrics.counter("ssz.digests").value() == ssz_hash.digest_count()


# ---------------------------------------------------------------------------
# phase attribution
# ---------------------------------------------------------------------------


def test_phase_attribution_from_synthetic_spans():
    def rec(span_id, parent_id, name, t0, t1):
        r = spans.SpanRecord(span_id, parent_id, name, 0, t0, {})
        r.t1 = t1
        return r

    records = [
        rec(1, 0, "transition.slot_advance", 0.0, 0.10),
        rec(2, 1, "transition.state_htr", 0.02, 0.06),       # htr inside slots
        rec(3, 0, "transition.block", 0.10, 1.10),
        rec(4, 3, "transition.operations", 0.10, 0.90),
        rec(5, 4, "transition.committees", 0.20, 0.30),
        rec(6, 3, "transition.sig_batch", 0.90, 1.00),
        rec(7, 3, "transition.state_htr", 1.00, 1.10),       # root check
    ]
    out = phases.attribution(records)
    assert out["slot_advance_s"] == pytest.approx(0.10)
    assert out["block_apply_s"] == pytest.approx(1.00)
    assert out["sig_batch_s"] == pytest.approx(0.10)
    assert out["state_htr_s"] == pytest.approx(0.14)
    assert out["state_htr_in_slot_advance_s"] == pytest.approx(0.04)
    assert out["committee_s"] == pytest.approx(0.10)
    # residual: (0.10 + 1.00) - (0.10 + 0.14 + 0.10)
    assert out["operations_s"] == pytest.approx(0.76)


def test_transition_emits_all_phase_spans():
    """A real minimal-preset transition recorded end-to-end emits every
    phase span the attribution contract names."""
    from chain_utils import fresh_genesis, produce_block

    from ethereum_consensus_tpu.models.phase0.state_transition import (
        state_transition,
    )

    state, ctx = fresh_genesis(64, "minimal")
    signed = produce_block(state.copy(), 2, ctx)
    with spans.recording():
        state_transition(state, signed, ctx)
        names = {r.name for r in spans.RECORDER.records()}
    assert {
        "transition.slot_advance",
        "transition.block",
        "transition.operations",
        "transition.sig_batch",
        "transition.state_htr",
        "transition.committees",
    } <= names
    out = phases.attribution(spans.RECORDER.records())
    assert out["block_apply_s"] > 0


# ---------------------------------------------------------------------------
# disabled-path overhead guard
# ---------------------------------------------------------------------------


def _replay_seconds(state, ctx, blocks, reps=5):
    from ethereum_consensus_tpu.executor import Executor

    best = None
    for _ in range(reps):
        ex = Executor(state.copy(), ctx)
        t0 = time.perf_counter()
        for b in blocks:
            ex.apply_block(b)
        elapsed = time.perf_counter() - t0
        best = elapsed if best is None or elapsed < best else best
    return best


def test_disabled_recording_overhead_within_threshold(monkeypatch):
    """The ISSUE's overhead guard, in-test form: a warm replay with
    telemetry present-but-off must be within a generous factor of the
    same replay with every span call no-op'd out (the pre-telemetry
    shape of the call sites). The acceptance bound is < 2% on the
    mainnet warm-block replay, where per-span overhead is amortized over
    ~0.3 s blocks; this minimal-preset guard uses much smaller blocks
    (microseconds of span overhead against milliseconds of block work),
    so the threshold is generous — it exists to catch a regression that
    makes the DISABLED path do real work (formatting, recording,
    locking), which would show up here as an integer factor."""
    from contextlib import contextmanager, nullcontext

    from chain_utils import fresh_genesis, produce_chain

    assert not spans.RECORDER.enabled
    state, ctx = fresh_genesis(64, "minimal")
    blocks = produce_chain(state, ctx, 4)

    _replay_seconds(state, ctx, blocks, reps=2)  # warm caches/memos
    with_telemetry = _replay_seconds(state, ctx, blocks)

    def noop_span(name, **fields):
        return nullcontext()

    @contextmanager
    def _noop_ctx():
        yield

    monkeypatch.setattr(trace, "span", noop_span)
    monkeypatch.setattr(trace, "event", lambda name, **fields: None)
    without_spans = _replay_seconds(state, ctx, blocks)
    monkeypatch.undo()

    assert with_telemetry <= without_spans * 1.5 + 0.005, (
        f"disabled-path span overhead too high: {with_telemetry:.4f}s with "
        f"spans vs {without_spans:.4f}s without"
    )


def _span_totals():
    return {k: v for k, v in metrics.snapshot().items()
            if k.startswith("span.")}


@pytest.mark.parametrize("opener", ["span", "scope"])
def test_disabled_span_microcost(opener):
    """Absolute sanity bound on one disabled span, and on a counter scope,
    which is a span (not a benchmark — a regression tripwire: the
    disabled path must stay allocation-light), and no ``span.*`` total
    moves while no sink is on."""
    assert not spans.RECORDER.enabled
    assert spans.profiler_annotation() is None
    open_span = getattr(trace, opener)
    totals0 = _span_totals()
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with open_span("micro.guard", slot=1):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 50e-6, f"{per_span * 1e6:.1f}µs per disabled {opener}"
    assert totals0 == _span_totals()


def _best_per_call(fn, n=20_000, reps=7):
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        elapsed = (time.perf_counter() - t0) / n
        best = elapsed if best is None or elapsed < best else best
    return best


def test_profiler_sink_check_fits_its_budget():
    """The one check the profiler sink adds to the disabled path of
    ``span`` (is a jax.profiler session live?) has a budget of 0.5 µs a
    span, with jax imported and without: it never imports jax, it looks
    in ``sys.modules`` and asks the profiler."""
    pytest.importorskip("jax")
    import jax.profiler  # noqa: F401  the dearer case: the module is there

    assert spans.profiler_annotation() is None
    loop = _best_per_call(lambda: None)
    check = _best_per_call(spans.profiler_annotation) - loop
    assert check < 0.5e-6, f"{check * 1e6:.2f}µs per profiler-sink check"


# ---------------------------------------------------------------------------
# basic_setup idempotency (the handler-leak satellite)
# ---------------------------------------------------------------------------


def test_basic_setup_is_idempotent():
    logger = trace.logger
    before_handlers = list(logger.handlers)
    before_level = logger.level
    try:
        trace.basic_setup()
        added_once = [h for h in logger.handlers if h not in before_handlers]
        assert len(added_once) == 1
        trace.basic_setup()
        trace.basic_setup(logging.DEBUG)
        added = [h for h in logger.handlers if h not in before_handlers]
        assert added == added_once, "repeated basic_setup stacked handlers"
        assert logger.level == logging.DEBUG  # level updates still apply
    finally:
        for h in [h for h in logger.handlers if h not in before_handlers]:
            logger.removeHandler(h)
        logger.setLevel(before_level)


# ---------------------------------------------------------------------------
# PipelineStats as a registry view
# ---------------------------------------------------------------------------


def test_pipeline_stats_views_registry_and_freezes_on_stop():
    from ethereum_consensus_tpu.pipeline.stats import PipelineStats

    a = PipelineStats()
    a.start()
    a.block_submitted(0.5)
    a.blocks_were_committed(3)
    a.flush_dispatched(7)
    a.queue_depth(2)
    assert a.blocks_submitted == 1
    assert a.blocks_committed == 3
    assert a.flush_sizes == [7]
    assert a.queue_high_watermark == 2
    # registry totals visible without the stats object
    assert metrics.counter("pipeline.blocks_committed").value() >= 3
    a.stop()
    frozen = a.snapshot()

    # a second run increments the shared registry; the first run's
    # frozen view must not move
    b = PipelineStats()
    b.start()
    b.blocks_were_committed(11)
    b.flush_dispatched(5)
    b.stop()
    assert a.snapshot()["blocks_committed"] == frozen["blocks_committed"] == 3
    assert a.flush_sizes == [7]
    assert b.blocks_committed == 11
    assert b.flush_sizes == [5]
