"""Causal trace plane (docs/OBSERVABILITY.md): cross-lane lineage.

Covers the trace-plane contract end to end:

* a 3-lane ``FlushPolicy`` run where every settled block's flight
  lineage resolves through ``trace_tree`` to one CONNECTED span tree
  (single root, zero orphans) and the Chrome export carries the
  cross-lane flow arrows;
* exemplar determinism under the seeded-reservoir contract — passing
  trace ids never touches the reservoir RNG, and the worst-N table is
  value-ordered and reproducible;
* the ``/trace`` endpoint round trip through ``api/client.py``
  (``get_trace``), including the 404 unknown-id and 400 bad-id error
  paths, the device-evidence join (both recorder rings, rebased onto
  the recording origin), and the exemplar tables on bare ``/trace``;
* the classic-scrape guard: ``/metrics`` stays strict text format
  0.0.4 — no OpenMetrics exemplar appendage — even while histograms
  hold exemplars;
* the sub-µs inactive-path guard: tracing off, ``trace.context()``
  costs one attribute read;
* the ``trace_smoke`` tier-1 gate (``make trace-smoke``): one
  end-to-end linked trace on a 2-lane pipeline with zero dropped spans.
"""

import sys
import threading
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))
from chain_utils import fresh_genesis, produce_chain  # noqa: E402

from ethereum_consensus_tpu.api.errors import ApiError  # noqa: E402
from ethereum_consensus_tpu.executor import Executor  # noqa: E402
from ethereum_consensus_tpu.pipeline import FlushPolicy  # noqa: E402
from ethereum_consensus_tpu.telemetry import flight, metrics, spans  # noqa: E402
from ethereum_consensus_tpu.utils import trace  # noqa: E402


@pytest.fixture(scope="module")
def chain():
    state, ctx = fresh_genesis(64, "minimal")
    blocks = produce_chain(state, ctx, 9)
    return state, ctx, blocks


def _run_traced(state, ctx, blocks, policy):
    """Stream ``blocks`` with both the span recorder and the flight
    recorder live; return (stats, lineage, trees, audit, chrome_doc)
    captured before either recording stops."""
    flight.start()
    try:
        # pin the default capacity: SpanRecorder.start keeps the LAST
        # capacity, and earlier test files shrink the shared ring
        with spans.recording(capacity=spans.DEFAULT_CAPACITY):
            executor = Executor(state.copy(), ctx)
            stats = executor.stream(blocks, policy=policy)
            lineage = flight.RECORDER.records()
            trees = {
                r.trace_id: spans.RECORDER.trace_tree(r.trace_id)
                for r in lineage
                if r.trace_id is not None
            }
            audit = spans.RECORDER.audit()
            doc = spans.RECORDER.chrome_trace()
    finally:
        flight.stop()
    return stats, lineage, trees, audit, doc


# ---------------------------------------------------------------------------
# 3-lane pipeline: every settled block resolves to one connected tree
# ---------------------------------------------------------------------------


def test_three_lane_lineage_resolves_to_connected_trees(chain):
    state, ctx, blocks = chain
    stats, lineage, trees, audit, doc = _run_traced(
        state, ctx, blocks,
        FlushPolicy(window_size=3, max_in_flight=2, verify_lanes=3),
    )
    assert stats.blocks_committed == len(blocks)
    assert len(lineage) == len(blocks)
    assert audit["orphans"] == 0
    assert audit["dropped"] == 0

    # every settled block carries a trace id that assembles into one
    # connected tree: a single root, no orphan spans
    assert all(r.trace_id is not None for r in lineage)
    for record in lineage:
        tree = trees[record.trace_id]
        assert tree["connected"], (
            f"slot {record.slot}: trace {record.trace_id} disconnected "
            f"(roots={tree['roots']}, orphans={tree['orphans']})"
        )
        assert tree["roots"] == 1
        assert tree["orphans"] == 0
        assert tree["span_count"] >= 1

    # blocks of one flush window settle under ONE trace (the window is
    # the causal unit), and the verify lanes put >1 thread lane in it
    by_window = {}
    for record in lineage:
        by_window.setdefault(record.flush_seq, set()).add(record.trace_id)
    assert all(len(tids) == 1 for tids in by_window.values())
    assert any(len(tree["lanes"]) > 1 for tree in trees.values())

    # the windows were counted as linked
    assert any(
        tree["span_count"] > 1 for tree in trees.values()
    )


def test_chrome_trace_flow_arrows_cross_lanes(chain):
    state, ctx, blocks = chain
    _, _, _, _, doc = _run_traced(
        state, ctx, blocks[:6],
        FlushPolicy(window_size=3, max_in_flight=2, verify_lanes=3),
    )
    flows = [e for e in doc["traceEvents"] if e.get("ph") in ("s", "f")]
    assert flows, "cross-lane adoption must emit flow start/finish pairs"
    starts = {e["id"] for e in flows if e["ph"] == "s"}
    finishes = {e["id"] for e in flows if e["ph"] == "f"}
    assert finishes and finishes <= starts | finishes
    # every finish has its start, and the pair spans two tids
    by_id = {}
    for e in flows:
        by_id.setdefault(e["id"], []).append(e)
    paired = [v for v in by_id.values() if len(v) == 2]
    assert paired
    assert any(v[0]["tid"] != v[1]["tid"] for v in paired)


# ---------------------------------------------------------------------------
# exemplar determinism under the seeded-reservoir contract
# ---------------------------------------------------------------------------


def test_exemplar_table_deterministic_and_reservoir_neutral():
    values = [((i * 37) % 101) / 100.0 for i in range(40)]
    a = metrics.Histogram("tracetest.exemplar.det")
    b = metrics.Histogram("tracetest.exemplar.det")  # same seed: same name
    plain = metrics.Histogram("tracetest.exemplar.det")
    for i, v in enumerate(values):
        a.observe(v, trace_id=i + 1, fields={"i": i})
        b.observe(v, trace_id=i + 1, fields={"i": i})
        plain.observe(v)

    # deterministic: same observations + trace ids -> identical tables
    assert a.exemplars() == b.exemplars()
    # worst-N by value, largest first
    worst = sorted(values, reverse=True)[: a.exemplar_limit]
    assert [e["value"] for e in a.exemplars()] == worst
    # no silent cap: every non-retained trace-carrying observation counted
    assert a.exemplar_dropped == len(values) - a.exemplar_limit

    # reservoir contract unchanged: exemplar bookkeeping never touches
    # the seeded RNG, so the sample matches a no-trace-id twin exactly
    assert a.values() == plain.values()
    assert a.quantiles() == plain.quantiles()
    assert plain.exemplars() == []

    # reset clears the table, not the accounting total
    a.reset_exemplars()
    assert a.exemplars() == []
    assert a.exemplar_dropped == len(values) - a.exemplar_limit


# ---------------------------------------------------------------------------
# /trace round trip through api/client.py
# ---------------------------------------------------------------------------


@pytest.fixture()
def live_server():
    from ethereum_consensus_tpu.telemetry.server import IntrospectionServer

    server = IntrospectionServer(port=0)
    server.start(start_flight=False)
    yield server
    server.stop()


def _client(server):
    from ethereum_consensus_tpu.api.client import Client

    return Client(server.url().rstrip("/"))


def test_trace_endpoint_round_trip(live_server):
    client = _client(live_server)
    flight.start()
    try:
        with spans.recording(capacity=spans.DEFAULT_CAPACITY):
            with trace.span("pool.admit", source="test"):
                ctx = trace.context()
            assert ctx is not None

            def settle():
                with trace.adopt(ctx):
                    with trace.span("pipeline.settle", slot=1):
                        pass

            worker = threading.Thread(target=settle, name="settle")
            worker.start()
            worker.join()
            trace.note_trace(ctx, "pool.window", 0.25, sets=3)
            flight.RECORDER.handle(
                "block",
                flight.BlockLineage(
                    slot=1, root="0x" + "11" * 32, trace_id=ctx.trace_id
                ),
            )

            # bare /trace: the slow-trace index
            index = client.get_trace()
            assert index["recording"] is True
            assert any(
                entry["trace_id"] == ctx.trace_id
                for entry in index["slow_traces"]
            )
            assert index["audit"]["orphans"] == 0

            # one assembled causal tree, lineage joined in
            tree = client.get_trace(ctx.trace_id)
            assert tree["trace_id"] == ctx.trace_id
            assert tree["connected"]
            assert tree["roots"] == 1
            names = {s["name"] for s in tree["spans"]}
            assert {"pool.admit", "pipeline.settle"} <= names
            assert [r["slot"] for r in tree["lineage"]] == [1]
            assert tree["lineage"][0]["trace_id"] == ctx.trace_id

            # error paths: unknown id -> 404, non-integer id -> 400
            with pytest.raises(ApiError) as unknown:
                client.get_trace(ctx.trace_id + 1_000_000)
            assert unknown.value.code == 404
            with pytest.raises(ApiError) as bad:
                client.http_get("trace", params={"id": "zebra"})
            assert bad.value.code == 400
    finally:
        flight.stop()


def test_trace_endpoint_joins_device_evidence_from_both_rings(live_server):
    """The ?id= device join: pre-timed device spans (completed ring: a
    compile) AND device.route instants (events ring) land in ``device``,
    with stamps rebased onto the recording origin so they sit inside the
    trace's relative window. A transfer is no longer on that lane: the
    seam's facade span is a node of the trace's own tree, under the span
    that paid for the copy."""
    pytest.importorskip("jax")
    np = pytest.importorskip("numpy")
    from ethereum_consensus_tpu.telemetry import device as device_obs

    client = _client(live_server)
    with spans.recording(capacity=spans.DEFAULT_CAPACITY):
        recorder = spans.RECORDER
        lane = recorder.named_lane("device")
        with trace.span("pool.admit", source="devjoin"):
            ctx = trace.context()
            now = time.perf_counter()
            recorder.add_complete(
                "device.compile",
                now,
                now + 1e-4,
                {"fn": "devjoin", "signature": "()", "recompile": False},
                lane=lane,
            )
            recorder.add_instant(
                "device.route",
                time.perf_counter(),
                {"kind": "verify", "choice": "device", "reason": "fits"},
                lane=lane,
            )
            device_obs.h2d("devjoin", np.arange(8, dtype=np.uint8))
        tree = client.get_trace(ctx.trace_id)
        names = [e["name"] for e in tree["device"]]
        assert names == ["device.compile", "device.route"]
        assert tree["device_count"] == 2
        t_lo = tree["t0_s"]
        t_hi = t_lo + tree["duration_s"]
        for event in tree["device"]:
            assert t_lo <= event["t0_s"] <= t_hi
        assert tree["device"][0]["duration_s"] == pytest.approx(1e-4)
        by_name = {s["name"]: s for s in tree["spans"]}
        upload = by_name["devjoin.h2d"]
        assert upload["parent_id"] == by_name["pool.admit"]["span_id"]
        assert upload["fields"]["bytes"] == 8
        assert tree["connected"]


def test_metrics_scrape_stays_classic_while_exemplars_live_on_trace(
    live_server,
):
    """The high-severity regression guard: an exemplar-holding
    histogram must NOT leak OpenMetrics ``# {...}`` syntax into the
    0.0.4 text exposition (a classic parser reads it as a malformed
    timestamp and fails the whole scrape); the table is served as JSON
    on bare ``/trace`` instead."""
    from ethereum_consensus_tpu.telemetry import server as tel_server

    hist = metrics.histogram("tracetest.scrape_guard_s")
    hist.reset_exemplars()
    hist.observe(0.5, trace_id=77, fields={"slot": 9})

    text = tel_server.render_prometheus([hist])
    assert "# {" not in text
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        float(value)  # classic sample lines: `name[{labels}] value`

    index = _client(live_server).get_trace()
    table = index["exemplars"]["tracetest.scrape_guard_s"]
    assert table[0]["trace_id"] == 77
    assert table[0]["value"] == 0.5


# ---------------------------------------------------------------------------
# inactive-path guard: tracing off costs one attribute read
# ---------------------------------------------------------------------------


def test_inactive_trace_context_is_one_attribute_read():
    assert not spans.RECORDER.enabled
    assert trace.context() is None
    # the off-path adopt is one shared instance, no allocation
    assert trace.adopt(None) is trace.adopt(None)

    n = 100_000
    context = trace.context
    t0 = time.perf_counter()
    for _ in range(n):
        context()
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 1e-6, (
        f"{per_call * 1e9:.0f}ns per disabled trace.context()"
    )


def test_span_ring_drop_counter_accounts_for_evictions():
    # a private recorder so the tiny ring never resizes the process-wide
    # one (SpanRecorder.start keeps its capacity across recordings)
    recorder = spans.SpanRecorder(capacity=4)
    before = metrics.counter("spans.dropped").value()
    recorder.start()
    for i in range(16):
        rec = recorder.begin("drop.guard", {"i": i})
        recorder.end(rec)
    recorder.stop()
    audit = recorder.audit()
    assert audit["dropped"] == 12
    assert metrics.counter("spans.dropped").value() - before == audit["dropped"]


# ---------------------------------------------------------------------------
# make trace-smoke: the tier-1 linked-trace gate
# ---------------------------------------------------------------------------


@pytest.mark.trace_smoke
def test_trace_smoke_two_lane_end_to_end(chain):
    state, ctx, blocks = chain
    linked_before = metrics.counter("trace.windows_linked").value()
    stats, lineage, trees, audit, _ = _run_traced(
        state, ctx, blocks[:6],
        FlushPolicy(window_size=3, max_in_flight=2, verify_lanes=2),
    )
    assert stats.blocks_committed == 6
    assert audit["dropped"] == 0
    assert audit["orphans"] == 0
    assert lineage and all(r.trace_id is not None for r in lineage)
    tree = trees[lineage[-1].trace_id]
    assert tree["connected"] and tree["orphans"] == 0
    assert metrics.counter("trace.windows_linked").value() > linked_before
