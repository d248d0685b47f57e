"""Device execution observatory (telemetry/device.py): compile ledger +
recompile sentinel, host<->device transfer ledger, device-vs-host
routing journal, the Chrome-trace device lane, the transfer seams' facade
spans, the /device endpoint,
BlockLineage.verify_route, and the off-path overhead guard."""

import json
import sys
import time
import urllib.request
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))
from chain_utils import (  # noqa: E402
    fresh_genesis,
    fresh_genesis_altair,
    produce_chain,
)

from ethereum_consensus_tpu import _device_flags  # noqa: E402
from ethereum_consensus_tpu.executor import Executor  # noqa: E402
from ethereum_consensus_tpu.pipeline import FlushPolicy  # noqa: E402
from ethereum_consensus_tpu.telemetry import device as device_obs  # noqa: E402
from ethereum_consensus_tpu.telemetry import flight  # noqa: E402
from ethereum_consensus_tpu.telemetry import metrics  # noqa: E402
from ethereum_consensus_tpu.telemetry import spans  # noqa: E402

np = pytest.importorskip("numpy")


@pytest.fixture(autouse=True)
def _observatory_off_between_tests():
    yield
    device_obs.stop()
    if spans.RECORDER.enabled:
        spans.stop_recording()


def _metric(name):
    return metrics.counter(name).value()


def _fallback_total():
    return sum(
        v for k, v in metrics.snapshot().items()
        if k.startswith("epoch_vector.fallback.")
    )


def _recorded_events(name):
    doc = spans.RECORDER.chrome_trace()
    return [e for e in doc["traceEvents"]
            if e.get("ph") == "i" and e.get("name") == name]


def _lane_names(doc):
    return {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }


# ---------------------------------------------------------------------------
# compile ledger + jit cache
# ---------------------------------------------------------------------------


def test_compile_ledger_and_jit_cache_hits():
    """A fresh shape through an observed kernel records exactly one
    compile with its signature; the same shape again is a jit-cache
    hit, not a compile."""
    pytest.importorskip("jax")
    from ethereum_consensus_tpu.ops import shuffle

    n, seed, rounds = 67, b"\x37" * 32, 10  # a shape nothing else here uses
    with device_obs.observing() as obs:
        compiles0 = _metric("device.compiles")
        hits0 = _metric("device.jit_cache.hits")
        shuffle.shuffled_indices_device(n, seed, rounds)
        compiles_after_first = _metric("device.compiles")
        shuffle.shuffled_indices_device(n, seed, rounds)
        assert compiles_after_first == compiles0 + 1
        assert _metric("device.compiles") == compiles_after_first
        assert _metric("device.jit_cache.hits") >= hits0 + 1
        ledger = obs.compiles()
    mine = [c for c in ledger
            if c["fn"] == "ops.shuffle._shuffle_rounds"
            and f"[{n}]" in c["signature"]]
    assert len(mine) == 1
    assert mine[0]["compile_s"] > 0
    assert f"uint32[{n}]" in mine[0]["signature"]


def test_recompile_sentinel_fires_once_with_both_signatures():
    """The acceptance check: a deliberate shape-drift re-trace of the
    same kernel fires the sentinel EXACTLY once, naming the old and new
    signatures; further drift keeps counting but does not re-fire the
    one-shot event (the ops_vector.fallback idiom)."""
    pytest.importorskip("jax")
    from ethereum_consensus_tpu.models.epoch_vector import jitted_kernels

    kernel = jitted_kernels()["inactivity_scores"]

    def run(n):
        return kernel(
            np.zeros(n, np.uint64), np.ones(n, bool), np.ones(n, bool),
            4, 16, False,
        )

    spans.start_recording()
    with device_obs.observing():
        recompiles0 = _metric("device.recompiles")
        run(64)                      # first compile — no drift yet
        assert _metric("device.recompiles") == recompiles0
        run(96)                      # drift: recompile + sentinel
        assert _metric("device.recompiles") == recompiles0 + 1
        run(128)                     # more drift: counter only
        assert _metric("device.recompiles") == recompiles0 + 2
        run(96)                      # known shape: cache hit, no count
        assert _metric("device.recompiles") == recompiles0 + 2
        events = _recorded_events("device.recompile")
    spans.stop_recording()
    ours = [e for e in events
            if e["args"]["fn"] == "epoch_vector.inactivity_scores_kernel"]
    assert len(ours) == 1, f"sentinel fired {len(ours)}x, want exactly 1"
    args = ours[0]["args"]
    assert "uint64[64]" in args["old_signature"]
    assert "uint64[96]" in args["new_signature"]


def test_jitted_epoch_kernels_bit_identical_to_numpy():
    """The observed jit route of the epoch kernels stays bit-identical
    to the production numpy path (the device-epoch-kernel staging
    contract)."""
    pytest.importorskip("jax")
    from ethereum_consensus_tpu.models import epoch_vector

    rng = np.random.default_rng(3)
    n = 257
    scores = rng.integers(0, 1 << 20, n, dtype=np.uint64)
    eligible = rng.random(n) < 0.9
    participating = rng.random(n) < 0.7
    host = epoch_vector.inactivity_scores_kernel(
        np, scores, eligible, participating, 4, 16, True
    )
    dev = epoch_vector.jitted_kernels()["inactivity_scores"](
        scores, eligible, participating, 4, 16, True
    )
    assert np.array_equal(np.asarray(dev), host)


# ---------------------------------------------------------------------------
# transfer ledger
# ---------------------------------------------------------------------------


def test_transfer_ledger_counts_and_bytes_per_site():
    pytest.importorskip("jax")
    arr = np.arange(100, dtype=np.uint64)  # 800 bytes
    with device_obs.observing() as obs:
        h2d_bytes0 = _metric("device.transfer.h2d_bytes")
        h2d_count0 = _metric("device.transfer.h2d_count")
        out = device_obs.h2d("test.site", arr)
        back = device_obs.d2h("test.site", out)
        assert _metric("device.transfer.h2d_bytes") == h2d_bytes0 + 800
        assert _metric("device.transfer.h2d_count") == h2d_count0 + 1
        summary = obs.transfer_summary()
    assert np.array_equal(back, arr)
    site = summary["sites"]["test.site"]
    assert site["h2d_count"] == 1 and site["h2d_bytes"] == 800
    assert site["d2h_count"] == 1 and site["d2h_bytes"] == 800
    assert summary["totals"]["h2d_bytes"] >= 800


def test_transfers_are_facade_spans_under_the_callers_span():
    """A copy through the seams is a facade span ``<site>.h2d`` /
    ``<site>.d2h`` with ``bytes=``, on the thread that paid it and
    nested under the caller's span: not a pre-timed interval on the
    virtual ``device`` lane."""
    pytest.importorskip("jax")
    from ethereum_consensus_tpu.utils import trace

    arr = np.arange(64, dtype=np.uint64)
    spans.start_recording()
    try:
        with device_obs.observing():
            with trace.span("lane.caller"):
                device_obs.d2h("lane.site", device_obs.h2d("lane.site", arr))
        records = spans.RECORDER.records()
        doc = spans.RECORDER.chrome_trace()
    finally:
        spans.stop_recording()
    by_name = {r.name: r for r in records}
    caller = by_name["lane.caller"]
    for name in ("lane.site.h2d", "lane.site.d2h"):
        rec = by_name[name]
        assert rec.parent_id == caller.span_id and rec.lane == caller.lane
        assert rec.fields["bytes"] == arr.nbytes
        assert caller.t0 <= rec.t0 and rec.t1 <= caller.t1
    assert "device" not in _lane_names(doc)
    assert not [e for e in doc["traceEvents"]
                if e.get("name") in ("device.h2d", "device.d2h")]


def test_transfer_spans_need_no_observatory():
    """The seams' spans follow the facade's sinks, not the observatory:
    with the ledger off the copy is still timed (and the byte tallies
    stay where they were)."""
    pytest.importorskip("jax")
    assert not device_obs.is_observing()
    h2d_bytes0 = _metric("device.transfer.h2d_bytes")
    n0 = _metric("span.quiet.site.h2d.n")
    with spans.recording() as recorder:
        (placed,) = device_obs.h2d_put("quiet.site", [np.arange(8)])
        device_obs.d2h("quiet.site", placed)
        names = sorted(r.name for r in recorder.records())
    assert names == ["quiet.site.d2h", "quiet.site.h2d"]
    assert _metric("span.quiet.site.h2d.n") == n0 + 1
    assert _metric("device.transfer.h2d_bytes") == h2d_bytes0


def test_a_started_download_is_one_span_and_one_ledger_entry():
    """``d2h_start`` only queues the copy: no span and no ledger entry of
    its own; the ``d2h`` that collects it owns both, once."""
    pytest.importorskip("jax")
    arr = np.arange(100, dtype=np.uint32).reshape(4, 25)  # 400 bytes
    with device_obs.observing() as obs, spans.recording() as recorder:
        placed = device_obs.h2d("started.site", arr)
        device_obs.d2h_start(placed)
        assert obs.transfer_summary()["sites"]["started.site"]["d2h_count"] == 0
        back = device_obs.d2h("started.site", placed)
        names = sorted(r.name for r in recorder.records())
        site = obs.transfer_summary()["sites"]["started.site"]
    assert np.array_equal(back, arr)
    assert names == ["started.site.d2h", "started.site.h2d"]
    assert site["d2h_count"] == 1 and site["d2h_bytes"] == 400


# ---------------------------------------------------------------------------
# routing journal
# ---------------------------------------------------------------------------


def test_device_flags_journal_threshold_decisions(monkeypatch):
    monkeypatch.setattr(_device_flags, "SWEEPS_MIN_N", 100)
    with device_obs.observing() as obs:
        assert not _device_flags.sweeps_enabled(10)
        assert _device_flags.sweeps_enabled(1000)
        routes = obs.routes()
    mine = [r for r in routes if r["kind"] == "sweeps"]
    assert len(mine) == 2
    below, above = mine
    assert below["choice"] == "host"
    assert below["reason"] == "below_threshold"
    assert below["inputs"] == {"n": 10, "threshold": 100}
    assert above["choice"] == "device"
    assert above["reason"] == "routed"
    # the tallies and the device.route.* counters agree (the bench's
    # journal_consistent cross-check, in miniature)
    tallies = obs.route_tallies()["sweeps"]
    assert tallies == {"host": 1, "device": 1}


def test_pairing_route_journaled_and_thread_local(monkeypatch):
    """A host RLC batch journals pairing→host with its threshold inputs
    and stamps the thread-local last_batch_route."""
    from ethereum_consensus_tpu.crypto import bls

    sks = [bls.SecretKey(i + 31) for i in range(3)]
    sets = [
        bls.SignatureSet([sk.public_key()], b"msg-%d" % i,
                         sk.sign(b"msg-%d" % i))
        for i, sk in enumerate(sks)
    ]
    with device_obs.observing() as obs:
        host0 = _metric("bls.pairing_route.host")
        verdicts = bls.verify_signature_sets(sets)
        assert verdicts == [True, True, True]
        host_routes = [r for r in obs.routes() if r["kind"] == "pairing"]
    assert bls.last_batch_route() == "host"
    assert _metric("bls.pairing_route.host") == host0 + 1
    assert len(host_routes) == 1
    assert host_routes[0]["choice"] == "host"
    assert host_routes[0]["inputs"]["sets"] == 3
    # threshold inputs present (None = device route not installed)
    assert "threshold" in host_routes[0]["inputs"]


def test_epoch_vector_decline_reasons_counted_and_one_shot(monkeypatch):
    """ISSUE 10 satellite: the previously-silent decline
    (below_threshold) gets the PR 5 treatment — a counter per occurrence
    and ONE trace event per reason per process — and lands in the
    routing journal with its threshold inputs. Its opposite: an
    installed sweeps gate is no reason to decline. A phase0 pass above
    the (lowered) engine threshold engages with the gate on, counts no
    fallback, never consults the gate (it selects the altair family's
    fused kernel and nothing else) and leaves the literal stage list's
    state."""
    from ethereum_consensus_tpu.models import epoch_vector
    from ethereum_consensus_tpu.models.phase0 import epoch_processing
    from ethereum_consensus_tpu.models.phase0.slot_processing import (
        process_slots,
    )

    state, ctx = fresh_genesis(64, "minimal")
    state = state.copy()
    process_slots(state, int(ctx.SLOTS_PER_EPOCH) - 1, ctx)
    # a clean slate for the one-shot set so this test is order-free
    monkeypatch.setattr(epoch_vector, "_FALLBACK_SEEN", set())

    spans.start_recording()
    with device_obs.observing() as obs:
        below0 = _metric("epoch_vector.fallback.below_threshold")
        assert not epoch_vector.process_epoch_columnar(state, ctx, "phase0")
        assert not epoch_vector.process_epoch_columnar(state, ctx, "phase0")
        assert (
            _metric("epoch_vector.fallback.below_threshold") == below0 + 2
        )
        literal = state.copy()
        epoch_processing.process_epoch(literal, ctx)  # declines: the list

        monkeypatch.setattr(epoch_vector, "EPOCH_VECTOR_MIN_VALIDATORS", 0)
        monkeypatch.setattr(_device_flags, "SWEEPS_MIN_N", 1)
        declines0 = _fallback_total()
        epochs0 = _metric("epoch_vector.epochs")
        columnar = state.copy()
        assert epoch_vector.process_epoch_columnar(columnar, ctx, "phase0")
        assert _fallback_total() == declines0
        assert _metric("epoch_vector.epochs") == epochs0 + 1
        journal = [r for r in obs.routes() if r["kind"] == "epoch_vector"]
        gate = [r for r in obs.routes() if r["kind"] == "sweeps"]
        events = _recorded_events("epoch_vector.fallback")
    spans.stop_recording()

    by_reason = {}
    for e in events:
        by_reason.setdefault(e["args"]["reason"], []).append(e)
    assert list(by_reason) == ["below_threshold"]
    assert len(by_reason["below_threshold"]) == 1  # one-shot
    below = [r for r in journal if r["reason"] == "below_threshold"]
    assert len(below) == 3  # the two above and the literal run's own
    assert below[0]["inputs"]["validators"] == 64
    assert below[0]["inputs"]["threshold"] > 64
    engaged = [r for r in journal if r["reason"] == "engaged"]
    assert len(engaged) == 1 and engaged[0]["choice"] == "columnar"
    assert gate == []
    assert type(columnar).serialize(columnar) == type(literal).serialize(
        literal
    )
    assert type(columnar).hash_tree_root(columnar) == type(
        literal
    ).hash_tree_root(literal)


# ---------------------------------------------------------------------------
# the acceptance replay: device lane in a pipelined trace + verify_route
# ---------------------------------------------------------------------------


def test_pipelined_replay_trace_has_device_lane_and_verify_route(monkeypatch):
    """A pipelined replay with recording on, crossing an epoch boundary
    with the fused epoch kernel routed (host JAX backend here — the same
    machinery chip_smoke.py drives on the chip), yields a Chrome
    trace whose `device` lane carries the compiles and whose thread
    lanes carry the transfers as facade spans inside the epoch pass; the
    flight lineage of every flushed block names the pairing route that
    verified its window."""
    pytest.importorskip("jax")
    from ethereum_consensus_tpu import ops
    from ethereum_consensus_tpu.models import epoch_vector
    from ethereum_consensus_tpu.models.altair.slot_processing import (
        process_slots,
    )

    # the fused route is the altair family's, and the genesis epoch's
    # boundary runs no inactivity or rewards stage: start in epoch 1
    # (minimal SLOTS_PER_EPOCH=8) and cross the boundary at slot 16
    state, ctx = fresh_genesis_altair(64, "minimal")
    state = state.copy()
    process_slots(state, 11, ctx)
    blocks = produce_chain(state, ctx, 8, fork_name="altair")

    sequential = Executor(state.copy(), ctx)  # the literal stage list
    for b in blocks:
        sequential.apply_block(b)

    # the columnar pass engages at 64 validators for this run, and its
    # gate selects the fused jitted kernel
    monkeypatch.setattr(epoch_vector, "EPOCH_VECTOR_MIN_VALIDATORS", 0)
    # jitted kernels of this test's own, so that its boundary compiles:
    # every registry of up to 2^16 rows is dispatched in one shape
    # (fused_dispatch_rows), which an earlier test of this process has
    # compiled by now
    monkeypatch.setattr(epoch_vector, "_JITTED_KERNELS", {})
    fused0 = _metric("epoch_vector.fused.jit")
    ops.install(
        sweeps_min_n=1,            # the epoch pass runs the fused kernel
        shuffle_min_n=1 << 30,     # keep everything else host-side
        bls_agg_min_n=1 << 30,
        pairing_min_sets=None,
    )
    flight.start()
    spans.start_recording()
    try:
        with device_obs.observing() as obs:
            ex = Executor(state.copy(), ctx)
            ex.stream(blocks, policy=FlushPolicy(window_size=4))
            doc = spans.RECORDER.chrome_trace()
            compiles = obs.compiles()
    finally:
        spans.stop_recording()
        flight.stop()
        ops.uninstall()

    # bit-identity is not negotiable under instrumentation
    assert (
        ex.state.hash_tree_root() == sequential.state.hash_tree_root()
    )
    assert _metric("epoch_vector.fused.jit") == fused0 + 1
    assert any(
        c["fn"] == "epoch_vector.fused_epoch_kernel" for c in compiles
    ), "the epoch boundary's fused kernel should have compiled"

    assert "device" in _lane_names(doc)
    device_lane = next(
        e["tid"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
        and e["args"]["name"] == "device"
    )
    by_name = {}
    for e in doc["traceEvents"]:
        if e.get("ph") == "X":
            by_name.setdefault(e["name"], []).append(e)
    compiles_on_lane = by_name.get("device.compile", [])
    assert compiles_on_lane, "no compile events"
    assert all(e["tid"] == device_lane for e in compiles_on_lane)
    # the fused route's uploads: facade spans from the h2d seam on the
    # thread that ran the epoch stage, inside its span, with the bytes moved
    by_id = {e["args"]["span_id"]: e for es in by_name.values() for e in es}
    uploads = [e for name, es in by_name.items() if name.endswith(".h2d")
               for e in es]
    assert uploads, "no transfer span from the h2d seam"
    for upload in uploads:
        assert upload["name"].startswith("epoch_vector.fused")
        parent = by_id[upload["args"]["parent_id"]]
        assert parent["name"] == "epoch_vector.fused"
        assert parent["tid"] == upload["tid"] != device_lane
        assert upload["args"]["bytes"] > 0

    # lineage: every committed block that rode a non-empty flush window
    # carries the route that verified it (host on this box)
    committed = flight.RECORDER.by_outcome("committed")
    assert committed
    flushed = [r for r in committed if r.flush_sets]
    assert flushed
    assert all(r.verify_route == "host" for r in flushed)
    # and the JSONL/dict surface carries it too
    assert flushed[0].to_dict()["verify_route"] == "host"


# ---------------------------------------------------------------------------
# /device endpoint
# ---------------------------------------------------------------------------


def test_device_endpoint_serves_ledgers():
    pytest.importorskip("jax")
    from ethereum_consensus_tpu.telemetry.server import IntrospectionServer

    with device_obs.observing() as obs:
        device_obs.d2h(
            "endpoint.site",
            device_obs.h2d("endpoint.site", np.arange(8, dtype=np.uint64)),
        )
        device_obs.route("pairing", "host", "below_threshold", sets=2,
                         threshold=512)
        srv = IntrospectionServer(port=0).start(start_flight=False)
        try:
            doc = json.loads(
                urllib.request.urlopen(srv.url("/device?n=16"), timeout=10)
                .read()
            )
        finally:
            srv.stop()
        assert doc["observing"] is True
        site = doc["transfer_ledger"]["sites"]["endpoint.site"]
        assert site["h2d_bytes"] == 64
        tallies = doc["routing_journal"]["tallies"]
        assert tallies["pairing"]["host"] >= 1
        recent = doc["routing_journal"]["recent"]
        assert any(r["kind"] == "pairing" and r["inputs"]["sets"] == 2
                   for r in recent)
        assert "persistent_cache" in doc and "dir" in doc["persistent_cache"]
        assert doc["compile_ledger"]["compiles"] == len(obs.compiles())


def test_metrics_endpoint_carries_build_info():
    from ethereum_consensus_tpu.telemetry.server import (
        IntrospectionServer,
        build_info_labels,
    )

    labels = build_info_labels()
    assert set(labels) == {"git_sha", "jax", "numpy", "x64", "backend"}
    srv = IntrospectionServer(port=0).start(start_flight=False)
    try:
        text = urllib.request.urlopen(
            srv.url("/metrics"), timeout=10
        ).read().decode()
    finally:
        srv.stop()
    lines = [line for line in text.splitlines()
             if line.startswith("build_info{")]
    assert len(lines) == 1
    assert 'numpy="' + labels["numpy"] + '"' in lines[0]
    assert lines[0].endswith(" 1")
    assert "# TYPE build_info gauge" in text


def test_sse_keepalive_pings_idle_subscriber():
    """ISSUE 10 satellite: an idle /events subscriber sees `: ping`
    keepalive comments on the configured interval — read across two
    intervals."""
    from ethereum_consensus_tpu.telemetry.server import IntrospectionServer

    srv = IntrospectionServer(port=0, sse_keepalive_s=0.3).start(
        start_flight=False
    )
    try:
        req = urllib.request.urlopen(srv.url("/events"), timeout=10)
        pings = 0
        t0 = time.monotonic()
        for raw in req:
            if raw.decode().strip() == ": ping":
                pings += 1
                if pings >= 2:
                    break
            assert time.monotonic() - t0 < 8, "keepalives never arrived"
        elapsed = time.monotonic() - t0
        req.close()
    finally:
        srv.stop()
    assert pings >= 2
    # two pings require at least two full intervals of idle stream
    assert elapsed >= 0.6


# ---------------------------------------------------------------------------
# off-path overhead
# ---------------------------------------------------------------------------


def test_inactive_observatory_guard_is_sub_microsecond():
    """With the observatory off, the hot dispatch seams pay one bool
    read (the span-recorder/commit-hook contract): sub-µs per check."""
    assert not device_obs.is_observing()
    obs = device_obs.OBSERVATORY
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        if obs.active:  # pragma: no cover - never true here
            raise AssertionError
    per_read = (time.perf_counter() - t0) / n
    assert per_read < 5e-6, f"{per_read * 1e6:.2f}µs per inactive check"
    # the journal entry point itself short-circuits on the same read
    # (ledgers from earlier observations stay readable after stop(), so
    # compare counts, not emptiness)
    journal_before = len(device_obs.OBSERVATORY.routes())
    t0 = time.perf_counter()
    for _ in range(n):
        device_obs.route("pairing", "host", "below_threshold", sets=1)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 5e-6, f"{per_call * 1e6:.2f}µs per inactive route()"
    assert len(device_obs.OBSERVATORY.routes()) == journal_before


def test_observed_jit_inactive_passthrough():
    """An observed kernel with the observatory off records nothing and
    returns the jitted result unchanged."""
    jax = pytest.importorskip("jax")

    calls = []

    def f(x):
        calls.append(1)
        return x + 1

    wrapped = device_obs.observe_jit(jax.jit(f), "test.passthrough")
    compiles0 = _metric("device.compiles")
    out = wrapped(np.arange(4))
    assert np.array_equal(np.asarray(out), np.arange(4) + 1)
    assert _metric("device.compiles") == compiles0
    assert wrapped.__wrapped__ is not None
