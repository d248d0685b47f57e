"""Memory & bandwidth observatory tests (telemetry/memory.py,
docs/OBSERVABILITY.md memory lane — ISSUE 15).

Differential discipline: the census rows are checked against DIRECTLY
measured ``nbytes``/lengths of the structures they claim to attribute
(a census that can't be cross-checked is a guess with a dashboard);
the bandwidth counters are checked byte-exact at ``bulk_store``; the
phase ledger is checked across a REAL 2^14 epoch transition; and the
off path is bounded sub-µs (the spans/device observatory contract).
``test_mem_smoke`` is the ``make mem-smoke`` gate.
"""

import json
import os
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))

import chain_utils  # noqa: E402

from ethereum_consensus_tpu.models import ops_vector  # noqa: E402
from ethereum_consensus_tpu.serving import HeadStore  # noqa: E402
from ethereum_consensus_tpu.soak import LeakSentinel, SoakConfig  # noqa: E402
from ethereum_consensus_tpu.soak.runner import load_profile  # noqa: E402
from ethereum_consensus_tpu.ssz import core as ssz_core  # noqa: E402
from ethereum_consensus_tpu.telemetry import memory as mem  # noqa: E402
from ethereum_consensus_tpu.telemetry import metrics  # noqa: E402
from ethereum_consensus_tpu.utils import trace  # noqa: E402


@pytest.fixture()
def observatory():
    """A fresh observation per test (ledgers cleared; stopped after)."""
    mem.start()
    try:
        yield mem.OBSERVATORY
    finally:
        mem.stop()


# ---------------------------------------------------------------------------
# resident-set census: rows vs directly measured bytes
# ---------------------------------------------------------------------------


def test_census_column_owner_exact(observatory):
    """A list-resident column cache censuses at exactly its array's
    nbytes, and the entry appears only once however many walks run."""
    n = 4096
    lst = ssz_core.CachedRootList([0] * n)
    ops_vector.install_zero_column(lst, n)  # uint8 zero column: n bytes
    before = observatory.census()["ssz.columns"]
    assert before["bytes"] == n
    assert before["entries"] == 1
    again = observatory.census()["ssz.columns"]
    assert again == before  # probes are idempotent, no double count


def test_census_column_owner_dedups_shared_buffers(observatory):
    """Copy-on-write column travel shares ONE buffer across state
    copies — the census must count it once, not per holder."""
    n = 2048
    lst = ssz_core.CachedRootList([0] * n)
    ops_vector.install_zero_column(lst, n)
    copied = ssz_core._copy_value(
        type("T", (), {"elem": None})(), lst
    )
    assert copied._col_cache[1] is lst._col_cache[1]  # shared buffer
    row = observatory.census()["ssz.columns"]
    assert row["bytes"] == n, row  # once, not twice
    assert row["entries"] == 1


def test_census_bitpack_owner_exact(observatory):
    """The Bitlist root cache's packed-bits entry censuses at exactly
    the packed byte length."""
    bits = 1000
    bl = ssz_core.CachedRootList([True, False] * (bits // 2))
    t = ssz_core.Bitlist(2048)
    t.hash_tree_root(bl)  # populates _root_cache["bitpack"]
    assert bl._root_cache.get("bitpack") is not None
    row = observatory.census()["ssz.bitpack"]
    assert row["bytes"] == (bits + 7) // 8
    assert row["entries"] == 1


def test_census_tree_memo_counts_every_stored_level_once(observatory):
    """A container list's memo names its element roots twice (the memo's
    chunks ARE the tree's level 0): the census counts each stored level
    once, and a copy that shares the memo adds nothing."""
    class Row(ssz_core.Container):
        a: ssz_core.uint64
        b: ssz_core.uint64

    n = 4096 + 40
    LT = ssz_core.List[Row, 1 << 20]
    rows = ssz_core.CachedRootList(Row(a=i, b=i) for i in range(n))
    LT.hash_tree_root(rows)
    tm = rows._tree_memo
    assert tm[1] is tm[2].levels[0] and len(tm[1]) == 32 * n
    want, width = 0, n
    for _ in range(21):  # levels 0..20 of a 2^20 limit, populated region
        want += 32 * width
        width = (width + 1) // 2
    row = observatory.census()["ssz.tree_memo"]
    assert row == {"bytes": want, "entries": 1}
    twin = ssz_core._copy_value(LT, rows)
    assert twin._tree_memo is tm
    assert observatory.census()["ssz.tree_memo"] == row
    twin[7].a = 9  # the first splice clones: now two trees
    LT.hash_tree_root(twin)
    assert observatory.census()["ssz.tree_memo"] == {
        "bytes": 2 * want, "entries": 2,
    }


def test_census_snapshot_owner_exact(observatory):
    """A HeadStore snapshot's frozen column bundle censuses at exactly
    the sum of its (deduped) array nbytes."""
    state, ctx = chain_utils.fresh_genesis(8)
    store = HeadStore()
    snap = store.publish(state, ctx)
    bundle = snap.bundle()
    assert bundle is not None
    expected = 0
    seen = set()
    for arr in bundle.values():
        if id(arr) not in seen:
            seen.add(id(arr))
            expected += arr.nbytes
    nbytes, entries = store.memory_census()
    assert nbytes == expected
    assert entries == 1
    row = observatory.census()["serving.snapshots"]
    assert row["bytes"] >= expected  # other live stores may add to it
    assert row["entries"] >= 1


def test_worst_table_ranks_by_bytes(observatory):
    """worst(n) is the attribution table: largest owner first, with
    mb/entries columns."""
    big = ssz_core.CachedRootList([0] * 8192)
    small = ssz_core.CachedRootList([0] * 512)
    ops_vector.install_zero_column(big, 8192)
    ops_vector.install_zero_column(small, 512)
    table = observatory.worst(4)
    assert table, "no owners reported"
    assert table[0]["owner"] == "ssz.columns"
    assert table[0]["bytes"] == 8192 + 512
    assert [row["bytes"] for row in table] == sorted(
        (row["bytes"] for row in table), reverse=True
    )


def test_owner_gauges_set_by_census(observatory):
    lst = ssz_core.CachedRootList([0] * 1024)
    ops_vector.install_zero_column(lst, 1024)
    observatory.census()
    assert metrics.gauge("memory.owner.ssz.columns.bytes").value() == 1024


# ---------------------------------------------------------------------------
# bandwidth ledger: byte-exact at bulk_store
# ---------------------------------------------------------------------------


def test_bulk_store_bandwidth_byte_exact(observatory):
    """A wire-width column handed to bulk_store counts exactly its
    nbytes at the ssz.bulk_store site (and in the registry counters)."""
    n = 1 << 12
    lst = ssz_core.CachedRootList([0] * n)
    col = np.arange(n, dtype=np.uint64)
    before = metrics.counter("memory.copy_bytes").value()
    ssz_core.bulk_store(lst, col, np.arange(n))
    sites = observatory.copy_summary()["sites"]
    assert sites["ssz.bulk_store"]["bytes"] == col.nbytes  # 8n, exact
    assert sites["ssz.bulk_store"]["count"] == 1
    assert (
        metrics.counter("memory.copy_bytes").value() - before == col.nbytes
    )
    # plain-list splices use the documented pointer-width estimate
    ssz_core.bulk_store(lst, [1] * n, range(n))
    assert sites_after_bytes(observatory) == col.nbytes + n * 8


def sites_after_bytes(observatory):
    return observatory.copy_summary()["sites"]["ssz.bulk_store"]["bytes"]


def test_state_copy_bandwidth_counts_pointer_bytes(observatory):
    """A state copy's structural list traffic lands at ssz.state_copy
    (8 bytes per element slot)."""
    state, _ctx = chain_utils.fresh_genesis(8)
    before = observatory.copy_summary()["sites"].get(
        "ssz.state_copy", {"bytes": 0}
    )["bytes"]
    state.copy()
    after = observatory.copy_summary()["sites"]["ssz.state_copy"]
    assert after["bytes"] > before  # the copy moved measurable bytes
    assert after["count"] > 0


def _packed_list(n: int):
    """A u64 list past the tracking threshold whose pack tree is built, so
    its next root after a write is a splice."""
    lst = ssz_core.CachedRootList([0] * n)
    ssz_core.List(ssz_core.uint64, 1 << 20).hash_tree_root(lst)
    return lst


def _bulk_store_site(n: int):
    lst = ssz_core.CachedRootList([0] * n)
    ssz_core.bulk_store(lst, np.ones(n, dtype=np.uint64), np.arange(n))


def _packed_splice_site(n: int):
    lst = _packed_list(n)
    ssz_core.bulk_store(lst, [1] * n, range(n))  # every group dirty
    ssz_core.List(ssz_core.uint64, 1 << 20).hash_tree_root(lst)


def _column_serialize_site(n: int):
    ssz_core.List(ssz_core.uint64, 1 << 20).hash_tree_root(
        ssz_core.CachedRootList([1] * n)
    )


@pytest.mark.parametrize("site, make, span", [
    ("ssz.bulk_store", _bulk_store_site, None),
    ("ssz.packed_splice", _packed_splice_site, "ssz.packed_splice"),
    ("ssz.column_serialize", _column_serialize_site, "ssz.full_pack"),
])
def test_bandwidth_renders_on_memory_trace_lane(observatory, site, make, span):
    """A timed copy site renders as complete events on the `memory`
    virtual lane of the Chrome trace (the device-lane idiom). The two ssz
    sites inside a root keep their bytes and take no time of their own:
    their seconds are the facade span around the same work."""
    from ethereum_consensus_tpu.telemetry import spans as tel_spans

    n = 1 << 15  # 8,192 chunks: over the tracking threshold
    before = observatory.copy_summary()["sites"].get(site, {"bytes": 0})
    with tel_spans.recording():
        make(n)
        doc = tel_spans.RECORDER.chrome_trace()
    after = observatory.copy_summary()["sites"][site]
    assert after["bytes"] - before["bytes"] == n * 8
    lanes = {
        e["args"]["name"]: e["tid"]
        for e in doc["traceEvents"]
        if e.get("name") == "thread_name"
    }
    copies = [
        e for e in doc["traceEvents"]
        if e.get("name") == "memory.copy"
        and e["tid"] == lanes.get("memory")
        and e["args"]["site"] == site
    ]
    if span is None:
        assert "memory" in lanes
        assert copies and copies[0]["args"]["bytes"] == n * 8
    else:
        assert copies == []
        (event,) = [e for e in doc["traceEvents"] if e.get("name") == span]
        assert event["dur"] > 0


# ---------------------------------------------------------------------------
# phase RSS ledger
# ---------------------------------------------------------------------------


def test_phase_ledger_brackets_transition_spans(observatory):
    """transition.* spans through the trace facade land in the phase
    ledger with counts and an RSS reading; non-phase spans don't."""
    with trace.span("transition.block", slot=1):
        pass
    with trace.span("pipeline.flush.verify"):
        pass
    ledger = observatory.phase_ledger()
    assert ledger["transition.block"]["count"] == 1
    assert ledger["transition.block"]["rss_end_mb"] > 0
    assert "pipeline.flush.verify" not in ledger


def test_explicit_phase_brackets(observatory):
    """memory.phase(...) brackets arbitrary mem.* names — the bench's
    state-build/cold/warm decomposition seam — and records retained
    growth for a bracket that allocates and keeps."""
    held = []
    with mem.phase("mem.test_alloc"):
        held.append(bytearray(32 << 20))  # 32 MB retained
        held[0][::4096] = b"x" * (len(held[0]) // 4096)  # touch pages
    rec = observatory.phase_ledger()["mem.test_alloc"]
    assert rec["count"] == 1
    assert rec["rss_delta_mb"] > 16, rec  # most of the 32 MB is resident
    del held


# ---------------------------------------------------------------------------
# zero-overhead guard
# ---------------------------------------------------------------------------


def test_inactive_observatory_guard_is_sub_microsecond():
    """With the observatory off, the hot seams pay one bool read (the
    span-recorder/device-observatory contract): sub-µs per check."""
    assert not mem.is_observing()
    obs = mem.OBSERVATORY
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        if obs.active:  # pragma: no cover - never true here
            raise AssertionError
    per_read = (time.perf_counter() - t0) / n
    assert per_read < 5e-6, f"{per_read * 1e6:.2f}µs per inactive check"
    # the module-level copy() entry point short-circuits on the same
    # read: totals must not move while off
    before = metrics.counter("memory.copy_bytes").value()
    t0 = time.perf_counter()
    for _ in range(n):
        mem.copy("test.site", 123)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 5e-6, f"{per_call * 1e6:.2f}µs per inactive copy()"
    assert metrics.counter("memory.copy_bytes").value() == before


def test_inactive_bulk_store_records_nothing():
    assert not mem.is_observing()
    lst = ssz_core.CachedRootList([0] * 64)
    before = dict(mem.OBSERVATORY.copy_summary()["totals"])
    ssz_core.bulk_store(lst, [1] * 64, range(64))
    assert mem.OBSERVATORY.copy_summary()["totals"] == before


# ---------------------------------------------------------------------------
# /memory endpoint round-trip
# ---------------------------------------------------------------------------


def test_memory_endpoint_roundtrip(observatory):
    from ethereum_consensus_tpu.telemetry.server import IntrospectionServer

    n = 4096
    lst = ssz_core.CachedRootList([0] * n)
    ops_vector.install_zero_column(lst, n)
    ssz_core.bulk_store(lst, np.ones(n, dtype=np.uint64), np.arange(n))
    with trace.span("transition.state_htr"):
        pass
    server = IntrospectionServer(port=0).start()
    try:
        with urllib.request.urlopen(
            server.url("/memory?n=4"), timeout=10
        ) as response:
            doc = json.loads(response.read())
    finally:
        server.stop()
    assert doc["observing"] is True
    assert doc["rss_mb"] > 0 and doc["peak_rss_mb"] >= doc["rss_mb"] - 1
    assert doc["census"]["ssz.columns"]["bytes"] == n
    assert len(doc["worst"]) <= 4
    assert doc["bandwidth"]["sites"]["ssz.bulk_store"]["bytes"] == n * 8
    assert doc["phase_ledger"]["transition.state_htr"]["count"] == 1
    # the endpoint is listed on the index document
    server2 = IntrospectionServer(port=0).start()
    try:
        with urllib.request.urlopen(server2.url("/"), timeout=10) as r:
            index = json.loads(r.read())
    finally:
        server2.stop()
    assert "/memory" in index["endpoints"]


# ---------------------------------------------------------------------------
# tracemalloc opt-in lifecycle
# ---------------------------------------------------------------------------


def test_tracemalloc_opt_in_lifecycle(monkeypatch):
    """ECT_TRACEMALLOC=1 starts tracemalloc with the observation, the
    phase ledger records traced deltas, top_sites reports, and stop()
    stops the tracing it started. Without the env, nothing traces."""
    import tracemalloc

    assert not tracemalloc.is_tracing()
    mem.start()
    try:
        assert not tracemalloc.is_tracing()  # opt-in only
    finally:
        mem.stop()

    monkeypatch.setenv("ECT_TRACEMALLOC", "1")
    mem.start()
    try:
        assert tracemalloc.is_tracing()
        held = []
        with mem.phase("mem.traced_alloc"):
            held.append(bytes(8 << 20))
        rec = mem.OBSERVATORY.phase_ledger()["mem.traced_alloc"]
        assert rec["traced_delta_mb"] > 7, rec
        sites = mem.top_sites(4)
        assert sites and sites[0]["bytes"] > 0
        del held
    finally:
        mem.stop()
    assert not tracemalloc.is_tracing()  # stopped what it started


# ---------------------------------------------------------------------------
# the LeakSentinel consumes THIS census (one implementation)
# ---------------------------------------------------------------------------


def test_sentinel_watch_owner_reads_observatory_census():
    flight_like = []
    mem.register_owner(
        "test.owned", lambda: (len(flight_like) * 100, len(flight_like))
    )
    try:
        sentinel = LeakSentinel()
        sentinel.watch_owner("owned", bound=3, owner="test.owned")
        for cycle in range(5):
            flight_like.append(cycle)
            sentinel.sample(cycle)
        verdict = sentinel.gate(budget_mb=1 << 20, warmup=1)
        assert verdict["census"]["owned"]["final"] == 5
        assert verdict["census"]["owned"]["ok"] is False  # 5 > bound 3
        assert verdict["ok"] is False
    finally:
        mem.OBSERVATORY.unregister_owner("test.owned")


def test_sentinel_watch_owner_fails_closed_on_unknown_owner():
    sentinel = LeakSentinel()
    sentinel.watch_owner("ghost", bound=10, owner="no.such.owner")
    for cycle in range(4):
        sentinel.sample(cycle)
    verdict = sentinel.gate(budget_mb=1 << 20, warmup=1)
    assert verdict["census"]["ghost"]["final"] == -1
    assert verdict["ok"] is False  # -1 rejects the bound: fail closed


def test_sentinel_ceiling_gate():
    """The per-deployment absolute ceiling trips on an impossible bound
    and passes on a generous one (growth budget untouched)."""
    sentinel = LeakSentinel()
    for cycle in range(4):
        sentinel.sample(cycle)
    verdict = sentinel.gate(budget_mb=1 << 20, warmup=1, ceiling_mb=1.0)
    assert verdict["ceiling_ok"] is False and verdict["ok"] is False
    verdict = sentinel.gate(budget_mb=1 << 20, warmup=1,
                            ceiling_mb=1 << 20)
    assert verdict["ceiling_ok"] is True and verdict["ok"] is True


# ---------------------------------------------------------------------------
# deployment profile (SoakConfig.from_file)
# ---------------------------------------------------------------------------


def test_soak_config_from_shipped_profile():
    config = SoakConfig.from_file()
    # the shipped profile IS the catastrophe-catcher defaults
    assert config.slo_verify_p99_s == 2.0
    assert config.rss_budget_mb == 96.0
    assert config.rss_ceiling_mb is None
    assert config.memory_ceilings["epoch"] == 12288
    # overrides win over the file
    assert SoakConfig.from_file(rss_budget_mb=10.0).rss_budget_mb == 10.0


def test_soak_config_from_toml_profile(tmp_path):
    path = tmp_path / "tight.toml"
    path.write_text(
        "name = \"tight\"\n"
        "[slo]\n"
        "verify_p99_s = 0.5\n"
        "[rss]\n"
        "budget_mb = 64\n"
        "ceiling_mb = 4096.0\n"
        "[load]\n"
        "cycles = 4\n"
    )
    config = SoakConfig.from_file(str(path))
    assert config.slo_verify_p99_s == 0.5
    assert config.rss_budget_mb == 64
    assert config.rss_ceiling_mb == 4096.0
    assert config.cycles == 4


def test_soak_config_profile_rejects_typos(tmp_path):
    path = tmp_path / "typo.json"
    path.write_text(json.dumps({"slo": {}, "load": {"cylces": 4}}))
    with pytest.raises(ValueError, match="cylces"):
        SoakConfig.from_file(str(path))
    path.write_text(json.dumps({"rs": {"budget_mb": 1}}))
    with pytest.raises(ValueError, match="rs"):
        SoakConfig.from_file(str(path))


def test_load_profile_memory_ceilings():
    ceilings = load_profile()["memory_ceilings"]
    assert ceilings["epoch"] < ceilings["epoch_xl"]


# ---------------------------------------------------------------------------
# the mem-smoke gate: a real 2^14 epoch under the observatory
# ---------------------------------------------------------------------------


@pytest.mark.mem_smoke
def test_mem_smoke():
    """``make mem-smoke``: one 2^14 deneb epoch transition with the
    observatory active — the phase ledger brackets the real transition
    spans, >=3 owners report entries, the bandwidth ledger saw the copy
    and the root's splice (and no boxing commit), and peak RSS sits under
    the profile ceiling
    (the bench ``mem`` evidence block's machinery, tier-1-sized)."""
    N = 1 << 14
    state, ctx = chain_utils.fast_registry_state(N, "deneb")
    import importlib

    sp = importlib.import_module(
        "ethereum_consensus_tpu.models.deneb.slot_processing"
    )
    spe = int(ctx.SLOTS_PER_EPOCH)
    sp.process_slots(state, spe, ctx)
    state.previous_epoch_participation = [0b111] * N

    mem.start()
    try:
        with mem.phase("mem.smoke_epoch"):
            s = state.copy()
            sp.process_slots(s, 2 * spe, ctx)
        ledger = mem.OBSERVATORY.phase_ledger()
        # the transition spans bracketed a REAL epoch: slot advances,
        # the epoch pass, state HTRs
        assert ledger["mem.smoke_epoch"]["count"] == 1
        transition_phases = [
            name for name in ledger if name.startswith("transition.")
        ]
        assert "transition.slot_advance" in transition_phases
        assert any(
            name in ledger
            for name in ("transition.process_epoch", "epoch_vector.pass")
        ), sorted(ledger)
        # >=3 owners reporting entries (columns + memos at minimum)
        census = mem.census()
        reporting = [
            name for name, row in census.items() if row["entries"] > 0
        ]
        assert len(reporting) >= 3, census
        assert census["ssz.columns"]["bytes"] > 0
        # the bandwidth ledger saw the copy and the post-epoch root; the
        # epoch commit itself moves no bytes any more: the balances column
        # is handed over, not boxed (ssz/column_list.py), so no
        # ``ssz.bulk_store`` of the registry's size shows
        sites = mem.OBSERVATORY.copy_summary()["sites"]
        assert sites.get("ssz.state_copy", {}).get("bytes", 0) > 0, sites
        assert sites.get("ssz.packed_splice", {}).get("bytes", 0) >= N * 8, sites
        assert sites.get("ssz.bulk_store", {}).get("bytes", 0) < N * 8, sites
        assert s.balances.__class__.__name__ == "ColumnList"
        # ceiling assertion off the shipped profile (the bench fold)
        ceiling = load_profile()["memory_ceilings"]["epoch"]
        assert mem.peak_rss_mb() <= ceiling, (
            f"2^14 smoke peaked {mem.peak_rss_mb():.0f} MB over the "
            f"{ceiling} MB epoch ceiling"
        )
    finally:
        mem.stop()
