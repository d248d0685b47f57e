"""Production soak tests (ethereum_consensus_tpu/soak/, docs/SOAK.md).

``test_soak_smoke`` is the ``make soak-smoke`` gate: a short but
complete soak — fork-boundary storm cycles + fault injection + reader
swarm + SSE subscriber + pool spam + equivocation (double AND surround)
traffic — with all three hard gates asserted. The leak-sentinel tests
guard the gate itself: a deliberately-leaky snapshot retainer MUST trip
the flat-RSS verdict (a sentinel that cannot fail is not a gate), and
the census/fail-closed edges are pinned at the unit level.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))

from ethereum_consensus_tpu.pipeline import (  # noqa: E402
    FlushPolicy,
    auto_verify_lanes,
)
from ethereum_consensus_tpu.soak import (  # noqa: E402
    LeakSentinel,
    SoakConfig,
    run_soak,
)


@pytest.fixture(autouse=True)
def _unlatched_health():
    """``/healthz`` reads process-wide latches (the ``pipeline.degraded``
    and ``pipeline.broken`` gauges, the flight recorder's last broken
    window) that a fault test of another file leaves set when it ran
    earlier in the same worker; each soak starts from a pipeline that
    nothing has broken yet, so its gate reads what the soak itself did."""
    from ethereum_consensus_tpu.telemetry import flight, metrics

    metrics.gauge("pipeline.degraded").set(0)
    metrics.gauge("pipeline.broken").set(0)
    flight.RECORDER.clear()


def _smoke_config(**overrides):
    base = dict(
        cycles=3,
        deadline_s=240.0,
        min_windows=20,
        readers=1,
        sse_subscribers=1,
        pool_spam_rounds=6,
        equivocate_every=1,
        rss_budget_mb=256.0,
        rss_warmup_cycles=1,
    )
    base.update(overrides)
    return SoakConfig(**base)


# ---------------------------------------------------------------------------
# the soak-smoke gate
# ---------------------------------------------------------------------------


@pytest.mark.soak_smoke
def test_soak_smoke():
    """A complete short soak: every load lane live, all three gates
    green, the surround-vote slashing surfaced AND executed."""
    report = run_soak(_smoke_config())
    gates = report["gates"]
    # gate 1: SLOs + healthz pinned to ok
    assert gates["slo"]["ok"], gates["slo"]
    assert gates["slo"]["healthz_all_ok"]
    assert gates["slo"]["healthz_samples"] == report["cycles"]
    # gate 2: flat RSS with every census inside its bound
    assert gates["rss"]["ok"], gates["rss"]
    assert all(c["ok"] for c in gates["rss"]["census"].values())
    # gate 3: bit-identity — roots, blame, ledger refeed, slashings
    identity = gates["identity"]
    assert identity["cycle_roots_ok"] and identity["blame_ok"]
    ledger = identity["ledger"]
    assert ledger["ledger_identical"], ledger
    assert ledger["surround_surfaced"] and ledger["surround_packed"], ledger
    assert ledger["equivocators_slashed"], ledger
    # sustained-load evidence: windows, reads, SSE commits, spam
    # accounting (no silent drops — PoolSpammer asserts internally too)
    assert report["windows"] >= report["min_windows"]
    assert report["storm_failures"] > 0  # the storm actually stormed
    assert report["faults_injected"], report  # injector lanes fired
    assert report["readers"]["ok"], report["readers"]
    assert report["readers"]["samples"] > 0
    assert report["sse_events"].get("commit", 0) > 0
    assert report["pool_spam_ok"] and report["pool_spam"]["fed"] > 0
    assert report["blocks_per_s"] > 0 and report["queries_per_s"] > 0
    assert report["ok"], {k: v for k, v in report.items() if k != "gates"}


# ---------------------------------------------------------------------------
# the leak sentinel must be trip-ABLE (guard against a vacuous gate)
# ---------------------------------------------------------------------------


def test_leak_sentinel_trips_on_leaky_retainer():
    """A deliberately-leaky snapshot retainer — the exact bug class the
    sentinel exists for — must trip the flat-RSS gate while the other
    gates stay green."""
    leaked = []

    def leaky_retainer(cycle, state):
        # retain a fresh multi-MB buffer per cycle (a "cache" that
        # never evicts): ~12 MB/cycle against a 10 MB budget. Anonymous
        # mmap, not the heap: in a warm test process the allocator can
        # satisfy heap requests from freed-but-resident pages (no RSS
        # delta), while touched anonymous mappings ALWAYS add resident
        # pages — the shape of a real leak the sentinel must see.
        import mmap

        buf = mmap.mmap(-1, 12 << 20)
        buf.write(bytes(len(buf)))  # touch every page
        leaked.append(buf)

    report = run_soak(_smoke_config(
        readers=0, sse_subscribers=0, pool_spam_rounds=0,
        storm_fraction=0.05, rss_budget_mb=10.0,
        retainers=(leaky_retainer,),
    ))
    assert len(leaked) == report["cycles"] >= 3
    rss = report["gates"]["rss"]
    assert rss["ok"] is False, rss
    assert rss["growth_mb"] > 10.0, rss
    # the leak is the ONLY thing wrong: identity + healthz still hold
    assert report["gates"]["identity"]["ok"], report["gates"]["identity"]
    assert report["gates"]["slo"]["healthz_all_ok"]
    assert report["ok"] is False


def test_leak_sentinel_census_bound_trips():
    """A watched structure census past its declared bound trips the
    gate even when RSS stays flat."""
    sentinel = LeakSentinel()
    grows = []
    sentinel.watch("grows", lambda: len(grows), bound=3)
    for cycle in range(5):
        grows.extend(range(2))
        sentinel.sample(cycle)
    verdict = sentinel.gate(budget_mb=1 << 20, warmup=1)
    assert verdict["ok"] is False
    assert verdict["census"]["grows"]["final"] == 10
    assert verdict["census"]["grows"]["ok"] is False


def test_leak_sentinel_fails_closed_without_samples():
    """Too few post-warmup samples must FAIL the gate — a soak that
    never sampled cannot claim flat memory."""
    sentinel = LeakSentinel()
    sentinel.sample(0)
    verdict = sentinel.gate(budget_mb=64, warmup=2)
    assert verdict["ok"] is False
    assert "too few" in verdict["error"]


def test_leak_sentinel_passes_flat_series():
    sentinel = LeakSentinel()
    for cycle in range(6):
        sentinel.sample(cycle)
    verdict = sentinel.gate(budget_mb=256, warmup=2)
    assert verdict["ok"] is True
    assert verdict["growth_mb"] <= 256


# ---------------------------------------------------------------------------
# verifier-lane auto-sizing (ROADMAP PR 12 residue)
# ---------------------------------------------------------------------------


def test_flush_policy_auto_sizes_verify_lanes():
    """Unset ``verify_lanes`` resolves to the machine-derived lane
    count; explicit values are untouched; zero still rejects."""
    auto = auto_verify_lanes()
    assert 1 <= auto <= 8
    assert FlushPolicy().verify_lanes == auto
    assert SoakConfig().policy.verify_lanes == auto  # the soak default
    assert FlushPolicy(verify_lanes=3).verify_lanes == 3
    with pytest.raises(ValueError):
        FlushPolicy(verify_lanes=0)


def test_auto_verify_lanes_respects_mesh_devices(monkeypatch):
    """Under ECT_MESH the auto size is min(cores, devices): this
    hermetic process provisions a 1-device mesh, so lanes resolve to 1
    regardless of core count."""
    from ethereum_consensus_tpu.parallel import runtime

    runtime.reset()
    monkeypatch.setenv("ECT_MESH", "1")
    try:
        assert auto_verify_lanes() == 1
    finally:
        monkeypatch.delenv("ECT_MESH", raising=False)
        runtime.reset()
