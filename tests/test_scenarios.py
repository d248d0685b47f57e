"""Adversarial scenario harness tests (ethereum_consensus_tpu/scenarios/):
every family at tier-1 shape, every storm geometry, and the pipeline's
fault hardening under deterministic injection.

Hang-proofing: every test that can wedge the verifier runs under a
``FlushPolicy.settle_timeout_s`` bound — a stuck worker raises
``PipelineBrokenError`` with the window's attribution instead of
deadlocking the suite (the satellite's "timeout-bounded joins" contract,
asserted directly in test_settle_timeout_raises_with_attribution).
"""

import random
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))

import chain_utils  # noqa: E402

from ethereum_consensus_tpu.error import InvalidBlock  # noqa: E402
from ethereum_consensus_tpu.executor import Executor  # noqa: E402
from ethereum_consensus_tpu.pipeline import (  # noqa: E402
    ChainPipeline,
    FaultInjector,
    FlushPolicy,
    PipelineBrokenError,
)
from ethereum_consensus_tpu.scenarios import (  # noqa: E402
    assert_bit_identical,
    bad_attestation_signature,
    bad_proposer_signature,
    bad_state_root,
    forced_columnar,
    future_slot,
    malformed_operation,
    oracle_replay,
    plan_storm,
    run_storm,
)
from ethereum_consensus_tpu.scenarios import families  # noqa: E402


# ---------------------------------------------------------------------------
# family 1 — full phase0→electra upgrade replay
# ---------------------------------------------------------------------------


def test_fork_boundary_replay_family():
    out = families.fork_boundary_replay()
    assert out["edges_checked"] == 5
    assert out["stats"]["rollbacks"] == 0
    assert out["stats"]["blocks_committed"] == out["blocks"]


def test_full_upgrade_chain_has_live_traffic_at_every_edge():
    """The chain the family replays must actually carry attestations in
    every fork segment and withdrawals in every capella+ segment —
    otherwise the boundary assertions are vacuous."""
    state, ctx, blocks = chain_utils.produce_full_upgrade_chain(64)
    spe = int(ctx.SLOTS_PER_EPOCH)
    by_epoch: dict = {}
    for b in blocks:
        by_epoch.setdefault(int(b.message.slot) // spe, []).append(b)
    assert sorted(by_epoch) == [0, 1, 2, 3, 4, 5]
    for epoch, segment in by_epoch.items():
        atts = sum(len(b.message.body.attestations) for b in segment)
        assert atts > 0, f"epoch {epoch}: no attestation traffic"
    for epoch in (3, 4, 5):  # capella, deneb, electra
        withdrawals = sum(
            len(b.message.body.execution_payload.withdrawals)
            for b in by_epoch[epoch]
        )
        assert withdrawals > 0, f"epoch {epoch}: no withdrawal traffic"


def test_full_upgrade_cache_key_isolated_by_parameters():
    """Satellite fix: differently-parameterized adversarial/scenario
    chains must land under different disk-cache keys than the honest
    bundle — same params hit the same artifact, any param or tag change
    misses it."""
    a = chain_utils.produce_full_upgrade_chain(64, atts_per_block=2)
    b = chain_utils.produce_full_upgrade_chain(64, atts_per_block=1)
    assert len(a[2]) == len(b[2])
    assert sum(len(x.message.body.attestations) for x in a[2]) > sum(
        len(x.message.body.attestations) for x in b[2]
    ), "atts_per_block=1 chain served from the =2 cache entry"
    # a scenario tag changes the key but not the content contract
    c = chain_utils.produce_full_upgrade_chain(64, cache_tag="scenario-x")
    assert [bytes(x.signature) for x in c[2]] == [
        bytes(x.signature) for x in a[2]
    ]


# ---------------------------------------------------------------------------
# family 2 — storm geometries
# ---------------------------------------------------------------------------

# window_size=4, checkpoint_interval=2 (run_storm default policy):
# window 0 = blocks 0-3, window 1 = blocks 4-7 (checkpoint-carrying)
GEOMETRIES = {
    "first_in_window": {0: bad_proposer_signature},
    "first_of_second_window": {4: bad_proposer_signature},
    "mid_window": {5: bad_proposer_signature},
    "last_in_window": {7: bad_proposer_signature},
    "two_in_one_flush": {4: bad_proposer_signature,
                         6: bad_proposer_signature},
    "checkpoint_edge": {7: bad_state_root},
    "bad_attestation_mid": {5: bad_attestation_signature},
    "structural_pair": {2: malformed_operation, 8: future_slot},
}


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_storm_geometry(geometry):
    plan = GEOMETRIES[geometry]
    report, ex = families.invalid_block_storm(n_blocks=10, plan=plan)
    assert [f.index for f in report.failures] == sorted(plan)
    for failure in report.failures:
        assert plan[failure.index].matches(failure.error)
    # pairing-path corruptions must have exercised a real rollback
    if any(not m.structural for m in plan.values()):
        assert any(
            snap["rollbacks"] > 0 for snap in report.stats_snapshots
        ), "no rollback recorded for a pairing-path corruption"


def test_storm_random_fraction_all_mutators():
    """A seeded random storm drawing from all five mutators recovers
    every failure with exact blame and bit-identical final state (the
    harness asserts both internally)."""
    report, ex = families.invalid_block_storm(
        n_blocks=12, fraction=0.4, seed=7
    )
    assert len(report.failures) == max(1, int(12 * 0.4))
    names = {f.mutator.name for f in report.failures}
    assert len(names) >= 3, f"storm drew too few mutator kinds: {names}"


def test_storm_on_multi_fork_chain():
    """A storm ACROSS the phase0→altair boundary: corruption on both
    sides of the upgrade, recovery state still bit-identical."""
    state, ctx, blocks = chain_utils.produce_multi_fork_chain(64)
    plan = {2: bad_proposer_signature, 8: bad_state_root}
    with forced_columnar():
        report, ex = run_storm(
            state, ctx, blocks, plan,
            sign=chain_utils.sign_block,
        )
    assert [f.index for f in report.failures] == [2, 8]


# ---------------------------------------------------------------------------
# families 3 + 4
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fork", ["altair", "deneb", "electra"])
def test_equivocation_family(fork):
    out = families.equivocation_traffic(fork)
    assert out["stats"]["rollbacks"] == 0
    assert out["stats"]["blocks_committed"] == out["blocks"]


def test_reorg_checkpoint_restore_family():
    out = families.deep_reorg_checkpoint_restore()
    assert out["head_a"] != out["head_b"]


def test_reorg_deeper_than_checkpoint_interval():
    out = families.deep_reorg_checkpoint_restore(
        prefix_len=6, branch_len=6,
        policy=FlushPolicy(window_size=3, max_in_flight=2,
                           checkpoint_interval=2),
    )
    assert out["reorg_depth"] == 6


# ---------------------------------------------------------------------------
# family 5 — injected infrastructure faults
# ---------------------------------------------------------------------------


def test_infrastructure_faults_family():
    out = families.infrastructure_faults()
    assert out["transient"]["fault_retries"] >= 3
    assert out["transient"]["degraded_flushes"] == 0
    assert out["worker_death"]["degraded_flushes"] >= 1
    assert out["wedged"]["window_seq"] == 0


def test_transient_exhaustion_degrades_instead_of_failing():
    """A PERSISTENT transient fault burns the retry budget, then the
    window degrades to in-line verification — the chain still lands
    bit-identically, no hang, no spurious consensus error."""
    state, ctx, blocks = chain_utils.produce_multi_fork_chain(64)
    oracle_ex, _ = oracle_replay(state, ctx, blocks)
    inj = FaultInjector().fail_flush(0, times=99)
    ex = Executor(state.copy(), ctx)
    pipe = ChainPipeline(
        ex,
        policy=FlushPolicy(window_size=3, max_in_flight=2,
                           flush_retries=1, retry_backoff_s=0.01,
                           settle_timeout_s=60.0),
        fault_injector=inj,
    )
    for block in blocks:
        pipe.submit(block)
    stats = pipe.close()
    assert stats.degraded_flushes >= 1
    assert stats.fault_retries == 1
    assert stats.rollbacks == 0
    assert_bit_identical(ex.state, oracle_ex.state, "exhausted-retry replay")


def test_settle_timeout_raises_with_attribution():
    """The bounded settle: a wedged verifier raises PipelineBrokenError
    naming the stuck window and its slots, the executor lands on the
    last committed position, and the pipeline refuses further blocks.
    This test's own bound IS the policy timeout — no external watchdog."""
    state, ctx, blocks = chain_utils.produce_multi_fork_chain(64)
    inj = FaultInjector().delay_flush(0, seconds=0.8)
    ex = Executor(state.copy(), ctx)
    pipe = ChainPipeline(
        ex,
        policy=FlushPolicy(window_size=2, max_in_flight=1,
                           settle_timeout_s=0.1, flush_retries=0),
        fault_injector=inj,
    )
    with pytest.raises(PipelineBrokenError) as excinfo:
        for block in blocks:
            pipe.submit(block)
        pipe.close()
    exc = excinfo.value
    assert exc.window_seq == 0
    assert list(exc.slots) == [int(b.message.slot) for b in blocks[:2]]
    assert_bit_identical(ex.state, state, "post-wedge committed position")
    with pytest.raises(PipelineBrokenError):
        pipe.submit(blocks[0])


def test_fault_during_storm_composes():
    """Faults and corruption TOGETHER: a transient fault on the same
    window whose block carries a bad signature — the retry must not
    launder the bad verdict, and the rollback still lands exactly."""
    state, ctx, blocks = chain_utils.produce_multi_fork_chain(64)
    plan = {1: bad_proposer_signature}
    inj = FaultInjector().fail_flush(0, times=1)
    with forced_columnar():
        report, ex = run_storm(
            state, ctx, blocks, plan,
            policy=FlushPolicy(window_size=3, max_in_flight=2,
                               flush_retries=2, retry_backoff_s=0.01,
                               checkpoint_interval=2),
            sign=chain_utils.sign_block,
            fault_injector=inj,
        )
    assert [f.index for f in report.failures] == [1]
    assert isinstance(report.failures[0].error, InvalidBlock)
    assert inj.injected, "the transient fault never fired"


# ---------------------------------------------------------------------------
# family 6 — electra EIP-7251 churn at the epoch boundary
# ---------------------------------------------------------------------------


def test_eip7251_churn_segment_family():
    """The full churn surface — ripe/slashed/unripe consolidations,
    pending deposits, paid partial withdrawals, the 0x01→0x02 switch —
    through the pipeline with the columnar-primary epoch pass forced:
    bit-identical to the scalar oracle and column-consistent at every
    edge (the assertions live in the family)."""
    out = families.eip7251_churn_segment()
    assert out["boundaries"] >= 2
    assert out["pending_deposits_left"] == 0
    assert out["pending_consolidations_left"] == 1  # the unripe one
    assert out["pending_partials_left"] == 0
    assert out["stats"]["rollbacks"] == 0


@pytest.mark.slow
def test_eip7251_churn_segment_natural_threshold():
    """The same churn segment at 4,096 validators — above
    EPOCH_VECTOR_MIN_VALIDATORS, so the columnar pass engages at its
    PRODUCTION threshold (no forced override doing the work)."""
    from ethereum_consensus_tpu.telemetry import metrics as _metrics

    before = _metrics.counter("epoch_vector.epochs").value()
    out = families.eip7251_churn_segment(validator_count=4096, epochs=1)
    assert out["boundaries"] >= 1
    assert _metrics.counter("epoch_vector.epochs").value() > before


# ---------------------------------------------------------------------------
# chaos smoke (make chaos) + the slow mainnet-scale storm
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.chaos_smoke
def test_chaos_smoke():
    """`make chaos`: one short storm + one full fork-boundary chain in
    minutes, asserting the harness contract end-to-end (the
    bench_smoke-style marker gate)."""
    out = families.fork_boundary_replay()
    assert out["edges_checked"] == 5
    report, _ = families.invalid_block_storm(
        n_blocks=8, plan={2: bad_proposer_signature, 5: bad_state_root}
    )
    assert [f.index for f in report.failures] == [2, 5]
    churn = families.eip7251_churn_segment()
    assert churn["boundaries"] >= 2


@pytest.mark.slow
def test_storm_mainnet_scale_2pow17():
    """The acceptance shape: a 10% invalid-block storm over a deneb
    chain at 2^17 validators recovers every failure and lands
    bit-identically to the scalar oracle. Slow-marked (the chain bundle
    build alone costs minutes cold)."""
    state, ctx, blocks = chain_utils.mainnet_chain_bundle(
        "deneb", 1 << 17, 16, 8
    )
    plan = plan_storm(len(blocks), 0.1, random.Random(0x5702),
                      [bad_proposer_signature])
    report, ex = run_storm(
        state, ctx, blocks, plan,
        policy=FlushPolicy(window_size=8, max_in_flight=2),
    )
    assert len(report.failures) == len(plan)
    assert report.recovery_latencies
