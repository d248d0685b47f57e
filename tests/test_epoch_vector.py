"""Columnar-primary epoch engine (models/epoch_vector.py): differential
bit-identity against the literal stage lists across all six forks —
including electra's EIP-7251 churn — plus copy-on-write column travel,
the write-direction adoption contract, and the XLA-jittability of the
numeric kernels."""

import os
import random
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))
import chain_utils  # noqa: E402

from ethereum_consensus_tpu.models import epoch_vector, ops_vector  # noqa: E402
from ethereum_consensus_tpu.primitives import FAR_FUTURE_EPOCH  # noqa: E402
from ethereum_consensus_tpu.scenarios.harness import (  # noqa: E402
    assert_bit_identical,
    assert_column_consistency,
)
from ethereum_consensus_tpu.ssz.core import CachedRootList  # noqa: E402
from ethereum_consensus_tpu.telemetry import metrics  # noqa: E402

np = pytest.importorskip("numpy")

FORKS = ("phase0", "altair", "bellatrix", "capella", "deneb", "electra")


@pytest.fixture
def forced_engine(monkeypatch):
    """Engage the columnar pass on toy registries (the production
    threshold is 2^12)."""
    monkeypatch.setattr(epoch_vector, "EPOCH_VECTOR_MIN_VALIDATORS", 0)


def _slot_processing(fork):
    import importlib

    return importlib.import_module(
        f"ethereum_consensus_tpu.models.{fork}.slot_processing"
    )


def _scramble(state, ctx, fork, rng, epoch):
    """Out-of-contract-free state churn: ejection candidates, entrants,
    finalized-eligible activations, slashed validators at the penalty
    halfway point, hysteresis triggers in both directions, inactivity
    scores — and for electra the full EIP-7251 churn surface. Mutating
    activity fields directly bypasses initiate_validator_exit, so the
    memo caches are stripped afterwards (the documented epoch-horizon
    gap — chain_utils._strip_spec_caches)."""
    n = len(state.validators)
    for i in rng.sample(range(n), 6):
        state.validators[i].effective_balance = int(ctx.ejection_balance)
    for i in rng.sample(range(n), 4):
        v = state.validators[i]
        v.activation_eligibility_epoch = FAR_FUTURE_EPOCH
        v.activation_epoch = FAR_FUTURE_EPOCH
    for i in rng.sample(range(n), 5):
        v = state.validators[i]
        v.activation_eligibility_epoch = 0
        v.activation_epoch = FAR_FUTURE_EPOCH
    half = int(ctx.EPOCHS_PER_SLASHINGS_VECTOR) // 2
    for i in rng.sample(range(n), 3):
        v = state.validators[i]
        v.slashed = True
        v.withdrawable_epoch = epoch + half
        state.slashings[epoch % int(ctx.EPOCHS_PER_SLASHINGS_VECTOR)] = 10**9
    for i in rng.sample(range(n), 8):
        state.balances[i] = rng.choice(
            [10**9, 33 * 10**9, 62 * 10**9, 2100 * 10**9]
        )
    for i in rng.sample(range(n), 2):
        state.validators[i].exit_epoch = epoch + 7
    if hasattr(state, "inactivity_scores"):
        for i in rng.sample(range(n), 10):
            state.inactivity_scores[i] = rng.randrange(0, 200)
    if fork == "electra":
        import importlib

        ns = importlib.import_module(
            "ethereum_consensus_tpu.models.electra.containers"
        )
        for i in range(0, n, 3):
            v = state.validators[i]
            v.withdrawal_credentials = b"\x01" + bytes(
                v.withdrawal_credentials
            )[1:]
        for i in range(1, n, 5):
            v = state.validators[i]
            v.withdrawal_credentials = b"\x02" + bytes(
                v.withdrawal_credentials
            )[1:]
        for k in range(12):
            state.pending_balance_deposits.append(
                ns.PendingBalanceDeposit(
                    index=k, amount=10**9 * (k % 5 + 1)
                )
            )
        src_ripe, src_slash, src_unripe = 7, 11, 13
        state.validators[src_ripe].exit_epoch = max(1, epoch)
        state.validators[src_ripe].withdrawable_epoch = epoch
        state.validators[src_slash].slashed = True
        state.validators[src_unripe].exit_epoch = epoch + 3
        state.validators[src_unripe].withdrawable_epoch = epoch + 9
        for source, target in (
            (src_ripe, 0), (src_slash, 3), (src_unripe, 6), (src_ripe, 9),
        ):
            state.pending_consolidations.append(
                ns.PendingConsolidation(
                    source_index=source, target_index=target
                )
            )
    chain_utils._strip_spec_caches(state)


@pytest.mark.parametrize("fork", FORKS)
@pytest.mark.parametrize(
    "participation", [0b111, 0b000, 0b010], ids=["full", "leak", "target"]
)
def test_columnar_epoch_bit_identity(fork, participation, forced_engine):
    """The whole-epoch differential: columnar-primary pass vs the
    literal stage list, root AND bytes, across 6 scrambled epochs —
    ejections, activations, slashings, leak conditions, hysteresis, and
    (electra) consolidations + pending deposits all land inside the
    pass. Column caches must agree with the literal values with
    ``_col_dirty`` drained after every boundary."""
    state, ctx = chain_utils.fresh_genesis_fork(fork, 96, "minimal")
    sp = _slot_processing(fork)
    spe = int(ctx.SLOTS_PER_EPOCH)
    engaged_ctr = metrics.counter("epoch_vector.epochs")
    s_col = state.copy()
    s_scl = state.copy()
    for target_epoch in range(1, 7):
        for s in (s_col, s_scl):
            rng = random.Random((target_epoch, participation).__hash__())
            _scramble(s, ctx, fork, rng, target_epoch - 1)
            if hasattr(s, "previous_epoch_participation"):
                n = len(s.validators)
                s.previous_epoch_participation = [participation] * n
                s.current_epoch_participation = [participation & 0b110] * n
        before = engaged_ctr.value()
        sp.process_slots(s_col, target_epoch * spe, ctx)
        assert engaged_ctr.value() - before == 1, (
            f"columnar pass did not engage at epoch {target_epoch}"
        )
        os.environ["ECT_EPOCH_VECTOR"] = "off"
        try:
            sp.process_slots(s_scl, target_epoch * spe, ctx)
        finally:
            os.environ.pop("ECT_EPOCH_VECTOR", None)
        assert_bit_identical(
            s_col, s_scl, f"{fork} epoch {target_epoch}"
        )
        assert_column_consistency(s_col, f"{fork} epoch {target_epoch}")


@pytest.mark.parametrize("fork", FORKS[1:])
def test_post_epoch_root_packs_from_the_columns(fork, forced_engine):
    """The first ``hash_tree_root`` after the columnar pass is the literal
    oracle's, and the lists the pass left as clean columns with no memo
    to serve them pack from those columns (``ssz.pack.from_column``): the
    rotation's fresh ``current_epoch_participation`` at every boundary,
    and, at test size, the adopted ``balances`` once rewards move them
    (under the tracking threshold they have no ``_pack_tree`` to splice)."""
    from ethereum_consensus_tpu.ssz import core as ssz_core

    state, ctx = chain_utils.fresh_genesis_fork(fork, 96, "minimal")
    sp = _slot_processing(fork)
    spe = int(ctx.SLOTS_PER_EPOCH)
    packs = metrics.counter("ssz.pack.from_column")
    widths = {
        "balances": 8, "inactivity_scores": 8,
        "previous_epoch_participation": 1, "current_epoch_participation": 1,
    }
    s_col, s_lit = state.copy(), state.copy()
    for epoch in (1, 2):
        for s in (s_col, s_lit):
            n = len(s.validators)
            s.previous_epoch_participation = [0b111] * n
            s.current_epoch_participation = [0b110] * n
        sp.process_slots(s_col, epoch * spe, ctx)
        os.environ["ECT_EPOCH_VECTOR"] = "off"
        try:
            sp.process_slots(s_lit, epoch * spe, ctx)
        finally:
            os.environ.pop("ECT_EPOCH_VECTOR", None)
        served = [
            name for name, size in widths.items()
            for lst in [getattr(s_col, name)]
            if ssz_core._clean_wire_column(lst, size) is not None
            and lst._pack_gen != lst._mut_gen
        ]
        assert "current_epoch_participation" in served
        assert ("balances" in served) == (epoch == 2)
        before = packs.value()
        root = type(s_col).hash_tree_root(s_col)
        assert root == type(s_lit).hash_tree_root(s_lit), f"{fork} epoch {epoch}"
        assert packs.value() - before == len(served), served
        assert_bit_identical(s_col, s_lit, f"{fork} epoch {epoch}")


@pytest.mark.parametrize("fork", FORKS[1:])
def test_sync_takes_columns_from_clean_pack_trees(
    fork, forced_engine, small_groups
):
    """A list that was assigned and then rooted holds its serialization
    (a clean ``_pack_tree``) and no column: ``_sync`` makes the column
    from those bytes (``ops_vector.columns.from_pack``), once for each
    such list and for no other, and the post-state is the literal
    oracle's, root AND bytes. 160 validators put every scalar list over
    the tracking threshold of ``small_groups``."""
    from ethereum_consensus_tpu.ssz import core as ssz_core

    state, ctx = chain_utils.fresh_genesis_fork(fork, 160, "minimal")
    sp = _slot_processing(fork)
    spe = int(ctx.SLOTS_PER_EPOCH)
    from_pack = metrics.counter("ops_vector.columns.from_pack")
    engaged = metrics.counter("epoch_vector.epochs")
    widths = {
        "balances": 8, "inactivity_scores": 8,
        "previous_epoch_participation": 1, "current_epoch_participation": 1,
    }
    s_col, s_lit = state.copy(), state.copy()
    for epoch in (1, 2):
        for s in (s_col, s_lit):
            sp.process_slots(s, epoch * spe - 1, ctx)
            n = len(s.validators)
            s.previous_epoch_participation = [0b111] * n
            s.current_epoch_participation = [0b110] * n
            type(s).hash_tree_root(s)
        served = [
            name for name, size in widths.items()
            for lst in [getattr(s_col, name)]
            if ssz_core._clean_pack_bytes(lst, size) is not None
            and lst._col_cache is None
        ]
        assert {
            "previous_epoch_participation", "current_epoch_participation"
        } <= set(served)
        if epoch == 2:
            # the first boundary's commit left these two their columns
            assert "balances" not in served
            assert "inactivity_scores" not in served
        before = from_pack.value(), engaged.value()
        sp.process_slots(s_col, epoch * spe, ctx)
        assert from_pack.value() - before[0] == len(served), served
        assert engaged.value() - before[1] == 1
        os.environ["ECT_EPOCH_VECTOR"] = "off"
        try:
            sp.process_slots(s_lit, epoch * spe, ctx)
        finally:
            os.environ.pop("ECT_EPOCH_VECTOR", None)
        assert_bit_identical(s_col, s_lit, f"{fork} epoch {epoch}")
        assert_column_consistency(s_col, f"{fork} epoch {epoch}")


def _mainnet_registry_world(seed):
    """A registry of the benchmark's ``mainnet-deneb-2m`` composition at
    2^13 entries (its groups times 2^-8: half the rows exited and
    withdrawn, interleaved; slashed rows; an exit queue, an activation
    queue and fresh deposits), on slot 63 with seeded participation, and a
    refill of participation for each later epoch."""
    import json

    from benchmark import worlds

    root = Path(__file__).parent.parent
    with open(root / "benchmark/configs/mainnet-deneb-2m.json") as handle:
        config = json.load(handle)
    config["validators"] = 1 << 13
    return worlds.build(
        config,
        {"kind": "mainnet_registry", "epoch": 1, "miss_share": [0.01, 0.03],
         "chain_epochs": 6},
        seed,
    )


@pytest.mark.parametrize("seed", [28, (1 << 31) + 28])
def test_columnar_epoch_bit_identity_on_the_mainnet_registry(seed, forced_engine):
    """``test_columnar_epoch_bit_identity``'s differential on a registry
    nobody scrambled: the composition a mainnet node holds, over 6
    epochs, root AND bytes, columns consistent after every boundary —
    and the churn really ran (activations under the churn limit, stamps
    on the fresh deposits, exits leaving the active set)."""
    world = _mainnet_registry_world(seed)
    ctx = world.context
    sp = _slot_processing("deneb")
    engaged_ctr = metrics.counter("epoch_vector.epochs")
    s_col = world.pre.copy()
    s_scl = world.pre.copy()
    queue_before = sum(
        1 for v in world.pre.validators
        if int(v.activation_epoch) == FAR_FUTURE_EPOCH
    )
    for place in range(6):
        slot = world.target_slot + 32 * place
        for s in (s_col, s_scl):
            if place:
                sp.process_slots(s, slot - 1, ctx)
                s.current_epoch_participation = world.refills[place - 1].tolist()
        before = engaged_ctr.value()
        sp.process_slots(s_col, slot, ctx)
        assert engaged_ctr.value() - before == 1
        os.environ["ECT_EPOCH_VECTOR"] = "off"
        try:
            sp.process_slots(s_scl, slot, ctx)
        finally:
            os.environ.pop("ECT_EPOCH_VECTOR", None)
        assert_bit_identical(s_col, s_scl, f"mainnet registry crossing {place}")
        assert_column_consistency(s_col, f"mainnet registry crossing {place}")
    queue_after = sum(
        1 for v in s_col.validators
        if int(v.activation_epoch) == FAR_FUTURE_EPOCH
    )
    assert queue_before == 10 and queue_after == 0  # 8 queued, 2 fresh deposits
    active = sum(
        1 for v in s_col.validators
        if int(v.activation_epoch) <= 7 < int(v.exit_epoch)
    )
    # 4,096 at epoch 1; six exits and four earlier activations have landed,
    # and of the ten rows the chain activated those written for epochs 6, 7
    assert active == 4096 - 6 + 4 + 7


def test_pass_counters_and_span_fields_read_what_the_pass_did(forced_engine):
    """``epoch_vector.rows``, ``.rows_active``, ``.registry.queued``,
    ``.registry.activated`` and ``.validator_writes`` count one pass's
    work, and the registry and commit spans carry the same numbers."""
    from ethereum_consensus_tpu.telemetry import spans

    world = _mainnet_registry_world(5)
    state = world.pre.copy()
    names = ("rows", "rows_active", "registry.queued", "registry.activated",
             "validator_writes")

    def read():
        return {
            name: metrics.counter(f"epoch_vector.{name}").value() for name in names
        }

    def cross(slot):
        before = read()
        with spans.recording():
            _slot_processing("deneb").process_slots(state, slot, world.context)
            fields = {
                r.name: r.fields for r in spans.RECORDER.records()
                if r.name in ("epoch_vector.registry", "epoch_vector.commit")
            }
        after = read()
        return {name: after[name] - before[name] for name in names}, fields

    # the first boundary: both fresh deposits stamped, the activation churn
    # limit's worth (min(8, max(4, 4096 // 65536)) = 4) of the queue let in
    moved, fields = cross(world.target_slot)
    assert moved == {
        "rows": 1 << 13, "rows_active": 4096, "registry.queued": 2,
        "registry.activated": 4, "validator_writes": 6,
    }
    assert fields["epoch_vector.registry"] == {"queued": 2, "activated": 4}
    assert fields["epoch_vector.commit"] == {
        "validators": 1 << 13, "writes": 6, "scores_changed": 0,
        "eff_changed": 0,
    }
    # the second: nobody new, the three rows left that were eligible at epoch 0
    state.current_epoch_participation = world.refills[0].tolist()
    moved, fields = cross(world.target_slot + 32)
    assert moved["registry.queued"] == 0 and moved["registry.activated"] == 3
    assert moved["rows_active"] == 4096 - 1 + 1  # one exit, one activation
    assert fields["epoch_vector.registry"] == {"queued": 0, "activated": 3}
    assert fields["epoch_vector.commit"]["writes"] == moved["validator_writes"] == 3


def test_engine_declines_cleanly(forced_engine):
    """Every decline path leaves the state untouched for the literal
    list: env kill switches, the u64 lane guard, and the registry-size
    threshold (without the fixture's override)."""
    state, ctx = chain_utils.fresh_genesis_fork("deneb", 64, "minimal")
    sp = _slot_processing("deneb")
    spe = int(ctx.SLOTS_PER_EPOCH)

    for env in ("ECT_EPOCH_VECTOR", "ECT_OPS_VECTOR"):
        s = state.copy()
        before = metrics.counter("epoch_vector.epochs").value()
        os.environ[env] = "off"
        try:
            sp.process_slots(s, spe, ctx)
        finally:
            os.environ.pop(env, None)
        assert metrics.counter("epoch_vector.epochs").value() == before

    # adversarial near-2^64 balance: the lane guard declines BEFORE any
    # mutation and the literal path still produces the exact state
    hot = state.copy()
    hot.balances[5] = (1 << 64) - 3
    twin = hot.copy()
    guard = metrics.counter("epoch_vector.fallback.u64_guard")
    before = guard.value()
    s = hot.copy()
    sp.process_slots(s, spe, ctx)
    assert guard.value() > before, "lane guard did not fire"
    os.environ["ECT_EPOCH_VECTOR"] = "off"
    try:
        sp.process_slots(twin, spe, ctx)
    finally:
        os.environ.pop("ECT_EPOCH_VECTOR", None)
    assert_bit_identical(s, twin, "lane-guard decline")


def test_engine_threshold_without_override():
    """Below EPOCH_VECTOR_MIN_VALIDATORS the pass stays out of the way
    (tier-1's toy states must keep running the literal lists)."""
    state, ctx = chain_utils.fresh_genesis_fork("deneb", 64, "minimal")
    sp = _slot_processing("deneb")
    before = metrics.counter("epoch_vector.epochs").value()
    s = state.copy()
    sp.process_slots(s, int(ctx.SLOTS_PER_EPOCH), ctx)
    assert metrics.counter("epoch_vector.epochs").value() == before


# ---------------------------------------------------------------------------
# write-direction column adoption
# ---------------------------------------------------------------------------


def test_adopt_list_column_contract():
    """adopt_list_column makes the authoritative array the SSZ list's
    content without boxing a row (the list turns column-primary,
    ssz/column_list.py) and installs it as the clean, owned column —
    and the incremental root off the adopted commit matches a cold
    recompute."""
    from ethereum_consensus_tpu.ssz.column_list import UNBOXED, ColumnList
    from ethereum_consensus_tpu.ssz.core import List, uint64

    typ = List[uint64, 1 << 20]
    lst = CachedRootList(range(10_000))
    typ.hash_tree_root(lst)  # memoize so the adopted commit splices
    # attach a columnar consumer (arms _col_dirty)
    arr0 = np.arange(10_000, dtype=np.uint64)
    lst._col_cache = ("list", arr0, (1 << 64) - 1)
    lst._col_owned = True
    lst._col_dirty = set()

    work = arr0.copy()
    work[17] += 5
    work[9_999] = 123
    changed = np.nonzero(work != arr0)[0]
    ops_vector.adopt_list_column(lst, work, changed, (1 << 64) - 1)
    assert lst.__class__ is ColumnList
    assert lst[17] == 17 + 5 and lst[9_999] == 123 and lst[-1] == 123
    assert list.__getitem__(lst, 17) is UNBOXED  # no boxed copy is kept
    assert lst._col_cache[1] is work, "authoritative array not adopted"
    assert lst._col_owned and lst._col_dirty == set()
    assert typ.hash_tree_root(lst) == typ.hash_tree_root(
        CachedRootList(work.tolist())
    )
    # a no-change adoption must not touch the list (free commit)
    gen = lst._mut_gen
    ops_vector.adopt_list_column(
        lst, work.copy(), np.empty(0, dtype=np.int64), (1 << 64) - 1
    )
    assert lst._mut_gen == gen


def test_install_zero_column():
    lst = CachedRootList([0] * 512)
    ops_vector.install_zero_column(lst, 512, 0xFF)
    assert lst._col_cache[1].dtype == np.uint8
    assert not lst._col_cache[1].any()
    assert lst._uniform_kind == ("int",)
    # the installed column serves reads through the normal accessor
    class _S:  # noqa: N801 — minimal field bag
        pass

    s = _S()
    s.current_epoch_participation = lst
    cols = ops_vector.RegistryColumns(s)
    col = cols.list_column(s, "current_epoch_participation")
    assert col is not None and not col.any()


# ---------------------------------------------------------------------------
# copy-on-write column travel
# ---------------------------------------------------------------------------


def test_copy_on_write_shared_base_and_post_write_isolation(forced_engine):
    """state.copy() under the columnar-primary backend must NOT copy
    column buffers until a write lands on either side: the copy shares
    the exact array objects (ownership dropped on both sides), and the
    first post-write sync clones the writer's arrays while the sibling
    keeps the originals."""
    state, ctx = chain_utils.fresh_genesis_fork("deneb", 96, "minimal")
    sp = _slot_processing("deneb")
    sp.process_slots(state, int(ctx.SLOTS_PER_EPOCH), ctx)  # builds columns

    cols = ops_vector.columns_for(state)
    cols.validator_columns(state)
    cols.list_column(state, "balances")
    base_val_arrays = state.validators._col_cache[1]
    base_bal_array = state.balances._col_cache[1]

    copied = state.copy()
    # shared base: the SAME buffers, ownership dropped on both sides
    assert copied.validators._col_cache[1] is base_val_arrays
    assert copied.balances._col_cache[1] is base_bal_array
    assert not state.validators._col_owned
    assert not copied.validators._col_owned
    assert not state.balances._col_owned
    assert not copied.balances._col_owned

    # a write on the COPY clones the copy's arrays on its next sync...
    copied.balances[3] = 77 * 10**9
    copied.validators[4].effective_balance = 17 * 10**9
    ccols = ops_vector.columns_for(copied)
    assert int(ccols.list_column(copied, "balances")[3]) == 77 * 10**9
    assert (
        int(ccols.validator_columns(copied)["effective_balance"][4])
        == 17 * 10**9
    )
    assert copied.balances._col_cache[1] is not base_bal_array
    assert copied.validators._col_cache[1] is not base_val_arrays
    # ...while the original still shares the untouched base buffers
    assert state.balances._col_cache[1] is base_bal_array
    assert int(cols.list_column(state, "balances")[3]) != 77 * 10**9
    assert_column_consistency(state, "original after sibling write")
    assert_column_consistency(copied, "copy after write")


def test_columnar_epoch_travels_across_copy(forced_engine):
    """An epoch processed on a COPY (the pipeline checkpoint shape) must
    not leak adopted arrays or dirty state back into the original."""
    state, ctx = chain_utils.fresh_genesis_fork("deneb", 96, "minimal")
    sp = _slot_processing("deneb")
    spe = int(ctx.SLOTS_PER_EPOCH)
    sp.process_slots(state, spe, ctx)
    root_before = type(state).hash_tree_root(state)
    serialized_before = type(state).serialize(state)

    checkpoint = state.copy()
    sp.process_slots(checkpoint, 2 * spe, ctx)  # columnar pass on the copy
    assert type(state).hash_tree_root(state) == root_before
    assert type(state).serialize(state) == serialized_before
    assert_column_consistency(state, "original after copy's epoch")
    assert_column_consistency(checkpoint, "checkpoint after its epoch")


@pytest.mark.slow
def test_copy_on_write_at_flagship_scale():
    """The 2^21 CoW contract with a peak-RSS guard: snapshotting the
    flagship state for serving (the HeadStore shape) must not duplicate
    the ~130 MB of column buffers per copy — four copies' column
    bundles together must add well under one bundle's worth of RSS,
    because they are the SAME shared arrays."""
    N = 1 << 21
    state, ctx = chain_utils.fast_registry_state(N, "deneb")
    cols = ops_vector.columns_for(state)
    bundle = cols.registry_snapshot(state)
    assert bundle is not None
    column_bytes = sum(a.nbytes for a in bundle.values())
    assert column_bytes >= 100 * (1 << 20)  # 100 MiB at 2^21

    def rss_mb() -> float:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) / 1024.0
        return 0.0

    copies = [state.copy() for _ in range(4)]
    before = rss_mb()
    bundles = []
    for c in copies:
        ccols = ops_vector.columns_for(c)
        b = ccols.registry_snapshot(c)
        assert b is not None
        bundles.append(b)
    grown = rss_mb() - before
    # shared-base: every copy's bundle views the ORIGINAL buffers
    for b in bundles:
        for key, arr in b.items():
            assert np.shares_memory(arr, bundle[key]), key
    assert grown < column_bytes / (1 << 20) / 2, (
        f"4 copies' column bundles grew RSS by {grown:.0f} MB — "
        "buffers are being copied, not shared"
    )
    # post-write isolation still holds at scale
    copies[0].balances[123] = 9 * 10**9
    c0 = ops_vector.columns_for(copies[0])
    refreshed = c0.list_column(copies[0], "balances")
    assert int(refreshed[123]) == 9 * 10**9
    assert int(bundle["balances"][123]) != 9 * 10**9


# ---------------------------------------------------------------------------
# kernels: XLA-jittable, bit-identical under jax
# ---------------------------------------------------------------------------


def _kernel_inputs(n=4096, seed=7):
    rng = np.random.default_rng(seed)
    return dict(
        scores=rng.integers(0, 1 << 20, n, dtype=np.uint64),
        eligible=rng.random(n) < 0.9,
        participating=rng.random(n) < 0.7,
        base_reward=rng.integers(0, 1 << 26, n, dtype=np.uint64),
        unslashed=rng.random(n) < 0.6,
        balances=rng.integers(0, 1 << 45, n, dtype=np.uint64),
    )


def test_kernels_jittable_bit_identical():
    """The numeric cores run under jax.numpy inside jax.jit with x64
    enabled and produce bit-identical uint64 outputs to the numpy path —
    the XLA route for the device epoch kernel (BASELINE.json north
    star)."""
    jax = pytest.importorskip("jax")
    jax.config.update("jax_enable_x64", True)
    import functools

    import jax.numpy as jnp

    k = _kernel_inputs()
    host_scores = epoch_vector.inactivity_scores_kernel(
        np, k["scores"], k["eligible"], k["participating"], 4, 16, False
    )
    host_r, host_p = epoch_vector.flag_deltas_kernel(
        np, k["base_reward"], k["eligible"], k["unslashed"],
        14, 2_000, 2_048, 64, False, False,
    )
    host_bal = epoch_vector.apply_delta_pairs_kernel(
        np, k["balances"], [(host_r, host_p)]
    )

    @functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
    def device(scores, eligible, participating, bias, rec, leaking,
               weight, u_incr, a_incr, base_reward, unslashed, balances):
        s = epoch_vector.inactivity_scores_kernel(
            jnp, scores, eligible, participating, bias, rec, leaking
        )
        r, p = epoch_vector.flag_deltas_kernel(
            jnp, base_reward, eligible, unslashed, weight, u_incr, a_incr,
            64, leaking, False,
        )
        b = epoch_vector.apply_delta_pairs_kernel(jnp, balances, [(r, p)])
        return s, r, p, b

    dev_scores, dev_r, dev_p, dev_bal = device(
        jnp.asarray(k["scores"]), jnp.asarray(k["eligible"]),
        jnp.asarray(k["participating"]), 4, 16, False, 14, 2_000, 2_048,
        jnp.asarray(k["base_reward"]), jnp.asarray(k["unslashed"]),
        jnp.asarray(k["balances"]),
    )
    assert np.array_equal(np.asarray(dev_scores), host_scores)
    assert np.array_equal(np.asarray(dev_r), host_r)
    assert np.array_equal(np.asarray(dev_p), host_p)
    assert np.array_equal(np.asarray(dev_bal), host_bal)


def _fused_inputs(n=4097, seed=13, high_words=False, half_inactive=False):
    """``high_words``: every balance is over 2^32 gwei and the scores mix
    small values with 2^32 + 1 and 2^63, so both result columns need both
    of their 32-bit planes (a u64 product that wraps does so alike on the
    host and under jit: the lane guard is the caller's).
    ``half_inactive``: a registry as mainnet holds it: every other row or
    so, interleaved, has exited and been withdrawn (no balance, no flags,
    not eligible), some of those are slashed, and a few slashed rows are
    still eligible (not yet withdrawable) with a balance to lose."""
    rng = np.random.default_rng(seed)
    k = dict(
        balances=rng.integers(0, 1 << 45, n, dtype=np.uint64),
        eff=rng.integers(1 << 30, 1 << 35, n, dtype=np.uint64),
        prev_part=rng.integers(0, 8, n, dtype=np.uint8),
        slashed=rng.random(n) < 0.05,
        active_prev=rng.random(n) < 0.95,
        eligible=rng.random(n) < 0.96,
        scores=rng.integers(0, 1 << 20, n, dtype=np.uint64),
    )
    if high_words:
        k["balances"] += np.uint64(1 << 33)
        k["scores"][1::3] = (1 << 32) + 1
        k["scores"][2::3] = 1 << 63
    if half_inactive:
        gone = rng.random(n) < 0.5
        k["active_prev"] = ~gone
        k["slashed"] = gone & (rng.random(n) < 0.02)
        held = k["slashed"] & (rng.random(n) < 0.25)  # not yet withdrawable
        k["eligible"] = ~gone | held
        for name in ("balances", "eff", "prev_part", "scores"):
            k[name][gone & ~held] = 0
        assert 0.4 < gone.mean() < 0.6 and held.any() and (gone[1:] != gone[:-1]).sum() > n // 4
    return k


# the second and third shapes are no multiple of 128 rows (the chip's
# lane width) and fill the high words of both columns
@pytest.mark.parametrize(
    "n, high_words, half_inactive",
    [(4097, False, False), (1000, True, False), (129, True, False),
     (4096, False, True)],
    ids=["4097", "1000-high-words", "129-high-words", "4096-half-inactive"],
)
@pytest.mark.parametrize("leaking", [False, True])
def test_fused_kernel_matches_staged_kernels_and_jit(leaking, n, high_words,
                                                     half_inactive):
    """The fused epoch kernel (ISSUE 14) must equal the staged kernels it
    collapses — inactivity update, three flag-delta pairs off in-kernel
    sums, inactivity penalties off post-update scores, in-order
    application — on host numpy AND bit-identically under jax.jit with
    x64 (the jitted_kernels() discipline), through the 32-bit planes the
    jit route brings its two columns down as."""
    k = _fused_inputs(n, high_words=high_words, half_inactive=half_inactive)
    increment, brpi = 10**9, 907
    weights, wd = (14, 26, 14), 64
    bias, recovery = 4, 16
    denominator = bias * (3 * 10**7)
    active_increments = max(1, int(k["eff"].sum()) // increment)

    # staged composition (the live host fallback path)
    target_bit = ((k["prev_part"] >> np.uint8(1)) & np.uint8(1)).astype(bool)
    participating = k["active_prev"] & ~k["slashed"] & target_bit
    staged_scores = epoch_vector.inactivity_scores_kernel(
        np, k["scores"], k["eligible"], participating, bias, recovery,
        leaking,
    )
    base_reward = (k["eff"] // np.uint64(increment)) * np.uint64(brpi)
    pairs = []
    for flag_index, weight in enumerate(weights):
        bit = ((k["prev_part"] >> np.uint8(flag_index)) & np.uint8(1)).astype(
            bool
        )
        unslashed = k["active_prev"] & ~k["slashed"] & bit
        u_incr = max(increment, int(k["eff"][unslashed].sum())) // increment
        pairs.append(
            epoch_vector.flag_deltas_kernel(
                np, base_reward, k["eligible"], unslashed, weight, u_incr,
                active_increments, wd, leaking, flag_index == 2,
            )
        )
    missed = k["eligible"] & ~participating
    pen = np.where(
        missed,
        k["eff"] * staged_scores // np.uint64(denominator),
        np.uint64(0),
    )
    pairs.append((np.zeros(n, dtype=np.uint64), pen))
    staged_balances = epoch_vector.apply_delta_pairs_kernel(
        np, k["balances"], pairs
    )

    host_scores, host_balances, host_wrapped = (
        epoch_vector.fused_epoch_kernel(
            np, k["balances"], k["eff"], k["prev_part"], k["slashed"],
            k["active_prev"], k["eligible"], k["scores"],
            np.uint64(increment), np.uint64(brpi),
            np.uint64(active_increments), np.uint64(denominator),
            bias, recovery, weights, wd, leaking, 2, 1,
        )
    )
    assert np.array_equal(host_scores, staged_scores)
    assert np.array_equal(host_balances, staged_balances)
    if high_words:
        # (outside a leak the recovery takes 2^32 + 1 back under 2^32)
        for column in (staged_scores, staged_balances):
            assert np.count_nonzero(column >> np.uint64(32)) > n // 4
    else:
        assert int(host_wrapped) == 0
    if half_inactive:
        # a row that is gone is paid nothing and loses nothing
        gone = ~k["eligible"]
        assert gone.sum() > n // 3
        assert not staged_balances[gone].any() and not staged_scores[gone].any()
        assert (staged_balances != k["balances"])[k["eligible"]].mean() > 0.5

    jax = pytest.importorskip("jax")
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    fused = epoch_vector.jitted_kernels()["fused_epoch"]
    planes, dev_wrapped = fused(
        jnp.asarray(k["balances"]), jnp.asarray(k["eff"]),
        jnp.asarray(k["prev_part"]), jnp.asarray(k["slashed"]),
        jnp.asarray(k["active_prev"]), jnp.asarray(k["eligible"]),
        jnp.asarray(k["scores"]),
        jnp.uint64(increment), jnp.uint64(brpi),
        jnp.uint64(active_increments), jnp.uint64(denominator),
        bias, recovery, weights, wd, leaking, 2, 1,
    )
    assert planes.dtype == jnp.uint32 and planes.shape == (4, n)
    dev_scores, dev_balances = epoch_vector.u64_columns(np.asarray(planes))
    assert np.array_equal(dev_scores, staged_scores)
    assert np.array_equal(dev_balances, staged_balances)
    assert int(dev_wrapped) == int(host_wrapped)


@pytest.mark.parametrize("n", [1, 127, 128, 1000])
def test_u64_columns_rebuilds_what_u32_planes_split(n):
    """The plane order is (low, high) per column, in column order; the
    host side rebuilds fresh, owned, contiguous u64 columns from it."""
    edges = np.array(
        [0, 1, (1 << 32) - 1, 1 << 32, (1 << 32) + 1, 1 << 63, (1 << 64) - 1],
        dtype=np.uint64,
    )
    rng = np.random.default_rng(n)
    a = rng.integers(0, 1 << 64, n, dtype=np.uint64)
    b = rng.integers(0, 1 << 64, n, dtype=np.uint64)
    a[: len(edges)] = edges[:n]
    b[: len(edges)] = edges[::-1][:n]
    planes = epoch_vector.u32_planes(np, a, b)
    assert planes.dtype == np.uint32 and planes.shape == (4, n)
    for row, want in enumerate(
        (a & 0xFFFFFFFF, a >> 32, b & 0xFFFFFFFF, b >> 32)
    ):
        assert np.array_equal(planes[row], want), row
    got_a, got_b = epoch_vector.u64_columns(planes)
    for got, want in ((got_a, a), (got_b, b)):
        assert got.dtype == np.uint64 and got.shape == (n,)
        assert got.flags.owndata and got.flags.c_contiguous
        assert got.flags.writeable
        assert np.array_equal(got, want)


def test_fused_jit_route_bit_identical_through_the_pass(forced_engine,
                                                       monkeypatch):
    """ops.install's sweeps flag routes the columnar pass through the
    jitted fused kernel — the full transition must stay bit-identical to
    the host staged path, with the fused engagement counted."""
    from ethereum_consensus_tpu import _device_flags

    state, ctx = chain_utils.fresh_genesis_fork("deneb", 96, "minimal")
    sp = _slot_processing("deneb")
    spe = int(ctx.SLOTS_PER_EPOCH)
    sp.process_slots(state, spe, ctx)
    n = len(state.validators)
    state.previous_epoch_participation = [0b111] * n
    for i in range(0, n, 5):
        state.previous_epoch_participation[i] = 0b001
    chain_utils._strip_spec_caches(state)

    host = state.copy()
    sp.process_slots(host, 2 * spe, ctx)

    monkeypatch.setattr(_device_flags, "SWEEPS_MIN_N", 1)
    fused_ctr = metrics.counter("epoch_vector.fused.jit")
    before = fused_ctr.value()
    dev = state.copy()
    sp.process_slots(dev, 2 * spe, ctx)
    assert fused_ctr.value() == before + 1, "fused jit route did not engage"
    assert type(host).hash_tree_root(host) == type(dev).hash_tree_root(dev)
    assert type(host).serialize(host) == type(dev).serialize(dev)


def test_phase0_pass_runs_its_host_kernels_with_the_gate_on(forced_engine,
                                                           monkeypatch):
    """The sweeps gate selects the altair family's fused kernel and
    nothing else: a phase0 pass with the gate on engages (it used to
    stand aside for a staged device hysteresis), declines nothing, runs
    no jitted kernel, and leaves the bytes of the gate-off pass and of
    the literal stage list."""
    from ethereum_consensus_tpu import _device_flags
    from ethereum_consensus_tpu.models.phase0 import epoch_processing
    from ethereum_consensus_tpu.models.phase0.state_transition import (
        state_transition,
    )

    state, ctx = chain_utils.fresh_genesis_fork("phase0", 96, "minimal")
    spe = int(ctx.SLOTS_PER_EPOCH)
    # attested slots through epoch 1, so the rewards stage has pending
    # attestations of the previous epoch to pay
    for block in chain_utils.produce_chain(state, ctx, 2 * spe - 1):
        state_transition(state, block, ctx)
    assert int(state.slot) == 2 * spe - 1
    assert len(state.previous_epoch_attestations) > 0
    # stage triggers that leave the attested epochs' committees alone:
    # hysteresis both ways, ejection candidates, a slashing that falls due
    rng = random.Random(30)
    for i in rng.sample(range(96), 8):
        state.balances[i] = rng.choice([10**9, 33 * 10**9, 62 * 10**9])
    for i in rng.sample(range(96), 4):
        state.validators[i].effective_balance = int(ctx.ejection_balance)
    half = int(ctx.EPOCHS_PER_SLASHINGS_VECTOR) // 2
    for i in rng.sample(range(96), 3):
        state.validators[i].slashed = True
        state.validators[i].withdrawable_epoch = 1 + half
    state.slashings[1 % int(ctx.EPOCHS_PER_SLASHINGS_VECTOR)] = 10**9
    chain_utils._strip_spec_caches(state)

    literal = state.copy()
    os.environ["ECT_EPOCH_VECTOR"] = "off"
    try:
        epoch_processing.process_epoch(literal, ctx)
    finally:
        os.environ.pop("ECT_EPOCH_VECTOR", None)

    gate_off = state.copy()
    assert epoch_vector.process_epoch_columnar(gate_off, ctx, "phase0")

    monkeypatch.setattr(_device_flags, "SWEEPS_MIN_N", 1)
    before = metrics.snapshot()
    gate_on = state.copy()
    assert epoch_vector.process_epoch_columnar(gate_on, ctx, "phase0")
    moved = {k: v for k, v in metrics.delta(before).items() if v}
    assert moved.get("epoch_vector.epochs") == 1
    assert not [k for k in moved if k.startswith("epoch_vector.fallback.")]
    assert not [k for k in moved if k.startswith("epoch_vector.fused")]
    assert not [k for k in moved if k.startswith("device.")]

    assert_bit_identical(gate_on, gate_off, "phase0, gate on vs off")
    assert_bit_identical(gate_on, literal, "phase0, gate on vs literal")
    assert type(state).serialize(state) != type(literal).serialize(literal)


# ---------------------------------------------------------------------------
# bench smoke: the 2^18 columnar-primary engagement check (make bench-smoke)
# ---------------------------------------------------------------------------


@pytest.mark.bench_smoke
@pytest.mark.slow
def test_columnar_primary_engagement_2e18():
    """One warm deneb epoch at 2^18 (mainnet preset, disk-cached state):
    the columnar-primary pass must engage at its NATURAL threshold with
    zero fallbacks, zero column builds (copies share the primed columns
    copy-on-write) and a sub-second epoch — the bench-smoke tripwire for
    the 2^21 flagship path."""
    import time

    N = 1 << 18
    state, ctx = chain_utils.fast_registry_state(N, "deneb")
    sp = _slot_processing("deneb")
    spe = int(ctx.SLOTS_PER_EPOCH)
    sp.process_slots(state, spe, ctx)
    state.previous_epoch_participation = [0b111] * N
    type(state).hash_tree_root(state)
    cols = ops_vector.columns_for(state)
    cols.validator_columns(state)
    for field in ops_vector.RegistryColumns.LIST_FIELDS:
        cols.list_column(state, field)
    warmup = state.copy()
    sp.process_slots(warmup, 2 * spe, ctx)
    del warmup

    base = metrics.snapshot()
    s = state.copy()
    t0 = time.perf_counter()
    sp.process_slots(s, 2 * spe, ctx)
    warm_s = time.perf_counter() - t0
    d = metrics.delta(base)
    assert d.get("epoch_vector.epochs", 0) == 1
    assert not any(
        k.startswith("epoch_vector.fallback.") and v for k, v in d.items()
    ), {k: v for k, v in d.items() if k.startswith("epoch_vector.fallback.")}
    assert d.get("ops_vector.columns.builds", 0) == 0
    assert warm_s < 1.0, f"2^18 warm epoch took {warm_s:.2f}s"


# ---------------------------------------------------------------------------
# the block path boxes nothing (ssz/column_list.py): what no cell times
# ---------------------------------------------------------------------------


def _column_list_counters() -> dict:
    return {
        k: metrics.counter(f"ssz.column_list.{k}").value()
        for k in ("stores", "left", "boxed_rows")
    }


def test_blocks_between_two_boundaries_box_nothing(monkeypatch):
    """deneb at 2^12 validators, the pass at its natural threshold: a
    boundary adopts the balances column, three blocks then read and write
    it element by element (attestations and their proposer reward, a full
    sync aggregate's 512 members, over forty withdrawals, a deposit that
    appends a validator), and the next boundary adopts again. No row is
    ever boxed, one store a boundary, and every root is the literal
    oracle's."""
    from chain_utils import h

    from ethereum_consensus_tpu.models.phase0.containers import DepositData
    from ethereum_consensus_tpu.ssz.column_list import ColumnList
    from ethereum_consensus_tpu.ssz.hash import hash_pair

    N = 1 << 12
    assert epoch_vector.EPOCH_VECTOR_MIN_VALIDATORS == N
    state, ctx = chain_utils.fast_registry_state(N, "deneb")
    mod = chain_utils._fork_module("deneb")
    sp, stm = mod.slot_processing, mod.state_transition
    spe = int(ctx.SLOTS_PER_EPOCH)
    rng = random.Random(33)
    for i in rng.sample(range(N), 43):  # partial withdrawals, three blocks' worth
        state.validators[i].withdrawal_credentials = (
            b"\x01" + b"\x00" * 11 + b"\xaa" * 20
        )
        state.balances[i] = int(ctx.MAX_EFFECTIVE_BALANCE) + rng.randrange(1, 10**9)
    # the one deposit the chain carries, in a deposit tree of its own
    data = chain_utils.make_deposit_data(N, ctx)
    deposit = chain_utils.deposits_from_datas([data], ctx)[0]
    node = DepositData.hash_tree_root(data)
    for sibling in deposit.proof:
        node = hash_pair(node, bytes(sibling))
    state.eth1_data.deposit_root = node
    state.eth1_data.deposit_count = 1
    state.eth1_deposit_index = 0

    sp.process_slots(state, 2 * spe - 1, ctx)
    state.previous_epoch_participation = [0b111] * N
    state.current_epoch_participation = [0b111] * N
    later = [0b111 if rng.random() < 0.9 else 0 for _ in range(N + 1)]
    first, n_blocks = 2 * spe + 1, 3
    probe, signers = state.copy(), set()
    for slot in range(first, first + n_blocks):
        sp.process_slots(probe, slot, ctx)
        signers.add(h.get_beacon_proposer_index(probe, ctx))
    for slot in range(first - 1, first + n_blocks):
        signers.update(h.get_beacon_committee(probe, slot, 0, ctx))
    chain_utils.realize_validator_keys(state, signers)
    literal = state.copy()

    def root(s) -> bytes:
        return type(s).hash_tree_root(s)

    # -- the columnar side builds the blocks as it imports them
    start = _column_list_counters()
    sp.process_slots(state, 2 * spe, ctx)
    roots = [root(state)]
    assert state.balances.__class__ is ColumnList
    after_first = _column_list_counters()
    assert after_first == {**start, "stores": start["stores"] + 1}
    pending, blocks = [chain_utils.make_attestation(state, first - 1, 0, ctx)], []
    for slot in range(first, first + n_blocks):
        extras = {"deposits": [deposit]} if slot == first else {}
        blocks.append(
            chain_utils.produce_block_fork(
                "deneb", state, slot, ctx, attestations=pending, apply=True,
                **extras,
            )
        )
        pending = [chain_utils.make_attestation(state, slot, 0, ctx)]
        roots.append(root(state))
        assert_column_consistency(state, f"block at slot {slot}")
    swept = [len(b.message.body.execution_payload.withdrawals) for b in blocks]
    assert swept[:2] == [16, 16] and swept[2] >= 11  # the seeded 43 and more
    assert len(state.balances) == N + 1 and int(state.balances[N]) == int(data.amount)
    assert state.balances.__class__ is ColumnList
    assert _column_list_counters() == after_first  # the blocks: nothing at all
    state.previous_epoch_participation = later
    sp.process_slots(state, 3 * spe, ctx)
    roots.append(root(state))
    assert state.balances.__class__ is ColumnList
    assert _column_list_counters() == {
        **after_first, "stores": after_first["stores"] + 1
    }
    assert_column_consistency(state, "after the second boundary")

    # -- the literal oracle imports the same blocks
    monkeypatch.setenv("ECT_EPOCH_VECTOR", "off")
    before = _column_list_counters()
    sp.process_slots(literal, 2 * spe, ctx)
    want = [root(literal)]
    for block in blocks:
        stm.state_transition(literal, block, ctx)
        want.append(root(literal))
    literal.previous_epoch_participation = later
    sp.process_slots(literal, 3 * spe, ctx)
    want.append(root(literal))
    assert literal.balances.__class__ is CachedRootList
    assert _column_list_counters() == before
    assert roots == want and len(set(roots)) == len(roots)
    assert_bit_identical(state, literal, "after two boundaries and three blocks")
