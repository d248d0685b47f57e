"""Columnar operations engine (models/ops_vector.py, docs/OPS_VECTOR.md).

Three layers:

* DIFFERENTIAL — randomized multi-attestation blocks across
  altair→electra replayed through the vectorized block engine and
  through the scalar fallback must produce bit-identical
  ``hash_tree_root`` and identical balances (the proposer-reward
  surface), including mid-block validation failure (the partial state
  the sequential loop leaves). The ``ops_vector.*`` counters assert the
  fast path actually engaged and committed via ``bulk_store`` — it
  cannot silently degrade to scalar writes.
* COLUMN CACHE — the delta-invalidation contract: field writes /
  setitems refresh exactly the dirty rows (counter-checked), structural
  mutations rebuild, state copies get their own cache, participation
  rotation re-keys instead of rebuilding, and the handed-out views are
  read-only.
* SWEEP PARITY — capella/electra ``get_expected_withdrawals`` and the
  phase0/electra effective-balance hysteresis through the columnar path
  vs the literal loops.
"""

import importlib
import random
import sys
import os

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import chain_utils

from ethereum_consensus_tpu.models import ops_vector
from ethereum_consensus_tpu.telemetry import metrics

FLAG_FORKS = ["altair", "bellatrix", "capella", "deneb", "electra"]


def _st(fork):
    return importlib.import_module(
        f"ethereum_consensus_tpu.models.{fork}.state_transition"
    )


def _produce_attestation_chain(fork, state, ctx, n_blocks, rng):
    """``n_blocks`` signed blocks, each carrying randomized-participation
    attestations over every committee of the two preceding slots (plus a
    deliberate duplicate to exercise already-set-flag suppression)."""
    stmod = _st(fork)
    st = state.copy()
    signed_blocks = []
    from ethereum_consensus_tpu.models.phase0 import helpers as ph

    for _ in range(n_blocks):
        target = st.slot + 1
        atts = []
        if target >= ctx.MIN_ATTESTATION_INCLUSION_DELAY + 1:
            sc = st.copy()
            stmod.process_slots(sc, target, ctx)
            slot = target - ctx.MIN_ATTESTATION_INCLUSION_DELAY
            if fork == "electra":
                atts = [
                    chain_utils.make_attestation_electra(
                        sc, slot, ctx,
                        participation=rng.uniform(0.3, 1.0),
                    )
                ]
            else:
                epoch = slot // ctx.SLOTS_PER_EPOCH
                count = ph.get_committee_count_per_slot(sc, epoch, ctx)
                atts = [
                    chain_utils.make_attestation(
                        sc, slot, index, ctx,
                        participation=rng.uniform(0.3, 1.0),
                    )
                    for index in range(count)
                ]
            if atts:
                atts.append(atts[0])  # duplicate: second pass sets 0 flags
        producer = getattr(chain_utils, f"produce_block_{fork}")
        signed = producer(st.copy(), target, ctx, attestations=atts)
        stmod.state_transition(st, signed, ctx)
        signed_blocks.append(signed)
    return signed_blocks


def _replay(fork, state, ctx, blocks, force_batch, monkeypatch):
    stmod = _st(fork)
    s = state.copy()
    threshold = 0 if force_batch else 1 << 60
    monkeypatch.setattr(ops_vector, "BATCH_MIN_VALIDATORS", threshold)
    for b in blocks:
        stmod.state_transition(s, b, ctx)
    return s


@pytest.mark.parametrize("fork", FLAG_FORKS)
def test_batch_attestations_bit_identical(fork, monkeypatch):
    rng = random.Random(0xA17 + hash(fork) % 1000)
    state, ctx = chain_utils.fresh_genesis_fork(fork, 256, "minimal")
    blocks = _produce_attestation_chain(fork, state, ctx, 4, rng)
    assert any(len(b.message.body.attestations) >= 2 for b in blocks)

    before = metrics.snapshot()
    vec = _replay(fork, state, ctx, blocks, True, monkeypatch)
    delta = metrics.delta(before)
    scalar = _replay(fork, state, ctx, blocks, False, monkeypatch)

    assert type(vec).hash_tree_root(vec) == type(scalar).hash_tree_root(
        scalar
    ), f"{fork}: vectorized transition diverged from the scalar oracle"
    assert list(vec.balances) == list(scalar.balances)
    assert list(vec.current_epoch_participation) == list(
        scalar.current_epoch_participation
    )

    # engagement: every block with attestations batched, committed via
    # bulk_store, and no fallback fired — the fast path cannot silently
    # degrade to ~130k scalar writes
    blocks_with_atts = sum(
        1 for b in blocks if b.message.body.attestations
    )
    assert delta.get("ops_vector.attestations.blocks", 0) == blocks_with_atts
    assert delta.get("ops_vector.bulk_store.calls", 0) >= blocks_with_atts
    fallbacks = {
        k: v
        for k, v in delta.items()
        if k.startswith("ops_vector.fallback.") and v
    }
    assert not fallbacks, f"{fork}: unexpected fallbacks {fallbacks}"


def test_batch_commits_partial_state_on_invalid_attestation(monkeypatch):
    """Attestation k invalid ⇒ attestations 0..k-1's flags are already
    committed when the error propagates — byte-for-byte the scalar
    loop's partial state."""
    from ethereum_consensus_tpu.error import InvalidAttestation
    from ethereum_consensus_tpu.models.deneb import block_processing as bp

    fork = "deneb"
    state, ctx = chain_utils.fresh_genesis_fork(fork, 256, "minimal")
    stmod = _st(fork)
    st = state.copy()
    for _ in range(3):  # advance so attestations exist
        target = st.slot + 1
        signed = chain_utils.produce_block_deneb(st.copy(), target, ctx)
        stmod.state_transition(st, signed, ctx)
    sc = st.copy()
    stmod.process_slots(sc, st.slot + 1, ctx)
    slot = st.slot + 1 - ctx.MIN_ATTESTATION_INCLUSION_DELAY
    good = chain_utils.make_attestation(sc, slot, 0, ctx, participation=0.9)
    bad = chain_utils.make_attestation(sc, slot, 0, ctx, participation=0.5)
    bad.data.target.root = b"\xee" * 32  # fails the matching-target check?
    # target mismatch only drops flags; make it structurally invalid:
    bad.data.index = 10**6

    def run(force):
        s = st.copy()
        monkeypatch.setattr(
            ops_vector, "BATCH_MIN_VALIDATORS", 0 if force else 1 << 60
        )
        with pytest.raises(InvalidAttestation):
            bp.process_operations(
                s, _FakeBody([good, bad]), ctx
            )
        return s

    vec, scalar = run(True), run(False)
    assert type(vec).hash_tree_root(vec) == type(scalar).hash_tree_root(scalar)


@pytest.mark.parametrize("fork", ["altair", "deneb", "electra"])
def test_partial_commit_at_fork_boundary(fork, monkeypatch):
    """The mid-block invalid-attestation partial-commit path ON a fork
    boundary: the state has JUST crossed the fork's upgrade slot (the
    participation lists freshly rotated, column caches traveled through
    the upgrade), attestation 0 is valid, attestation 1 structurally
    invalid — the earlier partial state must commit before the error
    propagates, and the columnar engine must agree with the scalar loop
    on it byte-for-byte."""
    from ethereum_consensus_tpu.error import InvalidAttestation
    from ethereum_consensus_tpu.executor import Executor

    state, ctx, blocks = chain_utils.produce_full_upgrade_chain(64)
    bp = __import__(
        f"ethereum_consensus_tpu.models.{fork}.block_processing",
        fromlist=["process_operations"],
    )
    stmod = _st(fork)
    spe = int(ctx.SLOTS_PER_EPOCH)
    edge_slot = int(getattr(ctx, f"{fork}_fork_epoch")) * spe
    ex = Executor(state.copy(), ctx)
    for b in blocks:
        ex.apply_block(b)
        if int(b.message.slot) == edge_slot:
            break  # the first block of the new fork just applied
    st = ex.state.data
    assert int(st.slot) == edge_slot

    sc = st.copy()
    stmod.process_slots(sc, int(st.slot) + 1, ctx)
    slot = int(st.slot) + 1 - int(ctx.MIN_ATTESTATION_INCLUSION_DELAY)
    if fork == "electra":
        good = chain_utils.make_attestation_electra(
            sc, slot, ctx, participation=0.9
        )
        bad = chain_utils.make_attestation_electra(
            sc, slot, ctx, participation=0.5
        )
        bad.data.index = 7  # EIP-7549: attestation data index must be 0
    else:
        good = chain_utils.make_attestation(sc, slot, 0, ctx,
                                            participation=0.9)
        bad = chain_utils.make_attestation(sc, slot, 0, ctx,
                                           participation=0.5)
        bad.data.index = 10**6  # no such committee
    pre_participation = list(sc.current_epoch_participation) + list(
        sc.previous_epoch_participation
    )

    def run(force):
        # sc (one slot past the edge) satisfies the inclusion delay for
        # an attestation over the upgrade slot itself
        s = sc.copy()
        monkeypatch.setattr(
            ops_vector, "BATCH_MIN_VALIDATORS", 0 if force else 1 << 60
        )
        with pytest.raises(InvalidAttestation):
            bp.process_operations(s, _FakeBody([good, bad]), ctx)
        return s

    vec, scalar = run(True), run(False)
    assert type(vec).hash_tree_root(vec) == type(scalar).hash_tree_root(
        scalar
    ), f"{fork}: partial-commit state diverged at the fork edge"
    assert type(vec).serialize(vec) == type(scalar).serialize(scalar)
    # the good attestation really landed flags (non-vacuous partiality)
    post_participation = list(vec.current_epoch_participation) + list(
        vec.previous_epoch_participation
    )
    assert post_participation != pre_participation, (
        f"{fork}: the valid attestation set no flags — the partial-"
        "commit path was not exercised"
    )


class _FakeBody:
    """Minimal operations body: only attestations populated."""

    def __init__(self, atts):
        self.proposer_slashings = []
        self.attester_slashings = []
        self.attestations = atts
        self.deposits = []
        self.voluntary_exits = []
        self.bls_to_execution_changes = []

    @property
    def eth1_data(self):
        class _E:
            deposit_count = 0

        return _E()


# ---------------------------------------------------------------------------
# column cache invalidation
# ---------------------------------------------------------------------------


def _warm_state(n=64):
    state, ctx = chain_utils.fresh_genesis_fork("deneb", n, "minimal")
    state = state.copy()
    type(state).hash_tree_root(state)  # register weak parents / arm tracking
    return state, ctx


def test_validator_column_delta_refresh():
    state, _ = _warm_state()
    cols = ops_vector.columns_for(state)
    vc = cols.validator_columns(state)
    assert vc is not None
    builds0 = metrics.counter("ops_vector.columns.builds").value()
    state.validators[3].effective_balance = 17 * 10**9
    state.validators[5].slashed = True
    vc2 = cols.validator_columns(state)
    assert int(vc2["effective_balance"][3]) == 17 * 10**9
    assert bool(vc2["slashed"][5]) is True
    # a delta refresh, not a rebuild
    assert metrics.counter("ops_vector.columns.builds").value() == builds0


def test_list_column_delta_refresh_and_bulk_store():
    from ethereum_consensus_tpu.ssz.core import bulk_store

    state, _ = _warm_state()
    cols = ops_vector.columns_for(state)
    col = cols.list_column(state, "balances")
    assert col is not None
    builds0 = metrics.counter("ops_vector.columns.builds").value()
    state.balances[2] = 123
    new = list(state.balances)
    new[7] = 456
    bulk_store(state.balances, new, [7])
    col2 = cols.list_column(state, "balances")
    assert int(col2[2]) == 123 and int(col2[7]) == 456
    assert metrics.counter("ops_vector.columns.builds").value() == builds0


@pytest.mark.parametrize("mutate", [
    lambda balances: balances.insert(0, 5),
    lambda balances: balances.pop(),
    lambda balances: (balances.append(5), balances.pop(0)),
], ids=["insert", "pop", "append_then_pop"])
def test_structural_mutation_rebuilds(mutate):
    state, _ = _warm_state()
    cols = ops_vector.columns_for(state)
    cols.list_column(state, "balances")
    builds0 = metrics.counter("ops_vector.columns.builds").value()
    mutate(state.balances)
    col = cols.list_column(state, "balances")
    assert col.tolist() == list(state.balances)
    assert metrics.counter("ops_vector.columns.builds").value() == builds0 + 1


def test_an_append_extends_and_rebuilds_nothing():
    """A deposit appends: the column follows by the new row (and a write
    to an old row beside it), the tracking stays, nothing is rebuilt."""
    state, _ = _warm_state()
    cols = ops_vector.columns_for(state)
    first = cols.list_column(state, "balances")
    n = len(state.balances)
    builds0 = metrics.counter("ops_vector.columns.builds").value()
    extended0 = metrics.counter("ops_vector.columns.extended_rows").value()
    state.balances.append(5)
    state.balances[3] = 77
    state.balances.append((1 << 64) - 1)
    assert state.balances._col_dirty == {n, 3, n + 1}
    col = cols.list_column(state, "balances")
    assert col.tolist() == list(state.balances) and col.shape[0] == n + 2
    assert state.balances._col_dirty == set()
    assert metrics.counter("ops_vector.columns.builds").value() == builds0
    assert metrics.counter("ops_vector.columns.extended_rows").value() == extended0 + 2
    assert first.shape[0] == n  # a view handed out earlier keeps its length
    # the next append lands in the buffer the first extension bought
    buffer = state.balances._col_cache[3]
    state.balances.append(9)
    assert cols.list_column(state, "balances").tolist() == list(state.balances)
    assert state.balances._col_cache[3] is buffer


def test_state_copy_gets_its_own_columns():
    state, _ = _warm_state()
    cols = ops_vector.columns_for(state)
    cols.list_column(state, "balances")
    copy = state.copy()
    copy.balances[0] = 999
    state.balances[0] = 111
    assert int(ops_vector.columns_for(copy).list_column(copy, "balances")[0]) == 999
    assert int(ops_vector.columns_for(state).list_column(state, "balances")[0]) == 111
    assert ops_vector.columns_for(copy) is not ops_vector.columns_for(state)


def test_participation_rotation_rekeys_column():
    state, ctx = _warm_state()
    cols = ops_vector.columns_for(state)
    state.current_epoch_participation[1] = 0b101
    cols.list_column(state, "current_epoch_participation")
    from ethereum_consensus_tpu.models.altair.epoch_processing import (
        process_participation_flag_updates,
    )

    process_participation_flag_updates(state, ctx)
    prev = cols.list_column(state, "previous_epoch_participation")
    cur = cols.list_column(state, "current_epoch_participation")
    assert int(prev[1]) == 0b101
    assert int(cur[1]) == 0
    assert list(prev.tolist()) == [int(x) for x in state.previous_epoch_participation]


def test_columns_are_readonly():
    import numpy as np

    state, _ = _warm_state()
    cols = ops_vector.columns_for(state)
    col = cols.list_column(state, "balances")
    with pytest.raises(ValueError):
        col[0] = 1
    vc = cols.validator_columns(state)
    with pytest.raises(ValueError):
        vc["effective_balance"][0] = 1
    assert isinstance(col, np.ndarray)


def test_exotic_value_disarms_column():
    """A participation value outside u8 (invalid SSZ, but spec code must
    never read a stale column because of it) falls back instead of
    serving a wrapped value."""
    state, _ = _warm_state()
    cols = ops_vector.columns_for(state)
    assert cols.list_column(state, "current_epoch_participation") is not None
    state.current_epoch_participation[0] = 300  # > u8
    assert cols.list_column(state, "current_epoch_participation") is None
    state.current_epoch_participation[0] = 1
    col = cols.list_column(state, "current_epoch_participation")
    assert col is not None and int(col[0]) == 1


# ---------------------------------------------------------------------------
# a column off the list's clean _pack_tree (ssz/core.py _clean_pack_bytes)
# ---------------------------------------------------------------------------

# element type (its name is its numpy dtype's) -> (the vmax its column
# record carries, a length over the tracking threshold of small_groups)
_PACK_CASES = {"uint8": (0xFF, 300), "uint64": (2**64 - 1, 50)}
_PACK_LIMIT = 1 << 12
_PACK_FAULTS = ["dirty", "untracked", "absent", "width", "length", "kind"]


def _pack_list_type(width):
    from ethereum_consensus_tpu.ssz import core as ssz_core

    return ssz_core.List[getattr(ssz_core, width), _PACK_LIMIT]


def _pack_ints(width, top=None):
    """(seeded ints of the case's length up to ``top``, the case's vmax)."""
    vmax, n = _PACK_CASES[width]
    rng = random.Random(n)
    top = min(vmax, 2**63) if top is None else top
    return [rng.randrange(top + 1) for _ in range(n)], vmax


def _rooted_list(width, top=None):
    """(list type, a CachedRootList rooted once and holding no column, the
    same ints as a plain list, the column record's vmax)."""
    from ethereum_consensus_tpu.ssz.core import CachedRootList

    ints, vmax = _pack_ints(width, top)
    lst = CachedRootList(ints)
    LT = _pack_list_type(width)
    LT.hash_tree_root(lst)
    assert lst._pack_tree is not None and lst._dirty_groups == set()
    assert lst._col_cache is None and lst._uniform_kind == ("int",)
    return LT, lst, ints, vmax


def _column_counters() -> tuple:
    return (
        metrics.counter("ops_vector.columns.from_pack").value(),
        metrics.counter("ops_vector.columns.builds").value(),
    )


@pytest.mark.parametrize("width", ["uint8", "uint64"])
def test_pack_column_is_the_lists_ints(width, small_groups):
    """(a) no column, a clean _pack_tree of the asked width: the column
    is the tree's bytes, counted, installed owned and clean."""
    import numpy as np

    _LT, lst, ints, vmax = _rooted_list(width)
    before = _column_counters()
    col = ops_vector._sync_list_col(lst, np.dtype(width), vmax)
    assert _column_counters() == (before[0] + 1, before[1] + 1)
    assert col.dtype == np.dtype(width) and col.flags.owndata
    assert np.array_equal(col, np.array(list(lst), dtype=np.uint64).astype(width))
    assert lst._col_cache[0] == "list" and lst._col_cache[1] is col
    assert lst._col_cache[2] == vmax
    assert lst._col_owned is True and lst._col_dirty == set()
    # served from the record now: neither counter moves again
    assert ops_vector._sync_list_col(lst, np.dtype(width), vmax) is col
    assert _column_counters() == (before[0] + 1, before[1] + 1)
    assert list(lst) == ints


@pytest.mark.parametrize("fault", _PACK_FAULTS)
@pytest.mark.parametrize("width", ["uint8", "uint64"])
def test_pack_column_refused(width, fault, small_groups):
    """(b) a tree that is not provably the list's serialization at the
    asked width is not read: the ints are unboxed as before."""
    import numpy as np

    from ethereum_consensus_tpu.ssz.core import CachedRootList

    asked = width
    _LT, lst, ints, vmax = _rooted_list(
        width, top=0x7F if fault == "width" else None
    )
    if fault == "dirty":
        lst[5] = ints[5] = 7  # one write since the root
        assert lst._dirty_groups == {5 >> 2}
    elif fault == "untracked":
        lst._dirty_groups = None
    elif fault == "absent":
        lst = CachedRootList(ints)  # never rooted
        assert lst._pack_tree is None
    elif fault == "width":
        # the values fit both widths, so only the tree's key and its
        # byte length can refuse the other dtype
        asked = "uint64" if width == "uint8" else "uint8"
        vmax = _PACK_CASES[asked][0]
    elif fault == "length":
        lst._pack_tree[1].extend(bytes(np.dtype(width).itemsize))
    else:
        lst._uniform_kind = None
    before = _column_counters()
    col = ops_vector._sync_list_col(lst, np.dtype(asked), vmax)
    assert _column_counters() == (before[0], before[1] + 1)
    assert col.dtype == np.dtype(asked)
    assert np.array_equal(col, np.array(ints, dtype=np.uint64).astype(asked))
    assert lst._col_owned is True and lst._col_dirty == set()


@pytest.mark.parametrize("width", ["uint8", "uint64"])
def test_pack_column_is_a_copy(width, small_groups):
    """(c) the tree's buffer is spliced in place by the next root: the
    array handed out earlier must not follow it behind _col_dirty."""
    import numpy as np

    LT, lst, ints, vmax = _rooted_list(width)
    col = ops_vector._sync_list_col(lst, np.dtype(width), vmax)
    raw = lst._pack_tree[1]
    i = len(ints) - 3
    old, new = ints[i], (ints[i] + 1) & 0x7F
    lst[i] = new
    assert lst._col_dirty == {i}
    LT.hash_tree_root(lst)
    size = np.dtype(width).itemsize
    assert lst._pack_tree[1] is raw  # owned: spliced where it lies
    assert int.from_bytes(raw[i * size:(i + 1) * size], "little") == new
    assert int(col[i]) == old and lst._col_dirty == {i}
    before = _column_counters()
    rows = metrics.counter("ops_vector.columns.refresh_rows").value()
    again = ops_vector._sync_list_col(lst, np.dtype(width), vmax)
    assert again is col and int(again[i]) == new
    assert _column_counters() == before  # a one-row refresh, no build
    assert metrics.counter("ops_vector.columns.refresh_rows").value() == rows + 1
    assert lst._col_dirty == set()


@pytest.mark.parametrize("width", ["uint8", "uint64"])
def test_pack_column_of_copy_siblings(width, small_groups):
    """(d) copies share the tree (``_memos_owned`` false on both): each
    builds its own column off it, and one's writes stay its own."""
    import numpy as np

    from ethereum_consensus_tpu.ssz.core import CachedRootList, Container

    class Holder(Container):
        values: _pack_list_type(width)

    ints, vmax = _pack_ints(width)
    a = Holder(values=list(ints))
    Holder.hash_tree_root(a)
    b = a.copy()
    la, lb = a.values, b.values
    assert la.__class__ is CachedRootList and lb.__class__ is CachedRootList
    assert la._pack_tree is lb._pack_tree and la._pack_tree is not None
    assert la._memos_owned is False and lb._memos_owned is False
    before = _column_counters()
    col_a = ops_vector._sync_list_col(la, np.dtype(width), vmax)
    i = 7
    new = (ints[i] + 1) & 0x7F
    la[i] = new
    Holder.hash_tree_root(a)  # clones the shared buffer, then splices
    assert la._pack_tree is not lb._pack_tree
    col_b = ops_vector._sync_list_col(lb, np.dtype(width), vmax)
    assert _column_counters() == (before[0] + 2, before[1] + 2)
    assert col_a is not col_b
    assert np.array_equal(col_b, np.array(ints, dtype=np.uint64).astype(width))
    col_a = ops_vector._sync_list_col(la, np.dtype(width), vmax)
    assert int(col_a[i]) == new and int(col_b[i]) == ints[i]
    lb[i + 1] = 3
    assert int(ops_vector._sync_list_col(lb, np.dtype(width), vmax)[i + 1]) == 3
    assert int(col_a[i + 1]) == ints[i + 1] and list(la)[i + 1] == ints[i + 1]
    assert Holder.hash_tree_root(b) == Holder.hash_tree_root(
        Holder(values=ints[:i + 1] + [3] + ints[i + 2:])
    )


# ---------------------------------------------------------------------------
# withdrawal sweep parity
# ---------------------------------------------------------------------------


def _seed_withdrawal_candidates(state, ctx, fork, rng):
    n = len(state.validators)
    eth1 = b"\x01" + b"\x00" * 11 + b"\xaa" * 20
    compounding = b"\x02" + b"\x00" * 11 + b"\xbb" * 20
    for i in rng.sample(range(n), 24):
        v = state.validators[i]
        kind = rng.random()
        if kind < 0.4:  # fully withdrawable
            v.withdrawal_credentials = eth1
            v.withdrawable_epoch = 0
            state.balances[i] = rng.randrange(1, 10**10)
        elif kind < 0.8:  # partially withdrawable
            v.withdrawal_credentials = eth1
            v.effective_balance = int(ctx.MAX_EFFECTIVE_BALANCE)
            state.balances[i] = int(ctx.MAX_EFFECTIVE_BALANCE) + rng.randrange(
                1, 10**9
            )
        elif fork == "electra":  # compounding partial (EIP-7251)
            v.withdrawal_credentials = compounding
            v.effective_balance = int(ctx.MAX_EFFECTIVE_BALANCE_ELECTRA)
            state.balances[i] = int(
                ctx.MAX_EFFECTIVE_BALANCE_ELECTRA
            ) + rng.randrange(1, 10**9)


@pytest.mark.parametrize("fork", ["capella", "deneb", "electra"])
def test_withdrawals_sweep_columnar_matches_literal(fork, monkeypatch):
    bp = importlib.import_module(
        f"ethereum_consensus_tpu.models.{fork}.block_processing"
    )
    rng = random.Random(0x57E + len(fork))
    state, ctx = chain_utils.fresh_genesis_fork(fork, 256, "minimal")
    state = state.copy()
    _seed_withdrawal_candidates(state, ctx, fork, rng)
    state.next_withdrawal_validator_index = rng.randrange(len(state.validators))
    type(state).hash_tree_root(state)

    columnar = bp.get_expected_withdrawals(state, ctx)
    monkeypatch.setenv("ECT_OPS_VECTOR", "off")
    literal = bp.get_expected_withdrawals(state, ctx)
    assert columnar == literal


# ---------------------------------------------------------------------------
# effective-balance hysteresis parity
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# bench smoke (make bench-smoke): tier-1-adjacent engagement gate
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.bench_smoke
def test_bench_smoke_warm_block_engages_columnar_engine():
    """One warm mainnet-preset 2^14 deneb block: the columnar engine must
    engage (ops_vector.* counters), commit via bulk_store, and keep the
    named hot-scan spans off the per-block path — the cheap standing
    proof that the fast path didn't silently degrade to scalar writes."""
    from ethereum_consensus_tpu.models.altair.block_processing import (
        _registry_pubkey_index,
    )
    from ethereum_consensus_tpu.models.deneb import helpers
    from ethereum_consensus_tpu.models.deneb.state_transition import (
        state_transition,
    )
    from ethereum_consensus_tpu.models.phase0.helpers import (
        _registry_pubkey_objects,
    )
    from ethereum_consensus_tpu.telemetry import phases as tel_phases
    from ethereum_consensus_tpu.telemetry import spans as tel_spans

    state, ctx, signed = chain_utils.mainnet_block_bundle("deneb", 1 << 14, 8)
    # the state-level epoch memos, the pubkey memos and the registry
    # columns, filled on the original as a resident client holds them:
    # copies share all three
    epoch = helpers.get_current_epoch(state, ctx)
    for e in {epoch, max(0, epoch - 1)}:
        helpers.get_active_validator_indices(state, e)
    helpers.get_total_active_balance(state, ctx)
    _registry_pubkey_objects(state)
    _registry_pubkey_index(state)
    cols = ops_vector.columns_for(state)
    assert cols is not None
    cols.validator_columns(state)
    for field in (
        "balances",
        "inactivity_scores",
        "previous_epoch_participation",
        "current_epoch_participation",
    ):
        cols.list_column(state, field)
    warm = state.copy()
    state_transition(warm, signed, ctx)  # warm caches/compiles

    before = metrics.snapshot()
    with tel_spans.recording(capacity=1 << 17):
        s = state.copy()
        state_transition(s, signed, ctx)
        records = tel_spans.RECORDER.records()
    delta = metrics.delta(before)

    assert delta.get("ops_vector.attestations.blocks", 0) >= 1, (
        "columnar attestation engine did not engage on a warm mainnet "
        f"block; fallbacks: "
        f"{ {k: v for k, v in delta.items() if 'fallback' in k and v} }"
    )
    assert delta.get("ops_vector.bulk_store.calls", 0) >= 1
    report = tel_phases.hot_sweep_report(records)
    assert report["per_block_absent"], report


@pytest.mark.parametrize("fork", ["phase0", "electra"])
def test_effective_balance_hits_match_literal(fork):
    rng = random.Random(0xEB + len(fork))
    state, ctx = chain_utils.fresh_genesis_fork(fork, 256, "minimal")
    state = state.copy()
    for i in rng.sample(range(len(state.validators)), 64):
        state.balances[i] = rng.randrange(0, 2 * int(ctx.MAX_EFFECTIVE_BALANCE))
    if fork == "electra":
        comp = b"\x02" + b"\x00" * 11 + b"\xcc" * 20
        for i in rng.sample(range(len(state.validators)), 16):
            state.validators[i].withdrawal_credentials = comp
            state.balances[i] = rng.randrange(
                0, 2 * int(ctx.MAX_EFFECTIVE_BALANCE_ELECTRA)
            )
    type(state).hash_tree_root(state)

    hits = ops_vector.effective_balance_update_hits(
        state, ctx, per_validator_limit=(fork == "electra")
    )
    assert hits is not None

    literal = state.copy()
    ep = importlib.import_module(
        f"ethereum_consensus_tpu.models.{fork}.epoch_processing"
    )
    # run the LITERAL loop on the copy (below the vectorized threshold,
    # so process_effective_balance_updates takes the scalar branch)
    ep.process_effective_balance_updates(literal, ctx)
    applied = state.copy()
    for index, value in hits:
        applied.validators[index].effective_balance = value
    assert [v.effective_balance for v in applied.validators] == [
        v.effective_balance for v in literal.validators
    ]
