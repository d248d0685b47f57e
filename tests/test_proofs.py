"""Proof plane (proofs/, docs/PROOFS.md): the stored-levels walker vs
the cold oracles — ``Tree.proof``, ``IncrementalPaddedTree``-derived
branches and ``ssz.core.prove`` pinned byte-identical across padding /
truncation edges, warm single-branch + batched multiproof extraction,
decline accounting, and the ``make proofs-smoke`` gate.
"""

import random
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))

import chain_utils  # noqa: E402

from ethereum_consensus_tpu.proofs import (  # noqa: E402
    ProofContext,
    calculate_multi_merkle_root,
    extract_multiproof,
    extract_proof,
    get_helper_indices,
    verify_multiproof,
)
from ethereum_consensus_tpu.ssz import (  # noqa: E402
    ByteList,
    List,
    uint64,
)
from ethereum_consensus_tpu.ssz import core as ssz_core  # noqa: E402
from ethereum_consensus_tpu.ssz.core import CachedRootList  # noqa: E402
from ethereum_consensus_tpu.ssz.hash import hash_pair  # noqa: E402
from ethereum_consensus_tpu.ssz.merkle import (  # noqa: E402
    IncrementalPaddedTree,
    Tree,
    is_valid_merkle_branch,
    is_valid_merkle_branch_for_generalized_index,
    next_pow_of_two,
    zero_hash,
)
from ethereum_consensus_tpu.telemetry import metrics  # noqa: E402


# ---------------------------------------------------------------------------
# satellite: the three branch sources pinned identical at the chunk layer
# ---------------------------------------------------------------------------


def _brute_branch(chunks, limit, index):
    """Independent oracle: materialize the whole zero-padded tree with
    plain ``hash_pair`` and read the siblings off it."""
    width = next_pow_of_two(limit)
    depth = (width - 1).bit_length()
    level = list(chunks) + [zero_hash(0)] * (width - len(chunks))
    levels = [level]
    while len(level) > 1:
        level = [
            hash_pair(level[i], level[i + 1]) for i in range(0, len(level), 2)
        ]
        levels.append(level)
    branch = []
    at = index
    for d in range(depth):
        branch.append(levels[d][at ^ 1])
        at >>= 1
    return branch, levels[-1][0]


def _ipt_branch(ipt, index):
    """Leaf-first branch for level-0 node ``index`` read off an
    ``IncrementalPaddedTree``'s stored levels (the walker's warm read)."""
    ipt.root()  # settle: every level fresh
    branch = []
    at = index
    for d in range(ipt.depth):
        sibling = at ^ 1
        level = ipt.levels[d] if d < len(ipt.levels) else b""
        off = 32 * sibling
        if off < len(level):
            branch.append(bytes(level[off : off + 32]))
        else:
            branch.append(zero_hash(d))
        at >>= 1
    return branch


def test_tree_ipt_and_brute_branches_identical():
    """``Tree.proof``, the IncrementalPaddedTree-derived branch, and the
    brute-force oracle agree byte-for-byte across odd counts, heavy
    zero-padding, and post-truncation shapes."""
    rng = random.Random(0x17)
    shapes = [
        (1, 1), (1, 8), (2, 2), (3, 4), (3, 1 << 10),
        (5, 8), (31, 32), (33, 64), (100, 1 << 12), (257, 1 << 12),
    ]
    for n_leaves, limit in shapes:
        chunks = [rng.randbytes(32) for _ in range(n_leaves)]
        tree = Tree(chunks, limit)
        ipt = IncrementalPaddedTree(b"".join(chunks), limit)
        brute_root = None
        for index in {0, n_leaves - 1, rng.randrange(n_leaves)}:
            expect, brute_root = _brute_branch(chunks, limit, index)
            depth = len(expect)
            got_tree = tree.proof(index)
            got_ipt = _ipt_branch(ipt, index)
            assert got_tree == expect, (n_leaves, limit, index, "Tree")
            assert got_ipt == expect, (n_leaves, limit, index, "IPT")
            assert is_valid_merkle_branch(
                chunks[index], expect, depth, index, brute_root
            ), (n_leaves, limit, index)
        assert ipt.root() == brute_root == tree.root


def test_ipt_branches_after_truncate_and_edit():
    """The stored levels keep serving correct branches through the edge
    mutations: append, in-place edit, truncate (full-rebuild path)."""
    rng = random.Random(0x18)
    limit = 1 << 8
    chunks = [rng.randbytes(32) for _ in range(10)]
    ipt = IncrementalPaddedTree(b"".join(chunks), limit)
    ipt.root()
    # edit + append through the incremental path
    chunks[3] = rng.randbytes(32)
    ipt.set_node(3, chunks[3])
    chunks.append(rng.randbytes(32))
    ipt.set_node(10, chunks[10])
    for index in (0, 3, 10):
        expect, root = _brute_branch(chunks, limit, index)
        assert _ipt_branch(ipt, index) == expect
        assert ipt.root() == root
    # truncate schedules the full-rebuild path
    del chunks[6:]
    ipt.truncate(6)
    for index in (0, 5):
        expect, root = _brute_branch(chunks, limit, index)
        assert _ipt_branch(ipt, index) == expect
        assert ipt.root() == root


# ---------------------------------------------------------------------------
# the warm walker vs ssz.core.prove (the cold value walk)
# ---------------------------------------------------------------------------


def test_walker_differential_packed_list(small_groups):
    """Warm branches off ``_pack_tree`` byte-identical to ``prove`` for
    random indices, across group boundaries, after mutation+resettle."""
    rng = random.Random(0x19)
    LT = List[uint64, 1 << 12]
    values = CachedRootList(rng.randrange(1 << 60) for _ in range(300))
    pc = ProofContext(LT, values)
    assert pc.warm(), pc.declines
    indices = [0, 3, 4, 150, 298, 299]
    for i in indices:
        g = int(ssz_core.get_generalized_index(LT, i))
        branch = pc.proof(g)
        assert branch == ssz_core.prove(LT, values, g), i
        assert is_valid_merkle_branch_for_generalized_index(
            pc.node_at(g), branch, g, pc.root
        ), i
    # the length mix-in leaf
    assert pc.node_at(3) == (300).to_bytes(32, "little")
    # mutate, re-settle, extract again: the splice path must stay warm
    values[150] = 424242
    pc2 = ProofContext(LT, values)
    assert pc2.warm(), pc2.declines
    for i in indices:
        g = int(ssz_core.get_generalized_index(LT, i))
        assert pc2.proof(g) == ssz_core.prove(LT, values, g), ("post-mut", i)


def test_walker_differential_container_registry(small_groups):
    """Warm branches off ``_tree_memo`` (scalar-leaf container elements)
    down THROUGH the elements, identical to the cold walk."""
    rng = random.Random(0x20)
    state, ctx = chain_utils.fresh_genesis(64)
    state_type = type(state)
    pc = ProofContext(state_type, state)
    paths = [
        ("slot",),
        ("validators", 0, "effective_balance"),
        ("validators", 63, "public_key"),
        ("validators", rng.randrange(64)),
        ("balances", 17),
        ("finalized_checkpoint", "root"),
        ("latest_block_header", "state_root"),
    ]
    for path in paths:
        g = int(ssz_core.get_generalized_index(state_type, *path))
        branch = pc.proof(g)
        assert branch == ssz_core.prove(state_type, state, g), path
        assert is_valid_merkle_branch_for_generalized_index(
            pc.node_at(g), branch, g, pc.root
        ), path
        assert pc.node_at(g) == ssz_core.compute_subtree_root(
            state_type, state, g
        ), path


def test_walker_decline_paths(small_groups):
    """Unservable large layers decline LOUDLY — the context records the
    (layer, reason) and the ``proofs.fallback.{reason}`` counter bumps —
    then serve correct branches through the cold provider."""
    VLT = List[ByteList[64], 1 << 10]  # variable elements: no memo form
    values = [b"x" * (i % 64) for i in range(40)]
    base = metrics.snapshot()
    branch = extract_proof(VLT, values, int(ssz_core.get_generalized_index(VLT, 7)))
    g = int(ssz_core.get_generalized_index(VLT, 7))
    assert branch == ssz_core.prove(VLT, values, g)
    d = metrics.delta(base)
    fallbacks = {
        k.split("proofs.fallback.", 1)[1]: v
        for k, v in d.items()
        if k.startswith("proofs.fallback.") and v
    }
    assert fallbacks, "a large unsupported layer must be a counted decline"

    # a tracked list whose memos were never settled by THIS walk shape:
    # plain (non-CachedRootList) value declines as untracked
    LT = List[uint64, 1 << 12]
    plain = list(range(40))
    base = metrics.snapshot()
    g = int(ssz_core.get_generalized_index(LT, 5))
    assert extract_proof(LT, plain, g) == ssz_core.prove(LT, plain, g)
    d = metrics.delta(base)
    assert d.get("proofs.fallback.untracked_list"), d


# ---------------------------------------------------------------------------
# multiproof layout + batched extraction
# ---------------------------------------------------------------------------


def test_helper_indices_spec_shape():
    # two leaves sharing a parent need only the OUTER helpers
    assert get_helper_indices([8, 9]) == [5, 3]
    # a single leaf degenerates to its branch indices, descending
    assert get_helper_indices([9]) == [8, 5, 3]
    # an index plus its own ancestor: the ancestor's subtree helpers
    # still resolve (path indices never appear as helpers)
    assert 2 not in get_helper_indices([4, 2])


def test_multiproof_batched_vs_single(small_groups):
    """The batched multiproof resolves to the object root, every leaf is
    the single-extraction node, and duplicates are rejected."""
    rng = random.Random(0x21)
    LT = List[uint64, 1 << 12]
    values = CachedRootList(rng.randrange(1 << 60) for _ in range(300))
    pc = ProofContext(LT, values)
    gis = sorted(
        {int(ssz_core.get_generalized_index(LT, i)) for i in
         (0, 4, 5, 120, 121, 299)}
    )
    base = metrics.snapshot()
    mp = extract_multiproof(pc, gindices=gis)
    assert metrics.delta(base).get("proofs.batched") == 1
    assert mp.verify(pc.root)
    assert verify_multiproof(mp.leaves, mp.proof, mp.gindices, pc.root)
    assert calculate_multi_merkle_root(
        mp.leaves, mp.proof, mp.gindices
    ) == pc.root
    for g, leaf in zip(mp.gindices, mp.leaves):
        assert leaf == pc.node_at(g)
        assert leaf == ssz_core.compute_subtree_root(LT, values, g)
    # helpers byte-identical to the cold walk too
    for h, node in zip(get_helper_indices(gis), mp.proof):
        assert node == ssz_core.compute_subtree_root(LT, values, h)
    with pytest.raises(ValueError):
        extract_multiproof(pc, gindices=[gis[0], gis[0]])
    # a corrupted helper must not fold back to the root
    if mp.proof:
        bad = list(mp.proof)
        bad[0] = b"\xff" * 32
        assert not verify_multiproof(mp.leaves, bad, mp.gindices, pc.root)


def test_multiproof_on_beacon_state(small_groups):
    state, ctx = chain_utils.fresh_genesis(64)
    state_type = type(state)
    pc = ProofContext(state_type, state)
    gis = sorted(
        int(ssz_core.get_generalized_index(state_type, *path))
        for path in (
            ("slot",),
            ("balances", 3),
            ("validators", 11),
            ("finalized_checkpoint", "root"),
        )
    )
    mp = extract_multiproof(pc, gindices=gis)
    assert mp.verify(pc.root)


# ---------------------------------------------------------------------------
# the `make proofs-smoke` gate
# ---------------------------------------------------------------------------


@pytest.mark.proofs_smoke
def test_proofs_smoke():
    """One warm walk at a real (if small) registry: zero declines, zero
    fallback counters, branches byte-identical to the cold walk and
    verifying against the settled root — the proof-plane gate."""
    state, ctx = chain_utils.fresh_genesis(64)
    state_type = type(state)
    base = metrics.snapshot()
    pc = ProofContext(state_type, state)
    gis = [
        int(ssz_core.get_generalized_index(state_type, *path))
        for path in (
            ("slot",), ("balances", 5), ("validators", 40),
            ("finalized_checkpoint", "root"),
        )
    ]
    for g in gis:
        branch = pc.proof(g)
        assert branch == ssz_core.prove(state_type, state, g)
        assert is_valid_merkle_branch_for_generalized_index(
            pc.node_at(g), branch, g, pc.root
        )
    mp = extract_multiproof(pc, gindices=sorted(gis))
    assert mp.verify(pc.root)
    d = metrics.delta(base)
    assert pc.warm(), pc.declines
    assert not any(
        k.startswith("proofs.fallback.") and v for k, v in d.items()
    ), d
    assert d.get("proofs.served", 0) >= len(gis)
    assert d.get("proofs.batched") == 1
