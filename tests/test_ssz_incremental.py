"""Incremental hash_tree_root: dirty-group tracking regression tests.

Two layers of evidence (docs/INCREMENTAL_HTR.md):

* WORK-DONE regression — the digest-count instrumentation (ssz/hash.py)
  proves a single-element edit re-merkleizes one 4096-leaf group plus the
  log-depth path, not the whole collection. Wall-clock can't prove that
  on shared CI hardware; a hash count can (the CPU proxy for the
  ``one_validator_edit_s`` acceptance number in ISSUE 1).
* BIT-IDENTITY property — randomized mutation sequences (store / append /
  pop / nested-field writes / slice stores / bulk_store sweeps / index-
  shifting fallbacks) keep the incremental root equal to an independent
  naive hashlib merkleizer on small geometry, and equal to a cold
  deserialize-then-rehash on real BeaconStates across all six forks.
"""

import hashlib
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from ethereum_consensus_tpu.ssz import core as ssz_core
from ethereum_consensus_tpu.ssz import hash as ssz_hash
from ethereum_consensus_tpu.telemetry import metrics
from ethereum_consensus_tpu.ssz.core import (
    ByteVector,
    CachedRootList,
    Container,
    List,
    bulk_store,
    uint8,
    uint64,
)


def _h(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def _naive_merkleize(chunks: list, limit: int) -> bytes:
    """Independent reference: full zero-padded tree, plain hashlib."""
    width = 1
    while width < limit:
        width *= 2
    nodes = list(chunks) + [b"\x00" * 32] * (width - len(chunks))
    while len(nodes) > 1:
        nodes = [_h(nodes[i] + nodes[i + 1]) for i in range(0, len(nodes), 2)]
    return nodes[0]


class Val(Container):
    a: uint64
    b: ByteVector[32]


def _naive_val_root(v) -> bytes:
    return _h(int(v.a).to_bytes(8, "little").ljust(32, b"\x00") + bytes(v.b))


def _naive_list_root(values, limit: int) -> bytes:
    root = _naive_merkleize([_naive_val_root(v) for v in values], limit)
    return _h(root + len(values).to_bytes(32, "little"))


def _naive_uint_list_root(values, limit: int, size: int) -> bytes:
    packed = b"".join(int(v).to_bytes(size, "little") for v in values)
    if len(packed) % 32:
        packed += b"\x00" * (32 - len(packed) % 32)
    chunks = [packed[i : i + 32] for i in range(0, len(packed), 32)]
    root = _naive_merkleize(chunks, (limit * size + 31) // 32)
    return _h(root + len(values).to_bytes(32, "little"))


def _naive_u64_list_root(values, limit: int) -> bytes:
    return _naive_uint_list_root(values, limit, 8)


# ---------------------------------------------------------------------------
# work-done regression (real 4096-leaf geometry)
# ---------------------------------------------------------------------------


def test_digest_count_single_container_edit():
    """One field write on one element of an 8192-element scalar-leaf
    container list costs the element's own root and its path through the
    stored levels: never its 4096-leaf group, let alone the collection."""
    LT = List[Val, 1 << 40]
    values = CachedRootList(
        Val(a=i, b=i.to_bytes(4, "little") * 8) for i in range(8192)
    )
    LT.hash_tree_root(values)
    assert values._dirty_groups == set(), "tracking must be armed"

    # warm re-walk: zero tree work (root served from the group tree)
    before = ssz_hash.digest_count()
    LT.hash_tree_root(values)
    assert ssz_hash.digest_count() - before <= 2  # length mix-in only

    before = ssz_hash.digest_count()
    base = metrics.snapshot()
    values[5000].a = 10**15
    root = LT.hash_tree_root(values)
    delta = ssz_hash.digest_count() - before
    # the element's own root (1 for Val; 8 for a Validator) + its path
    # (12 levels to its group's root, 28 more for limit 2^40) + the
    # length mix-in, and a small slack
    assert delta <= 1 + 12 + 28 + 1 + 4, f"single edit cost {delta} digests"
    moved = metrics.delta(base)
    assert moved.get("ssz.tree_splice.path_rows") == 1
    assert not moved.get("ssz.tree_splice.group_walks")

    # bit-identity of the spliced root vs a cold rebuild
    cold = CachedRootList(Val(a=v.a, b=v.b) for v in values)
    assert LT.hash_tree_root(cold) == root


def test_digest_count_single_packed_edit():
    """One store into a 2^20-element uint64 list re-merkleizes ≤ one
    4096-chunk group + the log-depth path."""
    LT = List[uint64, 1 << 24]
    values = CachedRootList(range(1 << 20))
    LT.hash_tree_root(values)
    assert values._dirty_groups == set(), "tracking must be armed"

    before = ssz_hash.digest_count()
    values[777_777] = 31 * 10**9
    root = LT.hash_tree_root(values)
    delta = ssz_hash.digest_count() - before
    # group (4095) + path (limit 2^22 chunks -> 2^10 groups: depth 10)
    assert delta <= 4096 + 24, f"single edit cost {delta} digests"

    cold = CachedRootList(values)
    assert LT.hash_tree_root(cold) == root


def test_digest_count_bulk_store_few_groups():
    """A bulk_store that certifies a handful of changed indices costs a
    few groups, not a full re-merkleization."""
    LT = List[uint64, 1 << 24]
    values = CachedRootList(range(1 << 20))
    LT.hash_tree_root(values)

    new = list(values)
    for i in (3, 500_000, 1_000_000):
        new[i] += 1
    before = ssz_hash.digest_count()
    bulk_store(values, new, [3, 500_000, 1_000_000])
    root = LT.hash_tree_root(values)
    delta = ssz_hash.digest_count() - before
    assert delta <= 3 * 4096 + 64, f"3-element bulk edit cost {delta} digests"
    assert root == LT.hash_tree_root(CachedRootList(new))


def _armed_registry(count: int):
    LT = List[Val, 1 << 40]
    values = CachedRootList(
        Val(a=i, b=i.to_bytes(4, "little") * 8) for i in range(count)
    )
    LT.hash_tree_root(values)
    assert values._dirty_groups == set() and values._dirty_elems == set()
    return LT, values


def _cold_root(LT, values) -> bytes:
    return LT.hash_tree_root(CachedRootList(Val(a=v.a, b=v.b) for v in values))


def test_splice_rehashes_the_written_rows_not_their_groups():
    """A few scattered field writes dirty every group they fall in; the
    splice re-hashes those rows and their paths and reads every sibling
    from the levels it holds: four paths, not four groups. Shown also by
    an element it must not look at: a root cache planted wrong, without
    notice, on an unwritten row of a dirty group would come out in a
    whole-group walk."""
    LT, values = _armed_registry(3 * 4096 + 100)
    written = [7, 4096 + 9, 2 * 4096 + 11, 3 * 4096 + 50]
    for i in written:
        values[i].a = 10**15 + i
    assert values._dirty_groups == {0, 1, 2, 3}
    assert values._dirty_elems == set(written)
    true_root = values[8].__dict__["_htr_cache"]
    values[8].__dict__["_htr_cache"] = b"\x11" * 32  # never read
    before = ssz_hash.digest_count()
    base = metrics.snapshot()
    root = LT.hash_tree_root(values)
    # four roots, four paths of 40 that meet under the list's root
    assert ssz_hash.digest_count() - before <= 4 * (1 + 12) + 2 * 4 + 28 + 1 + 4
    moved = metrics.delta(base)
    assert moved.get("ssz.tree_splice.path_rows") == 4
    assert not moved.get("ssz.tree_splice.group_walks")
    values[8].__dict__["_htr_cache"] = true_root
    assert root == _cold_root(LT, values)
    assert values._dirty_groups == set() and values._dirty_elems == set()


@pytest.mark.parametrize("through_the_list", ["setitem", "append", "bulk_store"])
def test_a_mutation_through_the_list_falls_back_to_group_precision(through_the_list):
    """An element stored, appended or bulk-stored is known by group alone:
    element precision is off until the walk has serviced it, the root is
    right, and the next walk is armed again."""
    LT, values = _armed_registry(2 * 4096 + 5)
    values[10].a = 123  # an element write first: precision still on
    assert values._dirty_elems == {10}
    fresh = Val(a=77, b=b"\x07" * 32)
    if through_the_list == "setitem":
        values[4096 + 1] = fresh
    elif through_the_list == "append":
        values.append(fresh)
    else:
        new = list(values)
        new[4096 + 1] = fresh
        bulk_store(values, new, [4096 + 1])
    assert values._dirty_elems is None and values._dirty_groups
    values[20].a = 456  # and one after: marked by group
    base = metrics.snapshot()
    assert LT.hash_tree_root(values) == _cold_root(LT, values)
    moved = metrics.delta(base)
    # the written rows' group and the stored element's, each walked whole
    assert moved.get("ssz.tree_splice.group_walks") >= 2
    assert not moved.get("ssz.tree_splice.path_rows")
    assert values._dirty_elems == set()
    values[4096 + 1].a = 78  # the stored element is wired like the others
    assert values._dirty_elems == {4096 + 1}
    assert LT.hash_tree_root(values) == _cold_root(LT, values)


def test_copies_carry_their_own_written_rows():
    class Reg(Container):
        vals: List[Val, 1 << 40]

    reg = Reg(vals=[Val(a=i, b=i.to_bytes(4, "little") * 8) for i in range(8200)])
    Reg.hash_tree_root(reg)
    reg.vals[5].a = 1
    twin = reg.copy()
    assert twin.vals._dirty_elems == {5} and twin.vals._dirty_elems is not reg.vals._dirty_elems
    twin.vals[5000].a = 2
    reg.vals[8100].a = 3
    assert reg.vals._dirty_elems == {5, 8100} and twin.vals._dirty_elems == {5, 5000}
    for side in (reg, twin):
        cold = Reg(vals=[Val(a=v.a, b=v.b) for v in side.vals])
        assert Reg.hash_tree_root(side) == Reg.hash_tree_root(cold)


def _write(values, rows, salt=0):
    for i in rows:
        values[i].a = 10**12 + 7 * i + salt


def _scattered_in_every_group(LT, values):
    _write(values, range(11, len(values), 1000))
    return {"path_rows": len(range(11, len(values), 1000)), "group_walks": 0}


def _dense_in_one_group(LT, values):
    _write(values, range(4096 + 100, 4096 + 620))
    return {"path_rows": 520, "group_walks": 0}


def _in_a_partial_last_group(LT, values):
    n = len(values)
    assert n % 4096
    _write(values, (n - 1, n - 2, n - 37, (n >> 12 << 12)))
    return {"path_rows": 4, "group_walks": 0}


def _append_pop_truncate_between_roots(LT, values):
    for k in range(3):
        values.append(Val(a=k, b=bytes([k]) * 32))
    _write(values, (5, 9000))
    assert LT.hash_tree_root(values) == _cold_root(LT, values)
    values.pop()
    values.pop()
    _write(values, (6,))
    assert LT.hash_tree_root(values) == _cold_root(LT, values)
    _write(values, (7, len(values) - 1))
    assert LT.hash_tree_root(values) == _cold_root(LT, values)
    del values[2 * 4096 + 17 :]  # tracking lost: the discovery walk
    assert LT.hash_tree_root(values) == _cold_root(LT, values)
    for k in range(4096):  # over a group's edge, and over a level's width
        values.append(Val(a=k, b=bytes([k % 251]) * 32))
    _write(values, (8,))
    return {}


def _a_group_mark_among_element_marks(LT, values):
    _write(values, (3, 4096 + 3))
    values[2 * 4096 + 1 : 2 * 4096 + 3] = [
        Val(a=1, b=b"\x01" * 32), Val(a=2, b=b"\x02" * 32)
    ]
    _write(values, (2 * 4096 + 2, 3 * 4096 + 1), salt=1)
    # known by group from the slice store on: groups 0..3, each whole
    return {"path_rows": 0, "group_walks": 4}


def _an_element_that_refuses_caching(LT, values):
    values[4096 + 5].b = bytearray(b"\x05" * 32)  # can change without notice
    _write(values, (9,))
    assert LT.hash_tree_root(values) == _cold_root(LT, values)
    assert values._dirty_groups == {1} and values._dirty_elems is None
    values[4096 + 5].b[0] = 0x77  # and does
    _write(values, (2 * 4096 + 1,))
    # the sticky group is walked whole at every root, the other by group
    return {"path_rows": 0, "group_walks": 2}


@pytest.mark.parametrize(
    "scenario",
    [
        _scattered_in_every_group,
        _dense_in_one_group,
        _in_a_partial_last_group,
        _append_pop_truncate_between_roots,
        _a_group_mark_among_element_marks,
        _an_element_that_refuses_caching,
    ],
    ids=lambda f: f.__name__.strip("_"),
)
def test_spliced_root_is_the_cold_root(scenario):
    """Whatever marked the list, by element or by group, the spliced root
    is the cold ``hash_tree_root`` of a fresh list of the same values,
    and the route taken is the one the marks name."""
    LT, values = _armed_registry(3 * 4096 + 100)
    expected = scenario(LT, values)
    base = metrics.snapshot()
    root = LT.hash_tree_root(values)
    moved = metrics.delta(base)
    assert root == _cold_root(LT, values)
    for name, count in expected.items():
        assert moved.get("ssz.tree_splice." + name, 0) == count, (name, moved)
    assert LT.hash_tree_root(values) == root  # and it stands


def test_copy_siblings_written_in_turn_do_not_move_each_other():
    """Two copies share the stored levels until one writes; each write
    clones, so neither root moves with the other's rows."""
    class Reg(Container):
        vals: List[Val, 1 << 40]

    reg = Reg(vals=[Val(a=i, b=i.to_bytes(4, "little") * 8) for i in range(8200)])
    Reg.hash_tree_root(reg)
    twin = reg.copy()
    assert twin.vals._tree_memo is reg.vals._tree_memo

    def cold(side):
        return Reg.hash_tree_root(Reg(vals=[Val(a=v.a, b=v.b) for v in side.vals]))

    untouched = Reg.hash_tree_root(reg)
    _write(twin.vals, (5, 5000))
    twin_root = Reg.hash_tree_root(twin)
    assert twin_root == cold(twin) != untouched
    assert twin.vals._tree_memo[2] is not reg.vals._tree_memo[2]
    assert Reg.hash_tree_root(reg) == untouched == cold(reg)
    _write(reg.vals, (5, 8100), salt=3)
    reg_root = Reg.hash_tree_root(reg)
    assert reg_root == cold(reg) and reg_root not in (untouched, twin_root)
    assert Reg.hash_tree_root(twin) == twin_root == cold(twin)
    third = twin.copy()  # a copy of a written copy, written in its turn
    _write(third.vals, (8199,), salt=5)
    twin.vals.append(Val(a=1, b=b"\x09" * 32))
    assert Reg.hash_tree_root(third) == cold(third)
    assert Reg.hash_tree_root(twin) == cold(twin)
    assert Reg.hash_tree_root(reg) == reg_root


@pytest.mark.parametrize("offset", [0, 3])
@pytest.mark.parametrize("limit", [64, 1 << 20])
def test_stored_levels_follow_any_run_of_marks(limit, offset):
    """``IncrementalPaddedTree``: single marks, runs, growth a node and a
    run at a time, truncation to any width and to nothing, each root the
    plain merkleization of the nodes it holds (batched levels above the
    native hasher's width, pair by pair under it)."""
    from ethereum_consensus_tpu.ssz.merkle import (
        IncrementalPaddedTree,
        merkleize_chunks,
    )

    rng = random.Random(99 + limit + offset)
    nodes = [rng.randbytes(32) for _ in range(37)]
    tree = IncrementalPaddedTree(b"".join(nodes), limit, level_offset=offset)

    def check():
        want = merkleize_chunks(b"".join(nodes), limit=limit, level_offset=offset)
        assert tree.root() == want
        assert tree.node_count() == len(nodes)
        keep = len(nodes)
        for level in tree.levels:  # the populated region, and no more
            assert len(level) == 32 * keep
            keep = (keep + 1) // 2

    check()
    for step in range(60):
        op = rng.randrange(6)
        n = len(nodes)
        if op == 0 and n:  # a few single marks
            for i in rng.sample(range(n), min(n, rng.choice((1, 3, 20)))):
                nodes[i] = rng.randbytes(32)
                tree.set_node(i, nodes[i])
        elif op == 1 and n < limit:  # grow by one
            nodes.append(rng.randbytes(32))
            tree.set_node(n, nodes[-1])
        elif op == 2 and n:  # a run inside, or over the end
            start = rng.randrange(n + 1)
            run = [rng.randbytes(32) for _ in range(rng.choice((1, 2, 9, 24)))]
            run = run[: limit - start]
            nodes[start : start + len(run)] = run
            tree.set_nodes(start, b"".join(run))
        elif op == 3 and n:  # cut, with marks pending on both sides of it
            i = rng.randrange(n)
            nodes[i] = rng.randbytes(32)
            tree.set_node(i, nodes[i])
            keep = rng.randrange(n + 1)
            del nodes[keep:]
            tree.truncate(keep)
        elif op == 4 and n:  # a clone goes its own way
            twin = tree.clone()
            twin.set_node(0, b"\xee" * 32)
            twin.root()
        if step % 3 == 0:
            check()
    check()
    with pytest.raises(IndexError):
        tree.set_nodes(len(nodes) + 1, b"\x00" * 32)


# ---------------------------------------------------------------------------
# bit-identity property (shrunk geometry, independent naive reference)
# ---------------------------------------------------------------------------


def test_property_container_list_random_mutations(small_groups):
    LIMIT = 4096
    LT = List[Val, LIMIT]
    rng = random.Random(1234)
    values = CachedRootList(
        Val(a=i, b=bytes([i % 256]) * 32) for i in range(24)
    )
    shadow = [(int(v.a), bytes(v.b)) for v in values]

    def check():
        got = LT.hash_tree_root(values)
        want = _naive_list_root(
            [Val(a=a, b=b) for a, b in shadow], LIMIT
        )
        assert got == want

    check()
    for step in range(300):
        op = rng.randrange(8)
        n = len(values)
        if op == 0 and n:  # store a fresh element
            i = rng.randrange(n)
            v = Val(a=rng.getrandbits(60), b=rng.randbytes(32))
            values[i] = v
            shadow[i] = (int(v.a), bytes(v.b))
        elif op == 1:  # append
            v = Val(a=rng.getrandbits(60), b=rng.randbytes(32))
            values.append(v)
            shadow.append((int(v.a), bytes(v.b)))
        elif op == 2 and n > 4:  # end pop (tracked)
            values.pop()
            shadow.pop()
        elif op == 3 and n:  # nested field write through the parent chain
            i = rng.randrange(n)
            values[i].a = rng.getrandbits(60)
            shadow[i] = (int(values[i].a), shadow[i][1])
        elif op == 4 and n:  # second field
            i = rng.randrange(n)
            values[i].b = rng.randbytes(32)
            shadow[i] = (shadow[i][0], bytes(values[i].b))
        elif op == 5 and n > 2:  # contiguous slice store
            i = rng.randrange(n - 2)
            repl = [
                Val(a=rng.getrandbits(60), b=rng.randbytes(32))
                for _ in range(2)
            ]
            values[i : i + 2] = repl
            shadow[i : i + 2] = [(int(v.a), bytes(v.b)) for v in repl]
        elif op == 6 and n:  # index-shifting mutation: tracking must drop
            i = rng.randrange(n)
            v = Val(a=rng.getrandbits(60), b=rng.randbytes(32))
            values.insert(i, v)
            shadow.insert(i, (int(v.a), bytes(v.b)))
        elif op == 7 and n > 8:  # interior delete: tracking must drop
            i = rng.randrange(n - 1)
            del values[i]
            del shadow[i]
        if step % 17 == 0:
            check()
    check()


def test_property_packed_list_random_mutations(small_groups):
    LIMIT = 1 << 16
    LT = List[uint64, LIMIT]
    rng = random.Random(4321)
    values = CachedRootList(range(40))
    shadow = list(range(40))

    def check():
        assert LT.hash_tree_root(values) == _naive_u64_list_root(
            shadow, LIMIT
        )

    check()
    for step in range(300):
        op = rng.randrange(6)
        n = len(values)
        if op == 0 and n:
            i = rng.randrange(n)
            values[i] = shadow[i] = rng.getrandbits(64)
        elif op == 1:
            v = rng.getrandbits(64)
            values.append(v)
            shadow.append(v)
        elif op == 2 and n > 4:
            values.pop()
            shadow.pop()
        elif op == 3 and n > 4:  # bulk sweep with certified indices
            new = list(shadow)
            idxs = sorted(rng.sample(range(n), max(1, n // 4)))
            for i in idxs:
                new[i] = rng.getrandbits(63)
            bulk_store(values, new, idxs)
            shadow = new
        elif op == 4 and n > 2:  # bulk sweep, unknown indices
            new = [v ^ 0xFF for v in shadow]
            bulk_store(values, new)
            shadow = new
        elif op == 5 and n > 8:  # index-shifting mutation
            i = rng.randrange(n - 1)
            del values[i]
            del shadow[i]
        if step % 13 == 0:
            check()
    check()


def test_property_copies_diverge_independently(small_groups):
    """state.copy() shares memos copy-on-write: mutate original and copy
    in interleaved sequence; both must keep exact roots."""
    LIMIT = 4096
    LT = List[Val, LIMIT]
    rng = random.Random(99)
    a = CachedRootList(Val(a=i, b=bytes([i]) * 32) for i in range(30))
    LT.hash_tree_root(a)  # arm tracking before copying
    b = ssz_core._copy_value(LT, a)
    sa = [(int(v.a), bytes(v.b)) for v in a]
    sb = list(sa)
    for _ in range(120):
        which = rng.randrange(2)
        vals, shadow = (a, sa) if which == 0 else (b, sb)
        op = rng.randrange(3)
        n = len(vals)
        if op == 0 and n:
            i = rng.randrange(n)
            vals[i].a = rng.getrandbits(50)
            shadow[i] = (int(vals[i].a), shadow[i][1])
        elif op == 1:
            v = Val(a=rng.getrandbits(50), b=rng.randbytes(32))
            vals.append(v)
            shadow.append((int(v.a), bytes(v.b)))
        elif op == 2 and n > 4:
            vals.pop()
            shadow.pop()
        if rng.randrange(4) == 0:
            got_a = LT.hash_tree_root(a)
            got_b = LT.hash_tree_root(b)
            assert got_a == _naive_list_root(
                [Val(a=x, b=y) for x, y in sa], LIMIT
            )
            assert got_b == _naive_list_root(
                [Val(a=x, b=y) for x, y in sb], LIMIT
            )


# ---------------------------------------------------------------------------
# manifest lockstep: every instrumented mutator keeps the incremental root
# ---------------------------------------------------------------------------


def test_every_manifest_mutator_keeps_incremental_root(small_groups):
    """Runtime counterpart of tools/speclint's mutation-purity analyzer:
    drive every mutator named in ssz/core.py's instrumented-surface
    manifest against an armed (dirty-group-tracked) list and assert the
    incremental root stays bit-identical to a cold recompute. The
    coverage assertion fails the moment a new mutator enters the
    manifest without a script here — manifest, analyzer, and runtime
    stay in lockstep."""
    surface = ssz_core.instrumented_surface()
    rng = random.Random(20260804)

    def setitem(xs):
        xs[rng.randrange(len(xs))] = rng.getrandbits(60)

    def setitem_slice(xs):
        xs[1:3] = [rng.getrandbits(60), rng.getrandbits(60)]

    def delitem(xs):
        del xs[rng.randrange(len(xs))]

    def iadd(xs):
        ys = xs
        ys += [rng.getrandbits(60) for _ in range(3)]

    def imul(xs):
        ys = xs
        ys *= 2

    scripts = {
        "__setitem__": [setitem, setitem_slice],
        "__delitem__": [delitem],
        "__iadd__": [iadd],
        "__imul__": [imul],
        "append": [lambda xs: xs.append(rng.getrandbits(60))],
        "extend": [lambda xs: xs.extend(rng.getrandbits(60) for _ in range(5))],
        "insert": [lambda xs: xs.insert(rng.randrange(len(xs) + 1), rng.getrandbits(60))],
        "pop": [lambda xs: xs.pop(), lambda xs: xs.pop(rng.randrange(len(xs)))],
        "remove": [lambda xs: xs.remove(xs[rng.randrange(len(xs))])],
        "clear": [lambda xs: xs.clear()],
        "sort": [lambda xs: xs.sort()],
        "reverse": [lambda xs: xs.reverse()],
    }
    # lockstep: a manifest mutator with no script here must fail loudly
    assert set(scripts) == set(surface["list_mutators"])
    assert surface["bulk_mutators"] == ("bulk_store",)

    LT = List[uint64, 1 << 16]
    for name in surface["list_mutators"]:
        for script in scripts[name]:
            values = CachedRootList(rng.getrandbits(60) for _ in range(40))
            LT.hash_tree_root(values)  # arm tracking/memos
            script(values)
            got = LT.hash_tree_root(values)
            want = LT.hash_tree_root(CachedRootList(list(values)))
            assert got == want, f"mutator {name} left a stale incremental root"

    # the bulk-mutator channel, certified and uncertified
    for changed in ([2, 17, 33], None):
        values = CachedRootList(rng.getrandbits(60) for _ in range(40))
        LT.hash_tree_root(values)
        new = list(values)
        for i in (2, 17, 33):
            new[i] += 1
        bulk_store(values, new, changed)
        assert LT.hash_tree_root(values) == LT.hash_tree_root(CachedRootList(new))

    # the container-field-write channel (Container.__setattr__)
    assert surface["container_field_write"] == "Container.__setattr__"
    CLT = List[Val, 4096]
    values = CachedRootList(Val(a=i, b=bytes([i % 256]) * 32) for i in range(24))
    CLT.hash_tree_root(values)
    values[7].a = rng.getrandbits(50)
    values[19].b = rng.randbytes(32)
    got = CLT.hash_tree_root(values)
    want = CLT.hash_tree_root(CachedRootList(Val(a=v.a, b=v.b) for v in values))
    assert got == want


# ---------------------------------------------------------------------------
# the full pack off a clean wire-width column (_clean_wire_column)
# ---------------------------------------------------------------------------

# element type (its name is its wire-width numpy dtype's) -> (descriptor,
# the vmax its column record carries, a length under the tracking
# threshold of small_groups (4 chunks), one over it)
_COLUMN_CASES = {
    "uint8": (uint8, 0xFF, 40, 300),
    "uint64": (uint64, 2**64 - 1, 12, 50),
}
_COLUMN_LIMIT = 1 << 12


def _column_list(width: str, side: str, how: str):
    """(list type, a never-rooted CachedRootList that holds its content as
    a clean column, the same ints as a plain list, the element's bytes)."""
    import numpy as np

    from ethereum_consensus_tpu.models import ops_vector

    elem, vmax, under, over = _COLUMN_CASES[width]
    n = under if side == "under" else over
    if how == "installed":  # the participation rotation's fresh zeros
        ints = [0] * n
        lst = CachedRootList(ints)
        ops_vector.install_zero_column(lst, n, vmax)
    else:  # an epoch commit: the authoritative array spliced in
        rng = random.Random(n)
        ints = [rng.randrange(min(vmax, 2**63)) for _ in range(n)]
        lst = CachedRootList([0] * n)
        arr = np.array(ints, dtype=width)
        ops_vector.adopt_list_column(lst, arr, np.nonzero(arr)[0], vmax)
    assert lst._pack_tree is None and lst._pack_memo is None
    assert lst._col_dirty == set() and list(lst) == ints
    return List[elem, _COLUMN_LIMIT], lst, ints, elem.byte_length


def _from_column() -> tuple:
    return (
        ssz_core._PACK_FROM_COLUMN.value(),
        ssz_core._PACK_FROM_COLUMN_BYTES.value(),
    )


def _memo_state(lst) -> tuple:
    pt = lst._pack_tree
    tree = None if pt is None else (
        pt[0], bytes(pt[1]), [bytes(lv) for lv in pt[2].levels], pt[3]
    )
    return tree, lst._pack_memo, lst._dirty_groups, lst._uniform_kind


@pytest.mark.parametrize("how", ["installed", "adopted"])
@pytest.mark.parametrize("side", ["under", "over"])
@pytest.mark.parametrize("width", ["uint8", "uint64"])
def test_column_pack_roots_as_the_plain_list(width, side, how, small_groups):
    """(a) a clean column and no _pack_tree: the plain list's root, the
    plain list's memos, one engagement of the column's byte length."""
    LT, lst, ints, size = _column_list(width, side, how)
    before = _from_column()
    got = LT.hash_tree_root(lst)
    assert _from_column() == (before[0] + 1, before[1] + len(ints) * size)
    assert got == LT.hash_tree_root(list(ints))
    assert got == _naive_uint_list_root(ints, _COLUMN_LIMIT, size)
    plain = CachedRootList(ints)
    assert LT.hash_tree_root(plain) == got
    assert _from_column()[0] == before[0] + 1  # the plain list has no column
    assert _memo_state(lst) == _memo_state(plain)
    assert (lst._pack_tree is not None) == (side == "over")


@pytest.mark.parametrize("fault", ["dirty", "untracked", "width", "length"])
@pytest.mark.parametrize("side", ["under", "over"])
@pytest.mark.parametrize("width", ["uint8", "uint64"])
def test_column_pack_refused(width, side, fault, small_groups):
    """(b) a column that is not provably the list's content at wire width
    is not read, and the root is the ints'."""
    import numpy as np

    from ethereum_consensus_tpu.ssz import column_list

    LT, lst, ints, size = _column_list(width, side, "adopted")
    # the column as a CACHE beside boxed content (what a bulk_store of
    # the array leaves): a uint64 adoption is column-primary, where the
    # column is the content and cannot go stale (tests/test_column_list.py)
    column_list.leave(lst)
    vmax = lst._col_cache[2]
    if fault == "dirty":
        lst[5] = ints[5] = 7  # the instrumented mutator names the index
        assert lst._col_dirty == {5}
    elif fault == "untracked":
        lst._col_dirty = None
    elif fault == "width":
        # the values fit the narrow width both ways, so only the dtype's
        # itemsize can refuse it
        ints = [v & 0x7F for v in ints]
        lst = CachedRootList(ints)
        other = "uint64" if width == "uint8" else "uint8"
        lst._col_cache = ("list", np.array(ints, dtype=other), vmax)
        lst._col_dirty = set()
    else:
        lst._col_cache = ("list", np.zeros(len(ints) + 1, dtype=width), vmax)
    before = _from_column()
    assert LT.hash_tree_root(lst) == _naive_uint_list_root(
        ints, _COLUMN_LIMIT, size
    )
    assert _from_column() == before


@pytest.mark.parametrize("side", ["under", "over"])
@pytest.mark.parametrize("width", ["uint8", "uint64"])
def test_column_pack_second_root_moves_no_counter(width, side, small_groups):
    """(c) the shortcut builds the memo the next walk is served from."""
    LT, lst, _ints, _size = _column_list(width, side, "installed")
    first = LT.hash_tree_root(lst)
    before = _from_column()
    digests = ssz_hash.digest_count()
    lst._root_cache.clear()  # past the per-descriptor root cache
    assert LT.hash_tree_root(lst) == first
    assert _from_column() == before
    # generation memo under the threshold, the splice's clean return over
    # it: neither packs nor hashes the elements again (the length mix-in)
    assert ssz_hash.digest_count() - digests <= 1


@pytest.mark.parametrize("side", ["under", "over"])
@pytest.mark.parametrize("width", ["uint8", "uint64"])
def test_column_pack_then_write(width, side, small_groups):
    """(d) dirty tracking is armed by the shortcut's memo as by any full
    pack: a write marks its group and the next root is the plain list's."""
    from ethereum_consensus_tpu.ssz import column_list

    LT, lst, ints, size = _column_list(width, side, "adopted")
    column_list.leave(lst)  # the plain list and its cache, as above
    LT.hash_tree_root(lst)
    i = len(ints) - 3
    lst[i] = ints[i] = 99
    assert lst._col_dirty == {i}
    if side == "over":
        assert lst._dirty_groups == {i >> ssz_core._DIRTY_GROUP_SHIFT}
    before = _from_column()
    assert LT.hash_tree_root(lst) == _naive_uint_list_root(
        ints, _COLUMN_LIMIT, size
    )
    assert _from_column() == before  # the column is dirty now


# ---------------------------------------------------------------------------
# six-fork state-level bit-identity (incremental vs cold deserialize)
# ---------------------------------------------------------------------------

FORKS = ["phase0", "altair", "bellatrix", "capella", "deneb", "electra"]


@pytest.mark.parametrize("fork", FORKS)
def test_state_roots_match_cold_recompute(fork, small_groups):
    """Randomized state mutations (balances stores, bulk sweeps, registry
    field writes, appends, randao writes, participation sweeps) keep the
    incremental root bit-identical to a cold serialize->deserialize->
    rehash on a fresh object graph."""
    import chain_utils

    state, ctx = chain_utils.fresh_genesis_fork(fork, 64, "minimal")
    state_type = type(state)
    # decouple from the module-level genesis cache: memos built under the
    # shrunk geometry must never leak into other tests' copies
    state = state_type.deserialize(state_type.serialize(state))
    rng = random.Random(hash(fork) & 0xFFFF)

    def cold_root():
        fresh = state_type.deserialize(state_type.serialize(state))
        return state_type.hash_tree_root(fresh)

    assert state_type.hash_tree_root(state) == cold_root()
    n = len(state.validators)
    for step in range(40):
        op = rng.randrange(6)
        if op == 0:
            state.balances[rng.randrange(n)] = rng.getrandbits(40)
        elif op == 1:
            new = [v + rng.randrange(3) for v in state.balances]
            changed = [i for i, (x, y) in enumerate(zip(new, state.balances)) if x != y]
            bulk_store(state.balances, new, changed)
        elif op == 2:
            v = state.validators[rng.randrange(n)]
            v.effective_balance = rng.getrandbits(40)
        elif op == 3:
            src = state.validators[rng.randrange(n)]
            state.validators.append(src.copy())
            state.balances.append(32 * 10**9)
            n += 1
        elif op == 4:
            mixes = state.randao_mixes
            mixes[rng.randrange(len(mixes))] = rng.randbytes(32)
        elif op == 5 and fork != "phase0":
            part = state.previous_epoch_participation
            if len(part):
                part[rng.randrange(len(part))] = rng.randrange(8)
        if step % 8 == 0:
            assert state_type.hash_tree_root(state) == cold_root(), (
                f"{fork}: divergence at step {step}"
            )
    assert state_type.hash_tree_root(state) == cold_root()


# ---------------------------------------------------------------------------
# the post-epoch root's counter scope (utils/trace.scope) and the ssz spans
# ---------------------------------------------------------------------------

EPOCH_ROOT = "transition.epoch_root"
# what the scope's own time leaves to its direct children: the three ssz
# spans that never nest in each other, and a collection that falls there
SCOPE_CHILDREN = ("ssz.packed_splice", "ssz.tree_splice", "ssz.full_pack",
                  "gc.collect")


def _counters() -> dict:
    return {k: v for k, v in metrics.snapshot().items() if isinstance(v, int)}


def _moved(before: dict) -> dict:
    return {
        name: value - before.get(name, 0)
        for name, value in _counters().items()
        if value != before.get(name, 0)
    }


def _scope_moves(moved: dict) -> dict:
    return {
        name: value for name, value in moved.items()
        if name.startswith(("span." + EPOCH_ROOT, EPOCH_ROOT))
    }


@pytest.fixture(scope="module")
def driver_chain():
    """The 1m cell's driver cut to 2^16 rows (above
    ``_DIRTY_TRACK_MIN_CHUNKS``: the balances are 16 chunk-groups), under
    the span recorder: a crossing, then the untimed advance (31 empty
    slots, the participation refill, a root), the next crossing and its
    root, and that root again. Returns what each step moved and the
    crossing's root beside the literal oracle's (``ECT_EPOCH_VECTOR=off``
    on a copy)."""
    import gc
    import json

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmark import worlds
    from ethereum_consensus_tpu.models.deneb import slot_processing
    from ethereum_consensus_tpu.telemetry import spans

    root_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root_dir, "benchmark/configs/mainnet-deneb-1m.json")) as f:
        config = json.load(f)
    config["validators"] = 1 << 16
    world = worlds.build(
        config,
        {"kind": "epoch_edge", "epoch": 1, "miss_share": [0.01, 0.03],
         "chain_epochs": 2},
        3700000017,
    )
    state = world.pre.copy()
    htr = type(state).hash_tree_root
    target = world.target_slot
    out = {}
    with spans.recording():
        slot_processing.process_slots(state, target, world.context)
        htr(state)
        # the driver's advance: 31 empty slots, the refill, a root
        before = _counters()
        slot_processing.process_slots(state, target + 31, world.context)
        state.current_epoch_participation = world.refills[0].tolist()
        htr(state)
        out["advance"] = _moved(before)
        literal = state.copy()
        before = _counters()
        slot_processing.process_slots(state, target + 32, world.context)
        out["marked"] = ssz_core._ROOT_SCOPE in state.__dict__
        out["copy_marked"] = ssz_core._ROOT_SCOPE in state.copy().__dict__
        gc.disable()  # a collection inside a splice would sit a level down
        try:
            out["root"] = htr(state)
        finally:
            gc.enable()
        out["crossing"] = _moved(before)
        before = _counters()
        assert htr(state) == out["root"]
        out["again"] = _moved(before)
    os.environ["ECT_EPOCH_VECTOR"] = "off"
    try:
        slot_processing.process_slots(literal, target + 32, world.context)
    finally:
        os.environ.pop("ECT_EPOCH_VECTOR", None)
    out["literal"] = type(literal).hash_tree_root(literal)
    return out


def test_the_untimed_advance_opens_no_epoch_root_scope(driver_chain):
    """31 slot roots and the refill's root hash, and none is the root after
    an epoch pass: no scoped total and no scoped digest moves."""
    moved = driver_chain["advance"]
    assert moved["ssz.digests"] > 0
    assert moved.get("span.transition.state_htr.n") == 31
    assert _scope_moves(moved) == {}


def test_a_crossing_opens_the_scope_once_with_the_oracles_root(driver_chain):
    moved = driver_chain["crossing"]
    assert driver_chain["marked"] and not driver_chain["copy_marked"]
    assert moved["span.transition.epoch_root.n"] == 1
    assert driver_chain["root"] == driver_chain["literal"]
    # every compression of the crossing's root is the scope's; the slot
    # root before the pass is not
    assert 0 < moved[EPOCH_ROOT + ".digests"] < moved["ssz.digests"]
    assert moved[f"span.{EPOCH_ROOT}/ssz.packed_splice.n"] >= 1
    assert moved[f"span.{EPOCH_ROOT}/ssz.full_pack.n"] >= 1


@pytest.mark.parametrize("what", ["n", "ns", "self_ns"])
def test_a_scoped_total_is_the_spans_own_total_inside_the_scope(
    driver_chain, what
):
    """Every ``ssz.*`` span of the crossing ended inside the root's scope:
    its scoped totals are its totals."""
    moved = driver_chain["crossing"]
    for part in ("ssz.packed_splice", "ssz.full_pack"):
        assert moved[f"span.{EPOCH_ROOT}/{part}.{what}"] == moved[
            f"span.{part}.{what}"
        ], part


def test_the_parts_and_the_rest_are_the_scope(driver_chain):
    moved = driver_chain["crossing"]
    parts = sum(
        moved.get(f"span.{EPOCH_ROOT}/{child}.ns", 0)
        for child in SCOPE_CHILDREN
    )
    rest = moved[f"span.{EPOCH_ROOT}.self_ns"]
    assert parts > 0 and rest > 0
    assert parts + rest == moved[f"span.{EPOCH_ROOT}.ns"]


def test_a_second_root_of_an_unchanged_state_opens_no_ssz_span(driver_chain):
    moved = driver_chain["again"]
    assert not [name for name in moved if name.startswith("span.ssz.")]
    assert _scope_moves(moved) == {}


def test_the_block_path_opens_the_scope_inside_its_state_root_check():
    """A node: the block at an epoch's first slot is imported, and the
    root its import checks is the root after the pass."""
    import chain_utils
    from ethereum_consensus_tpu.models.deneb.state_transition import (
        Validation,
        state_transition_block_in_slot,
    )
    from ethereum_consensus_tpu.telemetry import spans

    state, ctx = chain_utils.fresh_genesis_deneb(16, "minimal")
    state = state.copy()
    spe = int(ctx.SLOTS_PER_EPOCH)
    block = chain_utils.produce_block_deneb(state, spe, ctx)
    assert ssz_core._ROOT_SCOPE in state.__dict__  # the advance's pass
    with spans.recording() as recorder:
        state_transition_block_in_slot(state, block, Validation.ENABLED, ctx)
        records = recorder.records()
    by_id = {r.span_id: r for r in records}
    (scope,) = [r for r in records if r.name == EPOCH_ROOT]
    parent = by_id[scope.parent_id]
    assert parent.name == "transition.state_htr"
    assert int(parent.fields["slot"]) == spe
    assert ssz_core._ROOT_SCOPE not in state.__dict__


def test_a_copy_does_not_carry_the_mark():
    """The mark belongs to the value the pass ran on: its copy roots
    without a scope, the original opens it at its own next root."""
    import chain_utils
    from ethereum_consensus_tpu.models.deneb import slot_processing
    from ethereum_consensus_tpu.telemetry import spans

    state, ctx = chain_utils.fresh_genesis_deneb(16, "minimal")
    state = state.copy()
    slot_processing.process_slots(state, int(ctx.SLOTS_PER_EPOCH), ctx)
    copy = state.copy()
    assert ssz_core._ROOT_SCOPE not in copy.__dict__
    assert copy == state  # not a field: never compared
    htr = type(state).hash_tree_root
    with spans.recording():
        before = _counters()
        copy_root = htr(copy)
        after_copy = _moved(before)
        assert htr(state) == copy_root
        after_state = _moved(before)
    assert _scope_moves(after_copy) == {}
    assert after_state["span.transition.epoch_root.n"] == 1
