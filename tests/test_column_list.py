"""Column-primary list storage (ssz/column_list.py): the surface as a
property test.

A list whose whole content ``adopt`` took from a ``uint64`` column and a
plain ``CachedRootList`` with the same content must be indistinguishable
through the list's surface: every read, every mutator of
``INSTRUMENTED_LIST_MUTATORS``, ``hash_tree_root`` against a cold
recompute, ``serialize``. What differs is what the list keeps: no boxed
row while it stays in the mode (int reads and writes, ``append``), one
``tolist`` when anything else makes it leave.
"""

import copy
import os
import pickle
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from ethereum_consensus_tpu.models import ops_vector
from ethereum_consensus_tpu.ssz import column_list
from ethereum_consensus_tpu.ssz import core as ssz_core
from ethereum_consensus_tpu.ssz.column_list import UNBOXED, ColumnList
from ethereum_consensus_tpu.ssz.core import (
    CachedRootList,
    Container,
    List,
    bulk_store,
    uint64,
)
from ethereum_consensus_tpu.telemetry import metrics

U64_MAX = (1 << 64) - 1
LT = List[uint64, 1 << 16]
N = 45  # eleven groups and a tail of one under small_groups


def _counters() -> tuple:
    return tuple(
        metrics.counter(f"ssz.column_list.{k}").value()
        for k in ("stores", "left", "boxed_rows")
    )


def _pair(n: int = N, rooted: bool = True, seed: int = 7):
    """(column-primary list, plain list) with the same content, the way a
    commit makes the first: a rooted plain list adopts a finished
    column."""
    rng = random.Random(seed)
    old = [rng.getrandbits(60) for _ in range(n)]
    new = [v + rng.randrange(3) for v in old]
    new[0], new[-1] = U64_MAX, 0  # the lane's two ends
    col = CachedRootList(old)
    if rooted:
        LT.hash_tree_root(col)  # memo and tracking armed, as a state's list
    arr = np.array(new, dtype=np.uint64)
    mask = arr != np.array(old, dtype=np.uint64)
    assert column_list.adopt(col, arr, mask, U64_MAX)
    assert col.__class__ is ColumnList
    return col, CachedRootList(new)


def _cold_root(values) -> bytes:
    return LT.hash_tree_root(CachedRootList(list(values)))


def _assert_same(col, plain) -> None:
    assert len(col) == len(plain)
    assert list(col) == list(plain)
    assert LT.hash_tree_root(col) == LT.hash_tree_root(plain) == _cold_root(plain)
    assert LT.serialize(col) == LT.serialize(plain)


# ---------------------------------------------------------------------------
# reads: equal results, nothing kept, the mode unchanged
# ---------------------------------------------------------------------------

_READS = {
    "len": len,
    "bool": bool,
    "getitem": lambda xs: [xs[i] for i in (0, 1, N - 1, -1, -N, True)],
    "getitem-type": lambda xs: {type(xs[i]) for i in range(N)},
    "slice": lambda xs: (xs[3:9], xs[::-2], xs[-4:], xs[40:400], xs[:]),
    "slice-type": lambda xs: type(xs[1:3]),
    "iter": lambda xs: [v for v in xs],
    "reversed": lambda xs: list(reversed(xs)),
    "contains": lambda xs: (xs[5] in xs, -1 in xs, U64_MAX in xs, 1.5 in xs),
    "index": lambda xs: (xs.index(xs[9]), xs.index(xs[9], 2, 30)),
    "count": lambda xs: (xs.count(xs[3]), xs.count(-5)),
    "list": list,
    "tuple": tuple,
    "sorted": sorted,
    "sum": sum,
    "max-min": lambda xs: (max(xs), min(xs)),
    "unpack": lambda xs: [*xs],
    "repr": repr,
    "add": lambda xs: (xs + [1, 2], [1, 2] + xs, xs + xs),
    "mul": lambda xs: (xs * 2, 2 * xs),
    "list.copy": lambda xs: xs.copy(),
    "copy.copy": lambda xs: list(copy.copy(xs)),
    "pickle": lambda xs: list(pickle.loads(pickle.dumps(xs))),
    "np.array": lambda xs: np.array(xs, dtype=np.uint64).tolist(),
    # (the plain list's ints, where numpy picks float64 for a list that
    # holds 2^64 - 1; the column's own dtype for the other)
    "np.asarray": lambda xs: [int(v) for v in np.asarray(xs, dtype=object)],
    "np.asarray-dtype": lambda xs: (
        np.asarray(xs).dtype.kind if xs.__class__ is ColumnList else "u"
    ),
    "np.fromiter": lambda xs: np.fromiter(xs, np.uint64, len(xs)).tolist(),
    "enumerate-zip": lambda xs: list(zip(range(5), xs)),
    "serialize": LT.serialize,
    "to_json": LT.to_json,
    "bytes-join": lambda xs: _raises(lambda: b"".join(xs)),
}


def _raises(fn):
    try:
        return fn()
    except Exception as exc:  # noqa: BLE001 — the type is the result
        return type(exc)


@pytest.mark.parametrize("read", sorted(_READS))
def test_read_surface(read, small_groups):
    col, plain = _pair()
    before = _counters()
    arr = col._col_cache[1]
    assert _READS[read](col) == _READS[read](plain)
    assert col.__class__ is ColumnList and col._col_cache[1] is arr
    assert _counters() == before
    _assert_same(col, plain)


@pytest.mark.parametrize(
    "other", ["plain", "list", "column", "shorter", "differs"]
)
def test_comparisons_both_ways(other, small_groups):
    col, plain = _pair()
    rhs = {
        "plain": CachedRootList(plain),
        "list": list(plain),
        "column": _pair()[0],
        "shorter": list(plain)[:-1],
        "differs": [plain[0] - 1] + list(plain)[1:],
    }[other]
    as_plain = list(rhs)
    for op in ("__eq__", "__ne__", "__lt__", "__le__", "__gt__", "__ge__"):
        import operator

        fn = getattr(operator, op)
        assert fn(col, rhs) == fn(list(plain), as_plain), op
        assert fn(rhs, col) == fn(as_plain, list(plain)), op
    assert col.__class__ is ColumnList


@pytest.mark.parametrize("index", [N, -N - 1, 1 << 70])
def test_index_out_of_range(index, small_groups):
    col, plain = _pair()
    before = _counters()
    with pytest.raises(IndexError):
        col[index]
    with pytest.raises(IndexError):
        col[index] = 5
    with pytest.raises(TypeError):
        col["3"]
    assert col.__class__ is ColumnList and _counters() == before
    _assert_same(col, plain)


# ---------------------------------------------------------------------------
# the raw storage cannot lie
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "after", ["adopt", "write", "append", "copy", "second-adopt"]
)
def test_raw_slots_never_hold_a_value(after, small_groups):
    col, plain = _pair()
    if after == "write":
        col[4] = 99
    elif after == "append":
        col.append(99)
    elif after == "copy":
        col = ssz_core._copy_value(LT, col)
    elif after == "second-adopt":
        arr = np.arange(len(col), dtype=np.uint64)
        assert column_list.adopt(col, arr, np.ones(len(col), bool), U64_MAX)
    assert list.__len__(col) == len(col)
    raw = list.__getitem__(col, slice(None))
    assert all(v is UNBOXED for v in raw)
    for misuse in (
        lambda: int(raw[0]),
        lambda: raw[0] + 1,
        lambda: 1 + raw[0],
        lambda: raw[0] == 3,
        lambda: raw[0] < 3,
        lambda: raw[0].__index__(),
        lambda: np.asarray(raw, dtype=np.uint64),
        lambda: sum(raw),
        lambda: list.__contains__(col, 3),
        lambda: list.index(col, 3),
    ):
        with pytest.raises(TypeError):
            misuse()


# ---------------------------------------------------------------------------
# mutators: equal results, equal roots, the expected mode afterwards
# ---------------------------------------------------------------------------


def _mutator_scripts(rng) -> dict:
    def iadd(xs):
        xs += [rng.getrandbits(60) for _ in range(3)]

    def imul(xs):
        xs *= 2

    def delitem(xs):
        del xs[rng.randrange(len(xs))]

    def delslice(xs):
        del xs[2:7]

    def setslice(xs):
        xs[1:3] = [rng.getrandbits(60), rng.getrandbits(60)]

    def setslice_resize(xs):
        xs[1:3] = [5]

    # name -> [(label, script, stays in the mode)]
    return {
        "__setitem__": [
            ("int", lambda xs: xs.__setitem__(rng.randrange(len(xs)), rng.getrandbits(60)), True),
            ("negative-index", lambda xs: xs.__setitem__(-2, 17), True),
            ("lane-top", lambda xs: xs.__setitem__(3, U64_MAX), True),
            ("zero", lambda xs: xs.__setitem__(N - 1, 0), True),
            ("slice", setslice, False),
            ("slice-resize", setslice_resize, False),
        ],
        "__delitem__": [("int", delitem, False), ("slice", delslice, False)],
        "__iadd__": [("list", iadd, False)],
        "__imul__": [("twice", imul, False)],
        "append": [
            ("int", lambda xs: xs.append(rng.getrandbits(60)), True),
            ("lane-top", lambda xs: xs.append(U64_MAX), True),
        ],
        "extend": [("gen", lambda xs: xs.extend(rng.getrandbits(60) for _ in range(5)), False)],
        "insert": [("mid", lambda xs: xs.insert(rng.randrange(len(xs) + 1), 11), False)],
        "pop": [
            ("end", lambda xs: xs.pop(), False),
            ("mid", lambda xs: xs.pop(rng.randrange(len(xs))), False),
        ],
        "remove": [("value", lambda xs: xs.remove(xs[rng.randrange(len(xs))]), False)],
        "clear": [("all", lambda xs: xs.clear(), False)],
        "sort": [("asc", lambda xs: xs.sort(), False)],
        "reverse": [("all", lambda xs: xs.reverse(), False)],
    }


_MUTATOR_CASES = [
    (name, label)
    for name, scripts in sorted(_mutator_scripts(random.Random(0)).items())
    for label, _fn, _stays in scripts
]


def test_mutator_scripts_cover_the_manifest():
    """Lockstep: a mutator entering the manifest without a script here
    fails, as in tests/test_ssz_incremental.py."""
    surface = ssz_core.instrumented_surface()
    assert {n for n, _ in _MUTATOR_CASES} == set(surface["list_mutators"])
    entry = surface["column_list"]
    assert entry["list_type"] == ColumnList.__name__
    assert entry["raw_list_calls"] == ssz_core.COLUMN_LIST_RAW_CALLS
    stays = {
        n for n, scripts in _mutator_scripts(random.Random(0)).items()
        if any(s for _l, _f, s in scripts)
    }
    assert stays == set(entry["stays"])
    # every mutator is the column list's own: none falls through to the
    # plain list's wrapper (which would store into sentinel slots)
    for name in surface["list_mutators"]:
        assert getattr(ColumnList, name) is not getattr(CachedRootList, name)


@pytest.mark.parametrize("geometry", ["tracked", "untracked"])
@pytest.mark.parametrize("name,label", _MUTATOR_CASES)
def test_mutator_surface(name, label, geometry, request):
    if geometry == "tracked":
        request.getfixturevalue("small_groups")
    seed = hash((name, label)) & 0xFFFF
    fn, stays = next(
        (f, s)
        for lb, f, s in _mutator_scripts(random.Random(seed))[name]
        if lb == label
    )
    fn_plain = next(
        f for lb, f, _s in _mutator_scripts(random.Random(seed))[name]
        if lb == label
    )
    col, plain = _pair()
    assert (col._dirty_groups is not None) == (geometry == "tracked")
    LT.hash_tree_root(col)
    LT.hash_tree_root(plain)
    stores, left, boxed = _counters()
    n = len(col)
    fn(col)
    fn_plain(plain)
    if stays:
        assert col.__class__ is ColumnList
        assert _counters() == (stores, left, boxed)
        assert col._col_dirty == set()
    else:
        assert col.__class__ is CachedRootList
        assert _counters() == (stores, left + 1, boxed + n)
        assert not any(v is UNBOXED for v in list.__getitem__(col, slice(None)))
    _assert_same(col, plain)
    # and once more through the other kind of write, from either mode
    if len(plain):
        col[0] = plain[0] = 12345
    col.append(6)
    plain.append(6)
    _assert_same(col, plain)


@pytest.mark.parametrize("how", ["setitem", "append"])
@pytest.mark.parametrize(
    "value", [1 << 64, -1, True, 1.0, np.uint64(5), "7", None],
    ids=["2^64", "-1", "True", "1.0", "np.uint64", "str", "None"],
)
def test_value_the_column_cannot_hold_leaves(value, how, small_groups):
    """Not an int, negative, 2^64: the list boxes itself and takes the
    value as the plain list does, structured errors included."""
    col, plain = _pair()
    stores, left, boxed = _counters()
    for xs in (col, plain):
        if how == "setitem":
            xs[7] = value
        else:
            xs.append(value)
    assert col.__class__ is CachedRootList
    assert _counters() == (stores, left + 1, boxed + N)
    assert list.__getitem__(col, slice(None)) == list(plain)
    assert _raises(lambda: LT.hash_tree_root(col)) == _raises(
        lambda: LT.hash_tree_root(plain)
    )
    assert _raises(lambda: LT.serialize(col)) == _raises(
        lambda: LT.serialize(plain)
    )


def test_a_lower_cap_is_a_value_the_column_cannot_hold(small_groups):
    col = CachedRootList([1, 2, 3])
    assert column_list.adopt(
        col, np.array([4, 5, 6], dtype=np.uint64), np.ones(3, bool), 0xFF
    )
    col[0] = 0xFF
    assert col.__class__ is ColumnList
    col[1] = 0x100
    assert col.__class__ is CachedRootList and list(col) == [0xFF, 0x100, 6]


@pytest.mark.parametrize("changed", ["certified", "uncertified", "ndarray"])
def test_bulk_store_aimed_at_the_list_leaves_first(changed, small_groups):
    col, plain = _pair()
    LT.hash_tree_root(col)
    LT.hash_tree_root(plain)
    stores, left, boxed = _counters()
    new = list(plain)
    for i in (2, 17, 33):
        new[i] += 1
    payload = np.array(new, dtype=np.uint64) if changed == "ndarray" else new
    idx = None if changed == "uncertified" else [2, 17, 33]
    bulk_store(col, payload, idx)
    bulk_store(plain, list(new), idx)
    assert col.__class__ is CachedRootList
    assert _counters() == (stores, left + 1, boxed + N)
    _assert_same(col, plain)


# ---------------------------------------------------------------------------
# entry, re-entry, refusals, marks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("how", ["mask", "indices"])
@pytest.mark.parametrize("n", [1, 3, 4, 5, 45, 64])
def test_store_marks_exactly_the_changed_groups(n, how, small_groups):
    gs = ssz_core._DIRTY_GROUP_SHIFT
    rng = random.Random(n)
    old = [rng.getrandbits(50) for _ in range(n)]
    lst = CachedRootList(old)
    big = List[uint64, 1 << 16]
    big.hash_tree_root(lst)
    tracked = lst._dirty_groups is not None
    new = list(old)
    moved = sorted({0, n - 1, n // 2})
    for i in moved:
        new[i] += 1
    arr = np.array(new, dtype=np.uint64)
    mask = arr != np.array(old, dtype=np.uint64)
    gen = lst._mut_gen
    before = _counters()
    assert column_list.adopt(
        lst, arr, mask if how == "mask" else np.flatnonzero(mask), U64_MAX
    )
    assert _counters() == (before[0] + 1, before[1], before[2])
    assert lst._mut_gen == gen + 1 and not lst._root_cache
    assert lst._uniform_kind == ("int",) and lst._col_dirty == set()
    assert lst._col_owned and lst._col_cache[1] is arr
    if tracked:
        assert lst._dirty_groups == {i >> gs for i in moved}
        assert lst._dirty_elems is None
    assert big.hash_tree_root(lst) == big.hash_tree_root(CachedRootList(new))
    assert lst._dirty_groups in (None, set())


class _Holder(Container):
    values: List[uint64, 1 << 16]
    tag: uint64


def test_store_and_writes_reach_the_container_parents(small_groups):
    """The nested-root scheme: a holder that cached its root over the list
    hears of the store, of a single write and of an append."""
    h = _Holder(values=list(range(N)), tag=3)
    for step in ("adopt", "write", "append"):
        root = _Holder.hash_tree_root(h)
        assert "_htr_cache" in h.__dict__
        if step == "adopt":
            arr = np.arange(N, dtype=np.uint64) + 1
            assert column_list.adopt(h.values, arr, np.ones(N, bool), U64_MAX)
        elif step == "write":
            h.values[5] = 77
        else:
            h.values.append(5)
        assert "_htr_cache" not in h.__dict__, step
        assert h.values.__class__ is ColumnList
        fresh = _Holder(values=list(h.values), tag=3)
        assert _Holder.hash_tree_root(h) == _Holder.hash_tree_root(fresh) != root
    assert _Holder.deserialize(_Holder.serialize(h)) == h


@pytest.mark.parametrize(
    "fault", ["dtype", "length", "ndim", "signed", "class", "not-array"]
)
def test_adopt_refuses_what_is_not_such_a_column(fault, small_groups):
    class Other(CachedRootList):
        __slots__ = ()

    lst = (Other if fault == "class" else CachedRootList)(range(N))
    arr = {
        "dtype": np.zeros(N, dtype=np.uint32),
        "length": np.zeros(N + 1, dtype=np.uint64),
        "ndim": np.zeros((N, 1), dtype=np.uint64),
        "signed": np.zeros(N, dtype=np.int64),
        "class": np.zeros(N, dtype=np.uint64),
        "not-array": [0] * N,
    }[fault]
    before = _counters()
    assert not column_list.adopt(lst, arr, np.ones(N, bool), U64_MAX)
    assert _counters() == before
    assert list(lst) == list(range(N)) and lst._col_cache is None
    assert lst.__class__ is not ColumnList


@pytest.mark.parametrize("width", ["uint8", "uint32"])
def test_adopt_list_column_keeps_bulk_store_for_other_lists(width, small_groups):
    """The participation lists and any narrower column are not part of the
    mode: the commit stores them as it always did."""
    lst = CachedRootList([0] * N)
    arr = np.arange(N, dtype=width)
    before = _counters()
    assert ops_vector.adopt_list_column(lst, arr, np.flatnonzero(arr), 0xFF) == N - 1
    assert _counters() == before and lst.__class__ is CachedRootList
    assert list.__getitem__(lst, slice(None)) == list(range(N))
    assert lst._col_cache[1] is arr and lst._col_dirty == set()


def test_a_no_change_commit_is_no_store(small_groups):
    """With finality the scores' commit finds nothing: a plain list stays
    plain, a column-primary one takes the fresh array and its ownership."""
    plain = CachedRootList([0] * N)
    col, _ = _pair()
    sibling = ssz_core._copy_value(LT, col)
    assert not col._col_owned
    before = _counters()
    for lst in (plain, col):
        arr = np.array(list(lst), dtype=np.uint64)
        gen = lst._mut_gen
        assert ops_vector.adopt_list_column(
            lst, arr, np.zeros(N, bool), U64_MAX
        ) == 0
        assert lst._mut_gen == gen and lst._col_cache[1] is arr
        assert lst._col_owned and lst._col_dirty == set()
    assert _counters() == before
    assert plain.__class__ is CachedRootList and col.__class__ is ColumnList
    assert list(sibling) == list(col)


# ---------------------------------------------------------------------------
# copies share; a write on either side is the writer's alone
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("writer", ["original", "copy"])
@pytest.mark.parametrize("write", ["setitem", "append", "leave"])
def test_copy_shares_until_written(writer, write, small_groups):
    col, plain = _pair()
    root = LT.hash_tree_root(col)
    before = _counters()
    dup = ssz_core._copy_value(LT, col)
    assert dup.__class__ is ColumnList and _counters() == before
    assert dup._col_cache[1] is col._col_cache[1]
    assert not dup._col_owned and not col._col_owned
    assert dup._pack_tree is col._pack_tree
    assert LT.hash_tree_root(dup) == root
    a, b = (col, dup) if writer == "original" else (dup, col)
    shared = b._col_cache[1]
    want = list(plain)
    if write == "setitem":
        a[3] = want[3] = 4242
    elif write == "append":
        a.append(4242)
        want.append(4242)
    else:
        a.reverse()
        want.reverse()
    assert list(a) == want and list(b) == list(plain)
    assert b._col_cache[1] is shared and shared.tolist() == list(plain)
    assert LT.hash_tree_root(b) == root == _cold_root(plain)
    assert LT.hash_tree_root(a) == _cold_root(want)
    # the reader writes next: it clones too, and the writer keeps its own
    b[0] = 1
    assert a[0] == want[0] and b[0] == 1
    assert LT.hash_tree_root(a) == _cold_root(want)


def test_state_copy_boxes_nothing_and_shares(small_groups):
    h = _Holder(values=list(range(N)), tag=1)
    arr = np.arange(N, dtype=np.uint64) * 3
    column_list.adopt(h.values, arr, arr != np.arange(N, dtype=np.uint64), U64_MAX)
    before = _counters()
    dup = h.copy()
    assert dup.values is not h.values and dup.values.__class__ is ColumnList
    assert dup.values._col_cache[1] is arr and _counters() == before
    dup.values[1] = 8
    assert h.values[1] == 3 and dup.values[1] == 8 and arr[1] == 3
    assert _Holder.hash_tree_root(h) != _Holder.hash_tree_root(dup)


def test_a_read_only_column_is_cloned_before_its_first_write(small_groups):
    arr = np.arange(N, dtype=np.uint64)
    arr.flags.writeable = False
    lst = CachedRootList([0] * N)
    assert column_list.adopt(lst, arr, np.ones(N, bool), U64_MAX)
    lst[2] = 9
    assert lst[2] == 9 and arr[2] == 2 and lst._col_owned


# ---------------------------------------------------------------------------
# append is amortised, and the column's consumers stay engaged
# ---------------------------------------------------------------------------


def test_append_grows_the_column_amortised(small_groups):
    col, plain = _pair()
    before = _counters()
    buffers = set()
    for k in range(600):
        col.append(k)
        plain.append(k)
        buffers.add(id(col._col_cache[3]))
        assert col._col_cache[1].shape[0] == len(col) == N + k + 1
    assert len(buffers) <= 8  # an eighth more rows a copy, not one a row
    assert col.__class__ is ColumnList and _counters() == before
    _assert_same(col, plain)


class _Bag:
    pass


def test_the_column_consumers_stay_engaged(small_groups):
    """list_column serves the array itself (no build, no refresh), the
    root packs off it, and the next adoption finds the list as it is."""
    col, plain = _pair()
    state = _Bag()
    state.balances = col
    cols = ops_vector.RegistryColumns(state)
    base = metrics.snapshot()
    view = cols.list_column(state, "balances")
    assert view is not None and not view.flags.writeable
    assert np.shares_memory(view, col._col_cache[1])
    col[3] = 31
    assert int(cols.list_column(state, "balances")[3]) == 31
    assert ssz_core._clean_wire_column(col, 8) is col._col_cache[1]
    d = metrics.delta(base)
    assert not d.get("ops_vector.columns.builds")
    assert not d.get("ops_vector.columns.refresh_rows")
    assert not d.get("ssz.column_list.left")
    # the pack tree's bytes are the column's once rooted (PR 31's reader)
    plain[3] = 31
    _assert_same(col, plain)
    raw = ssz_core._clean_pack_bytes(col, 8)
    assert raw is not None and bytes(raw) == col._col_cache[1].tobytes()


def test_a_column_asked_for_under_another_dtype_leaves(small_groups):
    col, plain = _pair()
    col[0] = plain[0] = 200  # fits a byte: only the dtype differs
    for i in range(1, N):
        col[i] = plain[i] = i
    before = _counters()
    got = ops_vector._sync_list_col(col, np.dtype(np.uint8), 0xFF)
    assert got is not None and got.dtype == np.uint8
    assert got.tolist() == list(plain)
    assert col.__class__ is CachedRootList
    assert _counters() == (before[0], before[1] + 1, before[2] + N)
    _assert_same(col, plain)


def test_plain_lists_keep_c_speed_reads():
    """No Python-level read method on CachedRootList itself: a list never
    adopted shares nothing with the mode."""
    for name in (
        "__getitem__", "__iter__", "__len__", "__contains__", "__eq__",
        "__reversed__", "index", "count", "__add__", "__mul__", "copy",
    ):
        assert getattr(CachedRootList, name) is getattr(list, name), name
    assert ColumnList.__slots__ == ()
    assert not hasattr(CachedRootList, "__array__")
