"""The packed splice's dirty chunk-groups rooted in one native batch over
the host's cores (ssz/merkle.py merkleize_chunk_groups, native
ec_merkle_groups): every group root, list root and digest count is the
serial loop's (pack_bytes + merkleize_chunks(limit=4096) a group), at the
real 4,096-chunk geometry."""

from __future__ import annotations

import threading

import numpy as np
import pytest

from ethereum_consensus_tpu import native
from ethereum_consensus_tpu.ssz import hash as ssz_hash
from ethereum_consensus_tpu.ssz import merkle as ssz_merkle
from ethereum_consensus_tpu.ssz.core import (
    ByteVector,
    CachedRootList,
    List,
    bulk_store,
    uint8,
    uint64,
)
from ethereum_consensus_tpu.ssz.merkle import (
    merkleize_chunk_groups,
    merkleize_chunks,
    pack_bytes,
)
from ethereum_consensus_tpu.telemetry import metrics

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no C++ toolchain: no native batch"
)

DEPTH = 12
GBYTES = 32 << DEPTH
ssz_merkle.zero_hash(DEPTH)  # the table's own digests, before any count


def _serial(raw, cgs):
    """Today's loop, one group after another, with its digest count."""
    before = ssz_hash.digest_count()
    roots = [
        merkleize_chunks(
            pack_bytes(bytes(raw[cg * GBYTES : (cg + 1) * GBYTES])),
            limit=1 << DEPTH,
        )
        for cg in cgs
    ]
    return roots, ssz_hash.digest_count() - before


def _batch(raw, cgs):
    before = ssz_hash.digest_count()
    roots, _threads = merkleize_chunk_groups(raw, cgs, DEPTH)
    return roots, ssz_hash.digest_count() - before


def _raw(nbytes: int, seed: int) -> bytearray:
    rng = np.random.default_rng(seed)
    return bytearray(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())


def _dirty(kind: str, n_cgs: int) -> list:
    if kind == "all":
        return list(range(n_cgs))
    if kind == "scattered":
        return [0, n_cgs // 2, n_cgs - 1]
    return [g for g in range(n_cgs) if g % 3 != 1]  # runs with holes


@pytest.mark.parametrize("kind", ["all", "scattered", "noncontiguous"])
@pytest.mark.parametrize("width", [1, 8, 32])
def test_batch_roots_and_digests_are_the_serial_loops(width, kind):
    # rows that leave the last group partial at every width
    rows = (7 * GBYTES) // width - 13
    raw = _raw(rows * width, seed=width)
    n_cgs = -(-len(raw) // GBYTES)
    cgs = _dirty(kind, n_cgs)
    assert _batch(raw, cgs) == _serial(raw, cgs)


@pytest.mark.parametrize("rows", [1_905_000, 1_905_512])
def test_partial_last_group_of_the_growing_registry(rows):
    raw = _raw(rows * 8, seed=rows)
    n_cgs = -(-len(raw) // GBYTES)
    assert n_cgs == 117 and len(raw) % GBYTES
    cgs = list(range(n_cgs))
    assert _batch(raw, cgs) == _serial(raw, cgs)


def test_one_thread_is_all_threads():
    raw = _raw(16 * GBYTES + 40, seed=3)
    cgs = list(range(17))
    zh = ssz_merkle._zero_hashes_joined(DEPTH)
    one, used_one = native.merkle_groups_native(raw, cgs, DEPTH, zh, 1)
    many, used_many = native.merkle_groups_native(raw, cgs, DEPTH, zh, 64)
    assert used_one == 1 and used_many == 17  # capped at the groups
    assert one == many
    assert [one[i : i + 32] for i in range(0, len(one), 32)] == _serial(
        raw, cgs
    )[0]


_ELEMS = {
    "uint8": (uint8, 1, lambda i: (i * 7 + 1) & 0xFF),
    "uint64": (uint64, 8, lambda i: i * 2_654_435_761 % 2**64),
    "bytes32": (ByteVector[32], 32, lambda i: i.to_bytes(32, "little")),
}


def _list(name: str, rows: int):
    elem, _width, make = _ELEMS[name]
    limit = 1 << 24 if name != "bytes32" else 1 << 20
    lt = List[elem, limit]
    values = CachedRootList([make(i) for i in range(rows)])
    lt.hash_tree_root(values)
    assert values._dirty_groups == set(), "tracking must be armed"
    return lt, values, make


def _rows(name: str) -> int:
    # five chunk-groups and a partial sixth
    return (5 * GBYTES + 100 * 32) // _ELEMS[name][1]


def _rewrite_all(values, make):
    new = [make(i + 1) for i in range(len(values))]
    bulk_store(values, new)
    return new


@pytest.mark.parametrize("name", sorted(_ELEMS))
def test_all_dirty_list_root_and_digests_without_the_native_batch(
    name, monkeypatch
):
    lt, batched, make = _list(name, _rows(name))
    _lt, looped, _make = _list(name, _rows(name))
    new = _rewrite_all(batched, make)
    _rewrite_all(looped, make)
    before = ssz_hash.digest_count()
    root = lt.hash_tree_root(batched)
    batch_digests = ssz_hash.digest_count() - before

    threaded = metrics.counter("ssz.group_roots.threaded").value()
    inline = metrics.counter("ssz.group_roots.inline").value()
    monkeypatch.setattr(native, "available", lambda: False)
    before = ssz_hash.digest_count()
    assert lt.hash_tree_root(looped) == root
    assert ssz_hash.digest_count() - before == batch_digests
    # the fallback loop roots without the batch: neither counter moves
    assert metrics.counter("ssz.group_roots.threaded").value() == threaded
    assert metrics.counter("ssz.group_roots.inline").value() == inline
    monkeypatch.undo()
    assert lt.hash_tree_root(CachedRootList(new)) == root


def test_a_list_that_shrank_below_a_dirty_group():
    lt, values, make = _list("uint64", _rows("uint64"))
    shadow = [make(i) for i in range(len(values))]
    values[len(values) - 1] = shadow[-1] = 5  # the partial last group
    values[3] = shadow[3] = 6
    for _ in range(3 * 4096 + 200):  # below the dirty last group
        values.pop()
        shadow.pop()
    assert lt.hash_tree_root(values) == lt.hash_tree_root(
        CachedRootList(shadow)
    )


def test_two_python_threads_splice_two_lists_at_once():
    lt, a, make = _list("uint64", 8 * 4096 * 4)
    _lt, b, _make = _list("uint64", 8 * 4096 * 4)
    new_a = _rewrite_all(a, make)
    new_b = [v ^ 0xFFFF for v in new_a]
    bulk_store(b, new_b)
    roots = {}
    barrier = threading.Barrier(2)

    def root(key, values):
        barrier.wait()
        roots[key] = lt.hash_tree_root(values)

    threads = [
        threading.Thread(target=root, args=("a", a)),
        threading.Thread(target=root, args=("b", b)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert roots["a"] == lt.hash_tree_root(CachedRootList(new_a))
    assert roots["b"] == lt.hash_tree_root(CachedRootList(new_b))


def _counted_root(lt, values):
    threaded = metrics.counter("ssz.group_roots.threaded").value()
    inline = metrics.counter("ssz.group_roots.inline").value()
    root = lt.hash_tree_root(values)
    return (
        root,
        metrics.counter("ssz.group_roots.threaded").value() - threaded,
        metrics.counter("ssz.group_roots.inline").value() - inline,
    )


def test_one_dirty_group_goes_inline():
    lt, values, _make = _list("uint64", _rows("uint64"))
    values[12_345] = 7
    _root, threaded, inline = _counted_root(lt, values)
    assert (threaded, inline) == (0, 1)


@pytest.mark.skipif(
    ssz_merkle._usable_cores() < 2, reason="one usable core: all inline"
)
def test_all_dirty_groups_go_threaded():
    lt, values, make = _list("uint64", _rows("uint64"))
    new = _rewrite_all(values, make)
    root, threaded, inline = _counted_root(lt, values)
    assert (threaded, inline) == (6, 0)
    assert root == lt.hash_tree_root(CachedRootList(new))


def test_a_group_past_the_end_is_the_zero_subtree():
    raw = _raw(GBYTES + 96, seed=5)
    roots, _threads = merkleize_chunk_groups(raw, [0, 1, 2, 5], DEPTH)
    assert roots[:2] == _serial(raw, [0, 1])[0]
    assert roots[2:] == [ssz_merkle.zero_hash(DEPTH)] * 2
