"""SSZ binary merkleization: merkleize, mix_in_length, zero-subtree cache,
generalized indices and single-branch Merkle proofs.

Reference parity: `ssz_rs`'s `hash_tree_root` / `prove` /
`is_valid_merkle_branch_for_generalized_index` machinery (see SURVEY.md L0,
ethereum-consensus/src/ssz/mod.rs:1-8 and
spec-tests/runners/light_client.rs:10-13).
"""

from __future__ import annotations

from ..native import usable_cores as _usable_cores
from ..telemetry import metrics as _metrics
from . import hash as _hash_mod
from .hash import hash_bytes, hash_level, hash_pair

__all__ = [
    "BYTES_PER_CHUNK",
    "ZERO_CHUNK",
    "zero_hash",
    "merkleize",
    "merkleize_chunks",
    "merkleize_chunk_groups",
    "mix_in_length",
    "mix_in_selector",
    "pack_bytes",
    "next_pow_of_two",
    "get_generalized_index_length",
    "get_generalized_index_bit",
    "concat_generalized_indices",
    "compute_merkle_proof",
    "is_valid_merkle_branch",
    "is_valid_merkle_branch_for_generalized_index",
    "IncrementalPaddedTree",
]

BYTES_PER_CHUNK = 32
ZERO_CHUNK = b"\x00" * BYTES_PER_CHUNK

# -- mesh merkleization seam --------------------------------------------------
#
# Installed by parallel/runtime.py when an ECT_MESH mesh provisions: large
# flat rebuilds (cold column materializations, whole-list roots) divide
# their leaf ranges over the device mesh (parallel/merkle.py). This module
# stays jax-free: the hook is PUSHED in (the register_device_hasher idiom,
# ssz/hash.py) and a None return — any device trouble, any shape the mesh
# cannot own — falls through to the host merkleizer below, which remains
# the differential oracle for every mesh root.

_MESH_MERKLEIZER = None
_MESH_MIN_CHUNKS: "int | None" = None


def register_mesh_merkleizer(fn, min_chunks: "int | None") -> None:
    """Install (or, with ``fn=None``, clear) the mesh merkleization hook:
    ``fn(chunks, limit) -> root | None`` for flat trees of at least
    ``min_chunks`` populated chunks."""
    global _MESH_MERKLEIZER, _MESH_MIN_CHUNKS
    _MESH_MERKLEIZER = fn
    _MESH_MIN_CHUNKS = min_chunks

# zero_hash(i) = root of a fully-zero subtree of depth i.
_ZERO_HASHES: list[bytes] = [ZERO_CHUNK]


def zero_hash(depth: int) -> bytes:
    while len(_ZERO_HASHES) <= depth:
        h = _ZERO_HASHES[-1]
        _ZERO_HASHES.append(hash_pair(h, h))
    return _ZERO_HASHES[depth]


def next_pow_of_two(value: int) -> int:
    if value <= 1:
        return 1
    return 1 << (value - 1).bit_length()


def pack_bytes(data: bytes) -> bytes:
    """Right-pad serialized basic values to a whole number of chunks."""
    rem = len(data) % BYTES_PER_CHUNK
    if rem:
        data = data + b"\x00" * (BYTES_PER_CHUNK - rem)
    return data


def merkleize_chunks(
    chunks: bytes, limit: int | None = None, level_offset: int = 0
) -> bytes:
    """Merkleize packed ``chunks`` (concatenated 32-byte chunks) into a root.

    ``limit`` is the chunk-count bound (virtual tree width); ``None`` means
    the tree width is the padded actual chunk count. Sparse padding uses the
    zero-subtree cache, so a List[..., 2**40] bound costs only ~40 extra
    hashes above the populated subtree.

    ``level_offset`` declares that each input "chunk" is actually the root
    of a full zero-padded subtree of that height, so sparse padding must
    use ``zero_hash(level_offset + i)`` per level — the contract the
    two-level tree memo needs to merkleize subtree mids (padding with leaf
    zero chunks there would change every sparse root).
    """
    if len(chunks) % BYTES_PER_CHUNK != 0:
        raise ValueError(
            f"chunks byte length {len(chunks)} is not a multiple of {BYTES_PER_CHUNK}; "
            "pack inputs with pack_bytes() first"
        )
    count = len(chunks) // BYTES_PER_CHUNK
    if limit is None:
        width = next_pow_of_two(count)
    else:
        if count > limit:
            raise ValueError(f"chunk count {count} exceeds limit {limit}")
        width = next_pow_of_two(limit)
    depth = (width - 1).bit_length()

    if count == 0:
        return zero_hash(depth + level_offset)

    # mesh-sharded rebuilds (parallel/runtime.py hook): big flat trees
    # split by leaf range over the device mesh. Bit-identical by
    # construction; a None return (device trouble, un-ownable shape)
    # falls through to the host path. Guarded to level_offset 0 — the
    # sharded reducer pads with the standard zero table.
    if (
        _MESH_MERKLEIZER is not None
        and level_offset == 0
        and count >= _MESH_MIN_CHUNKS
    ):
        root = _MESH_MERKLEIZER(chunks, limit)
        if root is not None:
            # exact level-sum work accounting, as _native_tree_root does
            _hash_mod.add_digests(_level_sum(count, depth))
            return root

    # medium-to-large flat trees: one native call walks every level
    # (the per-level Python loop pays a join + two ctypes copies per
    # level — ~3x the hash cost at randao_mixes size). Trees big enough
    # that a level would route to the DEVICE hasher keep the loop.
    # (The native walk pads with the standard zero table, so it only
    # applies at level_offset 0.)
    if level_offset == 0 and 64 <= count < 2 * _hash_mod.DEVICE_MIN_NODES:
        root = _native_tree_root(chunks, depth)
        if root is not None:
            return root

    nodes = chunks
    for level in range(depth):
        n = len(nodes) // BYTES_PER_CHUNK
        if n % 2 == 1:
            nodes = nodes + zero_hash(level + level_offset)
        nodes = hash_level(nodes)
    return nodes


_ZH_JOINED: dict = {}


def _native_tree_root(chunks: bytes, depth: int) -> "bytes | None":
    """Whole-tree reduction in one native call (ec_merkle_root), or None
    when the native backend is unavailable."""
    try:
        from .. import native
    except Exception:  # noqa: BLE001 — no toolchain: python loop
        return None
    if not native.available():
        return None
    _hash_mod.add_digests(_level_sum(len(chunks) // BYTES_PER_CHUNK, depth))
    return native.merkle_root_native(chunks, depth, _zero_hashes_joined(depth))


def _zero_hashes_joined(depth: int) -> bytes:
    zh = _ZH_JOINED.get(depth)
    if zh is None:
        zh = b"".join(zero_hash(level) for level in range(depth + 1))
        _ZH_JOINED[depth] = zh
    return zh


def _level_sum(count: int, depth: int) -> int:
    """Compressions of a depth-``depth`` tree over ``count`` leaves whose
    zero-pad siblings come from the table: ceil(n/2) a level."""
    total = 0
    for _ in range(depth):
        count = (count + 1) // 2
        total += count
    return total


# groups of a merkleize_chunk_groups batch, by whether it ran on more
# than one host thread (ssz/core.py _splice_dirty_groups is the caller)
_GROUPS_THREADED = _metrics.counter("ssz.group_roots.threaded")
_GROUPS_INLINE = _metrics.counter("ssz.group_roots.inline")


def merkleize_chunk_groups(
    raw, group_ids: "list[int]", depth: int
) -> "tuple[list[bytes], int] | None":
    """Roots of the chunk-groups ``group_ids`` of ``raw`` (2**depth chunks
    each, zero past the end of ``raw``), each what
    ``merkleize_chunks(pack_bytes(group bytes), limit=2**depth)`` gives,
    in one native call over min(groups, usable cores) host threads; with
    the threads that ran. None when the native backend is unavailable or
    the mesh hook could take a group: the caller roots them one by one."""
    if _MESH_MERKLEIZER is not None and _MESH_MIN_CHUNKS <= 1 << depth:
        return None
    try:
        from .. import native
    except Exception:  # noqa: BLE001 — no toolchain: python loop
        return None
    if not native.available():
        return None
    got = native.merkle_groups_native(
        raw,
        group_ids,
        depth,
        _zero_hashes_joined(depth),
        min(len(group_ids), _usable_cores()),
    )
    if got is None:
        return None
    out, threads = got
    # the level-sum of each group's populated chunks, as the loop counts
    gchunks = 1 << depth
    chunks = (len(raw) + BYTES_PER_CHUNK - 1) // BYTES_PER_CHUNK
    _hash_mod.add_digests(
        sum(
            _level_sum(min(max(chunks - g * gchunks, 0), gchunks), depth)
            for g in group_ids
        )
    )
    (_GROUPS_THREADED if threads > 1 else _GROUPS_INLINE).inc(len(group_ids))
    return [out[i : i + 32] for i in range(0, len(out), 32)], threads


def merkleize(chunks: list[bytes], limit: int | None = None) -> bytes:
    return merkleize_chunks(b"".join(chunks), limit)


def mix_in_length(root: bytes, length: int) -> bytes:
    return hash_pair(root, length.to_bytes(32, "little"))


def mix_in_selector(root: bytes, selector: int) -> bytes:
    return hash_pair(root, selector.to_bytes(32, "little"))


# -- generalized indices -----------------------------------------------------


def get_generalized_index_length(index: int) -> int:
    """Depth of a generalized index (number of branch nodes in its proof)."""
    return index.bit_length() - 1


def get_generalized_index_bit(index: int, position: int) -> bool:
    return (index >> position) & 1 == 1


def _floor_pow_of_two(value: int) -> int:
    return 1 << (value.bit_length() - 1)


def concat_generalized_indices(*indices: int) -> int:
    out = 1
    for index in indices:
        fp = _floor_pow_of_two(index)
        out = out * fp + (index - fp)
    return out


def is_valid_merkle_branch(
    leaf: bytes, branch: list[bytes], depth: int, index: int, root: bytes
) -> bool:
    """Spec `is_valid_merkle_branch` (phase0): verify a depth/index proof."""
    value = leaf
    for i in range(depth):
        if (index >> i) & 1:
            value = hash_pair(branch[i], value)
        else:
            value = hash_pair(value, branch[i])
    return value == root


def is_valid_merkle_branch_for_generalized_index(
    leaf: bytes, branch: list[bytes], generalized_index: int, root: bytes
) -> bool:
    depth = get_generalized_index_length(generalized_index)
    index = generalized_index - (1 << depth)
    if len(branch) != depth:
        return False
    return is_valid_merkle_branch(leaf, branch, depth, index, root)


# -- proof construction ------------------------------------------------------


class Tree:
    """A fully materialized binary merkle tree over padded chunks, used for
    proof generation (``compute_merkle_proof``). Nodes are stored per level,
    level 0 = leaves."""

    def __init__(self, chunks: list[bytes], limit: int | None = None):
        count = len(chunks)
        width = next_pow_of_two(count if limit is None else limit)
        self.depth = (width - 1).bit_length()
        # Only materialize the populated region; zero-subtree roots fill the
        # rest. Each level hashes as ONE hash_level call so proof
        # construction rides the native/device backends instead of a
        # per-pair Python loop.
        level = list(chunks)
        self.levels: list[list[bytes]] = [level]
        for d in range(self.depth):
            if len(level) % 2 == 1:
                level = level + [zero_hash(d)]
            joined = hash_level(b"".join(level))
            nxt = [joined[i : i + 32] for i in range(0, len(joined), 32)]
            self.levels.append(nxt)
            level = nxt

    @property
    def root(self) -> bytes:
        if not self.levels[-1]:
            return zero_hash(self.depth)
        return self.levels[-1][0]

    def node(self, depth_from_leaves: int, index: int) -> bytes:
        level = self.levels[depth_from_leaves]
        if index < len(level):
            return level[index]
        return zero_hash(depth_from_leaves)

    def proof(self, leaf_index: int) -> list[bytes]:
        """Sibling branch for ``leaf_index``, leaf-level first."""
        branch = []
        index = leaf_index
        for d in range(self.depth):
            branch.append(self.node(d, index ^ 1))
            index >>= 1
        return branch


def compute_merkle_proof(chunks: list[bytes], leaf_index: int, limit: int | None = None) -> list[bytes]:
    return Tree(chunks, limit).proof(leaf_index)


# -- incremental padded tree (the dirty-group memo substrate) ----------------


class IncrementalPaddedTree:
    """Stored-levels binary merkle tree over a dynamic array of nodes, each
    node the root of a depth-``level_offset`` subtree, zero-padded to a
    virtual width of ``limit`` nodes.

    The substrate of the incremental hash_tree_root scheme (ssz/core.py).
    A packed collection keeps 4096-chunk group roots at level 0
    (``level_offset`` 12); a list of scalar-leaf containers keeps the
    element roots themselves (``level_offset`` 0), so one written row
    costs its path and nothing beside it. ``set_node``/``set_nodes``
    mark, ``root()`` recomputes only marked paths, one ``hash_level``
    call a level. Levels store the populated region only; sparse padding
    uses the zero-subtree table, so a List[..., 2**40] bound adds ~28
    cheap path hashes, never width.
    """

    __slots__ = ("depth", "level_offset", "levels", "_dirty", "_root")

    def __init__(self, nodes: bytes, limit: int, level_offset: int = 0):
        width = next_pow_of_two(limit)
        self.depth = (width - 1).bit_length()
        self.level_offset = level_offset
        self.levels: list[bytearray] = [bytearray(nodes)]
        # level-0 marks since the last root(): ints and ranges, in any
        # order and with repeats. None => full (re)build pending
        self._dirty: "list | None" = None
        self._root: bytes | None = None

    def clone(self) -> "IncrementalPaddedTree":
        new = IncrementalPaddedTree.__new__(IncrementalPaddedTree)
        new.depth = self.depth
        new.level_offset = self.level_offset
        new.levels = [bytearray(level) for level in self.levels]
        new._dirty = list(self._dirty) if self._dirty is not None else None
        new._root = self._root
        return new

    def node_count(self) -> int:
        return len(self.levels[0]) // 32

    def set_node(self, index: int, node: bytes) -> None:
        """Replace (or append at ``node_count()``) one level-0 node."""
        level0 = self.levels[0]
        n = len(level0) // 32
        if index == n:
            level0 += node
        elif index < n:
            level0[32 * index : 32 * (index + 1)] = node
        else:
            raise IndexError(f"node {index} beyond populated width {n}")
        if self._dirty is not None:
            self._dirty.append(index)

    def set_nodes(self, start: int, nodes: bytes) -> None:
        """Replace a run of level-0 nodes from ``start`` on; the run may
        reach past ``node_count()`` (and start at it), never leave a gap."""
        level0 = self.levels[0]
        if 32 * start > len(level0):
            raise IndexError(
                f"node {start} beyond populated width {len(level0) // 32}"
            )
        level0[32 * start : 32 * start + len(nodes)] = nodes
        if self._dirty is not None:
            self._dirty.append(range(start, start + len(nodes) // 32))

    def truncate(self, count: int) -> None:
        """Drop level-0 nodes beyond ``count``: every level is cut to the
        nodes that still cover something, and the last survivor's path is
        marked (its siblings turned into padding)."""
        if len(self.levels[0]) // 32 <= count:
            return
        if self._dirty is None or count == 0:
            del self.levels[0][32 * count :]
            self._dirty = None
            return
        keep = count
        for level in self.levels:
            del level[32 * keep :]
            keep = (keep + 1) // 2
        marks = []
        for m in self._dirty:
            if m.__class__ is range:
                m = range(m.start, min(m.stop, count))
                if not m:
                    continue
            elif m >= count:
                continue
            marks.append(m)
        marks.append(count - 1)
        self._dirty = marks

    def root(self) -> bytes:
        if self._dirty is None:
            self._rebuild()
        elif self._dirty:
            self._update_paths()
        self._dirty = []
        return self._root  # type: ignore[return-value]

    def _rebuild(self) -> None:
        self.levels = self.levels[:1]
        cur = self.levels[0]
        for d in range(self.depth):
            data = bytes(cur)
            if (len(data) // 32) % 2 == 1:
                data += zero_hash(self.level_offset + d)
            cur = bytearray(hash_level(data)) if data else bytearray()
            self.levels.append(cur)
        self._root = (
            bytes(cur[:32]) if cur else zero_hash(self.level_offset + self.depth)
        )

    def _update_paths(self) -> None:
        """Re-hash the marked nodes' paths a level at a time: each level's
        dirty parents are gathered into one buffer and hashed by one
        ``hash_level`` call, every sibling read from the stored levels.
        Where a level has fewer dirty parents than the native hasher
        takes (the upper levels always; a block's few written rows all the
        way) they are hashed pair by pair, without the gather."""
        import numpy as np

        marks = self._dirty
        few = _hash_mod.NATIVE_MIN_NODES
        idx = None
        runs = [m for m in marks if m.__class__ is range]
        if runs or len(marks) >= few:
            parts = [np.arange(r.start, r.stop, dtype=np.int64) for r in runs]
            if len(runs) < len(marks):
                parts.append(
                    np.array(
                        [m for m in marks if m.__class__ is not range],
                        dtype=np.int64,
                    )
                )
            idx = parts[0] if len(parts) == 1 else np.concatenate(parts)
        below = marks  # the level's dirty nodes, while idx is None
        for d in range(self.depth):
            pad = zero_hash(self.level_offset + d)
            cur = self.levels[d]
            nxt = self.levels[d + 1]
            if idx is not None:
                idx = np.unique(idx >> 1)
                if idx.shape[0] >= few:
                    _scatter_nodes(nxt, idx, _hash_parents(cur, idx, pad))
                    continue
                parents, idx = idx.tolist(), None
            else:
                parents = sorted({i >> 1 for i in below})
            n = len(cur) // 32
            for j in parents:
                left = bytes(cur[64 * j : 64 * j + 32])
                if 2 * j + 1 < n:
                    right = bytes(cur[64 * j + 32 : 64 * j + 64])
                else:
                    right = pad
                nxt[32 * j : 32 * j + 32] = hash_pair(left, right)
            below = parents
        self._root = bytes(self.levels[-1][:32])


def _hash_parents(level: bytearray, parents, pad: bytes) -> bytes:
    """The digests of ``level``'s node pairs ``(2j, 2j + 1)`` for the
    sorted parent indices ``parents``, in one ``hash_level`` call; a right
    sibling past the populated width (the last parent's alone can be) is
    ``pad``."""
    import numpy as np

    n = len(level) // 32
    nodes = np.frombuffer(level, dtype=np.uint8).reshape(n, 32)
    pairs = np.empty((parents.shape[0], 2, 32), dtype=np.uint8)
    left = parents << 1
    pairs[:, 0] = nodes[left]
    if int(left[-1]) + 1 < n:
        pairs[:, 1] = nodes[left + 1]
    else:
        pairs[:-1, 1] = nodes[left[:-1] + 1]
        pairs[-1, 1] = np.frombuffer(pad, dtype=np.uint8)
    return hash_level(pairs.tobytes())


def _scatter_nodes(level: bytearray, indices, nodes: bytes) -> None:
    """Store ``nodes`` at the sorted ``indices`` of ``level``, growing it
    to hold the last (a grown level's new nodes are all among them)."""
    import numpy as np

    need = 32 * (int(indices[-1]) + 1)
    if need > len(level):
        level.extend(bytes(need - len(level)))
    view = np.frombuffer(level, dtype=np.uint8).reshape(-1, 32)
    view[indices] = np.frombuffer(nodes, dtype=np.uint8).reshape(-1, 32)
