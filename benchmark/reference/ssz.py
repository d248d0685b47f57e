"""SSZ merkleization, written from the specification (simple-serialize.md)
with hashlib and numpy: no import of the program, no table it has made.

Only what a deneb ``BeaconState`` needs: basic values, byte vectors, fixed
containers, vectors and lists of them, bitvectors. Big levels are hashed
from one contiguous buffer, 64 bytes a time."""

from __future__ import annotations

import gc
from hashlib import sha256

import numpy as np

ZERO = b"\x00" * 32
ZERO_HASHES = [ZERO]
for _ in range(64):
    ZERO_HASHES.append(sha256(ZERO_HASHES[-1] * 2).digest())


def hash_pairs(level: bytes) -> bytes:
    """One level up: the hashes of consecutive 64-byte pairs."""
    view = memoryview(level)
    out = bytearray(len(level) // 2)
    block = 1 << 22  # 65,536 pairs at a time keeps the live objects few
    was_enabled = gc.isenabled()
    gc.disable()  # millions of short-lived digests beside a 2^20 state
    try:
        for base in range(0, len(level), block):
            stop = min(base + block, len(level))
            out[base // 2 : stop // 2] = b"".join(
                [sha256(view[i : i + 64]).digest() for i in range(base, stop, 64)]
            )
    finally:
        if was_enabled:
            gc.enable()
    return bytes(out)


def merkleize(chunks: bytes, limit: int | None = None) -> bytes:
    """The root of ``chunks`` (a whole number of 32-byte chunks) padded with
    zero chunks to ``limit`` leaves (default: the next power of two)."""
    count = len(chunks) // 32
    if limit is None:
        limit = max(count, 1)
    depth = (limit - 1).bit_length()
    if count == 0:
        return ZERO_HASHES[depth]
    level, height = chunks, 0
    while height < depth:
        if (len(level) // 32) % 2:
            level += ZERO_HASHES[height]
        level = hash_pairs(level)
        height += 1
    return level


def mix_in_length(root: bytes, length: int) -> bytes:
    return sha256(root + length.to_bytes(32, "little")).digest()


def uint(value: int, size: int = 8) -> bytes:
    """The root of a uintN: its little-endian bytes padded to a chunk."""
    return int(value).to_bytes(size, "little").ljust(32, b"\x00")


def pad_chunks(data: bytes) -> bytes:
    return data + b"\x00" * (-len(data) % 32)


def byte_vector(data: bytes) -> bytes:
    return merkleize(pad_chunks(bytes(data)))


def byte_list(data: bytes, limit_bytes: int) -> bytes:
    data = bytes(data)
    return mix_in_length(
        merkleize(pad_chunks(data), (limit_bytes + 31) // 32), len(data)
    )


def container(field_roots: list) -> bytes:
    return merkleize(b"".join(field_roots))


def packed_list(values: np.ndarray, limit_elements: int) -> bytes:
    """List[uintN, limit] from a numpy array of the element type."""
    data = np.ascontiguousarray(values).astype(
        values.dtype.newbyteorder("<"), copy=False
    ).tobytes()
    limit_chunks = (limit_elements * values.dtype.itemsize + 31) // 32
    return mix_in_length(merkleize(pad_chunks(data), limit_chunks), len(values))


def packed_vector(values: np.ndarray) -> bytes:
    data = np.ascontiguousarray(values).astype(
        values.dtype.newbyteorder("<"), copy=False
    ).tobytes()
    return merkleize(pad_chunks(data))


class RootsVector:
    """Vector[Bytes32, N] (N a power of two) kept as its whole tree, so that
    a slot's one new leaf costs one path of hashes and not the vector's."""

    def __init__(self, roots: list):
        self.levels = [[bytes(r) for r in roots]]
        level = b"".join(self.levels[0])
        while len(level) > 32:
            level = hash_pairs(level)
            self.levels.append([level[i : i + 32] for i in range(0, len(level), 32)])

    def __len__(self) -> int:
        return len(self.levels[0])

    def __getitem__(self, index: int) -> bytes:
        return self.levels[0][index]

    def __setitem__(self, index: int, leaf: bytes) -> None:
        self.levels[0][index] = bytes(leaf)
        for height in range(1, len(self.levels)):
            index //= 2
            below = self.levels[height - 1]
            self.levels[height][index] = sha256(
                below[2 * index] + below[2 * index + 1]
            ).digest()

    def root(self) -> bytes:
        return self.levels[-1][0]


def roots_list(roots: list, limit: int) -> bytes:
    return mix_in_length(
        merkleize(b"".join(bytes(r) for r in roots), limit), len(roots)
    )


def bitvector(bits: list) -> bytes:
    out = bytearray((len(bits) + 7) // 8)
    for i, bit in enumerate(bits):
        if bit:
            out[i // 8] |= 1 << (i % 8)
    return merkleize(pad_chunks(bytes(out)))
