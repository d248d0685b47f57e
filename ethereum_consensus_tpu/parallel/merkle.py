"""Sharded SSZ merkleization over a device mesh.

The merkle tree over N leaf chunks is split by leaf range: each device
reduces its contiguous 2^k-leaf subtree locally (pure VPU work, zero
communication), then one ``all_gather`` of the 32-byte subtree roots crosses
ICI and every device finishes the top log2(D) levels redundantly (cheaper
than a log-depth halving exchange for D ≤ 256: the top tree is D hashes).

This is the ring/all-reduce-shaped pattern SURVEY.md §5 calls for ("blockwise
kernels over leaf chunks with tree reduction across chips"), replacing the
reference's single-core `ssz_rs` merkleizer. Bit-identical to
ssz/merkle.py's host merkleizer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..ops.merkle import reduce_levels, zero_hash_words
from ..ssz.merkle import BYTES_PER_CHUNK, merkleize_chunks, next_pow_of_two, zero_hash
from ..telemetry import device as _obs
from .mesh import SHARD_AXIS

__all__ = ["sharded_merkle_root_words", "sharded_merkleize_chunks"]


@functools.partial(
    jax.jit, static_argnames=("depth", "mesh", "axis_name"), static_argnums=()
)
def sharded_merkle_root_words(
    nodes: jax.Array,
    zero_words: jax.Array,
    depth: int,
    mesh: Mesh,
    axis_name: str = SHARD_AXIS,
) -> jax.Array:
    """Root of a depth-``depth`` tree over ``nodes`` (8, N), N sharded.

    N must be a power of two divisible by the mesh axis size. Returns (8,)
    root words, replicated.
    """
    n = nodes.shape[1]
    n_dev = mesh.shape[axis_name]
    if n % n_dev != 0:
        raise ValueError(f"leaf count {n} not divisible by mesh size {n_dev}")
    local_n = n // n_dev
    if local_n == 0 or local_n & (local_n - 1):
        raise ValueError(f"local leaf count {local_n} must be a power of two")
    local_depth = (local_n - 1).bit_length()

    def body(local_nodes, zw):
        sub = reduce_levels(local_nodes, zw, local_depth)  # (8,)
        roots = jax.lax.all_gather(sub, axis_name)  # (n_dev, 8)
        return reduce_levels(roots.T, zw, depth, start_level=local_depth)

    # check_vma=False: see parallel/step.py — the SHA-256 fori_loop carry
    # mixes unvarying literals with varying lanes.
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, axis_name), P(None, None)),
        out_specs=P(None),
        check_vma=False,
    )(nodes, zero_words)


sharded_merkle_root_words = _obs.observe_jit(
    sharded_merkle_root_words, "parallel.merkle.sharded_merkle_root_words"
)


def sharded_merkleize_chunks(
    chunks: bytes, mesh: Mesh, limit: int | None = None, axis_name: str = SHARD_AXIS
) -> bytes:
    """Mesh-sharded equivalent of ssz.merkle.merkleize_chunks (bit-identical).

    Pads the populated leaves up to a power-of-two multiple of the mesh size
    with zero chunks; the virtual tree above (up to ``limit``) chains
    zero-subtree hashes exactly like the host merkleizer.
    """
    if len(chunks) % BYTES_PER_CHUNK != 0:
        raise ValueError("chunks must be a multiple of 32 bytes")
    count = len(chunks) // BYTES_PER_CHUNK
    if limit is None:
        width = next_pow_of_two(count)
    else:
        if count > limit:
            raise ValueError(f"chunk count {count} exceeds limit {limit}")
        width = next_pow_of_two(limit)
    depth = (width - 1).bit_length()
    if count == 0:
        return zero_hash(depth)

    n_dev = mesh.shape[axis_name]
    # shardable only when every device owns a full, aligned 2^k-leaf subtree
    # inside the virtual tree: mesh size a power of two and ≤ width. Anything
    # else (tiny trees, odd meshes) goes to the host merkleizer, which
    # handles every input.
    if n_dev & (n_dev - 1) or n_dev > width:
        return merkleize_chunks(chunks, limit)
    local = max(1, next_pow_of_two(count) // n_dev)
    padded = local * n_dev  # == max(next_pow_of_two(count), n_dev) ≤ width
    data = chunks + b"\x00" * ((padded - count) * BYTES_PER_CHUNK)
    words = np.ascontiguousarray(
        np.frombuffer(data, dtype=">u4").astype(np.uint32).reshape(padded, 8).T
    )
    # each device receives its own leaf range straight from the host
    (words_d,) = _obs.h2d_put(
        "parallel.merkle.sharded_merkleize",
        (words,),
        NamedSharding(mesh, P(None, axis_name)),
    )
    (zero_d,) = _obs.h2d_put(
        "parallel.merkle.sharded_merkleize",
        (zero_hash_words(),),
        NamedSharding(mesh, P()),
    )
    root = sharded_merkle_root_words(
        words_d,
        zero_d,
        depth=depth,
        mesh=mesh,
        axis_name=axis_name,
    )
    return _obs.d2h(
        "parallel.merkle.sharded_merkleize", root
    ).astype(">u4").tobytes()
