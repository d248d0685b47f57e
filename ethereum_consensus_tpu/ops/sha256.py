"""Batched SHA-256 on device (JAX/XLA + Pallas TPU kernel).

The SSZ merkleization hot path (reference: `ssz_rs::hash_tree_root`, the #1
hot path per SURVEY.md §3.1) is millions of *independent* SHA-256 hashes of
exactly 64 bytes (two 32-byte child nodes). A 64-byte message compresses in
exactly two rounds: one over the message block, one over the constant padding
block (0x80…, bit length 512). That makes the workload a pure data-parallel
uint32 VPU problem — no MXU, no dynamic shapes.

Layout: messages are held as uint32 words with shape ``(16, N)`` (words on
the sublane axis, hash lanes on the 128-wide lane axis), outputs ``(8, N)``.
Words use SHA-256's big-endian convention; conversion from byte strings
happens host-side via numpy ``>u4`` views.

The 64 rounds run as a ``lax.fori_loop`` with a rolling 16-entry message
schedule window (W[t+16] = W[t] + σ0(W[t+1]) + W[t+9] + σ1(W[t+14])) —
constant-size graph, so tracing/compilation stays cheap at every batch size
while the VPU still sees full-width vector ops per round.

Three execution paths, all bit-identical:
  - ``sha256_64b_xla``: pure jax.numpy (reference; what a CPU backend runs)
  - ``sha256_64b_pallas``: Pallas TPU kernel (tiled over lanes; what a TPU
    runs, at every width)
  - host hashlib (see ssz/hash.py)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import device as _obs
from ..telemetry import metrics as _metrics

__all__ = [
    "sha256_64b_xla",
    "sha256_64b_pallas",
    "sha256_64b",
    "hash_level_bytes",
    "install_device_hasher",
    "K",
    "H0",
]

# SHA-256 round constants (FIPS 180-4).
K = np.array(
    [
        0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5,
        0x3956C25B, 0x59F111F1, 0x923F82A4, 0xAB1C5ED5,
        0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
        0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174,
        0xE49B69C1, 0xEFBE4786, 0x0FC19DC6, 0x240CA1CC,
        0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
        0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7,
        0xC6E00BF3, 0xD5A79147, 0x06CA6351, 0x14292967,
        0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
        0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85,
        0xA2BFE8A1, 0xA81A664B, 0xC24B8B70, 0xC76C51A3,
        0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
        0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5,
        0x391C0CB3, 0x4ED8AA4A, 0x5B9CCA4F, 0x682E6FF3,
        0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
        0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
    ],
    dtype=np.uint32,
)

# Initial hash state.
H0 = np.array(
    [
        0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
        0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
    ],
    dtype=np.uint32,
)


def _rotr(x, n: int):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _compress(state, window, k_at):
    """One SHA-256 compression.

    ``state`` (8, N) uint32 working state; ``window`` (16, N) message block;
    ``k_at(t)`` returns the round-t constant as a scalar (an accessor so the
    Pallas path can do SMEM scalar loads while the XLA path indexes an
    array). Returns updated (8, N) state.
    """

    def round_body(t, carry):
        window, s = carry
        a, b, c, d, e, f, g, h = (s[i] for i in range(8))
        wt = jax.lax.dynamic_index_in_dim(window, t % 16, axis=0, keepdims=False)

        big_s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        kt = k_at(t)
        t1 = h + big_s1 + ch + kt + wt
        big_s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = big_s0 + maj

        # rolling schedule: this slot next holds W[t+16]
        w1 = jax.lax.dynamic_index_in_dim(window, (t + 1) % 16, axis=0, keepdims=False)
        w9 = jax.lax.dynamic_index_in_dim(window, (t + 9) % 16, axis=0, keepdims=False)
        w14 = jax.lax.dynamic_index_in_dim(window, (t + 14) % 16, axis=0, keepdims=False)
        s0 = _rotr(w1, 7) ^ _rotr(w1, 18) ^ (w1 >> np.uint32(3))
        s1 = _rotr(w14, 17) ^ _rotr(w14, 19) ^ (w14 >> np.uint32(10))
        w_next = wt + s0 + w9 + s1
        window = jax.lax.dynamic_update_index_in_dim(window, w_next, t % 16, axis=0)

        new_s = jnp.stack([t1 + t2, a, b, c, d + t1, e, f, g])
        return window, new_s

    _, out = jax.lax.fori_loop(0, 64, round_body, (window, state))
    return state + out


def _compress_unrolled(state, window):
    """One SHA-256 compression, fully unrolled (static indices only).

    Used inside the Pallas kernel: mosaic cannot lower dynamic_slice on
    loop-carried values, and the kernel has a single fixed tile shape so the
    larger graph compiles exactly once. Bit-identical to ``_compress``.
    """
    w = [window[i] for i in range(16)]
    a, b, c, d, e, f, g, h = (state[i] for i in range(8))
    for t in range(64):
        if t < 16:
            wt = w[t]
        else:
            w15 = w[(t - 15) % 16]
            w2 = w[(t - 2) % 16]
            s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> np.uint32(3))
            s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> np.uint32(10))
            wt = w[t % 16] + s0 + w[(t - 7) % 16] + s1
            w[t % 16] = wt
        big_s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + big_s1 + ch + np.uint32(int(K[t])) + wt
        big_s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = big_s0 + maj
        h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + t2
    return jnp.stack(
        [
            state[0] + a, state[1] + b, state[2] + c, state[3] + d,
            state[4] + e, state[5] + f, state[6] + g, state[7] + h,
        ]
    )


def _initial_state(n: int):
    """(8, N) initial state built from scalar literals (Pallas-safe)."""
    return jnp.stack([jnp.full((n,), int(v), jnp.uint32) for v in H0])


def _pad_block(n: int):
    """(16, N) padding block for 64-byte messages, from scalar literals."""
    rows = [jnp.full((n,), 0x80000000, jnp.uint32)]
    rows += [jnp.zeros((n,), jnp.uint32)] * 14
    rows += [jnp.full((n,), 512, jnp.uint32)]
    return jnp.stack(rows)


def _sha256_64b_words(msgs, k_at):
    """SHA-256 of N 64-byte messages: ``msgs`` (16, N) uint32 → (8, N)."""
    n = msgs.shape[1]
    state = _compress(_initial_state(n), msgs, k_at)
    return _compress(state, _pad_block(n), k_at)


@jax.jit
def sha256_64b_xla(msgs: jax.Array) -> jax.Array:
    """Pure-XLA batched SHA-256 of 64-byte messages. (16, N) → (8, N)."""
    k_arr = jnp.asarray(K)
    return _sha256_64b_words(msgs, lambda t: k_arr[t])


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------

_PALLAS_CALLS = _metrics.counter("ops.sha256.pallas")

# Lanes per grid step. 8 sublane-tiles of 128 lanes for 32-bit data keeps the
# VPU fed while staying far under VMEM limits ((16+8)*1024*4B = 96KiB/step).
_TILE_N = 1024


def _lane_tile(i):
    """Block index of grid step ``i``: all rows, the i-th lane tile. The
    row index is an explicit int32 — a Python ``0`` is traced as int64
    under jax_enable_x64 (which ops.install() turns on), and Mosaic
    rejects an index map that returns (i64, i32)."""
    return np.int32(0), i


def _sha256_kernel(in_ref, out_ref):
    msgs = in_ref[:]
    n = msgs.shape[1]
    state = _compress_unrolled(_initial_state(n), msgs)
    out_ref[:] = _compress_unrolled(state, _pad_block(n))


@functools.partial(jax.jit, static_argnames=("interpret",))
def sha256_64b_pallas(msgs: jax.Array, interpret: bool = False) -> jax.Array:
    """Pallas-TPU batched SHA-256 of 64-byte messages. (16, N) → (8, N).

    N must be a multiple of _TILE_N (callers pad; merkle levels are powers
    of two so this is cheap). ``interpret=True`` runs the kernel in the
    Pallas interpreter (CPU) for testing.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = msgs.shape[1]
    if n % _TILE_N != 0:
        raise ValueError(
            f"sha256_64b_pallas requires N % {_TILE_N} == 0, got {n}; "
            "pad the batch or use sha256_64b_xla"
        )
    grid = (n // _TILE_N,)
    return pl.pallas_call(
        _sha256_kernel,
        out_shape=jax.ShapeDtypeStruct((8, n), jnp.uint32),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (16, _TILE_N), _lane_tile, memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (8, _TILE_N), _lane_tile, memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(msgs)


def _supports_pallas() -> bool:
    return jax.default_backend() == "tpu"


def sha256_64b(msgs: jax.Array) -> jax.Array:
    """Batched SHA-256: the Pallas kernel on a TPU, the XLA twin elsewhere.

    On a TPU every width takes the kernel, padded up to whole lane tiles:
    the chip's compiler needs ~12 s for each distinct width of the XLA
    twin (its rolling-window ``fori_loop``), which made the ten sub-tile
    levels of one merkle tree two minutes of compile, while all sub-tile
    levels padded to one tile share a single kernel. A Pallas compile
    failure there is an error and surfaces as one — there is no demotion
    to the XLA twin. ``ops.sha256.pallas`` counts entries into the Pallas
    branch at the Python level (eager calls and traces — a jitted caller
    counts once per compile)."""
    if not _supports_pallas():
        return sha256_64b_xla(msgs)
    _PALLAS_CALLS.inc()
    n = msgs.shape[1]
    pad = -n % _TILE_N
    if not pad:
        return sha256_64b_pallas(msgs)
    return sha256_64b_pallas(jnp.pad(msgs, ((0, 0), (0, pad))))[:, :n]


# ---------------------------------------------------------------------------
# Host bridge: bytes ↔ device words
# ---------------------------------------------------------------------------


def hash_level_bytes(nodes: bytes) -> bytes:
    """Device equivalent of ssz.hash.hash_level_host: ``nodes`` is 2n 32-byte
    nodes concatenated; returns n parent nodes. Bit-identical to hashlib."""
    n = len(nodes) // 64
    # (n, 16) big-endian words → (16, n) lanes-last layout
    words = np.frombuffer(nodes, dtype=">u4").astype(np.uint32).reshape(n, 16).T
    out = _obs.d2h(
        "ops.sha256.hash_level",
        sha256_64b(_obs.h2d("ops.sha256.hash_level", words)),
    )
    # (8, n) → (n, 8) → big-endian bytes
    return out.T.astype(">u4").tobytes()


def install_device_hasher(force: bool = False) -> None:
    """Route ssz merkleization's large levels through the device backend.

    No-op on a CPU default backend unless ``force``: the jnp compression
    there is ~30x slower than the native C++ hasher, and a degraded
    (chip-less) ``ops.install()`` was silently poisoning every
    subsequent big merkle level in the process — measured 6.3s vs 0.2s
    per 2^19-pair level, which turned the 2^20-registry cold walk from
    6s into 59s once any config had installed device routing."""
    import jax

    if jax.default_backend() == "cpu" and not force:
        return
    from ..ssz.hash import register_device_hasher

    register_device_hasher(hash_level_bytes)
