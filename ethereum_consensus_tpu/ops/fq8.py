"""MXU-shaped Montgomery multiplication: the int8 digit product.

The lazy tower (ops/fql.py) made the batched pairing COMPILE and run
correct, but on chips that emulate wide-integer lane multiplies (v5e)
its u64 column products lose to the native ADX backend. The TPU's
arithmetic actually lives in the MXU, whose integer path is
int8×int8→int32. ``mont7r`` re-shapes the schoolbook column product to
feed it: each operand is carry-normalized to 25 exact 16-bit columns and
cut into 58 7-bit digits (which fit SIGNED int8); the digits of ``a``
form a per-element matrix of shifted copies, and the whole product is
one batched int8 dot_general with exact int32 accumulation
(58 terms × 127² < 2^20). The Montgomery reduction that follows is the
same column-serial sweep as fql.mont at byte granularity (52 rounds).

``mont7r`` takes the lazy tower's redundant operands and is a drop-in
for ``fql.mont``, selected by ``fql.set_multiplier("mxu")`` /
``EC_PAIRING_MULT=mxu``; correctness is pinned by
tests/test_ops_pairing.py (column-exact vs fql.mont on redundant and
canonical inputs, full batch-verdict parity under the mxu multiplier).
Which multiplier wins is a per-chip question; see docs/DEVICE_PAIRING.md.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import fql

__all__ = ["carry_norm", "product_cols7r", "mont7r"]

L8 = 48          # 8-bit limbs per 384-bit value

_P8 = np.zeros(L8, dtype=np.int64)
for _i in range(L8):
    _P8[_i] = (fql.P_INT >> (8 * _i)) & 0xFF


def _reduce8(t):
    """Byte-granular Montgomery reduction of deferred uint64 byte columns
    (value weight 2^(8i)): 52 rounds for R' = 2^416, carry-normalize,
    regroup to 16-bit columns."""
    n0_8 = (-pow(fql.P_INT, -1, 1 << 8)) % (1 << 8)
    batch = t.shape[:-1]
    p8 = jnp.asarray(_P8.astype(np.uint64))
    mask8 = jnp.uint64(0xFF)
    rounds = 52  # R' = 2^416 = 2^(8·52)

    def step(i, t):
        m = (t[..., 0] * jnp.uint64(n0_8)) & mask8
        t = t.at[..., :L8].add(m[..., None] * p8)
        carry0 = t[..., 0] >> jnp.uint64(8)
        shifted = jnp.concatenate(
            [t[..., 1:], jnp.zeros(batch + (1,), jnp.uint64)], axis=-1
        )
        return shifted.at[..., 0].add(carry0)

    t = jax.lax.fori_loop(0, rounds, step, t)

    def carry_step(carry, col):
        v = col + carry
        return v >> jnp.uint64(8), v & mask8

    _, limbs8 = jax.lax.scan(
        carry_step, jnp.zeros(batch, jnp.uint64), jnp.moveaxis(t, -1, 0)
    )
    limbs8 = jnp.moveaxis(limbs8, 0, -1)[..., :L8]
    lo = limbs8[..., 0::2]
    hi = limbs8[..., 1::2]
    return lo | (hi << jnp.uint64(8))


# -- mont7r: the int8 MXU product for REDUNDANT inputs ----------------------
#
# fql.mont's callers (the whole pairing tower) feed lazily-redundant
# columns (< 2^24, value < ~2^397), which digit slicing cannot take
# directly. mont7r normalizes each operand first with one carry scan
# (exact, 25 16-bit columns), then runs the digit extraction, the batched
# int8 matmul, and the byte regroup fully vectorized — a handful of XLA
# ops per multiply, so the Miller loop's thousands of monts stay
# compilable. Drop-in replacement for fql.mont (same R' = 2^416, same
# output form); routed via fql.set_multiplier / EC_PAIRING_MULT.

L7R = 58            # 7-bit digits covering 25 16-bit columns (406 ≥ 400 bits)
COLS7R = 2 * L7R - 1
NORM_COLS = 25

_D7_Q = np.array([(7 * d) // 16 for d in range(L7R)])
_D7_R = np.array([(7 * d) % 16 for d in range(L7R)], np.uint64)
# A[n, j, k] = a7[n, k - j]: one gather with an out-of-range slot -> 0
_K_MINUS_J = np.arange(COLS7R)[None, :] - np.arange(L7R)[:, None]
_KJ_IDX = np.where(
    (_K_MINUS_J >= 0) & (_K_MINUS_J < L7R), _K_MINUS_J, L7R
)  # (58, 115); index L7R hits the appended zero slot
_R7_Q = np.array([(7 * i) // 8 for i in range(COLS7R)])
_R7_S = np.array([(7 * i) % 8 for i in range(COLS7R)], np.uint64)


def carry_norm(cols):
    """Exact carry propagation: redundant (..., 24) uint64 columns
    (value < 2^400) → (..., 25) canonical 16-bit columns."""
    batch = cols.shape[:-1]
    mask = jnp.uint64(fql.MASK)

    def step(carry, col):
        v = col + carry
        return v >> jnp.uint64(16), v & mask

    carry, out = jax.lax.scan(
        step, jnp.zeros(batch, jnp.uint64), jnp.moveaxis(cols, -1, 0)
    )
    out = jnp.moveaxis(out, 0, -1)
    return jnp.concatenate([out, carry[..., None]], axis=-1)


def _to7r(cols25):
    """(..., 25) exact 16-bit columns → (..., 58) 7-bit digits as SIGNED
    int8, fully vectorized (two gathers + shifts)."""
    padded = jnp.concatenate(
        [cols25, jnp.zeros(cols25.shape[:-1] + (1,), jnp.uint64)], axis=-1
    )
    c0 = padded[..., _D7_Q]
    c1 = padded[..., _D7_Q + 1]
    r = jnp.asarray(_D7_R)
    # bits ≥ 7 are masked off, so the uniform (16 − r) splice is exact
    # for every r (at r = 0 the c1 term lands at bit 16, masked away)
    v = (c0 >> r) | (c1 << (jnp.uint64(16) - r))
    return (v & jnp.uint64(0x7F)).astype(jnp.int8)


def product_cols7r(a25, b25):
    """Exact 115-column 7-bit-weighted product of two 25-column values via
    the batched int8 matmul (int32 accumulation: 58 terms × 127² < 2^20)."""
    a7 = _to7r(a25)
    b7 = _to7r(b25)
    batch = a7.shape[:-1]
    a7p = jnp.concatenate(
        [a7, jnp.zeros(batch + (1,), jnp.int8)], axis=-1
    )
    A = a7p[..., _KJ_IDX]                      # (..., 58, 115) int8
    nb = len(batch)
    cols = jax.lax.dot_general(
        b7[..., None, :],
        A,
        (((nb + 1,), (nb,)), (tuple(range(nb)), tuple(range(nb)))),
        preferred_element_type=jnp.int32,
    )[..., 0, :]
    return cols.astype(jnp.uint64)


def mont7r(a, b):
    """Montgomery product a·b·(2^416)⁻¹ for REDUNDANT operands — the
    drop-in MXU-path replacement for ``fql.mont``: same input contract
    (uint64 columns < 2^24, values < ~2^397), same output (exact 16-bit
    columns, value < 1.1·p). Verified column-exact vs fql.mont in
    tests/test_ops_pairing.py."""
    # fql.mont broadcasts (e.g. mont(x, ONE_COLS) canonicalizes a batch
    # against one constant); the batched dot_general needs explicit
    # common batch shapes
    if a.shape != b.shape:
        shape = jnp.broadcast_shapes(a.shape, b.shape)
        a = jnp.broadcast_to(a, shape)
        b = jnp.broadcast_to(b, shape)
    cols7 = product_cols7r(carry_norm(a), carry_norm(b))
    batch = cols7.shape[:-1]
    shifted = cols7 << jnp.asarray(_R7_S)
    t = (
        jnp.zeros(batch + (2 * L8 + 4,), jnp.uint64)
        .at[..., _R7_Q]
        .add(shifted)
    )
    return _reduce8(t)
