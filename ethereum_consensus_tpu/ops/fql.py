"""Lazy-reduction device field arithmetic for the batched pairing stack.

Why a second field layer (vs ops/fq.py): the strict kernels canonicalize
after every add/sub with compare-and-subtract chains (`_geq` + borrow
propagation). Those long sequential integer chains are precisely what
XLA's optimizer chokes on — a single strict Fq2 multiply costs ~17s of
compile time, which makes a Miller loop (thousands of field ops)
uncompilable. This layer removes every comparison from the hot path:

* Elements are (..., 24) **uint64** columns of 16-bit limbs, but columns
  may exceed 16 bits between multiplications (redundant form). Values are
  bounded, never canonical: every element is ≡ its value mod p with
  columns < 2^24 and the 24-column integer < 2^397.
* Addition is a plain elementwise `+` (one XLA op). Subtraction adds a
  precomputed redistributed multiple of p (``SUB_PAD`` ≈ 2^391, every
  column ≥ 2^23 − 16) so columns never underflow: requires the
  subtrahend's columns < 2^23 − 16 — audited per formula; the deepest
  chains in fq12's line multiply stay below 2^22.5.
* Multiplication is Montgomery CIOS with **R' = 2^416** (26 rounds).
  The two extra rounds buy slack: for input VALUES up to ~2^397 (far
  beyond anything the formulas produce, pads included) the output is
  < 1.1·p with exact 16-bit columns, WITHOUT any conditional
  subtraction. An output that is ≡ 0 mod p is exactly 0 or exactly p,
  which is what `is_zero_cols` pattern-checks.
* Export to canonical integers reduces mod p on host (ints are exact).

Bit-identical parity with the strict/native backends is checked on
canonical exports (tests/test_ops_pairing.py) — the internal R' form is
invisible outside this package.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import _env
from . import fq as _strict

__all__ = [
    "P_INT",
    "LIMBS",
    "ONE_MONT",
    "to_mont_cols",
    "from_mont_ints",
    "mont",
    "add",
    "sub",
    "dbl",
    "is_zero_cols",
]

P_INT = _strict.P_INT
LIMBS = 24
MASK = (1 << 16) - 1
R_PRIME = 1 << 416
R2_PRIME = (R_PRIME * R_PRIME) % P_INT
N0_INT = (-pow(P_INT, -1, 1 << 16)) % (1 << 16)

P_COLS = np.array([(P_INT >> (16 * i)) & MASK for i in range(LIMBS)], np.uint64)


def _int_to_cols(v: int) -> np.ndarray:
    return np.array([(v >> (16 * i)) & MASK for i in range(LIMBS)], np.uint64)


def _redistribute(value: int, slack_bits: int) -> np.ndarray:
    """Rewrite ``value`` as 24 columns each ≥ 2^slack − 16 (borrowing
    across columns), preserving the integer exactly."""
    cols = []
    rem = value
    for i in range(LIMBS - 1):
        d = (rem >> (16 * i)) & MASK
        ci = d + (1 << slack_bits)
        cols.append(ci)
        rem -= ci << (16 * i)
    top = rem >> (16 * (LIMBS - 1))
    assert 0 < top < (1 << (slack_bits + 3)), hex(top)
    cols.append(top)
    assert sum(v << (16 * i) for i, v in enumerate(cols)) == value
    assert all(c >= (1 << slack_bits) - 16 for c in cols)
    return np.array(cols, np.uint64)


# ~2^391 multiple of p, every column ≥ 2^23 − 16 — covers any subtrahend
# the formulas produce (audited bound: < 2^22.5 per column)
SUB_PAD = _redistribute(((1 << 391) // P_INT + 1) * P_INT, 23)
# top column of SUB_PAD must also dominate the subtrahend's top column
assert SUB_PAD[-1] >= (1 << 23)

ONE_MONT = _int_to_cols(R_PRIME % P_INT)  # 1 in R'-Montgomery form


def add(a, b):
    return a + b


def dbl(a):
    return a + a


def sub(a, b):
    """(a − b) + SUB_PAD, columnwise nonnegative for b cols < 2^23 − 16."""
    return (a + jnp.asarray(SUB_PAD)) - b


# Which product kernel `mont` runs: "u64" = the CIOS fori_loop below
# (wide-integer lane products); "mxu" = the int8 digit matmul
# (fq8.mont7r — the MXU's native int8×int8→int32 path). Same contract
# either way; the switch exists because which one wins is a per-chip
# hardware question (v5e emulates u64 lane products; see
# docs/DEVICE_PAIRING.md).
_MULTIPLIER = _env.raw("EC_PAIRING_MULT", "u64")


def set_multiplier(kind: str) -> None:
    """Switch the pairing-stack product kernel ("u64" | "mxu").

    Clears every jit cache: compiled pairing traces bake the multiplier
    in, so stale executables must not outlive the switch."""
    global _MULTIPLIER
    assert kind in ("u64", "mxu"), kind
    if kind != _MULTIPLIER:
        _MULTIPLIER = kind
        jax.clear_caches()


def get_multiplier() -> str:
    return _MULTIPLIER


def mont(a, b):
    """Montgomery product a·b·R'⁻¹ (mod p up to one multiple): inputs are
    redundant columns (< 2^24, value < 2^397), output has exact 16-bit
    columns and value < 1.1·p. 26 CIOS rounds under one `fori_loop`,
    carry-normalized by one scan — no comparisons, no conditional
    subtraction. With the "mxu" multiplier selected the same contract is
    served by the int8 digit matmul instead (fq8.mont7r)."""
    if _MULTIPLIER == "mxu":
        from . import fq8

        return fq8.mont7r(a, b)
    p64 = jnp.asarray(P_COLS)
    n0 = jnp.uint64(N0_INT)
    mask = jnp.uint64(MASK)
    shift = jnp.uint64(16)
    batch = a.shape[:-1]
    apad = jnp.concatenate([a, jnp.zeros(batch + (2,), jnp.uint64)], axis=-1)
    t0 = jnp.zeros(batch + (LIMBS + 2,), jnp.uint64)

    def step(i, t):
        ai = jax.lax.dynamic_index_in_dim(apad, i, axis=-1, keepdims=True)
        t = t.at[..., :LIMBS].add(ai * b)
        m = (t[..., 0] * n0) & mask
        t = t.at[..., :LIMBS].add(m[..., None] * p64)
        carry0 = t[..., 0] >> shift
        shifted = jnp.concatenate(
            [t[..., 1:], jnp.zeros(batch + (1,), jnp.uint64)], axis=-1
        )
        return shifted.at[..., 0].add(carry0)

    t = jax.lax.fori_loop(0, LIMBS + 2, step, t0)

    def carry_step(carry, col):
        v = col + carry
        return v >> shift, v & mask

    _, limbs = jax.lax.scan(
        carry_step, jnp.zeros(batch, jnp.uint64), jnp.moveaxis(t, -1, 0)
    )
    return jnp.moveaxis(limbs, 0, -1)[..., :LIMBS]


def is_zero_cols(x):
    """x ≡ 0 mod p for a MONT OUTPUT (value < 1.1·p ⇒ value ∈ {0, p})."""
    zero = jnp.all(x == 0, axis=-1)
    isp = jnp.all(x == jnp.asarray(P_COLS), axis=-1)
    return zero | isp


_ONE_COLS = _int_to_cols(1)
R2_COLS = _int_to_cols(R2_PRIME)


def is_zero_any(x):
    """x ≡ 0 mod p for ANY redundant value: one mont by the integer 1
    canonicalizes (x·R'⁻¹, value < 1.1p), then pattern-checks {0, p}."""
    return is_zero_cols(mont(x, jnp.asarray(_ONE_COLS)))


def to_mont_device(x):
    """Plain canonical columns → R'-Montgomery form, on device."""
    return mont(x, jnp.asarray(R2_COLS))


# ---------------------------------------------------------------------------
# Bound-tracked lazy values: the hand-audit of column/value growth across
# the Fq12 tower is exactly the kind of bookkeeping that silently breaks
# (round-3 lesson: the first cut wrapped uint64 columns in fp12_mul).
# LV carries STATIC Python-int bounds beside the traced array; `lv_sub`
# picks the smallest adequate pad from a ladder and `lv_mont` asserts the
# no-overflow preconditions — any violation fails loudly at TRACE time,
# with zero runtime cost.
# ---------------------------------------------------------------------------

from typing import NamedTuple  # noqa: E402


class LV(NamedTuple):
    """A lazy field element (or stack of them): uint64 columns on the last
    axis plus static value/column upper bounds (exclusive)."""

    arr: "jax.Array"
    vmax: int
    cmax: int


class _Pad(NamedTuple):
    arr: np.ndarray
    value: int
    cmin: int
    cmax: int


def _make_pad(slack_bits: int) -> _Pad:
    # smallest multiple of p whose redistributed columns all reach the
    # slack floor
    need = sum((1 << slack_bits) << (16 * i) for i in range(LIMBS))
    m = need // P_INT + 1
    cols = _redistribute(m * P_INT, slack_bits)
    return _Pad(cols, m * P_INT, int(cols.min()), int(cols.max()))


_PAD_LADDER = [_make_pad(s) for s in range(17, 31)]

# Montgomery preconditions: output must stay < 2^384 (24 columns), and
# the CIOS accumulator columns must stay < 2^64.
_MAX_AB = ((1 << 384) - 1 - P_INT) * R_PRIME
_CANON_VMAX = P_INT + (P_INT >> 8)  # < 1.004·p covers every mont output


def lv_canon(arr) -> LV:
    """Wrap a mont output (16-bit columns, value < 1.004p)."""
    return LV(arr, _CANON_VMAX, 1 << 16)


def lv_const(value: int) -> LV:
    """R'-Montgomery constant."""
    return LV(jnp.asarray(to_mont_cols(value)), _CANON_VMAX, 1 << 16)


def lv_zero_like(a: LV) -> LV:
    return LV(jnp.zeros_like(a.arr), 1, 1)


def lv_add(a: LV, b: LV) -> LV:
    return LV(a.arr + b.arr, a.vmax + b.vmax, a.cmax + b.cmax)


def lv_dbl(a: LV) -> LV:
    return lv_add(a, a)


def lv_sub(a: LV, b: LV) -> LV:
    """a − b + (smallest ladder pad covering b's columns)."""
    for pad in _PAD_LADDER:
        if pad.cmin >= b.cmax:
            return LV(
                (a.arr + jnp.asarray(pad.arr)) - b.arr,
                a.vmax + pad.value,
                a.cmax + pad.cmax,
            )
    raise AssertionError(
        f"no pad covers subtrahend columns < {b.cmax:#x}; add a bigger "
        "ladder entry or normalize the operand"
    )


def lv_mont(a: LV, b: LV) -> LV:
    assert a.vmax * b.vmax <= _MAX_AB, (
        f"mont value overflow: vmax {a.vmax.bit_length()}+"
        f"{b.vmax.bit_length()} bits"
    )
    assert 32 * a.cmax * b.cmax < (1 << 63), (
        f"mont column overflow: cmax {a.cmax:#x} * {b.cmax:#x}"
    )
    return lv_canon(mont(a.arr, b.arr))


def lv_stack(items: "list[LV]", axis: int = 0) -> LV:
    return LV(
        jnp.stack([i.arr for i in items], axis=axis),
        max(i.vmax for i in items),
        max(i.cmax for i in items),
    )


def lv_coerce(arr, like: LV) -> LV:
    """Rebrand a raw array (e.g. a scan carry) with declared bounds."""
    return LV(arr, like.vmax, like.cmax)


def lv_assert_within(a: LV, vmax: int, cmax: int) -> LV:
    """Trace-time check that actual bounds fit a declared envelope (used
    at scan-carry boundaries, where bounds must be iteration-stable)."""
    assert a.vmax <= vmax and a.cmax <= cmax, (
        f"bounds exceed declared envelope: vmax 2^{a.vmax.bit_length()}"
        f" > 2^{vmax.bit_length()} or cmax {a.cmax:#x} > {cmax:#x}"
    )
    return LV(a.arr, vmax, cmax)


def to_mont_cols(values: "int | list[int]") -> np.ndarray:
    """Canonical int(s) → R'-Montgomery columns (host side)."""
    if isinstance(values, int):
        return _int_to_cols((values * R_PRIME) % P_INT)
    return np.stack([to_mont_cols(v) for v in values])


def from_mont_ints(cols) -> "int | list[int]":
    """R'-Montgomery columns (any redundancy) → canonical int(s), host."""
    arr = np.asarray(cols)
    if arr.ndim == 1:
        v = sum(int(c) << (16 * i) for i, c in enumerate(arr))
        return (v * pow(R_PRIME, -1, P_INT)) % P_INT
    return [from_mont_ints(row) for row in arr]
