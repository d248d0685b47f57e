"""Device batched optimal-ate pairing for BLS12-381 — the north-star
kernel: N Miller loops run data-parallel over the set axis, their product
tree-reduces on device, and ONE final exponentiation (host native, a
single Fq12 predicate) yields the batch verdict.

This is the TPU-shaped decomposition of `verify_signature_sets`'
N+1-pairing product (crypto/bls.py): the O(N·bits) Miller work — line
evaluations, sparse Fq12 multiplies, accumulator doubling — is
embarrassingly data-parallel across pairs and runs as ONE jitted scan
over the 63 static bits of |x| (add steps fire under `lax.cond` on the
static bit pattern — no data-dependent control flow). The O(1)
exponentiation that follows is scalar, branchy, and latency-bound — the
wrong shape for the device — so it stays on the native C++ backend
(bls12_381.cpp final_exp_for_verdict) behind a 576-byte Fq12 handoff.

Formulas mirror native/bls12_381.cpp's fused Miller steps (same line
slots, same subfield scaling killed by the final exponentiation), so
device and native Miller values agree exactly on canonical export — the
parity anchor in tests/test_ops_pairing.py. Field arithmetic is the
bound-tracked lazy layer (ops/fql.py): all correctness-critical
column/value bounds are asserted at trace time.

Reference role: blst's pairing engine under crypto/bls.rs (C6); design
per SURVEY.md §2.5 (batch axes as mesh axes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import device as _obs
from ..utils import trace
from . import fq2, fq12, fql
from .fql import LV

__all__ = [
    "BLS_X_ABS",
    "g1_affine_from_raw",
    "g2_affine_from_raw",
    "miller_loop_batched",
    "fp12_product",
    "miller_product_device",
    "g2_sum_points",
    "g1_mul_batched",
    "g2_mul_batched",
    "batch_verify_device",
    "finalize_verdict",
]

BLS_X_ABS = 0xD201000000010000
# bits below the MSB, MSB-first — the static Miller schedule
_X_BITS = np.array([int(b) for b in bin(BLS_X_ABS)[3:]], dtype=np.bool_)

# scan/tree carry envelopes (trace-time asserted fixpoints)
_ENV_V = 1 << 392
_ENV_C = 1 << 26


def _env(arr) -> LV:
    return LV(arr, _ENV_V, _ENV_C)


def _clamp(a: LV):
    return fql.lv_assert_within(a, _ENV_V, _ENV_C).arr


# ---------------------------------------------------------------------------
# marshalling (raw affine big-endian bytes <-> R'-Montgomery columns)
# ---------------------------------------------------------------------------

def g1_affine_from_raw(raws: "list[bytes]") -> tuple[LV, LV]:
    """Affine raw96 G1 points → ((N, 24), (N, 24)) R'-Montgomery x, y.
    Callers must exclude infinity (the Miller loop skips such pairs)."""
    n = len(raws)
    words = np.frombuffer(b"".join(raws), dtype=">u2").reshape(n, 48)
    x = np.ascontiguousarray(words[:, :24][:, ::-1]).astype(np.uint64)
    y = np.ascontiguousarray(words[:, 24:][:, ::-1]).astype(np.uint64)
    xy = fql.to_mont_device(
        _obs.h2d("ops.pairing.g1_affine_from_raw", np.concatenate([x, y]))
    )
    return fql.lv_canon(xy[:n]), fql.lv_canon(xy[n:])


def g2_affine_from_raw(raws: "list[bytes]") -> tuple[LV, LV]:
    """Affine raw192 G2 points (x.c0||x.c1||y.c0||y.c1, 48-byte BE each,
    the native backend's format) → ((N, 2, 24), (N, 2, 24)) LVs."""
    n = len(raws)
    words = np.frombuffer(b"".join(raws), dtype=">u2").reshape(n, 4, 24)
    limbs = np.ascontiguousarray(words[:, :, ::-1]).astype(np.uint64)
    m = fql.to_mont_device(
        _obs.h2d("ops.pairing.g2_affine_from_raw", limbs.reshape(n * 4, 24))
    ).reshape(n, 4, 24)
    x = fql.lv_canon(jnp.stack([m[:, 0], m[:, 1]], axis=-2))
    y = fql.lv_canon(jnp.stack([m[:, 2], m[:, 3]], axis=-2))
    return x, y


# ---------------------------------------------------------------------------
# G1 point arithmetic on the lazy field (Jacobian, branchless)
# ---------------------------------------------------------------------------

def _fq_comp(p: LV, i: int) -> LV:
    return LV(p.arr[..., i, :], p.vmax, p.cmax)


def _g1_pack(x: LV, y: LV, z: LV) -> LV:
    return LV(
        jnp.stack([x.arr, y.arr, z.arr], axis=-2),
        max(x.vmax, y.vmax, z.vmax),
        max(x.cmax, y.cmax, z.cmax),
    )


def _fq_is_zero(a: LV):
    return fql.is_zero_any(a.arr)


def _lv_row(t: LV, k: int) -> LV:
    return LV(t.arr[k], t.vmax, t.cmax)


def _g1_double(p: LV) -> LV:
    """dbl-2009-l over the lazy scalar field; infinity (z ≡ 0) stays
    infinity through the algebra (z3 = 2yz ≡ 0)."""
    x, y, z = (_fq_comp(p, i) for i in range(3))
    s = fql.lv_mont(fql.lv_stack([x, y, z]), fql.lv_stack([x, y, z]))
    a, b, zz = _lv_row(s, 0), _lv_row(s, 1), _lv_row(s, 2)  # x², y², z²
    s2 = fql.lv_mont(
        fql.lv_stack([b, fql.lv_add(x, b), y]),
        fql.lv_stack([b, fql.lv_add(x, b), z]),
    )
    c, xb2, yz = _lv_row(s2, 0), _lv_row(s2, 1), _lv_row(s2, 2)
    d = fql.lv_sub(fql.lv_sub(xb2, a), c)
    d = fql.lv_add(d, d)
    e = fql.lv_add(fql.lv_add(a, a), a)
    f = fql.lv_mont(e, e)
    x3 = fql.lv_sub(f, fql.lv_add(d, d))
    c8 = fql.lv_add(c, c)
    c8 = fql.lv_add(c8, c8)
    c8 = fql.lv_add(c8, c8)
    y3m = fql.lv_mont(e, fql.lv_sub(d, x3))
    y3 = fql.lv_sub(y3m, c8)
    z3 = fql.lv_add(yz, yz)
    return _g1_pack(x3, y3, z3)


def _g1_add(p: LV, q: LV) -> LV:
    """Branchless add-2007-bl with infinity / P==Q / P==-Q selects."""
    x1, y1, z1 = (_fq_comp(p, i) for i in range(3))
    x2, y2, z2 = (_fq_comp(q, i) for i in range(3))
    s = fql.lv_mont(fql.lv_stack([z1, z2]), fql.lv_stack([z1, z2]))
    z1z1, z2z2 = _lv_row(s, 0), _lv_row(s, 1)
    s = fql.lv_mont(
        fql.lv_stack([x1, x2, y1, y2]),
        fql.lv_stack([z2z2, z1z1, z2, z1]),
    )
    u1, u2, s1p, s2p = (_lv_row(s, i) for i in range(4))
    s = fql.lv_mont(fql.lv_stack([s1p, s2p]), fql.lv_stack([z2z2, z1z1]))
    s1, s2 = _lv_row(s, 0), _lv_row(s, 1)
    h = fql.lv_sub(u2, u1)
    r = fql.lv_sub(s2, s1)
    h_zero = _fq_is_zero(h)
    r_zero = _fq_is_zero(r)
    hh = fql.lv_add(h, h)
    s = fql.lv_mont(fql.lv_stack([hh, z1]), fql.lv_stack([hh, z2]))
    i4, zz = _lv_row(s, 0), _lv_row(s, 1)  # (2h)², z1z2
    s = fql.lv_mont(fql.lv_stack([h, u1]), fql.lv_stack([i4, i4]))
    j, v = _lv_row(s, 0), _lv_row(s, 1)
    r2 = fql.lv_add(r, r)
    s = fql.lv_mont(
        fql.lv_stack([r2, s1, fql.lv_add(zz, zz)]),
        fql.lv_stack([r2, j, h]),
    )
    r2sq, s1j, z3 = _lv_row(s, 0), _lv_row(s, 1), _lv_row(s, 2)
    x3 = fql.lv_sub(fql.lv_sub(r2sq, j), fql.lv_add(v, v))
    y3m = fql.lv_mont(r2, fql.lv_sub(v, x3))
    y3 = fql.lv_sub(y3m, fql.lv_add(s1j, s1j))
    added = _g1_pack(x3, y3, z3)

    doubled = _g1_double(p)
    p_inf = _fq_is_zero(z1)
    q_inf = _fq_is_zero(z2)
    both = ~p_inf & ~q_inf
    same = both & h_zero & r_zero
    negat = both & h_zero & ~r_zero

    sel = lambda m: m[..., None, None]  # noqa: E731
    out = added.arr
    out = jnp.where(sel(same), doubled.arr, out)
    out = jnp.where(sel(negat), jnp.zeros_like(out), out)
    out = jnp.where(sel(p_inf), q.arr, out)
    out = jnp.where(sel(q_inf), p.arr, out)
    vmax = max(added.vmax, doubled.vmax, p.vmax, q.vmax)
    cmax = max(added.cmax, doubled.cmax, p.cmax, q.cmax)
    return LV(out, vmax, cmax)


# ---------------------------------------------------------------------------
# G2 point arithmetic over fq2 (Jacobian, branchless)
# ---------------------------------------------------------------------------

def _g2_comp(p: LV, i: int) -> LV:
    return LV(p.arr[..., i, :, :], p.vmax, p.cmax)


def _g2_pack(x: LV, y: LV, z: LV) -> LV:
    return LV(
        jnp.stack([x.arr, y.arr, z.arr], axis=-3),
        max(x.vmax, y.vmax, z.vmax),
        max(x.cmax, y.cmax, z.cmax),
    )


def g2_point_double(p: LV) -> LV:
    x, y, z = (_g2_comp(p, i) for i in range(3))
    a, b, zz = fq2.square_many([x, y, z])
    c, xb2 = fq2.square_many([b, fq2.add(x, b)])
    d = fq2.sub(fq2.sub(xb2, a), c)
    d = fq2.add(d, d)
    e = fq2.add(fq2.add(a, a), a)
    f, = fq2.square_many([e])
    x3 = fq2.sub(f, fq2.add(d, d))
    c8 = fq2.dbl(fq2.dbl(fq2.dbl(c)))
    em, yzm = fq2.mul_many([(e, fq2.sub(d, x3)), (y, z)])
    y3 = fq2.sub(em, c8)
    z3 = fq2.add(yzm, yzm)
    return _g2_pack(x3, y3, z3)


def g2_point_add(p: LV, q: LV) -> LV:
    x1, y1, z1 = (_g2_comp(p, i) for i in range(3))
    x2, y2, z2 = (_g2_comp(q, i) for i in range(3))
    z1z1, z2z2 = fq2.square_many([z1, z2])
    u1, u2, s1p, s2p = fq2.mul_many(
        [(x1, z2z2), (x2, z1z1), (y1, z2), (y2, z1)]
    )
    s1, s2 = fq2.mul_many([(s1p, z2z2), (s2p, z1z1)])
    h = fq2.sub(u2, u1)
    r = fq2.sub(s2, s1)
    h_zero = fq2.is_zero(h)
    r_zero = fq2.is_zero(r)
    hh = fq2.add(h, h)
    i4, = fq2.square_many([hh])
    j, v, zz = fq2.mul_many([(h, i4), (u1, i4), (z1, z2)])
    r2 = fq2.add(r, r)
    r2sq, = fq2.square_many([r2])
    s1j, z3 = fq2.mul_many([(s1, j), (fq2.add(zz, zz), h)])
    x3 = fq2.sub(fq2.sub(r2sq, j), fq2.add(v, v))
    y3m, = fq2.mul_many([(r2, fq2.sub(v, x3))])
    y3 = fq2.sub(y3m, fq2.add(s1j, s1j))
    added = _g2_pack(x3, y3, z3)

    doubled = g2_point_double(p)
    p_inf = fq2.is_zero(z1)
    q_inf = fq2.is_zero(z2)
    both = ~p_inf & ~q_inf
    same = both & h_zero & r_zero
    negat = both & h_zero & ~r_zero

    sel = lambda m: m[..., None, None, None]  # noqa: E731
    out = added.arr
    out = jnp.where(sel(same), doubled.arr, out)
    out = jnp.where(sel(negat), jnp.zeros_like(out), out)
    out = jnp.where(sel(p_inf), q.arr, out)
    out = jnp.where(sel(q_inf), p.arr, out)
    vmax = max(added.vmax, doubled.vmax, p.vmax, q.vmax)
    cmax = max(added.cmax, doubled.cmax, p.cmax, q.cmax)
    return LV(out, vmax, cmax)


@functools.partial(jax.jit, static_argnames=("levels",))
def _g2_tree_reduce(points, levels: int):
    """(2^levels, 3, 2, 24) → (3, 2, 24) XOR-fold point sum (one compile
    for all levels — same trick as ops/g1._tree_reduce)."""
    width = points.shape[0]
    idx = jnp.arange(width)

    def level(k, pts):
        bit = jnp.left_shift(jnp.int32(1), k)
        summed = g2_point_add(_env(pts), _env(pts[idx ^ bit]))
        keep = (idx & bit) == 0
        return jnp.where(
            keep[:, None, None, None], _clamp(summed), jnp.zeros_like(pts)
        )

    return jax.lax.fori_loop(0, levels, level, points)[0]


def g2_sum_points(points: LV) -> LV:
    """Sum an (N, 3, 2, 24) batch of Jacobian G2 points on device."""
    n = points.arr.shape[0]
    width = 1 << (n - 1).bit_length() if n > 1 else 1
    arr = points.arr
    if width != n:
        pad = jnp.zeros((width - n, 3, 2, 24), jnp.uint64)
        arr = jnp.concatenate([arr, pad], axis=0)
    return _env(_g2_tree_reduce(arr, (width - 1).bit_length()))


# ---------------------------------------------------------------------------
# batched scalar multiplication (per-element scalars — the RLC blinders)
# ---------------------------------------------------------------------------

def _scalars_to_bits(scalars: "list[int]", bits: int) -> np.ndarray:
    out = np.zeros((len(scalars), bits), dtype=np.bool_)
    for i, s in enumerate(scalars):
        for b in range(bits):
            out[i, b] = (s >> (bits - 1 - b)) & 1
    return out


@jax.jit
def _mul_scan_g1(points, bits):  # observed below
    """points (N, 3, 24) Jacobian, bits (N, B) MSB-first →
    (N, 3, 24) [scalar]·P, double-and-add with per-element selects."""
    acc0 = jnp.zeros_like(points)

    def step(acc, bit_col):
        a = _g1_double(_env(acc))
        added = _g1_add(a, _env(points))
        out = jnp.where(bit_col[:, None, None], _clamp(added), _clamp(a))
        return out, None

    acc, _ = jax.lax.scan(step, acc0, jnp.moveaxis(bits, 1, 0))
    return acc


@jax.jit
def _mul_scan_g2(points, bits):  # observed below
    acc0 = jnp.zeros_like(points)

    def step(acc, bit_col):
        a = g2_point_double(_env(acc))
        added = g2_point_add(a, _env(points))
        out = jnp.where(bit_col[:, None, None, None], _clamp(added), _clamp(a))
        return out, None

    acc, _ = jax.lax.scan(step, acc0, jnp.moveaxis(bits, 1, 0))
    return acc


_mul_scan_g1 = _obs.observe_jit(_mul_scan_g1, "ops.pairing._mul_scan_g1")
_mul_scan_g2 = _obs.observe_jit(_mul_scan_g2, "ops.pairing._mul_scan_g2")


def g1_mul_batched(points: LV, scalars: "list[int]", bits: int = 128) -> LV:
    """(N, 3, 24) Jacobian × per-element scalars → (N, 3, 24)."""
    return _env(_mul_scan_g1(points.arr, jnp.asarray(_scalars_to_bits(scalars, bits))))


def g2_mul_batched(points: LV, scalars: "list[int]", bits: int = 128) -> LV:
    return _env(_mul_scan_g2(points.arr, jnp.asarray(_scalars_to_bits(scalars, bits))))


# ---------------------------------------------------------------------------
# the batched Miller loop
# ---------------------------------------------------------------------------

def _double_step(f: LV, t: LV, xp: LV, yp: LV):
    """Fused tangent-line + doubling (bls12_381.cpp miller_double_step)."""
    x, y, z = (_g2_comp(t, i) for i in range(3))
    a, b, zz = fq2.square_many([x, y, z])
    c, xb2 = fq2.square_many([b, fq2.add(x, b)])
    z3c, x3c, yz = fq2.mul_many([(zz, z), (a, x), (y, z)])
    line_l = fq2.dbl(fq2.mul_many([(y, z3c)])[0])
    e = fq2.add(fq2.add(a, a), a)
    ez2, = fq2.mul_many([(e, zz)])
    c00 = fq2.neg(fq2.mul_by_xi(fq2.scalar_mul(line_l, yp)))
    c11 = fq2.sub(fq2.dbl(b), fq2.add(fq2.add(x3c, x3c), x3c))
    c12 = fq2.scalar_mul(ez2, xp)
    f = fq12.fp12_mul_by_line(f, c00, c11, c12)
    # T ← 2T reusing a, b, c, e
    d = fq2.sub(fq2.sub(xb2, a), c)
    d = fq2.add(d, d)
    fsq, = fq2.square_many([e])
    x3 = fq2.sub(fsq, fq2.add(d, d))
    c8 = fq2.dbl(fq2.dbl(fq2.dbl(c)))
    em, = fq2.mul_many([(e, fq2.sub(d, x3))])
    y3 = fq2.sub(em, c8)
    z3 = fq2.add(yz, yz)
    return f, _g2_pack(x3, y3, z3)


def _add_step(f: LV, t: LV, xp: LV, yp: LV, xq: LV, yq: LV):
    """Fused secant-line + mixed addition (bls12_381.cpp miller_add_step).
    T == ±Q is unreachable inside the loop (T = [k]Q, 1 < k << r)."""
    x, y, z = (_g2_comp(t, i) for i in range(3))
    z2, = fq2.square_many([z])
    z3c, u2 = fq2.mul_many([(z2, z), (xq, z2)])
    s2, = fq2.mul_many([(yq, z3c)])
    lam_n = fq2.sub(y, s2)
    lam_d, = fq2.mul_many([(fq2.sub(x, u2), z)])
    c00 = fq2.neg(fq2.mul_by_xi(fq2.scalar_mul(lam_d, yp)))
    t1m, t2m = fq2.mul_many([(yq, lam_d), (lam_n, xq)])
    c11 = fq2.sub(t1m, t2m)
    c12 = fq2.scalar_mul(lam_n, xp)
    f = fq12.fp12_mul_by_line(f, c00, c11, c12)
    # T ← T + Q (madd-2007-bl) reusing z2, z3c, u2, s2
    h = fq2.sub(u2, x)
    hh, = fq2.square_many([h])
    i4 = fq2.dbl(fq2.dbl(hh))
    j, v = fq2.mul_many([(h, i4), (x, i4)])
    rr = fq2.dbl(fq2.sub(s2, y))
    rrsq, zh2 = fq2.square_many([rr, fq2.add(z, h)])
    x3 = fq2.sub(fq2.sub(rrsq, j), fq2.dbl(v))
    ym, yj = fq2.mul_many([(rr, fq2.sub(v, x3)), (y, j)])
    y3 = fq2.sub(ym, fq2.dbl(yj))
    z3 = fq2.sub(fq2.sub(zh2, z2), hh)
    return f, _g2_pack(x3, y3, z3)


@jax.jit
def miller_loop_batched(xp, yp, xq, yq):
    """N Miller loops f_{|x|,Q_i}(P_i), conjugated for the negative BLS x.

    xp, yp: (N, 24) R'-Montgomery G1 affine; xq, yq: (N, 2, 24) G2
    affine (raw arrays — mont outputs). Returns a raw (N, 2, 3, 2, 24)
    Fq12 batch whose canonical export is bit-identical to the native
    backend's per-pair Miller values."""
    n = xp.shape[0]
    xp_lv, yp_lv = fql.lv_canon(xp), fql.lv_canon(yp)
    xq_lv, yq_lv = fql.lv_canon(xq), fql.lv_canon(yq)
    f0 = fq12.fp12_one((n,))
    one2 = jnp.broadcast_to(
        jnp.asarray(np.stack([fql.to_mont_cols(1), np.zeros(24, np.uint64)])),
        yq.shape,
    )
    t0 = jnp.stack([xq, yq, one2], axis=-3)

    def step(carry, bit):
        f_arr, t_arr = carry
        f, t = _env(f_arr), _env(t_arr)
        f = fq12.fp12_sqr(f)
        f, t = _double_step(f, t, xp_lv, yp_lv)

        def with_add(args):
            fa, ta = args
            f2, t2 = _add_step(_env(fa), _env(ta), xp_lv, yp_lv, xq_lv, yq_lv)
            return _clamp(f2), _clamp(t2)

        f_arr, t_arr = jax.lax.cond(
            bit, with_add, lambda args: args, (_clamp(f), _clamp(t))
        )
        return (f_arr, t_arr), None

    (f_arr, _), _ = jax.lax.scan(step, (f0.arr, t0), jnp.asarray(_X_BITS))
    return fq12.fp12_conj(_env(f_arr)).arr


@functools.partial(jax.jit, static_argnames=("levels",))
def _fp12_tree(fs, levels: int):
    """(2^levels, 2, 3, 2, 24) → (2, 3, 2, 24) XOR-fold product."""
    width = fs.shape[0]
    idx = jnp.arange(width)
    one = fq12.fp12_one((width,)).arr

    def level(k, vals):
        bit = jnp.left_shift(jnp.int32(1), k)
        prod = fq12.fp12_mul(_env(vals), _env(vals[idx ^ bit]))
        keep = (idx & bit) == 0
        return jnp.where(keep[:, None, None, None, None], _clamp(prod), one)

    return jax.lax.fori_loop(0, levels, level, fs)[0]


miller_loop_batched = _obs.observe_jit(
    miller_loop_batched, "ops.pairing.miller_loop_batched"
)


def fp12_product(fs) -> jax.Array:
    """Product of an (N, 2, 3, 2, 24) raw batch of Fq12 values."""
    n = fs.shape[0]
    if n == 1:
        return fs[0]
    width = 1 << (n - 1).bit_length()
    if width != n:
        fs = jnp.concatenate([fs, fq12.fp12_one((width - n,)).arr], axis=0)
    return _fp12_tree(fs, (width - 1).bit_length())


_CHUNK = 8192  # pairs per device dispatch (bounds peak HBM for the f batch)


def _generator_raws() -> "tuple[bytes, bytes]":
    from ..native import bls as native_bls

    return native_bls.g1_generator_raw(), native_bls.g2_generator_raw()


def _pad_pow2(items: list, filler) -> list:
    n = len(items)
    width = 1 << (n - 1).bit_length() if n > 1 else 1
    return items + [filler] * (width - n)


def miller_product_device(g1_raws: "list[bytes]", g2_raws: "list[bytes]") -> "list[int]":
    """Π_i miller(P_i, Q_i) over raw affine inputs, as 12 canonical-int
    Fq12 coefficients (the native backend's final-exp handoff format).
    Inputs must be finite points (callers skip infinity pairs).

    Batches are padded to the next power of two with generator pairs —
    the padding lanes' Miller values are sliced off before the product —
    so the jitted kernels compile for at most log2(_CHUNK) shapes instead
    of one shape per distinct set count."""
    assert len(g1_raws) == len(g2_raws) and g1_raws
    n_total = len(g1_raws)
    g1f, g2f = _generator_raws()
    chunks = []
    for lo in range(0, n_total, _CHUNK):
        g1c = g1_raws[lo:lo + _CHUNK]
        g2c = g2_raws[lo:lo + _CHUNK]
        n = len(g1c)
        xp, yp = g1_affine_from_raw(_pad_pow2(g1c, g1f))
        xq, yq = g2_affine_from_raw(_pad_pow2(g2c, g2f))
        fs = miller_loop_batched(xp.arr, yp.arr, xq.arr, yq.arr)[:n]
        chunks.append(fp12_product(fs))
    total = fp12_product(jnp.stack(chunks)) if len(chunks) > 1 else chunks[0]
    return fq12.fp12_to_ints(total)


# ---------------------------------------------------------------------------
# the full RLC batch verdict, device-shaped
# ---------------------------------------------------------------------------

@jax.jit
def _g1_jacobian_to_affine(jac):
    """(N, 3, 24) Jacobian raw columns → ((N, 24), (N, 24)) affine; one
    batched Fermat inversion scan. Callers exclude infinity."""
    # canonicalize z so the inversion scan carries stay mont outputs
    z = fql.mont(jac[..., 2, :], jnp.asarray(fql._ONE_COLS))
    z = fql.mont(z, jnp.asarray(fql.R2_COLS))
    zinv = fq2.fq_inv_raw(z)
    zinv2 = fql.mont(zinv, zinv)
    x = fql.mont(jac[..., 0, :], zinv2)
    y = fql.mont(jac[..., 1, :], fql.mont(zinv2, zinv))
    return x, y


def _g2_point_to_raw(point: LV) -> "tuple[bytes, bool]":
    """One (3, 2, 24) Jacobian G2 LV → (raw192 affine, is_inf); the O(1)
    affine conversion runs host-side big-int."""
    canon = np.asarray(point.arr).reshape(3, 2, 24)
    x0, x1 = fq2.from_lv_ints(fql.lv_canon(jnp.asarray(canon[0])))
    y0, y1 = fq2.from_lv_ints(fql.lv_canon(jnp.asarray(canon[1])))
    z0, z1 = fq2.from_lv_ints(fql.lv_canon(jnp.asarray(canon[2])))
    if z0 == 0 and z1 == 0:
        return b"\x00" * 192, True
    p = fql.P_INT
    norm_inv = pow((z0 * z0 + z1 * z1) % p, -1, p)
    zi0, zi1 = (z0 * norm_inv) % p, (-z1 * norm_inv) % p
    s0 = (zi0 * zi0 - zi1 * zi1) % p
    s1 = (2 * zi0 * zi1) % p
    c0 = (s0 * zi0 - s1 * zi1) % p
    c1 = (s0 * zi1 + s1 * zi0) % p
    ax0 = (x0 * s0 - x1 * s1) % p
    ax1 = (x0 * s1 + x1 * s0) % p
    ay0 = (y0 * c0 - y1 * c1) % p
    ay1 = (y0 * c1 + y1 * c0) % p
    return (ax0.to_bytes(48, "big") + ax1.to_bytes(48, "big")
            + ay0.to_bytes(48, "big") + ay1.to_bytes(48, "big")), False


_NEG_G1_GEN_RAW = None


def _neg_g1_generator_raw() -> bytes:
    global _NEG_G1_GEN_RAW
    if _NEG_G1_GEN_RAW is None:
        from ..native import bls as native_bls

        raw = native_bls.g1_generator_raw()
        x = int.from_bytes(raw[:48], "big")
        y = (fql.P_INT - int.from_bytes(raw[48:], "big")) % fql.P_INT
        _NEG_G1_GEN_RAW = x.to_bytes(48, "big") + y.to_bytes(48, "big")
    return _NEG_G1_GEN_RAW


def _g1_jac_from_affine_raws(raws: "list[bytes]") -> LV:
    x, y = g1_affine_from_raw(raws)
    one = jnp.broadcast_to(jnp.asarray(fql.to_mont_cols(1)), x.arr.shape)
    return _env(jnp.stack([x.arr, y.arr, one], axis=-2))


# ---------------------------------------------------------------------------
# lazy-field G1 set aggregation (the verify_signature_sets batch boundary)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("levels",))
def _g1_tree_reduce_segmented(points, levels: int):
    """(S, 2^levels, 3, 24) → (S, 3, 24): the XOR-fold point sum along
    axis 1 over the LAZY field — S independent aggregations in one
    program. The fast-compiling twin of ops/g1._tree_reduce_segmented:
    the strict-field fold costs ~130s of cold XLA compile (its
    compare-and-subtract canonicalization chains are what fql exists to
    avoid); this one reuses the pairing's lazy adds and compiles in
    seconds."""
    width = points.shape[1]
    idx = jnp.arange(width)

    def level(k, pts):
        bit = jnp.left_shift(jnp.int32(1), k)
        summed = _g1_add(_env(pts), _env(pts[:, idx ^ bit]))
        keep = (idx & bit) == 0
        return jnp.where(
            keep[None, :, None, None], _clamp(summed), jnp.zeros_like(pts)
        )

    return jax.lax.fori_loop(0, levels, level, points)[:, 0]


def g1_sum_sets(
    raw_sets: "list[list[bytes]]", sharding=None
) -> "list[tuple[bytes, bool]]":
    """S independent G1 point sums on device over the lazy field:
    raw96 affine inputs (all-zero = infinity), (raw96, is_inf) outputs.
    Sets pad to the widest set (power of two) with infinity lanes; pass
    ``sharding`` (a NamedSharding over the set axis) to distribute the
    batch over a mesh before the fold."""
    import jax as _jax

    _jax.config.update("jax_enable_x64", True)
    if not raw_sets:
        return []
    widest = max(max(len(s) for s in raw_sets), 1)
    width = 1 << (widest - 1).bit_length() if widest > 1 else 1
    flat: list[bytes] = []
    live = np.zeros((len(raw_sets), width), np.bool_)
    for i, s in enumerate(raw_sets):
        flat.extend(s)
        flat.extend([b"\x00" * 96] * (width - len(s)))
        for j, raw in enumerate(s):
            live[i, j] = any(raw)
    x, y = g1_affine_from_raw(flat)
    one = np.asarray(fql.to_mont_cols(1))
    z = jnp.asarray(
        live.reshape(-1)[:, None] * one[None, :]
    )  # z=1 live, z=0 infinity
    batch = jnp.stack([x.arr, y.arr, z], axis=-2).reshape(
        len(raw_sets), width, 3, 24
    )
    if sharding is not None:
        (batch,) = _obs.h2d_put("ops.pairing.g1_sum_sets", (batch,), sharding)
    sums = _g1_tree_reduce_segmented(batch, (width - 1).bit_length())
    # host export: R'-Montgomery columns → canonical ints → affine bytes
    ints = fql.from_mont_ints(np.asarray(sums).reshape(len(raw_sets) * 3, 24))
    out: "list[tuple[bytes, bool]]" = []
    p = fql.P_INT
    for s in range(len(raw_sets)):
        xi, yi, zi = ints[3 * s], ints[3 * s + 1], ints[3 * s + 2]
        if zi == 0:
            out.append((b"\x00" * 96, True))
            continue
        z_inv = pow(zi, -1, p)
        z2 = (z_inv * z_inv) % p
        ax = (xi * z2) % p
        ay = (yi * z2 * z_inv) % p
        out.append((ax.to_bytes(48, "big") + ay.to_bytes(48, "big"), False))
    return out


def batch_verify_device(
    pk_raws: "list[bytes]",
    h_raws: "list[bytes]",
    sig_raws: "list[bytes]",
    scalars: "list[int]",
) -> bool:
    """The RLC batch verdict, device-shaped:

        Π e([r_i]·pk_i, H_i) · e(−G, Σ [r_i]·sig_i)  ==  1

    pk_raws: per-set aggregated pubkeys (raw96 affine, non-identity —
    the caller rejects identity aggregates, as the host batch does);
    h_raws: per-set message hash points (raw192 affine, hash_to_g2
    output — never infinity); sig_raws: per-set signatures (raw192
    affine); scalars: per-set nonzero 128-bit blinders.

    All O(N) group work — blinder multiplications, the signature sum,
    the N Miller loops, the Fq12 product tree — runs on device; the one
    extra pair and the final exponentiation verdict are the native
    backend's."""
    from ..native import bls as native_bls

    n = len(pk_raws)
    assert n and len(h_raws) == n and len(sig_raws) == n and len(scalars) == n

    # pad to the next power of two so the jitted kernels see log2-many
    # shapes: pk/H lanes pad with generator points and blinder 1 (their
    # Miller values are sliced off before the product); sig lanes pad
    # with blinder 0, whose scalar mult is the identity — the branchless
    # sum skips it
    g1f, g2f = _generator_raws()
    pk_padded = _pad_pow2(pk_raws, g1f)
    h_padded = _pad_pow2(h_raws, g2f)
    sig_padded = _pad_pow2(sig_raws, g2f)
    pk_scalars = _pad_pow2(list(scalars), 1)
    sig_scalars = list(scalars) + [0] * (len(pk_padded) - n)

    # raw bytes to R'-Montgomery columns on the device: three uploads and
    # the eager field conversions, op by op, before any batched kernel
    with trace.span("pairing.convert", sets=n, lanes=len(pk_padded)):
        pk_jac = _g1_jac_from_affine_raws(pk_padded)
        xq, yq = g2_affine_from_raw(h_padded)
        sx, sy = g2_affine_from_raw(sig_padded)
        one2 = jnp.broadcast_to(
            jnp.asarray(
                np.stack([fql.to_mont_cols(1), np.zeros(24, np.uint64)])
            ),
            sy.arr.shape,
        )
        sig_jac = _env(jnp.stack([sx.arr, sy.arr, one2], axis=-3))

    pk_blinded = g1_mul_batched(pk_jac, pk_scalars, bits=128)
    xp, yp = _g1_jacobian_to_affine(pk_blinded.arr)

    sig_sum = g2_sum_points(g2_mul_batched(sig_jac, sig_scalars, bits=128))
    s_raw, s_inf = _g2_point_to_raw(sig_sum)

    fs = miller_loop_batched(xp, yp, xq.arr, yq.arr)[:n]
    f_total = fp12_product(fs)
    return finalize_verdict(f_total, s_raw, s_inf)


def finalize_verdict(f_total, s_raw: bytes, s_inf: bool) -> bool:
    """Close an RLC batch from its device partials: multiply the Fq12
    Miller product by the extra pair e(−G, Σ [r_i]·sig_i) and ask the
    native backend for the final-exponentiation verdict. Shared by the
    single-device route above and the mesh-sharded route
    (parallel/pairing.py)."""
    from ..native import bls as native_bls

    if not s_inf:
        f_extra_ints = fq12.fp12_to_ints(
            miller_loop_batched(
                *(v.arr for v in g1_affine_from_raw([_neg_g1_generator_raw()])),
                *(v.arr for v in g2_affine_from_raw([s_raw])),
            )[0]
        )
        # combine on host via the native fp12 handoff (one multiply's worth
        # of work either way; avoids another device dispatch)
        f_ints = fq12.fp12_to_ints(f_total)
        from ..crypto.fields import Fq, Fq2, Fq6, Fq12

        def lift(vals):
            def f2(i):
                return Fq2(Fq(vals[2 * i]), Fq(vals[2 * i + 1]))
            return Fq12(Fq6(f2(0), f2(1), f2(2)), Fq6(f2(3), f2(4), f2(5)))

        prod = lift(f_ints) * lift(f_extra_ints)
        out = []
        for c6 in (prod.c0, prod.c1):
            for c2 in (c6.c0, c6.c1, c6.c2):
                out += [c2.c0.n, c2.c1.n]
        f_final_ints = out
    else:
        f_final_ints = fq12.fp12_to_ints(f_total)
    raw576 = b"".join(v.to_bytes(48, "big") for v in f_final_ints)
    return native_bls.fp12_final_exp_is_one(raw576)