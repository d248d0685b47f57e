"""Device batched pairing (ops/{fql,fq2,fq12,pairing}.py) vs the native
C++ backend and the pure-Python oracle — exact parity on canonical
exports.

The device Miller loop mirrors native/bls12_381.cpp's fused steps, so
per-pair Miller values must match ec_miller_loop_raw EXACTLY; the final
exponentiation verdict then closes the loop on real signatures."""

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from ethereum_consensus_tpu.crypto.curves import (  # noqa: E402
    G1_GENERATOR,
    G2_GENERATOR,
)
from ethereum_consensus_tpu.crypto.fields import Fq, Fq2, Fq6, Fq12  # noqa: E402
from ethereum_consensus_tpu.native import bls as native_bls  # noqa: E402
from ethereum_consensus_tpu.ops import fq2, fq12, fql, pairing  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native_bls.available(), reason="no C++ toolchain for the native backend"
)


def _g1_raw(p):
    x, y = p.to_affine()
    return x.n.to_bytes(48, "big") + y.n.to_bytes(48, "big")


def _g2_raw(p):
    x, y = p.to_affine()
    return (x.c0.n.to_bytes(48, "big") + x.c1.n.to_bytes(48, "big")
            + y.c0.n.to_bytes(48, "big") + y.c1.n.to_bytes(48, "big"))


def _fq12_from_ints(vals):
    def f2(c0, c1):
        return Fq2(Fq(c0), Fq(c1))
    return Fq12(
        Fq6(f2(vals[0], vals[1]), f2(vals[2], vals[3]), f2(vals[4], vals[5])),
        Fq6(f2(vals[6], vals[7]), f2(vals[8], vals[9]), f2(vals[10], vals[11])),
    )


def _ints_from_raw576(raw):
    return [int.from_bytes(raw[i * 48:(i + 1) * 48], "big") for i in range(12)]


# ---------------------------------------------------------------------------
# field towers
# ---------------------------------------------------------------------------


def test_fq2_ops_match_host_field():
    rng = np.random.default_rng(7)
    vals = [(int(rng.integers(1 << 62)) << 300) ^ int(rng.integers(1 << 62))
            for _ in range(4)]
    a = Fq2(Fq(vals[0]), Fq(vals[1]))
    b = Fq2(Fq(vals[2]), Fq(vals[3]))
    import jax.numpy as jnp

    am = fql.LV(jnp.asarray(np.stack([fq2.to_lv(a.c0.n, a.c1.n).arr])),
                fql._CANON_VMAX, 1 << 16)
    bm = fql.LV(jnp.asarray(np.stack([fq2.to_lv(b.c0.n, b.c1.n).arr])),
                fql._CANON_VMAX, 1 << 16)

    def out(lv):
        return fq2.from_lv_ints(fql.LV(lv.arr[0], lv.vmax, lv.cmax))

    assert out(fq2.mul(am, bm)) == ((a * b).c0.n, (a * b).c1.n)
    assert out(fq2.square(am)) == (a.square().c0.n, a.square().c1.n)
    xi = Fq2(Fq(1), Fq(1))
    assert out(fq2.mul_by_xi(am)) == ((a * xi).c0.n, (a * xi).c1.n)
    inv = a.inverse()
    assert out(fq2.inv(am)) == (inv.c0.n, inv.c1.n)
    assert out(fq2.sub(am, bm)) == ((a - b).c0.n, (a - b).c1.n)


def test_fp12_mul_matches_host_field():
    rng = np.random.default_rng(8)
    a_vals = [int(rng.integers(1, 1 << 63)) for _ in range(12)]
    b_vals = [int(rng.integers(1, 1 << 63)) for _ in range(12)]
    a_host = _fq12_from_ints(a_vals)
    b_host = _fq12_from_ints(b_vals)
    import jax.numpy as jnp

    def batch1(lv):
        return fql.LV(jnp.asarray(np.stack([np.asarray(lv.arr)])), lv.vmax, lv.cmax)

    a_dev = batch1(fq12.fp12_from_ints(a_vals))
    b_dev = batch1(fq12.fp12_from_ints(b_vals))

    got_mul = fq12.fp12_to_ints(
        fql.LV(fq12.fp12_mul(a_dev, b_dev).arr[0], 1, 1)
    )
    assert _fq12_from_ints(got_mul) == a_host * b_host

    got_sqr = fq12.fp12_to_ints(fql.LV(fq12.fp12_sqr(a_dev).arr[0], 1, 1))
    assert _fq12_from_ints(got_sqr) == a_host.square()


# ---------------------------------------------------------------------------
# G2 device point ops
# ---------------------------------------------------------------------------


def test_g2_sum_and_mul_match_host_points():
    import jax.numpy as jnp

    pts = [G2_GENERATOR * (i + 2) for i in range(5)]
    raws = [_g2_raw(p) for p in pts]
    xq, yq = pairing.g2_affine_from_raw(raws)
    one2 = jnp.broadcast_to(
        jnp.asarray(np.stack([fql.to_mont_cols(1), np.zeros(24, np.uint64)])),
        yq.arr.shape,
    )
    jac = pairing._env(jnp.stack([xq.arr, yq.arr, one2], axis=-3))

    def to_host_point(lv_arr):
        arr = np.asarray(lv_arr).reshape(3, 2, 24)
        comps = [fq2.from_lv_ints(fql.lv_canon(jnp.asarray(arr[i])))
                 for i in range(3)]
        from ethereum_consensus_tpu.crypto.curves import G2Point

        return G2Point(
            Fq2(Fq(comps[0][0]), Fq(comps[0][1])),
            Fq2(Fq(comps[1][0]), Fq(comps[1][1])),
            Fq2(Fq(comps[2][0]), Fq(comps[2][1])),
        )

    total = pairing.g2_sum_points(jac)
    expected = pts[0]
    for p in pts[1:]:
        expected = expected + p
    assert to_host_point(total.arr) == expected

    scalars = [3, 1 << 64, (1 << 127) - 5, 2, 7]
    mult = pairing.g2_mul_batched(jac, scalars, bits=128)
    for i, (p, s) in enumerate(zip(pts, scalars)):
        assert to_host_point(mult.arr[i]) == p * s, i


# ---------------------------------------------------------------------------
# the Miller loop itself
# ---------------------------------------------------------------------------


def test_device_miller_matches_native_bitwise():
    pairs = [
        (G1_GENERATOR, G2_GENERATOR),
        (G1_GENERATOR * 7, G2_GENERATOR * 11),
        (G1_GENERATOR * (2**100 + 3), G2_GENERATOR * 5),
    ]
    for p, q in pairs:
        g1r, g2r = _g1_raw(p), _g2_raw(q)
        native = _ints_from_raw576(native_bls.miller_loop_raw(g1r, g2r))
        device = pairing.miller_product_device([g1r], [g2r])
        assert device == native, "device Miller diverges from native"


def test_device_miller_product_matches_native_product():
    pairs = [(G1_GENERATOR * (i + 1), G2_GENERATOR * (2 * i + 3)) for i in range(5)]
    g1rs = [_g1_raw(p) for p, _ in pairs]
    g2rs = [_g2_raw(q) for _, q in pairs]
    native_prod = Fq12.one()
    for a, b in zip(g1rs, g2rs):
        native_prod = native_prod * _fq12_from_ints(
            _ints_from_raw576(native_bls.miller_loop_raw(a, b))
        )
    device = _fq12_from_ints(pairing.miller_product_device(g1rs, g2rs))
    assert device == native_prod


def test_device_pairing_verdict_on_real_signature():
    """e(pk, H(m)) · e(-G, sig) == 1 via device Miller + native final exp."""
    from ethereum_consensus_tpu.crypto import bls
    from ethereum_consensus_tpu.crypto.hash_to_curve import ETH_DST

    sk = bls.SecretKey(0xA11CE)
    msg = b"device pairing verdict"
    sig = sk.sign(msg)
    pk_raw = sk.public_key().raw_uncompressed()
    rc, sig_raw, _ = native_bls.g2_decompress(sig.to_bytes(), True)
    assert rc == 0
    h_compressed = native_bls.hash_to_g2_compressed(msg, ETH_DST)
    rc, h_raw, _ = native_bls.g2_decompress(h_compressed, False)
    assert rc == 0
    neg_gen = _g1_raw(-G1_GENERATOR)

    f = pairing.miller_product_device([pk_raw, neg_gen], [h_raw, sig_raw])
    raw576 = b"".join(v.to_bytes(48, "big") for v in f)
    assert native_bls.fp12_final_exp_is_one(raw576)

    h2 = native_bls.hash_to_g2_compressed(b"other message", ETH_DST)
    rc, h2_raw, _ = native_bls.g2_decompress(h2, False)
    f_bad = pairing.miller_product_device([pk_raw, neg_gen], [h2_raw, sig_raw])
    raw576 = b"".join(v.to_bytes(48, "big") for v in f_bad)
    assert not native_bls.fp12_final_exp_is_one(raw576)


def test_batch_verify_device_verdicts():
    """The full device RLC batch: valid batch accepts, tampered rejects."""
    import secrets

    from ethereum_consensus_tpu.crypto import bls
    from ethereum_consensus_tpu.crypto.hash_to_curve import ETH_DST

    sks = [bls.SecretKey(100 + i) for i in range(6)]
    pk_raws, h_raws, sig_raws = [], [], []
    for i, sk in enumerate(sks):
        msg = secrets.token_bytes(32)
        sig = sk.sign(msg)
        pk_raws.append(sk.public_key().raw_uncompressed())
        rc, sraw, _ = native_bls.g2_decompress(sig.to_bytes(), True)
        assert rc == 0
        sig_raws.append(sraw)
        rc, hraw, _ = native_bls.g2_decompress(
            native_bls.hash_to_g2_compressed(msg, ETH_DST), False
        )
        assert rc == 0
        h_raws.append(hraw)
    scalars = [1] + [int.from_bytes(secrets.token_bytes(16), "big") | 1
                     for _ in range(5)]
    assert pairing.batch_verify_device(pk_raws, h_raws, sig_raws, scalars)
    # tamper: swap two signatures
    bad = list(sig_raws)
    bad[1], bad[2] = bad[2], bad[1]
    assert not pairing.batch_verify_device(pk_raws, h_raws, bad, scalars)


def test_mont7r_redundant_inputs_match_fql():
    """mont7r — the routed MXU multiplier — takes the SAME redundant
    inputs as fql.mont (uint64 columns < 2^24, values < ~2^397) and must
    be column-exact against it; carry_norm must be value-exact."""
    import jax.numpy as jnp

    from ethereum_consensus_tpu.ops import fq8

    rng = np.random.default_rng(17)
    # redundant columns: up to 24 bits per column, values ~2^397
    a = jnp.asarray(rng.integers(0, 1 << 24, size=(16, 24), dtype=np.uint64))
    b = jnp.asarray(rng.integers(0, 1 << 24, size=(16, 24), dtype=np.uint64))
    want = np.asarray(fql.mont(a, b))
    got = np.asarray(fq8.mont7r(a, b))
    assert (want == got).all()
    # carry_norm: exact 16-bit columns preserving the integer value
    norm = np.asarray(fq8.carry_norm(a))
    assert (norm < (1 << 16)).all()
    for n in range(4):
        va = sum(int(c) << (16 * i) for i, c in enumerate(np.asarray(a[n])))
        vn = sum(int(c) << (16 * i) for i, c in enumerate(norm[n]))
        assert vn == va, n
    # canonical inputs too (the common mont-output-to-mont-input case)
    c = jnp.asarray(rng.integers(0, 1 << 16, size=(8, 24), dtype=np.uint64))
    d = jnp.asarray(rng.integers(0, 1 << 16, size=(8, 24), dtype=np.uint64))
    assert (np.asarray(fql.mont(c, d)) == np.asarray(fq8.mont7r(c, d))).all()


def test_mxu_multiplier_pairing_parity(cpu_mesh):
    """With EC_PAIRING_MULT=mxu the ENTIRE device pairing stack must
    produce the same Miller product and batch verdicts as the u64 path —
    run in a subprocess so the multiplier is set before any trace."""
    out = cpu_mesh(
        """
import os
os.environ["EC_PAIRING_MULT"] = "mxu"
import secrets

import jax

jax.config.update("jax_enable_x64", True)
import numpy as np

from ethereum_consensus_tpu.crypto import bls
from ethereum_consensus_tpu.native import bls as native_bls
from ethereum_consensus_tpu.ops import fql, pairing

assert fql.get_multiplier() == "mxu"
sks = [bls.SecretKey(i + 31) for i in range(4)]
pk_raws, h_raws, sig_raws = [], [], []
for i, sk in enumerate(sks):
    msg = b"q" * 31 + bytes([i])
    sig = sk.sign(msg)
    pk_raws.append(sk.public_key().raw_uncompressed())
    rc, raw, _ = native_bls.g2_decompress(
        native_bls.hash_to_g2_compressed(msg, bls.ETH_DST),
        check_subgroup=False,
    )
    assert rc == 0
    h_raws.append(raw)
    sig_raws.append(sig.raw_uncompressed())
scalars = [1, 5, 9, 13]
assert pairing.batch_verify_device(pk_raws, h_raws, sig_raws, scalars)
bad = list(sig_raws)
bad[1], bad[2] = bad[2], bad[1]
assert not pairing.batch_verify_device(pk_raws, h_raws, bad, scalars)
print("mxu-pairing-ok")
""",
        n_devices=1,
    )
    assert "mxu-pairing-ok" in out
