"""Native C++ BLS backend vs pure-Python oracle parity.

The two implementations are independent (Montgomery-limb C++ vs bigint
Python); agreement on randomized corpora and edge cases is the correctness
anchor for both — the same role the blst-vs-spec vectors play for the
reference (spec-tests/runners/bls.rs).
"""

import secrets

import pytest

from ethereum_consensus_tpu.crypto import bls
from ethereum_consensus_tpu.crypto.curves import (
    G1_GENERATOR,
    G2_GENERATOR,
    G1Point,
    G2Point,
)
from ethereum_consensus_tpu.crypto.hash_to_curve import ETH_DST, hash_to_g2
from ethereum_consensus_tpu.error import InvalidPublicKeyError, InvalidSignatureError
from ethereum_consensus_tpu.native import bls as native_bls

pytestmark = pytest.mark.skipif(
    not native_bls.available(), reason="no C++ toolchain for the native backend"
)


def force_backend(name):
    bls._BACKEND = name


@pytest.fixture(autouse=True)
def restore_backend():
    yield
    bls._BACKEND = None


def test_native_is_default_when_available():
    bls._BACKEND = None
    assert bls.backend_name() == "native"


def test_hash_to_g2_parity():
    for msg in [b"", b"abc", b"a" * 200, secrets.token_bytes(73)]:
        expected = hash_to_g2(msg).serialize()
        assert native_bls.hash_to_g2_compressed(msg, ETH_DST) == expected


def test_sign_and_pk_parity():
    sk = bls.SecretKey(0xDEADBEEF)
    force_backend("python")
    pk_py = sk.public_key().to_bytes()
    sig_py = sk.sign(b"message").to_bytes()
    force_backend("native")
    assert sk.public_key().to_bytes() == pk_py
    assert sk.sign(b"message").to_bytes() == sig_py


def test_verify_verdict_parity_on_corpus():
    sk = bls.SecretKey(7)
    pk = sk.public_key()
    msg = b"\x42" * 32
    sig = sk.sign(msg)
    wrong_sig = bls.SecretKey(8).sign(msg)
    cases = [
        (pk, msg, sig, True),
        (pk, b"\x43" * 32, sig, False),
        (pk, msg, wrong_sig, False),
    ]
    for public_key, message, signature, expected in cases:
        force_backend("native")
        assert bls.verify_signature(public_key, message, signature) is expected
        force_backend("python")
        assert bls.verify_signature(public_key, message, signature) is expected


def test_infinity_pubkey_never_verifies():
    sk = bls.SecretKey(3)
    sig = sk.sign(b"m")
    inf_pk = bls.PublicKey(G1Point.infinity())
    force_backend("native")
    assert bls.verify_signature(inf_pk, b"m", sig) is False
    force_backend("python")
    assert bls.verify_signature(inf_pk, b"m", sig) is False


def test_infinity_signature_never_verifies():
    sk = bls.SecretKey(3)
    pk = sk.public_key()
    inf_sig = bls.Signature(G2Point.infinity())
    assert bls.verify_signature(pk, b"m", inf_sig) is False


def test_parse_rejections_match():
    # non-subgroup G2 x-coordinate: take a curve point NOT in the r-subgroup.
    # Easiest construction: tweak a valid compressed sig until decode fails
    # identically under both backends.
    sk = bls.SecretKey(11)
    sig = bytearray(sk.sign(b"x").to_bytes())
    sig[95] ^= 1
    native_exc = python_exc = None
    try:
        force_backend("native")
        bls.Signature.from_bytes(bytes(sig))
    except InvalidSignatureError as e:
        native_exc = True
    try:
        force_backend("python")
        bls.Signature.from_bytes(bytes(sig))
    except InvalidSignatureError as e:
        python_exc = True
    assert native_exc == python_exc

    bad_pk = bytearray(sk.public_key().to_bytes())
    bad_pk[0] &= 0x7F  # drop compression flag
    for backend in ("native", "python"):
        force_backend(backend)
        with pytest.raises(InvalidPublicKeyError):
            bls.PublicKey.from_bytes(bytes(bad_pk))
    # infinity pubkey encoding rejected by both
    inf = bytes([0xC0]) + bytes(47)
    for backend in ("native", "python"):
        force_backend(backend)
        with pytest.raises(InvalidPublicKeyError):
            bls.PublicKey.from_bytes(inf)


def test_aggregate_parity():
    sks = [bls.SecretKey(i + 1) for i in range(4)]
    msg = b"\x99" * 32
    sigs = [sk.sign(msg) for sk in sks]
    pks = [sk.public_key() for sk in sks]
    force_backend("native")
    agg_native = bls.aggregate(sigs).to_bytes()
    pk_agg_native = bls.eth_aggregate_public_keys(pks).to_bytes()
    assert bls.fast_aggregate_verify(pks, msg, bls.aggregate(sigs))
    force_backend("python")
    assert bls.aggregate(sigs).to_bytes() == agg_native
    assert bls.eth_aggregate_public_keys(pks).to_bytes() == pk_agg_native


def test_eth_fast_aggregate_verify_infinity_rule():
    inf_sig = bls.Signature(G2Point.infinity())
    for backend in ("native", "python"):
        force_backend(backend)
        assert bls.eth_fast_aggregate_verify([], b"m", inf_sig) is True
        assert bls.eth_fast_aggregate_verify([], b"m", bls.SecretKey(2).sign(b"m")) is False


def test_aggregate_verify_distinct_messages():
    sks = [bls.SecretKey(i + 5) for i in range(3)]
    pks = [sk.public_key() for sk in sks]
    msgs = [bytes([i]) * 32 for i in range(3)]
    agg = bls.aggregate([sk.sign(m) for sk, m in zip(sks, msgs)])
    force_backend("native")
    assert bls.aggregate_verify(pks, msgs, agg) is True
    assert bls.aggregate_verify(pks, list(reversed(msgs)), agg) is False
    assert bls.aggregate_verify(pks, msgs[:2], agg) is False
    assert bls.aggregate_verify([], [], agg) is False


def test_batch_verify_all_valid_and_attribution():
    sks = [bls.SecretKey(i + 1) for i in range(6)]
    msgs = [bytes([i]) * 32 for i in range(3)]
    sets = []
    for i, m in enumerate(msgs):
        keys = sks[2 * i : 2 * i + 2]
        agg = bls.aggregate([k.sign(m) for k in keys])
        sets.append(bls.SignatureSet([k.public_key() for k in keys], m, agg))
    force_backend("native")
    assert bls.verify_signature_sets(sets) == [True, True, True]
    # corrupt the middle set's signature -> exact attribution
    bad = bls.SignatureSet(sets[1].public_keys, sets[1].message, sets[0].signature)
    verdicts = bls.verify_signature_sets([sets[0], bad, sets[2]])
    assert verdicts == [True, False, True]
    assert bls.verify_signature_sets([]) == []


def test_batch_verify_empty_keyset_is_invalid():
    sk = bls.SecretKey(9)
    good = bls.SignatureSet([sk.public_key()], b"\x01" * 32, sk.sign(b"\x01" * 32))
    empty = bls.SignatureSet([], b"\x02" * 32, sk.sign(b"\x02" * 32))
    force_backend("native")
    assert bls.verify_signature_sets([good, empty]) == [True, False]


def test_msm_matches_oracle():
    pts = [G1_GENERATOR * (i + 2) for i in range(17)]
    scalars = [secrets.randbelow(2**255) for _ in range(17)]
    expected = G1Point.infinity()
    for p, s in zip(pts, scalars):
        expected = expected + p * s
    raws = b""
    for p in pts:
        x, y = p.to_affine()
        raws += x.n.to_bytes(48, "big") + y.n.to_bytes(48, "big")
    out, is_inf = native_bls.g1_msm(
        raws, b"".join(s.to_bytes(32, "big") for s in scalars), len(pts)
    )
    ex, ey = expected.to_affine()
    assert not is_inf
    assert out == ex.n.to_bytes(48, "big") + ey.n.to_bytes(48, "big")

    # G2 MSM
    qts = [G2_GENERATOR * (i + 2) for i in range(5)]
    qscalars = [secrets.randbelow(2**200) for _ in range(5)]
    qexpected = G2Point.infinity()
    for p, s in zip(qts, qscalars):
        qexpected = qexpected + p * s
    qraws = b""
    for p in qts:
        x, y = p.to_affine()
        qraws += (x.c0.n.to_bytes(48, "big") + x.c1.n.to_bytes(48, "big")
                  + y.c0.n.to_bytes(48, "big") + y.c1.n.to_bytes(48, "big"))
    qout, q_inf = native_bls.g2_msm(
        qraws, b"".join(s.to_bytes(32, "big") for s in qscalars), len(qts)
    )
    qx, qy = qexpected.to_affine()
    assert not q_inf
    assert qout == (qx.c0.n.to_bytes(48, "big") + qx.c1.n.to_bytes(48, "big")
                    + qy.c0.n.to_bytes(48, "big") + qy.c1.n.to_bytes(48, "big"))


def test_pairing_product_raw_bilinearity():
    def g1raw(p):
        x, y = p.to_affine()
        return (x.n.to_bytes(48, "big") + y.n.to_bytes(48, "big"), False)

    def g2raw(p):
        x, y = p.to_affine()
        return (x.c0.n.to_bytes(48, "big") + x.c1.n.to_bytes(48, "big")
                + y.c0.n.to_bytes(48, "big") + y.c1.n.to_bytes(48, "big"), False)

    P, Q = G1_GENERATOR, G2_GENERATOR
    assert native_bls.pairing_product_is_one_raw(
        [g1raw(P * 3), g1raw(-(P * 15))], [g2raw(Q * 5), g2raw(Q)]
    )
    assert not native_bls.pairing_product_is_one_raw([g1raw(P)], [g2raw(Q)])
    # infinity entries are skipped (empty product == 1)
    assert native_bls.pairing_product_is_one_raw(
        [(bytes(96), True)], [(bytes(192), True)]
    )


def _off_subgroup_encodings(point_cls, field_from_counter, count):
    """Deterministic compressed encodings of curve points OUTSIDE the
    order-r subgroup.

    Incremental x-search over x = field_from_counter(1, 2, ...) — the
    first handful of curve points found this way are off-subgroup (the
    subgroup has huge index in the full curve group: cofactor ~2^125 for
    E(Fq), ~2^250 for E'(Fq2)), and `in_subgroup()` pins that down
    exactly, so the corpus is fixed forever. `serialize()` only emits the
    compressed x + flag bits, so it encodes off-subgroup points fine."""
    out = []
    a = 0
    while len(out) < count:
        a += 1
        x = field_from_counter(a)
        y = (x.square() * x + point_cls.B).sqrt()
        if y is None:
            continue
        point = point_cls.from_affine(x, y)
        assert not point.in_subgroup(), f"x={a} unexpectedly lies in the subgroup"
        out.append(point.serialize())
    return out


def test_g2_fast_subgroup_check_rejects_off_subgroup_points():
    """The ψ-criterion subgroup check (validated against the order
    multiplication at first use) must still reject curve points OUTSIDE
    G2. Candidates are constructed deterministically (incremental
    x-search) so the test is reproducible run-to-run."""
    from ethereum_consensus_tpu.crypto.fields import Fq, Fq2

    for cand in _off_subgroup_encodings(G2Point, lambda a: Fq2(Fq(a), Fq(0)), 3):
        rc, _raw, is_inf = native_bls.g2_decompress(cand, check_subgroup=False)
        assert rc == 0 and not is_inf, "constructed curve point failed to decompress"
        rc2, _, _ = native_bls.g2_decompress(cand, check_subgroup=True)
        assert rc2 == -6, f"off-subgroup point accepted (rc={rc2})"


def test_g1_fast_subgroup_check_rejects_off_subgroup_points():
    """GLV-criterion G1 membership must reject curve points outside G1
    (deterministic incremental x-search candidates)."""
    from ethereum_consensus_tpu.crypto.fields import Fq

    for cand in _off_subgroup_encodings(G1Point, Fq, 3):
        rc, _raw, is_inf = native_bls.g1_decompress(cand, check_subgroup=False)
        assert rc == 0 and not is_inf, "constructed curve point failed to decompress"
        rc2, _, _ = native_bls.g1_decompress(cand, check_subgroup=True)
        assert rc2 == -6, f"off-subgroup G1 point accepted (rc={rc2})"


class TestFp8Engine:
    """The eight-wide AVX-512 IFMA field engine (native fp8_*): active
    only after an init self-check; its batched sqrt chains must agree
    with the scalar field on every family (deep randomized cross-check
    lives in C so it exercises the exact production kernels)."""

    def test_selftest_clean(self):
        from ethereum_consensus_tpu.native import bls as nb

        if not nb.available():
            pytest.skip("native backend unavailable")
        # rc 0 = all families agree (also the required answer when the
        # host has no IFMA and the engine reports inactive)
        assert nb.fp8_selftest(seed=7, rounds=100) == 0

    def test_active_implies_selfchecked(self):
        from ethereum_consensus_tpu.native import bls as nb

        if not nb.available():
            pytest.skip("native backend unavailable")
        # fp8_active is allowed to be False (non-IFMA host) but must be a
        # clean bool either way
        assert nb.fp8_active() in (True, False)


class TestBatchPhasesSoundness:
    """The phased RLC batch (eight-wide decompression, hash-to-G2,
    blinder mults, Miller lanes) must agree with per-set verification on
    randomized valid/invalid mixes — a batch may never accept a mix
    containing a bad set, and must accept any all-valid mix."""

    def test_random_mixes_agree_with_per_set_verdicts(self):
        import random

        from ethereum_consensus_tpu.native import bls as nb

        if not nb.available():
            pytest.skip("native backend unavailable")
        rng = random.Random(0xEC)
        dst = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"
        sks = [int.to_bytes(40_000 + i, 32, "big") for i in range(24)]
        pks = [nb.sk_to_pk(sk) for sk in sks]
        raws = [nb.g1_decompress(pk, check_subgroup=False)[1] for pk in pks]
        for trial in range(6):
            n_sets = rng.choice([3, 17, 24])  # below/above the x8 cutovers
            sets = []
            per_set_ok = []
            for i in range(n_sets):
                k = rng.randrange(1, 4)
                idxs = [rng.randrange(len(sks)) for _ in range(k)]
                msg = bytes([trial, i]) * 16
                sigs = [nb.sign(sks[j], msg, dst) for j in idxs]
                rc, agg = nb.aggregate_signatures(sigs)
                assert rc == 0
                valid = rng.random() < 0.8
                if not valid:
                    corrupt = rng.choice(["msg", "sig"])
                    if corrupt == "msg":
                        msg = bytes(32)
                    else:
                        # a different set's aggregate: wrong but well-formed
                        other = nb.sign(sks[0], b"other" * 6, dst)
                        agg = other
                sets.append(([raws[j] for j in idxs], msg, agg))
                ok = all(
                    nb.fast_aggregate_verify_raw(
                        [raws[j] for j in idxs], msg, agg, dst,
                        assume_valid=False,
                    ) == 1
                    for _ in range(1)
                )
                per_set_ok.append(ok)
            scalars = [int.to_bytes(rng.getrandbits(128) | 1, 16, "big")
                       for _ in range(n_sets)]
            got = nb.batch_verify_raw(sets, dst, scalars)
            assert got == all(per_set_ok), (trial, per_set_ok, got)


class TestPreparedMsmAndFr:
    """Edge semantics of the fixed-base MSM handle and the native Fr
    barycentric helpers."""

    def test_prepared_msm_matches_plain(self):
        import secrets

        from ethereum_consensus_tpu.native import bls as nb

        if not nb.available():
            pytest.skip("native backend unavailable")
        gen = nb.g1_generator_raw()
        pts = []
        for i in range(40):
            raw, _ = nb.g1_mul_raw(gen, False, (i * 31 + 5).to_bytes(32, "big"))
            pts.append(raw)
        scal = b"".join(secrets.token_bytes(31).rjust(32, b"\0") for _ in range(40))
        want, winf = nb.g1_msm(b"".join(pts), scal, 40)
        pre = nb.PreparedMsm(b"".join(pts), 40, window_bits=6)
        got, ginf = pre.run(scal)
        assert (got, ginf) == (want, winf)

    def test_prepared_msm_rejects_wrong_length(self):
        import secrets

        from ethereum_consensus_tpu.native import bls as nb

        if not nb.available():
            pytest.skip("native backend unavailable")
        gen = nb.g1_generator_raw()
        pre = nb.PreparedMsm(gen, 1, window_bits=4)
        ok, _ = pre.run(secrets.token_bytes(31).rjust(32, b"\0"))
        assert len(ok) == 96

    def test_fr_eval_rejects_non_canonical(self):
        from ethereum_consensus_tpu.native import bls as nb

        if not nb.available():
            pytest.skip("native backend unavailable")
        bad = b"\xff" * 32  # >= r
        with pytest.raises(nb.NativeBlsError):
            nb.fr_eval_poly(bad, bad, 1, b"\x00" * 32)


def test_msm_same_point_annihilating_digits():
    """Regression: a pairing-tree round whose pairs ALL annihilate (same
    point under opposite signed digits — reachable with duplicated MSM
    inputs at small sizes) must still cancel the bucket instead of
    leaking its first item. Caught by tests/soak_native.py."""
    import random

    from ethereum_consensus_tpu.native import bls as nb

    if not nb.available():
        pytest.skip("native backend unavailable")
    gen = nb.g1_generator_raw()
    p, _ = nb.g1_mul_raw(gen, False, (424242).to_bytes(32, "big"))
    rng = random.Random(10)
    for n in (2, 3, 8, 16):
        pts = [p] * n
        scs = [rng.randbytes(31).rjust(32, b"\0") for _ in range(n)]
        got, got_inf = nb.g1_msm(b"".join(pts), b"".join(scs), n)
        acc, acc_inf = None, True
        for pt, s in zip(pts, scs):
            m, mi = nb.g1_mul_raw(pt, False, s)
            if acc_inf:
                acc, acc_inf = m, mi
            else:
                acc, acc_inf = nb.g1_add_raw(acc, acc_inf, m, mi)
        assert got_inf == acc_inf and (got_inf or got == acc), n


# -- eth_aggregate_public_keys from the decompressed-pubkey cache ---------------

_AGG_COUNTERS = ("from_cache", "decompressed")
# (0, 2) is on the curve and outside the order-r subgroup
_OUTSIDE_THE_SUBGROUP = bytes([0x80]) + bytes(47)


def _agg_counts():
    from ethereum_consensus_tpu.telemetry import metrics

    return {
        name: metrics.counter(f"bls.aggregate_pubkeys.{name}").value()
        for name in _AGG_COUNTERS
    }


def _agg_moved(before):
    after = _agg_counts()
    return {name: after[name] - before[name] for name in _AGG_COUNTERS}


@pytest.fixture(scope="module")
def distinct_keys():
    return [bls.SecretKey(90_001 + i).public_key().to_bytes() for i in range(512)]


def _negated(key: bytes) -> bytes:
    # the sign flag picks the other root y: the compressed encoding of -P
    return bytes([key[0] ^ 0x20]) + key[1:]


def _aggregate_case(kind: str, n: int, base: list) -> list:
    if kind == "distinct":
        return base[:n]
    if kind == "repeated":
        # the serial chain's second add and lane 1's first eight-wide add
        # both meet their own partial sum: the doubling case
        return [base[0]] + [base[i % 8] for i in range(n - 1)]
    if kind == "negated_neighbour":
        # k, -k side by side: a partial sum (and, at even n, the whole
        # sum) is the identity
        keys = []
        for i in range(n):
            keys.append(_negated(keys[-1]) if i % 2 else base[i])
        return keys
    if kind == "negated_lane":
        # every second block of eight negates the block before it: each
        # eight-wide lane's running sum returns to the identity
        return [
            _negated(base[i - 8]) if (i // 8) % 2 else base[i] for i in range(n)
        ]
    raise AssertionError(kind)


@pytest.mark.parametrize("n", [1, 7, 31, 32, 33, 512])
@pytest.mark.parametrize(
    "kind", ["distinct", "repeated", "negated_neighbour", "negated_lane"]
)
def test_cached_aggregate_equals_the_compressed_call(kind, n, distinct_keys):
    force_backend("native")
    keys = _aggregate_case(kind, n, distinct_keys)
    public_keys = [bls.PublicKey.from_bytes(key) for key in keys]
    assert all(key in bls._RAW_PK_CACHE for key in keys)
    rc, want = native_bls.aggregate_public_keys(keys)
    assert rc == 0
    before = _agg_counts()
    got = bls.eth_aggregate_public_keys(public_keys).to_bytes()
    assert got == want
    assert _agg_moved(before) == {"from_cache": 1, "decompressed": 0}
    if kind == "negated_neighbour" and n % 2 == 0:
        assert got == bytes([0xC0]) + bytes(47)  # the identity


def _deferred_keys(keys: list, filler: str) -> list:
    """Keys parsed as the registry parses them, their affine form filled
    without the subgroup check, none of them in the validated cache."""
    for key in keys:
        bls._RAW_PK_CACHE.pop(key, None)
    public_keys = [bls.PublicKey.from_validated_bytes(key) for key in keys]
    if filler == "warm_raw_keys":
        bls.warm_raw_keys(public_keys)
    else:
        for pk in public_keys:
            pk.raw_uncompressed()
    assert all(pk._raw is not None for pk in public_keys)
    assert not any(key in bls._RAW_PK_CACHE for key in keys)
    return public_keys


@pytest.mark.parametrize(
    "case",
    [
        "raw_uncompressed",
        "warm_raw_keys",
        "outside_the_subgroup.raw_uncompressed",
        "outside_the_subgroup.warm_raw_keys",
        "evicted",
        "empty",
    ],
)
def test_aggregate_falls_back_to_the_compressed_call(case, distinct_keys):
    force_backend("native")
    keys = [bls.SecretKey(70_001 + i).public_key().to_bytes() for i in range(9)]
    before = _agg_counts()
    if case == "empty":
        with pytest.raises(InvalidPublicKeyError, match="zero public keys"):
            bls.eth_aggregate_public_keys([])
        assert _agg_moved(before) == {"from_cache": 0, "decompressed": 0}
        return
    if case.startswith("outside_the_subgroup"):
        keys[3] = _OUTSIDE_THE_SUBGROUP
        public_keys = _deferred_keys(keys, case.split(".")[1])
        with pytest.raises(InvalidPublicKeyError) as raised:
            bls.eth_aggregate_public_keys(public_keys)
        assert str(raised.value) == native_bls.decode_error_message(-6)
        assert _agg_moved(before) == {"from_cache": 0, "decompressed": 1}
        return
    if case == "evicted":
        public_keys = [bls.PublicKey.from_bytes(key) for key in keys]
        bls._RAW_PK_CACHE.pop(keys[5])
        assert public_keys[5]._raw is not None
    else:
        # the first key validated and cached, the other eight deferred
        # (warm_raw_keys fills nothing below eight keys)
        public_keys = [bls.PublicKey.from_bytes(keys[0])]
        public_keys += _deferred_keys(keys[1:], case)
    rc, want = native_bls.aggregate_public_keys(keys)
    assert rc == 0
    assert bls.eth_aggregate_public_keys(public_keys).to_bytes() == want
    assert _agg_moved(before) == {"from_cache": 0, "decompressed": 1}


def test_raw_aggregate_refuses_the_identity_and_off_curve_points(distinct_keys):
    raws = [native_bls.g1_decompress(k, check_subgroup=True)[1] for k in distinct_keys[:40]]
    for n in (3, 40):  # the serial chain and the eight-wide sum
        assert native_bls.aggregate_public_keys_raw(raws[:n])[0] == 0
        for bad in (bytes(96), raws[1][:95] + bytes([raws[1][95] ^ 1])):
            assert native_bls.aggregate_public_keys_raw(raws[: n - 1] + [bad])[0] == -5
    assert native_bls.aggregate_public_keys_raw([])[0] == -1
    with pytest.raises(ValueError):
        native_bls.aggregate_public_keys_raw(raws[:2] + [raws[2][:48]])
