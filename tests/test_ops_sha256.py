"""Device SHA-256 + merkle kernels vs hashlib: bit-identical checks.

Runs on whatever backend jax has (the CPU under tier-1). The Pallas
kernel's two compressions are also run as plain jax.numpy, so the kernel
math is checked without TPU hardware; the kernel itself in interpreter
mode (tiling/grid included) takes >30min/test on a CPU-only backend, so
those tests skip there unless EC_RUN_INTERPRET_TESTS=1 opts in. That the
chip's compiler accepts the kernel is tests/test_chip_compile.py.
"""

import hashlib

import numpy as np
import pytest

import jax.numpy as jnp

from ethereum_consensus_tpu.ops.sha256 import (
    hash_level_bytes,
    sha256_64b_pallas,
    sha256_64b_xla,
)
from ethereum_consensus_tpu.ops.merkle import merkleize_chunks_device
from ethereum_consensus_tpu.ssz.merkle import merkleize_chunks


def _ref_hashes(msgs: bytes, n: int) -> np.ndarray:
    out = np.zeros((n, 8), dtype=np.uint32)
    for i in range(n):
        d = hashlib.sha256(msgs[i * 64 : (i + 1) * 64]).digest()
        out[i] = np.frombuffer(d, dtype=">u4").astype(np.uint32)
    return out


def _to_words(msgs: bytes, n: int) -> jnp.ndarray:
    return jnp.asarray(
        np.frombuffer(msgs, dtype=">u4").astype(np.uint32).reshape(n, 16).T
    )


@pytest.mark.parametrize("n", [1, 2, 7, 64])
def test_sha256_xla_matches_hashlib(n):
    rng = np.random.default_rng(n)
    msgs = rng.bytes(64 * n)
    got = np.asarray(sha256_64b_xla(_to_words(msgs, n)))
    assert (got.T == _ref_hashes(msgs, n)).all()


def test_sha256_xla_edge_patterns():
    for pattern in [b"\x00" * 64, b"\xff" * 64, bytes(range(64))]:
        got = np.asarray(sha256_64b_xla(_to_words(pattern, 1)))
        expect = np.frombuffer(
            hashlib.sha256(pattern).digest(), dtype=">u4"
        ).astype(np.uint32)
        assert (got[:, 0] == expect).all()


def test_pallas_kernel_math_matches_hashlib():
    """The kernel body's arithmetic (both unrolled compressions, the
    literal initial state and padding block) as plain jax.numpy."""
    from ethereum_consensus_tpu.ops import sha256

    n = 8
    msgs = np.random.default_rng(4).bytes(64 * n)
    state = sha256._compress_unrolled(
        sha256._initial_state(n), _to_words(msgs, n)
    )
    got = np.asarray(sha256._compress_unrolled(state, sha256._pad_block(n)))
    assert (got.T == _ref_hashes(msgs, n)).all()


@pytest.fixture
def interpret_mode():
    """Skip where the Pallas interpreter is too slow to be a test."""
    import os

    import jax

    if jax.default_backend() == "cpu" and not os.environ.get(
        "EC_RUN_INTERPRET_TESTS"
    ):
        pytest.skip(
            "pallas interpret-mode emulation is pathologically slow on a "
            "CPU-only backend (>30min/test); set EC_RUN_INTERPRET_TESTS=1 "
            "to run it anyway — the kernel math is covered above"
        )


def test_sha256_pallas_interpret_matches_hashlib(interpret_mode):
    n = 1024  # one tile
    rng = np.random.default_rng(0)
    msgs = rng.bytes(64 * n)
    got = np.asarray(sha256_64b_pallas(_to_words(msgs, n), interpret=True))
    assert (got.T == _ref_hashes(msgs, n)).all()


def test_sha256_pallas_interpret_multi_tile(interpret_mode):
    n = 2048  # two grid steps
    rng = np.random.default_rng(1)
    msgs = rng.bytes(64 * n)
    got = np.asarray(sha256_64b_pallas(_to_words(msgs, n), interpret=True))
    assert (got.T == _ref_hashes(msgs, n)).all()


def test_tpu_route_pads_every_width_into_the_kernel(monkeypatch):
    """On a TPU every width takes the Pallas kernel, padded to whole lane
    tiles and sliced back (control flow only: the kernel is stood in for
    by its XLA twin, which is bit-identical)."""
    from ethereum_consensus_tpu.ops import sha256

    widths = []

    def fake_kernel(msgs):
        widths.append(msgs.shape[1])
        return sha256_64b_xla(msgs)

    monkeypatch.setattr(sha256, "_supports_pallas", lambda: True)
    monkeypatch.setattr(sha256, "sha256_64b_pallas", fake_kernel)
    before = sha256._PALLAS_CALLS.value()
    for n in (3, 1024, 1500):
        msgs = np.random.default_rng(n).bytes(64 * n)
        got = np.asarray(sha256.sha256_64b(_to_words(msgs, n)))
        assert got.shape == (8, n)
        assert (got.T == _ref_hashes(msgs, n)).all()
    assert widths == [1024, 1024, 2048]
    assert sha256._PALLAS_CALLS.value() - before == 3


def test_hash_level_bytes_matches_host():
    rng = np.random.default_rng(2)
    nodes = rng.bytes(64 * 33)
    expect = b"".join(
        hashlib.sha256(nodes[i : i + 64]).digest() for i in range(0, len(nodes), 64)
    )
    assert hash_level_bytes(nodes) == expect


@pytest.mark.parametrize(
    "count,limit",
    [(1, None), (2, None), (5, None), (8, None), (1, 16), (3, 2**20), (1, 2**40), (100, 2**40)],
)
def test_merkleize_device_matches_host(count, limit):
    rng = np.random.default_rng(count)
    chunks = rng.bytes(32 * count)
    assert merkleize_chunks_device(chunks, limit) == merkleize_chunks(chunks, limit)


def test_merkleize_device_empty():
    assert merkleize_chunks_device(b"", 2**40) == merkleize_chunks(b"", 2**40)


def test_device_hasher_integration(monkeypatch):
    """register_device_hasher routes big levels through device, small via host;
    roots stay identical either way. The threshold is lowered so the device
    path is actually exercised (and its invocation asserted)."""
    from ethereum_consensus_tpu.ssz import hash as ssz_hash
    from ethereum_consensus_tpu.ops.sha256 import hash_level_bytes as dev

    rng = np.random.default_rng(3)
    chunks = rng.bytes(32 * 4096)
    before = merkleize_chunks(chunks)

    calls = []

    def counting_dev(nodes: bytes) -> bytes:
        calls.append(len(nodes) // 64)
        return dev(nodes)

    monkeypatch.setattr(ssz_hash, "DEVICE_MIN_NODES", 1024)
    old = ssz_hash._device_hasher
    try:
        ssz_hash.register_device_hasher(counting_dev)
        after = merkleize_chunks(chunks)
    finally:
        ssz_hash._device_hasher = old
    assert before == after
    assert calls == [2048, 1024], calls  # top two levels routed to device
