"""Deterministic validator keys (copied from tests/chain_utils.py, which
any PR may edit; this copy is the yardstick's)."""

from __future__ import annotations

import functools

from ethereum_consensus_tpu.crypto import bls


@functools.lru_cache(maxsize=None)
def secret_key(index: int) -> bls.SecretKey:
    return bls.SecretKey(index + 1)


@functools.lru_cache(maxsize=None)
def public_key_bytes(index: int) -> bytes:
    return secret_key(index).public_key().to_bytes()


def synthetic_pubkey_bytes(index: int) -> bytes:
    """48 deterministic bytes that can never decompress (leading 0xFF), so
    a crypto path that touches a validator nobody gave a real key fails
    loudly."""
    return b"\xff" * 16 + index.to_bytes(32, "big")


def realize_validator_keys(state, indices) -> None:
    """Swap the synthetic pubkeys of ``indices`` for the real keys."""
    for i in set(indices):
        validator = state.validators[i]
        real = public_key_bytes(i)
        if bytes(validator.public_key) != real:
            validator.public_key = real
