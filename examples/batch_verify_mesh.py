"""Mesh-sharded batch signature verification, end to end.

The TPU-native analogue of the reference's `fast_aggregate_verify` hot
path (crypto/bls.rs:114): N signature sets become ONE random-linear-
combination multi-pairing whose set axis is sharded over a device mesh
(parallel/pairing.py), with per-set pubkey aggregation as one segmented
device fold (ops/pairing.g1_sum_sets).

Runs on whatever devices JAX sees; to try the multi-chip path without
hardware, use a virtual CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/batch_verify_mesh.py
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax

jax.config.update("jax_enable_x64", True)

from ethereum_consensus_tpu import ops
from ethereum_consensus_tpu.crypto import bls


def main() -> None:
    n_sets, keys_per_set = 12, 4
    print(f"platform: {jax.devices()[0].platform}, devices: {jax.devices()}")

    sks = [bls.SecretKey(1_000 + i) for i in range(n_sets * keys_per_set)]
    sets = []
    for s in range(n_sets):
        group = sks[s * keys_per_set : (s + 1) * keys_per_set]
        message = s.to_bytes(32, "big")
        aggregate = bls.aggregate([sk.sign(message) for sk in group])
        sets.append(
            bls.SignatureSet(
                [sk.public_key() for sk in group], message, aggregate
            )
        )

    # route the whole batch through the device kernels: segmented G1
    # fold for the per-set aggregations, then the RLC multi-pairing —
    # sharded over the mesh when >1 device is visible
    ops.install(bls_agg_min_n=1, pairing_min_sets=1)
    try:
        verdicts = bls.verify_signature_sets(sets)
        print(f"{n_sets} sets x {keys_per_set} keys: {verdicts}")
        assert all(verdicts)

        forged = list(sets)
        forged[5] = bls.SignatureSet(
            sets[5].public_keys, b"\xff" * 32, sets[5].signature
        )
        verdicts = bls.verify_signature_sets(forged)
        print(f"with set 5 forged:              {verdicts}")
        assert verdicts == [True] * 5 + [False] + [True] * (n_sets - 6)
    finally:
        ops.uninstall()
    print("ok")


if __name__ == "__main__":
    main()
