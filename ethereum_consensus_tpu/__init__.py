"""ethereum_consensus_tpu — a TPU-native Ethereum beacon-chain consensus
framework.

A ground-up reimplementation of the capabilities of
`ralexstokes/ethereum_consensus` (the Rust reference surveyed in SURVEY.md)
designed for TPUs: spec logic is host Python with exact u64 semantics; the
hot paths — SHA-256 merkleization, batched BLS aggregate verification,
shuffling, and the epoch pass's inactivity and rewards — run as
JAX/XLA/Pallas kernels sharded over device meshes.

Layout:
  ssz/       SSZ type algebra, codec, merkleization (replaces ssz_rs)
  crypto/    BLS12-381 + KZG (replaces blst/c-kzg) with oracle + device paths
  models/    per-fork spec modules (phase0..electra) + polymorphic types
  ops/       JAX/Pallas device kernels (sha256, merkle, shuffle, BLS)
  parallel/  mesh construction, shard_map distributed reductions
  config/    presets, network configs, Context, networks
  utils/     clock, serde presentation helpers, math
  api/       Beacon-API client
  cli/       `ec`-equivalent CLI (keys, keystores, blobs)
"""

__version__ = "0.1.0"

from . import error, fork, primitives, ssz  # noqa: F401
from .fork import Fork  # noqa: F401


def __getattr__(name):
    # heavyweight subsystems load lazily so `import ethereum_consensus_tpu`
    # stays cheap (models pulls crypto + every fork's containers)
    import importlib

    if name in {
        "api", "builder", "cli", "clock", "config", "crypto", "executor", "execution_engine",
        "models", "networking", "ops", "parallel", "serde", "signing", "types",
        "utils",
    }:
        if name == "clock":
            return importlib.import_module(".utils.clock", __name__)
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
