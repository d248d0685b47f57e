"""The benchmark's own tests: every driver end to end on the CPU at a tiny
size. They live with the benchmark and are not part of the repo's tier-1
run (``python -m pytest benchmark/tests -q`` from the checkout's root).

The command itself refuses a non-TPU device; these tests go past that
refusal by calling ``harness.execute`` with an ``install`` of their own,
which lowers the routing thresholds so that the routed kinds engage at toy
sizes on the CPU backend."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

@pytest.fixture
def routing(monkeypatch):
    """An ``install`` for ``harness.execute`` that steers the sweeps, the
    shuffle and the hasher on at toy sizes (the device pairing stays off:
    its Miller loop takes minutes to compile for the CPU); routing is taken
    off afterwards."""
    import jax

    from ethereum_consensus_tpu import ops
    from ethereum_consensus_tpu.ops import sha256
    from ethereum_consensus_tpu.ssz import hash as ssz_hash

    monkeypatch.setattr(ssz_hash, "DEVICE_MIN_NODES", 1 << 9)
    monkeypatch.setattr(ssz_hash, "_device_hasher", None)
    monkeypatch.setattr(sha256, "_supports_pallas", lambda: True)
    monkeypatch.setattr(sha256, "sha256_64b_pallas", sha256.sha256_64b_xla)
    was_x64 = jax.config.jax_enable_x64

    def install():
        ops.install(
            sweeps_min_n=1, shuffle_min_n=1, pairing_min_sets=None,
            hasher_on_cpu=True,
        )

    yield install
    ops.uninstall()
    jax.config.update("jax_enable_x64", was_x64)


@pytest.fixture
def recorded_trace():
    """The reduction of the small trace recorded on the v5e."""
    from benchmark import trace_reduce

    path = os.path.join(os.path.dirname(__file__), "epoch_small.xplane.pb.xz")
    return trace_reduce.reduce(trace_reduce.load(path))
