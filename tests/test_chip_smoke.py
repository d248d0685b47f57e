"""chip_smoke.py rehearsed on the CPU, and the launch rules around it.

The script's phase functions take their sizes as arguments (its command
line has one deployment, the 2^20 one), so they run here on a small
registry: roots from the device-routed served path must equal the host
reference's. Routing thresholds are lowered HERE — the script installs
``ops.install()`` defaults and nothing else. Without a TPU the script
must refuse: non-zero exit, no result line. What counts as a route
declining to the host is the benchmark's rule (``benchmark/meters.py``).
"""

import os
import re
import sys

import jax
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

from ethereum_consensus_tpu import _jax_cache  # noqa: E402


@pytest.fixture
def device_routing(monkeypatch):
    """Every route of ``ops.install()`` steered on at toy sizes, on the CPU
    backend: low thresholds, the per-level device hasher forced and given
    small levels, and the TPU branch of ``sha256_64b`` taken with the XLA
    twin standing in for the Pallas kernel (bit-identical; the kernel
    itself cannot run here). The device pairing stays off: its Miller
    loop takes minutes to compile for the CPU."""
    from ethereum_consensus_tpu import ops
    from ethereum_consensus_tpu.ops import sha256
    from ethereum_consensus_tpu.ssz import hash as ssz_hash
    from ethereum_consensus_tpu.telemetry import device as tel_device

    monkeypatch.setattr(ssz_hash, "DEVICE_MIN_NODES", 1 << 9)
    monkeypatch.setattr(ssz_hash, "_device_hasher", None)
    monkeypatch.setattr(sha256, "_supports_pallas", lambda: True)
    monkeypatch.setattr(sha256, "sha256_64b_pallas", sha256.sha256_64b_xla)
    was_x64 = jax.config.jax_enable_x64
    tel_device.start()

    def install():
        ops.install(
            sweeps_min_n=1, shuffle_min_n=1, bls_agg_min_n=1,
            pairing_min_sets=None, hasher_on_cpu=True,
        )

    yield install
    tel_device.stop()
    ops.uninstall()
    jax.config.update("jax_enable_x64", was_x64)


def test_phases_agree_at_small_registry(device_routing, capsys):
    """Host reference, then the same work device-routed: the cold state
    root, the streamed blocks and the epoch boundaries all land on the
    host's roots, every routed kind reaches the device, none declines."""
    since = chip_smoke.counters()
    chip_smoke.check_native()
    world = chip_smoke.generate(validators=1 << 12, n_blocks=2, attestations=2)
    roots = chip_smoke.host_reference(world)
    assert len(set(roots.values())) == 3

    device_routing()
    chip_smoke.cold_state_root(world, roots)
    state = chip_smoke.stream_blocks(world, roots)
    chip_smoke.epoch_boundary(state, world, roots)
    evidence = chip_smoke.check_evidence(
        since, [kind for kind in chip_smoke.ROUTED_KINDS if kind != "pairing"]
    )
    assert evidence["declines"] == {}

    lines = capsys.readouterr().out.splitlines()
    phases = [line for line in lines if line.startswith('{"phase"')]
    assert len(phases) == 7
    assert not any('"ok"' in line for line in lines)
    # with the pairing kind demanded too, the evidence check refuses
    with pytest.raises(chip_smoke.SmokeFailure, match="pairing"):
        chip_smoke.check_evidence(since)


def test_a_wrong_root_fails_the_phase(device_routing):
    world = chip_smoke.generate(validators=1 << 12, n_blocks=1, attestations=1)
    roots = chip_smoke.host_reference(world)
    roots["pre"] = bytes(32)
    with pytest.raises(chip_smoke.SmokeFailure, match="cold pre-state root"):
        chip_smoke.cold_state_root(world, roots)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["one", "four"])
def test_main_refuses_without_tpu(argv, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


@pytest.mark.parametrize(
    "name, declined",
    [
        ("chip_smoke_test.fallback.case", True),
        ("chip_smoke_test.fused_fallback.case", True),
        ("mesh.decline.chip_smoke_test", True),
        ("bls.device_decline.chip_smoke_test", True),
        ("ops_vector.attestations.blocks", False),
        ("bls.aggregate_pubkeys.from_cache", False),
    ],
)
def test_evidence_declines_are_the_benchmarks(name, declined):
    """A counter that moved is a decline exactly when the benchmark's
    meters say so: the evidence check refuses it by name, and lets any
    other counter pass."""
    from ethereum_consensus_tpu.telemetry import metrics

    since = chip_smoke.counters()
    metrics.counter("ops.sha256.pallas").inc()
    metrics.counter(name).inc()
    if declined:
        with pytest.raises(chip_smoke.SmokeFailure, match=re.escape(name)):
            chip_smoke.check_evidence(since, routed_kinds=())
    else:
        evidence = chip_smoke.check_evidence(since, routed_kinds=())
        assert evidence["declines"] == {}


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_compile_cache_directory(placed_from_outside, monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set jax reads it itself and the
    program sets no directory; unset, the cache is the fixed
    ``<checkout>/.jax_cache``."""
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(_jax_cache, "_ENABLED", False)
    if placed_from_outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        jax.config.update("jax_compilation_cache_dir", "left-alone")
        _jax_cache.enable()
        fixed = os.path.join(REPO_ROOT, ".jax_cache")
        if placed_from_outside:
            assert jax.config.jax_compilation_cache_dir == "left-alone"
            assert _jax_cache.status()["dir"] == str(tmp_path)
        else:
            assert jax.config.jax_compilation_cache_dir == fixed
            assert _jax_cache.status()["dir"] == fixed
        assert _jax_cache.status()["enabled"]
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
