"""The main path's kernels, compiled for a described TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached: what it refuses here it refuses on the chip, at
no chip time. Nothing runs — these are compiles only, never chip runs.
The shapes are the ones ``chip_smoke.py`` drives at the 2^20-validator
deployment.

Only one process may load the TPU library, so the topology is described
inside a module-scoped fixture (never at import, never in conftest.py)
and every compile happens in the test's own process; this is the one
file that touches it. Code that asks ``jax.default_backend()`` still
sees the CPU here, so the tests lower the jitted kernels themselves
(``observe_jit`` hides ``.lower``: lower its ``__wrapped__`` jit) and
steer the Pallas branch on from the test.

The two slow compiles (``ops/g1._tree_reduce_segmented``, the one-device
fused epoch kernel — about a minute each) are marked ``slow``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

N_VALIDATORS = 1 << 20
# what follows the four u64 scalars in the fused epoch kernel's arguments:
# the altair-family chain constants it is jitted with (static: inactivity
# bias / recovery rate, flag weights, weight denominator, head and target
# flag indices) and, fifth of them, ``leaking``, which is traced
FUSED_STATICS = (4, 16, (14, 26, 14), 64, False, 2, 1)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — no TPU compiler: skip the file
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from ethereum_consensus_tpu.parallel.mesh import SHARD_AXIS

    return Mesh(np.asarray(topo.devices[:4]), (SHARD_AXIS,))


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def x64():
    """Set jax_enable_x64 for one test; restored afterwards."""
    was = jax.config.jax_enable_x64

    def set_to(on: bool) -> None:
        jax.config.update("jax_enable_x64", on)

    yield set_to
    jax.config.update("jax_enable_x64", was)


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
    )


@pytest.mark.parametrize("x64_on", [False, True], ids=["x64_off", "x64_on"])
def test_sha256_pallas_compiles(one_chip, no_compile_cache, x64, x64_on):
    """ops.install() turns x64 on, so the kernel must compile in both
    states (with x64 on, Python ints in the index maps were i64 and
    Mosaic refused the kernel)."""
    from ethereum_consensus_tpu.ops.sha256 import sha256_64b_pallas

    x64(x64_on)
    compiled = sha256_64b_pallas.lower(
        _shape((16, 1 << 17), jnp.uint32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def merkle_single(one_chip, no_compile_cache):
    """``merkle_root_words`` at 2^20 leaves, depth 20, for one device, with
    x64 on and the Pallas branch steered on (the program asks
    ``jax.default_backend()``, which is the CPU here). Compiled once for
    the two tests that read it."""
    from ethereum_consensus_tpu.ops import merkle, sha256

    was_x64 = jax.config.jax_enable_x64
    was_supports = sha256._supports_pallas
    jax.config.update("jax_enable_x64", True)
    sha256._supports_pallas = lambda: True
    try:
        return merkle.merkle_root_words.__wrapped__.lower(
            _shape((8, 1 << 20), jnp.uint32, one_chip),
            _shape((64, 8), jnp.uint32, one_chip),
            depth=20,
        ).compile()
    finally:
        sha256._supports_pallas = was_supports
        jax.config.update("jax_enable_x64", was_x64)


def test_merkle_root_words_takes_pallas(merkle_single):
    """The whole-tree reduction with the Pallas branch taken under a trace
    (where the old eager ``try`` never saw the compile failure)."""
    assert "tpu_custom_call" in merkle_single.as_text()


@pytest.mark.parametrize("multiplier", ["u64", "mxu"])
def test_miller_loop_compiles(one_chip, no_compile_cache, x64, multiplier):
    """One flush window of the deployment: >= 512 sets, padded to a power
    of two, under each product kernel EC_PAIRING_MULT routes."""
    from ethereum_consensus_tpu.ops import fql, pairing

    x64(True)
    was = fql.get_multiplier()
    fql.set_multiplier(multiplier)
    try:
        n = 512
        g1 = _shape((n, 24), jnp.uint64, one_chip)
        g2 = _shape((n, 2, 24), jnp.uint64, one_chip)
        pairing.miller_loop_batched.__wrapped__.lower(
            g1, g1, g2, g2
        ).compile()
    finally:
        fql.set_multiplier(was)


def test_shuffle_rounds_compiles(one_chip, no_compile_cache, x64):
    from ethereum_consensus_tpu.ops.shuffle import _shuffle_rounds_jit

    x64(True)
    rounds = 90  # mainnet SHUFFLE_ROUND_COUNT
    _shuffle_rounds_jit.__wrapped__.lower(
        _shape((N_VALIDATORS,), jnp.uint32, one_chip),
        _shape((rounds,), jnp.uint32, one_chip),
        _shape((rounds, N_VALIDATORS // 256 * 32), jnp.uint8, one_chip),
        count=N_VALIDATORS,
        forward=True,
    ).compile()


def test_sharded_merkle_quarters_per_device(
    merkle_single, mesh4, no_compile_cache, x64, monkeypatch
):
    """2^20 chunks over four devices: the Pallas kernel inside the
    shard_map body, and about a quarter of the one-device program's bytes
    on each device."""
    from ethereum_consensus_tpu.ops import sha256
    from ethereum_consensus_tpu.parallel.merkle import (
        sharded_merkle_root_words,
    )
    from ethereum_consensus_tpu.parallel.mesh import SHARD_AXIS

    x64(True)
    monkeypatch.setattr(sha256, "_supports_pallas", lambda: True)
    sharded = sharded_merkle_root_words.__wrapped__.lower(
        _shape(
            (8, 1 << 20),
            jnp.uint32,
            NamedSharding(mesh4, P(None, SHARD_AXIS)),
        ),
        _shape((64, 8), jnp.uint32, NamedSharding(mesh4, P())),
        depth=20,
        mesh=mesh4,
    ).compile()
    assert "tpu_custom_call" in sharded.as_text()
    ratio = _device_bytes(sharded) / _device_bytes(merkle_single)
    assert 0.15 < ratio < 0.35, ratio


def _fused_columns(sharding):
    """The seven packed columns of the fused epoch kernel at 2^20 rows."""
    u64 = _shape((N_VALIDATORS,), jnp.uint64, sharding)
    u8 = _shape((N_VALIDATORS,), jnp.uint8, sharding)
    flag = _shape((N_VALIDATORS,), jnp.bool_, sharding)
    return (u64, u64, u8, flag, flag, flag, u64)


def _fused_sharded_compile(mesh):
    from ethereum_consensus_tpu.parallel import epoch

    replicated = NamedSharding(mesh, P())
    scalar = _shape((), jnp.uint64, replicated)
    kernel = epoch._fused_sharded(mesh, *FUSED_STATICS[:4], *FUSED_STATICS[5:])
    return kernel.__wrapped__.lower(
        *_fused_columns(NamedSharding(mesh, P(epoch.SHARD_AXIS))),
        *(scalar,) * 4,
        _shape((), jnp.bool_, replicated),  # leaking
    ).compile()


def test_mesh_fused_epoch_quarters_per_device(mesh4, no_compile_cache, x64):
    """MeshEpochSweeps.fused's program at 2^20 rows over four devices:
    it compiles (a u64 ``psum`` does not — parallel/mesh.py psum_u64),
    the totals cross the mesh, and each device holds a quarter of the
    columns (arguments and outputs; the one-device program itself is the
    slow compile below)."""
    x64(True)
    compiled = _fused_sharded_compile(mesh4)
    assert "all-reduce" in compiled.as_text()
    m = compiled.memory_analysis()
    column_bytes = N_VALIDATORS * (8 + 8 + 1 + 1 + 1 + 1 + 8)
    assert m.argument_size_in_bytes == pytest.approx(
        column_bytes / 4, rel=0.01
    )
    # new scores + new balances, sharded; the wrap census is a scalar
    assert m.output_size_in_bytes == pytest.approx(
        N_VALIDATORS * 16 / 4, rel=0.01
    )


@pytest.mark.slow
def test_fused_epoch_one_device_compiles(
    one_chip, mesh4, no_compile_cache, x64
):
    """The jitted fused epoch program ops.install() routes to (about a
    minute of compile): the kernel and the split of its two u64 columns
    into one ``uint32[4, n]``; and the mesh program's bytes against it
    (a quarter of the columns, and none of the planes, whose temporaries
    are 35 MB of the one-chip program's 91 MB)."""
    from ethereum_consensus_tpu.models.epoch_vector import jitted_kernels

    x64(True)
    scalar = _shape((), jnp.uint64, one_chip)
    single = jitted_kernels()["fused_epoch"].__wrapped__.lower(
        *_fused_columns(one_chip), *(scalar,) * 4, *FUSED_STATICS
    ).compile()
    # the planes are 16 B a row, as the two u64 columns were
    assert single.memory_analysis().output_size_in_bytes == pytest.approx(
        N_VALIDATORS * 16, rel=0.01
    )
    ratio = _device_bytes(_fused_sharded_compile(mesh4)) / _device_bytes(
        single
    )
    assert 0.1 < ratio < 0.2, ratio


@pytest.mark.slow
def test_g1_strict_fold_compiles(one_chip, no_compile_cache, x64):
    """ops/g1.py's strict-field segmented fold at one block's shape (64
    sets of 512 keys): about a minute of compile, 181 MB of temporaries."""
    from ethereum_consensus_tpu.ops import g1

    x64(True)
    g1._tree_reduce_segmented.__wrapped__.lower(
        _shape((64, 512, 3, 24), jnp.uint32, one_chip), levels=9
    ).compile()
