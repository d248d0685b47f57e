"""Test configuration.

The suite runs on XLA's CPU backend: ``JAX_PLATFORMS`` defaults to
``cpu`` here, before anything imports jax. The chip is exercised by
``chip_smoke.py`` (and measured by ``benchmark/``), not by the
correctness suite; what the chip's compiler accepts is tests/test_chip_compile.py.

Tests that need a multi-device mesh spawn a subprocess on a virtual
8-device CPU platform (``cpu_mesh_env`` below): the device count is an
XLA flag read when the backend starts, so it cannot change in-process.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def cpu_mesh_env(n_devices: int = 8) -> dict:
    """Environment for a subprocess with an n-device virtual CPU platform."""
    from ethereum_consensus_tpu.parallel.virtual_mesh import cpu_mesh_env as _env

    return _env(n_devices, repo_root=REPO_ROOT)


def run_in_cpu_mesh(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run ``code`` in a subprocess on the virtual CPU mesh; returns stdout."""
    from ethereum_consensus_tpu.parallel.virtual_mesh import (
        run_in_cpu_mesh as _run,
    )

    return _run(code, n_devices=n_devices, timeout=timeout, repo_root=REPO_ROOT)


@pytest.fixture
def cpu_mesh():
    return run_in_cpu_mesh


@pytest.fixture
def small_groups():
    """Shrink the dirty-group geometry so small collections exercise many
    stored-level groups (the module globals exist for exactly this, and
    the walkers read them live): tier-1 covers the tracked-list branches
    cheaply."""
    from ethereum_consensus_tpu.ssz import core as ssz_core

    saved = (
        ssz_core._DIRTY_GROUP_SHIFT,
        ssz_core._DIRTY_TRACK_MIN_CHUNKS,
        ssz_core._BULK_ROOTS_MIN,
    )
    ssz_core._DIRTY_GROUP_SHIFT = 2
    ssz_core._DIRTY_TRACK_MIN_CHUNKS = 1 << 2
    ssz_core._BULK_ROOTS_MIN = 4
    try:
        yield
    finally:
        (
            ssz_core._DIRTY_GROUP_SHIFT,
            ssz_core._DIRTY_TRACK_MIN_CHUNKS,
            ssz_core._BULK_ROOTS_MIN,
        ) = saved
        # a genesis first built in here was warmed under the shrunk
        # geometry, and every later copy in this process would carry it
        chain_utils = sys.modules.get("chain_utils")
        if chain_utils is not None:
            chain_utils.cached_genesis.cache_clear()
            chain_utils._cached_genesis_fork.cache_clear()
