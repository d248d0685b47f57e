"""Device-mesh construction helpers.

One logical axis ``shard`` carries every batch axis in this framework (merkle
leaf ranges, signature batches, validator-registry rows) — the domain has no
tensor/pipeline dimension to split, so a 1-D mesh maps the whole ICI
bandwidth onto the one axis that matters. Multi-host meshes come for free:
``jax.devices()`` spans hosts under ``jax.distributed``, and the collectives
(`all_gather`/`psum`) ride ICI within a host and DCN across.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

SHARD_AXIS = "shard"

__all__ = ["SHARD_AXIS", "chip_mesh", "default_device_mesh", "psum_u64"]


def chip_mesh(n_devices: int | None = None, axis_name: str = SHARD_AXIS) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (all by default)."""
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} available"
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def default_device_mesh() -> Mesh:
    return chip_mesh()


def psum_u64(value, axis_name: str = SHARD_AXIS):
    """Sum a uint64 value over the mesh axis (inside a shard_map body).

    Not ``jax.lax.psum``: the TPU compiler has no all-reduce for the
    emulated 64-bit add ("UNIMPLEMENTED: Supported lowering only of Sum
    all reduce", tests/test_chip_compile.py). Gathering the per-device
    values is data movement only and lowers; the local sum over them is
    the same number on every device (u64 addition is associative and
    wraps identically)."""
    return jnp.sum(jax.lax.all_gather(value, axis_name), axis=0)
