"""Mesh-sharded RLC batch signature verification.

The signature-set axis IS the mesh axis (SURVEY.md §2.5, batch axes as
mesh axes): each device runs the blinder multiplications, Miller loops,
and local Fq12-product/G2-sum reductions for its slice of the batch
under one ``shard_map``; only the tiny per-device partials (one Fq12
value and one Jacobian G2 point per device) cross the mesh, and the O(1)
final exponentiation stays on the host native backend — the same
decomposition as the single-device route (ops/pairing.py), with the
chunk axis promoted to devices.

Padding discipline mirrors ops/pairing.batch_verify_device: lanes pad to
``n_dev × 2^k`` with generator points; padded pk/H lanes are masked out
of the local Fq12 product by a validity column (slicing cannot cross
shard boundaries), and padded signature lanes carry blinder 0, whose
scalar multiple is the identity the branchless sum skips.

Reference role: blst's pairing engine under crypto/bls.rs (C6). The
reference itself has NO distributed backend (SURVEY.md §2.5 — it is a
single-process library); this mesh decomposition is the green-field
TPU-native scale-out of its batch-verification semantics, not a port
of any reference communication layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..ops import fq12, pairing as dp
from ..telemetry import device as _obs
from .mesh import SHARD_AXIS, default_device_mesh

__all__ = ["batch_verify_sharded", "miller_partials_sharded"]


@functools.lru_cache(maxsize=8)
def _sharded_parts(mesh):
    """Jitted shard_map over the set axis: per-device blinder mults +
    Miller loops + local reductions → (n_dev, 2, 3, 2, 24) Fq12 partial
    products and (n_dev, 3, 2, 24) Jacobian G2 partial signature sums."""

    def body(pk_jac, pk_bits, xq, yq, sig_jac, sig_bits, valid):
        k = pk_jac.shape[0]  # lanes per device (power of two)
        pk_blinded = dp._mul_scan_g1(pk_jac, pk_bits)
        xp, yp = dp._g1_jacobian_to_affine(pk_blinded)
        fs = dp.miller_loop_batched(xp, yp, xq, yq)
        one = fq12.fp12_one((k,)).arr
        fs = jnp.where(valid[:, None, None, None, None], fs, one)
        local_f = dp.fp12_product(fs)
        sig_mul = dp._mul_scan_g2(sig_jac, sig_bits)
        local_sig = dp._g2_tree_reduce(sig_mul, (k - 1).bit_length())
        return local_f[None], local_sig[None]

    # check_vma=False: the Miller scan mixes device-varying lanes with
    # unvarying constants (same situation as parallel/step.py's SHA loop)
    return _obs.observe_jit(
        jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(P(SHARD_AXIS),) * 7,
                out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                check_vma=False,
            )
        ),
        "parallel.pairing._sharded_parts",
    )


def _pad_width(n: int, n_dev: int) -> int:
    """Lanes per device: the next power of two covering ceil(n/n_dev)."""
    per = -(-n // n_dev)
    return 1 << (per - 1).bit_length() if per > 1 else 1


def miller_partials_sharded(mesh, pk_raws, h_raws, sig_raws, scalars):
    """Shard the batch over ``mesh`` and return host-side partials:
    ``(f_total, s_raw, s_inf)`` ready for ``ops.pairing.finalize_verdict``.

    Inputs are the same raw affine byte strings + blinder ints as
    ``batch_verify_device`` (non-identity pk aggregates, hash points,
    signatures, nonzero 128-bit blinders).
    """
    n = len(pk_raws)
    n_dev = mesh.devices.size
    assert n and len(h_raws) == n and len(sig_raws) == n and len(scalars) == n
    # fault-injection seam (runtime.install_fault_hook): an injected
    # fault surfaces here exactly where real device trouble would — the
    # caller's device-unusable fallback recovers on the host engine with
    # identical verdicts, the decline journaled as injected_fault
    from . import runtime as _runtime

    _runtime.fault_point("pairing", sets=n, devices=int(n_dev))

    k = _pad_width(n, n_dev)
    width = n_dev * k
    g1f, g2f = dp._generator_raws()
    pk_padded = list(pk_raws) + [g1f] * (width - n)
    h_padded = list(h_raws) + [g2f] * (width - n)
    sig_padded = list(sig_raws) + [g2f] * (width - n)
    pk_scalars = list(scalars) + [1] * (width - n)
    sig_scalars = list(scalars) + [0] * (width - n)
    valid = np.zeros(width, np.bool_)
    valid[:n] = True

    pk_jac = dp._g1_jac_from_affine_raws(pk_padded).arr
    xq, yq = dp.g2_affine_from_raw(h_padded)
    sx, sy = dp.g2_affine_from_raw(sig_padded)
    one2 = jnp.broadcast_to(
        _obs.h2d(
            "parallel.pairing.const_one2",
            np.stack([
                np.asarray(dp.fql.to_mont_cols(1)), np.zeros(24, np.uint64),
            ]),
        ),
        sy.arr.shape,
    )
    sig_jac = jnp.stack([sx.arr, sy.arr, one2], axis=-3)
    pk_bits, sig_bits = _obs.h2d(
        "parallel.pairing.scalar_bits",
        dp._scalars_to_bits(pk_scalars, 128),
        dp._scalars_to_bits(sig_scalars, 128),
    )

    shard = NamedSharding(mesh, P(SHARD_AXIS))
    # ``valid`` rides as the host np array — the seam's device_put IS
    # its one transfer
    staged = (pk_jac, pk_bits, xq.arr, yq.arr, sig_jac, sig_bits, valid)
    args = _obs.h2d_put("parallel.pairing.shard_put", staged, shard)
    partial_fs, partial_sigs = _sharded_parts(mesh)(*args)

    # per-shard partials come back as device arrays already — reduce in
    # place, no re-wrap
    f_total = dp.fp12_product(partial_fs)
    sig_sum = dp.g2_sum_points(dp._env(partial_sigs))
    s_raw, s_inf = dp._g2_point_to_raw(sig_sum)
    return f_total, s_raw, s_inf


def batch_verify_sharded(
    pk_raws, h_raws, sig_raws, scalars, mesh=None
) -> bool:
    """The RLC batch verdict with the set axis sharded over a device mesh
    — semantics identical to ``ops.pairing.batch_verify_device``."""
    mesh = mesh if mesh is not None else default_device_mesh()
    f_total, s_raw, s_inf = miller_partials_sharded(
        mesh, pk_raws, h_raws, sig_raws, scalars
    )
    return dp.finalize_verdict(f_total, s_raw, s_inf)
