"""Mesh-sharded epoch sweeps: the production epoch hot path on devices.

The columnar-primary epoch engine (models/epoch_vector.py) runs its
per-validator math as three numeric kernels over numpy columns. This
module lifts exactly those sweeps onto the 1-D ``shard`` mesh: the
validator axis shards row-wise over the devices, the masked
effective-balance reductions the rewards formula needs become ``psum``
collectives, and the results come home bit-identical to the host kernels
(same u64 arithmetic, same floor divisions, same application order — the
bodies REUSE the epoch_vector kernel functions wherever the scalars are
static, and mirror them operation-for-operation where a per-epoch scalar
must stay dynamic to keep XLA from re-tracing every epoch).

Padding discipline: the registry length pads up to a multiple of the
mesh size with neutral rows (zero balances/scores, all-False masks) —
padded rows contribute zero to every psum, earn zero deltas, and are
sliced back off before the columns return to the host pass. Exactness:
the caller (models/epoch_vector.py ``_sync``) has already guarded every
product/sum into the u64 lane, so device sums equal host sums exactly
(u64 addition is associative) and a decline happens BEFORE any dispatch.

The overflow contract survives sharding: the apply chain counts wrapped
lanes through a ``psum`` and the host wrapper returns ``None`` when any
wrapped — the caller then falls back to the host path, whose literal
mirror raises the structured error at the exact index (the same
unreachable-under-guards terminal the host pass keeps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..telemetry import device as _obs
from ..telemetry import memory as _mem
from .mesh import SHARD_AXIS, psum_u64

__all__ = ["MeshEpochSweeps", "pad_to_mesh"]


def pad_to_mesh(n: int, n_dev: int) -> int:
    """Smallest multiple of ``n_dev`` covering ``n`` rows — elementwise
    sweeps need no power-of-two subtrees (unlike the merkle shards), so
    a non-power-of-two registry pads by at most ``n_dev - 1`` neutral
    rows."""
    return -(-n // n_dev) * n_dev


def _bit_mask(part, flag_index: int):
    """The kernel-side twin of epoch_vector._flag_mask (u8 column →
    bool participation mask for one flag)."""
    return ((part >> np.uint8(flag_index)) & np.uint8(1)).astype(bool)


@functools.lru_cache(maxsize=16)
def _inactivity_sharded(mesh, bias: int, recovery: int, leaking: bool):
    """Sharded twin of epoch_vector.inactivity_scores_kernel — the SAME
    kernel body, row-sharded (it is purely elementwise; bias/recovery
    are chain constants, so static args cost one compile per chain)."""
    from ..models.epoch_vector import inactivity_scores_kernel

    def body(scores, eligible, participating):
        return inactivity_scores_kernel(
            jnp, scores, eligible, participating, bias, recovery, leaking
        )

    spec = P(SHARD_AXIS)
    return _obs.observe_jit(
        jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(spec,) * 3,
                out_specs=spec,
                check_vma=False,
            )
        ),
        "parallel.epoch.inactivity_sweep",
    )


@functools.lru_cache(maxsize=16)
def _fused_sharded(
    mesh,
    bias: int,
    recovery_rate: int,
    weights: tuple,
    weight_denominator: int,
    head_flag_index: int,
    target_flag_index: int,
):
    """The FUSED epoch kernel (ISSUE 14), mesh-sharded: the SAME
    ``epoch_vector.fused_epoch_kernel`` body the jit route runs, with
    its scalar reductions wrapped in ``psum`` — inactivity update, flag
    deltas, inactivity penalties, and in-order application in ONE
    dispatch, so the packed columns ship to the devices once and stay
    there across every stage. ``leaking`` is a replicated scalar beside
    the four u64 ones, as in the jit route: one program in and out of a
    leak."""
    from ..models.epoch_vector import fused_epoch_kernel

    def body(balances, eff, prev_part, slashed, active_prev, eligible,
             scores, increment, brpi, active_increments, denominator,
             leaking):
        return fused_epoch_kernel(
            jnp, balances, eff, prev_part, slashed, active_prev, eligible,
            scores, increment, brpi, active_increments, denominator,
            bias, recovery_rate, weights, weight_denominator, leaking,
            head_flag_index, target_flag_index,
            psum=psum_u64,
        )

    spec = P(SHARD_AXIS)
    return _obs.observe_jit(
        jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(spec,) * 7 + (P(),) * 5,
                out_specs=(spec, spec, P()),
                check_vma=False,
            )
        ),
        "parallel.epoch.fused_sweep",
    )


@functools.lru_cache(maxsize=16)
def _rewards_sharded(
    mesh,
    weights: tuple,
    weight_denominator: int,
    leaking: bool,
    head_flag_index: int,
    target_flag_index: int,
):
    """The whole altair rewards stage as ONE sharded sweep: per-flag
    masked effective-balance ``psum`` reductions, the three flag-delta
    pairs, the inactivity-penalty pair off the post-update scores, and
    the in-order saturating application — operation-for-operation the
    host stage (models/epoch_vector.py _rewards_altair), with the
    per-epoch scalars (base-reward-per-increment, active increments,
    penalty denominator) DYNAMIC so a steady-state replay compiles once.

    Returns ``(new_balances  [sharded], wrapped_lanes [replicated],
    unslashed_sums (3,) [replicated])``; a nonzero ``wrapped_lanes``
    means a u64 wrap the guards should have made unreachable — the host
    wrapper declines so the literal overflow mirror keeps its structured
    error."""

    def body(balances, eff, prev_part, slashed, active_prev, eligible,
             scores, increment, brpi, active_increments, denominator):
        zero = jnp.uint64(0)
        base_reward = (eff // increment) * brpi
        divisor = active_increments * jnp.uint64(weight_denominator)
        unslashed_all = ~slashed
        pairs = []
        sums = []
        target_unslashed = None
        for flag_index, weight in enumerate(weights):
            unslashed = (
                active_prev & unslashed_all & _bit_mask(prev_part, flag_index)
            )
            if flag_index == target_flag_index:
                target_unslashed = unslashed
            flag_sum = psum_u64(jnp.sum(jnp.where(unslashed, eff, zero)))
            sums.append(flag_sum)
            # get_total_balance floors at one increment
            unslashed_increments = (
                jnp.maximum(increment, flag_sum) // increment
            )
            w = jnp.uint64(weight)
            if leaking:
                rewards = jnp.zeros_like(base_reward)
            else:
                rewards = jnp.where(
                    eligible & unslashed,
                    base_reward * w * unslashed_increments // divisor,
                    zero,
                )
            if flag_index == head_flag_index:
                penalties = jnp.zeros_like(base_reward)
            else:
                penalties = jnp.where(
                    eligible & ~unslashed,
                    base_reward * w // jnp.uint64(weight_denominator),
                    zero,
                )
            pairs.append((rewards, penalties))

        # inactivity penalties off the POST-UPDATE scores (spec order)
        missed = eligible & ~target_unslashed
        inactivity_penalties = jnp.where(
            missed, eff * scores // denominator, zero
        )
        pairs.append((jnp.zeros_like(base_reward), inactivity_penalties))

        # apply in spec sequence with zero saturation BETWEEN pairs —
        # apply_delta_pairs_kernel's exact ops, plus the per-pair wrap
        # census the host path keeps
        wrapped = zero
        for rewards, penalties in pairs:
            raised = balances + rewards
            wrapped = wrapped + jnp.sum(
                (raised < balances).astype(jnp.uint64)
            )
            balances = jnp.where(raised >= penalties, raised - penalties, zero)
        wrapped_total = psum_u64(wrapped)
        return balances, wrapped_total, jnp.stack(sums)

    spec = P(SHARD_AXIS)
    return _obs.observe_jit(
        jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(spec,) * 7 + (P(),) * 4,
                out_specs=(spec, P(), P()),
                check_vma=False,
            )
        ),
        "parallel.epoch.rewards_sweep",
    )


class MeshEpochSweeps:
    """Host-facing runner: pads, ships, runs the sharded sweeps, and
    unpads — one instance per provisioned mesh (parallel/runtime.py).
    Every entry point is a drop-in for the host kernel it shadows and
    returns plain numpy (the epoch pass's working-column dtype)."""

    __slots__ = ("mesh", "n_dev")

    def __init__(self, mesh):
        self.mesh = mesh
        self.n_dev = int(mesh.devices.size)

    def _pad(self, arr, fill=0):
        n = arr.shape[0]
        padded = pad_to_mesh(n, self.n_dev)
        if padded == n:
            return np.ascontiguousarray(arr)
        out = np.full(padded, fill, dtype=arr.dtype)
        out[:n] = arr
        # bandwidth: the mesh staging copy (the upload itself is the
        # device observatory's h2d ledger; this is the host-side
        # re-materialization the padding costs)
        mem = _mem.OBSERVATORY
        if mem.active:
            mem.record_copy("parallel.pad_to_mesh", int(out.nbytes))
        return out

    def _shard(self, site: str, *columns):
        """Pad the host columns and place each ROW-SHARDED over the mesh:
        every device receives its own rows straight from the host (a
        plain ``jnp.asarray`` would land the whole column on device 0
        and leave the spreading to the jitted call)."""
        return _obs.h2d_put(
            site,
            columns,
            NamedSharding(self.mesh, P(SHARD_AXIS)),
        )

    def inactivity_scores(self, scores, eligible, participating, bias: int,
                          recovery_rate: int, leaking: bool):
        """Sharded ``process_inactivity_updates`` sweep; returns the new
        scores column (numpy uint64, original length)."""
        from . import runtime as _runtime

        n = scores.shape[0]
        # fault-injection seam: an injected fault raises before any
        # dispatch, and the caller's device-trouble fallback (the host
        # kernel) recovers bit-identically — blame journaled by the seam
        _runtime.fault_point(
            "epoch", stage="inactivity", validators=n, devices=self.n_dev
        )
        kernel = _inactivity_sharded(
            self.mesh, int(bias), int(recovery_rate), bool(leaking)
        )
        args = self._shard(
            "parallel.epoch.inactivity",
            self._pad(scores),
            self._pad(eligible, False),
            self._pad(participating, False),
        )
        out = kernel(*args)
        return _obs.d2h("parallel.epoch.inactivity", out)[:n]

    def fused(self, balances, eff, prev_part, slashed, active_prev,
              eligible, scores, increment: int, brpi: int,
              active_increments: int, denominator: int, bias: int,
              recovery_rate: int, weights: tuple, weight_denominator: int,
              leaking: bool, head_flag_index: int,
              target_flag_index: int) -> "tuple | None":
        """Inactivity + the full rewards stage as ONE sharded dispatch;
        returns ``(new_scores, new_balances)`` as numpy columns — or
        ``None`` when a u64 wrap surfaced (caller falls back to the
        staged host path and its literal overflow mirror)."""
        from . import runtime as _runtime

        n = balances.shape[0]
        _runtime.fault_point(
            "epoch", stage="fused", validators=n, devices=self.n_dev
        )
        kernel = _fused_sharded(
            self.mesh,
            int(bias),
            int(recovery_rate),
            tuple(int(w) for w in weights),
            int(weight_denominator),
            int(head_flag_index),
            int(target_flag_index),
        )
        sharded = self._shard(
            "parallel.epoch.fused",
            self._pad(balances),
            self._pad(eff),
            self._pad(prev_part),
            self._pad(slashed, False),
            self._pad(active_prev, False),
            self._pad(eligible, False),
            self._pad(scores),
        )
        scalars = (
            jnp.uint64(increment),
            jnp.uint64(brpi),
            jnp.uint64(active_increments),
            jnp.uint64(denominator),
            jnp.bool_(leaking),
        )
        new_scores, new_balances, wrapped = kernel(*sharded, *scalars)
        if int(wrapped):
            return None
        return (
            _obs.d2h("parallel.epoch.fused", new_scores)[:n],
            _obs.d2h("parallel.epoch.fused", new_balances)[:n],
        )

    def rewards(self, balances, eff, prev_part, slashed, active_prev,
                eligible, scores, increment: int, brpi: int,
                active_increments: int, denominator: int, weights: tuple,
                weight_denominator: int, leaking: bool,
                head_flag_index: int, target_flag_index: int):
        """The full rewards stage, sharded; returns the new balances
        column — or ``None`` when a u64 wrap surfaced (caller falls back
        to the host path and its literal overflow mirror)."""
        from . import runtime as _runtime

        n = balances.shape[0]
        _runtime.fault_point(
            "epoch", stage="rewards", validators=n, devices=self.n_dev
        )
        kernel = _rewards_sharded(
            self.mesh,
            tuple(int(w) for w in weights),
            int(weight_denominator),
            bool(leaking),
            int(head_flag_index),
            int(target_flag_index),
        )
        sharded = self._shard(
            "parallel.epoch.rewards",
            self._pad(balances),
            self._pad(eff),
            self._pad(prev_part),
            self._pad(slashed, False),
            self._pad(active_prev, False),
            self._pad(eligible, False),
            self._pad(scores),
        )
        scalars = (
            jnp.uint64(increment),
            jnp.uint64(brpi),
            jnp.uint64(active_increments),
            jnp.uint64(denominator),
        )
        new_balances, wrapped, _sums = kernel(*sharded, *scalars)
        if int(wrapped):
            return None
        return _obs.d2h("parallel.epoch.rewards", new_balances)[:n]
