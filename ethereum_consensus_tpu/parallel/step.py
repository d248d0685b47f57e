"""The distributed chain step: one epoch-boundary device sweep, sharded.

This is the multi-chip "training step" of the framework: the validator
registry (the only axis at mainnet scale — VALIDATOR_REGISTRY_LIMIT = 2^40,
phase0/presets/mainnet.rs:26) is sharded row-wise over the mesh, and one
jitted step performs, entirely on device:

  1. the effective-balance hysteresis sweep
     (reference: phase0/epoch_processing.rs process_effective_balance_updates)
  2. the total-active-balance reduction (``psum`` across chips)
  3. the SSZ ``hash_tree_root`` of the balances list — per-device subtree
     reduction, one ``all_gather`` of subtree roots over ICI, replicated top
     tree + length mix-in — bit-identical to the host merkleizer.

Exact u64 spec semantics require ``jax_enable_x64`` (SURVEY.md §7 hard
parts); callers enable it before building the step (see __graft_entry__ and
tests). Sweep math is exact integer arithmetic — no floats anywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..ops.merkle import reduce_levels
from ..ops.sha256 import sha256_64b
from ..ssz.merkle import next_pow_of_two
from ..telemetry import device as _obs
from .mesh import SHARD_AXIS, psum_u64

__all__ = [
    "make_chain_step",
    "make_epoch_sweep_step",
    "pad_registry_for_mesh",
    "run_chain_step",
    "u64_to_be_words",
]


def _bswap32(x):
    x = x.astype(jnp.uint32)
    return (
        (x >> np.uint32(24))
        | ((x >> np.uint32(8)) & np.uint32(0xFF00))
        | ((x << np.uint32(8)) & np.uint32(0xFF0000))
        | (x << np.uint32(24))
    )


def u64_to_be_words(values):
    """(N,) uint64 → (2N,) uint32: the big-endian-word view of the
    little-endian u64 byte serialization (SSZ basic-value packing)."""
    lo = (values & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (values >> jnp.uint64(32)).astype(jnp.uint32)
    return jnp.stack([_bswap32(lo), _bswap32(hi)], axis=1).reshape(-1)


def _length_words(length: int) -> np.ndarray:
    """(8,) uint32 word view of the SSZ length mix-in chunk."""
    chunk = length.to_bytes(8, "little") + b"\x00" * 24
    return np.frombuffer(chunk, dtype=">u4").astype(np.uint32)


# lru_cache IS the staging discipline here (speclint device/jit-outside-
# staging): every distinct (mesh, constants) tuple compiles exactly once
# per process, so a driver looping over epochs re-enters the SAME jitted
# step instead of re-tracing a fresh one each call.
@functools.lru_cache(maxsize=8)
def make_chain_step(
    mesh: Mesh,
    axis_name: str = SHARD_AXIS,
    registry_limit: int = 2**40,
    effective_balance_increment: int = 10**9,
    max_effective_balance: int = 32 * 10**9,
    hysteresis_quotient: int = 4,
    hysteresis_downward_multiplier: int = 1,
    hysteresis_upward_multiplier: int = 5,
):
    """Build the jitted distributed chain step over ``mesh``.

    Returns ``step(balances, effective_balances, active_mask, zero_words,
    length_words)`` where the first three are (N,) arrays sharded over
    ``axis_name`` (N divisible by mesh size; N/devices a power-of-two
    multiple of 4 — one SSZ chunk packs four u64 balances; use
    ``run_chain_step`` for arbitrary sizes, which zero-pads and passes the
    TRUE length's mix-in words), ``zero_words`` is
    ops.merkle.zero_hash_words() and ``length_words`` is the (8,) uint32
    word view of the SSZ length mix-in chunk.
    Returns ``(new_effective_balances, total_active_balance, balances_root)``
    with the root as (8,) uint32 words, replicated.
    """
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "make_chain_step needs exact u64 semantics: enable jax_enable_x64"
        )
    n_dev = mesh.shape[axis_name]
    chunk_limit = (registry_limit + 3) // 4
    depth = (next_pow_of_two(chunk_limit) - 1).bit_length()

    increment = np.uint64(effective_balance_increment)
    hysteresis_increment = np.uint64(effective_balance_increment // hysteresis_quotient)
    downward = hysteresis_increment * np.uint64(hysteresis_downward_multiplier)
    upward = hysteresis_increment * np.uint64(hysteresis_upward_multiplier)
    max_eff = np.uint64(max_effective_balance)

    def body(balances, eff, active, zero_words, length_words):
        local_n = balances.shape[0]
        if local_n % 4:
            raise ValueError("per-device balance count must be a multiple of 4")
        # each device must own a full, aligned 2^k-chunk subtree; otherwise
        # the zero-padded local reduction computes a root over misplaced
        # leaves (chunk owned by the next device replaced by a zero chunk)
        local_chunks = local_n // 4
        if local_chunks == 0 or local_chunks & (local_chunks - 1):
            raise ValueError(
                f"per-device chunk count {local_chunks} must be a power of two"
            )

        # 1. hysteresis sweep (epoch_processing.rs process_effective_balance_updates)
        candidate = jnp.minimum(balances - balances % increment, max_eff)
        new_eff = jnp.where(
            (balances + downward < eff) | (eff + upward < balances), candidate, eff
        )

        # 2. total active balance across the whole mesh
        total = psum_u64(
            jnp.sum(jnp.where(active, new_eff, jnp.uint64(0))), axis_name
        )

        # 3. hash_tree_root(balances): local subtree → all_gather → top tree
        words = u64_to_be_words(balances).reshape(local_n // 4, 8).T
        local_depth = (local_n // 4 - 1).bit_length()
        sub = reduce_levels(words, zero_words, local_depth)
        roots = jax.lax.all_gather(sub, axis_name)  # (n_dev, 8)
        merkle = reduce_levels(roots.T, zero_words, depth, start_level=local_depth)
        # SSZ List → mix_in_length(root, true length)
        msg = jnp.concatenate([merkle, length_words]).reshape(16, 1)
        root = sha256_64b(msg)[:, 0]
        return new_eff, total, root

    # check_vma=False: the SHA-256 fori_loop carries a mix of unvarying
    # (padding-block literals) and device-varying lanes, which the vma type
    # system rejects; replication of the psum/top-tree outputs is guaranteed
    # by construction here.
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(axis_name), P(axis_name), P(axis_name), P(None, None), P(None),
            ),
            out_specs=(P(axis_name), P(), P(None)),
            check_vma=False,
        )
    )


def pad_registry_for_mesh(n: int, n_dev: int) -> int:
    """Padded registry length for an arbitrary ``n`` on an ``n_dev`` mesh:
    each device owns an aligned power-of-two subtree of whole SSZ chunks
    (4 u64 per chunk). Zero-padding is exactly the merkleizer's own
    padding, so roots are unchanged as long as the TRUE length feeds the
    SSZ length mix-in."""
    per_dev_chunks = next_pow_of_two(max(1, -(-n // (4 * n_dev))))
    return n_dev * per_dev_chunks * 4


def run_chain_step(step, mesh, balances, effective, active, zero_words,
                   axis_name: str = SHARD_AXIS):
    """Host wrapper around ``make_chain_step``'s jitted step for ARBITRARY
    (non-aligned) registry sizes: zero-pads the inputs to the mesh-aligned
    width (inactive padding cannot contribute to the psum total, and zero
    chunks are the merkleizer's own padding), runs the step with the true
    length in the mix-in, and slices the padded tail back off."""
    n = len(balances)
    n_dev = mesh.shape[axis_name]
    padded = pad_registry_for_mesh(n, n_dev)
    bal = np.zeros(padded, np.uint64)
    bal[:n] = balances
    eff = np.zeros(padded, np.uint64)
    eff[:n] = effective
    act = np.zeros(padded, np.bool_)
    act[:n] = active
    bal_d, eff_d, act_d, len_d = _obs.h2d(
        "parallel.step.registry", bal, eff, act, _length_words(n)
    )
    new_eff, total, root_words = step(bal_d, eff_d, act_d, zero_words, len_d)
    return (
        _obs.d2h("parallel.step.new_effective", new_eff)[:n],
        int(total),
        _obs.d2h("parallel.step.balances_root", root_words),
    )


def make_epoch_sweep_step(
    mesh: Mesh,
    context,
    axis_name: str = SHARD_AXIS,
    is_leaking: bool = False,
    check_score_bound: bool = True,
):
    """The distributed altair epoch sweep (the real per-epoch hot loop):
    inactivity-score updates, the three participation-flag delta sweeps,
    inactivity penalties, and balance application — sharded row-wise over
    the mesh with ``psum`` totals, matching altair
    process_inactivity_updates + process_rewards_and_penalties
    (epoch_processing.rs:104,160) bit-for-bit including saturating
    decreases and application order.

    Returns ``step(balances, effective, participation, slashed,
    active_previous, active_current, eligible, scores)`` over sharded (N,)
    arrays → ``(new_balances, new_scores, total_active_balance)``.
    ``participation`` is the uint8 flag byte for the delta epoch
    (previous, or current in the genesis corner — the caller picks when
    packing, see models.registry_columns.pack_registry).

    Precondition for the bit-identical guarantee: every
    ``effective_balance * inactivity_score`` product must fit in uint64,
    i.e. max score < 2^64 / max_effective_balance (~5.8e8 at 32 ETH,
    ~9e6 at electra's 2048 ETH cap — both need a years-long leak).
    Inside jit the sweep cannot branch on data, so by default the
    returned step wraps the jitted kernel with a host-side check of
    ``max(effective) * max(scores)`` (one small device reduction + sync
    per call) and raises ``OverflowError`` when the bound is exceeded —
    that epoch must then run through the host spec path. Pass ``check_score_bound=False`` to get the raw jitted step
    for composition inside a larger jit.

    The context object is unhashable, so this wrapper extracts the five
    scalars the sweep actually closes over and defers to the lru-cached
    factory — two epochs under the same constants share ONE compiled
    step (speclint device/jit-outside-staging)."""
    return _epoch_sweep_step(
        mesh,
        int(context.EFFECTIVE_BALANCE_INCREMENT),
        int(context.BASE_REWARD_FACTOR),
        int(context.inactivity_score_bias),
        int(context.inactivity_score_recovery_rate),
        int(context.INACTIVITY_PENALTY_QUOTIENT_ALTAIR),
        axis_name,
        is_leaking,
        check_score_bound,
    )


@functools.lru_cache(maxsize=16)
def _epoch_sweep_step(
    mesh: Mesh,
    effective_balance_increment: int,
    base_reward_factor_int: int,
    inactivity_score_bias: int,
    inactivity_score_recovery_rate: int,
    inactivity_penalty_quotient: int,
    axis_name: str,
    is_leaking: bool,
    check_score_bound: bool,
):
    from ..models.altair.constants import (
        PARTICIPATION_FLAG_WEIGHTS,
        TIMELY_HEAD_FLAG_INDEX,
        TIMELY_TARGET_FLAG_INDEX,
        WEIGHT_DENOMINATOR,
    )

    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "make_epoch_sweep_step needs exact u64 semantics: enable jax_enable_x64"
        )

    increment = np.uint64(effective_balance_increment)
    base_reward_factor = np.uint64(base_reward_factor_int)
    score_bias = np.uint64(inactivity_score_bias)
    recovery_rate = np.uint64(inactivity_score_recovery_rate)
    inactivity_quotient = np.uint64(inactivity_penalty_quotient)

    def _isqrt(x):
        guess = jnp.sqrt(x.astype(jnp.float64)).astype(jnp.uint64) + jnp.uint64(1)

        def newton(_, g):
            g = jnp.maximum(g, jnp.uint64(1))
            return (g + x // g) >> jnp.uint64(1)

        g = jax.lax.fori_loop(0, 6, newton, guess)
        g = jnp.where(g * g > x, g - jnp.uint64(1), g)
        return jnp.where((g + 1) * (g + 1) <= x, g + jnp.uint64(1), g)

    def body(balances, eff, participation, slashed, active_prev, active_cur,
             eligible, scores):
        # --- process_inactivity_updates (epoch_processing.rs:104) ---
        target_participating = (
            ((participation >> np.uint8(TIMELY_TARGET_FLAG_INDEX)) & 1).astype(bool)
            & ~slashed
            & active_prev
        )
        decreased = scores - jnp.minimum(jnp.uint64(1), scores)
        increased = scores + score_bias
        new_scores = jnp.where(
            eligible,
            jnp.where(target_participating, decreased, increased),
            scores,
        )
        if not is_leaking:
            new_scores = jnp.where(
                eligible,
                new_scores - jnp.minimum(recovery_rate, new_scores),
                new_scores,
            )

        # --- totals (psum over the mesh — the ICI collectives) ---
        total_active = psum_u64(
            jnp.sum(jnp.where(active_cur, eff, jnp.uint64(0))), axis_name
        )
        total_active = jnp.maximum(total_active, increment)
        base_reward_per_increment = increment * base_reward_factor // _isqrt(
            total_active
        )
        base_reward = (eff // increment) * base_reward_per_increment
        active_increments = total_active // increment

        # --- the three flag-delta sweeps (helpers.rs:265) ---
        new_balances = balances
        for flag_index, weight in enumerate(PARTICIPATION_FLAG_WEIGHTS):
            w = jnp.uint64(weight)
            participating = (
                ((participation >> np.uint8(flag_index)) & 1).astype(bool)
                & ~slashed
                & active_prev
            )
            unslashed_increments = (
                psum_u64(
                    jnp.sum(jnp.where(participating, eff, jnp.uint64(0))),
                    axis_name,
                )
                // increment
            )
            rewards = jnp.where(
                participating & eligible & jnp.bool_(not is_leaking),
                base_reward
                * w
                * unslashed_increments
                // (active_increments * jnp.uint64(WEIGHT_DENOMINATOR)),
                jnp.uint64(0),
            )
            if flag_index == TIMELY_HEAD_FLAG_INDEX:
                penalties = jnp.zeros_like(rewards)
            else:
                penalties = jnp.where(
                    eligible & ~participating,
                    base_reward * w // jnp.uint64(WEIGHT_DENOMINATOR),
                    jnp.uint64(0),
                )
            # spec application order: increase then saturating decrease
            new_balances = new_balances + rewards
            new_balances = new_balances - jnp.minimum(penalties, new_balances)

        # --- inactivity penalties (uses the UPDATED scores) ---
        not_target = eligible & ~target_participating
        inactivity_penalties = jnp.where(
            not_target,
            eff * new_scores // (score_bias * inactivity_quotient),
            jnp.uint64(0),
        )
        new_balances = new_balances - jnp.minimum(inactivity_penalties, new_balances)

        return new_balances, new_scores, total_active

    spec = P(axis_name)
    jitted = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(spec,) * 8,
            out_specs=(spec, spec, P()),
            check_vma=False,
        )
    )
    if not check_score_bound:
        return jitted

    def checked_step(balances, eff, participation, slashed, active_prev,
                     active_cur, eligible, scores):
        max_product = int(jnp.max(eff)) * int(jnp.max(scores))
        if max_product >= 1 << 64:
            raise OverflowError(
                "inactivity score × effective balance exceeds uint64: the "
                "device epoch sweep would wrap; route this epoch through "
                "the host spec path (see make_epoch_sweep_step docstring)"
            )
        return jitted(balances, eff, participation, slashed, active_prev,
                      active_cur, eligible, scores)

    return checked_step
