"""Columnar-primary epoch transition engine (docs/OPS_VECTOR.md).

The ownership inversion this module implements: for the epoch hot path
the ``RegistryColumns`` arrays are the AUTHORITATIVE store of validator
epoch fields, balances, participation, inactivity and slashed /
credential-prefix data, and the SSZ list elements are a materialization
— produced once per epoch, at commit: the validators' changed fields
by per-hit writes, the scalar lists by handing the finished column to
``ops_vector.adopt_list_column``, which makes it the list's content
without boxing a row (``ssz/column_list.py``). Everything the
epoch transition computes between sync and commit reads and writes the
arrays; no stage walks ``state.validators`` elements, so the pass costs
vector passes + a handful of per-hit writes instead of ~10 Python
sweeps over a million-validator registry.

One engine serves all six forks (phase0 → electra, including electra's
EIP-7251 churn stages: pending balance deposits and pending
consolidations). Each fork's ``process_epoch`` calls
``process_epoch_columnar(state, context, fork)`` first and falls back
to its literal stage list when the engine declines — no numpy, the
engine disabled (``ECT_OPS_VECTOR=off`` / ``ECT_EPOCH_VECTOR=off``),
registry below ``EPOCH_VECTOR_MIN_VALIDATORS``, or a value outside the
u64 lane contract. The literal loops remain the oracle:
tests/test_epoch_vector.py diffs root AND bytes across every fork,
including the churn scenarios.

Soundness rules:

* every fallback decision happens BEFORE any state mutation (the
  upfront guards in ``_sync``), so a declined pass leaves the state
  untouched for the literal path — bit-identity is structural;
* scalar container writes that later columnar stages READ (the
  justification checkpoint updates feeding the registry stage's
  finalized-epoch predicate, electra's churn scalars) happen in spec
  order on the state itself — they are O(1);
* the per-epoch memo caches the scalar helpers consult
  (``_total_active_balance_cache``) are SEEDED from the columns with
  exactly the value the scalar sweep would compute, so a mid-pass
  helper call never pays (or needs) a per-validator walk — asserted by
  the bench: no ``helpers.active_indices_sweep`` /
  ``helpers.total_balance_sweep`` span and zero
  ``epoch_vector.fallback.*`` inside a warm epoch pass.

The numeric cores (``inactivity_scores_kernel``, ``flag_deltas_kernel``,
``apply_delta_pairs_kernel``) are written against an ``xp`` array
namespace with every scalar wrapped to uint64 and no data-dependent
Python branching — they run under numpy on the host path and are
XLA-jittable as-is (tests/test_epoch_vector.py jits them under
``jax.numpy`` with x64 enabled and asserts bit-identical outputs); the
u64-overflow guards live in the CALLER, which routes pathological
states to exact Python-int fallbacks before any kernel runs. On the
device routes the altair-family inactivity + rewards stages collapse
into the ONE ``fused_epoch_kernel`` dispatch (``jitted_kernels()``'s
``fused_epoch``, selected by ops.install's ``sweeps_min_n`` gate and
by nothing else; ``MeshEpochSweeps.fused`` under ``ECT_MESH``) — packed
columns upload once and stay on device across the stages, with the
staged host kernels as the live fallback (declines in
``epoch_vector.fused_fallback.{reason}``).
phase0's justification and rewards are fed by the committee-mask
kernel (``models/committees.py``), with the spec-helper walks as
fallback + oracle.

Telemetry: ``epoch_vector.epochs`` counts engaged passes,
``epoch_vector.fallback.{reason}`` every decline (one-shot trace event
per reason), and per-stage spans (``epoch_vector.justification`` …
``epoch_vector.commit``) give the bench its per-phase attribution. What
a pass did to the registry: ``epoch_vector.rows``, ``.rows_active``
(where the churn limit was asked for), ``.registry.queued`` (rows whose
eligibility epoch was stamped), ``.registry.activated`` (rows dequeued)
and ``.validator_writes`` (validator fields written at commit); the
registry span carries ``queued`` and ``activated``, the commit span
``writes``. A registry that grows: ``epoch_vector.rows_appended`` (rows
by which the registry is longer than at the last pass on this state's
lineage), ``ops_vector.columns.extended_rows`` (rows the working columns
were extended by, not rebuilt; span ``epoch_vector.sync.extend``) and
``epoch_vector.fused.pad_rows`` (inert rows a fused dispatch carried:
its shape is ``fused_dispatch_rows(n)``, not ``n``); the fused span
carries ``rows`` and ``padded``. The sweep before the pass (span
``epoch_vector.sync.scan``): ``epoch_vector.scan.threads`` (the host
threads it ran on) and ``epoch_vector.scan.fallback`` (passes that took
the numpy sequence, reason on a one-shot event). The period
boundaries: span ``epoch_vector.sync_committee`` (children ``.active``,
``.sample``, ``.aggregate``) and counter
``epoch_vector.sync_committee.rotations``; the sampler's counters
``epoch_vector.sync_committee.batched`` (rotations it drew natively),
``.candidates`` (candidates drawn) and ``.fallback`` (rotations the
literal helper drew, reason on an event); span
``epoch_vector.historical_summary`` and counter
``epoch_vector.historical_summaries``.
"""

from __future__ import annotations

import hashlib
import threading

from .. import _device_flags, _env
from ..primitives import FAR_FUTURE_EPOCH, GENESIS_EPOCH
from ..telemetry import device as _device_obs
from ..telemetry import metrics
from ..utils import trace
from . import ops_vector

__all__ = [
    "process_epoch_columnar",
    "inactivity_scores_kernel",
    "flag_deltas_kernel",
    "apply_delta_pairs_kernel",
    "fused_epoch_kernel",
    "u32_planes",
    "u64_columns",
    "jitted_kernels",
    "fused_dispatch_rows",
    "EPOCH_VECTOR_MIN_VALIDATORS",
]

# Below this registry size the literal Python stages win (column sync +
# working-array copies cost more than the loops they replace); the
# differential tests lower it to 0 to force the engine on tiny states.
EPOCH_VECTOR_MIN_VALIDATORS = 1 << 12

_DISABLE_ENV = "ECT_EPOCH_VECTOR"  # =off disables just this engine

_U64_MAX = (1 << 64) - 1
# every balance/epoch value the pass computes with stays below 2^63 so
# u64 adds can never wrap mid-kernel; states outside the lane fall back
# to the literal loops BEFORE any mutation
_LANE_MAX = 1 << 63

_FALLBACK_SEEN: set = set()
_FALLBACK_LOCK = threading.Lock()


def _np():
    try:
        import numpy

        return numpy
    except Exception:  # noqa: BLE001 — environment without numpy
        return None


def fallback(reason: str, **inputs) -> None:
    """Count a decline to the literal epoch path (trace event once per
    reason per process, mirroring ops_vector.fallback). EVERY decline
    path runs through here — including the deliberate one
    (``below_threshold``): a production-threshold decline is a
    routing decision worth seeing. While the device observatory is on,
    the decline also lands in its routing journal with the threshold
    inputs (telemetry/device.py)."""
    metrics.counter(f"epoch_vector.fallback.{reason}").inc()
    if _device_obs.OBSERVATORY.active:
        _device_obs.route("epoch_vector", "literal", reason, **inputs)
    if reason not in _FALLBACK_SEEN:
        with _FALLBACK_LOCK:
            if reason not in _FALLBACK_SEEN:
                _FALLBACK_SEEN.add(reason)
                trace.event("epoch_vector.fallback", reason=reason)


def _mesh_requested() -> bool:
    """Plain env read — the parallel.runtime import (and with it jax)
    only happens when the mesh is actually switched on (ECT_MESH)."""
    return _env.mesh_requested()


_JITTED_KERNELS = {}
_JITTED_KERNELS_LOCK = threading.Lock()


def jitted_kernels() -> dict:
    """The three numeric cores bound to ``jax.numpy``, jitted, and
    wrapped through the device observatory's compile ledger
    (telemetry/device.py ``observe_jit``) — the XLA route for the device
    epoch kernel (the ROADMAP's "put the kernels on the chip" residue).
    Production host passes keep the numpy ``xp``; this surface exists so
    the device route, its compile/recompile telemetry, and the
    jit-identity tests all exercise the SAME wrapped callables. Returns
    ``{"inactivity_scores": fn, "flag_deltas": fn, "apply_delta_pairs":
    fn, "fused_epoch": fn}``; built once per process. ``fused_epoch``
    alone does not return its kernel's tuple: its two u64 columns leave
    the program as their 32-bit words (``u32_planes``), which is the
    form the host takes them off the device in."""
    if _JITTED_KERNELS:
        return _JITTED_KERNELS
    with _JITTED_KERNELS_LOCK:
        if _JITTED_KERNELS:
            return _JITTED_KERNELS
        import jax
        import jax.numpy as jnp

        jax.config.update("jax_enable_x64", True)

        def bound(name: str, kernel):
            """``kernel`` with ``jax.numpy`` for its ``xp``, under a name
            of its own: jit calls the program ``jit_<name>``, which is
            what a device trace prints (a ``functools.partial`` has no
            name and prints ``jit__unknown``)."""

            def run(*args):
                return kernel(jnp, *args)

            run.__name__ = run.__qualname__ = name
            return run

        def fused_epoch(*args):
            """``fused_epoch_kernel`` with its two result columns split
            into their 32-bit words inside the same program: the chip
            has no 64-bit lanes, and a 64-bit array comes off it twenty
            times slower a byte than the same bytes as u32 (PERF.md
            section 7 (c))."""
            scores, balances, wrapped = fused_epoch_kernel(jnp, *args)
            return u32_planes(jnp, scores, balances), wrapped

        built = {
            "inactivity_scores": _device_obs.observe_jit(
                jax.jit(
                    bound("inactivity_scores", inactivity_scores_kernel),
                    static_argnums=(3, 4, 5),  # bias, recovery, leaking
                ),
                "epoch_vector.inactivity_scores_kernel",
            ),
            "flag_deltas": _device_obs.observe_jit(
                jax.jit(
                    bound("flag_deltas", flag_deltas_kernel),
                    # weight, increments, denominator, leaking, head flag
                    static_argnums=(3, 4, 5, 6, 7, 8),
                ),
                "epoch_vector.flag_deltas_kernel",
            ),
            "apply_delta_pairs": _device_obs.observe_jit(
                jax.jit(bound("apply_delta_pairs", apply_delta_pairs_kernel)),
                "epoch_vector.apply_delta_pairs_kernel",
            ),
            # the FUSED device epoch kernel (ISSUE 14): inactivity +
            # flag deltas + inactivity penalties + application as ONE
            # dispatch — dynamic per-epoch u64 scalars, static chain
            # constants, so a steady-state replay compiles exactly once.
            # ``leaking`` (argument 15) is traced with the scalars: the
            # boundary at which finality is lost, or comes back, runs
            # the program the boundaries before it compiled
            "fused_epoch": _device_obs.observe_jit(
                jax.jit(
                    fused_epoch,
                    # bias, recovery, weights, weight_denominator,
                    # head/target flag indices
                    static_argnums=(11, 12, 13, 14, 16, 17),
                ),
                "epoch_vector.fused_epoch_kernel",
            ),
        }
        _JITTED_KERNELS.update(built)
    return _JITTED_KERNELS


# The fused program is dispatched at the registry's length rounded up to
# a whole granule of rows, so that its shape (which keys the trace, the
# XLA compile and the persistent cache) changes once in 128 epochs of
# MAX_DEPOSITS x SLOTS_PER_EPOCH = 512 new validators and not at every
# epoch that held one deposit. At most 3.4 % more rows at 1.9 M; 2^20 and
# 2^21 pad nothing. A module global, as the dirty-group geometry of
# ssz/core.py is, so that tests can shrink it and cross an edge cheaply.
FUSED_ROW_GRANULE = 1 << 16


def fused_dispatch_rows(n: int) -> int:
    """The length ``_fused_route`` dispatches the fused program at for a
    registry of ``n`` rows: a function of ``n`` alone."""
    return -(-n // FUSED_ROW_GRANULE) * FUSED_ROW_GRANULE


def _padded(np, column, rows: int):
    """``column`` at the dispatched length: itself where it has it, else
    an owned copy with zero rows behind it. A pad row is inert by
    construction: not eligible and not active (the two masks gate every
    reward, penalty, score update and masked sum of the kernel), its
    effective balance, balance, score and flags 0 (so it adds 0 to 0 and
    the wrap census cannot see it)."""
    n = column.shape[0]
    if n == rows:
        return column
    out = np.empty(rows, dtype=column.dtype)
    out[:n] = column
    out[n:] = 0
    return out


def kernel_cache_census() -> "tuple[int, int]":
    """(bytes, entries) for the memory observatory's
    ``epoch_vector.jit_kernels`` owner (telemetry/memory.py): one entry
    per wrapped kernel plus its executable-cache population beyond the
    first (``_cache_size``). Bytes stay 0 — XLA does not expose
    executable sizes, and an honest unknown beats a guess."""
    entries = 0
    for kernel in _JITTED_KERNELS.values():
        entries += 1 + max(0, kernel.__wrapped__._cache_size() - 1)
    return 0, entries


def _disabled() -> bool:
    return _env.flag_off(_DISABLE_ENV) or _env.flag_off(ops_vector._DISABLE_ENV)


# ---------------------------------------------------------------------------
# XLA-jittable numeric kernels (xp = numpy | jax.numpy; scalars uint64)
# ---------------------------------------------------------------------------


def inactivity_scores_kernel(xp, scores, eligible, participating, bias,
                             recovery_rate, leaking):
    """altair ``process_inactivity_updates`` over columns — per eligible
    validator: participating → score -= min(1, score); absent → score +=
    bias; then (outside a leak) score -= min(recovery_rate, score).
    ``leaking`` is a static Python bool (jit static arg)."""
    one = xp.uint64(1)
    hit = eligible & participating
    miss = eligible & ~participating
    new = xp.where(hit, scores - xp.minimum(one, scores), scores)
    new = xp.where(miss, new + xp.uint64(bias), new)
    if not leaking:
        rec = xp.uint64(recovery_rate)
        new = xp.where(eligible, new - xp.minimum(rec, new), new)
    return new


def flag_deltas_kernel(xp, base_reward, eligible, unslashed, weight,
                       unslashed_increments, active_increments,
                       weight_denominator, leaking, is_head_flag):
    """One participation flag's (rewards, penalties) pair — the altair
    flag-delta formula with the spec's two-step floor division.
    ``weight``/``*_increments``/``leaking``/``is_head_flag`` are static
    scalars; products stay in u64 by the caller's lane guard."""
    zero = xp.uint64(0)
    if leaking:
        rewards = xp.zeros_like(base_reward)  # no flag rewards in a leak
    else:
        attesting = eligible & unslashed
        rewards = xp.where(
            attesting,
            (
                base_reward
                * xp.uint64(weight)
                * xp.uint64(unslashed_increments)
            )
            // xp.uint64(active_increments * weight_denominator),
            zero,
        )
    if is_head_flag:
        penalties = xp.zeros_like(base_reward)
    else:
        absent = eligible & ~unslashed
        penalties = xp.where(
            absent,
            base_reward * xp.uint64(weight) // xp.uint64(weight_denominator),
            zero,
        )
    return rewards, penalties


def apply_delta_pairs_kernel(xp, balances, pairs):
    """Apply (rewards, penalties) pairs IN SEQUENCE, saturating at zero
    between pairs — the spec's application order (summing first and
    clamping once diverges for a low-balance validator whose early-pair
    penalty saturates before a later-pair reward lands)."""
    zero = xp.uint64(0)
    for rewards, penalties in pairs:
        raised = balances + rewards
        balances = xp.where(raised >= penalties, raised - penalties, zero)
    return balances


def fused_epoch_kernel(xp, balances, eff, prev_part, slashed, active_prev,
                       eligible, scores, increment, brpi, active_increments,
                       denominator, bias, recovery_rate, weights,
                       weight_denominator, leaking, head_flag_index,
                       target_flag_index, psum=None):
    """The altair-family epoch delta passes FUSED into one kernel:
    inactivity score update → three flag-delta pairs off in-kernel
    masked effective-balance sums → inactivity penalties off the
    POST-update scores → in-order saturating application with a wrap
    census. Operation-for-operation the staged kernels above (which stay
    the live host fallback), so the outputs are bit-identical u64.

    ``increment``/``brpi``/``active_increments``/``denominator`` are
    DYNAMIC u64 scalars (a steady-state replay compiles once), and so is
    ``leaking`` (a bool): it only selects, with ``where``, between two
    pairs of scalars (the recovery rate or 0, a flag's increments or 0),
    so one program serves the epochs with finality and those without,
    neither crossing compiles, and the rows' arithmetic is the same.
    ``bias``/``recovery_rate``/``weights``/``weight_denominator``/flag
    indices are static chain constants. ``psum`` wraps the scalar
    reductions for the mesh-sharded twin (parallel/epoch.py); None runs
    them whole-array.

    Returns ``(new_scores, new_balances, wrapped_lanes)`` — a nonzero
    wrap count means a u64 wrap the caller's lane guards should have
    made unreachable; the caller re-runs the staged path so the literal
    overflow mirror raises its structured error."""
    zero = xp.uint64(0)
    one = xp.uint64(1)
    unslashed_all = ~slashed
    target_bit = (
        (prev_part >> xp.uint8(target_flag_index)) & xp.uint8(1)
    ).astype(bool)
    participating = active_prev & unslashed_all & target_bit

    # process_inactivity_updates (spec order: before the reward deltas)
    new_scores = xp.where(
        eligible & participating, scores - xp.minimum(one, scores), scores
    )
    new_scores = xp.where(
        eligible & ~participating, new_scores + xp.uint64(bias), new_scores
    )
    # a leak's two differences are two scalars, so no row pays for the
    # choice: the recovery rate is 0, and no increment earns a flag reward
    finalizing = xp.logical_not(leaking)
    rec = xp.where(finalizing, xp.uint64(recovery_rate), zero)
    new_scores = xp.where(
        eligible, new_scores - xp.minimum(rec, new_scores), new_scores
    )

    base_reward = (eff // increment) * brpi
    divisor = active_increments * xp.uint64(weight_denominator)
    pairs = []
    target_unslashed = None
    for flag_index, weight in enumerate(weights):
        flag_bit = (
            (prev_part >> xp.uint8(flag_index)) & xp.uint8(1)
        ).astype(bool)
        unslashed = active_prev & unslashed_all & flag_bit
        if flag_index == target_flag_index:
            target_unslashed = unslashed
        flag_sum = xp.sum(xp.where(unslashed, eff, zero))
        if psum is not None:
            flag_sum = psum(flag_sum)
        # get_total_balance floors at one increment
        unslashed_increments = xp.maximum(increment, flag_sum) // increment
        w = xp.uint64(weight)
        rewarded_increments = xp.where(finalizing, unslashed_increments, zero)
        rewards = xp.where(
            eligible & unslashed,
            base_reward * w * rewarded_increments // divisor,
            zero,
        )
        if flag_index == head_flag_index:
            penalties = xp.zeros_like(base_reward)
        else:
            penalties = xp.where(
                eligible & ~unslashed,
                base_reward * w // xp.uint64(weight_denominator),
                zero,
            )
        pairs.append((rewards, penalties))

    # inactivity penalties off the POST-update scores (spec order)
    missed = eligible & ~target_unslashed
    pairs.append(
        (
            xp.zeros_like(base_reward),
            xp.where(missed, eff * new_scores // denominator, zero),
        )
    )

    # apply in spec sequence with zero saturation BETWEEN pairs, keeping
    # the per-pair wrap census the staged path checks
    wrapped = zero
    new_balances = balances
    for rewards, penalties in pairs:
        raised = new_balances + rewards
        wrapped = wrapped + xp.sum((raised < new_balances).astype(xp.uint64))
        new_balances = xp.where(
            raised >= penalties, raised - penalties, zero
        )
    if psum is not None:
        wrapped = psum(wrapped)
    return new_scores, new_balances, wrapped


def u32_planes(xp, *columns):
    """u64 columns as their 32-bit words, ``uint32[2 * len(columns), n]``:
    each column's low plane, then its high plane. The form
    ``jitted_kernels()['fused_epoch']`` returns its results in;
    ``u64_columns`` is the inverse on the host."""
    low_word = xp.uint64(0xFFFFFFFF)
    planes = []
    for column in columns:
        planes.append((column & low_word).astype(xp.uint32))
        planes.append((column >> xp.uint64(32)).astype(xp.uint32))
    return xp.stack(planes)


def u64_columns(planes) -> list:
    """The host inverse of ``u32_planes``: one fresh, owned, contiguous
    ``uint64[n]`` per (low, high) pair of rows, each plane written into
    its strided half of the column's little-endian words (cheaper than
    ``lo | hi << 32``, which makes two u64 temporaries)."""
    np = _np()
    n = planes.shape[1]
    columns = []
    for k in range(0, planes.shape[0], 2):
        column = np.empty(n, dtype="<u8")
        words = column.view("<u4").reshape(n, 2)
        words[:, 0] = planes[k]
        words[:, 1] = planes[k + 1]
        columns.append(column)
    return columns


# ---------------------------------------------------------------------------
# fork knobs
# ---------------------------------------------------------------------------

# family: "phase0" (pending-attestation rewards) | "altair" (flag rewards)
# quot: the fork's inactivity-penalty quotient attribute
# slash_mult: the fork's proportional slashing multiplier attribute
# historical: "roots" | "summaries"
# activation: "churn" (exit churn cap) | "activation_churn" (EIP-7514) |
#             "unbounded" (EIP-7251)
_FORK_CFG = {
    "phase0": dict(family="phase0", quot=None,
                   slash_mult="PROPORTIONAL_SLASHING_MULTIPLIER",
                   historical="roots", activation="churn"),
    "altair": dict(family="altair", quot="INACTIVITY_PENALTY_QUOTIENT_ALTAIR",
                   slash_mult="PROPORTIONAL_SLASHING_MULTIPLIER_ALTAIR",
                   historical="roots", activation="churn"),
    "bellatrix": dict(family="altair",
                      quot="INACTIVITY_PENALTY_QUOTIENT_BELLATRIX",
                      slash_mult="PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX",
                      historical="roots", activation="churn"),
    "capella": dict(family="altair",
                    quot="INACTIVITY_PENALTY_QUOTIENT_BELLATRIX",
                    slash_mult="PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX",
                    historical="summaries", activation="churn"),
    "deneb": dict(family="altair",
                  quot="INACTIVITY_PENALTY_QUOTIENT_BELLATRIX",
                  slash_mult="PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX",
                  historical="summaries", activation="activation_churn"),
    "electra": dict(family="altair",
                    quot="INACTIVITY_PENALTY_QUOTIENT_BELLATRIX",
                    slash_mult="PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX",
                    historical="summaries", activation="unbounded"),
}

_TIMELY_TARGET_FLAG_INDEX = 1  # altair constants; import-checked in _sync


class _EpochColumns:
    """The pass's working set: read-only BASE views straight off the
    list-resident column caches, and owned WORK copies the stages
    mutate. Commit diffs work against base per column."""

    __slots__ = (
        "np", "state", "context", "fork", "cfg", "n", "cur", "prev",
        "increment",
        # base views (never written)
        "b_eff", "b_elig", "b_act", "b_exit", "b_wdr", "b_prefix",
        "b_balances", "b_inact",
        "slashed", "prev_part", "cur_part",
        # working copies (authoritative during the pass)
        "eff", "elig", "act", "exit", "wdr", "prefix",
        "balances", "inact",
        # lazy scalars
        "_total_active", "_active_cur_count",
        # the pre-pass sweep's maxima, counts and masked eff sums (_scan)
        "scan",
        # masks at the pre-pass registry (activity is stable within the
        # epoch window — every spec write targets future epochs)
        "active_prev", "active_cur", "eligible",
        "credential_switches",
        # the mesh runner for this pass (parallel/runtime.py) — None
        # when the mesh is off/declined, and the host kernels run
        "mesh",
        # the jitted fused epoch kernel when ops.install's sweeps gate
        # selected it (None = host/mesh routes decide)
        "fused",
    )


# a sweep thread is worth its start and join from this many rows up: on the
# v5e's 13-core host each thread past the first costs about 0.25 ms, and a
# thread sweeps 2^18 rows in about 1.6 ms (PERF.md section 6, the thread curve)
SCAN_MIN_ROWS_PER_THREAD = 1 << 18

_SCAN_FALLBACK_SEEN: set = set()


def _scan_fallback(reason: str) -> None:
    """A pass whose sweep took the numpy sequence: counted, and a trace
    event once per reason per process."""
    metrics.counter("epoch_vector.scan.fallback").inc()
    if reason not in _SCAN_FALLBACK_SEEN:
        with _FALLBACK_LOCK:
            if reason not in _SCAN_FALLBACK_SEEN:
                _SCAN_FALLBACK_SEEN.add(reason)
                trace.event("epoch_vector.scan.fallback", reason=reason)


def _scan_columns(ec) -> tuple:
    return (
        ec.b_balances, ec.b_eff, ec.b_act, ec.b_exit, ec.b_wdr, ec.slashed,
        ec.prev_part, ec.cur_part, ec.b_inact,
    )


def _scan(ec) -> dict:
    """Set ``ec.active_prev``, ``ec.active_cur`` and ``ec.eligible`` and
    return the scalars of ``native.epoch_scan.SCAN_FIELDS``: one native
    sweep over the columns on min(usable cores, rows / 2^18) host threads,
    or, where it cannot run, the numpy sequence it replaces (counted)."""
    from ..native import epoch_scan, usable_cores

    if not epoch_scan.available():
        _scan_fallback("native_unavailable")
        return _scan_numpy(ec)
    threads = max(1, min(usable_cores(), ec.n // SCAN_MIN_ROWS_PER_THREAD))
    swept = epoch_scan.epoch_scan(
        ec.np, *_scan_columns(ec), ec.prev, ec.cur,
        _TIMELY_TARGET_FLAG_INDEX, threads,
    )
    if swept is None:
        _scan_fallback("column_layout")
        return _scan_numpy(ec)
    masks, scan, ran = swept
    ec.active_prev, ec.active_cur, ec.eligible = masks
    metrics.counter("epoch_vector.scan.threads").inc(ran)
    return scan


def _scan_numpy(ec) -> dict:
    """``_scan``'s numpy sequence: the fallback, and the oracle the
    native sweep is tested against."""
    np = ec.np
    far = np.uint64(FAR_FUTURE_EPOCH)
    real_exits = ec.b_exit[ec.b_exit != far]
    prev64 = np.uint64(ec.prev)
    cur64 = np.uint64(ec.cur)
    ec.active_prev = (ec.b_act <= prev64) & (prev64 < ec.b_exit)
    ec.active_cur = (ec.b_act <= cur64) & (cur64 < ec.b_exit)
    ec.eligible = ec.active_prev | (
        ec.slashed & (prev64 + np.uint64(1) < ec.b_wdr)
    )
    scan = {
        "balance_max": int(ec.b_balances.max(initial=0)),
        "eff_max": int(ec.b_eff.max(initial=0)),
        "exit_max": int(real_exits.max()) if real_exits.size else 0,
        "inact_max": 0,
        "n_active_prev": int(np.count_nonzero(ec.active_prev)),
        "n_active_cur": int(np.count_nonzero(ec.active_cur)),
        "n_eligible": int(np.count_nonzero(ec.eligible)),
        "active_cur_eff": int(ec.b_eff[ec.active_cur].sum()),
        "prev_target_eff": 0,
        "cur_target_eff": 0,
    }
    if ec.b_inact is not None:
        unslashed = ~ec.slashed
        prev_mask = (
            ec.active_prev
            & unslashed
            & _flag_mask(ec, ec.prev_part, _TIMELY_TARGET_FLAG_INDEX)
        )
        cur_mask = (
            ec.active_cur
            & unslashed
            & _flag_mask(ec, ec.cur_part, _TIMELY_TARGET_FLAG_INDEX)
        )
        scan["inact_max"] = int(ec.b_inact.max(initial=0))
        scan["prev_target_eff"] = int(ec.b_eff[prev_mask].sum())
        scan["cur_target_eff"] = int(ec.b_eff[cur_mask].sum())
    return scan


def _sync(state, context, fork):
    """Build the working set, running EVERY fallback guard before any
    mutation. Returns None to decline (state untouched)."""
    np = _np()
    cols = ops_vector.columns_for(state)
    if cols is None:
        fallback("columns_unavailable")
        return None
    # the column acquisition, apart from the guards and masks below
    with trace.span("epoch_vector.sync.columns"):
        held = ops_vector.resident_rows(state.validators)
        vc = cols.validator_columns(state)
        balances = cols.list_column(state, "balances")
        if vc is None or balances is None:
            fallback("columns_unavailable")
            return None
        n = len(state.validators)
        if balances.shape[0] != n:
            fallback("length_mismatch")
            return None
        if held is not None and n > held:
            # the registry is longer than at the last pass on this
            # state's lineage: deposits came in
            metrics.counter("epoch_vector.rows_appended").inc(n - held)
        ec = _EpochColumns()
        ec.np = np
        ec.state = state
        ec.context = context
        ec.fork = fork
        ec.cfg = _FORK_CFG[fork]
        ec.n = n
        cur = int(state.slot) // int(context.SLOTS_PER_EPOCH)
        ec.cur = cur
        ec.prev = GENESIS_EPOCH if cur == GENESIS_EPOCH else cur - 1
        ec.increment = int(context.EFFECTIVE_BALANCE_INCREMENT)
        ec.b_eff = vc["effective_balance"]
        ec.b_elig = vc["activation_eligibility_epoch"]
        ec.b_act = vc["activation_epoch"]
        ec.b_exit = vc["exit_epoch"]
        ec.b_wdr = vc["withdrawable_epoch"]
        ec.b_prefix = vc["withdrawal_prefix"]
        ec.slashed = vc["slashed"]
        ec.b_balances = balances
        if ec.cfg["family"] == "altair":
            prev_part = cols.list_column(
                state, "previous_epoch_participation"
            )
            cur_part = cols.list_column(
                state, "current_epoch_participation"
            )
            inact = cols.list_column(state, "inactivity_scores")
            if prev_part is None or cur_part is None or inact is None:
                fallback("columns_unavailable")
                return None
            if (
                prev_part.shape[0] != n
                or cur_part.shape[0] != n
                or inact.shape[0] != n
            ):
                fallback("length_mismatch")
                return None
            ec.prev_part = prev_part
            ec.cur_part = cur_part
            ec.b_inact = inact
        else:
            ec.prev_part = ec.cur_part = ec.b_inact = None

    # every registry-wide reduction the pass takes before its kernels, in
    # one sweep over the columns: the masks at the PRE-PASS registry
    # (every spec mutation of the activity schedule targets a future
    # epoch, the get_active_validator_indices contract, so they stay
    # exact for the whole pass), the guards' maxima and the masked sums
    with trace.span("epoch_vector.sync.scan"):
        scan = _scan(ec)
    ec.scan = scan

    # --- u64 lane guards: everything the pass adds/multiplies must stay
    # below 2^63 so no kernel op can wrap; a state outside the lane
    # (adversarial near-2^64 values) declines BEFORE any mutation and
    # the literal loops keep their exact big-int/structured-error paths
    if scan["balance_max"] >= _LANE_MAX:
        fallback("u64_guard")
        return None
    if scan["eff_max"] >= _LANE_MAX:
        fallback("u64_guard")
        return None
    if scan["exit_max"] >= _LANE_MAX:
        fallback("u64_guard")
        return None
    if cur >= _LANE_MAX - (2 + int(context.MAX_SEED_LOOKAHEAD)):
        fallback("u64_guard")
        return None
    if ec.b_inact is not None:
        bias = int(context.inactivity_score_bias)
        if scan["inact_max"] >= _U64_MAX - bias:
            fallback("u64_guard")
            return None
    # masked eff sums must be exact in u64: cap n * max(eff) below 2^64
    eff_max = scan["eff_max"]
    if n and eff_max * n >= 1 << 64:
        fallback("u64_guard")
        return None

    if ec.cfg["family"] == "altair" and cur != GENESIS_EPOCH:
        # rewards-kernel product guard, BEFORE any mutation: the largest
        # product formed is base_reward * weight(<=64) * increments, so
        # bound it with the whole-registry increment ceiling. Real
        # states clear this by ~10 bits; a decline costs nothing.
        from .phase0.helpers import integer_squareroot

        total_active = max(ec.increment, scan["active_cur_eff"])
        brpi = (
            ec.increment
            * int(context.BASE_REWARD_FACTOR)
            // integer_squareroot(total_active)
        )
        max_base_reward = (eff_max // ec.increment) * brpi
        incr_ceiling = max(1, n * (eff_max // ec.increment))
        if max_base_reward * 64 * incr_ceiling >= 1 << 64:
            fallback("u64_guard")
            return None

    # the working set STARTS as the base views (read-only — an
    # accidental in-place write raises instead of corrupting the cache);
    # stages that rebind (rewards, inactivity, hysteresis) replace the
    # reference with a fresh owned array, and in-place writers (registry
    # hits, slashings, churn) take an owned copy via _own on their FIRST
    # actual write — a typical epoch therefore copies only the columns
    # it really changes
    ec.eff = ec.b_eff
    ec.elig = ec.b_elig
    ec.act = ec.b_act
    ec.exit = ec.b_exit
    ec.wdr = ec.b_wdr
    ec.prefix = ec.b_prefix
    ec.balances = ec.b_balances
    ec.inact = ec.b_inact
    # slashed, exited, not yet withdrawable: flag penalties and score
    # updates for rows outside every active mask
    eligible_inactive = scan["n_eligible"] - scan["n_active_prev"]
    if eligible_inactive:
        metrics.counter("epoch_vector.rows_eligible_inactive").inc(
            eligible_inactive
        )
    ec._total_active = None
    ec._active_cur_count = None
    ec.credential_switches = []
    ec.mesh = None
    ec.fused = None
    return ec


def _own(ec, name: str):
    """Copy-on-first-write for a working column: the base views are
    read-only, so in-place stages must take ownership before writing."""
    arr = getattr(ec, name)
    if not arr.flags.writeable:
        arr = arr.copy()
        setattr(ec, name, arr)
    return arr


def _total_active(ec) -> int:
    """max(increment, sum of active-at-current effective balances) —
    exactly ``get_total_active_balance``'s value; seeded into the
    state's memo so every scalar helper call mid-pass hits it."""
    if ec._total_active is None:
        # the sweep's sum while no stage has written an effective balance
        active_eff = (
            ec.scan["active_cur_eff"]
            if ec.eff is ec.b_eff
            else int(ec.eff[ec.active_cur].sum())
        )
        total = max(ec.increment, active_eff)
        ec._total_active = total
        ec.state.__dict__["_total_active_balance_cache"] = (
            (ec.cur, ec.n),
            total,
        )
    return ec._total_active


def _active_cur_count(ec) -> int:
    if ec._active_cur_count is None:
        ec._active_cur_count = ec.scan["n_active_cur"]
    return ec._active_cur_count


def _churn_limit(ec) -> int:
    ctx = ec.context
    return max(
        int(ctx.min_per_epoch_churn_limit),
        _active_cur_count(ec) // int(ctx.churn_limit_quotient),
    )


def _seed_active_indices(ec, epoch: int, mask, rows=None) -> tuple:
    """Materialize (once) the active-index tuple for ``epoch`` from the
    columns and install it in the state's ``_active_idx_cache`` with the
    helper's exact rebind discipline — the committee machinery (phase0
    pendings, sync-committee sampling) then never pays the per-validator
    sweep. ``rows``: ``mask``'s row numbers, where the caller has them."""
    state = ec.state
    key = (epoch, ec.n)
    cache = state.__dict__.get("_active_idx_cache")
    if isinstance(cache, dict):
        hit = cache.get(key)
        if hit is not None:
            return hit
        items = list(cache.items())
    else:
        items = []
    if rows is None:
        rows = ec.np.nonzero(mask)[0]
    out = tuple(rows.tolist())
    if len(items) >= 4:
        items = items[1:]
    state.__dict__["_active_idx_cache"] = dict(items + [(key, out)])
    return out


def _flag_mask(ec, participation, flag_index: int):
    np = ec.np
    return (
        (participation >> np.uint8(flag_index)) & np.uint8(1)
    ).astype(bool)


# ---------------------------------------------------------------------------
# stages (altair family unless noted)
# ---------------------------------------------------------------------------


def _justification_altair(ec) -> None:
    if ec.cur <= GENESIS_EPOCH + 1:
        return
    from .phase0.epoch_processing import weigh_justification_and_finalization

    # the pass's first stage: the effective balances are still the ones
    # the sweep summed over the unslashed target flags
    total_active = _total_active(ec)
    previous_target = max(ec.increment, ec.scan["prev_target_eff"])
    current_target = max(ec.increment, ec.scan["cur_target_eff"])
    weigh_justification_and_finalization(
        ec.state, total_active, previous_target, current_target, ec.context
    )


def _justification_phase0(ec) -> None:
    if ec.cur <= GENESIS_EPOCH + 1:
        return
    from .committees import pending_masks_for
    from .phase0 import epoch_processing as pep
    from .phase0 import helpers as h
    from .phase0.epoch_processing import weigh_justification_and_finalization

    state, context, np = ec.state, ec.context, ec.np
    _seed_active_indices(ec, ec.prev, ec.active_prev)
    _seed_active_indices(ec, ec.cur, ec.active_cur)

    # the committee-mask kernel (models/committees.py): target masks for
    # both epochs off ONE shuffled table + bitfield pass per epoch; its
    # bundle is memoized on the state, so the rewards stage reuses it
    prev_bundle = pending_masks_for(state, ec.prev, context)
    cur_bundle = (
        pending_masks_for(state, ec.cur, context)
        if prev_bundle is not None
        else None
    )
    if prev_bundle is not None and cur_bundle is not None:
        unslashed = ~ec.slashed
        previous_target = max(
            ec.increment, int(ec.eff[prev_bundle.target & unslashed].sum())
        )
        current_target = max(
            ec.increment, int(ec.eff[cur_bundle.target & unslashed].sum())
        )
        weigh_justification_and_finalization(
            state, _total_active(ec), previous_target, current_target,
            context,
        )
        return

    def attesting_balance(atts) -> int:
        mask = np.zeros(ec.n, dtype=bool)
        for a in atts:
            idx = h.get_attesting_indices(
                state, a.data, a.aggregation_bits, context
            )
            mask[np.fromiter(idx, dtype=np.int64, count=len(idx))] = True
        mask &= ~ec.slashed
        return max(ec.increment, int(ec.eff[mask].sum()))

    previous_atts = pep.get_matching_target_attestations(
        state, ec.prev, context
    )
    current_atts = pep.get_matching_target_attestations(
        state, ec.cur, context
    )
    weigh_justification_and_finalization(
        state,
        _total_active(ec),
        attesting_balance(previous_atts),
        attesting_balance(current_atts),
        context,
    )


def _inactivity_updates(ec) -> None:
    if ec.cur == GENESIS_EPOCH:
        return
    from .phase0.epoch_processing import get_finality_delay

    context = ec.context
    leaking = (
        get_finality_delay(ec.state, context)
        > context.MIN_EPOCHS_TO_INACTIVITY_PENALTY
    )
    participating = (
        ec.active_prev
        & ~ec.slashed
        & _flag_mask(ec, ec.prev_part, _TIMELY_TARGET_FLAG_INDEX)
    )
    bias = int(context.inactivity_score_bias)
    recovery = int(context.inactivity_score_recovery_rate)
    if ec.mesh is not None:
        # the sharded sweep (parallel/epoch.py) reuses the SAME kernel
        # body under shard_map; any device trouble journals and the
        # host kernel below stays the live fallback
        try:
            ec.inact = ec.mesh.inactivity_scores(
                ec.inact, ec.eligible, participating, bias, recovery,
                leaking,
            )
            return
        except Exception as exc:  # noqa: BLE001 — host fallback
            # an injected fault (runtime.fault_point) already journaled
            # its own decline as injected_fault; journaling it again as
            # device_unusable would double-count the one routing decision
            if not getattr(exc, "mesh_fault", False):
                from ..parallel import runtime as _mesh_runtime

                _mesh_runtime.decline(
                    "epoch", "device_unusable", stage="inactivity",
                    error=repr(exc)[:160],
                )
    ec.inact = inactivity_scores_kernel(
        ec.np,
        ec.inact,
        ec.eligible,
        participating,
        bias,
        recovery,
        leaking,
    )


def _rewards_altair(ec) -> None:
    """Flag deltas ×3 + inactivity penalties, applied in sequence with
    zero saturation — the literal helpers' exact integer semantics over
    the working columns. Overflow on application (unreachable for real
    balances) mirrors the literal fallback: it applies the SAME deltas
    per index on the real state so ``checked_add`` raises its structured
    error at the exact index — committing the stages so far first."""
    if ec.cur == GENESIS_EPOCH:
        return
    np = ec.np
    context = ec.context
    from .altair.constants import (
        PARTICIPATION_FLAG_WEIGHTS,
        TIMELY_HEAD_FLAG_INDEX,
        WEIGHT_DENOMINATOR,
    )
    from .phase0.epoch_processing import get_finality_delay
    from .phase0.helpers import integer_squareroot

    total_active = _total_active(ec)
    increment = ec.increment
    brpi = (
        increment
        * int(context.BASE_REWARD_FACTOR)
        // integer_squareroot(total_active)
    )
    active_increments = total_active // increment
    base_reward = (ec.eff // np.uint64(increment)) * np.uint64(brpi)
    leaking = (
        get_finality_delay(ec.state, context)
        > context.MIN_EPOCHS_TO_INACTIVITY_PENALTY
    )
    if ec.mesh is not None:
        new_balances = _mesh_rewards(
            ec, brpi, active_increments, leaking
        )
        if new_balances is not None:
            ec.balances = new_balances
            return
    unslashed_all = ~ec.slashed
    pairs = []
    target_unslashed = None
    for flag_index, weight in enumerate(PARTICIPATION_FLAG_WEIGHTS):
        unslashed = (
            ec.active_prev
            & unslashed_all
            & _flag_mask(ec, ec.prev_part, flag_index)
        )
        if flag_index == _TIMELY_TARGET_FLAG_INDEX:
            target_unslashed = unslashed
        # get_total_balance floors at one increment
        unslashed_increments = (
            max(increment, int(ec.eff[unslashed].sum())) // increment
        )
        pairs.append(
            flag_deltas_kernel(
                np,
                base_reward,
                ec.eligible,
                unslashed,
                int(weight),
                unslashed_increments,
                active_increments,
                int(WEIGHT_DENOMINATOR),
                leaking,
                flag_index == TIMELY_HEAD_FLAG_INDEX,
            )
        )

    # inactivity penalties off the POST-UPDATE scores (spec order)
    scores = ec.inact
    missed = ec.eligible & ~target_unslashed
    denominator = int(context.inactivity_score_bias) * int(
        getattr(context, ec.cfg["quot"])
    )
    penalties = np.zeros(ec.n, dtype=np.uint64)
    if ec.n == 0 or int(ec.eff.max(initial=0)) * int(
        scores.max(initial=0)
    ) < 1 << 64:
        penalties[missed] = (
            ec.eff[missed] * scores[missed] // np.uint64(denominator)
        )
    else:
        # pathological scores: exact per-index Python ints clamped to the
        # u64 lane — a penalty at the clamp already saturates any real
        # balance to zero, so the applied result is unchanged
        for i in np.nonzero(missed)[0]:
            penalties[i] = min(
                int(ec.eff[i]) * int(scores[i]) // denominator, _U64_MAX
            )
    pairs.append((np.zeros(ec.n, dtype=np.uint64), penalties))

    # apply the pairs in spec sequence (apply_delta_pairs_kernel's exact
    # ops, unrolled here so the per-pair wrap check matches the literal
    # vector path's overflow contract; the _sync guards make the wrap
    # branch unreachable, but a guard regression must degrade to the
    # structured error, never to silently wrapped balances)
    balances = ec.balances
    zero = np.uint64(0)
    for rewards, penalties in pairs:
        raised = balances + rewards
        if bool((raised < balances).any()):
            return _rewards_literal_apply(ec, pairs)
        balances = np.where(raised >= penalties, raised - penalties, zero)
    ec.balances = balances


def _mesh_rewards(ec, brpi: int, active_increments: int,
                  leaking: bool) -> "object | None":
    """Route the whole rewards stage through the mesh runner (ONE
    sharded sweep: per-flag psum reductions + flag deltas + inactivity
    penalties + in-order application — parallel/epoch.py). Returns the
    new balances column, or None with the decline journaled — the host
    stage below then recomputes everything (live fallback AND
    differential oracle; the bench asserts bit-identity between the
    two)."""
    from .altair.constants import (
        PARTICIPATION_FLAG_WEIGHTS,
        TIMELY_HEAD_FLAG_INDEX,
        WEIGHT_DENOMINATOR,
    )
    from ..parallel import runtime as _mesh_runtime

    context = ec.context
    denominator = int(context.inactivity_score_bias) * int(
        getattr(context, ec.cfg["quot"])
    )
    # the host stage clamps pathological eff*score products through exact
    # python ints — a kernel cannot, so those states decline up front
    if ec.n and int(ec.eff.max(initial=0)) * int(
        ec.inact.max(initial=0)
    ) >= 1 << 64:
        _mesh_runtime.decline(
            "epoch", "u64_product", stage="rewards", validators=ec.n
        )
        return None
    try:
        new_balances = ec.mesh.rewards(
            ec.balances, ec.eff, ec.prev_part, ec.slashed, ec.active_prev,
            ec.eligible, ec.inact,
            increment=ec.increment,
            brpi=brpi,
            active_increments=active_increments,
            denominator=denominator,
            weights=tuple(int(w) for w in PARTICIPATION_FLAG_WEIGHTS),
            weight_denominator=int(WEIGHT_DENOMINATOR),
            leaking=leaking,
            head_flag_index=int(TIMELY_HEAD_FLAG_INDEX),
            target_flag_index=_TIMELY_TARGET_FLAG_INDEX,
        )
    except Exception as exc:  # noqa: BLE001 — host fallback
        # injected faults journaled at the seam (fault_point) — see the
        # inactivity catch site
        if not getattr(exc, "mesh_fault", False):
            _mesh_runtime.decline(
                "epoch", "device_unusable", stage="rewards",
                error=repr(exc)[:160],
            )
        return None
    if new_balances is None:
        # a u64 wrap the lane guards should have made unreachable: the
        # host path re-runs and its literal mirror raises the structured
        # error at the exact index (the same terminal contract)
        _mesh_runtime.decline(
            "epoch", "wrap_guard", stage="rewards", validators=ec.n
        )
    return new_balances


def _rewards_literal_apply(ec, pairs) -> None:
    """Terminal mirror of the literal overflow fallback: commit the
    stages so far, then apply the SAME deltas through increase /
    decrease_balance so ``checked_add`` raises the structured error at
    the exact index (scalar parity). Unreachable under the _sync guards;
    kept so the contract survives a guard regression."""
    import importlib

    _commit(ec)
    hm = importlib.import_module(
        f"ethereum_consensus_tpu.models.{ec.fork}.helpers"
    )
    for rewards, penalties in pairs:
        for index in range(ec.n):
            hm.increase_balance(ec.state, index, int(rewards[index]))
            hm.decrease_balance(ec.state, index, int(penalties[index]))
    raise _PassComplete()


def _fused_fallback(ec, reason: str, **inputs) -> None:
    """A fused-route decline is NOT an engine fallback (the staged host
    kernels run and the pass stays columnar) — separate counter +
    journal kind so the bench can assert zero ``epoch_vector.fallback.*``
    while still seeing every fused routing decision."""
    metrics.counter(f"epoch_vector.fused_fallback.{reason}").inc()
    if _device_obs.OBSERVATORY.active:
        _device_obs.route("epoch_fused", "staged", reason, **inputs)


def _fused_route(ec, leaking: bool) -> bool:
    """Run inactivity + rewards as ONE fused dispatch — mesh-sharded
    (parallel/epoch.py) when the mesh owns the pass, jitted
    (``jitted_kernels()['fused_epoch']``) when ``ops.install``'s sweeps
    gate selected it. Returns True with ``ec.inact``/``ec.balances``
    rebound; False = run the staged host kernels (live fallback,
    bit-identical)."""
    if ec.mesh is None and ec.fused is None:
        return False
    np = ec.np
    context = ec.context
    from .altair.constants import (
        PARTICIPATION_FLAG_WEIGHTS,
        TIMELY_HEAD_FLAG_INDEX,
        WEIGHT_DENOMINATOR,
    )
    from .phase0.helpers import integer_squareroot

    bias = int(context.inactivity_score_bias)
    recovery = int(context.inactivity_score_recovery_rate)
    # the staged host path clamps pathological eff*score products through
    # exact Python ints — a kernel cannot; post-update scores are bounded
    # by pre-update max + bias, so this guard covers the fused product;
    # the columns are the sweep's until a stage rebinds them
    if ec.eff is ec.b_eff and ec.inact is ec.b_inact:
        eff_max, inact_max = ec.scan["eff_max"], ec.scan["inact_max"]
    else:
        eff_max = int(ec.eff.max(initial=0))
        inact_max = int(ec.inact.max(initial=0))
    if ec.n and eff_max * (inact_max + bias) >= 1 << 64:
        _fused_fallback(ec, "u64_product", validators=ec.n)
        return False
    total_active = _total_active(ec)
    increment = ec.increment
    brpi = (
        increment
        * int(context.BASE_REWARD_FACTOR)
        // integer_squareroot(total_active)
    )
    active_increments = total_active // increment
    denominator = bias * int(getattr(context, ec.cfg["quot"]))
    weights = tuple(int(w) for w in PARTICIPATION_FLAG_WEIGHTS)
    if ec.mesh is not None:
        try:
            with trace.span(
                "epoch_vector.fused", validators=ec.n, route="mesh"
            ):
                out = ec.mesh.fused(
                    ec.balances, ec.eff, ec.prev_part, ec.slashed,
                    ec.active_prev, ec.eligible, ec.inact,
                    increment=increment,
                    brpi=brpi,
                    active_increments=active_increments,
                    denominator=denominator,
                    bias=bias,
                    recovery_rate=recovery,
                    weights=weights,
                    weight_denominator=int(WEIGHT_DENOMINATOR),
                    leaking=leaking,
                    head_flag_index=int(TIMELY_HEAD_FLAG_INDEX),
                    target_flag_index=_TIMELY_TARGET_FLAG_INDEX,
                )
        except Exception as exc:  # noqa: BLE001 — host fallback
            # injected faults journal at the seam (runtime.fault_point)
            if not getattr(exc, "mesh_fault", False):
                from ..parallel import runtime as _mesh_runtime

                _mesh_runtime.decline(
                    "epoch", "device_unusable", stage="fused",
                    error=repr(exc)[:160],
                )
            return False
        if out is None:
            # a wrap the guards should have made unreachable: the staged
            # path re-runs and its literal mirror raises the structured
            # error at the exact index
            from ..parallel import runtime as _mesh_runtime

            _mesh_runtime.decline(
                "epoch", "wrap_guard", stage="fused", validators=ec.n
            )
            return False
        ec.inact, ec.balances = out
        metrics.counter("epoch_vector.fused.mesh").inc()
        return True
    try:
        import jax.numpy as jnp

        padded = fused_dispatch_rows(ec.n)
        with trace.span(
            "epoch_vector.fused", validators=ec.n, route="jit",
            rows=ec.n, padded=padded,
        ):
            # ONE upload of the packed columns for BOTH stages — the
            # per-stage h2d transfers the staged device route paid are
            # gone (the transfer ledger proves it: a single
            # epoch_vector.fused site instead of inactivity + rewards).
            # They go up at the dispatched length, inert rows behind the
            # registry's, and the results are cut back to ``n`` below.
            arrays = _device_obs.h2d(
                "epoch_vector.fused",
                *(
                    _padded(np, column, padded)
                    for column in (
                        ec.balances, ec.eff, ec.prev_part, ec.slashed,
                        ec.active_prev, ec.eligible, ec.inact,
                    )
                ),
            )
            metrics.counter("epoch_vector.fused.pad_rows").inc(padded - ec.n)
            planes, wrapped = ec.fused(
                *arrays,
                jnp.uint64(increment),
                jnp.uint64(brpi),
                jnp.uint64(active_increments),
                jnp.uint64(denominator),
                bias,
                recovery,
                weights,
                int(WEIGHT_DENOMINATOR),
                leaking,
                int(TIMELY_HEAD_FLAG_INDEX),
                _TIMELY_TARGET_FLAG_INDEX,
            )
            # queue the results' copy behind the kernel now: it starts
            # when the program ends, not when the host comes to ask
            _device_obs.d2h_start(planes)
            # the first point that blocks on the kernel (and on whatever
            # of the upload its dispatch did not wait for)
            with trace.span("epoch_vector.fused.wait"):
                wrapped = int(wrapped)
            if wrapped:
                _fused_fallback(ec, "wrap_guard", validators=ec.n)
                return False
            planes = _device_obs.d2h("epoch_vector.fused", planes)
            with trace.span("epoch_vector.fused.unpack"):
                new_scores, new_balances = u64_columns(planes[:, : ec.n])
    except Exception as exc:  # noqa: BLE001 — host fallback
        _fused_fallback(
            ec, "device_unusable", error=repr(exc)[:160], validators=ec.n
        )
        return False
    ec.inact = new_scores
    ec.balances = new_balances
    metrics.counter("epoch_vector.fused.jit").inc()
    if _device_obs.OBSERVATORY.active:
        _device_obs.route(
            "epoch_fused", "device", "engaged", validators=ec.n
        )
    return True


def _inactivity_and_rewards(ec) -> None:
    """The altair-family inactivity + rewards stages: ONE fused dispatch
    on the device routes (mesh / jitted kernel), the staged host kernels
    otherwise — and always when the fused route declines (every decline
    counted + journaled, none silent)."""
    if ec.cur == GENESIS_EPOCH:
        return
    from .phase0.epoch_processing import get_finality_delay

    leaking = (
        get_finality_delay(ec.state, ec.context)
        > ec.context.MIN_EPOCHS_TO_INACTIVITY_PENALTY
    )
    if leaking:
        metrics.counter("epoch_vector.leak.epochs").inc()
    if _fused_route(ec, leaking):
        return
    with trace.span("epoch_vector.inactivity"):
        _inactivity_updates(ec)
    with trace.span("epoch_vector.rewards"):
        _rewards_altair(ec)


def _rewards_phase0(ec) -> None:
    if ec.cur == GENESIS_EPOCH:
        return
    from .phase0 import epoch_processing as pep
    from .phase0 import helpers as h

    np = ec.np
    _seed_active_indices(ec, ec.prev, ec.active_prev)
    _seed_active_indices(ec, ec.cur, ec.active_cur)
    # seed the total-active-balance memo from the columns BEFORE the
    # deltas consult it: at the epoch-1 boundary justification is
    # skipped (cur <= GENESIS+1) and nothing else has seeded it — an
    # unseeded memo costs get_total_active_balance a full per-validator
    # Python sweep inside the hot pass
    _total_active(ec)
    # hand the deltas the pass's own column views (working = base here:
    # nothing earlier in the pass mutates these columns for phase0), so
    # the activity masks aren't re-derived mid-pass
    rewards, penalties = pep._attestation_deltas_vectorized(
        ec.state, ec.context,
        packed={
            "effective_balance": ec.eff,
            "slashed": ec.slashed,
            "active_previous": ec.active_prev,
            "eligible": ec.eligible,
        },
    )
    raised = ec.balances + rewards
    if bool((raised < ec.balances).any()):
        # u64 overflow: commit, then re-run literally so checked_add
        # raises the structured error at the exact index
        _commit(ec)
        rewards_l, penalties_l = pep._get_attestation_deltas_literal(
            ec.state, ec.context
        )
        for index in range(ec.n):
            h.increase_balance(ec.state, index, rewards_l[index])
            h.decrease_balance(ec.state, index, penalties_l[index])
        raise _PassComplete()
    ec.balances = np.where(raised >= penalties, raised - penalties, 0)


def _registry_updates(ec) -> None:
    """Queue entries, ejections and activations over the working
    columns. Ejection exit scheduling replicates the literal
    ``initiate_validator_exit`` incrementally (phase0 family) or through
    the EIP-7251 churn scalars (electra)."""
    np = ec.np
    context = ec.context
    from .phase0.helpers import compute_activation_exit_epoch

    far = np.uint64(FAR_FUTURE_EPOCH)
    if ec.cfg["activation"] == "unbounded":
        balance_rule = ec.eff >= np.uint64(
            int(context.MIN_ACTIVATION_BALANCE)
        )
    else:
        balance_rule = ec.eff == np.uint64(int(context.MAX_EFFECTIVE_BALANCE))
    queue_entry = (ec.elig == far) & balance_rule
    queued = int(np.count_nonzero(queue_entry))
    if queued:
        _own(ec, "elig")[queue_entry] = np.uint64(ec.cur + 1)
        metrics.counter("epoch_vector.registry.queued").inc(queued)

    ejection = ec.active_cur & (
        ec.eff <= np.uint64(int(context.ejection_balance))
    )
    hits = np.nonzero(ejection)[0]
    if hits.size:
        if ec.fork == "electra":
            for i in hits.tolist():
                _initiate_exit_electra(ec, i)
        else:
            _initiate_exits_phase0(ec, hits.tolist())

    # ec.elig already carries the queue-entry writes, so this is the
    # literal "re-read eligibility" order
    activatable = (
        ec.elig <= np.uint64(int(ec.state.finalized_checkpoint.epoch))
    ) & (ec.act == far)
    cand = np.nonzero(activatable)[0]
    if cand.size and ec.cfg["activation"] != "unbounded":
        # phase0..deneb: ascending (eligibility, index) queue, churn-capped
        limit = _churn_limit(ec)
        if ec.cfg["activation"] == "activation_churn":
            limit = min(
                int(ec.context.max_per_epoch_activation_churn_limit), limit
            )
        order = np.argsort(ec.elig[cand], kind="stable")
        cand = cand[order][:limit]
    if cand.size:
        _own(ec, "act")[cand] = np.uint64(
            compute_activation_exit_epoch(ec.cur, context)
        )
        metrics.counter("epoch_vector.registry.activated").inc(cand.size)
    # what this boundary did to the queue, on the registry span's event
    trace.note(queued=queued, activated=int(cand.size))


def _initiate_exits_phase0(ec, indices) -> None:
    """The literal ``initiate_validator_exit`` for a batch of ejections,
    maintained incrementally: the literal recomputes (max exit epoch,
    churn at it) per call — after each write the max is the write's
    epoch, so the running pair reproduces every per-call recompute."""
    np = ec.np
    context = ec.context
    from .phase0.helpers import compute_activation_exit_epoch

    far = np.uint64(FAR_FUTURE_EPOCH)
    _own(ec, "exit")
    _own(ec, "wdr")
    real = ec.exit[ec.exit != far]
    aee = compute_activation_exit_epoch(ec.cur, context)
    exit_queue_epoch = max(int(real.max()) if real.size else 0, aee)
    churn = int((ec.exit == np.uint64(exit_queue_epoch)).sum())
    limit = _churn_limit(ec)
    delay = int(context.min_validator_withdrawability_delay)
    for i in indices:
        if int(ec.exit[i]) != FAR_FUTURE_EPOCH:
            continue
        if churn >= limit:
            exit_queue_epoch += 1
            churn = 0
        ec.exit[i] = np.uint64(exit_queue_epoch)
        ec.wdr[i] = np.uint64(exit_queue_epoch + delay)
        churn += 1


def _initiate_exit_electra(ec, index: int) -> None:
    """electra ``initiate_validator_exit``: balance-weighted churn via
    the state's EIP-7251 scalars (mutated exactly as the literal helper
    mutates them — they are plain state fields, not columns)."""
    if int(ec.exit[index]) != FAR_FUTURE_EPOCH:
        return
    exit_queue_epoch = _compute_exit_epoch_and_update_churn(
        ec, int(ec.eff[index])
    )
    np = ec.np
    _own(ec, "exit")[index] = np.uint64(exit_queue_epoch)
    _own(ec, "wdr")[index] = np.uint64(
        exit_queue_epoch
        + int(ec.context.min_validator_withdrawability_delay)
    )


def _activation_exit_churn_limit(ec) -> int:
    context = ec.context
    churn_limit = _total_active(ec) // int(context.churn_limit_quotient)
    churn = max(int(context.min_per_epoch_churn_limit_electra), churn_limit)
    churn -= churn % ec.increment
    return min(
        int(context.max_per_epoch_activation_exit_churn_limit), churn
    )


def _compute_exit_epoch_and_update_churn(ec, exit_balance: int) -> int:
    state, context = ec.state, ec.context
    from .phase0.helpers import compute_activation_exit_epoch

    activation_exit_epoch = compute_activation_exit_epoch(ec.cur, context)
    earliest_exit_epoch = max(
        int(state.earliest_exit_epoch), activation_exit_epoch
    )
    per_epoch_churn = _activation_exit_churn_limit(ec)
    if int(state.earliest_exit_epoch) < earliest_exit_epoch:
        exit_balance_to_consume = per_epoch_churn
    else:
        exit_balance_to_consume = int(state.exit_balance_to_consume)
    if exit_balance > exit_balance_to_consume:
        balance_to_process = exit_balance - exit_balance_to_consume
        additional_epochs = (balance_to_process - 1) // per_epoch_churn + 1
        earliest_exit_epoch += additional_epochs
        exit_balance_to_consume += additional_epochs * per_epoch_churn
    state.exit_balance_to_consume = exit_balance_to_consume - exit_balance
    state.earliest_exit_epoch = earliest_exit_epoch
    return earliest_exit_epoch


def _slashings(ec) -> None:
    np = ec.np
    context = ec.context
    total_balance = _total_active(ec)
    adjusted = min(
        sum(ec.state.slashings) * int(getattr(context, ec.cfg["slash_mult"])),
        total_balance,
    )
    target = ec.cur + int(context.EPOCHS_PER_SLASHINGS_VECTOR) // 2
    mask = ec.slashed & (ec.wdr == np.uint64(target))
    hits = np.nonzero(mask)[0]
    increment = ec.increment
    trace.note(hits=int(hits.size))
    if hits.size:
        _own(ec, "balances")
        metrics.counter("epoch_vector.slashings.penalised").inc(int(hits.size))
    for i in hits.tolist():
        # exact big-int math per hit (the eff//inc * adjusted product
        # exceeds u64 at mainnet totals); hits are the few slashed
        # validators at their halfway point, never a registry sweep
        penalty_numerator = (int(ec.eff[i]) // increment) * adjusted
        penalty = penalty_numerator // total_balance * increment
        bal = int(ec.balances[i])
        ec.balances[i] = np.uint64(bal - penalty if bal > penalty else 0)


def _pending_balance_deposits(ec) -> None:
    """electra ``process_pending_balance_deposits`` — the pending list
    is bounded churn state, not registry-sized; per-deposit reads are
    container reads of that queue, balances land in the working
    column."""
    state = ec.state
    from ..error import checked_add

    np = ec.np
    available = int(state.deposit_balance_to_consume) + (
        _activation_exit_churn_limit(ec)
    )
    processed = 0
    next_index = 0
    if len(state.pending_balance_deposits):
        _own(ec, "balances")
    for deposit in state.pending_balance_deposits:
        amount = int(deposit.amount)
        if processed + amount > available:
            break
        index = int(deposit.index)
        ec.balances[index] = np.uint64(
            checked_add(int(ec.balances[index]), amount)
        )
        processed += amount
        next_index += 1
    del state.pending_balance_deposits[:next_index]
    if len(state.pending_balance_deposits) == 0:
        state.deposit_balance_to_consume = 0
    else:
        state.deposit_balance_to_consume = available - processed


def _pending_consolidations(ec) -> None:
    """electra ``process_pending_consolidations`` over the columns; the
    compounding-credential switch lands in the prefix column now and the
    actual credential bytes at commit (nothing between reads them)."""
    state, context = ec.state, ec.context
    np = ec.np
    from ..error import checked_add

    min_activation = int(context.MIN_ACTIVATION_BALANCE)
    max_eb_electra = int(context.MAX_EFFECTIVE_BALANCE_ELECTRA)
    next_pending = 0
    if len(state.pending_consolidations):
        _own(ec, "balances")
    for pending in state.pending_consolidations:
        src = int(pending.source_index)
        tgt = int(pending.target_index)
        if bool(ec.slashed[src]):
            next_pending += 1
            continue
        if int(ec.wdr[src]) > ec.cur:
            break
        # switch_to_compounding_validator(target)
        if int(ec.prefix[tgt]) == 0x01:
            _own(ec, "prefix")[tgt] = np.uint8(0x02)
            ec.credential_switches.append(tgt)
            # queue_excess_active_balance(target)
            bal = int(ec.balances[tgt])
            if bal > min_activation:
                from .electra.containers import PendingBalanceDeposit

                ec.balances[tgt] = np.uint64(min_activation)
                state.pending_balance_deposits.append(
                    PendingBalanceDeposit(
                        index=tgt, amount=bal - min_activation
                    )
                )
        limit = (
            max_eb_electra
            if int(ec.prefix[src]) == 0x02
            else min_activation
        )
        active_balance = min(int(ec.balances[src]), limit)
        src_bal = int(ec.balances[src])
        ec.balances[src] = np.uint64(
            src_bal - active_balance if src_bal > active_balance else 0
        )
        ec.balances[tgt] = np.uint64(
            checked_add(int(ec.balances[tgt]), active_balance)
        )
        next_pending += 1
    del state.pending_consolidations[:next_pending]


def _effective_balance_updates(ec) -> None:
    """The hysteresis sweep on the working columns (electra: EIP-7251
    per-validator cap via the prefix column, post-consolidation)."""
    np = ec.np
    context = ec.context
    # the ONLY spec site that mutates effective balances: drop the
    # total-active-balance memo exactly like the literal stage does
    ec.state.__dict__.pop("_total_active_balance_cache", None)
    increment = ec.increment
    hysteresis_increment = increment // int(context.HYSTERESIS_QUOTIENT)
    down = hysteresis_increment * int(context.HYSTERESIS_DOWNWARD_MULTIPLIER)
    up = hysteresis_increment * int(context.HYSTERESIS_UPWARD_MULTIPLIER)
    if ec.fork == "electra":
        limit = np.where(
            ec.prefix == np.uint8(0x02),
            np.uint64(int(context.MAX_EFFECTIVE_BALANCE_ELECTRA)),
            np.uint64(int(context.MIN_ACTIVATION_BALANCE)),
        )
    else:
        limit = np.uint64(int(context.MAX_EFFECTIVE_BALANCE))
    update = (ec.balances + np.uint64(down) < ec.eff) | (
        ec.eff + np.uint64(up) < ec.balances
    )
    candidate = np.minimum(
        ec.balances - ec.balances % np.uint64(increment), limit
    )
    ec.eff = np.where(update, candidate, ec.eff)


# ---------------------------------------------------------------------------
# commit — materialize the columns back into the SSZ lists
# ---------------------------------------------------------------------------

_VAL_FIELD_COLS = (
    ("effective_balance", "eff", "b_eff"),
    ("activation_eligibility_epoch", "elig", "b_elig"),
    ("activation_epoch", "act", "b_act"),
    ("exit_epoch", "exit", "b_exit"),
    ("withdrawable_epoch", "wdr", "b_wdr"),
)


def _commit(ec) -> None:
    """Materialize: ONE adopted column per scalar list (balances,
    inactivity scores), handed to ``ops_vector.adopt_list_column`` with
    the comparison that says which rows moved: the list turns
    column-primary (``ssz/column_list.py``), its dirty groups are marked
    from the mask and no row is boxed. Per-hit instrumented writes for
    the handful of changed validator epoch fields and credential
    switches. After this the SSZ state and the columns agree by
    construction: for the two lists the column is the content.

    With finality every score is 0 and stays 0, so the scores' commit
    finds nothing and the boundary pays one registry-sized store; in a
    leak the scores move on every boundary, interleaved, and it pays two,
    plus the effective balances the hysteresis stepped down. The cell
    ``deneb-1m.epoch-leak`` runs that commit; the three child spans and
    the two counters below are how it is read."""
    np = ec.np
    state = ec.state
    with trace.span("epoch_vector.commit", validators=ec.n):
        with trace.span("epoch_vector.commit.balances"):
            if ec.balances is not ec.b_balances:
                ops_vector.adopt_list_column(
                    state.balances,
                    ec.balances,
                    ec.balances != ec.b_balances,
                    _U64_MAX,
                )
        scores_changed = 0
        with trace.span("epoch_vector.commit.scores"):
            if ec.inact is not None and ec.inact is not ec.b_inact:
                scores_changed = ops_vector.adopt_list_column(
                    state.inactivity_scores,
                    ec.inact,
                    ec.inact != ec.b_inact,
                    _U64_MAX,
                )
        writes = eff_changed = 0
        with trace.span("epoch_vector.commit.validators"):
            validators = state.validators
            for field, work_name, base_name in _VAL_FIELD_COLS:
                work = getattr(ec, work_name)
                base = getattr(ec, base_name)
                if work is base:
                    continue
                hits = np.nonzero(work != base)[0].tolist()
                for i in hits:
                    setattr(validators[i], field, int(work[i]))
                writes += len(hits)
                if field == "effective_balance":
                    eff_changed = len(hits)
            for i in ec.credential_switches:
                v = validators[i]
                v.withdrawal_credentials = (
                    b"\x02" + bytes(v.withdrawal_credentials)[1:]
                )
                writes += 1
        if writes:
            metrics.counter("epoch_vector.validator_writes").inc(writes)
        if scores_changed:
            metrics.counter("epoch_vector.scores.changed").inc(scores_changed)
        if eff_changed:
            metrics.counter("epoch_vector.eff.changed").inc(eff_changed)
        trace.note(
            writes=writes, scores_changed=scores_changed,
            eff_changed=eff_changed,
        )


def _count_pass(ec) -> None:
    """A finished pass in the registry's counters: the pass, the rows it
    swept and, where a stage asked for it (the churn limit does, whenever
    somebody waits in the activation queue), how many of them are active
    this epoch. No sweep of its own."""
    metrics.counter("epoch_vector.epochs").inc()
    metrics.counter("epoch_vector.rows").inc(ec.n)
    if ec._active_cur_count is not None:
        metrics.counter("epoch_vector.rows_active").inc(ec._active_cur_count)


def _sampler_fallback(reason: str) -> None:
    """A rotation whose committee the literal helper drew: counted, with
    its reason on a trace event (a rotation is rare: no one-shot guard)."""
    metrics.counter("epoch_vector.sync_committee.fallback").inc()
    trace.event("epoch_vector.sync_committee.fallback", reason=reason)


def _sample_sync_committee(ec, active, seed: bytes) -> "list[int] | None":
    """``get_next_sync_committee_indices`` step for step, with candidates
    drawn ``SYNC_COMMITTEE_SIZE`` at a time: a block's shuffled positions
    in one native swap-or-not pass over all of them
    (``native.shuffle_positions``), its random bytes from one digest per 32
    candidates, its acceptance tested in the literal order against the
    ``eff`` column, which ``_commit`` has written to the validators (so it
    is the post-hysteresis balance the literal helper reads); the next
    block only while the committee is short. ``active`` is the next
    epoch's active rows. None, counted, where the native library is not
    loaded or nobody is active: the caller then asks the literal helper."""
    from .. import native

    if not native.available():
        _sampler_fallback("native_unavailable")
        return None
    if len(active) == 0:
        _sampler_fallback("no_active")
        return None
    np, context = ec.np, ec.context
    size = int(context.SYNC_COMMITTEE_SIZE)
    count = len(active)
    max_balance = np.uint64(int(context.MAX_EFFECTIVE_BALANCE))
    indices: list = []
    start = 0
    while len(indices) < size:
        shuffled, _ = native.shuffle_positions(
            seed, count, int(context.SHUFFLE_ROUND_COUNT),
            np.arange(start, start + size, dtype=np.uint64) % np.uint64(count),
        )
        candidates = active[shuffled]
        first, last = start // 32, (start + size - 1) // 32
        random_bytes = np.frombuffer(
            b"".join(
                hashlib.sha256(seed + k.to_bytes(8, "little")).digest()
                for k in range(first, last + 1)
            ),
            dtype=np.uint8,
        )[start % 32:start % 32 + size]
        # effective * 255 >= MAX_EFFECTIVE_BALANCE * random_byte, as the
        # least balance that passes, so that no u64 product can wrap
        least = (
            max_balance * random_bytes.astype(np.uint64) + np.uint64(254)
        ) // np.uint64(255)
        accepted = candidates[ec.eff[candidates] >= least]
        indices.extend(accepted[:size - len(indices)].tolist())
        start += size
    metrics.counter("epoch_vector.sync_committee.batched").inc()
    metrics.counter("epoch_vector.sync_committee.candidates").inc(start)
    trace.note(candidates=start, blocks=start // size)
    return indices


def _sync_committee_updates(ec) -> None:
    """``process_sync_committee_updates`` at a period boundary, step for
    step, each of its three parts under a span of its own: the next
    epoch's active-index tuple (from the committed columns, so the
    sampler never walks the registry), the sampler
    (``_sample_sync_committee``, the literal helper where it declines),
    and the parse and aggregate of the sampled keys."""
    from ..crypto import bls
    from ..domains import DomainType
    from .altair.containers import build
    from .altair.helpers import get_next_sync_committee_indices, get_seed

    state, context, np = ec.state, ec.context, ec.np
    next_epoch = ec.cur + 1
    with trace.span("epoch_vector.sync_committee"):
        with trace.span("epoch_vector.sync_committee.active"):
            mask = (ec.act <= np.uint64(next_epoch)) & (
                np.uint64(next_epoch) < ec.exit
            )
            active = np.nonzero(mask)[0]
            _seed_active_indices(ec, next_epoch, mask, active)
        with trace.span("epoch_vector.sync_committee.sample"):
            seed = get_seed(state, next_epoch, DomainType.SYNC_COMMITTEE, context)
            indices = _sample_sync_committee(ec, active, seed)
            if indices is None:
                indices = get_next_sync_committee_indices(state, context)
        with trace.span("epoch_vector.sync_committee.aggregate"):
            public_keys = [bytes(state.validators[i].public_key) for i in indices]
            aggregate = bls.eth_aggregate_public_keys(
                [bls.PublicKey.from_bytes(key) for key in public_keys]
            )
        state.current_sync_committee = state.next_sync_committee
        state.next_sync_committee = build(context.preset).SyncCommittee(
            public_keys=public_keys, aggregate_public_key=aggregate.to_bytes()
        )
    metrics.counter("epoch_vector.sync_committee.rotations").inc()


class _PassComplete(Exception):
    """Internal control flow: a stage finished the pass itself (the
    literal overflow mirrors, which must raise the structured error
    after committing). Never escapes ``process_epoch_columnar``."""


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------


def process_epoch_columnar(state, context, fork: str) -> bool:
    """Run the fork's full epoch transition as one vectorized pass over
    the authoritative columns. Returns False (state untouched) when the
    engine declines — the caller then runs its literal stage list."""
    n = len(state.validators)
    if n < EPOCH_VECTOR_MIN_VALIDATORS:
        # a deliberate cost threshold, not a degradation — but still a
        # routing decision: counted + one-shot-evented like every other
        # decline so a production-size miss is visible outside the bench
        fallback(
            "below_threshold",
            validators=n,
            threshold=EPOCH_VECTOR_MIN_VALIDATORS,
        )
        return False
    if _disabled():
        fallback("disabled", validators=n)
        return False
    # the one thing ops.install's sweeps gate selects: an altair-family
    # pass runs inactivity + rewards as the ONE jitted fused kernel
    # (one compile, one column upload) in place of the host kernels.
    # phase0 has no fused kernel: its pass runs the host kernels whatever
    # is installed, and the gate is not consulted
    fused_jit = (
        _FORK_CFG[fork]["family"] == "altair"
        and _device_flags.sweeps_enabled(n)
    )
    if _np() is None:
        fallback("no_numpy", validators=n)
        return False
    try:
        from .altair.constants import TIMELY_TARGET_FLAG_INDEX

        assert TIMELY_TARGET_FLAG_INDEX == _TIMELY_TARGET_FLAG_INDEX
    except Exception:  # noqa: BLE001 — constants unavailable/mismatched
        fallback("constants")
        return False
    # the build of the working columns, before the pass opens
    with trace.span("epoch_vector.sync", validators=n):
        ec = _sync(state, context, fork)
    if ec is None:
        return False
    cfg = ec.cfg
    if fused_jit:
        try:
            ec.fused = jitted_kernels()["fused_epoch"]
        except Exception:  # noqa: BLE001 — jax unusable: host kernels
            _fused_fallback(ec, "jit_unavailable", validators=n)
    if _mesh_requested():
        # the mesh runtime consult (parallel/runtime.py): engage routes
        # the inactivity + rewards sweeps through the sharded kernels;
        # every decline is journaled by the runtime — the guard here is
        # just the env read, so a mesh-off process never imports jax
        from ..parallel import runtime as _mesh_runtime

        ec.mesh = _mesh_runtime.epoch_sweeps(n, family=cfg["family"])
    if _device_obs.OBSERVATORY.active:
        # every guard passed: the engage decision, journaled next to the
        # declines so the /device routing journal tells the whole story
        _device_obs.route(
            "epoch_vector", "columnar", "engaged", validators=n, fork=fork
        )
    with trace.span("epoch_vector.pass", fork=fork, validators=n):
        try:
            with trace.span("epoch_vector.justification"):
                if cfg["family"] == "phase0":
                    _justification_phase0(ec)
                else:
                    _justification_altair(ec)
            if cfg["family"] == "altair":
                _inactivity_and_rewards(ec)
            else:
                with trace.span("epoch_vector.rewards"):
                    _rewards_phase0(ec)
            with trace.span("epoch_vector.registry"):
                _registry_updates(ec)
            with trace.span("epoch_vector.slashings"):
                _slashings(ec)
            from .phase0.epoch_processing import (
                process_eth1_data_reset,
                process_randao_mixes_reset,
                process_slashings_reset,
            )

            process_eth1_data_reset(state, context)
            if fork == "electra":
                with trace.span("epoch_vector.pendings"):
                    _pending_balance_deposits(ec)
                    _pending_consolidations(ec)
            with trace.span("epoch_vector.hysteresis"):
                _effective_balance_updates(ec)
            _commit(ec)
        except _PassComplete:
            _count_pass(ec)
            return True
        process_slashings_reset(state, context)
        process_randao_mixes_reset(state, context)
        if cfg["historical"] == "roots":
            from .phase0.epoch_processing import (
                process_historical_roots_update,
            )

            process_historical_roots_update(state, context)
        elif (ec.cur + 1) % (
            int(context.SLOTS_PER_HISTORICAL_ROOT) // int(context.SLOTS_PER_EPOCH)
        ) == 0:
            from .capella.epoch_processing import (
                process_historical_summaries_update,
            )

            with trace.span("epoch_vector.historical_summary"):
                process_historical_summaries_update(state, context)
            metrics.counter("epoch_vector.historical_summaries").inc()
        with trace.span("epoch_vector.rotation"):
            if cfg["family"] == "phase0":
                from .committees import drop_masks_memo

                # pending lists swap: this epoch's mask bundles are done
                drop_masks_memo(state)
                state.previous_epoch_attestations = (
                    state.current_epoch_attestations
                )
                state.current_epoch_attestations = []
            else:
                state.previous_epoch_participation = (
                    state.current_epoch_participation
                )
                state.current_epoch_participation = [0] * n
                ops_vector.install_zero_column(
                    state.current_epoch_participation, n, 0xFF
                )
        if cfg["family"] == "altair" and (ec.cur + 1) % int(
            context.EPOCHS_PER_SYNC_COMMITTEE_PERIOD
        ) == 0:
            _sync_committee_updates(ec)
    _count_pass(ec)
    return True
