"""Columnar operations engine — batched attestation participation and
cached registry columns (docs/OPS_VECTOR.md).

The warm deneb block is operations-bound (ROADMAP): at 2^17/64 atts the
altair+ attestation loop performs ~130k individual instrumented
``participation[index] = add_flag(...)`` SSZ writes per block, and the
epoch-boundary sweeps re-extract full registry columns per call. Both
costs come off the hot path here:

* ``RegistryColumns`` — numpy column views over a state's registry
  (validator scalar fields + the scalar lists: balances, participation,
  inactivity scores), built once warm and **delta-invalidated through
  the SSZ mutation instrumentation**: every sanctioned write channel
  (``CachedRootList`` instrumented mutators, ``Container.__setattr__``'s
  weak-parent notify, ``bulk_store``'s changed-indices contract) marks
  the list's ``_col_dirty`` element set (the ``column_channel`` entry of
  ``ssz/core.py``'s ``instrumented_surface()`` manifest), and the cache
  refreshes exactly those rows on next access. Anything untrackable
  resets the channel and the cache rebuilds — stale reads are
  structurally impossible, the cost model degrades, never the values.

* ``process_attestations_batch`` — the block-scoped altair→electra
  attestation fast path: per attestation the full spec validation runs
  through the SAME ``_prepare_attestation`` the scalar path uses (no
  duplicated checks to drift), but the participation-flag writes land in
  working numpy arrays and commit ONCE per participation list via
  ``bulk_store`` with exact changed indices. Bit-identical to the scalar
  loop (which remains the fallback and the differential-test oracle in
  tests/test_ops_vector.py), including mid-block failure: an invalid
  attestation commits the earlier attestations' flags before re-raising,
  exactly the partial state the sequential loop leaves.

* columnar epoch/withdrawal helpers — ``pack_registry_cached`` feeds the
  altair+ reward/inactivity sweeps from the cache instead of per-call
  ``np.fromiter`` walks, ``effective_balance_update_hits`` vectorizes
  the hysteresis sweep (phase0 and the electra compounding variant), and
  ``withdrawal_columns`` backs the capella/electra withdrawals sweeps.

Contract for every array this module hands out: READ-ONLY views
(``writeable=False``); consumers copy before mutating. Mutating a
backing buffer in place would corrupt the cache silently — the
``aliasflow`` speclint rules guard the pattern statically.

Telemetry: ``ops_vector.*`` counters (columns.builds / columns.refresh_rows,
attestations.blocks / attestations.count, bulk_store.calls /
bulk_store.elements) show engagement in every bench ``metrics`` block;
``ops_vector.fallback.{reason}`` counts every degradation to the scalar
path, with a one-shot ``ops_vector.fallback`` trace event per reason so
a degraded host is visible, not just slow.
"""

from __future__ import annotations

import threading

from .. import _env
from ..ssz import column_list as _column_list
from ..ssz.core import CachedRootList, _clean_pack_bytes, bulk_store
from ..telemetry import device as _device_obs
from ..telemetry import memory as _memory
from ..telemetry import metrics
from ..utils import trace

__all__ = [
    "RegistryColumns",
    "columns_for",
    "resident_rows",
    "gather_rows",
    "pack_registry_cached",
    "process_attestations_batch",
    "register_attestation_preparer",
    "effective_balance_update_hits",
    "withdrawal_columns",
    "adopt_list_column",
    "install_zero_column",
    "fallback",
    "BATCH_MIN_VALIDATORS",
    "BATCH_MIN_ATTESTATIONS",
]

# Below this registry size the scalar loops win (column extraction and
# working-array copies cost more than ~n dict/flag operations); the
# differential tests lower it to 0 to force the engine on tiny states.
BATCH_MIN_VALIDATORS = 1 << 10
BATCH_MIN_ATTESTATIONS = 1

_DISABLE_ENV = "ECT_OPS_VECTOR"  # =off disables every columnar path


def _np():
    try:
        import numpy

        return numpy
    except Exception:  # noqa: BLE001 — environment without numpy
        return None


# one-shot trace events per fallback reason (the counters count every
# occurrence; the event makes the FIRST degradation jump out of a trace)
_FALLBACK_SEEN: set = set()
_FALLBACK_LOCK = threading.Lock()


def fallback(reason: str) -> None:
    """Record a degradation to a scalar path: counter per occurrence,
    trace event once per reason per process (plus a routing-journal
    entry while the device observatory is on)."""
    metrics.counter(f"ops_vector.fallback.{reason}").inc()
    if _device_obs.OBSERVATORY.active:
        _device_obs.route("ops_vector", "scalar", reason)
    if reason not in _FALLBACK_SEEN:
        with _FALLBACK_LOCK:
            if reason not in _FALLBACK_SEEN:
                _FALLBACK_SEEN.add(reason)
                trace.event("ops_vector.fallback", reason=reason)


def _disabled() -> bool:
    return _env.flag_off(_DISABLE_ENV)


# ---------------------------------------------------------------------------
# registry columns
# ---------------------------------------------------------------------------


_VAL_INT_FIELDS = (
    "effective_balance",
    "activation_epoch",
    "activation_eligibility_epoch",
    "exit_epoch",
    "withdrawable_epoch",
)


def _read_validator_row(v):
    """(ints..., slashed, prefix) for one validator, or None when a field
    holds a type the column contract can't trust (mutable buffer)."""
    creds = v.withdrawal_credentials
    if type(creds) is not bytes or len(creds) == 0:
        return None
    try:
        ints = tuple(int(getattr(v, f)) for f in _VAL_INT_FIELDS)
    except (TypeError, ValueError):
        return None
    for x in ints:
        if x < 0 or x >= 1 << 64:
            return None
    return ints, bool(v.slashed), creds[0]


# _col_cache records, stored ON the CachedRootList itself so they travel
# across state copies (ssz/core.py _share_col_cache — structural share,
# copy-on-write via _col_owned): ("validators", arrays_dict) for the
# registry, ("list", arr, vmax) for scalar lists. A further entry, where
# present, is the over-allocated buffer (for the registry: the dict of
# them) the arrays are the heads of, so that growth by ``append`` is
# amortised: ``_grown`` here, ``ColumnList.append`` for a column-primary
# list. A writer that replaces the arrays leaves it out.

# the exact classes whose scalar-list column this module serves: the
# plain list (a cache beside the boxed content) and the column-primary
# one (ssz/column_list.py: the column IS the content)
_COLUMN_BEARING = (CachedRootList, _column_list.ColumnList)


def _build_validator_cols(vals) -> "dict | None":
    np = _np()
    if np is None or vals.__class__ is not CachedRootList:
        return None
    n = len(vals)
    try:
        # the credentials type scan is the purity guard: a bytes value is
        # immutable, so every later change MUST flow through __setattr__
        # (which marks _col_dirty); a bytearray could mutate in place
        if not all(
            type(v.withdrawal_credentials) is bytes
            and len(v.withdrawal_credentials) >= 1
            for v in vals
        ):
            return None
        arrays = {
            f: np.fromiter((getattr(v, f) for v in vals), np.uint64, n)
            for f in _VAL_INT_FIELDS
        }
        arrays["slashed"] = np.fromiter(
            (bool(v.slashed) for v in vals), np.bool_, n
        )
        arrays["withdrawal_prefix"] = np.fromiter(
            (v.withdrawal_credentials[0] for v in vals), np.uint8, n
        )
    except (TypeError, ValueError, OverflowError):
        return None
    # arm the element-dirty channel only when the weak-parent wiring is
    # installed (every element notifies the list on __setattr__); without
    # it a field write would be invisible — no cache, rebuild per access
    if not vals._parents_registered:
        metrics.counter("ops_vector.columns.untracked_builds").inc()
        return arrays
    vals._col_cache = ("validators", arrays)
    vals._col_owned = True
    vals._col_dirty = set()
    metrics.counter("ops_vector.columns.builds").inc()
    return arrays


# rows by which working columns were extended in place: the validator
# column set once a row and each list column once a row (a column-primary
# list extends its own column in ``append`` and counts there, under this
# name: ssz/column_list.py)
_EXTENDED_ROWS = metrics.counter("ops_vector.columns.extended_rows")


def _appended_since(cd, have: int, n: int) -> bool:
    """A list of ``n`` elements whose columns hold ``have`` rows grew by
    ``append`` alone since the two were in step: every index from
    ``have`` up is marked (``_mutation_elems`` names an append's element
    and drops the tracking at any other length change)."""
    return n > have and cd.issuperset(range(have, n))


def _grown(np, arr, buf, owned: bool, n: int):
    """(``arr`` at ``n`` rows, its buffer), the rows past ``arr``'s own
    not yet written: the head of ``buf`` where that buffer is ours and
    long enough, else of a fresh one with an eighth more rows than asked
    for (amortised, as ``ColumnList.append`` grows) and ``arr`` copied in,
    which is also the clone a column shared with a copy owes its first
    write."""
    if buf is None or not owned or buf.shape[0] < n:
        buf = np.empty(n + max(n >> 3, 64), dtype=arr.dtype)
        buf[: arr.shape[0]] = arr
    return buf[:n], buf


def _extend_validator_cols(vals, cc, have: int, n: int) -> "dict | None":
    """The resident columns extended from ``have`` rows by the rows
    appended to ``vals``; None where a new row holds what the column
    contract cannot trust."""
    np = _np()
    bufs = cc[2] if len(cc) > 2 else {}
    with trace.span("epoch_vector.sync.extend", rows=n - have):
        rows = [
            _read_validator_row(list.__getitem__(vals, i)) for i in range(have, n)
        ]
        if None in rows:
            return None
        ints, slashed, prefixes = zip(*rows)
        grown, held = {}, {}
        for f, a in cc[1].items():
            grown[f], held[f] = _grown(np, a, bufs.get(f), vals._col_owned, n)
        for f, column in zip(_VAL_INT_FIELDS, zip(*ints)):
            grown[f][have:] = np.array(column, dtype=np.uint64)
        grown["slashed"][have:] = slashed
        grown["withdrawal_prefix"][have:] = prefixes
        vals._col_cache = ("validators", grown, held)
        vals._col_owned = True
        vals._col_dirty.difference_update(range(have, n))
    _EXTENDED_ROWS.inc(n - have)
    return grown


def resident_rows(vals) -> "int | None":
    """How many rows the registry's resident columns hold (they travel
    with copies, so: the registry's length at the last sync on this
    state's lineage), or None where none are resident."""
    cc = getattr(vals, "_col_cache", None)
    if cc is None or cc[0] != "validators":
        return None
    return next(iter(cc[1].values())).shape[0]


def _sync_validator_cols(vals) -> "dict | None":
    cc = vals._col_cache
    cd = vals._col_dirty
    if cc is None or cd is None or cc[0] != "validators":
        return _build_validator_cols(vals)
    arrays = cc[1]
    have, n = next(iter(arrays.values())).shape[0], len(vals)
    if have != n:
        # deposits append: the columns follow by the new rows alone; any
        # other history of a changed length rebuilds
        arrays = (
            _extend_validator_cols(vals, cc, have, n)
            if _appended_since(cd, have, n)
            else None
        )
        if arrays is None:
            vals._col_dirty = None
            return _build_validator_cols(vals)
    if cd:
        if not vals._col_owned:
            # shared with a copy sibling: clone before the first refresh
            arrays = {k: a.copy() for k, a in arrays.items()}
            vals._col_cache = ("validators", arrays)
            vals._col_owned = True
        for i in cd:
            row = _read_validator_row(list.__getitem__(vals, i))
            if row is None:
                vals._col_dirty = None
                return _build_validator_cols(vals)
            ints, sl, px = row
            for f, x in zip(_VAL_INT_FIELDS, ints):
                arrays[f][i] = x
            arrays["slashed"][i] = sl
            arrays["withdrawal_prefix"][i] = px
        metrics.counter("ops_vector.columns.refresh_rows").inc(len(cd))
        cd.clear()
    return arrays


def _build_list_col(src, dtype, vmax):
    np = _np()
    # a column-primary list holds the column asked for by construction
    # (_sync_list_col found it): one that reaches here is asked for
    # another dtype or cap, or lost an invariant, and is rebuilt as the
    # plain list it becomes (``ssz.column_list.left`` counts it)
    _column_list.leave(src)
    if np is None or src.__class__ is not CachedRootList:
        return None
    esize = np.dtype(dtype).itemsize
    raw = _clean_pack_bytes(src, esize)
    if raw is not None:
        # the list's serialization is already in memory (its clean
        # _pack_tree): the column is those bytes, as an owned copy (the
        # buffer is spliced in place and shared with copy siblings)
        arr = np.frombuffer(raw, dtype="<u%d" % esize, count=len(src)).astype(
            dtype
        )
        if vmax < (1 << (8 * esize)) - 1 and bool((arr > vmax).any()):
            return None
        metrics.counter("ops_vector.columns.from_pack").inc()
    else:
        try:
            wide = np.array(src, dtype=np.uint64)
        except (TypeError, ValueError, OverflowError):
            return None
        if wide.ndim != 1 or wide.shape[0] != len(src):
            return None
        if vmax < (1 << 64) - 1 and bool((wide > vmax).any()):
            return None
        arr = wide.astype(dtype) if dtype is not np.uint64 else wide
    src._col_cache = ("list", arr, vmax)
    src._col_owned = True
    src._col_dirty = set()
    metrics.counter("ops_vector.columns.builds").inc()
    return arr


def _extend_list_col(src, cc, have: int, n: int):
    """``_extend_validator_cols`` for a scalar list's one column."""
    np = _np()
    vmax = cc[2]
    with trace.span("epoch_vector.sync.extend", rows=n - have):
        tail = list.__getitem__(src, slice(have, n))
        if not all(type(v) is int and 0 <= v <= vmax for v in tail):
            return None
        arr, buf = _grown(
            np, cc[1], cc[3] if len(cc) > 3 else None, src._col_owned, n
        )
        arr[have:] = np.array(tail, dtype=np.uint64)
        src._col_cache = ("list", arr, vmax, buf)
        src._col_owned = True
        src._col_dirty.difference_update(range(have, n))
    _EXTENDED_ROWS.inc(n - have)
    return arr


def _sync_list_col(src, dtype, vmax):
    cc = src._col_cache
    cd = src._col_dirty
    if (
        cc is None
        or cd is None
        or cc[0] != "list"
        or cc[2] != vmax
        or cc[1].dtype != dtype
    ):
        return _build_list_col(src, dtype, vmax)
    arr = cc[1]
    have, n = arr.shape[0], len(src)
    if have != n:
        # (a column-primary list grows its column in its own ``append``
        # and never comes here with another length)
        arr = (
            _extend_list_col(src, cc, have, n)
            if _appended_since(cd, have, n)
            else None
        )
        if arr is None:
            src._col_dirty = None
            return _build_list_col(src, dtype, vmax)
    if cd:
        if not src._col_owned:
            arr = arr.copy()
            src._col_cache = ("list", arr, vmax)
            src._col_owned = True
        for i in cd:
            # (a column-primary list keeps cd empty; were it not, its slot
            # holds the sentinel, no int: it leaves the mode just below)
            v = list.__getitem__(src, i)
            if type(v) is not int or v < 0 or v > vmax:
                src._col_dirty = None
                return _build_list_col(src, dtype, vmax)
            arr[i] = v
        metrics.counter("ops_vector.columns.refresh_rows").inc(len(cd))
        cd.clear()
    return arr


def _readonly(arr):
    view = arr.view()
    view.flags.writeable = False
    return view


class RegistryColumns:
    """Thin per-state accessor over the list-resident column caches.

    The caches live on the ``CachedRootList`` objects themselves
    (``_col_cache``/``_col_owned``/``_col_dirty``, ssz/core.py), so they
    travel across ``state.copy()`` structurally (copy-on-write) and the
    participation rotation at the epoch boundary keeps its column
    automatically — the list carries it to its new field name. This
    object only resolves fields and applies the dtype contract."""

    # scalar-list fields this cache serves, with their column value cap
    LIST_FIELDS = {
        "balances": (1 << 64) - 1,
        "inactivity_scores": (1 << 64) - 1,
        "previous_epoch_participation": 0xFF,
        "current_epoch_participation": 0xFF,
    }

    __slots__ = ("_state",)

    def __init__(self, state):
        self._state = state

    def validator_columns(self, state=None) -> "dict | None":
        """Read-only validator field columns, or None (no numpy / exotic
        values — callers fall back to their scalar loop)."""
        vals = (state or self._state).validators
        arrays = _sync_validator_cols(vals)
        if arrays is None:
            return None
        return {k: _readonly(a) for k, a in arrays.items()}

    def list_column(self, state, field: str):
        """Read-only uint column over ``state.<field>`` or None."""
        np = _np()
        if np is None:
            return None
        vmax = self.LIST_FIELDS[field]
        dtype = np.dtype(np.uint8) if vmax == 0xFF else np.dtype(np.uint64)
        src = getattr(state, field, None)
        if src is None or src.__class__ not in _COLUMN_BEARING:
            return None
        arr = _sync_list_col(src, dtype, vmax)
        if arr is None:
            return None
        return _readonly(arr)

    def registry_snapshot(self, state=None) -> "dict | None":
        """One read-only column bundle for the serving data plane
        (serving/headstore.py): the validator columns plus the balances
        column, synced in one pass. The HeadStore freezes exactly this
        dict per committed snapshot; every array is a ``writeable=False``
        view, so a reader thread can gather from it but never corrupt
        the cache. None → the caller's scalar fallback (no numpy /
        exotic values / engine disabled).

        Thread contract: building/syncing mutates the list-resident
        cache records, so the FIRST call on a given state must be
        serialized by the caller (the HeadStore builds under its
        snapshot lock); the returned views are then safe to share."""
        state = self._state if state is None else state
        vc = self.validator_columns(state)
        if vc is None:
            return None
        balances = self.list_column(state, "balances")
        if balances is None or balances.shape[0] != next(
            iter(vc.values())
        ).shape[0]:
            return None
        out = dict(vc)
        out["balances"] = balances
        return out


def columns_for(state) -> "RegistryColumns | None":
    """Column accessor for ``state`` (None when disabled / no numpy)."""
    if _disabled() or _np() is None:
        return None
    return RegistryColumns(state)


def gather_rows(bundle: dict, indices, fields=None) -> "dict | None":
    """ONE vectorized gather over a ``registry_snapshot`` bundle: fancy-
    index every requested column (default: all) at ``indices`` in a
    single pass — the serving data plane's per-request-batch unit (the
    bench asserts exactly one of these per batched read). The outputs
    are fresh arrays owned by the caller; the bundle stays untouched."""
    np = _np()
    if np is None:
        return None
    idx = np.asarray(indices, dtype=np.int64)
    return {
        f: bundle[f][idx] for f in (fields if fields is not None else bundle)
    }


def pack_registry_cached(state, previous_epoch: int,
                         use_current_participation: bool = False) -> dict:
    """Cache-backed twin of ``registry_columns.pack_registry`` — the
    same dict shape and the same ``activity_masks`` eligibility formula,
    fed from the delta-refreshed columns instead of per-call fromiter
    walks. Falls back to the literal packing when columns are
    unavailable."""
    cols = columns_for(state)
    packed = None
    if cols is not None:
        packed = _pack_from_columns(
            cols, state, previous_epoch, use_current_participation
        )
    if packed is None:
        fallback("pack_registry")
        from .registry_columns import pack_registry

        return pack_registry(state, previous_epoch, use_current_participation)
    return packed


def _pack_from_columns(cols, state, previous_epoch,
                       use_current_participation) -> "dict | None":
    np = _np()
    vc = cols.validator_columns(state)
    if vc is None:
        return None
    n = len(state.validators)
    part_field = (
        "current_epoch_participation"
        if use_current_participation
        else "previous_epoch_participation"
    )
    if getattr(state, part_field, None) is None:  # phase0 states
        participation = np.zeros(n, dtype=np.uint8)
    else:
        participation = cols.list_column(state, part_field)
        if participation is None:
            return None
    if getattr(state, "inactivity_scores", None) is None:
        inactivity = np.zeros(n, dtype=np.uint64)
    else:
        inactivity = cols.list_column(state, "inactivity_scores")
        if inactivity is None:
            return None
    balances = cols.list_column(state, "balances")
    if balances is None:
        return None
    from .registry_columns import activity_masks

    active_previous, eligible = activity_masks(
        vc["activation_epoch"],
        vc["exit_epoch"],
        vc["withdrawable_epoch"],
        vc["slashed"],
        previous_epoch,
    )
    return {
        "effective_balance": vc["effective_balance"],
        "slashed": vc["slashed"],
        "active_previous": active_previous,
        "eligible": eligible,
        "previous_participation": participation,
        "inactivity_scores": inactivity,
        "balances": balances,
    }


# ---------------------------------------------------------------------------
# write-direction column commits (the columnar-primary epoch engine,
# models/epoch_vector.py)
# ---------------------------------------------------------------------------


def adopt_list_column(lst, arr, changed, vmax) -> int:
    """Columnar-primary commit of a scalar-list column: ``arr`` is the
    AUTHORITATIVE new content (the epoch engine computed the whole epoch
    on it) and becomes the list's content as it is. A plain
    ``CachedRootList`` given a ``uint64`` column of its length turns
    column-primary (``ssz/column_list.py adopt``): the dirty 4096-element
    groups are marked from ``changed`` (so incremental HTR re-merkleizes
    only what moved), the array is installed as the owned clean column,
    and NO row is boxed: reads, single writes and ``append`` are served
    by the array until a structural mutation makes the list box itself
    again (docs/OPS_VECTOR.md, "The storage contract"). Anything else (a
    list of another class, a narrower column) takes the ``bulk_store``
    it always took: one ``tolist``, one slice store, the array as the
    clean cache beside the boxed content.

    ``changed`` names every position whose value differs from the list's
    current content: a boolean mask over the rows (the comparison
    itself; the store takes its groups from it without an index array)
    or the indices. Returns how many rows that is.

    Ownership contract: the caller HANDS OVER ``arr`` — it must never
    mutate it afterwards (the epoch engine drops its working references
    at commit). A no-change commit stores nothing: with finality that is
    the inactivity scores' commit at every boundary (all 0, and they
    stay 0), so a boundary pays one registry-sized store, the balances'.
    Without finality the scores move too, and the boundary pays two: the
    cell ``deneb-1m.epoch-leak`` is the one that runs the two-store
    commit."""
    np = _np()
    n = len(lst)
    if np is None or arr.shape[0] != n:
        fallback("adopt_shape")
        changed = [int(i) for i in changed]
        bulk_store(lst, [int(x) for x in arr], changed)
        return len(changed)
    changed = np.asarray(changed)
    is_mask = changed.dtype.kind == "b"
    n_changed = int(np.count_nonzero(changed)) if is_mask else int(changed.size)
    if n_changed and _column_list.adopt(lst, arr, changed, vmax):
        metrics.counter("ops_vector.columns.adopted").inc()
        return n_changed
    if n_changed:
        bulk_store(lst, arr, np.flatnonzero(changed) if is_mask else changed)
        metrics.counter("ops_vector.bulk_store.calls").inc()
        metrics.counter("ops_vector.bulk_store.elements").inc(n_changed)
    if lst.__class__ in _COLUMN_BEARING:
        # equal content, a fresh array: ours alone again after a copy
        lst._col_cache = ("list", arr, vmax)
        lst._col_owned = True
        lst._col_dirty = set()
        metrics.counter("ops_vector.columns.adopted").inc()
    return n_changed


def install_zero_column(lst, n: int, vmax: int = 0xFF) -> None:
    """Column adoption for a FRESH all-zero list (the participation
    rotation writes ``[0] * n``): the list already holds exactly zeros,
    so no splice is needed — just install the matching zero column as
    the owned, clean cache, and certify uniformity (every element is a
    literal int 0) so the next hash walk skips the type scan."""
    np = _np()
    if np is None or lst.__class__ is not CachedRootList or len(lst) != n:
        return
    dtype = np.uint8 if vmax == 0xFF else np.uint64
    lst._col_cache = ("list", np.zeros(n, dtype=dtype), vmax)
    lst._col_owned = True
    lst._col_dirty = set()
    lst._uniform_kind = ("int",)
    metrics.counter("ops_vector.columns.adopted").inc()


# ---------------------------------------------------------------------------
# block-scoped attestation fast path
# ---------------------------------------------------------------------------

# attestation_fn -> (prepare_fn, helpers_module); each fork's
# block_processing registers its pair at import (models/altair/...py
# bottom), so the engine recognizes exactly the functions whose
# validation it can reuse and falls back on any custom hook. Writes are
# import-time but lock-held anyway (two threads importing fork modules
# concurrently); reads stay lock-free (dict get is atomic).
_ATTESTATION_PREPARERS: dict = {}
_PREPARER_LOCK = threading.Lock()


def register_attestation_preparer(attestation_fn, prepare_fn, helpers) -> None:
    with _PREPARER_LOCK:
        _ATTESTATION_PREPARERS[attestation_fn] = (prepare_fn, helpers)


def process_attestations_batch(state, attestations, context,
                               attestation_fn) -> bool:
    """Apply every attestation of a block through the columnar fast path.

    Returns True when fully applied (validation, participation flags,
    proposer rewards — bit-identical to the scalar loop); False when the
    caller must run the scalar fallback. On a validation error the
    already-processed attestations' flags are committed before the error
    propagates — the exact partial state the sequential loop leaves."""
    n_atts = len(attestations)
    if n_atts < BATCH_MIN_ATTESTATIONS:
        # an EMPTY list is no work at all, not a decline of work — only
        # journal when real attestations were routed to the scalar loop
        if n_atts:
            fallback("below_threshold")
        return False
    if _disabled():
        fallback("disabled")
        return False
    entry = _ATTESTATION_PREPARERS.get(attestation_fn)
    if entry is None:
        fallback("unregistered_attestation_fn")
        return False
    if len(state.validators) < BATCH_MIN_VALIDATORS:
        # deliberate cost threshold, not a degradation — but journaled
        # all the same: a soak that never crosses it should show WHY
        # the columnar path never engaged
        fallback("below_threshold")
        return False
    np = _np()
    if np is None:
        fallback("no_numpy")
        return False
    cur_list = getattr(state, "current_epoch_participation", None)
    prev_list = getattr(state, "previous_epoch_participation", None)
    if cur_list is None or prev_list is None or cur_list is prev_list:
        fallback("participation_shape")
        return False
    cols = columns_for(state)
    if cols is None:
        fallback("columns_unavailable")
        return False
    vc = cols.validator_columns(state)
    cur_col = cols.list_column(state, "current_epoch_participation")
    prev_col = cols.list_column(state, "previous_epoch_participation")
    if vc is None or cur_col is None or prev_col is None:
        fallback("columns_unavailable")
        return False

    prepare, hm = entry
    from .altair.constants import (
        PARTICIPATION_FLAG_WEIGHTS,
        PROPOSER_WEIGHT,
        WEIGHT_DENOMINATOR,
    )

    increment = int(context.EFFECTIVE_BALANCE_INCREMENT)
    base_increments = vc["effective_balance"] // np.uint64(increment)
    brpi = int(hm.get_base_reward_per_increment(state, context))
    proposer_denominator = (
        (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT)
        * WEIGHT_DENOMINATOR
        // PROPOSER_WEIGHT
    )
    # working copies: reads and writes stay here until the single commit
    cur = cur_col.copy()
    prev = prev_col.copy()
    if _memory.OBSERVATORY.active:
        # bandwidth: the per-block participation working set (two full
        # column materializations per batched block)
        _memory.OBSERVATORY.record_copy(
            "ops_vector.working_copies", int(cur.nbytes) + int(prev.nbytes)
        )

    def commit() -> None:
        for arr, orig, lst in (
            (cur, cur_col, cur_list),
            (prev, prev_col, prev_list),
        ):
            changed = np.nonzero(arr != orig)[0]
            if changed.size:
                bulk_store(lst, arr.tolist(), changed)
                metrics.counter("ops_vector.bulk_store.calls").inc()
                metrics.counter("ops_vector.bulk_store.elements").inc(
                    int(changed.size)
                )

    with trace.span(
        "ops_vector.attestations",
        attestations=n_atts,
        validators=len(state.validators),
    ):
        try:
            for attestation in attestations:
                attesting_indices, flag_indices, is_current = prepare(
                    state, attestation, context
                )
                k = len(attesting_indices)
                idx = np.fromiter(attesting_indices, np.int64, k)
                arr = cur if is_current else prev
                vals = arr[idx]
                numerator_increments = 0
                mask = 0
                for flag_index in flag_indices:
                    bit = np.uint8(1 << flag_index)
                    newly = (vals & bit) == 0
                    if newly.any():
                        numerator_increments += PARTICIPATION_FLAG_WEIGHTS[
                            flag_index
                        ] * int(base_increments[idx[newly]].sum())
                    mask |= 1 << flag_index
                if mask and k:
                    arr[idx] = vals | np.uint8(mask)
                proposer_reward = (
                    numerator_increments * brpi
                ) // proposer_denominator
                hm.increase_balance(
                    state,
                    hm.get_beacon_proposer_index(state, context),
                    proposer_reward,
                )
        except BaseException:
            # the sequential loop leaves attestations 0..k-1 applied when
            # attestation k fails — commit that exact partial state
            commit()
            raise
        commit()
    metrics.counter("ops_vector.attestations.blocks").inc()
    metrics.counter("ops_vector.attestations.count").inc(n_atts)
    return True


# ---------------------------------------------------------------------------
# columnar epoch-boundary / withdrawal helpers
# ---------------------------------------------------------------------------


def effective_balance_update_hits(state, context,
                                  per_validator_limit: bool = False):
    """The hysteresis sweep as (index, new_effective_balance) hits —
    exactly the writes the literal loop performs (it only ever stores a
    DIFFERENT value on a threshold crossing, so changed-only is the
    identical state). ``per_validator_limit`` selects the electra
    compounding cap (EIP-7251); None = fall back to the scalar loop."""
    np = _np()
    if np is None:
        fallback("no_numpy")
        return None
    cols = columns_for(state)
    vc = cols.validator_columns(state) if cols is not None else None
    balances = cols.list_column(state, "balances") if cols is not None else None
    if vc is None or balances is None:
        fallback("columns_unavailable")
        return None
    eff = vc["effective_balance"]
    increment = int(context.EFFECTIVE_BALANCE_INCREMENT)
    hysteresis_increment = increment // int(context.HYSTERESIS_QUOTIENT)
    down = hysteresis_increment * int(context.HYSTERESIS_DOWNWARD_MULTIPLIER)
    up = hysteresis_increment * int(context.HYSTERESIS_UPWARD_MULTIPLIER)
    # balance + threshold must stay inside the u64 lane (adversarial
    # near-2^64 balances would wrap the comparison)
    top = (1 << 64) - 1 - max(down, up)
    if int(balances.max(initial=0)) > top or int(eff.max(initial=0)) > top:
        fallback("u64_guard")
        return None
    if per_validator_limit:
        limit = np.where(
            vc["withdrawal_prefix"] == np.uint8(0x02),
            np.uint64(int(context.MAX_EFFECTIVE_BALANCE_ELECTRA)),
            np.uint64(int(context.MIN_ACTIVATION_BALANCE)),
        )
    else:
        limit = np.uint64(int(context.MAX_EFFECTIVE_BALANCE))
    update = (balances + np.uint64(down) < eff) | (
        eff + np.uint64(up) < balances
    )
    candidate = np.minimum(
        balances - balances % np.uint64(increment), limit
    )
    hit = update & (candidate != eff)
    idxs = np.nonzero(hit)[0]
    return [(int(i), int(candidate[i])) for i in idxs.tolist()]


def withdrawal_columns(state) -> "dict | None":
    """Read-only columns for the capella/electra withdrawals sweeps:
    withdrawal_prefix (first credentials byte), withdrawable_epoch,
    effective_balance, balances. None = scalar fallback (counted)."""
    cols = columns_for(state)
    if cols is None:
        fallback("columns_unavailable")
        return None
    vc = cols.validator_columns(state)
    balances = cols.list_column(state, "balances")
    if vc is None or balances is None:
        fallback("columns_unavailable")
        return None
    if balances.shape[0] != vc["withdrawable_epoch"].shape[0]:
        fallback("length_mismatch")
        return None
    return {
        "withdrawal_prefix": vc["withdrawal_prefix"],
        "withdrawable_epoch": vc["withdrawable_epoch"],
        "effective_balance": vc["effective_balance"],
        "balances": balances,
    }
