"""Runtime switches for device acceleration of spec-path functions.

Deliberately free of any jax import: the host layers (models/, ssz/)
consult these flags on every call and only lazily import jax (the ops
package, the jitted epoch kernel) when a flag is on, so a host-only
process never pays for jax (tests/test_host_only.py holds it to that).
The flags are set by ``ops.install()`` (and unset by ``ops.uninstall()``).

Thresholds are minimum element counts: below the threshold the host path
runs. What each one selects is in its predicate's docstring.

These predicates are ALSO the routing journal's primary source
(telemetry/device.py): every consult is a device-vs-host decision, so
while the device observatory is active each one is journaled with its
threshold inputs — the gate functions pre-guard on the observatory's
``active`` bool, keeping the off path at one extra read.
"""

from __future__ import annotations

from .telemetry import device as _device_obs

SWEEPS_MIN_N: int | None = None
SHUFFLE_MIN_N: int | None = None
BLS_AGG_MIN_N: int | None = None
PAIRING_MIN_SETS: int | None = None


def _journal(kind: str, routed: bool, n: int, threshold: "int | None") -> None:
    _device_obs.route(
        kind,
        "device" if routed else "host",
        reason=(
            "routed"
            if routed
            else ("not_installed" if threshold is None else "below_threshold")
        ),
        n=n,
        threshold=threshold,
    )


def sweeps_enabled(n: int) -> bool:
    """Does the columnar epoch pass (models/epoch_vector.py) of an
    altair-family fork run ``jitted_kernels()["fused_epoch"]`` in place
    of the host kernels for its inactivity + rewards step, on an
    ``n``-validator registry? Nothing else reads this gate: its one
    caller is ``process_epoch_columnar``, and the literal per-fork
    functions are host code whatever is installed. Renaming the journal
    kind (``sweeps``) and ``ops.install``'s keyword (``sweeps_min_n``)
    for what they select is the benchmark's to do (ROADMAP D1d)."""
    routed = SWEEPS_MIN_N is not None and n >= SWEEPS_MIN_N
    if _device_obs.OBSERVATORY.active:
        _journal("sweeps", routed, n, SWEEPS_MIN_N)
    return routed


def shuffle_enabled(n: int) -> bool:
    """Route committee shuffling to the device whole-list kernel for an
    ``n``-element index list?"""
    routed = SHUFFLE_MIN_N is not None and n >= SHUFFLE_MIN_N
    if _device_obs.OBSERVATORY.active:
        _journal("shuffle", routed, n, SHUFFLE_MIN_N)
    return routed


def bls_agg_enabled(n: int) -> bool:
    """Route G1 pubkey aggregation to the device limb kernels for an
    ``n``-point batch? (Below the threshold the native C++ adds win —
    the device fold is latency-bound, not work-bound.)"""
    routed = BLS_AGG_MIN_N is not None and n >= BLS_AGG_MIN_N
    if _device_obs.OBSERVATORY.active:
        _journal("bls_agg", routed, n, BLS_AGG_MIN_N)
    return routed


def pairing_enabled(n_sets: int) -> bool:
    """Route the RLC batch verification (blinder mults + Miller loops +
    Fq12 product) to the device pairing kernels for an ``n_sets``
    batch? The native multi-pairing wins below the threshold.

    NOTE: the definitive pairing-route journal entry (device attempt
    succeeded / fell back to host) is written by ``crypto/bls.py`` at
    the verdict site — this gate only journals the threshold decision
    for batches it declines, so the two don't double-count routed
    batches."""
    routed = PAIRING_MIN_SETS is not None and n_sets >= PAIRING_MIN_SETS
    if not routed and _device_obs.OBSERVATORY.active:
        _journal("pairing_gate", routed, n_sets, PAIRING_MIN_SETS)
    return routed
