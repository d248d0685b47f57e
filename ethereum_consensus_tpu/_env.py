"""Central readers for the package's ``EC_*``/``ECT_*`` environment flags.

Every environ read of a repo flag goes through this module — speclint's
``envflags`` analyzer enforces it (``envflags/scattered-env-read``).
Centralizing buys three things the scattered ``os.environ.get`` sites
could not:

* one truth for the parse idioms ("off"/"0"/"false" vs "1"/"on" vs
  mode strings), so a new site cannot invent a subtly different
  spelling of "disabled";
* a statically readable key registry (``KNOWN_KEYS``) that the linter
  diffs against the documented flag table in docs/OBSERVABILITY.md, so
  an undocumented flag cannot land; and
* the import-ordering guarantee stays auditable: this module imports
  NOTHING but the stdlib, so a gate check like ``flag_off(...)`` can
  never drag jax in — the "plain env read before jax import" discipline
  (a mesh-off process must never pay for jax) is preserved by
  construction at the reader layer.

Readers deliberately take the key STRING (not an enum): call sites read
``_env.flag_off(_DISABLE_ENV)`` and the linter resolves the constant to
its ``ECT_*`` value for the KNOWN_KEYS cross-check.
"""

from __future__ import annotations

import os
from contextlib import contextmanager

# Every environment flag the PACKAGE reads, with a one-line meaning.
# The envflags analyzer checks (a) every EC_/ECT_ environ read in the
# package resolves to a key listed here, and (b) every key here has a
# row in the "Environment flags" table in docs/OBSERVABILITY.md.
KNOWN_KEYS = {
    "ECT_OPS_VECTOR": "=off disables every columnar path (scalar oracle mode)",
    "ECT_EPOCH_VECTOR": "=off disables just the columnar-primary epoch engine",
    "ECT_COMMITTEE_MASKS": "=off disables just the phase0 committee-mask kernel",
    "ECT_POOL_RLC": "=off forces the pool's scalar per-message admission twin",
    "ECT_MESH": "mesh size: N devices | auto | off (plain read gates jax import)",
    "ECT_MESH_EPOCH_MIN_N": "registry size below which epoch sweeps stay host-routed",
    "ECT_MESH_MERKLE_MIN_CHUNKS": "flat-tree chunk count below which merkle stays host",
    "ECT_MESH_PROOF_MIN_CHUNKS": "proof-group chunk count below which gathers stay host",
    "ECT_PAIRING_MIN_SETS": "pairing-batch size routed to device; off pins the host engine",
    "ECT_TRACEMALLOC": "=1/on adds tracemalloc deltas to the memory observatory",
    "EC_PAIRING_MULT": "pairing product kernel: u64 (CIOS lanes) | mxu (int8 matmul)",
    "EC_BLS_BACKEND": "BLS backend pin: auto | native | python",
    "EC_NATIVE_SHA_NI": "native SHA extension toggle (build-probe cache key input)",
}


def raw(key: str, default: str = "") -> str:
    """The raw environ value (``os.environ.get`` with a default)."""
    return os.environ.get(key, default)


def raw_or_none(key: str) -> "str | None":
    """The raw environ value, or None when the key is unset — for flags
    whose unset/empty states mean different things (ECT_PAIRING_MIN_SETS:
    unset = auto threshold, "off" = host pinned)."""
    return os.environ.get(key)


def mode(key: str, default: str = "") -> str:
    """Stripped, lowercased environ value — the mode-string idiom
    (``ECT_MESH=Auto`` reads as ``"auto"``)."""
    return os.environ.get(key, default).strip().lower()


def flag_off(key: str) -> bool:
    """True when the flag explicitly disables its feature: the repo-wide
    ``=off`` idiom (off/0/false, case-insensitive). Unset is NOT off —
    features default on and are opted out."""
    return os.environ.get(key, "").strip().lower() in ("off", "0", "false")


def flag_on(key: str) -> bool:
    """True when the flag explicitly enables its feature: the opt-in
    ``=1``/``=on`` idiom (ECT_TRACEMALLOC). Unset is NOT on."""
    return os.environ.get(key, "").strip().lower() in ("1", "on")


def mesh_requested(key: str = "ECT_MESH") -> bool:
    """Is a mesh explicitly requested? The gate host layers consult
    BEFORE importing anything jax-adjacent: unset/off/0/none/host all
    mean "no mesh" and must not trigger a jax import downstream."""
    return mode(key) not in ("", "off", "0", "none", "host")


@contextmanager
def override(key: str, value: "str | None"):
    """Temporarily pin (or, with ``None``, unset) a flag for the scope,
    restoring the prior state on exit — the scenario harness's
    scalar-mode/forced-columnar save-set-restore idiom, centralized so
    environ WRITES stay on this module's surface too."""
    old = os.environ.get(key)
    if value is None:
        os.environ.pop(key, None)
    else:
        os.environ[key] = value
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = old
