"""Native loader for the epoch pass's column sweep (epoch_scan.cpp).

Built and cached like the SHA-256 merkle library (``native/__init__.py``):
compiled once per source and host under ``_build/``, loaded through
ctypes. ``models/epoch_vector.py _sync`` calls ``epoch_scan`` once a pass
and keeps its numpy sequence as the fallback and the oracle.
"""

from __future__ import annotations

import ctypes
import os

from . import artifact_tag, build_shared

__all__ = ["load", "available", "epoch_scan", "SCAN_FIELDS"]

_SOURCE = os.path.join(os.path.dirname(__file__), "epoch_scan.cpp")
_LIB = None
_TRIED = False

# the scalars ``ec_epoch_scan`` writes, in its order
SCAN_FIELDS = (
    "balance_max",
    "eff_max",
    "exit_max",
    "inact_max",
    "n_active_prev",
    "n_active_cur",
    "n_eligible",
    "active_cur_eff",
    "prev_target_eff",
    "cur_target_eff",
)


def load():
    """Compile (once per source hash and host) + load the library, or
    None."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    lib_path = build_shared(
        "epoch_scan", artifact_tag([_SOURCE]), _SOURCE, ["-pthread"],
        timeout=120,
    )
    if lib_path is None:
        return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:
        return None
    ptr, u64 = ctypes.c_void_p, ctypes.c_uint64
    lib.ec_epoch_scan.argtypes = [
        ctypes.c_size_t, ptr, ptr, ptr, ptr, ptr, ptr, ptr, ptr, ptr,
        u64, u64, ctypes.c_uint32, ptr, ptr, ptr,
        ctypes.POINTER(u64), ctypes.c_uint32,
    ]
    lib.ec_epoch_scan.restype = ctypes.c_uint32
    _LIB = lib
    return lib


def available() -> bool:
    return load() is not None


# the dtype of each column ``ec_epoch_scan`` reads, in its argument order
_DTYPES = ("uint64",) * 5 + ("bool", "uint8", "uint8", "uint64")


def epoch_scan(
    np, balances, eff, act, exit_, wdr, slashed, prev_part, cur_part, inact,
    prev: int, cur: int, target_flag: int, n_threads: int,
):
    """One sweep over the registry's columns (``uint64`` but ``slashed``
    ``bool`` and the participation ``uint8``; the last three None for
    phase0 or all three given) on up to ``n_threads`` host threads.
    Returns the masks ``(active_prev, active_cur, eligible)`` as new
    ``bool`` arrays, a dict of ``SCAN_FIELDS``, and the threads that ran;
    None where a column is not C-contiguous, of its dtype and of ``eff``'s
    length, so that the sweep cannot read it in place."""
    lib = load()
    n = eff.shape[0]
    columns = (
        balances, eff, act, exit_, wdr, slashed, prev_part, cur_part, inact,
    )
    if len({prev_part is None, cur_part is None, inact is None}) > 1:
        return None
    for column, dtype in zip(columns, _DTYPES):
        if column is not None and not (
            column.dtype == dtype
            and column.flags.c_contiguous
            and column.shape == (n,)
        ):
            return None
    masks = tuple(np.empty(n, dtype=np.bool_) for _ in range(3))
    out = (ctypes.c_uint64 * len(SCAN_FIELDS))()
    threads = lib.ec_epoch_scan(
        n,
        *(None if column is None else column.ctypes.data for column in columns),
        prev, cur, target_flag,
        *(mask.ctypes.data for mask in masks),
        out, n_threads,
    )
    return masks, dict(zip(SCAN_FIELDS, out)), int(threads)
