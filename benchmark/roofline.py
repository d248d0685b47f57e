"""The bytes a kernel has to move, computed from its shapes: the numerator
of a roofline share. One function per kernel, found by name from a metric
file's ``bytes_fn``."""

from __future__ import annotations

# epoch_vector.fused_epoch_kernel, per registry row: its array arguments and
# results (the scalars are a few bytes a call)
FUSED_EPOCH_COLUMNS_IN = {
    "balances": 8,     # uint64
    "eff": 8,          # uint64 effective balance
    "prev_part": 1,    # uint8 participation flags
    "slashed": 1,      # bool
    "active_prev": 1,  # bool
    "eligible": 1,     # bool
    "scores": 8,       # uint64 inactivity scores
}
FUSED_EPOCH_COLUMNS_OUT = {"scores": 8, "balances": 8}


def fused_epoch_sweep(rows: int) -> int:
    """Every input column read once and every output column written once:
    28 + 16 = 44 bytes a row."""
    per_row = sum(FUSED_EPOCH_COLUMNS_IN.values()) + sum(
        FUSED_EPOCH_COLUMNS_OUT.values()
    )
    return int(rows) * per_row


BYTES_FNS = {"fused_epoch_sweep": fused_epoch_sweep}
