"""The bytes of the fused epoch sweep against the kernel's own argument
shapes, and the peaks table."""

import inspect

import numpy as np
import pytest

from benchmark import peaks, roofline


def test_fused_epoch_bytes_match_the_kernel_columns():
    from ethereum_consensus_tpu.models import epoch_vector

    params = list(inspect.signature(epoch_vector.fused_epoch_kernel).parameters)
    # xp, then the seven row columns, then the scalars
    assert params[1:8] == list(roofline.FUSED_EPOCH_COLUMNS_IN)
    dtypes = dict(balances=np.uint64, eff=np.uint64, prev_part=np.uint8,
                  slashed=np.bool_, active_prev=np.bool_, eligible=np.bool_,
                  scores=np.uint64)
    for name, width in roofline.FUSED_EPOCH_COLUMNS_IN.items():
        assert np.dtype(dtypes[name]).itemsize == width
    assert roofline.fused_epoch_sweep(1) == 28 + 16 == 44
    # PR 23's transfers at 2^20 rows: 29.4 MB up, 16.8 MB down
    rows = 1 << 20
    assert round(rows * 28 / 1e6, 1) == 29.4 and round(rows * 16 / 1e6, 1) == 16.8
    assert roofline.fused_epoch_sweep(rows) == rows * 44


def test_fused_epoch_outputs_are_two_u64_columns():
    from ethereum_consensus_tpu.models import epoch_vector

    n = 64
    rng = np.random.default_rng(0)
    eff = np.full(n, 32 * 10**9, dtype=np.uint64)
    scores, balances, _ = epoch_vector.fused_epoch_kernel(
        np, eff + np.uint64(5), eff, rng.integers(0, 8, n, dtype=np.uint8),
        np.zeros(n, bool), np.ones(n, bool), np.ones(n, bool),
        np.zeros(n, np.uint64), np.uint64(10**9), np.uint64(1000),
        np.uint64(n * 32), np.uint64(4 * 2**24), 4, 16, (14, 26, 14), 64,
        False, 2, 1,
    )
    assert scores.dtype == balances.dtype == np.uint64
    assert scores.shape == balances.shape == (n,)


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert "v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
