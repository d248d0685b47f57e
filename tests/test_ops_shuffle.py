"""Device shuffle kernel vs the host spec functions — bit-identical
whole-list and per-index results."""

import sys
from pathlib import Path

import jax

jax.config.update("jax_enable_x64", True)

sys.path.insert(0, str(Path(__file__).parent))

import numpy as np  # noqa: E402
from chain_utils import fresh_genesis_altair  # noqa: E402

from ethereum_consensus_tpu.models.phase0 import helpers as h  # noqa: E402
from ethereum_consensus_tpu.ops import shuffle  # noqa: E402


def test_shuffle_device_matches_host():
    state, ctx = fresh_genesis_altair(16, "minimal")
    seed = b"\x37" * 32
    for count in (1, 2, 16, 100, 257):
        indices = list(range(count))
        host = h.compute_shuffled_indices(indices, seed, ctx)
        device = shuffle.compute_shuffled_indices_device(indices, seed, ctx)
        assert device == host, count
        # spot-check per-index parity too
        mapping = np.asarray(
            shuffle.shuffled_indices_device(count, seed, ctx.SHUFFLE_ROUND_COUNT)
        )
        for i in (0, count // 2, count - 1):
            assert mapping[i] == h.compute_shuffled_index(i, count, seed, ctx)
