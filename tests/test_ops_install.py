"""ops.install() routing: the spec path must produce bit-identical results
with device sweeps/shuffle routing on vs off (VERDICT #7 — the twins are
cross-checked numerically in test_ops_sweeps; here the *wiring* through the
real spec functions is proven)."""

import sys
from pathlib import Path

import jax
import pytest

jax.config.update("jax_enable_x64", True)

sys.path.insert(0, str(Path(__file__).parent))

from chain_utils import (  # noqa: E402
    fresh_genesis_altair,
    make_attestation,
    produce_block_altair,
)

from ethereum_consensus_tpu import ops  # noqa: E402
from ethereum_consensus_tpu.models import altair  # noqa: E402
from ethereum_consensus_tpu.models.altair.state_transition import (  # noqa: E402
    state_transition,
)
from ethereum_consensus_tpu.models.altair.slot_processing import (  # noqa: E402
    process_slots,
)


@pytest.fixture
def attested_state():
    """An altair state a few slots into epoch 1 with participation flags
    set by real attestations."""
    state, ctx = fresh_genesis_altair(32, "minimal")
    for _ in range(3):
        target = state.slot + 1
        scratch = state.copy()
        process_slots(scratch, target, ctx)
        atts = (
            [make_attestation(state, state.slot, 0, ctx)]
            if state.slot + ctx.MIN_ATTESTATION_INCLUSION_DELAY <= target
            else []
        )
        signed = produce_block_altair(state.copy(), target, ctx, attestations=atts)
        state_transition(state, signed, ctx)
    return state, ctx


@pytest.fixture
def installed():
    """Device routing with thresholds lowered so a 32-validator registry
    takes the device path."""
    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    try:
        yield
    finally:
        ops.uninstall()


def test_flag_deltas_identical(attested_state, installed):
    state, ctx = attested_state
    h = altair.build(ctx.preset)  # noqa: F841 — force container build
    from ethereum_consensus_tpu.models.altair import helpers as ah

    for flag_index in range(3):
        ops.uninstall()
        host = ah.get_flag_index_deltas(state, flag_index, ctx)
        ops.install(sweeps_min_n=1, shuffle_min_n=1)
        dev = ah.get_flag_index_deltas(state, flag_index, ctx)
        assert [list(x) for x in dev] == [list(x) for x in host]


def test_inactivity_identical(attested_state, installed):
    state, ctx = attested_state
    from ethereum_consensus_tpu.models.altair import helpers as ah
    from ethereum_consensus_tpu.models.altair.epoch_processing import (
        process_inactivity_updates,
    )

    ops.uninstall()
    host_pair = ah.get_inactivity_penalty_deltas(state, ctx)
    host_state = state.copy()
    process_inactivity_updates(host_state, ctx)

    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    dev_pair = ah.get_inactivity_penalty_deltas(state, ctx)
    dev_state = state.copy()
    process_inactivity_updates(dev_state, ctx)

    assert [list(x) for x in dev_pair] == [list(x) for x in host_pair]
    assert list(dev_state.inactivity_scores) == list(host_state.inactivity_scores)


def test_effective_balance_identical(attested_state, installed):
    state, ctx = attested_state
    from ethereum_consensus_tpu.models.phase0.epoch_processing import (
        process_effective_balance_updates,
    )

    # skew some balances so hysteresis actually fires
    state = state.copy()
    state.balances[0] += 10**9
    state.balances[1] -= min(10**9, state.balances[1])

    ops.uninstall()
    host_state = state.copy()
    process_effective_balance_updates(host_state, ctx)

    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    dev_state = state.copy()
    process_effective_balance_updates(dev_state, ctx)

    assert [v.effective_balance for v in dev_state.validators] == [
        v.effective_balance for v in host_state.validators
    ]


def test_committee_identical(attested_state, installed):
    state, ctx = attested_state
    from ethereum_consensus_tpu.models.phase0 import helpers as ph

    ops.uninstall()
    host = ph.get_beacon_committee(state, state.slot, 0, ctx)
    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    ph._SHUFFLE_CACHE.clear()
    dev = ph.get_beacon_committee(state, state.slot, 0, ctx)
    assert dev == host


def test_multi_epoch_chain_identical(attested_state, installed):
    """A full multi-slot chain segment produces the same state root with
    routing on vs off (the epoch boundary exercises every routed sweep)."""
    state, ctx = attested_state
    target = (2 * ctx.SLOTS_PER_EPOCH) + 1

    ops.uninstall()
    host_state = state.copy()
    process_slots(host_state, target, ctx)

    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    from ethereum_consensus_tpu.models.phase0 import helpers as ph

    ph._SHUFFLE_CACHE.clear()
    dev_state = state.copy()
    process_slots(dev_state, target, ctx)

    assert type(host_state).hash_tree_root(host_state) == type(
        dev_state
    ).hash_tree_root(dev_state)


def test_install_tells_the_allocator_to_keep_freed_memory(installed):
    """``install()`` applies utils/allocator.py's two glibc settings: a
    whole-registry temporary (16 MiB at 2^21 rows) comes from the heap and
    not from a mapping of its own, and stays on the heap once freed, so
    the next temporary of its size faults in no page."""
    import ctypes

    import numpy as np

    from ethereum_consensus_tpu.utils import allocator

    assert allocator.keep_freed_memory() is allocator.keep_freed_memory()
    if not allocator.keep_freed_memory():
        pytest.skip("not glibc: the settings do not exist here")

    class Mallinfo2(ctypes.Structure):
        _fields_ = [(name, ctypes.c_size_t) for name in (
            "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
            "fsmblks", "uordblks", "fordblks", "keepcost",
        )]

    try:
        mallinfo2 = ctypes.CDLL(None).mallinfo2
    except AttributeError:
        pytest.skip("glibc before 2.33: no mallinfo2")
    mallinfo2.restype = Mallinfo2
    rows = 1 << 21
    before = mallinfo2()
    column = np.ones(rows, dtype=np.uint64)
    held = mallinfo2()
    assert held.hblks == before.hblks, "the block is a mapping of its own"
    assert held.uordblks >= before.uordblks + column.nbytes
    del column
    assert mallinfo2().fordblks >= rows * 8, "the heap gave the block back"
