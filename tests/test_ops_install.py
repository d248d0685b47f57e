"""ops.install() routing: the spec path must produce bit-identical results
with the fused epoch kernel and the device shuffle routed on vs off (the
kernels are cross-checked numerically in test_epoch_vector and
test_ops_shuffle; here the *wiring* through ``process_slots`` and the
committee helpers is proven)."""

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
def installed(monkeypatch):
    """Device routing with thresholds lowered so a 32-validator registry
    takes the device routes: the columnar epoch pass engages
    (``EPOCH_VECTOR_MIN_VALIDATORS`` 0) and its gate selects the fused
    jitted kernel (``sweeps_min_n`` 1); the shuffle goes to its kernel."""
    from ethereum_consensus_tpu.models import epoch_vector

    monkeypatch.setattr(epoch_vector, "EPOCH_VECTOR_MIN_VALIDATORS", 0)
    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    try:
        yield
    finally:
        ops.uninstall()


def _skewed(state):
    """A copy with scores that move, a missed-target penalty to pay and
    hysteresis to fire both ways."""
    state = state.copy()
    for i, score in ((2, 7), (5, 40), (11, 3)):
        state.inactivity_scores[i] = score
    state.balances[0] += 10**9
    state.balances[1] -= min(3 * 10**9, state.balances[1])
    return state


def _balances(state):
    return list(state.balances)


def _inactivity_scores(state):
    return list(state.inactivity_scores)


def _effective_balances(state):
    return [v.effective_balance for v in state.validators]


def _root(state):
    return type(state).hash_tree_root(state)


@pytest.mark.parametrize(
    "result",
    [_balances, _inactivity_scores, _effective_balances, _root],
    ids=["balances", "inactivity_scores", "effective_balances", "chain_root"],
)
def test_fused_route_identical_through_process_slots(
    attested_state, installed, result
):
    """``process_slots`` over three epoch boundaries (the genesis epoch's,
    which runs no inactivity or rewards stage, and two that do) leaves the
    same stage-visible result with routing on (the pass runs inactivity +
    rewards as the fused jitted kernel, counted) as with routing off (the
    same pass on its host kernels)."""
    from ethereum_consensus_tpu.models.phase0 import helpers as ph
    from ethereum_consensus_tpu.telemetry import metrics

    state, ctx = attested_state
    state = _skewed(state)
    target = (3 * ctx.SLOTS_PER_EPOCH) + 1
    fused = metrics.counter("epoch_vector.fused.jit")
    epochs = metrics.counter("epoch_vector.epochs")

    ops.uninstall()
    at = fused.value(), epochs.value()
    host_state = state.copy()
    process_slots(host_state, target, ctx)
    assert (fused.value(), epochs.value()) == (at[0], at[1] + 3)

    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    ph._SHUFFLE_CACHE.clear()
    dev_state = state.copy()
    process_slots(dev_state, target, ctx)
    assert (fused.value(), epochs.value()) == (at[0] + 2, at[1] + 6)

    assert result(dev_state) == result(host_state)
    assert result(host_state) != result(state)


def _flag_deltas(state, ctx):
    from ethereum_consensus_tpu.models.altair import helpers as ah

    return [ah.get_flag_index_deltas(state, flag, ctx) for flag in range(3)]


def _penalties_altair(state, ctx):
    from ethereum_consensus_tpu.models.altair import helpers as ah

    return ah.get_inactivity_penalty_deltas(state, ctx)


def _penalties_bellatrix(state, ctx):
    from ethereum_consensus_tpu.models.bellatrix import helpers as bh

    return bh.get_inactivity_penalty_deltas(state, ctx)


def _inactivity_updates(state, ctx):
    from ethereum_consensus_tpu.models.altair.epoch_processing import (
        process_inactivity_updates,
    )

    process_inactivity_updates(state, ctx)
    return _inactivity_scores(state)


def _effective_balance_updates(state, ctx):
    from ethereum_consensus_tpu.models.phase0.epoch_processing import (
        process_effective_balance_updates,
    )

    process_effective_balance_updates(state, ctx)
    return _effective_balances(state)


@pytest.mark.parametrize(
    "literal",
    [
        _flag_deltas, _penalties_altair, _penalties_bellatrix,
        _inactivity_updates, _effective_balance_updates,
    ],
    ids=lambda fn: fn.__name__.lstrip("_"),
)
def test_literal_functions_are_host_code_whatever_is_installed(
    attested_state, installed, monkeypatch, literal
):
    """The oracle the tests and the spec-test rewards surface hold every
    fast path to: with the gate on (and the columnar pass out of the
    way) a literal per-fork function consults no gate, opens no transfer
    seam, compiles nothing, and answers as it does with nothing
    installed."""
    from ethereum_consensus_tpu.telemetry import device as device_obs

    monkeypatch.setenv("ECT_EPOCH_VECTOR", "off")
    state, ctx = attested_state
    state = state.copy()
    process_slots(state, 2 * ctx.SLOTS_PER_EPOCH - 1, ctx)
    state = _skewed(state)

    with device_obs.observing() as obs:
        got = literal(state.copy(), ctx)
        assert obs.routes() == []
        assert obs.compiles() == []
        assert obs.transfer_summary()["sites"] == {}
    ops.uninstall()
    assert got == literal(state.copy(), ctx)


def test_committee_identical(attested_state, installed):
    state, ctx = attested_state
    from ethereum_consensus_tpu.models.phase0 import helpers as ph

    ops.uninstall()
    host = ph.get_beacon_committee(state, state.slot, 0, ctx)
    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    ph._SHUFFLE_CACHE.clear()
    dev = ph.get_beacon_committee(state, state.slot, 0, ctx)
    assert dev == host


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
