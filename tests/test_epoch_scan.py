"""The epoch pass's registry-wide scans in one native sweep over the
columns (``models/epoch_vector.py _scan``, ``native/epoch_scan.cpp``): the
three masks byte for byte and every scalar against the numpy sequence the
sweep replaced, at every thread count, on the compositions the benchmark's
deployments hold; every u64 lane guard declining through the sweep with the
state untouched, as through the numpy sequence; a whole pass's roots with
the sweep on and forced off; the counted fallback."""

import os
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
sys.path.insert(0, str(Path(__file__).parent.parent))
import chain_utils  # noqa: E402

from ethereum_consensus_tpu import native, ops  # noqa: E402
from ethereum_consensus_tpu.models import epoch_vector  # noqa: E402
from ethereum_consensus_tpu.models.deneb import slot_processing  # noqa: E402
from ethereum_consensus_tpu.native import epoch_scan  # noqa: E402
from ethereum_consensus_tpu.scenarios.harness import (  # noqa: E402
    assert_bit_identical,
    assert_column_consistency,
)
from ethereum_consensus_tpu.telemetry import metrics, spans  # noqa: E402

pytestmark = pytest.mark.skipif(
    not epoch_scan.available(), reason="no C++ toolchain: no native sweep"
)

FAR = np.uint64((1 << 64) - 1)
ETH = 10**9
LANE = 1 << 63
U64_MAX = (1 << 64) - 1
# 1,905,000 rows (the growing deployment) over 16: a multiple of no block
ODD_ROWS = 119_063


def _counter(name: str) -> int:
    return metrics.counter(name).value()


def _columns(composition: str, n: int, seed: int = 40):
    """The columns ``_scan`` reads, at epoch 1,000 -> 1,001, in one of the
    compositions of the benchmark's worlds."""
    rng = np.random.default_rng(seed)
    prev, cur = 1000, 1001
    act = np.zeros(n, np.uint64)
    exit_ = np.full(n, FAR, np.uint64)
    wdr = np.full(n, FAR, np.uint64)
    slashed = np.zeros(n, np.bool_)
    eff = np.full(n, 32 * ETH, np.uint64)
    flags = 0b111 if composition == "all_ones" else None
    if composition == "interleaved":
        # the 2m world: exited and withdrawn rows interleaved, their share
        # falling from 0.9 at index 0 to 0.1; a few of them slashed; a
        # queue (not yet active) and rows leaving at the next epoch
        gone = rng.random(n) < 0.9 - 0.8 * np.arange(n) / max(n, 1)
        exit_[gone] = rng.integers(10, prev, int(gone.sum()), dtype=np.uint64)
        wdr[gone] = exit_[gone] + np.uint64(256)
        slashed[gone & (rng.random(n) < 0.01)] = True
        # slashed this week: still active, exit and withdrawal ahead, so
        # only the flag sums' unslashed term leaves them out
        fresh = ~gone & (rng.random(n) < 0.01)
        slashed[fresh] = True
        exit_[fresh] = np.uint64(cur + 5)
        wdr[fresh] = np.uint64(cur + 8192)
        act[rng.random(n) < 0.002] = FAR
        exit_[rng.random(n) < 0.002] = np.uint64(cur)
        eff[rng.random(n) < 0.005] = np.uint64(31 * ETH)
    elif composition in ("slashed_exited", "phase0"):
        # the slashed world: runs of slashed rows, exited, withdrawable
        # long after the previous epoch, so eligible and in no active mask
        # (a run in four withdrawable at the next epoch or the one after:
        # the eligibility edge, prev + 1 < withdrawable_epoch)
        for k, start in enumerate(
            rng.integers(0, max(n - 32, 1), max(n // 64, 1))
        ):
            run = slice(int(start), int(start) + 32)
            slashed[run] = True
            exit_[run] = np.uint64(prev - 10)
            wdr[run] = np.uint64(prev + (1, 2, 4096, 4096)[k % 4])
        eff[slashed & (rng.random(n) < 0.5)] = np.uint64(31 * ETH)
    balances = eff + rng.integers(0, ETH, n, dtype=np.uint64)
    if composition == "phase0":
        prev_part = cur_part = inact = None
    else:
        prev_part = (
            np.full(n, flags, np.uint8)
            if flags is not None
            else rng.integers(0, 8, n, dtype=np.uint8)
        )
        cur_part = (
            np.full(n, flags & 0b110, np.uint8)
            if flags is not None
            else rng.integers(0, 8, n, dtype=np.uint8)
        )
        inact = rng.integers(0, 4096, n, dtype=np.uint64)
    return SimpleNamespace(
        np=np, n=n, prev=prev, cur=cur, b_balances=balances, b_eff=eff,
        b_act=act, b_exit=exit_, b_wdr=wdr, slashed=slashed,
        prev_part=prev_part, cur_part=cur_part, b_inact=inact,
    )


@pytest.mark.parametrize("rows", [0, 5, 1 << 13, ODD_ROWS])
@pytest.mark.parametrize(
    "composition", ["all_ones", "interleaved", "slashed_exited", "phase0"]
)
def test_the_native_sweep_is_the_numpy_sequence(composition, rows):
    """Masks byte for byte (``bool``, 0/1) and every scalar equal to the
    numpy sequence's, on every thread count from one to the usable cores
    (and more than a short registry has 64-row blocks for)."""
    ec = _columns(composition, rows)
    want = epoch_vector._scan_numpy(ec)
    want_masks = [ec.active_prev, ec.active_cur, ec.eligible]
    if composition == "all_ones" and rows:
        assert all(mask.all() for mask in want_masks)
    if composition == "slashed_exited" and rows > 5:
        assert want["n_eligible"] > want["n_active_prev"]
    if composition == "interleaved" and rows > 5:
        assert 0 < want["n_active_cur"] < rows
    for threads in range(1, native.usable_cores() + 1):
        masks, got, ran = epoch_scan.epoch_scan(
            np, *epoch_vector._scan_columns(ec), ec.prev, ec.cur,
            epoch_vector._TIMELY_TARGET_FLAG_INDEX, threads,
        )
        assert got == want, threads
        assert ran == min(threads, max(-(-rows // 64), 1))
        for mask, want_mask in zip(masks, want_masks):
            assert mask.dtype == np.bool_
            assert mask.tobytes() == want_mask.tobytes(), threads


@pytest.mark.parametrize(
    "name, spoil",
    [
        ("b_exit", lambda c: np.repeat(c, 2)[::2]),
        ("slashed", lambda c: c.view(np.uint8)),
        ("b_wdr", lambda c: c[:-1]),
        ("cur_part", lambda c: None),
    ],
    ids=["strided", "dtype", "length", "participation_half_given"],
)
def test_the_sweep_refuses_a_column_it_cannot_read_in_place(name, spoil):
    ec = _columns("interleaved", 4099)
    setattr(ec, name, spoil(getattr(ec, name)))
    assert epoch_scan.epoch_scan(
        np, *epoch_vector._scan_columns(ec), ec.prev, ec.cur,
        epoch_vector._TIMELY_TARGET_FLAG_INDEX, 2,
    ) is None


def _boundary_state():
    """A deneb state on the last slot of epoch 2 (96 validators, minimal),
    crossed by the literal path."""
    state, ctx = chain_utils.fresh_genesis_fork("deneb", 96, "minimal")
    slot_processing.process_slots(state, 3 * int(ctx.SLOTS_PER_EPOCH) - 1, ctx)
    return state, ctx


def _set_balance(state, ctx, value):
    state.balances[5] = value


def _set_eff(state, ctx, value):
    state.validators[5].effective_balance = value


def _set_exit(state, ctx, value):
    state.validators[5].exit_epoch = value


def _set_score(state, ctx, value):
    state.inactivity_scores[5] = value - int(ctx.inactivity_score_bias)


# (what is written at row 5, the value, whether ``_sync`` declines)
GUARDS = {
    "balance": (_set_balance, LANE, True),
    "balance_below": (_set_balance, LANE - 1, False),
    "eff": (_set_eff, LANE, True),
    "exit": (_set_exit, LANE, True),
    "exit_below": (_set_exit, LANE - 1, False),
    # the score plus the bias at the u64 ceiling
    "score": (_set_score, U64_MAX, True),
    "score_below": (_set_score, U64_MAX - 1, False),
    # max(eff) * n at 2^64 with max(eff) inside the lane (96 rows)
    "eff_times_n": (_set_eff, 1 << 62, True),
    # the rewards kernel's product: base reward x 64 x increments
    "product": (_set_eff, 1 << 52, True),
    "product_below": (_set_eff, 1 << 44, False),
}


@pytest.mark.parametrize("path", ["native", "numpy"])
@pytest.mark.parametrize("guard", list(GUARDS))
def test_every_lane_guard_declines_through_the_sweep(guard, path, monkeypatch):
    """Each guard of ``_sync`` read off the sweep's scalars: a state outside
    the lane declines with ``u64_guard`` and is left as it was (root, bytes,
    no memo written), exactly as through the numpy sequence; just inside
    the lane the pass engages."""
    write, value, declines = GUARDS[guard]
    state, ctx = _boundary_state()
    write(state, ctx, value)
    if path == "numpy":
        monkeypatch.setattr(epoch_scan, "available", lambda: False)
    root = type(state).hash_tree_root(state)
    wire = type(state).serialize(state)
    memo = state.__dict__.get("_total_active_balance_cache")
    before = {
        name: _counter(name)
        for name in (
            "epoch_vector.fallback.u64_guard", "epoch_vector.scan.threads",
            "epoch_vector.scan.fallback",
        )
    }
    ec = epoch_vector._sync(state, ctx, "deneb")
    moved = {name: _counter(name) - value for name, value in before.items()}
    assert (ec is None) == declines
    assert moved["epoch_vector.fallback.u64_guard"] == int(declines)
    if path == "native":
        assert moved["epoch_vector.scan.threads"] == 1
        assert moved["epoch_vector.scan.fallback"] == 0
    else:
        assert moved["epoch_vector.scan.threads"] == 0
        assert moved["epoch_vector.scan.fallback"] == 1
    assert type(state).hash_tree_root(state) == root
    assert type(state).serialize(state) == wire
    assert state.__dict__.get("_total_active_balance_cache") == memo


def _world(kind: str, seed: int):
    import json

    from benchmark import worlds

    name = {
        "mainnet_registry": "mainnet-deneb-2m",
        "slashed_edge": "mainnet-deneb-1m-slashed",
    }[kind]
    root = Path(__file__).parent.parent
    with open(root / f"benchmark/configs/{name}.json") as handle:
        config = json.load(handle)
    config["validators"] = 1 << 13
    traffic = {"kind": kind, "miss_share": [0.01, 0.03], "chain_epochs": 3}
    if kind == "mainnet_registry":
        traffic["epoch"] = 1
    return worlds.build(config, traffic, seed)


def _cross(state, world, place: int) -> bytes:
    slot = world.target_slot + 32 * place
    if place:
        slot_processing.process_slots(state, slot - 1, world.context)
        state.current_epoch_participation = world.refills[place - 1].tolist()
    slot_processing.process_slots(state, slot, world.context)
    return type(state).hash_tree_root(state)


@pytest.mark.parametrize("kind", ["mainnet_registry", "slashed_edge"])
def test_a_whole_pass_is_the_same_with_the_sweep_forced_off(kind, monkeypatch):
    """Three crossings of the 2m and slashed deployments at 2^13 rows, with
    the fused kernel routed as ``ops.install`` routes it: roots and bytes
    with the sweep on eight threads (its minimum rows per thread lowered)
    equal those with the sweep forced off, and both the literal path's."""
    monkeypatch.setattr(epoch_vector, "EPOCH_VECTOR_MIN_VALIDATORS", 0)
    monkeypatch.setattr(epoch_vector, "SCAN_MIN_ROWS_PER_THREAD", 1 << 10)
    world = _world(kind, 40)
    swept, numpy_side, literal = (world.pre.copy() for _ in range(3))
    threads = min(native.usable_cores(), 8)
    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    try:
        for place in range(3):
            before = {
                name: _counter(name)
                for name in (
                    "epoch_vector.scan.threads", "epoch_vector.scan.fallback",
                    "epoch_vector.fused.jit", "span.epoch_vector.sync.scan.n",
                )
            }
            with spans.recording():
                root = _cross(swept, world, place)
            moved = {name: _counter(name) - value for name, value in before.items()}
            assert moved == {
                "epoch_vector.scan.threads": threads,
                "epoch_vector.scan.fallback": 0,
                "epoch_vector.fused.jit": 1,
                "span.epoch_vector.sync.scan.n": 1,
            }
            with monkeypatch.context() as off:
                off.setattr(epoch_scan, "available", lambda: False)
                fallback = _counter("epoch_vector.scan.fallback")
                assert _cross(numpy_side, world, place) == root
                assert _counter("epoch_vector.scan.fallback") == fallback + 1
            os.environ["ECT_EPOCH_VECTOR"] = "off"
            try:
                assert _cross(literal, world, place) == root
            finally:
                os.environ.pop("ECT_EPOCH_VECTOR", None)
            assert_bit_identical(swept, numpy_side, f"{kind} crossing {place}")
            assert_bit_identical(swept, literal, f"{kind} crossing {place}")
            assert_column_consistency(swept, f"{kind} crossing {place}")
    finally:
        ops.uninstall()


def _strided(column):
    return np.repeat(column, 2)[::2]


def _widened(column):
    return column.astype(np.uint16)


@pytest.mark.parametrize(
    "reason, spoil",
    [
        ("column_layout", ("b_balances", _strided)),
        ("column_layout", ("prev_part", _widened)),
        ("native_unavailable", None),
    ],
    ids=["strided", "dtype", "unavailable"],
)
def test_the_fallback_is_counted_with_its_reason(reason, spoil, monkeypatch):
    """A column the sweep cannot read in place (not C-contiguous, or not
    its dtype), or no native library: the numpy sequence answers with the
    same scalars and masks, ``epoch_vector.scan.fallback`` moves by one,
    no thread is counted, and the reason is a one-shot trace event."""
    state, ctx = _boundary_state()
    ec = epoch_vector._sync(state, ctx, "deneb")
    want, want_masks = ec.scan, (ec.active_prev, ec.active_cur, ec.eligible)
    monkeypatch.setattr(epoch_vector, "_SCAN_FALLBACK_SEEN", set())
    if spoil is None:
        monkeypatch.setattr(epoch_scan, "available", lambda: False)
    else:
        name, how = spoil
        setattr(ec, name, how(getattr(ec, name)))
    fallback = _counter("epoch_vector.scan.fallback")
    threads = _counter("epoch_vector.scan.threads")
    with spans.recording():
        assert epoch_vector._scan(ec) == want
        assert epoch_vector._scan(ec) == want
        doc = spans.RECORDER.chrome_trace()
    assert _counter("epoch_vector.scan.fallback") == fallback + 2
    assert _counter("epoch_vector.scan.threads") == threads
    for mask, want_mask in zip(
        (ec.active_prev, ec.active_cur, ec.eligible), want_masks
    ):
        assert mask.tobytes() == want_mask.tobytes()
    events = [
        e["args"]["reason"] for e in doc["traceEvents"]
        if e.get("ph") == "i" and e.get("name") == "epoch_vector.scan.fallback"
    ]
    assert events == [reason]
