"""The sync committee's sampler (``models/epoch_vector.py
_sample_sync_committee``) and the native swap-or-not pass under it
(``native/sha256_merkle.cpp ec_shuffle_positions``).

The shuffled positions against the literal ``compute_shuffled_index``
through the 8-lane routine, the scalar tail and a second tile; the committee
against the literal ``get_next_sync_committee_indices`` on registries where
nobody is refused, where a quarter of the candidates are refused (a second
block is drawn), and where fewer rows are active than a committee holds (the
candidates wrap); the literal helper answering, counted, where the native
library is absent; and a whole period crossing through
``process_epoch_columnar`` against the literal stage list."""

import hashlib
import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmark import worlds  # noqa: E402
from benchmark.reference import deneb_epoch_period  # noqa: E402
from benchmark.worlds import keys  # noqa: E402
from ethereum_consensus_tpu import native, ops  # noqa: E402
from ethereum_consensus_tpu.config.context import Context  # noqa: E402
from ethereum_consensus_tpu.domains import DomainType  # noqa: E402
from ethereum_consensus_tpu.models import epoch_vector  # noqa: E402
from ethereum_consensus_tpu.models.altair.helpers import (  # noqa: E402
    get_next_sync_committee_indices,
)
from ethereum_consensus_tpu.models.deneb import slot_processing  # noqa: E402
from ethereum_consensus_tpu.models.phase0.helpers import (  # noqa: E402
    compute_shuffled_index,
    get_seed,
)
from ethereum_consensus_tpu.primitives import FAR_FUTURE_EPOCH  # noqa: E402
from ethereum_consensus_tpu.scenarios.harness import assert_bit_identical  # noqa: E402
from ethereum_consensus_tpu.telemetry import metrics, spans  # noqa: E402
from ethereum_consensus_tpu.utils import trace  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no C++ toolchain: no native shuffle"
)

ROOT = Path(__file__).parent.parent
MAINNET = Context.for_mainnet()
ETH = 10**9
# the epoch the sampled committee serves: a period's first
SERVED = 4352
COUNTERS = ("batched", "candidates", "fallback")


def moved(before: dict) -> dict:
    return {name: value - before[name] for name, value in counts().items()}


def counts() -> dict:
    return {
        name: metrics.counter(f"epoch_vector.sync_committee.{name}").value()
        for name in COUNTERS
    }


# -- the native pass -------------------------------------------------------------------

COUNTS = [1, 2, 255, 256, 257, 1_000_003, 1 << 20, (1 << 21) + 5, 1 << 40]


# 5 lanes: the scalar routine alone; 8: one group of 8; 300: a tile of 256
# (32 groups of 8), then a tile of 44 (five groups and a scalar tail of 4)
@pytest.mark.parametrize("lanes", [5, 8, 300])
@pytest.mark.parametrize("count", COUNTS)
def test_the_native_pass_is_compute_shuffled_index(count, lanes):
    rng = np.random.default_rng(count)
    seed = hashlib.sha256(count.to_bytes(8, "little")).digest()
    positions = rng.integers(0, count, lanes, dtype=np.uint64)
    positions[:2] = [0, count - 1]
    shuffled, used = native.shuffle_positions(
        seed, count, int(MAINNET.SHUFFLE_ROUND_COUNT), positions
    )
    assert used in (1, 8)
    assert shuffled.tolist() == [
        compute_shuffled_index(int(i), count, seed, MAINNET) for i in positions
    ]


REFUSED = {
    "no_rows": dict(count=0),
    "past_the_largest_list": dict(count=(1 << 40) + 1),
    "a_position_not_below_count": dict(positions=np.array([0, 7], np.uint64)),
    "a_short_seed": dict(seed=b"\x01" * 31),
    "past_the_round_byte": dict(rounds=257),
    "not_uint64": dict(positions=np.array([0, 1], np.int64)),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_the_native_pass_refuses_what_the_shuffle_cannot_take(name):
    args = dict(seed=b"\x01" * 32, count=7, rounds=90,
                positions=np.array([0, 6], np.uint64))
    args.update(REFUSED[name])
    with pytest.raises(ValueError):
        native.shuffle_positions(
            args["seed"], args["count"], args["rounds"], args["positions"]
        )


# -- the sampler against the literal helper -----------------------------------------------


def registry(balances, active):
    """A state as far as the literal sampler reads it (the slot, the mixes,
    each row's activity and effective balance), the columns as far as the
    columnar one reads them, and the committee's seed."""
    validators = [
        SimpleNamespace(
            activation_epoch=0 if live else FAR_FUTURE_EPOCH,
            exit_epoch=FAR_FUTURE_EPOCH,
            effective_balance=int(balance),
        )
        for balance, live in zip(balances, active)
    ]
    state = SimpleNamespace(
        slot=SERVED * int(MAINNET.SLOTS_PER_EPOCH) - 1,
        randao_mixes=[hashlib.sha256(b"mix %d" % k).digest() for k in range(64)],
        validators=validators,
    )
    ec = SimpleNamespace(np=np, context=MAINNET, eff=np.asarray(balances, np.uint64))
    seed = get_seed(state, SERVED, DomainType.SYNC_COMMITTEE, MAINNET)
    return state, ec, np.nonzero(active)[0], seed


def _registries():
    rng = np.random.default_rng(42)
    spread = rng.integers(16, 33, 4096, dtype=np.uint64) * np.uint64(ETH)
    few = rng.random(700) < 0.43
    return {
        # every row at 32 ETH: none refused, one block
        "all_at_32_eth": (np.full(4096, 32 * ETH, np.uint64), np.ones(4096, bool), 1),
        # 16-32 ETH: a quarter of the candidates refused, a second block
        "balances_16_to_32_eth": (spread, np.ones(4096, bool), 2),
        # every row 1 gwei under what a random byte of 128 asks: refused at
        # 128 and above, accepted below, about half of them (not a balance
        # the hysteresis gives; it holds the acceptance test to exactness)
        "balances_on_a_threshold": (
            np.full(4096, 32 * ETH * 128 // 255, np.uint64), np.ones(4096, bool), 2,
        ),
        # 300-odd active rows among 700: i % count wraps within a block
        "fewer_active_than_a_committee": (
            rng.integers(16, 33, 700, dtype=np.uint64) * np.uint64(ETH), few, 2,
        ),
    }


REGISTRIES = _registries()


@pytest.mark.parametrize("name", sorted(REGISTRIES))
def test_the_sampler_draws_the_literal_committee(name):
    balances, active, blocks = REGISTRIES[name]
    state, ec, rows, seed = registry(balances, active)
    want = get_next_sync_committee_indices(state, MAINNET)
    before = counts()
    with spans.recording():
        with trace.span("epoch_vector.sync_committee.sample"):
            got = epoch_vector._sample_sync_committee(ec, rows, seed)
        (record,) = [
            r for r in spans.RECORDER.records()
            if r.name == "epoch_vector.sync_committee.sample"
        ]
    assert got == want and len(got) == int(MAINNET.SYNC_COMMITTEE_SIZE)
    drawn = moved(before)
    assert drawn["batched"] == 1 and drawn["fallback"] == 0
    assert drawn["candidates"] == 512 * blocks
    assert record.fields == {"candidates": 512 * blocks, "blocks": blocks}
    if name == "fewer_active_than_a_committee":
        assert len(rows) < 512 and len(set(got)) < len(got)


# -- the sampler inside the pass ------------------------------------------------------------

_WORLDS: dict = {}


def period_world(seed: int):
    """``mainnet-deneb-1m-period`` cut to 2^13 rows: the last slot of epoch
    4,351, so the crossing rotates the sync committee."""
    if seed not in _WORLDS:
        with open(ROOT / "benchmark/configs/mainnet-deneb-1m-period.json") as handle:
            config = json.load(handle)
        config["validators"] = 1 << 13
        _WORLDS[seed] = worlds.build(
            config,
            {"kind": "period_edge", "miss_share": [0.01, 0.03], "chain_epochs": 1},
            seed,
        )
    return _WORLDS[seed]


@pytest.fixture
def fused_route():
    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    try:
        yield
    finally:
        ops.uninstall()


def cross(state, world) -> bytes:
    slot_processing.process_slots(state, world.target_slot, world.context)
    return type(state).hash_tree_root(state)


def literal_cross(state, world) -> bytes:
    os.environ["ECT_EPOCH_VECTOR"] = "off"
    try:
        return cross(state, world)
    finally:
        os.environ.pop("ECT_EPOCH_VECTOR", None)


def committee(c) -> tuple:
    return [bytes(k) for k in c.public_keys], bytes(c.aggregate_public_key)


@pytest.mark.parametrize("library", ["loaded", "absent"])
def test_a_period_crossing_rotates_to_the_literal_committee(
    library, fused_route, monkeypatch
):
    """The pass's committee, root and bytes against the literal stage
    list's; the native sampler draws it where the library is loaded, the
    literal helper, counted, where it is not."""
    world = period_world(29)
    columnar, literal = world.pre.copy(), world.pre.copy()
    if library == "absent":
        monkeypatch.setattr(native, "available", lambda: False)
    before = counts()
    assert cross(columnar, world) == literal_cross(literal, world)
    assert_bit_identical(columnar, literal, "period crossing")
    assert committee(columnar.next_sync_committee) == committee(
        literal.next_sync_committee
    )
    assert committee(columnar.next_sync_committee) != committee(
        world.pre.next_sync_committee
    )
    if library == "loaded":
        assert moved(before) == {"batched": 1, "candidates": 512, "fallback": 0}
    else:
        assert moved(before) == {"batched": 0, "candidates": 0, "fallback": 1}


def test_the_pass_samples_the_effective_balances_after_its_hysteresis(fused_route):
    """A third of the rows hold 20.5 ETH before the crossing, so the pass's
    hysteresis steps their effective balances from 32 to 20 ETH. The sampler
    runs after it and has to read the stepped-down balances: it refuses
    some of those rows and draws a second block, and the committee, root
    and bytes are the literal stage list's. Sampled against the balances
    before the hysteresis, the committee would differ."""
    world = period_world(29)
    pre = world.pre.copy()
    lowered = np.arange(0, len(pre.validators), 3)
    for i in lowered.tolist():
        pre.balances[i] = 20 * ETH + ETH // 2
    seed = get_seed(pre, SERVED, DomainType.SYNC_COMMITTEE, MAINNET)
    active = np.array([
        i for i, v in enumerate(pre.validators)
        if int(v.activation_epoch) <= SERVED < int(v.exit_epoch)
    ])
    before_hysteresis = np.array(
        [int(v.effective_balance) for v in pre.validators], np.uint64
    )
    assert (before_hysteresis == 32 * ETH).all()
    after_hysteresis = before_hysteresis.copy()
    after_hysteresis[lowered] = 20 * ETH
    want = deneb_epoch_period.sync_committee_indices(seed, active, after_hysteresis)
    assert want != deneb_epoch_period.sync_committee_indices(
        seed, active, before_hysteresis
    )
    keys.realize_validator_keys(pre, want)
    columnar, literal = pre.copy(), pre.copy()
    before = counts()
    assert cross(columnar, world) == literal_cross(literal, world)
    assert_bit_identical(columnar, literal, "period crossing, rows stepped down")
    assert [int(v.effective_balance) for v in columnar.validators] == (
        after_hysteresis.tolist()
    )
    assert committee(columnar.next_sync_committee) == committee(
        literal.next_sync_committee
    )
    assert [bytes(k) for k in columnar.next_sync_committee.public_keys] == [
        keys.public_key_bytes(i) for i in want
    ]
    assert moved(before) == {"batched": 1, "candidates": 1024, "fallback": 0}
