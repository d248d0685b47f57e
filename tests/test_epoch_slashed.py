"""The epoch pass halfway through a correlated slashing: the deployment
``mainnet-deneb-1m-slashed`` of the benchmark (16,384 slashed validators,
exited and not yet withdrawable, 1,024 of them paying ``process_slashings``'
proportional penalty at every boundary) cut to 2^13 rows, with the fused
kernel routed as ``ops.install`` routes it.

The program's roots against the literal spec functions and against the
plain reference (``benchmark/reference/deneb_epoch_registry.py``), the two
counters the deployment added, the eligible rows outside every active mask,
bellatrix's multiplier, and the faults the reference has to call wrong."""

import json
import os
import sys
from math import isqrt
from pathlib import Path

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmark import worlds  # noqa: E402
from benchmark.reference import deneb_epoch_registry  # noqa: E402
from benchmark.tests import faults_slashed  # noqa: E402
from benchmark.worlds import slashed_edge  # noqa: E402
from ethereum_consensus_tpu import ops  # noqa: E402
from ethereum_consensus_tpu.models.deneb import slot_processing  # noqa: E402
from ethereum_consensus_tpu.scenarios.harness import (  # noqa: E402
    assert_bit_identical,
    assert_column_consistency,
)
from ethereum_consensus_tpu.telemetry import metrics, spans  # noqa: E402

ROOT = Path(__file__).parent.parent
SMALL = 1 << 13
SCALE = SMALL / (1 << 20)
CHAIN = 4
ETH = 10**9
MISS_SHARE = [0.01, 0.03]
COUNTERS = ("slashings.penalised", "rows_eligible_inactive", "fused.jit", "epochs",
            "eff.changed")

_WORLDS: dict = {}


def configuration(name: str) -> dict:
    with open(ROOT / f"benchmark/configs/{name}.json") as handle:
        config = json.load(handle)
    config["validators"] = SMALL
    return config


def slashed_world(seed: int, chain: int = CHAIN):
    """The deployment at 2^13 rows (its counts scaled, its slot and epochs
    as written)."""
    key = ("slashed", seed, chain)
    if key not in _WORLDS:
        _WORLDS[key] = worlds.build(
            configuration("mainnet-deneb-1m-slashed"),
            {"kind": "slashed_edge", "miss_share": MISS_SHARE, "chain_epochs": chain},
            seed,
        )
    return _WORLDS[key]


def happy_world(seed: int):
    """``mainnet-deneb-1m`` at 2^13 rows: nobody slashed."""
    key = ("happy", seed)
    if key not in _WORLDS:
        _WORLDS[key] = worlds.build(
            configuration("mainnet-deneb-1m"),
            {"kind": "epoch_edge", "epoch": 1, "miss_share": MISS_SHARE,
             "chain_epochs": 2},
            seed,
        )
    return _WORLDS[key]


@pytest.fixture
def fused_route():
    """``ops.install`` with the sweeps gate open at this size: the pass runs
    inactivity + rewards as the jitted fused kernel."""
    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    try:
        yield
    finally:
        ops.uninstall()


def counters() -> dict:
    return {name: metrics.counter(f"epoch_vector.{name}").value() for name in COUNTERS}


def cross(state, world, place: int) -> bytes:
    """The epoch_boundary driver's step: the refill of the epoch just ended, then the
    boundary and the root."""
    slot = world.target_slot + 32 * place
    if place:
        slot_processing.process_slots(state, slot - 1, world.context)
        state.current_epoch_participation = world.refills[place - 1].tolist()
    slot_processing.process_slots(state, slot, world.context)
    return type(state).hash_tree_root(state)


def literal_cross(state, world, place: int) -> bytes:
    os.environ["ECT_EPOCH_VECTOR"] = "off"
    try:
        return cross(state, world, place)
    finally:
        os.environ.pop("ECT_EPOCH_VECTOR", None)


def test_the_world_is_the_files_deployment_cut_to_size():
    config = configuration("mainnet-deneb-1m-slashed")
    assert slashed_edge.counts(config) == (128, 8, 2)  # 16,384, 1,024, 32 scaled
    world = slashed_world(5)
    pre = world.pre
    assert int(pre.slot) == 134431 and len(pre.validators) == SMALL
    slashed = [i for i, v in enumerate(pre.validators) if v.slashed]
    assert len(slashed) == 128 and 0 not in slashed and SMALL - 1 not in slashed
    withdrawable = sorted(int(pre.validators[i].withdrawable_epoch) for i in slashed)
    assert withdrawable == sorted(list(range(104 + 8192, 120 + 8192)) * 8)
    assert all(int(pre.validators[i].exit_epoch) < 4199 for i in slashed)
    assert sum(int(x) for x in pre.slashings) == 128 * 32 * ETH
    # runs of two adjacent rows (two runs may touch)
    rows = np.asarray(sorted(slashed))
    breaks = np.nonzero(np.diff(rows) > 1)[0]
    lengths = np.diff(np.concatenate([[-1], breaks, [len(rows) - 1]]))
    assert (lengths % 2 == 0).all() and len(lengths) > 32
    # an exited slashed row carries no flag
    for flags in [pre.previous_epoch_participation, pre.current_epoch_participation,
                  *world.refills]:
        assert not any(int(flags[i]) for i in slashed)


def test_the_columnar_pass_equals_the_literal_spec_functions(fused_route):
    """Balances, effective balances and root, bytes included, against
    ``models/altair``'s and ``models/deneb``'s own stage list on the same
    states, at four crossings that each pay the penalty."""
    world = slashed_world(7)
    columnar, literal = world.pre.copy(), world.pre.copy()
    before = counters()
    for place in range(CHAIN):
        cross(columnar, world, place)
        literal_cross(literal, world, place)
        assert_bit_identical(columnar, literal, f"slashed crossing {place}")
        assert_column_consistency(columnar, f"slashed crossing {place}")
        assert [int(v.effective_balance) for v in columnar.validators] == [
            int(v.effective_balance) for v in literal.validators
        ]
    moved = {name: value - before[name] for name, value in counters().items()}
    assert moved["epochs"] == moved["fused.jit"] == CHAIN
    assert moved["slashings.penalised"] == CHAIN * 8


@pytest.mark.parametrize("seed", [39, (1 << 31) + 39])
def test_the_chain_equals_the_registry_reference(seed, fused_route):
    world = slashed_world(seed)
    state = world.pre.copy()
    served = [cross(state, world, place) for place in range(CHAIN)]
    want = deneb_epoch_registry.chain_roots(world.pre, world.target_slot, world.refills)
    assert served == want and len(set(served)) == CHAIN
    # finality holds: the chain's last crossing finalized the epoch before it
    assert int(state.finalized_checkpoint.epoch) == 4200 + CHAIN - 2
    assert_column_consistency(state, "after four slashed crossings")


def test_the_counters_read_the_deployment_and_nothing_elsewhere(fused_route):
    """1,024 and 16,384 scaled, at every boundary of the slashed chain; the
    ``epoch_vector.slashings`` span's event carries the hits; neither counter
    moves on the 1m world."""
    world = slashed_world(11)
    state = world.pre.copy()
    for place in range(CHAIN):
        before = counters()
        with spans.recording():
            cross(state, world, place)
            hits = [
                r.fields for r in spans.RECORDER.records()
                if r.name == "epoch_vector.slashings"
            ]
        moved = {name: value - before[name] for name, value in counters().items()}
        assert moved["slashings.penalised"] == 1024 * SCALE == 8
        assert moved["rows_eligible_inactive"] == 16384 * SCALE == 128
        assert hits == [{"hits": 8}]
    happy = happy_world(11)
    state = happy.pre.copy()
    before = counters()
    with spans.recording():
        cross(state, happy, 0)
        hits = [
            r.fields for r in spans.RECORDER.records()
            if r.name == "epoch_vector.slashings"
        ]
    moved = {name: value - before[name] for name, value in counters().items()}
    assert moved["epochs"] == 1
    assert moved["slashings.penalised"] == moved["rows_eligible_inactive"] == 0
    assert hits == [{"hits": 0}]


def _base_reward(state, index: int) -> int:
    active = [
        int(v.effective_balance) for v in state.validators
        if int(v.activation_epoch) <= int(state.slot) // 32 < int(v.exit_epoch)
    ]
    per_increment = ETH * 64 // isqrt(sum(active))
    return int(state.validators[index].effective_balance) // ETH * per_increment


def test_an_exited_slashed_row_is_eligible_and_pays_bellatrixs_penalty(fused_route):
    """At the first crossing: a slashed row of a later epoch is not active
    and still pays the source and target penalties; a row at the halfway
    point pays them and (effective_balance // 10^9) x 3 x slashings // total
    increments, 1 ETH here, where altair's multiplier 2 would take 0 from a
    row at 31 ETH."""
    world = slashed_world(13)
    pre = world.pre
    epoch = int(pre.slot) // 32
    slashed = [i for i, v in enumerate(pre.validators) if v.slashed]
    halfway = [i for i in slashed
               if int(pre.validators[i].withdrawable_epoch) == epoch + 4096]
    later = [i for i in slashed if i not in halfway]
    assert len(halfway) == 8 and len(later) == 120
    assert all(int(pre.validators[i].exit_epoch) <= epoch - 1 for i in slashed)
    state = pre.copy()
    cross(state, world, 0)

    def drop(index: int) -> int:
        return int(pre.balances[index]) - int(state.balances[index])

    flag_penalties = {
        i: _base_reward(pre, i) * 14 // 64 + _base_reward(pre, i) * 26 // 64
        for i in slashed
    }
    assert all(flag_penalties[i] > 0 and drop(i) == flag_penalties[i] for i in later)
    total = sum(
        int(v.effective_balance) for v in pre.validators
        if int(v.activation_epoch) <= epoch < int(v.exit_epoch)
    )
    assert total == (SMALL - 128) * 32 * ETH
    slashings = sum(int(x) for x in pre.slashings)

    def penalty(index: int, multiplier: int) -> int:
        increments = int(pre.validators[index].effective_balance) // ETH
        return increments * min(slashings * multiplier, total) // total * ETH

    for i in halfway:
        assert drop(i) == flag_penalties[i] + penalty(i, 3) == flag_penalties[i] + ETH
    at_31 = [i for i in halfway if int(pre.validators[i].effective_balance) == 31 * ETH]
    assert at_31 and all(penalty(i, 2) == 0 for i in at_31)


PLANTS = faults_slashed.FAULTS + [faults_slashed.CONTROL]


@pytest.mark.parametrize("plant", PLANTS, ids=[p.__name__ for p in PLANTS])
def test_the_reference_calls_a_wrong_slashing_wrong(plant, fused_route, monkeypatch):
    """Each fault, and the control, planted under the served path: the
    sound path's root is the reference's, the faulty one's is not, at both
    crossings."""
    world = slashed_world(21, chain=2)
    want = deneb_epoch_registry.chain_roots(world.pre, world.target_slot, world.refills)
    sound = world.pre.copy()
    assert [cross(sound, world, place) for place in (0, 1)] == want
    plant(monkeypatch)
    faulty = world.pre.copy()
    served = [cross(faulty, world, place) for place in (0, 1)]
    assert served[0] != want[0] and served[1] != want[1]
