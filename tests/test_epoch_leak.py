"""The epoch pass on a chain that does not finalize: the deployment
``mainnet-deneb-1m-leak`` of the benchmark (35 % of the registry offline for
1,024 epochs, scores of 4,096, balances bled, effective balances stepping
down at every boundary) cut to 2^13 rows, with the fused kernel routed as
``ops.install`` routes it.

The program's roots against the plain reference
(``benchmark/reference/deneb_epoch_leak.py``) and against the literal spec
functions, the three counters the deployment added, entering and leaving a
leak without a compile, and the faults the reference has to call wrong."""

import json
import os
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmark import worlds  # noqa: E402
from benchmark.reference import deneb_epoch_leak  # noqa: E402
from benchmark.tests import faults_leak  # noqa: E402
from benchmark.worlds import epoch_edge  # noqa: E402
from ethereum_consensus_tpu import ops  # noqa: E402
from ethereum_consensus_tpu.models import epoch_vector  # noqa: E402
from ethereum_consensus_tpu.models.deneb import slot_processing  # noqa: E402
from ethereum_consensus_tpu.scenarios.harness import (  # noqa: E402
    assert_bit_identical,
    assert_column_consistency,
)
from ethereum_consensus_tpu.telemetry import metrics, spans  # noqa: E402

ROOT = Path(__file__).parent.parent
SMALL = 1 << 13
CHAIN = 16
MISS_SHARE = [0.01, 0.03]
COUNTERS = ("leak.epochs", "scores.changed", "eff.changed", "fused.jit", "epochs")

_WORLDS: dict = {}


def leak_world(seed: int, chain: int = CHAIN, **finality):
    """The deployment at 2^13 rows (its offline count scaled, its epochs and
    scores as written), ``finality`` overriding keys of that group."""
    key = (seed, chain, tuple(sorted(finality.items())))
    if key not in _WORLDS:
        with open(ROOT / "benchmark/configs/mainnet-deneb-1m-leak.json") as handle:
            config = json.load(handle)
        config["validators"] = SMALL
        steps = finality.pop("online_walk_steps", None)
        config["finality"].update(finality)
        if steps is not None:
            config["finality"]["online_walk"]["steps"] = steps
        _WORLDS[key] = worlds.build(
            config,
            {"kind": "leak_edge", "miss_share": MISS_SHARE, "chain_epochs": chain},
            seed,
        )
    return _WORLDS[key]


def before_the_leak(seed: int, chain: int):
    """The same registry four epochs after finality: no score, no balance
    bled, the offline rows offline from now on."""
    return leak_world(
        seed, chain, finalized_epoch=1100 - 1 - 4, offline_epochs=0,
        online_walk_steps=0,
    )


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
    """The driver's step: the refill of the epoch just ended, then the
    boundary and the root."""
    slot = world.target_slot + 32 * place
    if place:
        slot_processing.process_slots(state, slot - 1, world.context)
        state.current_epoch_participation = world.refills[place - 1].tolist()
    slot_processing.process_slots(state, slot, world.context)
    return type(state).hash_tree_root(state)


def fused_programs() -> int:
    return epoch_vector.jitted_kernels()["fused_epoch"].__wrapped__._cache_size()


@pytest.mark.parametrize("seed", [32, (1 << 31) + 32])
def test_sixteen_leaking_roots_equal_the_plain_references(seed, fused_route):
    world = leak_world(seed)
    assert int(world.pre.slot) == 35231 and len(world.refills) == CHAIN - 1
    before = counters()
    state = world.pre.copy()
    served = [cross(state, world, place) for place in range(CHAIN)]
    moved = {name: value - before[name] for name, value in counters().items()}
    counts = {}
    want = deneb_epoch_leak.chain_roots(
        world.pre, world.target_slot, world.refills, counts
    )
    assert served == want and len(set(served)) == CHAIN
    assert counts["leaking"] == [True] * CHAIN
    # nothing was justified: the checkpoints stand where the world put them
    assert int(state.finalized_checkpoint.epoch) == 75
    assert int(state.current_justified_checkpoint.epoch) == 75
    assert not any(state.justification_bits)
    # every pass took the fused kernel, and every pass was a leaking one
    assert moved["epochs"] == moved["fused.jit"] == moved["leak.epochs"] == CHAIN
    assert_column_consistency(state, "after sixteen leaking crossings")


def test_the_columnar_pass_equals_the_literal_spec_functions(fused_route):
    """Scores, balances, effective balances and root, bytes included, against
    ``models/altair`` and ``models/deneb``'s own stage list on the same
    states."""
    world = leak_world(7, chain=4)
    columnar, literal = world.pre.copy(), world.pre.copy()
    for place in range(4):
        cross(columnar, world, place)
        os.environ["ECT_EPOCH_VECTOR"] = "off"
        try:
            cross(literal, world, place)
        finally:
            os.environ.pop("ECT_EPOCH_VECTOR", None)
        assert_bit_identical(columnar, literal, f"leaking crossing {place}")
        assert_column_consistency(columnar, f"leaking crossing {place}")
        assert list(columnar.inactivity_scores) == list(literal.inactivity_scores)
        assert [int(v.effective_balance) for v in columnar.validators] == [
            int(v.effective_balance) for v in literal.validators
        ]
    offline = np.asarray(list(world.pre.inactivity_scores)) == 4096
    scores = np.asarray(list(columnar.inactivity_scores))
    assert offline.sum() == 367001 * SMALL // (1 << 20)
    assert (scores[offline] == 4096 + 4 * 4).all()


def test_every_boundary_steps_balances_down_and_the_counters_say_so(fused_route):
    """The three counters and the commit's event read what the reference
    counts, boundary by boundary."""
    world = leak_world(32)
    counts = {}
    deneb_epoch_leak.chain_roots(world.pre, world.target_slot, world.refills, counts)
    assert min(counts["eff_changed"]) >= 1  # somebody steps down every time
    state = world.pre.copy()
    for place in range(CHAIN):
        effective_before = [int(v.effective_balance) for v in state.validators]
        before = counters()
        with spans.recording():
            cross(state, world, place)
            commit = [
                r.fields for r in spans.RECORDER.records()
                if r.name == "epoch_vector.commit"
            ]
            children = {
                r.name for r in spans.RECORDER.records()
                if r.name.startswith("epoch_vector.commit.")
            }
        moved = {name: value - before[name] for name, value in counters().items()}
        assert moved["leak.epochs"] == 1
        assert moved["scores.changed"] == counts["scores_changed"][place]
        assert moved["eff.changed"] == counts["eff_changed"][place]
        stepped = [
            (was, int(v.effective_balance))
            for was, v in zip(effective_before, state.validators)
            if was != int(v.effective_balance)
        ]
        assert len(stepped) == moved["eff.changed"]
        assert all(now == was - 10**9 for was, now in stepped)  # one step, down
        assert commit == [{
            "validators": SMALL, "writes": moved["eff.changed"],
            "scores_changed": moved["scores.changed"],
            "eff_changed": moved["eff.changed"],
        }]
        assert children == {
            "epoch_vector.commit.balances", "epoch_vector.commit.scores",
            "epoch_vector.commit.validators",
        }
    # the offline rows and the online rows that moved: over a third, every time
    assert min(counts["scores_changed"]) > SMALL * 0.35


def column_list_counters() -> dict:
    return {
        name: metrics.counter(f"ssz.column_list.{name}").value()
        for name in ("stores", "left", "boxed_rows")
    }


def test_the_two_store_commit_boxes_nothing(fused_route):
    """A leaking boundary stores two registry-sized lists and, here, steps
    some hundreds of validators down: both lists turn column-primary
    (ssz/column_list.py), two stores a boundary, no row boxed at any of
    them nor by the writes between them, and the roots are the literal
    spec functions'."""
    from ethereum_consensus_tpu.ssz.column_list import ColumnList

    world = leak_world(7, chain=4)
    pre = world.pre.copy()
    online = [
        i for i, (score, v) in enumerate(zip(pre.inactivity_scores, pre.validators))
        if score < 4096 and int(v.effective_balance) == 32 * 10**9
    ]
    for i in online[:400]:  # under the downward threshold of 31.75 ETH
        pre.balances[i] = 31_700_000_000 + i
    columnar, literal = pre.copy(), pre.copy()
    for place in range(4):
        before, moved_before = column_list_counters(), counters()
        cross(columnar, world, place)
        after = column_list_counters()
        assert after == {**before, "stores": before["stores"] + 2}, place
        assert columnar.balances.__class__ is ColumnList
        assert columnar.inactivity_scores.__class__ is ColumnList
        stepped = counters()["eff.changed"] - moved_before["eff.changed"]
        assert stepped >= (400 if place == 0 else 1)
        os.environ["ECT_EPOCH_VECTOR"] = "off"
        try:
            cross(literal, world, place)
        finally:
            os.environ.pop("ECT_EPOCH_VECTOR", None)
        assert column_list_counters() == after  # the literal side: no store
        assert_bit_identical(columnar, literal, f"leaking crossing {place}")
        assert_column_consistency(columnar, f"leaking crossing {place}")
        # what a block does between two boundaries, on both sides
        for state in (columnar, literal):
            state.balances[place] = int(state.balances[place]) + 1
            state.inactivity_scores[place + 9] = 5
        assert column_list_counters() == after
    assert list(columnar.balances) == list(literal.balances)
    assert list(columnar.inactivity_scores) == list(literal.inactivity_scores)


def test_entering_a_leak_compiles_nothing_at_the_boundary(fused_route):
    """A chain that starts four epochs after finality: its first boundary
    still pays flag rewards and recovers scores, its second is a leaking
    one, and it runs the program the first compiled."""
    world = before_the_leak(11, chain=4)
    counts = {}
    want = deneb_epoch_leak.chain_roots(
        world.pre, world.target_slot, world.refills, counts
    )
    assert counts["leaking"] == [False, True, True, True]
    state = world.pre.copy()
    served = [cross(state, world, 0)]
    programs = fused_programs()
    before = counters()
    served += [cross(state, world, place) for place in (1, 2, 3)]
    assert served == want
    assert fused_programs() == programs  # no further compile: one program
    moved = {name: value - before[name] for name, value in counters().items()}
    assert moved["leak.epochs"] == moved["fused.jit"] == 3


def test_leaving_a_leak_compiles_nothing_at_the_boundary(fused_route):
    """Every row comes back online: the first boundary is still a leaking
    one and justifies, the second finalizes, and from then on the recovery
    rate of 16 applies again, in the program the leak compiled."""
    world = leak_world(13, chain=4)
    count = len(world.pre.validators)

    def everybody(stream):
        return epoch_edge.participation(13, stream, count, *MISS_SHARE)[0]

    pre = world.pre.copy()
    pre.previous_epoch_participation = everybody("back-previous").tolist()
    pre.current_epoch_participation = everybody("back-current").tolist()
    refills = [everybody(f"back-{k}") for k in range(3)]
    counts = {}
    want = deneb_epoch_leak.chain_roots(pre, world.target_slot, refills, counts)
    assert counts["leaking"] == [True, False, False, False]

    back = epoch_edge.EpochEdgeWorld(
        fork=world.fork, context=world.context, pre=pre,
        target_slot=world.target_slot, miss_shares={}, refills=refills,
    )
    state = pre.copy()
    served = [cross(state, back, 0)]
    programs = fused_programs()
    served += [cross(state, back, place) for place in (1, 2, 3)]
    assert served == want
    assert fused_programs() == programs
    assert int(state.finalized_checkpoint.epoch) == 1102
    # a row that was offline and hits every target since: 4,096 - 1 in the
    # leak's last epoch, then 17 off in each of the three that finalize
    was_offline = np.asarray(list(pre.inactivity_scores)) == 4096
    scores = np.asarray(list(state.inactivity_scores))[was_offline]
    assert (scores == 4096 - 1 - 3 * 17).mean() > 0.8


PLANTS = faults_leak.FAULTS + [faults_leak.CONTROL]


@pytest.mark.parametrize("plant", PLANTS, ids=[p.__name__ for p in PLANTS])
def test_the_reference_calls_a_wrong_leak_wrong(plant, fused_route, monkeypatch):
    """Each fault, and the control, planted under the served path: the
    sound path's root is the reference's, the faulty one's is not."""
    world = leak_world(21, chain=2)
    want = deneb_epoch_leak.chain_roots(world.pre, world.target_slot, world.refills)
    sound = world.pre.copy()
    assert [cross(sound, world, place) for place in (0, 1)] == want
    plant(monkeypatch)
    faulty = world.pre.copy()
    served = [cross(faulty, world, place) for place in (0, 1)]
    assert served[0] != want[0] and served[1] != want[1]
