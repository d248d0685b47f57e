"""The registry as mainnet holds it (``worlds/mainnet_registry.py``) and its
plain reference (``reference/deneb_epoch_registry.py``): the generator
follows the configuration file and the seed, the reference agrees with the
served path over a chain in which the churn really runs, it is independent
of the program, and a fault in the churn is not correct."""

import json
import os
import time

import numpy as np
import pytest

from benchmark import harness, worlds
from benchmark.reference import deneb_epoch, deneb_epoch_registry
from benchmark.tests.faults_registry import (
    dropped_activation,
    exited_row_rewarded,
    queue_in_index_order,
)
from benchmark.tests.rehearsal import ROOT
from benchmark.worlds import mainnet_registry

CELL = "deneb-2m.epoch-boundary"
FAR = (1 << 64) - 1
SMALL = 1 << 13  # the table's groups times 2^-8
WORLD = {"kind": "mainnet_registry", "epoch": 1, "miss_share": [0.01, 0.03],
         "chain_epochs": 7}


def configuration(validators=None) -> dict:
    with open(os.path.join(ROOT, "benchmark/configs/mainnet-deneb-2m.json")) as handle:
        config = json.load(handle)
    if validators:
        config["validators"] = validators
    return config


def test_the_configuration_file_is_the_issues_table():
    config = configuration()
    reg = config["registry"]
    assert config["validators"] == reg["at_validators"] == 1 << 21
    assert config["reduced"] == {} and reg["active"] == 1 << 20
    assert (reg["slashed"], reg["queued"], reg["fresh_deposits"]) == (512, 2048, 64)
    assert reg["exiting"] == {"per_epoch": 16, "epochs": [2, 17]}
    assert reg["activated"] == {"per_epoch": 8, "epochs": [2, 5]}
    # every group of the composition is under `assumed` with its reason
    assert {"registry.active", "registry.exited", "registry.interleaving",
            "registry.exiting", "registry.activated", "registry.queued",
            "registry.queued_late_every", "registry.fresh_deposits",
            "registry.tail"} <= set(config["assumed"])
    counts = mainnet_registry._counts(config)
    tail = 8 * 4 + 2048 + 64
    assert (1 << 21) - counts["active"] - tail == 1_046_432  # exited, withdrawn


@pytest.mark.parametrize("seed", [1, 77, (1 << 31) + 5])
def test_the_composition_is_the_files_at_every_seed(seed):
    config = configuration(SMALL)
    made = mainnet_registry.composition(config, seed)
    c, groups = made.columns, made.groups
    sizes = {name: len(rows) for name, rows in groups.items()}
    assert sizes == {
        "active": 4096, "exited": 4096 - 14, "slashed": 2, "exiting": 16,
        "low_balance": 20, "activated": 4, "queued": 8, "queued_late": 1,
        "fresh_deposits": 2,
    }
    active_at_1 = (c["activation_epoch"] <= 1) & (c["exit_epoch"] > 1)
    assert int(active_at_1.sum()) == 4096
    assert np.array_equal(np.nonzero(active_at_1)[0], groups["active"])
    # the deposit tail is the last indices, in deposit order
    tail = np.concatenate([groups["activated"], groups["queued"], groups["fresh_deposits"]])
    assert np.array_equal(tail, np.arange(SMALL - 14, SMALL))
    exited = groups["exited"]
    for name in ("effective_balance", "exit_epoch", "withdrawable_epoch",
                 "activation_epoch"):
        assert not c[name][exited].any()
    assert not made.balances[exited].any()
    assert set(groups["slashed"]) <= set(exited) and c["slashed"].sum() == 2
    # one exit an epoch for each of the epochs a chain of 16 enters
    assert sorted(c["exit_epoch"][groups["exiting"]].tolist()) == list(range(2, 18))
    assert np.array_equal(
        c["withdrawable_epoch"][groups["exiting"]],
        c["exit_epoch"][groups["exiting"]] + np.uint64(256),
    )
    assert c["activation_epoch"][groups["activated"]].tolist() == [2, 3, 4, 5]
    assert (c["activation_epoch"][groups["queued"]] == FAR).all()
    assert c["activation_eligibility_epoch"][groups["queued"]].tolist() == [1] + [0] * 7
    assert (c["activation_eligibility_epoch"][groups["fresh_deposits"]] == FAR).all()
    low = groups["low_balance"]
    assert (c["effective_balance"][low] == 31 * 10**9).all()
    assert ((made.balances[low] >= 31 * 10**9) & (made.balances[low] < 32 * 10**9)).all()
    # another seed, another registry of the same counts
    other = mainnet_registry.composition(config, seed + 1)
    assert not np.array_equal(other.groups["exited"], exited)


def test_exited_rows_are_interleaved_not_a_tail():
    """At the deployment's own size (the columns alone: no state is built):
    no run of 4,096 rows is all active or all exited, and the old indices
    hold most of the exits."""
    made = mainnet_registry.composition(configuration(), 2028)
    head = (1 << 21) - 2144
    is_exited = np.zeros(head, dtype=bool)
    is_exited[made.groups["exited"]] = True
    assert len(made.groups["active"]) == 1 << 20
    turns = np.nonzero(np.diff(is_exited.astype(np.int8)))[0]
    runs = np.diff(np.concatenate([[-1], turns, [head - 1]]))
    assert runs.max() < 4096
    tenth = head // 10
    assert 0.8 < is_exited[:tenth].mean() < 0.9
    assert 0.1 < is_exited[-tenth:].mean() < 0.2


def test_the_reference_imports_nothing_of_the_program():
    with open(deneb_epoch_registry.__file__) as handle:
        source = handle.read()
    assert "import ethereum_consensus_tpu" not in source
    assert "from ethereum_consensus_tpu" not in source
    imported = [
        line.split()[1] for line in source.splitlines()
        if line.startswith(("import ", "from "))
    ]
    assert set(imported) <= {
        "__future__", "math", "numpy", "benchmark.reference",
        "benchmark.reference.deneb_epoch",
    }


def test_the_validators_tree_keeps_its_root():
    world = worlds.build(configuration(1 << 12), {**WORLD, "chain_epochs": 1}, 3)
    plain, tree = deneb_epoch_registry.read_state(world.pre)
    assert tree.root() == deneb_epoch.validators_root(plain)
    rows = np.array([0, 5, 4094, 4095])
    column = plain.columns["activation_epoch"].copy()
    column[rows] = [9, 9, 7, FAR]
    plain.columns["activation_epoch"] = column
    tree.update(plain.columns, rows)
    assert tree.root() == deneb_epoch.validators_root(plain)


@pytest.mark.parametrize("seed", [5, (1 << 31) + 11])
def test_the_reference_agrees_with_the_served_path_where_the_churn_runs(seed):
    from ethereum_consensus_tpu.models.deneb.slot_processing import process_slots
    from ethereum_consensus_tpu.telemetry import metrics

    config = configuration(SMALL)
    world = worlds.build(config, WORLD, seed)
    made = mainnet_registry.composition(config, seed)
    assert int(world.pre.slot) == 63 and len(world.refills) == 6
    assert int(world.pre.eth1_deposit_index) == SMALL
    assert int(world.pre.eth1_data.deposit_count) == SMALL
    exited = made.groups["exited"]
    for flags in [world.pre.previous_epoch_participation,
                  world.pre.current_epoch_participation, *world.refills]:
        assert not np.asarray(list(flags))[exited].any()  # an exited row carries 0
    plain, _ = deneb_epoch_registry.read_state(world.pre)
    assert deneb_epoch.state_root(plain) == type(world.pre).hash_tree_root(world.pre)

    writes = metrics.counter("epoch_vector.validator_writes")
    writes_before = writes.value()
    state = world.pre.copy()
    served = []
    for place in range(7):
        if place:
            process_slots(state, world.target_slot + 32 * place - 1, world.context)
            state.current_epoch_participation = world.refills[place - 1].tolist()
        process_slots(state, world.target_slot + 32 * place, world.context)
        served.append(type(state).hash_tree_root(state))
    want = deneb_epoch_registry.chain_roots(world.pre, world.target_slot, world.refills)
    assert want == served and len(set(served)) == 7
    assert int(state.finalized_checkpoint.epoch) == 6  # finality kept pace

    # the chain really churned: it cannot pass on an idle registry
    validators = state.validators
    queue = made.groups["queued"].tolist()
    activation = [int(validators[i].activation_epoch) for i in queue]
    # the first boundary takes the churn limit's worth (4 of 4,096 active rows)
    # of the rows eligible since epoch 0, the second the rest of them; the
    # late row waits for finality to pass its epoch
    assert activation[1:5] == [6] * 4 and activation[5:] == [7] * 3
    assert 7 < activation[0] < FAR
    for i in made.groups["fresh_deposits"].tolist():
        assert int(validators[i].activation_eligibility_epoch) == 2  # stamped
        assert int(validators[i].activation_epoch) < FAR  # and let in after finality
    epoch = int(state.slot) // 32
    still_active = sum(
        1 for i in made.groups["exiting"].tolist()
        if int(validators[i].exit_epoch) > epoch
    )
    assert epoch == 8 and still_active == 16 - 7  # epochs 2..8 each lost one
    assert writes.value() - writes_before >= 8 + 2 + 2  # queue, stamps, deposits


FAULTS = [dropped_activation, queue_in_index_order, exited_row_rewarded]


def small_cell(queued=None):
    cell = harness.load_cell(CELL)
    cell.config["validators"] = SMALL
    if queued:
        # a queue that outlasts finality at this size, as the deployment's does
        cell.config["registry"]["queued"] = queued * (1 << 21) // SMALL
    return cell


def test_the_cell_runs_at_a_small_size_and_counts_its_churn(routing):
    from ethereum_consensus_tpu.telemetry import metrics

    activated = metrics.counter("epoch_vector.registry.activated")
    before = activated.value()
    result = harness.execute(
        small_cell(queued=64), (1 << 31) + 3, 1.0, False, time.perf_counter(), routing
    )
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["boundary_roots_wrong"] == {"value": 0, "limit": 0}
    # the world's own boundary found no queue; every crossing since took 4
    assert (activated.value() - before) % 4 == 0 and activated.value() > before


@pytest.mark.parametrize("fault", FAULTS, ids=[f.__name__ for f in FAULTS])
def test_a_fault_in_the_churn_is_not_correct(fault, routing, monkeypatch):
    def install():
        routing()
        fault(monkeypatch)

    result = harness.execute(
        small_cell(queued=64), 11, 3.0, False, time.perf_counter(), install
    )
    assert result["compared"]["boundary_roots_wrong"]["value"] > 0
    assert result["correct"] is False


def test_every_counter_metric_finds_its_file_and_reader_in_both_cells():
    """``test_window_counter.py`` pins the benchmark to its first cell
    (twelve entries, each on that cell alone); this is the same check for
    the benchmark as it stands: the twelve on both cells, this cell's three
    on it alone."""
    from benchmark.tests.rehearsal import read_benchmark
    from benchmark.tests.test_window_counter import new_entries

    bench = read_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    assert cells == ["deneb-1m.epoch-boundary", CELL]
    entries = new_entries()
    assert len(entries) == 15
    for entry, spec in entries[:12]:
        assert entry["workloads"] == cells and entry["better"] == "lower"
    assert [e["name"] for e, _ in entries[12:]] == [
        "epoch.activated_per_boundary", "epoch.validator_writes_per_boundary",
        "epoch.active_rows_k",
    ]
    for entry, spec in entries[12:]:
        assert entry["workloads"] == [CELL] and entry["source"] == "program_counter"
        assert entry["moves"] == "epoch_boundary_s"
        assert spec["params"]["per"] == "boundaries"
        assert spec["params"]["counter"].startswith("epoch_vector.")
    ours = harness.load_cell(CELL).per_layer
    assert len(ours) == 20 and all(e in ours for e, _ in entries)
    theirs = harness.load_cell(cells[0]).per_layer
    assert len(theirs) == 17 and not any(e in theirs for e, _ in entries[12:])


def test_a_traced_run_reads_the_churn_beside_the_split(routing, monkeypatch):
    """The cell at 2^13 on the CPU backend under a real profiler session
    (no device plane there, so the reduction is stood in for): every
    counter metric reads, and the three that prove the mechanism read what
    this size's churn is."""
    import shutil

    import jax.profiler

    from benchmark.tests.test_window_counter import new_entries

    def stop_without_reducing(self):
        jax.profiler.stop_trace()
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return {"busy_s": 0.1, "window_s": 1.0, "programs": {}, "spans": {},
                "device_ops": [], "idle_gaps": []}

    monkeypatch.setattr(harness.Tracing, "stop_and_reduce", stop_without_reducing)
    result = harness.execute(
        small_cell(queued=64), 2147483677, 0.5, True, time.perf_counter(), routing
    )
    assert result["correct"] is True and result["failed"] == 0
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert {e["name"] for e, _ in new_entries()} <= set(values)
    assert {"epoch.transition_s", "epoch.root_s"} <= set(values)
    # the activation churn limit at 4,096 active rows, at every boundary
    assert values["epoch.activated_per_boundary"] == 4.0
    assert values["epoch.validator_writes_per_boundary"] >= 4.0
    assert 4.0 < values["epoch.active_rows_k"] < 4.2
