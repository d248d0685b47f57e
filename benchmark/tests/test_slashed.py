"""Mainnet halfway through a correlated slashing (``worlds/slashed_edge.py``)
held to the registry's plain reference (``reference/deneb_epoch_registry.py``,
unedited): the generator follows the configuration file and the seed at the
cell's own counts, the cell rehearses through the harness on the CPU, every
new metric finds its file and its reader, and a wrong slashing is not
correct."""

import json
import os
import time
from math import isqrt

import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import deneb_epoch_registry
from benchmark.tests import faults_slashed
from benchmark.tests.rehearsal import ROOT, read_benchmark
from benchmark.worlds import slashed_edge

CELL = "deneb-1m.epoch-slashed"
CONFIG = "mainnet-deneb-1m-slashed"
SMALL = 1 << 13
ETH = 10**9
NEW = {
    "epoch.slashings_ms": ("program_span", None),
    "epoch.slashing_penalties_per_boundary": ("program_counter", [CELL]),
    "epoch.eligible_inactive_rows_k": ("program_counter", [CELL]),
}
ALSO = [
    "epoch.validator_writes_per_boundary", "epoch.eff_rows_changed_per_boundary",
    "epoch.root_tree_splice_ms", "epoch.root_path_rows_per_boundary",
]


def configuration(validators=None) -> dict:
    with open(os.path.join(ROOT, f"benchmark/configs/{CONFIG}.json")) as handle:
        config = json.load(handle)
    if validators:
        config["validators"] = validators
    return config


@pytest.fixture(scope="module")
def full():
    """The composition at the deployment's own size (columns alone: no
    container is built)."""
    return slashed_edge.composition(configuration(), 3900000001)


def test_the_configuration_file_is_the_deployment():
    config = configuration()
    group, shapes = config["slashing"], config["shapes_from_source"]
    assert (config["fork"], config["preset"], config["architecture"]) == (
        "deneb", "mainnet", None
    )
    assert config["validators"] == group["at_validators"] == 1 << 20
    assert list(config["reduced"]) == ["validators"]
    assert (group["at_slot"], group["slashed"], group["per_epoch"],
            group["run_length"], group["epochs"]) == (134431, 16384, 1024, 32, [104, 119])
    # every constant is the source's
    assert shapes["EPOCHS_PER_SLASHINGS_VECTOR"] == 8192
    assert shapes["PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX"] == 3
    assert shapes["MIN_SLASHING_PENALTY_QUOTIENT_BELLATRIX"] == 32
    assert shapes["MIN_VALIDATOR_WITHDRAWABILITY_DELAY"] == 256
    assert shapes["CHURN_LIMIT_QUOTIENT"] == 65536
    assert (shapes["MAX_ATTESTER_SLASHINGS"], shapes["MAX_VALIDATORS_PER_COMMITTEE"]) == (
        2, 2048
    )
    assert (shapes["HYSTERESIS_QUOTIENT"], shapes["HYSTERESIS_DOWNWARD_MULTIPLIER"],
            shapes["HYSTERESIS_UPWARD_MULTIPLIER"]) == (4, 1, 5)
    assert {"slashing", "slashing.incident", "slashing.at_slot", "slashing.run_length",
            "slashing.slashings", "slashing.exit_epoch", "slashing.withdrawable_epoch",
            "slashing.balances", "slashing.others", "finality", "derived",
            "participation"} <= set(config["assumed"])
    assert "bit-exact" in config["guarantees"]["state_roots"]
    assert len(next(c for c in read_benchmark()["configs"]
                    if c["name"] == CONFIG)["source"]) <= 200
    # the 16 epochs a chain enters cross no period the reference refuses
    entered = range(group["at_slot"] // 32 + 1, group["at_slot"] // 32 + 17)
    assert not any(epoch % 64 == 0 for epoch in entered)


def test_the_composition_at_the_cells_own_counts(full):
    config = configuration()
    derived = config["derived"]
    n = 1 << 20
    rows = full.slashed
    assert len(rows) == len(set(rows.tolist())) == full.is_slashed.sum() == 16384
    # runs of 32 adjacent rows at seeded places, never a prefix or a tail
    ordered = np.sort(rows)
    assert ordered[0] > 0 and ordered[-1] < n - 1
    breaks = np.nonzero(np.diff(ordered) > 1)[0]
    lengths = np.diff(np.concatenate([[-1], breaks, [len(ordered) - 1]]))
    assert (lengths % 32 == 0).all() and len(lengths) > 400
    for tenth in np.array_split(full.is_slashed, 10):
        assert 0.01 < tenth.mean() < 0.022
    # 1,024 an epoch of 104..119, withdrawable 8,192 later, exited long ago
    epochs, per = np.unique(full.slashed_epoch, return_counts=True)
    assert epochs.tolist() == list(range(104, 120)) and (per == 1024).all()
    assert (full.withdrawable_epoch == full.slashed_epoch.astype(np.uint64) + 8192).all()
    assert [int(full.exit_epoch.min()), int(full.exit_epoch.max())] == derived["exit_epochs"]
    assert (np.diff(full.exit_epoch.astype(np.int64)) >= 0).all()
    per_exit = np.unique(full.exit_epoch, return_counts=True)[1]
    assert per_exit.max() == 16 and (per_exit[1:-1] >= 15).all()
    assert sum(full.slashings.values()) == 524288 * ETH
    # balances: 32 ETH + excess - 1 ETH - about 0.029 ETH; hysteresis left 30-32
    balances, effective = full.balances[rows], full.effective_balance[rows]
    assert 30.97 * ETH < balances.min() and balances.max() < 31.972 * ETH
    assert set(np.unique(effective).tolist()) <= {30 * ETH, 31 * ETH, 32 * ETH}
    assert ((effective == 32 * ETH) == (balances >= 31.75 * ETH)).all()
    others = ~full.is_slashed
    assert (full.effective_balance[others] == 32 * ETH).all()
    assert (full.balances[others] >= 32 * ETH).all()
    # the penalty the file derives: 1 ETH, at the total the file states
    total = derived["active_rows"] * 32 * ETH
    assert total == (n - 16384) * 32 * ETH
    adjusted = min(sum(full.slashings.values()) * 3, total)
    for increments in (30, 31, 32):
        assert increments * adjusted // total * ETH == derived["halfway_penalty_gwei"]
    assert derived["churn_limit"] == (n - 16384) // 65536
    # another seed, another registry of the same counts
    other = slashed_edge.composition(config, 3900000002)
    assert len(other.slashed) == 16384
    assert not np.array_equal(np.sort(other.slashed), ordered)


def steps_over_a_chain(made, total: int, slashings: int) -> list:
    """Effective balances a boundary of the slashed rows over a chain of
    16, by the specification's arithmetic on the composition: the numbers
    the file derives."""
    rows = made.slashed
    balances = made.balances[rows].astype(np.int64)
    effective = made.effective_balance[rows].astype(np.int64)
    withdrawable = made.withdrawable_epoch.astype(np.int64)
    per_increment = ETH * 64 // isqrt(total)
    steps = []
    for current in range(4200, 4216):
        base_reward = effective // ETH * per_increment
        balances = balances - base_reward * 14 // 64 - base_reward * 26 // 64
        due = withdrawable == current + 4096
        balances[due] -= effective[due] // ETH * min(3 * slashings, total) // total * ETH
        down = balances + ETH // 4 < effective
        steps.append(int(down.sum()))
        effective = np.where(down, balances - balances % ETH, effective)
    return steps


def test_about_1024_rows_step_down_at_every_boundary(full):
    derived = configuration()["derived"]
    steps = steps_over_a_chain(
        full, derived["active_rows"] * 32 * ETH, sum(full.slashings.values())
    )
    low, high = derived["eff_rows_changed_per_boundary_over_a_chain_12_seeds"]
    assert low <= sum(steps) / 16 <= high
    assert all(abs(s - derived["eff_rows_changed_per_boundary"]) <= 2 for s in steps)


def small_cell():
    cell = harness.load_cell(CELL)
    cell.config["validators"] = SMALL
    return cell


def test_the_cell_rehearses_at_a_small_size_and_every_crossing_pays(routing):
    from ethereum_consensus_tpu.telemetry import metrics

    penalised = metrics.counter("epoch_vector.slashings.penalised")
    passes = metrics.counter("epoch_vector.epochs")
    before = penalised.value(), passes.value()
    result = harness.execute(
        small_cell(), (1 << 31) + 39, 1.0, False, time.perf_counter(), routing
    )
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["boundary_roots_wrong"] == {"value": 0, "limit": 0}
    assert set(result["metrics"]) == {"epoch_boundary_s", "setup_s"}
    # 8 a pass (1,024 scaled), but for the genesis boundary the world crossed
    paid, crossed = penalised.value() - before[0], passes.value() - before[1] - 1
    assert paid == 8 * crossed and crossed > 3


PLANTS = faults_slashed.FAULTS + [faults_slashed.CONTROL]


@pytest.mark.parametrize("plant", PLANTS, ids=[p.__name__ for p in PLANTS])
def test_a_wrong_slashing_is_not_correct(plant, routing, monkeypatch):
    def install():
        routing()
        plant(monkeypatch)

    result = harness.execute(small_cell(), 11, 1.0, False, time.perf_counter(), install)
    assert result["compared"]["boundary_roots_wrong"]["value"] > 0
    assert result["correct"] is False


def test_every_new_metric_finds_its_file_and_reader():
    bench = read_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "epoch-boundary-slashed", 1
    )
    traffic = harness.load_cell(CELL).traffic
    assert traffic["reference"] == "deneb_epoch_registry"
    assert traffic["world"]["kind"] == "slashed_edge"
    entries = {e["name"]: e for e in bench["per_layer"]}
    happy = {e["name"] for e in harness.load_cell("deneb-1m.epoch-boundary").per_layer}
    ours = {e["name"] for e in harness.load_cell(CELL).per_layer}
    assert happy < ours
    assert ours - happy == set(ALSO) | set(NEW) - {"epoch.slashings_ms"}
    every_cell = [w["name"] for w in bench["workloads"]]
    for name, (source, cells) in NEW.items():
        entry = entries[name]
        assert entry["moves"] == "epoch_boundary_s" and entry["source"] == source
        assert entry["layer"] == "models/epoch_vector.py + ops/shuffle.py"
        assert entry["workloads"] == (cells or every_cell)
        with open(os.path.join(ROOT, f"benchmark/metrics/{name}.json")) as handle:
            spec = json.load(handle)
        assert spec["reader"] == "window_counter"
        assert spec["params"]["per"] == "boundaries"
        harness.load_module(ROOT, bench["paths"], "readers", spec["reader"])


def test_a_traced_rehearsal_reads_the_slashing(routing, monkeypatch):
    """The cell at 2^13 on the CPU backend under a real profiler session (no
    device plane there, so the reduction is stood in for): the three new
    metrics read what the deployment holds, and the writes are the rows
    the reference steps down."""
    import shutil

    import jax.profiler

    def stop_without_reducing(self):
        jax.profiler.stop_trace()
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return {"busy_s": 0.1, "window_s": 1.0, "programs": {}, "spans": {},
                "device_ops": [], "idle_gaps": []}

    monkeypatch.setattr(harness.Tracing, "stop_and_reduce", stop_without_reducing)
    seed = 3900000039
    result = harness.execute(small_cell(), seed, 0.5, True, time.perf_counter(), routing)
    assert result["correct"] is True and result["failed"] == 0
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(values)
    assert values["epoch.slashing_penalties_per_boundary"] == 8.0
    assert values["epoch.eligible_inactive_rows_k"] == pytest.approx(0.128)
    assert values["epoch.slashings_ms"] > 0
    # what the composition's arithmetic steps down over the crossings the window held
    made = slashed_edge.composition(configuration(SMALL), seed)
    steps = steps_over_a_chain(made, (SMALL - 128) * 32 * ETH, sum(made.slashings.values()))
    places = [k % 16 for k in range(result["attempted"])]
    assert values["epoch.eff_rows_changed_per_boundary"] == pytest.approx(
        sum(steps[p] for p in places) / len(places)
    )
    assert values["epoch.validator_writes_per_boundary"] == pytest.approx(
        values["epoch.eff_rows_changed_per_boundary"]
    )
    assert values["epoch.root_path_rows_per_boundary"] == pytest.approx(
        values["epoch.eff_rows_changed_per_boundary"]
    )


def test_the_reference_is_the_registry_files_unedited():
    """The cell's reference writes process_slashings and the slashed branch
    of eligible out under the specification's names, and imports nothing of
    the program."""
    with open(deneb_epoch_registry.__file__) as handle:
        source = handle.read()
    assert "ethereum_consensus_tpu" not in source
    for name in ("process_slashings", "eligible_validators", "unslashed_participating"):
        assert f"def {name}(" in source
    assert "PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX = 3" in source
