"""Mainnet without finality (``worlds/leak_edge.py``) and its plain reference
(``reference/deneb_epoch_leak.py``): the generator follows the configuration
file and the seed at the cell's own counts, no seed can restore finality,
the cell rehearses through the harness on the CPU, every new metric finds
its file and its reader, and a wrong leak is not correct."""

import json
import os
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import deneb_epoch_leak
from benchmark.tests import faults_leak
from benchmark.tests.rehearsal import ROOT, read_benchmark
from benchmark.worlds import leak_edge

CELL = "deneb-1m.epoch-leak"
CONFIG = "mainnet-deneb-1m-leak"
SMALL = 1 << 13
ETH = 10**9
COMMIT_SPANS = [
    "epoch.commit_balances_ms", "epoch.commit_scores_ms", "epoch.commit_validators_ms",
]
LEAK_COUNTERS = [
    "epoch.leaking_per_boundary", "epoch.score_rows_changed_k",
    "epoch.eff_rows_changed_per_boundary",
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
    return leak_edge.composition(configuration(), 3200000032)


def test_the_configuration_file_is_the_issues_deployment():
    config = configuration()
    fin, shapes = config["finality"], config["shapes_from_source"]
    assert (config["fork"], config["preset"]) == ("deneb", "mainnet")
    assert config["validators"] == fin["at_validators"] == 1 << 20
    assert list(config["reduced"]) == ["validators"]
    assert (fin["at_slot"], fin["finalized_epoch"]) == (1101 * 32 - 1, 75)
    assert (fin["offline"], fin["offline_epochs"]) == (367001, 1024)
    assert abs(fin["offline"] / (1 << 20) - 0.35) < 1e-6
    # every constant is the source's
    assert shapes["MIN_EPOCHS_TO_INACTIVITY_PENALTY"] == 4
    assert shapes["INACTIVITY_SCORE_BIAS"] == 4
    assert shapes["INACTIVITY_SCORE_RECOVERY_RATE"] == 16
    assert shapes["INACTIVITY_PENALTY_QUOTIENT_BELLATRIX"] == 1 << 24
    assert (shapes["HYSTERESIS_QUOTIENT"], shapes["HYSTERESIS_DOWNWARD_MULTIPLIER"],
            shapes["HYSTERESIS_UPWARD_MULTIPLIER"]) == (4, 1, 5)
    assert shapes["EJECTION_BALANCE"] == 16 * ETH
    # every part of the finality group is under `assumed` with its reason
    assert {"finality", "finality.at_slot", "finality.finalized_epoch",
            "finality.offline", "finality.offline_epochs", "finality.online",
            "finality.online_walk", "participation"} <= set(config["assumed"])
    assert "bit-exact" in config["guarantees"]["state_roots"]
    # the 16 epochs a chain enters cross no period the reference refuses
    entered = range(fin["at_slot"] // 32 + 1, fin["at_slot"] // 32 + 17)
    assert not any(epoch % 64 == 0 for epoch in entered)


def test_the_composition_at_the_cells_own_counts(full):
    config = configuration()
    n = 1 << 20
    offline, online = full.is_offline, ~full.is_offline
    assert len(full.offline) == offline.sum() == 367001
    # interleaved, never a prefix or a tail
    turns = np.nonzero(np.diff(offline.astype(np.int8)))[0]
    runs = np.diff(np.concatenate([[-1], turns, [n - 1]]))
    assert runs.max() < 128
    for tenth in np.array_split(offline, 10):
        assert 0.34 < tenth.mean() < 0.36
    # the offline rows: 1,024 epochs of the leak
    assert (full.scores[offline] == 4096).all()
    balances = full.balances[offline]
    assert 31 * ETH < balances.min() < 31.03 * ETH
    assert 31.98 * ETH < balances.max() < 32 * ETH
    effective = full.effective_balance[offline]
    assert set(np.unique(effective).tolist()) == {31 * ETH, 32 * ETH}
    assert ((effective == 31 * ETH) == (balances < 31.75 * ETH)).all()  # hysteresis
    # what the next boundary takes (about 1.96 M gwei) steps about 720 rows down
    next_loss = 32 * ETH * 4 * 1025 // (4 << 24) + 7_000
    about_to_step = (effective == 32 * ETH) & (balances - next_loss < 31.75 * ETH)
    assert 600 < about_to_step.sum() < 850
    # and none reaches the next step (30.75 ETH) within 16 boundaries
    assert balances.min() - 16 * 2_000_000 > 30.75 * ETH
    # the online rows: whole, and a small walk of scores
    assert (full.effective_balance[online] == 32 * ETH).all()
    assert (full.balances[online] >= 32 * ETH).all()
    assert (full.balances[online] < 33 * ETH).all()
    scores = full.scores[online]
    assert 0.8 < (scores == 0).mean() < 0.97 and scores.max() <= 32
    assert 0.01 <= full.online_miss_share <= 0.03
    # another seed, another registry of the same counts
    other = leak_edge.composition(config, 3200000033)
    assert len(other.offline) == 367001
    assert not np.array_equal(other.offline, full.offline)


def test_no_list_of_the_cell_can_justify_an_epoch(full):
    """The traffic at the cell's own counts: in both lists of the world and
    in every refill the target's weight stays under the 2/3 that justifies,
    so every crossing of every chain is a leaking one."""
    world = harness.load_cell(CELL).traffic["world"]
    low, high = world["miss_share"]
    total = int(full.effective_balance.sum())
    streams = ["previous", "current"] + [
        f"refill-{k}" for k in range(1, world["chain_epochs"])
    ]
    for stream in streams:
        flags, _ = leak_edge._flags(3200000032, stream, full.is_offline, low, high)
        assert not flags[full.is_offline].any()  # an offline row carries no flag
        on_target = (flags >> 1) & 1 == 1
        weight = int(full.effective_balance[on_target].sum())
        assert 0.63 < weight / total < 0.65


def test_the_reference_imports_nothing_of_the_program():
    with open(deneb_epoch_leak.__file__) as handle:
        source = handle.read()
    assert "ethereum_consensus_tpu" not in source
    imported = [
        line.split()[1] for line in source.splitlines()
        if line.startswith(("import ", "from "))
    ]
    assert set(imported) <= {
        "__future__", "math", "numpy", "benchmark.reference",
        "benchmark.reference.deneb_epoch", "benchmark.reference.deneb_epoch_registry",
    }
    # the leak's stages are written out here, under the specification's names
    for name in ("is_in_inactivity_leak", "get_finality_delay",
                 "process_inactivity_updates", "get_flag_index_deltas",
                 "get_inactivity_penalty_deltas", "process_effective_balance_updates"):
        assert f"def {name}(" in source


def small_cell():
    cell = harness.load_cell(CELL)
    cell.config["validators"] = SMALL
    return cell


def test_the_cell_rehearses_at_a_small_size_and_every_crossing_leaks(routing):
    from ethereum_consensus_tpu.telemetry import metrics

    leaked = metrics.counter("epoch_vector.leak.epochs")
    passes = metrics.counter("epoch_vector.epochs")
    before = leaked.value(), passes.value()
    result = harness.execute(
        small_cell(), (1 << 31) + 32, 1.0, False, time.perf_counter(), routing
    )
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["boundary_roots_wrong"] == {"value": 0, "limit": 0}
    assert set(result["metrics"]) == {"epoch_boundary_s", "setup_s"}
    # every pass a leaking one, but for the genesis boundary the world crossed
    assert leaked.value() - before[0] == passes.value() - before[1] - 1 > 3


PLANTS = faults_leak.FAULTS + [faults_leak.CONTROL]


@pytest.mark.parametrize("plant", PLANTS, ids=[p.__name__ for p in PLANTS])
def test_a_wrong_leak_is_not_correct(plant, routing, monkeypatch):
    def install():
        routing()
        plant(monkeypatch)

    result = harness.execute(small_cell(), 11, 1.0, False, time.perf_counter(), install)
    assert result["compared"]["boundary_roots_wrong"]["value"] > 0
    assert result["correct"] is False


def test_every_new_metric_finds_its_file_and_reader():
    bench = read_benchmark()
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    cell = bench["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "epoch-boundary-leak", 1
    )
    entries = {e["name"]: e for e in bench["per_layer"]}
    happy = harness.load_cell("deneb-1m.epoch-boundary").per_layer
    ours = harness.load_cell(CELL).per_layer
    # everything the 1m cell reports, the 2m cell's writes, and the counters
    assert {e["name"] for e in happy} < {e["name"] for e in ours}
    assert {e["name"] for e in ours} - {e["name"] for e in happy} == {
        "epoch.validator_writes_per_boundary", *LEAK_COUNTERS
    }
    for name in COMMIT_SPANS + LEAK_COUNTERS:
        entry = entries[name]
        assert entry["moves"] == "epoch_boundary_s"
        assert entry["layer"] == "models/epoch_vector.py + ops/shuffle.py"
        with open(os.path.join(ROOT, f"benchmark/metrics/{name}.json")) as handle:
            spec = json.load(handle)
        assert spec["reader"] == "window_counter"
        assert spec["params"]["per"] == "boundaries"
        harness.load_module(ROOT, bench["paths"], "readers", spec["reader"])
    for name in COMMIT_SPANS:
        assert len(entries[name]["workloads"]) == 3
        assert entries[name]["source"] == "program_span"
    for name in LEAK_COUNTERS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["source"] == "program_counter"


def test_a_traced_rehearsal_reads_the_leak_beside_the_split(routing, monkeypatch):
    """The cell at 2^13 on the CPU backend under a real profiler session (no
    device plane there, so the reduction is stood in for): the six new
    metrics read, the three child spans partition the commit, and the
    counters read what the reference counts."""
    import shutil

    import jax.profiler

    def stop_without_reducing(self):
        jax.profiler.stop_trace()
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return {"busy_s": 0.1, "window_s": 1.0, "programs": {}, "spans": {},
                "device_ops": [], "idle_gaps": []}

    monkeypatch.setattr(harness.Tracing, "stop_and_reduce", stop_without_reducing)
    cell = small_cell()
    seed = 2147483680
    result = harness.execute(cell, seed, 0.5, True, time.perf_counter(), routing)
    assert result["correct"] is True and result["failed"] == 0
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(COMMIT_SPANS + LEAK_COUNTERS) <= set(values)
    assert values["epoch.leaking_per_boundary"] == 1.0
    split = sum(values[name] for name in COMMIT_SPANS)
    assert 0.9 * values["epoch.commit_ms"] < split <= values["epoch.commit_ms"]
    # what the reference counts over the crossings the window held
    from benchmark import worlds

    world = worlds.build(cell.config, cell.traffic["world"], seed)
    counts = {}
    deneb_epoch_leak.chain_roots(world.pre, world.target_slot, world.refills, counts)
    crossings = result["attempted"]
    places = [k % 16 for k in range(crossings)]  # the window starts a chain of its own
    assert values["epoch.eff_rows_changed_per_boundary"] == pytest.approx(
        sum(counts["eff_changed"][p] for p in places) / crossings
    )
    assert values["epoch.score_rows_changed_k"] == pytest.approx(
        sum(counts["scores_changed"][p] for p in places) / crossings / 1000
    )
    assert values["epoch.validator_writes_per_boundary"] == pytest.approx(
        values["epoch.eff_rows_changed_per_boundary"]
    )
    assert values["epoch.score_rows_changed_k"] * 1000 > 0.35 * SMALL
