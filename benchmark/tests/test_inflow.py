"""Mainnet while deposits flood in (``worlds/mainnet_registry_inflow.py``,
``drivers/epoch_boundary_inflow.py``) and its plain reference
(``reference/deneb_epoch_inflow.py``): the configuration is the issue's, the
world follows it and the seed, every length a chain reaches is dispatched in
one shape, the cell rehearses through the harness on the CPU and reads its
five metrics, and a new row got wrong is not correct."""

import json
import os
import time

import numpy as np
import pytest

from benchmark import harness, worlds
from benchmark.reference import deneb_epoch_inflow
from benchmark.tests import faults_inflow
from benchmark.tests.rehearsal import ROOT, read_benchmark
from benchmark.worlds import mainnet_registry, mainnet_registry_inflow

CELL = "deneb-growing.epoch-boundary"
CONFIG = "mainnet-deneb-growing"
SMALL = (1 << 13) + 37
NEW_METRICS = [
    "epoch.rows_appended_per_boundary", "epoch.columns_extended_rows_per_boundary",
    "epoch.sync_extend_ms", "epoch.fused_pad_rows_k",
    "epoch.fused_cache_hits_per_boundary",
]
WORLD = {"kind": "mainnet_registry_inflow", "epoch": 1, "miss_share": [0.01, 0.03],
         "chain_epochs": 5}


def configuration(validators=None) -> dict:
    with open(os.path.join(ROOT, f"benchmark/configs/{CONFIG}.json")) as handle:
        config = json.load(handle)
    if validators:
        config["validators"] = validators
    return config


def test_the_configuration_file_is_the_issues_deployment():
    config = configuration()
    reg, inflow, shapes = config["registry"], config["inflow"], config["shapes_from_source"]
    assert config["architecture"] is None and config["reduced"] == {}
    assert config["validators"] == reg["at_validators"] == 1_905_000
    assert (reg["active"], reg["fresh_deposits"], reg["queued"], reg["slashed"]) == (
        1_050_000, 512, 2048, 512
    )
    # every other group of the registry is mainnet-deneb-2m's, as written
    with open(os.path.join(ROOT, "benchmark/configs/mainnet-deneb-2m.json")) as handle:
        two_m = json.load(handle)
    differ = {k for k in reg if reg[k] != two_m["registry"][k]}
    assert differ == {"at_validators", "active", "fresh_deposits"}
    assert {k: v for k, v in shapes.items() if k in two_m["shapes_from_source"]} == (
        two_m["shapes_from_source"]
    )
    assert set(shapes) - set(two_m["shapes_from_source"]) == {
        "MAX_DEPOSITS", "MIN_DEPOSIT_AMOUNT", "EFFECTIVE_BALANCE_INCREMENT"
    }
    assert inflow["per_epoch"] == shapes["MAX_DEPOSITS"] * shapes["SLOTS_PER_EPOCH"] == 512
    assert inflow["amount_gwei"] == shapes["MAX_EFFECTIVE_BALANCE"]
    assert shapes["MIN_DEPOSIT_AMOUNT"] == shapes["EFFECTIVE_BALANCE_INCREMENT"] == 10**9
    assert reg["active"] // shapes["CHURN_LIMIT_QUOTIENT"] == reg["exiting"]["per_epoch"]
    assert {"registry.at_validators", "registry.active", "registry.fresh_deposits",
            "inflow", "inflow.per_epoch", "inflow.participation",
            "inflow.within_one_shape"} <= set(config["assumed"])
    assert "all five" in config["guarantees"]["state_roots"]


def test_no_length_a_chain_reaches_is_round_and_all_are_one_shape():
    """The last group of every list is partial and moves, and the program
    dispatches every crossing of a chain in the shape the file states."""
    from ethereum_consensus_tpu.models import epoch_vector

    config = configuration()
    chain = harness.load_cell(CELL).traffic["world"]["chain_epochs"]
    lengths = [config["validators"] + config["inflow"]["per_epoch"] * k for k in range(chain)]
    assert (lengths[0], lengths[-1]) == (1_905_000, 1_912_680)
    assert all(n % 4096 for n in lengths)
    assert len({n >> 12 for n in lengths}) > 1
    for granule in (1 << 14, 1 << 15, 1 << 16, 1 << 17):
        assert len({-(-n // granule) for n in lengths}) == 1
    assert epoch_vector.FUSED_ROW_GRANULE == config["inflow"]["granule_rows"]
    assert {epoch_vector.fused_dispatch_rows(n) for n in lengths} == {
        config["inflow"]["dispatched_rows"]
    }
    # the accepted cells' registries pad nothing
    assert epoch_vector.fused_dispatch_rows(1 << 20) == 1 << 20
    assert epoch_vector.fused_dispatch_rows(1 << 21) == 1 << 21


@pytest.mark.parametrize("seed", [3, (1 << 31) + 35])
def test_the_world_appends_nothing_and_says_what_each_epoch_brings(seed):
    config = configuration(SMALL)
    world = worlds.build(config, WORLD, seed)
    plain = mainnet_registry.build(config, WORLD, seed)
    assert type(world.pre).hash_tree_root(world.pre) == type(plain.pre).hash_tree_root(plain.pre)
    count = mainnet_registry_inflow.per_epoch(config)
    assert count == 512 * SMALL // 1_905_000 == 2
    assert mainnet_registry_inflow.per_epoch(configuration()) == 512
    assert len(world.pre.validators) == SMALL
    assert [len(batch) for batch in world.deposits] == [count] * 4
    keys = [key for batch in world.deposits for key, _, _ in batch]
    held = {bytes(v.public_key) for v in world.pre.validators}
    assert len(set(keys)) == len(keys) and not held & set(keys)
    assert all(len(key) == 48 for key in keys)
    index = SMALL
    for batch in world.deposits:
        for _, credentials, amount in batch:
            assert credentials == b"\x00" * 12 + index.to_bytes(20, "big")
            assert amount == 32 * 10**9
            index += 1
    # a refill is as long as the registry at its place; a new row has no flag
    for k, (ours, theirs) in enumerate(zip(world.refills, plain.refills), start=1):
        assert len(ours) == SMALL + count * k
        assert np.array_equal(ours[:SMALL], theirs) and not ours[SMALL:].any()
    other = worlds.build(config, WORLD, seed + 1)
    assert other.deposits[0][0][0] != world.deposits[0][0][0]


def test_the_reference_imports_nothing_of_the_program():
    with open(deneb_epoch_inflow.__file__) as handle:
        source = handle.read()
    assert "ethereum_consensus_tpu" not in source
    imported = [
        line.split()[1] for line in source.splitlines()
        if line.startswith(("import ", "from "))
    ]
    assert set(imported) <= {
        "__future__", "numpy", "benchmark.reference",
        "benchmark.reference.deneb_epoch", "benchmark.reference.deneb_epoch_registry",
    }
    for name in ("get_validator_from_deposit", "process_deposits"):
        assert f"def {name}(" in source
    assert "add_validator_to_registry" in source


def test_the_growing_tree_is_the_tree_built_whole():
    """Leaves appended across a power of two: the same root as a tree built
    from all the columns at once."""
    rng = np.random.default_rng(5)

    def columns(count):
        made = {
            "public_key": [bytes([i % 251]) * 48 for i in range(count)],
            "withdrawal_credentials": [bytes([i % 7]) * 32 for i in range(count)],
        }
        for k, name in enumerate(deneb_epoch_inflow.VALIDATOR_FIELDS):
            made[name] = (np.arange(count, dtype=np.uint64) * np.uint64(k + 3)) % np.uint64(11)
        return made

    tree = deneb_epoch_inflow.GrowingValidatorsTree(columns(61))
    for count in (62, 64, 65, 130, 131 + int(rng.integers(1, 9))):
        tree.append(columns(count))
        whole = deneb_epoch_inflow.ValidatorsTree(columns(count))
        assert tree.root() == whole.root() and tree.count == count


def small_cell():
    cell = harness.load_cell(CELL)
    cell.config["validators"] = SMALL
    return cell


def test_the_cell_rehearses_at_a_small_size_and_every_crossing_meets_new_rows(routing):
    from ethereum_consensus_tpu.telemetry import metrics

    appended = metrics.counter("epoch_vector.rows_appended")
    before = appended.value()
    result = harness.execute(
        small_cell(), (1 << 31) + 35, 1.0, False, time.perf_counter(), routing
    )
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["boundary_roots_wrong"] == {"value": 0, "limit": 0}
    assert set(result["metrics"]) == {"epoch_boundary_s", "setup_s"}
    assert appended.value() - before >= 2 * 2  # two rows a later crossing


PLANTS = faults_inflow.FAULTS + [faults_inflow.CONTROL]


@pytest.mark.parametrize("plant", PLANTS, ids=[p.__name__ for p in PLANTS])
def test_a_new_row_got_wrong_is_not_correct(plant, routing, monkeypatch):
    def install():
        routing()
        plant(monkeypatch)

    result = harness.execute(small_cell(), 11, 1.0, False, time.perf_counter(), install)
    assert result["compared"]["boundary_roots_wrong"]["value"] > 0
    assert result["correct"] is False


def test_every_new_metric_finds_its_file_and_reader():
    # by name, not by place or count: a later PR adds entries behind these
    bench = read_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "epoch-boundary-inflow", 1
    )
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == [] and len(config["source"]) <= 200
    entries = {e["name"]: e for e in bench["per_layer"]}
    two_m = {e["name"] for e in harness.load_cell("deneb-2m.epoch-boundary").per_layer}
    ours = {e["name"] for e in harness.load_cell(CELL).per_layer}
    assert set(NEW_METRICS) <= ours - two_m and two_m <= ours
    for name in NEW_METRICS:
        entry = entries[name]
        assert CELL in entry["workloads"] and entry["moves"] == "epoch_boundary_s"
        with open(os.path.join(ROOT, f"benchmark/metrics/{name}.json")) as handle:
            spec = json.load(handle)
        assert spec["reader"] == "window_counter"
        assert spec["params"]["per"] == "boundaries"


def test_a_traced_rehearsal_reads_all_five_new_metrics(routing, monkeypatch):
    """The cell at a cut size on the CPU backend under a real profiler
    session (no device plane there, so the reduction is stood in for)."""
    import shutil

    import jax.profiler

    def stop_without_reducing(self):
        jax.profiler.stop_trace()
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return {"busy_s": 0.1, "window_s": 1.0, "programs": {}, "spans": {},
                "device_ops": [], "idle_gaps": []}

    monkeypatch.setattr(harness.Tracing, "stop_and_reduce", stop_without_reducing)
    cell = small_cell()
    result = harness.execute(cell, 2147483683, 1.5, True, time.perf_counter(), routing)
    assert result["correct"] is True and result["failed"] == 0
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(values)
    crossings = result["attempted"]
    # every crossing but a chain's first met two new rows (the window starts
    # a chain of its own) ...
    later = sum(1 for k in range(crossings) if k % 16)
    assert later >= 1
    assert values["epoch.rows_appended_per_boundary"] == pytest.approx(2 * later / crossings)
    # ... in four column sets: validators, balances, the flags that were
    # current, the scores (the refill replaces the fifth list)
    assert values["epoch.columns_extended_rows_per_boundary"] == pytest.approx(
        4 * values["epoch.rows_appended_per_boundary"]
    )
    assert values["epoch.fused_cache_hits_per_boundary"] == 1.0
    assert 0 < values["epoch.fused_pad_rows_k"] < 65.536
    assert 0 < values["epoch.sync_extend_ms"] < values["epoch.sync_columns_ms"]
