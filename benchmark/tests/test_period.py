"""Mainnet at the boundary that starts a sync committee period
(``worlds/period_edge.py``) held to its plain reference
(``reference/deneb_epoch_period.py``): the configuration file is the
deployment, the cell rehearses through the harness on the CPU and every
timed crossing rotates, every new metric finds its file and its reader and
reads in a traced rehearsal, and a wrong rotation or summary is not
correct."""

import json
import os
import time

import pytest

from benchmark import harness
from benchmark.reference import deneb_epoch_period, g1
from benchmark.tests import faults_period
from benchmark.tests.rehearsal import ROOT, read_benchmark

CELL = "deneb-1m.epoch-period"
CONFIG = "mainnet-deneb-1m-period"
SMALL = 1 << 13
VECTOR_LAYER = "models/epoch_vector.py + ops/shuffle.py"
NEW = {
    "epoch.sync_committee_ms": ("program_span", VECTOR_LAYER),
    "epoch.sync_committee_active_ms": ("program_span", VECTOR_LAYER),
    "epoch.sync_committee_sample_ms": ("program_span", VECTOR_LAYER),
    "epoch.sync_committee_aggregate_ms": ("program_span", "crypto/bls.py"),
    "epoch.historical_summary_ms": ("program_span", "ssz/hash.py + ops/sha256.py"),
    "epoch.sync_committee_rotations_per_boundary": ("program_counter", VECTOR_LAYER),
}
# what a CPU rehearsal at 2^13 rows cannot read: the device plane, and the
# mechanisms of lists longer than one 4,096-chunk group
SILENT_ON_THE_CPU = {
    "epoch_fused.device_ms", "epoch_fused_roofline", "device_idle_share.epoch",
    "epoch.sync_columns_from_pack_per_boundary", "epoch.root_packed_splice_ms",
    "epoch.root_clone_ms", "epoch.root_threaded_groups_per_boundary",
}


def configuration() -> dict:
    with open(os.path.join(ROOT, f"benchmark/configs/{CONFIG}.json")) as handle:
        return json.load(handle)


def test_the_configuration_file_is_the_deployment():
    config = configuration()
    shapes, group = config["shapes_from_source"], config["period"]
    assert (config["fork"], config["preset"], config["architecture"]) == (
        "deneb", "mainnet", None
    )
    assert config["validators"] == 1 << 20
    assert list(config["reduced"]) == ["validators"]
    assert (group["at_slot"], group["sync_committee_size"]) == (139263, 512)
    # every constant is the source's, and the reference's
    assert (shapes["SYNC_COMMITTEE_SIZE"], shapes["EPOCHS_PER_SYNC_COMMITTEE_PERIOD"]) == (
        deneb_epoch_period.SYNC_COMMITTEE_SIZE, deneb_epoch_period.EPOCHS_PER_SYNC_COMMITTEE_PERIOD
    ) == (512, 256)
    assert shapes["SHUFFLE_ROUND_COUNT"] == deneb_epoch_period.SHUFFLE_ROUND_COUNT == 90
    assert shapes["MIN_SEED_LOOKAHEAD"] == deneb_epoch_period.MIN_SEED_LOOKAHEAD == 1
    assert (shapes["SLOTS_PER_HISTORICAL_ROOT"], shapes["EPOCHS_PER_HISTORICAL_VECTOR"],
            shapes["HISTORICAL_ROOTS_LIMIT"]) == (8192, 65536, 1 << 24)
    assert bytes.fromhex(shapes["DOMAIN_SYNC_COMMITTEE"][2:]) == (
        deneb_epoch_period.DOMAIN_SYNC_COMMITTEE
    )
    # the crossing enters 17 x 256, a multiple of 64 and a summary's epoch
    entered = (group["at_slot"] + 1) // 32
    assert entered == 17 * 256 and entered % 64 == 0 and entered * 32 % 8192 == 0
    assert {"period", "period.at_slot", "randao_mixes", "block_roots, state_roots",
            "historical_summaries", "current_sync_committee, next_sync_committee",
            "keys", "pubkey_cache", "active_indices", "finality",
            "participation"} <= set(config["assumed"])
    assert "bit-exact" in config["guarantees"]["state_roots"]
    entry = next(c for c in read_benchmark()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["validators"]


def small_cell():
    cell = harness.load_cell(CELL)
    cell.config["validators"] = SMALL
    return cell


def test_the_cell_rehearses_at_a_small_size_and_every_crossing_rotates(routing):
    from ethereum_consensus_tpu.telemetry import metrics

    rotations = metrics.counter("epoch_vector.sync_committee.rotations")
    summaries = metrics.counter("epoch_vector.historical_summaries")
    passes = metrics.counter("epoch_vector.epochs")
    before = rotations.value(), summaries.value(), passes.value()
    result = harness.execute(
        small_cell(), (1 << 31) + 41, 1.0, False, time.perf_counter(), routing
    )
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["boundary_roots_wrong"] == {"value": 0, "limit": 0}
    assert set(result["metrics"]) == {"epoch_boundary_s", "setup_s"}
    # every pass but the genesis boundary the world crossed
    crossed = passes.value() - before[2] - 1
    assert crossed == result["attempted"] + 3  # and the three of the warm-up
    assert rotations.value() - before[0] == summaries.value() - before[1] == crossed


PLANTS = faults_period.FAULTS + [faults_period.CONTROL]


@pytest.mark.parametrize("plant", PLANTS, ids=[p.__name__ for p in PLANTS])
def test_a_wrong_period_is_not_correct(plant, routing, monkeypatch):
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
        CONFIG, "epoch-boundary-period", 1
    )
    traffic = harness.load_cell(CELL).traffic
    assert traffic["reference"] == "deneb_epoch_period"
    assert traffic["world"]["kind"] == "period_edge"
    assert traffic["world"]["chain_epochs"] == 1
    entries = {e["name"]: e for e in bench["per_layer"]}
    happy = {e["name"] for e in harness.load_cell("deneb-1m.epoch-boundary").per_layer}
    ours = {e["name"] for e in harness.load_cell(CELL).per_layer}
    assert ours == happy | set(NEW)
    for name, (source, layer) in NEW.items():
        entry = entries[name]
        assert entry["moves"] == "epoch_boundary_s" and entry["source"] == source
        assert entry["layer"] == layer and entry["workloads"] == [CELL]
        with open(os.path.join(ROOT, f"benchmark/metrics/{name}.json")) as handle:
            spec = json.load(handle)
        assert spec["reader"] == "window_counter"
        assert spec["params"]["per"] == "boundaries"
        harness.load_module(ROOT, bench["paths"], "readers", spec["reader"])


def test_a_traced_rehearsal_reads_the_period(routing, monkeypatch):
    """The cell at 2^13 on the CPU backend under a real profiler session (no
    device plane there, so the reduction is stood in for): every metric of
    the cell reads but what the CPU and the size cannot show, the rotation
    once a crossing, and the stages inside their parent spans."""
    import shutil

    import jax.profiler

    def stop_without_reducing(self):
        jax.profiler.stop_trace()
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return {"busy_s": 0.1, "window_s": 1.0, "programs": {}, "spans": {},
                "device_ops": [], "idle_gaps": []}

    monkeypatch.setattr(harness.Tracing, "stop_and_reduce", stop_without_reducing)
    cell = small_cell()
    result = harness.execute(cell, 4100000039, 1.0, True, time.perf_counter(), routing)
    assert result["correct"] is True and result["failed"] == 0
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert {e["name"] for e in cell.per_layer} - set(values) <= SILENT_ON_THE_CPU
    assert set(NEW) <= set(values)
    assert values["epoch.sync_committee_rotations_per_boundary"] == 1.0
    parts = sum(values[f"epoch.sync_committee_{p}_ms"] for p in ("active", "sample", "aggregate"))
    assert 0 < parts <= values["epoch.sync_committee_ms"]
    assert values["epoch.historical_summary_ms"] > 0
    # both period stages lie outside the seven stage spans: in the rest
    assert values["epoch.rest_ms"] > values["epoch.sync_committee_ms"] + values[
        "epoch.historical_summary_ms"
    ]


def test_the_reference_is_its_own():
    """The cell's reference writes the two period stages out under the
    specification's names, reuses the registry reference's stages unedited,
    and neither it nor its G1 imports anything of the program."""
    for module in (deneb_epoch_period, g1):
        with open(module.__file__) as handle:
            assert "ethereum_consensus_tpu" not in handle.read()
    with open(deneb_epoch_period.__file__) as handle:
        source = handle.read()
    for name in ("get_seed", "compute_shuffled_index", "get_next_sync_committee_indices",
                 "get_next_sync_committee", "process_sync_committee_updates",
                 "process_historical_summaries_update", "process_epoch"):
        assert f"def {name}(" in source
    with open(g1.__file__) as handle:
        assert "def eth_aggregate_pubkeys(" in handle.read()
