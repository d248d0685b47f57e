"""The reader of the program's counters over the window, on a made ``Run``,
and the per-layer metrics that read the program's spans through it: each
finds its metric file and its reader, reads nothing on a program without
the spans (the parent commit, ``--trace 0``), and reads the split of a
boundary from a traced run on the CPU."""

import json
import os
import time

import pytest

from benchmark import harness
from benchmark.readers import window_counter
from benchmark.tests.rehearsal import ROOT, read_benchmark

CELL = "deneb-1m.epoch-boundary"
STAGES = ["sync", "justification", "fused", "registry", "hysteresis",
          "commit", "rotation"]


def made_run(counters, counts=None):
    return harness.Run(
        cell=None, counters=counters,
        observations={"counts": counts or {"boundaries": 4}},
    )


def test_one_counter_scaled_per_count():
    run = made_run({"span.a.ns": 8_000_000})
    params = {"counter": "span.a.ns", "scale": 1e-6, "per": "boundaries"}
    assert window_counter.read(params, run) == pytest.approx(2.0)
    # no "per": the window's total; no "scale": as counted
    assert window_counter.read({"counter": "span.a.ns"}, run) == 8_000_000


def test_a_list_is_summed_and_minus_is_taken_off():
    run = made_run({"x.ns": 10_000, "y.ns": 5_000, "z.ns": 3_000})
    params = {"counter": ["x.ns", "y.ns", "absent.ns"],
              "minus": ["z.ns", "also_absent.ns"], "per": "boundaries"}
    assert window_counter.read(params, run) == pytest.approx(3_000.0)


def test_none_where_the_first_counter_did_not_move():
    # ``run.counters`` holds only what moved (meters.moved)
    run = made_run({"y.ns": 5_000})
    assert window_counter.read({"counter": "x.ns"}, run) is None
    assert window_counter.read({"counter": ["x.ns", "y.ns"]}, run) is None
    assert window_counter.read({"counter": ["y.ns", "x.ns"]}, run) == 5_000
    # and where the window counted nothing to divide by
    empty = made_run({"x.ns": 1}, counts={"boundaries": 0})
    assert window_counter.read({"counter": "x.ns", "per": "boundaries"}, empty) is None
    assert window_counter.read({"counter": "x.ns", "per": "absent"}, empty) is None


def new_entries():
    """The per-layer metrics whose file names this reader."""
    out = []
    for entry in read_benchmark()["per_layer"]:
        path = os.path.join(ROOT, "benchmark/metrics", entry["name"] + ".json")
        with open(path) as handle:
            spec = json.load(handle)
        if spec["reader"] == "window_counter":
            out.append((entry, spec))
    return out


def test_every_new_entry_finds_its_file_and_reader():
    bench = read_benchmark()
    cell = harness.load_cell(CELL)
    entries = new_entries()
    assert [e["name"] for e, _ in entries] == [
        "epoch.sync_ms", "epoch.justification_ms", "epoch.fused_ms",
        "epoch.fused_h2d_ms", "epoch.fused_d2h_ms", "epoch.registry_ms",
        "epoch.hysteresis_ms", "epoch.commit_ms", "epoch.rotation_ms",
        "epoch.rest_ms", "epoch.h2d_mb", "epoch.d2h_mb",
    ]
    layers = {m["layer"] for m in bench["per_layer"]}
    for entry, spec in entries:
        assert entry in cell.per_layer
        assert entry["workloads"] == [CELL] and entry["better"] == "lower"
        assert entry["moves"] == "epoch_boundary_s"
        assert entry["source"] in ("program_span", "program_counter")
        assert entry["layer"] in layers
        reader = harness.load_module(ROOT, bench["paths"], "readers", spec["reader"])
        assert reader is not None and spec["params"]["per"] == "boundaries"
    # the rest is the whole transition less exactly the stage metrics
    by_name = {e["name"]: s["params"] for e, s in entries}
    assert by_name["epoch.rest_ms"]["counter"] == "span.transition.process_epoch.ns"
    assert by_name["epoch.rest_ms"]["minus"] == [
        by_name[f"epoch.{stage}_ms"]["counter"] for stage in STAGES
    ]


def test_nothing_to_read_on_a_program_without_the_spans():
    """The parent commit, and every ``--trace 0`` run: only the byte ledger
    moved, so the ten span metrics are left out and nothing raises."""
    cell = harness.load_cell(CELL)
    run = harness.Run(
        cell=cell,
        counters={"device.transfer.h2d_bytes": 4 * 28 << 20,
                  "device.transfer.d2h_bytes": 4 * 16 << 20,
                  "epoch_vector.epochs": 4},
        observations={"counts": {"boundaries": 4, "rows": 1 << 20}},
    )
    got = harness.read_metrics(run, [e for e, _ in new_entries()])
    assert set(got) == {"epoch.h2d_mb", "epoch.d2h_mb"}
    assert got["epoch.h2d_mb"]["value"] == pytest.approx(29.360128)
    assert got["epoch.d2h_mb"]["value"] == pytest.approx(16.777216)
    assert harness.read_metrics(harness.Run(cell=cell), [e for e, _ in new_entries()]) == {}


def test_a_traced_run_reads_the_split_of_a_boundary(tmp_path, routing, monkeypatch):
    """The cell's driver at 2^12 on the CPU backend under a real profiler
    session (the CPU's trace has no device plane, so the reduction is
    stood in for): all twelve read, the stages and the rest add up to the
    program's own span around the epoch, inside what the harness's clock
    saw around ``process_slots``."""
    import shutil

    import jax.profiler

    def stop_without_reducing(self):
        jax.profiler.stop_trace()
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return {"busy_s": 0.1, "window_s": 1.0, "programs": {}, "spans": {},
                "device_ops": [], "idle_gaps": []}

    monkeypatch.setattr(harness.Tracing, "stop_and_reduce", stop_without_reducing)
    root = tmp_path / "checkout"
    root.mkdir()
    os.symlink(os.path.join(ROOT, "benchmark"), root / "benchmark")
    bench = read_benchmark()
    with open(os.path.join(ROOT, bench["configs"][0]["file"])) as handle:
        config = json.load(handle)
    config["validators"] = 1 << 12
    os.makedirs(root / "small")
    with open(root / "small/config.json", "w") as handle:
        json.dump(config, handle)
    bench["configs"][0]["file"] = "small/config.json"
    with open(root / "BENCHMARK.json", "w") as handle:
        json.dump(bench, handle)

    cell = harness.load_cell(CELL, str(root))
    result = harness.execute(cell, 2147483659, 0.5, True, time.perf_counter(), routing)
    assert result["correct"] is True and result["failed"] == 0
    values = {k: v["value"] for k, v in result["metrics"].items()}
    names = [e["name"] for e, _ in new_entries()]
    assert set(names) <= set(values)
    assert all(values[n] > 0 for n in names if n != "epoch.rest_ms")
    stages = sum(values[f"epoch.{stage}_ms"] for stage in STAGES)
    whole = stages + values["epoch.rest_ms"]
    assert values["epoch.rest_ms"] >= 0
    assert values["epoch.fused_h2d_ms"] + values["epoch.fused_d2h_ms"] < values["epoch.fused_ms"]
    # the program's span is inside the harness's clock around process_slots
    assert whole <= values["epoch.transition_s"] * 1e3
    # at least the fused kernel's columns (here the lowered gates route the
    # hasher too, and its levels are in the ledger with them)
    assert values["epoch.h2d_mb"] >= (1 << 12) * 28 / 1e6
    assert values["epoch.d2h_mb"] >= (1 << 12) * 16 / 1e6
    # and the accepted per-layer metrics that need no device plane still read
    assert {"epoch.transition_s", "epoch.root_s"} <= set(values)
