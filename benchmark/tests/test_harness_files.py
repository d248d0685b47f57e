"""Driven by data: a cell, a traffic mix and a per-layer metric that exist
only as new files are found by name, with no edit to a file that is there."""

import json
import os
import time

from benchmark import harness
from benchmark.tests.rehearsal import ROOT, read_benchmark


def write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as handle:
        json.dump(obj, handle)


def grown_checkout(tmp_path):
    """A checkout in which a later PR has added a directory of its own,
    ``bench_more``, holding a configuration (a smaller registry), a traffic
    mix (more validators missing their flags, through the same driver) and
    a metric (another statistic through the same reader), and their entries
    in BENCHMARK.json."""
    root = tmp_path / "checkout"
    root.mkdir()
    os.symlink(os.path.join(ROOT, "benchmark"), root / "benchmark")
    bench = read_benchmark()
    bench["paths"].append("bench_more")
    with open(os.path.join(ROOT, "benchmark/configs/mainnet-deneb-1m.json")) as handle:
        config = json.load(handle)
    config["validators"] = 1 << 12
    write(root / "bench_more/configs/mainnet-deneb-4k.json", config)
    write(root / "bench_more/traffic/epoch-boundary-absent.json", {
        "driver": "epoch_boundary",
        "world": {"kind": "epoch_edge", "epoch": 1, "miss_share": [0.1, 0.2]},
        "warmup_ops": 1,
        "routed_kinds": ["epoch_fused"],
        "reference": "deneb_epoch",
    })
    write(root / "bench_more/metrics/epoch.slowest_s.json", {
        "reader": "client_latency",
        "params": {"series": "boundary_s", "stat": "max"},
    })
    bench["configs"].append({
        "name": "mainnet-deneb-4k", "source": "test",
        "file": "bench_more/configs/mainnet-deneb-4k.json", "reduced": [],
        "why": "test",
    })
    bench["workloads"].append({
        "name": "deneb-4k.epoch-boundary-absent", "config": "mainnet-deneb-4k",
        "traffic": "epoch-boundary-absent", "chips": 1, "why": "test",
    })
    for metric in bench["end_to_end"]:
        if metric["name"] == "epoch_boundary_s":
            metric["workloads"].append("deneb-4k.epoch-boundary-absent")
    bench["per_layer"].append({
        "name": "epoch.slowest_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "models/epoch_vector.py + ops/shuffle.py",
        "moves": "epoch_boundary_s", "workloads": ["deneb-4k.epoch-boundary-absent"],
    })
    write(root / "BENCHMARK.json", bench)
    return str(root)


def test_new_files_are_found_by_name(tmp_path, routing, monkeypatch):
    root = grown_checkout(tmp_path)
    cell = harness.load_cell("deneb-4k.epoch-boundary-absent", root)
    assert cell.config["validators"] == 1 << 12
    assert cell.traffic["world"]["miss_share"] == [0.1, 0.2]
    assert [m["name"] for m in cell.end_to_end] == ["epoch_boundary_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["epoch.slowest_s"]

    result = harness.execute(cell, 3, 0.2, False, time.perf_counter(), routing)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"epoch_boundary_s", "setup_s"}

    # the traced run reads the new metric through the reader that is there
    monkeypatch.setattr(harness.Tracing, "start", lambda self: None)
    monkeypatch.setattr(
        harness.Tracing, "stop_and_reduce",
        lambda self: {"busy_s": 0.1, "window_s": 1.0, "programs": {},
                      "spans": {}, "device_ops": [], "idle_gaps": []},
    )
    traced = harness.execute(cell, 4, 0.2, True, time.perf_counter(), routing)
    assert set(traced["metrics"]) == {"epoch.slowest_s"}


def test_the_old_cells_are_untouched_by_the_new_files(tmp_path):
    root = grown_checkout(tmp_path)
    for workload in read_benchmark()["workloads"]:
        grown = harness.load_cell(workload["name"], root)
        plain = harness.load_cell(workload["name"])
        assert grown.config == plain.config and grown.traffic == plain.traffic
        assert grown.per_layer == plain.per_layer


def test_every_name_in_benchmark_json_has_its_file():
    bench = read_benchmark()
    for workload in bench["workloads"]:
        cell = harness.load_cell(workload["name"])
        harness.load_module(cell.root, cell.paths, "drivers", cell.traffic["driver"])
    for metric in bench["end_to_end"] + bench["per_layer"]:
        path = os.path.join(ROOT, "benchmark/metrics", metric["name"] + ".json")
        with open(path) as handle:
            spec = json.load(handle)
        harness.load_module(ROOT, bench["paths"], "readers", spec["reader"])
        cells = metric.get("workloads") or [w["name"] for w in bench["workloads"]]
        if "moves" in metric:
            moved = next(m for m in bench["end_to_end"] if m["name"] == metric["moves"])
            assert set(cells) <= set(moved.get("workloads", cells))
