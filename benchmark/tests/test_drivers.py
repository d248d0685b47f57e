"""Every driver end to end on the CPU at a tiny size, and ``correct`` coming
out false when the timed path is broken underneath or a guarantee is.

``harness.execute`` is the whole of a run but the look for a chip. The
faults are planted from the ``install`` seam, which runs after the plain
reference has been taken, so they break the served path and not the
reference."""

import time

import pytest

from benchmark import harness

from benchmark.tests.faults import altered_answer, rounded_balances, unchanged_state
from benchmark.tests.rehearsal import read_benchmark, tiny

CELLS = [w["name"] for w in read_benchmark()["workloads"]]


def run(cell_name, install, seconds=0.5, trace=False, seed=(1 << 31) + 7):
    cell = tiny(harness.load_cell(cell_name))
    return harness.execute(cell, seed, seconds, trace, time.perf_counter(), install)


@pytest.mark.parametrize("cell_name", CELLS)
def test_driver_end_to_end(cell_name, routing, capsys):
    result = run(cell_name, routing)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    bench = read_benchmark()
    due = {
        m["name"] for m in bench["end_to_end"]
        if "workloads" not in m or cell_name in m["workloads"]
    }
    assert set(result["metrics"]) == due and "setup_s" in due
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "compared"  # the comparison comes last
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    harness.report(result)
    out, err = capsys.readouterr()
    assert out.rstrip().splitlines()[-1].startswith('{"correct": true')
    assert err.rstrip().splitlines()[-1] == "correct: True"
    assert "compared " in err


def test_traced_run_reports_the_per_layer_metrics(routing, monkeypatch, recorded_trace):
    """The CPU backend writes no device plane, so the reduction of the
    trace recorded on the chip stands in for this run's."""
    monkeypatch.setattr(harness.Tracing, "start", lambda self: None)
    monkeypatch.setattr(harness.Tracing, "stop_and_reduce", lambda self: recorded_trace)
    # the trace is the v5e's, so the roofline is read against its peaks
    monkeypatch.setattr(
        harness, "device_record",
        lambda: {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    )
    result = run("deneb-1m.epoch-boundary", routing, trace=True)
    assert result["correct"] is True
    names = set(result["metrics"])
    assert {"epoch.transition_s", "epoch.root_s", "device_idle_share.epoch",
            "epoch_fused.device_ms", "epoch_fused_roofline"} <= names
    assert 0 < result["device"]["busy_s"] < result["device"]["window_s"]
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert len(result["breakdown"]["idle_gaps"]) <= 10


FAULTS = [(cell, fault) for cell in CELLS for fault in (unchanged_state, altered_answer)]
CONTROLS = [(cell, rounded_balances) for cell in CELLS]


@pytest.mark.parametrize(
    "cell_name,fault", FAULTS + CONTROLS,
    ids=[f"{c}-{f.__name__}" for c, f in FAULTS + CONTROLS],
)
def test_a_broken_path_is_not_correct(cell_name, fault, routing, monkeypatch):
    def install():
        routing()
        fault(monkeypatch)

    cell = tiny(harness.load_cell(cell_name))
    result = harness.execute(cell, 11, 0.3, False, time.perf_counter(), install)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["compared"].values())
