"""The reduction from a trace to busy/idle, program and gap times: its
arithmetic on events written by hand, and on a small trace recorded on the
v5e (two crossings of the epoch cell at 2^18 rows; PERF.md section 6 says
what planes and names it showed)."""

import os

import numpy as np
import pytest

from benchmark import trace_reduce
from benchmark.trace_reduce import Events, Trace

MS = 1e6  # nanoseconds


def hand_made() -> Trace:
    """A 100 ms window. Device ops: 10-20, 15-30 (overlapping: union 20),
    50-60, and one that straddles the window's end, 95-120 (5 inside).
    Spans: copy 0-40, step 40-100 with an inner root 55-100."""
    trace = Trace()
    trace.devices["/device:TPU:0"] = {
        trace_reduce.OPS_LINE: Events.of([
            ("%fusion.1 = u32[8] fusion(...)", 10 * MS, 10 * MS),
            ("%fusion.2 = u32[8] fusion(...)", 15 * MS, 15 * MS),
            ("%fusion.1 = u32[8] fusion(...)", 50 * MS, 10 * MS),
            ("%copy.3 = u32[8] copy(...)", 95 * MS, 25 * MS),
        ], rename=trace_reduce.op_name),
        trace_reduce.MODULES_LINE: Events.of([
            ("jit_step(123)", 10 * MS, 20 * MS),
            ("jit_step(456)", 50 * MS, 10 * MS),
            ("jit_other(9)", 95 * MS, 25 * MS),
        ], rename=trace_reduce.program_name),
    }
    trace.spans = Events.of([
        ("bench:window", 0.0, 100 * MS),
        ("bench:copy", 0.0, 40 * MS),
        ("bench:step", 40 * MS, 60 * MS),
        ("bench:root", 55 * MS, 45 * MS),
    ])
    return trace


def test_busy_is_the_union_clipped_to_the_window():
    got = trace_reduce.reduce(hand_made())
    assert got["window_s"] == pytest.approx(0.100)
    assert got["busy_s"] == pytest.approx(0.020 + 0.010 + 0.005)
    assert got["devices"] == 1


def test_programs_lose_their_fingerprint_and_add_up():
    programs = trace_reduce.reduce(hand_made())["programs"]
    assert programs["jit_step"] == {"seconds": pytest.approx(0.030), "count": 2}
    assert programs["jit_other"]["seconds"] == pytest.approx(0.005)


def test_ops_are_named_by_their_instruction_and_ranked():
    ops = trace_reduce.reduce(hand_made())["device_ops"]
    assert ops[0] == ["%fusion.1", pytest.approx(0.020)]
    assert [name for name, _ in ops] == ["%fusion.1", "%fusion.2", "%copy.3"]
    assert len(trace_reduce.reduce(hand_made(), top=2)["device_ops"]) == 2


def test_idle_goes_to_the_innermost_span_that_covers_it():
    gaps = dict(trace_reduce.reduce(hand_made())["idle_gaps"])
    # idle: 0-10, 30-50, 60-95 = 65 ms of the 100
    assert gaps["bench:copy"] == pytest.approx(0.010 + 0.010)   # 0-10, 30-40
    assert gaps["bench:step"] == pytest.approx(0.010)           # 40-50
    assert gaps["bench:root"] == pytest.approx(0.035)           # 60-95
    assert sum(gaps.values()) == pytest.approx(0.065)


def test_the_window_is_split_by_innermost_span():
    spans = trace_reduce.reduce(hand_made())["spans"]
    assert spans["bench:copy"] == {
        "seconds": pytest.approx(0.040), "idle_s": pytest.approx(0.020)}
    assert spans["bench:step"] == {
        "seconds": pytest.approx(0.015), "idle_s": pytest.approx(0.010)}
    assert spans["bench:root"] == {
        "seconds": pytest.approx(0.045), "idle_s": pytest.approx(0.035)}
    assert sum(s["seconds"] for s in spans.values()) == pytest.approx(0.100)


def test_the_idle_share_is_over_the_spans_the_metric_names():
    from types import SimpleNamespace

    from benchmark.readers import trace_busy

    run = SimpleNamespace(trace=trace_reduce.reduce(hand_made()))
    # step + root: 60 ms of which 45 idle; the 40 ms copy is in neither term
    assert trace_busy.read(
        {"spans": ["bench:step", "bench:root"]}, run
    ) == pytest.approx(75.0)
    assert trace_busy.read({"spans": ["bench:absent"]}, run) is None
    assert trace_busy.read({"spans": ["bench:step"]}, SimpleNamespace(trace=None)) is None


def test_the_window_defaults_to_the_extent_of_the_device_events():
    trace = hand_made()
    trace.spans = Events.of([])
    got = trace_reduce.reduce(trace)
    assert got["window_s"] == pytest.approx(0.110)
    assert dict(got["idle_gaps"]) == {
        "(no harness span)": pytest.approx(0.110 - 0.055)
    }


def test_two_devices_are_averaged():
    trace = hand_made()
    trace.devices["/device:TPU:1"] = {
        trace_reduce.OPS_LINE: Events.of([("%fusion.1 = x", 0.0, 100 * MS)]),
    }
    got = trace_reduce.reduce(trace)
    assert got["devices"] == 2
    assert got["busy_s"] == pytest.approx((0.035 + 0.100) / 2)


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce(Trace(spans=Events.of([])))


def test_union():
    start = np.array([5.0, 0.0, 1.0, 10.0])
    end = np.array([6.0, 2.0, 3.0, 11.0])
    u_start, u_end = trace_reduce._union(start, end)
    assert list(u_start) == [0.0, 5.0, 10.0] and list(u_end) == [3.0, 6.0, 11.0]


def test_the_recorded_trace():
    path = os.path.join(os.path.dirname(__file__), "epoch_small.xplane.pb.xz")
    trace = trace_reduce.load(path)
    assert list(trace.devices) == ["/device:TPU:0"]
    lines = trace.devices["/device:TPU:0"]
    assert len(lines[trace_reduce.OPS_LINE]) > len(lines[trace_reduce.MODULES_LINE]) > 0
    names = set(trace.spans.names)
    assert {"bench:window", "bench:copy", "bench:process_slots", "bench:root"} <= names
    got = trace_reduce.reduce(trace)
    assert 0 < got["busy_s"] < 0.05 * got["window_s"]  # the chip is idle here
    crossings = trace.spans.names.count("bench:process_slots")
    # the fused epoch kernel ran once a crossing; the trace prints it as
    # jit__unknown (it is jitted from a functools.partial)
    assert got["programs"]["jit__unknown"]["count"] == crossings
    assert dict(got["idle_gaps"])["bench:copy"] > 0
    # the copies are most of the window and none of the timed spans
    timed = [got["spans"][n] for n in ("bench:process_slots", "bench:root")]
    seconds = sum(s["seconds"] for s in timed)
    assert 0 < seconds < 0.5 * got["window_s"]
    busy_timed = seconds - sum(s["idle_s"] for s in timed)
    assert 0 < busy_timed <= got["busy_s"] + 1e-9
    assert busy_timed / seconds > got["busy_s"] / got["window_s"]
    assert all(len(name) <= 80 for name, _ in got["device_ops"])

