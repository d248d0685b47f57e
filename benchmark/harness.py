"""The harness: find a cell's files by the names in BENCHMARK.json, run its
driver once (set-up, warm-up, window, comparison), read its metrics through
the readers the metric files name, and assemble the result line.

Nothing here knows a cell, a traffic mix or a metric by name: a later PR
adds each as files of its own (README.md)."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

from . import meters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = ".bench_trace"  # inside the checkout, git-ignored, emptied per run


class BenchmarkError(Exception):
    """The benchmark's own files do not fit together."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list   # this cell's entries of BENCHMARK.json end_to_end
    per_layer: list    # this cell's entries of per_layer
    paths: list
    root: str


@dataclass
class Run:
    """What the readers read."""

    cell: Cell
    observations: dict = field(default_factory=dict)
    counters: dict = field(default_factory=dict)     # registry deltas, window
    observatory: dict = field(default_factory=dict)  # route/transfer deltas
    compiles: dict = field(default_factory=dict)     # CompileMeter delta
    trace: "dict | None" = None                      # trace_reduce.reduce()
    device: dict = field(default_factory=dict)


# -- finding files by name ---------------------------------------------------


def _read_json(path: str) -> dict:
    with open(path) as handle:
        return json.load(handle)


def _find(root: str, paths: list, *parts: str) -> str:
    for base in paths:
        candidate = os.path.join(root, base, *parts)
        if os.path.isfile(candidate):
            return candidate
    raise BenchmarkError(
        f"{os.path.join(*parts)} is under none of the benchmark's paths {paths}"
    )


def load_module(root: str, paths: list, kind: str, name: str):
    """``<path>/<kind>/<name>.py`` of whichever benchmark directory has it."""
    path = _find(root, paths, kind, f"{name}.py")
    module_name = f"_bench_{kind}_{name}"
    if module_name in sys.modules:
        return sys.modules[module_name]
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise BenchmarkError(f"BENCHMARK.json has no workload {workload!r}")
    config_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    paths = bench["paths"]
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=_read_json(os.path.join(root, config_entry["file"])),
        traffic=_read_json(_find(root, paths, "traffic", f"{entry['traffic']}.json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        paths=paths,
        root=root,
    )


def read_metrics(run: Run, entries: list) -> dict:
    """Each metric through the reader its file names. A reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    cell = run.cell
    for entry in entries:
        spec = _read_json(_find(cell.root, cell.paths, "metrics", f"{entry['name']}.json"))
        reader = load_module(cell.root, cell.paths, "readers", spec["reader"])
        value = reader.read(spec.get("params", {}), run)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


# -- spans and the profiler ---------------------------------------------------


@contextmanager
def span(name: str):
    """A harness span on the profiler's clock (a no-op when no trace runs)."""
    import jax.profiler

    with jax.profiler.TraceAnnotation(f"bench:{name}"):
        yield


class Tracing:
    """The profiler over the window. The trace is written under the
    checkout, reduced and deleted."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def start(self) -> None:
        import jax.profiler

        shutil.rmtree(self.log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1  # TraceAnnotation spans, not every TraceMe
        options.enable_hlo_proto = False  # the reduction reads events only
        jax.profiler.start_trace(self.log_dir, profiler_options=options)

    def stop_and_reduce(self) -> dict:
        import jax.profiler

        from . import trace_reduce

        t0 = time.perf_counter()
        jax.profiler.stop_trace()  # some 27 us for each device event
        t1 = time.perf_counter()
        try:
            path = trace_reduce.find_xplane(self.log_dir)
            trace = trace_reduce.load(path)
            t2 = time.perf_counter()
            reduced = trace_reduce.reduce(trace)
            log("trace", {
                "inventory": trace.inventory,
                # the whole window, what the harness does between the timed
                # operations included; the per-layer idle share is over the
                # spans its metric file names
                "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
                "spans": reduced["spans"],
                "xplane_bytes": os.path.getsize(path), "stop_s": t1 - t0,
                "load_s": t2 - t1, "reduce_s": time.perf_counter() - t2,
            })
            return reduced
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


# -- one run -------------------------------------------------------------------


def log(kind: str, record: dict) -> None:
    """An earlier line of standard output: never the last."""
    print(json.dumps({"bench": kind, **record}, default=str), flush=True)


def device_record() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes() -> int:
    """The peak on the fullest chip, where the backend reports it."""
    import jax

    peaks = []
    for device in jax.devices():
        stats = device.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def default_install() -> None:
    """``ops.install()`` with its defaults: no threshold is overridden and no
    environment variable is set by the benchmark."""
    from ethereum_consensus_tpu import ops

    ops.install()


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, install=default_install) -> dict:
    """One run of one cell; returns the result line's object. ``install`` is
    the tests' seam: on the CPU they lower the routing thresholds there,
    which the command never does."""
    from ethereum_consensus_tpu import _jax_cache, ops  # noqa: F401  (cache on)
    from ethereum_consensus_tpu.telemetry import device as tel_device

    tel_device.start()
    meter = meters.CompileMeter()
    device = device_record()
    log("start", {"cell": cell.name, "seed": seed, "seconds": seconds,
                  "trace": trace, "device": device,
                  "compile_cache": _jax_cache.status()})
    driver_module = load_module(cell.root, cell.paths, "drivers", cell.traffic["driver"])
    driver = driver_module.Driver(cell, seed)

    t0 = time.perf_counter()
    driver.prepare()   # the world and the plain reference: before install
    log("prepared", {"s": time.perf_counter() - t0, **driver.facts()})
    install()
    t0 = time.perf_counter()
    warm_up_failed = 0
    try:
        driver.warm_up()
    except Exception as error:  # the window and the comparison still run
        log("warm_up_failed", {"error": repr(error)})
        warm_up_failed = 1
    meters.device_sync()
    log("warmed", {"s": time.perf_counter() - t0, **meter.read(),
                   "routes": meters.observatory()["routes"]})

    run = Run(cell=cell, device=device)
    log_dir = os.path.join(cell.root, TRACE_DIR, f"{cell.name}-{seed}")
    counters0, obs0, compiles0 = meters.counters(), meters.observatory(), meter.read()
    setup_s = time.perf_counter() - t_start
    tracing = Tracing(log_dir) if trace else None
    if tracing:
        tracing.start()
    try:
        with span("window"):
            run.observations = driver.measure(seconds)
            meters.device_sync()
    finally:
        # before the comparison: beside other work the stop takes several
        # times as long (my chip runs, PR 25, PERF.md section 6)
        if tracing:
            run.trace = tracing.stop_and_reduce()
    run.observations["setup_s"] = setup_s
    run.counters = meters.moved(counters0, meters.counters())
    run.observatory = meters.observatory_delta(obs0, meters.observatory())
    compiles1 = meter.read()
    run.compiles = {k: compiles1[k] - compiles0[k] for k in compiles1}
    device["memory_peak_bytes"] = memory_peak_bytes()
    log("window", {
        "window_s": run.observations.get("window_s"),
        "samples": {
            k: {"n": len(v), "min": min(v), "median": sorted(v)[len(v) // 2],
                "p90": sorted(v)[(9 * len(v)) // 10], "max": max(v)}
            for k, v in run.observations.get("series", {}).items() if v
        },
        "compiles_in_window": run.compiles,
        "routes": run.observatory["routes"],
        "transfers": run.observatory["transfers"],
        "declines": {n: d for n, d in run.counters.items() if meters.is_decline(n)},
    })

    # the comparison: after the window has closed and the peak has been read
    t0 = time.perf_counter()
    compared = driver.verify(run.observations)
    compared.append({"name": "warm_up_failed", "value": warm_up_failed, "limit": 0})
    log("verified", {"s": time.perf_counter() - t0})
    attempted, failed = driver.tally(run)
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]

    result = {
        "correct": all(c["value"] <= c["limit"] for c in compared),
        "attempted": attempted,
        "failed": failed,
        "metrics": read_metrics(run, cell.per_layer if trace else cell.end_to_end),
        "device": device,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": run.trace["device_ops"],
            "idle_gaps": run.trace["idle_gaps"],
        }
        log("programs", {"programs": run.trace["programs"]})
    result["compared"] = {
        c["name"]: {"value": c["value"], "limit": c["limit"]} for c in compared
    }
    tel_device.stop()
    return result


def report(result: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    sys.stdout.flush()
    for name, pair in result["compared"].items():
        print(f"compared {name}: value {pair['value']} limit {pair['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
