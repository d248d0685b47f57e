"""The profiler sink behind ``utils/trace.span`` and the per-name span
totals: while a ``jax.profiler`` session is live every facade span is also
an ``ect:<name>`` annotation in the xplane, on the clock the device events
have; while a timing sink is on, the end of a span adds to
``span.<name>.{n,ns,self_ns}``; the jitted epoch kernels have names a
device trace can print."""

import glob
import sys
import threading
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))
import chain_utils  # noqa: E402

from ethereum_consensus_tpu.models import epoch_vector  # noqa: E402
from ethereum_consensus_tpu.telemetry import metrics, spans  # noqa: E402
from ethereum_consensus_tpu.utils import trace  # noqa: E402

np = pytest.importorskip("numpy")


def _span_counters() -> dict:
    return {
        name: value
        for name, value in metrics.snapshot().items()
        if name.startswith("span.")
    }


def _moved(before: dict) -> dict:
    return {
        name: value - before.get(name, 0)
        for name, value in _span_counters().items()
        if value != before.get(name, 0)
    }


# ---------------------------------------------------------------------------
# one profiler session a process: a small columnar epoch pass inside it
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """A ``jax.profiler`` session on the CPU backend around one columnar
    epoch pass (the jitted fused route, 96 validators) and one span on a
    second thread. Returns the host plane's lines as lists of (name,
    start_ns, end_ns, stats) and the ``span.*`` counters that moved."""
    jax = pytest.importorskip("jax")
    import importlib

    import jax.profiler
    from jax.profiler import ProfileData

    from ethereum_consensus_tpu import _device_flags

    from ethereum_consensus_tpu.ssz import core as ssz_core

    patch = pytest.MonkeyPatch()
    patch.setattr(epoch_vector, "EPOCH_VECTOR_MIN_VALIDATORS", 0)
    patch.setattr(_device_flags, "SWEEPS_MIN_N", 1)
    # the dirty-group geometry shrunk (conftest's small_groups), so that
    # the balances of 96 rows are spliced by groups as a registry's are
    patch.setattr(ssz_core, "_DIRTY_GROUP_SHIFT", 2)
    patch.setattr(ssz_core, "_DIRTY_TRACK_MIN_CHUNKS", 1 << 2)
    state, ctx = chain_utils.fresh_genesis_fork("deneb", 96, "minimal")
    # a graph of its own: memos built under the shrunk geometry stay here
    state = type(state).deserialize(type(state).serialize(state))
    sp = importlib.import_module(
        "ethereum_consensus_tpu.models.deneb.slot_processing"
    )
    spe = int(ctx.SLOTS_PER_EPOCH)
    sp.process_slots(state, spe, ctx)
    state.previous_epoch_participation = [0b111] * len(state.validators)
    # a registry with something to do: three deposits no boundary has seen
    # and an activation queue of five
    far = (1 << 64) - 1
    for i in range(88, 96):
        validator = state.validators[i]
        validator.activation_epoch = far
        if i >= 93:
            validator.activation_eligibility_epoch = far
    chain_utils._strip_spec_caches(state)
    # the root the first boundary's pass marked is taken before the session
    type(state).hash_tree_root(state)

    def on_a_second_thread():
        with trace.span("test.worker", lane="second"):
            time.sleep(0.001)

    log_dir = str(tmp_path_factory.mktemp("xplane"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    assert not spans.RECORDER.enabled
    assert spans.profiler_annotation() is None
    before = _span_counters()
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        live = spans.profiler_annotation()
        with jax.profiler.TraceAnnotation("bench:process_slots"):
            sp.process_slots(state, 2 * spe, ctx)
        with jax.profiler.TraceAnnotation("bench:root"):
            type(state).hash_tree_root(state)
        worker = threading.Thread(target=on_a_second_thread)
        worker.start()
        worker.join()
        with pytest.raises(KeyError):
            with trace.span("test.raises", where="body"):
                raise KeyError("from the body")
    finally:
        jax.profiler.stop_trace()
        patch.undo()
    moved = _moved(before)
    after_stop = spans.profiler_annotation()

    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for e in line.events
                if e.name.startswith(("ect:", "bench:"))
            ]
            if events:
                lines.append(events)
    return {"lines": lines, "moved": moved, "live": live,
            "after_stop": after_stop}


def _line_with(session, name):
    (line,) = [ln for ln in session["lines"] if any(e[0] == name for e in ln)]
    return line


def _only(line, name):
    (event,) = [e for e in line if e[0] == name]
    return event


def test_registry_and_commit_events_say_what_the_pass_did(session):
    """Fields a span learns in its body (``trace.note``) are stats of its
    event in the xplane: the registry span's queue, the commit's writes."""
    line = _line_with(session, "ect:epoch_vector.pass")
    registry = _only(line, "ect:epoch_vector.registry")[3]
    commit = _only(line, "ect:epoch_vector.commit")[3]
    # minimal preset: the churn limit is min(4, max(2, 88 // 32)) = 2
    assert registry == {"queued": 3, "activated": 2}
    assert commit == {
        "validators": 96, "writes": 5, "scores_changed": 0, "eff_changed": 0,
    }


def test_a_note_outside_any_span_is_a_no_op():
    assert not spans.RECORDER.enabled
    trace.note(rows=1)  # no sink, no open span: nothing to add to
    with spans.recording():
        with trace.span("test.noted", a=1):
            trace.note(b=2)
        (record,) = [
            r for r in spans.RECORDER.records() if r.name == "test.noted"
        ]
    assert record.fields == {"a": 1, "b": 2}


def test_the_session_is_the_switch(session):
    import jax.profiler

    assert session["live"] is jax.profiler.TraceAnnotation
    assert session["after_stop"] is None


def test_epoch_spans_nest_in_the_xplane_on_the_callers_line(session):
    line = _line_with(session, "ect:epoch_vector.pass")
    outer = _only(line, "bench:process_slots")
    epoch = _only(line, "ect:transition.process_epoch")
    sync = _only(line, "ect:epoch_vector.sync")
    the_pass = _only(line, "ect:epoch_vector.pass")
    commit = _only(line, "ect:epoch_vector.commit")
    # nested as they were opened, inside the harness's span, one clock
    assert outer[1] <= epoch[1] and epoch[2] <= outer[2]
    assert epoch[1] <= sync[1] and sync[2] <= the_pass[1]
    assert the_pass[1] <= commit[1] and commit[2] <= the_pass[2] <= epoch[2]
    # the call site's fields are the event's stats
    assert the_pass[3]["fork"] == "deneb"
    assert int(the_pass[3]["validators"]) == 96
    assert int(commit[3]["validators"]) == 96


def test_the_fused_route_shows_its_copies_and_its_wait(session):
    line = _line_with(session, "ect:epoch_vector.fused")
    fused = _only(line, "ect:epoch_vector.fused")
    upload = _only(line, "ect:epoch_vector.fused.h2d")
    wait = _only(line, "ect:epoch_vector.fused.wait")
    # scores and balances come down as one uint32[4, padded]: 16 B a row
    # of the dispatched shape, the registry's rows and the inert ones
    # behind them (the copy is started before the wait, so its span may
    # find it done)
    download = _only(line, "ect:epoch_vector.fused.d2h")
    unpack = _only(line, "ect:epoch_vector.fused.unpack")
    assert fused[3]["route"] == "jit"
    assert int(fused[3]["rows"]) == int(fused[3]["validators"]) == 96
    assert int(fused[3]["padded"]) == 1 << 16  # one granule holds 96 rows
    # seven columns a row: three u64, four of one byte
    assert int(upload[3]["bytes"]) == 28 * int(fused[3]["padded"])
    assert int(download[3]["bytes"]) == 16 * int(fused[3]["padded"])
    order = [upload, wait, download, unpack]
    assert all(fused[1] <= e[1] and e[2] <= fused[2] for e in order)
    assert all(a[2] <= b[1] for a, b in zip(order, order[1:]))


def test_the_epoch_root_scope_holds_the_splice_on_the_callers_line(session):
    """The root after the pass is ``ect:transition.epoch_root`` inside the
    harness's ``bench:root``, and the balances' splice is inside it, on
    the same line, with what it did as stats."""
    line = _line_with(session, "ect:transition.epoch_root")
    outer = _only(line, "bench:root")
    scope = _only(line, "ect:transition.epoch_root")
    assert outer[1] <= scope[1] and scope[2] <= outer[2]
    # the slot roots of process_slots splice too, outside the scope
    splices = [
        e for e in line if e[0] == "ect:ssz.packed_splice"
        and scope[1] <= e[1] and e[2] <= scope[2]
    ]
    assert splices
    assert all(int(e[3]["groups"]) >= 1 and int(e[3]["bytes"]) > 0
               for e in splices)
    moved = session["moved"]
    assert moved["span.transition.epoch_root.n"] == 1
    assert (moved["span.transition.epoch_root/ssz.packed_splice.n"]
            == len(splices))


def test_a_second_threads_span_is_on_that_threads_line(session):
    main = _line_with(session, "ect:epoch_vector.pass")
    other = _line_with(session, "ect:test.worker")
    assert other is not main
    assert _only(other, "ect:test.worker")[3]["lane"] == "second"
    assert not [e for e in main if e[0] == "ect:test.worker"]


def test_a_raising_body_still_ends_span_and_annotation(session):
    line = _line_with(session, "ect:test.raises")
    event = _only(line, "ect:test.raises")
    assert event[2] >= event[1] and event[3]["where"] == "body"
    assert session["moved"]["span.test.raises.n"] == 1
    # and the thread's stack is clean: the next span is nobody's child
    with spans.recording() as recorder:
        with trace.span("test.after_raise"):
            pass
        (rec,) = recorder.records()
    assert rec.parent_id == 0


def test_a_profiler_session_alone_moves_the_totals(session):
    moved = session["moved"]
    for name in ("transition.process_epoch", "epoch_vector.sync",
                 "epoch_vector.pass", "epoch_vector.fused",
                 "epoch_vector.fused.h2d", "epoch_vector.fused.wait",
                 "epoch_vector.fused.d2h", "epoch_vector.fused.unpack",
                 "epoch_vector.commit", "test.worker"):
        assert moved[f"span.{name}.n"] >= 1, name
        assert moved[f"span.{name}.ns"] > 0, name
    # the pass covers its stages: what is its own is what they left
    stages = sum(
        ns for key, ns in moved.items()
        if key.endswith(".ns") and key.startswith("span.epoch_vector.")
        and key.count(".") == 3 and key not in (
            "span.epoch_vector.pass.ns", "span.epoch_vector.sync.ns")
    )
    own = moved["span.epoch_vector.pass.self_ns"]
    assert own == moved["span.epoch_vector.pass.ns"] - stages
    # the ring took nothing: recording was off
    assert not spans.RECORDER.enabled


# ---------------------------------------------------------------------------
# the totals under the in-memory recorder, and nothing with no sink
# ---------------------------------------------------------------------------


def _nest_of_three():
    with trace.span("nest.a"):
        time.sleep(0.002)
        with trace.span("nest.b"):
            with trace.span("nest.c"):
                time.sleep(0.001)
            with trace.span("nest.c"):
                pass
        time.sleep(0.001)


def test_self_ns_is_ns_minus_children_for_a_nest_of_three():
    before = _span_counters()
    with spans.recording():
        _nest_of_three()
    moved = _moved(before)
    a, b, c = (
        {what: moved[f"span.nest.{x}.{what}"] for what in ("n", "ns", "self_ns")}
        for x in "abc"
    )
    assert (a["n"], b["n"], c["n"]) == (1, 1, 2)
    assert c["self_ns"] == c["ns"] >= 1_000_000
    assert b["self_ns"] == b["ns"] - c["ns"] >= 0
    assert a["self_ns"] == a["ns"] - b["ns"] >= 3_000_000
    assert a["ns"] >= b["ns"] >= c["ns"]


def test_self_ns_is_per_thread():
    """A span on another thread is nobody's child here: the stack that
    knows the parent is the thread's own."""
    before = _span_counters()

    def worker():
        with trace.span("nest.worker"):
            time.sleep(0.002)

    with spans.recording():
        with trace.span("nest.main"):
            thread = threading.Thread(target=worker)
            thread.start()
            thread.join()
    moved = _moved(before)
    assert moved["span.nest.main.self_ns"] == moved["span.nest.main.ns"]
    assert moved["span.nest.worker.self_ns"] == moved["span.nest.worker.ns"]
    assert moved["span.nest.main.ns"] >= moved["span.nest.worker.ns"]


def test_an_exception_ends_the_span_and_counts_it():
    before = _span_counters()
    with spans.recording() as recorder:
        with pytest.raises(ValueError):
            with trace.span("nest.outer"):
                with trace.span("nest.raises"):
                    raise ValueError("inside")
        records = {r.name: r for r in recorder.records()}
    moved = _moved(before)
    assert moved["span.nest.raises.n"] == moved["span.nest.outer.n"] == 1
    assert (moved["span.nest.outer.self_ns"]
            == moved["span.nest.outer.ns"] - moved["span.nest.raises.ns"])
    assert "ValueError" in records["nest.raises"].error
    assert "ValueError" in records["nest.outer"].error


def _scoped_nest():
    with trace.scope("nest.scope"):
        with trace.span("nest.a"):
            with trace.span("nest.b"):
                time.sleep(0.001)
        with trace.span("nest.b"):
            pass
    with trace.span("nest.b"):  # outside: no scoped total
        pass


def test_a_scope_qualifies_what_ends_inside_it():
    before = _span_counters()
    with spans.recording():
        _scoped_nest()
    moved = _moved(before)
    assert moved["span.nest.scope.n"] == 1
    assert moved["span.nest.b.n"] == 3
    assert moved["span.nest.scope/nest.b.n"] == 2
    assert moved["span.nest.scope/nest.a.n"] == 1
    for what in ("ns", "self_ns"):
        assert (moved[f"span.nest.scope/nest.a.{what}"]
                == moved[f"span.nest.a.{what}"])
    # the scope's own time is what its direct children left: nest.a and
    # the second nest.b (the first is nest.a's only child)
    direct = moved["span.nest.scope/nest.a.ns"] + (
        moved["span.nest.scope/nest.b.ns"]
        - (moved["span.nest.a.ns"] - moved["span.nest.a.self_ns"])
    )
    assert moved["span.nest.scope.self_ns"] + direct == moved["span.nest.scope.ns"]


def test_a_scope_is_per_thread():
    """A span another thread ends while the scope is open is not inside
    it: the stack that carries the scope is the thread's own."""
    before = _span_counters()

    def worker():
        with trace.span("nest.worker"):
            pass

    with spans.recording():
        with trace.scope("nest.scope"):
            thread = threading.Thread(target=worker)
            thread.start()
            thread.join()
    moved = _moved(before)
    assert moved["span.nest.worker.n"] == 1
    assert not [name for name in moved if name.startswith("span.nest.scope/")]


def test_a_collection_is_counted_always_and_a_span_under_a_sink():
    import gc

    assert not spans.RECORDER.enabled
    generations = [f"gc.collections.gen{g}" for g in range(3)]
    snapshot = metrics.snapshot()
    before = {name: snapshot[name] for name in generations + ["gc.pause_ns"]}
    spans_before = _span_counters()
    gc.collect(1)
    snapshot = metrics.snapshot()
    assert snapshot["gc.collections.gen1"] == before["gc.collections.gen1"] + 1
    assert snapshot["gc.pause_ns"] > before["gc.pause_ns"]
    assert _moved(spans_before) == {}  # no sink: no span
    with spans.recording() as recorder:
        with trace.scope("nest.scope"):
            gc.collect(2)
        with trace.span("nest.after"):
            pass
        collections = [r for r in recorder.records() if r.name == "gc.collect"]
    moved = _moved(spans_before)
    assert metrics.snapshot()["gc.collections.gen2"] >= before["gc.collections.gen2"] + 1
    assert [r.fields["generation"] for r in collections] == [2]
    assert collections[0].fields["collected"] >= 0
    assert moved["span.gc.collect.n"] == moved["span.nest.scope/gc.collect.n"] == 1
    assert (moved["span.nest.scope.self_ns"] + moved["span.gc.collect.ns"]
            == moved["span.nest.scope.ns"])


def test_no_sink_no_totals():
    """No session and no recorder: no ``span.*`` counter moves, the
    recorder's stack is not touched, and no annotation is made."""
    assert not spans.RECORDER.enabled
    assert spans.profiler_annotation() is None
    before = _span_counters()
    mark = spans.RECORDER.mark()
    _nest_of_three()
    with pytest.raises(ValueError):
        with trace.span("nest.raises"):
            raise ValueError("inside")
    assert _moved(before) == {}
    assert spans.RECORDER.mark() == mark + 1  # no span id was taken
    assert spans.RECORDER.context() is None


def test_the_profiler_sink_never_imports_jax():
    """``telemetry`` stays importable without jax, and the sink looks only
    where jax already is: a host-only process never pays the import."""
    import subprocess

    code = (
        "import sys\n"
        "from ethereum_consensus_tpu.telemetry import spans, device\n"
        "from ethereum_consensus_tpu.utils import trace\n"
        "with trace.span('host.only', n=1):\n"
        "    pass\n"
        "assert spans.profiler_annotation() is None\n"
        "assert not [m for m in sys.modules if m == 'jax' "
        "or m.startswith('jax.')], 'jax was imported'\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=str(Path(__file__).parent.parent),
    )
    assert done.returncode == 0, done.stderr


# ---------------------------------------------------------------------------
# the kernels' names
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kernel", ["fused_epoch", "inactivity_scores", "flag_deltas",
               "apply_delta_pairs"],
)
def test_jitted_kernels_have_names_a_trace_can_print(kernel):
    """Jitted from a ``functools.partial`` the program was ``jit__unknown``
    in a device trace; each lowers to a module named after it now."""
    jax = pytest.importorskip("jax")
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    n = 8
    u64 = jnp.zeros(n, jnp.uint64)
    flag = jnp.zeros(n, bool)
    args = {
        "fused_epoch": (
            u64, u64, jnp.zeros(n, jnp.uint8), flag, flag, flag, u64,
            jnp.uint64(10**9), jnp.uint64(907), jnp.uint64(8),
            jnp.uint64(4 * 3 * 10**7), 4, 16, (14, 26, 14), 64, False, 2, 1,
        ),
        "inactivity_scores": (u64, flag, flag, 4, 16, False),
        "flag_deltas": (u64, flag, flag, 14, 8, 8, 64, False, False),
        "apply_delta_pairs": (u64, [(u64, u64)]),
    }[kernel]
    jitted = epoch_vector.jitted_kernels()[kernel].__wrapped__
    text = jitted.lower(*args).as_text()
    assert f"module @jit_{kernel}" in text
