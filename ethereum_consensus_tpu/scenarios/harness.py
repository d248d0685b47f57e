"""Scenario harness core: fault-injected pipeline replay verified against
the sequential scalar executor (docs/SCENARIOS.md).

The contract every scenario family asserts, after every recovery:

* **bit-identical committed state** — the pipelined replay's committed
  position equals the sequential SCALAR executor's state (columnar
  engine off: ``ECT_OPS_VECTOR=off``) at the same chain position, by
  hash_tree_root AND serialized bytes;
* **exact blame** — the structured error raised for a corrupted block
  is the one its mutator declares, surfaced in call-site order across
  window geometries (coalesced flushes settle FIFO, structural aborts
  settle earlier work first — so failures always surface in CHAIN
  order, which is what lets ``run_storm`` resume deterministically);
* **column-cache consistency** — every ``_col_cache`` resident on the
  recovered state's lists still agrees element-for-element with the
  literal SSZ values, and its ``_col_dirty`` channel drains clean (the
  delta-invalidation never leaks a stale row across rollback,
  checkpoint-restore, or a fork boundary).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager

from .. import _env
from ..error import Error
from ..executor import Executor
from ..models import ops_vector
from ..models.signature_batch import SignatureBatch, defer_flushes
from ..pipeline import ChainPipeline, FlushPolicy
from ..ssz.core import CachedRootList
from ..telemetry import flight as _flight
from ..telemetry import metrics
from ..utils import trace
from ..serving import oracle as oracle_mod
from .mutators import MutationEnv

__all__ = [
    "scalar_mode",
    "forced_columnar",
    "assert_bit_identical",
    "assert_column_consistency",
    "oracle_replay",
    "build_corrupted_stream",
    "run_storm",
    "StormReport",
    "StormFailure",
    "ReaderSwarm",
    "PoolSpammer",
]


@contextmanager
def scalar_mode():
    """Force every columnar path off for the scope — the sequential
    SCALAR oracle the families diff against."""
    with _env.override(ops_vector._DISABLE_ENV, "off"):
        yield


@contextmanager
def forced_columnar():
    """Drop the columnar engines' registry-size thresholds for the
    scope, so toy-scale scenario chains exercise the batched attestation
    path AND the columnar-primary epoch pass (models/epoch_vector.py)
    the way a 2^21 registry would."""
    from ..models import epoch_vector

    old = ops_vector.BATCH_MIN_VALIDATORS
    old_epoch = epoch_vector.EPOCH_VECTOR_MIN_VALIDATORS
    ops_vector.BATCH_MIN_VALIDATORS = 0
    epoch_vector.EPOCH_VECTOR_MIN_VALIDATORS = 0
    try:
        yield
    finally:
        ops_vector.BATCH_MIN_VALIDATORS = old
        epoch_vector.EPOCH_VECTOR_MIN_VALIDATORS = old_epoch


def _unwrap(state):
    """The raw fork-typed state under the Executor's polymorphic wrapper."""
    return getattr(state, "data", state)


def assert_bit_identical(a, b, where: str = "") -> None:
    a, b = _unwrap(a), _unwrap(b)
    ra = type(a).hash_tree_root(a)
    rb = type(b).hash_tree_root(b)
    assert ra == rb, (
        f"{where}: state roots diverge ({ra.hex()[:16]} != {rb.hex()[:16]})"
    )
    assert type(a).serialize(a) == type(b).serialize(b), (
        f"{where}: equal roots but serialized bytes diverge — "
        "hash memo corruption"
    )


def assert_column_consistency(state, where: str = "") -> None:
    """Every list-resident column cache on ``state`` must agree
    element-for-element with the literal SSZ values, and syncing must
    drain its ``_col_dirty`` channel. Lists without a cache are vacuously
    consistent (nothing resident to go stale). A column-primary list
    (ssz/column_list.py) is checked like any other: its values are read
    through the list, its column through ``list_column``, and the two
    must be one array's worth."""
    state = _unwrap(state)
    cols = ops_vector.columns_for(state)
    if cols is None:  # no numpy / engine disabled: nothing cached anywhere
        return
    vals = state.validators
    if vals.__class__ is CachedRootList and vals._col_cache is not None:
        vc = cols.validator_columns(state)  # refreshes dirty rows
        assert vc is not None, f"{where}: resident validator columns " \
            "became unreadable"
        for f in ops_vector._VAL_INT_FIELDS:
            expect = [int(getattr(v, f)) for v in vals]
            got = [int(x) for x in vc[f]]
            assert got == expect, (
                f"{where}: stale validator column {f!r} "
                f"(first divergence at index "
                f"{next(i for i, (g, e) in enumerate(zip(got, expect)) if g != e)})"
            )
        assert [bool(x) for x in vc["slashed"]] == [
            bool(v.slashed) for v in vals
        ], f"{where}: stale slashed column"
        assert [int(x) for x in vc["withdrawal_prefix"]] == [
            v.withdrawal_credentials[0] for v in vals
        ], f"{where}: stale withdrawal_prefix column"
        assert not vals._col_dirty, (
            f"{where}: _col_dirty not drained after sync: {vals._col_dirty}"
        )
    for field in ops_vector.RegistryColumns.LIST_FIELDS:
        src = getattr(state, field, None)
        if src is None or src.__class__ not in ops_vector._COLUMN_BEARING:
            continue
        if src._col_cache is None:
            continue
        arr = cols.list_column(state, field)
        assert arr is not None, f"{where}: resident {field} column " \
            "became unreadable"
        got = [int(x) for x in arr]
        expect = [int(x) for x in src]
        assert got == expect, (
            f"{where}: stale {field} column (first divergence at index "
            f"{next(i for i, (g, e) in enumerate(zip(got, expect)) if g != e)})"
        )
        assert not src._col_dirty, (
            f"{where}: {field} _col_dirty not drained after sync"
        )
    metrics.counter("scenario.column_checks").inc()


# ---------------------------------------------------------------------------
# the sequential scalar oracle
# ---------------------------------------------------------------------------


def oracle_replay(pre_state, context, blocks, capture_at=()):
    """Sequential SCALAR replay of the honest ``blocks`` from
    ``pre_state``. Returns (final executor, {index: state copy BEFORE
    applying block[index]} for every index in ``capture_at``) — the
    captured prefixes are exactly the committed positions a pipelined
    replay must recover to when block[index] is corrupted."""
    capture_at = set(capture_at)
    captured: dict = {}
    with scalar_mode():
        ex = Executor(pre_state.copy(), context)
        for i, block in enumerate(blocks):
            if i in capture_at:
                captured[i] = ex.state.copy()
            ex.apply_block(block)
    return ex, captured


def _advance_to_slot(state_wrapper, slot: int, context):
    """A copy of the wrapped state advanced to ``slot`` — UPGRADE-AWARE
    (the mutator pre-state for proposer re-signing): when ``slot``
    crosses a fork activation, the intermediate boundaries run exactly
    the executor's ladder (slots under the old fork's rules, then the
    upgrade function), so a block sitting ON an upgrade slot re-signs
    under the NEW fork's domain. Advancing with only the old fork's
    ``process_slots`` — the pre-soak behavior — produced a state whose
    fork version (and therefore signing domain) was stale, turning a
    re-signed ``bad_state_root`` corruption into a bogus
    ``InvalidBlock`` at the proposer-signature check."""
    from ..executor import _UPGRADE_FN
    from ..types import FORK_SEQUENCE, fork_module

    copied = state_wrapper.copy()
    state = copied.data
    fork = copied.version()
    target_epoch = slot // int(context.SLOTS_PER_EPOCH)
    destination = fork
    for candidate in FORK_SEQUENCE[fork + 1:]:
        if int(context.fork_activation_epoch(candidate)) <= target_epoch:
            destination = candidate
    for next_fork in FORK_SEQUENCE[fork + 1: destination + 1]:
        fork_slot = (
            int(context.fork_activation_epoch(next_fork))
            * int(context.SLOTS_PER_EPOCH)
        )
        if int(state.slot) < fork_slot:
            fork_module(fork).slot_processing.process_slots(
                state, fork_slot, context
            )
        state = getattr(fork_module(next_fork), _UPGRADE_FN[next_fork])(
            state, context
        )
        fork = next_fork
    if int(state.slot) < slot:
        fork_module(fork).slot_processing.process_slots(
            state, slot, context
        )
    return state


def build_corrupted_stream(pre_state, context, blocks, plan, sign=None,
                           with_oracle: bool = True):
    """(stream, oracle_prefixes, oracle_executor): the block list with
    every planned corruption applied, plus the scalar oracle's
    committed-prefix state for each corrupted index (what the pipeline
    must roll back to).

    Runs the scalar oracle once over the HONEST chain, capturing the
    pre-block state at every corrupted index — both the recovery target
    and the domain-correct signing state for mutators that re-sign.
    ``with_oracle=False`` (the bench shape, which only measures) skips
    that replay when no planned mutator needs a signing state; prefixes
    and the oracle executor come back empty/None."""
    if not with_oracle and any(m.needs_sign for m in plan.values()):
        with_oracle = True  # re-signing needs the pre-block states
    if with_oracle:
        oracle_ex, prefixes = oracle_replay(
            pre_state, context, blocks, capture_at=plan.keys()
        )
    else:
        oracle_ex, prefixes = None, {}
    stream = list(blocks)
    for i, mutator in plan.items():
        donor = blocks[(i + 1) % len(blocks)]
        env = MutationEnv(
            context,
            donor=donor,
            pre_state=(
                _advance_to_slot(
                    prefixes[i], int(blocks[i].message.slot), context
                )
                if mutator.needs_sign
                else None
            ),
            sign=sign,
        )
        stream[i] = mutator(blocks[i], env)
    return stream, prefixes, oracle_ex


class StormFailure:
    """One observed failure+recovery during a storm replay."""

    __slots__ = ("index", "mutator", "error", "recovery_s")

    def __init__(self, index, mutator, error, recovery_s):
        self.index = index
        self.mutator = mutator
        self.error = error
        self.recovery_s = recovery_s

    def __repr__(self) -> str:
        return (
            f"StormFailure(#{self.index} {self.mutator.name} -> "
            f"{type(self.error).__name__}, recovery {self.recovery_s * 1e3:.1f}ms)"
        )


class StormReport:
    __slots__ = ("failures", "blocks_applied", "wall_s", "stats_snapshots",
                 "reader_samples", "reader_roots", "pool_spam")

    def __init__(self):
        self.failures: list[StormFailure] = []
        self.blocks_applied = 0
        self.wall_s = 0.0
        self.stats_snapshots: list = []
        # reader-chaos evidence (run_storm(readers=N)): verified
        # response samples and the distinct snapshot roots they pinned
        self.reader_samples = 0
        self.reader_roots = 0
        # pool-spam accounting (run_storm(pool_spam=N)): fed/admitted
        # counts + per-reason rejection tallies, no silent drops
        self.pool_spam: "dict | None" = None

    @property
    def recovery_latencies(self) -> list:
        return [f.recovery_s for f in self.failures]


class ReaderSwarm:
    """N reader threads hammering the serving data plane while a storm
    replays — the concurrent-reader chaos family (PR 6 residue).

    Each reader loops over the read endpoints (validators / balances /
    single validator / root) against ``state_id=head``, recording every
    response together with the ``snapshot_root`` the data plane pins it
    to. ``verify`` then asserts the torn-read contract offline:

    * every sampled root is a COMMITTED honest chain position (the map
      of scalar-oracle states per position) — a rolled-back or partially
      applied state can never be served, because the engine publishes
      snapshots only after a window's signatures prove;
    * every response body is bit-identical to the scalar oracle's answer
      recomputed on that exact state — a response torn across two
      snapshots cannot equal any single state's document.

    Threads come from a ``ThreadPoolExecutor`` (the repo's sanctioned
    worker primitive); stop is a lock-held flag.

    ``max_samples`` bounds the RETAINED responses (every response past
    the cap is still counted in ``samples_seen``, just not kept for the
    offline verification) — a soak-length run would otherwise retain
    hundreds of MB of response bodies and read as a leak to the very
    sentinel it runs under (docs/SOAK.md). ``None`` keeps everything
    (the storm families' historical behavior)."""

    def __init__(self, base_url: str, n_readers: int = 2, ids=(0, 1, 2, 3),
                 max_samples: "int | None" = None):
        self._lock = threading.Lock()
        self._base = base_url.rstrip("/")
        self._ids = tuple(int(i) for i in ids)
        self._stop = False
        self._max_samples = max_samples
        self.samples_seen = 0  # lock-held
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, n_readers), thread_name_prefix="chaos-reader"
        )
        self._futures = [
            self._pool.submit(self._reader_loop, i) for i in range(n_readers)
        ]
        self.samples: list = []  # (endpoint, root_hex, data) — lock-held
        self.errors: list = []
        # connection-level failures (timeout, reset, refused — no HTTP
        # status): counted, not fatal. The torn-read contract is about
        # response CONTENT; a loaded box stalling one urlopen is not
        # evidence, and a genuinely dead server yields zero samples,
        # which the callers' sample assertions catch.
        self.connection_errors = 0

    def _should_stop(self) -> bool:
        with self._lock:
            return self._stop

    def _record(self, endpoint: str, doc) -> None:
        with self._lock:
            self.samples_seen += 1
            if (self._max_samples is None
                    or len(self.samples) < self._max_samples):
                self.samples.append((endpoint, doc.get("snapshot_root"),
                                     doc.get("data")))

    def _reader_loop(self, seed: int) -> None:
        import json as _json
        import urllib.request

        ids = ",".join(str(i) for i in self._ids)
        endpoints = (
            f"/eth/v1/beacon/states/head/validators?id={ids}",
            f"/eth/v1/beacon/states/head/validator_balances?id={ids}",
            f"/eth/v1/beacon/states/head/validators/{self._ids[seed % len(self._ids)]}",
            "/eth/v1/beacon/states/head/root",
        )
        at = seed  # stagger the swarm across the endpoint mix
        while not self._should_stop():
            endpoint = endpoints[at % len(endpoints)]
            at += 1
            try:
                with urllib.request.urlopen(
                    self._base + endpoint, timeout=10
                ) as response:
                    doc = _json.loads(response.read())
            except OSError as exc:
                # 404 pre-first-commit is expected; another HTTP status
                # is evidence; a connection-level failure (no status —
                # timeout/reset under load) is counted, not fatal
                code = getattr(exc, "code", None)
                if code is None:
                    with self._lock:
                        self.connection_errors += 1
                elif code != 404:
                    with self._lock:
                        self.errors.append((endpoint, repr(exc)))
                continue
            self._record(endpoint, doc)

    def stop(self) -> None:
        with self._lock:
            self._stop = True
        for future in self._futures:
            future.result(timeout=30)  # surface reader crashes
        self._pool.shutdown(wait=True)

    def verify(self, states_by_root: dict, context) -> int:
        """Assert every sample against the committed-position oracle
        map; returns the number of distinct snapshot roots observed."""
        import json as _json

        assert not self.errors, f"reader errors: {self.errors[:3]}"
        roots = set()
        for endpoint, root_hex, data in self.samples:
            assert root_hex is not None, f"{endpoint}: no snapshot_root"
            state = states_by_root.get(root_hex)
            assert state is not None, (
                f"{endpoint}: served root {root_hex} is not a committed "
                "honest chain position — a rolled-back or torn state "
                "leaked into the data plane"
            )
            roots.add(root_hex)
            raw = getattr(state, "data", state)
            if "validator_balances" in endpoint:
                expect = oracle_mod.balances_data(raw, list(self._ids))
            elif "validators?" in endpoint:
                expect = oracle_mod.validators_data(
                    raw, context, list(self._ids)
                )
            elif "/validators/" in endpoint:
                index = int(endpoint.rsplit("/", 1)[1])
                expect = oracle_mod.validators_data(raw, context, [index])[0]
            else:  # /root
                expect = {
                    "root": "0x"
                    + type(raw).hash_tree_root(raw).hex()
                }
            assert _json.dumps(data, sort_keys=True) == _json.dumps(
                expect, sort_keys=True
            ), (
                f"{endpoint}: response for {root_hex} diverges from the "
                "scalar oracle on that state — torn read"
            )
        return len(roots)


class PoolSpammer:
    """The pool-spam mutator lane of ``run_storm``: a background thread
    feeding hostile gossip (every ``families.POOL_SPAM_LANES`` shape,
    derived from the honest chain's own attestations) into an admission
    engine whose head tracks the storm's committed snapshots.

    The contract is ACCOUNTING, not geometry — the head rotates under
    the spammer, so which structured reason fires for a given message
    depends on timing; what may never happen is a silent drop: every fed
    message must settle ``admitted`` or ``rejected`` with a reason from
    the taxonomy, each rejection counted (``pool.rejected.{reason}``)
    with its one-shot trace event. (``families.pool_spam_chaos`` pins
    the head and asserts the exact per-lane reasons.)"""

    def __init__(self, store, context, blocks, rounds: int):
        from ..pool import AdmissionEngine, OperationPool

        self._lock = threading.Lock()
        self._store = store
        self._blocks = blocks
        self._rounds = int(rounds)
        self._stop = False
        self.pool = OperationPool()
        self.engine = AdmissionEngine(self.pool, store, context,
                                      window_size=8)
        self.tickets: list = []
        self._pool_exec = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pool-spammer"
        )
        self._future = self._pool_exec.submit(self._spam_loop)

    def _should_stop(self) -> bool:
        with self._lock:
            return self._stop

    def _spam_loop(self) -> None:
        from .families import build_pool_spam

        t0 = time.perf_counter()
        while self._store.head is None:
            if self._should_stop() or time.perf_counter() - t0 > 60:
                return
            time.sleep(0.01)
        donors = [
            (block.message.body.attestations[0].copy(),
             bytes(block.signature))
            for block in self._blocks
            if len(block.message.body.attestations)
        ]
        fed = 0
        for round_index in range(self._rounds):
            if self._should_stop():
                break
            honest, donor_sig = donors[round_index % len(donors)]
            tickets = [self.engine.admit_attestation(honest.copy())]
            for _lane, _reason, message in build_pool_spam(
                honest, donor_sig
            ):
                if self._should_stop():
                    break
                tickets.append(self.engine.admit_attestation(message))
            fed += len(tickets)
            with self._lock:
                self.tickets.extend(tickets)
        self.engine.settle()

    def stop(self) -> dict:
        """Join the spammer and return the accounting summary; raises if
        any message dropped silently."""
        with self._lock:
            self._stop = True
        self._future.result(timeout=120)
        self.engine.settle()
        self._pool_exec.shutdown(wait=True)
        with self._lock:
            tickets = list(self.tickets)
        unsettled = [t for t in tickets if t.status == "pending"]
        assert not unsettled, (
            f"{len(unsettled)} spam messages never settled — silent drop"
        )
        rejected: dict = {}
        for t in tickets:
            if t.status == "rejected":
                rejected[t.reason] = rejected.get(t.reason, 0) + 1
        from ..pool import REASONS

        unknown = set(rejected) - set(REASONS)
        assert not unknown, f"rejections outside the taxonomy: {unknown}"
        admitted = sum(1 for t in tickets if t.status == "admitted")
        assert admitted + sum(rejected.values()) == len(tickets), (
            "spam accounting leaked a message"
        )
        return {"fed": len(tickets), "admitted": admitted,
                "rejected": rejected}


def run_storm(pre_state, context, blocks, plan, policy=None, sign=None,
              fault_injector=None, check_states=True, check_columns=True,
              serve_port=None, readers: int = 0, pool_spam: int = 0):
    """Replay a storm-corrupted chain through the pipeline with recovery
    after every failure, asserting the full contract at each one.

    ``plan``: {block index -> BlockMutator} (``mutators.plan_storm``).
    ``sign``: ``chain_utils.sign_block`` (needed by re-signing mutators).
    ``check_states=False`` skips the per-failure bit-compare (the bench
    shape: measure recovery, still verify blame + final state).
    ``serve_port``: when set, an introspection server
    (``telemetry/server.py``) runs on 127.0.0.1:<port> for the storm's
    duration (0 = ephemeral), so an adversarial replay is observable
    live — ``/events`` streams every rollback, ``/blocks`` shows blame
    + recovery latency per corrupted slot.

    Observability (beyond the returned report): every failure observes
    ``scenario.recovery_latency_s`` (registry histogram — it shows up in
    ``/metrics`` and bench deltas) and bumps the per-mutator blame
    counter ``scenario.blame.<mutator name>``; when a flight recording
    is live, the corrupted block's lineage record is annotated with the
    measured recovery latency (``BlockLineage.recovery_s``).

    Failure order: coalesced flushes settle FIFO and structural aborts
    settle earlier queued work first, so errors surface strictly in
    chain order — each raised error is asserted against the SMALLEST
    outstanding corrupted index, and the replay resumes there with the
    block's honest twin substituted (a real node re-fetches the valid
    block). Recovery latency is measured from catching the error to
    a fresh pipeline standing ready over the recovered state (the
    engine-internal rollback already ran inside the raising submit; the
    measured tail is the verification + snapshot cost of coming back).

    ``pool_spam``: N > 0 runs the pool-spam mutator lane: a background
    ``PoolSpammer`` feeds N rounds of hostile gossip (malformed SSZ,
    garbage and wrong-domain signatures, duplicate/subset bitfields,
    future-slot attestations — ``families.POOL_SPAM_LANES``) into an
    admission engine tracking the storm's committed heads, THROUGH the
    rollbacks and recoveries. Every message must settle with a
    structured outcome — ``report.pool_spam`` carries the accounting and
    the per-reason rejection tallies; a silent drop asserts.

    ``readers``: N > 0 spawns the concurrent-reader chaos swarm
    (``ReaderSwarm``): the serving data plane (serving/handlers.py over
    a pipeline-fed ``HeadStore``) is mounted on the storm's server and N
    reader threads hammer the read endpoints THROUGH the storm — every
    rollback, recovery, and commit happening under live read traffic.
    After the replay, every sampled response is verified against the
    scalar oracle at its pinned snapshot root: no torn reads (each
    response internally consistent with exactly one committed snapshot)
    and no rolled-back state ever served. Implies a server
    (``serve_port=0`` when none was requested); verified sample counts
    land in ``report.reader_samples`` / ``report.reader_roots``.

    Returns (StormReport, final executor)."""
    policy = policy or FlushPolicy(window_size=4, max_in_flight=2,
                                   checkpoint_interval=2)
    if readers and serve_port is None:
        serve_port = 0  # chaos readers need a wire to hammer
    server = None
    store = swarm = spammer = None
    if serve_port is not None:
        from ..telemetry.server import IntrospectionServer

        server = IntrospectionServer(port=serve_port).start()
        if readers:
            from ..serving import BeaconDataPlane, HeadStore

            store = HeadStore().attach()
            server.mount(BeaconDataPlane(store))
            swarm = ReaderSwarm(server.url(), n_readers=readers)
    if pool_spam:
        if store is None:
            from ..serving import HeadStore

            store = HeadStore().attach()
        spammer = PoolSpammer(store, context, blocks, pool_spam)
    try:
        report, ex = _run_storm(pre_state, context, blocks, plan, policy,
                                sign, fault_injector, check_states,
                                check_columns)
        if spammer is not None:
            report.pool_spam = spammer.stop()
            spammer = None
            metrics.counter("scenario.pool_spam.messages").inc(
                report.pool_spam["fed"]
            )
        if swarm is not None:
            swarm.stop()
            # committed-position oracle: the scalar state AFTER each
            # honest block (rollback resumes substitute honest twins, so
            # every published snapshot is one of these positions)
            oracle_ex, pre_states = oracle_replay(
                pre_state, context, blocks, capture_at=range(len(blocks))
            )
            states_by_root = {}
            for state in list(pre_states.values()) + [oracle_ex.state]:
                raw = getattr(state, "data", state)
                root = "0x" + type(raw).hash_tree_root(raw).hex()
                states_by_root[root] = state
            report.reader_roots = swarm.verify(states_by_root, context)
            report.reader_samples = len(swarm.samples)
            metrics.counter("scenario.reader_chaos.samples").inc(
                report.reader_samples
            )
        return report, ex
    finally:
        if spammer is not None:
            spammer.stop()
        if swarm is not None:
            swarm.stop()
        if store is not None:
            store.detach()
        if server is not None:
            server.stop()


def _run_storm(pre_state, context, blocks, plan, policy, sign,
               fault_injector, check_states, check_columns):
    stream, prefixes, oracle_ex = build_corrupted_stream(
        pre_state, context, blocks, plan, sign=sign,
        with_oracle=check_states or check_columns,
    )
    remaining = sorted(plan.keys())
    report = StormReport()
    t_start = time.perf_counter()

    ex = Executor(pre_state.copy(), context)
    pipe = ChainPipeline(ex, policy=policy, fault_injector=fault_injector)
    i = 0
    with trace.span("scenario.storm", blocks=len(blocks), invalid=len(plan)):
        while True:
            try:
                if i < len(stream):
                    pipe.submit(stream[i])
                    i += 1
                    continue
                pipe.close()
                break
            except Error as exc:
                t_caught = time.perf_counter()
                assert remaining, (
                    f"unexpected failure with no corrupted block "
                    f"outstanding: {exc!r}"
                )
                f = remaining.pop(0)
                mutator = plan[f]
                assert mutator.matches(exc), (
                    f"block #{f} corrupted by {mutator.name} raised "
                    f"{type(exc).__name__}: {exc} — expected "
                    f"{mutator.expected_error.__name__}"
                )
                if check_states:
                    assert_bit_identical(
                        ex.state, prefixes[f],
                        where=f"recovery after #{f} ({mutator.name})",
                    )
                if check_columns:
                    assert_column_consistency(
                        ex.state,
                        where=f"recovery after #{f} ({mutator.name})",
                    )
                report.stats_snapshots.append(pipe.stats.snapshot())
                metrics.counter("scenario.storm.failures").inc()
                # resume: a broken pipeline accepts no further blocks —
                # restart on a fresh pipeline over the SAME executor
                # (already at the committed position), substituting the
                # failed block's HONEST twin (a real node re-fetches the
                # valid block for the slot; its descendants need it).
                # A corrupted successor raises on a later iteration.
                pipe = ChainPipeline(
                    ex, policy=policy, fault_injector=fault_injector
                )
                stream[f] = blocks[f]
                i = f
                recovery_s = time.perf_counter() - t_caught
                report.failures.append(
                    StormFailure(f, mutator, exc, recovery_s)
                )
                metrics.counter("scenario.storm.recoveries").inc()
                # recovery latency + blame into the registry (visible in
                # /metrics and bench metric deltas, not just this report)
                metrics.histogram("scenario.recovery_latency_s").observe(
                    recovery_s
                )
                metrics.counter(f"scenario.blame.{mutator.name}").inc()
                if _flight.is_recording():
                    _flight.RECORDER.annotate_recovery(
                        int(blocks[f].message.slot), recovery_s
                    )
    report.wall_s = time.perf_counter() - t_start
    report.blocks_applied = len(blocks)  # honest twins replace failures
    report.stats_snapshots.append(pipe.stats.snapshot())
    assert not remaining, f"corrupted blocks never surfaced: {remaining}"
    if oracle_ex is not None:
        assert_bit_identical(ex.state, oracle_ex.state, where="storm final")
    if check_columns:
        assert_column_consistency(ex.state, where="storm final")
    metrics.counter("scenario.storm.runs").inc()
    return report, ex


# ---------------------------------------------------------------------------
# throwaway-sink replay (checkpoint-restore support)
# ---------------------------------------------------------------------------


def replay_proven(executor, blocks, validation) -> None:
    """Re-apply already-proven blocks without re-pairing (the engine's
    own committed-position rebuild, exposed for the reorg family)."""
    throwaway = SignatureBatch()
    with defer_flushes(throwaway):
        for block in blocks:
            executor.apply_block_with_validation(block, validation)
