"""Production soak: every hostility at once, sustained, with hard gates.

The scenario families (scenarios/families.py) each prove one hostility
in isolation; production is all of them CONCURRENTLY for hours. One
``SoakRunner.run()`` composes everything PRs 6-12 landed into a single
sustained mixed load:

* **pipeline replay across fork boundaries** — the full phase0→electra
  upgrade chain (tests/chain_utils.produce_full_upgrade_chain), cycled
  for thousands of flush windows under the bounded two-stage pipeline;
* **an invalid-block storm** — ``storm_fraction`` of each cycle's blocks
  corrupted by the mutator library, recovered with exact blame and the
  honest twin resumed (scenarios/mutators.py);
* **fault injection** — rotating ``FaultInjector`` plans: transient
  flush faults (retried), delayed flushes (inside the settle bound),
  and — when the mesh runtime is switched on — injected DEVICE faults
  on the sharded pairing/epoch routes (``fail_mesh``), recovered by the
  host fallback with the decline journaled;
* **read traffic** — a ``ReaderSwarm`` hammering the Beacon-API data
  plane and SSE subscribers on ``/events`` for the whole run, verified
  against the scalar oracle at the end (no torn reads, no rolled-back
  state served);
* **pool ingestion** — a ``PoolSpammer`` feeding hostile gossip through
  ``admit_attestation`` against the rotating heads (accounting
  contract: no silent drops), plus a DETERMINISTIC equivocation feed
  through ``admit_attestation_batch`` whose double AND surround votes
  must surface slashings that EXECUTE in soak-produced blocks.

Three hard gates fold into ``report["ok"]`` (docs/SOAK.md):

1. **SLOs** — p99 ``pipeline.verify_s`` / ``pipeline.settle_s`` /
   ``serving.gather_s`` bounded straight off the reservoir histograms
   (telemetry/metrics.py), and ``/healthz`` answering ``ok`` at every
   cycle's sample — which is why the soak's fault mix deliberately
   excludes worker-death (that lane legitimately latches the
   ``degraded`` gauge and belongs to the faults family, not a
   steady-state soak);
2. **flat RSS** — the ``LeakSentinel`` (sentinel.py): post-warmup RSS
   growth within budget and every watched structure census inside its
   declared bound;
3. **bit-identity** — every cycle's committed head equals the scalar
   oracle's root, every corruption blamed exactly, and the equivocation
   ledger + surfaced slashings of the live run identical to a clean
   refeed of the recorded admission schedule.

The run additionally executes with the causal trace plane ACTIVE
(telemetry/spans.py): a fourth ``trace`` gate folds into ``ok`` —
every SLO histogram's worst-N exemplar table must name at least one
trace_id that resolves into a connected admission→settle span tree,
settled windows must actually have linked (``trace.windows_linked``),
and an SLO breach or sentinel trip names its exemplar/slow trace ids
so the tail is a ``/trace`` lookup away, not a re-run.
"""

from __future__ import annotations

import json
import os
import random
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

from ..error import Error
from ..executor import Executor
from ..pipeline import ChainPipeline, FaultInjector, FlushPolicy
from ..scenarios.harness import (
    PoolSpammer,
    ReaderSwarm,
    _advance_to_slot,
    forced_columnar,
    oracle_replay,
)
from ..scenarios.mutators import MUTATORS, MutationEnv, by_name, plan_storm
from ..telemetry import flight as _flight
from ..telemetry import memory as _memory
from ..telemetry import metrics as _metrics
from ..telemetry import spans as _spans
from ..utils import trace
from .sentinel import LeakSentinel

__all__ = ["SoakConfig", "SoakRunner", "run_soak", "load_profile",
           "DEFAULT_PROFILE_PATH"]

# the shipped per-deployment profile (ROADMAP soak residue → ISSUE 15):
# the catastrophe-catcher defaults, as a FILE a deployment can copy and
# tighten — p99 SLO bounds, RSS budget/ceiling, and the bench epoch
# configs' memory ceilings all live here (docs/SOAK.md)
DEFAULT_PROFILE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "profiles", "default.json"
)

# the p99 SLO histograms (gate 1) — also the histograms whose exemplar
# tables the trace gate resolves into connected causal trees
_SLO_HISTOGRAMS = ("pipeline.verify_s", "pipeline.settle_s",
                   "serving.gather_s")


def _parse_flat_toml(text: str) -> dict:
    """A minimal TOML subset parser (``[section]`` + ``key = value``
    with ints/floats/bools/quoted strings) for py3.10 boxes without
    ``tomllib`` — exactly the shape a soak profile needs, nothing
    more. Full TOML goes through ``tomllib`` when available."""
    out: dict = {}
    section = out
    for raw_line in text.splitlines():
        line = raw_line.split("#", 1)[0].strip()
        if not line:
            continue
        if line.startswith("[") and line.endswith("]"):
            section = out.setdefault(line[1:-1].strip(), {})
            continue
        if "=" not in line:
            raise ValueError(f"unparsable profile line: {raw_line!r}")
        key, value = (part.strip() for part in line.split("=", 1))
        if value.lower() in ("true", "false"):
            section[key] = value.lower() == "true"
        elif value.startswith(('"', "'")) and value.endswith(value[0]):
            section[key] = value[1:-1]
        else:
            try:
                section[key] = int(value)
            except ValueError:
                section[key] = float(value)
    return out


def load_profile(path: "str | None" = None) -> dict:
    """The deployment profile document: JSON or TOML by extension
    (``tomllib`` when the interpreter has it, the flat-subset parser
    otherwise). ``None`` loads the shipped default profile."""
    path = path or DEFAULT_PROFILE_PATH
    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".toml"):
        try:
            import tomllib  # py3.11+

            return tomllib.loads(raw.decode("utf-8"))
        except ModuleNotFoundError:
            return _parse_flat_toml(raw.decode("utf-8"))
    return json.loads(raw)


class SoakConfig:
    """One soak's shape. Defaults are the ``make soak-smoke`` scale; a
    sustained run raises cycles/deadline/spam."""

    __slots__ = (
        "validator_count", "atts_per_block", "cycles", "deadline_s",
        "min_windows", "storm_fraction", "policy", "readers",
        "sse_subscribers", "pool_spam_rounds", "equivocate_every",
        "rss_budget_mb", "rss_warmup_cycles", "rss_ceiling_mb",
        "retainers", "seed",
        "slo_verify_p99_s", "slo_settle_p99_s", "slo_gather_p99_s",
        "mesh_faults", "check_columns_every", "memory_ceilings",
    )

    def __init__(self, validator_count: int = 64, atts_per_block: int = 2,
                 cycles: int = 8, deadline_s: float = 300.0,
                 min_windows: int = 40, storm_fraction: float = 0.10,
                 policy: "FlushPolicy | None" = None, readers: int = 2,
                 sse_subscribers: int = 1, pool_spam_rounds: int = 40,
                 equivocate_every: int = 2, rss_budget_mb: float = 96.0,
                 rss_warmup_cycles: int = 2, retainers=(), seed: int = 0x50AC,
                 slo_verify_p99_s: float = 2.0,
                 slo_settle_p99_s: float = 10.0,
                 slo_gather_p99_s: float = 0.25,
                 mesh_faults: "bool | None" = None,
                 check_columns_every: int = 4,
                 rss_ceiling_mb: "float | None" = None,
                 memory_ceilings: "dict | None" = None):
        self.validator_count = int(validator_count)
        self.atts_per_block = int(atts_per_block)
        self.cycles = int(cycles)
        self.deadline_s = float(deadline_s)
        self.min_windows = int(min_windows)
        self.storm_fraction = float(storm_fraction)
        # the soak default IS the auto-sized lane policy (ROADMAP PR 12
        # residue): verify_lanes unset resolves to min(cores, devices)
        self.policy = policy or FlushPolicy(
            window_size=2, max_in_flight=2, checkpoint_interval=2,
            settle_timeout_s=60.0, flush_retries=2, retry_backoff_s=0.01,
        )
        self.readers = int(readers)
        self.sse_subscribers = int(sse_subscribers)
        self.pool_spam_rounds = int(pool_spam_rounds)
        self.equivocate_every = max(1, int(equivocate_every))
        self.rss_budget_mb = float(rss_budget_mb)
        self.rss_warmup_cycles = int(rss_warmup_cycles)
        self.retainers = tuple(retainers)  # (cycle, state) callables
        self.seed = int(seed)
        self.slo_verify_p99_s = float(slo_verify_p99_s)
        self.slo_settle_p99_s = float(slo_settle_p99_s)
        self.slo_gather_p99_s = float(slo_gather_p99_s)
        # None = follow the runtime (inject device faults exactly when
        # ECT_MESH is switched on); True/False force it for tests
        self.mesh_faults = mesh_faults
        self.check_columns_every = max(1, int(check_columns_every))
        # per-deployment memory envelope (ISSUE 15): an ABSOLUTE peak
        # ceiling the flat-RSS gate additionally asserts (None = growth
        # budget only — the shipped catastrophe-catcher default), plus
        # the profile's per-workload ceiling table (``memory_ceilings``),
        # carried through for whoever loads it
        self.rss_ceiling_mb = (
            None if rss_ceiling_mb is None else float(rss_ceiling_mb)
        )
        self.memory_ceilings = dict(memory_ceilings or {})

    @classmethod
    def from_file(cls, path: "str | None" = None,
                  **overrides) -> "SoakConfig":
        """Build a config from a deployment profile (TOML or JSON —
        ``load_profile``): ``[slo]`` p99 bounds, ``[rss]``
        budget/warmup/ceiling, ``[load]`` traffic shape, and the
        ``[memory_ceilings]`` table. Unknown keys raise (a typo'd bound
        must not silently keep the catastrophe-catcher default);
        keyword ``overrides`` win over the file."""
        doc = load_profile(path)
        kwargs: dict = {}
        slo = doc.get("slo", {})
        for key, kw in (("verify_p99_s", "slo_verify_p99_s"),
                        ("settle_p99_s", "slo_settle_p99_s"),
                        ("gather_p99_s", "slo_gather_p99_s")):
            if key in slo:
                kwargs[kw] = float(slo[key])
        rss = doc.get("rss", {})
        for key, kw in (("budget_mb", "rss_budget_mb"),
                        ("warmup_cycles", "rss_warmup_cycles"),
                        ("ceiling_mb", "rss_ceiling_mb")):
            if key in rss and rss[key] is not None:
                kwargs[kw] = rss[key]
        load = doc.get("load", {})
        allowed_load = {
            "validator_count", "atts_per_block", "cycles", "deadline_s",
            "min_windows", "storm_fraction", "readers", "sse_subscribers",
            "pool_spam_rounds", "equivocate_every", "seed",
            "check_columns_every",
        }
        unknown = set(load) - allowed_load
        if unknown:
            raise ValueError(
                f"unknown [load] profile keys: {sorted(unknown)}"
            )
        kwargs.update(load)
        if "memory_ceilings" in doc:
            kwargs["memory_ceilings"] = dict(doc["memory_ceilings"])
        unknown_sections = set(doc) - {"slo", "rss", "load",
                                       "memory_ceilings", "name", "notes"}
        if unknown_sections:
            raise ValueError(
                f"unknown profile sections: {sorted(unknown_sections)}"
            )
        kwargs.update(overrides)
        return cls(**kwargs)


class _SSESubscriber:
    """One /events SSE client counting events per kind for the run (a
    long-lived subscriber is itself soak load: the per-client queue and
    keepalive path run for the whole duration)."""

    def __init__(self, base_url: str, name: str):
        import threading

        self._lock = threading.Lock()
        self._stop = False
        self._response = None
        self.counts: dict = {}
        self.errors: list = []
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix=name)
        self._future = self._pool.submit(self._loop, base_url)

    def _should_stop(self) -> bool:
        with self._lock:
            return self._stop

    def _loop(self, base_url: str) -> None:
        try:
            response = urllib.request.urlopen(
                base_url + "/events?kinds=head,commit,rollback,broken",
                timeout=30,
            )
        except OSError as exc:
            with self._lock:
                self.errors.append(repr(exc))
            return
        with self._lock:
            self._response = response
        try:
            for raw in response:
                if self._should_stop():
                    break
                line = raw.decode("utf-8", "replace").strip()
                if line.startswith("event:"):
                    kind = line.split(":", 1)[1].strip()
                    with self._lock:
                        self.counts[kind] = self.counts.get(kind, 0) + 1
        except (OSError, ValueError):
            # closed under us by stop(): normal shutdown
            pass

    def stop(self) -> dict:
        with self._lock:
            self._stop = True
            response = self._response
        if response is not None:
            try:
                response.close()
            except OSError:
                pass
        self._future.result(timeout=30)
        self._pool.shutdown(wait=True)
        with self._lock:
            return dict(self.counts)


class SoakRunner:
    """Drives one soak (see module docstring); ``run()`` returns the
    JSON-ready report with the three gates folded into ``ok``."""

    def __init__(self, config: "SoakConfig | None" = None):
        self.config = config or SoakConfig()
        self._oracle_root_memo: "bytes | None" = None

    # -- pieces ---------------------------------------------------------------
    def _chain(self):
        from ..scenarios.families import _chain_utils

        cu = _chain_utils()
        state, ctx, blocks = cu.produce_full_upgrade_chain(
            self.config.validator_count, self.config.atts_per_block
        )
        return cu, state, ctx, blocks

    def _corrupt(self, cu, ctx, blocks, plan, prefixes) -> list:
        """The cycle's corrupted stream off the PRE-COMPUTED oracle
        prefixes (harness.build_corrupted_stream re-runs the oracle per
        call; a thousand-cycle soak amortizes it to once)."""
        stream = list(blocks)
        for i, mutator in plan.items():
            env = MutationEnv(
                ctx,
                donor=blocks[(i + 1) % len(blocks)],
                pre_state=(
                    _advance_to_slot(
                        prefixes[i], int(blocks[i].message.slot), ctx
                    )
                    if mutator.needs_sign
                    else None
                ),
                sign=cu.sign_block,
            )
            stream[i] = mutator(blocks[i], env)
        return stream

    def _injector_for(self, cycle: int, n_windows_est: int,
                      mesh_on: bool) -> "tuple[FaultInjector | None, bool]":
        """The cycle's rotating fault plan: none / transient / delayed /
        mesh. Worker-death is deliberately absent — it latches the
        ``degraded`` gauge, and this run's /healthz gate pins ``ok``
        (the faults family owns that lane). Returns (injector,
        mesh_installed)."""
        lane = cycle % 4
        if lane == 0:
            return None, False
        inj = FaultInjector()
        seq = cycle % max(1, n_windows_est)
        if lane == 1:
            inj.fail_flush(seq, times=1)
        elif lane == 2:
            inj.delay_flush(seq, seconds=0.05)
        elif lane == 3:
            if mesh_on:
                inj.fail_mesh("pairing", 1).fail_mesh("epoch", 1)
                inj.install_mesh()
                return inj, True
            inj.fail_flush(seq, times=2)
        return inj, False

    def _surround_slots(self, ctx, head_state) -> "tuple | None":
        """Pick (outer_slot, inner_slot) for the surround pair such that
        (a) both slots clear the admission inclusion window, (b) the two
        slots' committees share at least one validator (every validator
        attests once per epoch, so a cross-epoch overlap always exists —
        but a BLIND slot pair can miss it), and (c) the outer slot is
        not the double-vote slot (epoch-``E`` committees partition the
        active set, so distinct slots keep the two slashings' attester
        intersections DISJOINT — drain order can't starve either of
        slashable validators)."""
        from ..models.phase0 import helpers as h

        spe = int(ctx.SLOTS_PER_EPOCH)
        head_slot = int(head_state.slot)
        epoch = head_slot // spe
        if epoch < 3:
            return None

        def slot_members(slot: int) -> set:
            count = h.get_committee_count_per_slot(
                head_state, slot // spe, ctx
            )
            members: set = set()
            for index in range(count):
                members.update(
                    int(v)
                    for v in h.get_beacon_committee(head_state, slot, index,
                                                    ctx)
                )
            return members

        double_slot = head_slot - 1
        inner_lo = max((epoch - 1) * spe, head_slot - spe)
        for inner_slot in range(epoch * spe - 1, inner_lo - 1, -1):
            inner_members = slot_members(inner_slot)
            for outer_slot in range(epoch * spe, head_slot + 1):
                if outer_slot == double_slot:
                    continue
                if inner_members & slot_members(outer_slot):
                    return outer_slot, inner_slot
        return None

    def _equivocation_traffic(self, cu, ctx, head_state) -> "list":
        """The deterministic double + surround feed for one cycle,
        derived from the head (the same committed position every cycle,
        so the end-of-run refeed replays the identical schedule).
        Returns the attestation containers in feed order."""
        import importlib

        spe = int(ctx.SLOTS_PER_EPOCH)
        head_slot = int(head_state.slot)
        epoch = head_slot // spe
        fork_name = cu.full_upgrade_fork_at_slot(head_slot, ctx)
        electra = fork_name == "electra"
        ns = importlib.import_module(
            f"ethereum_consensus_tpu.models.{fork_name}"
        ).build(ctx.preset)

        def make(slot, **kwargs):
            if electra:
                return cu.make_attestation_electra(head_state, slot, ctx,
                                                   **kwargs)
            return cu.make_attestation(head_state, slot, 0, ctx, **kwargs)

        out = []
        # double vote: honest head vote + a properly-signed contradictory
        # vote at the same slot (same target epoch, different data)
        double_slot = head_slot - 1
        out.append(make(double_slot))
        out.append(make(double_slot, beacon_block_root=b"\x66" * 32))
        # surround pair: the later-epoch vote's (source, target) span
        # strictly contains the earlier-epoch vote's — slots picked so
        # the pair's attester intersection is provably non-empty
        pair = self._surround_slots(ctx, head_state)
        if pair is not None:
            outer_slot, inner_slot = pair
            inner = make(
                inner_slot,
                source=ns.Checkpoint(epoch=epoch - 2, root=b"\x21" * 32),
            )
            outer = make(
                outer_slot,
                source=ns.Checkpoint(epoch=epoch - 3, root=b"\x21" * 32),
            )
            out.extend((inner, outer))
        return out

    def _healthz(self, server) -> "dict | None":
        try:
            with urllib.request.urlopen(
                server.url("/healthz"), timeout=10
            ) as response:
                return json.loads(response.read())
        except OSError as exc:
            return {"status": f"unreachable: {exc!r}"}

    # -- the run --------------------------------------------------------------
    def run(self) -> dict:
        with forced_columnar():
            return self._run()

    def _run(self) -> dict:
        from ..pool import AdmissionEngine, OperationPool, produce_block
        from ..serving import BeaconDataPlane, HeadStore
        from ..telemetry.server import IntrospectionServer

        config = self.config
        cu, pre_state, ctx, blocks = self._chain()
        n_blocks = len(blocks)
        rng = random.Random(config.seed)

        # the scalar oracle, once: per-index prefixes feed the mutators'
        # re-signing AND the reader-verification map; the final state is
        # the bit-identity target of every cycle
        oracle_ex, prefixes = oracle_replay(
            pre_state, ctx, blocks, capture_at=range(n_blocks)
        )
        oracle_raw = getattr(oracle_ex.state, "data", oracle_ex.state)
        oracle_root = type(oracle_raw).hash_tree_root(oracle_raw)
        self._oracle_root_memo = bytes(oracle_root)
        states_by_root = {}
        for state in list(prefixes.values()) + [oracle_ex.state]:
            raw = getattr(state, "data", state)
            states_by_root[
                "0x" + type(raw).hash_tree_root(raw).hex()
            ] = state

        mesh_on = config.mesh_faults
        if mesh_on is None:
            from ..models.epoch_vector import _mesh_requested

            mesh_on = _mesh_requested()

        # causal tracing is ON for the whole soak: the trace gate below
        # must resolve every SLO histogram's exemplars into connected
        # admission→settle trees. A fresh recording clears the span
        # ring, and resetting the SLO exemplar tables drops any ids
        # minted by earlier runs in this process — every exemplar this
        # run reports resolves against this run's recording.
        trace_started = not _spans.is_recording()
        if trace_started:
            _spans.start_recording()
        for hist_name in _SLO_HISTOGRAMS:
            _metrics.histogram(hist_name).reset_exemplars()

        sentinel = LeakSentinel()
        store = HeadStore().attach()
        server = IntrospectionServer(port=0, sse_keepalive_s=1.0).start()
        server.mount(BeaconDataPlane(store))
        swarm = (
            # bounded retention: the swarm verifies a 4096-sample
            # reservoir offline and counts the rest — unbounded response
            # retention would read as a leak to the sentinel below
            ReaderSwarm(server.url(), n_readers=config.readers,
                        max_samples=4096)
            if config.readers
            else None
        )
        subscribers = [
            _SSESubscriber(server.url(), f"soak-sse-{i}")
            for i in range(config.sse_subscribers)
        ]
        spammer = (
            PoolSpammer(store, ctx, blocks, config.pool_spam_rounds)
            if config.pool_spam_rounds
            else None
        )
        eq_pool = OperationPool()
        eq_engine = AdmissionEngine(eq_pool, store, ctx, window_size=8)
        eq_schedule: list = []

        # the census reads come from the memory observatory's registry —
        # ONE census implementation (ISSUE 15): the process-wide owners
        # for the ring and the serving history, plus a run-local owner
        # for this soak's equivocation pool (registered here, dropped in
        # the finally — the process-wide "pool.store" owner would also
        # count the spammer's hostile-gossip pool)
        _memory.register_owner(
            "soak.eq_pool", lambda: eq_pool.memory_census()
        )
        _memory.register_owner(
            "soak.headstore", lambda: store.memory_census()
        )
        sentinel.watch_owner("flight_ring",
                             bound=_flight.RECORDER.capacity,
                             owner="flight.ring")
        sentinel.watch_owner("serving_snapshots", bound=64,
                             owner="soak.headstore")
        sentinel.watch_owner("pool_rows", bound=4096, owner="soak.eq_pool")

        metrics_base = _metrics.snapshot()
        report: dict = {"config": {
            "validators": config.validator_count,
            "chain_blocks": n_blocks,
            "cycles_planned": config.cycles,
            "storm_fraction": config.storm_fraction,
            "readers": config.readers,
            "sse_subscribers": config.sse_subscribers,
            "pool_spam_rounds": config.pool_spam_rounds,
            "verify_lanes": self.config.policy.verify_lanes,
            "mesh_faults": bool(mesh_on),
        }}
        healthz_samples = 0
        healthz_ok = True
        last_health = None
        cycles_run = 0
        failures = 0
        blame_ok = True
        roots_ok = True
        columns_ok = True
        faults: dict = {}
        final_state = None
        t0 = time.perf_counter()
        try:
            with trace.span("soak.run", cycles=config.cycles):
                for cycle in range(config.cycles):
                    if time.perf_counter() - t0 > config.deadline_s:
                        break
                    outcome = self._cycle(
                        cu, ctx, pre_state, blocks, prefixes, plan_rng=rng,
                        cycle=cycle, mesh_on=mesh_on,
                    )
                    cycles_run += 1
                    failures += outcome["failures"]
                    blame_ok = blame_ok and outcome["blame_ok"]
                    roots_ok = roots_ok and outcome["root_ok"]
                    columns_ok = columns_ok and outcome["columns_ok"]
                    for kind, count in outcome["faults"].items():
                        faults[kind] = faults.get(kind, 0) + count
                    final_state = outcome["state"]
                    _metrics.counter("soak.cycles").inc()

                    health = self._healthz(server)
                    healthz_samples += 1
                    last_health = health
                    healthz_ok = healthz_ok and (
                        health is not None and health.get("status") == "ok"
                    )
                    if cycle % config.equivocate_every == 0:
                        head_raw = getattr(final_state, "data", final_state)
                        traffic = self._equivocation_traffic(
                            cu, ctx, head_raw
                        )
                        eq_schedule.extend(a.copy() for a in traffic)
                        eq_engine.admit_attestation_batch(traffic)
                        eq_engine.settle()
                    for retainer in config.retainers:
                        retainer(cycle, final_state)
                    sentinel.sample(cycle)
        finally:
            spam_summary = spammer.stop() if spammer is not None else None
            sse_counts: dict = {}
            for subscriber in subscribers:
                for kind, count in subscriber.stop().items():
                    sse_counts[kind] = sse_counts.get(kind, 0) + count
            reader_samples = reader_roots = 0
            reader_error = None
            if swarm is not None:
                swarm.stop()
                try:
                    reader_roots = swarm.verify(states_by_root, ctx)
                    reader_samples = swarm.samples_seen
                except AssertionError as exc:
                    reader_error = str(exc)[:300]
            # detach/stop here so an exception mid-cycle can't leave the
            # process-wide commit hook subscribed or the server running
            store.detach()
            server.stop()
            # the run-local census owners die with the run (samples
            # already recorded their values; the gate reads samples)
            _memory.OBSERVATORY.unregister_owner("soak.eq_pool")
            _memory.OBSERVATORY.unregister_owner("soak.headstore")

        wall_s = time.perf_counter() - t0
        delta = _metrics.delta(metrics_base)

        # -- gate 3: bit-identity (roots + blame + ledger) --------------------
        ledger = self._ledger_identity(
            cu, ctx, eq_pool, eq_schedule, final_state, produce_block,
        )
        identity = {
            "cycle_roots_ok": roots_ok,
            "blame_ok": blame_ok,
            "columns_ok": columns_ok,
            "final_root": "0x" + bytes(oracle_root).hex(),
            "ledger": ledger,
            "ok": bool(
                roots_ok and blame_ok and columns_ok and ledger["ok"]
            ),
        }

        # -- gate 1: SLOs off the reservoir histograms ------------------------
        slo = self._slo_gate(healthz_ok, healthz_samples, last_health)

        # -- gate 2: flat RSS -------------------------------------------------
        rss = sentinel.gate(config.rss_budget_mb,
                            warmup=config.rss_warmup_cycles,
                            ceiling_mb=config.rss_ceiling_mb)
        if not rss["ok"]:
            # a sentinel trip names the run's worst traces: the windows
            # most likely to have been live while memory ratcheted
            rss["slow_trace_ids"] = [
                entry["trace_id"]
                for entry in _spans.RECORDER.slow_traces()[:8]
            ]

        # -- trace gate: exemplars resolve into connected causal trees --------
        trace_gate = self._trace_gate(delta)
        if trace_started:
            _spans.stop_recording()

        windows = delta.get("pipeline.flushes", 0)
        blocks_committed = delta.get("pipeline.blocks_committed", 0)
        queries = delta.get("serving.requests", 0)
        spam_ok = spam_summary is None or (
            spam_summary["admitted"] + sum(spam_summary["rejected"].values())
            == spam_summary["fed"]
        )
        readers_ok = reader_error is None
        report.update(
            cycles=cycles_run,
            windows=windows,
            blocks_committed=blocks_committed,
            wall_s=round(wall_s, 2),
            blocks_per_s=round(blocks_committed / wall_s, 2) if wall_s else 0,
            queries_served=queries,
            queries_per_s=round(queries / wall_s, 2) if wall_s else 0,
            storm_failures=failures,
            faults_injected=faults,
            gates={"slo": slo, "rss": rss, "identity": identity,
                   "trace": trace_gate},
            pool_spam=spam_summary,
            pool_spam_ok=spam_ok,
            readers={"samples": reader_samples, "roots": reader_roots,
                     "connection_errors": (
                         swarm.connection_errors if swarm is not None else 0
                     ),
                     "ok": readers_ok, "error": reader_error},
            sse_events=sse_counts,
            ok=bool(
                slo["ok"] and rss["ok"] and identity["ok"]
                and trace_gate["ok"] and spam_ok
                and readers_ok and windows >= config.min_windows
                and cycles_run > 0
            ),
            min_windows=config.min_windows,
        )
        return report

    def _cycle(self, cu, ctx, pre_state, blocks, prefixes, plan_rng,
               cycle: int, mesh_on: bool) -> dict:
        """One storm replay over the fixed chain: corrupt, replay with
        rollback+resume, verify blame and the committed root."""
        from ..scenarios.harness import assert_column_consistency

        config = self.config
        n_blocks = len(blocks)
        plan = plan_storm(n_blocks, config.storm_fraction, plan_rng,
                          MUTATORS)
        for index, mutator in list(plan.items()):
            # an attestation mutator drawn for an attestation-less block
            # (early upgrade-chain slots) re-rolls to the proposer-sig
            # corruption — same rollback path, no content requirement
            if mutator.name == "bad_attestation_sig" and not len(
                blocks[index].message.body.attestations
            ):
                plan[index] = by_name("bad_proposer_sig")
        stream = self._corrupt(cu, ctx, blocks, plan, prefixes)
        est_windows = max(1, n_blocks // config.policy.window_size)
        injector, mesh_installed = self._injector_for(
            cycle, est_windows, mesh_on
        )
        remaining = sorted(plan)
        blame_ok = True
        failures = 0
        ex = Executor(pre_state.copy(), ctx)
        pipe = ChainPipeline(ex, policy=config.policy,
                             fault_injector=injector)
        i = 0
        try:
            while True:
                try:
                    if i < len(stream):
                        pipe.submit(stream[i])
                        i += 1
                        continue
                    pipe.close()
                    break
                except Error as exc:
                    failures += 1
                    if not remaining:
                        blame_ok = False
                        break
                    f = remaining.pop(0)
                    if not plan[f].matches(exc):
                        blame_ok = False
                    pipe = ChainPipeline(ex, policy=config.policy,
                                         fault_injector=injector)
                    stream[f] = blocks[f]
                    i = f
                    _metrics.counter("soak.recoveries").inc()
        finally:
            if mesh_installed:
                injector.uninstall_mesh()
        blame_ok = blame_ok and not remaining
        raw = getattr(ex.state, "data", ex.state)
        columns_ok = True
        # committed head vs the scalar oracle: root compare every cycle
        # (cheap — the incremental-HTR memo makes it a cached read);
        # column consistency on its sampling interval
        root_ok = bytes(type(raw).hash_tree_root(raw)) == bytes(
            self._oracle_root(ctx, pre_state, blocks)
        )
        if cycle % config.check_columns_every == 0:
            try:
                assert_column_consistency(ex.state, where=f"cycle {cycle}")
            except AssertionError:
                columns_ok = False
        faults = {}
        if injector is not None:
            for _seq, _attempt, kind in injector.injected:
                faults[kind] = faults.get(kind, 0) + 1
        return {
            "failures": failures,
            "blame_ok": blame_ok,
            "root_ok": root_ok,
            "columns_ok": columns_ok,
            "faults": faults,
            "state": ex.state,
        }

    def _oracle_root(self, ctx, pre_state, blocks) -> bytes:
        """The honest chain's final root, computed once per runner (one
        fixed chain per run)."""
        if self._oracle_root_memo is None:
            oracle_ex, _ = oracle_replay(pre_state, ctx, blocks)
            raw = getattr(oracle_ex.state, "data", oracle_ex.state)
            self._oracle_root_memo = bytes(type(raw).hash_tree_root(raw))
        return self._oracle_root_memo

    def _slo_gate(self, healthz_ok: bool, healthz_samples: int,
                  last_health) -> dict:
        config = self.config
        quantiles = {}
        verdicts = {}
        for name, bound in zip(_SLO_HISTOGRAMS,
                               (config.slo_verify_p99_s,
                                config.slo_settle_p99_s,
                                config.slo_gather_p99_s)):
            hist = _metrics.histogram(name)
            qs = hist.quantiles((0.5, 0.9, 0.99))
            p99 = qs.get(0.99)
            quantiles[name] = {
                "p50": qs.get(0.5), "p90": qs.get(0.9), "p99": p99,
                "count": hist.summary()["count"], "bound_p99": bound,
                # the causal trace plane: which windows WERE the tail —
                # a breach names the traces to pull from /trace
                "exemplar_trace_ids": [
                    e["trace_id"] for e in hist.exemplars()
                ],
            }
            verdicts[name] = p99 is not None and p99 <= bound
        return {
            "quantiles": quantiles,
            "healthz_samples": healthz_samples,
            "healthz_all_ok": healthz_ok,
            "healthz_last": last_health,
            "ok": bool(all(verdicts.values()) and healthz_ok
                       and healthz_samples > 0),
        }

    def _trace_gate(self, delta: dict) -> dict:
        """The causal-trace verdict: every SLO histogram's exemplar
        table must hold at least one trace_id that resolves — against
        the run's own span recording — into a CONNECTED causal tree
        (one root, zero orphans), and the pipeline/pool settle paths
        must have linked windows (``trace.windows_linked`` moved).
        Whole-buffer orphans gate only while nothing was evicted — a
        ring that dropped its oldest spans can legitimately strand
        children, and that loss is already counted, not silent."""
        recorder = _spans.RECORDER
        audit = recorder.audit()
        exemplars = {}
        resolved_ok = True
        for name in _SLO_HISTOGRAMS:
            ids = [
                e["trace_id"]
                for e in _metrics.histogram(name).exemplars()
            ]
            connected = [
                t for t in ids if recorder.trace_tree(t)["connected"]
            ]
            exemplars[name] = {
                "trace_ids": ids,
                "connected": len(connected),
            }
            resolved_ok = resolved_ok and bool(connected)
        windows_linked = delta.get("trace.windows_linked", 0)
        orphans_ok = audit["dropped"] > 0 or audit["orphans"] == 0
        return {
            "windows_linked": windows_linked,
            "audit": audit,
            "exemplars": exemplars,
            "slow_traces": recorder.slow_traces()[:8],
            "ok": bool(
                resolved_ok and orphans_ok and windows_linked > 0
            ),
        }

    def _ledger_identity(self, cu, ctx, eq_pool, eq_schedule,
                         final_state, produce_block) -> dict:
        """End-of-run equivocation-ledger identity + slashing execution:
        a clean refeed of the recorded admission schedule into a fresh
        engine over the SAME final head must reproduce the ledger and
        the surfaced slashings bit-for-bit, and draining the live pool
        into produced blocks must actually slash the equivocators."""
        from ..pool import AdmissionEngine, OperationPool
        from ..serving import HeadStore

        out: dict = {"schedule": len(eq_schedule)}
        if final_state is None or not eq_schedule:
            out.update(ok=False, error="no completed cycle / empty schedule")
            return out

        live_roots = sorted(
            bytes(type(s).hash_tree_root(s)).hex()
            for s in eq_pool.attester_slashings()
        )
        live_digest = eq_pool.vote_ledger_digest()

        refeed_store = HeadStore()
        refeed_store.publish(final_state.copy(), ctx)
        refeed_pool = OperationPool()
        refeed_engine = AdmissionEngine(refeed_pool, refeed_store, ctx,
                                        window_size=8)
        refeed_engine.admit_attestation_batch(
            [a.copy() for a in eq_schedule]
        )
        refeed_engine.settle()
        refeed_roots = sorted(
            bytes(type(s).hash_tree_root(s)).hex()
            for s in refeed_pool.attester_slashings()
        )
        ledger_identical = (
            live_roots == refeed_roots
            and live_digest == refeed_pool.vote_ledger_digest()
        )

        # the surfaced slashings EXECUTE in soak-produced blocks: drain
        # the live pool block by block on top of the committed head and
        # apply each produced block through the full sequential path.
        # The feed keeps the double and surround intersections DISJOINT
        # (distinct epoch-E slots partition the active set), so drain
        # order cannot leave either slashing without a slashable index.
        surfaced = eq_pool.attester_slashings()
        surround_surfaced = any(
            int(s.attestation_1.data.target.epoch)
            != int(s.attestation_2.data.target.epoch)
            for s in surfaced
        )
        drain_ex = Executor(final_state.copy(), ctx)
        drain_store = HeadStore()
        packed: list = []
        produced_blocks = 0
        error = None

        def extras(state, slot, context):
            fork = cu.full_upgrade_fork_at_slot(int(slot), context)
            body: dict = {}
            if fork not in ("phase0", "altair"):
                body["execution_payload"] = cu.make_execution_payload_fork(
                    fork, state, context, block_number=int(slot)
                )
            if fork != "phase0":
                body["sync_aggregate"] = cu.make_sync_aggregate(
                    state, context
                )
            return body

        try:
            while eq_pool.attester_slashings() and produced_blocks < 4:
                snap = drain_store.publish(drain_ex.state.copy(), ctx)
                produced = produce_block(
                    snap, eq_pool, ctx, randao=cu.make_randao_reveal,
                    sign=cu.sign_block, body_extras=extras,
                )
                produced_blocks += 1
                packed.extend(produced.message.body.attester_slashings)
                drain_ex.apply_block(produced)
                eq_pool.prune_included(produced.message.body)
        except Exception as exc:  # noqa: BLE001 — the gate reports, never hides
            error = f"{type(exc).__name__}: {str(exc)[:200]}"
        final_raw = getattr(drain_ex.state, "data", drain_ex.state)
        slashed = {
            i for i, v in enumerate(final_raw.validators) if bool(v.slashed)
        }
        expected_slashed: set = set()
        surround_packed = False
        for slashing in packed:
            expected_slashed |= set(
                int(i) for i in slashing.attestation_1.attesting_indices
            ) & set(int(i) for i in slashing.attestation_2.attesting_indices)
            if int(slashing.attestation_1.data.target.epoch) != int(
                slashing.attestation_2.data.target.epoch
            ):
                surround_packed = True
        executed = bool(
            packed
            and expected_slashed
            and expected_slashed <= slashed
            and (surround_packed or not surround_surfaced)
        )
        out.update(
            ledger_identical=bool(ledger_identical),
            slashings_surfaced=len(live_roots),
            surround_surfaced=bool(surround_surfaced),
            surround_packed=bool(surround_packed),
            slashings_packed=len(packed),
            produced_blocks=produced_blocks,
            equivocators=sorted(expected_slashed),
            equivocators_slashed=bool(executed),
            error=error,
            ok=bool(ledger_identical and executed and error is None),
        )
        return out


def run_soak(config: "SoakConfig | None" = None) -> dict:
    """One full soak; returns the report (``report["ok"]`` folds the
    three gates — docs/SOAK.md)."""
    return SoakRunner(config).run()
