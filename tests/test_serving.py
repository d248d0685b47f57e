"""Beacon-API read data plane (serving/): client↔server round-trip
bit-identity vs the scalar oracle across forks, state_id resolution,
snapshot isolation across commits, gather discipline, and the
concurrent-reader chaos family (docs/SERVING.md).
"""

import json
import random
import sys
import urllib.request
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))

import chain_utils  # noqa: E402
from chain_utils import fresh_genesis, produce_chain, sign_block  # noqa: E402

from ethereum_consensus_tpu.api.client import Client  # noqa: E402
from ethereum_consensus_tpu.api.errors import ApiError  # noqa: E402
from ethereum_consensus_tpu.api.types import CommitteeFilter  # noqa: E402
from ethereum_consensus_tpu.executor import Executor  # noqa: E402
from ethereum_consensus_tpu.pipeline import FlushPolicy  # noqa: E402
from ethereum_consensus_tpu.scenarios import (  # noqa: E402
    bad_proposer_signature,
    bad_state_root,
    plan_storm,
    run_storm,
)
from ethereum_consensus_tpu.scenarios.harness import (  # noqa: E402
    forced_columnar,
    scalar_mode,
)
from ethereum_consensus_tpu.serving import (  # noqa: E402
    BeaconDataPlane,
    HeadStore,
)
from ethereum_consensus_tpu.serving import oracle, views  # noqa: E402
from ethereum_consensus_tpu.telemetry import flight, metrics  # noqa: E402
from ethereum_consensus_tpu.telemetry.server import (  # noqa: E402
    IntrospectionServer,
)

# the ≥3-fork conformance matrix (phase0 is covered by the smoke +
# resolution tests; these four exercise participation flags, sync
# committees, withdrawals-era credentials, and electra's containers)
FORKS = ("altair", "capella", "deneb", "electra")


@pytest.fixture(scope="module")
def fork_states():
    """{fork: committed state} at the last block of each fork segment of
    the five-boundary upgrade chain (disk-cached), plus the context."""
    state, ctx, blocks = chain_utils.produce_full_upgrade_chain(64)
    ex = Executor(state.copy(), ctx)
    out = {}
    for block in blocks:
        ex.apply_block(block)
        out[ex.state.version().name.lower()] = ex.state.copy()
    return out, ctx


@pytest.fixture()
def served():
    """(store, server, client factory) with teardown."""
    store = HeadStore()
    server = IntrospectionServer(port=0).start(start_flight=False)
    server.mount(BeaconDataPlane(store))
    try:
        yield store, server
    finally:
        store.detach()
        server.stop()


def _client(server) -> Client:
    return Client(server.url().rstrip("/"))


def _dumps(doc) -> str:
    return json.dumps(doc, sort_keys=True)


def _get_body(client, path, params=None) -> dict:
    return client.http_get(path, params=params).json()


# ---------------------------------------------------------------------------
# client↔server round-trip bit-identity vs the scalar oracle, per fork
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fork", FORKS)
def test_roundtrip_bit_identity(fork, fork_states, served):
    states, ctx = fork_states
    store, server = served
    state = states[fork]
    snap = store.publish(state.copy(), ctx)
    raw, client = snap.raw, _client(server)
    epoch = int(raw.slot) // int(ctx.SLOTS_PER_EPOCH)

    # -- validators: full list, index+pubkey subset, status filter ----------
    pubkey = "0x" + bytes(raw.validators[3].public_key).hex()
    cases = [
        ("eth/v1/beacon/states/head/validators", None,
         oracle.validators_data(raw, ctx)),
        ("eth/v1/beacon/states/head/validators", {"id": f"0,5,{pubkey},63"},
         oracle.validators_data(raw, ctx, [0, 5, 3, 63])),
        ("eth/v1/beacon/states/head/validators", {"status": "active"},
         oracle.validators_data(
             raw, ctx, None,
             {"active_ongoing", "active_exiting", "active_slashed"})),
        ("eth/v1/beacon/states/head/validator_balances", {"id": "1,2,3"},
         oracle.balances_data(raw, [1, 2, 3])),
        ("eth/v1/beacon/states/head/validator_balances", None,
         oracle.balances_data(raw)),
        (f"eth/v1/beacon/states/{snap.root_hex()}/validators/7", None,
         oracle.validators_data(raw, ctx, [7])[0]),
        ("eth/v1/beacon/states/head/committees", None,
         oracle.committees_data(raw, ctx)),
        ("eth/v1/beacon/states/head/committees",
         {"slot": str(int(raw.slot))},
         oracle.committees_data(raw, ctx, slot=int(raw.slot))),
        ("eth/v1/beacon/states/head/sync_committees", None,
         oracle.sync_committees_data(raw, ctx)),
        ("eth/v1/beacon/states/head/epoch_rewards", None,
         oracle.rewards_summary_data(raw, ctx)),
        ("eth/v1/validator/duties/proposer/" + str(epoch), None,
         oracle.proposer_duties_data(raw, ctx, epoch)),
    ]
    for path, params, expect in cases:
        served_doc = _get_body(client, path, params)["data"]
        assert _dumps(served_doc) == _dumps(expect), (
            f"{fork} {path} {params}: served != scalar oracle"
        )
        # the scalar fallback serves the SAME bytes (fresh snapshot so
        # nothing columnar is memoized)
        with scalar_mode():
            fallback_snap = store.publish(state.copy(), ctx)
            assert fallback_snap.bundle() is None
            fallback_doc = _get_body(client, path, params)["data"]
        assert _dumps(fallback_doc) == _dumps(served_doc), (
            f"{fork} {path} {params}: columnar != scalar-served bytes"
        )
        store.publish(state.copy(), ctx)  # restore a columnar head

    # -- typed client methods parse the same documents ----------------------
    summaries = client.get_validators("head", indices=[0, 5])
    assert [v.index for v in summaries] == [0, 5]
    assert summaries[0].balance == int(raw.balances[0])
    balances = client.get_balances("head", indices=[1, 2])
    assert [(b.index, b.balance) for b in balances] == [
        (1, int(raw.balances[1])), (2, int(raw.balances[2]))
    ]
    committees = client.get_committees("head", CommitteeFilter(epoch=epoch))
    assert {c.slot for c in committees} == set(
        range(epoch * int(ctx.SLOTS_PER_EPOCH),
              (epoch + 1) * int(ctx.SLOTS_PER_EPOCH))
    )
    sync = client.get_sync_committees("head")
    assert sync.validators == [
        int(v) for v in oracle.sync_committees_data(raw, ctx)["validators"]
    ]
    assert client.get_state_root("head") == snap.root
    assert client.get_fork("head") == type(raw.fork).to_json(raw.fork)
    finality = client.get_finality_checkpoints("head")
    assert finality.finalized == type(raw.finalized_checkpoint).to_json(
        raw.finalized_checkpoint
    )
    randao = client.get_randao("head")
    from ethereum_consensus_tpu.models.phase0.helpers import get_randao_mix

    assert randao == bytes(get_randao_mix(raw, epoch))
    genesis = client.get_genesis_details()
    assert genesis.genesis_time == int(raw.genesis_time)
    assert genesis.genesis_validators_root == bytes(
        raw.genesis_validators_root
    )

    # -- duties round-trip --------------------------------------------------
    dependent_root, duties = client.get_attester_duties(epoch, [0, 1, 2, 9])
    # a REAL block root (PR 8 residue closed): the last block before the
    # epoch the shuffling depends on, not the state-root placeholder
    assert dependent_root == oracle.dependent_root(
        raw, ctx, epoch, "attester", head_root=snap.block_root
    )
    assert dependent_root != snap.root
    duty_map = oracle.attester_duty_map(raw, ctx, epoch)
    expect_rows = oracle.attester_duties_data(raw, duty_map, [0, 1, 2, 9])
    assert [
        (d.validator_index, d.slot, d.committee_index,
         d.validator_committee_index)
        for d in duties
    ] == [
        (int(r["validator_index"]), int(r["slot"]),
         int(r["committee_index"]), int(r["validator_committee_index"]))
        for r in expect_rows
    ]
    root, proposers = client.get_proposer_duties(epoch)
    assert root == oracle.dependent_root(
        raw, ctx, epoch, "proposer", head_root=snap.block_root
    )
    assert root != snap.root
    assert len(proposers) == int(ctx.SLOTS_PER_EPOCH)
    assert all(
        bytes(raw.validators[d.validator_index].public_key) == d.public_key
        for d in proposers
    )


def test_phase0_validators_and_sync_committee_400(served):
    store, server = served
    state, ctx = fresh_genesis(32, "minimal")
    store.publish(state, ctx)
    client = _client(server)
    raw = store.head.raw
    doc = _get_body(client, "eth/v1/beacon/states/head/validators",
                    {"id": "0,1"})["data"]
    assert _dumps(doc) == _dumps(oracle.validators_data(raw, ctx, [0, 1]))
    with pytest.raises(ApiError) as err:
        client.get_sync_committees("head")
    assert err.value.code == 400
    with pytest.raises(ApiError) as err:
        client.get("eth/v1/beacon/states/head/epoch_rewards")
    assert err.value.code == 400


def test_bad_requests(served):
    store, server = served
    state, ctx = fresh_genesis(32, "minimal")
    store.publish(state, ctx)
    client = _client(server)
    for path, params, code in (
        ("eth/v1/beacon/states/head/validators", {"status": "nonsense"}, 400),
        ("eth/v1/beacon/states/head/validators", {"id": "zzz"}, 400),
        ("eth/v1/beacon/states/head/validators/999999", None, 404),
        ("eth/v1/beacon/states/head/committees", {"epoch": "99"}, 400),
        ("eth/v1/beacon/states/nonsense/validators", None, 404),
        ("eth/v1/beacon/states/head/nope", None, 404),
        ("eth/v1/validator/duties/proposer/99", None, 400),
    ):
        with pytest.raises(ApiError) as err:
            client.get(path, params)
        assert err.value.code == code, f"{path} {params}"


# ---------------------------------------------------------------------------
# state_id resolution over pipeline-published snapshots
# ---------------------------------------------------------------------------


def test_state_id_resolution(served):
    store, server = served
    state, ctx = fresh_genesis(64, "minimal")
    blocks = produce_chain(state, ctx, 8)
    store.attach()
    genesis_snap = store.publish(state.copy(), ctx)  # slot-0 snapshot
    ex = Executor(state.copy(), ctx)
    ex.stream(blocks, policy=FlushPolicy(window_size=3, max_in_flight=2))
    assert len(store) >= 3
    client = _client(server)

    head = store.head
    assert head.slot == 8
    # head, by slot, by root all resolve to the same document
    by_head = _get_body(client, "eth/v1/beacon/states/head/root")
    by_slot = _get_body(client, f"eth/v1/beacon/states/{head.slot}/root")
    by_root = _get_body(
        client, f"eth/v1/beacon/states/{head.root_hex()}/root"
    )
    assert by_head == by_slot == by_root
    assert by_head["data"]["root"] == head.root_hex()
    # an older retained snapshot resolves by its own slot
    older = store.snapshots()[1]
    assert older.root != head.root
    assert _get_body(
        client, f"eth/v1/beacon/states/{older.slot}/root"
    )["data"]["root"] == older.root_hex()
    # finalized: the toy chain finalizes epoch 0 → the slot-0 snapshot
    assert store.resolve("finalized") is genesis_snap
    assert _get_body(
        client, "eth/v1/beacon/states/finalized/root"
    )["data"]["root"] == genesis_snap.root_hex()
    # unknowns → 404 with the standard error envelope
    for state_id in ("4091", "0x" + "ab" * 32):
        with pytest.raises(ApiError) as err:
            client.get_state_root(state_id)
        assert err.value.code == 404


def test_resolution_matches_api_types_state_id(served):
    """The store accepts api.types.StateId objects too (the typed client
    stringifies them — this pins the untyped seam)."""
    from ethereum_consensus_tpu.api.types import StateId

    store, _ = served
    state, ctx = fresh_genesis(16, "minimal")
    snap = store.publish(state, ctx)
    assert store.resolve(StateId.HEAD) is snap
    assert store.resolve(StateId(snap.root)) is snap
    assert store.resolve(StateId(int(snap.slot))) is snap


# ---------------------------------------------------------------------------
# snapshot isolation across commits
# ---------------------------------------------------------------------------


def test_snapshot_isolation_across_commit(served):
    store, server = served
    state, ctx = fresh_genesis(64, "minimal")
    blocks = produce_chain(state, ctx, 8)
    store.attach()
    client = _client(server)
    with forced_columnar():
        ex = Executor(state.copy(), ctx)
        policy = FlushPolicy(window_size=2, max_in_flight=2)
        from ethereum_consensus_tpu.pipeline import ChainPipeline

        pipe = ChainPipeline(ex, policy=policy)
        for block in blocks[:4]:
            pipe.submit(block)
        while not pipe._sched.idle:
            pipe._settle_oldest()
        s1 = store.head
        assert s1 is not None and s1.slot == 4
        # force the column bundle to exist BEFORE the next commits, so
        # the copy-on-write discipline (not just object isolation) is
        # what keeps the response stable
        assert s1.bundle() is not None
        path = f"eth/v1/beacon/states/{s1.root_hex()}/validators"
        before = client.http_get(path).content
        # later commits mutate the live registry (participation flags,
        # balances) through the columnar bulk_store channel
        for block in blocks[4:]:
            pipe.submit(block)
        pipe.close()
    s2 = store.head
    assert s2.slot == 8 and s2.root != s1.root
    after = client.http_get(path).content
    assert after == before, "snapshot torn by a later commit"
    # and the snapshot really is frozen: served balances == the oracle
    # on the snapshot state, != the new head's
    assert _dumps(json.loads(after)["data"]) == _dumps(
        oracle.validators_data(s1.raw, ctx)
    )
    # (balances can coincide across early phase0 epochs — the roots
    # asserted distinct above are the real did-the-chain-move check)
    # column views handed to readers are write-protected
    bundle = s1.bundle()
    assert not bundle["balances"].flags.writeable
    with pytest.raises(ValueError):
        bundle["balances"][0] = 1


@pytest.mark.parametrize("bundle_built", ["before-the-writes", "after-the-writes"])
def test_frozen_snapshot_survives_writes_to_column_primary_balances(bundle_built):
    """The live state's balances are column-primary after an epoch commit
    (ssz/column_list.py): a single write there goes straight into the
    array. A snapshot is a copy, the copy shares the array with ownership
    dropped on both sides, so the live side clones before its first write
    and the frozen bundle, and the oracle's element reads on the frozen
    state, never move."""
    import numpy as np

    from ethereum_consensus_tpu.models import ops_vector
    from ethereum_consensus_tpu.models.phase0 import helpers as h
    from ethereum_consensus_tpu.serving.headstore import Snapshot
    from ethereum_consensus_tpu.ssz.column_list import ColumnList

    live, ctx = fresh_genesis(64, "minimal")
    live = live.copy()
    old = np.array(list(live.balances), dtype=np.uint64)
    committed = old + np.arange(64, dtype=np.uint64)
    ops_vector.adopt_list_column(
        live.balances, committed, committed != old, (1 << 64) - 1
    )
    assert live.balances.__class__ is ColumnList
    boxed = metrics.counter("ssz.column_list.boxed_rows").value()
    frozen = live.copy()
    snap = Snapshot(frozen, ctx, int(frozen.slot), type(frozen).hash_tree_root(frozen))
    want = committed.tolist()
    if bundle_built == "before-the-writes":
        assert snap.bundle()["balances"].tolist() == want
    for i in (0, 5, 63):  # what a block's rewards and penalties do
        h.increase_balance(live, i, 7)
        h.decrease_balance(live, i + 0, 2)
    bundle = snap.bundle()
    assert bundle["balances"].tolist() == want
    assert not bundle["balances"].flags.writeable
    assert [int(snap.raw.balances[i]) for i in range(64)] == want
    assert type(frozen).hash_tree_root(frozen) == snap.root
    moved = list(want)
    for i in (0, 5, 63):
        moved[i] += 5
    assert list(live.balances) == moved
    assert ops_vector.columns_for(live).list_column(live, "balances").tolist() == moved
    assert live.balances.__class__ is ColumnList
    assert frozen.balances.__class__ is ColumnList
    assert metrics.counter("ssz.column_list.boxed_rows").value() == boxed


def test_rollback_never_published(served):
    """A storm's rolled-back states must never reach the store: every
    published root is a committed honest-chain position."""
    store, server = served
    state, ctx = fresh_genesis(64, "minimal")
    blocks = produce_chain(state, ctx, 8)
    plan = plan_storm(8, 0.25, random.Random(3),
                      [bad_proposer_signature, bad_state_root])
    store.attach()
    report, ex = run_storm(state, ctx, blocks, plan, sign=sign_block)
    assert report.failures
    honest = Executor(state.copy(), ctx)
    honest_roots = set()
    for block in blocks:
        honest.apply_block(block)
        honest_roots.add(
            type(honest.state.data).hash_tree_root(honest.state.data)
        )
    published = {snap.root for snap in store.snapshots()}
    assert published, "storm committed nothing through the state channel"
    assert published <= honest_roots, (
        "a rolled-back or torn state was published to the data plane"
    )
    assert store.head.root == type(ex.state.data).hash_tree_root(
        ex.state.data
    )


def test_reader_chaos_during_storm():
    """PR 6 residue: N reader threads hammering the data plane during an
    invalid-block storm — no torn reads, no rolled-back state served
    (the swarm's verify recomputes every sample on its pinned root)."""
    state, ctx = fresh_genesis(64, "minimal")
    blocks = produce_chain(state, ctx, 10)
    plan = plan_storm(10, 0.2, random.Random(11),
                      [bad_proposer_signature, bad_state_root])
    report, _ = run_storm(state, ctx, blocks, plan, sign=sign_block,
                          readers=3)
    assert len(report.failures) == len(plan)
    assert report.reader_samples > 0
    assert report.reader_roots >= 1
    assert metrics.counter("scenario.reader_chaos.samples").value() > 0


# ---------------------------------------------------------------------------
# gather discipline
# ---------------------------------------------------------------------------


def test_one_gather_per_batch(served):
    store, server = served
    state, ctx = fresh_genesis(256, "minimal")
    store.publish(state, ctx)
    client = _client(server)
    client.get_validators("head", indices=[1])  # build the bundle
    for path, params in (
        ("eth/v1/beacon/states/head/validators",
         {"id": ",".join(str(i) for i in range(0, 200, 2))}),
        ("eth/v1/beacon/states/head/validator_balances",
         {"id": ",".join(str(i) for i in range(100))}),
        ("eth/v1/beacon/states/head/validators", {"status": "active"}),
        ("eth/v1/beacon/states/head/validator_balances", None),
    ):
        before_g = metrics.counter("serving.gathers").value()
        before_r = metrics.counter("serving.requests").value()
        client.get(path, params)
        assert metrics.counter("serving.gathers").value() - before_g == 1, (
            f"{path} {params}: expected exactly ONE columnar gather"
        )
        assert metrics.counter("serving.requests").value() - before_r == 1


def test_registry_snapshot_and_gather_rows():
    """The ops_vector serving surface: one bundle, read-only views, one
    fancy-index gather."""
    import numpy as np

    from ethereum_consensus_tpu.models import ops_vector

    state, _ = fresh_genesis(64, "minimal")
    cols = ops_vector.columns_for(state)
    bundle = cols.registry_snapshot()
    assert bundle is not None
    assert set(bundle) == {
        "effective_balance", "activation_epoch",
        "activation_eligibility_epoch", "exit_epoch", "withdrawable_epoch",
        "slashed", "withdrawal_prefix", "balances",
    }
    for arr in bundle.values():
        assert not arr.flags.writeable
    rows = ops_vector.gather_rows(bundle, [3, 1, 3], ("balances",))
    assert rows["balances"].tolist() == [
        int(state.balances[3]), int(state.balances[1]), int(state.balances[3])
    ]
    assert rows["balances"].flags.writeable  # caller owns the output
    codes = views.status_code_column(bundle, 0)
    assert codes.dtype == np.uint8
    expect = [
        oracle.validator_status(v, int(state.balances[i]), 0)
        for i, v in enumerate(state.validators)
    ]
    assert [views.STATUS_NAMES[c] for c in codes.tolist()] == expect


def test_status_machine_lockstep():
    """views.status_code_column vs oracle.validator_status over a
    synthetic registry hitting every status, including the slashed and
    zero-balance corners."""
    import numpy as np

    from ethereum_consensus_tpu.primitives import FAR_FUTURE_EPOCH as FAR

    epoch = 10
    rows = [
        # (elig, act, exit, wd, slashed, balance) → expected status
        ((FAR, FAR, FAR, FAR, False, 1), "pending_initialized"),
        ((5, 20, FAR, FAR, False, 1), "pending_queued"),
        ((0, 0, FAR, FAR, False, 1), "active_ongoing"),
        ((0, 0, 15, 20, False, 1), "active_exiting"),
        ((0, 0, 15, 20, True, 1), "active_slashed"),
        ((0, 0, 5, 20, False, 1), "exited_unslashed"),
        ((0, 0, 5, 20, True, 1), "exited_slashed"),
        ((0, 0, 5, 9, False, 1), "withdrawal_possible"),
        ((0, 0, 5, 9, True, 0), "withdrawal_done"),
    ]
    bundle = {
        "activation_eligibility_epoch": np.array(
            [r[0][0] for r in rows], np.uint64
        ),
        "activation_epoch": np.array([r[0][1] for r in rows], np.uint64),
        "exit_epoch": np.array([r[0][2] for r in rows], np.uint64),
        "withdrawable_epoch": np.array([r[0][3] for r in rows], np.uint64),
        "slashed": np.array([r[0][4] for r in rows], np.bool_),
        "balances": np.array([r[0][5] for r in rows], np.uint64),
    }
    codes = views.status_code_column(bundle, epoch)
    assert [views.STATUS_NAMES[c] for c in codes.tolist()] == [
        r[1] for r in rows
    ]

    class _V:  # scalar twin over the same rows
        def __init__(self, elig, act, exit_epoch, wd, slashed):
            self.activation_eligibility_epoch = elig
            self.activation_epoch = act
            self.exit_epoch = exit_epoch
            self.withdrawable_epoch = wd
            self.slashed = slashed

    assert [
        oracle.validator_status(_V(*r[0][:5]), r[0][5], epoch) for r in rows
    ] == [r[1] for r in rows]


# ---------------------------------------------------------------------------
# tier-1 smoke (make serving-smoke / folded into make bench-smoke)
# ---------------------------------------------------------------------------


@pytest.mark.serving_smoke
def test_serving_smoke(served):
    """One pipelined replay feeding the data plane; client round-trip
    of the core read endpoints vs the scalar oracle."""
    # earlier suite members latch the process-wide health gauges (storm
    # and broken-pipeline tests); this smoke's pipeline is healthy
    from ethereum_consensus_tpu.telemetry import flight

    metrics.gauge("pipeline.degraded").set(0)
    metrics.gauge("pipeline.broken").set(0)
    flight.RECORDER.clear()
    store, server = served
    state, ctx = fresh_genesis(64, "minimal")
    blocks = produce_chain(state, ctx, 6)
    store.attach()
    ex = Executor(state.copy(), ctx)
    ex.stream(blocks, policy=FlushPolicy(window_size=3, max_in_flight=2))
    client = _client(server)
    raw = store.head.raw
    assert _dumps(
        _get_body(client, "eth/v1/beacon/states/head/validators",
                  {"id": "0,1,2"})["data"]
    ) == _dumps(oracle.validators_data(raw, ctx, [0, 1, 2]))
    assert _dumps(
        _get_body(client, "eth/v1/beacon/states/head/validator_balances")[
            "data"
        ]
    ) == _dumps(oracle.balances_data(raw))
    epoch = int(raw.slot) // int(ctx.SLOTS_PER_EPOCH)
    _, duties = client.get_attester_duties(epoch, [0, 1, 2, 3])
    assert duties  # the toy registry is fully active
    # the observability half still serves on the same socket
    health = json.loads(
        urllib.request.urlopen(server.url("/healthz"), timeout=10).read()
    )
    assert health["status"] in ("ok", "degraded")


# ---------------------------------------------------------------------------
# dependent_root + the block-root index (PR 8 residue)
# ---------------------------------------------------------------------------


def test_dependent_root_is_a_real_block_root(served):
    """Duties responses carry the REAL dependent_root — the root of the
    last block before the epoch the duty shuffling reads — sourced from
    the pipeline's flight-lineage claimed block roots, resolvable
    through the HeadStore's block-root index, and bit-identical to the
    oracle recomputation from the snapshot state."""
    store, server = served
    state, ctx = fresh_genesis(64, "minimal")
    blocks = produce_chain(state, ctx, 10)
    store.attach()
    rec = flight.start()
    try:
        ex = Executor(state.copy(), ctx)
        ex.stream(blocks, policy=FlushPolicy(window_size=3, max_in_flight=2))
    finally:
        flight.stop()
    client = _client(server)
    snap = store.head
    raw = snap.raw
    epoch = int(raw.slot) // int(ctx.SLOTS_PER_EPOCH)

    lineage_block_roots = {
        bytes.fromhex(r.block_root)
        for r in rec.records()
        if r.committed and r.block_root
    }
    # the engine's claimed block roots ARE the chain's block roots
    assert lineage_block_roots == {
        type(b.message).hash_tree_root(b.message) for b in blocks
    }
    # the head snapshot carries its block root and the index resolves it
    assert snap.block_root in lineage_block_roots
    assert store.resolve("0x" + snap.block_root.hex()) is snap
    # ...and the derived (state-only) form agrees with the claimed one
    assert oracle.head_block_root(raw) == snap.block_root

    for duty, fetch in (
        ("attester", lambda: client.get_attester_duties(epoch, [0, 1])[0]),
        ("proposer", lambda: client.get_proposer_duties(epoch)[0]),
    ):
        served_root = fetch()
        expect = oracle.dependent_root(
            raw, ctx, epoch, duty, head_root=snap.block_root
        )
        assert served_root == expect, (duty, served_root.hex())
        assert served_root != snap.root, "state-root placeholder returned"
        # the dependent slot is inside the replayed chain, so the root
        # must be one of the lineage's claimed block roots
        assert served_root in lineage_block_roots, duty
        # spec form: the block root AT the dependent slot
        spe = int(ctx.SLOTS_PER_EPOCH)
        dep_slot = (epoch if duty == "proposer" else epoch - 1) * spe - 1
        if 0 <= dep_slot < int(raw.slot):
            from ethereum_consensus_tpu.models.phase0.helpers import (
                get_block_root_at_slot,
            )

            assert served_root == get_block_root_at_slot(raw, dep_slot)


def test_dependent_root_head_and_genesis_edges(served):
    """Dependent slots at or past the head resolve to the head block
    root; pre-genesis dependent slots resolve to the genesis block
    root — both derived purely from the snapshot state."""
    store, server = served
    state, ctx = fresh_genesis(64, "minimal")
    store.attach()
    snap = store.publish(state.copy(), ctx)  # slot-0 snapshot
    raw = snap.raw
    # epoch 0, attester: dependent slot is pre-genesis → genesis block root
    dep = oracle.dependent_root(raw, ctx, 0, "attester")
    assert dep == oracle.head_block_root(raw) == snap.block_root
    # pipeline-less publishes still land in the block-root index
    assert store.resolve("0x" + snap.block_root.hex()) is snap
