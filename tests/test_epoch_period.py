"""The epoch pass at the boundary that starts a sync committee period: the
deployment ``mainnet-deneb-1m-period`` of the benchmark (the last slot of
epoch 4,351, so the crossing rotates the sync committee and appends a
historical summary) cut to 2^13 rows, with the fused kernel routed as
``ops.install`` routes it.

The program's rotation against the literal stage list and against the
plain reference (``benchmark/reference/deneb_epoch_period.py``), field by
field; the spans and counters of the two period stages, which move at a
period boundary and nowhere else; the faults the reference has to call
wrong; and the reference's own G1 arithmetic (``benchmark/reference/g1.py``)
against the program's BLS."""

import json
import os
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmark import worlds  # noqa: E402
from benchmark.reference import deneb_epoch_period, g1  # noqa: E402
from benchmark.tests import faults_period  # noqa: E402
from benchmark.worlds import keys  # noqa: E402
from ethereum_consensus_tpu import ops  # noqa: E402
from ethereum_consensus_tpu.crypto import bls  # noqa: E402
from ethereum_consensus_tpu.models.deneb import slot_processing  # noqa: E402
from ethereum_consensus_tpu.scenarios.harness import (  # noqa: E402
    assert_bit_identical,
    assert_column_consistency,
)
from ethereum_consensus_tpu.telemetry import metrics, spans  # noqa: E402

ROOT = Path(__file__).parent.parent
SMALL = 1 << 13
ENTERED = 4352
MISS_SHARE = [0.01, 0.03]
COUNTERS = ("sync_committee.rotations", "historical_summaries", "epochs", "fused.jit")
SPANS = ("epoch_vector.sync_committee", "epoch_vector.sync_committee.active",
         "epoch_vector.sync_committee.sample", "epoch_vector.sync_committee.aggregate",
         "epoch_vector.historical_summary")

_WORLDS: dict = {}


def configuration(name: str) -> dict:
    with open(ROOT / f"benchmark/configs/{name}.json") as handle:
        config = json.load(handle)
    config["validators"] = SMALL
    return config


def period_world(seed: int):
    """The deployment at 2^13 rows (its slot and committee as written)."""
    key = ("period", seed)
    if key not in _WORLDS:
        _WORLDS[key] = worlds.build(
            configuration("mainnet-deneb-1m-period"),
            {"kind": "period_edge", "miss_share": MISS_SHARE, "chain_epochs": 1},
            seed,
        )
    return _WORLDS[key]


def happy_world(seed: int):
    """``mainnet-deneb-1m`` at 2^13 rows, on the last slot of epoch 1."""
    key = ("happy", seed)
    if key not in _WORLDS:
        _WORLDS[key] = worlds.build(
            configuration("mainnet-deneb-1m"),
            {"kind": "epoch_edge", "epoch": 1, "miss_share": MISS_SHARE,
             "chain_epochs": 1},
            seed,
        )
    return _WORLDS[key]


def reference_root(seed: int) -> bytes:
    key = ("reference", seed)
    if key not in _WORLDS:
        world = period_world(seed)
        (_WORLDS[key],) = deneb_epoch_period.chain_roots(world.pre, world.target_slot, [])
    return _WORLDS[key]


@pytest.fixture
def fused_route():
    """``ops.install`` with the sweeps gate open at this size: the pass runs
    inactivity + rewards as the jitted fused kernel."""
    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    try:
        yield
    finally:
        ops.uninstall()


def counters() -> dict:
    return {name: metrics.counter(f"epoch_vector.{name}").value() for name in COUNTERS}


def cross(state, world) -> bytes:
    """The epoch_boundary driver's timed step: the boundary, then the root."""
    slot_processing.process_slots(state, world.target_slot, world.context)
    return type(state).hash_tree_root(state)


def literal_cross(state, world) -> bytes:
    os.environ["ECT_EPOCH_VECTOR"] = "off"
    try:
        return cross(state, world)
    finally:
        os.environ.pop("ECT_EPOCH_VECTOR", None)


def committee(c) -> tuple:
    return [bytes(k) for k in c.public_keys], bytes(c.aggregate_public_key)


def test_the_world_is_the_files_deployment_cut_to_size():
    world = period_world(5)
    pre = world.pre
    assert int(pre.slot) == 139263 and len(pre.validators) == SMALL
    assert world.target_slot // 32 == ENTERED == 17 * 256 and world.refills == []
    assert ENTERED % 64 == 0 and world.target_slot % 8192 == 0
    # a distinct mix for each epoch lived through; both root vectors full
    mixes = [bytes(m) for m in pre.randao_mixes]
    assert len(set(mixes[:ENTERED])) == ENTERED
    for vector in (pre.block_roots, pre.state_roots):
        assert len({bytes(r) for r in vector}) == 8192
    assert len(pre.historical_summaries) == 16
    current, following = committee(pre.current_sync_committee), committee(
        pre.next_sync_committee
    )
    assert current != following and len(current[0]) == len(following[0]) == 512
    # the next committee is what the sampler gives at the boundary into 4,096
    assert bytes(pre.next_sync_committee.aggregate_public_key) == g1.eth_aggregate_pubkeys(
        following[0]
    )
    # every row the crossing samples holds its real key
    upcoming = deneb_epoch_period.sync_committee_indices(
        deneb_epoch_period.get_seed(mixes, ENTERED, deneb_epoch_period.DOMAIN_SYNC_COMMITTEE),
        np.arange(SMALL),
        np.full(SMALL, 32 * 10**9, dtype=np.uint64),
    )
    assert all(bytes(pre.validators[i].public_key) == keys.public_key_bytes(i)
               for i in upcoming)
    cache = pre.__dict__.get("_active_idx_cache") or {}
    assert (ENTERED, SMALL) not in cache


def test_the_columnar_rotation_equals_the_literal_stage_list(fused_route):
    """Root and bytes, and both committees, the aggregate key and the new
    summary field by field, against ``models/altair``'s and
    ``models/capella``'s own stages on the same state."""
    world = period_world(7)
    columnar, literal = world.pre.copy(), world.pre.copy()
    before = counters()
    cross(columnar, world)
    literal_cross(literal, world)
    assert_bit_identical(columnar, literal, "period crossing")
    assert_column_consistency(columnar, "period crossing")
    for name in ("current_sync_committee", "next_sync_committee"):
        assert committee(getattr(columnar, name)) == committee(getattr(literal, name))
    assert committee(columnar.current_sync_committee) == committee(
        world.pre.next_sync_committee
    )
    assert committee(columnar.next_sync_committee) != committee(world.pre.next_sync_committee)
    assert len(columnar.historical_summaries) == len(literal.historical_summaries) == 17
    new, want = columnar.historical_summaries[-1], literal.historical_summaries[-1]
    assert bytes(new.block_summary_root) == bytes(want.block_summary_root)
    assert bytes(new.state_summary_root) == bytes(want.state_summary_root)
    assert bytes(new.block_summary_root) != bytes(new.state_summary_root)
    moved = {name: value - before[name] for name, value in counters().items()}
    # the literal pass declines the engine: only the columnar one counts
    assert moved == {"sync_committee.rotations": 1, "historical_summaries": 1,
                     "epochs": 1, "fused.jit": 1}


def test_the_period_stages_count_and_span_there_and_nowhere_else(fused_route):
    world = period_world(11)
    state = world.pre.copy()
    before = counters()
    with spans.recording():
        cross(state, world)
        records = [r for r in spans.RECORDER.records() if r.name in SPANS]
    moved = {name: value - before[name] for name, value in counters().items()}
    assert moved["sync_committee.rotations"] == moved["historical_summaries"] == 1
    assert sorted(r.name for r in records) == sorted(SPANS)
    by_name = {r.name: r for r in records}
    parent = by_name["epoch_vector.sync_committee"]
    for part in ("active", "sample", "aggregate"):
        assert by_name[f"epoch_vector.sync_committee.{part}"].parent_id == parent.span_id
    happy = happy_world(11)
    state = happy.pre.copy()
    before = counters()
    with spans.recording():
        cross(state, happy)
        records = [r for r in spans.RECORDER.records() if r.name in SPANS]
    moved = {name: value - before[name] for name, value in counters().items()}
    assert moved["epochs"] == 1
    assert moved["sync_committee.rotations"] == moved["historical_summaries"] == 0
    assert records == []


@pytest.mark.parametrize("seed", [41, (1 << 31) + 41])
def test_the_crossing_equals_the_period_reference(seed, fused_route):
    world = period_world(seed)
    state = world.pre.copy()
    assert cross(state, world) == reference_root(seed)
    assert_column_consistency(state, "after a period crossing")
    assert int(state.finalized_checkpoint.epoch) == ENTERED - 2


def test_the_rotation_sums_the_cached_keys(fused_route):
    """The sampled keys are parsed (and so cached, subgroup-checked) before
    the aggregate: it sums their cached affine points in one native call
    and decompresses nothing again, and the root is the reference's."""
    world = period_world(41)
    state = world.pre.copy()
    names = ("from_cache", "decompressed")
    before = [metrics.counter(f"bls.aggregate_pubkeys.{n}").value() for n in names]
    assert cross(state, world) == reference_root(41)
    after = [metrics.counter(f"bls.aggregate_pubkeys.{n}").value() for n in names]
    assert [a - b for a, b in zip(after, before)] == [1, 0]
    public, aggregate = committee(state.next_sync_committee)
    assert aggregate == g1.eth_aggregate_pubkeys(public)


PLANTS = faults_period.FAULTS + [faults_period.CONTROL]


@pytest.mark.parametrize("plant", PLANTS, ids=[p.__name__ for p in PLANTS])
def test_the_reference_calls_a_wrong_period_wrong(plant, fused_route, monkeypatch):
    """Each fault, and the control, planted under the served path: the
    sound path's root is the reference's, the faulty one's is not (a
    crossing that raises, as one that samples rows without their keys does,
    is wrong too)."""
    world = period_world(41)
    want = reference_root(41)
    assert cross(world.pre.copy(), world) == want
    plant(monkeypatch)
    try:
        served = cross(world.pre.copy(), world)
    except Exception:  # noqa: BLE001 - a crossing that raises is an answer
        served = None
    assert served != want


# -- the reference's G1 ---------------------------------------------------------------


def test_the_plain_g1_decompresses_the_generator_and_back():
    key = bls.SecretKey(1).public_key().to_bytes()
    assert key.hex().startswith("97f1d3a7") and key.hex().endswith("c6bb")
    assert g1.decompress(key) == g1.GENERATOR
    assert g1.compress(g1.GENERATOR) == key
    assert g1.to_affine(g1.multiply(g1.to_jacobian(g1.GENERATOR), g1.R)) is None


def test_the_plain_aggregate_equals_the_programs():
    rng = np.random.default_rng(64)
    secrets = [int(k) for k in rng.integers(1, 1 << 62, 64)]
    public = [bls.SecretKey(k).public_key().to_bytes() for k in secrets]
    served = bls.eth_aggregate_public_keys([bls.PublicKey.from_bytes(k) for k in public])
    assert g1.eth_aggregate_pubkeys(public) == served.to_bytes()
    # a repeated key counts each time it appears
    doubled = bls.eth_aggregate_public_keys(
        [bls.PublicKey.from_bytes(k) for k in public + public[:1]]
    )
    assert g1.eth_aggregate_pubkeys(public + public[:1]) == doubled.to_bytes()


def _off_curve() -> bytes:
    x = 1
    while pow((x**3 + g1.B) % g1.P, (g1.P - 1) // 2, g1.P) == 1:
        x += 1
    return bytes([g1.COMPRESSION_FLAG]) + x.to_bytes(47, "big")


REFUSED = {
    "off_the_curve": _off_curve(),
    # (0, 2) is on the curve, and not in the subgroup of order r
    "outside_the_subgroup": bytes([g1.COMPRESSION_FLAG]) + b"\x00" * 47,
    "the_identity": bytes([g1.COMPRESSION_FLAG | g1.INFINITY_FLAG]) + b"\x00" * 47,
    "without_the_compression_flag": bytes.fromhex(
        "17f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac586c55e83ff97a1aeffb3af00adb22c6bb"
    ),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_the_plain_g1_refuses_what_keyvalidate_refuses(name):
    key = REFUSED[name]
    with pytest.raises(ValueError):
        g1.key_validate(key)
    with pytest.raises(ValueError):
        g1.eth_aggregate_pubkeys([bls.SecretKey(2).public_key().to_bytes(), key])
    with pytest.raises(bls.InvalidPublicKeyError):
        bls.PublicKey.from_bytes(key)
