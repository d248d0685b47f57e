"""The epoch pass on a registry that grows: deposits append validators
between two boundaries (the deployment ``mainnet-deneb-growing`` of the
benchmark, cut to 2^12 + 37 rows), with the fused kernel routed as
``ops.install`` routes it.

The program's roots against the literal spec functions over chains whose
every crossing meets a longer registry; the working columns extended by the
appended rows and never rebuilt; the fused program compiled once for every
length inside one dispatched shape, and once more past its edge; pad rows
inert; the driver's shortcut (``add_validator_to_registry``) the same state
as ``process_deposit`` with proofs and signatures; and the faults the plain
reference (``benchmark/reference/deneb_epoch_inflow.py``) has to call
wrong."""

import json
import os
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

sys.path.insert(0, str(Path(__file__).parent.parent))
sys.path.insert(0, str(Path(__file__).parent))

import chain_utils  # noqa: E402
from benchmark import worlds  # noqa: E402
from benchmark.drivers.epoch_boundary_inflow import deliver  # noqa: E402
from benchmark.reference import deneb_epoch_inflow  # noqa: E402
from benchmark.tests import faults_inflow  # noqa: E402
from benchmark.worlds import mainnet_registry_inflow, registry  # noqa: E402
from ethereum_consensus_tpu import ops  # noqa: E402
from ethereum_consensus_tpu.models import epoch_vector  # noqa: E402
from ethereum_consensus_tpu.models.deneb import slot_processing  # noqa: E402
from ethereum_consensus_tpu.scenarios.harness import (  # noqa: E402
    assert_bit_identical,
    assert_column_consistency,
)
from ethereum_consensus_tpu.telemetry import metrics, spans  # noqa: E402

ROOT = Path(__file__).parent.parent
SMALL = (1 << 12) + 37
CHAIN = 6
MISS_SHARE = [0.01, 0.03]
# the column sets a new row extends when the flags are written in place:
# validators, balances, both participation lists, inactivity scores
COLUMN_SETS = 5

_WORLDS: dict = {}


def growing_world(seed: int, chain: int = CHAIN, validators: int = SMALL):
    """The deployment at ``validators`` rows (its groups scaled, two deposits
    an epoch)."""
    key = (seed, chain, validators)
    if key not in _WORLDS:
        with open(ROOT / "benchmark/configs/mainnet-deneb-growing.json") as handle:
            config = json.load(handle)
        config["validators"] = validators
        _WORLDS[key] = worlds.build(
            config,
            {"kind": "mainnet_registry_inflow", "epoch": 1,
             "miss_share": MISS_SHARE, "chain_epochs": chain},
            seed,
        )
    return _WORLDS[key]


@pytest.fixture
def fused_route(monkeypatch):
    """``ops.install`` with the sweeps gate open at this size, and jitted
    kernels of this test's own: their executable caches start empty."""
    monkeypatch.setattr(epoch_vector, "_JITTED_KERNELS", {})
    ops.install(sweeps_min_n=1, shuffle_min_n=1)
    try:
        yield
    finally:
        ops.uninstall()


def fused_programs() -> int:
    return epoch_vector.jitted_kernels()["fused_epoch"].__wrapped__._cache_size()


def counter(name: str) -> int:
    return metrics.counter(name).value()


def new_validators(seed: int, first: int, count: int) -> list:
    """``count`` deposits of keys nobody holds, for indices from ``first``."""
    keys = registry.rng_for(seed, f"tier1-pubkeys-{first}").bytes(48 * count)
    return [
        (keys[48 * j : 48 * (j + 1)], b"\x00" * 12 + (first + j).to_bytes(20, "big"),
         32 * 10**9)
        for j in range(count)
    ]


def flags_at(world, place: int, length: int) -> list:
    """The world's refill for ``place``, as long as the registry is now."""
    flags = world.refills[place - 1]
    out = np.zeros(length, dtype=np.uint8)
    out[: min(length, len(flags))] = flags[:length]
    return out.tolist()


def advance(state, world, place: int, deposits: list, between=None) -> None:
    """The untimed part of the driver's step: 31 empty slots, that epoch's
    deposits, whatever else a block did (``between``), and the flags,
    written in place as attestations write them."""
    slot_processing.process_slots(
        state, world.target_slot + 32 * place - 1, world.context
    )
    deliver(state, deposits, world.context)
    if between is not None:
        between(state)
    state.current_epoch_participation[:] = flags_at(world, place, len(state.validators))


def boundary(state, world, place: int, literal: bool = False) -> bytes:
    if literal:
        os.environ["ECT_EPOCH_VECTOR"] = "off"
    try:
        slot_processing.process_slots(
            state, world.target_slot + 32 * place, world.context
        )
    finally:
        os.environ.pop("ECT_EPOCH_VECTOR", None)
    return type(state).hash_tree_root(state)


def setattr_on_an_old_row(state) -> None:
    """A block slashes somebody: a field write on a row the columns hold."""
    state.validators[5].slashed = True
    state.validators[5].withdrawable_epoch = 9000


def tracking_lost(state) -> None:
    """Mutators whose elements cannot be named (a sort that moves nothing, a
    reverse and back): ``_col_dirty`` goes to None, the columns are rebuilt."""
    state.validators.sort(key=lambda validator: 0)
    state.inactivity_scores.reverse()
    state.inactivity_scores.reverse()
    assert state.validators._col_dirty is None
    assert state.inactivity_scores._col_dirty is None


HISTORIES = {
    "append_only": None,
    "append_then_setattr": setattr_on_an_old_row,
    "append_then_tracking_lost": tracking_lost,
}


COUNTED = (
    "ops_vector.columns.builds", "ops_vector.columns.extended_rows",
    "epoch_vector.rows_appended",
)


class Moved(dict):
    """What the counters moved by inside ``with moved:`` blocks (the
    literal twin syncs columns of its own, outside them)."""

    def __enter__(self):
        self._before = {name: counter(name) for name in COUNTED}

    def __exit__(self, *exc):
        for name, was in self._before.items():
            self[name] = self.get(name, 0) + counter(name) - was


@pytest.mark.parametrize("history", list(HISTORIES))
def test_six_crossings_of_a_growing_registry_equal_the_literal_path(history, fused_route):
    """Every crossing meets a registry a seeded number of rows longer than
    the last: the roots are the literal stage list's, one fused program
    serves every length, and the columns are extended, not rebuilt."""
    world = growing_world(35)
    between = HISTORIES[history]
    counts = registry.rng_for(35, "tier1-inflow").integers(1, 40, CHAIN).tolist()
    columnar, literal = world.pre.copy(), world.pre.copy()
    appended, moved = 0, Moved()
    for place in range(CHAIN):
        if place:
            batch = new_validators(35, len(columnar.validators), counts[place])
            with moved:
                advance(columnar, world, place, batch, between)
            advance(literal, world, place, batch, between)
            appended += len(batch)
        assert len(columnar.validators) == SMALL + appended
        if place == 0:
            served = boundary(columnar, world, place)
        else:
            with moved:
                served = boundary(columnar, world, place)
        assert served == boundary(literal, world, place, literal=True), place
        assert_bit_identical(columnar, literal, f"{history}, crossing {place}")
        with moved:
            assert_column_consistency(columnar, f"{history}, crossing {place}")
    assert len({SMALL + sum(counts[1 : k + 1]) for k in range(CHAIN)}) == CHAIN
    assert fused_programs() == 1  # six lengths, one shape, one executable
    assert moved["epoch_vector.rows_appended"] == appended
    if between is tracking_lost:
        # two lists a crossing lost their tracking and were rebuilt; the
        # other three sets were extended all the same
        assert moved["ops_vector.columns.builds"] == 2 * (CHAIN - 1)
        assert moved["ops_vector.columns.extended_rows"] == 3 * appended
    else:
        assert moved["ops_vector.columns.builds"] == 0  # none after the first crossing
        assert moved["ops_vector.columns.extended_rows"] == COLUMN_SETS * appended


def test_a_copys_sibling_that_appends_clones_the_shared_columns(fused_route):
    """A state and its copy share their columns; each then appends rows of
    its own and crosses: neither sees the other's, both equal the literal
    path, and the shared arrays are as they were."""
    world = growing_world(36)
    first = world.pre.copy()
    boundary(first, world, 0)
    sibling = first.copy()
    shared = first.validators._col_cache[1]["effective_balance"]
    assert sibling.validators._col_cache[1]["effective_balance"] is shared
    assert not first.validators._col_owned and not sibling.validators._col_owned
    before = shared.copy()
    twins = {}
    for name, state, count in (("first", first, 7), ("sibling", sibling, 19)):
        literal = state.copy()
        batch = new_validators(36 + count, SMALL, count)
        moved = Moved()
        with moved:
            advance(state, world, 1, batch)
            served = boundary(state, world, 1)
        advance(literal, world, 1, batch)
        assert served == boundary(literal, world, 1, literal=True)
        assert moved["ops_vector.columns.extended_rows"] == COLUMN_SETS * count
        assert moved["ops_vector.columns.builds"] == 0
        assert_column_consistency(state, name)
        assert state.validators._col_owned
        twins[name] = state
    assert len(first.validators) == SMALL + 7 and len(sibling.validators) == SMALL + 19
    assert first.validators._col_cache[1]["effective_balance"] is not shared
    assert sibling.validators._col_cache[1]["effective_balance"] is not shared
    assert np.array_equal(shared, before) and shared.shape[0] == SMALL
    assert bytes(first.validators[SMALL].public_key) != bytes(
        sibling.validators[SMALL].public_key
    )


def test_past_the_edge_of_a_dispatched_shape_one_more_program(fused_route, monkeypatch):
    """With the granule shrunk to 64 rows a chain crosses an edge: the
    lengths on either side of it are two shapes, two executables, and the
    roots are the literal path's all along."""
    monkeypatch.setattr(epoch_vector, "FUSED_ROW_GRANULE", 64)
    world = growing_world(37)
    assert SMALL % 64 == 37 and epoch_vector.fused_dispatch_rows(SMALL) == SMALL + 27
    columnar, literal = world.pre.copy(), world.pre.copy()
    programs, shapes = [], []
    for place, count in enumerate([0, 20, 20, 3]):
        if place:
            batch = new_validators(37, len(columnar.validators), count)
            for state in (columnar, literal):
                advance(state, world, place, batch)
        shapes.append(epoch_vector.fused_dispatch_rows(len(columnar.validators)))
        with spans.recording():
            served = boundary(columnar, world, place)
            fused = [
                r.fields for r in spans.RECORDER.records()
                if r.name == "epoch_vector.fused"
            ]
        assert served == boundary(literal, world, place, literal=True), place
        assert (fused[0]["rows"], fused[0]["padded"]) == (
            len(columnar.validators), shapes[-1]
        )
        programs.append(fused_programs())
    # 4,133 and 4,153 rows go up as 4,160; 4,173 and 4,176 as 4,224
    assert shapes == [SMALL + 27, SMALL + 27, SMALL + 91, SMALL + 91]
    assert programs == [1, 1, 2, 2]


# -- pad rows are inert -----------------------------------------------------------


def random_columns(rng, n: int) -> list:
    """The seven columns of a registry that is no special case: slashed
    rows, rows that are eligible and not active, scores, every flag."""
    eth = 10**9
    effective = rng.integers(0, 33, n).astype(np.uint64) * np.uint64(eth)
    return [
        effective + rng.integers(0, 2 * eth, n).astype(np.uint64),   # balances
        effective,
        rng.integers(0, 8, n).astype(np.uint8),                      # flags
        rng.random(n) < 0.05,                                        # slashed
        rng.random(n) < 0.8,                                         # active
        rng.random(n) < 0.85,                                        # eligible
        rng.integers(0, 5000, n).astype(np.uint64),                  # scores
    ]


def run_fused(columns: list, leaking: bool):
    u64 = np.uint64
    return epoch_vector.fused_epoch_kernel(
        np, *columns, u64(10**9), u64(357), u64(31_000_000), u64(4 << 24),
        4, 16, (14, 26, 14), 64, leaking, 2, 1,
    )


@pytest.mark.parametrize("leaking", [False, True], ids=["finalizing", "leaking"])
@pytest.mark.parametrize("n", [1, SMALL, 70_001])
def test_pad_rows_change_no_real_rows_result(n, leaking):
    """The kernel on columns padded to the dispatched length, cut back to
    ``n``, is the kernel on the columns: scores, balances and the wrap
    census. First as the route pads (``_padded``: every column's pad rows
    0), then with pad rows that are hostile in the five columns of values
    (balances and scores at the top of the lane, every flag, slashed): they
    stay inert, because the two masks alone gate every term of the kernel.
    A pad row that is eligible or active is the one thing that would count,
    and the route cannot dispatch one: ``_padded`` is its only source of pad
    rows and writes 0 into every column, the boolean ones included."""
    rng = np.random.default_rng(n + leaking)
    columns = random_columns(rng, n)
    want_scores, want_balances, want_wrapped = run_fused(columns, leaking)
    rows = epoch_vector.fused_dispatch_rows(n)
    assert rows % epoch_vector.FUSED_ROW_GRANULE == 0 and 0 < rows - n < 1 << 16
    padded = [epoch_vector._padded(np, column, rows) for column in columns]
    assert all(len(column) == rows and not column[n:].any() for column in padded)
    assert all(p.dtype == c.dtype for p, c in zip(padded, columns))
    hostile = [column.copy() for column in padded]
    hostile[0][n:] = np.uint64((1 << 63) - 1)
    hostile[1][n:] = np.uint64(2048 * 10**9)
    hostile[2][n:] = 0b111
    hostile[3][n:] = True
    hostile[6][n:] = np.uint64((1 << 63) - 1)
    for dispatched in (padded, hostile):
        with np.errstate(over="ignore"):
            scores, balances, wrapped = run_fused(dispatched, leaking)
        assert np.array_equal(scores[:n], want_scores)
        assert np.array_equal(balances[:n], want_balances)
        assert int(wrapped) == int(want_wrapped)
    # and a column that has the dispatched length goes up as it is
    assert epoch_vector._padded(np, padded[0], rows) is padded[0]


# -- the shortcut is the normal path ------------------------------------------------


def test_sixteen_deposits_through_process_deposit_leave_the_drivers_state():
    """A block's ``MAX_DEPOSITS`` through ``process_deposit``, each with its
    Merkle proof against ``eth1_data.deposit_root`` and its proof of
    possession, and the same sixteen by the driver's ``deliver``: root for
    root the same state."""
    from ethereum_consensus_tpu.models.deneb.block_processing import process_deposit
    from ethereum_consensus_tpu.models.phase0.containers import DepositData
    from ethereum_consensus_tpu.ssz import List as SSZList

    held, new = 64, 16
    state, context = chain_utils.fresh_genesis_deneb(held, "minimal")
    deposits = chain_utils.make_deposits(held + new, context)
    datas = [deposit.data for deposit in deposits]
    by_blocks, by_driver = state.copy(), state.copy()
    for index in range(held, held + new):
        # the deposit contract's tree as the eth1 vote has it by then
        root = SSZList[DepositData, 2**32].hash_tree_root(datas[: index + 1])
        for side in (by_blocks, by_driver):
            side.eth1_data.deposit_root = root
            side.eth1_data.deposit_count = index + 1
        process_deposit(by_blocks, deposits[index], context)
        data = datas[index]
        deliver(
            by_driver,
            [(bytes(data.public_key), bytes(data.withdrawal_credentials), int(data.amount))],
            context,
        )
        assert type(by_blocks).hash_tree_root(by_blocks) == type(by_driver).hash_tree_root(
            by_driver
        )
    assert len(by_blocks.validators) == held + new
    assert int(by_blocks.eth1_deposit_index) == held + new
    assert_bit_identical(by_blocks, by_driver, "sixteen deposits")


# -- the plain reference, and what it has to call wrong ------------------------------


def cross(state, world, place: int) -> bytes:
    """The driver's step."""
    slot = world.target_slot + 32 * place
    if place:
        slot_processing.process_slots(state, slot - 1, world.context)
        deliver(state, world.deposits[place - 1], world.context)
        state.current_epoch_participation = world.refills[place - 1].tolist()
    slot_processing.process_slots(state, slot, world.context)
    return type(state).hash_tree_root(state)


@pytest.mark.parametrize("seed", [35, (1 << 31) + 35])
def test_the_drivers_chain_equals_the_plain_reference(seed, fused_route):
    world = growing_world(seed, chain=8)
    count = mainnet_registry_inflow.per_epoch({
        "validators": SMALL, "inflow": {"per_epoch": 512},
        "registry": {"at_validators": 1_905_000},
    })
    assert [len(batch) for batch in world.deposits] == [count] * 7
    before = counter("epoch_vector.rows_appended")
    state = world.pre.copy()
    served = [cross(state, world, place) for place in range(8)]
    want = deneb_epoch_inflow.chain_roots(
        world.pre, world.target_slot, world.refills, world.deposits
    )
    assert served == want and len(set(served)) == 8
    assert len(state.validators) == SMALL + 7 * count
    assert counter("epoch_vector.rows_appended") - before == 7 * count
    assert fused_programs() == 1
    assert_column_consistency(state, "after eight crossings of the driver's chain")


PLANTS = faults_inflow.FAULTS + [faults_inflow.dropped_score_entry, faults_inflow.CONTROL]


@pytest.mark.parametrize("plant", PLANTS, ids=[p.__name__ for p in PLANTS])
def test_the_reference_calls_a_wrong_new_row_wrong(plant, fused_route, monkeypatch):
    """Each fault, and the control, planted under the served path: the sound
    path's roots are the reference's; the faulty one's are not (a path that
    raises on lists out of step has served no root at all)."""
    world = growing_world(21, chain=3)
    want = deneb_epoch_inflow.chain_roots(
        world.pre, world.target_slot, world.refills, world.deposits
    )
    sound = world.pre.copy()
    assert [cross(sound, world, place) for place in range(3)] == want
    plant(monkeypatch)
    faulty = world.pre.copy()
    served = []
    for place in range(3):
        try:
            served.append(cross(faulty, world, place))
        except IndexError:
            assert plant is faults_inflow.dropped_score_entry
            served.append(None)
            break
    assert served[-1] != want[len(served) - 1]
    assert any(got != root for got, root in zip(served[1:], want[1:]))
