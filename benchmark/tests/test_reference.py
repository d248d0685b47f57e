"""The epoch cell's plain reference: it agrees with the program's host path
at a small size, and it is independent of the program."""

import os
from hashlib import sha256

import numpy as np
import pytest

from benchmark import worlds
from benchmark.reference import deneb_epoch, ssz

DENEB = {"fork": "deneb", "preset": "mainnet", "validators": 1 << 12}
EDGE = {"kind": "epoch_edge", "epoch": 1, "miss_share": [0.01, 0.03]}
CHAIN = {**EDGE, "chain_epochs": 7}


def test_the_reference_imports_nothing_of_the_program():
    folder = os.path.dirname(deneb_epoch.__file__)
    for name in os.listdir(folder):
        if name.endswith(".py"):
            with open(os.path.join(folder, name)) as handle:
                source = handle.read()
            assert "import ethereum_consensus_tpu" not in source
            assert "from ethereum_consensus_tpu" not in source


def test_merkleize_against_hand_worked_trees():
    a, b, c = (bytes([i]) * 32 for i in (1, 2, 3))
    h = lambda x, y: sha256(x + y).digest()  # noqa: E731
    assert ssz.merkleize(a) == a
    assert ssz.merkleize(a + b) == h(a, b)
    assert ssz.merkleize(a + b + c) == h(h(a, b), h(c, ssz.ZERO))
    assert ssz.merkleize(a, limit=4) == h(h(a, ssz.ZERO), h(ssz.ZERO, ssz.ZERO))
    assert ssz.merkleize(b"", limit=4) == ssz.ZERO_HASHES[2]
    assert ssz.uint(1) == b"\x01" + bytes(31)
    four = np.array([1, 2, 3, 4], dtype=np.uint64)
    packed = b"".join(int(x).to_bytes(8, "little") for x in four)
    assert ssz.packed_list(four, 8) == h(h(packed, ssz.ZERO), (4).to_bytes(32, "little"))
    assert ssz.bitvector([True, False, True, True]) == bytes([0b1101]) + bytes(31)


@pytest.mark.parametrize("seed", [3, (1 << 31) + 9])
def test_the_reference_agrees_with_the_host_path(seed):
    from ethereum_consensus_tpu.models.deneb.slot_processing import process_slots

    world = worlds.build(DENEB, EDGE, seed)
    plain = deneb_epoch.read_state(world.pre)
    assert deneb_epoch.state_root(plain) == type(world.pre).hash_tree_root(world.pre)
    state = world.pre.copy()
    process_slots(state, world.target_slot, world.context)
    want = type(state).hash_tree_root(state)
    assert deneb_epoch.crossing_root(world.pre, world.target_slot) == want
    # the rewards really moved: not one balance is what it was
    before = np.array(list(world.pre.balances), dtype=np.uint64)
    after = np.array(list(state.balances), dtype=np.uint64)
    assert (before != after).mean() > 0.99


def test_the_reference_follows_a_chain_of_crossings():
    """Seven crossings with the epochs between them: justification and
    finalization from the second on, a state root at every slot."""
    from ethereum_consensus_tpu.models.deneb.slot_processing import process_slots

    world = worlds.build(DENEB, CHAIN, (1 << 31) + 9)
    assert len(world.refills) == 6
    state = world.pre.copy()
    served = []
    for place in range(7):
        if place:
            process_slots(state, world.target_slot + 32 * place - 1, world.context)
            state.current_epoch_participation = world.refills[place - 1].tolist()
        process_slots(state, world.target_slot + 32 * place, world.context)
        served.append(type(state).hash_tree_root(state))
    assert deneb_epoch.chain_roots(world.pre, world.target_slot, world.refills) == served
    assert len(set(served)) == 7
    assert int(state.finalized_checkpoint.epoch) == 6  # finality kept pace
    # a chain cut short is the same chain as far as it goes
    assert deneb_epoch.chain_roots(
        world.pre, world.target_slot, world.refills[:2]
    ) == served[:3]


def test_a_roots_vector_keeps_its_tree():
    leaves = [bytes([i]) * 32 for i in range(8)]
    vector = ssz.RootsVector(leaves)
    assert vector.root() == ssz.merkleize(b"".join(leaves))
    vector[5] = b"\xaa" * 32
    leaves[5] = b"\xaa" * 32
    assert vector[5] == leaves[5] and len(vector) == 8
    assert vector.root() == ssz.merkleize(b"".join(leaves))


def test_the_reference_refuses_what_it_does_not_cover():
    world = worlds.build(DENEB, EDGE, 3)
    state = world.pre.copy()
    state.validators[5].slashed = True
    with pytest.raises(NotImplementedError, match="slashed"):
        deneb_epoch.crossing_root(state, world.target_slot)
