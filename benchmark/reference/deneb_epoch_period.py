"""The plain reference of the epoch cell at a sync committee period
boundary: ``deneb_epoch_registry.py``'s stages, unedited, and the two
stages every other reference refuses, written out from the consensus
specification under its names: ``process_historical_summaries_update``
(specs/capella/beacon-chain.md) and ``process_sync_committee_updates`` with
``get_seed``, ``compute_shuffled_index``,
``get_next_sync_committee_indices`` and ``get_next_sync_committee``
(specs/phase0 and specs/altair/beacon-chain.md) in hashlib, the aggregate
key by ``g1.py``'s ``eth_aggregate_pubkeys`` (specs/altair/bls.md).

It takes from ``deneb_epoch.py`` and ``deneb_epoch_registry.py`` the state's
plain values, its root, ``process_slot`` and the other stages, and imports
nothing of the program. It refuses a crossing that is not a period
boundary: the other references answer those."""

from __future__ import annotations

from hashlib import sha256

import numpy as np

from benchmark.reference import deneb_epoch as base
from benchmark.reference import deneb_epoch_registry as registry
from benchmark.reference import g1
from benchmark.reference.deneb_epoch import (
    EPOCHS_PER_ETH1_VOTING_PERIOD,
    EPOCHS_PER_HISTORICAL_VECTOR,
    EPOCHS_PER_SLASHINGS_VECTOR,
    EPOCHS_PER_SYNC_COMMITTEE_PERIOD,
    GENESIS_EPOCH,
    MAX_EFFECTIVE_BALANCE,
    SLOTS_PER_EPOCH,
    SLOTS_PER_HISTORICAL_ROOT,
    VALIDATOR_FIELDS,
    Plain,
)

# presets/mainnet/{phase0,altair}.yaml, specs/altair/beacon-chain.md
SHUFFLE_ROUND_COUNT = 90
MIN_SEED_LOOKAHEAD = 1
SYNC_COMMITTEE_SIZE = 512
MAX_RANDOM_BYTE = 2**8 - 1
DOMAIN_SYNC_COMMITTEE = bytes.fromhex("07000000")


# -- the sync committee -------------------------------------------------------------


def get_seed(randao_mixes, epoch: int, domain_type: bytes) -> bytes:
    mix = randao_mixes[
        (epoch + EPOCHS_PER_HISTORICAL_VECTOR - MIN_SEED_LOOKAHEAD - 1)
        % EPOCHS_PER_HISTORICAL_VECTOR
    ]
    return sha256(domain_type + epoch.to_bytes(8, "little") + bytes(mix)).digest()


def compute_shuffled_index(index: int, index_count: int, seed: bytes) -> int:
    """The swap-or-not shuffle of one index."""
    assert index < index_count
    for current_round in range(SHUFFLE_ROUND_COUNT):
        round_byte = current_round.to_bytes(1, "little")
        pivot = int.from_bytes(sha256(seed + round_byte).digest()[:8], "little") % index_count
        flip = (pivot + index_count - index) % index_count
        position = max(index, flip)
        source = sha256(
            seed + round_byte + (position // 256).to_bytes(4, "little")
        ).digest()
        byte = source[(position % 256) // 8]
        if (byte >> (position % 8)) % 2:
            index = flip
    return index


def sync_committee_indices(seed: bytes, active: np.ndarray,
                           effective_balance: np.ndarray) -> list:
    """``get_next_sync_committee_indices``' loop: candidates by the shuffle,
    each accepted with its effective balance's weight; duplicates allowed."""
    active_validator_count = len(active)
    indices: list = []
    i = 0
    while len(indices) < SYNC_COMMITTEE_SIZE:
        shuffled_index = compute_shuffled_index(
            i % active_validator_count, active_validator_count, seed
        )
        candidate_index = int(active[shuffled_index])
        random_byte = sha256(seed + (i // 32).to_bytes(8, "little")).digest()[i % 32]
        effective = int(effective_balance[candidate_index])
        if effective * MAX_RANDOM_BYTE >= MAX_EFFECTIVE_BALANCE * random_byte:
            indices.append(candidate_index)
        i += 1
    return indices


def get_next_sync_committee_indices(plain: Plain) -> list:
    s, c = plain.scalars, plain.columns
    epoch = s["slot"] // SLOTS_PER_EPOCH + 1
    active = np.nonzero(registry.is_active(c, epoch))[0]
    seed = get_seed(s["randao_mixes"], epoch, DOMAIN_SYNC_COMMITTEE)
    return sync_committee_indices(seed, active, c["effective_balance"])


def get_next_sync_committee(plain: Plain) -> tuple:
    """(the 512 keys, their aggregate)."""
    keys = [plain.columns["public_key"][i] for i in get_next_sync_committee_indices(plain)]
    return keys, g1.eth_aggregate_pubkeys(keys)


def process_sync_committee_updates(plain: Plain, following: int) -> None:
    if following % EPOCHS_PER_SYNC_COMMITTEE_PERIOD == 0:
        s = plain.scalars
        s["current_sync_committee"] = s["next_sync_committee"]
        s["next_sync_committee"] = get_next_sync_committee(plain)


# -- the historical summary ---------------------------------------------------------


def process_historical_summaries_update(plain: Plain, following: int) -> None:
    if following % (SLOTS_PER_HISTORICAL_ROOT // SLOTS_PER_EPOCH) == 0:
        s = plain.scalars
        # both vectors are kept as their whole trees (ssz.RootsVector)
        s["historical_summaries"] = s["historical_summaries"] + [
            (s["block_roots"].root(), s["state_roots"].root())
        ]


# -- the epoch ----------------------------------------------------------------------


def process_epoch(plain: Plain, tree: registry.ValidatorsTree) -> None:
    s, c = plain.scalars, plain.columns
    current = s["slot"] // SLOTS_PER_EPOCH
    previous = max(current - 1, GENESIS_EPOCH)
    following = current + 1
    base._refuse(
        following % EPOCHS_PER_SYNC_COMMITTEE_PERIOD, "a boundary inside a period"
    )
    before = {name: c[name] for name in VALIDATOR_FIELDS}

    registry.process_justification_and_finalization(plain, current, previous)
    registry.process_inactivity_updates(plain, current, previous)
    registry.process_rewards_and_penalties(plain, current, previous)
    registry.process_registry_updates(plain, current)
    registry.process_slashings(plain, current)
    # process_eth1_data_reset
    if following % EPOCHS_PER_ETH1_VOTING_PERIOD == 0:
        s["eth1_data_votes"] = []
    registry.process_effective_balance_updates(plain)
    # process_slashings_reset, process_randao_mixes_reset
    s["slashings"][following % EPOCHS_PER_SLASHINGS_VECTOR] = 0
    plain.memo.pop("slashings", None)  # written into, not replaced
    s["randao_mixes"][following % EPOCHS_PER_HISTORICAL_VECTOR] = s["randao_mixes"][
        current % EPOCHS_PER_HISTORICAL_VECTOR
    ]
    process_historical_summaries_update(plain, following)
    # process_participation_flag_updates
    c["previous_epoch_participation"] = c["current_epoch_participation"]
    c["current_epoch_participation"] = np.zeros_like(c["previous_epoch_participation"])
    process_sync_committee_updates(plain, following)

    # the validators' root: the rows whose numbers a stage replaced
    written = np.zeros(tree.count, dtype=bool)
    for name, old in before.items():
        if c[name] is not old:
            written |= c[name] != old
    if written.any():
        tree.update(c, np.nonzero(written)[0])
        plain.validators_root = tree.root()


def chain_roots(state, target_slot: int, refills: list) -> list:
    """The root after ``process_slots(state, target_slot)`` over one period
    boundary, worked out again from the state's plain values: a chain of one
    crossing (the next period boundary is 256 epochs on), so ``refills`` is
    empty. ``state`` is only read."""
    if refills:
        raise NotImplementedError("the reference does not cover two period boundaries")
    plain, tree = registry.read_state(state)
    s = plain.scalars
    if s["slot"] >= target_slot:
        raise ValueError("cannot process slots backwards")
    while s["slot"] < target_slot:
        base.process_slot(plain)
        if (s["slot"] + 1) % SLOTS_PER_EPOCH == 0:
            process_epoch(plain, tree)
        s["slot"] += 1
    return [base.state_root(plain)]
