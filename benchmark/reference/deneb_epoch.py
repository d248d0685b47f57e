"""The plain reference of the epoch cell: one slot advance across an epoch
boundary on a deneb state, and the state's root, from the consensus
specification (phase0/altair/bellatrix/capella/deneb beacon-chain.md) in
numpy and hashlib. It imports nothing of the program and takes nothing the
program has computed: ``read_state`` copies the plain values (ints, bytes)
out of the generated state's fields, and everything after that is this
file's own arithmetic and ``ssz.py``'s own hashing.

It covers the branches the benchmark's worlds reach and refuses a state
that would need the others (slashed validators, registry churn, a sync
committee rotation, a historical summary), instead of answering wrongly.

A chain of crossings asks for a state root at every slot, 32 an epoch, so
the roots of what a slot leaves alone are kept: a column's root beside the
array it was hashed from (the transition replaces arrays, it never writes
into one), and the three vectors of roots as whole trees (``ssz.RootsVector``)."""

from __future__ import annotations

from dataclasses import dataclass, field
from math import isqrt

import numpy as np

from benchmark.reference import ssz

# the mainnet preset and config, from the specification's own files
SLOTS_PER_EPOCH = 32
SLOTS_PER_HISTORICAL_ROOT = 8192
EPOCHS_PER_HISTORICAL_VECTOR = 65536
EPOCHS_PER_SLASHINGS_VECTOR = 8192
EPOCHS_PER_ETH1_VOTING_PERIOD = 64
EPOCHS_PER_SYNC_COMMITTEE_PERIOD = 256
HISTORICAL_ROOTS_LIMIT = 1 << 24
VALIDATOR_REGISTRY_LIMIT = 1 << 40
EFFECTIVE_BALANCE_INCREMENT = 10**9
MAX_EFFECTIVE_BALANCE = 32 * 10**9
EJECTION_BALANCE = 16 * 10**9
BASE_REWARD_FACTOR = 64
HYSTERESIS_QUOTIENT = 4
HYSTERESIS_DOWNWARD_MULTIPLIER = 1
HYSTERESIS_UPWARD_MULTIPLIER = 5
INACTIVITY_SCORE_BIAS = 4
INACTIVITY_SCORE_RECOVERY_RATE = 16
INACTIVITY_PENALTY_QUOTIENT_BELLATRIX = 1 << 24
MIN_EPOCHS_TO_INACTIVITY_PENALTY = 4
FAR_FUTURE_EPOCH = (1 << 64) - 1
GENESIS_EPOCH = 0
TIMELY_SOURCE, TIMELY_TARGET, TIMELY_HEAD = 0, 1, 2
FLAG_WEIGHTS = (14, 26, 14)
WEIGHT_DENOMINATOR = 64
MAX_EXTRA_DATA_BYTES = 32

U64 = np.uint64


@dataclass
class Plain:
    """A deneb BeaconState as plain values; registry fields as columns."""

    scalars: dict = field(default_factory=dict)
    columns: dict = field(default_factory=dict)
    validators_root: bytes | None = None  # memo, dropped when a column changes
    memo: dict = field(default_factory=dict)  # name -> (value hashed, its root)


VALIDATOR_FIELDS = (
    "effective_balance", "slashed", "activation_eligibility_epoch",
    "activation_epoch", "exit_epoch", "withdrawable_epoch",
)


def _checkpoint(c) -> tuple:
    return int(c.epoch), bytes(c.root)


def read_state(state) -> Plain:
    """Copy the plain values out of a generated deneb state."""
    plain = Plain()
    s = plain.scalars
    for name in ("genesis_time", "slot", "eth1_deposit_index",
                 "next_withdrawal_index", "next_withdrawal_validator_index"):
        s[name] = int(getattr(state, name))
    s["genesis_validators_root"] = bytes(state.genesis_validators_root)
    s["fork"] = (bytes(state.fork.previous_version),
                 bytes(state.fork.current_version), int(state.fork.epoch))
    h = state.latest_block_header
    s["latest_block_header"] = dict(
        slot=int(h.slot), proposer_index=int(h.proposer_index),
        parent_root=bytes(h.parent_root), state_root=bytes(h.state_root),
        body_root=bytes(h.body_root),
    )
    s["block_roots"] = ssz.RootsVector(state.block_roots)
    s["state_roots"] = ssz.RootsVector(state.state_roots)
    s["historical_roots"] = [bytes(r) for r in state.historical_roots]
    e = state.eth1_data
    s["eth1_data"] = (bytes(e.deposit_root), int(e.deposit_count), bytes(e.block_hash))
    s["eth1_data_votes"] = [
        (bytes(v.deposit_root), int(v.deposit_count), bytes(v.block_hash))
        for v in state.eth1_data_votes
    ]
    s["randao_mixes"] = ssz.RootsVector(state.randao_mixes)
    s["slashings"] = np.array([int(x) for x in state.slashings], dtype=U64)
    s["justification_bits"] = [bool(b) for b in state.justification_bits]
    s["previous_justified_checkpoint"] = _checkpoint(state.previous_justified_checkpoint)
    s["current_justified_checkpoint"] = _checkpoint(state.current_justified_checkpoint)
    s["finalized_checkpoint"] = _checkpoint(state.finalized_checkpoint)
    for name in ("current_sync_committee", "next_sync_committee"):
        committee = getattr(state, name)
        s[name] = ([bytes(k) for k in committee.public_keys],
                   bytes(committee.aggregate_public_key))
    p = state.latest_execution_payload_header
    s["latest_execution_payload_header"] = {
        name: (int(getattr(p, name)) if name in (
            "block_number", "gas_limit", "gas_used", "timestamp",
            "base_fee_per_gas", "blob_gas_used", "excess_blob_gas",
        ) else bytes(getattr(p, name)))
        for name in (
            "parent_hash", "fee_recipient", "state_root", "receipts_root",
            "logs_bloom", "prev_randao", "block_number", "gas_limit",
            "gas_used", "timestamp", "extra_data", "base_fee_per_gas",
            "block_hash", "transactions_root", "withdrawals_root",
            "blob_gas_used", "excess_blob_gas",
        )
    }
    s["historical_summaries"] = [
        (bytes(x.block_summary_root), bytes(x.state_summary_root))
        for x in state.historical_summaries
    ]
    c = plain.columns
    validators = state.validators
    c["public_key"] = [bytes(v.public_key) for v in validators]
    c["withdrawal_credentials"] = [bytes(v.withdrawal_credentials) for v in validators]
    for name in VALIDATOR_FIELDS:
        c[name] = np.array(
            [int(getattr(v, name)) for v in validators], dtype=U64
        )
    c["balances"] = np.array([int(b) for b in state.balances], dtype=U64)
    c["inactivity_scores"] = np.array(
        [int(x) for x in state.inactivity_scores], dtype=U64
    )
    c["previous_epoch_participation"] = np.array(
        [int(x) for x in state.previous_epoch_participation], dtype=np.uint8
    )
    c["current_epoch_participation"] = np.array(
        [int(x) for x in state.current_epoch_participation], dtype=np.uint8
    )
    return plain


# -- the state's root -----------------------------------------------------------


def _u64_chunks(column: np.ndarray) -> np.ndarray:
    """(n, 32) bytes: each uint64 as its own zero-padded chunk."""
    out = np.zeros((len(column), 32), dtype=np.uint8)
    out[:, :8] = column.astype("<u8").view(np.uint8).reshape(-1, 8)
    return out


def _as_rows(digests: bytes) -> np.ndarray:
    return np.frombuffer(digests, dtype=np.uint8).reshape(-1, 32)


def _hash_rows(left: np.ndarray, right: np.ndarray) -> np.ndarray:
    """Row-wise sha256(left || right) of two (n, 32) byte arrays."""
    return _as_rows(ssz.hash_pairs(np.concatenate([left, right], axis=1).tobytes()))


def validators_root(plain: Plain) -> bytes:
    """List[Validator, 2^40]. A validator is eight leaves: key, credentials,
    and six numbers. The nodes over the six numbers are hashed once for each
    distinct row of them (a registry holds few) and shared, which leaves
    four hashes a validator instead of eight; every hash is still computed
    here, from the values."""
    c = plain.columns
    n = len(c["public_key"])
    numbers = np.stack([c[name] for name in VALIDATOR_FIELDS], axis=1)
    distinct, which = np.unique(numbers, axis=0, return_inverse=True)
    which = which.reshape(-1)
    chunk = [_u64_chunks(distinct[:, k]) for k in range(6)]
    node_23 = _hash_rows(chunk[0], chunk[1])
    node_4567 = _hash_rows(
        _hash_rows(chunk[2], chunk[3]), _hash_rows(chunk[4], chunk[5])
    )
    # a 48-byte key is two chunks: its root is one hash
    key_roots = _as_rows(
        ssz.hash_pairs(b"".join(k + b"\x00" * 16 for k in c["public_key"]))
    )
    credentials = np.frombuffer(
        b"".join(c["withdrawal_credentials"]), dtype=np.uint8
    ).reshape(n, 32)
    node_0123 = _hash_rows(_hash_rows(key_roots, credentials), node_23[which])
    roots = _hash_rows(node_0123, node_4567[which])
    return ssz.mix_in_length(
        ssz.merkleize(roots.tobytes(), VALIDATOR_REGISTRY_LIMIT), n
    )


def _checkpoint_root(checkpoint: tuple) -> bytes:
    return ssz.container([ssz.uint(checkpoint[0]), checkpoint[1]])


def _eth1_root(data: tuple) -> bytes:
    return ssz.container([data[0], ssz.uint(data[1]), data[2]])


def _sync_committee_root(committee: tuple) -> bytes:
    keys, aggregate = committee
    key_roots = ssz.hash_pairs(b"".join(k + b"\x00" * 16 for k in keys))
    return ssz.container([ssz.merkleize(key_roots), ssz.byte_vector(aggregate)])


def _payload_header_root(p: dict) -> bytes:
    return ssz.container([
        p["parent_hash"], ssz.byte_vector(p["fee_recipient"]), p["state_root"],
        p["receipts_root"], ssz.byte_vector(p["logs_bloom"]), p["prev_randao"],
        ssz.uint(p["block_number"]), ssz.uint(p["gas_limit"]),
        ssz.uint(p["gas_used"]), ssz.uint(p["timestamp"]),
        ssz.byte_list(p["extra_data"], MAX_EXTRA_DATA_BYTES),
        ssz.uint(p["base_fee_per_gas"], 32), p["block_hash"],
        p["transactions_root"], p["withdrawals_root"],
        ssz.uint(p["blob_gas_used"]), ssz.uint(p["excess_blob_gas"]),
    ])


def header_root(h: dict) -> bytes:
    return ssz.container([
        ssz.uint(h["slot"]), ssz.uint(h["proposer_index"]), h["parent_root"],
        h["state_root"], h["body_root"],
    ])


def _kept(plain: Plain, name: str, value, root_of) -> bytes:
    """``root_of(value)``, worked out once for each object that stands under
    ``name``: a value that is replaced is hashed again, one that is written
    into has to be dropped from ``plain.memo`` by the writer."""
    held = plain.memo.get(name)
    if held is None or held[0] is not value:
        held = plain.memo[name] = (value, root_of(value))
    return held[1]


def state_root(plain: Plain) -> bytes:
    s, c = plain.scalars, plain.columns
    if plain.validators_root is None:
        plain.validators_root = validators_root(plain)
    fork = s["fork"]

    def column(name: str) -> bytes:
        return _kept(
            plain, name, c[name],
            lambda values: ssz.packed_list(values, VALIDATOR_REGISTRY_LIMIT),
        )

    return ssz.container([
        ssz.uint(s["genesis_time"]),
        s["genesis_validators_root"],
        ssz.uint(s["slot"]),
        ssz.container([ssz.byte_vector(fork[0]), ssz.byte_vector(fork[1]),
                       ssz.uint(fork[2])]),
        header_root(s["latest_block_header"]),
        s["block_roots"].root(),
        s["state_roots"].root(),
        ssz.roots_list(s["historical_roots"], HISTORICAL_ROOTS_LIMIT),
        _eth1_root(s["eth1_data"]),
        ssz.roots_list(
            [_eth1_root(v) for v in s["eth1_data_votes"]],
            EPOCHS_PER_ETH1_VOTING_PERIOD * SLOTS_PER_EPOCH,
        ),
        ssz.uint(s["eth1_deposit_index"]),
        plain.validators_root,
        column("balances"),
        s["randao_mixes"].root(),
        _kept(plain, "slashings", s["slashings"], ssz.packed_vector),
        column("previous_epoch_participation"),
        column("current_epoch_participation"),
        ssz.bitvector(s["justification_bits"]),
        _checkpoint_root(s["previous_justified_checkpoint"]),
        _checkpoint_root(s["current_justified_checkpoint"]),
        _checkpoint_root(s["finalized_checkpoint"]),
        column("inactivity_scores"),
        _kept(plain, "current_sync_committee", s["current_sync_committee"],
              _sync_committee_root),
        _kept(plain, "next_sync_committee", s["next_sync_committee"],
              _sync_committee_root),
        _kept(plain, "latest_execution_payload_header",
              s["latest_execution_payload_header"], _payload_header_root),
        ssz.uint(s["next_withdrawal_index"]),
        ssz.uint(s["next_withdrawal_validator_index"]),
        ssz.roots_list(
            [ssz.container(list(x)) for x in s["historical_summaries"]],
            HISTORICAL_ROOTS_LIMIT,
        ),
    ])


# -- the epoch transition -----------------------------------------------------------


def _refuse(condition, what: str) -> None:
    if condition:
        raise NotImplementedError(f"the reference does not cover {what}")


def _active(c: dict, epoch: int) -> np.ndarray:
    return (c["activation_epoch"] <= U64(epoch)) & (U64(epoch) < c["exit_epoch"])


def process_epoch(plain: Plain) -> None:
    s, c = plain.scalars, plain.columns
    current = s["slot"] // SLOTS_PER_EPOCH
    previous = max(current - 1, GENESIS_EPOCH)
    following = current + 1
    slashed = c["slashed"].astype(bool)
    eff = c["effective_balance"]
    _refuse(slashed.any(), "slashed validators")

    active_previous = _active(c, previous)
    active_current = _active(c, current)
    eligible = active_previous | (
        slashed & (U64(previous + 1) < c["withdrawable_epoch"])
    )
    total_active = max(EFFECTIVE_BALANCE_INCREMENT, int(eff[active_current].sum()))

    def flagged(participation: np.ndarray, active: np.ndarray, flag: int):
        return active & ((participation >> np.uint8(flag)) & np.uint8(1)).astype(bool) & ~slashed

    part = c["previous_epoch_participation"] if previous != current else (
        c["current_epoch_participation"]
    )
    participating = [flagged(part, active_previous, flag) for flag in range(3)]

    # process_justification_and_finalization: skipped up to epoch 1
    if current > GENESIS_EPOCH + 1:
        def target_balance(indices: np.ndarray) -> int:
            return max(EFFECTIVE_BALANCE_INCREMENT, int(eff[indices].sum()))

        _weigh_justification_and_finalization(
            s, previous, current, total_active,
            target_balance(participating[TIMELY_TARGET]),
            target_balance(flagged(
                c["current_epoch_participation"], active_current, TIMELY_TARGET
            )),
        )
    leak = (previous - s["finalized_checkpoint"][0]) > MIN_EPOCHS_TO_INACTIVITY_PENALTY

    if current != GENESIS_EPOCH:
        # process_inactivity_updates
        scores = c["inactivity_scores"].copy()
        hit = eligible & participating[TIMELY_TARGET]
        miss = eligible & ~participating[TIMELY_TARGET]
        scores[hit] -= np.minimum(U64(1), scores[hit])
        scores[miss] += U64(INACTIVITY_SCORE_BIAS)
        if not leak:
            scores[eligible] -= np.minimum(
                U64(INACTIVITY_SCORE_RECOVERY_RATE), scores[eligible]
            )
        if not np.array_equal(scores, c["inactivity_scores"]):
            c["inactivity_scores"] = scores  # else the array and its root stay

        # process_rewards_and_penalties
        per_increment = (
            EFFECTIVE_BALANCE_INCREMENT * BASE_REWARD_FACTOR // isqrt(total_active)
        )
        base_reward = eff // U64(EFFECTIVE_BALANCE_INCREMENT) * U64(per_increment)
        active_increments = total_active // EFFECTIVE_BALANCE_INCREMENT
        balances = c["balances"].copy()
        for flag, weight in enumerate(FLAG_WEIGHTS):
            took_part = participating[flag]
            flag_balance = max(EFFECTIVE_BALANCE_INCREMENT, int(eff[took_part].sum()))
            increments = flag_balance // EFFECTIVE_BALANCE_INCREMENT
            rewards = np.zeros_like(balances)
            penalties = np.zeros_like(balances)
            rewarded = eligible & took_part
            if not leak:
                rewards[rewarded] = (
                    base_reward[rewarded] * U64(weight) * U64(increments)
                ) // U64(active_increments * WEIGHT_DENOMINATOR)
            if flag != TIMELY_HEAD:
                punished = eligible & ~took_part
                penalties[punished] = (
                    base_reward[punished] * U64(weight)
                ) // U64(WEIGHT_DENOMINATOR)
            balances = balances + rewards
            balances = np.where(penalties > balances, U64(0), balances - penalties)
        inactive = eligible & ~participating[TIMELY_TARGET]
        penalties = np.zeros_like(balances)
        penalties[inactive] = (eff[inactive] * c["inactivity_scores"][inactive]) // U64(
            INACTIVITY_SCORE_BIAS * INACTIVITY_PENALTY_QUOTIENT_BELLATRIX
        )
        balances = np.where(penalties > balances, U64(0), balances - penalties)
        c["balances"] = balances

    # process_registry_updates: nobody to queue, eject or activate
    _refuse(
        ((c["activation_eligibility_epoch"] == U64(FAR_FUTURE_EPOCH))
         & (eff == U64(MAX_EFFECTIVE_BALANCE))).any(),
        "validators becoming eligible for activation",
    )
    _refuse(
        (active_current & (eff <= U64(EJECTION_BALANCE))).any(), "ejections"
    )
    _refuse(
        (c["activation_epoch"] == U64(FAR_FUTURE_EPOCH)).any(), "an activation queue"
    )
    # process_slashings: nobody slashed (refused above)

    # process_eth1_data_reset
    if following % EPOCHS_PER_ETH1_VOTING_PERIOD == 0:
        s["eth1_data_votes"] = []

    # process_effective_balance_updates
    hysteresis = EFFECTIVE_BALANCE_INCREMENT // HYSTERESIS_QUOTIENT
    down = U64(hysteresis * HYSTERESIS_DOWNWARD_MULTIPLIER)
    up = U64(hysteresis * HYSTERESIS_UPWARD_MULTIPLIER)
    balances = c["balances"]
    moved = (balances + down < eff) | (eff + up < balances)
    if moved.any():
        new_eff = np.minimum(
            balances - balances % U64(EFFECTIVE_BALANCE_INCREMENT),
            U64(MAX_EFFECTIVE_BALANCE),
        )
        c["effective_balance"] = np.where(moved, new_eff, eff)
        plain.validators_root = None

    # process_slashings_reset, process_randao_mixes_reset
    s["slashings"][following % EPOCHS_PER_SLASHINGS_VECTOR] = 0
    plain.memo.pop("slashings", None)  # written into, not replaced
    s["randao_mixes"][following % EPOCHS_PER_HISTORICAL_VECTOR] = s["randao_mixes"][
        current % EPOCHS_PER_HISTORICAL_VECTOR
    ]
    # process_historical_summaries_update
    _refuse(
        following % (SLOTS_PER_HISTORICAL_ROOT // SLOTS_PER_EPOCH) == 0,
        "a historical summary",
    )
    # process_participation_flag_updates
    c["previous_epoch_participation"] = c["current_epoch_participation"]
    c["current_epoch_participation"] = np.zeros_like(c["previous_epoch_participation"])
    # process_sync_committee_updates
    _refuse(
        following % EPOCHS_PER_SYNC_COMMITTEE_PERIOD == 0, "a sync committee rotation"
    )


def _weigh_justification_and_finalization(
    s: dict, previous: int, current: int, total_active: int,
    previous_target: int, current_target: int,
) -> None:
    def block_root_at_start_of(epoch: int) -> bytes:
        slot = epoch * SLOTS_PER_EPOCH
        if not slot < s["slot"] <= slot + SLOTS_PER_HISTORICAL_ROOT:
            raise ValueError("block root out of the vector's reach")
        return s["block_roots"][slot % SLOTS_PER_HISTORICAL_ROOT]

    old_previous = s["previous_justified_checkpoint"]
    old_current = s["current_justified_checkpoint"]
    s["previous_justified_checkpoint"] = old_current
    bits = [False] + s["justification_bits"][:-1]
    if previous_target * 3 >= total_active * 2:
        s["current_justified_checkpoint"] = (previous, block_root_at_start_of(previous))
        bits[1] = True
    if current_target * 3 >= total_active * 2:
        s["current_justified_checkpoint"] = (current, block_root_at_start_of(current))
        bits[0] = True
    s["justification_bits"] = bits
    # the 2nd/3rd/4th, 2nd/3rd, 1st/2nd/3rd and 1st/2nd most recent epochs
    if all(bits[1:4]) and old_previous[0] + 3 == current:
        s["finalized_checkpoint"] = old_previous
    if all(bits[1:3]) and old_previous[0] + 2 == current:
        s["finalized_checkpoint"] = old_previous
    if all(bits[0:3]) and old_current[0] + 2 == current:
        s["finalized_checkpoint"] = old_current
    if all(bits[0:2]) and old_current[0] + 1 == current:
        s["finalized_checkpoint"] = old_current


def process_slot(plain: Plain) -> None:
    s = plain.scalars
    previous_state_root = state_root(plain)
    s["state_roots"][s["slot"] % SLOTS_PER_HISTORICAL_ROOT] = previous_state_root
    if s["latest_block_header"]["state_root"] == ssz.ZERO:
        s["latest_block_header"]["state_root"] = previous_state_root
    s["block_roots"][s["slot"] % SLOTS_PER_HISTORICAL_ROOT] = header_root(
        s["latest_block_header"]
    )


def process_slots(plain: Plain, slot: int) -> None:
    s = plain.scalars
    if s["slot"] >= slot:
        raise ValueError("cannot process slots backwards")
    while s["slot"] < slot:
        process_slot(plain)
        if (s["slot"] + 1) % SLOTS_PER_EPOCH == 0:
            process_epoch(plain)
        s["slot"] += 1


def chain_roots(state, target_slot: int, refills: list) -> list:
    """The roots after each crossing of a chain, worked out again from the
    state's plain values: ``process_slots(state, target_slot)``; then, for
    each of ``refills`` (uint8 participation flags of every validator), 31
    empty slots, that epoch's ``current_epoch_participation`` set to the
    flags, and the next crossing. ``len(refills) + 1`` roots. ``state`` is
    only read."""
    plain = read_state(state)
    process_slots(plain, target_slot)
    roots = [state_root(plain)]
    for flags in refills:
        target_slot += SLOTS_PER_EPOCH
        process_slots(plain, target_slot - 1)
        plain.columns["current_epoch_participation"] = np.asarray(flags, dtype=np.uint8)
        process_slots(plain, target_slot)
        roots.append(state_root(plain))
    return roots


def crossing_root(state, target_slot: int) -> bytes:
    """The root after ``process_slots(state, target_slot)``."""
    return chain_roots(state, target_slot, [])[0]
