"""The plain reference of the epoch cell on a registry that is not all
active: ``deneb_epoch.py``'s chain walk with the branches it refuses written
out from the consensus specification (specs/{phase0,altair,deneb}/
beacon-chain.md): slashed validators in ``eligible`` and in every flag mask,
``process_registry_updates`` (the stamp on a fresh deposit, the sorted
activation queue under ``get_validator_activation_churn_limit``) and
``process_slashings`` in full. Each stage below is the specification's
function of that name, over columns in numpy.

It takes from ``deneb_epoch.py`` what the registry does not touch (reading a
state's plain values, the state's root, ``process_slot``, the weighing of
justification) and from ``ssz.py`` the hashing; it imports nothing of the
program. It still refuses what its worlds cannot reach: an ejection, a sync
committee rotation, a historical summary.

A boundary of this deployment writes a few validators' epochs, so the
``validators`` list is kept as its whole tree (``ValidatorsTree``): a
boundary costs the paths of the rows it wrote, every hash of them computed
here from the values, and not the 2^21 leaves again."""

from __future__ import annotations

from math import isqrt

import numpy as np

from benchmark.reference import deneb_epoch as base
from benchmark.reference import ssz
from benchmark.reference.deneb_epoch import (
    BASE_REWARD_FACTOR,
    EFFECTIVE_BALANCE_INCREMENT,
    EJECTION_BALANCE,
    EPOCHS_PER_ETH1_VOTING_PERIOD,
    EPOCHS_PER_HISTORICAL_VECTOR,
    EPOCHS_PER_SLASHINGS_VECTOR,
    EPOCHS_PER_SYNC_COMMITTEE_PERIOD,
    FAR_FUTURE_EPOCH,
    FLAG_WEIGHTS,
    GENESIS_EPOCH,
    HYSTERESIS_DOWNWARD_MULTIPLIER,
    HYSTERESIS_QUOTIENT,
    HYSTERESIS_UPWARD_MULTIPLIER,
    INACTIVITY_PENALTY_QUOTIENT_BELLATRIX,
    INACTIVITY_SCORE_BIAS,
    INACTIVITY_SCORE_RECOVERY_RATE,
    MAX_EFFECTIVE_BALANCE,
    MIN_EPOCHS_TO_INACTIVITY_PENALTY,
    SLOTS_PER_EPOCH,
    SLOTS_PER_HISTORICAL_ROOT,
    TIMELY_HEAD,
    TIMELY_TARGET,
    U64,
    VALIDATOR_FIELDS,
    VALIDATOR_REGISTRY_LIMIT,
    WEIGHT_DENOMINATOR,
    Plain,
)

# configs/mainnet.yaml and presets/mainnet/{phase0,bellatrix}.yaml
MIN_PER_EPOCH_CHURN_LIMIT = 4
CHURN_LIMIT_QUOTIENT = 65536
MAX_PER_EPOCH_ACTIVATION_CHURN_LIMIT = 8
MAX_SEED_LOOKAHEAD = 4
PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX = 3


# -- the validators list as a tree ------------------------------------------------


def _zero_row(height: int) -> np.ndarray:
    return np.frombuffer(ssz.ZERO_HASHES[height], dtype=np.uint8).reshape(1, 32)


class ValidatorsTree:
    """List[Validator, 2^40] kept as its whole tree. A validator is eight
    leaves: key, credentials and six numbers. The node over key and
    credentials never changes here and is kept; the nodes over the numbers
    are hashed once for each distinct row of them."""

    def __init__(self, columns: dict):
        count = len(columns["public_key"])
        # a 48-byte key is two chunks: its root is one hash
        key_roots = base._as_rows(ssz.hash_pairs(
            b"".join(k + b"\x00" * 16 for k in columns["public_key"])
        ))
        credentials = np.frombuffer(
            b"".join(columns["withdrawal_credentials"]), dtype=np.uint8
        ).reshape(count, 32)
        self.key_and_credentials = base._hash_rows(key_roots, credentials)
        self.count = count
        # copies: a level is written into, and a digest buffer is read-only
        level = self._validator_roots(columns, np.arange(count)).copy()
        self.levels = [level]
        while len(level) > 1:
            level = base._hash_rows(
                *self._halves(level, len(self.levels) - 1)
            ).copy()
            self.levels.append(level)

    @staticmethod
    def _halves(level: np.ndarray, height: int) -> tuple:
        if len(level) % 2:
            level = np.concatenate([level, _zero_row(height)])
        return level[0::2], level[1::2]

    def _validator_roots(self, columns: dict, rows: np.ndarray) -> np.ndarray:
        numbers = np.stack([columns[name][rows] for name in VALIDATOR_FIELDS], axis=1)
        distinct, which = np.unique(numbers, axis=0, return_inverse=True)
        which = which.reshape(-1)
        chunk = [base._u64_chunks(distinct[:, k]) for k in range(6)]
        node_23 = base._hash_rows(chunk[0], chunk[1])
        node_4567 = base._hash_rows(
            base._hash_rows(chunk[2], chunk[3]), base._hash_rows(chunk[4], chunk[5])
        )
        node_0123 = base._hash_rows(self.key_and_credentials[rows], node_23[which])
        return base._hash_rows(node_0123, node_4567[which])

    def update(self, columns: dict, rows: np.ndarray) -> None:
        """``rows``' numbers have changed: their roots and their paths."""
        self.levels[0][rows] = self._validator_roots(columns, rows)
        for height in range(len(self.levels) - 1):
            below = self.levels[height]
            rows = np.unique(rows // 2)
            right = np.minimum(2 * rows + 1, len(below) - 1)
            rights = np.where(
                (2 * rows + 1 < len(below))[:, None], below[right], _zero_row(height)
            )
            self.levels[height + 1][rows] = base._hash_rows(below[2 * rows], rights)

    def root(self) -> bytes:
        node = self.levels[-1][0].tobytes()
        depth = (VALIDATOR_REGISTRY_LIMIT - 1).bit_length()
        for height in range(len(self.levels) - 1, depth):
            node = ssz.hash_pairs(node + ssz.ZERO_HASHES[height])
        return ssz.mix_in_length(node, self.count)


# -- the helpers of the specification, over columns ---------------------------------


def is_active(c: dict, epoch: int) -> np.ndarray:
    return (c["activation_epoch"] <= U64(epoch)) & (U64(epoch) < c["exit_epoch"])


def total_balance(c: dict, members: np.ndarray) -> int:
    """``get_total_balance``: never below one increment."""
    return max(
        EFFECTIVE_BALANCE_INCREMENT, int(c["effective_balance"][members].sum())
    )


def unslashed_participating(c: dict, flag: int, epoch: int, current: int) -> np.ndarray:
    """``get_unslashed_participating_indices`` as a mask."""
    flags = c["current_epoch_participation" if epoch == current
              else "previous_epoch_participation"]
    has_flag = ((flags >> np.uint8(flag)) & np.uint8(1)).astype(bool)
    return is_active(c, epoch) & has_flag & ~c["slashed"].astype(bool)


def eligible_validators(c: dict, previous: int) -> np.ndarray:
    """``get_eligible_validator_indices`` as a mask: active in the previous
    epoch, or slashed and not yet withdrawable."""
    return is_active(c, previous) | (
        c["slashed"].astype(bool) & (U64(previous + 1) < c["withdrawable_epoch"])
    )


def decrease(balances: np.ndarray, penalties: np.ndarray) -> np.ndarray:
    """``decrease_balance`` on every row: never below zero."""
    return np.where(penalties > balances, U64(0), balances - penalties)


# -- the stages of process_epoch ----------------------------------------------------


def process_justification_and_finalization(plain: Plain, current: int, previous: int):
    if current <= GENESIS_EPOCH + 1:
        return
    c = plain.columns
    base._weigh_justification_and_finalization(
        plain.scalars, previous, current,
        total_balance(c, is_active(c, current)),
        total_balance(c, unslashed_participating(c, TIMELY_TARGET, previous, current)),
        total_balance(c, unslashed_participating(c, TIMELY_TARGET, current, current)),
    )


def in_inactivity_leak(plain: Plain, previous: int) -> bool:
    finality_delay = previous - plain.scalars["finalized_checkpoint"][0]
    return finality_delay > MIN_EPOCHS_TO_INACTIVITY_PENALTY


def process_inactivity_updates(plain: Plain, current: int, previous: int) -> None:
    if current == GENESIS_EPOCH:
        return
    c = plain.columns
    eligible = eligible_validators(c, previous)
    on_target = unslashed_participating(c, TIMELY_TARGET, previous, current)
    scores = c["inactivity_scores"].copy()
    hit, miss = eligible & on_target, eligible & ~on_target
    scores[hit] -= np.minimum(U64(1), scores[hit])
    scores[miss] += U64(INACTIVITY_SCORE_BIAS)
    if not in_inactivity_leak(plain, previous):
        scores[eligible] -= np.minimum(
            U64(INACTIVITY_SCORE_RECOVERY_RATE), scores[eligible]
        )
    if not np.array_equal(scores, c["inactivity_scores"]):
        c["inactivity_scores"] = scores  # else the array and its root stay


def process_rewards_and_penalties(plain: Plain, current: int, previous: int) -> None:
    if current == GENESIS_EPOCH:
        return
    c = plain.columns
    eff = c["effective_balance"]
    eligible = eligible_validators(c, previous)
    leak = in_inactivity_leak(plain, previous)
    total_active = total_balance(c, is_active(c, current))
    per_increment = (
        EFFECTIVE_BALANCE_INCREMENT * BASE_REWARD_FACTOR // isqrt(total_active)
    )
    base_reward = eff // U64(EFFECTIVE_BALANCE_INCREMENT) * U64(per_increment)
    active_increments = total_active // EFFECTIVE_BALANCE_INCREMENT
    balances = c["balances"]
    # get_flag_index_deltas, one flag after the other, each applied in turn
    for flag, weight in enumerate(FLAG_WEIGHTS):
        took_part = unslashed_participating(c, flag, previous, current)
        increments = total_balance(c, took_part) // EFFECTIVE_BALANCE_INCREMENT
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
        balances = decrease(balances + rewards, penalties)
    # get_inactivity_penalty_deltas, on the scores as just updated
    off_target = eligible & ~unslashed_participating(c, TIMELY_TARGET, previous, current)
    penalties = np.zeros_like(balances)
    penalties[off_target] = (
        eff[off_target] * c["inactivity_scores"][off_target]
    ) // U64(INACTIVITY_SCORE_BIAS * INACTIVITY_PENALTY_QUOTIENT_BELLATRIX)
    c["balances"] = decrease(balances, penalties)


def process_registry_updates(plain: Plain, current: int) -> None:
    c = plain.columns
    far = U64(FAR_FUTURE_EPOCH)
    active = is_active(c, current)
    # is_eligible_for_activation_queue: stamped with the epoch after this one
    fresh = (c["activation_eligibility_epoch"] == far) & (
        c["effective_balance"] == U64(MAX_EFFECTIVE_BALANCE)
    )
    if fresh.any():
        c["activation_eligibility_epoch"] = np.where(
            fresh, U64(current + 1), c["activation_eligibility_epoch"]
        )
    base._refuse(
        (active & (c["effective_balance"] <= U64(EJECTION_BALANCE))).any(),
        "ejections",
    )
    # is_eligible_for_activation, read after the stamps; the queue is sorted
    # by (eligibility epoch, index) and cut to the activation churn limit
    finalized = plain.scalars["finalized_checkpoint"][0]
    waiting = np.nonzero(
        (c["activation_eligibility_epoch"] <= U64(finalized))
        & (c["activation_epoch"] == far)
    )[0]
    queue = sorted(
        waiting.tolist(),
        key=lambda index: (int(c["activation_eligibility_epoch"][index]), index),
    )
    churn_limit = max(
        MIN_PER_EPOCH_CHURN_LIMIT, int(active.sum()) // CHURN_LIMIT_QUOTIENT
    )
    activation_churn_limit = min(MAX_PER_EPOCH_ACTIVATION_CHURN_LIMIT, churn_limit)
    dequeued = queue[:activation_churn_limit]
    if dequeued:
        activation = c["activation_epoch"].copy()
        # compute_activation_exit_epoch
        activation[dequeued] = current + 1 + MAX_SEED_LOOKAHEAD
        c["activation_epoch"] = activation


def process_slashings(plain: Plain, current: int) -> None:
    """In full. This deployment's slashed rows became withdrawable long ago,
    so the scan finds nobody at the halfway point: it is still made."""
    c = plain.columns
    total = total_balance(c, is_active(c, current))
    adjusted = min(
        sum(plain.scalars["slashings"].tolist())
        * PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX,
        total,
    )
    halfway = c["slashed"].astype(bool) & (
        c["withdrawable_epoch"] == U64(current + EPOCHS_PER_SLASHINGS_VECTOR // 2)
    )
    due = np.nonzero(halfway)[0].tolist()
    if not due:
        return
    balances = c["balances"].copy()
    for index in due:  # whole numbers: the product passes 64 bits
        increments = int(c["effective_balance"][index]) // EFFECTIVE_BALANCE_INCREMENT
        penalty = increments * adjusted // total * EFFECTIVE_BALANCE_INCREMENT
        balances[index] = max(0, int(balances[index]) - penalty)
    c["balances"] = balances


def process_effective_balance_updates(plain: Plain) -> None:
    c = plain.columns
    eff, balances = c["effective_balance"], c["balances"]
    hysteresis = EFFECTIVE_BALANCE_INCREMENT // HYSTERESIS_QUOTIENT
    down = U64(hysteresis * HYSTERESIS_DOWNWARD_MULTIPLIER)
    up = U64(hysteresis * HYSTERESIS_UPWARD_MULTIPLIER)
    moved = (balances + down < eff) | (eff + up < balances)
    if moved.any():
        c["effective_balance"] = np.where(
            moved,
            np.minimum(
                balances - balances % U64(EFFECTIVE_BALANCE_INCREMENT),
                U64(MAX_EFFECTIVE_BALANCE),
            ),
            eff,
        )


def process_epoch(plain: Plain, tree: ValidatorsTree) -> None:
    s, c = plain.scalars, plain.columns
    current = s["slot"] // SLOTS_PER_EPOCH
    previous = max(current - 1, GENESIS_EPOCH)
    following = current + 1
    before = {name: c[name] for name in VALIDATOR_FIELDS}

    process_justification_and_finalization(plain, current, previous)
    process_inactivity_updates(plain, current, previous)
    process_rewards_and_penalties(plain, current, previous)
    process_registry_updates(plain, current)
    process_slashings(plain, current)
    # process_eth1_data_reset
    if following % EPOCHS_PER_ETH1_VOTING_PERIOD == 0:
        s["eth1_data_votes"] = []
    process_effective_balance_updates(plain)
    # process_slashings_reset, process_randao_mixes_reset
    s["slashings"][following % EPOCHS_PER_SLASHINGS_VECTOR] = 0
    plain.memo.pop("slashings", None)  # written into, not replaced
    s["randao_mixes"][following % EPOCHS_PER_HISTORICAL_VECTOR] = s["randao_mixes"][
        current % EPOCHS_PER_HISTORICAL_VECTOR
    ]
    # process_historical_summaries_update
    base._refuse(
        following % (SLOTS_PER_HISTORICAL_ROOT // SLOTS_PER_EPOCH) == 0,
        "a historical summary",
    )
    # process_participation_flag_updates
    c["previous_epoch_participation"] = c["current_epoch_participation"]
    c["current_epoch_participation"] = np.zeros_like(c["previous_epoch_participation"])
    # process_sync_committee_updates
    base._refuse(
        following % EPOCHS_PER_SYNC_COMMITTEE_PERIOD == 0, "a sync committee rotation"
    )

    # the validators' root: the rows whose numbers a stage replaced
    written = np.zeros(tree.count, dtype=bool)
    for name, old in before.items():
        if c[name] is not old:
            written |= c[name] != old
    if written.any():
        tree.update(c, np.nonzero(written)[0])
        plain.validators_root = tree.root()


def process_slots(plain: Plain, tree: ValidatorsTree, slot: int) -> None:
    s = plain.scalars
    if s["slot"] >= slot:
        raise ValueError("cannot process slots backwards")
    while s["slot"] < slot:
        base.process_slot(plain)
        if (s["slot"] + 1) % SLOTS_PER_EPOCH == 0:
            process_epoch(plain, tree)
        s["slot"] += 1


def read_state(state) -> tuple:
    """(plain values, the validators' tree) of a generated deneb state."""
    plain = base.read_state(state)
    tree = ValidatorsTree(plain.columns)
    plain.validators_root = tree.root()
    return plain, tree


def chain_roots(state, target_slot: int, refills: list) -> list:
    """The roots after each crossing of a chain: ``deneb_epoch.chain_roots``
    with this file's epoch transition. ``state`` is only read."""
    plain, tree = read_state(state)
    process_slots(plain, tree, target_slot)
    roots = [base.state_root(plain)]
    for flags in refills:
        target_slot += SLOTS_PER_EPOCH
        process_slots(plain, tree, target_slot - 1)
        plain.columns["current_epoch_participation"] = np.asarray(flags, dtype=np.uint8)
        process_slots(plain, tree, target_slot)
        roots.append(base.state_root(plain))
    return roots
