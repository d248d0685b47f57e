"""The plain reference of the epoch cell on a chain that does not finalize:
``deneb_epoch.py``'s chain walk with the stages an inactivity leak changes
written out from the consensus specification, each under the
specification's own name, over columns in numpy:

- phase0 ``get_finality_delay`` / ``is_in_inactivity_leak``;
- altair ``process_inactivity_updates`` (no recovery in a leak);
- altair ``get_flag_index_deltas`` (no rewards in a leak; the head flag is
  never penalised) and ``get_inactivity_penalty_deltas`` (off the scores as
  ``process_inactivity_updates`` has just left them, quotient
  ``INACTIVITY_PENALTY_QUOTIENT_BELLATRIX``), applied one after the other by
  ``process_rewards_and_penalties``;
- phase0 ``process_effective_balance_updates``, which hands the rows it
  wrote to the validators' tree.

The same stages outside a leak are here too (a chain may enter a leak or
leave one). It takes from ``deneb_epoch.py`` what the leak does not touch
(reading a state's plain values, the state's root, ``process_slot``, the
weighing of justification) and from ``deneb_epoch_registry.py`` the
``validators`` list kept as its whole tree: some hundreds of effective
balances move at every boundary of a leak, and ``deneb_epoch.py`` would hash
all 2^20 validators again for them. It imports nothing of the program. It
refuses what its worlds cannot reach: a slashed validator, an activation
queue, an ejection, a sync committee rotation, a historical summary.

``counts`` (filled by ``chain_roots`` when handed a dict) is what the
crossings did, for the tests of the program's counters: a list a crossing of
``leaking``, ``scores_changed`` and ``eff_changed``."""

from __future__ import annotations

from math import isqrt

import numpy as np

from benchmark.reference import deneb_epoch as base
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
    WEIGHT_DENOMINATOR,
    Plain,
)
from benchmark.reference.deneb_epoch_registry import ValidatorsTree


# -- the helpers of the specification, over columns ---------------------------------


def get_previous_epoch(current: int) -> int:
    return GENESIS_EPOCH if current == GENESIS_EPOCH else current - 1


def is_active_validator(c: dict, epoch: int) -> np.ndarray:
    return (c["activation_epoch"] <= U64(epoch)) & (U64(epoch) < c["exit_epoch"])


def get_total_balance(c: dict, members: np.ndarray) -> int:
    """Never below one increment."""
    return max(
        EFFECTIVE_BALANCE_INCREMENT, int(c["effective_balance"][members].sum())
    )


def get_total_active_balance(c: dict, current: int) -> int:
    return get_total_balance(c, is_active_validator(c, current))


def get_unslashed_participating_indices(
    c: dict, flag: int, epoch: int, current: int
) -> np.ndarray:
    """As a mask. Nobody is slashed here (``process_epoch`` refuses it)."""
    flags = c["current_epoch_participation" if epoch == current
              else "previous_epoch_participation"]
    has_flag = ((flags >> np.uint8(flag)) & np.uint8(1)).astype(bool)
    return is_active_validator(c, epoch) & has_flag


def get_eligible_validator_indices(c: dict, current: int) -> np.ndarray:
    """As a mask: active in the previous epoch (nobody is slashed)."""
    return is_active_validator(c, get_previous_epoch(current))


def get_finality_delay(plain: Plain, current: int) -> int:
    return get_previous_epoch(current) - plain.scalars["finalized_checkpoint"][0]


def is_in_inactivity_leak(plain: Plain, current: int) -> bool:
    return get_finality_delay(plain, current) > MIN_EPOCHS_TO_INACTIVITY_PENALTY


def decrease_balance(balances: np.ndarray, penalties: np.ndarray) -> np.ndarray:
    """On every row: never below zero."""
    return np.where(penalties > balances, U64(0), balances - penalties)


# -- the stages of process_epoch ----------------------------------------------------


def process_justification_and_finalization(plain: Plain, current: int) -> None:
    if current <= GENESIS_EPOCH + 1:
        return
    c = plain.columns
    previous = get_previous_epoch(current)
    base._weigh_justification_and_finalization(
        plain.scalars, previous, current,
        get_total_active_balance(c, current),
        get_total_balance(
            c, get_unslashed_participating_indices(c, TIMELY_TARGET, previous, current)
        ),
        get_total_balance(
            c, get_unslashed_participating_indices(c, TIMELY_TARGET, current, current)
        ),
    )


def process_inactivity_updates(plain: Plain, current: int) -> None:
    if current == GENESIS_EPOCH:
        return
    c = plain.columns
    eligible = get_eligible_validator_indices(c, current)
    on_target = get_unslashed_participating_indices(
        c, TIMELY_TARGET, get_previous_epoch(current), current
    )
    scores = c["inactivity_scores"].copy()
    # increase the score on a missed target, else decrease it by one
    hit, miss = eligible & on_target, eligible & ~on_target
    scores[hit] -= np.minimum(U64(1), scores[hit])
    scores[miss] += U64(INACTIVITY_SCORE_BIAS)
    # decrease the score of every eligible validator during a leak-free epoch
    if not is_in_inactivity_leak(plain, current):
        scores[eligible] -= np.minimum(
            U64(INACTIVITY_SCORE_RECOVERY_RATE), scores[eligible]
        )
    if not np.array_equal(scores, c["inactivity_scores"]):
        c["inactivity_scores"] = scores  # else the array and its root stay


def get_base_reward(c: dict, current: int) -> np.ndarray:
    per_increment = (
        EFFECTIVE_BALANCE_INCREMENT * BASE_REWARD_FACTOR
        // isqrt(get_total_active_balance(c, current))
    )
    increments = c["effective_balance"] // U64(EFFECTIVE_BALANCE_INCREMENT)
    return increments * U64(per_increment)


def get_flag_index_deltas(plain: Plain, flag: int, current: int) -> tuple:
    """(rewards, penalties) of one participation flag."""
    c = plain.columns
    previous = get_previous_epoch(current)
    took_part = get_unslashed_participating_indices(c, flag, previous, current)
    weight = FLAG_WEIGHTS[flag]
    unslashed_increments = (
        get_total_balance(c, took_part) // EFFECTIVE_BALANCE_INCREMENT
    )
    active_increments = (
        get_total_active_balance(c, current) // EFFECTIVE_BALANCE_INCREMENT
    )
    base_reward = get_base_reward(c, current)
    eligible = get_eligible_validator_indices(c, current)
    rewards = np.zeros_like(base_reward)
    penalties = np.zeros_like(base_reward)
    rewarded = eligible & took_part
    if not is_in_inactivity_leak(plain, current):
        rewards[rewarded] = (
            base_reward[rewarded] * U64(weight) * U64(unslashed_increments)
        ) // U64(active_increments * WEIGHT_DENOMINATOR)
    if flag != TIMELY_HEAD:
        punished = eligible & ~took_part
        penalties[punished] = (
            base_reward[punished] * U64(weight) // U64(WEIGHT_DENOMINATOR)
        )
    return rewards, penalties


def get_inactivity_penalty_deltas(plain: Plain, current: int) -> tuple:
    """(rewards, penalties): the scores are read as
    ``process_inactivity_updates`` has just left them, in a leak or not."""
    c = plain.columns
    off_target = get_eligible_validator_indices(c, current) & ~(
        get_unslashed_participating_indices(
            c, TIMELY_TARGET, get_previous_epoch(current), current
        )
    )
    penalties = np.zeros_like(c["balances"])
    penalties[off_target] = (
        c["effective_balance"][off_target] * c["inactivity_scores"][off_target]
    ) // U64(INACTIVITY_SCORE_BIAS * INACTIVITY_PENALTY_QUOTIENT_BELLATRIX)
    return np.zeros_like(penalties), penalties


def process_rewards_and_penalties(plain: Plain, current: int) -> None:
    if current == GENESIS_EPOCH:
        return
    deltas = [
        get_flag_index_deltas(plain, flag, current)
        for flag in range(len(FLAG_WEIGHTS))
    ]
    deltas.append(get_inactivity_penalty_deltas(plain, current))
    balances = plain.columns["balances"]
    for rewards, penalties in deltas:  # each applied in turn, to every row
        balances = decrease_balance(balances + rewards, penalties)
    plain.columns["balances"] = balances


def process_registry_updates(plain: Plain, current: int) -> None:
    """Nobody to queue, to eject or to activate: refused, not answered."""
    c = plain.columns
    far = U64(FAR_FUTURE_EPOCH)
    base._refuse(
        ((c["activation_eligibility_epoch"] == far)
         & (c["effective_balance"] == U64(MAX_EFFECTIVE_BALANCE))).any(),
        "validators becoming eligible for activation",
    )
    base._refuse(
        (is_active_validator(c, current)
         & (c["effective_balance"] <= U64(EJECTION_BALANCE))).any(),
        "ejections",
    )
    base._refuse((c["activation_epoch"] == far).any(), "an activation queue")


def process_effective_balance_updates(plain: Plain, tree: ValidatorsTree) -> int:
    """The rows written, handed to the validators' tree; their count."""
    c = plain.columns
    eff, balances = c["effective_balance"], c["balances"]
    hysteresis_increment = EFFECTIVE_BALANCE_INCREMENT // HYSTERESIS_QUOTIENT
    downward = U64(hysteresis_increment * HYSTERESIS_DOWNWARD_MULTIPLIER)
    upward = U64(hysteresis_increment * HYSTERESIS_UPWARD_MULTIPLIER)
    moved = (balances + downward < eff) | (eff + upward < balances)
    rows = np.nonzero(moved)[0]
    if not len(rows):
        return 0
    updated = eff.copy()
    updated[rows] = np.minimum(
        balances[rows] - balances[rows] % U64(EFFECTIVE_BALANCE_INCREMENT),
        U64(MAX_EFFECTIVE_BALANCE),
    )
    rows = rows[updated[rows] != eff[rows]]
    c["effective_balance"] = updated
    tree.update(c, rows)
    plain.validators_root = tree.root()
    return len(rows)


def process_epoch(plain: Plain, tree: ValidatorsTree) -> dict:
    """One epoch's transition; returns what it did, for ``counts``."""
    s, c = plain.scalars, plain.columns
    current = s["slot"] // SLOTS_PER_EPOCH
    following = current + 1
    base._refuse(c["slashed"].any(), "slashed validators")

    process_justification_and_finalization(plain, current)
    leaking = is_in_inactivity_leak(plain, current)
    scores_before = c["inactivity_scores"]
    process_inactivity_updates(plain, current)
    scores_changed = int((c["inactivity_scores"] != scores_before).sum())
    process_rewards_and_penalties(plain, current)
    process_registry_updates(plain, current)
    # process_slashings: nobody slashed (refused above)
    # process_eth1_data_reset
    if following % EPOCHS_PER_ETH1_VOTING_PERIOD == 0:
        s["eth1_data_votes"] = []
    eff_changed = process_effective_balance_updates(plain, tree)
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
    return {
        "leaking": leaking, "scores_changed": scores_changed,
        "eff_changed": eff_changed,
    }


def process_slots(plain: Plain, tree: ValidatorsTree, slot: int) -> list:
    """Returns what each epoch transition on the way did."""
    s = plain.scalars
    if s["slot"] >= slot:
        raise ValueError("cannot process slots backwards")
    did = []
    while s["slot"] < slot:
        base.process_slot(plain)
        if (s["slot"] + 1) % SLOTS_PER_EPOCH == 0:
            did.append(process_epoch(plain, tree))
        s["slot"] += 1
    return did


def read_state(state) -> tuple:
    """(plain values, the validators' tree) of a generated deneb state."""
    plain = base.read_state(state)
    tree = ValidatorsTree(plain.columns)
    plain.validators_root = tree.root()
    return plain, tree


def chain_roots(state, target_slot: int, refills: list, counts: "dict | None" = None) -> list:
    """The roots after each crossing of a chain: ``deneb_epoch.chain_roots``
    with this file's epoch transition. ``state`` is only read."""
    plain, tree = read_state(state)
    did = process_slots(plain, tree, target_slot)
    roots = [base.state_root(plain)]
    for flags in refills:
        target_slot += SLOTS_PER_EPOCH
        process_slots(plain, tree, target_slot - 1)
        plain.columns["current_epoch_participation"] = np.asarray(flags, dtype=np.uint8)
        did += process_slots(plain, tree, target_slot)
        roots.append(base.state_root(plain))
    if counts is not None:
        for name in ("leaking", "scores_changed", "eff_changed"):
            counts[name] = [crossing[name] for crossing in did]
    return roots
