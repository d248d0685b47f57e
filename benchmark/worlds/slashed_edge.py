"""World kind ``slashed_edge``: a state on the last slot of an epoch, with
finality, halfway through a correlated slashing: at every crossing of a
chain one epoch's slashed rows reach ``withdrawable_epoch - 4096`` and pay
``process_slashings``' proportional penalty.

The composition is the configuration file's ``slashing`` group, every draw
from ``--seed`` by a named stream: which rows were slashed (runs of
``run_length`` adjacent indices, one operator's deposit batches, at seeded
places across the registry, never a prefix or a tail), in which epoch, what
the exit queue gave them and what the epochs since took from them.
``composition`` makes the columns; ``build`` makes the state.

The state is made directly: the registry of ``registry.py`` at slot 0 with
the slashed rows' fields, the balances, the effective balances and
``state.slashings`` written, then ``slot``, the checkpoints and the
justification bits. The epochs before it are not walked: what they did to a
slashed row is summed here, epoch by epoch, as the specification takes it
(``_bleed``). One boundary is crossed first, the genesis epoch's, on the
plain host path and before the jump, as ``leak_edge`` crosses it: by the
specification it pays and takes nothing and moves no effective balance of
this registry (asserted), and it leaves the lists with the working columns
that a node which has been crossing boundaries holds.

Participation: an exited slashed row carries no flag, in either list of the
world and in every refill; of the other rows a seeded share between
``miss_share`` = [low, high] misses each flag, per list, as ``epoch_edge``
draws it.

A test that cuts ``validators`` gets ``slashed``, ``per_epoch`` and
``run_length`` times ``validators / at_validators``, never below 2; the
slot and the epochs are never scaled. The cell runs the counts as written."""

from __future__ import annotations

import bisect
from dataclasses import dataclass
from math import isqrt

import numpy as np

from . import registry
from .epoch_edge import EpochEdgeWorld, participation

U64 = np.uint64
ETH = registry.GWEI_PER_ETH
FAR_FUTURE_EPOCH = (1 << 64) - 1
SLOTS_PER_EPOCH = 32
# presets/mainnet/{phase0,altair}.yaml, what ``_bleed`` needs of them
BASE_REWARD_FACTOR = 64
WEIGHT_DENOMINATOR = 64
PENALISED_FLAG_WEIGHTS = (14, 26)  # source, target; the head flag never is


@dataclass
class Composition:
    """The registry's columns the slashing has touched."""

    effective_balance: np.ndarray   # uint64[n]
    balances: np.ndarray            # uint64[n]
    slashed: np.ndarray             # row indices in slashing order
    slashed_epoch: np.ndarray       # the epoch each of them was slashed in
    exit_epoch: np.ndarray          # what initiate_validator_exit gave each
    withdrawable_epoch: np.ndarray  # max(exit + delay, slashed + vector)
    is_slashed: np.ndarray          # bool[n]
    slashings: dict                 # epoch -> state.slashings[epoch % vector]


def counts(config: dict) -> tuple:
    """(slashed, per_epoch, run_length) at ``config``'s size."""
    group = config["slashing"]
    n, full = int(config["validators"]), int(group["at_validators"])

    def scaled(key: str) -> int:
        count = int(group[key])
        return count if n == full else max(2, count * n // full)

    slashed, per_epoch, run = scaled("slashed"), scaled("per_epoch"), scaled("run_length")
    first, last = group["epochs"]
    if slashed != per_epoch * (last - first + 1) or per_epoch % run:
        raise ValueError("the slashing group's counts do not fit together")
    return slashed, per_epoch, run


def _slashed_rows(config: dict, seed: int) -> tuple:
    """(rows in slashing order, their slashing epochs): runs of ``run``
    adjacent indices, each inside a block of ``2 * run`` rows of its own at a
    seeded offset; never the first or last block."""
    n = int(config["validators"])
    slashed, per_epoch, run = counts(config)
    rng = registry.rng_for(seed, "slashed-runs")
    blocks = rng.choice(np.arange(1, n // (2 * run) - 1), slashed // run, replace=False)
    starts = blocks * (2 * run) + rng.integers(0, run + 1, len(blocks))
    rows = (starts[:, None] + np.arange(run)[None, :]).reshape(-1)
    first = int(config["slashing"]["epochs"][0])
    return rows, first + np.arange(slashed) // per_epoch


def _exit_queue(epochs: np.ndarray, n: int, shapes: dict) -> np.ndarray:
    """``initiate_validator_exit`` for each row, in slashing order, at its
    slashing epoch: the queue's last epoch, or compute_activation_exit_epoch,
    one later where that epoch holds the churn limit; the churn limit counts
    the rows active in the epoch of the call. Nobody else is exiting."""
    lookahead = int(shapes["MAX_SEED_LOOKAHEAD"])
    quotient = int(shapes["CHURN_LIMIT_QUOTIENT"])
    least = int(shapes["MIN_PER_EPOCH_CHURN_LIMIT"])
    exits: list = []  # non-decreasing
    for epoch in epochs.tolist():
        active = n - bisect.bisect_right(exits, epoch)
        churn = max(least, active // quotient)
        queue = max(exits[-1] if exits else 0, epoch + 1 + lookahead)
        if bisect.bisect_right(exits, queue) - bisect.bisect_left(exits, queue) >= churn:
            queue += 1
        exits.append(queue)
    return np.asarray(exits, dtype=U64)


def _bleed(shapes: dict, n: int, start: np.ndarray, slashed_epoch: np.ndarray,
           exit_epoch: np.ndarray, last_epoch: int):
    """(balances, effective balances) of the slashed rows after the
    boundaries up to ``last_epoch``: a row starts at ``start`` gwei and 32 ETH
    effective; in its slashing epoch it loses ``effective_balance //
    MIN_SLASHING_PENALTY_QUOTIENT_BELLATRIX``; at every boundary from then on
    it is eligible and flagless, so it pays the source and target penalties
    of its base reward (its score goes +4 and back to 0: finality holds),
    then the hysteresis. The base reward follows the total active balance:
    every row not yet exited, the slashed ones at their effective balance of
    the moment, the others at 32 ETH."""
    max_effective = int(shapes["MAX_EFFECTIVE_BALANCE"])
    quotient = U64(int(shapes["MIN_SLASHING_PENALTY_QUOTIENT_BELLATRIX"]))
    down = U64(ETH // int(shapes["HYSTERESIS_QUOTIENT"])
               * int(shapes["HYSTERESIS_DOWNWARD_MULTIPLIER"]))
    balances = start.copy()
    eff = np.full(len(start), max_effective, dtype=U64)
    others = (n - len(start)) * max_effective
    for epoch in range(int(slashed_epoch.min()), last_epoch + 1):
        now = slashed_epoch == epoch
        balances[now] -= eff[now] // quotient
        total_active = others + int(eff[exit_epoch > U64(epoch)].sum())
        per_increment = ETH * BASE_REWARD_FACTOR // isqrt(total_active)
        paying = slashed_epoch <= epoch
        base_reward = eff[paying] // U64(ETH) * U64(per_increment)
        for weight in PENALISED_FLAG_WEIGHTS:
            balances[paying] -= base_reward * U64(weight) // U64(WEIGHT_DENOMINATOR)
        stepped = paying & (balances + down < eff)
        eff[stepped] = balances[stepped] - balances[stepped] % U64(ETH)
    return balances, eff


def composition(config: dict, seed: int) -> Composition:
    group, shapes = config["slashing"], config["shapes_from_source"]
    n = int(config["validators"])
    max_effective = int(shapes["MAX_EFFECTIVE_BALANCE"])
    vector = int(shapes["EPOCHS_PER_SLASHINGS_VECTOR"])
    rows, epochs = _slashed_rows(config, seed)
    exits = _exit_queue(epochs, n, shapes)
    withdrawable = np.maximum(
        exits + U64(int(shapes["MIN_VALIDATOR_WITHDRAWABILITY_DELAY"])),
        epochs.astype(U64) + U64(vector),
    )
    # 0-1 ETH over 32 ETH, as worlds/registry.py draws it
    balances = U64(max_effective) + registry.rng_for(
        seed, "balance-excess"
    ).integers(0, ETH, n, dtype=np.int64).astype(U64)
    effective = np.full(n, max_effective, dtype=U64)
    last_epoch = (int(group["at_slot"]) + 1) // SLOTS_PER_EPOCH - 1
    balances[rows], effective[rows] = _bleed(
        shapes, n, balances[rows], epochs, exits, last_epoch
    )
    is_slashed = np.zeros(n, dtype=bool)
    is_slashed[rows] = True
    slashings = {
        int(epoch): int(np.count_nonzero(epochs == epoch)) * max_effective
        for epoch in np.unique(epochs)
    }
    return Composition(
        effective_balance=effective, balances=balances, slashed=rows,
        slashed_epoch=epochs, exit_epoch=exits, withdrawable_epoch=withdrawable,
        is_slashed=is_slashed, slashings=slashings,
    )


def _flags(seed: int, stream: str, is_slashed: np.ndarray, low: float, high: float):
    flags, shares = participation(seed, stream, len(is_slashed), low, high)
    flags[is_slashed] = 0
    return flags, shares


def build(config: dict, world: dict, seed: int) -> EpochEdgeWorld:
    made = composition(config, seed)
    state, context = registry.build_registry_state(config, seed)
    group = config["slashing"]
    last_slot = int(group["at_slot"])
    if (last_slot + 1) % SLOTS_PER_EPOCH:
        raise ValueError("the slashing group is the state at an epoch's last slot")
    vector = int(config["shapes_from_source"]["EPOCHS_PER_SLASHINGS_VECTOR"])
    validators = state.validators
    for i, exit_epoch, withdrawable in zip(
        made.slashed.tolist(), made.exit_epoch.tolist(),
        made.withdrawable_epoch.tolist(),
    ):
        v = validators[i]
        v.slashed = True
        v.exit_epoch = exit_epoch
        v.withdrawable_epoch = withdrawable
        v.effective_balance = int(made.effective_balance[i])
    state.balances = made.balances.tolist()
    for epoch, amount in made.slashings.items():
        state.slashings[epoch % vector] = amount
    state.__dict__.pop("_total_active_balance_cache", None)
    state.__dict__.pop("_active_idx_cache", None)
    # the genesis epoch's boundary: nothing of the composition moves
    registry.fork_module(config["fork"]).slot_processing.process_slots(
        state, SLOTS_PER_EPOCH, context
    )
    after = (
        np.array(state.balances, dtype=U64),
        np.fromiter((v.effective_balance for v in validators), U64, len(validators)),
        np.fromiter((v.exit_epoch for v in validators), U64, len(validators)),
    )
    exit_epoch = np.full(len(validators), FAR_FUTURE_EPOCH, dtype=U64)
    exit_epoch[made.slashed] = made.exit_epoch
    before = (made.balances, made.effective_balance, exit_epoch)
    if not all(np.array_equal(a, b) for a, b in zip(after, before)):
        raise ValueError("the genesis epoch's boundary moved the composition")

    # a chain that finalizes: the epoch before the last justified and
    # finalized, the last justified, every bit set
    state.slot = last_slot
    epoch = (last_slot + 1) // SLOTS_PER_EPOCH - 1
    rng = registry.rng_for(seed, "slashed-checkpoint-roots")
    finalized, justified = rng.bytes(32), rng.bytes(32)
    for name, at, root in (("finalized_checkpoint", epoch - 2, finalized),
                           ("previous_justified_checkpoint", epoch - 2, finalized),
                           ("current_justified_checkpoint", epoch - 1, justified)):
        checkpoint = getattr(state, name)
        checkpoint.epoch = at
        checkpoint.root = root
    state.justification_bits = [True] * len(state.justification_bits)

    low, high = world["miss_share"]
    previous, prev_shares = _flags(seed, "previous", made.is_slashed, low, high)
    current, cur_shares = _flags(seed, "current", made.is_slashed, low, high)
    state.previous_epoch_participation = previous.tolist()
    state.current_epoch_participation = current.tolist()
    type(state).hash_tree_root(state)  # the root memo travels with copies
    refills = [
        _flags(seed, f"refill-{k}", made.is_slashed, low, high)[0]
        for k in range(1, int(world.get("chain_epochs", 1)))
    ]
    return EpochEdgeWorld(
        fork=config["fork"],
        context=context,
        pre=state,
        target_slot=last_slot + 1,
        miss_shares={"previous": prev_shares, "current": cur_shares},
        refills=refills,
    )
