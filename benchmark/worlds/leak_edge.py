"""World kind ``leak_edge``: a state on the last slot of an epoch, deep in
an inactivity leak, ready for a chain of crossings none of which finalizes.

The composition is the configuration file's ``finality`` group, every draw
from ``--seed`` by a named stream: which rows are offline (uniformly over
the registry, interleaved), what the leak has done to them (score, balance,
effective balance), and the small scores the online rows' own misses leave.
``composition`` makes the columns; ``build`` makes the state.

The state is made directly: the registry of ``registry.py`` at slot 0 with
the scores, the balances and the effective balances written, then ``slot``
and the three checkpoints. The epochs before it are not walked: what they
did to an offline row is summed here, epoch by epoch, as the specification
takes it (``_bleed``), so the state tells one story. One boundary is
crossed, the genesis epoch's, on the plain host path and before the jump:
by the specification it pays and takes nothing and moves no effective
balance of this registry (asserted), and it leaves the state as a node that
has been crossing boundaries holds it, with the working columns the epoch
pass keeps on the registry's lists, which every chain's copy then shares.
Without it each chain's first crossing would build them from 2^20
containers, about a second that no node in a leak pays (``epoch_edge`` and
``mainnet_registry`` cross their own first boundary for the same effect).

Participation: an offline row carries no flag, in either list of the world
and in every refill; of the online rows a seeded share between
``miss_share`` = [low, high] misses each flag, per list, as ``epoch_edge``
draws it. The online rows then hold under two thirds of the active balance,
so nothing is justified and every crossing of every chain is a leaking one.

A test that cuts ``validators`` gets ``offline`` times ``validators /
at_validators``, never below 2; the slot, the epochs and the scores are
never scaled. The cell runs the counts as written."""

from __future__ import annotations

from dataclasses import dataclass
from math import isqrt

import numpy as np

from . import registry
from .epoch_edge import EpochEdgeWorld, participation

U64 = np.uint64
ETH = registry.GWEI_PER_ETH
SLOTS_PER_EPOCH = 32
# presets/mainnet/{phase0,altair}.yaml, what ``_bleed`` needs of them
BASE_REWARD_FACTOR = 64
WEIGHT_DENOMINATOR = 64
PENALISED_FLAG_WEIGHTS = (14, 26)  # source, target; the head flag never is


@dataclass
class Composition:
    """The registry's columns the leak has touched, and who is offline."""

    effective_balance: np.ndarray  # uint64[n]
    balances: np.ndarray           # uint64[n]
    scores: np.ndarray             # uint64[n]
    offline: np.ndarray            # sorted row indices
    is_offline: np.ndarray         # bool[n]
    online_miss_share: float       # what the online rows' walk was drawn at


def offline_count(config: dict) -> int:
    fin = config["finality"]
    n, full = int(config["validators"]), int(fin["at_validators"])
    count = int(fin["offline"])
    return count if n == full else max(2, count * n // full)


def _bleed(shapes: dict, start: np.ndarray, online_balance: int, epochs: int):
    """(balances, effective balances) of rows that start at ``start`` gwei
    and 32 ETH effective and miss every flag of ``epochs`` leaking epochs:
    in epoch k the source and target penalties, the inactivity penalty off
    a score of 4k, then the hysteresis. ``online_balance`` is the other
    rows' effective balance, which does not move."""
    increment = ETH
    bias = int(shapes["INACTIVITY_SCORE_BIAS"])
    denominator = U64(bias * int(shapes["INACTIVITY_PENALTY_QUOTIENT_BELLATRIX"]))
    down = U64(
        increment // int(shapes["HYSTERESIS_QUOTIENT"])
        * int(shapes["HYSTERESIS_DOWNWARD_MULTIPLIER"])
    )
    balances = start.copy()
    eff = np.full(len(start), int(shapes["MAX_EFFECTIVE_BALANCE"]), dtype=U64)
    for k in range(1, epochs + 1):
        total_active = online_balance + int(eff.sum())
        per_increment = increment * BASE_REWARD_FACTOR // isqrt(total_active)
        base_reward = eff // U64(increment) * U64(per_increment)
        for weight in PENALISED_FLAG_WEIGHTS:
            balances -= base_reward * U64(weight) // U64(WEIGHT_DENOMINATOR)
        balances -= eff * U64(bias * k) // denominator
        stepped = balances + down < eff
        eff[stepped] = balances[stepped] - balances[stepped] % U64(increment)
    return balances, eff


def composition(config: dict, seed: int) -> Composition:
    fin, shapes = config["finality"], config["shapes_from_source"]
    n = int(config["validators"])
    count = offline_count(config)
    max_effective = int(shapes["MAX_EFFECTIVE_BALANCE"])
    offline = np.sort(
        registry.rng_for(seed, "leak-offline").choice(n, count, replace=False)
    )
    is_offline = np.zeros(n, dtype=bool)
    is_offline[offline] = True
    # 0-1 ETH over 32 ETH, as worlds/registry.py draws it
    balances = U64(max_effective) + registry.rng_for(
        seed, "balance-excess"
    ).integers(0, ETH, n, dtype=np.int64).astype(U64)
    effective = np.full(n, max_effective, dtype=U64)
    epochs = int(fin["offline_epochs"])
    balances[offline], effective[offline] = _bleed(
        shapes, balances[offline], (n - count) * max_effective, epochs
    )
    scores = np.zeros(n, dtype=U64)
    scores[offline] = int(shapes["INACTIVITY_SCORE_BIAS"]) * epochs

    # the online rows' own misses, while the leak keeps the recovery off
    walk = fin["online_walk"]
    rng = registry.rng_for(seed, "leak-online-walk")
    share = float(rng.uniform(*walk["miss_share"]))
    online = np.zeros(n - count, dtype=U64)
    for _ in range(int(walk["steps"])):
        missed = rng.random(n - count) < share
        online = np.where(
            missed, online + U64(shapes["INACTIVITY_SCORE_BIAS"]),
            online - np.minimum(online, U64(1)),
        )
    scores[~is_offline] = online
    return Composition(
        effective_balance=effective, balances=balances, scores=scores,
        offline=offline, is_offline=is_offline, online_miss_share=share,
    )


def _flags(seed: int, stream: str, is_offline: np.ndarray, low: float, high: float):
    flags, shares = participation(seed, stream, len(is_offline), low, high)
    flags[is_offline] = 0
    return flags, shares


def build(config: dict, world: dict, seed: int) -> EpochEdgeWorld:
    made = composition(config, seed)
    state, context = registry.build_registry_state(config, seed)
    fin = config["finality"]
    last_slot = int(fin["at_slot"])
    if (last_slot + 1) % SLOTS_PER_EPOCH:
        raise ValueError("the finality group is the state at an epoch's last slot")
    state.inactivity_scores = made.scores.tolist()
    state.balances = made.balances.tolist()
    max_effective = int(config["shapes_from_source"]["MAX_EFFECTIVE_BALANCE"])
    validators = state.validators
    for i in np.nonzero(made.effective_balance != U64(max_effective))[0].tolist():
        validators[i].effective_balance = int(made.effective_balance[i])
    state.__dict__.pop("_total_active_balance_cache", None)
    # the genesis epoch's boundary: nothing of the composition moves
    registry.fork_module(config["fork"]).slot_processing.process_slots(
        state, SLOTS_PER_EPOCH, context
    )
    after = (
        np.array(state.balances, dtype=U64),
        np.array(state.inactivity_scores, dtype=U64),
        np.fromiter((v.effective_balance for v in validators), U64, len(validators)),
    )
    before = (made.balances, made.scores, made.effective_balance)
    if not all(np.array_equal(a, b) for a, b in zip(after, before)):
        raise ValueError("the genesis epoch's boundary moved the composition")

    state.slot = last_slot
    root = registry.rng_for(seed, "leak-checkpoint-root").bytes(32)
    for name in ("finalized_checkpoint", "previous_justified_checkpoint",
                 "current_justified_checkpoint"):
        checkpoint = getattr(state, name)
        checkpoint.epoch = int(fin["finalized_epoch"])
        checkpoint.root = root
    state.justification_bits = [False] * len(state.justification_bits)

    low, high = world["miss_share"]
    previous, prev_shares = _flags(seed, "previous", made.is_offline, low, high)
    current, cur_shares = _flags(seed, "current", made.is_offline, low, high)
    state.previous_epoch_participation = previous.tolist()
    state.current_epoch_participation = current.tolist()
    type(state).hash_tree_root(state)  # the root memo travels with copies
    refills = [
        _flags(seed, f"refill-{k}", made.is_offline, low, high)[0]
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
