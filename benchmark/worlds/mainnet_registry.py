"""World kind ``mainnet_registry``: the registry a node that follows mainnet
holds, on the last slot of an epoch, ready for a chain of crossings.

The composition is the configuration file's ``registry`` group, every draw
from ``--seed`` by a named stream: active rows and exited, withdrawn rows
interleaved (exits densest among the oldest indices), a few of the exited
slashed, a saturated exit queue (rows that leave the active set in each
epoch the chain enters), and a deposit tail: rows activated but not yet
active, the activation queue (some of it eligible an epoch later than its
index says), and deposits no boundary has seen yet.
``composition`` makes the columns; ``build`` makes the state.

The table is the state **at slot 63**. The state is built at slot 0 with the
tail inert (no balance, not eligible: a boundary leaves such a row alone),
advanced blockless on the plain host path, and only then given the tail's
balances and epochs, as ``epoch_edge`` gives the participation: the boundary
from epoch 0 to 1 therefore meets no queue, and the chain's first crossing
meets all of it.

Sync-committee keys are realised over **active rows only**: the committee
is sampled from the rows active at epoch 1, and an exited row in it would
be a fault of the sampling (asserted).

Participation: a row carries flags in an epoch's list while it can attest
there or waits in the queue (the masks drop a flag on a row that is not yet
active; on mainnet such a row carries 0 until its activation epoch, which
this generator does not work out); an exited row and a fresh deposit carry
0. Of the rows that carry flags, a seeded share between ``miss_share`` =
[low, high] misses each flag, per list, as ``epoch_edge`` draws it.

A test that cuts ``validators`` gets every count times
``validators / at_validators``, never below 2 a group and 1 an epoch; the
cell runs the counts as written."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import keys, registry
from .epoch_edge import EpochEdgeWorld, participation

FAR_FUTURE_EPOCH = (1 << 64) - 1
U64 = np.uint64
TAIL = ("activated", "queued", "fresh_deposits")  # the last indices, in this order


@dataclass
class Composition:
    """The registry as columns, and who is in which group (row indices)."""

    columns: dict   # validator field -> uint64[n] (slashed: bool[n])
    balances: np.ndarray
    groups: dict    # name -> sorted row indices


def _counts(config: dict) -> dict:
    """The group counts at this configuration's size."""
    reg = config["registry"]
    n, full = int(config["validators"]), int(reg["at_validators"])

    def scaled(count: int, floor: int) -> int:
        return count if n == full else max(floor, count * n // full)

    def epochs(group: dict) -> list:
        first, last = group["epochs"]
        return list(range(first, last + 1))

    return {
        "n": n,
        "active": scaled(int(reg["active"]), 2),
        "slashed": scaled(int(reg["slashed"]), 2),
        "queued": scaled(int(reg["queued"]), 2),
        "fresh_deposits": scaled(int(reg["fresh_deposits"]), 2),
        "exiting_per_epoch": scaled(int(reg["exiting"]["per_epoch"]), 1),
        "exiting_epochs": epochs(reg["exiting"]),
        "activated_per_epoch": scaled(int(reg["activated"]["per_epoch"]), 1),
        "activated_epochs": epochs(reg["activated"]),
    }


def composition(config: dict, seed: int) -> Composition:
    reg = config["registry"]
    k = _counts(config)
    n = k["n"]
    n_activated = k["activated_per_epoch"] * len(k["activated_epochs"])
    tail = n_activated + k["queued"] + k["fresh_deposits"]
    head = n - tail
    n_exited = head - k["active"]
    if n_exited < k["slashed"]:
        raise ValueError("the registry group does not fit this many validators")

    # who has exited: likelier the older the index, exactly n_exited of them
    first, last = reg["exited_share_first_to_last"]
    score = registry.rng_for(seed, "registry-exited").random(head) - np.linspace(
        first, last, head
    )
    exited = np.sort(np.argpartition(score, n_exited)[:n_exited])
    is_exited = np.zeros(head, dtype=bool)
    is_exited[exited] = True
    active = np.nonzero(~is_exited)[0]

    def drawn(stream: str, parent: np.ndarray, count: int) -> np.ndarray:
        return registry.rng_for(seed, stream).choice(parent, count, replace=False)

    slashed = np.sort(drawn("registry-slashed", exited, k["slashed"]))
    exiting = drawn(
        "registry-exiting", active, k["exiting_per_epoch"] * len(k["exiting_epochs"])
    )
    low = np.sort(drawn(
        "registry-low-balance", active, int(reg["low_balance_share"] * len(active))
    ))
    tail_rows = np.arange(head, n)
    activated = tail_rows[:n_activated]
    queued = tail_rows[n_activated : n_activated + k["queued"]]
    fresh = tail_rows[n_activated + k["queued"] :]

    eth = registry.GWEI_PER_ETH
    far = U64(FAR_FUTURE_EPOCH)
    effective = np.full(n, 32 * eth, dtype=U64)
    eligibility = np.zeros(n, dtype=U64)
    activation = np.zeros(n, dtype=U64)
    exit_epoch = np.full(n, far, dtype=U64)
    withdrawable = np.full(n, far, dtype=U64)
    is_slashed = np.zeros(n, dtype=bool)
    # 0-1 ETH over the effective balance, as worlds/registry.py draws it
    balances = U64(32 * eth) + registry.rng_for(seed, "balance-excess").integers(
        0, eth, n, dtype=np.int64
    ).astype(U64)

    effective[low] = 31 * eth
    balances[low] = U64(31 * eth) + registry.rng_for(
        seed, "registry-low-balance-excess"
    ).integers(0, 99 * eth // 100, len(low), dtype=np.int64).astype(U64)
    for column in (effective, balances, exit_epoch, withdrawable):
        column[exited] = 0
    is_slashed[slashed] = True
    delay = int(config["shapes_from_source"]["MIN_VALIDATOR_WITHDRAWABILITY_DELAY"])
    exiting_epochs = np.repeat(
        np.array(k["exiting_epochs"], dtype=U64), k["exiting_per_epoch"]
    )
    exit_epoch[exiting] = exiting_epochs
    withdrawable[exiting] = exiting_epochs + U64(delay)
    balances[tail_rows] = 32 * eth  # a deposit is 32 ETH to the gwei
    activation[activated] = np.repeat(
        np.array(k["activated_epochs"], dtype=U64), k["activated_per_epoch"]
    )
    activation[queued] = far
    late = queued[:: int(reg["queued_late_every"])]
    eligibility[late] = 1
    activation[fresh] = far
    eligibility[fresh] = far
    return Composition(
        columns={
            "effective_balance": effective,
            "slashed": is_slashed,
            "activation_eligibility_epoch": eligibility,
            "activation_epoch": activation,
            "exit_epoch": exit_epoch,
            "withdrawable_epoch": withdrawable,
        },
        balances=balances,
        groups={
            "active": active, "exited": exited, "slashed": slashed,
            "exiting": np.sort(exiting), "low_balance": low,
            "activated": activated, "queued": queued, "queued_late": late,
            "fresh_deposits": fresh,
        },
    )


def build_state(config: dict, seed: int, made: Composition):
    """(state at slot 0, context): ``made``'s registry with its deposit tail
    inert, sync committees over the active rows."""
    from ethereum_consensus_tpu.models.altair.helpers import (
        get_next_sync_committee,
        get_next_sync_committee_indices,
    )
    from ethereum_consensus_tpu.models.genesis_common import (
        initialize_state_generic,
    )

    fork_name = config["fork"]
    context = registry.context_for(config["preset"])
    ns = registry.fork_module(fork_name).build(context.preset)
    eth1_hash = registry.eth1_block_hash(seed)
    state = initialize_state_generic(
        ns,
        getattr(context, f"{fork_name}_fork_version"),
        eth1_hash,
        registry.ETH1_TIMESTAMP,
        [],  # no deposits: the registry is injected below
        context,
        process_deposit_fn=lambda *a, **k: None,
        get_next_sync_committee_fn=None,
        execution_payload_header=ns.ExecutionPayloadHeader(
            block_hash=registry.GENESIS_PAYLOAD_BLOCK_HASH,
            timestamp=registry.ETH1_TIMESTAMP + context.genesis_delay,
            prev_randao=eth1_hash,
        ),
    )
    count = len(made.balances)
    tail = np.concatenate([made.groups[name] for name in TAIL])
    at_genesis = dict(made.columns)
    for name, inert in (("effective_balance", 0),
                        ("activation_eligibility_epoch", FAR_FUTURE_EPOCH),
                        ("activation_epoch", FAR_FUTURE_EPOCH)):
        at_genesis[name] = at_genesis[name].copy()
        at_genesis[name][tail] = inert
    balances = made.balances.copy()
    balances[tail] = 0
    fields = tuple(at_genesis)
    state.validators = [
        ns.Validator(
            public_key=keys.synthetic_pubkey_bytes(i),
            withdrawal_credentials=b"\x00" * 12 + i.to_bytes(20, "big"),
            **dict(zip(fields, row)),
        )
        for i, row in enumerate(
            zip(*(at_genesis[name].tolist() for name in fields))
        )
    ]
    state.balances = balances.tolist()
    state.eth1_data.deposit_count = count
    state.eth1_deposit_index = count
    state.previous_epoch_participation = [0] * count
    state.current_epoch_participation = [0] * count
    state.inactivity_scores = [0] * count
    state.__dict__.pop("_active_idx_cache", None)
    state.__dict__.pop("_total_active_balance_cache", None)
    state.genesis_validators_root = type(state).__ssz_fields__[
        "validators"
    ].hash_tree_root(state.validators)

    committee = get_next_sync_committee_indices(state, context)
    active_at_1 = (at_genesis["activation_epoch"] <= 1) & (1 < at_genesis["exit_epoch"])
    assert active_at_1[np.asarray(committee)].all(), (
        "a sync committee seat on a row that is not active"
    )
    keys.realize_validator_keys(state, committee)
    sync_committee = get_next_sync_committee(state, context)
    state.current_sync_committee = sync_committee
    state.next_sync_committee = sync_committee.copy()
    return state, context


def _flags(seed: int, stream: str, carries: np.ndarray, low: float, high: float):
    flags, shares = participation(seed, stream, len(carries), low, high)
    flags[~carries] = 0
    return flags, shares


def build(config: dict, world: dict, seed: int) -> EpochEdgeWorld:
    made = composition(config, seed)
    state, context = build_state(config, seed, made)
    mod = registry.fork_module(config["fork"])
    spe = int(context.SLOTS_PER_EPOCH)
    epoch = int(world["epoch"])
    last_slot = (epoch + 1) * spe - 1
    if last_slot != int(config["registry"]["at_slot"]):
        raise ValueError("the registry group is the state at its at_slot")
    mod.slot_processing.process_slots(state, last_slot, context)

    # the table, at this slot: the deposit tail as the configuration has it
    c = made.columns
    for name in TAIL:
        for i in made.groups[name].tolist():
            validator = state.validators[i]
            validator.effective_balance = int(c["effective_balance"][i])
            validator.activation_eligibility_epoch = int(
                c["activation_eligibility_epoch"][i]
            )
            validator.activation_epoch = int(c["activation_epoch"][i])
            state.balances[i] = int(made.balances[i])

    def carries(at_epoch: int) -> np.ndarray:
        """Rows whose flags are drawn in ``at_epoch``'s list."""
        e = U64(at_epoch)
        waits = c["activation_epoch"] == U64(FAR_FUTURE_EPOCH)
        deposited = c["activation_eligibility_epoch"] != U64(FAR_FUTURE_EPOCH)
        return ((c["activation_epoch"] <= e) | (waits & deposited)) & (
            e < c["exit_epoch"]
        )

    low, high = world["miss_share"]
    previous, prev_shares = _flags(seed, "previous", carries(epoch - 1), low, high)
    current, cur_shares = _flags(seed, "current", carries(epoch), low, high)
    state.previous_epoch_participation = previous.tolist()
    state.current_epoch_participation = current.tolist()
    type(state).hash_tree_root(state)  # the root memo travels with copies
    refills = [
        _flags(seed, f"refill-{j}", carries(epoch + j), low, high)[0]
        for j in range(1, int(world.get("chain_epochs", 1)))
    ]
    return EpochEdgeWorld(
        fork=config["fork"],
        context=context,
        pre=state,
        target_slot=last_slot + 1,
        miss_shares={"previous": prev_shares, "current": cur_shares},
        refills=refills,
    )
