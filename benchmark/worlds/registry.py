"""A mainnet-shaped registry built directly (no deposit crypto), seeded.

Copied from tests/chain_utils.py ``build_fast_registry_state`` with two
changes: ``seed`` derives the eth1 block hash (hence every randao mix,
committee, proposer and sync committee) and a balance excess of 0-1 ETH per
validator. Every validator gets a synthetic pubkey that cannot decompress;
only those that sign get real keys (index selection never reads pubkeys)."""

from __future__ import annotations

import hashlib
import importlib

import numpy as np

from ethereum_consensus_tpu.config import Context

from . import keys

ETH1_TIMESTAMP = 1578009600
GENESIS_PAYLOAD_BLOCK_HASH = b"\x77" * 32
PAYLOAD_FORKS = ("bellatrix", "capella", "deneb")
GWEI_PER_ETH = 10**9


def fork_module(fork_name: str):
    return importlib.import_module(f"ethereum_consensus_tpu.models.{fork_name}")


def context_for(preset: str):
    return Context.for_minimal() if preset == "minimal" else Context.for_mainnet()


def eth1_block_hash(seed: int) -> bytes:
    return hashlib.sha256(
        b"benchmark-world-eth1" + int(seed).to_bytes(8, "little")
    ).digest()


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """One independent generator per named stream of one seed."""
    digest = hashlib.sha256(stream.encode() + int(seed).to_bytes(8, "little"))
    return np.random.default_rng(int.from_bytes(digest.digest()[:8], "little"))


def build_registry_state(config: dict, seed: int):
    """(state at slot 0, context) for ``config`` (fork, preset, validators)."""
    from ethereum_consensus_tpu.models.genesis_common import (
        initialize_state_generic,
    )
    from ethereum_consensus_tpu.primitives import FAR_FUTURE_EPOCH, GENESIS_EPOCH

    fork_name = config["fork"]
    count = int(config["validators"])
    context = context_for(config["preset"])
    ns = fork_module(fork_name).build(context.preset)
    eth1_hash = eth1_block_hash(seed)
    kwargs = {}
    if fork_name in PAYLOAD_FORKS:
        kwargs["execution_payload_header"] = ns.ExecutionPayloadHeader(
            block_hash=GENESIS_PAYLOAD_BLOCK_HASH,
            timestamp=ETH1_TIMESTAMP + context.genesis_delay,
            prev_randao=eth1_hash,
        )
    state = initialize_state_generic(
        ns,
        getattr(context, f"{fork_name}_fork_version"),
        eth1_hash,
        ETH1_TIMESTAMP,
        [],  # no deposits: the registry is injected below
        context,
        process_deposit_fn=lambda *a, **k: None,
        get_next_sync_committee_fn=None,
        **kwargs,
    )
    effective = int(context.MAX_EFFECTIVE_BALANCE)
    state.validators = [
        ns.Validator(
            public_key=keys.synthetic_pubkey_bytes(i),
            withdrawal_credentials=b"\x00" * 12 + i.to_bytes(20, "big"),
            effective_balance=effective,
            activation_eligibility_epoch=GENESIS_EPOCH,
            activation_epoch=GENESIS_EPOCH,
            exit_epoch=FAR_FUTURE_EPOCH,
            withdrawable_epoch=FAR_FUTURE_EPOCH,
        )
        for i in range(count)
    ]
    # 0-1 ETH over the effective balance: under the 1.25 ETH hysteresis, so
    # effective balances hold, and every balance leaf differs
    excess = rng_for(seed, "balance-excess").integers(
        0, GWEI_PER_ETH, count, dtype=np.int64
    )
    state.balances = (excess + int(context.MAX_EFFECTIVE_BALANCE)).tolist()
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

    from ethereum_consensus_tpu.models.altair.helpers import (
        get_next_sync_committee,
        get_next_sync_committee_indices,
    )

    keys.realize_validator_keys(
        state, get_next_sync_committee_indices(state, context)
    )
    sync_committee = get_next_sync_committee(state, context)
    state.current_sync_committee = sync_committee
    state.next_sync_committee = sync_committee.copy()
    return state, context
