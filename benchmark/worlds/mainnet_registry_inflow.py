"""World kind ``mainnet_registry_inflow``: ``mainnet_registry``'s state on the
last slot of an epoch, and what the blocks of every later epoch of a chain
bring: ``inflow.per_epoch`` deposits of public keys the registry does not
hold, each a new validator appended to the registry.

Nothing here appends a row: the state is ``mainnet_registry.build``'s, whose
``fresh_deposits`` are the deposits of its own epoch. ``deposits[k - 1]`` is
what the driver delivers while it advances to the chain's place ``k``, in
deposit order: ``(public key, withdrawal credentials, amount)``, 48 seeded
bytes (stream ``inflow-pubkeys``), 0x00 credentials of the new index as
``worlds/registry.py`` writes them, ``inflow.amount_gwei``. ``refills[k - 1]``
has the length the registry has at place ``k``; a row the chain appended
carries no flag.

A test that cuts ``validators`` gets ``per_epoch`` times
``validators / at_validators``, never below 2."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import mainnet_registry, registry
from .epoch_edge import EpochEdgeWorld

PUBKEY_BYTES = 48


@dataclass
class InflowWorld(EpochEdgeWorld):
    deposits: list  # one list of (pubkey, credentials, amount) per later crossing


def per_epoch(config: dict) -> int:
    """The deposits an epoch brings at this configuration's size."""
    count = int(config["inflow"]["per_epoch"])
    n, full = int(config["validators"]), int(config["registry"]["at_validators"])
    return count if n == full else max(2, count * n // full)


def deposits_for(config: dict, seed: int, epochs: int) -> list:
    """``epochs`` batches of new validators, indices following the registry."""
    count, amount = per_epoch(config), int(config["inflow"]["amount_gwei"])
    keys = registry.rng_for(seed, "inflow-pubkeys").bytes(
        PUBKEY_BYTES * count * epochs
    )
    first = int(config["validators"])
    batches = []
    for k in range(epochs):
        batch = []
        for j in range(k * count, (k + 1) * count):
            batch.append((
                keys[PUBKEY_BYTES * j : PUBKEY_BYTES * (j + 1)],
                b"\x00" * 12 + (first + j).to_bytes(20, "big"),
                amount,
            ))
        batches.append(batch)
    return batches


def build(config: dict, world: dict, seed: int) -> InflowWorld:
    made = mainnet_registry.build(config, world, seed)
    deposits = deposits_for(config, seed, len(made.refills))
    grown, refills = 0, []
    for flags, batch in zip(made.refills, deposits):
        grown += len(batch)
        refills.append(np.concatenate([flags, np.zeros(grown, dtype=flags.dtype)]))
    return InflowWorld(
        fork=made.fork, context=made.context, pre=made.pre,
        target_slot=made.target_slot, miss_shares=made.miss_shares,
        refills=refills, deposits=deposits,
    )
