"""The plain reference of the epoch cell on a registry that grows:
``deneb_epoch_registry.py``'s chain walk, and between two crossings what
that epoch's blocks did with their deposits, written out from the consensus
specification under its own names:

- phase0 ``process_deposit`` as far as the state goes (``eth1_deposit_index``
  moves by one a deposit; the Merkle proof and the proof of possession are
  block work and no part of this chain);
- phase0 ``get_validator_from_deposit``: the effective balance the amount
  gives, every epoch far future;
- altair ``add_validator_to_registry``: the new validator appended to
  ``validators``, its amount to ``balances``, a 0 to
  ``previous_epoch_participation``, ``current_epoch_participation`` and
  ``inactivity_scores``. An epoch's deposits are taken one by one in their
  order, and the five columns are then lengthened once by what they gave
  (a column here is an array that is replaced, never written into).

Every deposit of this traffic is of a public key the registry does not hold,
and one that is not is refused. The epoch transition, the state's root and
``process_slot`` are ``deneb_epoch_registry.py``'s and ``deneb_epoch.py``'s;
the ``validators`` list is the former's whole tree, which here learns to take
new leaves (``GrowingValidatorsTree``). It imports nothing of the program,
and refuses what its siblings refuse: an ejection, a sync committee rotation,
a historical summary."""

from __future__ import annotations

import numpy as np

from benchmark.reference import deneb_epoch as base
from benchmark.reference import deneb_epoch_registry as registry
from benchmark.reference import ssz
from benchmark.reference.deneb_epoch import (
    EFFECTIVE_BALANCE_INCREMENT,
    FAR_FUTURE_EPOCH,
    MAX_EFFECTIVE_BALANCE,
    SLOTS_PER_EPOCH,
    U64,
    VALIDATOR_FIELDS,
    Plain,
)
from benchmark.reference.deneb_epoch_registry import ValidatorsTree

PARTICIPATION_AND_SCORES = (
    "previous_epoch_participation", "current_epoch_participation",
    "inactivity_scores",
)


class GrowingValidatorsTree(ValidatorsTree):
    """``ValidatorsTree`` that takes new leaves at its end, and knows the
    public keys it holds."""

    def __init__(self, columns: dict):
        super().__init__(columns)
        self.public_keys = set(columns["public_key"])

    def append(self, columns: dict) -> None:
        """``columns`` holds more validators than the tree: the new ones'
        key nodes, their roots, and every node above them."""
        count = len(columns["public_key"])
        new = np.arange(self.count, count)
        key_roots = base._as_rows(ssz.hash_pairs(
            b"".join(columns["public_key"][i] + b"\x00" * 16 for i in new.tolist())
        ))
        credentials = np.frombuffer(
            b"".join(columns["withdrawal_credentials"][self.count:]), dtype=np.uint8
        ).reshape(len(new), 32)
        self.key_and_credentials = np.concatenate(
            [self.key_and_credentials, base._hash_rows(key_roots, credentials)]
        )
        self.count = count
        # every level at its new length (the new nodes are written by
        # ``update`` below: each is above a new leaf), and a level more
        # wherever the count has passed a power of two
        size, height = count, 0
        while True:
            if height == len(self.levels):
                self.levels.append(np.zeros((0, 32), dtype=np.uint8))
            level = self.levels[height]
            self.levels[height] = np.concatenate(
                [level, np.zeros((size - len(level), 32), dtype=np.uint8)]
            )
            if size == 1:
                break
            size, height = (size + 1) // 2, height + 1
        self.update(columns, new)


# -- the deposits of an epoch's blocks ----------------------------------------------


def get_validator_from_deposit(public_key: bytes, withdrawal_credentials: bytes,
                               amount: int) -> dict:
    effective_balance = min(
        amount - amount % EFFECTIVE_BALANCE_INCREMENT, MAX_EFFECTIVE_BALANCE
    )
    return {
        "public_key": public_key,
        "withdrawal_credentials": withdrawal_credentials,
        "effective_balance": effective_balance,
        "slashed": 0,
        "activation_eligibility_epoch": FAR_FUTURE_EPOCH,
        "activation_epoch": FAR_FUTURE_EPOCH,
        "exit_epoch": FAR_FUTURE_EPOCH,
        "withdrawable_epoch": FAR_FUTURE_EPOCH,
    }


def process_deposits(plain: Plain, tree: GrowingValidatorsTree, deposits: list) -> None:
    """``process_deposit`` for each of ``deposits``, (public key, withdrawal
    credentials, amount) in their order, as far as the state goes."""
    c = plain.columns
    known = tree.public_keys
    validators, balances = [], []
    for public_key, withdrawal_credentials, amount in deposits:
        plain.scalars["eth1_deposit_index"] += 1
        # apply_deposit: a key the registry holds would be a top-up
        base._refuse(public_key in known, "a deposit that tops up a validator")
        known.add(public_key)
        # add_validator_to_registry
        validators.append(
            get_validator_from_deposit(public_key, withdrawal_credentials, amount)
        )
        balances.append(amount)
    if not validators:
        return
    for name in ("public_key", "withdrawal_credentials"):
        c[name] = c[name] + [validator[name] for validator in validators]
    for name in VALIDATOR_FIELDS:
        c[name] = np.concatenate(
            [c[name], np.array([validator[name] for validator in validators], dtype=U64)]
        )
    c["balances"] = np.concatenate([c["balances"], np.array(balances, dtype=U64)])
    for name in PARTICIPATION_AND_SCORES:
        c[name] = np.concatenate(
            [c[name], np.zeros(len(validators), dtype=c[name].dtype)]
        )
    tree.append(c)
    plain.validators_root = tree.root()


def read_state(state) -> tuple:
    """(plain values, the validators' tree) of a generated deneb state."""
    plain = base.read_state(state)
    tree = GrowingValidatorsTree(plain.columns)
    plain.validators_root = tree.root()
    return plain, tree


def chain_roots(state, target_slot: int, refills: list, deposits: list) -> list:
    """The roots after each crossing of a chain: ``process_slots(state,
    target_slot)``; then, for each of ``refills`` and its ``deposits``, 31
    empty slots, that epoch's deposits, its ``current_epoch_participation``
    set to the flags (as long as the registry then is), and the next
    crossing. ``state`` is only read."""
    plain, tree = read_state(state)
    registry.process_slots(plain, tree, target_slot)
    roots = [base.state_root(plain)]
    for flags, batch in zip(refills, deposits):
        target_slot += SLOTS_PER_EPOCH
        registry.process_slots(plain, tree, target_slot - 1)
        process_deposits(plain, tree, batch)
        flags = np.asarray(flags, dtype=np.uint8)
        if len(flags) != tree.count:
            raise ValueError("a refill is as long as the registry it is written to")
        plain.columns["current_epoch_participation"] = flags
        registry.process_slots(plain, tree, target_slot)
        roots.append(base.state_root(plain))
    return roots
