"""World kind ``period_edge``: a state on the last slot of the last epoch of
a sync committee period, with finality, so that the crossing rotates the
sync committee (``process_sync_committee_updates``) and appends a
historical summary (``process_historical_summaries_update``).

The composition is the configuration file's ``period`` group, every draw
from ``--seed`` by a named stream. The state is made directly, as
``slashed_edge`` makes it: the registry of ``registry.py``, the genesis
epoch's boundary crossed first on the plain host path (it leaves the lists
with the working columns a node that has been crossing boundaries holds),
then what a chain from genesis holds at ``at_slot`` and the epochs before
it are not walked for: a distinct randao mix for every epoch it has lived
through, the two vectors of roots full, one historical summary for every
period boundary passed, the checkpoints of normal finality, and the two
sync committees the sampler gave at the last two period boundaries.

Every row of those two committees and of the committee the crossing will
sample gets its real key (``keys.py``); every other row keeps its
synthetic key, which nothing reads. The world samples the three
committees with the program's helper and checks each against the plain
reference's sampler, and raises if a sampled row lacks its real key. It
leaves no active-index tuple of the epoch the crossing enters in the
state, so each timed crossing builds the tuple a node would build.

Participation: a seeded share between ``miss_share`` = [low, high] misses
each flag, per list, as ``epoch_edge`` draws it. A chain has one crossing:
the next period boundary is 256 epochs on."""

from __future__ import annotations

import numpy as np

from benchmark.reference import deneb_epoch_period

from . import keys, registry
from .epoch_edge import EpochEdgeWorld, participation

SLOTS_PER_EPOCH = 32


def _sample(state, context, epoch: int, columns: tuple) -> list:
    """The committee the sampler gives at the boundary into ``epoch``, by
    the program's helper, checked against the plain reference's loop over
    ``columns`` (activation epochs, exit epochs, effective balances)."""
    from ethereum_consensus_tpu.models.altair.helpers import (
        get_next_sync_committee_indices,
    )

    state.slot = epoch * SLOTS_PER_EPOCH - 1
    indices = get_next_sync_committee_indices(state, context)
    activation, exit_epoch, effective = columns
    active = np.nonzero((activation <= epoch) & (epoch < exit_epoch))[0]
    seed = deneb_epoch_period.get_seed(
        state.randao_mixes, epoch, deneb_epoch_period.DOMAIN_SYNC_COMMITTEE
    )
    if indices != deneb_epoch_period.sync_committee_indices(seed, active, effective):
        raise ValueError(f"the program and the reference sample epoch {epoch} apart")
    return indices


def build(config: dict, world: dict, seed: int) -> EpochEdgeWorld:
    if int(world.get("chain_epochs", 1)) != 1:
        raise ValueError("a period boundary is a chain of one crossing")
    group = config["period"]
    last_slot = int(group["at_slot"])
    entered = (last_slot + 1) // SLOTS_PER_EPOCH
    state, context = registry.build_registry_state(config, seed)
    period = int(context.EPOCHS_PER_SYNC_COMMITTEE_PERIOD)
    if (last_slot + 1) % SLOTS_PER_EPOCH or entered % period or entered < 2 * period:
        raise ValueError("the period group is the last slot of a sync committee period")
    if int(group["sync_committee_size"]) != int(context.SYNC_COMMITTEE_SIZE):
        raise ValueError("the period group's committee is not the preset's")
    module = registry.fork_module(config["fork"])
    ns = module.build(context.preset)
    module.slot_processing.process_slots(state, SLOTS_PER_EPOCH, context)

    # what a chain from genesis holds at the last slot of epoch entered - 1
    rng = registry.rng_for(seed, "period-randao-mixes")
    mixes = list(state.randao_mixes)
    for epoch in range(entered):
        mixes[epoch % len(mixes)] = rng.bytes(32)
    state.randao_mixes = mixes
    for name in ("block_roots", "state_roots"):
        rng = registry.rng_for(seed, f"period-{name}")
        setattr(state, name, [rng.bytes(32) for _ in range(len(getattr(state, name)))])
    rng = registry.rng_for(seed, "period-historical-summaries")
    summaries_per = int(context.SLOTS_PER_HISTORICAL_ROOT) // SLOTS_PER_EPOCH
    state.historical_summaries = [
        ns.HistoricalSummary(block_summary_root=rng.bytes(32), state_summary_root=rng.bytes(32))
        for _ in range(entered // summaries_per - 1)
    ]

    # the committees of the two periods the state is in and enters next,
    # and the one the crossing samples
    from ethereum_consensus_tpu.models.altair.helpers import get_next_sync_committee

    validators = state.validators
    columns = tuple(
        np.fromiter((int(getattr(v, name)) for v in validators), np.uint64, len(validators))
        for name in ("activation_epoch", "exit_epoch", "effective_balance")
    )
    committees = {}
    for epoch in (entered - 2 * period, entered - period, entered):
        committees[epoch] = _sample(state, context, epoch, columns)
        keys.realize_validator_keys(state, committees[epoch])
    for name, epoch in (("current_sync_committee", entered - 2 * period),
                        ("next_sync_committee", entered - period)):
        state.slot = epoch * SLOTS_PER_EPOCH - 1
        setattr(state, name, get_next_sync_committee(state, context))
    for epoch, indices in committees.items():
        if any(bytes(state.validators[i].public_key) != keys.public_key_bytes(i)
               for i in indices):
            raise ValueError(f"a row of epoch {epoch}'s committee lacks its real key")

    # a chain that finalizes: the epoch before the last justified and
    # finalized, the last justified, every bit set
    state.slot = last_slot
    epoch = entered - 1
    rng = registry.rng_for(seed, "period-checkpoint-roots")
    finalized, justified = rng.bytes(32), rng.bytes(32)
    for name, at, root in (("finalized_checkpoint", epoch - 2, finalized),
                           ("previous_justified_checkpoint", epoch - 2, finalized),
                           ("current_justified_checkpoint", epoch - 1, justified)):
        checkpoint = getattr(state, name)
        checkpoint.epoch = at
        checkpoint.root = root
    state.justification_bits = [True] * len(state.justification_bits)

    low, high = world["miss_share"]
    count = len(state.validators)
    previous, prev_shares = participation(seed, "previous", count, low, high)
    current, cur_shares = participation(seed, "current", count, low, high)
    state.previous_epoch_participation = previous.tolist()
    state.current_epoch_participation = current.tolist()
    # no active-index tuple of the sampled epochs stays behind
    cache = state.__dict__.get("_active_idx_cache")
    if isinstance(cache, dict):
        state.__dict__["_active_idx_cache"] = {
            key: value for key, value in cache.items() if key[0] not in committees
        }
    type(state).hash_tree_root(state)  # the root memo travels with copies
    return EpochEdgeWorld(
        fork=config["fork"],
        context=context,
        pre=state,
        target_slot=last_slot + 1,
        miss_shares={"previous": prev_shares, "current": cur_shares},
        refills=[],
    )
