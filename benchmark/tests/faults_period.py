"""Faults of a sync committee period boundary, for the cell that crosses
one: planted as ``faults.py``'s are, through a patcher with pytest's
``monkeypatch.setattr`` interface, underneath a whole run or a crossing.
Each is a way to get ``process_sync_committee_updates`` or
``process_historical_summaries_update`` wrong that a node could ship; the
plain reference (``reference/deneb_epoch_period.py``) has to call each one
wrong. ``CONTROL`` is the control the cell shares with the others."""

from __future__ import annotations

from benchmark.tests.faults import rounded_balances


def rotation_skipped(monkeypatch):
    """``process_sync_committee_updates`` left out: both committees stay."""
    from ethereum_consensus_tpu.models import epoch_vector

    monkeypatch.setattr(epoch_vector, "_sync_committee_updates", lambda ec: None)


def attester_domain_seed(monkeypatch):
    """The sampler's seed taken under ``DOMAIN_BEACON_ATTESTER`` in place of
    ``DOMAIN_SYNC_COMMITTEE``: another committee is sampled."""
    from ethereum_consensus_tpu.domains import DomainType
    from ethereum_consensus_tpu.models.altair import helpers

    served = helpers.get_seed

    def faulty(state, epoch, domain_type, context):
        if domain_type == DomainType.SYNC_COMMITTEE:
            domain_type = DomainType.BEACON_ATTESTER
        return served(state, epoch, domain_type, context)

    monkeypatch.setattr(helpers, "get_seed", faulty)


def aggregate_without_last_key(monkeypatch):
    """The committee's aggregate key summed over all keys but the last."""
    from ethereum_consensus_tpu.crypto import bls

    served = bls.eth_aggregate_public_keys
    monkeypatch.setattr(bls, "eth_aggregate_public_keys", lambda keys: served(keys[:-1]))


def summary_roots_swapped(monkeypatch):
    """The historical summary with its block and state roots swapped."""
    from ethereum_consensus_tpu.models.capella import epoch_processing

    served = epoch_processing.process_historical_summaries_update

    def faulty(state, context):
        before = len(state.historical_summaries)
        served(state, context)
        if len(state.historical_summaries) > before:
            summary = state.historical_summaries[-1]
            state.historical_summaries[-1] = type(summary)(
                block_summary_root=summary.state_summary_root,
                state_summary_root=summary.block_summary_root,
            )

    monkeypatch.setattr(epoch_processing, "process_historical_summaries_update", faulty)


FAULTS = [rotation_skipped, attester_domain_seed, aggregate_without_last_key,
          summary_roots_swapped]
CONTROL = rounded_balances
