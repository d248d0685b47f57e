"""Shared toy-chain helpers: deterministic validator keys, valid deposits
with merkle proofs, genesis construction, block production and attestation
crafting — the scaffolding the sanity/finality-style tests drive.
"""

from __future__ import annotations

import functools
import os

from ethereum_consensus_tpu.config import Context
from ethereum_consensus_tpu.crypto import bls
from ethereum_consensus_tpu.domains import DomainType
from ethereum_consensus_tpu.models.phase0 import (
    build,
    genesis,
    helpers as h,
)
from ethereum_consensus_tpu.models.phase0.containers import (
    DepositData,
    DepositMessage,
    DEPOSIT_CONTRACT_TREE_DEPTH,
)
from ethereum_consensus_tpu.signing import compute_signing_root
from pathlib import Path

from ethereum_consensus_tpu.ssz import List as SSZList
from ethereum_consensus_tpu.ssz import uint64
from ethereum_consensus_tpu.ssz.merkle import Tree

ETH1_BLOCK_HASH = b"\x42" * 32
ETH1_TIMESTAMP = 1578009600


@functools.lru_cache(maxsize=None)
def secret_key(index: int) -> bls.SecretKey:
    return bls.SecretKey(index + 1)


_INDEX_OF_KEY: dict = {}  # public_key_bytes(i) -> i, for _indices_of_keys


@functools.lru_cache(maxsize=None)
def public_key_bytes(index: int) -> bytes:
    key = secret_key(index).public_key().to_bytes()
    _INDEX_OF_KEY[key] = index
    return key


def aggregate_sign(indices, message: bytes) -> bls.Signature:
    """The aggregate of ``secret_key(i).sign(message)`` over ``indices``,
    computed as ONE signature under the summed secret: every signer signs
    the same message, so Σ sk_i·H(m) = (Σ sk_i)·H(m) — the same G2 point,
    hence the same bytes, at one scalar multiplication instead of one per
    signer (a 2^20-registry chain signs ~300k committee votes)."""
    from ethereum_consensus_tpu.crypto.fields import R

    total = sum(secret_key(i)._scalar for i in indices) % R
    return bls.SecretKey(total).sign(message)


def withdrawal_credentials(index: int) -> bytes:
    return b"\x00" + bls.hash(public_key_bytes(index))[1:]


def make_deposit_data(index: int, context, amount: int | None = None) -> DepositData:
    if amount is None:
        amount = context.MAX_EFFECTIVE_BALANCE
    message = DepositMessage(
        public_key=public_key_bytes(index),
        withdrawal_credentials=withdrawal_credentials(index),
        amount=amount,
    )
    domain = h.compute_domain(DomainType.DEPOSIT, None, None, context)
    root = compute_signing_root(DepositMessage, message, domain)
    signature = secret_key(index).sign(root).to_bytes()
    return DepositData(
        public_key=message.public_key,
        withdrawal_credentials=message.withdrawal_credentials,
        amount=amount,
        signature=signature,
    )


def deposits_from_datas(datas, context):
    """Deposits with valid incremental-tree merkle proofs (deposit i
    proven against the tree holding deposits 0..i, mixed with count
    i+1) for the given DepositData list.

    Uses the EIP deposit contract's incremental-branch algorithm: the
    proof of the newest leaf needs only the stored left-subtree roots
    plus zero hashes — O(n log n) total, where rebuilding a full Tree
    per deposit was O(n²) hashing (the dominant cost of big test
    geneses)."""
    from ethereum_consensus_tpu.ssz.hash import hash_pair
    from ethereum_consensus_tpu.ssz.merkle import zero_hash

    ns = build(context.preset)
    depth = DEPOSIT_CONTRACT_TREE_DEPTH
    branch: list[bytes | None] = [None] * depth
    deposits = []
    for i, data in enumerate(datas):
        leaf = DepositData.hash_tree_root(data)
        # proof of leaf i against the (i+1)-leaf tree: set bits of i pick
        # the stored left-subtree roots, clear bits an empty (zero) right
        proof = [
            branch[hgt] if (i >> hgt) & 1 else zero_hash(hgt)
            for hgt in range(depth)
        ]
        proof.append((i + 1).to_bytes(32, "little"))
        deposits.append(ns.Deposit(proof=proof, data=data))
        # deposit-contract insert of leaf i
        node = leaf
        size = i + 1
        hgt = 0
        while size % 2 == 0:
            node = hash_pair(branch[hgt], node)
            size //= 2
            hgt += 1
        branch[hgt] = node
    return deposits


_DEPOSIT_CACHE_DIR = Path(__file__).parent / ".deposit_cache"


@functools.lru_cache(maxsize=1)
def _cache_source_digest() -> str:
    """Digest of every source file the cached artifacts depend on: any
    edit to deposit construction, genesis logic, or the SSZ codec gets a
    fresh cache key automatically — a stale cache can never mask a
    regression in the code under test."""
    import hashlib as _hashlib

    repo = Path(__file__).parent.parent
    files = sorted(
        [Path(__file__)]
        + list((repo / "ethereum_consensus_tpu" / "models").glob("*/genesis.py"))
        + list(
            (repo / "ethereum_consensus_tpu" / "models").glob(
                "*/block_processing.py"
            )
        )
        # fork upgrade functions shape the full-upgrade chain bundles
        + list((repo / "ethereum_consensus_tpu" / "models").glob("*/fork.py"))
        + [repo / "ethereum_consensus_tpu" / "models" / "genesis_common.py"]
        + [repo / "ethereum_consensus_tpu" / "ssz" / "core.py"]
    )
    h = _hashlib.sha256()
    for f in files:
        h.update(f.read_bytes())
    return h.hexdigest()[:16]


def _disk_cached(name: str, serialize, deserialize, builder):
    """Race-safe cross-process artifact cache under tests/.deposit_cache:
    per-writer tmp names, missing_ok unlinks, and source-digest keys
    (see _cache_source_digest)."""
    path = _DEPOSIT_CACHE_DIR / f"{_cache_source_digest()}-{name}.ssz"
    try:
        return deserialize(path.read_bytes())
    except FileNotFoundError:
        pass
    except Exception:  # corrupt/partial entry: rebuild
        path.unlink(missing_ok=True)
    value = builder()
    _DEPOSIT_CACHE_DIR.mkdir(exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp")
    tmp.write_bytes(serialize(value))
    tmp.replace(path)  # atomic; concurrent writers race benignly
    return value


def make_deposits(count: int, context):
    """Deterministic bootstrap deposits, disk-cached across processes:
    the BLS signing + proof construction for large counts costs seconds
    per fresh process (bench child, spec harness, every test session)
    for bytes that never change."""
    ns = build(context.preset)
    deposit_list_type = SSZList[ns.Deposit, 2**32]
    name = (
        f"deposits-{bytes(context.genesis_fork_version).hex()}-"
        f"{int(context.MAX_EFFECTIVE_BALANCE)}-{count}"
    )
    return _disk_cached(
        name,
        deposit_list_type.serialize,
        deposit_list_type.deserialize,
        lambda: deposits_from_datas(
            [make_deposit_data(i, context) for i in range(count)], context
        ),
    )


def make_genesis_state(validator_count: int, context):
    deposits = make_deposits(validator_count, context)
    state = genesis.initialize_beacon_state_from_eth1(
        ETH1_BLOCK_HASH, ETH1_TIMESTAMP, deposits, context
    )
    return state


@functools.lru_cache(maxsize=4)
def cached_genesis(validator_count: int, preset_name: str):
    """Genesis construction is slow (BLS deposit signatures); cached per
    (count, preset) in-process AND on disk (geneses are deterministic —
    the frozen-root KATs pin them — so a fresh process deserializes
    ~10ms of SSZ instead of seconds of deposit crypto)."""
    context = Context.for_minimal() if preset_name == "minimal" else Context.for_mainnet()
    ns = build(context.preset)
    state = _disk_cached(
        f"genesis-phase0-{preset_name}-{validator_count}",
        ns.BeaconState.serialize,
        ns.BeaconState.deserialize,
        lambda: make_genesis_state(validator_count, context),
    )
    # A disk-cache hit deserializes with COLD hash-tree-root memos, while
    # an in-process build leaves them warm — downstream users (and the
    # block benches especially) would measure disk-cache luck instead of
    # steady-state processing. One throwaway root warms the memo; every
    # fresh_genesis copy carries it, matching a live client mid-chain.
    from ethereum_consensus_tpu.ssz.core import hash_tree_root as _htr

    _htr(state)
    _strip_spec_caches(state)
    return state, context


def _strip_spec_caches(state) -> None:
    """Hand cached states out PRISTINE: whether a disk-cache round-trip
    happened (cold per-state caches) or the state was just built
    in-process (warm ones — genesis sync-committee construction queries
    epoch 1, for example) must not change downstream behavior. Tests
    that mutate activity fields DIRECTLY (bypassing
    initiate_validator_exit) would otherwise hit the active-index
    cache's documented epoch-horizon gap only on in-process builds —
    a digest-change-dependent flake (round 5: flag-delta device parity
    failed only in runs that rebuilt the genesis artifacts)."""
    for key in (
        "_active_idx_cache",
        "_proposer_cache",
        "_total_active_balance_cache",
        "_pending_masks_memo",
    ):
        state.__dict__.pop(key, None)


def fresh_genesis(validator_count: int = 64, preset_name: str = "minimal"):
    state, context = cached_genesis(validator_count, preset_name)
    return state.copy(), context


def make_randao_reveal(state, slot: int, context) -> bytes:
    """Caller must have advanced ``state`` to ``slot`` for proposer lookup."""
    epoch = slot // context.SLOTS_PER_EPOCH
    proposer_sk = secret_key(h.get_beacon_proposer_index(state, context))
    domain = h.get_domain(state, DomainType.RANDAO, epoch, context)
    root = compute_signing_root(uint64, epoch, domain)
    return proposer_sk.sign(root).to_bytes()


def produce_block(state, slot: int, context, attestations=()):
    """Advance ``state`` to ``slot`` and build a valid signed block on top.
    Mutates ``state`` only by slot-advancing (the block is NOT applied)."""
    from ethereum_consensus_tpu.models.phase0.slot_processing import process_slots
    from ethereum_consensus_tpu.models.phase0.block_processing import process_block
    from ethereum_consensus_tpu.models.phase0.containers import BeaconBlockHeader

    ns = build(context.preset)
    if state.slot < slot:
        process_slots(state, slot, context)
    proposer_index = h.get_beacon_proposer_index(state, context)
    body = ns.BeaconBlockBody(
        randao_reveal=make_randao_reveal(state, slot, context),
        eth1_data=state.eth1_data.copy(),
        attestations=list(attestations),
    )
    block = ns.BeaconBlock(
        slot=slot,
        proposer_index=proposer_index,
        parent_root=BeaconBlockHeader.hash_tree_root(state.latest_block_header),
        body=body,
    )
    # compute post-state root on a scratch copy
    scratch = state.copy()
    process_block(scratch, block, context)
    block.state_root = type(scratch).hash_tree_root(scratch)

    domain = h.get_domain(state, DomainType.BEACON_PROPOSER, None, context)
    root = compute_signing_root(ns.BeaconBlock, block, domain)
    signature = secret_key(proposer_index).sign(root).to_bytes()
    return ns.SignedBeaconBlock(message=block, signature=signature)


def sign_block(state, block, context) -> bytes:
    """(Re-)sign ``block`` with its proposer's key against ``state``'s
    fork. Fork-generic: the signing root is computed with the block's
    OWN SSZ type, so any fork's block re-signs correctly (the scenario
    mutators re-sign altair→electra blocks through this)."""
    domain = h.get_domain(state, DomainType.BEACON_PROPOSER, None, context)
    root = compute_signing_root(type(block), block, domain)
    return secret_key(block.proposer_index).sign(root).to_bytes()


def make_attestation(state, slot: int, index: int, context, participation=1.0,
                     beacon_block_root=None, source=None):
    """A valid attestation for (slot, committee index) on ``state`` (which
    must be at a slot where [slot]'s data is known, i.e. state.slot >= slot).
    ``beacon_block_root`` overrides the honest head vote — a PROPERLY
    SIGNED equivocation (same slot/committee/target, different data): the
    attester-slashing scenario's double-vote half. ``source`` overrides
    the honest source checkpoint (a ``Checkpoint`` container): a properly
    signed SURROUND vote — pair one widened-source attestation in a later
    epoch against an honest one in an earlier epoch and the spans nest."""
    ns = build(context.preset)
    committee = h.get_beacon_committee(state, slot, index, context)
    epoch = slot // context.SLOTS_PER_EPOCH
    if source is not None:
        source = source.copy()
    elif epoch == h.get_current_epoch(state, context):
        source = state.current_justified_checkpoint.copy()
    else:
        source = state.previous_justified_checkpoint.copy()
    start_slot = h.compute_start_slot_at_epoch(epoch, context)
    data = ns.AttestationData(
        slot=slot,
        index=index,
        beacon_block_root=(
            _block_root_at_or_latest(state, slot)
            if beacon_block_root is None
            else bytes(beacon_block_root)
        ),
        source=source,
        target=ns.Checkpoint(
            epoch=epoch, root=_block_root_at_or_latest(state, start_slot)
        ),
    )
    n_participants = max(1, int(len(committee) * participation))
    bits = [i < n_participants for i in range(len(committee))]
    domain = h.get_domain(state, DomainType.BEACON_ATTESTER, epoch, context)
    root = compute_signing_root(ns.AttestationData, data, domain)
    signature = aggregate_sign(
        [committee[i] for i in range(len(committee)) if bits[i]], root
    ).to_bytes()
    return ns.Attestation(
        aggregation_bits=bits, data=data, signature=signature
    )


def _block_root_at_or_latest(state, slot: int) -> bytes:
    """Block root for ``slot``: from history if in the past, else the root
    the latest header will take once its state root is filled."""
    from ethereum_consensus_tpu.models.phase0.containers import BeaconBlockHeader

    if slot < state.slot:
        return h.get_block_root_at_slot(state, slot)
    header = state.latest_block_header.copy()
    if header.state_root == b"\x00" * 32:
        header.state_root = type(state).hash_tree_root(state)
    return BeaconBlockHeader.hash_tree_root(header)


# ---------------------------------------------------------------------------
# post-phase0 forks — one generic genesis/payload/block factory
# (forks differ only in module, genesis payload header, and body extras)
# ---------------------------------------------------------------------------

GENESIS_PAYLOAD_BLOCK_HASH = b"\x77" * 32

# forks whose genesis takes an execution payload header
_PAYLOAD_FORKS = ("bellatrix", "capella", "deneb", "electra")


def _fork_module(fork_name: str):
    import importlib

    return importlib.import_module(f"ethereum_consensus_tpu.models.{fork_name}")


def make_genesis_payload_header(context, fork_name: str = "bellatrix"):
    """A non-default genesis ExecutionPayloadHeader (post-merge genesis)."""
    ns = _fork_module(fork_name).build(context.preset)
    return ns.ExecutionPayloadHeader(
        block_hash=GENESIS_PAYLOAD_BLOCK_HASH,
        timestamp=ETH1_TIMESTAMP + context.genesis_delay,
        prev_randao=ETH1_BLOCK_HASH,
    )


@functools.lru_cache(maxsize=24)
def _cached_genesis_fork(fork_name: str, validator_count: int, preset_name: str):
    mod = _fork_module(fork_name)
    context = Context.for_minimal() if preset_name == "minimal" else Context.for_mainnet()

    def builder():
        deposits = make_deposits(validator_count, context)
        kwargs = {}
        if fork_name in _PAYLOAD_FORKS:
            kwargs["execution_payload_header"] = make_genesis_payload_header(
                context, fork_name
            )
        return mod.genesis.initialize_beacon_state_from_eth1(
            ETH1_BLOCK_HASH, ETH1_TIMESTAMP, deposits, context, **kwargs
        )

    state_type = getattr(mod.build(context.preset), "BeaconState")
    state = _disk_cached(
        f"genesis-{fork_name}-{preset_name}-{validator_count}",
        state_type.serialize,
        state_type.deserialize,
        builder,
    )
    # warm the root memo (see cached_genesis): disk-cache hits must not
    # make downstream benches re-merkleize a cold state every iteration
    from ethereum_consensus_tpu.ssz.core import hash_tree_root as _htr

    _htr(state)
    _strip_spec_caches(state)
    return state, context


def fresh_genesis_fork(fork_name: str, validator_count: int = 64,
                       preset_name: str = "minimal"):
    state, context = _cached_genesis_fork(fork_name, validator_count, preset_name)
    return state.copy(), context


def _indices_of_keys(state, public_keys) -> list[int]:
    """Validator index of each key. Every signing key of these chains is
    ``public_key_bytes(i)`` for its own index ``i``, so the keys already
    derived in this process answer in O(1) — checked against the registry
    — and a full registry scan (seconds per call at 2^20) is only the
    fallback."""
    wanted = [bytes(pk) for pk in public_keys]
    validators = state.validators
    known = [_INDEX_OF_KEY.get(pk) for pk in wanted]
    if all(
        i is not None and i < len(validators)
        and bytes(validators[i].public_key) == pk
        for i, pk in zip(known, wanted)
    ):
        return known
    index_by_key = {bytes(v.public_key): i for i, v in enumerate(validators)}
    return [index_by_key[pk] for pk in wanted]


def make_sync_aggregate(state, context, participation=1.0):
    """Full (or partial) sync-committee signature over the previous slot's
    block root; ``state`` must be at the block's slot."""
    from ethereum_consensus_tpu.models.altair import build as altair_build
    from ethereum_consensus_tpu.models.altair import helpers as ah
    from ethereum_consensus_tpu.primitives import Root

    ns = altair_build(context.preset)
    previous_slot = max(state.slot, 1) - 1
    root = h.get_block_root_at_slot(state, previous_slot)
    domain = ah.get_domain(
        state,
        DomainType.SYNC_COMMITTEE,
        previous_slot // context.SLOTS_PER_EPOCH,
        context,
    )
    signing_root = compute_signing_root(Root, root, domain)

    committee_indices = _indices_of_keys(
        state, state.current_sync_committee.public_keys
    )
    n_participants = max(1, int(len(committee_indices) * participation))
    bits = [i < n_participants for i in range(len(committee_indices))]
    signers = [
        committee_indices[i] for i in range(len(committee_indices)) if bits[i]
    ]
    return ns.SyncAggregate(
        sync_committee_bits=bits,
        sync_committee_signature=aggregate_sign(
            signers, signing_root
        ).to_bytes(),
    )


def make_execution_payload_fork(fork_name: str, state, context, block_number=1,
                                **extra_fields):
    """A payload valid for ``state`` at its current slot: parent hash chains,
    prev_randao matches, timestamp matches; capella+ carries the expected
    withdrawals."""
    mod = _fork_module(fork_name)
    ns = mod.build(context.preset)
    epoch = state.slot // context.SLOTS_PER_EPOCH
    fields = dict(
        parent_hash=state.latest_execution_payload_header.block_hash,
        prev_randao=h.get_randao_mix(state, epoch),
        block_number=block_number,
        timestamp=mod.helpers.compute_timestamp_at_slot(state, state.slot, context),
        block_hash=bls.hash(b"exec-block-%s-%d" % (fork_name.encode(), int(state.slot))),
    )
    if fork_name == "capella" or fork_name == "deneb":
        from ethereum_consensus_tpu.models.capella.block_processing import (
            get_expected_withdrawals,
        )

        fields["withdrawals"] = get_expected_withdrawals(state, context)
    elif fork_name == "electra":
        from ethereum_consensus_tpu.models.electra.block_processing import (
            get_expected_withdrawals as electra_withdrawals,
        )

        fields["withdrawals"] = electra_withdrawals(state, context)[0]
    fields.update(extra_fields)
    return ns.ExecutionPayload(**fields)


def produce_block_fork(fork_name: str, state, slot: int, context,
                       attestations=(), payload_fields=None, apply=False,
                       **body_extras):
    """Generic produce_block for altair+ forks: advances the state, builds a
    body with attestations + a full sync aggregate (+ a chained execution
    payload on bellatrix+ and any fork-specific ``body_extras``), fills the
    post-state root on a scratch copy, and signs. With ``apply`` the block
    is applied to ``state`` itself instead of a copy — the chain builders'
    shape, which would otherwise copy the state and then apply the same
    block a second time."""
    from ethereum_consensus_tpu.models.phase0.containers import BeaconBlockHeader

    mod = _fork_module(fork_name)
    ns = mod.build(context.preset)
    if state.slot < slot:
        mod.slot_processing.process_slots(state, slot, context)
    proposer_index = h.get_beacon_proposer_index(state, context)
    body_kwargs = dict(
        randao_reveal=make_randao_reveal(state, slot, context),
        eth1_data=state.eth1_data.copy(),
        attestations=list(attestations),
        sync_aggregate=make_sync_aggregate(state, context),
    )
    if fork_name in _PAYLOAD_FORKS:
        body_kwargs["execution_payload"] = make_execution_payload_fork(
            fork_name, state, context, block_number=slot, **(payload_fields or {})
        )
    body_kwargs.update(body_extras)
    body = ns.BeaconBlockBody(**body_kwargs)
    block = ns.BeaconBlock(
        slot=slot,
        proposer_index=proposer_index,
        parent_root=BeaconBlockHeader.hash_tree_root(state.latest_block_header),
        body=body,
    )
    domain = h.get_domain(state, DomainType.BEACON_PROPOSER, None, context)
    scratch = state if apply else state.copy()
    mod.block_processing.process_block(scratch, block, context)
    block.state_root = type(scratch).hash_tree_root(scratch)

    root = compute_signing_root(ns.BeaconBlock, block, domain)
    signature = secret_key(proposer_index).sign(root).to_bytes()
    return ns.SignedBeaconBlock(message=block, signature=signature)


# -- per-fork conveniences (the names the test suites import) ----------------


def fresh_genesis_altair(validator_count: int = 64, preset_name: str = "minimal"):
    return fresh_genesis_fork("altair", validator_count, preset_name)


def fresh_genesis_bellatrix(validator_count: int = 64, preset_name: str = "minimal"):
    return fresh_genesis_fork("bellatrix", validator_count, preset_name)


def fresh_genesis_capella(validator_count: int = 64, preset_name: str = "minimal"):
    return fresh_genesis_fork("capella", validator_count, preset_name)


def fresh_genesis_deneb(validator_count: int = 64, preset_name: str = "minimal"):
    return fresh_genesis_fork("deneb", validator_count, preset_name)


def fresh_genesis_electra(validator_count: int = 64, preset_name: str = "minimal"):
    return fresh_genesis_fork("electra", validator_count, preset_name)


def make_genesis_payload_header_capella(context):
    return make_genesis_payload_header(context, "capella")


def make_genesis_payload_header_deneb(context):
    return make_genesis_payload_header(context, "deneb")


def make_genesis_payload_header_electra(context):
    return make_genesis_payload_header(context, "electra")


def make_execution_payload(state, context, block_number=1):
    return make_execution_payload_fork("bellatrix", state, context, block_number)


def make_execution_payload_capella(state, context, block_number=1):
    return make_execution_payload_fork("capella", state, context, block_number)


def make_execution_payload_deneb(state, context, block_number=1):
    return make_execution_payload_fork("deneb", state, context, block_number)


def make_execution_payload_electra(state, context, block_number=1,
                                   deposit_receipts=(), withdrawal_requests=()):
    return make_execution_payload_fork(
        "electra", state, context, block_number,
        deposit_receipts=list(deposit_receipts),
        withdrawal_requests=list(withdrawal_requests),
    )


def produce_block_altair(state, slot: int, context, attestations=()):
    return produce_block_fork("altair", state, slot, context, attestations)


def produce_block_bellatrix(state, slot: int, context, attestations=()):
    return produce_block_fork("bellatrix", state, slot, context, attestations)


def produce_block_capella(state, slot: int, context, attestations=(),
                          bls_to_execution_changes=()):
    return produce_block_fork(
        "capella", state, slot, context, attestations,
        bls_to_execution_changes=list(bls_to_execution_changes),
    )


def produce_block_deneb(state, slot: int, context, attestations=(),
                        blob_kzg_commitments=()):
    return produce_block_fork(
        "deneb", state, slot, context, attestations,
        blob_kzg_commitments=list(blob_kzg_commitments),
    )


def produce_block_electra(state, slot: int, context, attestations=(),
                          deposit_receipts=(), withdrawal_requests=(),
                          consolidations=()):
    return produce_block_fork(
        "electra", state, slot, context, attestations,
        payload_fields=dict(
            deposit_receipts=list(deposit_receipts),
            withdrawal_requests=list(withdrawal_requests),
        ),
        consolidations=list(consolidations),
    )


def make_attestation_electra(state, slot: int, context, participation=1.0,
                             beacon_block_root=None, source=None):
    """One committee-spanning electra attestation covering ALL committees of
    ``slot`` (EIP-7549). ``beacon_block_root``/``source`` override the
    honest vote exactly like ``make_attestation``'s equivocation and
    surround-vote seams."""
    from ethereum_consensus_tpu.models.electra import build as electra_build

    ns = electra_build(context.preset)
    epoch = slot // context.SLOTS_PER_EPOCH
    committee_count = h.get_committee_count_per_slot(state, epoch, context)
    committees = [
        h.get_beacon_committee(state, slot, index, context)
        for index in range(committee_count)
    ]
    if source is not None:
        source = source.copy()
    elif epoch == h.get_current_epoch(state, context):
        source = state.current_justified_checkpoint.copy()
    else:
        source = state.previous_justified_checkpoint.copy()
    start_slot = h.compute_start_slot_at_epoch(epoch, context)
    data = ns.AttestationData(
        slot=slot,
        index=0,
        beacon_block_root=(
            _block_root_at_or_latest(state, slot)
            if beacon_block_root is None
            else bytes(beacon_block_root)
        ),
        source=source,
        target=ns.Checkpoint(
            epoch=epoch, root=_block_root_at_or_latest(state, start_slot)
        ),
    )
    bits = []
    signers = set()
    for committee in committees:
        n_participants = max(1, int(len(committee) * participation))
        for i, v in enumerate(committee):
            take = i < n_participants
            bits.append(take)
            if take:
                signers.add(v)
    committee_bits = [True] * committee_count + [False] * (
        context.MAX_COMMITTEES_PER_SLOT - committee_count
    )
    domain = h.get_domain(state, DomainType.BEACON_ATTESTER, epoch, context)
    root = compute_signing_root(ns.AttestationData, data, domain)
    signature = aggregate_sign(signers, root)
    return ns.Attestation(
        aggregation_bits=bits,
        data=data,
        committee_bits=committee_bits,
        signature=signature.to_bytes(),
    )


# ---------------------------------------------------------------------------
# chains (pipeline/stream scaffolding): lists of consecutive signed blocks
# ---------------------------------------------------------------------------


def produce_chain(state, context, n_blocks: int, fork_name: str = "phase0",
                  atts_per_block: int = 1, start_slot: int | None = None):
    """``n_blocks`` consecutive valid signed blocks built on ``state``
    (which is NOT mutated), each carrying up to ``atts_per_block``
    attestations over the previous slot's committees. Returns the block
    list; replaying them in order from ``state`` is valid."""
    scratch = state.copy()
    first = int(scratch.slot) + 1 if start_slot is None else start_slot
    blocks = []
    pending_atts: list = []
    for slot in range(first, first + n_blocks):
        if fork_name == "phase0":
            block = produce_block(scratch, slot, context,
                                  attestations=pending_atts)
            p0t = _fork_module("phase0").state_transition
            p0t.state_transition_block_in_slot(
                scratch, block, p0t.Validation.ENABLED, context
            )
        else:
            block = produce_block_fork(fork_name, scratch, slot, context,
                                       attestations=pending_atts)
            stm = _fork_module(fork_name).state_transition
            stm.state_transition_block_in_slot(
                scratch, block, stm.Validation.ENABLED, context
            )
        per_slot = h.get_committee_count_per_slot(
            scratch, slot // context.SLOTS_PER_EPOCH, context
        )
        pending_atts = [
            make_attestation(scratch, slot, index, context)
            for index in range(min(atts_per_block, per_slot))
        ]
        blocks.append(block)
    return blocks


def produce_multi_fork_chain(validator_count: int = 64):
    """(genesis_state, context, blocks): a toy chain crossing the
    phase0→altair boundary — epoch 0 under phase0 rules, then altair
    blocks from the upgrade slot on (the first lands EXACTLY on it, the
    executor.rs:215-224 corner). Exercises the Executor's inline upgrade
    chain under streaming replay."""
    state, _ = fresh_genesis(validator_count, "minimal")
    context = Context.for_minimal()
    context.altair_fork_epoch = 1

    from ethereum_consensus_tpu.models.altair import upgrade_to_altair
    from ethereum_consensus_tpu.models.phase0.slot_processing import (
        process_slots,
    )

    scratch = state.copy()
    blocks = list(
        produce_chain(scratch, context, int(context.SLOTS_PER_EPOCH) - 1)
    )
    p0t = _fork_module("phase0").state_transition
    for block in blocks:
        p0t.state_transition(scratch, block, context)
    fork_slot = int(context.SLOTS_PER_EPOCH)
    process_slots(scratch, fork_slot, context)
    upgraded = upgrade_to_altair(scratch, context)
    at = _fork_module("altair").state_transition
    for slot in range(fork_slot, fork_slot + 3):
        block = produce_block_altair(upgraded, slot, context)
        at.state_transition_block_in_slot(
            upgraded, block, at.Validation.ENABLED, context
        )
        blocks.append(block)
    return state, context, blocks


FULL_UPGRADE_FORKS = (
    "phase0", "altair", "bellatrix", "capella", "deneb", "electra"
)


def full_upgrade_context():
    """A minimal-preset Context whose fork schedule activates one fork
    per epoch: altair@1, bellatrix@2, capella@3, deneb@4, electra@5 —
    the five-boundary ladder ``produce_full_upgrade_chain`` climbs."""
    context = Context.for_minimal()
    for epoch, fork in enumerate(FULL_UPGRADE_FORKS):
        if fork != "phase0":
            setattr(context, f"{fork}_fork_epoch", epoch)
    return context


def full_upgrade_fork_at_slot(slot: int, context) -> str:
    epoch = int(slot) // int(context.SLOTS_PER_EPOCH)
    return FULL_UPGRADE_FORKS[min(epoch, len(FULL_UPGRADE_FORKS) - 1)]


def produce_full_upgrade_chain(validator_count: int = 64,
                               atts_per_block: int = 2,
                               eth1_credential_validators: int = 4,
                               cache_tag: str = ""):
    """(genesis_state, context, blocks): ONE chain crossing ALL FIVE fork
    boundaries (phase0→altair→bellatrix→capella→deneb→electra, one epoch
    each on the minimal preset) with live traffic at every edge:

    * every block carries up to ``atts_per_block`` aggregate attestations
      over the previous slot's committees — including the cross-edge
      shape where attestations produced under fork F land in the first
      block of fork F+1 (previous-fork domain resolution). The deneb
      attestations pending at the electra edge are dropped (EIP-7549
      changed the container) and electra's committee-spanning aggregates
      take over.
    * ``eth1_credential_validators`` validators get 0x01 withdrawal
      credentials and an excess balance at genesis, so the capella/deneb/
      electra segments produce real partial withdrawals in every sweep
      (the balance re-accrues past the cap through attestation rewards).
    * the first block of each fork lands EXACTLY on the upgrade slot
      (the executor.rs:215-224 in-slot corner), five times over.

    Disk-cached with every parameter — and any caller-supplied
    ``cache_tag`` — in the key, so differently-parameterized (or
    scenario-derived) chains can never collide."""
    context = full_upgrade_context()
    spe = int(context.SLOTS_PER_EPOCH)
    p0ns = build(context.preset)

    def build_chain():
        state, _ = fresh_genesis(validator_count, "minimal")
        # 0x01 credentials + excess balance: live withdrawal traffic on
        # every capella+ sweep (partial withdrawals re-arm via rewards)
        for i in range(min(eth1_credential_validators, validator_count)):
            v = state.validators[i]
            v.withdrawal_credentials = (
                b"\x01" + b"\x00" * 11 + bls.hash(b"exec-addr-%d" % i)[:20]
            )
            state.balances[i] = int(state.balances[i]) + 10 * 10**9

        scratch = state.copy()
        blocks = []
        pending: list = []
        for epoch, fork in enumerate(FULL_UPGRADE_FORKS):
            first_slot = epoch * spe
            if fork != "phase0":
                prev_mod = _fork_module(FULL_UPGRADE_FORKS[epoch - 1])
                if scratch.slot < first_slot:
                    prev_mod.slot_processing.process_slots(
                        scratch, first_slot, context
                    )
                mod = _fork_module(fork)
                scratch = getattr(mod, f"upgrade_to_{fork}")(scratch, context)
                if fork == "electra":
                    pending = []  # EIP-7549 changed the Attestation container
            for slot in range(max(first_slot, 1), first_slot + spe):
                if fork == "phase0":
                    block = produce_block(
                        scratch, slot, context, attestations=pending
                    )
                else:
                    block = produce_block_fork(
                        fork, scratch, slot, context, attestations=pending
                    )
                stm = _fork_module(fork).state_transition
                if int(scratch.slot) == slot:
                    stm.state_transition_block_in_slot(
                        scratch, block, stm.Validation.ENABLED, context
                    )
                else:
                    stm.state_transition(scratch, block, context)
                if fork == "electra":
                    pending = [make_attestation_electra(scratch, slot, context)]
                else:
                    per_slot = h.get_committee_count_per_slot(
                        scratch, slot // spe, context
                    )
                    pending = [
                        make_attestation(scratch, slot, index, context)
                        for index in range(min(atts_per_block, per_slot))
                    ]
                blocks.append(block)
        return state, blocks

    def block_type_at(slot: int):
        ns = _fork_module(full_upgrade_fork_at_slot(slot, context)).build(
            context.preset
        )
        return ns.SignedBeaconBlock

    def serialize(value):
        state, blocks = value
        sb = p0ns.BeaconState.serialize(state)
        out = [len(blocks).to_bytes(4, "little"),
               len(sb).to_bytes(8, "little"), sb]
        for block in blocks:
            slot = int(block.message.slot)
            bb = block_type_at(slot).serialize(block)
            out.append(slot.to_bytes(8, "little"))
            out.append(len(bb).to_bytes(8, "little"))
            out.append(bb)
        return b"".join(out)

    def deserialize(data):
        n = int.from_bytes(data[:4], "little")
        at = 4
        ln = int.from_bytes(data[at: at + 8], "little")
        at += 8
        state = p0ns.BeaconState.deserialize(data[at: at + ln])
        at += ln
        blocks = []
        for _ in range(n):
            slot = int.from_bytes(data[at: at + 8], "little")
            at += 8
            ln = int.from_bytes(data[at: at + 8], "little")
            at += 8
            blocks.append(block_type_at(slot).deserialize(data[at: at + ln]))
            at += ln
        return state, blocks

    tag = f"-{cache_tag}" if cache_tag else ""
    state, blocks = _disk_cached(
        f"fullupgrade-{validator_count}-{atts_per_block}a-"
        f"{eth1_credential_validators}w{tag}",
        serialize,
        deserialize,
        build_chain,
    )
    from ethereum_consensus_tpu.ssz.core import hash_tree_root as _htr

    _htr(state)  # warm the root memo (see cached_genesis)
    _strip_spec_caches(state)
    return state.copy(), context, blocks


def build_mainnet_chain(fork_name: str, validator_count: int,
                        n_blocks: int, atts: int, registry=None):
    """(pre_state, context, signed_blocks), built from nothing: ``n_blocks``
    consecutive valid blocks at mainnet committee structure on a
    ``validator_count`` registry, each carrying up to ``atts`` aggregate
    attestations plus a full sync aggregate / execution payload on
    altair+/bellatrix+. Deterministic. ``mainnet_chain_bundle`` is this
    behind the disk cache; ``chip_smoke.py`` calls it directly (a 2^20
    state costs more to serialize for the cache than to build).
    ``registry(validator_count, fork_name)`` supplies the starting state
    (default: ``build_fast_registry_state``, uncached)."""
    mod = _fork_module(fork_name)
    state, ctx = (registry or build_fast_registry_state)(
        validator_count, fork_name
    )
    start = int(state.slot) + 2
    # realize every key that will sign anywhere in the chain BEFORE
    # any root is computed: committee shuffling and proposer sampling
    # read seeds and effective balances, never pubkey bytes, and the
    # chain stays within epochs whose seeds come from pre-genesis
    # randao mixes — so index selection on a throwaway blockless
    # advance matches the real replay
    needed = set()
    probe = state.copy()
    for slot in range(start, start + n_blocks):
        mod.slot_processing.process_slots(probe, slot, ctx)
        needed.add(h.get_beacon_proposer_index(probe, ctx))
    for slot in range(max(0, start - 2), start + n_blocks):
        per_slot = h.get_committee_count_per_slot(
            probe, slot // ctx.SLOTS_PER_EPOCH, ctx
        )
        for index in range(min(atts, per_slot)):
            needed.update(h.get_beacon_committee(probe, slot, index, ctx))
    del probe
    realize_validator_keys(state, needed)
    scratch = state.copy()

    def attest(slot: int) -> list:
        per_slot = h.get_committee_count_per_slot(
            scratch, slot // ctx.SLOTS_PER_EPOCH, ctx
        )
        return [
            make_attestation(scratch, slot, index, ctx)
            for index in range(min(atts, per_slot))
        ]

    # the first block attests too — to the empty slot before it — so
    # EVERY block of the chain carries its ``atts`` aggregates
    mod.slot_processing.process_slots(scratch, start - 1, ctx)
    pending = attest(start - 1)
    blocks = []
    for slot in range(start, start + n_blocks):
        blocks.append(
            produce_block_fork(
                fork_name, scratch, slot, ctx, attestations=pending,
                apply=True,
            )
        )
        pending = attest(slot)
    return state, ctx, blocks


def mainnet_chain_bundle(fork_name: str, validator_count: int,
                         n_blocks: int, atts: int, cache_tag: str = ""):
    """``build_mainnet_chain`` behind the disk cache — the replay stream
    the pipeline bench drives (a cold build at 2^20 is minutes; a warm
    call pays one deserialize).

    ``cache_tag`` MUST name any scenario/mutator parameterization a
    caller derives a non-honest stream from AND THEN re-caches: it is
    folded into the disk key, so an adversarial bundle can never collide
    with (or be served as) the honest one. In-memory corruption of the
    returned blocks needs no tag — the cached bytes are never mutated
    (mutators copy, scenarios/mutators.py)."""
    context = Context.for_mainnet()
    ns = _fork_module(fork_name).build(context.preset)

    def build():
        state, _, blocks = build_mainnet_chain(
            fork_name, validator_count, n_blocks, atts,
            registry=fast_registry_state,
        )
        return state, blocks

    def serialize(value):
        state, blocks = value
        sb = type(state).serialize(state)
        out = [len(blocks).to_bytes(4, "little"),
               len(sb).to_bytes(8, "little"), sb]
        for block in blocks:
            bb = ns.SignedBeaconBlock.serialize(block)
            out.append(len(bb).to_bytes(8, "little"))
            out.append(bb)
        return b"".join(out)

    def deserialize(data):
        n = int.from_bytes(data[:4], "little")
        at = 4
        ln = int.from_bytes(data[at : at + 8], "little")
        at += 8
        state = ns.BeaconState.deserialize(data[at : at + ln])
        at += ln
        blocks = []
        for _ in range(n):
            ln = int.from_bytes(data[at : at + 8], "little")
            at += 8
            blocks.append(ns.SignedBeaconBlock.deserialize(data[at : at + ln]))
            at += ln
        return state, blocks

    tag = f"-{cache_tag}" if cache_tag else ""
    state, blocks = _disk_cached(
        f"chainbundle-{_FASTREG_VERSION}-{fork_name}-mainnet-"
        f"{validator_count}-{n_blocks}x{atts}{tag}",
        serialize,
        deserialize,
        build,
    )
    from ethereum_consensus_tpu.ssz.core import hash_tree_root as _htr

    _htr(state)  # warm the root memo
    _strip_spec_caches(state)
    return state.copy(), context, blocks


# ---------------------------------------------------------------------------
# mainnet-scale direct registry construction (bench + scale-test scaffolding)
#
# Deposit-crypto genesis is O(n) signatures + O(n) pairings — minutes at
# 2^17 validators. The benches need a mainnet-SHAPED state (full committee
# structure, real sync committees, verifiable attestation/proposer sigs),
# not a mainnet-HISTORY state, so this builds the registry directly: every
# validator gets a deterministic synthetic pubkey (an invalid G1 encoding —
# any crypto path touching a validator that wasn't explicitly given a real
# key fails loudly instead of silently verifying), and only the validators
# that actually sign in a bench (attesting committees, the proposer, sync
# committee members) get real EIP-2333-free bench keys. Shuffling, proposer
# sampling and sync-committee sampling read seeds and effective balances,
# never pubkey bytes, so realizing keys after index selection is sound.
# ---------------------------------------------------------------------------

_FASTREG_VERSION = "v1"  # bump to invalidate disk-cached artifacts


def synthetic_pubkey_bytes(index: int) -> bytes:
    """48 deterministic bytes that can NEVER decompress: leading byte 0xFF
    sets the compression+infinity bits with a nonzero remainder, which
    every BLS12-381 decoder rejects."""
    return b"\xff" + bls.hash(b"synthetic-pk" + index.to_bytes(8, "little"))[:15] + index.to_bytes(32, "big")


def _genesis_fork_version_for(context, fork_name: str) -> bytes:
    if fork_name == "phase0":
        return context.genesis_fork_version
    return getattr(context, f"{fork_name}_fork_version")


def build_fast_registry_state(validator_count: int, fork_name: str = "phase0",
                              preset_name: str = "mainnet"):
    """Uncached direct construction — see the section comment above."""
    from ethereum_consensus_tpu.models.genesis_common import (
        initialize_state_generic,
    )
    from ethereum_consensus_tpu.primitives import (
        FAR_FUTURE_EPOCH,
        GENESIS_EPOCH,
    )

    mod = _fork_module(fork_name) if fork_name != "phase0" else None
    from ethereum_consensus_tpu.models import phase0 as _phase0_mod

    mod = mod or _phase0_mod
    context = (
        Context.for_minimal() if preset_name == "minimal" else Context.for_mainnet()
    )
    ns = mod.build(context.preset)
    kwargs = {}
    if fork_name in _PAYLOAD_FORKS:
        kwargs["execution_payload_header"] = make_genesis_payload_header(
            context, fork_name
        )
    state = initialize_state_generic(
        ns,
        _genesis_fork_version_for(context, fork_name),
        ETH1_BLOCK_HASH,
        ETH1_TIMESTAMP,
        [],  # no deposits: the registry is injected below
        context,
        process_deposit_fn=lambda *a, **k: None,
        get_next_sync_committee_fn=None,
        **kwargs,
    )

    if fork_name == "electra":
        from ethereum_consensus_tpu.primitives import (
            UNSET_DEPOSIT_RECEIPTS_START_INDEX,
        )

        state.deposit_receipts_start_index = UNSET_DEPOSIT_RECEIPTS_START_INDEX
        effective = int(context.MIN_ACTIVATION_BALANCE)
    else:
        effective = int(context.MAX_EFFECTIVE_BALANCE)
    balance = int(context.MAX_EFFECTIVE_BALANCE)

    state.validators = [
        ns.Validator(
            public_key=synthetic_pubkey_bytes(i),
            withdrawal_credentials=b"\x00"
            + bls.hash(b"wc" + i.to_bytes(8, "little"))[1:],
            effective_balance=effective,
            activation_eligibility_epoch=GENESIS_EPOCH,
            activation_epoch=GENESIS_EPOCH,
            exit_epoch=FAR_FUTURE_EPOCH,
            withdrawable_epoch=FAR_FUTURE_EPOCH,
        )
        for i in range(validator_count)
    ]
    state.balances = [balance] * validator_count
    # deposit bookkeeping: all "deposits" are consumed, so block
    # processing expects zero new Deposit operations
    state.eth1_data.deposit_count = validator_count
    state.eth1_deposit_index = validator_count
    if hasattr(state, "previous_epoch_participation"):
        state.previous_epoch_participation = [0] * validator_count
        state.current_epoch_participation = [0] * validator_count
        state.inactivity_scores = [0] * validator_count
    state.__dict__.pop("_active_idx_cache", None)
    state.__dict__.pop("_total_active_balance_cache", None)

    state.genesis_validators_root = type(state).__ssz_fields__[
        "validators"
    ].hash_tree_root(state.validators)

    if hasattr(state, "current_sync_committee"):
        from ethereum_consensus_tpu.models.altair.helpers import (
            get_next_sync_committee,
            get_next_sync_committee_indices,
        )

        # realize members BEFORE building the committee containers so they
        # carry real keys and the aggregate pubkey is computable
        realize_validator_keys(
            state, get_next_sync_committee_indices(state, context)
        )
        sync_committee = get_next_sync_committee(state, context)
        state.current_sync_committee = sync_committee
        state.next_sync_committee = sync_committee.copy()
    return state, context


def realize_validator_keys(state, indices) -> None:
    """Swap the synthetic pubkeys of ``indices`` for the real deterministic
    bench keys (``secret_key(i)``); idempotent."""
    for i in set(indices):
        v = state.validators[i]
        real = public_key_bytes(i)
        if bytes(v.public_key) != real:
            v.public_key = real


@functools.lru_cache(maxsize=4)
def _cached_fast_registry(fork_name: str, validator_count: int, preset_name: str):
    context = (
        Context.for_minimal() if preset_name == "minimal" else Context.for_mainnet()
    )
    mod = _fork_module(fork_name)
    state_type = mod.build(context.preset).BeaconState
    state = _disk_cached(
        f"fastreg-{_FASTREG_VERSION}-{fork_name}-{preset_name}-{validator_count}",
        state_type.serialize,
        state_type.deserialize,
        lambda: build_fast_registry_state(validator_count, fork_name, preset_name)[0],
    )
    from ethereum_consensus_tpu.ssz.core import hash_tree_root as _htr

    _htr(state)  # warm the root memo (see cached_genesis)
    _strip_spec_caches(state)
    return state, context


def fast_registry_state(validator_count: int, fork_name: str = "phase0",
                        preset_name: str = "mainnet"):
    state, context = _cached_fast_registry(fork_name, validator_count, preset_name)
    return state.copy(), context


def mainnet_block_bundle(fork_name: str, validator_count: int, atts: int):
    """(pre_state, context, signed_block) at mainnet committee structure:
    a ``validator_count`` registry, a block at slot 2 carrying up to
    ``atts`` aggregate attestations (full participation) over slots 0-1's
    committees, plus a full sync aggregate and execution payload on
    altair+/bellatrix+ forks. Disk-cached: the driver-time bench pays one
    deserialize, not thousands of signatures."""
    context = Context.for_mainnet()
    mod = _fork_module(fork_name)
    ns = mod.build(context.preset)

    def build():
        state, ctx = fast_registry_state(validator_count, fork_name)
        target = state.slot + 2
        # index selection on a throwaway advance (shuffle is pubkey-blind)
        scratch = state.copy()
        mod.slot_processing.process_slots(scratch, target, ctx)
        per_slot = h.get_committee_count_per_slot(
            scratch, h.get_current_epoch(scratch, ctx), ctx
        )
        needed = set()
        att_plan = []  # (slot, committee_index) in inclusion order
        for slot in range(max(0, target - 2), target):
            if slot + ctx.MIN_ATTESTATION_INCLUSION_DELAY > target:
                continue
            if fork_name == "electra":
                if len(att_plan) < atts:
                    att_plan.append((slot, None))
                    for index in range(per_slot):
                        needed.update(
                            h.get_beacon_committee(scratch, slot, index, ctx)
                        )
                continue
            for index in range(per_slot):
                if len(att_plan) >= atts:
                    break
                att_plan.append((slot, index))
                needed.update(h.get_beacon_committee(scratch, slot, index, ctx))
        needed.add(h.get_beacon_proposer_index(scratch, ctx))
        realize_validator_keys(state, needed)

        # attestation data reads roots off the REALIZED state's advance
        scratch = state.copy()
        mod.slot_processing.process_slots(scratch, target, ctx)
        attestations = []
        for slot, index in att_plan:
            if fork_name == "electra":
                attestations.append(
                    make_attestation_electra(scratch, slot, ctx)
                )
            else:
                attestations.append(
                    make_attestation(scratch, slot, index, ctx)
                )
        if fork_name == "phase0":
            signed = produce_block(
                state.copy(), target, context, attestations=attestations
            )
        else:
            signed = produce_block_fork(
                fork_name, state.copy(), target, ctx,
                attestations=attestations,
            )
        return state, signed

    def serialize(value):
        state, signed = value
        sb = type(state).serialize(state)
        bb = ns.SignedBeaconBlock.serialize(signed)
        return len(sb).to_bytes(8, "little") + sb + bb

    def deserialize(data):
        n = int.from_bytes(data[:8], "little")
        state = ns.BeaconState.deserialize(data[8 : 8 + n])
        signed = ns.SignedBeaconBlock.deserialize(data[8 + n :])
        return state, signed

    state, signed = _disk_cached(
        f"blockbundle-{_FASTREG_VERSION}-{fork_name}-mainnet-"
        f"{validator_count}-{atts}",
        serialize,
        deserialize,
        build,
    )
    from ethereum_consensus_tpu.ssz.core import hash_tree_root as _htr

    _htr(state)  # warm the root memo
    _strip_spec_caches(state)
    return state.copy(), context, signed


def inject_full_epoch_pendings(state, context, epoch: int) -> int:
    """Fill ``state``'s pending-attestation list for ``epoch`` with full
    participation over every (slot, committee) — the realistic pre-epoch-
    boundary shape — WITHOUT signatures (epoch processing never verifies
    them; block processing already did). Returns the pending count.

    ``state`` must have advanced past the epoch so block roots exist."""
    ns = build(context.preset)
    start = epoch * int(context.SLOTS_PER_EPOCH)
    per_slot = h.get_committee_count_per_slot(state, epoch, context)
    current = epoch == h.get_current_epoch(state, context)
    if current:
        source = state.current_justified_checkpoint.copy()
        pendings = state.current_epoch_attestations
    else:
        source = state.previous_justified_checkpoint.copy()
        pendings = state.previous_epoch_attestations
    target_root = _block_root_at_or_latest(state, start)
    n = 0
    for slot in range(start, start + int(context.SLOTS_PER_EPOCH)):
        if slot + int(context.MIN_ATTESTATION_INCLUSION_DELAY) > state.slot:
            continue
        block_root = _block_root_at_or_latest(state, slot)
        for index in range(per_slot):
            committee = h.get_beacon_committee(state, slot, index, context)
            pendings.append(
                ns.PendingAttestation(
                    aggregation_bits=[True] * len(committee),
                    data=ns.AttestationData(
                        slot=slot,
                        index=index,
                        beacon_block_root=block_root,
                        source=source,
                        target=ns.Checkpoint(epoch=epoch, root=target_root),
                    ),
                    inclusion_delay=int(context.MIN_ATTESTATION_INCLUSION_DELAY),
                    proposer_index=committee[0],
                )
            )
            n += 1
    return n
