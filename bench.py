"""Benchmarks over the BASELINE.md configs. Needs a TPU.

Headline: SSZ hash_tree_root merkleization throughput — the device merkle
reduction (ops/merkle.py: Pallas SHA-256 on TPU, XLA elsewhere) over a
2^20-leaf tree, measured against the **native C++ single-core merkle
backend** (native/sha256_merkle.cpp — the honest stand-in for the
reference's single-core `ssz_rs`/`sha2` path; the reference publishes no
numbers, see BASELINE.md). Every ``vs_baseline`` ratio in this file is
against THIS repo's from-scratch single-core C++, not against blst;
``blst_class_estimate`` fields give the external scale where one exists.

Layout:

* one process for each chip: the parent process never imports jax. It
  starts the child once; the child is the process that holds the chip.
* the child first asks jax what it runs on. Without a TPU it exits with
  code 3 and so does ``python bench.py``, printing no result: sizes never
  depend on the backend, and no CPU number is written under a device
  name. Every result carries ``platform``, ``device_kind`` and
  ``device_count``; the two mesh configs (``multichip_pipeline``,
  ``epoch_mesh``) run their children on VIRTUAL CPU devices even on a chip
  host and label their results ``platform: cpu``.
* the child writes each config's result to a progress file as it
  completes; the parent assembles the final JSON from that file even if
  the child dies or exceeds its wall-clock budget mid-config — partial
  results with per-config ``error``/``skipped`` fields beat an empty
  artifact.

The ``detail.configs`` dict carries the BASELINE.md configs and more:
  * ``state_htr``       — mainnet BeaconState hash_tree_root (config 2)
  * ``proofs``          — proof-plane proofs/s at the 2^20 registry:
                          warm stored-levels extraction (single +
                          batched multiproof) vs the cold walk, under
                          ReaderSwarm load (ISSUE 17; proofs/)
  * ``att_batch``       — 512 attestation signature-set batch verify vs
                          sequential per-set verification (config 3)
  * ``sync_agg``        — 512-key sync-aggregate fast_aggregate_verify
                          (config 4)
  * ``process_block_mainnet`` / ``process_block_deneb`` /
    ``process_block_electra`` — full mainnet-preset block application
                          per fork (config 5; electra exceeds the
                          reference, which cannot execute it)
  * ``pipeline_blocks`` — chain-pipeline replay of a 32-block deneb
                          chain (sequential vs pipelined blocks/s with
                          per-stage occupancy; pipeline/engine.py)
  * ``adversarial_replay`` — the same chain under a 10% invalid-block
                          storm (scenarios/): blocks/s with rollback +
                          resume, per-failure recovery latency
  * ``process_block``   — minimal-preset orchestration floor
  * ``sig_128k``        — the 128k-signature north star (config 1)
  * ``epoch_mainnet``   — a full epoch incl. boundary sweeps with
                          pending attestations
  * ``kzg``             — EIP-4844 commit/proof/verify/batch-verify
  * ``pairing_device``  — device RLC pairing under both product kernels
                          (u64 vs int8-MXU), the routing-threshold probe
  * ``large_agg``       — 2^16-point G1 aggregation, device vs native

Telemetry (docs/OBSERVABILITY.md): every config's result carries a
``metrics`` block — registry counter deltas (SSZ digests, pubkey-cache
hit rate, bulk-decompress and pairing-route counts, flush shape) — and
the per-block configs attribute their ``phases`` from the transition's
own telemetry spans — plus a ``device`` block (ISSUE 10): compiles /
recompile-sentinel count / transfer bytes / routing-journal tallies /
jit-cache hits, cross-checked against the observatory's own ledgers
(``journal_consistent``, folded into ``ok`` for ``pipeline_blocks`` and
the epoch configs) — plus a ``mem`` block (ISSUE 15): peak/current RSS
and bulk-copy bytes for EVERY config, and for the epoch configs the
full attribution report (per-phase RSS deltas, worst-owner census,
per-site bandwidth, profile ceiling + >=80% attribution floor folded
into ``ok``). ``--trace-out PATH`` records the whole child run as
Chrome trace JSON (device + memory lanes included); ``--metrics-out
PATH`` dumps the final registry snapshot; ``--device-out PATH`` the
device observatory's ledgers; ``--memory-out PATH`` the memory
observatory's (census, phase ledger, bandwidth sites).

Prints ONE COMPACT JSON line as the last stdout line (small enough for
any log-tail window); the full per-config evidence is written to
``BENCH_FULL.json`` next to this file:
  {"metric": "hash_tree_root_leaves_per_sec", "value": ..., "unit":
   "leaves/sec", "vs_baseline": device/native-single-core speedup,
   "detail": {"platform": "tpu", "device_kind": ..., "device_count": ...,
   "full_results": "BENCH_FULL.json", ...}}
"""

import json
import os
import secrets
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

import numpy as np

CHILD_ENV = "EC_BENCH_CHILD"
PROGRESS_ENV = "EC_BENCH_PROGRESS"
TRACE_OUT_ENV = "EC_BENCH_TRACE_OUT"      # --trace-out (child records spans)
METRICS_OUT_ENV = "EC_BENCH_METRICS_OUT"  # --metrics-out (registry snapshot)
DEVICE_OUT_ENV = "EC_BENCH_DEVICE_OUT"    # --device-out (observatory ledgers)
MEMORY_OUT_ENV = "EC_BENCH_MEMORY_OUT"    # --memory-out (memory ledgers)
MEM_PROFILE_ENV = "EC_SOAK_PROFILE"       # deployment profile path override
SERVE_PORT_ENV = "EC_BENCH_SERVE_PORT"    # --serve-port (introspection server)

NO_TPU_RC = 3               # the child's (and so bench.py's) exit code
# the 2^21-flagship epoch configs (ISSUE 9) each cost ~3 minutes of
# honest cold/warm/oracle measurement on a single core, so the child
# budget grew with them (was 900/750 through PR 8, 1800/1500 through
# PR 11); the ISSUE-12 mesh configs spawn {1,2,4,8}-device virtual-mesh
# children per fork, so the battery budget grew again
CHILD_TIMEOUT_S = 2700      # hard parent-side budget for the whole child
CONFIG_DEADLINE_S = 2400    # child starts no new config after this

LOG2_LEAVES = 20
DEVICE_REPS = 20
ATT_SETS = 512
ATT_KEYS = 8  # keys per attestation set (committee participation)
SYNC_KEYS = 512
BLOCK_REPS = 3


def _fast_test() -> bool:
    """Tiny-shape mode (EC_BENCH_TEST_FAST=1) for exercising the bench
    plumbing by hand without paying real bench costs. Results of such a
    run are not measurements of any deployment."""
    return bool(os.environ.get("EC_BENCH_TEST_FAST"))


def _note(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# configs (child side)
# ---------------------------------------------------------------------------


def bench_device(words, zero_words, depth, reps):
    """(seconds per full-tree reduction on device (min over reps), root)."""
    from ethereum_consensus_tpu.ops.merkle import merkle_root_words

    root = np.asarray(merkle_root_words(words, zero_words, depth))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        # fetch the 32-byte root to host: forces full execution
        np.asarray(merkle_root_words(words, zero_words, depth))
        times.append(time.perf_counter() - t0)
    return min(times), root


def bench_native_single_core(chunks: bytes, depth: int):
    """Seconds for the native C++ merkle backend, one core — the honest
    single-core baseline (plays the reference's ssz_rs/sha2 role)."""
    from ethereum_consensus_tpu.native import available, merkle_root_native
    from ethereum_consensus_tpu.ssz.merkle import merkleize_chunks, zero_hash

    if available():
        zh = b"".join(zero_hash(i) for i in range(depth + 1))
        t0 = time.perf_counter()
        root = merkle_root_native(chunks, depth, zh)
        return time.perf_counter() - t0, root, "native-cpp"
    # toolchain-less fallback: pure-Python hashlib (much slower => would
    # overstate the speedup; flagged in the output)
    t0 = time.perf_counter()
    root = merkleize_chunks(chunks)
    return time.perf_counter() - t0, root, "python-hashlib"


def bench_htr():
    import jax

    from ethereum_consensus_tpu.ops.merkle import zero_hash_words

    log2 = 12 if _fast_test() else LOG2_LEAVES
    n = 1 << log2
    reps = 2 if _fast_test() else DEVICE_REPS
    from ethereum_consensus_tpu.telemetry import device as tel_device

    rng = np.random.default_rng(42)
    chunks = rng.integers(0, 256, size=n * 32, dtype=np.uint8).tobytes()
    # through the observatory's h2d seam: the headline config's upload
    # volume lands in the transfer ledger on a chip capture
    words, zero_words = tel_device.h2d(
        "bench.htr",
        np.ascontiguousarray(
            np.frombuffer(chunks, dtype=">u4").astype(np.uint32).reshape(n, 8).T
        ),
        zero_hash_words(),
    )

    device_s, device_root = bench_device(words, zero_words, log2, reps)
    host_s, host_root, host_kind = bench_native_single_core(chunks, log2)
    ok = device_root.astype(">u4").tobytes() == host_root
    return {
        "ok": ok,
        "device_s": device_s,
        "host_s": host_s,
        "host_kind": host_kind,
        "leaves": n,
        "backend": jax.default_backend(),
    }


def bench_state_htr(validators: int = 1 << 20):
    """Mainnet-preset BeaconState hash_tree_root at the real mainnet
    registry scale, ~1M validators (BASELINE config 2; mainnet carries
    ~2^20 — VERDICT r4 weak #4 flagged the old 2^15 as light).

    The state is synthesized structurally and disk-cached (no deposit
    crypto — this measures merkleization, not genesis). ``first_s`` is
    the cold whole-state walk on a deserialized state; ``warm_s`` the
    memoized re-walk; ``one_validator_edit_s`` the realistic per-block
    cost: one registry write then a full state root."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from chain_utils import fast_registry_state

    state, ctx = fast_registry_state(validators)
    ns_type = type(state)
    # cache-free clone: a .copy() shares element objects whose per-element
    # root memos are warm, which would understate the cold walk
    state = ns_type.deserialize(ns_type.serialize(state))
    t0 = time.perf_counter()
    ns_type.hash_tree_root(state)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    ns_type.hash_tree_root(state)
    second = time.perf_counter() - t0
    state.validators[validators // 2].effective_balance = 31 * 10**9
    t0 = time.perf_counter()
    ns_type.hash_tree_root(state)
    edit = time.perf_counter() - t0
    return {
        "validators": validators,
        "first_s": first,
        "warm_s": second,
        "one_validator_edit_s": edit,
    }


def bench_proofs(validators: int = 1 << 20):
    """The proof plane (ISSUE 17, proofs/, docs/PROOFS.md): proofs/s off
    the stored-levels walker at the mainnet 2^20 registry, single AND
    batched, warm vs the cold ``ssz.core.prove`` walk — measured while a
    ``ReaderSwarm`` hammers the mounted data plane, so the numbers carry
    real serving contention, not a quiet interpreter.

    ``ok`` folds in the whole acceptance: the walker engaged warm on
    every large layer (zero ``proofs.fallback.*`` at production
    thresholds), every sampled warm branch is byte-identical to the cold
    walk AND verifies under
    ``is_valid_merkle_branch_for_generalized_index``, the batched
    multiproof folds back to the state root, the endpoint round-trip
    matches the in-process extraction, and the swarm saw no errors."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import random as _random

    from chain_utils import fast_registry_state

    from ethereum_consensus_tpu.proofs import (
        ProofContext,
        calculate_multi_merkle_root,
        extract_multiproof,
    )
    from ethereum_consensus_tpu.scenarios.harness import ReaderSwarm
    from ethereum_consensus_tpu.serving import BeaconDataPlane, HeadStore
    from ethereum_consensus_tpu.ssz import core as ssz_core
    from ethereum_consensus_tpu.ssz.merkle import (
        is_valid_merkle_branch_for_generalized_index,
    )
    from ethereum_consensus_tpu.telemetry import metrics as tel_metrics
    from ethereum_consensus_tpu.telemetry.server import IntrospectionServer

    if _fast_test():
        validators = min(validators, 1 << 14)
    state, ctx = fast_registry_state(validators)
    state_type = type(state)

    pc = ProofContext(state_type, state)  # the settle: memos live after

    rng = _random.Random(0x17C0)
    n_single = 512 if not _fast_test() else 64
    gindices = [
        int(ssz_core.get_generalized_index(state_type, field, rng.randrange(validators)))
        for field in ("balances", "validators")
        for _ in range(n_single // 2)
    ]
    scalar_gis = [
        int(ssz_core.get_generalized_index(state_type, "slot")),
        int(ssz_core.get_generalized_index(state_type, "finalized_checkpoint", "root")),
    ]
    gindices[: len(scalar_gis)] = scalar_gis

    store = HeadStore().attach()
    server = IntrospectionServer(port=0).start(start_flight=False)
    server.mount(BeaconDataPlane(store))
    snap = store.publish(state, ctx)
    swarm = ReaderSwarm(
        server.url(""), n_readers=2,
        ids=tuple(rng.randrange(validators) for _ in range(4)),
        max_samples=64,
    )
    metrics_base = tel_metrics.snapshot()
    try:
        # warm singles under reader load
        t0 = time.perf_counter()
        branches = [pc.proof(g) for g in gindices]
        warm_s = time.perf_counter() - t0
        warm_per_s = len(gindices) / warm_s

        # batched multiproof over a distinct-chunk subset
        batch = sorted(set(gindices))[: 256 if not _fast_test() else 32]
        t0 = time.perf_counter()
        mp = extract_multiproof(pc, gindices=batch)
        batched_s = time.perf_counter() - t0
        batched_per_s = len(batch) / batched_s
        multiproof_ok = (
            calculate_multi_merkle_root(mp.leaves, mp.proof, mp.gindices)
            == pc.root
        )

        # cold oracle: byte-identity on a subsample + the honest cold
        # rate (every sibling recomputed from values — seconds each at
        # 2^20, so the sample stays small)
        n_cold = 4
        cold_sample = rng.sample(range(len(gindices)), n_cold)
        t0 = time.perf_counter()
        cold_identical = all(
            ssz_core.prove(state_type, state, gindices[i]) == branches[i]
            for i in cold_sample
        )
        cold_s = time.perf_counter() - t0
        cold_per_s = n_cold / cold_s

        verified = all(
            is_valid_merkle_branch_for_generalized_index(
                pc.node_at(g), branch, g, pc.root
            )
            for g, branch in zip(gindices[:64], branches[:64])
        )

        # endpoint round-trip: the served document IS the extraction
        import json as _json
        import urllib.request

        g0 = gindices[0]
        with urllib.request.urlopen(
            server.url(f"/eth/v1/beacon/states/head/proof?gindex={g0}"),
            timeout=30,
        ) as response:
            doc = _json.loads(response.read())["data"]
        endpoint_ok = doc["proof"] == [
            "0x" + node.hex() for node in pc.proof(g0)
        ] and doc["leaf"] == "0x" + pc.node_at(g0).hex()
    finally:
        swarm.stop()
        store.detach()
        server.stop()
    d = tel_metrics.delta(metrics_base)
    fallbacks = {
        key.split("proofs.fallback.", 1)[1]: value
        for key, value in d.items()
        if key.startswith("proofs.fallback.") and value
    }
    ok = bool(
        pc.warm()
        and not fallbacks
        and cold_identical
        and verified
        and multiproof_ok
        and endpoint_ok
        and not swarm.errors
        and swarm.samples_seen > 0
    )
    return {
        "ok": ok,
        "validators": validators,
        "proofs_per_s_warm": warm_per_s,
        "proofs_per_s_batched": batched_per_s,
        "proofs_per_s_cold": cold_per_s,
        "warm_vs_cold_speedup": warm_per_s / cold_per_s if cold_per_s else None,
        "single_proofs": len(gindices),
        "batched_gindices": len(batch),
        "branch_depth_max": max(len(b) for b in branches),
        "bit_identical_vs_cold_walk": bool(cold_identical),
        "branches_verified": bool(verified),
        "multiproof_root_ok": bool(multiproof_ok),
        "endpoint_roundtrip_ok": bool(endpoint_ok),
        "walker_warm": pc.warm(),
        "declines": pc.declines,
        "fallbacks": fallbacks,
        "proofs_served": d.get("proofs.served", 0),
        "proofs_batched": d.get("proofs.batched", 0),
        "swarm_samples": swarm.samples_seen,
        "swarm_connection_errors": swarm.connection_errors,
        "snapshot_root": snap.root_hex(),
    }


def bench_att_batch():
    """512 attestation-shaped signature sets: one RLC multi-pairing batch
    vs sequential per-set verification (BASELINE config 3)."""
    from ethereum_consensus_tpu.crypto import bls

    sks = [bls.SecretKey(i + 1_000_001) for i in range(ATT_KEYS)]
    pks = [sk.public_key() for sk in sks]
    sets = []
    for _ in range(ATT_SETS):
        msg = secrets.token_bytes(32)
        agg = bls.aggregate([sk.sign(msg) for sk in sks])
        sets.append(bls.SignatureSet(pks, msg, agg))

    t0 = time.perf_counter()
    verdicts = bls.verify_signature_sets(sets)
    batch_s = time.perf_counter() - t0

    # device-routed variant: per-set pubkey aggregation as one segmented
    # device fold, native multi-pairing on the aggregates
    from ethereum_consensus_tpu import ops

    ops.install(bls_agg_min_n=1)
    try:
        bls.verify_signature_sets(sets)  # warm the fold compile
        t0 = time.perf_counter()
        dev_verdicts = bls.verify_signature_sets(sets)
        device_s = time.perf_counter() - t0
    except Exception:  # noqa: BLE001 — report host numbers regardless
        dev_verdicts, device_s = verdicts, None
    finally:
        ops.uninstall()

    sample = sets[:32]
    t0 = time.perf_counter()
    seq_ok = all(s.verify() for s in sample)
    seq_s = (time.perf_counter() - t0) * (ATT_SETS / len(sample))

    return {
        "ok": all(verdicts) and all(dev_verdicts) and seq_ok,
        "sets": ATT_SETS,
        "keys_per_set": ATT_KEYS,
        "batch_s": batch_s,
        "batch_device_routed_s": device_s,
        "sequential_s_extrapolated": seq_s,
        "sets_per_s": ATT_SETS / batch_s,
        "backend": bls.backend_name(),
    }


def bench_sync_agg():
    """512-key fast_aggregate_verify (BASELINE config 4). One warm-up
    verify first: a live client verifies the SAME sync committee every
    block, so the steady state has the 512 pubkeys decompressed in the
    process-wide cache — timing the cold first call would measure
    one-time cache fill (~11ms of G1 sqrts), not the per-block cost.
    ``first_verify_s`` records the cold call for transparency."""
    from ethereum_consensus_tpu.crypto import bls

    msg = secrets.token_bytes(32)
    sks = [bls.SecretKey(i + 77) for i in range(SYNC_KEYS)]
    pks = [sk.public_key() for sk in sks]
    agg = bls.aggregate([sk.sign(msg) for sk in sks])
    t0 = time.perf_counter()
    bls.fast_aggregate_verify(pks, msg, agg)
    first = time.perf_counter() - t0
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        ok = bls.fast_aggregate_verify(pks, msg, agg)
        elapsed = time.perf_counter() - t0
        best = elapsed if best is None or elapsed < best else best
    return {"ok": ok, "keys": SYNC_KEYS, "verify_s": best,
            "first_verify_s": first}


def bench_large_agg(n_points: int = 1 << 16):
    """Large-batch G1 pubkey aggregation (the data-parallel piece of the
    128k-signature north star, BASELINE config 1): device XOR-fold
    (ops/g1.py limb kernels) vs sequential native C++ adds."""
    from ethereum_consensus_tpu.native import bls as native_bls
    from ethereum_consensus_tpu.ops import g1 as device_g1

    if not native_bls.available():
        return {"error": "native backend unavailable"}
    gen = native_bls.g1_generator_raw()
    base = []
    for i in range(512):
        raw, _ = native_bls.g1_mul_raw(gen, False, (i + 3).to_bytes(32, "big"))
        base.append(raw)
    raws = (base * ((n_points + 511) // 512))[:n_points]

    got, _ = device_g1.aggregate_pubkeys_device(raws)  # compile warm-up
    t0 = time.perf_counter()
    got, _ = device_g1.aggregate_pubkeys_device(raws)
    device_s = time.perf_counter() - t0

    sample = raws[:2048]
    t0 = time.perf_counter()
    acc, acc_inf = sample[0], False
    for raw in sample[1:]:
        acc, acc_inf = native_bls.g1_add_raw(acc, acc_inf, raw, False)
    native_s = (time.perf_counter() - t0) * (n_points / len(sample))

    # correctness spot-check on the sample prefix
    spot, _ = device_g1.aggregate_pubkeys_device(sample)
    return {
        "ok": spot == acc,
        "points": n_points,
        "device_s": device_s,
        "native_sequential_s_extrapolated": native_s,
        "points_per_s_device": n_points / device_s,
        "speedup_vs_native": native_s / device_s,
    }


def bench_sig_128k(n_sigs: int = 1 << 17, distinct: int = 1 << 12):
    """The literal BASELINE config 1 shape: one fast_aggregate_verify over
    128k public keys (spec-tests/runners/bls.rs:41-45 semantics — n keys,
    one message, one aggregate signature).

    Key material is ``distinct`` real keypairs tiled to ``n_sigs`` (the
    aggregate respects multiplicity, so the verify is exact). The
    dominant work is the n-point G1 aggregation + one pairing verify.
    ``blst_class_estimate_s`` is an order-of-magnitude estimate of
    single-core blst on the same shape (~0.5µs/point add + ~1.5ms
    verify) — the vs-native ratio here is against THIS repo's C++, not
    against blst."""
    from ethereum_consensus_tpu.crypto import bls
    from ethereum_consensus_tpu.native import bls as native_bls

    if not native_bls.available():
        return {"error": "native backend unavailable"}
    msg = secrets.token_bytes(32)
    sks = [bls.SecretKey(i + 9_000_001) for i in range(distinct)]
    pks = [sk.public_key() for sk in sks]
    agg_once = bls.aggregate([sk.sign(msg) for sk in sks])
    reps = n_sigs // distinct
    agg = bls.aggregate([agg_once] * reps)
    all_pks = (pks * reps)[:n_sigs]
    for pk in pks:
        pk.raw_uncompressed()  # parse-time cost, paid once per key in real use

    t0 = time.perf_counter()
    ok = bls.fast_aggregate_verify(all_pks, msg, agg)
    native_s = time.perf_counter() - t0

    # device-routed aggregation variant (the segmented G1 fold)
    from ethereum_consensus_tpu import ops

    ops.install(bls_agg_min_n=1)
    device_error = None
    try:
        bls.fast_aggregate_verify(all_pks, msg, agg)  # warm compile
        t0 = time.perf_counter()
        dev_ok = bls.fast_aggregate_verify(all_pks, msg, agg)
        device_s = time.perf_counter() - t0
    except Exception as exc:  # noqa: BLE001
        dev_ok, device_s = None, None
        device_error = str(exc)[:120]
    finally:
        ops.uninstall()

    return {
        "ok": bool(ok),
        "device_ok": dev_ok,
        "device_error": device_error,
        "signatures": n_sigs,
        "distinct_keys": distinct,
        "native_s": native_s,
        "device_routed_s": device_s,
        "sigs_per_s_native": n_sigs / native_s,
        "sigs_per_s_device": (n_sigs / device_s) if device_s else None,
        "baseline_kind": "native-cpp single-core (this repo)",
        "blst_class_estimate_s": round(n_sigs * 5e-7 + 0.0015, 3),
    }


def bench_pairing_device(n_sets: int = 64):
    """Device RLC multi-pairing (ops/pairing.py) vs the native C++
    multi-pairing on the same single-key sets, measured under BOTH
    product kernels — the u64 CIOS loop and the int8-MXU digit matmul
    (fql.set_multiplier) — the measurement that decides
    DEFAULT_PAIRING_MIN_SETS (docs/DEVICE_PAIRING.md)."""
    from ethereum_consensus_tpu.crypto import bls
    from ethereum_consensus_tpu.native import bls as native_bls

    if not native_bls.available():
        return {"error": "native backend unavailable"}
    sks = [bls.SecretKey(3_000_001 + i) for i in range(n_sets)]
    sets = []
    for i, sk in enumerate(sks):
        msg = secrets.token_bytes(32)
        sets.append(bls.SignatureSet([sk.public_key()], msg, sk.sign(msg)))
    scalars = [(1).to_bytes(16, "big")] + [
        secrets.token_bytes(16) for _ in range(n_sets - 1)
    ]
    triples = [
        ([pk.raw_uncompressed() for pk in s.public_keys], s.message,
         s.signature.to_bytes())
        for s in sets
    ]

    t0 = time.perf_counter()
    ok_native = native_bls.batch_verify_raw(triples, bls.ETH_DST, scalars)
    native_s = time.perf_counter() - t0

    from ethereum_consensus_tpu.crypto.bls import _batch_device_pairing
    from ethereum_consensus_tpu.ops import fql

    out = {
        "ok": bool(ok_native),
        "sets": n_sets,
        "native_s": native_s,
        "native_ms_per_pair": 1e3 * native_s / (n_sets + 1),
    }
    initial_mult = fql.get_multiplier()
    for mult in ("u64", "mxu"):
        try:
            fql.set_multiplier(mult)
            ok_dev = _batch_device_pairing(sets, bls.ETH_DST, scalars)  # warm
            t0 = time.perf_counter()
            ok_dev = _batch_device_pairing(sets, bls.ETH_DST, scalars)
            dev_s = time.perf_counter() - t0
            if ok_dev is None:  # device route unusable; timing meaningless
                out[f"device_{mult}_error"] = "device route returned None"
                out["ok"] = False
                continue
            out[f"device_{mult}_s"] = dev_s
            out[f"device_{mult}_ms_per_pair"] = 1e3 * dev_s / (n_sets + 1)
            out["ok"] = out["ok"] and ok_dev is True
        except Exception as exc:  # noqa: BLE001
            out[f"device_{mult}_error"] = f"{type(exc).__name__}: {str(exc)[:120]}"
        finally:
            fql.set_multiplier(initial_mult)
    return out


def _epoch_validators(default: int = 1 << 21) -> int:
    """The epoch-config flagship shape: 2^21 validators (mainnet is past
    2^20 and the columnar-primary epoch engine is registry-size-agnostic);
    ``EC_BENCH_XL=1`` lifts it to 2^22 — the slow-marked shape, excluded
    from the default battery exactly like ``slow`` tests from tier-1."""
    if os.environ.get("EC_BENCH_XL"):
        return 1 << 22
    return default


def _rss_mb() -> "tuple[float, float]":
    """(peak_rss_mb, current_rss_mb) — the memory observatory's readers
    (telemetry/memory.py): the getrusage high-water mark (monotonic
    across configs — the epoch configs are the biggest states in the
    battery, so the peak is theirs in practice) and the instantaneous
    statm RSS for per-config attribution."""
    from ethereum_consensus_tpu.telemetry import memory as tel_memory

    return tel_memory.peak_rss_mb(), tel_memory.rss_mb()


def _mem_ceiling_mb(validators: int) -> "float | None":
    """The epoch configs' peak-RSS ceiling from the deployment profile
    (soak/profiles/default.json ``memory_ceilings``; path overridable
    via ``EC_SOAK_PROFILE``): the 2^21 flagship asserts its known
    ~9 GB envelope, the ``EC_BENCH_XL`` 2^22 stretch its measured
    18.4 GB one. None (no ceiling) when the profile omits the table."""
    from ethereum_consensus_tpu.soak.runner import load_profile

    try:
        ceilings = load_profile(
            os.environ.get(MEM_PROFILE_ENV) or None
        ).get("memory_ceilings", {})
    except (OSError, ValueError):
        return None
    key = "epoch_xl" if validators >= (1 << 22) else "epoch"
    value = ceilings.get(key)
    return float(value) if value is not None else None


def _mem_phase_delta(before: dict, after: dict) -> dict:
    """Per-phase ledger delta between two ``phase_ledger()`` snapshots:
    counts/sums subtract, watermark fields report the after value —
    only phases that actually ran in the window appear."""
    out: dict = {}
    for name, a in after.items():
        b = before.get(name, {})
        if a.get("count", 0) == b.get("count", 0):
            continue
        out[name] = {
            "count": a["count"] - b.get("count", 0),
            "rss_delta_mb": round(
                a["rss_delta_mb"] - b.get("rss_delta_mb", 0.0), 1
            ),
            "seconds": round(a["seconds"] - b.get("seconds", 0.0), 3),
            "peak_mb": a["peak_mb"],
            "rss_end_mb": a["rss_end_mb"],
            "transient_mb": a["transient_mb"],
            "traced_delta_mb": round(
                a["traced_delta_mb"] - b.get("traced_delta_mb", 0.0), 2
            ),
        }
    return out


def _mem_evidence(baseline_mb: float, phases_before: dict,
                  copies_before: dict, validators: int) -> dict:
    """The epoch configs' ``mem`` evidence block (ISSUE 15): decompose
    the config's peak RSS into NAMED terms — the carried-in baseline
    (everything earlier configs left resident), each ``mem.*`` bracket's
    retained growth, and the peak bracket's transient working set —
    plus the worst-owner census table and the per-site bulk-copy bytes.
    ``ok`` folds the profile ceiling and (while the observatory was
    active for the whole config) the >=80% attribution floor."""
    from ethereum_consensus_tpu.telemetry import memory as tel_memory

    obs = tel_memory.OBSERVATORY
    peak_mb, now_mb = _rss_mb()
    phases = _mem_phase_delta(phases_before, obs.phase_ledger())
    copies_now = obs.copy_summary()
    bandwidth = {}
    for site, agg in copies_now["sites"].items():
        prev = copies_before.get("sites", {}).get(site, {})
        count = agg["count"] - prev.get("count", 0)
        nbytes = agg["bytes"] - prev.get("bytes", 0)
        if count:
            bandwidth[site] = {"count": count, "bytes": nbytes,
                               "mb": round(nbytes / (1 << 20), 1)}
    # attribution: baseline + every explicit bench bracket's retained
    # growth (the mem.* brackets partition the config's work and never
    # nest, so their deltas are additive; the transition/epoch spans
    # nest INSIDE them and stay report-only) + the transient headroom
    # of whichever bracket raised the process high-water mark
    bench_phases = {
        name: rec for name, rec in phases.items() if name.startswith("mem.")
    }
    retained = sum(
        max(0.0, rec["rss_delta_mb"]) for rec in bench_phases.values()
    )
    peak_phase = obs.peak_phase()
    transient = 0.0
    if peak_phase in bench_phases:
        transient = bench_phases[peak_phase]["transient_mb"]
    attributed = baseline_mb + retained + transient
    fraction = min(1.0, attributed / peak_mb) if peak_mb else 0.0
    owners = obs.worst(8)
    # flat numeric twin of the worst table so bench_compare --trend can
    # chart per-owner bytes (its leaf walk skips lists)
    owner_mb = {row["owner"]: row["mb"] for row in owners}
    ceiling = _mem_ceiling_mb(validators)
    observed = bool(obs.active and bench_phases)
    ok = True
    if ceiling is not None:
        ok = peak_mb <= ceiling
    if observed:
        ok = ok and fraction >= 0.8
    return {
        "peak_rss_mb": round(peak_mb, 1),
        "rss_mb": round(now_mb, 1),
        "baseline_mb": round(baseline_mb, 1),
        "phases": phases,
        "peak_phase": peak_phase,
        "attributed_mb": round(attributed, 1),
        "attribution_fraction": round(fraction, 3),
        "owners": owners,
        "owner_mb": owner_mb,
        "bandwidth": bandwidth,
        "ceiling_mb": ceiling,
        "observed": observed,
        "ok": bool(ok),
    }


def _trace_evidence(run, exemplar_hists=()):
    """Run ``run()`` under an active span recording and return
    ``(result, evidence)`` — the causal-trace evidence block the
    pipeline/pool/soak configs fold into ``ok``: settled windows that
    actually linked (``trace.windows_linked`` moved), zero orphan spans
    among the run's records, zero silent drops, plus the exemplar
    trace_ids the named histograms retained. When a recording is
    already live (``bench --trace``) the run rides it via a watermark;
    drops then reflect battery-wide ring pressure and are reported but
    not gated (a fresh recording gates them at zero)."""
    from ethereum_consensus_tpu.telemetry import metrics as tel_metrics
    from ethereum_consensus_tpu.telemetry import spans as tel_spans

    rec = tel_spans.RECORDER
    linked_before = tel_metrics.counter("trace.windows_linked").value()
    dropped_before = tel_metrics.counter("spans.dropped").value()
    riding = rec.enabled
    if riding:
        mark = rec.mark()
        result = run()
        records = rec.records_since(mark)
    else:
        with tel_spans.recording(capacity=1 << 18):
            result = run()
            records = rec.records()
    ids = {r.span_id for r in records}
    orphans = sum(
        1 for r in records if r.parent_id and r.parent_id not in ids
    )
    windows_linked = (
        tel_metrics.counter("trace.windows_linked").value() - linked_before
    )
    dropped = (
        tel_metrics.counter("spans.dropped").value() - dropped_before
    )
    exemplars = {
        name: [
            e["trace_id"]
            for e in tel_metrics.histogram(name).exemplars()
        ]
        for name in exemplar_hists
    }
    evidence = {
        "spans": len(records),
        "traces": len({r.trace_id for r in records}),
        "windows_linked": windows_linked,
        "orphans": orphans,
        "dropped": dropped,
        "exemplars": exemplars,
        # numeric twin for bench_compare --trend (lists are skipped by
        # its leaf walk): the fraction of the named histograms whose
        # worst-N table names at least one tail trace
        "exemplar_coverage": (
            sum(1 for ids in exemplars.values() if ids)
            / len(exemplars)
            if exemplars
            else 0.0
        ),
        "ok": bool(
            windows_linked > 0
            and orphans == 0
            and (riding or dropped == 0)
        ),
    }
    return result, evidence


_EPOCH_SWEEP_SPANS = (
    "helpers.active_indices_sweep",
    "helpers.total_balance_sweep",
)


def _epoch_phase_split(records) -> dict:
    """Per-stage seconds from the columnar pass's own spans (including
    the committee-mask kernel's build span) plus the 32 per-slot state
    HTRs — the epoch configs' ``phases`` block."""
    sums: dict = {}
    for r in records:
        name = r.name
        if (
            name.startswith("epoch_vector.")
            or name.startswith("committees.")
            or name in (
                "transition.state_htr",
                "transition.process_epoch",
            )
        ):
            key = name.split(".", 1)[1] + "_s"
            sums[key] = sums.get(key, 0.0) + r.duration_s
    return sums


def _streamed_identity(state_type, a, b) -> bool:
    """Bit-identity (root AND bytes) without materializing either
    serialization whole: roots first, then one FIELD at a time — each
    side's field bytes are sha256-digested in bounded chunks and freed
    before the next field. The transient is two field buffers (the
    registry column, ~130 MB at 2^20) instead of two whole states (the
    2.26 GB ``mem.identity_check`` spike in BENCH_r15_XL). Per-field
    digest equality is equivalent to whole-serialization equality: the
    offset table is a deterministic function of the field lengths."""
    import hashlib

    if state_type.hash_tree_root(a) != state_type.hash_tree_root(b):
        return False
    chunk = 1 << 24
    for name, ftyp in state_type.fields().items():
        digests = []
        for value in (getattr(a, name), getattr(b, name)):
            h = hashlib.sha256()
            buf = ftyp.serialize(value)
            for lo in range(0, len(buf), chunk):
                h.update(buf[lo:lo + chunk])
            del buf
            digests.append(h.digest())
        if digests[0] != digests[1]:
            return False
    return True


def _epoch_cold_warm(state_type, loaded, process_slots, slots, ctx,
                     fork: "str | None" = None):
    """Honest cold/warm split for the epoch configs (VERDICT next-round
    #2): cold = one epoch on a freshly DESERIALIZED state (every SSZ memo
    cold); warm = best-of-2 epochs on copies of the memo-warm, column-
    primed state (the steady state of a resident client — copies share
    the registry columns copy-on-write, _share_col_cache).

    Beyond the two seconds this also produces the columnar-primary
    acceptance evidence (ISSUE 9): per-stage ``phases`` from the engine's
    spans, peak RSS, a bit-identity check (root AND bytes) of the
    columnar epoch against the ``ECT_EPOCH_VECTOR=off`` prior path, and
    the no-per-validator-materialization assertion — the engine engaged,
    zero ``epoch_vector.fallback.*``, zero column builds, and no named
    registry-sweep span inside the warm pass (the
    ``hot_sweeps_per_block_absent`` discipline, epoch edition)."""
    from ethereum_consensus_tpu.telemetry import memory as tel_memory
    from ethereum_consensus_tpu.telemetry import metrics as tel_metrics
    from ethereum_consensus_tpu.telemetry import spans as tel_spans

    import gc

    # memory evidence (ISSUE 15): the config's RSS story decomposes into
    # the mem.* brackets below — baseline is everything earlier configs
    # left resident at entry
    mem_obs = tel_memory.OBSERVATORY
    mem_baseline_mb = tel_memory.rss_mb()
    mem_phases_before = mem_obs.phase_ledger()
    mem_copies_before = mem_obs.copy_summary()

    def timed_epoch(state) -> float:
        """One epoch with the collector parked (the pyperf discipline):
        a 2^21 state copy is ~20M tracked objects, and a gen-2 pass
        landing inside the timed window adds >1s of allocator walk that
        is neither the transition's work nor steady-state behavior (a
        resident client freezes its registry exactly like child_main
        does between configs). gc.collect() runs between timings, so
        nothing accumulates."""
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            process_slots(state, 2 * slots, ctx)
            return time.perf_counter() - t0
        finally:
            gc.enable()

    with tel_memory.phase("mem.cold_state_build"):
        cold_state = state_type.deserialize(state_type.serialize(loaded))
    with tel_memory.phase("mem.cold_epoch"):
        cold_s = timed_epoch(cold_state)
    del cold_state
    with tel_memory.phase("mem.warm_prime"):
        state_type.hash_tree_root(loaded)  # warm the root memo
        if fork is not None:
            _prime_warm_state(fork, loaded, ctx)  # columns live on original
        scratch = loaded.copy()
        process_slots(scratch, 2 * slots, ctx)  # warm imports/caches once
        del scratch

    # headline: best-of-3 uninstrumented warm epochs, timed straight
    # after the warm-up (the resident-client regime; later copies churn
    # 2 GB of allocator pages per iteration, a harness artifact best-of
    # filters out)
    times = []
    final = None
    with tel_memory.phase("mem.warm_epochs"):
        for _ in range(3):
            state = loaded.copy()
            times.append(timed_epoch(state))
            final = state
    warm_s = min(times)

    # instrumented warm run: engagement counters + per-stage spans
    metrics_base = tel_metrics.snapshot()
    rec = tel_spans.RECORDER
    state = loaded.copy()
    with tel_memory.phase("mem.instrumented_epoch"):
        if rec.enabled:
            before_id = max((r.span_id for r in rec.records()), default=0)
            process_slots(state, 2 * slots, ctx)
            records = [r for r in rec.records() if r.span_id > before_id]
        else:
            with tel_spans.recording(capacity=1 << 16):
                process_slots(state, 2 * slots, ctx)
                records = rec.records()
    d = tel_metrics.delta(metrics_base)
    fallbacks = {
        key.split("epoch_vector.fallback.", 1)[1]: value
        for key, value in d.items()
        if key.startswith("epoch_vector.fallback.") and value
    }
    sweep_spans = sorted(
        {r.name for r in records if r.name in _EPOCH_SWEEP_SPANS}
    )
    evidence = {
        "columnar_epochs": d.get("epoch_vector.epochs", 0),
        "fallbacks": fallbacks,
        "column_builds": d.get("ops_vector.columns.builds", 0),
        "sweep_spans_in_pass": sweep_spans,
        "validator_writes": d.get("epoch_vector.validator_writes", 0),
        # the committee-mask kernel's engagement (ISSUE 14): a consumed
        # bundle is a build OR a memo hit; any committees.fallback.*
        # means a spec-helper walk ran inside the pass
        "masks": {
            "builds": d.get("committees.masks.builds", 0),
            "hits": d.get("committees.masks.hits", 0),
            "shuffles": d.get("committees.shuffles", 0),
            "fallbacks": {
                key.split("committees.fallback.", 1)[1]: value
                for key, value in d.items()
                if key.startswith("committees.fallback.") and value
            },
        },
    }
    evidence["elem_materialization_absent"] = bool(
        evidence["columnar_epochs"] >= 1
        and not fallbacks
        and evidence["column_builds"] == 0
        and not sweep_spans
    )
    phases = _epoch_phase_split(records)
    del state

    # the scalar-oracle twin: the PRIOR epoch path (vectorized stages,
    # containers primary) — both the bit-identity oracle and the
    # speedup comparator
    old = os.environ.get("ECT_EPOCH_VECTOR")
    os.environ["ECT_EPOCH_VECTOR"] = "off"
    try:
        with tel_memory.phase("mem.oracle_epoch"):
            oracle = loaded.copy()
            oracle_s = timed_epoch(oracle)
    finally:
        if old is None:
            os.environ.pop("ECT_EPOCH_VECTOR", None)
        else:
            os.environ["ECT_EPOCH_VECTOR"] = old
    with tel_memory.phase("mem.identity_check"):
        identical = _streamed_identity(state_type, final, oracle)
    evidence["bit_identical_vs_oracle"] = bool(identical)
    mem = _mem_evidence(
        mem_baseline_mb, mem_phases_before, mem_copies_before,
        len(loaded.validators),
    )
    return {
        "cold_epoch_s": cold_s,
        "epoch_s": warm_s,
        "oracle_epoch_s": oracle_s,
        "columnar_vs_oracle_speedup": (
            round(oracle_s / warm_s, 2) if warm_s else None
        ),
        "phases": phases,
        "peak_rss_mb": mem["peak_rss_mb"],
        "rss_mb": mem["rss_mb"],
        "mem": mem,
        "columnar": evidence,
    }


def _fused_jit_evidence(state_type, loaded, process_slots, slots,
                        ctx) -> dict:
    """Prove the FUSED device epoch kernel (ISSUE 14) on this backend:
    route TWO warm epochs through the ops.install sweeps flag (the
    columnar pass then dispatches inactivity + rewards as the ONE jitted
    ``epoch_vector.fused_epoch_kernel``) and assert, from the device
    observatory's own ledgers: exactly ONE compile of the fused kernel
    across both epochs (zero RECOMPILE events — dynamic per-epoch
    scalars, static chain constants), the packed columns uploaded at the
    SINGLE ``epoch_vector.fused`` site (the per-stage
    inactivity/rewards upload sites stay silent), and the fused state
    bit-identical to the host pass."""
    import gc
    import hashlib

    from ethereum_consensus_tpu import _device_flags
    from ethereum_consensus_tpu.telemetry import device as tel_device
    from ethereum_consensus_tpu.telemetry import metrics as tel_metrics

    host = loaded.copy()
    process_slots(host, 2 * slots, ctx)
    host_root = state_type.hash_tree_root(host)
    host_bytes = hashlib.sha256(state_type.serialize(host)).hexdigest()
    del host
    gc.collect()

    obs = tel_device.OBSERVATORY
    started_here = not obs.active
    if started_here:
        tel_device.start()
    compiles_before = [
        c for c in obs.compiles()
        if c["fn"] == "epoch_vector.fused_epoch_kernel"
    ]
    sites_before = dict(obs.transfer_summary().get("sites", {}))
    metrics_base = tel_metrics.snapshot()
    saved = _device_flags.SWEEPS_MIN_N
    _device_flags.SWEEPS_MIN_N = 1
    try:
        times = []
        fused_state = None
        for _ in range(2):
            s = loaded.copy()
            gc.collect()
            t0 = time.perf_counter()
            process_slots(s, 2 * slots, ctx)
            times.append(time.perf_counter() - t0)
            fused_state = s
    finally:
        _device_flags.SWEEPS_MIN_N = saved
    d = tel_metrics.delta(metrics_base)
    fused_compiles = [
        c for c in obs.compiles()
        if c["fn"] == "epoch_vector.fused_epoch_kernel"
    ][len(compiles_before):]
    sites_after = obs.transfer_summary().get("sites", {})

    def _site_delta(site: str, field: str) -> int:
        now = sites_after.get(site, {}).get(field, 0)
        return now - sites_before.get(site, {}).get(field, 0)

    identical = bool(
        state_type.hash_tree_root(fused_state) == host_root
        and hashlib.sha256(
            state_type.serialize(fused_state)
        ).hexdigest() == host_bytes
    )
    if started_here:
        tel_device.stop()
    out = {
        "engaged": d.get("epoch_vector.fused.jit", 0),
        "compiles": len(fused_compiles),
        "recompiles": sum(1 for c in fused_compiles if c["recompile"]),
        "epoch_s_first": times[0],
        "epoch_s_warm": times[1],
        "fused_h2d_count": _site_delta("epoch_vector.fused", "h2d_count"),
        "fused_h2d_bytes": _site_delta("epoch_vector.fused", "h2d_bytes"),
        "staged_h2d_count": (
            _site_delta("parallel.epoch.inactivity", "h2d_count")
            + _site_delta("parallel.epoch.rewards", "h2d_count")
        ),
        "fused_fallbacks": {
            key.split("epoch_vector.fused_fallback.", 1)[1]: value
            for key, value in d.items()
            if key.startswith("epoch_vector.fused_fallback.") and value
        },
        "bit_identical_vs_host": identical,
    }
    # compiles == 0 is the compile-once discipline working ACROSS
    # configs: an earlier epoch config in the same battery already
    # compiled the kernel and these two epochs were pure cache hits —
    # record it, and gate on "at most one compile, never a recompile"
    out["compile_reused_from_earlier_config"] = out["compiles"] == 0
    out["ok"] = bool(
        out["engaged"] >= 2
        and out["compiles"] <= 1
        and out["recompiles"] == 0
        and out["fused_h2d_count"] > 0
        and out["staged_h2d_count"] == 0
        and not out["fused_fallbacks"]
        and identical
    )
    return out


def bench_epoch_mainnet(validators: "int | None" = None):
    """One full epoch of slot processing on a 2,097,152-validator
    registry (the 2^21 flagship shape — mainnet is past 2^20; see
    ``_epoch_validators``) WITH full pending-attestation coverage —
    1,024 pendings over all attesters, the realistic shape of the
    epoch-boundary rewards/penalties loops plus the per-slot state roots
    (phase0/epoch_processing.rs:1039, the HOT loops of SURVEY §3.1). The
    prepared pre-boundary state is disk-cached; pendings are injected
    unsigned (epoch processing never verifies signatures — block
    processing already did)."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import chain_utils

    from ethereum_consensus_tpu.models import phase0
    from ethereum_consensus_tpu.models.phase0.slot_processing import (
        process_slots,
    )

    ctx = chain_utils.Context.for_mainnet()
    ns = phase0.build(ctx.preset)
    slots = int(ctx.SLOTS_PER_EPOCH)
    validators = validators or _epoch_validators()

    def build():
        state, _ = chain_utils.fast_registry_state(validators)
        process_slots(state, slots, ctx)  # land on the epoch-1 boundary
        chain_utils.inject_full_epoch_pendings(state, ctx, epoch=0)
        return state

    loaded = chain_utils._disk_cached(
        f"epochstate-{chain_utils._FASTREG_VERSION}-mainnet-{validators}",
        ns.BeaconState.serialize,
        ns.BeaconState.deserialize,
        build,
    )
    n_atts = len(loaded.previous_epoch_attestations)
    out = _epoch_cold_warm(
        ns.BeaconState, loaded, process_slots, slots, ctx, fork="phase0"
    )
    # ISSUE 14 acceptance: at the 2^21+ flagship shape the committee-mask
    # kernel must be ENGAGED (bundles consumed, zero committees.fallback.*
    # inside the pass), the pass columnar with zero epoch_vector.fallback.*
    # (elem_materialization_absent covers it), and warm epoch_s <= 0.5 s
    flagship = validators >= (1 << 21)
    masks = out["columnar"]["masks"]
    masks_engaged = bool(
        (masks["builds"] + masks["hits"]) >= 1 and not masks["fallbacks"]
    )
    ok = bool(
        out["columnar"]["bit_identical_vs_oracle"]
        and out["columnar"]["elem_materialization_absent"]
        and masks_engaged
        and out["mem"]["ok"]  # ceiling + attribution (ISSUE 15)
    )
    if flagship:
        ok = ok and out["epoch_s"] <= 0.5
    out.update(
        validators=validators,
        slots=slots,
        pending_attestations=n_atts,
        ms_per_slot=1e3 * out["epoch_s"] / slots,
        mask_engaged=masks_engaged,
        target_epoch_s=0.5 if flagship else None,
        ok=ok,
    )
    return out


def _build_epoch_state(chain_utils, ns, ctx, fork: str, validators: int):
    """The deneb/electra epoch configs' prepared pre-boundary state —
    ONE builder (shared with the `epoch_mesh` children's loader) so
    every caller caches byte-identical artifacts under the same key:
    land on the epoch-1 boundary with full previous-epoch
    participation; electra additionally carries the EIP-7251 churn
    work (pending deposits, ripe consolidations, entrants, ejection
    candidates) so its boundary stages are never empty passes."""
    import importlib

    sp = importlib.import_module(
        f"ethereum_consensus_tpu.models.{fork}.slot_processing"
    )
    slots = int(ctx.SLOTS_PER_EPOCH)
    state, _ = chain_utils.fast_registry_state(validators, fork)
    sp.process_slots(state, slots, ctx)
    state.previous_epoch_participation = [0b111] * validators
    if fork == "electra":
        from ethereum_consensus_tpu.primitives import FAR_FUTURE_EPOCH

        for i in range(1 << 10):
            state.pending_balance_deposits.append(
                ns.PendingBalanceDeposit(index=i, amount=10**9)
            )
        for j in range(64):
            src = validators - 1 - j
            v = state.validators[src]
            v.exit_epoch = 1
            v.withdrawable_epoch = 1
            state.pending_consolidations.append(
                ns.PendingConsolidation(source_index=src, target_index=j)
            )
        for k in range(128):
            v = state.validators[1024 + k]
            v.activation_eligibility_epoch = FAR_FUTURE_EPOCH
            v.activation_epoch = FAR_FUTURE_EPOCH
            w = state.validators[4096 + k]
            w.effective_balance = int(ctx.ejection_balance)
    return state


def bench_epoch_deneb(validators: "int | None" = None):
    """THE flagship epoch config (ISSUE 9 acceptance): one full deneb
    epoch over a 2,097,152-validator registry — the altair-family epoch
    path (participation-flag rewards x3 + inactivity + sync/registry/
    slashings machinery) with FULL previous-epoch participation, plus
    the per-slot state roots, run as ONE columnar-primary vectorized
    pass (models/epoch_vector.py). ``ok`` requires bit-identity vs the
    prior path, the no-materialization assertion, AND — at the 2^21+
    flagship shape — warm epoch_s <= 1.0 s. ``EC_BENCH_XL=1`` lifts the
    shape to 2^22. Prepared pre-boundary state is disk-cached; honest
    cold/warm split."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import chain_utils

    from ethereum_consensus_tpu.models.deneb import containers as dc
    from ethereum_consensus_tpu.models.deneb.slot_processing import (
        process_slots,
    )

    ctx = chain_utils.Context.for_mainnet()
    ns = dc.build(ctx.preset)
    slots = int(ctx.SLOTS_PER_EPOCH)
    validators = validators or _epoch_validators()

    def build():
        # full epoch-0 participation (all three timely flags) — shared
        # builder, so epoch_mesh children reuse this exact artifact
        return _build_epoch_state(chain_utils, ns, ctx, "deneb", validators)

    loaded = chain_utils._disk_cached(
        f"epochstate-deneb-{chain_utils._FASTREG_VERSION}-mainnet-{validators}",
        ns.BeaconState.serialize,
        ns.BeaconState.deserialize,
        build,
    )
    out = _epoch_cold_warm(
        ns.BeaconState, loaded, process_slots, slots, ctx, fork="deneb"
    )
    # the fused device epoch kernel's proof (ISSUE 14): one compile, one
    # upload site, bit-identical — on this backend (cpu or chip alike)
    out["fused"] = _fused_jit_evidence(
        ns.BeaconState, loaded, process_slots, slots, ctx
    )
    flagship = validators >= (1 << 21)
    ok = bool(
        out["columnar"]["bit_identical_vs_oracle"]
        and out["columnar"]["elem_materialization_absent"]
        and out["fused"]["ok"]
        and out["mem"]["ok"]  # ceiling + attribution (ISSUE 15)
    )
    if flagship:
        ok = ok and out["epoch_s"] <= 1.0
    out.update(
        validators=validators,
        slots=slots,
        fork="deneb",
        full_participation=True,
        ms_per_slot=1e3 * out["epoch_s"] / slots,
        target_epoch_s=1.0 if flagship else None,
        ok=ok,
    )
    return out


def bench_epoch_electra(validators: "int | None" = None):
    """One full electra epoch at the 2^21 flagship shape with the
    EIP-7251 churn stages carrying REAL work — not empty passes: 1,024
    pending balance deposits, 64 ripe pending consolidations
    (withdrawable sources into compounding targets), 128
    activation-queue entrants, 128 ejection candidates, plus FULL
    previous-epoch participation. All of it runs inside the
    columnar-primary pass (models/epoch_vector.py — the churn loops read
    and write the working columns). The reference cannot execute electra
    at all (executor.rs:155-172). Honest cold/warm split."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import chain_utils

    from ethereum_consensus_tpu.models.electra import containers as ec
    from ethereum_consensus_tpu.models.electra.slot_processing import (
        process_slots,
    )

    ctx = chain_utils.Context.for_mainnet()
    ns = ec.build(ctx.preset)
    slots = int(ctx.SLOTS_PER_EPOCH)
    validators = validators or _epoch_validators()

    def build():
        # EIP-7251 boundary work (pending deposits, ripe consolidations,
        # entrants, ejection candidates) — shared builder, so epoch_mesh
        # children reuse this exact artifact
        return _build_epoch_state(
            chain_utils, ns, ctx, "electra", validators
        )

    loaded = chain_utils._disk_cached(
        f"epochstate-electra-{chain_utils._FASTREG_VERSION}-mainnet-"
        f"{validators}",
        ns.BeaconState.serialize,
        ns.BeaconState.deserialize,
        build,
    )
    out = _epoch_cold_warm(
        ns.BeaconState, loaded, process_slots, slots, ctx, fork="electra"
    )
    out["fused"] = _fused_jit_evidence(
        ns.BeaconState, loaded, process_slots, slots, ctx
    )
    out.update(
        validators=validators,
        slots=slots,
        fork="electra",
        full_participation=True,
        ms_per_slot=1e3 * out["epoch_s"] / slots,
        ok=bool(
            out["columnar"]["bit_identical_vs_oracle"]
            and out["columnar"]["elem_materialization_absent"]
            and out["fused"]["ok"]
            and out["mem"]["ok"]  # ceiling + attribution (ISSUE 15)
        ),
    )
    return out


def bench_kzg(n_blobs: int = 4):
    """KZG/EIP-4844 suite timings (the reference's named perf artifact:
    batch KZG proof verification, crypto/kzg.rs:139 — c-kzg's C role is
    played by the native MSM + pairing backend here)."""
    from ethereum_consensus_tpu.config import Context
    from ethereum_consensus_tpu.crypto import kzg
    from ethereum_consensus_tpu.native import bls as native_bls

    if not native_bls.available():
        return {"error": "native backend unavailable"}
    settings = Context.for_mainnet().kzg_settings
    rng = np.random.default_rng(77)
    # field elements uniform mod r (like canonical blob data) — small
    # scalars would flatter the MSM by emptying top Pippenger windows
    R = kzg.R
    blobs = [
        b"".join(
            (int.from_bytes(rng.bytes(32), "big") % R).to_bytes(32, "big")
            for _ in range(4096)
        )
        for _ in range(n_blobs)
    ]
    # one throwaway commit first: the fixed-base MSM tables for the (one,
    # process-lifetime) trusted setup precompute on first use — a live
    # node commits blobs against the same setup forever, so steady state
    # is the honest per-blob number; msm_prepare_s records the one-time
    # cost for transparency
    t0 = time.perf_counter()
    kzg.blob_to_kzg_commitment(blobs[0], settings)
    prepare_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    commitments = [bytes(kzg.blob_to_kzg_commitment(b, settings)) for b in blobs]
    commit_s = (time.perf_counter() - t0) / n_blobs
    t0 = time.perf_counter()
    proofs = [
        bytes(kzg.compute_blob_kzg_proof(b, c, settings))
        for b, c in zip(blobs, commitments)
    ]
    proof_s = (time.perf_counter() - t0) / n_blobs
    t0 = time.perf_counter()
    ok1 = kzg.verify_blob_kzg_proof(blobs[0], commitments[0], proofs[0], settings)
    verify_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    okb = kzg.verify_blob_kzg_proof_batch(blobs, commitments, proofs, settings)
    batch_s = time.perf_counter() - t0
    return {
        "ok": bool(ok1) and bool(okb),
        "blobs": n_blobs,
        "commit_s_per_blob": commit_s,
        "msm_prepare_s": prepare_s,
        "proof_s_per_blob": proof_s,
        "verify_s": verify_s,
        "batch_verify_s": batch_s,
        "batch_verify_s_per_blob": batch_s / n_blobs,
    }


def _phase_breakdown(fork: str, state, ctx, signed) -> dict:
    """One recorded transition on a warm state copy, attributed from the
    transition's OWN telemetry spans (models/transition.py + the fork
    helpers emit transition.slot_advance/.block/.sig_batch/.state_htr/
    .committees; telemetry/phases.py sums them and computes the
    operations residual) — the same attribution any entry point gets by
    recording a run, so this bench, the pipeline CLI, and the spec
    harness all speak one phase vocabulary. Recording overhead makes
    the phases sum slightly above the uninstrumented ``block_s``; the
    split is for ATTRIBUTION (VERDICT next-round #1b) — the headline
    number stays the uninstrumented run."""
    import importlib

    from ethereum_consensus_tpu.telemetry import phases as tel_phases
    from ethereum_consensus_tpu.telemetry import spans as tel_spans

    st = importlib.import_module(
        f"ethereum_consensus_tpu.models.{fork}.state_transition"
    )

    def run_transition():
        s = state.copy()
        st.process_slots(s, signed.message.slot, ctx)
        st.state_transition_block_in_slot(
            s, signed, st.Validation.ENABLED, ctx
        )

    rec = tel_spans.RECORDER
    if rec.enabled:
        # a bench-wide recording (--trace-out) is live: don't clobber its
        # buffer — attribute over the spans this transition appends
        before_id = max((r.span_id for r in rec.records()), default=0)
        run_transition()
        records = [r for r in rec.records() if r.span_id > before_id]
    else:
        with tel_spans.recording(capacity=1 << 17):
            run_transition()
            records = rec.records()
    out = tel_phases.attribution(records)
    # the three named ROADMAP hot scans must NOT appear per block on the
    # warm path (the epoch caches + columnar withdrawals take them off
    # it); boundary occurrences are legitimate once-per-epoch work
    out["hot_sweeps"] = tel_phases.hot_sweep_report(records)
    out["note"] = (
        "span-attributed instrumented run; headline block_s is "
        "uninstrumented"
    )
    return out


def _prime_warm_state(fork: str, state, ctx) -> None:
    """Warm the state-level epoch memos and registry columns on the
    ORIGINAL bundle state. Copies share both (dict-value sharing for the
    epoch memos, structural copy-on-write for the list-resident columns,
    ssz/core.py _share_col_cache), so the timed warm runs measure the
    steady state of a resident client instead of re-deriving per copy."""
    import importlib

    hmod = importlib.import_module(
        f"ethereum_consensus_tpu.models.{fork}.helpers"
    )
    epoch = hmod.get_current_epoch(state, ctx)
    for e in {epoch, max(0, epoch - 1)}:
        hmod.get_active_validator_indices(state, e)
    hmod.get_total_active_balance(state, ctx)
    if fork == "phase0":
        # prime the committee-mask bundles (ISSUE 14) on the ORIGINAL:
        # the memo travels across copies (guarded by the pending lists'
        # full-walk freshness), so the timed warm runs consume the
        # boundary masks a resident client would already hold
        from ethereum_consensus_tpu.models import committees

        for e in {epoch, max(0, epoch - 1)}:
            committees.pending_masks_for(state, e, ctx)
    from ethereum_consensus_tpu.models.phase0.helpers import (
        _registry_pubkey_objects,
    )

    # create the lazily-filled pubkey memos ON the original: copies share
    # the backing list/dict through __dict__ value sharing, so fills made
    # during one replayed block persist for the next (resident-client
    # steady state) instead of dying with each discarded copy
    _registry_pubkey_objects(state)
    if fork != "phase0":
        from ethereum_consensus_tpu.models.altair.block_processing import (
            _registry_pubkey_index,
        )

        _registry_pubkey_index(state)
    from ethereum_consensus_tpu.models import ops_vector

    cols = ops_vector.columns_for(state)
    if cols is not None:
        cols.validator_columns(state)
        for field in (
            "balances",
            "inactivity_scores",
            "previous_epoch_participation",
            "current_epoch_participation",
        ):
            if getattr(state, field, None) is not None:
                cols.list_column(state, field)


def _bench_mainnet_block(fork: str, validators: int, atts: int) -> dict:
    """Shared mainnet-preset block scaffold at REAL mainnet committee
    structure: a 2^20-validator registry (mainnet carries ~2^20; preset
    bounds MAX_COMMITTEES_PER_SLOT=64, TARGET_COMMITTEE_SIZE=128) so the
    block carries ``atts`` genuine aggregate attestations — not the
    1-committee light blocks VERDICT r4 weak #4 flagged. The (state,
    signed block) bundle is disk-cached by chain_utils.mainnet_block_bundle;
    every signature set is verified (batched) and the full per-slot state
    HTR runs.

    Honest cold/warm split (VERDICT next-round #2): ``cold_block_s`` is
    one transition on a freshly DESERIALIZED pre-state — every SSZ memo
    cold, the true first-contact cost; ``block_s`` is best-of-3 over
    copies of the memo-warm state — the steady per-block cost of a live
    client that keeps its state resident. ``phases`` attributes the warm
    cost (VERDICT next-round #1b)."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import chain_utils
    import importlib

    state_transition = importlib.import_module(
        f"ethereum_consensus_tpu.models.{fork}.state_transition"
    ).state_transition

    state, ctx, signed = chain_utils.mainnet_block_bundle(fork, validators, atts)
    state_cls = type(state)
    cold_state = state_cls.deserialize(state_cls.serialize(state))
    t0 = time.perf_counter()
    state_transition(cold_state, signed, ctx)
    cold_s = time.perf_counter() - t0
    del cold_state
    _prime_warm_state(fork, state, ctx)
    pre = state.copy()
    state_transition(pre, signed, ctx)  # warm caches/compiles
    times = []
    for _ in range(3):
        s = state.copy()
        t0 = time.perf_counter()
        state_transition(s, signed, ctx)
        times.append(time.perf_counter() - t0)
    best = min(times)
    phases = _phase_breakdown(fork, state, ctx, signed)
    out = {
        "blocks_per_s": 1.0 / best,
        "block_s": best,
        "cold_block_s": cold_s,
        "attestations_per_block": len(signed.message.body.attestations),
        "preset": "mainnet",
        "fork": fork,
        "validators": validators,
        "phases": phases,
        # the bench-level assertion the ISSUE 5 acceptance names: no
        # named hot-scan span on the warm per-block path
        "hot_sweeps_per_block_absent": phases["hot_sweeps"][
            "per_block_absent"
        ],
    }

    # device-routed variant
    try:
        from ethereum_consensus_tpu import ops

        ops.install(
            sweeps_min_n=1 << 12,
            shuffle_min_n=1 << 12,
            bls_agg_min_n=1 << 10,
        )
        try:
            s = state.copy()
            state_transition(s, signed, ctx)  # warm compiles
            dev_times = []
            for _ in range(3):
                s = state.copy()
                t0 = time.perf_counter()
                state_transition(s, signed, ctx)
                dev_times.append(time.perf_counter() - t0)
            out["device_routed_block_s"] = min(dev_times)
        finally:
            ops.uninstall()
    except Exception as exc:  # noqa: BLE001 — host numbers stand alone
        out["device_routed_error"] = f"{type(exc).__name__}: {str(exc)[:120]}"
    return out


def bench_process_block_mainnet(validators: int = 1 << 20, atts: int = 64):
    """BASELINE config 5 shape on the root fork at FULL mainnet scale:
    1,048,576 validators -> 64 committees/slot, a block carrying 64
    aggregate attestations over two slots — the shape of a real mainnet
    block (MAX_ATTESTATIONS=128, phase0/block_processing.rs:704). All
    signature sets batched, full per-slot state HTR, honest cold/warm
    split. The bundle is disk-cached."""
    return _bench_mainnet_block("phase0", validators, atts)


def bench_process_block_deneb(validators: int = 1 << 20, atts: int = 64):
    """The LITERAL BASELINE config 5 at FULL mainnet scale: deneb full
    ``process_block`` on a mainnet-preset BeaconState — execution
    payload, 512-key sync aggregate, 64 aggregate attestations over a
    1,048,576-validator registry, blob-commitment checks, all signature
    sets batched, full per-slot state HTR, honest cold/warm split
    (deneb/block_processing.rs:350)."""
    out = _bench_mainnet_block("deneb", validators, atts)
    from ethereum_consensus_tpu.config import Context

    out["sync_committee_size"] = int(Context.for_mainnet().SYNC_COMMITTEE_SIZE)
    return out


def bench_process_block_electra(validators: int = 1 << 20):
    """Electra full mainnet-preset ``process_block`` at FULL mainnet
    scale — committee-spanning EIP-7549 attestations (each spans all 64
    committees of its slot -> 16,384 signers per attestation), 512-key
    sync aggregate, execution payload, EIP-7251 machinery. The reference
    cannot execute electra at all (executor.rs:155-172 has no electra
    arm). Electra blocks carry one committee-spanning attestation per
    eligible slot — two here — so no attestation-count knob exists."""
    return _bench_mainnet_block("electra", validators, atts=2)


def bench_pipeline_blocks(validators: int = 1 << 20, n_blocks: int = 32,
                          atts: int = 64):
    """Chain-pipeline replay throughput (pipeline/engine.py): an
    ``n_blocks``-block deneb chain at mainnet committee structure,
    replayed warm (state memos resident) sequentially via
    ``Executor.apply_block`` and then via ``Executor.stream`` — stage-A
    host application overlapped with stage-B windowed cross-block
    signature flushes. Reports both per-block numbers, the speedup, and
    the per-stage occupancy split.

    The pubkey story is intentionally the serving-sync shape: each
    validator attests once per epoch, so at full scale a 32-block chain
    touches ~every key once and the 64k-entry decompression cache
    thrashes by construction — the cold-key crypto (eight-wide bulk
    decompression + the RLC multi-pairing) is exactly the work the
    pipeline moves off the application thread. Replays beyond the first
    therefore re-measure the same honest cache pressure, not an
    artificially warmed registry. The chain bundle is disk-cached."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import chain_utils

    from ethereum_consensus_tpu.executor import Executor
    from ethereum_consensus_tpu.pipeline import FlushPolicy

    if _fast_test():
        validators = min(validators, 1 << 14)
        n_blocks = min(n_blocks, 8)
        atts = min(atts, 8)
    state, ctx, blocks = chain_utils.mainnet_chain_bundle(
        "deneb", validators, n_blocks, atts
    )

    def replay_sequential():
        ex = Executor(state.copy(), ctx)
        t0 = time.perf_counter()
        for b in blocks:
            ex.apply_block(b)
        return time.perf_counter() - t0, ex

    def replay_pipelined(window_size=8, max_in_flight=2):
        ex = Executor(state.copy(), ctx)
        policy = FlushPolicy(
            window_size=window_size, max_in_flight=max_in_flight
        )
        t0 = time.perf_counter()
        stats = ex.stream(blocks, policy=policy)
        return time.perf_counter() - t0, stats, ex

    _prime_warm_state("deneb", state, ctx)
    replay_sequential()  # warm imports/caches/memos once
    reps = 1 if _fast_test() else 2
    seq_s, seq_ex = min(
        (replay_sequential() for _ in range(reps)), key=lambda t: t[0]
    )
    pipe_s, stats, pipe_ex = min(
        (replay_pipelined() for _ in range(reps)), key=lambda t: t[0]
    )
    ok = (
        type(pipe_ex.state.data).hash_tree_root(pipe_ex.state.data)
        == type(seq_ex.state.data).hash_tree_root(seq_ex.state.data)
    )
    # sweep-span audit over one recorded warm replay: the named hot
    # scans may fire at epoch boundaries, never on the per-block path
    from ethereum_consensus_tpu.telemetry import phases as tel_phases
    from ethereum_consensus_tpu.telemetry import spans as tel_spans

    rec = tel_spans.RECORDER
    if rec.enabled:
        before_id = max((r.span_id for r in rec.records()), default=0)
        replay_sequential()
        sweep_records = [r for r in rec.records() if r.span_id > before_id]
    else:
        with tel_spans.recording(capacity=1 << 18):
            replay_sequential()
            sweep_records = rec.records()
    hot_sweeps = tel_phases.hot_sweep_report(sweep_records)
    # the cache-backed sweeps (active set / total balance) legitimately
    # recompute ONCE per epoch — lazily at the first touch after the
    # boundary, which lands outside process_epoch — so they get an
    # epochs-touched budget; the withdrawals sweeps are per-block by
    # construction and must be fully absent (the columnar path replaces
    # them, models/ops_vector.py)
    epochs_touched = len(
        {int(b.message.slot) // int(ctx.SLOTS_PER_EPOCH) for b in blocks}
    ) + 1
    hot_sweeps["per_block_budget"] = epochs_touched
    sweeps_ok = all(
        ("withdrawals" not in name) and count <= epochs_touched
        for name, count in hot_sweeps["per_block"].items()
    )
    hot_sweeps["per_block_within_budget"] = sweeps_ok
    # causal-trace evidence: one pipelined replay under recording —
    # every settled window must link into a connected tree (zero
    # orphans, zero silent drops) and the verify/settle histograms
    # must name their tail windows by trace_id
    _, trace_evidence = _trace_evidence(
        replay_pipelined,
        exemplar_hists=("pipeline.verify_s", "pipeline.settle_s"),
    )
    sn = stats.snapshot()
    cores = os.cpu_count() or 1
    return {
        "ok": bool(ok) and sn["rollbacks"] == 0 and sweeps_ok
        and trace_evidence["ok"],
        "hot_sweeps": hot_sweeps,
        "trace": trace_evidence,
        "fork": "deneb",
        "validators": validators,
        "blocks": n_blocks,
        "attestations_per_block": max(
            len(b.message.body.attestations) for b in blocks
        ),
        "cpu_cores": cores,
        "sequential_s": seq_s,
        "sequential_block_s": seq_s / n_blocks,
        "pipelined_s": pipe_s,
        "pipelined_block_s": pipe_s / n_blocks,
        "pipelined_blocks_per_s": n_blocks / pipe_s,
        "speedup": seq_s / pipe_s,
        "window_size": 8,
        "flush_sizes": sn["flush_sizes"],
        "stage_a_occupancy": sn["stage_a_occupancy"],
        "stage_b_occupancy": sn["stage_b_occupancy"],
        "checkpoints": sn["checkpoints"],
        "note": (
            "compare pipelined_block_s against this config's own "
            "sequential_block_s (same chain, same warm state) and the "
            "process_block_deneb config's single-block block_s"
            + (
                "; SINGLE-CORE box: the two stages time-slice one core, "
                "so wall-clock speedup is capped at ~1x here — the "
                "occupancy split shows the concurrency that a second "
                "core or the device pairing route converts into "
                "throughput"
                if cores < 2
                else ""
            )
        ),
    }


def _mesh_child_env(n_devices: int, extra: "dict | None" = None) -> dict:
    """A child environment seeing an ``n_devices`` virtual CPU platform
    (parallel/virtual_mesh.py: ``JAX_PLATFORMS=cpu`` — these children
    never need the chip this process holds), with any pre-existing
    device-count flag REPLACED (duplicate flags are undefined behavior,
    so exactly one must survive)."""
    from ethereum_consensus_tpu.parallel.virtual_mesh import cpu_mesh_env

    env = cpu_mesh_env(n_devices, repo_root=REPO)
    flags = [
        flag
        for flag in env.get("XLA_FLAGS", "").split()
        if not flag.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    if extra:
        env.update(extra)
    return env


def _run_mesh_child(code: str, n_devices: int, timeout_s: int,
                    extra_env: "dict | None" = None) -> dict:
    """Run one virtual-mesh bench child; it must print a single line
    ``MESH_CHILD_JSON:{...}``. Errors come home as ``{"error": ...}`` —
    a dead child never kills the config."""
    env = _mesh_child_env(n_devices, extra_env)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env=env,
            capture_output=True,
            text=True,
            timeout=timeout_s,
            cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"mesh child timeout (> {timeout_s}s)"}
    if proc.returncode != 0:
        tail = "\n".join((proc.stderr or "").splitlines()[-12:])
        return {"error": f"mesh child rc={proc.returncode}: {tail[-600:]}"}
    for line in (proc.stdout or "").splitlines():
        if line.startswith("MESH_CHILD_JSON:"):
            out = json.loads(line[len("MESH_CHILD_JSON:"):])
            # virtual CPU devices, even on a chip host: not a chip number
            out["platform"] = "cpu"
            return out
    return {"error": f"no payload in child stdout: {proc.stdout[-300:]!r}"}


_MULTICHIP_PIPELINE_CHILD = r"""
import json, os, sys, time
REPO = os.getcwd()
sys.path.insert(0, os.path.join(REPO, "tests"))
import chain_utils

import jax
from ethereum_consensus_tpu import _device_flags
from ethereum_consensus_tpu.crypto import bls
from ethereum_consensus_tpu.executor import Executor
from ethereum_consensus_tpu.models.signature_batch import (
    SignatureBatch, defer_flushes,
)
from ethereum_consensus_tpu.models.transition import Validation
from ethereum_consensus_tpu.pipeline import FlushPolicy
from ethereum_consensus_tpu.telemetry import device as tel_device
from ethereum_consensus_tpu.telemetry import metrics as tel_metrics

V = int(os.environ["EC_MESH_BENCH_V"])
B = int(os.environ["EC_MESH_BENCH_B"])
A = int(os.environ["EC_MESH_BENCH_A"])
n_dev = len(jax.devices())
state, ctx, blocks = chain_utils.mainnet_chain_bundle("deneb", V, B, A)
tel_device.start()
metrics_base = tel_metrics.snapshot()

def replay():
    ex = Executor(state.copy(), ctx)
    policy = FlushPolicy(
        window_size=8, max_in_flight=max(2, n_dev), verify_lanes=n_dev
    )
    t0 = time.perf_counter()
    stats = ex.stream(blocks, policy=policy)
    return time.perf_counter() - t0, stats, ex

replay()  # warm imports/caches/memos once
wall, stats, ex = min((replay() for _ in range(2)), key=lambda t: t[0])
root = type(ex.state.data).hash_tree_root(ex.state.data).hex()
sn = stats.snapshot()

# mesh-sharded RLC pairing: one window's sets through the PRODUCTION
# route (pairing gate dropped so the mesh owns the batch), identical
# verdicts to the native host engine — including a tampered set's
# rejection, whose per-set blame fallback runs host-side on both routes
sink = SignatureBatch()
ex2 = Executor(state.copy(), ctx)
with defer_flushes(sink):
    for b in blocks[:4]:
        ex2.apply_block_with_validation(b, Validation.ENABLED)
sets = sink.sets
host_verdicts = bls.verify_signature_sets(sets)
host_route = bls.last_batch_route()
_device_flags.PAIRING_MIN_SETS = 1
mesh_verdicts = bls.verify_signature_sets(sets)
mesh_route = bls.last_batch_route()
# tamper: wrong message on one set -> exactly that set rejects
bad = list(sets)
bad[1] = bls.SignatureSet(
    bad[1].public_keys, b"\x00" * 32, bad[1].signature
)
mesh_bad = bls.verify_signature_sets(bad)
_device_flags.PAIRING_MIN_SETS = None
bad_expect = [True] * len(bad)
bad_expect[1] = False

d = tel_metrics.delta(metrics_base)
payload = {
    "devices": n_dev,
    "verify_lanes": n_dev,
    "pipelined_s": wall,
    "blocks_per_s": len(blocks) / wall,
    "root": root,
    "stage_a_occupancy": sn["stage_a_occupancy"],
    "stage_b_occupancy": sn["stage_b_occupancy"],
    "rollbacks": sn["rollbacks"],
    "pairing_identity": {
        "sets": len(sets),
        "host_route": host_route,
        "mesh_route": mesh_route,
        "verdicts_identical": mesh_verdicts == host_verdicts,
        "tamper_blamed_exactly": mesh_bad == bad_expect,
    },
    "mesh": {
        "engages": d.get("mesh.engage", 0),
        "declines": {
            k[len("mesh.decline."):]: v for k, v in d.items()
            if k.startswith("mesh.decline.") and v
        },
        "routes": tel_device.OBSERVATORY.route_tallies(),
        "pairing_journal": [
            r for r in tel_device.OBSERVATORY.routes()
            if r["kind"] == "mesh.pairing"
        ][-2:],
    },
}
print("MESH_CHILD_JSON:" + json.dumps(payload))
"""


def bench_multichip_pipeline(validators: int = 1 << 17, n_blocks: int = 32,
                             atts: int = 16):
    """THE scale-out config (ISSUE 12): the same warm deneb chain
    replayed through the pipeline at virtual device counts {1, 2, 4, 8}
    (``--xla_force_host_platform_device_count`` children — a multi-core
    box is a mesh, no chip required), each child running ``ECT_MESH=N``
    with N verifier lanes (``FlushPolicy.verify_lanes``). Asserted per
    child: final-state bit-identity to the host sequential oracle, and
    one flush window's sets proven through the mesh-sharded RLC pairing
    (parallel/pairing.py) with verdicts — including a tampered set's
    exact blame — identical to the native host engine. Work division
    comes from the mesh routing journal (sets_per_device at each count).
    Wall-clock scaling is asserted only where the hardware can deliver
    it: with ``cpu_cores >= 4``, blocks/s at 4 devices must reach 1.5x
    the 1-device run; a single-core box records the occupancy split
    instead (the concurrency is measured, the cores are not there)."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import chain_utils

    from ethereum_consensus_tpu.executor import Executor

    if _fast_test():
        validators = min(validators, 1 << 14)
        n_blocks = min(n_blocks, 8)
        atts = min(atts, 8)
    # parent-side: ensure the bundle is on disk (children must hit the
    # cache) and compute the sequential host oracle root
    state, ctx, blocks = chain_utils.mainnet_chain_bundle(
        "deneb", validators, n_blocks, atts
    )
    ex = Executor(state.copy(), ctx)
    for b in blocks:
        ex.apply_block(b)
    oracle_root = type(ex.state.data).hash_tree_root(ex.state.data).hex()
    del ex

    cores = os.cpu_count() or 1
    device_counts = (1, 2, 4, 8)
    runs = {}
    for n_dev in device_counts:
        _note(f"multichip_pipeline: {n_dev}-device child starting")
        runs[str(n_dev)] = _run_mesh_child(
            _MULTICHIP_PIPELINE_CHILD,
            n_dev,
            timeout_s=600,
            extra_env={
                "ECT_MESH": str(n_dev),
                "EC_MESH_BENCH_V": str(validators),
                "EC_MESH_BENCH_B": str(n_blocks),
                "EC_MESH_BENCH_A": str(atts),
            },
        )

    ok = True
    identity = {}
    for n_dev, run in runs.items():
        if "error" in run:
            ok = False
            identity[n_dev] = run["error"]
            continue
        bit_identical = run["root"] == oracle_root
        pairing = run["pairing_identity"]
        work_divided = all(
            j["inputs"].get("sets_per_device", 0) * int(n_dev)
            >= j["inputs"].get("sets", 0) > 0
            and j["inputs"].get("devices") == int(n_dev)
            for j in run["mesh"]["pairing_journal"]
        ) and bool(run["mesh"]["pairing_journal"])
        identity[n_dev] = {
            "bit_identical": bit_identical,
            "pairing_verdicts_identical": pairing["verdicts_identical"],
            "tamper_blamed_exactly": pairing["tamper_blamed_exactly"],
            "mesh_route_taken": pairing["mesh_route"] == "device",
            "work_divided": work_divided,
            "rollbacks": run["rollbacks"],
        }
        ok = ok and all(
            v is True or v == 0 for v in identity[n_dev].values()
        )

    scaling = {}
    if all("error" not in r for r in runs.values()):
        base = runs["1"]["blocks_per_s"]
        scaling = {
            n_dev: round(r["blocks_per_s"] / base, 3)
            for n_dev, r in runs.items()
        }
    scaling_asserted = cores >= 4
    if scaling_asserted:
        ok = ok and bool(scaling) and scaling.get("4", 0.0) >= 1.5
    return {
        "ok": ok,
        # every run below is a child on VIRTUAL CPU devices, on a chip
        # host too: none of its numbers is a chip number
        "platform": "cpu",
        "fork": "deneb",
        "validators": validators,
        "blocks": n_blocks,
        "cpu_cores": cores,
        "oracle_root": oracle_root,
        "device_counts": list(device_counts),
        "runs": runs,
        "identity": identity,
        "scaling_vs_1dev": scaling,
        "scaling_asserted": scaling_asserted,
        "note": (
            "blocks/s scaling asserted (cpu_cores >= 4): 4-device run "
            "must reach 1.5x the 1-device run"
            if scaling_asserted
            else "single/dual-core box: scaling recorded, not asserted — "
            "the occupancy split shows the concurrency N cores would "
            "convert into throughput"
        ),
    }


_EPOCH_MESH_CHILD = r"""
import json, gc, hashlib, os, sys, time
REPO = os.getcwd()
sys.path.insert(0, os.path.join(REPO, "tests"))
import chain_utils

import jax
from ethereum_consensus_tpu.telemetry import device as tel_device
from ethereum_consensus_tpu.telemetry import metrics as tel_metrics

fork = os.environ["EC_MESH_BENCH_FORK"]
V = int(os.environ["EC_MESH_BENCH_V"])
if fork == "deneb":
    from ethereum_consensus_tpu.models.deneb import containers as mc
    from ethereum_consensus_tpu.models.deneb.slot_processing import (
        process_slots,
    )
else:
    from ethereum_consensus_tpu.models.electra import containers as mc
    from ethereum_consensus_tpu.models.electra.slot_processing import (
        process_slots,
    )
ctx = chain_utils.Context.for_mainnet()
ns = mc.build(ctx.preset)
slots = int(ctx.SLOTS_PER_EPOCH)


def missing():
    raise RuntimeError("epoch state cache missing (parent must build it)")


loaded = chain_utils._disk_cached(
    f"epochstate-{fork}-{chain_utils._FASTREG_VERSION}-mainnet-{V}",
    ns.BeaconState.serialize,
    ns.BeaconState.deserialize,
    missing,
)
tel_device.start()
metrics_base = tel_metrics.snapshot()
scratch = loaded.copy()
process_slots(scratch, 2 * slots, ctx)  # warm: compiles + caches + memos
del scratch

times = []
final = None
for _ in range(2):
    state = loaded.copy()
    gc.collect()
    gc.disable()
    try:
        t0 = time.perf_counter()
        process_slots(state, 2 * slots, ctx)
        times.append(time.perf_counter() - t0)
    finally:
        gc.enable()
    final = state

d = tel_metrics.delta(metrics_base)
serialized = ns.BeaconState.serialize(final)
payload = {
    "devices": len(jax.devices()),
    "fork": fork,
    "validators": V,
    "epoch_s": min(times),
    "root": ns.BeaconState.hash_tree_root(final).hex(),
    "bytes_sha256": hashlib.sha256(serialized).hexdigest(),
    "mesh": {
        "engages": d.get("mesh.engage", 0),
        "declines": {
            k[len("mesh.decline."):]: v for k, v in d.items()
            if k.startswith("mesh.decline.") and v
        },
        "epoch_journal": [
            r for r in tel_device.OBSERVATORY.routes()
            if r["kind"] == "mesh.epoch"
        ][-2:],
    },
    "epoch_vector_epochs": d.get("epoch_vector.epochs", 0),
}
print("MESH_CHILD_JSON:" + json.dumps(payload))
"""


def bench_epoch_mesh(validators: "int | None" = None):
    """The epoch hot path mesh-sharded at the 2^21 flagship shape
    (ISSUE 12 acceptance): the SAME prepared pre-boundary states the
    epoch_deneb/epoch_electra configs cache, run through
    ``process_slots`` in virtual-mesh children at device counts
    {1, 2, 4, 8} with ``ECT_MESH=N`` — the columnar pass routes its
    inactivity + rewards sweeps through the sharded kernels with psum
    reductions (parallel/epoch.py). Asserted per child and fork:
    bit-identity (root AND serialized bytes digest) against the host
    oracle computed in-process with the mesh off, at least one engaged
    mesh epoch, and ZERO declines of any kind (no silent ones exist by
    construction — every decline is a counter + journal entry — and at
    this shape none may fire at all). Wall-clock scaling recorded at
    every count, asserted nowhere a core-starved box cannot deliver
    it."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import chain_utils

    validators = validators or _epoch_validators()
    if _fast_test():
        validators = min(validators, 1 << 14)
    cores = os.cpu_count() or 1
    device_counts = (1, 2, 4, 8)
    out = {
        # every run below is a child on VIRTUAL CPU devices, on a chip
        # host too: none of its numbers is a chip number
        "platform": "cpu",
        "validators": validators,
        "cpu_cores": cores,
        "device_counts": list(device_counts),
        "forks": {},
    }
    ok = True
    for fork in ("deneb", "electra"):
        import importlib

        mc = importlib.import_module(
            f"ethereum_consensus_tpu.models.{fork}.containers"
        )
        sp = importlib.import_module(
            f"ethereum_consensus_tpu.models.{fork}.slot_processing"
        )
        ctx = chain_utils.Context.for_mainnet()
        ns = mc.build(ctx.preset)
        slots = int(ctx.SLOTS_PER_EPOCH)
        # the epoch configs' cache when warm; else the SAME shared
        # builder they use, at exactly this size (mesh off here — this
        # process also computes the host oracle)
        loaded = _epoch_mesh_state(chain_utils, ns, ctx, fork, validators)
        if loaded is None:
            out["forks"][fork] = {"error": "state build failed"}
            ok = False
            continue
        import gc
        import hashlib as _hashlib

        oracle = loaded.copy()
        sp.process_slots(oracle, 2 * slots, ctx)
        oracle_root = ns.BeaconState.hash_tree_root(oracle).hex()
        oracle_digest = _hashlib.sha256(
            ns.BeaconState.serialize(oracle)
        ).hexdigest()
        del oracle
        gc.collect()

        runs = {}
        for n_dev in device_counts:
            _note(f"epoch_mesh: {fork} {n_dev}-device child starting")
            runs[str(n_dev)] = _run_mesh_child(
                _EPOCH_MESH_CHILD,
                n_dev,
                timeout_s=900,
                extra_env={
                    "ECT_MESH": str(n_dev),
                    "EC_MESH_BENCH_FORK": fork,
                    "EC_MESH_BENCH_V": str(validators),
                    # engage at whatever shape this run uses
                    "ECT_MESH_EPOCH_MIN_N": str(
                        min(validators, 1 << 17)
                    ),
                    # route only the truly-large cold rebuilds through
                    # the sharded merkleizer: on the CPU backend the jnp
                    # hasher loses to native C++, so the warm-up pays
                    # ONE engage for the evidence instead of many
                    "ECT_MESH_MERKLE_MIN_CHUNKS": str(1 << 18),
                },
            )
        fork_ok = True
        identity = {}
        for n_dev, run in runs.items():
            if "error" in run:
                fork_ok = False
                identity[n_dev] = run["error"]
                continue
            checks = {
                "bit_identical": (
                    run["root"] == oracle_root
                    and run["bytes_sha256"] == oracle_digest
                ),
                # 3 boundaries touched per child (warm + 2 timed runs),
                # each must engage; declines must be EMPTY — zero
                # silent declines is structural, zero loud ones is the
                # flagship-shape assertion
                "every_epoch_engaged": run["mesh"]["engages"]
                >= run["epoch_vector_epochs"] > 0,
                "zero_declines": not run["mesh"]["declines"],
                "work_divided": bool(run["mesh"]["epoch_journal"]) and all(
                    j["inputs"].get("rows_per_device", 0) * int(n_dev)
                    >= j["inputs"].get("validators", 0) > 0
                    for j in run["mesh"]["epoch_journal"]
                ),
            }
            identity[n_dev] = checks
            fork_ok = fork_ok and all(checks.values())
        scaling = {}
        if all("error" not in r for r in runs.values()):
            base = runs["1"]["epoch_s"]
            scaling = {
                n_dev: round(base / r["epoch_s"], 3)
                for n_dev, r in runs.items()
            }
        out["forks"][fork] = {
            "oracle_root": oracle_root,
            "runs": runs,
            "identity": identity,
            "speedup_vs_1dev": scaling,
            "ok": fork_ok,
        }
        ok = ok and fork_ok
    scaling_asserted = cores >= 4
    if scaling_asserted:
        for fork_out in out["forks"].values():
            ok = ok and fork_out.get("speedup_vs_1dev", {}).get(
                "4", 0.0
            ) >= 1.5
    out["scaling_asserted"] = scaling_asserted
    out["ok"] = ok
    return out


def _epoch_mesh_state(chain_utils, ns, ctx, fork: str, validators: int):
    """The fork's prepared pre-boundary state at EXACTLY ``validators``
    — the epoch configs' disk cache when warm, else built through the
    same shared builder those configs use (`_build_epoch_state`), so
    whoever builds first caches identical bytes for everyone."""
    try:
        return chain_utils._disk_cached(
            f"epochstate-{fork}-{chain_utils._FASTREG_VERSION}-mainnet-"
            f"{validators}",
            ns.BeaconState.serialize,
            ns.BeaconState.deserialize,
            lambda: _build_epoch_state(chain_utils, ns, ctx, fork,
                                       validators),
        )
    except Exception:  # noqa: BLE001
        return None


def bench_adversarial_replay(validators: int = 1 << 17, n_blocks: int = 32,
                             atts: int = 16, fraction: float = 0.10):
    """Chain-pipeline replay under a 10% invalid-block storm
    (scenarios/harness.py): the same warm deneb chain the pipeline bench
    drives, with ``fraction`` of its blocks carrying a corrupted
    proposer signature (a valid G2 point over the wrong message — fails
    only at the pairing, the rollback path). Every failure rolls the
    pipeline back to the committed position and the replay resumes with
    the honest block; reported are adversarial blocks/s, the overhead
    vs the honest pipelined replay of the same chain, and the
    per-failure recovery latency (error caught → fresh pipeline ready).
    ``ok`` requires the storm's final state to be BIT-IDENTICAL to the
    honest replay's and every corruption blamed exactly."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import random as _random

    import chain_utils

    from ethereum_consensus_tpu.executor import Executor
    from ethereum_consensus_tpu.pipeline import FlushPolicy
    from ethereum_consensus_tpu.scenarios import (
        bad_proposer_signature,
        plan_storm,
        run_storm,
    )

    if _fast_test():
        validators = min(validators, 1 << 14)
        n_blocks = min(n_blocks, 8)
        atts = min(atts, 8)
    state, ctx, blocks = chain_utils.mainnet_chain_bundle(
        "deneb", validators, n_blocks, atts
    )
    policy = FlushPolicy(window_size=8, max_in_flight=2)

    _prime_warm_state("deneb", state, ctx)
    # honest pipelined replay: the no-storm baseline AND the final-root
    # oracle (the storm substitutes honest twins after each failure, so
    # both runs commit the identical chain)
    ex = Executor(state.copy(), ctx)
    t0 = time.perf_counter()
    ex.stream(blocks, policy=policy)
    honest_s = time.perf_counter() - t0
    honest_root = type(ex.state.data).hash_tree_root(ex.state.data)

    plan = plan_storm(
        n_blocks, fraction, _random.Random(0x5702),
        [bad_proposer_signature],
    )
    report, storm_ex = run_storm(
        state, ctx, blocks, plan, policy=policy,
        check_states=False, check_columns=False,
    )
    storm_root = type(storm_ex.state.data).hash_tree_root(storm_ex.state.data)
    latencies = report.recovery_latencies
    rollbacks = sum(s["rollbacks"] for s in report.stats_snapshots)
    return {
        "ok": bool(storm_root == honest_root)
        and len(report.failures) == len(plan),
        "fork": "deneb",
        "validators": validators,
        "blocks": n_blocks,
        "invalid_fraction": fraction,
        "invalid_blocks": len(plan),
        "rollbacks": rollbacks,
        "honest_pipelined_s": honest_s,
        "honest_blocks_per_s": n_blocks / honest_s,
        "adversarial_s": report.wall_s,
        "adversarial_blocks_per_s": n_blocks / report.wall_s,
        "storm_slowdown": report.wall_s / honest_s,
        "recovery_latency_mean_s": sum(latencies) / len(latencies),
        "recovery_latency_max_s": max(latencies),
        "window_size": 8,
        "note": (
            "recovery latency = error caught -> fresh pipeline ready "
            "over the restored committed position (the rollback itself "
            "ran inside the raising submit); storm_slowdown folds in "
            "the re-application of speculative work discarded at each "
            "rollback"
        ),
    }


def bench_serving_queries(validators: int = 1 << 17, n_blocks: int = 16,
                          atts: int = 8):
    """Beacon-API read data plane throughput (serving/, docs/SERVING.md):
    queries/s against a live ``HeadStore`` + ``BeaconDataPlane`` mounted
    on the introspection server, measured WHILE a chain-pipeline replay
    loops in the background — every window commit rotates the served
    head, so the numbers include real snapshot churn, not a frozen
    cache.

    Three read shapes at the 2^17 registry: single-validator
    (``/validators/{id}``), a 1k-id batch (``validator_balances?id=`` —
    one columnar gather per request), and a full-committee-slot read
    (``/committees?slot=`` — 32 mainnet committees, the shuffle memoized
    per snapshot). The acceptance comparison times the resolution core
    in-process: the columnar batch resolve (one ``gather_rows`` + one
    vectorized status mask) vs the per-validator scalar walk
    (``serving/oracle.py``) over the SAME ids on the SAME snapshot —
    ``ok`` requires ≥10x, bit-identical documents both ways, and exactly
    one ``serving.gathers`` increment per batched request."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import threading
    from concurrent.futures import ThreadPoolExecutor

    import chain_utils

    from ethereum_consensus_tpu.executor import Executor
    from ethereum_consensus_tpu.pipeline import FlushPolicy
    from ethereum_consensus_tpu.serving import BeaconDataPlane, HeadStore
    from ethereum_consensus_tpu.telemetry.server import IntrospectionServer

    if _fast_test():
        validators = min(validators, 1 << 14)
        n_blocks = min(n_blocks, 8)
        atts = min(atts, 8)
    state, ctx, blocks = chain_utils.mainnet_chain_bundle(
        "deneb", validators, n_blocks, atts
    )
    _prime_warm_state("deneb", state, ctx)

    store = HeadStore().attach()
    server = IntrospectionServer(port=0).start(start_flight=False)
    server.mount(BeaconDataPlane(store))
    policy = FlushPolicy(window_size=8, max_in_flight=2)
    stop = threading.Lock()  # held = keep replaying
    stop.acquire()

    def replay_forever():
        # concurrent pipeline replay: publishes a fresh snapshot per
        # committed window until the measurement releases the lock
        while stop.locked():
            ex = Executor(state.copy(), ctx)
            ex.stream(blocks, policy=policy)

    pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="replayer")
    replay_future = pool.submit(replay_forever)
    try:
        return _serving_queries_measure(
            store, server, stop, replay_future, pool, state, ctx, blocks,
            validators, n_blocks,
        )
    finally:
        if stop.locked():
            stop.release()
        pool.shutdown(wait=True)
        store.detach()
        server.stop()


def _serving_queries_measure(store, server, stop, replay_future, pool,
                             state, ctx, blocks, validators, n_blocks):
    import json as _json
    import urllib.request

    from ethereum_consensus_tpu.serving import oracle, views
    from ethereum_consensus_tpu.telemetry import metrics as tel_metrics

    t_wait = time.perf_counter()
    while store.head is None and time.perf_counter() - t_wait < 120:
        time.sleep(0.05)
    assert store.head is not None, "pipeline never published a snapshot"

    import random as _random

    rng = _random.Random(0x5E21)
    ids_1k = sorted(rng.sample(range(validators), min(1000, validators)))
    ids_param = ",".join(str(i) for i in ids_1k)
    head_slot = store.head.slot

    def qps(path: str, seconds: float = 2.0) -> "tuple[float, int]":
        url = server.url(path)
        count = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with urllib.request.urlopen(url, timeout=30) as response:
                response.read()
            count += 1
        return count / (time.perf_counter() - t0), count

    single_qps, _ = qps(f"/eth/v1/beacon/states/head/validators/{ids_1k[0]}")
    batch_qps, _ = qps(
        f"/eth/v1/beacon/states/head/validator_balances?id={ids_param}"
    )
    committee_qps, _ = qps(
        f"/eth/v1/beacon/states/head/committees?slot={head_slot}"
    )

    # gather discipline: one batched request == exactly one columnar
    # gather (measured on a quiesced counter window)
    before_g = tel_metrics.counter("serving.gathers").value()
    before_r = tel_metrics.counter("serving.requests").value()
    with urllib.request.urlopen(
        server.url(
            f"/eth/v1/beacon/states/head/validator_balances?id={ids_param}"
        ),
        timeout=30,
    ) as response:
        _json.loads(response.read())  # parse like a real client would
    gathers_per_batch = (
        tel_metrics.counter("serving.gathers").value() - before_g
    )
    requests_seen = tel_metrics.counter("serving.requests").value() - before_r

    # the ≥10x core: columnar batch resolve vs the per-validator scalar
    # walk, same ids, same (now-quiesced) snapshot
    stop.release()  # let the replayer drain so the snapshot stays put
    replay_future.result(timeout=600)
    pool.shutdown(wait=True)
    snap = store.head
    bundle = views.snapshot_bundle(snap)
    assert bundle is not None, "columnar bundle unavailable at bench scale"
    reps = 3

    def best(fn):
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return min(times)

    columnar_s = best(
        lambda: views.resolve_validators(bundle, ids_1k)
    )
    scalar_s = best(
        lambda: [
            (
                index,
                int(snap.raw.balances[index]),
                oracle.validator_status(
                    snap.raw.validators[index],
                    int(snap.raw.balances[index]),
                    bundle["epoch"],
                ),
            )
            for index in ids_1k
        ]
    )
    speedup = scalar_s / columnar_s if columnar_s else float("inf")
    # bit-identity of the documents both engines serve for the batch
    idx, balances, codes = views.resolve_validators(bundle, ids_1k)
    columnar_rows = [
        {"index": str(i), "balance": str(int(b))}
        for i, b in zip(idx.tolist(), balances.tolist())
    ]
    scalar_rows = oracle.balances_data(snap.raw, ids_1k)
    identical = _json.dumps(columnar_rows, sort_keys=True) == _json.dumps(
        scalar_rows, sort_keys=True
    )
    statuses_identical = [
        views.STATUS_NAMES[c] for c in codes.tolist()
    ] == [
        oracle.validator_status(
            snap.raw.validators[i], int(snap.raw.balances[i]), bundle["epoch"]
        )
        for i in ids_1k
    ]
    snapshots_published = tel_metrics.counter(
        "serving.snapshots.published"
    ).value()
    return {
        "ok": bool(
            speedup >= 10.0
            and identical
            and statuses_identical
            and gathers_per_batch == 1
            and requests_seen == 1
        ),
        "fork": "deneb",
        "validators": validators,
        "blocks": n_blocks,
        "single_validator_qps": single_qps,
        "batch_1k_qps": batch_qps,
        "committee_slot_qps": committee_qps,
        "batch_size": len(ids_1k),
        "batch_rows_per_s": batch_qps * len(ids_1k),
        "gathers_per_batch_request": gathers_per_batch,
        "columnar_batch_resolve_s": columnar_s,
        "scalar_walk_resolve_s": scalar_s,
        "columnar_vs_scalar_speedup": speedup,
        "bit_identical": bool(identical and statuses_identical),
        "snapshots_published": snapshots_published,
        "served_head_slot": snap.slot,
        "note": (
            "qps measured over HTTP against state_id=head WHILE a "
            "pipelined replay loops (head rotates per committed "
            "window); the >=10x acceptance compares the in-process "
            "resolution core — one columnar gather + vectorized status "
            "vs the per-validator scalar walk — on the same ids and "
            "snapshot, excluding identical JSON/HTTP assembly costs"
        ),
    }


def bench_pool_ingest(validators: int = 1 << 17, n_blocks: int = 16,
                      atts: int = 8, groups: int = 8,
                      aggregators: int = 64, window: int = 512):
    """Operation-pool admission throughput (pool/, docs/POOL.md):
    admissions/s through the windowed RLC engine vs the per-message
    scalar twin at the 2^17 registry, UNDER a concurrent pipeline
    replay looping in the background (both engines share the single
    FIFO bls verifier with the pipeline's stage-B flushes — the real
    contention a live node sees).

    Traffic is gossip-shaped: ``groups`` distinct (slot, committee,
    data_root) claims × ``aggregators`` overlapping ~60%-participation
    aggregates each (the Wonderboom many-aggregators-per-committee
    shape), every message a REAL signed aggregate over the bundle's
    realized committee keys. The RLC engine admits them with deferred
    signatures: batched G2 membership (one blinded MSM per window),
    per-group claim fusion (multiplicity-count G1 MSM + signature sum),
    one ``verify_signature_sets_async`` RLC multi-pairing per window.
    The scalar twin pays one key-parse + pairing pair per message.

    ``ok`` gates on the acceptance: >=10x admissions/s, EXACTLY one RLC
    flush per admission window (metrics-counted), every message
    admitted by both engines, and bit-identity of the resulting pool —
    served views AND the vectorized-vs-brute-force aggregate selection."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import json as _json
    import random as _random
    import threading
    from concurrent.futures import ThreadPoolExecutor

    import chain_utils

    from ethereum_consensus_tpu.crypto import bls
    from ethereum_consensus_tpu.executor import Executor
    from ethereum_consensus_tpu.models.phase0 import helpers as ph
    from ethereum_consensus_tpu.pipeline import FlushPolicy
    from ethereum_consensus_tpu.pool import (
        AdmissionEngine,
        OperationPool,
        select_aggregates,
    )
    from ethereum_consensus_tpu.serving import HeadStore
    from ethereum_consensus_tpu.telemetry import metrics as tel_metrics

    if _fast_test():
        validators = min(validators, 1 << 14)
        n_blocks, atts, groups, aggregators = 8, 4, 4, 4
    state, ctx, blocks = chain_utils.mainnet_chain_bundle(
        "deneb", validators, n_blocks, atts
    )
    groups = min(groups, n_blocks - 1)

    # pinned head: the post-replay state published once — admission
    # validates against a stable snapshot while the pipeline replay
    # below churns purely as contention (its commits are not attached)
    head_ex = Executor(state.copy(), ctx)
    head_ex.stream(blocks, policy=FlushPolicy(window_size=8, max_in_flight=2))
    store = HeadStore()
    snap = store.publish(head_ex.state, ctx)
    head = head_ex.state.data

    # gossip-shaped traffic over realized committees (the bundle's
    # attested (slot, committee 0) pairs carry real keys)
    rng = _random.Random(0x9001)
    traffic = []
    head_slot = int(head.slot)
    for k in range(groups):
        slot = head_slot - k
        base = chain_utils.make_attestation(head, slot, 0, ctx)
        committee = ph.get_beacon_committee(head, slot, 0, ctx)
        data = base.data
        from ethereum_consensus_tpu.domains import DomainType
        from ethereum_consensus_tpu.signing import compute_signing_root

        domain = ph.get_domain(
            head, DomainType.BEACON_ATTESTER, int(data.target.epoch), ctx
        )
        root = compute_signing_root(type(data), data, domain)
        for _ in range(aggregators):
            bits = [rng.random() < 0.6 for _ in range(len(committee))]
            if not any(bits):
                bits[0] = True
            sigs = [
                chain_utils.secret_key(committee[i]).sign(root)
                for i, b in enumerate(bits)
                if b
            ]
            agg = base.copy()
            agg.aggregation_bits = bits
            agg.signature = bls.aggregate(sigs).to_bytes()
            traffic.append(agg)
    messages = len(traffic)

    # prime the shared snapshot memos (committee tables, domains) so
    # neither engine pays the one-time shuffle build inside its timing
    prime = AdmissionEngine(OperationPool(), store, ctx, rlc=False)
    for k in range(groups):
        probe = chain_utils.make_attestation(head, head_slot - k, 0, ctx,
                                             participation=0.1)
        prime.admit_attestation(probe)

    stop = threading.Lock()
    stop.acquire()

    def replay_forever():
        # window 4: the replay contends continuously (stage-A python on
        # the GIL, stage-B flushes on the shared FIFO verifier) without
        # parking the verifier in one multi-hundred-ms flush that any
        # pool window would just sit behind — finer-grained contention,
        # same sustained load
        while stop.locked():
            ex = Executor(state.copy(), ctx)
            ex.stream(blocks, policy=FlushPolicy(window_size=4,
                                                 max_in_flight=2))

    pool_exec = ThreadPoolExecutor(max_workers=1,
                                   thread_name_prefix="pool-replayer")
    replay_future = pool_exec.submit(replay_forever)
    time.sleep(2.0)  # let the replay reach steady state (its first
    # loop fronts a 2^17 state copy — GIL churn, not yet replay load)
    def run_rlc():
        pool = OperationPool()
        engine = AdmissionEngine(pool, store, ctx, window_size=window,
                                 rlc=True)
        flushes_before = tel_metrics.counter("pool.flushes").value()
        fused_before = tel_metrics.counter("pool.fused_groups").value()
        batch = [att.copy() for att in traffic]
        t0 = time.perf_counter()
        tickets = engine.admit_attestation_batch(batch)
        admit_s = time.perf_counter() - t0
        engine.settle()
        return {
            "pool": pool, "engine": engine, "tickets": tickets,
            "admit_s": admit_s,
            "total_s": time.perf_counter() - t0,
            "flushes": tel_metrics.counter("pool.flushes").value()
            - flushes_before,
            "fused": tel_metrics.counter("pool.fused_groups").value()
            - fused_before,
        }

    def run_scalar():
        pool = OperationPool()
        engine = AdmissionEngine(pool, store, ctx, window_size=window,
                                 rlc=False)
        batch = [att.copy() for att in traffic]
        t0 = time.perf_counter()
        tickets = [engine.admit_attestation(att) for att in batch]
        engine.settle()
        return {
            "pool": pool, "engine": engine, "tickets": tickets,
            "total_s": time.perf_counter() - t0,
        }

    try:
        # interleaved best-of-3 per engine, fresh pools each rep: the
        # replay's phase (state-copy GIL churn vs pairing stretches) is
        # the dominant noise source — interleaving samples both engines
        # across the same phases; RLC first, so any shared warming
        # favors the scalar baseline
        rlc_runs, scalar_runs = [], []
        for _ in range(3):
            rlc_runs.append(run_rlc())
            scalar_runs.append(run_scalar())
        rlc_best = min(rlc_runs, key=lambda r: r["total_s"])
        scalar_best = min(scalar_runs, key=lambda r: r["total_s"])
    finally:
        stop.release()
        replay_future.result(timeout=600)
        pool_exec.shutdown(wait=True)

    # causal-trace evidence: one more RLC ingest under recording (the
    # contending replay is gone — this measures linkage, not speed):
    # every dispatched window must settle into a connected
    # admission→settle tree, and pool.flush_verify_s must name its
    # tail windows by trace_id
    _, trace_evidence = _trace_evidence(
        run_rlc, exemplar_hists=("pool.flush_verify_s",)
    )

    rlc_pool, rlc_engine = rlc_best["pool"], rlc_best["engine"]
    rlc_tickets, rlc_s = rlc_best["tickets"], rlc_best["total_s"]
    scalar_pool = scalar_best["pool"]
    scalar_tickets, scalar_s = scalar_best["tickets"], scalar_best["total_s"]
    flushes, fused = rlc_best["flushes"], rlc_best["fused"]

    rlc_admitted = sum(1 for t in rlc_tickets if t.status == "admitted")
    scalar_admitted = sum(
        1 for t in scalar_tickets if t.status == "admitted"
    )
    verdicts_identical = [
        (t.status, t.reason) for t in rlc_tickets
    ] == [(t.status, t.reason) for t in scalar_tickets]

    views_identical = _json.dumps(
        [type(a).to_json(a) for a in rlc_pool.attestations_view()],
        sort_keys=True,
    ) == _json.dumps(
        [type(a).to_json(a) for a in scalar_pool.attestations_view()],
        sort_keys=True,
    )
    vec_picks = [
        (g.slot, g.committee_key, g.data_root, row)
        for g, row in select_aggregates(rlc_pool.groups(), 128)
    ]
    scalar_picks = [
        (g.slot, g.committee_key, g.data_root, row)
        for g, row in select_aggregates(scalar_pool.groups(), 128,
                                        scalar=True)
    ]
    selection_identical = vec_picks == scalar_picks and len(vec_picks) > 0

    expected_flushes = (messages + window - 1) // window
    speedup = scalar_s / rlc_s if rlc_s else float("inf")
    return {
        "ok": bool(
            rlc_engine.rlc
            and speedup >= 10.0
            and flushes == expected_flushes
            and rlc_admitted == messages
            and scalar_admitted == messages
            and verdicts_identical
            and views_identical
            and selection_identical
            and trace_evidence["ok"]
        ),
        "trace": trace_evidence,
        "validators": validators,
        "messages": messages,
        "groups": groups,
        "aggregators_per_group": aggregators,
        "window": window,
        "rlc_ingest_s": rlc_s,
        "rlc_admit_s": rlc_best["admit_s"],
        "scalar_ingest_s": scalar_s,
        "admissions_per_s_rlc": messages / rlc_s,
        "admissions_per_s_scalar": messages / scalar_s,
        "admission_speedup": speedup,
        "flushes": flushes,
        "flushes_expected": expected_flushes,
        "fused_groups": fused,
        "rlc_admitted": rlc_admitted,
        "scalar_admitted": scalar_admitted,
        "bit_identical": bool(
            verdicts_identical and views_identical and selection_identical
        ),
        "served_head_slot": int(snap.slot),
        "backend": _pool_backend_name(),
        "note": (
            "admissions/s to admit AND settle all messages, measured "
            "while a chain-pipeline replay loops on the shared bls "
            "verifier; the RLC engine defers signatures into one fused "
            "flush per window (batched G2 membership MSM + per-group "
            "multiplicity G1 MSM + one RLC multi-pairing) while the "
            "scalar twin pays per-message key parses and one pairing "
            "pair per message; bit_identical covers verdicts, served "
            "views, and vectorized-vs-brute-force aggregate selection"
        ),
    }


def _pool_backend_name() -> str:
    from ethereum_consensus_tpu.crypto import bls

    try:
        return bls.backend_name()
    except Exception:  # noqa: BLE001 — report, never fail the config
        return "unknown"


def _soak_mesh_fault_segment() -> dict:
    """Fault injection under the MESH route (ISSUE 13 acceptance): the
    same short storm schedule runs twice — once with ``ECT_MESH=1``
    (the sharded pairing + epoch routes forced on, device faults
    injected via ``FaultInjector.fail_mesh``) and once host-routed —
    and must land on the SAME final root with every corruption blamed
    exactly (``run_storm`` asserts blame internally). Journal evidence:
    the injected-fault declines and the mesh engages both routes paid
    around them (recovery = the host fallback, bit-identical)."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import chain_utils

    from ethereum_consensus_tpu import _device_flags
    from ethereum_consensus_tpu.parallel import runtime as mesh_runtime
    from ethereum_consensus_tpu.pipeline import FaultInjector
    from ethereum_consensus_tpu.scenarios import (
        bad_proposer_signature,
        bad_state_root,
        run_storm,
    )
    from ethereum_consensus_tpu.scenarios.harness import forced_columnar
    from ethereum_consensus_tpu.telemetry import device as tel_device
    from ethereum_consensus_tpu.telemetry import metrics as tel_metrics

    # 18 blocks = TWO epoch boundaries on the minimal preset: the
    # second one's transition runs the inactivity/rewards sweeps (the
    # first is the genesis epoch, which skips them), so the epoch
    # fault point is actually reachable
    state, ctx = chain_utils.fresh_genesis_fork("deneb", 64, "minimal")
    blocks = chain_utils.produce_chain(state, ctx, 18, fork_name="deneb",
                                       atts_per_block=1)
    plan = {3: bad_proposer_signature, 12: bad_state_root}

    def storm(fault_injector=None):
        with forced_columnar():
            report, ex = run_storm(
                state, ctx, blocks, plan, sign=chain_utils.sign_block,
                fault_injector=fault_injector, check_states=False,
                check_columns=False,
            )
        raw = ex.state.data
        return report, bytes(type(raw).hash_tree_root(raw))

    prior_env = os.environ.get("ECT_MESH")
    prior_epoch_min = os.environ.get("ECT_MESH_EPOCH_MIN_N")
    prior_pairing = _device_flags.PAIRING_MIN_SETS
    os.environ["ECT_MESH"] = "1"
    os.environ["ECT_MESH_EPOCH_MIN_N"] = "1"
    mesh_runtime.reset()
    _device_flags.PAIRING_MIN_SETS = 1
    injector = FaultInjector()
    injector.fail_mesh("pairing", 2).fail_mesh("epoch", 1).install_mesh()
    injected_base = tel_metrics.counter(
        "mesh.decline.injected_fault"
    ).value()
    routes_base = tel_device.OBSERVATORY.route_tallies()
    try:
        mesh_report, mesh_root = storm(fault_injector=injector)
    finally:
        injector.uninstall_mesh()
        _device_flags.PAIRING_MIN_SETS = prior_pairing
        if prior_env is None:
            os.environ.pop("ECT_MESH", None)
        else:
            os.environ["ECT_MESH"] = prior_env
        if prior_epoch_min is None:
            os.environ.pop("ECT_MESH_EPOCH_MIN_N", None)
        else:
            os.environ["ECT_MESH_EPOCH_MIN_N"] = prior_epoch_min
        mesh_runtime.reset()
    injected = (
        tel_metrics.counter("mesh.decline.injected_fault").value()
        - injected_base
    )
    routes_now = tel_device.OBSERVATORY.route_tallies()

    def engages(kind):
        return routes_now.get(kind, {}).get("device", 0) - routes_base.get(
            kind, {}
        ).get("device", 0)

    host_report, host_root = storm()
    fault_kinds = sorted(
        kind for _s, _a, kind in injector.injected
    )
    return {
        "ok": bool(
            mesh_root == host_root
            and injected == 3
            and fault_kinds == ["mesh_epoch", "mesh_pairing",
                                "mesh_pairing"]
            and engages("mesh.pairing") >= 1
            and engages("mesh.epoch") >= 1
            and len(mesh_report.failures) == len(plan)
            and len(host_report.failures) == len(plan)
        ),
        "final_root_identical": bool(mesh_root == host_root),
        "final_root": "0x" + mesh_root.hex(),
        "injected_faults": injected,
        "fault_kinds": fault_kinds,
        "mesh_pairing_engages": engages("mesh.pairing"),
        "mesh_epoch_engages": engages("mesh.epoch"),
        "storm_failures": len(mesh_report.failures),
        "blame": [
            {"index": f.index, "mutator": f.mutator.name,
             "error": type(f.error).__name__}
            for f in mesh_report.failures
        ],
        "note": (
            "same schedule, mesh vs host route: injected device faults "
            "on the sharded pairing/epoch paths journal as "
            "mesh.decline.injected_fault and recover through the host "
            "fallback — blame and the final root are differential-"
            "identical to the host-route run"
        ),
    }


def bench_soak(cycles: int = 150, deadline_s: float = 210.0,
               min_windows: int = 800):
    """Production soak (soak/, docs/SOAK.md — ISSUE 13): the sustained
    mixed-load run the north star asks for. Fork-boundary storm cycles
    + rotating fault injection + a reader swarm + SSE subscribers +
    pool ingestion spam + deterministic equivocation (double AND
    surround) traffic, for thousands of flush windows under a deadline
    budget, with the three hard gates folded into ``ok``: p99
    verify/settle/gather SLOs off the reservoir histograms with
    /healthz pinned to ``ok``, flat RSS via the leak sentinel, and
    end-of-run bit-identity (cycle roots vs the scalar oracle, exact
    blame, equivocation-ledger refeed identity, surfaced slashings —
    surround included — executing in soak-produced blocks). The run
    executes with the causal trace plane active, so the report's
    ``gates.trace`` block (folded into ``ok`` by the runner) proves
    every SLO histogram's exemplars resolve to connected trees. A
    second segment proves fault injection under the MESH route:
    differential-identical to the host-route run of the same schedule.

    Headline: the sustained blocks/s + queries/s pair."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from ethereum_consensus_tpu.soak import SoakConfig, run_soak

    if _fast_test():
        cycles, deadline_s, min_windows = 3, 60.0, 20
    # the deployment profile is the base (shipped catastrophe-catcher
    # defaults, docs/SOAK.md; EC_SOAK_PROFILE overrides the path) and
    # the bench's sustained shape rides on top as overrides
    config = SoakConfig.from_file(
        os.environ.get(MEM_PROFILE_ENV) or None,
        cycles=cycles,
        deadline_s=deadline_s,
        min_windows=min_windows,
        readers=2,
        sse_subscribers=1,
        pool_spam_rounds=200,
        equivocate_every=3,
        rss_budget_mb=192.0,
        rss_warmup_cycles=5,
        seed=0x5013,
    )
    report = run_soak(config)
    mesh_segment = _soak_mesh_fault_segment()
    return {
        "ok": bool(report["ok"] and mesh_segment["ok"]),
        "blocks_per_s": report["blocks_per_s"],
        "queries_per_s": report["queries_per_s"],
        "cycles": report["cycles"],
        "windows": report["windows"],
        "blocks_committed": report["blocks_committed"],
        "wall_s": report["wall_s"],
        "storm_failures": report["storm_failures"],
        "faults_injected": report["faults_injected"],
        "gates": report["gates"],
        "pool_spam": report["pool_spam"],
        "readers": report["readers"],
        "sse_events": report["sse_events"],
        "verify_lanes": report["config"]["verify_lanes"],
        "mesh_fault_injection": mesh_segment,
        "note": (
            "sustained mixed load over the phase0->electra upgrade "
            "chain: every cycle replays the storm-corrupted chain "
            "through the pipeline with recovery while readers, SSE "
            "subscribers, and pool spam run concurrently; ok folds the "
            "three soak gates (SLO/healthz, flat RSS, bit-identity), "
            "the causal-trace gate (every SLO exemplar resolves to a "
            "connected admission->settle tree), AND the mesh-route "
            "fault-injection differential"
        ),
    }


def bench_process_block():
    """Full block application incl. batched signature verification and the
    per-slot state HTR (minimal preset — the Python orchestration floor;
    see bench_process_block_mainnet for the BASELINE config 5 shape)."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from chain_utils import fresh_genesis, make_attestation, produce_block

    from ethereum_consensus_tpu.models.phase0.slot_processing import process_slots
    from ethereum_consensus_tpu.models.phase0.state_transition import (
        state_transition,
    )

    state, ctx = fresh_genesis(64, "minimal")
    times = []
    for _ in range(BLOCK_REPS):
        target = state.slot + 2
        scratch = state.copy()
        process_slots(scratch, target, ctx)
        atts = [
            make_attestation(scratch, slot, 0, ctx)
            for slot in range(target - 2, target)
            if slot + ctx.MIN_ATTESTATION_INCLUSION_DELAY <= target
        ]
        signed = produce_block(state.copy(), target, ctx, attestations=atts)
        t0 = time.perf_counter()
        state_transition(state, signed, ctx)
        times.append(time.perf_counter() - t0)
    best = min(times)
    return {
        "blocks_per_s": 1.0 / best,
        "block_s": best,
        "attestations_per_block": len(signed.message.body.attestations),
        "preset": "minimal",
        "validators": 64,
    }


# ---------------------------------------------------------------------------
# child driver: run configs in priority order, checkpoint each to disk
# ---------------------------------------------------------------------------

# (name, fn) in priority order — the HEADLINE source (htr) first, then the
# VERDICT-priority mainnet-scale numbers, then the rest; a mid-run death
# still captures everything above the cut
CONFIGS = [
    ("htr", bench_htr),  # fast-test mode runs exactly this one
    ("att_batch", bench_att_batch),
    # the 2^21-flagship epoch configs right after the headline sources:
    # they carry ISSUE 9's acceptance (columnar-primary epoch engine)
    # and must never be starved by a cold bundle rebuild below
    ("epoch_deneb", bench_epoch_deneb),
    ("epoch_electra", bench_epoch_electra),
    # the mesh flagship rides the two configs above: their disk-cached
    # pre-boundary states feed the virtual-mesh children (ISSUE 12)
    ("epoch_mesh", bench_epoch_mesh),
    ("epoch_mainnet", bench_epoch_mainnet),
    ("process_block_mainnet", bench_process_block_mainnet),
    ("process_block_deneb", bench_process_block_deneb),
    ("process_block_electra", bench_process_block_electra),
    ("pipeline_blocks", bench_pipeline_blocks),
    ("adversarial_replay", bench_adversarial_replay),
    # shares adversarial_replay's 2^17 chain bundle; spawns the
    # {1,2,4,8}-device virtual-mesh children (ISSUE 12)
    ("multichip_pipeline", bench_multichip_pipeline),
    ("serving_queries", bench_serving_queries),
    ("pool_ingest", bench_pool_ingest),
    # the sustained mixed-load soak (ISSUE 13): composes the pipeline,
    # scenario, serving, pool, and mesh layers above into one run with
    # SLO / flat-RSS / bit-identity gates — before the tail configs so
    # the deadline can never starve the acceptance
    ("soak", bench_soak),
    # the single heaviest cold-cache build (2^20-validator registry):
    # after the priority numbers
    ("state_htr", bench_state_htr),
    # rides state_htr's freshly warmed disk cache: the proof plane's
    # acceptance at the same 2^20 registry (ISSUE 17)
    ("proofs", bench_proofs),
    ("sig_128k", bench_sig_128k),
    ("sync_agg", bench_sync_agg),
    ("process_block", bench_process_block),
    ("kzg", bench_kzg),
    ("large_agg", bench_large_agg),
    # last: pays two cold Miller-loop compiles on a fresh chip — must not
    # starve the BASELINE configs above at the deadline
    ("pairing_device", bench_pairing_device),
]


def _obs_tallies() -> dict:
    """A flat snapshot of the device observatory's own ledgers (NOT the
    metrics registry) — the cross-structure side of the per-config
    consistency check in ``_device_block``."""
    from ethereum_consensus_tpu.telemetry import device as tel_device

    obs = tel_device.OBSERVATORY
    compiles = obs.compiles()
    totals = obs.transfer_summary()["totals"]
    routes: dict = {}
    for kind, choices in obs.route_tallies().items():
        for choice, count in choices.items():
            routes[f"{kind}.{choice}"] = count
    return {
        "compiles": len(compiles),
        "recompiles": sum(1 for c in compiles if c["recompile"]),
        "transfers": dict(totals),
        "routes": routes,
    }


# configs whose ``ok`` additionally requires the device evidence to be
# self-consistent (metrics-registry deltas == observatory-journal deltas):
# the device-routed measures a chip run brings home
DEVICE_OK_CONFIGS = ("pipeline_blocks", "epoch_deneb", "epoch_electra",
                     "epoch_mainnet", "epoch_mesh", "multichip_pipeline")


def _mesh_runtime_state() -> dict:
    """The mesh runtime's provisioning state (parallel/runtime.py) —
    imported only when ECT_MESH is on, so an off battery stays jax-free
    at this seam."""
    env = os.environ.get("ECT_MESH", "").strip()
    if env.lower() in ("", "off", "0", "none", "host"):
        return {"requested": False, "env": env or "off", "devices": 0}
    from ethereum_consensus_tpu.parallel import runtime as mesh_runtime

    return mesh_runtime.status()


def _device_block(metrics_before: dict, obs_before: dict) -> dict:
    """Per-config device-execution evidence (ISSUE 10): compiles,
    recompile count, transfer bytes, routing-journal tallies, jit-cache
    hits/misses — with a ``journal_consistent`` cross-check that the
    metrics-registry deltas and the observatory's own ledgers tell the
    same story (two independently-written structures; a guard drift or
    a half-active observatory shows up here as False)."""
    from ethereum_consensus_tpu.telemetry import metrics as tel_metrics

    d = tel_metrics.delta(metrics_before)
    now = _obs_tallies()
    compile_hist = d.get("device.compile_s")
    routes = {
        key: now["routes"].get(key, 0) - obs_before["routes"].get(key, 0)
        for key in set(now["routes"]) | set(obs_before["routes"])
    }
    routes = {key: count for key, count in routes.items() if count}
    transfers = {
        key: now["transfers"][key] - obs_before["transfers"].get(key, 0)
        for key in now["transfers"]
    }
    block = {
        "compiles": d.get("device.compiles", 0),
        "recompiles": d.get("device.recompiles", 0),
        "compile_s": (
            compile_hist.get("sum", 0.0)
            if isinstance(compile_hist, dict)
            else 0.0
        ),
        "jit_cache_hits": d.get("device.jit_cache.hits", 0),
        "jit_cache_misses": d.get("device.jit_cache.misses", 0),
        "h2d_count": d.get("device.transfer.h2d_count", 0),
        "h2d_bytes": d.get("device.transfer.h2d_bytes", 0),
        "d2h_count": d.get("device.transfer.d2h_count", 0),
        "d2h_bytes": d.get("device.transfer.d2h_bytes", 0),
        "routes": routes,
        "route_device": sum(
            count for key, count in routes.items()
            if key.endswith(".device") or key.endswith(".columnar")
        ),
        "route_host": sum(
            count for key, count in routes.items()
            if key.endswith(".host") or key.endswith(".literal")
            or key.endswith(".scalar")
        ),
    }
    # mesh-runtime evidence (ISSUE 12): engage/decline counters for this
    # config plus the provisioned-runtime state. Configs that spawn their
    # own virtual-mesh children (multichip_pipeline, epoch_mesh) carry
    # the child-side evidence in their payloads; this block covers
    # in-process engagement (ECT_MESH set on the whole battery).
    block["mesh"] = {
        "engages": d.get("mesh.engage", 0),
        "declines": {
            key[len("mesh.decline."):]: value
            for key, value in d.items()
            if key.startswith("mesh.decline.") and value
        },
        "runtime": _mesh_runtime_state(),
    }
    counter_routes: dict = {}
    for key, value in d.items():
        if key.startswith("device.route.") and value:
            counter_routes[key[len("device.route."):]] = value
    block["journal_consistent"] = bool(
        counter_routes == routes
        and block["compiles"] == now["compiles"] - obs_before["compiles"]
        and block["recompiles"]
        == now["recompiles"] - obs_before["recompiles"]
        and block["h2d_bytes"] == transfers["h2d_bytes"]
        and block["d2h_bytes"] == transfers["d2h_bytes"]
    )
    return block


def _metrics_block(before: dict) -> dict:
    """Per-config delta of the telemetry registry: the WORK a config did
    (digests, cache traffic, pairing routes, flush shape), not just its
    seconds — so BENCH_*.json trajectories capture counters too."""
    from ethereum_consensus_tpu.telemetry import metrics as tel_metrics

    d = tel_metrics.delta(before)
    hits = d.get("bls.pubkey_cache.hits", 0)
    misses = d.get("bls.pubkey_cache.misses", 0)
    out = {
        "ssz_digests": d.get("ssz.digests", 0),
        "pubkey_cache_hits": hits,
        "pubkey_cache_misses": misses,
        "pubkey_cache_hit_rate": (
            round(hits / (hits + misses), 4) if (hits + misses) else None
        ),
        "pubkey_cache_evictions": d.get("bls.pubkey_cache.evictions", 0),
        "warm_raw_keys_bulk_calls": d.get("bls.warm_raw_keys.calls", 0),
        "warm_raw_keys_keys": d.get("bls.warm_raw_keys.keys", 0),
        "pairing_route_device": d.get("bls.pairing_route.device", 0),
        "pairing_route_host": d.get("bls.pairing_route.host", 0),
    }
    flush = d.get("pipeline.flush_size")
    if isinstance(flush, dict) and flush.get("count"):
        out["flushes"] = flush["count"]
        out["mean_flush_size"] = round(flush["mean"], 2)
        out["queue_depth_high_watermark"] = d.get(
            "pipeline.queue_depth_high_watermark", 0
        )
    # columnar operations engine engagement (models/ops_vector.py):
    # batched blocks/attestations, bulk_store commits, column cache
    # traffic, and every degradation to a scalar path by reason
    ops = {
        key.split("ops_vector.", 1)[1]: value
        for key, value in d.items()
        if key.startswith("ops_vector.")
        and not key.startswith("ops_vector.fallback.")
        and value
    }
    fallbacks = {
        key.split("ops_vector.fallback.", 1)[1]: value
        for key, value in d.items()
        if key.startswith("ops_vector.fallback.") and value
    }
    if fallbacks:
        ops["fallbacks"] = fallbacks
    if ops:
        out["ops_vector"] = ops
    # columnar-primary epoch engine engagement (models/epoch_vector.py)
    ev = {
        key.split("epoch_vector.", 1)[1]: value
        for key, value in d.items()
        if key.startswith("epoch_vector.")
        and not key.startswith("epoch_vector.fallback.")
        and value
    }
    ev_fallbacks = {
        key.split("epoch_vector.fallback.", 1)[1]: value
        for key, value in d.items()
        if key.startswith("epoch_vector.fallback.") and value
    }
    if ev_fallbacks:
        ev["fallbacks"] = ev_fallbacks
    if ev:
        out["epoch_vector"] = ev
    # operation-pool engagement (pool/): admissions by kind, rejections
    # by structured reason, flush/fusion discipline
    pool_block = {
        key.split("pool.", 1)[1]: (
            value if not isinstance(value, dict)
            else {"count": value.get("count"),
                  "mean": round(value["mean"], 6)
                  if value.get("count") else None}
        )
        for key, value in d.items()
        if key.startswith("pool.") and value
    }
    if pool_block:
        out["pool"] = pool_block
    return out


def child_main() -> None:
    from ethereum_consensus_tpu.telemetry import device as tel_device
    from ethereum_consensus_tpu.telemetry import memory as tel_memory
    from ethereum_consensus_tpu.telemetry import metrics as tel_metrics
    from ethereum_consensus_tpu.telemetry import spans as tel_spans
    from ethereum_consensus_tpu.utils import trace

    import jax

    device = {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
    }
    if device["platform"] != "tpu":
        # a measurement path that finds no chip fails: no shrunk sizes,
        # no CPU numbers under device names
        _note(f"no TPU: jax found {device}; bench.py measures the chip")
        sys.exit(NO_TPU_RC)
    progress_path = os.environ[PROGRESS_ENV]
    results: dict = {"device_info": device}
    t_start = time.monotonic()
    trace_out = os.environ.get(TRACE_OUT_ENV)
    if trace_out:
        tel_spans.start_recording(capacity=1 << 18)
    # the device observatory runs for the whole battery: per-config
    # ``device`` evidence blocks + the BENCH_FULL device ledger; its
    # per-event cost is microseconds against kernel-scale work
    tel_device.start()
    # the memory observatory too (ISSUE 15): per-config ``mem`` blocks
    # (peak RSS for every config, the full attribution report for the
    # epoch configs), the bandwidth ledger, and the BENCH_FULL memory
    # ledger — every ok-gated config must stay ok with it active
    tel_memory.start()
    server = None
    serve_port = os.environ.get(SERVE_PORT_ENV)
    if serve_port:
        # live introspection for the whole bench run: /metrics scrapes
        # every config's counters mid-flight, /blocks + /events follow
        # the pipeline configs' replays (docs/OBSERVABILITY.md)
        from ethereum_consensus_tpu.telemetry.server import (
            IntrospectionServer,
        )

        server = IntrospectionServer(port=int(serve_port)).start()
        _note(f"introspection server on {server.url()}")

    def checkpoint():
        tmp = progress_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(results, f)
        os.replace(tmp, progress_path)

    configs = CONFIGS[:1] if _fast_test() else CONFIGS
    only = os.environ.get("EC_BENCH_ONLY")
    if only:
        # comma-separated config allowlist: targeted re-measures without
        # paying the whole battery (e.g. EC_BENCH_ONLY=serving_queries)
        wanted = {name.strip() for name in only.split(",") if name.strip()}
        configs = [(name, fn) for name, fn in configs if name in wanted]
    for name, fn in configs:
        elapsed = time.monotonic() - t_start
        if elapsed > CONFIG_DEADLINE_S:
            results[name] = {"skipped": f"time budget ({elapsed:.0f}s elapsed)"}
            checkpoint()
            continue
        _note(f"config {name} starting ({elapsed:.0f}s elapsed)")
        metrics_base = tel_metrics.snapshot()
        obs_base = _obs_tallies()
        mem_copies_base = tel_memory.OBSERVATORY.copy_summary()["totals"]
        mem_rss_base = tel_memory.rss_mb()
        t0 = time.monotonic()
        try:
            with trace.span("bench." + name):
                out = fn()
        except Exception as exc:  # noqa: BLE001 — never lose the other configs
            out = {"error": f"{type(exc).__name__}: {str(exc)[:200]}"}
        out["wall_s"] = round(time.monotonic() - t0, 2)
        for key, value in device.items():
            # the mesh configs ran on virtual CPU devices and say so
            out.setdefault(key, value)
        out["metrics"] = _metrics_block(metrics_base)
        out["device"] = _device_block(metrics_base, obs_base)
        # uniform memory evidence (ISSUE 15 satellite): EVERY config
        # records its peak/current RSS and bulk-copy traffic through
        # the observatory sampler, so bench_compare --trend can chart
        # the whole battery's memory story; the epoch configs' richer
        # attribution block (set inside _epoch_cold_warm) is preserved
        mem_totals = tel_memory.OBSERVATORY.copy_summary()["totals"]
        mem_block = out.setdefault("mem", {})
        mem_block.setdefault(
            "peak_rss_mb", round(tel_memory.peak_rss_mb(), 1)
        )
        mem_block.setdefault("rss_mb", round(tel_memory.rss_mb(), 1))
        mem_block.setdefault("baseline_mb", round(mem_rss_base, 1))
        mem_block.setdefault(
            "copy_bytes", mem_totals["bytes"] - mem_copies_base["bytes"]
        )
        mem_block.setdefault(
            "copies", mem_totals["count"] - mem_copies_base["count"]
        )
        out.setdefault("peak_rss_mb", mem_block["peak_rss_mb"])
        if name in DEVICE_OK_CONFIGS and "ok" in out:
            # the device evidence is part of these configs' acceptance:
            # route tallies / transfer bytes / recompile counts must
            # agree between the metrics registry and the observatory
            out["ok"] = bool(out["ok"]) and out["device"]["journal_consistent"]
        results[name] = out
        checkpoint()
        _note(f"config {name} done in {out['wall_s']}s")
        # earlier configs leave multi-hundred-MB states pinned in lru
        # caches; without freezing them out of the GC's tracked set,
        # gen-2 collections during a later config's million-object walk
        # cost ~10x its real time (measured: state_htr cold walk 6s
        # standalone vs 60s late in the child)
        import gc

        gc.collect()
        gc.freeze()

    # process-wide registry totals ride the progress file so the parent
    # can surface them in the full dump even though the registry lives
    # in this child process
    results["process_metrics"] = tel_metrics.snapshot()
    # the whole run's device ledgers ride along the same way (compile
    # census, per-site transfer bytes, routing-journal tallies)
    results["device_ledger"] = tel_device.snapshot(journal_n=64)
    # ... and the memory ledgers (census/worst table, phase RSS ledger,
    # per-site bulk-copy bytes) — the battery-wide memory story
    results["memory_ledger"] = tel_memory.snapshot(worst_n=12)
    checkpoint()
    if trace_out:
        tel_spans.stop_recording()
        tel_spans.write_chrome_trace(trace_out)
        _note(f"chrome trace written: {trace_out}")
    metrics_out = os.environ.get(METRICS_OUT_ENV)
    if metrics_out:
        with open(metrics_out, "w") as f:
            json.dump(tel_metrics.snapshot(), f, indent=1, sort_keys=True)
        _note(f"metrics snapshot written: {metrics_out}")
    device_out = os.environ.get(DEVICE_OUT_ENV)
    if device_out:
        with open(device_out, "w") as f:
            json.dump(tel_device.snapshot(), f, indent=1, sort_keys=True)
        _note(f"device ledger written: {device_out}")
    memory_out = os.environ.get(MEMORY_OUT_ENV)
    if memory_out:
        with open(memory_out, "w") as f:
            json.dump(tel_memory.snapshot(), f, indent=1, sort_keys=True)
        _note(f"memory ledger written: {memory_out}")
    if server is not None:
        server.stop()


# ---------------------------------------------------------------------------
# parent driver: spawn the child once, assemble the one JSON line
# ---------------------------------------------------------------------------


def main() -> None:
    if os.environ.get(CHILD_ENV):
        child_main()
        return

    # telemetry export flags (docs/OBSERVABILITY.md): the bench work all
    # happens in the child process, so the paths travel by env var
    argv = sys.argv[1:]
    for flag, env_key in (
        ("--trace-out", TRACE_OUT_ENV),
        ("--metrics-out", METRICS_OUT_ENV),
        ("--device-out", DEVICE_OUT_ENV),
        ("--memory-out", MEMORY_OUT_ENV),
    ):
        if flag in argv:
            at = argv.index(flag)
            if at + 1 >= len(argv):
                print(f"{flag} requires a path argument", file=sys.stderr)
                sys.exit(2)
            os.environ[env_key] = os.path.abspath(argv[at + 1])
    if "--serve-port" in argv:
        at = argv.index("--serve-port")
        if at + 1 >= len(argv):
            print("--serve-port requires a port argument", file=sys.stderr)
            sys.exit(2)
        os.environ[SERVE_PORT_ENV] = argv[at + 1]

    progress_path = os.path.join(REPO, ".bench_progress.json")
    if os.path.exists(progress_path):
        os.unlink(progress_path)

    # one process for each chip: this parent never imports jax, and the
    # one child it starts is the process that holds the chip
    env = dict(os.environ)
    env[CHILD_ENV] = "1"
    env[PROGRESS_ENV] = progress_path

    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env,
        cwd=REPO,
        stdout=sys.stderr,  # child stdout is notes only; JSON comes from us
        stderr=sys.stderr,
    )
    child_err = None
    try:
        rc = proc.wait(timeout=CHILD_TIMEOUT_S)
        if rc == NO_TPU_RC:
            # nothing was measured: no JSON line, non-zero exit
            sys.exit(NO_TPU_RC)
        if rc != 0:
            child_err = f"bench child exited rc={rc}"
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        child_err = f"bench child killed at {CHILD_TIMEOUT_S}s budget"

    configs: dict = {}
    if os.path.exists(progress_path):
        try:
            with open(progress_path) as f:
                configs = json.load(f)
        except Exception as exc:  # noqa: BLE001
            child_err = f"progress file unreadable: {exc}"

    def _round(obj):
        if isinstance(obj, dict):
            return {k: _round(v) for k, v in obj.items()}
        if isinstance(obj, float):
            return round(obj, 4)
        return obj

    process_metrics = configs.pop("process_metrics", None)
    device_ledger = configs.pop("device_ledger", None)
    device_info = configs.pop("device_info", None) or {}
    htr = configs.pop("htr", None) or {}
    value = vs = 0.0
    error = None
    if htr.get("device_s") and htr.get("ok"):
        value = htr["leaves"] / htr["device_s"]
        vs = htr["host_s"] / htr["device_s"]
    elif htr.get("ok") is False:
        error = "device root mismatch vs native merkleizer"
    else:
        error = htr.get("error") or child_err or "headline config missing"
    # Full evidence dump goes to a FILE; stdout's last line stays compact
    # (round-4 lesson: the driver tails stdout with a bounded window, and
    # a full per-config dump on the final line truncated mid-object —
    # BENCH_r04.json parsed:null).
    full = _round(
        {
            "leaves": htr.get("leaves"),
            "device_s": htr.get("device_s"),
            "baseline_s": htr.get("host_s"),
            "baseline_kind": htr.get("host_kind"),
            "baseline_note": (
                "every vs_baseline ratio is against THIS repo's "
                "from-scratch single-core C++ backend, not blst; "
                "blst_class_estimate fields give the external "
                "reference scale where one exists"
            ),
            "backend": htr.get("backend"),
            **device_info,
            "metrics": process_metrics,
            "device_ledger": device_ledger,
            "configs": configs,
        }
    )
    if child_err:
        full["child_error"] = child_err
    # EC_BENCH_FULL_PATH override exists so test harnesses exercising this
    # driver can't clobber a real run's evidence artifact in the repo root
    full_path = os.environ.get(
        "EC_BENCH_FULL_PATH", os.path.join(REPO, "BENCH_FULL.json")
    )
    full_results = os.path.basename(full_path)
    try:
        with open(full_path, "w") as f:
            json.dump(full, f, indent=1)
    except OSError as exc:
        full_results = f"unwritable ({exc}); do NOT trust any stale dump"

    out = {
        "metric": "hash_tree_root_leaves_per_sec",
        "value": round(value, 1),
        "unit": "leaves/sec",
        "vs_baseline": round(vs, 2),
        "detail": {
            "backend": htr.get("backend"),
            **device_info,
            "full_results": full_results,
            "configs_run": sorted(configs),
        },
    }
    if error:
        out["error"] = error[:200]
    if child_err and not error:
        out["detail"]["child_error"] = child_err[:200]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
