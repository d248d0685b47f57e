#!/usr/bin/env python3
"""The main path on the chip, end to end, in one process.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # the mesh path on a four-chip host

The deployment is deneb on the mainnet preset with a 2^20-validator
registry and a chain of 8 consecutive blocks, each carrying 64 aggregate
attestations over full committees (2^20 / 32 / 64 = 512 members), the
512-bit sync aggregate and an execution payload — built from nothing by
the repo's generator (tests/chain_utils.py ``build_mainnet_chain``,
deterministic; no cache is needed). 8 x (64 attestations + proposer +
randao + sync aggregate) = 536 signature sets make one flush window, which
is over the 512 sets ``ops.install()`` routes to the device pairing.

The work runs twice. First on the host, before any device routing is
installed: ``Executor.apply_block`` over the blocks, ``process_slots``
across the epoch boundaries, ``hash_tree_root``. Then ``ops.install()``
with its defaults and the same work through the served path: a cold
``hash_tree_root`` of the deserialized pre-state, ``Executor.stream``,
``process_slots``. Roots must agree bit for bit, every routed kind must
have reached the device and no route may have declined to the host.

Two epoch boundaries are crossed, not one: the chain lives in the genesis
epoch, where the spec skips rewards and penalties, so the first boundary
gives the fused epoch kernel nothing to do. The second one does.

One JSON object per phase on standard output, then — only when every
phase passed on a TPU — the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failure is an exception: non-zero exit, and no such line.

With ``--chips 4`` only the mesh path runs (``ECT_MESH=4``): one sharded
epoch pass at 2^20 rows, one sharded RLC flush of 520 sets (valid and
tampered) and one sharded merkleization of 2^20 chunks, each against the
host result on the same inputs, with every sharded input and output
required to span four devices.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from contextlib import contextmanager

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))  # the chain generator

from benchmark import meters  # noqa: E402

FORK = "deneb"
VALIDATORS = 1 << 20
N_BLOCKS = 8
ATTESTATIONS = 64
EPOCH_BOUNDARIES = 2
MESH_SETS = 520
MESH_CHUNKS = 1 << 20
SEED = 23

# kinds of the routing journal that must have reached the device
ROUTED_KINDS = (
    "hasher", "sweeps", "epoch_fused", "shuffle", "bls_agg", "pairing",
)
MESH_KERNELS = (
    "parallel.epoch.fused_sweep",
    "parallel.pairing._sharded_parts",
    "parallel.merkle.sharded_merkle_root_words",
)


class SmokeFailure(AssertionError):
    """A phase produced a wrong or missing result."""


def require(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# readings: the benchmark's meters (benchmark/meters.py) — compiles,
# transfers and routes, declines
# ---------------------------------------------------------------------------


_METER: "meters.CompileMeter | None" = None


def _meter() -> meters.CompileMeter:
    global _METER
    if _METER is None:
        _METER = meters.CompileMeter()
    return _METER


def native_state() -> dict:
    from ethereum_consensus_tpu import native
    from ethereum_consensus_tpu.native import bls as native_bls

    sha, bls = native.available(), native_bls.available()
    return {
        "sha256": sha,
        "bls": bls,
        "ec_fp8_active": int(native_bls.load().ec_fp8_active()) if bls else None,
    }


def _readings() -> dict:
    return {**_meter().read(), **meters.observatory()}


def _delta(before: dict, after: dict) -> dict:
    compiles = {
        key: after[key] - before[key]
        for key in ("compile_s", "compiles", "cache_misses", "cache_hits")
    }
    obs = meters.observatory_delta(before, after)
    return {
        **compiles,
        "compile_s": round(compiles["compile_s"], 3),
        "h2d_bytes": obs["transfers"]["h2d_bytes"],
        "d2h_bytes": obs["transfers"]["d2h_bytes"],
        "routes": obs["routes"],
    }


@contextmanager
def phase(name: str):
    """Run one phase; on success print its JSON line. The body may add
    fields to the yielded record. A failing phase raises and prints
    nothing — the traceback is its report."""
    record = {"phase": name}
    before = _readings()
    t0 = time.perf_counter()
    yield record
    meters.device_sync()
    record["wall_s"] = round(time.perf_counter() - t0, 3)
    record.update(_delta(before, _readings()))
    record["native"] = native_state()
    print(json.dumps(record), flush=True)


# The evidence checks read what moved since a snapshot of the counters taken
# when the run began: the registry is process-wide, and only a fresh process
# starts it at zero.
counters = meters.counters


# ---------------------------------------------------------------------------
# one chip: phases (sizes are arguments; the command line's only deployment
# is the 2^20 one)
# ---------------------------------------------------------------------------


def _root(state) -> bytes:
    return type(state).hash_tree_root(state)


def _process_slots(state, slot: int, context) -> None:
    importlib.import_module(
        f"ethereum_consensus_tpu.models.{FORK}.slot_processing"
    ).process_slots(state, slot, context)


def epoch_target_slot(blocks, context, boundaries: int) -> int:
    """First slot of the epoch ``boundaries`` epochs after the last block's."""
    spe = int(context.SLOTS_PER_EPOCH)
    return (int(blocks[-1].message.slot) // spe + boundaries) * spe


def check_native() -> None:
    """The native engines are built from source on THIS host (the artifact
    name is keyed on its CPU features and compiler); without them the BLS
    layer would fall to the pure-Python oracle."""
    from ethereum_consensus_tpu import native

    build_dir = os.path.join(os.path.dirname(native.__file__), "_build")
    before = set(os.listdir(build_dir)) if os.path.isdir(build_dir) else set()
    with phase("native_build") as record:
        state = native_state()
        require(
            state["sha256"] and state["bls"],
            f"native engines unavailable: {native.build_failures()}",
        )
        # artifacts that travelled here from another host are not loaded:
        # their names carry that host's CPU features
        record["artifacts_found"] = sorted(before)
        record["artifacts_built"] = sorted(set(os.listdir(build_dir)) - before)


def generate(validators: int, n_blocks: int, attestations: int) -> dict:
    """The deployment, from the generator: pre-state, context, signed
    blocks, and the pre-state's SSZ bytes (a deserialized state is the
    only cold one — copies share root memos)."""
    import chain_utils

    with phase("generate") as record:
        t0 = time.perf_counter()
        pre, context, blocks = chain_utils.build_mainnet_chain(
            FORK, validators, n_blocks, attestations
        )
        record["generator_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        pre_bytes = type(pre).serialize(pre)
        record["serialize_s"] = round(time.perf_counter() - t0, 3)
        record["validators"] = len(pre.validators)
        record["blocks"] = len(blocks)
        # per block: its attestations + proposer + randao + sync aggregate
        record["signature_sets"] = sum(
            len(b.message.body.attestations) + 3 for b in blocks
        )
        record["state_bytes"] = len(pre_bytes)
    return {
        "pre": pre, "context": context, "blocks": blocks,
        "pre_bytes": pre_bytes, "signature_sets": record["signature_sets"],
    }


def host_reference(world: dict, boundaries: int = EPOCH_BOUNDARIES) -> dict:
    """The plain path, to be run before any device routing is installed:
    sequential ``apply_block``, ``process_slots``, ``hash_tree_root``."""
    from ethereum_consensus_tpu import _device_flags
    from ethereum_consensus_tpu.executor import Executor

    require(
        _device_flags.SWEEPS_MIN_N is None
        and _device_flags.PAIRING_MIN_SETS is None,
        "host reference must run before ops.install()",
    )
    pre, context, blocks = world["pre"], world["context"], world["blocks"]
    with phase("host_reference") as record:
        t0 = time.perf_counter()
        roots = {"pre": _root(pre)}
        record["pre_root_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        executor = Executor(pre.copy(), context)
        for block in blocks:
            executor.apply_block(block)
        state = executor.state.data
        roots["post_block"] = _root(state)
        record["blocks_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        _process_slots(
            state, epoch_target_slot(blocks, context, boundaries), context
        )
        roots["post_epoch"] = _root(state)
        record["epochs_s"] = round(time.perf_counter() - t0, 3)
        record["roots"] = {k: v.hex() for k, v in roots.items()}
    return roots


def cold_state_root(world: dict, roots: dict) -> None:
    """A cold ``hash_tree_root`` of the deserialized pre-state — the one
    place merkle levels of >= 2^17 nodes occur (the registry's columnar
    bulk walk and the packed balance lists), so the one place the
    per-level device hasher is reached."""
    with phase("cold_state_root") as record:
        t0 = time.perf_counter()
        cold = type(world["pre"]).deserialize(world["pre_bytes"])
        record["deserialize_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        got = _root(cold)
        record["root_s"] = round(time.perf_counter() - t0, 3)
        require(
            got == roots["pre"],
            f"cold pre-state root {got.hex()} != host {roots['pre'].hex()}",
        )


def stream_blocks(world: dict, roots: dict):
    """The blocks through ``Executor.stream``: stage-A application on this
    thread, one windowed cross-block signature flush on the verifier."""
    from ethereum_consensus_tpu.executor import Executor
    from ethereum_consensus_tpu.pipeline import FlushPolicy

    with phase("stream_blocks") as record:
        executor = Executor(world["pre"].copy(), world["context"])
        # one window of 8 blocks, two in flight; the settle bound is raised
        # because a cold first flush compiles the pairing kernels
        policy = FlushPolicy(
            window_size=8, max_in_flight=2, settle_timeout_s=1000.0,
            verify_lanes=1,
        )
        stats = executor.stream(world["blocks"], policy=policy).snapshot()
        got = _root(executor.state.data)
        record["flush_sizes"] = stats["flush_sizes"]
        record["rollbacks"] = stats["rollbacks"]
        require(stats["rollbacks"] == 0, f"pipeline rolled back: {stats}")
        require(
            got == roots["post_block"],
            f"post-block root {got.hex()} != host "
            f"{roots['post_block'].hex()}",
        )
    return executor.state.data


def epoch_boundary(state, world: dict, roots: dict,
                   boundaries: int = EPOCH_BOUNDARIES) -> None:
    """``process_slots`` across the epoch boundaries: the columnar epoch
    pass with its fused device kernel, and the next epochs' shuffles."""
    with phase("epoch_boundary") as record:
        target = epoch_target_slot(
            world["blocks"], world["context"], boundaries
        )
        _process_slots(state, target, world["context"])
        got = _root(state)
        record["slot"] = target
        require(
            got == roots["post_epoch"],
            f"post-epoch root {got.hex()} != host "
            f"{roots['post_epoch'].hex()}",
        )


def check_evidence(since: dict, routed_kinds=ROUTED_KINDS) -> dict:
    """That the chip did the work, asserted: every routed kind reached the
    device, the Pallas hasher was entered, and no route declined — since
    the ``counters()`` snapshot ``since``."""
    from ethereum_consensus_tpu.telemetry import device as tel_device

    tallies = tel_device.OBSERVATORY.route_tallies()
    now = counters()
    moved = meters.moved(since, now)
    evidence = {
        "phase": "evidence",
        "route_device": {
            kind: tallies.get(kind, {}).get("device", 0)
            for kind in routed_kinds
        },
        "pallas_entries": moved.get("ops.sha256.pallas", 0),
        "device_hash_levels": moved.get("ssz.hash_level.device", 0),
        "declines": meters.declines(since, now),
        "bls_device_host_routes": tallies.get("bls_device", {}),
    }
    print(json.dumps(evidence), flush=True)
    unrouted = [k for k, n in evidence["route_device"].items() if not n]
    require(not unrouted, f"kinds that never reached the device: {unrouted}")
    require(evidence["pallas_entries"] > 0, "the Pallas hasher was not reached")
    require(
        not evidence["declines"],
        f"routes declined to the host: {evidence['declines']}",
    )
    return evidence


def run_one_chip() -> None:
    from ethereum_consensus_tpu import ops

    since = counters()
    check_native()
    world = generate(VALIDATORS, N_BLOCKS, ATTESTATIONS)
    require(
        world["signature_sets"] == N_BLOCKS * (ATTESTATIONS + 3),
        f"the chain carries {world['signature_sets']} signature sets, not "
        f"{N_BLOCKS} x ({ATTESTATIONS} + 3)",
    )
    roots = host_reference(world)
    # its defaults, no threshold overrides (install also drops the shuffle
    # memo the host reference has just filled, so the served path shuffles)
    ops.install()
    cold_state_root(world, roots)
    state = stream_blocks(world, roots)
    epoch_boundary(state, world, roots)
    check_evidence(since)


# ---------------------------------------------------------------------------
# four chips: the mesh path and what it is compared with, nothing else
# ---------------------------------------------------------------------------


def provision_mesh(n_devices: int):
    """``ECT_MESH=N`` through the runtime's own switch; a decline (for
    one, ``devices_unavailable``) is a failure here."""
    import jax

    from ethereum_consensus_tpu.parallel import runtime

    with phase("mesh_provision") as record:
        os.environ[runtime.MESH_ENV] = str(n_devices)
        mesh = runtime.mesh()
        record["status"] = runtime.status()
        require(
            jax.device_count() == n_devices,
            f"jax sees {jax.device_count()} devices, not {n_devices}",
        )
        require(
            mesh is not None
            and len({d.id for d in mesh.devices.flat}) == n_devices,
            f"mesh not provisioned over {n_devices} devices: "
            f"{runtime.status()}",
        )
    return mesh


def mesh_epoch(validators: int, seed: int = SEED) -> None:
    """One mesh-sharded fused epoch pass (``MeshEpochSweeps.fused``)
    against the same kernel body run whole-array by numpy."""
    import numpy as np

    from ethereum_consensus_tpu.config import Context
    from ethereum_consensus_tpu.models import epoch_vector
    from ethereum_consensus_tpu.models.altair.constants import (
        PARTICIPATION_FLAG_WEIGHTS,
        TIMELY_HEAD_FLAG_INDEX,
        TIMELY_TARGET_FLAG_INDEX,
        WEIGHT_DENOMINATOR,
    )
    from ethereum_consensus_tpu.models.phase0.helpers import integer_squareroot
    from ethereum_consensus_tpu.parallel import runtime

    context = Context.for_mainnet()
    rng = np.random.default_rng(seed)
    n = validators
    increment = int(context.EFFECTIVE_BALANCE_INCREMENT)
    eff = (rng.integers(16, 33, n, dtype=np.uint64)) * np.uint64(increment)
    columns = dict(
        balances=eff + rng.integers(0, increment, n, dtype=np.uint64),
        eff=eff,
        prev_part=rng.integers(0, 8, n, dtype=np.uint8),
        slashed=rng.random(n) < 0.01,
        active_prev=rng.random(n) < 0.97,
        eligible=rng.random(n) < 0.98,
        scores=rng.integers(0, 100, n, dtype=np.uint64),
    )
    bias = int(context.inactivity_score_bias)
    total_active = int(eff[columns["active_prev"]].sum())
    scalars = dict(
        increment=increment,
        brpi=increment * int(context.BASE_REWARD_FACTOR)
        // integer_squareroot(total_active),
        active_increments=total_active // increment,
        denominator=bias * int(context.INACTIVITY_PENALTY_QUOTIENT_BELLATRIX),
    )
    statics = dict(
        bias=bias,
        recovery_rate=int(context.inactivity_score_recovery_rate),
        weights=tuple(int(w) for w in PARTICIPATION_FLAG_WEIGHTS),
        weight_denominator=int(WEIGHT_DENOMINATOR),
        leaking=False,
        head_flag_index=int(TIMELY_HEAD_FLAG_INDEX),
        target_flag_index=int(TIMELY_TARGET_FLAG_INDEX),
    )
    with phase("mesh_epoch") as record:
        t0 = time.perf_counter()
        want_scores, want_balances, wrapped = epoch_vector.fused_epoch_kernel(
            np, *columns.values(),
            *(np.uint64(v) for v in scalars.values()),
            *statics.values(),
        )
        record["host_s"] = round(time.perf_counter() - t0, 3)
        require(int(wrapped) == 0, "the synthetic columns wrapped a u64 lane")
        runner = runtime.epoch_sweeps(n)
        require(runner is not None, f"mesh epoch declined: {runtime.status()}")
        t0 = time.perf_counter()
        got = runner.fused(**columns, **scalars, **statics)
        record["mesh_s"] = round(time.perf_counter() - t0, 3)
        record["validators"] = n
        require(got is not None, "mesh fused pass reported a u64 wrap")
        require(
            np.array_equal(got[0], want_scores)
            and np.array_equal(got[1], want_balances),
            "mesh fused epoch pass differs from the host kernel",
        )


def mesh_pairing(n_sets: int) -> None:
    """One RLC flush window through ``verify_signature_sets``, sharded
    over the mesh by ``parallel/pairing.py batch_verify_sharded``: all
    valid, then one set tampered — against the host engine's verdicts,
    taken before the routing is installed."""
    import chain_utils

    from ethereum_consensus_tpu import ops
    from ethereum_consensus_tpu.crypto import bls

    sets = []
    for i in range(n_sets):
        message = i.to_bytes(32, "big")
        sets.append(
            bls.SignatureSet(
                [chain_utils.secret_key(i).public_key()],
                message,
                chain_utils.secret_key(i).sign(message),
            )
        )
    tampered = list(sets)
    victim = n_sets // 3
    tampered[victim] = bls.SignatureSet(
        sets[victim].public_keys, b"\xff" * 32, sets[victim].signature
    )
    with phase("mesh_pairing") as record:
        t0 = time.perf_counter()
        want = [bls.verify_signature_sets(s) for s in (sets, tampered)]
        record["host_s"] = round(time.perf_counter() - t0, 3)
        require(
            all(want[0]) and want[1].count(False) == 1
            and want[1][victim] is False,
            "host verdicts are not what the sets were built to give",
        )
        ops.install()  # defaults: >= 512 sets route to the device pairing
        t0 = time.perf_counter()
        got = []
        for batch in (sets, tampered):
            got.append(bls.verify_signature_sets(batch))
            require(
                bls.last_batch_route() == "device",
                "the flush did not take the device pairing route",
            )
        record["mesh_s"] = round(time.perf_counter() - t0, 3)
        record["sets"] = n_sets
        require(got == want, "mesh pairing verdicts differ from the host's")


def mesh_merkle(chunks: int, seed: int = SEED) -> None:
    """One flat tree of ``chunks`` 32-byte chunks through the mesh hook of
    ``ssz.merkle.merkleize_chunks`` (``sharded_merkleize_chunks``) against
    the native whole-tree walk."""
    import numpy as np

    from ethereum_consensus_tpu import native
    from ethereum_consensus_tpu.ssz import merkle

    data = np.random.default_rng(seed).bytes(chunks * 32)
    depth = (chunks - 1).bit_length()
    with phase("mesh_merkle") as record:
        t0 = time.perf_counter()
        want = native.merkle_root_native(
            data, depth,
            b"".join(merkle.zero_hash(level) for level in range(depth + 1)),
        )
        record["host_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        got = merkle.merkleize_chunks(data)
        record["mesh_s"] = round(time.perf_counter() - t0, 3)
        record["chunks"] = chunks
        require(got == want, "mesh merkle root differs from the native walk")


def check_mesh_evidence(since: dict, n_devices: int) -> dict:
    """Engaged, never declined, and every sharded kernel's inputs and
    outputs laid out over all the devices (everything landing on device 0
    is the failure to look for)."""
    from ethereum_consensus_tpu.telemetry import device as tel_device

    span = tel_device.OBSERVATORY.device_span()
    now = counters()
    evidence = {
        "phase": "mesh_evidence",
        "mesh_engage": meters.moved(since, now).get("mesh.engage", 0),
        "declines": meters.declines(since, now),
        "device_span": {name: span.get(name) for name in MESH_KERNELS},
        "routes": {
            kind: choices
            for kind, choices in
            tel_device.OBSERVATORY.route_tallies().items()
            if kind.startswith("mesh.")
        },
    }
    print(json.dumps(evidence), flush=True)
    require(evidence["mesh_engage"] > 0, "the mesh never engaged")
    require(
        not evidence["declines"],
        f"routes declined to the host: {evidence['declines']}",
    )
    for name, seen in evidence["device_span"].items():
        require(
            seen == {"args": n_devices, "outs": n_devices},
            f"{name} spans {seen}, not {n_devices} devices",
        )
    return evidence


def run_mesh(n_devices: int) -> None:
    since = counters()
    check_native()
    provision_mesh(n_devices)
    mesh_epoch(VALIDATORS)
    mesh_merkle(MESH_CHUNKS)
    mesh_pairing(MESH_SETS)
    check_mesh_evidence(since, n_devices)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: the main path on one chip (default); 4: only the mesh "
        "path, on a four-chip host",
    )
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU — jax found {device}", file=sys.stderr)
        return 3
    if device["count"] < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but jax found {device}",
            file=sys.stderr,
        )
        return 3

    # importing ops turns the persistent compile cache on; it installs no
    # routing (ops.install() does, after the host reference)
    from ethereum_consensus_tpu import _jax_cache, ops  # noqa: F401
    from ethereum_consensus_tpu.telemetry import device as tel_device

    tel_device.start()
    _meter()
    print(
        json.dumps(
            {"phase": "start", "device": device, "chips": args.chips,
             "compile_cache": _jax_cache.status()}
        ),
        flush=True,
    )
    if args.chips == 4:
        run_mesh(4)
    else:
        run_one_chip()
    tel_device.stop()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
