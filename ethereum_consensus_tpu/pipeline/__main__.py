"""``python -m ethereum_consensus_tpu.pipeline --selfcheck`` — smoke the
pipeline end-to-end without pytest.

Two tiers, best available wins:

* **chain tier** (repo checkout: ``tests/chain_utils.py`` importable) —
  build a toy minimal-preset chain, replay it pipelined vs sequential,
  require bit-identical roots; then tamper a mid-stream block signature
  and require rollback to the last committed state with the structured
  error.
* **window tier** (installed package, no test scaffolding) — drive the
  scheduler + signature-window machinery directly with real BLS keys,
  including a tampered-set rollback-attribution check.

Telemetry exports (docs/OBSERVABILITY.md):

* ``--lanes N``          — run the chain tier with N verifier lanes
  (``FlushPolicy.verify_lanes``): windows fan over N FIFO workers,
  settle order preserved — the multi-core blocks/s shape.

* ``--trace-out PATH``   — record every span/event of the selfcheck and
  write a Chrome trace-event JSON (Perfetto / ``chrome://tracing``):
  stage A and the background verifier render as separate tracks with
  flush dispatch/verify/settle windows and rollbacks visible.
* ``--metrics-out PATH`` — dump the process-wide metrics registry
  snapshot (digests, pubkey-cache hit rates, flush shapes, ...) as JSON.
* ``--memory-out PATH`` — run the memory & bandwidth observatory for
  the selfcheck and write its ledgers (census/worst table, phase RSS
  ledger, bulk-copy sites) as JSON
* ``--device-out PATH``  — run the device execution observatory
  (``telemetry/device.py``) for the selfcheck's duration and dump its
  ledgers (compile ledger + recompile sentinel, per-site host<->device
  transfer bytes, the device-vs-host routing journal) as JSON. The
  three `-out` flags together are ``make profile``'s capture artifact.
* ``--serve PORT``       — run the live introspection server
  (``telemetry/server.py``: /metrics Prometheus exposition, /healthz,
  /blocks lineage, /events SSE) for the selfcheck's duration; 0 picks
  an ephemeral port. ``--hold SECONDS`` keeps it up after the checks
  finish so you can scrape/curl around (``make serve``).
* ``--serve-data``       — additionally mount the Beacon-API read data
  plane (``serving/``: validators, balances, committees, duties, ...)
  fed by the selfcheck replay's commits (``make serve-data``); requires
  ``--serve``.

Exit code 0 = all checks passed; any failure prints the reason and
exits 1.
"""

from __future__ import annotations

import sys
from pathlib import Path


def _find_chain_utils() -> bool:
    """Make tests/chain_utils importable when running from a repo
    checkout; False when only the installed package exists."""
    tests_dir = Path(__file__).resolve().parents[2] / "tests"
    if (tests_dir / "chain_utils.py").is_file():
        sys.path.insert(0, str(tests_dir))
        return True
    return False


def _selfcheck_chain(lanes: int = 1) -> None:
    from chain_utils import fresh_genesis, make_attestation, produce_block

    from ..error import InvalidBlock
    from ..executor import Executor
    from ..models.phase0.state_transition import (
        Validation as P0Validation,
        state_transition_block_in_slot as p0_transition,
    )
    from . import ChainPipeline, FlushPolicy

    state, ctx = fresh_genesis(64, "minimal")
    scratch = state.copy()
    blocks = []
    pending_atts = []
    n_blocks = 6
    for slot in range(1, n_blocks + 1):
        block = produce_block(scratch, slot, ctx, attestations=pending_atts)
        p0_transition(scratch, block, P0Validation.ENABLED, ctx)
        pending_atts = [make_attestation(scratch, slot, 0, ctx)]
        blocks.append(block)

    # pipelined replay must be bit-identical to sequential
    sequential = Executor(state.copy(), ctx)
    for block in blocks:
        sequential.apply_block(block)
    pipelined = Executor(state.copy(), ctx)
    stats = pipelined.stream(
        blocks,
        policy=FlushPolicy(
            window_size=3,
            max_in_flight=max(2, lanes),
            verify_lanes=lanes,
        ),
    )
    if pipelined.state.hash_tree_root() != sequential.state.hash_tree_root():
        raise AssertionError("pipelined root != sequential root")
    if lanes > 1:
        print(f"chain tier: {lanes} verifier lanes, settle order preserved")
    if stats.blocks_committed != n_blocks:
        raise AssertionError(f"committed {stats.blocks_committed}/{n_blocks}")
    print(
        f"chain tier: {n_blocks} blocks bit-identical; "
        f"flushes={stats.flushes} occ={stats.occupancy()}"
    )

    # mid-stream invalid proposer signature (a VALID G2 point signing the
    # wrong message, so it survives parsing and fails only at the pairing):
    # rollback + structured error
    bad = blocks[3].copy()
    bad.signature = bytes(blocks[2].signature)
    broken = Executor(state.copy(), ctx)
    pipe = ChainPipeline(broken, policy=FlushPolicy(window_size=2))
    caught = None
    try:
        for block in blocks[:3] + [bad] + blocks[4:]:
            pipe.submit(block)
        pipe.close()
    except Exception as exc:  # noqa: BLE001 — selfcheck inspects it
        caught = exc
    if not isinstance(caught, InvalidBlock):
        raise AssertionError(f"expected InvalidBlock, got {caught!r}")
    expect = Executor(state.copy(), ctx)
    for block in blocks[:3]:
        expect.apply_block(block)
    if broken.state.hash_tree_root() != expect.state.hash_tree_root():
        raise AssertionError("rollback state != last committed prefix")
    print("chain tier: mid-stream rollback + structured error OK")


def _selfcheck_window() -> None:
    from ..crypto import bls
    from ..error import InvalidAttestation
    from ..models.signature_batch import SignatureBatch
    from .scheduler import FlushPolicy, VerifyScheduler, Window
    from .stats import PipelineStats

    sks = [bls.SecretKey(i + 101) for i in range(6)]
    stats = PipelineStats()
    stats.start()
    sched = VerifyScheduler(FlushPolicy(window_size=3, max_in_flight=2), stats)

    def make_batch(tamper: bool) -> SignatureBatch:
        batch = SignatureBatch()
        for i, sk in enumerate(sks):
            msg = b"selfcheck-%d" % i
            sig = sk.sign(msg if not tamper or i != 3 else b"wrong")
            batch.defer(
                [sk.public_key()], msg, sig, InvalidAttestation(f"set {i}")
            )
        return batch

    good, bad = make_batch(False), make_batch(True)
    sched.dispatch(Window([None], good, None, 0))
    sched.dispatch(Window([None], bad, None, 1))
    if not sched.full:
        raise AssertionError("bounded queue did not fill at cap")
    _, verdicts = sched.settle_oldest()
    if not all(verdicts):
        raise AssertionError("valid window rejected")
    _, verdicts = sched.settle_oldest()
    if verdicts.index(False) != 3:
        raise AssertionError(f"bad set misattributed: {verdicts}")
    stats.stop()
    print(
        f"window tier: coalesced verify + attribution OK "
        f"(high_watermark={stats.queue_high_watermark})"
    )


def _flag_value(argv: "list[str]", flag: str) -> "str | None":
    if flag in argv:
        at = argv.index(flag)
        if at + 1 >= len(argv):
            raise SystemExit(f"{flag} requires a path argument")
        return argv[at + 1]
    return None


def main(argv: "list[str]") -> int:
    trace_out = _flag_value(argv, "--trace-out")
    metrics_out = _flag_value(argv, "--metrics-out")
    device_out = _flag_value(argv, "--device-out")
    memory_out = _flag_value(argv, "--memory-out")
    serve_port = _flag_value(argv, "--serve")
    hold_s = _flag_value(argv, "--hold")
    lanes = int(_flag_value(argv, "--lanes") or "1")
    if "--selfcheck" not in argv:
        print(__doc__)
        return 2
    from ..telemetry import device as device_obs
    from ..telemetry import memory as memory_obs
    from ..telemetry import metrics, spans

    server = None
    store = None
    if serve_port is not None:
        from ..telemetry.server import IntrospectionServer

        server = IntrospectionServer(port=int(serve_port)).start()
        print(
            f"introspection server on {server.url()} "
            "(/metrics /healthz /blocks /events)"
        )
        if "--serve-data" in argv:
            from ..serving import BeaconDataPlane, HeadStore

            store = HeadStore().attach()
            server.mount(BeaconDataPlane(store))
            print(
                f"beacon data plane mounted on {server.url('/eth/')} "
                "(validators, balances, committees, duties — fed by the "
                "selfcheck replay's commits)"
            )
    elif "--serve-data" in argv:
        raise SystemExit("--serve-data requires --serve PORT")
    if trace_out:
        spans.start_recording()
    if device_out:
        device_obs.start()
    if memory_out:
        memory_obs.start()
    try:
        if _find_chain_utils():
            _selfcheck_chain(lanes=lanes)
        _selfcheck_window()
    except Exception as exc:  # noqa: BLE001 — smoke must report, not crash
        print(f"SELFCHECK FAILED: {type(exc).__name__}: {exc}")
        if store is not None:
            store.detach()
        if server is not None:
            server.stop()
        return 1
    finally:
        if trace_out:
            spans.stop_recording()
            spans.write_chrome_trace(trace_out)
            print(f"chrome trace written: {trace_out}")
        if metrics_out:
            import json

            with open(metrics_out, "w", encoding="utf-8") as f:
                json.dump(metrics.snapshot(), f, indent=1, sort_keys=True)
            print(f"metrics snapshot written: {metrics_out}")
        if device_out:
            import json

            device_obs.stop()
            with open(device_out, "w", encoding="utf-8") as f:
                json.dump(
                    device_obs.snapshot(), f, indent=1, sort_keys=True
                )
            print(f"device ledger written: {device_out}")
        if memory_out:
            import json

            memory_obs.stop()
            with open(memory_out, "w", encoding="utf-8") as f:
                json.dump(
                    memory_obs.snapshot(), f, indent=1, sort_keys=True
                )
            print(f"memory ledger written: {memory_out}")
    print("selfcheck OK")
    if server is not None:
        if hold_s is not None and float(hold_s) > 0:
            import time as _time

            print(
                f"holding the introspection server for {hold_s}s "
                f"({server.url('/blocks')} has the selfcheck's lineage)"
            )
            if store is not None and store.head is not None:
                print(
                    f"data plane head: slot {store.head.slot} — try "
                    f"{server.url('/eth/v1/beacon/states/head/validators?id=0,1,2')}"
                )
            _time.sleep(float(hold_s))
        if store is not None:
            store.detach()
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
