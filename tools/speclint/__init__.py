"""speclint — AST static analysis for the invariants review can't hold.

Four analyzers (see ``docs/SPECLINT.md`` for the rule catalog):

* ``forkdiff``   — drift among the six near-copy ``models/<fork>/``
                   packages (shadowed duplicates, drifted copies,
                   missing re-exports, signature divergence).
* ``mutation``   — SSZ mutation purity in ``models/`` + ``pipeline/``:
                   every write must flow through the instrumented
                   surface ``ssz/core.py`` manifests, or incremental
                   hash_tree_root serves stale roots.
* ``concurrency``— shared mutable state in ``pipeline/`` +
                   ``telemetry/`` + ``crypto/bls.py`` +
                   ``models/ops_vector.py`` + the trace facade must be
                   lock-dominated; bare threading primitives outside
                   the blessed set flag.
* ``aliasflow``  — alias-dataflow purity over the mutation scope: a
                   buffer stored into a container field then mutated
                   through the stale alias, and in-place mutation of a
                   registry-column cache buffer (the ROADMAP-noted gap
                   the columnar engine made load-bearing).
* ``lockorder``  — lock acquisition ORDER over the concurrency scope:
                   a pair of locks taken in opposite orders on two
                   paths deadlocks the pipeline's two threads (the
                   ROADMAP-noted gap closed when the scenario
                   FaultInjector added a second lock to pipeline/).
* ``device``     — compile-once + transfer-seam discipline over the
                   WHOLE package: jit staging outside the blessed
                   factories, per-call-varying values reaching static
                   jit args, shape-dependent Python branching inside
                   kernel bodies, and host↔device transfers that dodge
                   the instrumented ``telemetry.device`` chokepoints.
* ``declines``   — no silent fallbacks: broad except handlers and
                   threshold early-returns on routed paths must reach a
                   counter/journal, and every decline-reason literal
                   must be documented in docs/OBSERVABILITY.md.
* ``obscontract``— the observability contract, both directions: every
                   emittable counter/gauge/histogram has a doc-table
                   row, every doc row has an emitting site, and journal
                   kinds + one-shot trace events appear in the docs.
* ``envflags``   — EC_*/ECT_* environment flags flow through the
                   central ``_env`` readers, are registered in
                   ``_env.KNOWN_KEYS``, are documented, and never land
                   after (or outside the blessed dirs, before) a
                   module-level jax import.

Run: ``python -m tools.speclint [--format text|json|sarif] [--changed]
[paths...]`` — or through the tier-1 gate ``tests/test_speclint.py``
(zero non-allowlisted findings over the repo). Exceptions live in
``allowlist.toml`` with a required justification AND a required spec/doc
citation each; stale or citation-less entries hard-fail.
"""

from __future__ import annotations

import os

from . import (
    aliasflow,
    concurrency,
    declines,
    device,
    envflags,
    forkdiff,
    lockorder,
    mutation,
    obscontract,
)
from .allowlist import ALLOWLIST_PATH, Allowlist, AllowlistError
from .base import Finding, iter_py_files

__all__ = [
    "Allowlist",
    "AllowlistError",
    "ALLOWLIST_PATH",
    "Finding",
    "run",
    "REPO_ROOT",
]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_PKG = "ethereum_consensus_tpu"


def _default_targets(root: str) -> dict:
    return {
        "models_dir": os.path.join(root, _PKG, "models"),
        "mutation_paths": iter_py_files(
            os.path.join(root, _PKG, "models"),
            os.path.join(root, _PKG, "pipeline"),
            # scenario mutators corrupt SSZ blocks — through sanctioned
            # channels only, or incremental roots would serve stale bytes
            os.path.join(root, _PKG, "scenarios"),
            # the serving data plane reads snapshot states + column
            # views; any write it made would corrupt a served snapshot —
            # and the column views handed to reader threads are exactly
            # the alias class aliasflow guards
            os.path.join(root, _PKG, "serving"),
            # the operation pool admits ops by running spec processors
            # on scratch states and stores SSZ containers it later
            # serves/produces — its writes must stay on the sanctioned
            # surface, and its bitfield matrices are aliasflow's
            # column-buffer class
            os.path.join(root, _PKG, "pool"),
            # the mesh layer pads/ships epoch columns and flush batches
            # to devices — any in-place write to a shared column buffer
            # before the dispatch would corrupt the host twin it must
            # stay bit-identical to (aliasflow's column-buffer class)
            os.path.join(root, _PKG, "parallel"),
            # the soak runner holds committed states, oracle prefixes,
            # and pool schedules across thousands of cycles — a stray
            # write through any of them breaks the bit-identity gate it
            # itself asserts
            os.path.join(root, _PKG, "soak"),
            # the proof plane reads the SAME memo trees a served
            # snapshot's hash_tree_root settled — a stray write through
            # its providers would corrupt every later branch AND the
            # snapshot root it must verify against
            os.path.join(root, _PKG, "proofs"),
            # column-primary list storage makes raw base-list calls ON
            # PURPOSE, like ssz/core.py — but only the two the manifest
            # names (COLUMN_LIST_RAW_CALLS); every other one is a finding
            os.path.join(root, _PKG, "ssz", "column_list.py"),
        ),
        "concurrency_paths": iter_py_files(
            os.path.join(root, _PKG, "pipeline"),
            os.path.join(root, _PKG, "telemetry"),
            os.path.join(root, _PKG, "crypto", "bls.py"),
            os.path.join(root, _PKG, "utils", "trace.py"),
            # the columnar engines keep process-wide state (one-shot
            # fallback events, the preparer registry) — lock-checked
            os.path.join(root, _PKG, "models", "ops_vector.py"),
            # the columnar-primary epoch engine's write path: adopted
            # arrays become shared column caches, and its fallback
            # one-shot set mirrors ops_vector's
            os.path.join(root, _PKG, "models", "epoch_vector.py"),
            # the committee-mask kernel (ISSUE 14): a process-wide
            # one-shot fallback set + per-state memos shared across
            # copies — the same lock discipline as the engines above
            os.path.join(root, _PKG, "models", "committees.py"),
            # the scenario harness drives the pipeline from test/driver
            # threads while the FaultInjector is read on the worker
            os.path.join(root, _PKG, "scenarios"),
            # the serving layer is concurrent by construction: handler
            # threads share the HeadStore and per-snapshot lazy builds
            os.path.join(root, _PKG, "serving"),
            # the pool's admission windows, in-flight futures, and
            # store maps are shared between POST handler threads, the
            # settling thread, and the spam/producer drivers — lock
            # discipline and acquisition order are load-bearing
            os.path.join(root, _PKG, "pool"),
            # the mesh runtime provisions once per process under a
            # double-checked lock while epoch passes, verifier lanes,
            # and merkle rebuilds consult it concurrently; its decline
            # one-shot set mirrors epoch_vector's fallback discipline
            os.path.join(root, _PKG, "parallel"),
            # proof extraction runs on handler threads against shared
            # snapshots (the ProofContext memo + the fallback one-shot
            # set are cross-thread state in the serving path)
            os.path.join(root, _PKG, "proofs"),
            # the soak drives reader/SSE/spam threads against the
            # pipeline driver concurrently; its sentinel and subscriber
            # state must stay lock-disciplined
            os.path.join(root, _PKG, "soak"),
        ),
        "core_path": os.path.join(root, _PKG, "ssz", "core.py"),
        # the v2 analyzer families (device / declines / obscontract /
        # envflags) run over the ENTIRE package: recompile hazards,
        # silent declines, metric drift, and stray env reads are not
        # confined to any subsystem list that would stay current
        "package_paths": iter_py_files(os.path.join(root, _PKG)),
    }


def run(
    root: "str | None" = None,
    paths: "list | None" = None,
    allowlist_path: "str | None" = None,
) -> list:
    """The full suite over the repo: every analyzer on its default
    scope, allowlist applied, stale allowlist entries reported. When
    ``paths`` is given, findings are filtered to files under those paths
    (and stale-allowlist reporting is skipped — a partial run can't
    judge staleness)."""
    root = root or REPO_ROOT
    targets = _default_targets(root)
    findings: list[Finding] = []
    findings.extend(forkdiff.analyze_models(targets["models_dir"], root))
    findings.extend(
        mutation.analyze(targets["mutation_paths"], root, targets["core_path"])
    )
    findings.extend(concurrency.analyze(targets["concurrency_paths"], root))
    findings.extend(aliasflow.analyze(targets["mutation_paths"], root))
    # lock order aggregates over the SAME scope the concurrency rules
    # police — both halves of a deadlock rarely sit in one file
    findings.extend(lockorder.analyze(targets["concurrency_paths"], root))
    findings.extend(device.analyze(targets["package_paths"], root))
    findings.extend(declines.analyze(targets["package_paths"], root))
    findings.extend(obscontract.analyze(targets["package_paths"], root))
    findings.extend(envflags.analyze(targets["package_paths"], root))

    if paths:
        wanted = [
            os.path.relpath(os.path.abspath(p), root).replace(os.sep, "/")
            for p in paths
        ]
        findings = [
            f
            for f in findings
            if any(f.path == w or f.path.startswith(w + "/") for w in wanted)
        ]

    allow = Allowlist.load(allowlist_path or ALLOWLIST_PATH)
    allow.apply(findings)
    if not paths:
        findings.extend(allow.stale_entries())
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
