"""speclint: the repo-wide gate plus the linter's own self-tests.

Three layers:

* THE GATE — ``test_repo_has_no_open_findings`` runs the full suite over
  the package and fails on any non-allowlisted finding. On failure the
  JSON report is written as an artifact (``SPECLINT_ARTIFACT_DIR``,
  default the system temp dir) so findings are readable without
  re-running locally.
* SELF-TESTS — every rule must catch its seeded violation in
  ``tests/speclint_fixtures/`` (and must NOT flag the sanctioned twins),
  so the linter cannot rot into a no-op. The fork-diff fixture
  reproduces the PR 2 ``Validation``-enum bug verbatim — the regression
  guard for that bug class.
* LOCKSTEP — the static manifest the mutation analyzer consumes
  (``ssz/core.py``'s ``INSTRUMENTED_LIST_MUTATORS``) must match the
  methods actually instrumented on ``CachedRootList`` at runtime.
"""

import json
import os
import subprocess
import sys
import tempfile

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools import speclint
from tools.speclint import (
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
from tools.speclint.allowlist import Allowlist, AllowlistError

REPO_ROOT = speclint.REPO_ROOT
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "speclint_fixtures")
CORE_PATH = os.path.join(REPO_ROOT, "ethereum_consensus_tpu", "ssz", "core.py")


# ---------------------------------------------------------------------------
# the tier-1 gate
# ---------------------------------------------------------------------------


def test_repo_has_no_open_findings():
    findings = speclint.run()
    open_findings = [f for f in findings if not f.allowlisted]
    if open_findings:
        artifact_dir = os.environ.get("SPECLINT_ARTIFACT_DIR", tempfile.gettempdir())
        os.makedirs(artifact_dir, exist_ok=True)
        artifact = os.path.join(artifact_dir, "speclint_report.json")
        with open(artifact, "w", encoding="utf-8") as f:
            json.dump([x.to_dict() for x in findings], f, indent=2)
        listing = "\n".join(x.format_text() for x in open_findings)
        pytest.fail(
            f"{len(open_findings)} open speclint finding(s) — fix or "
            f"allowlist with justification (full JSON report: {artifact}):\n"
            f"{listing}"
        )


def test_cli_exits_zero_on_clean_repo():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.speclint", "--format", "json"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["open"] == 0


# ---------------------------------------------------------------------------
# fork-diff self-tests (fixture seeds one violation per rule)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def forkdiff_findings():
    return forkdiff.analyze_models(
        os.path.join(FIXTURES, "forkdiff_models"), REPO_ROOT
    )


def _rules_by_symbol(findings):
    return {(f.rule, f.symbol) for f in findings}


def test_forkdiff_redetects_the_pr2_validation_bug(forkdiff_findings):
    """The acceptance regression guard: a fork module carrying a private
    duplicate of the shared skeleton's Validation enum must flag."""
    hits = [
        f
        for f in forkdiff_findings
        if f.rule == "forkdiff/shadowed-duplicate"
        and f.symbol == "phase0/state_transition.Validation"
    ]
    assert len(hits) == 1, forkdiff_findings
    assert "Validation" in hits[0].message
    assert hits[0].path.endswith("phase0/state_transition.py")
    assert hits[0].line > 0


def test_forkdiff_catches_drifted_copy(forkdiff_findings):
    assert (
        "forkdiff/drifted-copy",
        "altair/state_transition.process_slots",
    ) in _rules_by_symbol(forkdiff_findings)


def test_forkdiff_catches_missing_reexport(forkdiff_findings):
    assert (
        "forkdiff/missing-reexport",
        "altair/state_transition.Validation",
    ) in _rules_by_symbol(forkdiff_findings)


def test_forkdiff_catches_signature_divergence(forkdiff_findings):
    assert (
        "forkdiff/signature-divergence",
        "altair/state_transition.helper",
    ) in _rules_by_symbol(forkdiff_findings)


def test_forkdiff_no_false_positive_on_reexport(forkdiff_findings):
    """state_transition is imported (re-exported) by fixture altair —
    must not flag as missing or drifted."""
    assert not any(
        f.symbol == "altair/state_transition.state_transition"
        for f in forkdiff_findings
    )


def test_forkdiff_real_models_late_binding_not_flagged():
    """The repo's own process_slots (identical text per fork, but calling
    each fork's OWN process_epoch) is deliberate late-binding — the
    binding-key guard must keep it out of drifted-copy."""
    models_dir = os.path.join(REPO_ROOT, "ethereum_consensus_tpu", "models")
    findings = forkdiff.analyze_models(models_dir, REPO_ROOT)
    assert not any(
        f.rule == "forkdiff/drifted-copy" and f.symbol.endswith(".process_slots")
        for f in findings
    )


def test_render_forkdiff_report():
    models_dir = os.path.join(REPO_ROOT, "ethereum_consensus_tpu", "models")
    report = forkdiff.render_forkdiff(models_dir, REPO_ROOT)
    assert "phase0" in report and "electra" in report
    assert "## state_transition" in report


# ---------------------------------------------------------------------------
# mutation-purity self-tests
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mutation_findings():
    return mutation.analyze(
        [os.path.join(FIXTURES, "mutation_violations.py")], REPO_ROOT, CORE_PATH
    )


@pytest.mark.parametrize(
    "rule,symbol",
    [
        ("mutation/raw-list-call", "bad_raw_list_call"),
        ("mutation/setattr-bypass", "bad_setattr_bypass"),
        ("mutation/dict-bypass", "bad_dict_write"),
        ("mutation/dict-bypass", "bad_dict_update"),
        ("mutation/deepcopy", "bad_deepcopy"),
    ],
)
def test_mutation_catches_seeded_violation(mutation_findings, rule, symbol):
    assert (rule, symbol) in _rules_by_symbol(mutation_findings)


def test_mutation_memo_writes_not_flagged(mutation_findings):
    assert not any(f.symbol == "ok_memo_write" for f in mutation_findings)


def test_mutation_rules_derive_from_manifest():
    """The analyzer reads the instrumented surface out of ssz/core.py's
    AST; the static read must agree with the runtime manifest."""
    from ethereum_consensus_tpu.ssz import core as ssz_core

    static = mutation.load_manifest(CORE_PATH)
    assert static["list_mutators"] == ssz_core.INSTRUMENTED_LIST_MUTATORS
    assert (
        static["bulk_mutators"]
        == ssz_core.instrumented_surface()["bulk_mutators"]
    )


def test_manifest_matches_instrumented_runtime_methods():
    """Every name in the manifest is actually a wrapped (non-list-base)
    method on CachedRootList, and no other base list mutator slipped in
    uninstrumented — the manifest, the analyzer, and the runtime agree."""
    from ethereum_consensus_tpu.ssz.core import (
        INSTRUMENTED_LIST_MUTATORS,
        CachedRootList,
        instrumented_surface,
    )

    for name in INSTRUMENTED_LIST_MUTATORS:
        assert getattr(CachedRootList, name) is not getattr(list, name), name
    surface = instrumented_surface()
    assert surface["list_mutators"] == INSTRUMENTED_LIST_MUTATORS
    assert set(surface["public_list_mutators"]) == {
        n for n in INSTRUMENTED_LIST_MUTATORS if not n.startswith("__")
    }


def test_column_list_module_is_in_scope_with_its_sanctioned_raw_calls(tmp_path):
    """Column-primary storage (ssz/column_list.py) makes raw base-list
    calls on purpose, and only the ones ssz/core.py's manifest names: the
    analyzer covers the module, passes those, and flags any other."""
    from ethereum_consensus_tpu.ssz import core as ssz_core

    static = mutation.load_manifest(CORE_PATH)
    surface = ssz_core.instrumented_surface()["column_list"]
    assert static["column_list_module"] == surface["module"]
    assert static["column_list_raw_calls"] == surface["raw_list_calls"]
    real = os.path.join(
        REPO_ROOT, "ethereum_consensus_tpu", *surface["module"].split("/")
    )
    assert real in speclint._default_targets(REPO_ROOT)["mutation_paths"]
    assert mutation.analyze([real], REPO_ROOT, CORE_PATH) == []
    with open(real) as handle:
        source = handle.read()
    for name in surface["raw_list_calls"]:
        assert f"list.{name}(" in source, name  # sanctioned and really used
    # a copy of the module that also calls a mutator the manifest does not
    # name: flagged there, and the sanctioned calls flagged anywhere else
    seeded = source + "\n\ndef bad_raw_extend(lst):\n    list.extend(lst, [1])\n"
    inside = tmp_path / "pkg" / "ssz" / "column_list.py"
    inside.parent.mkdir(parents=True)
    inside.write_text(seeded)
    outside = tmp_path / "pkg" / "ssz" / "elsewhere.py"
    outside.write_text(seeded)
    found = mutation.analyze([str(inside)], str(tmp_path), CORE_PATH)
    assert _rules_by_symbol(found) == {("mutation/raw-list-call", "bad_raw_extend")}
    found = mutation.analyze([str(outside)], str(tmp_path), CORE_PATH)
    assert len(found) > 1 and {f.rule for f in found} == {"mutation/raw-list-call"}


# ---------------------------------------------------------------------------
# concurrency self-tests
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def concurrency_findings():
    return concurrency.analyze(
        [os.path.join(FIXTURES, "concurrency_violations.py")], REPO_ROOT
    )


def test_concurrency_catches_unlocked_global_write(concurrency_findings):
    assert (
        "concurrency/unlocked-global-write",
        "bad_unlocked_write/_CACHE",
    ) in _rules_by_symbol(concurrency_findings)


def test_concurrency_catches_unlocked_instance_write(concurrency_findings):
    assert (
        "concurrency/unlocked-instance-write",
        "SharedCounter.bad_bump/count",
    ) in _rules_by_symbol(concurrency_findings)


def test_concurrency_catches_bare_primitive(concurrency_findings):
    assert any(
        f.rule == "concurrency/bare-threading-primitive"
        and "Event" in f.symbol
        for f in concurrency_findings
    )


def test_concurrency_locked_twins_not_flagged(concurrency_findings):
    for sym in ("ok_locked_write", "ok_lockfree_read", "SharedCounter.ok_bump"):
        assert not any(f.symbol.startswith(sym) for f in concurrency_findings), sym
    assert not any(
        f.symbol.startswith("SharedCounter.__init__")
        for f in concurrency_findings
    )


# ---------------------------------------------------------------------------
# lockorder self-tests
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lockorder_findings():
    return lockorder.analyze(
        [os.path.join(FIXTURES, "lockorder_violations.py")], REPO_ROOT
    )


def test_lockorder_catches_reversed_acquisition(lockorder_findings):
    assert len(lockorder_findings) == 1, lockorder_findings
    f = lockorder_findings[0]
    assert f.rule == "lockorder/inconsistent-acquisition-order"
    assert f.symbol == "_LOCK_B->_LOCK_A"
    assert "bad_reversed_path" in f.message
    assert "ok_forward_path" in f.message  # names the opposite-order site


def test_lockorder_sanctioned_shapes_not_flagged(lockorder_findings):
    listing = " ".join(f.message for f in lockorder_findings)
    for sym in ("ok_same_order_again", "ok_disjoint_nesting",
                "ok_sequential_not_nested", "ok_closure_resets_stack",
                "Nested.ok_instance_under_module"):
        assert sym not in listing, sym


def test_lockorder_same_name_different_modules_not_aliased(tmp_path):
    """Two modules each defining their own `_LOCK` must not fold into
    one identity (a false cross-module cycle)."""
    a = tmp_path / "mod_a.py"
    b = tmp_path / "mod_b.py"
    a.write_text(
        "import threading\n_LOCK = threading.Lock()\n_OTHER = threading.Lock()\n"
        "def f():\n    with _LOCK:\n        with _OTHER:\n            pass\n"
    )
    b.write_text(
        "import threading\n_LOCK = threading.Lock()\n_OTHER = threading.Lock()\n"
        "def g():\n    with _OTHER:\n        with _LOCK:\n            pass\n"
    )
    findings = lockorder.analyze([str(a), str(b)], str(tmp_path))
    assert findings == [], [f.format_text() for f in findings]


def test_lockorder_scope_covers_pipeline_and_scenarios():
    """The deadlock check must see every file the concurrency rules see
    — pipeline/ (where the second lock landed) and scenarios/ included,
    with zero allowlist entries for either."""
    targets = speclint._default_targets(REPO_ROOT)
    paths = targets["concurrency_paths"]
    pkg = os.path.join(REPO_ROOT, "ethereum_consensus_tpu")
    assert os.path.join(pkg, "pipeline", "faults.py") in paths
    assert os.path.join(pkg, "scenarios", "harness.py") in paths
    assert os.path.join(pkg, "scenarios", "families.py") in paths
    allow = Allowlist.load(speclint.ALLOWLIST_PATH)
    assert not any(
        e.get("rule", "").startswith("lockorder/")
        or "scenarios/" in e.get("path", "")
        for e in allow.entries
    ), "the lockorder/scenarios widening must land with zero allowlist entries"


# ---------------------------------------------------------------------------
# aliasflow self-tests
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def aliasflow_findings():
    return aliasflow.analyze(
        [os.path.join(FIXTURES, "aliasflow_violations.py")], REPO_ROOT
    )


@pytest.mark.parametrize(
    "rule,symbol",
    [
        ("aliasflow/detached-store-mutation", "bad_detached_store"),
        ("aliasflow/detached-store-mutation", "bad_detached_append"),
        ("aliasflow/column-buffer-mutation", "bad_column_write"),
        ("aliasflow/column-buffer-mutation", "bad_column_alias_write"),
        ("aliasflow/column-buffer-mutation", "bad_column_fill"),
    ],
)
def test_aliasflow_catches_seeded_violation(aliasflow_findings, rule, symbol):
    assert (rule, symbol) in _rules_by_symbol(aliasflow_findings)


def test_aliasflow_sanctioned_twins_not_flagged(aliasflow_findings):
    for sym in (
        "ok_mutate_then_store",
        "ok_rebind_clears_taint",
        "ok_column_copy",
        "ok_mutate_through_field",
        "ok_self_attribute",
    ):
        assert not any(
            f.symbol.startswith(sym) for f in aliasflow_findings
        ), sym


def test_aliasflow_scope_covers_the_columnar_engine():
    """models/ops_vector.py (and the whole models/ tree) must be inside
    the aliasflow+mutation scope — the columnar cache is exactly the
    surface these rules exist for."""
    targets = speclint._default_targets(REPO_ROOT)
    ops_vector = os.path.join(
        REPO_ROOT, "ethereum_consensus_tpu", "models", "ops_vector.py"
    )
    assert ops_vector in targets["mutation_paths"]
    assert ops_vector in targets["concurrency_paths"]


# ---------------------------------------------------------------------------
# allowlist contract
# ---------------------------------------------------------------------------


def test_allowlist_requires_justification():
    with pytest.raises(AllowlistError, match="justification"):
        Allowlist(
            [{"rule": "r", "path": "p", "symbol": "s", "justification": "  ",
              "citation": "spec.md"}]
        )


def test_allowlist_requires_citation():
    """A citation-less entry is a hard failure (exit 2), not a warning —
    an exception nobody can check against the spec is not an exception."""
    with pytest.raises(AllowlistError, match="citation"):
        Allowlist(
            [{"rule": "r", "path": "p", "symbol": "s",
              "justification": "a perfectly reasonable justification"}]
        )
    with pytest.raises(AllowlistError, match="citation"):
        Allowlist(
            [{"rule": "r", "path": "p", "symbol": "s",
              "justification": "a perfectly reasonable justification",
              "citation": "   "}]
        )


def test_allowlist_marks_and_reports_stale():
    entries = [
        {
            "rule": "mutation/deepcopy",
            "path": "x.py",
            "symbol": "f",
            "justification": "because",
            "citation": "specs/phase0/beacon-chain.md",
        },
        {
            "rule": "mutation/deepcopy",
            "path": "gone.py",
            "symbol": "g",
            "justification": "stale",
            "citation": "specs/phase0/beacon-chain.md",
        },
    ]
    allow = Allowlist(entries)
    finding = speclint.Finding(
        rule="mutation/deepcopy", path="x.py", line=3, symbol="f", message="m"
    )
    allow.apply([finding])
    assert finding.allowlisted and finding.justification == "because"
    stale = allow.stale_entries()
    assert len(stale) == 1 and stale[0].symbol == "g"
    assert stale[0].rule == "speclint/stale-allowlist"


def test_checked_in_allowlist_is_wellformed():
    allow = Allowlist.load()
    for entry in allow.entries:
        assert len(entry["justification"].strip()) >= 20, (
            "justifications must actually explain the exception: "
            f"{entry['symbol']}"
        )
        assert len(entry["citation"].strip()) >= 10, (
            "citations must point at a spec/doc section: "
            f"{entry['symbol']}"
        )


# ---------------------------------------------------------------------------
# device self-tests (fixture seeds one violation per rule)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def device_findings():
    return device.analyze(
        [os.path.join(FIXTURES, "device_violations.py")], REPO_ROOT
    )


@pytest.mark.parametrize(
    "rule, symbol",
    [
        ("device/jit-outside-staging", "per_call_jit"),
        ("device/jit-outside-staging", "jit_in_loop"),
        ("device/varying-static-jit-arg", "call_with_raw_size/_bucketed"),
        ("device/shape-branch-in-kernel", "branchy_kernel"),
        ("device/unledgered-transfer", "raw_put"),
        ("device/unledgered-transfer", "raw_upload"),
        ("device/unledgered-transfer", "raw_download"),
    ],
)
def test_device_catches_seeded_violation(device_findings, rule, symbol):
    assert (rule, symbol) in _rules_by_symbol(device_findings)


def test_device_sanctioned_twins_not_flagged(device_findings):
    flagged = {f.symbol for f in device_findings}
    for blessed in (
        "staged_factory",
        "jitted_kernels",
        "call_with_log_size",
        "guarded_kernel",
        "host_shape_branch",
        "padded_kernel",
        "ledgered",
    ):
        assert blessed not in flagged, f"{blessed} is a sanctioned idiom"


# ---------------------------------------------------------------------------
# declines self-tests
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def declines_findings():
    return declines.analyze(
        [os.path.join(FIXTURES, "declines_violations.py")],
        REPO_ROOT,
        doc_path=os.path.join(FIXTURES, "declines_doc.md"),
    )


@pytest.mark.parametrize(
    "rule, symbol",
    [
        ("declines/silent-except", "swallow"),
        ("declines/silent-threshold-return", "route_silently/MIN_BATCH"),
        ("declines/undocumented-reason", "unheard_of_reason"),
    ],
)
def test_declines_catches_seeded_violation(declines_findings, rule, symbol):
    assert (rule, symbol) in _rules_by_symbol(declines_findings)


def test_declines_sanctioned_twins_not_flagged(declines_findings):
    flagged = {f.symbol for f in declines_findings}
    for blessed in ("counted", "probed", "route_loudly/MIN_BATCH"):
        assert blessed not in flagged, f"{blessed} records its decline"
    reasons = {
        f.symbol
        for f in declines_findings
        if f.rule == "declines/undocumented-reason"
    }
    assert "below_threshold" not in reasons
    assert "native_error" not in reasons


# ---------------------------------------------------------------------------
# obscontract self-tests
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def obscontract_findings():
    return obscontract.analyze(
        [os.path.join(FIXTURES, "obscontract_violations.py")],
        REPO_ROOT,
        doc_paths=[os.path.join(FIXTURES, "obscontract_doc.md")],
    )


@pytest.mark.parametrize(
    "rule, symbol",
    [
        ("obscontract/undocumented-metric", "fixture.mystery.total"),
        ("obscontract/orphaned-doc-row", "fixture.orphan.total"),
        ("obscontract/undocumented-journal-kind", "fixture.mystery_kind"),
        ("obscontract/undocumented-trace-event", "fixture.mystery_event"),
    ],
)
def test_obscontract_catches_seeded_violation(obscontract_findings, rule, symbol):
    assert (rule, symbol) in _rules_by_symbol(obscontract_findings)


def test_obscontract_documented_names_not_flagged(obscontract_findings):
    flagged = {f.symbol for f in obscontract_findings}
    for blessed in (
        "fixture.documented.total",
        "fixture.depth",
        "fixture.documented_kind",
        "fixture.documented_event",
    ):
        assert blessed not in flagged, f"{blessed} is documented"


def test_obscontract_live_diff_is_empty():
    """The real package ↔ docs diff must be EMPTY both ways: every
    registered metric/journal-kind/trace-event documented, every doc row
    backed by a call site. This is the PR's acceptance bar, pinned."""
    pkg = os.path.join(REPO_ROOT, "ethereum_consensus_tpu")
    findings = obscontract.analyze(speclint.iter_py_files(pkg), REPO_ROOT)
    assert not findings, "\n".join(f.format_text() for f in findings)


# ---------------------------------------------------------------------------
# envflags self-tests
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def envflags_findings():
    fx = os.path.join(FIXTURES, "envflags")
    return envflags.analyze(
        [os.path.join(fx, "_env.py"), os.path.join(fx, "violations.py")],
        REPO_ROOT,
        doc_path=os.path.join(FIXTURES, "envflags_doc.md"),
    )


@pytest.mark.parametrize(
    "rule, symbol",
    [
        ("envflags/eager-jax-import", "<module>"),
        ("envflags/env-read-after-jax-import", "<module>"),
        ("envflags/scattered-env-read", "scattered"),
        ("envflags/unknown-key", "ECT_FX_MYSTERY"),
        ("envflags/undocumented-key", "ECT_FX_UNDOCUMENTED"),
    ],
)
def test_envflags_catches_seeded_violation(envflags_findings, rule, symbol):
    assert (rule, symbol) in _rules_by_symbol(envflags_findings)


def test_envflags_sanctioned_reader_not_flagged(envflags_findings):
    flagged = {f.symbol for f in envflags_findings}
    assert "sanctioned" not in flagged
    documented = {
        f.symbol
        for f in envflags_findings
        if f.rule == "envflags/undocumented-key"
    }
    assert "ECT_FX_DOCUMENTED" not in documented


def test_envflags_live_registry_fully_documented():
    """Every key in the real ``_env.KNOWN_KEYS`` has a row in the
    OBSERVABILITY.md environment-flags table, and no package module
    reads the environ around the central readers."""
    pkg = os.path.join(REPO_ROOT, "ethereum_consensus_tpu")
    findings = envflags.analyze(speclint.iter_py_files(pkg), REPO_ROOT)
    assert not findings, "\n".join(f.format_text() for f in findings)


# ---------------------------------------------------------------------------
# CLI surfaces: SARIF and --changed
# ---------------------------------------------------------------------------


def test_cli_sarif_output():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.speclint", "--format", "sarif"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    sarif = json.loads(proc.stdout)
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "speclint"
    # every allowlisted finding is present, demoted to "note"
    assert all(r["level"] in ("error", "note") for r in run["results"])


def test_cli_changed_mode_runs():
    """--changed must never fail outright: with a clean tree it lints
    nothing (or just the working-set files) and exits 0 on this repo."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.speclint", "--changed"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_report_artifact(tmp_path):
    report = tmp_path / "speclint_report.json"
    proc = subprocess.run(
        [
            sys.executable, "-m", "tools.speclint",
            "--report", str(report),
        ],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(report.read_text())
    assert payload["open"] == 0
    assert isinstance(payload["findings"], list)
