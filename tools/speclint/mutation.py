"""SSZ mutation-purity analyzer.

PR 1 made a new invariant load-bearing: every mutation of an SSZ value
must flow through the instrumented surface (``CachedRootList``'s wrapped
mutators, ``Container.__setattr__``'s weak-parent chain, or
``bulk_store``'s explicit dirty contract) or the incremental
hash_tree_root serves a silently stale root. Spec code in ``models/``
and ``pipeline/`` therefore must never reach around that surface. The
rule set is DERIVED from the manifest ``ssz/core.py`` exports
(``INSTRUMENTED_LIST_MUTATORS`` / ``instrumented_surface()``) — read
statically out of its AST so the linter never imports the code under
analysis and stays honest if the surface grows.

* ``mutation/raw-list-call`` — ``list.append(values, v)`` and friends:
  calling the *base* list method on an SSZ collection skips the
  instrumented wrapper entirely (dirty groups unmarked, caches stale).
  This is exactly what ``ssz/core.py`` does internally ON PURPOSE, which
  is why it alone is outside this analyzer's scope. Column-primary
  storage (``ssz/column_list.py``) is INSIDE it: the manifest names the
  module (``COLUMN_LIST_MODULE``) and the base calls it may make
  (``COLUMN_LIST_RAW_CALLS``: the slice store that swaps a list's slots
  on entry and exit, the sentinel ``append``), and any other raw call
  there is a finding like anywhere else.
* ``mutation/setattr-bypass`` — ``object.__setattr__(container, ...)``
  skips ``Container.__setattr__``: no ``_htr_cache`` eviction, no parent
  notification.
* ``mutation/dict-bypass`` — writing ``x.__dict__[...]`` (or
  ``.update``/``.pop``/``.clear`` on it) with a key that could be an SSZ
  *field* name. Keys starting with ``_`` are the sanctioned idiom for
  non-SSZ memo caches (``_active_idx_cache`` etc. — deliberately outside
  the root) and are exempt; anything else bypasses invalidation.
* ``mutation/deepcopy`` — ``copy.deepcopy`` duplicates the weak-parent
  wiring and cached roots into an object graph they don't describe; SSZ
  values copy with ``.copy()`` (which re-wires memos copy-on-write).
"""

from __future__ import annotations

import ast
import os

from .base import Finding, SourceModule, literal_str_list

_DICT_MUTATORS = {"update", "pop", "clear", "popitem", "setdefault", "__setitem__"}


def load_manifest(core_path: str) -> dict:
    """The instrumented-surface manifest, read statically from
    ``ssz/core.py``'s AST (the ``INSTRUMENTED_LIST_MUTATORS`` tuple and
    the literals inside ``instrumented_surface``)."""
    with open(core_path, "rb") as f:
        tree = ast.parse(f.read(), filename=core_path)
    list_mutators = None
    bulk_mutators = ("bulk_store",)
    column_module = None
    column_raw_calls = ()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for target in node.targets:
                if not isinstance(target, ast.Name):
                    continue
                if target.id == "INSTRUMENTED_LIST_MUTATORS":
                    list_mutators = literal_str_list(node.value)
                elif target.id == "COLUMN_LIST_RAW_CALLS":
                    column_raw_calls = tuple(literal_str_list(node.value) or ())
                elif target.id == "COLUMN_LIST_MODULE" and isinstance(
                    node.value, ast.Constant
                ):
                    column_module = node.value.value
    if not list_mutators:
        raise RuntimeError(
            f"{core_path}: INSTRUMENTED_LIST_MUTATORS tuple not found — the "
            "mutation analyzer derives its rules from that manifest"
        )
    return {
        "list_mutators": tuple(list_mutators),
        "bulk_mutators": bulk_mutators,
        # the one module with sanctioned raw calls, and which ones
        "column_list_module": column_module,
        "column_list_raw_calls": column_raw_calls,
    }


def _enclosing_name(stack: list) -> str:
    return ".".join(stack) if stack else "<module>"


def _dict_attr(node: ast.AST) -> "ast.Attribute | None":
    """The ``x.__dict__`` attribute node when ``node`` is built on one."""
    if isinstance(node, ast.Attribute) and node.attr == "__dict__":
        return node
    return None


def _key_is_private_literal(key: ast.AST) -> bool:
    return (
        isinstance(key, ast.Constant)
        and isinstance(key.value, str)
        and key.value.startswith("_")
    )


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str, manifest: dict):
        self.path = path
        self.manifest = manifest
        self.findings: list[Finding] = []
        self.stack: list[str] = []
        module = manifest.get("column_list_module")
        self.sanctioned_raw = (
            manifest.get("column_list_raw_calls", ())
            if module and path.replace(os.sep, "/").endswith("/" + module)
            else ()
        )

    # -- scope tracking ------------------------------------------------------
    def visit_FunctionDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    # -- rules ---------------------------------------------------------------
    def _emit(self, rule: str, line: int, message: str, hint: str) -> None:
        self.findings.append(
            Finding(
                rule=rule,
                path=self.path,
                line=line,
                symbol=_enclosing_name(self.stack),
                message=message,
                hint=hint,
            )
        )

    def visit_Call(self, node):
        func = node.func
        if isinstance(func, ast.Attribute):
            base = func.value
            # list.append(x, v) / list.__setitem__(x, i, v) / ...
            if (
                isinstance(base, ast.Name)
                and base.id == "list"
                and func.attr in self.manifest["list_mutators"]
                and func.attr not in self.sanctioned_raw
            ):
                self._emit(
                    "mutation/raw-list-call",
                    node.lineno,
                    f"raw base-class call list.{func.attr}(...) bypasses the "
                    "instrumented CachedRootList mutator — dirty-group "
                    "tracking and root caches go silently stale",
                    f"call the value's own .{func.attr}(...) (instrumented), "
                    "or bulk_store for certified sweeps",
                )
            # object.__setattr__(c, "field", v)
            if (
                isinstance(base, ast.Name)
                and base.id == "object"
                and func.attr == "__setattr__"
            ):
                self._emit(
                    "mutation/setattr-bypass",
                    node.lineno,
                    "object.__setattr__ skips Container.__setattr__ — no "
                    "_htr_cache eviction, no weak-parent notification",
                    "assign the attribute normally (the instrumented path)",
                )
            # x.__dict__.update(...) / .pop("field") / .clear() ...
            dict_base = _dict_attr(base)
            if dict_base is not None and func.attr in _DICT_MUTATORS:
                exempt = (
                    func.attr in ("pop", "setdefault")
                    and node.args
                    and _key_is_private_literal(node.args[0])
                )
                if not exempt:
                    self._emit(
                        "mutation/dict-bypass",
                        node.lineno,
                        f"__dict__.{func.attr}(...) can rewrite SSZ field "
                        "slots without passing through Container.__setattr__",
                        "mutate fields by plain attribute assignment; only "
                        "underscore-prefixed memo keys may go through __dict__",
                    )
            # copy.deepcopy(state)
            if (
                isinstance(base, ast.Name)
                and base.id == "copy"
                and func.attr == "deepcopy"
            ):
                self._emit(
                    "mutation/deepcopy",
                    node.lineno,
                    "copy.deepcopy duplicates weak-parent wiring and cached "
                    "roots into an object graph they don't describe",
                    "use the SSZ value's .copy() (memo-aware structural copy)",
                )
        elif isinstance(func, ast.Name) and func.id == "deepcopy":
            self._emit(
                "mutation/deepcopy",
                node.lineno,
                "deepcopy duplicates weak-parent wiring and cached roots "
                "into an object graph they don't describe",
                "use the SSZ value's .copy() (memo-aware structural copy)",
            )
        self.generic_visit(node)

    def _check_dict_subscript_write(self, target: ast.AST, line: int) -> None:
        if isinstance(target, ast.Subscript) and _dict_attr(target.value) is not None:
            if not _key_is_private_literal(target.slice):
                self._emit(
                    "mutation/dict-bypass",
                    line,
                    "store into __dict__[...] with a non-underscore key can "
                    "rewrite an SSZ field slot without Container.__setattr__ "
                    "invalidation",
                    "assign the attribute normally; only underscore-prefixed "
                    "memo keys may go through __dict__",
                )

    def visit_Assign(self, node):
        for target in node.targets:
            self._check_dict_subscript_write(target, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check_dict_subscript_write(node.target, node.lineno)
        self.generic_visit(node)

    def visit_Delete(self, node):
        for target in node.targets:
            self._check_dict_subscript_write(target, node.lineno)
        self.generic_visit(node)


def analyze_file(abspath: str, root: str, manifest: dict) -> list[Finding]:
    src = SourceModule.load(abspath, root)
    visitor = _Visitor(src.path, manifest)
    visitor.visit(src.tree)
    return visitor.findings


def analyze(paths: list, root: str, core_path: str) -> list[Finding]:
    manifest = load_manifest(core_path)
    findings: list[Finding] = []
    for path in paths:
        if os.path.abspath(path) == os.path.abspath(core_path):
            continue  # the instrumented surface itself is the one exemption
        findings.extend(analyze_file(path, root, manifest))
    return findings
