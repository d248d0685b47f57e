"""The Makefile names only what exists.

Each target whose recipe runs a file or a module is one case: every
``$(PY) <file>.py`` and every ``tests/<file>.py`` it names is in the
checkout, every ``-m '<expr>'`` marker expression names only markers that
``pytest.ini`` registers, and every ``$(PY) -m <module>`` resolves. The
Makefile is parsed; no recipe is run.
"""

import configparser
import importlib.util
import os
import re
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

_TARGET = re.compile(r"^([a-z][a-zA-Z_-]*):(?!=)")
_SCRIPT = re.compile(r"\$\(PY\)\s+([\w./-]+\.py)\b")
_TEST_FILE = re.compile(r"\b(tests/[\w./-]+\.py)\b")
_MARKER_EXPR = re.compile(r"-m\s+'([^']*)'")
_MODULE = re.compile(r"\$\(PY\)\s+-m\s+([\w.]+)")


def _makefile() -> list:
    with open(os.path.join(REPO_ROOT, "Makefile")) as f:
        return f.read().splitlines()


def _recipes() -> dict:
    """``{target: [recipe line, ...]}`` in the Makefile's order."""
    recipes, target = {}, None
    for line in _makefile():
        match = _TARGET.match(line)
        if match:
            target = match.group(1)
            recipes[target] = []
        elif line.startswith("\t") and target is not None:
            recipes[target].append(line.strip())
        elif not line.strip() or line.startswith("#"):
            continue
        else:
            target = None
    return recipes


def _runs_something(lines: list) -> bool:
    return any(
        _SCRIPT.search(line) or _MODULE.search(line) for line in lines
    )


def _registered_markers() -> set:
    config = configparser.ConfigParser()
    config.read(os.path.join(REPO_ROOT, "pytest.ini"))
    return {
        entry.split(":", 1)[0].strip()
        for entry in config["pytest"]["markers"].splitlines()
        if entry.strip()
    }


RUNNING_TARGETS = [
    target for target, lines in _recipes().items() if _runs_something(lines)
]


@pytest.mark.parametrize("target", RUNNING_TARGETS)
def test_recipe_names_only_what_exists(target):
    recipe = " ".join(_recipes()[target])
    files = _SCRIPT.findall(recipe) + _TEST_FILE.findall(recipe)
    missing = [
        path for path in files
        if not os.path.isfile(os.path.join(REPO_ROOT, path))
    ]
    assert not missing, f"{target} names missing files: {missing}"

    registered = _registered_markers()
    for expr in _MARKER_EXPR.findall(recipe):
        names = set(re.findall(r"[A-Za-z_]\w*", expr)) - {"and", "or", "not"}
        unknown = names - registered
        assert not unknown, f"{target}: unregistered markers {unknown}"

    for module in _MODULE.findall(recipe):
        assert importlib.util.find_spec(module) is not None, (
            f"{target} runs a module that does not resolve: {module}"
        )


def test_phony_lists_the_defined_targets():
    """``.PHONY`` names every target and no target that is gone."""
    phony = next(
        line for line in _makefile() if line.startswith(".PHONY:")
    ).split(":", 1)[1].split()
    assert sorted(phony) == sorted(_recipes())
    assert len(RUNNING_TARGETS) >= 20
