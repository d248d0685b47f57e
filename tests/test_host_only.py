"""The host-only contract of ``_device_flags`` and ``ops/__init__``: a
process that never calls ``ops.install()`` never imports jax, whichever
epoch path its registry takes. A subprocess each, because this one has
jax loaded already."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent

_ONE_EPOCH = """
import sys
sys.path.insert(0, {tests!r})
import ethereum_consensus_tpu.executor  # the served entry point
import chain_utils
from ethereum_consensus_tpu.models.altair.slot_processing import process_slots
from ethereum_consensus_tpu.telemetry import metrics

n = 1 << 12  # EPOCH_VECTOR_MIN_VALIDATORS and the literal functions' numpy gates
state, ctx = chain_utils.build_fast_registry_state(n, "altair", "minimal")
state = state.copy()
spe = int(ctx.SLOTS_PER_EPOCH)
process_slots(state, spe, ctx)
state.previous_epoch_participation = [0b111] * n
for i in range(0, n, 5):
    state.previous_epoch_participation[i] = 0b001
before = metrics.snapshot()
process_slots(state, 2 * spe, ctx)
moved = metrics.delta(before)
print("EPOCHS", moved.get("epoch_vector.epochs", 0))
print("DISABLED", moved.get("epoch_vector.fallback.disabled", 0))
print("JAX", "jax" in sys.modules)
print("OPS", sorted(m for m in sys.modules
                    if m.startswith("ethereum_consensus_tpu.ops")))
"""


@pytest.mark.parametrize("engine", ["columnar", "literal"])
def test_host_only_epoch_never_imports_jax(engine):
    """One altair epoch boundary at 2^12 validators with nothing
    installed, through the columnar pass and through the literal stage
    list (``ECT_EPOCH_VECTOR=off``: its numpy branches pack the registry
    through ``models/registry_columns.py``): neither jax nor the ``ops``
    package is imported."""
    env = dict(os.environ, PYTHONPATH=str(REPO_ROOT))
    env.pop("ECT_EPOCH_VECTOR", None)
    if engine == "literal":
        env["ECT_EPOCH_VECTOR"] = "off"
    out = subprocess.run(
        [sys.executable, "-c", _ONE_EPOCH.format(tests=str(REPO_ROOT / "tests"))],
        env=env, capture_output=True, text=True, timeout=300, check=False,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    said = dict(line.split(" ", 1) for line in out.stdout.splitlines())
    if engine == "columnar":
        assert (said["EPOCHS"], said["DISABLED"]) == ("1", "0")
    else:
        assert (said["EPOCHS"], said["DISABLED"]) == ("0", "1")
    assert said["JAX"] == "False"
    assert said["OPS"] == "[]"
