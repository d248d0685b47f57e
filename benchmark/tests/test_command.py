"""The command refuses a machine without a TPU: non-zero, no result line."""

import os
import subprocess
import sys

from benchmark.tests.rehearsal import ROOT


def test_the_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "deneb-1m.epoch-boundary", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 3
    assert done.stdout.strip() == ""
    assert "needs 1 TPU chip" in done.stderr


def test_an_unknown_workload_is_an_error():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "no-such-cell",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0 and done.stdout.strip() == ""
