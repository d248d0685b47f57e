"""Virtual multi-device CPU platform provisioning.

Multi-device sharding is testable without several chips by running in a
subprocess whose JAX sees a virtual ``n``-device CPU platform. The device
count is an XLA flag read when the backend starts, so this MUST happen
via the environment of a fresh process — never in-process. This module
holds the one canonical recipe (used by tests/conftest.py and
__graft_entry__.dryrun_multichip). The child
is pinned to ``JAX_PLATFORMS=cpu``: it never needs the chip, so a parent
that holds one can start it.

Deliberately imports neither jax nor the rest of the package.
"""

from __future__ import annotations

import os
import subprocess
import sys

__all__ = ["cpu_mesh_env", "run_in_cpu_mesh", "REEXEC_SENTINEL"]

# Set (to the provisioned device count) in a child spawned for a specific
# request; a child provisioned for n devices that still can't see them must
# fail loudly instead of re-execing forever.
REEXEC_SENTINEL = "EC_VIRTUAL_MESH_CHILD"


def _default_repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def cpu_mesh_env(n_devices: int = 8, repo_root: str | None = None) -> dict:
    """Environment for a subprocess with an n-device virtual CPU platform.

    Preserves any pre-existing XLA_FLAGS (appends the device-count flag)
    and puts the repo root first on PYTHONPATH so the child imports this
    checkout.
    """
    if repo_root is None:
        repo_root = _default_repo_root()
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo_root, env.get("PYTHONPATH", "")) if p
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env[REEXEC_SENTINEL] = str(n_devices)
    return env


def run_in_cpu_mesh(
    code: str,
    n_devices: int = 8,
    timeout: int = 600,
    repo_root: str | None = None,
    stream: bool = False,
) -> str:
    """Run ``code`` in a subprocess on the virtual CPU mesh; returns stdout.

    With ``stream=True`` the child inherits this process's stdout so
    per-stage progress reaches the caller's output LIVE (a kill at any
    outer timeout still leaves the stages that ran on record); the
    return value is then "". Raises RuntimeError (with captured streams
    and the timeout) on nonzero exit or timeout.
    """
    if repo_root is None:
        repo_root = _default_repo_root()
    env = cpu_mesh_env(n_devices, repo_root=repo_root)
    if stream:
        sys.stdout.flush()
        proc = subprocess.Popen(
            [sys.executable, "-c", code],
            env=env,
            stderr=subprocess.PIPE,
            text=True,
            cwd=repo_root,
        )
        try:
            _, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            raise RuntimeError(
                f"cpu-mesh subprocess exceeded {timeout}s (stages that "
                "completed are on stdout above)"
            )
        if proc.returncode != 0:
            tail = "\n".join((err or "").splitlines()[-25:])
            raise RuntimeError(
                f"cpu-mesh subprocess failed (rc={proc.returncode}):\n"
                f"stderr tail:\n{tail}"
            )
        return ""
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=repo_root,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"cpu-mesh subprocess failed (rc={proc.returncode}):\n"
            f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
        )
    return proc.stdout
