"""Persistent XLA compilation cache.

The emulated-u64 programs of this repo take up to a minute each to
compile for the chip (tests/test_chip_compile.py records the figures);
caching them on disk makes every re-run — tests, bench, chip smoke —
load the compiled binary instead. Called from the jax chokepoints (ops/,
parallel/) so host-only imports never pull jax.

Where the cache lives is decided from outside: when
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this module
sets no directory; when it is not, the cache is the fixed
``<checkout>/.jax_cache`` (the path is part of the cache key, so it must
not move between runs).
"""

from __future__ import annotations

import os

from . import _env

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)
_JAX_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_ENABLED = False


def _cache_dir() -> str:
    """The directory jax uses: its own environment variable when set,
    the fixed checkout path otherwise."""
    return _env.raw(_JAX_DIR_ENV) or _DEFAULT_DIR


def enable() -> None:
    """Idempotently turn the on-disk compile cache on."""
    global _ENABLED
    if _ENABLED:
        return
    import jax

    if not _env.raw(_JAX_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _ENABLED = True


def status() -> dict:
    """Persistent-compile-cache state for the device observatory's
    ``/device`` document (telemetry/device.py): whether the on-disk XLA
    cache is wired up, where it lives, and how many compiled entries it
    holds right now. Never imports jax."""
    cache_dir = _cache_dir()
    entries = None
    try:
        entries = sum(
            1 for name in os.listdir(cache_dir) if not name.startswith(".")
        )
    except OSError:
        pass
    return {"enabled": _ENABLED, "dir": cache_dir, "entries": entries}
