"""Native C++ backend: builds and loads the SHA-256 merkle kernels.

The reference leans on native code for its crypto substrate (blst C/asm,
c-kzg, sha2 — SURVEY.md L0); this package is the equivalent native layer
here: a from-scratch C++ SHA-256 merkle library compiled on first use with
the system toolchain and loaded via ctypes (no pybind11 in this image).
Falls back cleanly to the pure-Python path when no compiler is available.
The same library holds the swap-or-not shuffle's per-index pass
(``shuffle_positions``), which reuses its SHA-256 lane routines.

``install()`` registers the native hasher with ssz.hash so every
hash_tree_root below the device threshold runs native.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import platform
import subprocess
import tempfile

from .. import _env

__all__ = [
    "load",
    "available",
    "build_failures",
    "hash_level_native",
    "merkle_root_native",
    "merkle_groups_native",
    "shuffle_positions",
    "usable_cores",
    "install",
]

_SOURCE = os.path.join(os.path.dirname(__file__), "sha256_merkle.cpp")
_LIB = None
_TRIED = False


def _build_dir() -> str:
    path = os.path.join(os.path.dirname(__file__), "_build")
    os.makedirs(path, exist_ok=True)
    return path


@functools.lru_cache(maxsize=1)
def _host_tag() -> bytes:
    """What ``-march=native`` depends on besides the source: this host's
    CPU feature flags and the compiler. Part of every artifact's name, so
    a ``_build/`` directory that travels with the tree to a different
    machine is rebuilt from source there instead of being loaded with
    instructions that host may lack."""
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    try:
        compiler = subprocess.run(
            ["g++", "-dumpfullversion", "-dumpmachine"],
            check=True, capture_output=True, text=True, timeout=30,
        ).stdout
    except (OSError, subprocess.SubprocessError):
        compiler = ""
    return "\0".join(
        (platform.machine(), flags or platform.processor(), compiler)
    ).encode()


@functools.lru_cache(maxsize=1)
def usable_cores() -> int:
    """The host cores this process may run on: what a native batch
    spreads its threads over."""
    return len(os.sched_getaffinity(0))


def artifact_tag(sources, extra: str = "") -> str:
    """Name tag of a built library: source bytes + build inputs + host."""
    digest = hashlib.sha256()
    for path in sources:
        with open(path, "rb") as f:
            digest.update(f.read())
    digest.update(extra.encode())
    digest.update(_host_tag())
    return digest.hexdigest()[:16]


# library stem -> why its last build failed (compiler stderr tail);
# available() stays a bool, this says what a False means
_BUILD_FAILURES: dict = {}


def build_failures() -> dict:
    return dict(_BUILD_FAILURES)


def build_shared(stem: str, tag: str, source: str, flags, timeout: int):
    """Path of ``_build/<stem>-<tag>.so``, compiling ``source`` with g++
    first when it is not there yet; None when the build fails."""
    lib_path = os.path.join(_build_dir(), f"{stem}-{tag}.so")
    if os.path.exists(lib_path):
        return lib_path
    tmp = None
    try:
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_build_dir())
        os.close(fd)
        subprocess.run(
            ["g++", "-O3", "-march=native", "-shared", "-fPIC", *flags,
             source, "-o", tmp],
            check=True,
            capture_output=True,
            timeout=timeout,
        )
        os.replace(tmp, lib_path)  # atomic under concurrent builders
        tmp = None
    except (OSError, subprocess.SubprocessError) as exc:
        stderr = getattr(exc, "stderr", None) or b""
        _BUILD_FAILURES[stem] = (
            f"{type(exc).__name__}: {exc}"[:300]
            + stderr.decode("utf-8", "replace")[-500:]
        )
        return None
    finally:
        if tmp and os.path.exists(tmp):
            os.unlink(tmp)
    return lib_path


def load():
    """Compile (once per source hash and host) + load the shared library,
    or None."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    # SHA-NI is opt-in: virtualized hosts may trap the sha instructions
    # (measured ~20x slower than scalar under emulation in this image)
    sha_ni = _env.raw("EC_NATIVE_SHA_NI")
    lib_path = build_shared(
        "sha256_merkle",
        artifact_tag([_SOURCE], sha_ni),
        _SOURCE,
        ["-pthread", *(["-DEC_USE_SHA_NI"] if sha_ni else [])],
        timeout=120,
    )
    if lib_path is None:
        return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:
        return None
    lib.ec_hash_level.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.ec_hash_level.restype = None
    lib.ec_merkle_root.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32,
        ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.ec_merkle_root.restype = None
    lib.ec_merkle_groups.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_size_t, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_uint32,
    ]
    lib.ec_merkle_groups.restype = ctypes.c_uint32
    lib.ec_shuffle_positions.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint32, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_size_t,
    ]
    lib.ec_shuffle_positions.restype = ctypes.c_uint32
    lib.ec_version.restype = ctypes.c_uint64
    _LIB = lib
    return lib


def available() -> bool:
    return load() is not None


def _require_lib():
    lib = load()
    if lib is None:
        raise RuntimeError(
            "native backend unavailable: no working C++ toolchain (g++) found"
        )
    return lib


def hash_level_native(nodes: bytes) -> bytes:
    """Native twin of ssz.hash.hash_level_host."""
    lib = _require_lib()
    n_pairs = len(nodes) // 64
    out = ctypes.create_string_buffer(n_pairs * 32)
    lib.ec_hash_level(nodes, out, n_pairs)
    return out.raw


def merkle_root_native(chunks: bytes, depth: int, zero_hashes: bytes) -> bytes:
    """Whole-tree reduction in one native call (``zero_hashes`` = depth+1
    concatenated 32-byte zero-subtree roots)."""
    lib = load()
    out = ctypes.create_string_buffer(32)
    lib.ec_merkle_root(chunks, len(chunks) // 32, depth, zero_hashes, out)
    return out.raw


def merkle_groups_native(
    raw, group_ids, depth: int, zero_hashes: bytes, n_threads: int
) -> "tuple[bytes, int] | None":
    """Roots of the 2^depth-chunk groups ``group_ids`` of the bytearray
    ``raw`` (read in place), 32 bytes each in the order given, on up to
    ``n_threads`` host threads; with the threads that ran. None when the
    native side wrote nothing."""
    lib = load()
    n = len(group_ids)
    ids = (ctypes.c_uint64 * n)(*group_ids)
    out = ctypes.create_string_buffer(32 * n)
    buf = (ctypes.c_char * len(raw)).from_buffer(raw)
    used = lib.ec_merkle_groups(
        buf, len(raw), ids, n, depth, zero_hashes, out, n_threads
    )
    del buf  # the bytearray may be resized again once its export is gone
    return (out.raw, used) if used else None


def shuffle_positions(seed: bytes, count: int, rounds: int, positions):
    """``compute_shuffled_index(i, count, seed)`` for every ``i`` of the
    1-D ``uint64`` numpy array ``positions``, in one native call that runs
    the rounds over all of them at once. Returns the shuffled positions as
    a new array and the widest lane routine the library hashed with (8 or
    1). ``ValueError`` for a seed not of 32 bytes, an array not so laid out,
    or what ``ec_shuffle_positions`` refuses: ``count`` outside 1..2^40,
    more than 256 rounds, a position not below ``count``."""
    lib = _require_lib()
    if len(seed) != 32:
        raise ValueError("shuffle: the seed is 32 bytes")
    if not (
        positions.dtype == "uint64"
        and positions.ndim == 1
        and positions.flags.c_contiguous
    ):
        raise ValueError("shuffle: positions must be a contiguous uint64 array")
    out = positions.copy()
    lanes = lib.ec_shuffle_positions(
        seed, count, rounds, positions.ctypes.data, out.ctypes.data,
        positions.size,
    )
    if not lanes:
        raise ValueError(
            "shuffle: 1 <= count <= 2^40, at most 256 rounds, positions below count"
        )
    return out, int(lanes)


def install() -> bool:
    """Register the native hasher with the SSZ hash dispatch; returns
    whether the native path is active."""
    if not available():
        return False
    from ..ssz import hash as hash_module

    hash_module.register_native_hasher(hash_level_native)
    return True
