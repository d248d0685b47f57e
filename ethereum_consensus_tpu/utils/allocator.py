"""glibc's allocator, told to keep what the process frees.

The columnar epoch pass works on whole-registry numpy columns: at 2^21
validators every temporary is 16 MiB, a dozen a boundary. glibc's defaults
hand such blocks back to the kernel when they are freed (``munmap``, or a
trim of the heap's top) and fetch them again a stage later, and every
fetched page is a fault: on the v5e's host 16 ms for each 16 MiB block, a
tenth of a boundary, paid or not by the luck of the heap's layout (the
hysteresis stage read 14 ms or 48 ms, the registry stage 8 or 24, from one
crossing to the next; PERF.md section 6, PR 28). With the two settings
below a freed block stays on the heap and the next temporary of its size
takes it, faulted in already.

The settings are process-wide and stay: setting either also ends glibc's
own moving of the two thresholds, and there is no call that brings that
back. ``ops.install()`` applies them, once; nothing else does."""

from __future__ import annotations

import ctypes
import sys

# <malloc.h>
_M_TRIM_THRESHOLD = -1
_M_MMAP_THRESHOLD = -3

# blocks up to this size come from the heap and not from a mapping of
# their own: glibc takes no larger value (HEAP_MAX_SIZE / 2 on 64 bits)
MMAP_THRESHOLD_BYTES = 32 << 20
# free memory on top of the heap beyond this goes back to the kernel:
# the largest value the call's ``int`` holds
TRIM_THRESHOLD_BYTES = (1 << 31) - 1

_applied: "bool | None" = None


def keep_freed_memory() -> bool:
    """Apply the two settings; True where glibc took both. Anywhere else
    (another libc, another platform) nothing happens. Idempotent."""
    global _applied
    if _applied is None:
        _applied = _apply()
    return _applied


def _apply() -> bool:
    if not sys.platform.startswith("linux"):
        return False
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):  # no dlopen of self, or not glibc
        return False
    mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    mallopt.restype = ctypes.c_int
    took_mmap = mallopt(_M_MMAP_THRESHOLD, MMAP_THRESHOLD_BYTES)
    took_trim = mallopt(_M_TRIM_THRESHOLD, TRIM_THRESHOLD_BYTES)
    return bool(took_mmap and took_trim)
