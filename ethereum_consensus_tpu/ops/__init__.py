"""Device kernels (JAX/XLA + Pallas TPU): SHA-256 merkleization, shuffling,
epoch-processing sweeps.

Import of this package pulls in jax; the pure-host layers (ssz/, models/)
never import it directly — device acceleration is installed explicitly via
``install()``.
"""

from .. import _device_flags, _env
from .._jax_cache import enable as _enable_jax_cache

_enable_jax_cache()

from .merkle import merkleize_chunks_device  # noqa: E402
from .sha256 import install_device_hasher, sha256_64b_pallas, sha256_64b_xla

# crossover vs the (O(n)-hoisted) host sweeps, measured on the v5e chip:
# a single routed sweep breaks even near 2^18 validators, but the epoch
# path packs once for four sweeps, which moves the win to ~2^17
DEFAULT_SWEEPS_MIN_N = 1 << 17
DEFAULT_SHUFFLE_MIN_N = 1 << 15
DEFAULT_BLS_AGG_MIN_N = 1 << 12
# Device RLC multi-pairing (ops/pairing.py): auto-thresholded. The kernel
# is bit-identical to the native backend and fully routed, but a SINGLE
# chip without native wide-integer multiply (v5e: u64 lane products are
# emulated) loses to the host IFMA engine (~119µs/pair) at block-sized
# batches, so small flushes must stay host. What changed with the chain
# pipeline (pipeline/engine.py): cross-block windowed flushes now reach
# hundreds of sets per call, the scale where the set axis shards over the
# mesh (parallel/pairing.py — N chips buy ~N× batch throughput) and the
# mont7 int8-MXU multiplier amortizes its launch cost. The auto default
# therefore routes only those large coalesced flushes to the device;
# everything below the threshold keeps the host engine. Override with
# ECT_PAIRING_MIN_SETS=<n> (fleet chips measured better/worse) or
# ECT_PAIRING_MIN_SETS=off to pin the host engine unconditionally; any
# device trouble still falls back to host without changing verdicts
# (crypto/bls.py _batch_device_pairing).
_AUTO_PAIRING_MIN_SETS = 512


def _pairing_min_sets_default() -> "int | None":
    env = _env.raw_or_none("ECT_PAIRING_MIN_SETS")
    if env is None:
        return _AUTO_PAIRING_MIN_SETS
    env = env.strip().lower()
    if env in ("", "off", "none", "host"):
        return None
    try:
        n = int(env)
    except ValueError:
        return _AUTO_PAIRING_MIN_SETS
    return n if n > 0 else None


DEFAULT_PAIRING_MIN_SETS = _pairing_min_sets_default()


def install(
    sweeps_min_n: int = DEFAULT_SWEEPS_MIN_N,
    shuffle_min_n: int = DEFAULT_SHUFFLE_MIN_N,
    bls_agg_min_n: int = DEFAULT_BLS_AGG_MIN_N,
    pairing_min_sets: "int | None" = DEFAULT_PAIRING_MIN_SETS,
    hasher_on_cpu: bool = False,
) -> None:
    """Install all device fast paths into the host layers:

    * SHA-256 hash levels above ssz.hash.DEVICE_MIN_NODES (merkleization)
      — on a REAL accelerator only: with a cpu default backend the jnp
      compression is ~30x slower than the native C++ hasher, so routing
      is skipped unless ``hasher_on_cpu`` forces it (device-wiring tests
      / deliberate jnp-hasher benches);
    * epoch-processing registry sweeps (altair+ flag deltas, inactivity
      updates/penalties, effective-balance hysteresis) above
      ``sweeps_min_n`` validators;
    * whole-list committee shuffling above ``shuffle_min_n`` indices;
    * G1 pubkey aggregation (fast_aggregate_verify / batched signature
      sets) above ``bls_agg_min_n`` total points.

    Spec semantics are unchanged — every device twin is bit-identical to
    its host function (cross-checked in tests); the thresholds only decide
    where the work runs. Exact u64 arithmetic needs jax x64 mode, enabled
    here. The process-wide shuffle memo is dropped (as ``uninstall`` does),
    so shuffles from here on take the installed route. The process's
    allocator is told to keep what is freed (``utils/allocator.py``): the
    host side of the epoch pass reuses its whole-registry temporaries
    instead of faulting them in anew at every boundary."""
    import jax

    from ..utils import allocator

    allocator.keep_freed_memory()
    jax.config.update("jax_enable_x64", True)
    install_device_hasher(force=hasher_on_cpu)
    _device_flags.SWEEPS_MIN_N = sweeps_min_n
    _device_flags.SHUFFLE_MIN_N = shuffle_min_n
    _device_flags.BLS_AGG_MIN_N = bls_agg_min_n
    _device_flags.PAIRING_MIN_SETS = pairing_min_sets
    _forget_shuffles()


def uninstall() -> None:
    """Turn the spec-path device routing back off (keeps the hasher)."""
    _device_flags.SWEEPS_MIN_N = None
    _device_flags.SHUFFLE_MIN_N = None
    _device_flags.BLS_AGG_MIN_N = None
    _device_flags.PAIRING_MIN_SETS = None
    _forget_shuffles()


def _forget_shuffles() -> None:
    """The committee shuffles are memoized process-wide by seed; a change
    of routing drops them, so the shuffles computed from here on take the
    route now installed (the memo would otherwise keep serving the other
    route's — bit-identical — results and the new route would never run)."""
    from ..models.phase0 import helpers as _phase0_helpers

    _phase0_helpers._SHUFFLE_CACHE.clear()


__all__ = [
    "DEFAULT_PAIRING_MIN_SETS",
    "DEFAULT_SHUFFLE_MIN_N",
    "DEFAULT_SWEEPS_MIN_N",
    "install",
    "install_device_hasher",
    "merkleize_chunks_device",
    "sha256_64b_pallas",
    "sha256_64b_xla",
    "uninstall",
]
