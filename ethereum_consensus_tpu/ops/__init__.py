"""Device kernels (JAX/XLA + Pallas TPU): SHA-256 merkleization, shuffling,
BLS field/curve/pairing arithmetic.

Import of this package pulls in jax; the pure-host layers (ssz/, models/)
import from it only behind a gate that ``install()`` has switched on (today
one site: the committee shuffle, models/phase0/helpers.py) — device
acceleration is installed explicitly via ``install()``. The epoch pass's
jitted kernel is not here: it is the ``xp``-generic kernel of
models/epoch_vector.py, jitted there.
"""

from .. import _device_flags, _env
from .._jax_cache import enable as _enable_jax_cache

_enable_jax_cache()

from .merkle import merkleize_chunks_device  # noqa: E402
from .sha256 import install_device_hasher, sha256_64b_pallas, sha256_64b_xla

# Registry size from which the columnar epoch pass of an altair-family
# fork runs the fused jitted kernel in place of its host kernels
# (_device_flags.sweeps_enabled). The crossover against the host kernels
# is not measured (PERF.md section 7): the value is a guess
DEFAULT_SWEEPS_MIN_N = 1 << 17
DEFAULT_SHUFFLE_MIN_N = 1 << 15
DEFAULT_BLS_AGG_MIN_N = 1 << 12
# Device RLC multi-pairing (ops/pairing.py): batches of at least this many
# signature sets go to the device kernel (bit-identical to the native
# backend), smaller ones keep the host engine. The crossover is not
# measured; PERF.md section 6: stage B 11.9 s a flush. Override with
# ECT_PAIRING_MIN_SETS=<n>, or ECT_PAIRING_MIN_SETS=off to pin the host
# engine unconditionally; any device trouble still falls back to host
# without changing verdicts (crypto/bls.py _batch_device_pairing).
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
    * from ``sweeps_min_n`` validators up, the columnar epoch pass of an
      altair-family fork runs its inactivity + rewards step as the fused
      jitted kernel (``models/epoch_vector.jitted_kernels()
      ["fused_epoch"]``) in place of the host kernels. Nothing else hangs
      on this keyword: phase0's pass and every literal per-fork function
      are host code whatever is installed;
    * whole-list committee shuffling above ``shuffle_min_n`` indices;
    * G1 pubkey aggregation (fast_aggregate_verify / batched signature
      sets) above ``bls_agg_min_n`` total points.

    Spec semantics are unchanged — every device route is bit-identical to
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
