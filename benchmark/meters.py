"""What the harness reads besides its own clock: compiles (jax.monitoring),
transfers and routes (the program's device observatory), counters (the
program's metrics registry). Copied from chip_smoke.py, whose readings were
proven on the chip in PR 23."""

from __future__ import annotations

import threading


class CompileMeter:
    """Counts what jax compiles, from jax's own monitoring events: every
    executable built or loaded (``compiles`` with ``compile_s``), and of
    those how many the persistent cache did not hold (``cache_misses``:
    real compiles) or did (``cache_hits``)."""

    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    _MISS = "/jax/compilation_cache/cache_misses"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()  # the verifier thread compiles too
        self._totals = {
            "compile_s": 0.0, "compiles": 0, "cache_misses": 0,
            "cache_hits": 0,
        }
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self._BACKEND_COMPILE:
            with self._lock:
                self._totals["compile_s"] += duration
                self._totals["compiles"] += 1

    def _event(self, event: str, **_) -> None:
        key = {self._MISS: "cache_misses", self._HIT: "cache_hits"}.get(event)
        if key:
            with self._lock:
                self._totals[key] += 1

    def read(self) -> dict:
        with self._lock:
            return dict(self._totals)


def device_sync() -> None:
    """Every device has finished what was enqueued on it: a device runs its
    queue in order, so a trivial program that is ready was preceded by
    everything dispatched before it."""
    import jax

    jax.block_until_ready(
        [jax.device_put(0, device) + 0 for device in jax.devices()]
    )


def counters() -> dict:
    """The integer counters of the program's metrics registry, now."""
    from ethereum_consensus_tpu.telemetry import metrics

    return {
        name: value
        for name, value in metrics.snapshot().items()
        if isinstance(value, int)
    }


def is_decline(name: str) -> bool:
    """A counter that says a device route declined to a host path."""
    return (
        ".fallback." in name
        or ".fused_fallback." in name
        or name.startswith(("mesh.decline.", "bls.device_decline."))
    )


def moved(before: dict, after: dict) -> dict:
    return {
        name: value - before.get(name, 0)
        for name, value in after.items()
        if value != before.get(name, 0)
    }


def declines(before: dict, after: dict) -> dict:
    return {n: d for n, d in moved(before, after).items() if is_decline(n)}


def observatory() -> dict:
    """Route tallies and transfer totals of the device observatory, now."""
    from ethereum_consensus_tpu.telemetry import device as tel_device

    obs = tel_device.OBSERVATORY
    return {
        "routes": obs.route_tallies(),
        "transfers": dict(obs.transfer_summary()["totals"]),
    }


def observatory_delta(before: dict, after: dict) -> dict:
    routes = {}
    for kind, choices in after["routes"].items():
        was = before["routes"].get(kind, {})
        delta = {c: n - was.get(c, 0) for c, n in choices.items()}
        delta = {c: n for c, n in delta.items() if n}
        if delta:
            routes[kind] = delta
    transfers = {
        key: value - before["transfers"].get(key, 0)
        for key, value in after["transfers"].items()
    }
    return {"routes": routes, "transfers": transfers}
