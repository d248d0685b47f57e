"""What the drivers share: the per-operation ledger (did a device route
decline to the host while it ran?) and the tally of attempted and failed
operations."""

from __future__ import annotations

from . import harness, meters


class OpLedger:
    """One entry per operation of the window. An operation *failed* when it
    raised, or when a decline counter moved while it ran: it was answered,
    but not by the path the cell exists to measure."""

    def __init__(self):
        self.failed: list = []
        self._before = None

    def begin(self) -> None:
        self._before = meters.counters()

    def end(self, error: "BaseException | None" = None) -> None:
        declined = meters.declines(self._before, meters.counters())
        bad = bool(declined) or error is not None
        if bad:
            harness.log("op_failed", {"declines": declined, "error": repr(error)})
        self.failed.append(bad)


class DriverBase:
    def __init__(self, cell, seed: int):
        self.cell = cell
        self.seed = seed
        self.traffic = cell.traffic
        self.world = None

    def facts(self) -> dict:
        return {}

    def tally(self, run) -> tuple:
        """(attempted, failed). Where a kind the cell must route to the
        device never reached it in the window, every operation failed."""
        failed = list(run.observations["ops_failed"])
        routes = run.observatory["routes"]
        unrouted = [
            kind for kind in self.traffic.get("routed_kinds", [])
            if not routes.get(kind, {}).get("device")
        ]
        if unrouted:
            harness.log("unrouted", {"kinds": unrouted, "routes": routes})
            return len(failed), len(failed)
        return len(failed), sum(failed)


def compared(name: str, value, limit=0) -> dict:
    return {"name": name, "value": value, "limit": limit}


def state_root(state) -> bytes:
    return type(state).hash_tree_root(state)
