"""What the harness's own clock saw from the client's side of the entry
point: a statistic of a latency series, or a scalar such as the set-up time.

params: {"series": <name>, "stat": "mean"|"p50"|"p90"|"p99"|"max"}
      | {"scalar": <observation name>}"""

from __future__ import annotations

import statistics


def percentile(values: list, p: int) -> float:
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=100, method="inclusive")[p - 1]


def read(params: dict, run):
    obs = run.observations
    if "scalar" in params:
        return obs.get(params["scalar"])
    values = obs.get("series", {}).get(params["series"])
    if not values:
        return None
    stat = params["stat"]
    if stat == "mean":
        return sum(values) / len(values)
    if stat == "max":
        return max(values)
    return percentile(values, int(stat[1:]))
