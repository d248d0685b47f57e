"""Production soak subsystem (docs/SOAK.md).

One sustained mixed-load run composing every hostility the scenario
harness can generate — fork-boundary pipeline replay, invalid-block
storms, injected infrastructure AND mesh-route faults, reader swarms,
SSE subscribers, pool ingestion spam, equivocation traffic — for
thousands of flush windows, asserting three hard gates: p99 latency
SLOs off the reservoir histograms (with /healthz pinned to ``ok``),
flat RSS via the leak sentinel, and end-of-run bit-identity (state
root, blame, equivocation ledger). Its report carries the sustained
blocks/s + queries/s pair.

Host-only by construction: importing this package never imports jax
(the mesh fault lane engages only when ``ECT_MESH`` is on).
"""

from .runner import SoakConfig, SoakRunner, run_soak
from .sentinel import LeakSentinel, rss_mb

__all__ = [
    "SoakConfig",
    "SoakRunner",
    "run_soak",
    "LeakSentinel",
    "rss_mb",
]
