"""Reading the program's own counters: histogram snapshots and the chunk
loop's stage table.

A histogram snapshot is a dict with ``count``, ``sum``, ``max`` and
``buckets``, the ``[upper_edge, count]`` pairs of its non-empty buckets
(``ModelServer.status()``, ``SnapshotPublisher.status()``, the stage
table).  The readers subtract the snapshot taken at the window's start,
so only the window counts, and read a quantile as the upper edge of the
bucket that holds it.  Where the program has no such counter, each
function returns None.
"""

from __future__ import annotations

import math
import sys


def window(run, key: str) -> dict | None:
    """The server histogram ``key`` over the window: its snapshot after
    the window less the one before it."""
    if run.server is None or key not in run.server:
        return None
    new, old = run.server[key], (run.server0 or {}).get(key)
    if old is None:
        return new
    before = {e: c for e, c in old["buckets"]}
    return {"count": new["count"] - old["count"],
            "sum": new["sum"] - old["sum"], "max": new["max"],
            "buckets": [[e, c - before.get(e, 0)] for e, c in new["buckets"]
                        if c > before.get(e, 0)]}


def quantile(snap: dict | None, q: float) -> float | None:
    """Nearest-rank ``q`` quantile: the upper edge of its bucket, capped
    at the maximum."""
    if snap is None or snap["count"] <= 0:
        return None
    rank = max(1, math.ceil(q * snap["count"]))
    seen = 0
    for e, c in sorted(snap["buckets"]):
        seen += c
        if seen >= rank:
            return min(e, snap["max"])
    return snap["max"]


def mean(snap: dict | None) -> float | None:
    if snap is None or snap["count"] <= 0:
        return None
    return snap["sum"] / snap["count"]


def stages() -> dict | None:
    """The stage table of the newest prequential run the program finished
    in this process -- after the window, the window's own run."""
    mod = sys.modules.get("repro.core.evaluation")
    if mod is None:
        return None
    return getattr(mod.ChunkedPrequentialEvaluation, "last_stages", None)


def stage_pct(run, stage: str) -> float | None:
    """Share of the window spent in one stage of the chunk loop."""
    table = stages()
    if not table or stage not in table or run.window_s <= 0:
        return None
    return 100.0 * table[stage]["total_s"] / run.window_s
