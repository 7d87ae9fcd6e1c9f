"""95th percentile (nearest rank) of how late the load generator
submitted each request behind its schedule."""

import math


def read(run):
    if run.late_ms is None or len(run.late_ms) == 0:
        return None
    ordered = sorted(float(v) for v in run.late_ms)
    return ordered[math.ceil(0.95 * len(ordered)) - 1]
