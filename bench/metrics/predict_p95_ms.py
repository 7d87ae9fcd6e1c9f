"""95th percentile (nearest rank) of the latency of every request due in
the window, from when it was due to its answer.  A request not answered
(shed, overloaded, unavailable) counts as infinitely late; where more
than 5% are, the percentile reads 1e9 ms."""

import math


def read(run):
    lat = run.latencies_ms
    if lat is None or len(lat) == 0:
        return None
    ordered = sorted(float(v) for v in lat)
    v = ordered[math.ceil(0.95 * len(ordered)) - 1]
    return v if math.isfinite(v) else 1e9
