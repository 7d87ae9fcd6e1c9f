"""95th percentile of the window's answered requests' wait from
submission to their batch's formation (``ModelServer.status()``
histogram ``queue_ms``, less what set-up counted)."""

from bench import counters


def read(run):
    return counters.quantile(counters.window(run, "queue_ms"), 0.95)
