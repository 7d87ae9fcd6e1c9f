"""95th percentile of the window's predict batches' time from formation
to answers on the host (``ModelServer.status()`` histogram ``device_ms``,
less what set-up counted; span ``repro.serve.predict``)."""

from bench import counters


def read(run):
    return counters.quantile(counters.window(run, "device_ms"), 0.95)
