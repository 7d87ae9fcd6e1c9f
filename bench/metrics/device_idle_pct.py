"""Share of the traced window in which no operation ran on the device:
100 x (1 - busy / window), busy being the union of the intervals of the
device's operations in the profiler trace and the window the host span
``bench.window``; both are clipped to that span (``bench/trace.py``)."""


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    busy = run.trace["devices"][0]["busy_s"]
    return 100.0 * (1.0 - busy / run.trace["window_s"])
