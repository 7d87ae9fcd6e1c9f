"""The traffic generator: what a traffic mix file asks the window to drive.

A mix (``bench/traffic/<mix>.json``) states how many warm-up chunks set-up
runs, whether training publishes a snapshot at every chunk boundary, and
optionally an open-loop predict load that starts once the window's
training has published ``lead_in_chunks`` snapshots and then runs for the
window's seconds: the arrival process it names (``arrivals``, a module in
``bench/arrivals/``, such as ``poisson`` at ``rate_per_s``), and the
server's settings.  Arrival times and request rows come from the run's seed
alone, so every run of a seed offers the same requests at the same
offsets.
"""

from __future__ import annotations

import threading
import time

import numpy as np


def arrivals(predict: dict, seconds: float, seed: int) -> np.ndarray:
    """Offsets (s) of the requests due in a window of ``seconds``, from
    the arrival process the mix names: ``bench/arrivals/<arrivals>.py``."""
    from bench.harness import BENCH, load_module
    kind = predict.get("arrivals", "poisson")
    path = BENCH / "arrivals" / f"{kind}.py"
    if not path.exists():
        raise ValueError(f"unknown arrivals {kind!r}: no {path.name} in "
                         "bench/arrivals/")
    rng = np.random.default_rng([seed % (1 << 62), 0x5e7e])
    return load_module(path).offsets(predict, seconds, rng)


class OpenLoop:
    """Submits request ``i`` at ``t0 + offsets[i]`` on one thread,
    whatever the server does, and records how late each submission ran.
    A request's latency runs from when it was due to its answer."""

    def __init__(self, submit, rows, offsets, deadline_ms: float):
        self.submit = submit
        self.rows = rows
        self.offsets = offsets
        self.deadline_ms = deadline_ms
        self.late_s = np.zeros(len(offsets))
        self.requests = [None] * len(offsets)
        self.t0 = None
        self._thread = None

    def start(self, ready=None):
        """Start the schedule now, or once ``ready()`` holds (polled every
        millisecond): offsets count from that moment."""
        self._thread = threading.Thread(target=self._run, args=(ready,),
                                        name="bench-load", daemon=True)
        self._thread.start()

    def _run(self, ready):
        from jax.profiler import TraceAnnotation
        while ready is not None and not ready():
            time.sleep(0.001)
        self.t0 = time.monotonic()
        for i, off in enumerate(self.offsets):
            due = self.t0 + off
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            self.late_s[i] = max(0.0, time.monotonic() - due)
            with TraceAnnotation("bench.submit"):
                self.requests[i] = self.submit(self.rows[i],
                                               deadline_ms=self.deadline_ms)

    def join(self, timeout: float):
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("load generator did not finish")

    def wait_answers(self, timeout_s: float):
        """Wait for every request (up to ``timeout_s`` in all)."""
        end = time.monotonic() + timeout_s
        for r in self.requests:
            try:
                r.result(max(0.0, end - time.monotonic()))
            except TimeoutError:
                pass                # counted as unanswered

    def latencies_ms(self) -> np.ndarray:
        """Due-to-answer latency of each request; a request that was not
        answered (shed, overloaded, unavailable, or never resolved)
        counts as infinitely late."""
        out = np.full(len(self.requests), np.inf)
        for i, r in enumerate(self.requests):
            if r is not None and r.status == "answered":
                answered_at = r.submitted_at + r.meta["latency_ms"] / 1e3
                out[i] = (answered_at - (self.t0 + self.offsets[i])) * 1e3
        return out
