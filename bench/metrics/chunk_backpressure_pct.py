"""Share of the window the chunk loop spent waiting for a free in-flight
slot (stage ``backpressure``, span ``repro.chunk.backpressure``).  High
means the device sets the pace; near 0, the host does."""

from bench import counters


def read(run):
    return counters.stage_pct(run, "backpressure")
