"""Share of the window the chunk loop spent taking the next chunk from
the stream (stage ``stream_wait``, span ``repro.chunk.stream_wait``)."""

from bench import counters


def read(run):
    return counters.stage_pct(run, "stream_wait")
