"""Always-on counters for the program's host stages, and stable program
names for its traces.

Spans are ``jax.profiler.TraceAnnotation``s named ``repro.<layer>.<stage>``
at the call sites; they cost one check when no profiler runs and share the
profiler's clock with the device trace.  Beside them, each stage keeps a
``Histogram``: log-spaced fixed buckets, a count, a sum and a max, with
no list that grows and no lock of its own -- the owner adds from one
thread, or under a lock it already holds.

``snapshot()`` is a plain dict (``count``, ``sum``, ``max`` and
``buckets``, the ``[upper_edge, count]`` pairs of the buckets that hold
anything), so two snapshots of one histogram subtract bucket by bucket
into what happened between them.
"""

from __future__ import annotations

import math

import jax

LO = 2.0 ** -10             # upper edge of the first bucket (0 lands there)
PER_OCTAVE = 32             # bucket edges 2.2% apart
N_BUCKETS = 30 * PER_OCTAVE + 1     # the last edge is LO * 2**30


def edge(i: int) -> float:
    """Upper edge of bucket ``i``."""
    return LO * 2.0 ** (i / PER_OCTAVE)


class Histogram:
    """Counts of values in log-spaced buckets: in milliseconds, the first
    bucket ends at about a microsecond and the last, which also takes
    everything above, at about 17 minutes."""

    __slots__ = ("counts", "count", "sum", "max")

    def __init__(self):
        self.counts = [0] * N_BUCKETS
        self.count = 0
        self.sum = 0.0
        self.max = 0.0

    def add(self, v: float):
        i = 0 if v <= LO else min(
            math.ceil(PER_OCTAVE * math.log2(v / LO)), N_BUCKETS - 1)
        self.counts[i] += 1
        self.count += 1
        self.sum += v
        if v > self.max:
            self.max = v

    def snapshot(self) -> dict:
        return {"count": self.count, "sum": self.sum, "max": self.max,
                "buckets": [[edge(i), c] for i, c in enumerate(self.counts)
                            if c]}


def program(fn, name: str, **jit_kwargs):
    """``jax.jit(fn)`` compiled under a fixed name: the device trace and
    the compiled module call it ``jit_<name>`` whatever the Python
    function is called."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)
