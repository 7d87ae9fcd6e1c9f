"""Poisson arrivals at ``rate_per_s``: exponential gaps drawn from the
seed."""

from __future__ import annotations

import numpy as np


def offsets(predict: dict, seconds: float, rng: np.random.Generator):
    """Offsets (s) of the requests due in a window of ``seconds``."""
    rate = float(predict["rate_per_s"])
    t = np.cumsum(rng.exponential(1.0 / rate, int(rate * seconds * 2 + 64)))
    while t[-1] < seconds:          # extremely unlikely: draw more
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, len(t)))])
    return t[t < seconds]
