"""Waveform stream (MOA WaveformGenerator), binned, with a numeric target.

A copy of the program's ``WaveformGenerator.sample`` and ``bin_numeric``
kept with the benchmark.  Three base waves over 21 positions; an instance
mixes two neighbouring waves with a uniform weight and adds Gaussian noise
(scale 0.1, clipped to [0, 1]) to the 21 signal attributes, followed by
19 uniform noise attributes.  The target is the wave index (0, 1 or 2)
as a float, as the AMRules regression experiments use it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class WaveformStream:
    n_signal = 21
    n_noise = 19

    def __init__(self):
        t = np.arange(self.n_signal)
        w = np.stack([
            np.maximum(6 - np.abs(t - 7), 0),
            np.maximum(6 - np.abs(t - 13), 0),
            np.maximum(6 - np.abs(t - 3), 0) + np.maximum(6 - np.abs(t - 17), 0),
        ]) / 6.0
        self._wave = jnp.asarray(w, jnp.float32)

    @property
    def n_attrs(self) -> int:
        return self.n_signal + self.n_noise

    def sample(self, key, n: int):
        """(x [n, 40] f32 in [0, 1], wave index [n] int32)."""
        kc, ku, kn, kz = jax.random.split(key, 4)
        y = jax.random.randint(kc, (n,), 0, 3)
        u = jax.random.uniform(ku, (n, 1))
        base = u * self._wave[y] + (1 - u) * self._wave[(y + 1) % 3]
        sig = base + 0.1 * jax.random.normal(kn, (n, self.n_signal))
        noise = jax.random.uniform(kz, (n, self.n_noise))
        return jnp.concatenate([jnp.clip(sig, 0, 1), noise], 1), y

    def sample_binned(self, key, n: int, n_bins: int):
        """(bins [n, 40] int32, target [n] f32)."""
        x, y = self.sample(key, n)
        bins = jnp.clip((x * n_bins).astype(jnp.int32), 0, n_bins - 1)
        return bins, y.astype(jnp.float32)
