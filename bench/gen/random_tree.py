"""Dense random-tree stream (SAMOA/MOA RandomTreeGenerator), pre-binned.

A copy of the program's ``RandomTreeGenerator.sample_binned`` kept with
the benchmark, so a change to the program cannot change the stream it is
measured on.  Instances are ``n_cat + n_num`` attributes drawn uniformly
over ``n_bins`` bins (the learners consume bins); the label is the class
of the leaf a hidden random decision tree of depth ``depth`` sends the
instance to, walked on the bin midpoints.  The hidden tree is fixed by
``concept_seed``; the instances by the key passed to ``sample_binned``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class RandomTreeStream:
    def __init__(self, n_cat: int, n_num: int, n_classes: int = 2,
                 depth: int = 8, concept_seed: int = 7):
        rng = np.random.RandomState(concept_seed)
        n_nodes = 2 ** depth - 1
        self.n_attrs = n_cat + n_num
        self.depth = depth
        self._attr = jnp.asarray(rng.randint(0, self.n_attrs, n_nodes),
                                 jnp.int32)
        self._thresh = jnp.asarray(rng.rand(n_nodes), jnp.float32)
        leaves = 2 ** depth
        labels = np.tile(np.arange(n_classes),
                         leaves // n_classes + 1)[:leaves]
        rng.shuffle(labels)
        self._leaf_label = jnp.asarray(labels, jnp.int32)

    def sample_binned(self, key, n: int, n_bins: int):
        """(bins [n, m] int32 in [0, n_bins), labels [n] int32).  One
        uint32 of random bits yields eight 4-bit nibbles, each masked to
        log2(n_bins) bits, so every bin is exactly equally likely."""
        if n_bins & (n_bins - 1) or not 0 < n_bins <= 16:
            raise ValueError(f"n_bins must be a power of two <= 16, "
                             f"got {n_bins}")
        m = self.n_attrs
        n_words = -(-n * m // 8)
        raw = jax.random.bits(key, (n_words,), jnp.uint32)
        shifts = (jnp.arange(8, dtype=jnp.uint32) * 4)[None, :]
        nibbles = (raw[:, None] >> shifts).reshape(-1)[: n * m]
        bins = (nibbles & jnp.uint32(n_bins - 1)).astype(jnp.int32)
        bins = bins.reshape(n, m)
        x = (bins.astype(jnp.float32) + 0.5) / n_bins
        node = jnp.zeros((n,), jnp.int32)
        for _ in range(self.depth):
            a = self._attr[node]
            v = jnp.take_along_axis(x, a[:, None], axis=1)[:, 0]
            node = 2 * node + 1 + (v > self._thresh[node]).astype(jnp.int32)
        return bins, self._leaf_label[node - (2 ** self.depth - 1)]
