"""Pallas kernel: VHT counter accumulation as one-hot MXU matmuls.

TPU adaptation of the paper's LS update (Alg. 2).  A scatter-add over
(leaf, attr, bin, class) is hostile to the TPU (serialized scatter); we
reformulate it as the weighted-moments contraction of
repro.kernels.rule_stats with the class one-hot as the moment matrix:

    delta[n, j, b, c] = sum_i leaf1h[i, n] * bin1h[i, j, b] * (y1h[i, c] w_i)

one [nt, B] x [B, ja*bins*C] matmul per statistics block -- MXU work over
the lane-dense 2-D view of the statistics, accumulated in place, with the
one-hots built in-kernel from iota comparisons (no HBM one-hot).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.rule_stats.kernel import rule_stats_pallas

f32 = jnp.float32


def stats_update_pallas(stats, leaf, xbin, y, w, *, n_classes: int = 0,
                        attr_tile: int = 0, interpret: bool = False):
    """stats: [N, m, bins, C], or its packed view [N, m*bins*C] with
    ``n_classes`` = C; returns updated stats of the same shape (aliased
    in-place)."""
    C = n_classes or stats.shape[3]
    mom = jax.nn.one_hot(y, C, dtype=f32) * w.astype(f32)[:, None]
    return rule_stats_pallas(stats, leaf, xbin, mom, attr_tile=attr_tile,
                             interpret=interpret, name="vht_stats_update")
