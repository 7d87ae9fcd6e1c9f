"""Public dispatcher for the rule-statistics (weighted moments) update.

Three implementations of the same contraction
``stats[r, j, b, c] += sum_i 1[seg_i = r] 1[x_ij = b] mom[i, c]``
(instances with seg == R are discarded):

  pallas   -- one-hot MXU matmuls, statistics tile resident in VMEM
              (kernel.py).  Default on TPU; off TPU it runs only in
              interpret mode, and only when the caller asks for it.
  segment  -- per-moment element scatter: each (instance, attribute) pair
              adds mom[i, c] at (seg_i, j, xbin_ij).  Never materializes
              the [B, m, bins] bin one-hot, let alone the dense
              [B, m, bins, C] product.  Default off-TPU.
  onehot   -- the legacy dense one-hot oracle (ref.py); kept for parity
              tests and before/after benchmarking.

This is the regression sibling of repro.kernels.vht_stats: the class
one-hot of the classification kernel becomes a dense per-instance moment
matrix, so the AMRules (cnt, sum, sumsq) moments -- and the default-rule
learner, via a 1-row stats tensor -- ride the same kernels as the VHT
counters.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed.sharding import kernel_mesh, run_replicated
from repro.kernels.rule_stats.kernel import rule_stats_pallas
from repro.kernels.rule_stats.ref import rule_stats_ref


def default_impl() -> str:
    """Pallas on backends that compile it; segment scatter elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "segment"


def rule_moments(y, w=None):
    """The AMRules moment matrix [B, 3]: (w, w*y, w*y^2) per instance."""
    w = jnp.ones_like(y) if w is None else w
    return jnp.stack([w, w * y, w * jnp.square(y)], -1)


def rule_stats_update_segment(stats, seg, xbin, mom):
    """Moment-segmented scatter-add, mirroring vht_stats' class-segmented
    formulation: each moment slice gets one [B, m, bins] rule-segment sum
    (mode="drop" discards seg == R, replacing the oracle's scratch row).
    The dense [B, m, bins, C] one-hot product never exists -- peak
    intermediate memory shrinks by the moment count, and the scatter stays
    the block-contiguous kind XLA vectorizes well.  R == 1 (the
    default-rule learner) needs no scatter at all: it reduces a masked
    product over the batch."""
    R, m, nb, C = stats.shape
    binoh = jax.nn.one_hot(xbin, nb, dtype=stats.dtype)            # [B,m,bins]
    if R == 1:
        momk = jnp.where(seg[:, None] == 0, mom, 0.0).astype(stats.dtype)
        for c in range(C):
            stats = stats.at[:, :, :, c].add(
                (binoh * momk[:, c][:, None, None]).sum(0)[None])
        return stats
    for c in range(C):
        mc = mom[:, c].astype(stats.dtype)
        stats = stats.at[seg, :, :, c].add(binoh * mc[:, None, None],
                                           mode="drop")
    return stats


def rule_stats_update(stats, seg, xbin, mom, *, impl: str = "auto",
                      attr_tile: int = 0, interpret: bool = False):
    """Accumulate weighted-moment statistics for a micro-batch.

    stats: [R, m, bins, C]; seg: [B] i32 in [0, R] (R = discard);
    xbin: [B, m] i32; mom: [B, C] f32.  impl="auto" picks Pallas on TPU and
    the segment scatter elsewhere; `attr_tile` overrides the Pallas
    kernel's heuristic attribute tile; `interpret=True` runs the Pallas
    kernel body in interpret mode (for validation off TPU).  Under a
    multi-device mesh the kernel runs replicated inside a shard_map
    (``run_replicated``).
    """
    return _rule_stats_update(stats, seg, xbin, mom, impl=impl,
                              attr_tile=attr_tile, interpret=interpret,
                              mesh=kernel_mesh())


@partial(jax.jit,
         static_argnames=("impl", "attr_tile", "interpret", "mesh"))
def _rule_stats_update(stats, seg, xbin, mom, *, impl, attr_tile,
                       interpret, mesh):
    if impl == "auto":
        impl = default_impl()
    if impl == "onehot":
        return rule_stats_ref(stats, seg, xbin, mom)
    if impl == "segment":
        return rule_stats_update_segment(stats, seg, xbin, mom)
    if impl != "pallas":
        raise ValueError(f"unknown stats impl {impl!r}")
    return run_replicated(
        partial(rule_stats_pallas, attr_tile=attr_tile, interpret=interpret),
        mesh, stats, seg, xbin, mom)
