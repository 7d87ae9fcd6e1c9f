"""Public dispatcher for the VHT statistics update.

Three implementations of the same contraction
``stats[n, j, b, c] += sum_i 1[leaf_i = n] 1[x_ij = b] 1[y_i = c] w_i``:

  pallas   -- one-hot MXU matmuls, statistics tile resident in VMEM
              (kernel.py).  Default on TPU; off TPU it runs only in
              interpret mode, and only when the caller asks for it.
  segment  -- class-segmented segment-sum: one [B, m, bins] leaf-segment
              scatter per class slice.  Never materializes the dense
              [B, m, bins, C] one-hot product (peak intermediate memory
              shrinks by the class count).  Default off-TPU.
  onehot   -- the legacy dense one-hot reference (ref.py); kept as the
              oracle for parity tests and before/after benchmarking.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed.sharding import kernel_mesh, run_replicated
from repro.kernels.vht_stats.kernel import stats_update_pallas
from repro.kernels.vht_stats.ref import stats_update_ref


def default_impl() -> str:
    """Pallas on backends that compile it; segment-sum elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "segment"


def stats_update_segment(stats, leaf, xbin, y, w):
    """Class-segmented scatter-add: the batch is partitioned into class
    segments by folding the class one-hot into per-class weights, and each
    class slice gets one [B, m, bins] leaf-segment sum.  The dense
    [B, m, bins, C] one-hot product never exists -- peak intermediate
    memory shrinks by the class count, and the scatter stays the
    block-contiguous kind XLA vectorizes well."""
    N, m, nb, C = stats.shape
    binoh = jax.nn.one_hot(xbin, nb, dtype=stats.dtype)            # [B,m,bins]
    for c in range(C):
        wc = (w * (y == c)).astype(stats.dtype)
        stats = stats.at[leaf, :, :, c].add(binoh * wc[:, None, None])
    return stats


def stats_update(stats, leaf, xbin, y, w, *, impl: str = "auto",
                 attr_tile: int = 0, interpret: bool = False,
                 n_classes: int = 0):
    """Accumulate VHT sufficient statistics for a micro-batch.

    stats is [N, m, bins, C]; the Pallas kernel also takes its packed
    row-major view [N, m*bins*C], given with ``n_classes`` = C, and
    returns that shape.  impl="auto" picks Pallas on TPU and the
    segment-sum formulation elsewhere; `attr_tile` overrides the Pallas
    kernel's heuristic attribute tile; `interpret=True` runs the Pallas
    kernel body in interpret mode (for validation off TPU).  Under a
    multi-device mesh the kernel runs replicated inside a shard_map
    (``run_replicated``).
    """
    return _stats_update(stats, leaf, xbin, y, w, impl=impl,
                         attr_tile=attr_tile, interpret=interpret,
                         n_classes=n_classes, mesh=kernel_mesh())


@partial(jax.jit, static_argnames=("impl", "attr_tile", "interpret",
                                   "n_classes", "mesh"))
def _stats_update(stats, leaf, xbin, y, w, *, impl, attr_tile, interpret,
                  n_classes, mesh):
    if impl == "auto":
        impl = default_impl()
    if impl == "onehot":
        return stats_update_ref(stats, leaf, xbin, y, w)
    if impl == "segment":
        return stats_update_segment(stats, leaf, xbin, y, w)
    if impl != "pallas":
        raise ValueError(f"unknown stats impl {impl!r}")
    return run_replicated(
        partial(stats_update_pallas, attr_tile=attr_tile,
                interpret=interpret, n_classes=n_classes),
        mesh, stats, leaf, xbin, y, w)
