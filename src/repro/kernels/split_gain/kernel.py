"""Pallas kernel: fused entropy / information-gain over statistics tiles.

The LS 'compute' event (paper Alg. 3): for each (leaf, attribute) compute
the split criterion over all candidate thresholds.  One pass over the
statistics tile resident in VMEM: cumulative class counts over the bin
axis, three entropies, and the weighted gain -- no HBM round-trips between
the reduction stages (XLA materializes cum/left/right to HBM between
fusions at large N*m).

The statistics arrive as their lane-dense 2-D view ``[N, m*bins*C]``
(column = (attr, bin, class)), tiled into ``(node tile, T)`` blocks of
whole attributes; the gains leave as ``[N, m*bins]`` in ``(node tile,
T/C)`` blocks.  Every reduction along the (bin, class) axes is a matmul
of the block with a 0/1 matrix built from iota comparisons: the prefix
sum over bins (block upper-triangular), the per-attribute class totals,
the per-threshold class sums -- so the kernel needs no cumsum and no
in-kernel reshape.  The matmuls run at HIGHEST precision: with exact 0/1
weights that reproduces f32 integer counts exactly.  A partial last
column block is masked to zero before any matmul (an out-of-range read
must not reach the in-range columns); out-of-range rows and columns are
never written back.  Grid = (node tiles, column tiles).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
i32 = jnp.int32
NEG = -1e30
HIGHEST = jax.lax.Precision.HIGHEST

NODE_TILE = 256
VMEM_LIMIT = 64 * 2 ** 20


def col_tile_for(m: int, n_bins: int, n_classes: int) -> int:
    """Columns per block: a whole number of attributes whose gain block
    (``T / C`` columns) is a multiple of 128 lanes; the full width when
    that is no wider."""
    group = n_bins * n_classes
    T = math.lcm(128 * n_classes, group)
    return m * group if m * group <= T else T


def _kernel(stats_ref, gain_ref, *, n_bins, n_classes, n_cols):
    nt, T = stats_ref.shape
    C = n_classes
    K = T // C
    group = n_bins * C
    col0 = pl.program_id(1) * T

    s = stats_ref[...].astype(f32)
    s = jnp.where(jax.lax.broadcasted_iota(i32, (nt, T), 1) + col0 < n_cols,
                  s, 0.0)
    r = jax.lax.broadcasted_iota(i32, (T, T), 0)
    c = jax.lax.broadcasted_iota(i32, (T, T), 1)
    same_cls = (r // group == c // group) & (r % C == c % C)
    prefix = (same_cls & ((r // C) % n_bins <= (c // C) % n_bins)).astype(f32)
    per_attr = same_cls.astype(f32)
    per_bin = (r // C == c // C).astype(f32)           # class-sum broadcast
    to_gain = (jax.lax.broadcasted_iota(i32, (T, K), 0) // C
               == jax.lax.broadcasted_iota(i32, (T, K), 1)).astype(f32)

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   precision=HIGHEST,
                                   preferred_element_type=f32)

    def entropy(counts):
        """(class sum [nt, K], entropy [nt, K]) per (attr, bin)."""
        tot = dot(counts, per_bin)
        p = counts / jnp.maximum(tot, 1e-12)
        plogp = jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-12)), 0.0)
        n = dot(counts, to_gain)
        return n, jnp.where(n > 0, -dot(plogp, to_gain), 0.0)

    left = dot(s, prefix)
    total = dot(s, per_attr)
    nl, hl = entropy(left)
    nr, hr = entropy(total - left)
    _, h_tot = entropy(total)
    n = jnp.maximum(nl + nr, 1e-12)
    gain = h_tot - (nl / n * hl + nr / n * hr)
    gain_ref[...] = jnp.where((nl > 0) & (nr > 0), gain, NEG)


def split_gain_pallas(stats, *, node_tile: int = 0, col_tile: int = 0,
                      interpret: bool = False):
    """stats: [N, m, bins, C] f32 -> gains [N, m, bins] f32."""
    N, m, nb, C = stats.shape
    W = m * nb * C
    nt = node_tile or (N if N <= NODE_TILE else NODE_TILE)
    T = col_tile or col_tile_for(m, nb, C)
    kern = functools.partial(_kernel, n_bins=nb, n_classes=C, n_cols=W)
    out = pl.pallas_call(
        kern,
        grid=(-(-N // nt), -(-W // T)),
        in_specs=[pl.BlockSpec((nt, T), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((nt, T // C), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, m * nb), f32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="split_gain",
    )(stats.astype(f32).reshape(N, W))
    return out.reshape(N, m, nb)
