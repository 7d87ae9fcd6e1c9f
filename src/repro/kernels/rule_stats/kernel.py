"""Pallas kernel: rule-statistics accumulation as one-hot MXU matmuls.

The weighted-moments form of the counter update shared by AMRules and the
VHT (repro.kernels.vht_stats builds its moment matrix from the class
one-hot): a dense per-instance moment matrix mom[i, c] (for AMRules:
(w, w*y, w*y^2)) covers regression moments, class counts and any other
per-instance weighting with one kernel:

    delta[r, j, b, c] = sum_i seg1h[i, r] * bin1h[i, j, b] * mom[i, c]

The statistics are handed over as their lane-dense 2-D view
``[R, m*bins*C]`` -- a reshape around the kernel for a 4-D caller, none
for a caller that holds them packed -- and tiled into
``(node tile, ja*bins*C)`` blocks whose column count is a multiple of
128, so no block puts a small axis in the lane dimension and the kernel
never reshapes.  Per block:

    VT[col, i]   = 1[xbin[i, j(col)] = b(col)] * mom[i, c(col)]   [T, B]
    delta[r, col] = sum_i seg1h[r, i] * VT[col, i]                 [nt, T]

the attribute column is spread over its bins*C lanes by a 0/1 expansion
matmul (exact: bin ids are small integers), the moment by a select per
moment, and the segment contraction is one matmul at HIGHEST precision,
so every f32 moment enters the sum unrounded (the one-hot side is exactly
0/1).  The statistics block is accumulated in place
(input_output_aliasing).  Instances with seg == R (uncovered /
discarded) match no row and contribute nothing.  Grid = (attribute
tiles, node tiles); a node count that is not a tile multiple leaves a
partial last block whose out-of-range rows are never written back.
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
HIGHEST = jax.lax.Precision.HIGHEST

TEMP_ELEMS = 2 ** 19       # cap on one [rows or cols, B] f32 temporary
VMEM_LIMIT = 64 * 2 ** 20  # scoped VMEM for the blocks + in-kernel temps


def node_tile_for(n: int, batch: int) -> int:
    """Statistics rows per block: all of them when few, else a multiple of
    8 sublanes whose [rows, B] segment one-hot stays under TEMP_ELEMS."""
    cap = max(8, min(512, TEMP_ELEMS // batch) // 8 * 8)
    return n if n <= cap else cap


def attr_tile_for(m: int, group: int, batch: int) -> int:
    """Attributes per block: the widest tile whose ``ja * group`` columns
    are a multiple of 128 lanes (and ``ja`` a multiple of 8 sublanes for
    the transposed bin block), divides ``m``, and keeps the [cols, B]
    value matrix under TEMP_ELEMS.  Narrow statistics take one
    full-width block; a width with no such divisor is padded by the
    caller."""
    max_cols = max(128, min(1024, TEMP_ELEMS // batch))
    if m * group <= max_cols:
        return m
    step = math.lcm(8, 128 // math.gcd(128, group))
    best = step
    for ja in range(step, max(max_cols // group, step) + 1, step):
        if m % ja == 0:
            best = ja
    return best


def _kernel(seg_ref, mom_ref, xbin_ref, stats_in_ref, stats_ref, *,
            n_bins, n_mom):
    nt, T = stats_ref.shape
    B = seg_ref.shape[1]
    ja = xbin_ref.shape[0]
    group = n_bins * n_mom

    # spread each attribute's bin id over its bins*C columns
    expand = (jax.lax.broadcasted_iota(i32, (T, ja), 0) // group
              == jax.lax.broadcasted_iota(i32, (T, ja), 1)).astype(f32)
    xcol = jax.lax.dot_general(
        expand, xbin_ref[...].astype(f32), (((1,), (0,)), ((), ())),
        preferred_element_type=f32)                        # [T, B]
    col = jax.lax.broadcasted_iota(i32, (T, B), 0)
    hit = xcol == ((col // n_mom) % n_bins).astype(f32)
    cls = col % n_mom
    mom = mom_ref[...]                                     # [C, B]
    v = jnp.zeros((T, B), f32)
    for c in range(n_mom):
        v = jnp.where(hit & (cls == c), mom[c:c + 1, :], v)

    row0 = pl.program_id(1) * nt
    seg1h = (jax.lax.broadcasted_iota(i32, (nt, B), 0) + row0
             == seg_ref[...]).astype(f32)                  # [nt, B]
    delta = jax.lax.dot_general(
        seg1h, v, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=f32)                        # [nt, T]
    stats_ref[...] = stats_in_ref[...] + delta


def packs(m: int, group: int, attr_tile: int = 0) -> bool:
    """Whether statistics of ``m`` attributes by ``group`` columns reach
    the kernel unpadded at every batch size (see ``attr_tile_for``), so a
    caller may hold them as the packed 2-D view ``[R, m*group]``."""
    if attr_tile:
        return m % min(attr_tile, m) == 0
    step = math.lcm(8, 128 // math.gcd(128, group))
    return m * group <= 128 or m % step == 0


def rule_stats_pallas(stats, seg, xbin, mom, *, attr_tile: int = 0,
                      interpret: bool = False,
                      name: str = "rule_stats_update"):
    """stats: [R, m, bins, C], or its packed 2-D view [R, m*bins*C] at a
    width that ``packs``; returns updated stats of the same shape (aliased
    in-place).  The packed view goes to the kernel with no reshape in or
    out.  ``name`` is the kernel's name in compiled programs and traces."""
    if stats.ndim == 2:
        return _pallas_2d(stats, seg, xbin, mom, attr_tile=attr_tile,
                          interpret=interpret, name=name)
    R, m, nb, C = stats.shape
    ja = min(attr_tile or attr_tile_for(m, nb * C, seg.shape[0]), m)
    mp = -(-m // ja) * ja
    if mp != m:
        xbin = jnp.pad(xbin, ((0, 0), (0, mp - m)))
        stats = jnp.pad(stats, ((0, 0), (0, mp - m), (0, 0), (0, 0)))
    out = _pallas_2d(stats.reshape(R, mp * nb * C), seg, xbin, mom,
                     attr_tile=ja, interpret=interpret, name=name)
    out = out.reshape(R, mp, nb, C)
    return out[:, :m] if mp != m else out


def _pallas_2d(stats, seg, xbin, mom, *, attr_tile, interpret, name):
    R, W = stats.shape
    B, m = xbin.shape
    C = mom.shape[1]
    nb = W // (m * C)
    group = nb * C
    ja = min(attr_tile or attr_tile_for(m, group, B), m)
    if m % ja:
        raise ValueError(f"{m} attributes need padding to tiles of {ja}: "
                         "pass the statistics as [R, m, bins, C]")
    nt = node_tile_for(R, B)
    T = ja * group

    kern = functools.partial(_kernel, n_bins=nb, n_mom=C)
    return pl.pallas_call(
        kern,
        grid=(m // ja, -(-R // nt)),
        in_specs=[
            pl.BlockSpec((1, B), lambda j, i: (0, 0)),     # seg
            pl.BlockSpec((C, B), lambda j, i: (0, 0)),     # moments^T
            pl.BlockSpec((ja, B), lambda j, i: (j, 0)),    # xbin^T tile
            pl.BlockSpec((nt, T), lambda j, i: (i, j)),    # stats in
        ],
        out_specs=pl.BlockSpec((nt, T), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, W), stats.dtype),
        input_output_aliases={3: 0},                       # stats aliased
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(seg.astype(i32)[None], mom.astype(f32).T, xbin.astype(i32).T, stats)
