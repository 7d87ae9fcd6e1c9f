"""Pallas kernel: multi-tree routing as shared-prefix one-hot MXU matmuls.

A pointer chase over a node pool is hostile to the TPU (serialized gather
per depth level, per member).  Reformulated per member: hold the member's
four node tables -- split_attr, split_bin, left child, right child -- as
one [4, N] f32 matrix resident in VMEM, and make every depth step a single

    vals[:, b] = tables @ node1h[:, b]          # [4, N] x [N, bt]

matmul (MXU work; the node one-hot is built in-register with
broadcasted_iota comparisons, never materialized in HBM).  The attribute
lookup v[b] = xbin[b, attr[b]] is a masked column reduction on the VPU
over the transposed [m, bt] instance block.  Instances run along the
lanes throughout, so the leaf ids come out as a lane-dense row and no
step relayouts.  The matmul runs at HIGHEST precision: node and
attribute ids above 256 are not exact in one bf16 pass, and with an
exactly 0/1 one-hot the f32 pass returns every id unrounded, so the
routing decisions -- and therefore the returned leaf ids -- are
bit-identical to the integer reference.

Grid = (members, instance tiles): every tree in the ensemble routes the
SAME micro-batch (the shared prefix), and each member's tables ride in as
a (1, 4, N) block of the stacked [M, 4, N] table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
i32 = jnp.int32
BATCH_TILE = 128
VMEM_LIMIT = 64 * 2 ** 20


def _kernel(tab_ref, xbin_ref, leaf_ref, *, max_depth):
    N = tab_ref.shape[2]
    m, bt = xbin_ref.shape
    tables = tab_ref[0].astype(f32)                      # [4, N]
    xb = xbin_ref[...].astype(f32)                       # [m, bt]
    iota_n = jax.lax.broadcasted_iota(i32, (N, bt), 0)
    iota_m = jax.lax.broadcasted_iota(i32, (m, bt), 0)

    def depth_step(_, node):
        node1h = (iota_n == node).astype(f32)            # [N, bt]
        vals = jax.lax.dot_general(
            tables, node1h, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32)                  # [4, bt]
        attr, thr = vals[0:1], vals[1:2]
        left, right = vals[2:3], vals[3:4]
        a = jnp.maximum(attr, 0.0).astype(i32)
        v = jnp.sum(jnp.where(iota_m == a, xb, 0.0), axis=0, keepdims=True)
        nxt = jnp.where(v > thr, right, left).astype(i32)
        return jnp.where(attr < 0, node, nxt)

    node = jax.lax.fori_loop(0, max_depth, depth_step,
                             jnp.zeros((1, bt), i32))
    leaf_ref[0] = node


def tree_route_pallas(split_attr, split_bin, children, xbin, max_depth: int,
                      *, interpret: bool = False):
    """split_attr/split_bin: [M, N]; children: [M, N, 2]; xbin: [B, m].
    Returns leaf ids [M, B] i32."""
    M, N = split_attr.shape
    B, m = xbin.shape
    bt = BATCH_TILE if B > BATCH_TILE else B
    Bp = -(-B // bt) * bt
    xbin_t = jnp.pad(xbin, ((0, Bp - B), (0, 0))).T if Bp != B else xbin.T
    tables = jnp.stack([split_attr, split_bin, children[..., 0],
                        children[..., 1]], axis=1)       # [M, 4, N]
    kern = functools.partial(_kernel, max_depth=max_depth)
    out = pl.pallas_call(
        kern,
        grid=(M, Bp // bt),
        in_specs=[
            pl.BlockSpec((1, 4, N), lambda j, b: (j, 0, 0)),  # member tables
            pl.BlockSpec((m, bt), lambda j, b: (0, b)),       # shared batch^T
        ],
        out_specs=pl.BlockSpec((1, 1, bt), lambda j, b: (j, 0, b)),
        out_shape=jax.ShapeDtypeStruct((M, 1, Bp), i32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="tree_route",
    )(tables.astype(i32), xbin_t.astype(i32))
    return out[:, 0, :B]
