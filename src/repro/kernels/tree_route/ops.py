"""Public dispatcher for the batched multi-tree router.

Three implementations of the same program -- sort one [B] micro-batch to a
leaf in each of M trees (the model-aggregator side of Alg. 1 line 1, run
for every ensemble member at once):

  pallas  -- shared-prefix one-hot gather program on the MXU: the member's
             node tables live in VMEM and every depth step is one
             [4, N] x [N, B] matmul (kernel.py).  Default on TPU; off
             TPU it runs only in interpret mode, when the caller asks.
  gather  -- flattened-table formulation: all M node tables concatenate to
             one [M*N] array and every depth step is a handful of flat 1-D
             takes over [M*B] indices -- no batched (vmap-of-gather)
             gathers, no fori_loop trip per member.  Default off-TPU.
  fori    -- the legacy per-member fori_loop (ref.py); kept as the parity
             oracle and for before/after benchmarking.

Routing is integer arithmetic throughout, so all three implementations are
exactly bit-identical (asserted in tests/test_fused.py and
tests/test_property.py).

Single-tree callers (htree.route / htree.predict) enter through the same
function with rank-1 tables: M == 1 skips the flat-offset bookkeeping
entirely, and B == 1 costs nothing extra (the takes are already flat).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed.sharding import kernel_mesh, run_replicated
from repro.kernels.tree_route.kernel import tree_route_pallas
from repro.kernels.tree_route.ref import tree_route_ref

i32 = jnp.int32


def default_impl() -> str:
    """Pallas on backends that compile it; flat gathers elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "gather"


def tree_route_gather(split_attr, split_bin, children, xbin, max_depth: int):
    """Flat-table router: one unrolled depth loop whose every step is a
    1-D take.  Member m's node n lives at flat row m*N + n, so a single
    gather serves all M trees; the shared micro-batch is addressed the
    same way (flat b*m_attrs + attr indices into xbin).  The M == 1 fast
    path (single-tree route) drops the offset bookkeeping."""
    M, N = split_attr.shape
    B, m = xbin.shape
    xflat = xbin.reshape(-1)
    brow = (jnp.arange(B, dtype=i32) * m)

    if M == 1:
        sa, sb = split_attr[0], split_bin[0]
        ch = children[0].reshape(-1)
        node = jnp.zeros((B,), i32)
        for _ in range(max_depth):
            attr = sa[node]
            is_leaf = attr < 0
            v = xflat[brow + jnp.maximum(attr, 0)]
            go_right = (v > sb[node]).astype(i32)
            node = jnp.where(is_leaf, node, ch[node * 2 + go_right])
        return node[None]

    sa = split_attr.reshape(-1)
    sb = split_bin.reshape(-1)
    ch = children.reshape(-1)
    base = (jnp.arange(M, dtype=i32) * N)[:, None]        # [M, 1]
    node = jnp.broadcast_to(base, (M, B))                 # flat root ids
    for _ in range(max_depth):
        attr = sa[node]                                   # [M, B]
        is_leaf = attr < 0
        v = xflat[brow[None] + jnp.maximum(attr, 0)]
        go_right = (v > sb[node]).astype(i32)
        nxt = base + ch[node * 2 + go_right]              # children are local
        node = jnp.where(is_leaf, node, nxt)
    return node - base


def tree_route(split_attr, split_bin, children, xbin, *, max_depth: int,
               impl: str = "auto", interpret: bool = False):
    """Route a shared [B, m] micro-batch through M trees -> leaf ids.

    split_attr/split_bin: [M, N] (or [N] for a single tree);
    children: [M, N, 2] (or [N, 2]); xbin: [B, m] i32.  Returns [M, B]
    ([B] when the tables were rank-1).  impl="auto" picks Pallas on TPU
    and the flat-gather formulation elsewhere; "fori" is the legacy
    oracle; `interpret=True` runs the Pallas kernel in interpret mode.
    Under a multi-device mesh the kernel runs replicated inside a
    shard_map (``run_replicated``).
    """
    return _tree_route(split_attr, split_bin, children, xbin,
                       max_depth=max_depth, impl=impl, interpret=interpret,
                       mesh=kernel_mesh())


@partial(jax.jit,
         static_argnames=("max_depth", "impl", "interpret", "mesh"))
def _tree_route(split_attr, split_bin, children, xbin, *, max_depth, impl,
                interpret, mesh):
    single = split_attr.ndim == 1
    if single:
        split_attr = split_attr[None]
        split_bin = split_bin[None]
        children = children[None]
    if impl == "auto":
        impl = default_impl()
    if impl == "fori":
        out = tree_route_ref(split_attr, split_bin, children, xbin, max_depth)
    elif impl == "gather":
        out = tree_route_gather(split_attr, split_bin, children, xbin,
                                max_depth)
    elif impl == "pallas":
        out = run_replicated(
            partial(tree_route_pallas, max_depth=max_depth,
                    interpret=interpret),
            mesh, split_attr, split_bin, children, xbin)
    else:
        raise ValueError(f"unknown route impl {impl!r}")
    return out[0] if single else out
