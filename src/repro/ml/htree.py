"""Tensorized streaming Hoeffding tree (VFDT) -- capacity-bounded, jit-able.

The JVM pointer tree becomes dense arrays (DESIGN.md section 2): a node pool
of `max_nodes`, binary threshold splits over *binned* attribute values, and
the sufficient statistics n_ijk as one tensor

    stats[node, attr, bin, class]

whose ATTRIBUTE axis is the paper's vertical-parallelism axis: key grouping
(leaf id, attr id) -> shard `attr` over the 'model' mesh axis.  One copy of
every counter lives in the system (the paper's memory argument); the split
criterion reduces over (bin, class) per attribute *in parallel across the
attribute shards*, exactly like the LS processors of Figure 2.

The tree functions also take the statistics as their packed row-major view
``stats[node, attr*bins*C]`` -- the statistics kernel's own layout, which a
scanned chunk program carries (``VHT.to_scan``) -- and pick the path from
the array's rank: 2 is packed.  Both give the same numbers.

Numeric attributes use histogram bins (the standard VFDT-with-histograms
approximation of MOA's Gaussian estimators); categorical attributes map
bins = categories and use one-vs-rest binary splits.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

f32 = jnp.float32
i32 = jnp.int32
NEG = -1e30


@dataclasses.dataclass(frozen=True)
class TreeConfig:
    n_attrs: int
    n_bins: int = 8
    n_classes: int = 2
    max_nodes: int = 255          # odd: root + 2k children
    max_depth: int = 24
    n_min: int = 200              # grace period between split attempts
    delta: float = 1e-7           # Hoeffding confidence
    tau: float = 0.05             # tie-break threshold
    split_delay: int = 0          # D engine-steps between decide & apply
    buffer_size: int = 0          # wk(z); 0 = wok when delay>0, local if D=0
    stats_impl: str = "auto"      # auto | pallas | segment | onehot (legacy)
    route_impl: str = "auto"      # auto | pallas | gather | fori (legacy)
    attr_tile: int = 0            # Pallas stats kernel attribute-tile override
    gate_splits: bool = True      # lax.cond-gate split checks on grace period
    check_tile: int = 16          # gated check: max due leaves examined via
                                  # gather before falling back to all nodes

    @property
    def range_r(self) -> float:
        return math.log2(max(self.n_classes, 2))


def init_tree(tc: TreeConfig):
    N = tc.max_nodes
    state = {
        "split_attr": jnp.full((N,), -1, i32),
        "split_bin": jnp.zeros((N,), i32),
        "children": jnp.zeros((N, 2), i32),
        "stats": jnp.zeros((N, tc.n_attrs, tc.n_bins, tc.n_classes), f32),
        "class_counts": jnp.zeros((N, tc.n_classes), f32),
        "since_attempt": jnp.zeros((N,), f32),
        "n_total": jnp.zeros((N,), f32),
        "depth": jnp.zeros((N,), i32),
        "n_nodes": jnp.ones((), i32),
        # pending split feedback (wok / wk(z) staleness emulation)
        "pending": jnp.zeros((N,), bool),
        "pending_attr": jnp.zeros((N,), i32),
        "pending_bin": jnp.zeros((N,), i32),
        "pending_timer": jnp.zeros((N,), i32),
        "n_splits": jnp.zeros((), i32),
    }
    if tc.buffer_size:
        state["buf_x"] = jnp.zeros((tc.buffer_size, tc.n_attrs), i32)
        state["buf_y"] = jnp.zeros((tc.buffer_size,), i32)
        state["buf_valid"] = jnp.zeros((tc.buffer_size,), bool)
        state["buf_n"] = jnp.zeros((), i32)
    return state


# --------------------------------------------------------------------------
# routing (model aggregator: sort instance to leaf -- Alg. 1 line 1)
# --------------------------------------------------------------------------

@jax.named_scope("route")
def route(state, xbin, tc: TreeConfig):
    """xbin: [B, m] int32 binned attributes -> leaf ids [B].

    Dispatched through repro.kernels.tree_route (the M == 1 fast path of
    the batched multi-tree router): Pallas one-hot matmuls on TPU, flat
    1-D gathers elsewhere, tc.route_impl="fori" keeps the legacy
    fori_loop oracle.  All impls return bit-identical leaf ids (integer
    routing)."""
    from repro.kernels.tree_route.ops import tree_route
    return tree_route(state["split_attr"], state["split_bin"],
                      state["children"], xbin, max_depth=tc.max_depth,
                      impl=tc.route_impl)


def route_members(trees, xbin, tc: TreeConfig, impl: str | None = None):
    """Route ONE shared micro-batch through M stacked member trees in a
    single batched router call -> leaf ids [M, B].  `trees` is the
    leading-axis-stacked tree state of an ensemble; the per-member
    fori_loop-in-vmap this replaces serialized a batched gather per depth
    level."""
    from repro.kernels.tree_route.ops import tree_route
    return tree_route(trees["split_attr"], trees["split_bin"],
                      trees["children"], xbin, max_depth=tc.max_depth,
                      impl=impl if impl is not None else tc.route_impl)


def predict(state, xbin, tc: TreeConfig):
    leaf = route(state, xbin, tc)
    counts = state["class_counts"][leaf]
    return jnp.argmax(counts, axis=-1), leaf


# --------------------------------------------------------------------------
# statistics update (LS processors: Alg. 2)
# --------------------------------------------------------------------------

@jax.named_scope("stats_update")
def update_stats(state, leaf, xbin, y, w, tc: TreeConfig):
    """Accumulate n_ijk for a micro-batch.  w: [B] weights (0 = dropped).

    Dispatched through repro.kernels.vht_stats: one-hot MXU matmuls on TPU
    (Pallas, default there), a class-segmented segment-sum elsewhere --
    neither materializes the dense [B, m, bins, C] one-hot product.
    """
    from repro.kernels.vht_stats.ops import stats_update
    clsoh = jax.nn.one_hot(y, tc.n_classes, dtype=f32) * w[:, None]
    state = dict(state)
    packed = state["stats"].ndim == 2
    state["stats"] = stats_update(
        state["stats"], leaf, xbin, y, w, impl=tc.stats_impl,
        attr_tile=tc.attr_tile, n_classes=tc.n_classes if packed else 0)
    state["class_counts"] = state["class_counts"].at[leaf].add(clsoh)
    state["since_attempt"] = state["since_attempt"].at[leaf].add(w)
    state["n_total"] = state["n_total"].at[leaf].add(w)
    return state


# --------------------------------------------------------------------------
# split criterion (LS: Alg. 3 + MA: Alg. 4)
# --------------------------------------------------------------------------

def split_gains(stats, tc: TreeConfig):
    """Information gain for every (node, attr, threshold-bin).

    stats: [N, m, bins, C] -> gains [N, m, bins]; the reduction over
    (bins, C) is the per-attribute work the paper parallelizes across LS
    processors -- under GSPMD the attr axis is sharded, so this reduction
    IS the parallel criterion computation.  Routed through
    repro.kernels.split_gain: the fused Pallas kernel on TPU, the
    numerically identical jnp reference elsewhere.
    """
    from repro.kernels.split_gain.ops import split_gain
    return split_gain(stats)


def hoeffding_bound(n, tc: TreeConfig):
    return jnp.sqrt(tc.range_r ** 2 * math.log(1.0 / tc.delta) / (2.0 * jnp.maximum(n, 1.0)))


def unpacked(stats, tc: TreeConfig):
    """[R, m, bins, C] statistics from either layout (a packed view is
    reshaped: a relayout, so only on paths that need the 4-D form)."""
    if stats.ndim == 2:
        return stats.reshape(-1, tc.n_attrs, tc.n_bins, tc.n_classes)
    return stats


def _decide_splits_impl(state, tc: TreeConfig):
    gains = split_gains(unpacked(state["stats"], tc), tc)   # [N, m, bins]
    N, m, bins = gains.shape
    # paper (Alg. 3/4): compare the best TWO ATTRIBUTES -- adjacent bins of
    # one attribute have near-identical gain and would make DeltaG ~ 0
    per_attr = gains.max(-1)                            # [N, m]
    best_bin_per_attr = gains.argmax(-1)                # [N, m]
    top2, idx2 = jax.lax.top_k(per_attr, 2)
    ga, gb = top2[:, 0], top2[:, 1]
    best_attr = idx2[:, 0]
    best_bin = jnp.take_along_axis(best_bin_per_attr, best_attr[:, None],
                                   1)[:, 0]
    eps = hoeffding_bound(state["n_total"], tc)
    is_leaf = state["split_attr"] < 0
    cls = state["class_counts"]
    pure = (cls > 0).sum(-1) <= 1
    attempted = state["since_attempt"] >= tc.n_min
    ok = (ga > 0) & ((ga - gb > eps) | (eps < tc.tau))
    depth_ok = state["depth"] < tc.max_depth - 1
    should = is_leaf & attempted & (~pure) & ok & depth_ok & (~state["pending"])
    return should, best_attr, best_bin


_DECIDE_KEYS = ("stats", "n_total", "split_attr", "class_counts",
                "since_attempt", "depth", "pending")


def due_topk(due, score, k):
    """Indices of up to k due rows, highest score first.  Non-due rows
    score -1 so they rank last; when fewer than k rows are due the filler
    rows MUST be masked out again by the caller's attempted/due test."""
    return jax.lax.top_k(jnp.where(due, score, -1.0), k)[1]


def child_counts_from_stats(stats, best_attr, best_bin, tc=None):
    """Left/right child class distributions for the chosen (attr, bin)
    thresholds, derived from the statistics cumsum over the bin axis.
    stats: [R, m, bins, C], or the packed [R, m*bins*C] with ``tc``, of
    which only each row's chosen attribute ([R, bins*C] columns) is
    gathered and summed; best_attr/best_bin: [R] -> ([R, C], [R, C])."""
    rows = jnp.arange(stats.shape[0])
    if stats.ndim == 2:
        group = tc.n_bins * tc.n_classes
        cols = jnp.maximum(best_attr, 0)[:, None] * group + jnp.arange(group)
        cum = jnp.cumsum(jnp.take_along_axis(stats, cols, 1).reshape(
            -1, tc.n_bins, tc.n_classes), axis=1)
        left = cum[rows, jnp.maximum(best_bin, 0)]
        return left, cum[:, -1] - left
    cum = jnp.cumsum(stats, axis=2)
    left = cum[rows, jnp.maximum(best_attr, 0), jnp.maximum(best_bin, 0)]
    right = cum[rows, jnp.maximum(best_attr, 0), -1] - left
    return left, right


def _splitting_child_counts(stats, do, best_attr, best_bin, tc: TreeConfig):
    """Child class distributions of the splitting rows ``do`` of packed
    statistics, [N, C] each (rows not splitting hold filler).  When no
    more rows split than the check tile holds -- always after a gathered
    split check -- only those rows are gathered; else every row is."""
    N = stats.shape[0]
    K = min(tc.check_tile, N)

    def few(_):
        idx = due_topk(do, jnp.zeros((N,), f32), K)
        left, right = child_counts_from_stats(stats[idx], best_attr[idx],
                                              best_bin[idx], tc)
        zero = jnp.zeros((N, tc.n_classes), f32)
        return zero.at[idx].set(left), zero.at[idx].set(right)

    return jax.lax.cond(
        jnp.sum(do.astype(i32)) <= K, few,
        lambda _: child_counts_from_stats(stats, best_attr, best_bin, tc),
        None)


def gather_decide_tile(flat_state, due, k, tc: TreeConfig,
                       with_children=False):
    """Gather up to k due rows of a (possibly member-flattened) node pool
    -- top-k on the grace counter -- and run the split decision on just
    that tile.  Returns (idx, should_k, attr_k, bin_k) plus the gathered
    rows' child class distributions when ``with_children``.  Filler rows
    (fewer than k due) fail _decide_splits_impl's attempted test, so
    their should_k is always False."""
    idx = due_topk(due, flat_state["since_attempt"], k)
    sub = {key: flat_state[key][idx] for key in _DECIDE_KEYS}
    s_k, a_k, b_k = _decide_splits_impl(sub, tc)
    if not with_children:
        return idx, s_k, a_k, b_k
    left_k, right_k = child_counts_from_stats(sub["stats"], a_k, b_k, tc)
    return idx, s_k, a_k, b_k, left_k, right_k


def gated_check(n_due, k, gathered, full, idle, operand):
    """The exact split-check gate shared by decide_splits and the LS
    processor: skip entirely when nothing is due, reduce a gathered row
    tile when the due set fits k, fall back to the full reduction
    otherwise."""
    return jax.lax.cond(
        n_due > 0,
        lambda op: jax.lax.cond(n_due <= k, gathered, full, op),
        idle, operand)


@jax.named_scope("split_check")
def decide_splits(state, tc: TreeConfig):
    """MA Receive(local_result): top-2 across attributes, Hoeffding test.

    Returns (should_split[N], best_attr[N], best_bin[N]).  With
    tc.gate_splits the gain reduction is lax.cond-gated on the grace
    period, exactly:

      * no leaf due            -> skip entirely; all-False is exact because
                                  only attempted leaves can split
      * <= check_tile leaves due -> gather just those rows (top_k on the
                                  grace counter) and reduce [K, m, bins, C]
                                  instead of [N, m, bins, C] (packed
                                  statistics: [K, m*bins*C] rows, reshaped
                                  as a tile); non-gathered
                                  nodes cannot split, and best_attr/bin are
                                  consumed only where should_split holds
      * more due than the tile -> fall back to the full reduction
    """
    if not tc.gate_splits:
        return _decide_splits_impl(state, tc)
    N = tc.max_nodes
    K = min(tc.check_tile, N)
    due = (state["split_attr"] < 0) & (state["since_attempt"] >= tc.n_min)

    def gathered(st):
        idx, s_k, a_k, b_k = gather_decide_tile(st, due, K, tc)
        return (jnp.zeros((N,), bool).at[idx].set(s_k),
                jnp.zeros((N,), i32).at[idx].set(a_k),
                jnp.zeros((N,), i32).at[idx].set(b_k))

    def idle(st):
        return (jnp.zeros((N,), bool), jnp.zeros((N,), i32),
                jnp.zeros((N,), i32))

    return gated_check(jnp.sum(due.astype(i32)), K, gathered,
                       lambda s: _decide_splits_impl(s, tc), idle, state)


@jax.named_scope("split_apply")
def apply_splits(state, split_mask, best_attr, best_bin, tc: TreeConfig,
                 child_counts=None):
    """Replace chosen leaves by split nodes, allocate 2 children each
    (MA Alg. 4 lines 6-10; the 'drop' event = children stats start at 0).

    `child_counts=(left[N, C], right[N, C])` supplies the child class
    distributions directly (the MA processor receives them in the
    local-result event and holds no statistics tensor); otherwise they are
    derived from state["stats"].  With tc.gate_splits the whole rewiring --
    including the child-distribution cumsum -- is skipped (lax.cond) on
    steps where no leaf splits, the common case in steady state."""
    if not tc.gate_splits:
        return _apply_splits_impl(state, split_mask, best_attr, best_bin, tc,
                                  child_counts)
    return jax.lax.cond(
        jnp.any(split_mask),
        lambda op: _apply_splits_impl(op[0], op[1], op[2], op[3], tc, op[4]),
        lambda op: (op[0], jnp.zeros((tc.max_nodes,), bool)),
        (state, split_mask, best_attr, best_bin, child_counts))


def _apply_splits_impl(state, split_mask, best_attr, best_bin, tc: TreeConfig,
                       child_counts=None):
    N = tc.max_nodes
    rank = jnp.cumsum(split_mask.astype(i32)) - 1       # [N]
    base = state["n_nodes"]
    room = (base + 2 * (rank + 1)) <= N
    do = split_mask & room
    lchild = base + 2 * rank
    rchild = base + 2 * rank + 1
    n_new = 2 * jnp.sum(do.astype(i32))

    state = dict(state)
    state["split_attr"] = jnp.where(do, best_attr, state["split_attr"])
    state["split_bin"] = jnp.where(do, best_bin, state["split_bin"])
    ch = state["children"]
    ch = jnp.where(do[:, None], jnp.stack([lchild, rchild], -1), ch)
    state["children"] = ch

    # initialize children class counts from the split distribution
    if child_counts is not None:
        left_cnt, right_cnt = child_counts
    elif state["stats"].ndim == 2:
        left_cnt, right_cnt = _splitting_child_counts(
            state["stats"], do, best_attr, best_bin, tc)
    else:
        left_cnt, right_cnt = child_counts_from_stats(
            state["stats"], best_attr, best_bin)

    # scratch-row scatter: rows not splitting write to a throwaway slot N
    l_idx = jnp.where(do, jnp.clip(lchild, 0, N - 1), N)
    r_idx = jnp.where(do, jnp.clip(rchild, 0, N - 1), N)

    def set_rows(arr, idx, val):
        pad_shape = (1, *arr.shape[1:])
        padded = jnp.concatenate([arr, jnp.zeros(pad_shape, arr.dtype)], 0)
        return padded.at[idx].set(val.astype(arr.dtype))[:N]

    cc = state["class_counts"]
    cc = set_rows(cc, l_idx, left_cnt)
    cc = set_rows(cc, r_idx, right_cnt)
    state["class_counts"] = cc
    child_depth = state["depth"] + 1
    dep = set_rows(state["depth"], l_idx, child_depth)
    dep = set_rows(dep, r_idx, child_depth)
    state["depth"] = dep
    # release the split leaf's statistics (drop content event); the MA
    # processor holds no statistics tensor -- its LS peers drop theirs on
    # the broadcast 'drop' event instead
    if "stats" in state:
        zero = jnp.zeros_like(state["stats"][0])
        rows = do[(slice(None),) + (None,) * zero.ndim]
        state["stats"] = jnp.where(rows, zero[None], state["stats"])
    state["since_attempt"] = jnp.where(do, 0.0, state["since_attempt"])
    state["n_nodes"] = base + n_new
    state["n_splits"] = state["n_splits"] + jnp.sum(do.astype(i32))
    return state, do
