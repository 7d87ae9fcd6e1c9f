"""Plain reference of the Vertical Hoeffding Tree, variant "local".

Written from the algorithm (SAMOA's VHT, arXiv:1805.11477, section 6;
Domingos & Hulten's VFDT with the Hoeffding bound), independent of the
program: it imports nothing of it.  One prequential step on a
micro-batch of binned instances (x [B, m] int32, y [B] int32):

1. test: each instance descends from the root -- at an internal node it
   goes right when its bin of the node's attribute is above the node's
   threshold bin -- and the leaf predicts its most frequent class (the
   lowest class on ties); the step counts correct predictions;
2. train: every instance adds one to the counter (leaf, class, attribute,
   bin) of each attribute, to its leaf's class counts, and to the leaf's
   instance count and grace counter;
3. split check, on every leaf whose grace counter reached ``n_min``:
   information gain (bits) of every threshold split "bin <= b" of every
   attribute; the best two attributes by their best threshold; split
   when the best gain is positive and beats the second by more than the
   Hoeffding bound eps = sqrt(R^2 ln(1/delta) / 2n), R = log2(classes),
   or when eps < tau; never a pure leaf or one at depth max_depth - 1.
   Every checked leaf's grace counter restarts at 0;
4. the splitting leaves, in node order, take the next two free node ids
   while the pool has room; the children start with the class counts of
   their side of the split and empty statistics, and the split node's
   statistics are released.

Counts are kept in ``dtype`` (float32: exact below 2^24).  The control of
the correctness check runs the same code with ``dtype=bfloat16``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

NEG = -1e30


def init(c: dict, dtype=jnp.float32) -> dict:
    N, m, nb, C = c["max_nodes"], c["n_attrs"], c["n_bins"], c["n_classes"]
    return {
        "split_attr": jnp.full((N,), -1, jnp.int32),
        "split_bin": jnp.zeros((N,), jnp.int32),
        "children": jnp.zeros((N, 2), jnp.int32),
        "depth": jnp.zeros((N,), jnp.int32),
        "n_nodes": jnp.ones((), jnp.int32),
        # counters[node, class, attr, bin]
        "counts": jnp.zeros((N, C, m, nb), dtype),
        "class_counts": jnp.zeros((N, C), dtype),
        "n_total": jnp.zeros((N,), dtype),
        "since_attempt": jnp.zeros((N,), dtype),
    }


def route(s, x, max_depth: int):
    """Leaf id of every instance."""
    rows = jnp.arange(x.shape[0])

    def down(_, node):
        a = s["split_attr"][node]
        right = x[rows, jnp.maximum(a, 0)] > s["split_bin"][node]
        nxt = s["children"][node, right.astype(jnp.int32)]
        return jnp.where(a < 0, node, nxt)

    return jax.lax.fori_loop(0, max_depth, down,
                             jnp.zeros((x.shape[0],), jnp.int32))


@partial(jax.jit, static_argnames=("max_depth",))
def predict(s, x, max_depth: int):
    leaf = route(s, x, max_depth)
    return jnp.argmax(s["class_counts"][leaf], axis=-1)


def _entropy(counts, n):
    """counts [..., C, m, bins], n [..., m, bins] -> entropy in bits."""
    p = counts / jnp.maximum(n, 1e-12)[..., None, :, :]
    plogp = jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-12)), 0.0)
    return -plogp.sum(-3)


def gains(counts):
    """counts [N, C, m, bins] -> gain of "bin <= b" for [N, m, bins]."""
    counts = counts.astype(jnp.float32)
    left = jnp.cumsum(counts, axis=-1)
    total = left[..., -1:]
    right = total - left
    nl, nr = left.sum(-3), right.sum(-3)
    n = nl + nr
    g = (_entropy(jnp.broadcast_to(total, left.shape), n)
         - (nl / jnp.maximum(n, 1e-12) * _entropy(left, nl)
            + nr / jnp.maximum(n, 1e-12) * _entropy(right, nr)))
    return jnp.where((nl > 0) & (nr > 0), g, NEG)


def decide(s, c: dict):
    """(split?, attribute, threshold bin) for every node."""
    g = gains(s["counts"])                              # [N, m, bins]
    per_attr = g.max(-1)
    best_bin = g.argmax(-1)
    attr = per_attr.argmax(-1)
    ga = per_attr.max(-1)
    m = per_attr.shape[-1]
    gb = jnp.where(jnp.arange(m)[None] == attr[:, None], NEG,
                   per_attr).max(-1)
    r = math.log2(max(c["n_classes"], 2))
    n = s["n_total"].astype(jnp.float32)
    eps = jnp.sqrt(r ** 2 * math.log(1.0 / c["delta"])
                   / (2.0 * jnp.maximum(n, 1.0)))
    ok = (ga > 0) & ((ga - gb > eps) | (eps < c["tau"]))
    pure = (s["class_counts"] > 0).sum(-1) <= 1
    bins = jnp.take_along_axis(best_bin, attr[:, None], 1)[:, 0]
    return ok & ~pure, attr, bins


def decide_due(s, due, c: dict, k: int = 32):
    """``decide`` where it matters: on no node when none is due, on the
    due nodes alone when at most ``k`` are (the others cannot split),
    else on every node."""
    N = due.shape[0]
    zeros = (jnp.zeros((N,), bool), jnp.zeros((N,), jnp.int32),
             jnp.zeros((N,), jnp.int32))
    keys = ("counts", "n_total", "class_counts")

    def some(st):
        idx = jnp.argsort(~due, stable=True)[:k]       # due nodes first
        ok, a, b = decide({key: st[key][idx] for key in keys}, c)
        return (zeros[0].at[idx].set(ok), zeros[1].at[idx].set(a),
                zeros[2].at[idx].set(b))

    n = due.sum()
    k = min(k, N)
    return jax.lax.cond(
        n == 0, lambda st: zeros,
        lambda st: jax.lax.cond(n <= k, some, lambda t: decide(t, c), st),
        {key: s[key] for key in keys})


def apply_splits(s, split, attr, tbin):
    N = split.shape[0]
    rank = jnp.cumsum(split.astype(jnp.int32)) - 1
    base = s["n_nodes"]
    do = split & (base + 2 * (rank + 1) <= N)
    lo = base + 2 * rank
    s = dict(s)
    s["split_attr"] = jnp.where(do, attr, s["split_attr"])
    s["split_bin"] = jnp.where(do, tbin, s["split_bin"])
    s["children"] = jnp.where(do[:, None], jnp.stack([lo, lo + 1], -1),
                              s["children"])
    rows = jnp.arange(N)
    side = s["counts"][rows, :, attr]                   # [N, C, bins]
    bins = jnp.arange(side.shape[-1])
    left = jnp.where(bins <= tbin[:, None, None], side, 0).sum(-1)
    right = side.sum(-1) - left
    li = jnp.where(do, lo, N)                           # N: dropped
    s["class_counts"] = (s["class_counts"].at[li].set(left, mode="drop")
                         .at[li + 1].set(right, mode="drop"))
    s["depth"] = (s["depth"].at[li].set(s["depth"] + 1, mode="drop")
                  .at[li + 1].set(s["depth"] + 1, mode="drop"))
    s["counts"] = jnp.where(do[:, None, None, None], 0, s["counts"])
    s["since_attempt"] = jnp.where(do, 0, s["since_attempt"])
    s["n_nodes"] = base + 2 * do.sum().astype(jnp.int32)
    return s


def step(s, x, y, c: dict):
    """One prequential step; returns (state, correct predictions)."""
    N, C, m, nb = s["counts"].shape
    B = x.shape[0]
    leaf = route(s, x, c["max_depth"])
    pred = jnp.argmax(s["class_counts"][leaf], axis=-1)
    correct = (pred == y).sum()

    dt = s["counts"].dtype
    who = jax.nn.one_hot(leaf * C + y, N * C, dtype=jnp.bfloat16)
    what = jax.nn.one_hot(x, nb, dtype=jnp.bfloat16).reshape(B, m * nb)
    add = jnp.dot(who.T, what, preferred_element_type=jnp.float32)
    s = dict(s)
    s["counts"] = s["counts"] + add.reshape(N, C, m, nb).astype(dt)
    s["class_counts"] = s["class_counts"].at[leaf, y].add(1)
    s["n_total"] = s["n_total"].at[leaf].add(1)
    s["since_attempt"] = s["since_attempt"].at[leaf].add(1)

    due = (s["split_attr"] < 0) & (s["since_attempt"] >= c["n_min"])
    split, attr, tbin = decide_due(s, due, c)
    split = split & due & (s["depth"] < c["max_depth"] - 1)
    s["since_attempt"] = jnp.where(due, 0, s["since_attempt"])
    s = jax.lax.cond(jnp.any(split),
                     lambda st: apply_splits(st, split, attr, tbin),
                     lambda st: st, s)
    return s, correct


@partial(jax.jit, static_argnames=("cfg",))
def run_chunk(s, x, y, cfg):
    """Steps over a chunk (x [L, B, m], y [L, B]); returns (state, correct
    per step).  ``cfg`` is a hashable tuple of the configuration items."""
    c = dict(cfg)
    return jax.lax.scan(lambda st, xy: step(st, *xy, c), s, (x, y))


def program_view(s) -> dict:
    """The state in the layout the program keeps: counters as
    [node, attr, bin, class]."""
    out = dict(s)
    out["stats"] = jnp.transpose(out.pop("counts"), (0, 2, 3, 1))
    return out
