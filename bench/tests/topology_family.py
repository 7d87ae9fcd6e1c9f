"""A family for the harness's tests on a mesh: the program's VHT topology
(``build_vht_topology``, the paper's Figure 2) and a plain reference of
what it computes.

The harness takes it in place of ``bench/families/vht.py`` (a test sets
``Cell.family``); no cell of ``BENCHMARK.json`` names it.  The topology
differs from the bare ``VHT`` the VHT cells run, and its reference says
how, step by step:

1. the model aggregator first applies the split feedback the local
   statistics answered one step earlier (rank order, while the pool has
   room); every leaf that was to split, with room or not, takes the two
   children's class counts summed as its own and is dropped from the
   statistics at the end of this step;
2. it routes the instances through the tree, predicts a leaf's most
   frequent class (its class counts change only at splits), adds to the
   leaf's instance and grace counters, and marks the leaves whose grace
   counter reached ``n_min`` (restarting it);
3. the local statistics add every instance, then answer for each marked
   leaf: the best and second-best (attribute, bin) of all, the Hoeffding
   test on their gains, and the children's class counts.

The reference (all but ``learner``) imports nothing of the program, and
places its statistics over the mesh it is given, split by attribute as
the program's are.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.families import vht as bare_family
from bench.ref import vht as bare

METRIC = bare_family.METRIC
EXACT = bare_family.EXACT
FLOAT = ()
sizes = bare_family.sizes
stream = bare_family.stream
ref_predict = bare_family.ref_predict
metric_gap = bare_family.metric_gap
P_ATTRS = P(None, None, "model", None)     # counts [node, class, attr, bin]


def learner(cfg: dict):
    """The program's VHT topology, with a sink that counts the model
    aggregator's correct predictions on the task's ``metrics`` stream."""
    from repro.core.topology import Grouping, Processor
    from repro.ml.htree import TreeConfig
    from repro.ml.vht import VHTConfig, build_vht_topology

    class Prequential(Processor):
        name = "prequential"

        def process(self, state, inputs):
            p = inputs["prediction"]
            return state, {"metrics": {
                "correct": jnp.sum((p["pred"] == p["y"]).astype(jnp.float32)),
                "seen": jnp.asarray(p["y"].shape[0], jnp.float32)}}

    c = sizes(cfg)
    topo = build_vht_topology(VHTConfig(TreeConfig(
        n_attrs=c["n_attrs"], n_bins=c["n_bins"], n_classes=c["n_classes"],
        max_nodes=c["max_nodes"], max_depth=c["max_depth"],
        n_min=c["n_min"], delta=c["delta"], tau=c["tau"])))
    topo.processors["prequential"] = Prequential()
    topo.parallelism["prequential"] = 1
    topo.streams["prediction"].destinations.append(
        ("prequential", Grouping.ALL))
    return topo


def program_state(states) -> dict:
    """The compared arrays from the topology's processor states."""
    return {**{k: states["model-aggregator"][k] for k in EXACT
               if k != "stats"}, "stats": states["local-statistic"]["stats"]}


def ref_init(cfg: dict, dtype=jnp.float32, mesh=None):
    c = sizes(cfg)
    N, C = c["max_nodes"], c["n_classes"]
    s = bare.init(c, dtype)
    s.update(fb_should=jnp.zeros((N,), bool),
             fb_attr=jnp.zeros((N,), jnp.int32),
             fb_bin=jnp.zeros((N,), jnp.int32),
             fb_left=jnp.zeros((N, C), dtype),
             fb_right=jnp.zeros((N, C), dtype))
    if mesh is None:
        return s
    rep = NamedSharding(mesh, P())
    return {k: jax.device_put(v, NamedSharding(mesh, P_ATTRS)
                              if k == "counts" else rep)
            for k, v in s.items()}


def _answer(counts, n_total, attempt, c: dict):
    """(split?, attribute, bin, left and right class counts) of each row:
    the best two (attribute, bin) of all by gain, the Hoeffding test."""
    R, C, m, nb = counts.shape
    g = bare.gains(counts).reshape(R, m * nb)
    top2, idx2 = jax.lax.top_k(g, 2)
    ga, gb = top2[:, 0], top2[:, 1]
    attr, tbin = idx2[:, 0] // nb, idx2[:, 0] % nb
    r = math.log2(max(c["n_classes"], 2))
    eps = jnp.sqrt(r ** 2 * math.log(1.0 / c["delta"])
                   / (2.0 * jnp.maximum(n_total.astype(jnp.float32), 1.0)))
    ok = (ga > 0) & ((ga - gb > eps) | (eps < c["tau"]))
    side = counts[jnp.arange(R), :, attr]               # [R, C, bins]
    left = jnp.where(jnp.arange(nb) <= tbin[:, None, None], side, 0).sum(-1)
    return attempt & ok, attr, tbin, left, side.sum(-1) - left


def _answer_due(counts, n_total, attempt, c: dict, k: int = 32):
    """``_answer`` where it matters: no row when none is marked, the
    marked rows alone when at most ``k`` are, else every row.  Only a
    marked row can split, so the others' answers are never read."""
    N, C = counts.shape[:2]
    none = (jnp.zeros((N,), bool), jnp.zeros((N,), jnp.int32),
            jnp.zeros((N,), jnp.int32), jnp.zeros((N, C), counts.dtype),
            jnp.zeros((N, C), counts.dtype))

    def some(_):
        idx = jnp.argsort(~attempt, stable=True)[:k]   # marked rows first
        got = _answer(counts[idx], n_total[idx], attempt[idx], c)
        return tuple(z.at[idx].set(v) for z, v in zip(none, got))

    n = attempt.sum()
    k = min(k, N)
    return jax.lax.cond(
        n == 0, lambda _: none,
        lambda _: jax.lax.cond(n <= k, some,
                               lambda _: _answer(counts, n_total, attempt, c),
                               None), None)


def _step(s, x, y, c: dict, counts_sharding):
    N, C, m, nb = s["counts"].shape
    B = x.shape[0]
    s = dict(s)
    # 1. the model aggregator applies last step's split feedback
    should = s["fb_should"] & (s["split_attr"] < 0)
    rank = jnp.cumsum(should.astype(jnp.int32)) - 1
    base = s["n_nodes"]
    do = should & (base + 2 * (rank + 1) <= N)
    lo = base + 2 * rank
    li = jnp.where(do, lo, N)                           # N: dropped
    s["split_attr"] = jnp.where(do, s["fb_attr"], s["split_attr"])
    s["split_bin"] = jnp.where(do, s["fb_bin"], s["split_bin"])
    s["children"] = jnp.where(do[:, None], jnp.stack([lo, lo + 1], -1),
                              s["children"])
    cc = (s["class_counts"].at[li].set(s["fb_left"], mode="drop")
          .at[li + 1].set(s["fb_right"], mode="drop"))
    s["class_counts"] = jnp.where(should[:, None],
                                  s["fb_left"] + s["fb_right"], cc)
    s["depth"] = (s["depth"].at[li].set(s["depth"] + 1, mode="drop")
                  .at[li + 1].set(s["depth"] + 1, mode="drop"))
    s["since_attempt"] = jnp.where(do, 0, s["since_attempt"])
    s["n_nodes"] = base + 2 * do.sum().astype(jnp.int32)
    # 2. it routes, predicts and counts
    leaf = bare.route(s, x, c["max_depth"])
    pred = jnp.argmax(s["class_counts"][leaf], axis=-1)
    correct = (pred == y).sum()
    s["n_total"] = s["n_total"].at[leaf].add(1)
    since = s["since_attempt"].at[leaf].add(1)
    attempt = since >= c["n_min"]
    s["since_attempt"] = jnp.where(attempt, 0, since)
    # 3. the statistics add the instances and answer the marked leaves
    dt = s["counts"].dtype
    who = jax.nn.one_hot(leaf * C + y, N * C, dtype=jnp.bfloat16)
    what = jax.nn.one_hot(x, nb, dtype=jnp.bfloat16).reshape(B, m * nb)
    add = jnp.dot(who.T, what, preferred_element_type=jnp.float32)
    counts = s["counts"] + add.reshape(N, C, m, nb).astype(dt)
    if counts_sharding is not None:
        counts = jax.lax.with_sharding_constraint(counts, counts_sharding)
    s.update(zip(("fb_should", "fb_attr", "fb_bin", "fb_left", "fb_right"),
                 _answer_due(counts, s["n_total"], attempt, c)))
    # the leaves that were to split leave the statistics
    s["counts"] = jnp.where(should[:, None, None, None], 0, counts)
    return s, correct


@partial(jax.jit, static_argnames=("cfg", "counts_sharding"))
def _run_chunk(s, x, y, cfg, counts_sharding):
    c = dict(cfg)
    return jax.lax.scan(lambda st, xy: _step(st, *xy, c, counts_sharding),
                        s, (x, y))


def ref_chunk(s, x, y, cfg: dict):
    """(state, correct predictions per step) over one chunk."""
    sh = s["counts"].sharding
    return _run_chunk(s, x, y, tuple(sorted(sizes(cfg).items())),
                      sh if isinstance(sh, NamedSharding) else None)


def ref_view(s) -> dict:
    return bare.program_view({k: v for k, v in s.items()
                              if not k.startswith("fb_")})
