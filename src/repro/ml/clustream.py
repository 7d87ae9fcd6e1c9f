"""Distributed CluStream (paper section 5): online micro-clusters + periodic
micro-batch macro-clustering.

Micro-clusters are cluster-feature vectors CF = (n, LS, SS, LT, ST) kept as
dense tensors [K, ...].  Online phase: each instance joins its nearest
micro-cluster if within the RMS radius boundary, else replaces the stalest
cluster (capacity-bounded: no dynamic allocation).  Every `period`
instances a micro-batch k-means over micro-cluster centroids produces the
macro-clusters -- exactly the paper's "triggered periodically, configured
via a command line parameter (e.g. every 10 000 examples)".

Performance (the fused/kernelized path):
  * nearest-cluster search uses the MXU matmul identity
    ||x - c||^2 = ||x||^2 + ||c||^2 - 2 x.c^T instead of materializing the
    [B, K, d] broadcast difference (CluStreamConfig.stats_impl="onehot"
    keeps the legacy broadcast + dense one-hot formulation as the oracle);
  * the CF scatter is a segment-sum over the assignment ids -- no [B, K+1]
    one-hot matmuls;
  * the CluStream learner class scans the whole stream (one compiled
    program) with the macro phase lax.cond-gated on the period boundary.

Distribution: horizontal -- the stream shards over the data axis, each
shard maintains local micro-clusters, and the macro phase merges them (a
psum-style reduction), matching SAMOA's distributed CluStream design.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.distributed.sharding import active_mesh

f32 = jnp.float32
i32 = jnp.int32




@dataclasses.dataclass(frozen=True)
class CluStreamConfig:
    n_dims: int
    n_micro: int = 100
    n_macro: int = 5
    radius_factor: float = 2.0
    period: int = 10_000        # macro-clustering trigger (instances)
    kmeans_iters: int = 10
    stats_impl: str = "auto"    # auto | segment (matmul+segment-sum) |
                                # onehot (legacy broadcast + one-hot matmul)
    macro_impl: str = "step"    # step (lax.cond inside every scanned step
                                #   -- the oracle, works on any driver) |
                                # boundary (macro k-means hoisted to the
                                #   chunk-boundary hook: the branch leaves
                                #   the step HLO entirely; requires the
                                #   chunked driver and fires on the first
                                #   boundary after each period crossing --
                                #   align period to chunk_len * batch for
                                #   step-mode-equivalent trigger points)


def _impl(cc: CluStreamConfig) -> str:
    if cc.stats_impl == "auto":
        return "segment"
    if cc.stats_impl not in ("segment", "onehot"):
        raise ValueError(f"unknown stats impl {cc.stats_impl!r}")
    return cc.stats_impl


def _macro_impl(cc: CluStreamConfig) -> str:
    if cc.macro_impl not in ("step", "boundary"):
        raise ValueError(f"unknown macro impl {cc.macro_impl!r}")
    return cc.macro_impl


def init_clustream(cc: CluStreamConfig, key, init_x=None):
    K, d = cc.n_micro, cc.n_dims
    if init_x is None:
        centers = jax.random.uniform(key, (K, d))
    else:
        centers = init_x[:K]
    # seed with a generous per-cluster variance so cold clusters absorb
    # their neighbourhood instead of starving (radius ~ 0.3*sqrt(d))
    var0 = 0.1
    return {
        "n": jnp.ones((K,), f32) * 1e-3,
        "ls": centers * 1e-3,
        "ss": (jnp.square(centers) + var0) * 1e-3,
        "lt": jnp.zeros((K,), f32),
        "st": jnp.zeros((K,), f32),
        "t": jnp.zeros((), f32),
    }


def _centroids(state):
    return state["ls"] / jnp.maximum(state["n"][:, None], 1e-9)


def _radius(state):
    n = jnp.maximum(state["n"], 1e-9)
    var = jnp.maximum(state["ss"] / n[:, None]
                      - jnp.square(state["ls"] / n[:, None]), 0.0)
    return jnp.sqrt(var.sum(-1))


def pairwise_d2(x, c, impl: str = "segment"):
    """[B, K] squared distances.  The fused path is one [B, d] x [d, K]
    matmul plus rank-1 norms (MXU work); the legacy path materializes the
    [B, K, d] broadcast difference."""
    if impl == "onehot":
        return jnp.sum(jnp.square(x[:, None] - c[None]), -1)
    d2 = (jnp.sum(jnp.square(x), -1)[:, None]
          + jnp.sum(jnp.square(c), -1)[None]
          - 2.0 * x @ c.T)
    return jnp.maximum(d2, 0.0)


def _cf_scatter(state, x, t, seg, cc: CluStreamConfig):
    """Accumulate CF moments (n, LS, SS, LT, ST) by micro-cluster id.
    seg: [B] in [0, K] with K = discard (outside every radius)."""
    K = cc.n_micro
    state = dict(state)
    if _impl(cc) == "onehot":
        oh = jax.nn.one_hot(seg, K + 1, dtype=f32)[:, :K]
        state["n"] = state["n"] + oh.sum(0)
        state["ls"] = state["ls"] + oh.T @ x
        state["ss"] = state["ss"] + oh.T @ jnp.square(x)
        state["lt"] = state["lt"] + oh.T @ t
        state["st"] = state["st"] + oh.T @ jnp.square(t)
        return state
    seg_sum = lambda v: jax.ops.segment_sum(v, seg, num_segments=K + 1)[:K]
    state["n"] = state["n"] + seg_sum(jnp.ones_like(t))
    state["ls"] = state["ls"] + seg_sum(x)
    state["ss"] = state["ss"] + seg_sum(jnp.square(x))
    state["lt"] = state["lt"] + seg_sum(t)
    state["st"] = state["st"] + seg_sum(jnp.square(t))
    return state


def update(state, x, cc: CluStreamConfig):
    """Online phase for a micro-batch x: [B, d]."""
    B = x.shape[0]
    impl = _impl(cc)
    cent = _centroids(state)
    d2 = pairwise_d2(x, cent, impl)                          # [B, K]
    nearest = jnp.argmin(d2, -1)
    ndist = jnp.sqrt(jnp.take_along_axis(d2, nearest[:, None], 1)[:, 0])
    rad = _radius(state)[nearest] * cc.radius_factor + 1e-6
    absorb = ndist <= rad

    t = state["t"] + jnp.arange(1, B + 1, dtype=f32)
    K = cc.n_micro
    seg = jnp.where(absorb, nearest, K)
    state = _cf_scatter(state, x, t, seg, cc)

    # non-absorbed instances replace the stalest micro-clusters (batch: the
    # first such instance wins; capacity-bounded replacement)
    stale = state["lt"] / jnp.maximum(state["n"], 1e-9)
    victim = jnp.argmin(stale)
    first_new = jnp.argmax(~absorb)
    any_new = jnp.any(~absorb)
    xn = x[first_new]
    tn = t[first_new]
    def repl(arr, val):
        return jnp.where(
            (jnp.arange(K) == victim).reshape((-1,) + (1,) * (arr.ndim - 1))
            & any_new, val, arr)
    state["n"] = repl(state["n"], 1.0)
    state["ls"] = repl(state["ls"], xn[None])
    state["ss"] = repl(state["ss"], jnp.square(xn)[None])
    state["lt"] = repl(state["lt"], tn)
    state["st"] = repl(state["st"], jnp.square(tn))
    state["t"] = state["t"] + B
    return state


def macro_cluster(state, cc: CluStreamConfig, key=None):
    """Micro-batch phase: weighted k-means over micro-cluster centroids.

    Under a mesh the CF state is sharded over the cluster axis; the k-means
    contractions over that axis (assignment mass, weighted centroid sums)
    would otherwise become partial-sum + psum chains whose float
    accumulation order differs from the single-device scan.  The [K] inputs
    are tiny, so we gather them to replicated first -- an exact collective
    -- and the k-means computes bit-identically to the unsharded path on
    every shard."""
    impl = _impl(cc)
    cent = _centroids(state)
    w = state["n"]
    mesh = active_mesh()
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(mesh, PartitionSpec())
        cent = jax.lax.with_sharding_constraint(cent, rep)
        w = jax.lax.with_sharding_constraint(w, rep)
    k = cc.n_macro
    init = cent[jnp.argsort(-w)[:k]]

    def step(c, _):
        d2 = pairwise_d2(cent, c, impl)                      # [K, k]
        a = jnp.argmin(d2, -1)
        oh = jax.nn.one_hot(a, k, dtype=f32) * w[:, None]
        tot = oh.sum(0)
        newc = (oh.T @ cent) / jnp.maximum(tot[:, None], 1e-9)
        newc = jnp.where(tot[:, None] > 0, newc, c)
        return newc, None

    centers, _ = jax.lax.scan(step, init, None, length=cc.kmeans_iters)
    return centers


def merge(states):
    """Merge shard-local micro-cluster states (distributed reduction).

    Every CF field is additive across disjoint stream shards -- including
    the scalar clock `t`: each shard advanced its local clock by the
    instances it absorbed, so the merged clock (and everything derived from
    state["t"], like the timestamps handed to future updates) is the total
    across shards, not shard 0's private count.  The `macro` centroids a
    CluStream learner state carries are NOT additive; they are taken from
    the first shard and callers should re-run macro_cluster on the merged
    CF state (the paper's macro phase after the shard reduction).
    """
    non_additive = ("macro", "macro_t")
    cf = [{k: v for k, v in s.items() if k not in non_additive}
          for s in states]
    out = jax.tree.map(lambda *xs: sum(xs), *cf)
    for k in non_additive:
        if k in states[0]:
            out[k] = states[0][k]
    return out


def assign(centers, x):
    return jnp.argmin(pairwise_d2(x, centers), -1)


def ssq(centers, x):
    return jnp.min(pairwise_d2(x, centers), -1).sum()


class CluStream:
    """Functional CluStream learner: state pytree + pure step, scan-able.

    The online CF phase runs every micro-batch; the macro k-means is
    lax.cond-gated on the period boundary (the paper's periodic trigger),
    so the whole stream compiles into one program on the scanned engines.
    State carries the latest macro centroids (plus ``macro_t``, the clock
    at their computation); metrics report the batch's sum of squared
    distances to them.

    With ``macro_impl="boundary"`` the k-means moves to the ``boundary``
    hook instead: the scanned step contains NO macro branch at all (at
    large ``n_micro`` the k-means cond bloats the step HLO), and the
    chunked driver fires the hook between chunks -- the macro recomputes
    on the first chunk boundary after each period crossing, from exactly
    the CF state a step-mode trigger at that instant would have used.
    """

    def __init__(self, cc: CluStreamConfig):
        self.cc = cc
        if _macro_impl(cc) == "boundary":
            # only boundary mode exposes the hook: step mode has no
            # boundary-phase work, and advertising a no-op would make the
            # chunked driver pay a jitted dispatch (plus, under a mesh, a
            # re-constraint pass) on every chunk for nothing
            self.boundary = self._boundary

    def init(self, key=None):
        key = jax.random.PRNGKey(0) if key is None else key
        state = init_clustream(self.cc, key)
        state["macro"] = _centroids(state)[: self.cc.n_macro]
        state["macro_t"] = jnp.zeros((), f32)
        return state

    def state_sharding(self):
        """ShardMapEngine hint: the CF tensors partition over their
        micro-cluster axis ('model' -- key grouping by cluster id, the
        vertical analogue of the paper's distributed CluStream); the macro
        centroids and the scalar clock stay replicated."""
        from repro.distributed.sharding import leading_axis_spec
        st = jax.eval_shape(self.init)
        hint = {k: None for k in st}
        for k in ("n", "ls", "ss", "lt", "st"):
            hint[k] = leading_axis_spec("model", st[k])
        return hint

    def step(self, state, x):
        cc = self.cc
        t0 = state["t"]
        state = dict(state)
        macro_prev = state.pop("macro")
        macro_t_prev = state.pop("macro_t")
        state = update(state, x, cc)
        if _macro_impl(cc) == "step":
            crossed = (t0 // cc.period) != (state["t"] // cc.period)
            state["macro"], state["macro_t"] = jax.lax.cond(
                crossed,
                lambda s: (macro_cluster(s, cc), s["t"]),
                lambda s: (macro_prev, macro_t_prev),
                state)
        else:
            # boundary mode: the k-means branch is absent from the step
            # HLO entirely; the chunked driver's boundary hook recomputes
            # the macro centroids between chunks
            state["macro"], state["macro_t"] = macro_prev, macro_t_prev
        metrics = {"seen": jnp.asarray(x.shape[0], f32),
                   "ssq": ssq(state["macro"], x),
                   "n_active": jnp.sum((state["n"] >= 1.0).astype(f32))}
        return state, metrics

    def _boundary(self, state):
        """Chunk-boundary phase (chunked driver hook, exposed as
        ``self.boundary`` in boundary mode only): recompute the macro
        centroids iff a period boundary was crossed since the last
        macro."""
        cc = self.cc
        state = dict(state)
        crossed = (state["t"] // cc.period) != (state["macro_t"] // cc.period)
        state["macro"], state["macro_t"] = jax.lax.cond(
            crossed,
            lambda s: (macro_cluster(s, cc), s["t"]),
            lambda s: (s["macro"], s["macro_t"]),
            state)
        return state

    def run(self, state, x_stream):
        if _macro_impl(self.cc) == "boundary":
            raise ValueError(
                "macro_impl='boundary' never fires inside a plain scan "
                "(the macro centroids would stay frozen at init): run "
                "through an engine's chunked driver, or use "
                "macro_impl='step'")
        def body(st, xb):
            st, m = self.step(st, xb)
            return st, m
        return jax.lax.scan(body, state, x_stream)
