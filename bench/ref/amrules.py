"""Plain reference of Adaptive Model Rules for regression, ordered rules.

Written from the algorithm (AMRules, Almeida, Ferreira & Gama; its
vertical distribution VAMR, Vu, Bifet & De Francisci Morales, IEEE
BigData 2014), independent of the program: it imports nothing of it.
One prequential step on a micro-batch (x [B, m] int32 bins, y [B] f32):

1. test: the first active rule whose predicates all hold covers an
   instance (predicate: "bin <= t" or "bin > t"); it predicts its head,
   the mean target of what it covered; an uncovered instance gets the
   default rule's mean.  The step sums the absolute errors;
2. train: each covering rule's head count, target sum and grace counter
   grow by what it covered, and its statistics -- (count, sum, sum of
   squares) of the target per (attribute, bin) -- by those instances;
   uncovered instances train the default rule the same way;
3. Page-Hinkley on each rule's mean absolute error of the step, against
   an exponential moving average (decay 0.99) of that error: a rule
   whose cumulative deviation minus its minimum exceeds lambda is removed
   (its head, statistics and detector cleared);
4. expansions decided one step earlier (VAMR's feedback delay of 1) are
   applied: the predicate goes into the rule's next free slot and the
   rule's statistics restart;
5. every active rule whose grace counter reached ``n_min`` is checked:
   standard-deviation reduction of every threshold of every attribute,
   the best two attributes by their best threshold, and an expansion when
   the second's reduction over the best's, plus the Hoeffding bound
   sqrt(ln(1/delta) / 2n), stays below 1, or when that bound is below
   tau.  The predicate keeps the side of the threshold with more mass.
   A rule with a free predicate slot queues the expansion; every checked
   rule's grace counter restarts;
6. the default rule, once its grace counter reaches ``n_min``, is checked
   the same way; an expansion creates a rule in the first free slot with
   that one predicate and a head seeded with the default mean, and the
   default rule restarts.

Statistics and heads are kept in ``dtype`` (float32 here; the control of
the correctness check runs the same code with ``dtype=bfloat16``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

NEG = -1e30
CNT, SUM, SQ = 0, 1, 2


def init(c: dict, dtype=jnp.float32) -> dict:
    R, F, m, nb = c["max_rules"], c["max_feats"], c["n_attrs"], c["n_bins"]
    zr = jnp.zeros((R,), dtype)
    zi = jnp.zeros((R, F), jnp.int32)
    return {
        "active": jnp.zeros((R,), bool),
        "pred_attr": zi, "pred_op": zi, "pred_bin": zi,
        "pred_valid": jnp.zeros((R, F), bool),
        "head_n": zr, "head_sum": zr, "since": zr,
        "stats": jnp.zeros((R, m, nb, 3), dtype),
        "d_stats": jnp.zeros((m, nb, 3), dtype),
        "d_n": jnp.zeros((), dtype), "d_sum": jnp.zeros((), dtype),
        "d_since": jnp.zeros((), dtype),
        "ph_m": zr, "ph_min": zr, "ph_err": zr,
        "n_created": jnp.zeros((), jnp.int32),
        "n_removed": jnp.zeros((), jnp.int32),
        "n_feats": jnp.zeros((), jnp.int32),
        "pend_rule_valid": jnp.zeros((R,), bool),
        "pend_attr": jnp.zeros((R,), jnp.int32),
        "pend_op": jnp.zeros((R,), jnp.int32),
        "pend_bin": jnp.zeros((R,), jnp.int32),
        "pend_timer": jnp.zeros((R,), jnp.int32),
    }


def pairwise_sum(v):
    """Sum of a vector in a fixed order: halves added elementwise (a zero
    appended to an odd length) until one value is left."""
    while v.shape[-1] > 1:
        if v.shape[-1] % 2:
            v = jnp.concatenate([v, jnp.zeros_like(v[..., :1])], -1)
        h = v.shape[-1] // 2
        v = v[..., :h] + v[..., h:]
    return v[..., 0]


def first_cover(s, x):
    """Index of the first active rule covering each instance; R if none."""
    R = s["active"].shape[0]
    v = x[:, s["pred_attr"]]                            # [B, R, F]
    holds = jnp.where(s["pred_op"] == 0, v <= s["pred_bin"],
                      v > s["pred_bin"])
    cov = jnp.all(holds | ~s["pred_valid"], -1) & s["active"]
    return jnp.min(jnp.where(cov, jnp.arange(R), R), -1)


@jax.jit
def predict(s, x):
    R = s["active"].shape[0]
    first = first_cover(s, x)
    head = s["head_sum"] / jnp.maximum(s["head_n"], 1.0)
    dmean = s["d_sum"] / jnp.maximum(s["d_n"], 1.0)
    return jnp.where(first < R, head[jnp.minimum(first, R - 1)], dmean)


def _sd(n, a, b):
    n = jnp.maximum(n, 1e-9)
    return jnp.sqrt(jnp.maximum(b / n - jnp.square(a / n), 0.0))


def decide(st, c: dict):
    """st [..., m, bins, 3] -> (expand?, attribute, bin, op)."""
    st = st.astype(jnp.float32)
    cnt = jnp.cumsum(st[..., CNT], -1)
    sm = jnp.cumsum(st[..., SUM], -1)
    sq = jnp.cumsum(st[..., SQ], -1)
    ct, smt, sqt = cnt[..., -1:], sm[..., -1:], sq[..., -1:]
    n = jnp.maximum(ct, 1e-9)
    sdr = (_sd(ct, smt, sqt) - (cnt / n) * _sd(cnt, sm, sq)
           - ((ct - cnt) / n) * _sd(ct - cnt, smt - sm, sqt - sq))
    sdr = jnp.where((cnt > 0) & (ct - cnt > 0), sdr, NEG)
    per_attr = sdr.max(-1)
    attr = per_attr.argmax(-1)
    s1 = per_attr.max(-1)
    m = per_attr.shape[-1]
    s2 = jnp.where(jnp.arange(m) == attr[..., None], NEG, per_attr).max(-1)
    tbin = jnp.take_along_axis(sdr.argmax(-1), attr[..., None], -1)[..., 0]
    n_seen = st[..., CNT].sum(-1).max(-1)
    eps = jnp.sqrt(math.log(1.0 / c["delta"]) / 2.0
                   / jnp.maximum(n_seen, 1.0))
    ratio = jnp.where(s1 > 0, jnp.maximum(s2, 0.0) / jnp.maximum(s1, 1e-9),
                      1.0)
    ok = (s1 > 0) & ((ratio + eps < 1.0) | (eps < c["tau"]))
    row = jnp.take_along_axis(cnt, attr[..., None, None], -2)[..., 0, :]
    sel = jnp.take_along_axis(row, tbin[..., None], -1)[..., 0]
    op = jnp.where(sel >= row[..., -1] - sel, 0, 1)
    return ok, attr.astype(jnp.int32), tbin.astype(jnp.int32), \
        op.astype(jnp.int32)


def _gated(fn, st, gate, c):
    """``fn(st)`` when ``gate`` holds, else the "no" answer; a check that
    is not due is never used, so skipping it changes nothing."""
    lead = st.shape[:-3]
    no = (jnp.zeros(lead, bool),) + (jnp.zeros(lead, jnp.int32),) * 3
    return jax.lax.cond(gate, lambda t: fn(t, c), lambda t: no, st)


def _expand(s, mask, attr, tbin, op):
    F = s["pred_valid"].shape[1]
    slot = jnp.minimum(s["pred_valid"].sum(-1), F - 1)
    put = jax.nn.one_hot(slot, F, dtype=bool) & mask[:, None]
    s["pred_attr"] = jnp.where(put, attr[:, None], s["pred_attr"])
    s["pred_bin"] = jnp.where(put, tbin[:, None], s["pred_bin"])
    s["pred_op"] = jnp.where(put, op[:, None], s["pred_op"])
    s["pred_valid"] = s["pred_valid"] | put
    s["stats"] = jnp.where(mask[:, None, None, None], 0, s["stats"])
    s["n_feats"] = s["n_feats"] + mask.sum().astype(jnp.int32)
    return s


def step(s, x, y, c: dict):
    """One prequential step; returns (state, sum of absolute errors)."""
    R, m, nb, _ = s["stats"].shape
    dt = s["stats"].dtype
    s = dict(s)
    first = first_cover(s, x)
    covered = first < R
    head = s["head_sum"] / jnp.maximum(s["head_n"], 1.0)
    dmean = s["d_sum"] / jnp.maximum(s["d_n"], 1.0)
    pred = jnp.where(covered, head[jnp.minimum(first, R - 1)], dmean)
    err = jnp.abs(y - pred)

    seg = jnp.where(covered, first, R)
    cnt = jax.ops.segment_sum(jnp.ones_like(y), seg, R + 1)[:R]
    s["head_n"] = s["head_n"] + cnt.astype(dt)
    s["head_sum"] = s["head_sum"] + jax.ops.segment_sum(y, seg,
                                                        R + 1)[:R].astype(dt)
    s["since"] = s["since"] + cnt.astype(dt)
    mom = jnp.stack([jnp.ones_like(y), y, y * y], -1)           # [B, 3]
    who = jax.nn.one_hot(seg, R + 1, dtype=jnp.float32)          # [B, R+1]
    where_ = jax.nn.one_hot(x, nb, dtype=jnp.float32).reshape(-1, m * nb)
    add = jnp.einsum("br,bc,bk->rkc", who, mom, where_,
                     precision=jax.lax.Precision.HIGHEST)
    add = add.reshape(R + 1, m, nb, 3).astype(dt)
    s["stats"] = s["stats"] + add[:R]
    s["d_stats"] = s["d_stats"] + add[R]
    w = (~covered).astype(y.dtype)
    s["d_n"] = s["d_n"] + w.sum().astype(dt)
    s["d_sum"] = s["d_sum"] + pairwise_sum(w * y).astype(dt)
    s["d_since"] = s["d_since"] + w.sum().astype(dt)

    # Page-Hinkley per rule, against an EMA of its error
    rule_err = (jax.ops.segment_sum(err, seg, R + 1)[:R]
                / jnp.maximum(cnt, 1.0)).astype(dt)
    has = cnt > 0
    mt = jnp.where(has, s["ph_m"] + rule_err - s["ph_err"] - c["ph_alpha"],
                   s["ph_m"])
    s["ph_err"] = jnp.where(has, 0.99 * s["ph_err"] + (1.0 - 0.99) * rule_err,
                            s["ph_err"])
    s["ph_min"] = jnp.minimum(s["ph_min"], mt)
    s["ph_m"] = mt
    drift = s["active"] & (mt - s["ph_min"] > c["ph_lambda"])
    s["active"] = s["active"] & ~drift
    s["pred_valid"] = jnp.where(drift[:, None], False, s["pred_valid"])
    for k in ("head_n", "head_sum", "since", "ph_m", "ph_min", "ph_err"):
        s[k] = jnp.where(drift, 0, s[k])
    s["stats"] = jnp.where(drift[:, None, None, None], 0, s["stats"])
    s["n_removed"] = s["n_removed"] + drift.sum().astype(jnp.int32)

    # expansions queued one step ago
    timer = jnp.where(s["pend_rule_valid"], s["pend_timer"] - 1,
                      s["pend_timer"])
    mature = s["pend_rule_valid"] & (timer <= 0)
    s["pend_timer"] = timer
    s["pend_rule_valid"] = s["pend_rule_valid"] & ~mature
    s = _expand(s, mature, s["pend_attr"], s["pend_bin"], s["pend_op"])

    # rule expansion checks
    ready = s["active"] & (s["since"] >= c["n_min"])
    ok, attr, tbin, op = _gated(decide, s["stats"], jnp.any(ready), c)
    room = s["pred_valid"].sum(-1) < s["pred_valid"].shape[1]
    expand = ready & ok & room
    s["since"] = jnp.where(ready, 0, s["since"])
    s["pend_rule_valid"] = s["pend_rule_valid"] | expand
    s["pend_attr"] = jnp.where(expand, attr, s["pend_attr"])
    s["pend_op"] = jnp.where(expand, op, s["pend_op"])
    s["pend_bin"] = jnp.where(expand, tbin, s["pend_bin"])
    s["pend_timer"] = jnp.where(expand, c["delay"], s["pend_timer"])

    # default rule: a new rule in the first free slot
    ready = s["d_since"] >= c["n_min"]
    ok, attr, tbin, op = _gated(decide, s["d_stats"], ready, c)
    free = ~s["active"]
    create = ready & ok & jnp.any(free)
    s["d_since"] = jnp.where(ready, 0, s["d_since"])
    new = jax.nn.one_hot(jnp.argmax(free), R, dtype=bool) & create
    col0 = new[:, None] & (jnp.arange(s["pred_valid"].shape[1]) == 0)
    s["active"] = s["active"] | new
    s["pred_attr"] = jnp.where(col0, attr, s["pred_attr"])
    s["pred_bin"] = jnp.where(col0, tbin, s["pred_bin"])
    s["pred_op"] = jnp.where(col0, op, s["pred_op"])
    s["pred_valid"] = jnp.where(new[:, None], col0, s["pred_valid"])
    dmean = s["d_sum"] / jnp.maximum(s["d_n"], 1.0)
    s["head_n"] = jnp.where(new, 1, s["head_n"])
    s["head_sum"] = jnp.where(new, dmean, s["head_sum"])
    for k in ("since", "ph_m", "ph_min", "ph_err"):
        s[k] = jnp.where(new, 0, s[k])
    s["stats"] = jnp.where(new[:, None, None, None], 0, s["stats"])
    for k in ("d_stats", "d_n", "d_sum"):
        s[k] = jnp.where(create, 0, s[k])
    s["n_created"] = s["n_created"] + create.astype(jnp.int32)
    return s, pairwise_sum(err)


@partial(jax.jit, static_argnames=("cfg",))
def run_chunk(s, x, y, cfg):
    """Steps over a chunk (x [L, B, m], y [L, B]); returns (state, sum of
    absolute errors per step).  ``cfg`` is a hashable tuple of the
    configuration items."""
    c = dict(cfg)
    return jax.lax.scan(lambda st, xy: step(st, *xy, c), s, (x, y))


def program_view(s) -> dict:
    """The state under the program's key names (it also counts the
    active rules)."""
    out = dict(s)
    out["n_rules"] = s["active"].sum().astype(jnp.int32)
    return out
