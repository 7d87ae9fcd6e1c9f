"""Distributed Adaptive Model Rules (paper section 7): MAMR / VAMR / HAMR.

Rule model (tensorized, capacity-bounded):
  * predicates: (attr, op, threshold-bin) triples, up to F per rule;
  * heads: adaptive target mean over covered instances;
  * per-rule expansion statistics: target (count, sum, sumsq) moments per
    (attr, bin), one tensor stats[rule, attr, bin, moment] -- the VAMR
    learner state, key-grouped by RULE ID ('rules' axis -> 'model' mesh
    axis);
  * default rule: covers the rest; expanding it creates a new rule
    (centralized default-rule learner in HAMR).

Expansion: standard-deviation reduction (SDR) with the Hoeffding bound on
the ratio of the two best SDRs (ratio + eps < 1, or eps < tau tie-break).
Change detection: Page-Hinkley on each rule's absolute error evicts drifted
rules.  Ordered-rules mode (the paper's focus): first covering rule
predicts and trains.

Performance (the fused/kernelized path, mirroring the VHT treatment):
  * statistics updates scatter (w, w*y, w*y^2) moments through
    repro.kernels.rule_stats -- Pallas MXU matmuls on TPU, an element
    scatter elsewhere; the dense [B, m, bins] bin one-hot product of the
    legacy path never materializes (RulesConfig.stats_impl="onehot" keeps
    the oracle);
  * the SDR cumsum + top-k expansion checks over [R, m, bins] are
    lax.cond-gated on the n_min grace period (RulesConfig.gate_expansions)
    and skip entirely on the (common) steps where no rule is due -- exact,
    because a non-due rule can never expand;
  * the per-rule Page-Hinkley detectors are a packed DetectorBank
    (repro.ml.detectors, ph_ema family): one batched update/reset pass
    over all R rules, sharded with the rule axis
    (RulesConfig.detector_impl="inline" keeps the legacy formulation).

Parallelism:
  MAMR -- sequential reference (the MOA baseline).
  VAMR -- aggregator holds thin bodies/heads; statistics sharded by rule id;
          expansion feedback delayed `delay` steps (DSPE queue staleness).
  HAMR -- `replicas` aggregator copies each process 1/replicas of the batch
          (horizontal parallelism) + one centralized default-rule learner;
          new rules are broadcast with the same delay.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.rule_stats.ops import (default_impl, rule_moments,
                                          rule_stats_update)

f32 = jnp.float32
i32 = jnp.int32
BIG = 1e30

# moment-axis layout of the statistics tensor [R, m, bins, 3]
CNT, SUM, SQ = 0, 1, 2


def batch_sum(x):
    """Sum of a float batch vector in a fixed pairwise order: halves are
    added elementwise until one value is left.  A reduce's association
    is the backend's choice and changes with the array's shape -- a
    fleet reduces its batches as one [F, B] array on one device but as
    [1, B] slices when the tenant axis is sharded -- which moved the
    float error sums by an ulp; elementwise adds are never
    reassociated, so every layout and sharding gives the same bits."""
    while x.shape[-1] > 1:
        if x.shape[-1] % 2:
            x = jnp.concatenate([x, jnp.zeros_like(x[..., :1])], -1)
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


@dataclasses.dataclass(frozen=True)
class RulesConfig:
    n_attrs: int
    n_bins: int = 8
    max_rules: int = 64
    max_feats: int = 8
    n_min: int = 200          # expansion grace period
    delta: float = 1e-7
    tau: float = 0.05
    ph_lambda: float = 35.0   # Page-Hinkley threshold
    ph_alpha: float = 0.005
    delay: int = 0            # expansion feedback staleness (VAMR/HAMR)
    ordered: bool = True
    stats_impl: str = "auto"  # auto | pallas | segment | onehot (legacy)
    attr_tile: int = 0        # Pallas stats kernel attribute-tile override
    gate_expansions: bool = True  # lax.cond-gate SDR checks on grace period
    detector_impl: str = "bank"   # bank (packed DetectorBank) | inline legacy

    @property
    def eps_n(self):
        return math.log(1.0 / self.delta) / 2.0


def init_rules(rc: RulesConfig):
    R, F, m, nb = rc.max_rules, rc.max_feats, rc.n_attrs, rc.n_bins
    return {
        "active": jnp.zeros((R,), bool),
        "pred_attr": jnp.zeros((R, F), i32),
        "pred_op": jnp.zeros((R, F), i32),       # 0: <= thr, 1: > thr
        "pred_bin": jnp.zeros((R, F), i32),
        "pred_valid": jnp.zeros((R, F), bool),
        "head_n": jnp.zeros((R,), f32),
        "head_sum": jnp.zeros((R,), f32),
        "since": jnp.zeros((R,), f32),
        # (cnt, sum, sumsq) target moments per (rule, attr, bin)
        "stats": jnp.zeros((R, m, nb, 3), f32),
        # default rule
        "d_stats": jnp.zeros((m, nb, 3), f32),
        "d_n": jnp.zeros((), f32),
        "d_sum": jnp.zeros((), f32),
        "d_since": jnp.zeros((), f32),
        # Page-Hinkley per rule
        "ph_m": jnp.zeros((R,), f32),
        "ph_min": jnp.zeros((R,), f32),
        "ph_err": jnp.zeros((R,), f32),
        "n_rules": jnp.zeros((), i32),
        "n_created": jnp.zeros((), i32),
        "n_removed": jnp.zeros((), i32),
        "n_feats": jnp.zeros((), i32),
        # delayed expansion feedback buffers
        "pend_rule_valid": jnp.zeros((R,), bool),
        "pend_attr": jnp.zeros((R,), i32),
        "pend_op": jnp.zeros((R,), i32),
        "pend_bin": jnp.zeros((R,), i32),
        "pend_timer": jnp.zeros((R,), i32),
    }


@jax.named_scope("route")
def coverage(state, xbin, rc: RulesConfig):
    """[B, R] bool: does rule r cover instance b?

    Formulated as a violated-predicate count so the batch side is one
    [B, m*bins] x [m*bins, R] matmul against the bin one-hot instead of a
    [B, R, F] gather (the gather serializes badly on CPU and wastes the
    MXU on TPU).  viol[r, a, v] counts rule r's predicates on attribute a
    that bin value v violates; the counts are small integers in f32, so
    `covered == (count == 0)` is exact and the result is bit-identical to
    the gather formulation.
    """
    pa, po, pb, pv = (state["pred_attr"], state["pred_op"],
                      state["pred_bin"], state["pred_valid"])
    B = xbin.shape[0]
    R = rc.max_rules
    m, nb = rc.n_attrs, rc.n_bins
    bins = jnp.arange(nb)
    # maskf[r, f, v]: predicate f of rule r is violated by bin value v
    maskf = jnp.where(po[..., None] == 0, bins[None, None] > pb[..., None],
                      bins[None, None] <= pb[..., None]) & pv[..., None]
    attr1h = jax.nn.one_hot(pa, m, dtype=f32)                  # [R, F, m]
    viol = jnp.einsum("rfa,rfv->rav", attr1h, maskf.astype(f32))
    binoh = jax.nn.one_hot(xbin, nb, dtype=f32)                # [B, m, nb]
    unsat = binoh.reshape(B, m * nb) @ viol.reshape(R, m * nb).T
    return (unsat < 0.5) & state["active"][None]


def first_cover(cov, rc: RulesConfig):
    """Ordered mode: index of first covering rule, R if none."""
    R = rc.max_rules
    idx = jnp.where(cov, jnp.arange(R)[None], R)
    return jnp.min(idx, axis=-1)


def _sdr(cnt, sm, sq):
    """Standard-deviation reduction for all (attr, bin) thresholds.
    cnt/sm/sq: [..., m, bins] per-bin target stats."""
    c = jnp.cumsum(cnt, -1)
    s = jnp.cumsum(sm, -1)
    q = jnp.cumsum(sq, -1)
    ct, st, qt = c[..., -1:], s[..., -1:], q[..., -1:]

    def sd(n, sm_, sq_):
        n = jnp.maximum(n, 1e-9)
        var = jnp.maximum(sq_ / n - jnp.square(sm_ / n), 0.0)
        return jnp.sqrt(var)

    tot_sd = sd(ct, st, qt)
    left_sd = sd(c, s, q)
    right_sd = sd(ct - c, st - s, qt - q)
    n = jnp.maximum(ct, 1e-9)
    sdr = tot_sd - (c / n) * left_sd - ((ct - c) / n) * right_sd
    valid = (c > 0) & ((ct - c) > 0)
    return jnp.where(valid, sdr, -BIG)


def _expansion_decision(cnt, sm, sq, rc: RulesConfig):
    """Return (expand?, attr, bin, op) from SDR + Hoeffding ratio test.

    Top-2 over ATTRIBUTES (adjacent thresholds of one attribute tie);
    the Hoeffding n is the rule's accumulated statistics count, derived
    from the cnt tensor itself.
    """
    sdr = _sdr(cnt, sm, sq)                       # [..., m, bins]
    per_attr = sdr.max(-1)                        # [..., m]
    bin_per_attr = sdr.argmax(-1)
    top2, idx2 = jax.lax.top_k(per_attr, 2)
    s1, s2 = top2[..., 0], top2[..., 1]
    attr = idx2[..., 0]
    tbin = jnp.take_along_axis(bin_per_attr, attr[..., None], -1)[..., 0]
    n_seen = cnt.sum(-1).max(-1)                  # instances in the stats
    eps = jnp.sqrt(rc.eps_n / jnp.maximum(n_seen, 1.0))
    ratio = jnp.where(s1 > 0, jnp.maximum(s2, 0.0) / jnp.maximum(s1, 1e-9), 1.0)
    ok = (s1 > 0) & ((ratio + eps < 1.0) | (eps < rc.tau))
    # keep the branch with more mass (documented simplification)
    c = jnp.cumsum(cnt, -1)
    sel_c = jnp.take_along_axis(
        c, attr[..., None, None].repeat(c.shape[-1], -1), -2)[..., 0, :]
    sel = jnp.take_along_axis(sel_c, tbin[..., None], -1)[..., 0]
    tot = sel_c[..., -1]
    op = jnp.where(sel >= tot - sel, 0, 1).astype(i32)   # 0: keep <=, 1: keep >
    return ok, attr.astype(i32), tbin.astype(i32), op


class AMRules:
    """Sequential reference (MAMR) and the shared mechanics."""

    def __init__(self, rc: RulesConfig):
        self.rc = rc
        # per-rule Page-Hinkley as a packed DetectorBank (ph_ema family:
        # deviation against an EMA error baseline); the bank state lives in
        # the flat ph_m/ph_min/ph_err keys so the rule-axis sharding hints
        # and the scanned-state layout are unchanged
        from repro.ml.detectors import DetectorBank, PhEmaConfig
        self._ph = DetectorBank(
            "ph_ema", rc.max_rules,
            PhEmaConfig(alpha=rc.ph_alpha, lam=rc.ph_lambda))

    def init(self, key=None):
        return init_rules(self.rc)

    # every per-rule array (leading axis = max_rules) -- the key-grouped
    # state a DSPE would route by rule id
    RULE_AXIS_KEYS = ("active", "pred_attr", "pred_op", "pred_bin",
                      "pred_valid", "head_n", "head_sum", "since", "stats",
                      "ph_m", "ph_min", "ph_err", "pend_rule_valid",
                      "pend_attr", "pend_op", "pend_bin", "pend_timer")

    def state_sharding(self):
        """ShardMapEngine hint: the rule axis is the paper's
        vertical-parallelism axis (key grouping by rule id), so every
        per-rule tensor -- statistics, predicates, heads, Page-Hinkley --
        partitions over 'model'.  Coverage then computes only the local
        rules' columns per shard, first-cover is a cross-shard min, and the
        head/stats segment sums scatter into the local rows; the default
        rule and the scalar counters stay replicated.  eval_shape
        enumerates the state without allocating it."""
        from repro.distributed.sharding import leading_axis_spec
        st = jax.eval_shape(lambda: init_rules(self.rc))
        return {k: leading_axis_spec("model", v)
                if k in self.RULE_AXIS_KEYS else None
                for k, v in st.items()}

    # ------------------------------------------------------------- step

    def step(self, state, xbin, y):
        """Prequential step.  xbin: [B,m] int bins; y: [B] float targets."""
        rc = self.rc
        R = rc.max_rules
        cov = coverage(state, xbin, rc)
        first = first_cover(cov, rc)                       # [B]
        covered = first < R
        head_mean = state["head_sum"] / jnp.maximum(state["head_n"], 1.0)
        d_mean = state["d_sum"] / jnp.maximum(state["d_n"], 1.0)
        pred = jnp.where(covered, head_mean[jnp.minimum(first, R - 1)], d_mean)
        err = y - pred
        abs_err = jnp.abs(err)

        state = dict(state)
        # ---- update covered rules' head + stats (scatter by rule id) ----
        # heads, grace counters, and the PH error reduce through one set of
        # rule-id segment sums (no [B, R] one-hot matvecs)
        ridx = jnp.where(covered, first, R)
        seg_sum = partial(jax.ops.segment_sum, segment_ids=ridx,
                          num_segments=R + 1)
        cnt = seg_sum(jnp.ones_like(y))[:R]
        state["head_n"] = state["head_n"] + cnt
        state["head_sum"] = state["head_sum"] + seg_sum(y)[:R]
        state["since"] = state["since"] + cnt
        mom = rule_moments(y)                                # [B, 3]
        state = self._scatter_stats(state, covered, first, xbin, mom)

        # ---- default rule head with uncovered instances ------------------
        w = (~covered).astype(f32)
        state["d_n"] = state["d_n"] + w.sum()
        state["d_sum"] = state["d_sum"] + batch_sum(w * y)
        state["d_since"] = state["d_since"] + w.sum()

        # ---- Page-Hinkley drift eviction (packed detector bank) ----------
        rule_err = seg_sum(abs_err)[:R] / jnp.maximum(cnt, 1.0)
        has = cnt > 0
        if rc.detector_impl == "bank":
            # one batched ph_ema pass over all R rules; rules without a
            # covered instance this step hold still (has mask)
            ph, raw = self._ph.update(self._ph_view(state), rule_err,
                                      has=has)
            state["ph_m"], state["ph_min"], state["ph_err"] = \
                ph["m"], ph["min"], ph["err"]
            drift = state["active"] & raw
        elif rc.detector_impl == "inline":
            # legacy inline formulation -- the bank's parity oracle
            mt = jnp.where(has, state["ph_m"] + rule_err - state["ph_err"]
                           - rc.ph_alpha, state["ph_m"])
            err_avg = jnp.where(
                has, 0.99 * state["ph_err"] + 0.01 * rule_err,
                state["ph_err"])
            ph_min = jnp.minimum(state["ph_min"], mt)
            drift = state["active"] & (mt - ph_min > rc.ph_lambda)
            state["ph_m"], state["ph_min"], state["ph_err"] = \
                mt, ph_min, err_avg
        else:
            raise ValueError(f"unknown detector impl {rc.detector_impl!r}")
        state = self._evict(state, drift)

        # ---- expansions (lax.cond-gated on the grace period) -------------
        state = self._apply_pending(state)
        state = self._try_expand(state)
        state = self._try_default_expand(state)
        state["n_rules"] = jnp.sum(state["active"].astype(i32))

        metrics = {
            "abs_err": batch_sum(abs_err),
            "sq_err": batch_sum(jnp.square(err)),
            "seen": jnp.asarray(y.shape[0], f32),
            "n_rules": jnp.sum(state["active"].astype(f32)),
        }
        return state, metrics

    # ------------------------------------------------------------ pieces

    @jax.named_scope("stats_update")
    def _scatter_stats(self, state, covered, first, xbin, mom):
        """Scatter (w, w*y, w*y^2) into the rule AND default-rule moment
        tensors.  The fused path runs ONE kernelized scatter over an
        [R+1]-row extension whose last row is the default rule (every
        instance lands in a real row); stats_impl="onehot" keeps the
        legacy pre-PR formulation of two dense one-hot updates."""
        rc = self.rc
        R = rc.max_rules
        state = dict(state)
        impl = default_impl() if rc.stats_impl == "auto" else rc.stats_impl
        if impl == "onehot":
            ridx = jnp.where(covered, first, R)              # R = discard
            state["stats"] = rule_stats_update(
                state["stats"], ridx, xbin, mom,
                impl="onehot", attr_tile=rc.attr_tile)
            d_seg = jnp.where(covered, 1, 0).astype(i32)
            state["d_stats"] = rule_stats_update(
                state["d_stats"][None], d_seg, xbin, mom,
                impl="onehot", attr_tile=rc.attr_tile)[0]
            return state
        ext = jnp.concatenate([state["stats"], state["d_stats"][None]], 0)
        seg = jnp.where(covered, first, R)                   # R = default row
        ext = rule_stats_update(ext, seg, xbin, mom,
                                impl=impl, attr_tile=rc.attr_tile)
        state["stats"], state["d_stats"] = ext[:R], ext[R]
        return state

    def _ph_view(self, state):
        """The per-rule Page-Hinkley state as the DetectorBank's packed
        layout -- a zero-copy re-labelling of the flat ph_* keys."""
        return {"m": state["ph_m"], "min": state["ph_min"],
                "err": state["ph_err"]}

    def _evict(self, state, drift):
        state = dict(state)
        state["active"] = state["active"] & ~drift
        state["pred_valid"] = jnp.where(drift[:, None], False,
                                        state["pred_valid"])
        zero = lambda a: jnp.where(
            drift.reshape((-1,) + (1,) * (a.ndim - 1)), 0, a)
        state["head_n"] = zero(state["head_n"])
        state["head_sum"] = zero(state["head_sum"])
        state["since"] = zero(state["since"])
        state["stats"] = zero(state["stats"])
        # drifted rules' detectors restart from scratch: the bank reset is
        # bit-identical to zeroing exactly the masked rows
        ph = self._ph.reset(self._ph_view(state), drift)
        state["ph_m"], state["ph_min"], state["ph_err"] = \
            ph["m"], ph["min"], ph["err"]
        state["n_removed"] = state["n_removed"] + drift.sum().astype(i32)
        return state

    @jax.named_scope("split_check")
    def _gated_decision(self, stats, gate):
        """The SDR cumsum + top-k over [..., m, bins] runs only when `gate`
        holds -- exact, because the caller consumes the decision exclusively
        under a mask that is all-False whenever the gate is closed.  Only
        the statistics tensor crosses the lax.cond (the whole-state variant
        measurably bloats the scanned step with buffer copies)."""
        rc = self.rc
        lead = stats.shape[:-3]

        def closed(st):
            return (jnp.zeros(lead, bool), jnp.zeros(lead, i32),
                    jnp.zeros(lead, i32), jnp.zeros(lead, i32))

        def open_(st):
            return _expansion_decision(
                st[..., CNT], st[..., SUM], st[..., SQ], rc)

        if not rc.gate_expansions:
            return open_(stats)
        return jax.lax.cond(gate, open_, closed, stats)

    def _try_expand(self, state):
        """Rules with >= n_min fresh updates attempt an SDR expansion."""
        rc = self.rc
        ready = state["active"] & (state["since"] >= rc.n_min)
        ok, attr, tbin, op = self._gated_decision(
            state["stats"], jnp.any(ready))
        room = state["pred_valid"].sum(-1) < rc.max_feats
        expand = ready & ok & room
        state = dict(state)
        state["since"] = jnp.where(ready, 0.0, state["since"])
        if rc.delay == 0:
            return self._do_expand(state, expand, attr, tbin, op)
        state["pend_rule_valid"] = state["pend_rule_valid"] | expand
        state["pend_attr"] = jnp.where(expand, attr, state["pend_attr"])
        state["pend_op"] = jnp.where(expand, op, state["pend_op"])
        state["pend_bin"] = jnp.where(expand, tbin, state["pend_bin"])
        state["pend_timer"] = jnp.where(expand, rc.delay, state["pend_timer"])
        return state

    def _apply_pending(self, state):
        rc = self.rc
        if rc.delay == 0:
            return state
        state = dict(state)
        timer = jnp.where(state["pend_rule_valid"], state["pend_timer"] - 1,
                          state["pend_timer"])
        mature = state["pend_rule_valid"] & (timer <= 0)
        state["pend_timer"] = timer
        state["pend_rule_valid"] = state["pend_rule_valid"] & ~mature
        return self._do_expand(state, mature, state["pend_attr"],
                               state["pend_bin"], state["pend_op"],
                               bins_are_pending=True)

    @jax.named_scope("split_apply")
    def _do_expand(self, state, expand, attr, tbin, op, bins_are_pending=False):
        rc = self.rc
        state = dict(state)
        slot = state["pred_valid"].sum(-1)                 # next free feat
        slot = jnp.minimum(slot, rc.max_feats - 1)
        F = rc.max_feats
        sl_oh = jax.nn.one_hot(slot, F, dtype=bool) & expand[:, None]
        state["pred_attr"] = jnp.where(sl_oh, attr[:, None], state["pred_attr"])
        state["pred_bin"] = jnp.where(sl_oh, tbin[:, None], state["pred_bin"])
        state["pred_op"] = jnp.where(sl_oh, op[:, None], state["pred_op"])
        state["pred_valid"] = state["pred_valid"] | sl_oh
        # expansion resets the rule's statistics (it now covers a subset)
        state["stats"] = jnp.where(expand[:, None, None, None], 0.0,
                                   state["stats"])
        state["n_feats"] = state["n_feats"] + expand.sum().astype(i32)
        return state

    def _try_default_expand(self, state):
        """Default rule expansion creates a NEW rule (Alg: add to rule set).
        The SDR decision is gated on the default rule's own grace period."""
        rc = self.rc
        ready = state["d_since"] >= rc.n_min
        ok, attr, tbin, op = self._gated_decision(
            state["d_stats"][None], ready)
        ok, attr, tbin, op = ok[0], attr[0], tbin[0], op[0]
        free = ~state["active"]
        has_free = jnp.any(free)
        slot = jnp.argmax(free)                            # first free slot
        create = ready & ok & has_free
        state = dict(state)
        state["d_since"] = jnp.where(ready, 0.0, state["d_since"])
        soh = jax.nn.one_hot(slot, rc.max_rules, dtype=bool) & create
        state["active"] = state["active"] | soh
        f0 = jax.nn.one_hot(0, rc.max_feats, dtype=bool)
        state["pred_attr"] = jnp.where(soh[:, None] & f0[None], attr,
                                       state["pred_attr"])
        state["pred_bin"] = jnp.where(soh[:, None] & f0[None], tbin,
                                      state["pred_bin"])
        state["pred_op"] = jnp.where(soh[:, None] & f0[None], op,
                                     state["pred_op"])
        state["pred_valid"] = jnp.where(soh[:, None], f0[None],
                                        state["pred_valid"])
        # head seeded from the default rule's mean; fresh stats
        d_mean = state["d_sum"] / jnp.maximum(state["d_n"], 1.0)
        state["head_n"] = jnp.where(soh, 1.0, state["head_n"])
        state["head_sum"] = jnp.where(soh, d_mean, state["head_sum"])
        reset = lambda a, v=0.0: jnp.where(
            soh.reshape((-1,) + (1,) * (a.ndim - 1)), v, a)
        state["stats"] = reset(state["stats"])
        state["since"] = reset(state["since"])
        state["ph_m"] = reset(state["ph_m"])
        state["ph_min"] = reset(state["ph_min"])
        state["ph_err"] = reset(state["ph_err"])
        # default rule restarts
        state["d_stats"] = jnp.where(create, 0.0, state["d_stats"])
        state["d_n"] = jnp.where(create, 0.0, state["d_n"])
        state["d_sum"] = jnp.where(create, 0.0, state["d_sum"])
        state["n_created"] = state["n_created"] + create.astype(i32)
        return state

    def run(self, state, x_stream, y_stream):
        def body(st, xy):
            st, m = self.step(st, *xy)
            return st, m
        return jax.lax.scan(body, state, (x_stream, y_stream))


class VAMR(AMRules):
    """Vertical AMRules: statistics sharded by rule id; expansion feedback
    delayed.  Functionally == AMRules with delay>0; under the ShardMapEngine
    the 'rules' axis shards over 'model' (see state_sharding)."""

    def __init__(self, rc: RulesConfig):
        if rc.delay == 0:
            rc = dataclasses.replace(rc, delay=1)
        super().__init__(rc)


class HAMR:
    """Hybrid AMRules (paper section 7.2 / Fig. 11): `replicas` model
    aggregators each process 1/replicas of the stream against the SAME rule
    set; learner statistics merge by rule-id key grouping; uncovered
    instances go to ONE centralized default-rule learner, whose expansions
    broadcast to all aggregators -- that centralization is what keeps the
    replicas in synch (the paper's fix for conflicting default rules).

    Tensorized: the replica axis is a leading vmap axis for the
    aggregator-side phase (coverage + prediction + per-replica error);
    statistics updates then SUM across replicas (the key-grouped shuffle a
    DSPE performs) through the same rule_stats kernels as MAMR, and the
    shared rule structure stays replica-free.
    """

    def __init__(self, rc: RulesConfig, replicas: int = 2):
        if rc.delay == 0:
            rc = dataclasses.replace(rc, delay=1)
        self.rc = rc
        self.replicas = replicas
        self._inner = AMRules(rc)

    def init(self, key=None):
        return init_rules(self.rc)

    def state_sharding(self):
        return self._inner.state_sharding()

    def step(self, state, xbin, y):
        rc = self.rc
        r = self.replicas
        B = y.shape[0]
        Bs = (B // r) * r
        xs = xbin[:Bs].reshape(r, B // r, -1)
        ys = y[:Bs].reshape(r, B // r)

        # ---- aggregator phase (per replica, shared rule set) -------------
        R = rc.max_rules
        head_mean = state["head_sum"] / jnp.maximum(state["head_n"], 1.0)
        d_mean = state["d_sum"] / jnp.maximum(state["d_n"], 1.0)

        def replica(xb, yb):
            cov = coverage(state, xb, rc)
            first = first_cover(cov, rc)
            covered = first < R
            pred = jnp.where(covered, head_mean[jnp.minimum(first, R - 1)],
                             d_mean)
            return first, covered, jnp.abs(yb - pred), jnp.square(yb - pred)

        first, covered, abse, sqe = jax.vmap(replica)(xs, ys)   # [r, B/r]

        # ---- learner phase: merge replica updates (key grouping) ---------
        flat_first = first.reshape(-1)
        flat_cov = covered.reshape(-1)
        flat_x = xs.reshape(Bs, -1)
        flat_y = ys.reshape(-1)
        merged = dict(state)
        ridx = jnp.where(flat_cov, flat_first, R)
        seg_sum = partial(jax.ops.segment_sum, segment_ids=ridx,
                          num_segments=R + 1)
        cnt = seg_sum(jnp.ones_like(flat_y))[:R]
        merged["head_n"] = state["head_n"] + cnt
        merged["head_sum"] = state["head_sum"] + seg_sum(flat_y)[:R]
        merged["since"] = state["since"] + cnt
        mom = rule_moments(flat_y)
        merged = self._inner._scatter_stats(merged, flat_cov, flat_first,
                                            flat_x, mom)

        # ---- centralized default-rule learner (head) ---------------------
        w = (~flat_cov).astype(f32)
        merged["d_n"] = state["d_n"] + w.sum()
        merged["d_sum"] = state["d_sum"] + batch_sum(w * flat_y)
        merged["d_since"] = state["d_since"] + w.sum()

        # ---- shared expansion/drift machinery (delayed broadcast) --------
        merged = self._inner._apply_pending(merged)
        merged = self._inner._try_expand(merged)
        merged = self._inner._try_default_expand(merged)
        merged["n_rules"] = jnp.sum(merged["active"].astype(i32))

        metrics = {"abs_err": batch_sum(abse.reshape(-1)),
                   "sq_err": batch_sum(sqe.reshape(-1)),
                   "seen": jnp.asarray(Bs, f32),
                   "n_rules": jnp.sum(merged["active"].astype(f32))}
        return merged, metrics

    def run(self, state, x_stream, y_stream):
        def body(st, xy):
            st, m = self.step(st, *xy)
            return st, m
        return jax.lax.scan(body, state, (x_stream, y_stream))
