"""Adaptive ensembles (paper section 5): OzaBag / OzaBoost with pluggable
change detectors (ADWIN / DDM / EDDM / Page-Hinkley).

Online bagging (Oza & Russell): each base learner trains on each instance
with weight ~ Poisson(1).  Online boosting: the Poisson rate is scaled up
for instances the previous learners got wrong.  Adaptive variants attach a
change detector per member; on drift the member is reset (ADWIN bagging).

Base learner: the tensorized Hoeffding tree (vmap'd across members) --
these are the meta-algorithms SAMOA pairs with external single-machine
classifiers; here the base is our own tree, pluggable via init/step fns.

Performance (the fused/kernelized path):

  * routing -- the whole micro-batch is sorted through ALL member trees by
    ONE batched multi-tree router call (repro.kernels.tree_route: Pallas
    one-hot matmuls on TPU, flat 1-D gathers elsewhere;
    EnsembleConfig.route_impl), and the resulting [M, B] leaf tensor
    serves BOTH the vote and the training scatter -- the per-member
    fori_loop-in-vmap it replaces serialized a batched gather per depth
    level and routed every instance twice;
  * detectors -- the per-member change detectors live in a packed
    DetectorBank (repro.ml.detectors): one struct-of-arrays state updated
    in a single tensor pass instead of a vmap of M scalar detector
    programs (EnsembleConfig.detector_impl="vmap" keeps the oracle);
  * statistics -- per-member updates dispatch through
    repro.kernels.vht_stats inside the vmap (the tree's stats_impl knob);
  * split checks -- gated across members (EnsembleConfig.gate_members):
    the M member node pools flatten to ONE [M*N] pool and the gain
    reduction runs over a gathered <= check_tile row tile of due leaves
    (child distributions from the gathered rows' cumsum), with the
    rewiring itself lax.cond-gated on a split actually landing; a
    lax.cond inside the member vmap would lower to a both-branches
    select, which is why the pre-bank path paid a full per-member
    [N, m, bins, C] reduction whenever any member came due.  The full
    vmapped pass survives as the ungated oracle and the tile-overflow
    fallback.  The fresh-tree reset constant is built once at
    construction instead of inside the (scanned) step.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (active_mesh,
                                        spmd_member_gather_suppressed)
from repro.ml import detectors, htree
from repro.ml.detectors import DetectorBank
from repro.ml.htree import TreeConfig
from repro.ml.vht import VHT, VHTConfig

f32 = jnp.float32
i32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class EnsembleConfig:
    tree: TreeConfig
    n_members: int = 10
    boost: bool = False
    detector: str = "adwin"      # adwin | ddm | eddm | ph | none
    gate_members: bool = True    # lax.cond-gate split work on any member due
    split_check: str = "pool"    # pool (flattened [M*N] gather tile; under
                                 # a mesh whose 'data' axis partitions the
                                 # member axis it runs as an explicit
                                 # shard_map: local top-K tile, all-gather
                                 # of candidates, global top-K, scatter
                                 # back by shard offset) |
                                 # member (per-member full pass behind the
                                 # any-due gate; the non-shard_map oracle
                                 # for partitioned runs)
    route_impl: str | None = None  # member router override: pallas | gather
                                   # | fori | auto; None -> tree.route_impl
    detector_impl: str = "bank"  # bank (packed tensor pass) | vmap (legacy)


class OzaEnsemble:
    def __init__(self, ec: EnsembleConfig):
        self.ec = ec
        self.tc = ec.tree
        self._vht = VHT(VHTConfig(self.tc))
        self._ac = detectors.AdwinConfig()
        # only the four documented member-detector families ("none" and
        # anything else mean no detector; ph_ema is AMRules-internal)
        self._bank = (DetectorBank(ec.detector, ec.n_members)
                      if ec.detector in ("adwin", "ddm", "eddm", "ph")
                      else None)
        # the drift-reset target is a constant of the config: build it once
        # instead of re-materializing it inside every (scanned) step
        self._fresh = htree.init_tree(self.tc)
        # inside the member vmap the gate must stay open (vmap lowers
        # lax.cond to a both-branches select); the cross-member gate below
        # is the real one
        self._tc_inner = dataclasses.replace(self.tc, gate_splits=False)

    def _det_init(self):
        if self._bank is None:
            return None
        # the packed bank state == the stacked scalar states, leaf for leaf
        return self._bank.init()

    def _det_update(self, dst, err_rate):
        if self._bank is None:
            return dst, jnp.zeros((self.ec.n_members,), bool)
        if self.ec.detector_impl == "bank":
            return self._bank.update(dst, err_rate)
        if self.ec.detector_impl != "vmap":
            raise ValueError(
                f"unknown detector impl {self.ec.detector_impl!r}")
        # legacy oracle: one scalar detector program per member, vmapped
        d = self.ec.detector
        if d == "adwin":
            fn = partial(detectors.adwin_update, ac=self._ac)
            return jax.vmap(lambda s, x: fn(s, x))(dst, err_rate)
        if d == "ddm":
            return jax.vmap(lambda s, x: detectors.ddm_update(s, x))(
                dst, err_rate)
        if d == "eddm":
            return jax.vmap(lambda s, x: detectors.eddm_update(s, x))(
                dst, err_rate)
        return jax.vmap(lambda s, x: detectors.ph_update(s, x))(dst, err_rate)

    def init(self, key):
        trees = jax.tree.map(lambda x: jnp.stack([x] * self.ec.n_members),
                             self._fresh)
        return {"trees": trees, "det": self._det_init(), "key": key}

    def state_sharding(self):
        """ShardMapEngine hint: the member axis is the ensemble's
        horizontal-parallelism axis (SAMOA runs each base learner in its
        own processor instance), so every per-member leaf -- the vmapped
        trees AND the packed detector bank -- partitions over 'data'; the
        shared PRNG key stays replicated.  The bank publishes its own
        leading-axis hints (DetectorBank.state_sharding), which the
        LearnerProcessor/ShardMapEngine chain picks up unchanged.
        eval_shape enumerates the tree state without allocating it."""
        from repro.distributed.sharding import leading_axis_spec
        st = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        member = partial(leading_axis_spec, "data")
        return {"trees": jax.tree.map(member, st["trees"]),
                "det": None if self._bank is None
                else self._bank.state_sharding("data"),
                "key": None}

    def step(self, state, xbin, y):
        ec, tc = self.ec, self.tc
        M = ec.n_members
        key, k1 = jax.random.split(state["key"])

        # --- route once through all members (batched multi-tree router) ---
        # the [M, B] leaf ids serve both the vote and the training scatter
        leaf = htree.route_members(state["trees"], xbin, tc,
                                   impl=ec.route_impl)

        # --- predict: weighted vote --------------------------------------
        counts = jnp.take_along_axis(state["trees"]["class_counts"],
                                     leaf[:, :, None], axis=1)  # [M, B, C]
        votes = jnp.argmax(counts, axis=-1)                 # [M, B]
        vote_oh = jax.nn.one_hot(votes, tc.n_classes).sum(0)
        pred = jnp.argmax(vote_oh, -1)
        correct = jnp.sum((pred == y).astype(f32))

        # --- per-member training weights ----------------------------------
        lam = jnp.ones((M, 1), f32)
        if ec.boost:
            # boosting: upweight instances mispredicted by earlier members
            # (parallel approximation: weight by current member error)
            member_err = (votes != y[None]).astype(f32)      # [M, B]
            cum_err = jnp.cumsum(member_err, 0) / jnp.arange(1, M + 1)[:, None]
            lam = 1.0 + 2.0 * jnp.concatenate(
                [jnp.zeros((1, member_err.shape[1])), cum_err[:-1]], 0)
        w = jax.random.poisson(k1, lam, (M, xbin.shape[0])).astype(f32)

        # --- train members: statistics (vmap, kernelized scatter) ---------
        def train_one(tree, lf, wts):
            return htree.update_stats(tree, lf, xbin, y, wts, tc)
        trees = jax.vmap(train_one)(state["trees"], leaf, w)

        # --- split checks, gated across members ---------------------------
        # exact: a member with no due leaf produces all-False should-split,
        # so skipping the whole decide/apply is an identity.  The gated
        # branch treats the M member node pools as ONE flattened [M*N]
        # pool and gain-reduces only a gathered <= check_tile row tile of
        # due leaves (the cross-member generalization of the single-tree
        # gather tile -- a lax.cond INSIDE the member vmap would lower to
        # a both-branches select, so per-member gating cannot work); child
        # class distributions come from the gathered rows' cumsum, so the
        # full [M, N, m, bins, C] reductions never run on the common path.
        # The full per-member vmap pass stays as the ungated oracle and
        # the overflow fallback.
        tci = self._tc_inner
        N = tc.max_nodes
        MN = M * N
        K = min(tc.check_tile, MN)
        C = tc.n_classes

        def split_all(ts):
            def split_one(tree):
                should, battr, bbin = htree.decide_splits(tree, tci)
                tree = dict(tree)
                att = (tree["split_attr"] < 0) & \
                    (tree["since_attempt"] >= tc.n_min)
                tree["since_attempt"] = jnp.where(att, 0.0,
                                                  tree["since_attempt"])
                tree, _ = htree.apply_splits(tree, should, battr, bbin, tci)
                return tree
            return jax.vmap(split_one)(ts)

        def split_gathered(ts):
            due = (ts["split_attr"] < 0) & (ts["since_attempt"] >= tc.n_min)
            flat = {k: ts[k].reshape((MN,) + ts[k].shape[2:])
                    for k in htree._DECIDE_KEYS}
            idx, s_k, a_k, b_k, left_k, right_k = htree.gather_decide_tile(
                flat, due.reshape(MN), K, tci, with_children=True)
            scat = lambda val, z: z.at[idx].set(val)
            should = scat(s_k, jnp.zeros((MN,), bool)).reshape(M, N)
            attr = scat(a_k, jnp.zeros((MN,), i32)).reshape(M, N)
            tbin = scat(b_k, jnp.zeros((MN,), i32)).reshape(M, N)
            left = scat(left_k, jnp.zeros((MN, C), f32)).reshape(M, N, C)
            right = scat(right_k, jnp.zeros((MN, C), f32)).reshape(M, N, C)
            ts = dict(ts)
            ts["since_attempt"] = jnp.where(due, 0.0, ts["since_attempt"])

            def apply_members(t):
                def one(tree, s, a, b, lc, rc):
                    tree, _ = htree.apply_splits(tree, s, a, b, tci,
                                                 child_counts=(lc, rc))
                    return tree
                return jax.vmap(one)(t, should, attr, tbin, left, right)

            # splits land far more rarely than leaves come due: skip the
            # whole rewiring (an identity when should is all-False)
            return jax.lax.cond(jnp.any(should), apply_members,
                                lambda t: t, ts)

        if not ec.gate_members:
            trees = split_all(trees)
        else:
            due_all = (trees["split_attr"] < 0) & \
                (trees["since_attempt"] >= tc.n_min)
            if ec.split_check == "pool":
                # under a mesh that partitions the member axis, the [M, N]
                # -> [M*N] flatten + global gather tile would make GSPMD
                # materialize cross-shard layouts; reformulate the pooled
                # check as an explicit shard_map (local tile, candidate
                # all-gather, global top-K) -- bit-identical, see below
                mesh = active_mesh()
                shards = (int(mesh.shape["data"]) if mesh is not None
                          and "data" in mesh.axis_names else 1)
                gathered = split_gathered
                if (shards > 1 and M % shards == 0
                        and not spmd_member_gather_suppressed()):
                    gathered = partial(self._split_pool_spmd, mesh=mesh,
                                       n_shards=shards)
                trees = htree.gated_check(jnp.sum(due_all.astype(i32)), K,
                                          gathered, split_all,
                                          lambda ts: ts, trees)
            elif ec.split_check == "member":
                # the shard-friendly gate: the [M, N] -> [M*N] flatten of
                # the pool tile would cross the partitioned member axis,
                # so sharded runs keep the per-member full pass behind
                # the cross-member any-due cond
                trees = jax.lax.cond(jnp.any(due_all), split_all,
                                     lambda ts: ts, trees)
            else:
                raise ValueError(
                    f"unknown split check {ec.split_check!r}")

        # --- change detection: reset drifted members ----------------------
        det = state["det"]
        if det is not None:
            member_err_rate = (votes != y[None]).astype(f32).mean(-1)
            det, drift = self._det_update(det, member_err_rate)
            def reset_member(old, fr):
                return jnp.where(
                    drift.reshape((-1,) + (1,) * (old.ndim - 1)), fr[None], old)
            trees = jax.tree.map(reset_member, trees, self._fresh)
        n_drift = drift.sum() if det is not None else jnp.zeros((), i32)

        new_state = {"trees": trees, "det": det, "key": key}
        metrics = {"correct": correct, "seen": jnp.asarray(y.shape[0], f32),
                   "drifts": n_drift.astype(f32)}
        return new_state, metrics

    def _split_pool_spmd(self, ts, *, mesh, n_shards):
        """The pooled split check as an explicit shard_map program over the
        partitioned member axis ('data').

        Per shard: flatten the local [M/S, N] pool, take the local top-K
        due tile (K = the global check_tile), all-gather ONLY those <= K
        candidate rows across shards, re-rank globally, run the gain
        reduction on the winning K rows, and scatter decisions back by
        global-index-minus-shard-offset.  Bit-identical to the
        single-shard ``split_gathered`` (and the "member" oracle): the
        gate guarantees n_due <= K, every due row survives its local
        top-K, per-row decide outputs depend only on that row's gathered
        stats, and apply_splits consumes scattered values only where
        ``should`` is True -- so filler-row selection order cannot leak
        into the result."""
        tc, tci, ec = self.tc, self._tc_inner, self.ec
        M, N, C = ec.n_members, tc.max_nodes, tc.n_classes
        K = min(tc.check_tile, M * N)
        LN = (M // n_shards) * N          # local pool rows per shard
        K_loc = min(K, LN)

        def shard_fn(ts_loc):
            M_loc = M // n_shards
            due = (ts_loc["split_attr"] < 0) & \
                (ts_loc["since_attempt"] >= tc.n_min)
            due_f = due.reshape(LN)
            flat = {k: ts_loc[k].reshape((LN,) + ts_loc[k].shape[2:])
                    for k in htree._DECIDE_KEYS}
            score = jnp.where(due_f, flat["since_attempt"], -1.0)
            loc_idx = jax.lax.top_k(score, K_loc)[1]
            shard = jax.lax.axis_index("data")
            cand = {k: flat[k][loc_idx] for k in htree._DECIDE_KEYS}
            cand["_score"] = score[loc_idx]
            cand["_gidx"] = loc_idx.astype(i32) + shard.astype(i32) * LN
            g = jax.tree.map(
                lambda v: jax.lax.all_gather(v, "data", axis=0, tiled=True),
                cand)                      # [n_shards*K_loc, ...]
            sel = jax.lax.top_k(g["_score"], K)[1]
            sub = {k: g[k][sel] for k in htree._DECIDE_KEYS}
            s_k, a_k, b_k = htree._decide_splits_impl(sub, tci)
            left_k, right_k = htree.child_counts_from_stats(
                sub["stats"], a_k, b_k)
            # scatter each decided row back to its owning shard; foreign
            # rows land on a scratch row past the local pool
            local = g["_gidx"][sel] - shard.astype(i32) * LN
            tgt = jnp.where((local >= 0) & (local < LN), local, LN)

            def scat(val, dtype, trail=()):
                z = jnp.zeros((LN + 1,) + trail, dtype)
                return z.at[tgt].set(val.astype(dtype))[:LN]

            should = scat(s_k, bool).reshape(M_loc, N)
            attr = scat(a_k, i32).reshape(M_loc, N)
            tbin = scat(b_k, i32).reshape(M_loc, N)
            left = scat(left_k, f32, (C,)).reshape(M_loc, N, C)
            right = scat(right_k, f32, (C,)).reshape(M_loc, N, C)
            out = dict(ts_loc)
            out["since_attempt"] = jnp.where(due, 0.0, out["since_attempt"])

            def apply_members(t):
                def one(tree, s, a, b, lc, rc):
                    tree, _ = htree.apply_splits(tree, s, a, b, tci,
                                                 child_counts=(lc, rc))
                    return tree
                return jax.vmap(one)(t, should, attr, tbin, left, right)

            # the rewiring gate must agree across shards: psum the local
            # landed-split counts (jnp.any of a local slice would diverge)
            landed = jax.lax.psum(jnp.sum(should.astype(i32)), "data")
            return jax.lax.cond(landed > 0, apply_members, lambda t: t, out)

        specs = jax.tree.map(lambda _: P("data"), ts)
        return jax.shard_map(shard_fn, mesh=mesh, in_specs=(specs,),
                             out_specs=specs, check_vma=False)(ts)

    def run(self, state, x_stream, y_stream):
        def body(st, xy):
            st, m = self.step(st, *xy)
            return st, m
        return jax.lax.scan(body, state, (x_stream, y_stream))
