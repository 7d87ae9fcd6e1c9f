"""Vertical Hoeffding Tree (paper section 6) + the horizontal baseline.

Variants (paper's experimental arms):

  local  -- split_delay=0: decisions applied within the step (== sequential
            VFDT; our 'moa' equivalent -- see EXPERIMENTS.md note).
  wok    -- split_delay=D>0, buffer_size=0: instances that reach a leaf
            with a pending split decision are DROPPED (load shedding).
  wk(z)  -- split_delay=D>0, buffer_size=z: such instances still update
            statistics downstream AND are buffered; when the split is
            applied the buffer is replayed through the new tree.
  sharding -- horizontal parallelism: ensemble of p trees over stream
            shards, majority vote (the paper's memory-hungry baseline).

The VHT step is one jit-able function; the same logic is also exposed as a
Topology (ModelAggregatorProcessor + LocalStatisticProcessor wired with key
grouping) so it runs on Local/Jit/ShardMap engines -- the platform claim.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.topology import Grouping, Processor, TopologyBuilder
from repro.ml import htree
from repro.ml.htree import TreeConfig

f32 = jnp.float32
i32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class VHTConfig:
    tree: TreeConfig

    @property
    def variant(self) -> str:
        if self.tree.split_delay == 0:
            return "local"
        return f"wk({self.tree.buffer_size})" if self.tree.buffer_size else "wok"


class VHT:
    """Functional VHT learner: state pytree + pure step."""

    def __init__(self, cfg: VHTConfig):
        self.cfg = cfg
        self.tc = cfg.tree

    def init(self, key=None):
        return htree.init_tree(self.tc)

    # -------------------------------------------------------------- step

    def step(self, state, xbin, y):
        """Prequential micro-batch step: test then train.

        Returns (state, metrics) with metrics = {correct, seen, dropped}.
        """
        tc = self.tc
        pred, leaf = htree.predict(state, xbin, tc)
        correct = jnp.sum((pred == y).astype(f32))

        pending_here = state["pending"][leaf]
        dropped = 0.0
        if tc.split_delay == 0:
            w = jnp.ones_like(y, f32)
        elif tc.buffer_size:
            # wk(z): buffered instances still train downstream -> none dropped
            w = jnp.ones_like(y, f32)
            state = self._buffer_add(state, xbin, y, pending_here)
        else:
            w = jnp.where(pending_here, 0.0, 1.0)   # wok: shed load
            dropped = jnp.sum((pending_here).astype(f32))

        state = htree.update_stats(state, leaf, xbin, y, w, tc)

        # countdown + apply matured split decisions (the feedback loop)
        state, applied = self._apply_pending(state)
        # trigger new decisions on current statistics (LS compute + MA recv)
        should, battr, bbin = htree.decide_splits(state, tc)
        state = dict(state)
        # reset the grace-period counter on every attempted leaf
        attempted = (state["split_attr"] < 0) & (state["since_attempt"] >= tc.n_min)
        state["since_attempt"] = jnp.where(attempted, 0.0, state["since_attempt"])
        if tc.split_delay == 0:
            state, _ = htree.apply_splits(state, should, battr, bbin, tc)
        else:
            state["pending"] = state["pending"] | should
            state["pending_attr"] = jnp.where(should, battr, state["pending_attr"])
            state["pending_bin"] = jnp.where(should, bbin, state["pending_bin"])
            state["pending_timer"] = jnp.where(
                should, tc.split_delay, state["pending_timer"])
        if tc.buffer_size:
            state = self._replay_if(state, applied)
        metrics = {"correct": correct, "seen": jnp.asarray(y.shape[0], f32),
                   "dropped": jnp.asarray(dropped, f32),
                   "n_nodes": state["n_nodes"].astype(f32)}
        return state, metrics

    def _apply_pending(self, state):
        tc = self.tc
        if tc.split_delay == 0:
            return state, jnp.zeros((), bool)
        state = dict(state)
        timer = jnp.where(state["pending"], state["pending_timer"] - 1,
                          state["pending_timer"])
        mature = state["pending"] & (timer <= 0)
        state["pending_timer"] = timer
        state, did = htree.apply_splits(
            state, mature, state["pending_attr"], state["pending_bin"], tc)
        state["pending"] = state["pending"] & ~mature
        return state, jnp.any(did)

    # ---------------------------------------------------- wk(z) buffering

    def _buffer_add(self, state, xbin, y, mask):
        tc = self.tc
        state = dict(state)
        Z = tc.buffer_size
        B = y.shape[0]
        # compact the masked instances to the front, then write a window
        order = jnp.argsort(~mask)                       # masked first
        xs = xbin[order]
        ys = y[order]
        k = jnp.sum(mask.astype(i32))
        idx = (state["buf_n"] + jnp.arange(B)) % Z
        take = jnp.arange(B) < jnp.minimum(k, Z)
        write_idx = jnp.where(take, idx, Z)              # scratch row Z
        bx = jnp.concatenate([state["buf_x"], jnp.zeros((1, tc.n_attrs), i32)], 0)
        by = jnp.concatenate([state["buf_y"], jnp.zeros((1,), i32)], 0)
        bv = jnp.concatenate([state["buf_valid"], jnp.zeros((1,), bool)], 0)
        bx = bx.at[write_idx].set(xs)[:Z]
        by = by.at[write_idx].set(ys)[:Z]
        bv = bv.at[write_idx].set(True)[:Z]
        state["buf_x"], state["buf_y"], state["buf_valid"] = bx, by, bv
        state["buf_n"] = (state["buf_n"] + jnp.minimum(k, Z)) % jnp.maximum(Z, 1)
        return state

    def _replay_if(self, state, applied):
        """Replay the buffer through the new tree when a split landed."""
        tc = self.tc
        state = dict(state)
        leaf = htree.route(state, state["buf_x"], tc)
        w = jnp.where(state["buf_valid"] & applied, 1.0, 0.0)
        state = htree.update_stats(state, leaf, state["buf_x"],
                                   state["buf_y"], w, tc)
        clear = applied
        state["buf_valid"] = jnp.where(clear, jnp.zeros_like(state["buf_valid"]),
                                       state["buf_valid"])
        return state

    # ------------------------------------------- chunk-program layout

    def to_scan(self, state):
        """The state as a scanned chunk program carries it: the statistics
        as the kernel's packed row-major view [N, m*bins*C], so no step
        converts them (a 4-D carry gets a layout of XLA's choosing, and
        every kernel call and split check then relayouts the whole
        tensor).  A width the kernel would pad stays 4-D, and so do the
        XLA statistics paths, which scatter into the 4-D form (packed, a
        point scatter ran dense-1000 at under half that speed on a v5e).
        ``from_scan`` restores [N, m, bins, C] at the program's exit."""
        from repro.kernels.rule_stats.kernel import packs
        from repro.kernels.vht_stats.ops import default_impl
        tc = self.tc
        impl = default_impl() if tc.stats_impl == "auto" else tc.stats_impl
        if impl != "pallas" or not packs(
                tc.n_attrs, tc.n_bins * tc.n_classes, tc.attr_tile):
            return state
        with jax.named_scope("stats_pack"):
            return {**state, "stats": state["stats"].reshape(
                tc.max_nodes, -1)}

    def from_scan(self, state):
        with jax.named_scope("stats_unpack"):
            return {**state, "stats": htree.unpacked(state["stats"],
                                                     self.tc)}

    # ---------------------------------------------------- prequential run

    def run(self, state, xbin_stream, y_stream):
        """scan over micro-batches; returns (state, per-batch accuracy)."""
        def body(st, xy):
            xb, yb = xy
            st, m = self.step(st, xb, yb)
            return st, m
        return jax.lax.scan(body, state, (xbin_stream, y_stream))


# ---------------------------------------------------------------------------
# horizontal parallelism baseline (paper: 'sharding')
# ---------------------------------------------------------------------------

class ShardingEnsemble:
    """p independent Hoeffding trees on stream shards; majority vote.

    Memory grows p-fold (each tree tracks ALL attributes) -- the blow-up the
    paper demonstrates OOMs at 20k dense attributes.
    """

    def __init__(self, tc: TreeConfig, p: int):
        self.tc = dataclasses.replace(tc, split_delay=0, buffer_size=0)
        self.p = p
        self._vht = VHT(VHTConfig(self.tc))

    def init(self, key=None):
        one = htree.init_tree(self.tc)
        return jax.tree.map(lambda x: jnp.stack([x] * self.p), one)

    def step(self, states, xbin, y):
        B = y.shape[0]
        p = self.p
        # majority-vote prediction over the full batch
        def pred_one(st):
            yhat, _ = htree.predict(st, xbin, self.tc)
            return yhat
        votes = jax.vmap(pred_one)(states)               # [p, B]
        onehot = jax.nn.one_hot(votes, self.tc.n_classes).sum(0)
        pred = jnp.argmax(onehot, -1)
        correct = jnp.sum((pred == y).astype(f32))
        # shuffle-group training: shard the batch across the ensemble
        xs = xbin[: (B // p) * p].reshape(p, B // p, -1)
        ys = y[: (B // p) * p].reshape(p, B // p)
        def train_one(st, xb, yb):
            st, _ = self._vht.step(st, xb, yb)
            return st
        states = jax.vmap(train_one)(states, xs, ys)
        return states, {"correct": correct, "seen": jnp.asarray(B, f32),
                        "dropped": jnp.zeros((), f32),
                        "n_nodes": states["n_nodes"].astype(f32).sum()}

    def run(self, states, xbin_stream, y_stream):
        def body(st, xy):
            xb, yb = xy
            st, m = self.step(st, xb, yb)
            return st, m
        return jax.lax.scan(body, states, (xbin_stream, y_stream))


# ---------------------------------------------------------------------------
# Topology wiring (the paper's Figure 2 as platform objects)
# ---------------------------------------------------------------------------

class ModelAggregatorProcessor(Processor):
    """Holds the tree structure; sorts instances; applies split feedback."""

    name = "model-aggregator"

    def __init__(self, cfg: VHTConfig):
        self.cfg = cfg
        self.tc = cfg.tree

    def init_state(self, key):
        st = htree.init_tree(self.tc)
        # MA holds everything except the big statistics tensor
        st.pop("stats")
        return st

    def process(self, state, inputs):
        tc = self.tc
        out = {}
        # split feedback from the statistics (local-result events); the
        # child class distributions ride along in the event, so no
        # statistics tensor (or cumsum over one) is needed here
        fb = inputs.get("local-result")
        if fb is not None:
            should = fb["should"] & (state["split_attr"] < 0)
            state, _ = htree.apply_splits(
                state, should, fb["attr"], fb["bin"], tc,
                child_counts=(fb["left"], fb["right"]))
            state = dict(state)
            state["class_counts"] = jnp.where(
                should[:, None], fb["left"] + fb["right"],
                state["class_counts"])
            out["drop"] = {"leaf_mask": should}
        src = inputs.get("__source__")
        if src is not None:
            xbin, y = src["x"], src["y"]
            leaf = htree.route(state, xbin, tc)
            counts = state["class_counts"][leaf]
            pred = jnp.argmax(counts, -1)
            state = dict(state)
            state["n_total"] = state["n_total"].at[leaf].add(1.0)
            state["since_attempt"] = state["since_attempt"].at[leaf].add(1.0)
            attempt = state["since_attempt"] >= tc.n_min
            state["since_attempt"] = jnp.where(attempt, 0.0, state["since_attempt"])
            # attribute events (key-grouped on (leaf, attr)) + compute events
            out["attribute"] = {"leaf": leaf, "x": xbin, "y": y}
            out["compute"] = {"attempt_mask": attempt,
                              "n_total": state["n_total"]}
            out["prediction"] = {"pred": pred, "y": y}
        return state, out


class LocalStatisticProcessor(Processor):
    """Key-grouped statistics: updates n_ijk, answers compute events."""

    name = "local-statistic"

    def __init__(self, cfg: VHTConfig):
        self.cfg = cfg
        self.tc = cfg.tree

    def init_state(self, key):
        tc = self.tc
        return {"stats": jnp.zeros((tc.max_nodes, tc.n_attrs, tc.n_bins,
                                    tc.n_classes), f32)}

    def state_sharding(self):
        from jax.sharding import PartitionSpec as P
        return {"stats": P(None, "model", None, None)}

    def _update(self, stats, ev):
        """Alg. 2 on the attribute events.  Under a mesh whose 'model'
        axis splits the attributes, each device updates its own attribute
        slice (a shard_map over the key grouping): the instances are
        replicated, the statistics never move, and the counters are
        exact, so the result is bit-identical to one device."""
        from repro.distributed.sharding import kernel_mesh, run_per_shard
        from repro.kernels.vht_stats.ops import stats_update
        tc = self.tc

        def update(stats, leaf, x, y):
            w = jnp.ones(y.shape[0], f32)
            return stats_update(stats, leaf, x, y, w, impl=tc.stats_impl,
                                attr_tile=tc.attr_tile)

        args = (stats, ev["leaf"], ev["x"], ev["y"])
        mesh = kernel_mesh()
        n = mesh.shape.get("model", 1) if mesh is not None else 1
        if n == 1 or tc.n_attrs % n:
            return update(*args)
        from jax.sharding import PartitionSpec as P
        spec = P(None, "model", None, None)
        return run_per_shard(update, mesh,
                             (spec, P(), P(None, "model"), P()), spec, *args)

    def process(self, state, inputs):
        tc = self.tc
        out = {}
        attr_ev = inputs.get("attribute")
        if attr_ev is not None:
            state = {"stats": self._update(state["stats"], attr_ev)}
        comp = inputs.get("compute")
        if comp is not None:
            N, C = tc.max_nodes, tc.n_classes

            def answer_rows(stats_rows, n_total_rows, mask_rows):
                """Split criterion over a row subset (Alg. 3): gains +
                Hoeffding test + child class distributions."""
                gains = htree.split_gains(stats_rows, tc)
                k, m, bins = gains.shape
                flat = gains.reshape(k, m * bins)
                top2, idx2 = jax.lax.top_k(flat, 2)
                ga, gb = top2[:, 0], top2[:, 1]
                battr, bbin = idx2[:, 0] // bins, idx2[:, 0] % bins
                eps = htree.hoeffding_bound(n_total_rows, tc)
                ok = (ga > 0) & ((ga - gb > eps) | (eps < tc.tau))
                should = mask_rows & ok
                rows = jnp.arange(k)
                cum = jnp.cumsum(stats_rows, axis=2)
                left = cum[rows, jnp.maximum(battr, 0), jnp.maximum(bbin, 0)]
                right = cum[rows, jnp.maximum(battr, 0), -1] - left
                return should, battr, bbin, left, right

            def full(stats):
                s, a, b, le, ri = answer_rows(stats, comp["n_total"],
                                              comp["attempt_mask"])
                return {"should": s, "attr": a, "bin": b,
                        "left": le, "right": ri}

            if tc.gate_splits:
                # the gain reduction only runs when a leaf exhausted its
                # grace period, and only over the (few) due rows when they
                # fit the check tile; an all-False answer is exact
                # otherwise because only attempted leaves can split
                K = min(tc.check_tile, N)

                def gathered(stats):
                    idx = htree.due_topk(comp["attempt_mask"],
                                         comp["n_total"], K)
                    s, a, b, le, ri = answer_rows(
                        stats[idx], comp["n_total"][idx],
                        comp["attempt_mask"][idx])
                    return {"should": jnp.zeros((N,), bool).at[idx].set(s),
                            "attr": jnp.zeros((N,), i32).at[idx].set(a),
                            "bin": jnp.zeros((N,), i32).at[idx].set(b),
                            "left": jnp.zeros((N, C), f32).at[idx].set(le),
                            "right": jnp.zeros((N, C), f32).at[idx].set(ri)}

                out["local-result"] = htree.gated_check(
                    jnp.sum(comp["attempt_mask"].astype(i32)), K,
                    gathered, full,
                    lambda st: {"should": jnp.zeros((N,), bool),
                                "attr": jnp.zeros((N,), i32),
                                "bin": jnp.zeros((N,), i32),
                                "left": jnp.zeros((N, C), f32),
                                "right": jnp.zeros((N, C), f32)},
                    state["stats"])
            else:
                out["local-result"] = full(state["stats"])
        drop = inputs.get("drop")
        if drop is not None:
            zero = jnp.zeros_like(state["stats"][0])
            state = {"stats": jnp.where(drop["leaf_mask"][:, None, None, None],
                                        zero[None], state["stats"])}
        return state, out


def build_vht_topology(cfg: VHTConfig) -> "Topology":
    """Figure 2: S -> MA -> (attribute: key grouping) -> LS -> (local-result)
    -> MA, with compute/drop broadcast (all grouping)."""
    b = TopologyBuilder("vht")
    ma = b.add_processor(ModelAggregatorProcessor(cfg), entry=True)
    ls = b.add_processor(LocalStatisticProcessor(cfg),
                         parallelism=cfg.tree.n_attrs)
    b.create_stream("attribute", ma)
    b.connect_key("attribute", ls)
    b.create_stream("compute", ma)
    b.connect_all("compute", ls)
    b.create_stream("drop", ma)
    b.connect_all("drop", ls)
    b.create_stream("local-result", ls)
    b.connect_key("local-result", ma)
    b.create_stream("prediction", ma)
    return b.build()
