"""Predict-only fast paths over a published snapshot.

The prequential step interleaves predict + train; serving traffic only
wants the predict half.  ``make_predict_fn(learner)`` returns ONE jitted
``f(state, x) -> pred`` per learner family containing exactly the read
path of that family's training step -- no statistics scatter, no split /
expansion checks, no RNG consumption:

  * VHT: ``kernels/tree_route`` + a class-count leaf read (the M == 1
    fast path of the batched router);
  * OzaBag/OzaBoost: one batched ``route_members`` call over all M trees
    + the same majority vote the step takes (member Poisson weights and
    detector updates are training-only and never run);
  * AMRules/VAMR/HAMR: the coverage matmul + first-cover + head-mean
    read (PH drift stats and rule expansion never run);
  * CluStream: nearest-macro-centroid assignment over the published
    macro centers (the online CF scatter never runs).

Each formula is kept OP-FOR-OP identical to the corresponding training
step's predict section, so a snapshot published at a chunk boundary
answers bit-identically to what the training loop itself would have
predicted at that point -- the serve/train parity property in
``tests/test_serving.py`` holds to the bit, not to a tolerance.

``reference_predict`` is the eager oracle for those tests: it recomputes
the prediction through the legacy (non-kernel) implementations where one
exists, so the fast path is checked against independent code.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.ml import amrules as _amrules
from repro.ml import clustream as _clustream
from repro.ml import htree as _htree
from repro.ml.amrules import AMRules, HAMR
from repro.ml.clustream import CluStream
from repro.ml.ensemble import OzaEnsemble
from repro.ml.vht import VHT
from repro.runtime.telemetry import program

f32 = jnp.float32


def _vht_predict(tc):
    def predict(state, xbin):
        leaf = _htree.route(state, xbin, tc)
        return jnp.argmax(state["class_counts"][leaf], axis=-1)
    return predict


def _ensemble_predict(ec, tc):
    def predict(state, xbin):
        leaf = _htree.route_members(state["trees"], xbin, tc,
                                    impl=ec.route_impl)
        counts = jnp.take_along_axis(state["trees"]["class_counts"],
                                     leaf[:, :, None], axis=1)   # [M, B, C]
        votes = jnp.argmax(counts, axis=-1)                      # [M, B]
        vote_oh = jax.nn.one_hot(votes, tc.n_classes).sum(0)
        return jnp.argmax(vote_oh, -1)
    return predict


def _amrules_predict(rc):
    R = rc.max_rules

    def predict(state, xbin):
        cov = _amrules.coverage(state, xbin, rc)
        first = _amrules.first_cover(cov, rc)
        covered = first < R
        head_mean = state["head_sum"] / jnp.maximum(state["head_n"], 1.0)
        d_mean = state["d_sum"] / jnp.maximum(state["d_n"], 1.0)
        return jnp.where(covered, head_mean[jnp.minimum(first, R - 1)],
                         d_mean)
    return predict


def _clustream_predict(cc):
    def predict(state, x):
        return _clustream.assign(state["macro"], x)
    return predict


def _fleet_predict(base):
    """Tenant-indexed predict over a packed fleet snapshot.

    ``predict(state, x, tenant)``: x is ``[B, ...]`` model inputs and
    tenant the ``[B]`` int ids naming whose model answers each row.  Each
    request's tenant rows are gathered out of the packed ``[F, ...]``
    state and the family's predict-only fast path runs vmapped over the
    batch -- one compiled program regardless of which tenants a batch
    mixes, answering row i exactly as tenant i's model would alone."""
    def predict(state, x, tenant):
        rows = jax.tree.map(lambda l: l[tenant], state["tenant"])
        return jax.vmap(lambda st, xi: base(st, xi[None])[0])(rows, x)
    return predict


def make_predict_fn(learner, *, jit: bool = True):
    """The jitted predict-only fast path for `learner`'s family.

    Returns ``f(state, x) -> pred`` where `state` is the learner state (a
    published ``Snapshot.state``) and `x` the batched model input (binned
    int attributes for the tree/rule families, float features for
    CluStream).  For a ``LearnerFleet`` the signature gains a tenant
    index: ``f(state, x, tenant) -> pred`` routes each row to its
    tenant's packed model."""
    from repro.ml.fleet import LearnerFleet
    if isinstance(learner, LearnerFleet):
        fn = _fleet_predict(make_predict_fn(learner.learner, jit=False))
    elif isinstance(learner, VHT):
        fn = _vht_predict(learner.tc)
    elif isinstance(learner, OzaEnsemble):
        fn = _ensemble_predict(learner.ec, learner.tc)
    elif isinstance(learner, (AMRules, HAMR)):
        fn = _amrules_predict(learner.rc)
    elif isinstance(learner, CluStream):
        fn = _clustream_predict(learner.cc)
    else:
        raise TypeError(
            f"no predict-only fast path for {type(learner).__name__}; "
            "expected VHT, OzaEnsemble, AMRules/VAMR/HAMR, or CluStream")
    return program(fn, "serve_predict") if jit else fn


def reference_predict(learner, state, x, tenant=None):
    """Eager oracle prediction -- independent (legacy) implementations
    where the fast path uses a kernel, the documented formula elsewhere.
    For a fleet, `tenant` names whose model answers each row and the
    oracle slices that tenant's state out and answers one row at a
    time -- no vmap, no gather program shared with the fast path."""
    from repro.ml.fleet import LearnerFleet
    if isinstance(learner, LearnerFleet):
        if tenant is None:
            raise ValueError("fleet reference_predict needs tenant ids")
        preds = [
            reference_predict(learner.learner,
                              learner.tenant_state(state, int(t)),
                              jnp.asarray(x)[i][None])[0]
            for i, t in enumerate(tenant)]
        return jnp.stack(preds)
    if isinstance(learner, VHT):
        tc = dataclasses.replace(learner.tc, route_impl="fori")
        pred, _ = _htree.predict(state, x, tc)
        return pred
    if isinstance(learner, OzaEnsemble):
        tc = learner.tc
        leaf = _htree.route_members(state["trees"], x, tc, impl="fori")
        counts = jnp.take_along_axis(state["trees"]["class_counts"],
                                     leaf[:, :, None], axis=1)
        votes = jnp.argmax(counts, axis=-1)
        vote_oh = jax.nn.one_hot(votes, tc.n_classes).sum(0)
        return jnp.argmax(vote_oh, -1)
    if isinstance(learner, (AMRules, HAMR)):
        return _amrules_predict(learner.rc)(state, x)
    if isinstance(learner, CluStream):
        d2 = _clustream.pairwise_d2(jnp.asarray(x), state["macro"],
                                    impl="onehot")
        return jnp.argmin(d2, -1)
    raise TypeError(f"no reference predict for {type(learner).__name__}")
