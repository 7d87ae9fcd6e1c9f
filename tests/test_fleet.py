"""Multi-tenant learner fleets: F independent learners of one family packed
into ``[F, ...]`` struct-of-arrays state, advanced by ONE compiled program.

The load-bearing property is fleet-vs-separate bit-parity: after any run,
row f of the fleet state and column f of the fleet metrics equal running
tenant f's learner ALONE on its own stream -- to the bit, for every family.
On top of that: the chunked runtime checkpoints/resumes the packed carry
bit-identically, per-tenant ``MetricAccumulator`` columns never mix, and
the serving path routes every request to its tenant's model."""

import pathlib
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.core.engines import JitEngine
from repro.core.evaluation import (ChunkedPrequentialEvaluation,
                                   MetricAccumulator)
from repro.data.generators import RandomTreeGenerator, bin_numeric
from repro.data.pipeline import ChunkedStream
from repro.ml import (AMRules, CluStream, CluStreamConfig, EnsembleConfig,
                      LearnerFleet, OzaEnsemble, RulesConfig, VHT, VHTConfig,
                      stack_payloads)
from repro.ml.htree import TreeConfig
from repro.serving import (ModelServer, ServeConfig, SnapshotPublisher,
                           make_predict_fn, model_state_of,
                           reference_predict, tenant_state_of)

B = 16          # tiny micro-batches: every (family, F, T) draw compiles
T_MAX = 6
F_MAX = 4

TC = TreeConfig(n_attrs=12, n_bins=8, n_classes=2, max_nodes=63, n_min=20,
                delta=0.05, tau=0.1)
RC = RulesConfig(n_attrs=12, n_bins=8, max_rules=16, n_min=100)
CC = CluStreamConfig(n_dims=12, n_micro=16, n_macro=3, period=2 * B)

LEARNERS = {
    "vht": VHT(VHTConfig(TC)),
    "ozabag": OzaEnsemble(EnsembleConfig(tree=TC, n_members=3)),
    "amrules": AMRules(RC),
    "clustream": CluStream(CC),
}
KEY = jax.random.PRNGKey(7)

_GEN = RandomTreeGenerator(n_cat=6, n_num=6, depth=5, seed=3)
_TENANT_XY: dict = {}


def _tenant_xy(f):
    """Tenant f's private stream -- DIFFERENT per tenant, so any cross-
    tenant mixing (state rows, metric columns) breaks parity loudly."""
    if f not in _TENANT_XY:
        key = jax.random.PRNGKey(100 + f)
        xs, ys = [], []
        for _ in range(T_MAX):
            key, k = jax.random.split(key)
            x, y = _GEN.sample(k, B)
            xs.append(bin_numeric(x, 8))
            ys.append(y)
        _TENANT_XY[f] = (jnp.stack(xs), jnp.stack(ys))
    return _TENANT_XY[f]


def _payload(family, f, t):
    xs, ys = _tenant_xy(f)
    if family == "clustream":
        return {"x": xs[:t].astype(jnp.float32)}
    if family == "amrules":
        return {"x": xs[:t], "y": ys[:t].astype(jnp.float32)}
    return {"x": xs[:t], "y": ys[:t]}


def _fleet_payload(family, n, t):
    return stack_payloads([_payload(family, f, t) for f in range(n)])


def _assert_trees_identical(a, b):
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree.leaves(b)
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=str(path))


def _run_fleet(family, n, t, c):
    """One chunked engine run of an n-tenant fleet; returns the fleet,
    its final packed state, and the stacked outputs ([T, F, ...])."""
    fleet = LearnerFleet(LEARNERS[family], n)
    eng = JitEngine()
    carry = eng.init(fleet, KEY)
    carry, outs = eng.run_stream(fleet, carry, _fleet_payload(family, n, t),
                                 chunk_len=c)
    state = model_state_of(carry)
    return fleet, state, outs


def _run_separate(family, fleet, f, t, c):
    """Tenant f's learner alone on its own stream, started from the SAME
    per-tenant init the fleet used (``init`` parity is its own test)."""
    learner = fleet.learner
    eng = JitEngine()
    carry = eng.init(learner, KEY)
    name = next(iter(carry["states"]))
    carry["states"][name] = learner.init(fleet.tenant_keys(
        jax.random.split(KEY, 1)[0])[f])
    carry, outs = eng.run_stream(learner, carry, _payload(family, f, t),
                                 chunk_len=c)
    return model_state_of(carry), outs


# -------------------- fleet == F separate runs, all families ---------------

@pytest.mark.parametrize("family", list(LEARNERS))
def test_fleet_bit_identical_to_separate_runs(family):
    """The tentpole acceptance at test scale: every tenant's row of the
    packed state AND every metric column equals the tenant's own
    single-learner run, bit for bit."""
    n, t, c = 3, 4, 2
    fleet, state, outs = _run_fleet(family, n, t, c)
    np.testing.assert_array_equal(np.asarray(state["cursor"]),
                                  np.full((n,), t))
    for f in range(n):
        sep_state, sep_outs = _run_separate(family, fleet, f, t, c)
        _assert_trees_identical(sep_state, fleet.tenant_state(state, f))
        _assert_trees_identical(sep_outs,
                                jax.tree.map(lambda x: x[:, f], outs))


def test_fleet_init_rows_match_separate_init():
    """Row f of the vmapped fleet init is bit-identical to the single
    learner initialized with row f of ``tenant_keys`` -- the contract a
    separate per-tenant run relies on to reproduce a fleet tenant."""
    for family, learner in LEARNERS.items():
        fleet = LearnerFleet(learner, 3)
        key = jax.random.PRNGKey(42)
        packed = fleet.init(key)
        assert packed["cursor"].shape == (3,)
        for f, k in enumerate(fleet.tenant_keys(key)):
            _assert_trees_identical(learner.init(k),
                                    fleet.tenant_state(packed, f))


def test_fleet_cursor_ignores_padding_steps():
    """T not divisible by chunk_len: the masked no-op tail steps must NOT
    advance any tenant's stream cursor (the engine's masking preserves
    the whole carry, cursor included)."""
    _, state, _ = _run_fleet("vht", 2, 5, 2)       # 3 chunks, 1 padded step
    np.testing.assert_array_equal(np.asarray(state["cursor"]), [5, 5])


# -------------------- hypothesis: random F / family / T --------------------

try:
    from hypothesis import example, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @given(family=st.sampled_from(sorted(LEARNERS)),
           n=st.integers(1, F_MAX), t=st.integers(1, T_MAX))
    @example(family="vht", n=1, t=3)         # F == 1: degenerate fleet
    @example(family="amrules", n=4, t=1)     # single-step stream
    @settings(max_examples=8, deadline=None)
    def test_fleet_property_bit_parity(family, n, t):
        """Fleet-vs-separate bit-parity over random fleet sizes, stream
        lengths, and families (chunk_len 2 keeps padded tails in play)."""
        fleet, state, outs = _run_fleet(family, n, t, 2)
        f = n - 1                  # the last tenant: most displaced row
        sep_state, sep_outs = _run_separate(family, fleet, f, t, 2)
        _assert_trees_identical(sep_state, fleet.tenant_state(state, f))
        _assert_trees_identical(sep_outs,
                                jax.tree.map(lambda x: x[:, f], outs))


# -------------------- stack / unstack / merge ------------------------------

def test_stack_unstack_round_trip():
    learner = LEARNERS["clustream"]
    fleet = LearnerFleet(learner, 3)
    seps = [learner.init(k) for k in fleet.tenant_keys(KEY)]
    packed = fleet.stack(seps, cursor=[4, 5, 6])
    np.testing.assert_array_equal(np.asarray(packed["cursor"]), [4, 5, 6])
    back = fleet.unstack(packed)
    assert len(back) == 3
    for sep, b in zip(seps, back):
        _assert_trees_identical(sep, b)


def test_stack_payloads_shapes_and_validation():
    fp = _fleet_payload("vht", 3, 4)
    assert fp["x"].shape[:3] == (4, 3, B)      # [T, F, B, ...]
    assert fp["y"].shape == (4, 3, B)
    with pytest.raises(ValueError, match="at least one"):
        stack_payloads([])


def test_fleet_rejects_bad_construction_and_indices():
    learner = LEARNERS["vht"]
    fleet = LearnerFleet(learner, 2)
    with pytest.raises(TypeError, match="do not nest"):
        LearnerFleet(fleet, 2)
    with pytest.raises(TypeError, match="no fleet support"):
        LearnerFleet(object(), 2)
    with pytest.raises(ValueError, match="n_tenants"):
        LearnerFleet(learner, 0)
    with pytest.raises(ValueError, match="expected 2 tenant states"):
        fleet.stack([learner.init(KEY)])
    with pytest.raises(ValueError, match="outside"):
        fleet.tenant_state(fleet.init(KEY), 2)


def test_fleet_merge_matches_per_tenant_merge():
    """Merging shard-local fleet states == merging every tenant's shard
    states separately (the packed CF merge is elementwise), and the
    per-tenant cursors add."""
    from repro.ml.clustream import merge as clustream_merge
    learner = LEARNERS["clustream"]
    fleet = LearnerFleet(learner, 2)
    eng = JitEngine()
    halves = []
    for half, (lo, hi) in enumerate(((0, 2), (2, 4))):
        carry = eng.init(fleet, KEY)
        payload = jax.tree.map(lambda x: x[lo:hi],
                               _fleet_payload("clustream", 2, 4))
        carry, _ = eng.run_stream(fleet, carry, payload, chunk_len=2)
        halves.append(model_state_of(carry))
    merged = fleet.merge(halves)
    np.testing.assert_array_equal(np.asarray(merged["cursor"]), [4, 4])
    for f in range(2):
        per_tenant = clustream_merge(
            [fleet.tenant_state(h, f) for h in halves])
        _assert_trees_identical(per_tenant, fleet.tenant_state(merged, f))
    with pytest.raises(TypeError, match="no merge"):
        LearnerFleet(LEARNERS["vht"], 2).merge(
            [LearnerFleet(LEARNERS["vht"], 2).init(KEY)])


# -------------------- sharding hints ---------------------------------------

def test_fleet_state_sharding_composes_inner_hints():
    """The fleet axis shards over 'data' on every leaf; family hints shift
    one dimension right ('model' axes survive), and an inner 'data'
    assignment (the ensemble member axis) yields to the fleet axis."""
    vht = LearnerFleet(LEARNERS["vht"], 4).state_sharding()
    assert vht["cursor"] == P("data")
    assert all(spec[0] == "data" for spec in jax.tree.leaves(
        vht["tenant"], is_leaf=lambda v: isinstance(v, P)))

    rules = LearnerFleet(LEARNERS["amrules"], 4).state_sharding()
    assert rules["tenant"]["stats"][:2] == ("data", "model")
    assert rules["tenant"]["head_n"] == P("data", "model")

    ens = LearnerFleet(LEARNERS["ozabag"], 4).state_sharding()
    member_leaf = ens["tenant"]["trees"]["stats"]
    assert member_leaf[0] == "data" and "data" not in member_leaf[1:]


# -------------------- chunked evaluation: metrics + kill/resume ------------

def test_fleet_per_tenant_metrics_never_mix():
    """``ChunkedPrequentialEvaluation`` over a fleet yields an [F] metric
    vector and [F]-row curve where column f equals tenant f's OWN
    single-learner evaluation -- different per-tenant streams, so any
    cross-tenant mixing shifts a column."""
    n, t, c = 3, 4, 2
    fleet = LearnerFleet(LEARNERS["vht"], n)
    r = ChunkedPrequentialEvaluation(
        fleet, ChunkedStream(_fleet_payload("vht", n, t), c),
        key=KEY).run()
    metric = np.asarray(r.metric)
    assert metric.shape == (n,)
    curve = np.asarray(r.curve)
    assert curve.shape == (t, n)
    for f in range(n):
        state, outs = _run_separate("vht", fleet, f, t, c)
        acc = MetricAccumulator()
        acc.update(outs["metrics"])
        assert metric[f] == acc.metric
        np.testing.assert_array_equal(curve[:, f], np.asarray(acc.curve))
    assert len(set(np.round(metric, 12))) > 1      # streams truly differ


def test_fleet_chunked_kill_resume_bit_identical(tmp_path):
    """A killed fleet run resumes from its checkpoint -- packed [F, ...]
    carry, per-tenant cursors, and the [F]-column metric accumulator all
    restored structurally -- and finishes EXACTLY like the uninterrupted
    run."""
    n, t, c = 3, 6, 2
    fleet = LearnerFleet(LEARNERS["amrules"], n)
    stream = ChunkedStream(_fleet_payload("amrules", n, t), c)

    r0 = ChunkedPrequentialEvaluation(fleet, stream, key=KEY).run()

    mgr = CheckpointManager(tmp_path, keep=0, async_write=False)
    full = ChunkedPrequentialEvaluation(fleet, stream, checkpoint=mgr,
                                        checkpoint_every=1, key=KEY)
    r1 = full.run(resume=False)
    np.testing.assert_array_equal(np.asarray(r1.metric),
                                  np.asarray(r0.metric))

    # "kill" after chunk 1: drop later checkpoints, resume mid-stream
    for s in mgr.all_steps():
        if s > 1:
            shutil.rmtree(pathlib.Path(tmp_path) / f"step_{s:010d}")
    assert mgr.latest_step() == 1
    resumed = ChunkedPrequentialEvaluation(
        fleet, stream, checkpoint=CheckpointManager(tmp_path, keep=0,
                                                    async_write=False),
        checkpoint_every=10 ** 9, key=KEY)
    r2 = resumed.run(resume=True)
    np.testing.assert_array_equal(np.asarray(r2.metric),
                                  np.asarray(r0.metric))
    np.testing.assert_array_equal(np.asarray(r2.curve),
                                  np.asarray(r0.curve))
    _assert_trees_identical(r0.extra["carry"], r2.extra["carry"])
    cursor = model_state_of(r2.extra["carry"])["cursor"]
    np.testing.assert_array_equal(np.asarray(cursor), np.full((n,), t))


# -------------------- serving: tenant routing ------------------------------

def _trained_fleet(family="vht", n=3):
    fleet, state, _ = _run_fleet(family, n, 4, 2)
    return fleet, state


def test_fleet_predict_fn_matches_reference_and_tenant_slices():
    """The batched tenant-indexed fast path answers every row exactly as
    that tenant's model would alone: against the eager oracle AND against
    the single-learner fast path run on the sliced-out tenant state."""
    fleet, state = _trained_fleet()
    xs = _tenant_xy(0)[0][5][:6]                       # 6 query rows
    tenants = jnp.asarray([0, 2, 1, 1, 0, 2], jnp.int32)
    fast = make_predict_fn(fleet)
    got = np.asarray(fast(state, xs, tenants))
    ref = np.asarray(reference_predict(fleet, state, xs, tenant=tenants))
    np.testing.assert_array_equal(got, ref)
    single = make_predict_fn(fleet.learner)
    for i, f in enumerate(np.asarray(tenants)):
        sliced = tenant_state_of(state, int(f))
        _assert_trees_identical(sliced, fleet.tenant_state(state, int(f)))
        np.testing.assert_array_equal(
            got[i], np.asarray(single(sliced, xs[i][None]))[0])
    with pytest.raises(ValueError, match="tenant"):
        reference_predict(fleet, state, xs)
    with pytest.raises(TypeError, match="not a fleet"):
        tenant_state_of({"stats": jnp.zeros(3)}, 0)


def test_fleet_server_routes_requests_to_their_tenant():
    """``ModelServer`` over a published fleet snapshot: requests carry a
    tenant id, answers come from THAT tenant's model (oracle-checked) and
    say so in their meta; tenant-less or out-of-range submits are
    rejected before any accounting."""
    fleet, state = _trained_fleet()
    pub = SnapshotPublisher()
    assert pub.publish(0, state)
    srv = ModelServer(fleet, pub, ServeConfig(max_batch=4, max_wait_ms=1.0))
    try:
        xs = _tenant_xy(0)[0][5][:4]
        # the first predict compiles the fleet predict program: wait for
        # it apart, so the checked requests' waits cover no compile
        assert srv.submit(xs[0], tenant=0).result(300.0).status == "answered"
        tenants = [2, 0, 1, 2]
        reqs = [srv.submit(xs[i], tenant=f)
                for i, f in enumerate(tenants)]
        preds = [int(r.result(5.0).pred) for r in reqs]
        ref = np.asarray(reference_predict(
            fleet, state, xs, tenant=jnp.asarray(tenants)))
        np.testing.assert_array_equal(preds, ref)
        assert [r.meta["tenant"] for r in reqs] == tenants
        with pytest.raises(ValueError, match="tenant=<id>"):
            srv.submit(xs[0])
        with pytest.raises(ValueError, match="outside"):
            srv.submit(xs[0], tenant=3)
        assert srv.status()["accounting_ok"]
    finally:
        srv.stop()
    single = ModelServer(fleet.learner, pub, start=False)
    with pytest.raises(ValueError, match="requires a LearnerFleet"):
        single.submit(xs[0], tenant=0)
