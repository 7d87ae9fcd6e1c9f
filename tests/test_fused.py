"""Fused whole-stream execution: scan-compiled engines, segment statistics,
and gated split checks must be *exactly* the semantics of the per-step
reference paths -- this PR is a perf change, not a behavior change."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engines import JitEngine, LocalEngine
from repro.core.evaluation import stack_outputs
from repro.data.generators import (ElectricityLikeGenerator,
                                   RandomTreeGenerator, bin_numeric)
from repro.kernels.rule_stats.ops import (rule_moments, rule_stats_update,
                                          rule_stats_update_segment)
from repro.kernels.rule_stats.ref import rule_stats_ref
from repro.kernels.tree_route.ops import tree_route
from repro.kernels.tree_route.ref import tree_route_ref
from repro.kernels.vht_stats.ops import stats_update, stats_update_segment
from repro.kernels.vht_stats.ref import stats_update_ref
from repro.ml import clustream
from repro.ml.amrules import AMRules, HAMR, RulesConfig, VAMR
from repro.ml.clustream import CluStream, CluStreamConfig
from repro.ml.ensemble import EnsembleConfig, OzaEnsemble
from repro.ml.htree import TreeConfig
from repro.ml.vht import VHT, VHTConfig, build_vht_topology

TC = TreeConfig(n_attrs=20, n_bins=8, n_classes=2, max_nodes=127, n_min=100)
RC = RulesConfig(n_attrs=12, n_bins=8, max_rules=32, n_min=150)


@pytest.fixture(scope="module")
def dense_stream():
    gen = RandomTreeGenerator(n_cat=10, n_num=10, depth=5, seed=3)
    key = jax.random.PRNGKey(0)
    xs, ys = [], []
    for _ in range(40):
        key, k = jax.random.split(key)
        x, y = gen.sample(k, 256)
        xs.append(bin_numeric(x, 8))
        ys.append(y)
    return jnp.stack(xs), jnp.stack(ys)


def _assert_trees_identical(a, b):
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree.leaves(b)
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=str(path))


# ------------------------- scanned engine == per-step loop -----------------

def test_jit_engine_run_stream_bit_identical_to_step_loop(dense_stream):
    """The tentpole acceptance: one compiled scan over the whole stream
    produces the same states AND the same per-step outputs, bit for bit,
    as N individual engine steps -- including through split feedback."""
    xs, ys = dense_stream
    cfg = VHTConfig(dataclasses.replace(TC, n_min=50))
    topo = build_vht_topology(cfg)

    eng = JitEngine()
    carry = eng.init(topo, jax.random.PRNGKey(0))
    outs = []
    for i in range(xs.shape[0]):
        carry, out = eng.step(topo, carry, {"x": xs[i], "y": ys[i]})
        outs.append(out)
    stacked = stack_outputs(outs)

    eng2 = JitEngine()
    carry2 = eng2.init(topo, jax.random.PRNGKey(0))
    carry2, souts = eng2.run_stream(topo, carry2, {"x": xs, "y": ys})

    # the feedback loop must actually have fired for this to mean anything
    assert int(carry2["states"]["model-aggregator"]["n_nodes"]) > 1
    _assert_trees_identical(carry, carry2)
    _assert_trees_identical(stacked, souts)


def test_jit_engine_run_stream_accepts_payload_list(dense_stream):
    xs, ys = dense_stream
    cfg = VHTConfig(TC)
    topo = build_vht_topology(cfg)
    eng = JitEngine()
    carry = eng.init(topo, jax.random.PRNGKey(0))
    payload_list = [{"x": xs[i], "y": ys[i]} for i in range(4)]
    carry, outs = eng.run_stream(topo, carry, payload_list)
    assert outs["prediction"]["pred"].shape == (4, ys.shape[1])


def test_local_engine_run_stream_reference_loop(dense_stream):
    """LocalEngine keeps eager per-step semantics: a list of outputs."""
    xs, ys = dense_stream
    cfg = VHTConfig(TC)
    topo = build_vht_topology(cfg)
    eng = LocalEngine()
    states = eng.init(topo, jax.random.PRNGKey(0))
    states, outs = eng.run_stream(topo, states,
                                  {"x": xs[:3], "y": ys[:3]})
    assert isinstance(outs, list) and len(outs) == 3
    assert outs[0]["prediction"]["pred"].shape == ys[0].shape


def test_vht_scan_run_bit_identical_to_step_loop(dense_stream):
    """The monolithic learner's lax.scan run equals the jitted step loop."""
    xs, ys = dense_stream
    vht = VHT(VHTConfig(dataclasses.replace(TC, split_delay=4)))
    st = vht.init()
    step = jax.jit(vht.step)
    ms = []
    for i in range(xs.shape[0]):
        st, m = step(st, xs[i], ys[i])
        ms.append(m)
    ms = stack_outputs(ms)
    st2, ms2 = jax.jit(vht.run)(vht.init(), xs, ys)
    _assert_trees_identical(st, st2)
    _assert_trees_identical(ms, ms2)


# ------------------------- segment stats == one-hot reference --------------

@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 1e-1),
                                        (jnp.float16, 1e-2)])
def test_segment_stats_matches_onehot_ref(dtype, atol):
    """Parity of the new segment-sum path vs the legacy dense one-hot
    reference, across dtypes and fractional/zero weights."""
    N, m, nb, C, B = 32, 17, 8, 3, 64
    key = jax.random.PRNGKey(7)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    stats = (jax.random.uniform(k1, (N, m, nb, C)) * 5).astype(dtype)
    leaf = jax.random.randint(k2, (B,), 0, N)
    xbin = jax.random.randint(k3, (B, m), 0, nb)
    y = jax.random.randint(k4, (B,), 0, C)
    w = jnp.where(jnp.arange(B) % 4 == 0, 0.0,
                  0.5 + jnp.arange(B) / B)           # zero + fractional
    out = stats_update_segment(stats, leaf, xbin, y, w)
    ref = stats_update_ref(stats.astype(jnp.float32), leaf, xbin, y, w)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=atol)


def test_auto_impl_off_tpu_is_segment():
    """On this container (CPU) the auto dispatch must take the segment
    path and agree exactly with the reference."""
    N, m, nb, C, B = 16, 9, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    stats = jnp.zeros((N, m, nb, C))
    leaf = jax.random.randint(ks[0], (B,), 0, N)
    xbin = jax.random.randint(ks[1], (B, m), 0, nb)
    y = jax.random.randint(ks[2], (B,), 0, C)
    w = jax.random.uniform(ks[3], (B,))
    out = stats_update(stats, leaf, xbin, y, w)      # impl="auto"
    ref = stats_update_ref(stats, leaf, xbin, y, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ------------------------- gated split checks are exact --------------------

@pytest.mark.parametrize("delay,buf", [(0, 0), (4, 0), (2, 64)])
def test_gated_split_checks_bit_identical_to_ungated(dense_stream,
                                                     delay, buf):
    """lax.cond gating (including the gather tile and its overflow
    fallback) must not change a single bit of the learned tree."""
    xs, ys = dense_stream
    tc = dataclasses.replace(TC, split_delay=delay, buffer_size=buf)
    gated = VHT(VHTConfig(tc))
    plain = VHT(VHTConfig(dataclasses.replace(tc, gate_splits=False)))
    s1, m1 = jax.jit(gated.run)(gated.init(), xs, ys)
    s0, m0 = jax.jit(plain.run)(plain.init(), xs, ys)
    assert int(s1["n_splits"]) > 0                  # checks actually fired
    _assert_trees_identical(s1, s0)
    _assert_trees_identical(m1, m0)


def test_gated_check_tile_overflow_fallback(dense_stream):
    """check_tile=1 forces the full-reduction fallback whenever more than
    one leaf is due -- still bit-identical."""
    xs, ys = dense_stream
    tc = dataclasses.replace(TC, check_tile=1)
    tiny = VHT(VHTConfig(tc))
    plain = VHT(VHTConfig(dataclasses.replace(tc, gate_splits=False)))
    s1, _ = jax.jit(tiny.run)(tiny.init(), xs, ys)
    s0, _ = jax.jit(plain.run)(plain.init(), xs, ys)
    _assert_trees_identical(s1, s0)


# ------------------------- rule stats == one-hot reference -----------------

@pytest.mark.parametrize("impl", ["segment", "pallas"])
@pytest.mark.parametrize("R", [1, 16])
def test_rule_stats_matches_onehot_ref(impl, R):
    """Parity of the kernelized weighted-moments scatter (segment and
    Pallas-interpret) vs the legacy dense one-hot oracle, including the
    seg == R discard row and the R == 1 default-rule fast path."""
    m, nb, B = 11, 8, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    stats = jax.random.uniform(ks[0], (R, m, nb, 3)) * 5
    seg = jax.random.randint(ks[1], (B,), 0, R + 1)     # R = discard
    xbin = jax.random.randint(ks[2], (B, m), 0, nb)
    mom = rule_moments(jax.random.uniform(ks[3], (B,)) * 2 - 1)
    out = rule_stats_update(stats, seg, xbin, mom, impl=impl,
                            interpret=True)
    ref = rule_stats_ref(stats, seg, xbin, mom)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="auto resolves to the Pallas kernel on TPU")
def test_rule_stats_auto_impl_off_tpu_is_segment():
    R, m, nb, B = 8, 5, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    stats = jnp.zeros((R, m, nb, 3))
    seg = jax.random.randint(ks[0], (B,), 0, R + 1)
    xbin = jax.random.randint(ks[1], (B, m), 0, nb)
    mom = rule_moments(jax.random.uniform(ks[2], (B,)))
    out = rule_stats_update(stats, seg, xbin, mom)      # impl="auto"
    seg_out = rule_stats_update_segment(stats, seg, xbin, mom)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seg_out))


@pytest.fixture(scope="module")
def reg_stream():
    gen = ElectricityLikeGenerator()
    key = jax.random.PRNGKey(1)
    xs, ys = [], []
    for _ in range(25):
        key, k = jax.random.split(key)
        x, y = gen.sample(k, 256)
        xs.append(bin_numeric(x, 8))
        ys.append(y.astype(jnp.float32))
    return jnp.stack(xs), jnp.stack(ys)


def _amrules_variants():
    return [("MAMR", AMRules), ("VAMR", VAMR),
            ("HAMR-2", lambda rc: HAMR(rc, replicas=2))]


@pytest.mark.parametrize("name,mk", _amrules_variants())
def test_amrules_scanned_bit_identical_to_step_loop(reg_stream, name, mk):
    """The fused lax.scan run of every AMRules variant equals the jitted
    per-step loop bit for bit -- state and metrics."""
    xs, ys = reg_stream
    learner = mk(RC)
    st = learner.init()
    step = jax.jit(learner.step)
    ms = []
    for i in range(xs.shape[0]):
        st, m = step(st, xs[i], ys[i])
        ms.append(m)
    ms = stack_outputs(ms)
    st2, ms2 = jax.jit(learner.run)(learner.init(), xs, ys)
    _assert_trees_identical(st, st2)
    _assert_trees_identical(ms, ms2)


@pytest.mark.parametrize("name,mk", _amrules_variants())
def test_amrules_gated_expansions_bit_identical_to_ungated(reg_stream,
                                                           name, mk):
    """lax.cond-gating the SDR expansion checks on the grace period must
    not change a single bit of the learned rule set."""
    xs, ys = reg_stream
    gated = mk(RC)
    plain = mk(dataclasses.replace(RC, gate_expansions=False))
    s1, m1 = jax.jit(gated.run)(gated.init(), xs, ys)
    s0, m0 = jax.jit(plain.run)(plain.init(), xs, ys)
    assert int(s1["n_created"]) > 0              # expansions actually fired
    _assert_trees_identical(s1, s0)
    _assert_trees_identical(m1, m0)


def test_amrules_segment_stats_match_onehot_oracle(reg_stream):
    """With expansions out of the picture (huge n_min) the kernelized
    statistics path accumulates the same moments as the legacy dense
    one-hot formulation."""
    xs, ys = reg_stream
    rc = dataclasses.replace(RC, n_min=10**9)
    seg = AMRules(rc)
    one = AMRules(dataclasses.replace(rc, stats_impl="onehot"))
    s1, _ = jax.jit(seg.run)(seg.init(), xs[:5], ys[:5])
    s0, _ = jax.jit(one.run)(one.init(), xs[:5], ys[:5])
    np.testing.assert_allclose(np.asarray(s1["stats"]),
                               np.asarray(s0["stats"]), rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s1["d_stats"]),
                               np.asarray(s0["d_stats"]), rtol=1e-5, atol=1e-3)


# ------------------------- ensemble gating ---------------------------------

@pytest.fixture(scope="module")
def cls_stream():
    gen = RandomTreeGenerator(n_cat=5, n_num=5, depth=4, seed=5)
    key = jax.random.PRNGKey(0)
    xs, ys = [], []
    for _ in range(20):
        key, k = jax.random.split(key)
        x, y = gen.sample(k, 128)
        xs.append(bin_numeric(x, 8))
        ys.append(y)
    return jnp.stack(xs), jnp.stack(ys)


ETC = TreeConfig(n_attrs=10, n_bins=8, n_classes=2, max_nodes=63, n_min=64)


def test_ensemble_scanned_bit_identical_to_step_loop(cls_stream):
    xs, ys = cls_stream
    ens = OzaEnsemble(EnsembleConfig(tree=ETC, n_members=4))
    st = ens.init(jax.random.PRNGKey(0))
    step = jax.jit(ens.step)
    for i in range(xs.shape[0]):
        st, _ = step(st, xs[i], ys[i])
    st2, _ = jax.jit(ens.run)(ens.init(jax.random.PRNGKey(0)), xs, ys)
    _assert_trees_identical(st, st2)


@pytest.mark.parametrize("check", ["pool", "member"])
def test_ensemble_gated_members_bit_identical_to_ungated(cls_stream, check):
    """Gating the member split machinery -- whether through the flattened
    [M*N]-pool gather tile or the shard-friendly per-member any-due gate
    -- must not change a single bit of any member tree."""
    xs, ys = cls_stream
    ec = EnsembleConfig(tree=ETC, n_members=4, split_check=check)
    gated = OzaEnsemble(ec)
    plain = OzaEnsemble(dataclasses.replace(ec, gate_members=False))
    s1, _ = jax.jit(gated.run)(gated.init(jax.random.PRNGKey(0)), xs, ys)
    s0, _ = jax.jit(plain.run)(plain.init(jax.random.PRNGKey(0)), xs, ys)
    assert int(s1["trees"]["n_splits"].sum()) > 0   # splits actually fired
    _assert_trees_identical(s1, s0)


def test_ensemble_pool_tile_overflow_fallback(cls_stream):
    """check_tile=1 forces the pooled gather tile to overflow into the
    full per-member reduction whenever more than one leaf is due across
    the whole member pool -- still bit-identical."""
    xs, ys = cls_stream
    tc1 = dataclasses.replace(ETC, check_tile=1)
    tiny = OzaEnsemble(EnsembleConfig(tree=tc1, n_members=4))
    plain = OzaEnsemble(EnsembleConfig(tree=ETC, n_members=4,
                                       gate_members=False))
    s1, _ = jax.jit(tiny.run)(tiny.init(jax.random.PRNGKey(0)), xs, ys)
    s0, _ = jax.jit(plain.run)(plain.init(jax.random.PRNGKey(0)), xs, ys)
    _assert_trees_identical(s1, s0)


# ------------------------- batched multi-tree router -----------------------

def _random_tables(key, M, N, m, nb):
    ks = jax.random.split(key, 4)
    sa = jax.random.randint(ks[0], (M, N), -1, m)
    sb = jax.random.randint(ks[1], (M, N), 0, nb)
    ch = jax.random.randint(ks[2], (M, N, 2), 0, N)
    xb = jax.random.randint(ks[3], (64, m), 0, nb)
    return sa, sb, ch, xb


@pytest.mark.parametrize("impl", ["gather", "pallas"])
@pytest.mark.parametrize("M", [1, 7])
def test_tree_route_matches_fori_oracle(impl, M):
    """The batched router (flat gathers and the Pallas one-hot matmul
    program in interpret mode) returns bit-identical leaf ids to the
    legacy per-member fori_loop, including the M == 1 fast path."""
    sa, sb, ch, xb = _random_tables(jax.random.PRNGKey(3), M, 31, 12, 8)
    ref = tree_route(sa, sb, ch, xb, max_depth=10, impl="fori")
    out = tree_route(sa, sb, ch, xb, max_depth=10, impl=impl,
                     interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_tree_route_single_tree_entry_matches_member_zero():
    """Rank-1 tables (htree.route's entry) give exactly member 0's row."""
    sa, sb, ch, xb = _random_tables(jax.random.PRNGKey(5), 3, 31, 12, 8)
    full = tree_route(sa, sb, ch, xb, max_depth=10, impl="gather")
    one = tree_route(sa[0], sb[0], ch[0], xb, max_depth=10, impl="gather")
    assert one.shape == (xb.shape[0],)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(full[0]))


def test_tree_route_on_learned_tree_matches_legacy_route(dense_stream):
    """On a REAL learned tree (not random tables) the dispatched
    htree.route equals the legacy fori formulation."""
    from repro.ml.htree import route
    xs, ys = dense_stream
    tc = dataclasses.replace(TC, n_min=50)
    vht = VHT(VHTConfig(tc))
    st, _ = jax.jit(vht.run)(vht.init(), xs[:20], ys[:20])
    tree = {k: st[k] for k in ("split_attr", "split_bin", "children")}
    got = route(st, xs[0], tc)
    ref = tree_route_ref(tree["split_attr"][None], tree["split_bin"][None],
                         tree["children"][None], xs[0], tc.max_depth)[0]
    assert int(st["n_nodes"]) > 1          # the tree actually grew
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_ensemble_route_impls_bit_identical(cls_stream):
    """The scanned ensemble stream under the batched gather router equals
    the legacy fori router bit for bit -- trees, detectors, and key."""
    xs, ys = cls_stream
    ec = EnsembleConfig(tree=ETC, n_members=4)
    fast = OzaEnsemble(ec)                              # auto -> gather here
    slow = OzaEnsemble(dataclasses.replace(ec, route_impl="fori"))
    s1, _ = jax.jit(fast.run)(fast.init(jax.random.PRNGKey(0)), xs, ys)
    s0, _ = jax.jit(slow.run)(slow.init(jax.random.PRNGKey(0)), xs, ys)
    assert int(s1["trees"]["n_splits"].sum()) > 0
    _assert_trees_identical(s1, s0)


# ------------------------- packed detector bank ----------------------------

@pytest.mark.parametrize("det", ["adwin", "ddm", "eddm", "ph"])
def test_ensemble_detector_bank_bit_identical_to_vmap(cls_stream, det):
    """The packed DetectorBank pass equals the legacy vmap-of-scalars
    detector path over a whole scanned stream, for every family."""
    xs, ys = cls_stream
    ec = EnsembleConfig(tree=ETC, n_members=4, detector=det)
    bank = OzaEnsemble(ec)
    vmapped = OzaEnsemble(dataclasses.replace(ec, detector_impl="vmap"))
    s1, m1 = jax.jit(bank.run)(bank.init(jax.random.PRNGKey(0)), xs, ys)
    s0, m0 = jax.jit(vmapped.run)(vmapped.init(jax.random.PRNGKey(0)),
                                  xs, ys)
    _assert_trees_identical(s1, s0)
    _assert_trees_identical(m1, m0)


@pytest.mark.parametrize("name,mk", _amrules_variants())
def test_amrules_detector_bank_bit_identical_to_inline(reg_stream, name, mk):
    """The per-rule Page-Hinkley rewired through the ph_ema DetectorBank
    equals the legacy inline formulation bit for bit, on a config whose
    tight threshold makes evictions actually fire."""
    xs, ys = reg_stream
    rc = dataclasses.replace(RC, ph_lambda=0.15)
    bank = mk(rc)
    inline = mk(dataclasses.replace(rc, detector_impl="inline"))
    s1, m1 = jax.jit(bank.run)(bank.init(), xs, ys)
    s0, m0 = jax.jit(inline.run)(inline.init(), xs, ys)
    if name == "MAMR":                    # HAMR/VAMR never evict in-step
        assert int(s1["n_removed"]) > 0   # drift eviction actually fired
    _assert_trees_identical(s1, s0)
    _assert_trees_identical(m1, m0)


# ------------------------- clustream ---------------------------------------

@pytest.fixture(scope="module")
def blob_stream():
    key = jax.random.PRNGKey(0)
    centers = jnp.stack([jnp.full((8,), v) for v in (0.2, 0.5, 0.8)])
    xs = []
    for _ in range(15):
        key, k1, k2 = jax.random.split(key, 3)
        c = jax.random.randint(k1, (128,), 0, 3)
        xs.append(centers[c] + 0.03 * jax.random.normal(k2, (128, 8)))
    return jnp.stack(xs)


CC = CluStreamConfig(n_dims=8, n_micro=32, n_macro=3, period=512)


def test_clustream_scanned_bit_identical_to_step_loop(blob_stream):
    """The scanned CluStream run (with its period-gated macro phase)
    equals the eager per-batch step loop bit for bit."""
    cs = CluStream(CC)
    st, ms = jax.jit(cs.run)(cs.init(), blob_stream)
    st2 = cs.init()
    step = jax.jit(cs.step)
    for i in range(blob_stream.shape[0]):
        st2, _ = step(st2, blob_stream[i])
    _assert_trees_identical(st, st2)
    # the macro phase fired at least once (period < stream length)
    assert float(st["t"]) > CC.period


def test_clustream_cf_scatter_segment_matches_onehot(blob_stream):
    """Given identical assignments, the segment-sum CF scatter equals the
    legacy one-hot matmul formulation (including the discard row K)."""
    st = clustream.init_clustream(CC, jax.random.PRNGKey(1))
    x = blob_stream[0]
    seg = jax.random.randint(jax.random.PRNGKey(2), (x.shape[0],), 0,
                             CC.n_micro + 1)
    t = jnp.arange(1, x.shape[0] + 1, dtype=jnp.float32)
    a = clustream._cf_scatter(st, x, t, seg, CC)
    b = clustream._cf_scatter(
        st, x, t, seg, dataclasses.replace(CC, stats_impl="onehot"))
    for k in ("n", "ls", "ss", "lt", "st"):
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=1e-5, atol=1e-4, err_msg=k)


def test_clustream_matmul_distance_matches_broadcast(blob_stream):
    x = blob_stream[0]
    c = blob_stream[1][:10]
    d_mat = clustream.pairwise_d2(x, c)
    d_ref = clustream.pairwise_d2(x, c, impl="onehot")
    np.testing.assert_allclose(np.asarray(d_mat), np.asarray(d_ref),
                               rtol=1e-4, atol=1e-5)


def test_clustream_merge_sums_scalar_clock():
    """The distributed merge must not silently take shard 0's clock, and
    must not sum the non-additive macro centroids of learner states."""
    cs = CluStream(CC)
    s1 = dict(cs.init(jax.random.PRNGKey(0)))
    s2 = dict(cs.init(jax.random.PRNGKey(1)))
    s1["t"] = jnp.asarray(100.0)
    s2["t"] = jnp.asarray(40.0)
    merged = clustream.merge([s1, s2])
    assert float(merged["t"]) == 140.0
    np.testing.assert_allclose(np.asarray(merged["ls"]),
                               np.asarray(s1["ls"] + s2["ls"]))
    np.testing.assert_array_equal(np.asarray(merged["macro"]),
                                  np.asarray(s1["macro"]))


# ------------------------- engines on bare learners ------------------------

def test_jit_engine_scans_bare_learner_stream(reg_stream):
    """run_stream accepts a plain learner (no hand-wired topology) and its
    scanned execution equals the eager jitted step loop bit for bit."""
    xs, ys = reg_stream
    amr = AMRules(RC)
    eng = JitEngine()
    carry = eng.init(amr, jax.random.PRNGKey(0))
    carry, outs = eng.run_stream(amr, carry, {"x": xs, "y": ys})

    st = amr.init()
    step = jax.jit(amr.step)
    ms = []
    for i in range(xs.shape[0]):
        st, m = step(st, xs[i], ys[i])
        ms.append(m)
    ms = stack_outputs(ms)
    _assert_trees_identical(carry["states"]["amrules"], st)
    _assert_trees_identical(outs["metrics"], ms)


def test_local_engine_runs_bare_learner(reg_stream):
    xs, ys = reg_stream
    amr = AMRules(RC)
    eng = LocalEngine()
    states = eng.init(amr, jax.random.PRNGKey(0))
    states, outs = eng.run_stream(amr, states, {"x": xs[:3], "y": ys[:3]})
    assert isinstance(outs, list) and len(outs) == 3
    assert outs[0]["metrics"]["seen"] == ys.shape[1]


def test_shard_map_engine_shards_bare_learner_state(reg_stream):
    """ShardMapEngine.init must wrap a bare learner BEFORE sharding its
    state (regression: it used to hand the learner itself to
    _shard_states) and honour the learner's state_sharding hint.  The mesh
    puts every available device on 'model' (not a hard-coded (1, 1)), so
    under a forced multi-device session this exercises real partitioning;
    tests/test_multidevice.py forces exactly that."""
    from jax.sharding import PartitionSpec as P
    from repro.core.engines import ShardMapEngine
    from repro.launch.mesh import auto_mesh
    xs, ys = reg_stream
    n = jax.device_count()
    model = n if RC.max_rules % n == 0 else 1
    mesh = auto_mesh((model, n // model), ("model", "data"))
    vamr = VAMR(RC)
    eng = ShardMapEngine(mesh)
    carry = eng.init(vamr, jax.random.PRNGKey(0))
    stats = carry["states"]["vamr"]["stats"]
    assert stats.sharding.spec == P("model", None, None, None)
    assert {s.data.shape[0] for s in stats.addressable_shards} \
        == {RC.max_rules // model}
    carry, outs = eng.run_stream(vamr, carry, {"x": xs[:4], "y": ys[:4]})
    assert outs["metrics"]["seen"].shape == (4,)
    stats = carry["states"]["vamr"]["stats"]
    assert {s.data.shape[0] for s in stats.addressable_shards} \
        == {RC.max_rules // model}


def test_jit_engine_scans_clustream_without_labels(blob_stream):
    """Payloads without 'y' (clustering) flow through the learner adapter,
    and the scanned engine path equals the per-step engine path."""
    cs = CluStream(CC)
    eng = JitEngine()
    carry = eng.init(cs, jax.random.PRNGKey(0))
    carry, outs = eng.run_stream(cs, carry, {"x": blob_stream})
    assert outs["metrics"]["ssq"].shape == (blob_stream.shape[0],)
    eng2 = JitEngine()
    carry2 = eng2.init(cs, jax.random.PRNGKey(0))
    for i in range(blob_stream.shape[0]):
        carry2, _ = eng2.step(cs, carry2, {"x": blob_stream[i]})
    _assert_trees_identical(carry["states"], carry2["states"])


# ------------------------- wk(z) drop accounting ---------------------------

def test_wkz_reports_zero_dropped_wok_reports_shed():
    """wk(z) buffers pending-leaf instances but still trains on them, so
    none are dropped; wok sheds them and must say so."""
    B = 64
    xbin = jnp.zeros((B, TC.n_attrs), jnp.int32)
    y = jnp.zeros((B,), jnp.int32)
    for delay, buf, want in [(3, 16, 0.0), (3, 0, float(B))]:
        tc = dataclasses.replace(TC, split_delay=delay, buffer_size=buf)
        vht = VHT(VHTConfig(tc))
        state = vht.init()
        # root has a pending split decision in flight
        state["pending"] = state["pending"].at[0].set(True)
        state["pending_timer"] = state["pending_timer"].at[0].set(5)
        _, metrics = jax.jit(vht.step)(state, xbin, y)
        assert float(metrics["dropped"]) == want
