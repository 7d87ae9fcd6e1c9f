"""Sharded learner execution end-to-end on a REAL multi-device mesh.

Everything else in the suite runs ShardMapEngine on a (1, 1) mesh, where
GSPMD partitioning is vacuous.  This module proves the sharding story on 8
virtual devices: state is actually placed per-shard (Array.sharding),
sharded scans are bit-identical to the single-device scans for VAMR
(rules axis over 'model'), OzaBag (member axis over 'data'), and CluStream
(micro-cluster axis over 'model'), and the distributed CluStream merge
round-trips under uneven shard loads.

Two modes:

  * >= 8 devices already visible (the CI `multidevice` job exports
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``): the suite
    runs inline in this process.
  * fewer devices (the plain tier-1 session -- XLA initialized its single
    CPU device long before this module imports, and the flag is read only
    once per process): one umbrella test re-runs this file under pytest in
    a subprocess with the flag forced, so the tier-1 command still covers
    the whole suite.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

N_DEVICES = 8
MULTI = jax.device_count() >= N_DEVICES


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


if not MULTI:

    def test_suite_on_8_forced_host_devices():
        """Re-run this module with 8 forced host devices in a subprocess
        (the flag must be set before the child's first jax init)."""
        from repro.launch.mesh import force_host_devices
        root = _repo_root()
        env = dict(os.environ)
        force_host_devices(N_DEVICES, env)   # replaces any smaller count
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (os.path.join(root, "src"),
                        env.get("PYTHONPATH", "")) if p)
        r = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             os.path.abspath(__file__)],
            env=env, cwd=root, capture_output=True, text=True, timeout=1500)
        if r.returncode != 0:
            raise AssertionError(
                f"multidevice suite failed (rc={r.returncode}):\n"
                f"{r.stdout}\n{r.stderr}")

else:

    from repro.core.engines import JitEngine, ShardMapEngine
    from repro.data.generators import (ElectricityLikeGenerator,
                                       RandomTreeGenerator, bin_numeric)
    from repro.launch.mesh import make_stream_mesh
    from repro.ml import clustream
    from repro.ml.amrules import RulesConfig, VAMR
    from repro.ml.clustream import CluStream, CluStreamConfig
    from repro.ml.ensemble import EnsembleConfig, OzaEnsemble
    from repro.ml.htree import TreeConfig

    RC = RulesConfig(n_attrs=12, n_bins=8, max_rules=32, n_min=150)
    ETC = TreeConfig(n_attrs=10, n_bins=8, n_classes=2, max_nodes=63,
                     n_min=64)
    CC = CluStreamConfig(n_dims=8, n_micro=32, n_macro=3, period=512)

    def _assert_trees_identical(a, b):
        la = jax.tree_util.tree_flatten_with_path(a)[0]
        lb = jax.tree.leaves(b)
        assert len(la) == len(lb)
        for (path, x), y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=str(path))

    def _assert_partitioned(arr, axis_size, n_rows):
        """The array really lives as per-device shards of the leading
        axis: every device holds 1/axis_size of the rows."""
        assert len(arr.sharding.device_set) == jax.device_count()
        shard_rows = {s.data.shape[0] for s in arr.addressable_shards}
        assert shard_rows == {n_rows // axis_size}, (
            f"expected {n_rows // axis_size}-row shards, got {shard_rows}")

    @pytest.fixture(scope="module")
    def reg_stream():
        gen = ElectricityLikeGenerator()
        key = jax.random.PRNGKey(1)
        xs, ys = [], []
        for _ in range(14):
            key, k = jax.random.split(key)
            x, y = gen.sample(k, 256)
            xs.append(bin_numeric(x, 8))
            ys.append(y.astype(jnp.float32))
        return jnp.stack(xs), jnp.stack(ys)

    @pytest.fixture(scope="module")
    def cls_stream():
        gen = RandomTreeGenerator(n_cat=5, n_num=5, depth=4, seed=5)
        key = jax.random.PRNGKey(0)
        xs, ys = [], []
        for _ in range(6):
            key, k = jax.random.split(key)
            x, y = gen.sample(k, 128)
            xs.append(bin_numeric(x, 8))
            ys.append(y)
        return jnp.stack(xs), jnp.stack(ys)

    @pytest.fixture(scope="module")
    def blob_stream():
        key = jax.random.PRNGKey(0)
        centers = jnp.stack([jnp.full((8,), v) for v in (0.2, 0.5, 0.8)])
        xs = []
        for _ in range(8):
            key, k1, k2 = jax.random.split(key, 3)
            c = jax.random.randint(k1, (128,), 0, 3)
            xs.append(centers[c] + 0.03 * jax.random.normal(k2, (128, 8)))
        return jnp.stack(xs)

    # ----------------------------------------------------------- VAMR

    def test_vamr_sharded_bit_identical_and_partitioned(reg_stream):
        """Rules axis over 'model' on all 8 devices: per-rule state is
        physically partitioned (before AND after the scanned run) and the
        sharded stream is bit-identical to the single-device scan."""
        xs, ys = reg_stream
        vamr = VAMR(RC)
        mesh = make_stream_mesh("model")
        n = mesh.shape["model"]

        base = JitEngine()
        c0 = base.init(vamr, jax.random.PRNGKey(0))
        c0, o0 = base.run_stream(vamr, c0, {"x": xs, "y": ys})

        eng = ShardMapEngine(mesh)
        carry = eng.init(vamr, jax.random.PRNGKey(0))
        st = carry["states"]["vamr"]
        assert st["stats"].sharding.spec == P("model", None, None, None)
        _assert_partitioned(st["stats"], n, RC.max_rules)
        _assert_partitioned(st["head_n"], n, RC.max_rules)

        carry, outs = eng.run_stream(vamr, carry, {"x": xs, "y": ys})
        st = carry["states"]["vamr"]
        _assert_partitioned(st["stats"], n, RC.max_rules)
        _assert_partitioned(st["ph_m"], n, RC.max_rules)
        assert int(st["n_created"]) > 0          # rules were actually built
        _assert_trees_identical(c0["states"], carry["states"])
        _assert_trees_identical(o0, outs)

    # --------------------------------------------------------- OzaBag

    def test_ozabag_sharded_bit_identical_and_partitioned(cls_stream):
        """Member axis over 'data': each device trains one member, the
        vote/detector path crosses shards, and the result is bit-identical
        to the single-device scan."""
        xs, ys = cls_stream
        ens = OzaEnsemble(EnsembleConfig(tree=ETC, n_members=N_DEVICES))
        mesh = make_stream_mesh("data")
        n = mesh.shape["data"]

        base = JitEngine()
        c0 = base.init(ens, jax.random.PRNGKey(0))
        c0, o0 = base.run_stream(ens, c0, {"x": xs, "y": ys})

        eng = ShardMapEngine(mesh)
        carry = eng.init(ens, jax.random.PRNGKey(0))
        trees = carry["states"]["ozaensemble"]["trees"]
        _assert_partitioned(trees["stats"], n, N_DEVICES)
        _assert_partitioned(carry["states"]["ozaensemble"]["det"]["cnt"],
                            n, N_DEVICES)

        carry, outs = eng.run_stream(ens, carry, {"x": xs, "y": ys})
        trees = carry["states"]["ozaensemble"]["trees"]
        _assert_partitioned(trees["stats"], n, N_DEVICES)
        assert int(trees["n_splits"].sum()) > 0   # members actually grew
        _assert_trees_identical(c0["states"], carry["states"])
        _assert_trees_identical(o0, outs)

    # ------------------------------------------------------ CluStream

    def test_clustream_sharded_bit_identical_and_partitioned(blob_stream):
        """Micro-cluster axis over 'model', macro k-means firing on period
        boundaries mid-stream: CF state is partitioned and the sharded
        scan (including the replicated macro centroids) is bit-identical
        to the single-device scan."""
        cs = CluStream(CC)
        mesh = make_stream_mesh("model")
        n = mesh.shape["model"]

        base = JitEngine()
        c0 = base.init(cs, jax.random.PRNGKey(0))
        c0, o0 = base.run_stream(cs, c0, {"x": blob_stream})

        eng = ShardMapEngine(mesh)
        carry = eng.init(cs, jax.random.PRNGKey(0))
        _assert_partitioned(carry["states"]["clustream"]["ls"], n, CC.n_micro)

        carry, outs = eng.run_stream(cs, carry, {"x": blob_stream})
        st = carry["states"]["clustream"]
        _assert_partitioned(st["ls"], n, CC.n_micro)
        _assert_partitioned(st["n"], n, CC.n_micro)
        # the period-gated macro phase fired inside the sharded scan
        assert float(st["t"]) > CC.period
        _assert_trees_identical(c0["states"], carry["states"])
        _assert_trees_identical(o0, outs)

    # ------------------------------------- chunked runtime under the mesh

    def _chunked_sharded_parity(learner, payload, state_key, leaf_names,
                                n_rows, *, chunk_len, mesh_axis):
        """Chunked sharded run == monolithic single-device run bit for
        bit, with the carry asserted physically partitioned at EVERY
        chunk boundary (not just before/after the stream)."""
        mesh = make_stream_mesh(mesh_axis)
        n = mesh.shape[mesh_axis]

        base = JitEngine()
        c0 = base.init(learner, jax.random.PRNGKey(0))
        c0, o0 = base.run_stream(learner, c0, payload)

        eng = ShardMapEngine(mesh)
        carry = eng.init(learner, jax.random.PRNGKey(0))
        boundaries = []

        def on_chunk(outs, chunk, carry):
            for path in leaf_names:
                leaf = carry["states"][state_key]
                for k in path:
                    leaf = leaf[k]
                _assert_partitioned(leaf, n, n_rows)
            boundaries.append(chunk.index)

        carry, outs = eng.run_stream(learner, carry, payload,
                                     chunk_len=chunk_len, on_chunk=on_chunk)
        n_steps = jax.tree.leaves(payload)[0].shape[0]
        assert boundaries == list(range(-(-n_steps // chunk_len)))
        assert n_steps % chunk_len != 0      # the padded tail ran masked
        _assert_trees_identical(c0["states"], carry["states"])
        _assert_trees_identical(o0, outs)
        return carry

    def test_vamr_chunked_sharded_bit_identical(reg_stream):
        """Rules axis over 'model', driven chunk by chunk (padded tail
        included): per-rule state stays partitioned across every chunk
        boundary and the result equals the monolithic single-device
        scan."""
        xs, ys = reg_stream
        carry = _chunked_sharded_parity(
            VAMR(RC), {"x": xs, "y": ys}, "vamr", (("stats",), ("ph_m",)),
            RC.max_rules, chunk_len=4, mesh_axis="model")
        assert int(carry["states"]["vamr"]["n_created"]) > 0

    def test_ozabag_chunked_sharded_bit_identical(cls_stream):
        """Member axis over 'data', chunked: one tree per device across
        chunk boundaries, bit-identical to the monolithic scan."""
        xs, ys = cls_stream
        ens = OzaEnsemble(EnsembleConfig(tree=ETC, n_members=N_DEVICES))
        _chunked_sharded_parity(
            ens, {"x": xs, "y": ys}, "ozaensemble", (("trees", "stats"),),
            N_DEVICES, chunk_len=4, mesh_axis="data")

    def test_clustream_chunked_sharded_bit_identical(blob_stream):
        """Micro-cluster axis over 'model', chunked, with the in-step
        macro phase firing mid-stream: CF state stays partitioned across
        chunk boundaries and matches the single-device monolithic scan."""
        carry = _chunked_sharded_parity(
            CluStream(CC), {"x": blob_stream}, "clustream",
            (("ls",), ("n",)), CC.n_micro, chunk_len=3, mesh_axis="model")
        assert float(carry["states"]["clustream"]["t"]) > CC.period

    def test_clustream_boundary_mode_sharded_matches_unsharded(blob_stream):
        """The chunk-boundary macro hoist under the mesh: the boundary
        hook's k-means (inputs gathered to replicated) leaves the carry
        partitioned and the sharded chunked run equals the single-device
        chunked run bit for bit."""
        import dataclasses
        cc = dataclasses.replace(CC, period=3 * 128,
                                 macro_impl="boundary")
        cs = CluStream(cc)
        payload = {"x": blob_stream}
        mesh = make_stream_mesh("model")
        n = mesh.shape["model"]

        base = JitEngine()
        c0 = base.init(cs, jax.random.PRNGKey(0))
        c0, o0 = base.run_stream(cs, c0, payload, chunk_len=3)

        eng = ShardMapEngine(mesh)
        carry = eng.init(cs, jax.random.PRNGKey(0))
        carry, outs = eng.run_stream(
            cs, carry, payload, chunk_len=3,
            on_chunk=lambda _o, _c, cr: _assert_partitioned(
                cr["states"]["clustream"]["ls"], n, CC.n_micro))
        assert float(carry["states"]["clustream"]["macro_t"]) > 0
        _assert_trees_identical(c0["states"], carry["states"])
        _assert_trees_identical(o0, outs)

    # ------------------------------------------- merge under uneven load

    def test_clustream_merge_round_trips_under_uneven_shard_loads(
            blob_stream):
        """Shard-local CluStream states that absorbed very different
        stream volumes merge exactly: CF fields and the scalar clock are
        additive, a singleton merge is the identity, and merging is
        associative (so a tree of pairwise shard reductions equals the
        flat reduction)."""
        cs = CluStream(CC)
        run = jax.jit(cs.run)
        # uneven loads: 1, 2, and 5 batches on three "shards"
        s1, _ = run(cs.init(jax.random.PRNGKey(0)), blob_stream[:1])
        s2, _ = run(cs.init(jax.random.PRNGKey(1)), blob_stream[1:3])
        s3, _ = run(cs.init(jax.random.PRNGKey(2)), blob_stream[3:8])

        single = clustream.merge([s1])
        _assert_trees_identical(s1, single)

        merged = clustream.merge([s1, s2, s3])
        assert float(merged["t"]) == float(s1["t"] + s2["t"] + s3["t"])
        assert float(merged["t"]) == 8 * 128     # every instance counted
        for k in ("n", "ls", "ss", "lt", "st"):
            np.testing.assert_allclose(
                np.asarray(merged[k]),
                np.asarray(s1[k] + s2[k] + s3[k]), err_msg=k)
        np.testing.assert_array_equal(np.asarray(merged["macro"]),
                                      np.asarray(s1["macro"]))

        paired = clustream.merge([clustream.merge([s1, s2]), s3])
        _assert_trees_identical(merged, paired)

        # the merged CF state feeds the paper's post-reduction macro phase
        macro = clustream.macro_cluster(merged, CC)
        assert bool(jnp.isfinite(macro).all())
        assert macro.shape == (CC.n_macro, CC.n_dims)

    # ------------------------------- elastic re-place after host loss

    def test_elastic_vht_kill_resume_8_to_4_bit_identical(cls_stream,
                                                          tmp_path):
        """The ISSUE-6 acceptance path: a chunked VHT run on the full
        8-device mesh is killed at a chunk boundary, half the hosts are
        declared dead, and the resumed run lands on the survivor mesh
        proposed by the supervisor (8 -> 4 devices via ``propose_mesh`` +
        ``make_mesh_from_proposal`` + ``place_carry``) -- finishing with
        final metrics, curve, and carry bit-identical to the
        uninterrupted single-device run."""
        from repro.checkpoint.manager import CheckpointManager
        from repro.core.evaluation import ChunkedPrequentialEvaluation
        from repro.data.pipeline import ChunkedStream
        from repro.launch.mesh import make_mesh_from_proposal
        from repro.ml.vht import VHT, VHTConfig
        from repro.runtime import FaultInjector, SimulatedKill, Supervisor

        xs, ys = cls_stream
        vht = VHT(VHTConfig(ETC))
        payload = {"x": xs, "y": ys}

        ref = ChunkedPrequentialEvaluation(
            vht, ChunkedStream(payload, 2)).run(resume=False)
        assert int(ref.extra["carry"]["states"]["vht"]["n_nodes"]) > 1

        sup = Supervisor([f"h{i}" for i in range(N_DEVICES)],
                         dead_after=1e9)
        for h in list(sup.hosts):
            sup.heartbeat(h, step=-1)
        shape, axes = sup.propose_mesh(1, model_parallel=4)
        assert shape == (2, 4)
        mesh8 = make_mesh_from_proposal(shape, axes)
        mgr = CheckpointManager(tmp_path, keep=0, async_write=False)
        killed = ChunkedPrequentialEvaluation(
            vht, ChunkedStream(payload, 2), engine=ShardMapEngine(mesh8),
            checkpoint=mgr, checkpoint_every=1, supervisor=sup, host="h0",
            injector=FaultInjector(kill_at_chunk=1))
        with pytest.raises(SimulatedKill):
            killed.run(resume=False)
        assert mgr.latest_step() == 1     # chunk 1's work was lost

        for h in ("h4", "h5", "h6", "h7"):     # half the fleet is gone
            sup.declare_dead(h)
        shape, axes = sup.propose_mesh(1, model_parallel=4)
        assert shape == (1, 4)                 # survivor mesh: 4 devices
        mesh4 = make_mesh_from_proposal(shape, axes)
        assert mesh4.devices.size == 4

        resumed = ChunkedPrequentialEvaluation(
            vht, ChunkedStream(payload, 2), engine=ShardMapEngine(mesh4),
            checkpoint=CheckpointManager(tmp_path, keep=0,
                                         async_write=False))
        r = resumed.run(resume=True)
        assert r.metric == ref.metric and r.curve == ref.curve
        _assert_trees_identical(ref.extra["carry"], r.extra["carry"])

    def test_elastic_vamr_replace_keeps_state_partitioned(reg_stream,
                                                          tmp_path):
        """Same elastic path with genuinely PARTITIONED state: VAMR's
        per-rule axis lives sharded over 'model' on the 8-device mesh; the
        resumed run re-places it onto the 4-device survivor mesh through
        the checkpoint (logical arrays) + ``place_carry`` and the final
        state equals the single-device run while physically occupying only
        the 4 surviving devices."""
        from repro.checkpoint.manager import CheckpointManager
        from repro.core.evaluation import ChunkedPrequentialEvaluation
        from repro.data.pipeline import ChunkedStream
        from repro.launch.mesh import make_mesh_from_proposal
        from repro.ml.amrules import VAMR
        from repro.runtime import FaultInjector, SimulatedKill, Supervisor

        xs, ys = reg_stream
        vamr = VAMR(RC)
        payload = {"x": xs, "y": ys}

        ref = ChunkedPrequentialEvaluation(
            vamr, ChunkedStream(payload, 4)).run(resume=False)
        assert int(ref.extra["carry"]["states"]["vamr"]["n_created"]) > 0

        sup = Supervisor([f"h{i}" for i in range(N_DEVICES)],
                         dead_after=1e9)
        # all 8 devices on the model axis (VAMR's float statistics are
        # only reduction-order-stable along 'model'; a data axis > 1
        # would reassociate the per-batch sums)
        shape, axes = sup.propose_mesh(1, model_parallel=8)
        assert shape == (1, 8)
        mesh8 = make_mesh_from_proposal(shape, axes)
        mgr = CheckpointManager(tmp_path, keep=0, async_write=False)
        killed = ChunkedPrequentialEvaluation(
            vamr, ChunkedStream(payload, 4), engine=ShardMapEngine(mesh8),
            checkpoint=mgr, checkpoint_every=1,
            injector=FaultInjector(kill_at_chunk=2))
        with pytest.raises(SimulatedKill):
            killed.run(resume=False)

        for h in ("h4", "h5", "h6", "h7"):
            sup.declare_dead(h)
        # the survivors cannot sustain TP=8 -- the supervisor says so
        # loudly, and the operator re-proposes at TP=4 (the checkpoint is
        # mesh-independent, so the re-partition is just place_carry)
        with pytest.raises(RuntimeError, match="not enough chips"):
            sup.propose_mesh(1, model_parallel=8)
        mesh4 = make_mesh_from_proposal(*sup.propose_mesh(
            1, model_parallel=4))
        resumed = ChunkedPrequentialEvaluation(
            vamr, ChunkedStream(payload, 4), engine=ShardMapEngine(mesh4),
            checkpoint=CheckpointManager(tmp_path, keep=0,
                                         async_write=False))
        r = resumed.run(resume=True)
        assert r.metric == ref.metric and r.curve == ref.curve
        _assert_trees_identical(ref.extra["carry"], r.extra["carry"])
        stats = r.extra["carry"]["states"]["vamr"]["stats"]
        # per-rule state physically lives on ONLY the 4 survivor devices
        assert len(stats.sharding.device_set) == 4
        assert set(stats.sharding.device_set) <= set(mesh4.devices.flat)
        shard_rows = {s.data.shape[0] for s in stats.addressable_shards}
        assert shard_rows == {RC.max_rules // 4}

    # ------------------------------------------------------------ fleet

    @pytest.mark.parametrize("family", ["vht", "amrules"])
    def test_fleet_sharded_bit_identical_and_partitioned(family):
        """A LearnerFleet shards its TENANT axis over 'data': packed state
        physically lives one-tenant-per-device, and the sharded fleet run
        is bit-identical to the single-device fleet run.  The fleet mesh
        puts every device on 'data' (the tenant axis is the scale axis);
        each tenant's own reductions then stay device-local, which is what
        keeps AMRules' float statistics bit-stable -- the same reasoning
        that pins single-learner VAMR to the 'model' axis above."""
        from repro.data.pipeline import ChunkedStream
        from repro.ml.fleet import LearnerFleet, stack_payloads
        from repro.ml.vht import VHT, VHTConfig
        from repro.ml.amrules import AMRules

        F, T, BF, CL = N_DEVICES, 4, 32, 2
        learner = (VHT(VHTConfig(ETC)) if family == "vht"
                   else AMRules(RulesConfig(n_attrs=12, n_bins=8,
                                            max_rules=16, n_min=100)))
        fleet = LearnerFleet(learner, F)
        gen = RandomTreeGenerator(n_cat=6, n_num=6, depth=5, seed=3)

        def tenant_payload(f):
            key = jax.random.PRNGKey(100 + f)
            xs, ys = [], []
            for _ in range(T):
                key, k = jax.random.split(key)
                x, y = gen.sample(k, BF)
                xs.append(bin_numeric(x, 8))
                ys.append(y)
            xs, ys = jnp.stack(xs), jnp.stack(ys)
            if family == "vht":
                return {"x": xs[:, :, :ETC.n_attrs], "y": ys}
            return {"x": xs, "y": ys.astype(jnp.float32)}

        payload = stack_payloads([tenant_payload(f) for f in range(F)])
        stream = lambda: ChunkedStream(payload, CL, to_device=False)

        base = JitEngine()
        c0 = base.init(fleet, jax.random.PRNGKey(0))
        c0, o0 = base.run_stream_chunked(fleet, c0, stream())

        mesh = make_stream_mesh("data")
        eng = ShardMapEngine(mesh)
        carry = eng.init(fleet, jax.random.PRNGKey(0))
        packed = carry["states"]["learnerfleet"]
        lead = packed["tenant"]["stats"]
        _assert_partitioned(lead, N_DEVICES, F)       # one tenant/device
        _assert_partitioned(packed["cursor"], N_DEVICES, F)

        carry, outs = eng.run_stream_chunked(fleet, carry, stream())
        packed = carry["states"]["learnerfleet"]
        _assert_partitioned(packed["tenant"]["stats"], N_DEVICES, F)
        np.testing.assert_array_equal(np.asarray(packed["cursor"]),
                                      np.full((F,), T))
        _assert_trees_identical(c0["states"], carry["states"])
        _assert_trees_identical(o0, outs)

    @pytest.mark.parametrize("n_tenants", [N_DEVICES, 2 * N_DEVICES])
    def test_fleet_tenant_reductions_stay_process_local(n_tenants):
        """Under a process-spanning 'data' axis every tenant must land
        WHOLE on one device -- and therefore inside one process, for any
        process grouping that owns whole devices.  Mock a 2-process split
        of the 8-device mesh (first half / second half, the layout
        ``make_global_stream_mesh`` produces) and check, leaf by leaf of
        ``LearnerFleet.state_sharding()``, via devices_indices_map: the
        tenant axis splits on device boundaries only, non-tenant dims are
        never partitioned, so no per-tenant reduction (stats scatter,
        metric column, cursor bump) ever needs a cross-process
        collective."""
        from jax.sharding import NamedSharding
        from repro.ml.fleet import LearnerFleet
        from repro.ml.vht import VHT, VHTConfig

        fleet = LearnerFleet(VHT(VHTConfig(ETC)), n_tenants)
        mesh = make_stream_mesh("data")
        shapes = jax.eval_shape(fleet.init, jax.random.PRNGKey(0))
        specs = fleet.state_sharding()
        order = list(mesh.devices.flat)
        proc_of = {d: i // (N_DEVICES // 2) for i, d in enumerate(order)}

        leaves = zip(
            jax.tree.leaves(shapes),
            jax.tree.leaves(specs, is_leaf=lambda v: isinstance(v, P)))
        n_checked = 0
        for shape, spec in leaves:
            sh = NamedSharding(mesh, spec)
            tenant_proc = {}
            for dev, idx in sh.devices_indices_map(shape.shape).items():
                rows, trailing = idx[0], idx[1:]
                # non-tenant dims whole: a tenant's reduction never
                # straddles devices
                for dim, sl in zip(shape.shape[1:], trailing):
                    assert (sl.start or 0) == 0 and \
                        (sl.stop is None or sl.stop == dim), (spec, idx)
                for f in range(*rows.indices(shape.shape[0])):
                    tenant_proc.setdefault(f, set()).add(proc_of[dev])
            assert set(tenant_proc) == set(range(n_tenants))
            for f, procs in tenant_proc.items():
                assert len(procs) == 1, \
                    f"tenant {f} spans processes {procs} in {spec}"
            n_checked += 1
        assert n_checked >= 4    # stats/counters/clock/cursor at least

    def test_pallas_kernel_runs_replicated_under_a_mesh():
        """A Pallas call traced under a multi-device mesh runs inside a
        replicated shard_map (GSPMD cannot partition a Mosaic kernel): on
        attribute-sharded statistics it equals the one-hot oracle."""
        from jax.sharding import NamedSharding
        from repro.distributed.sharding import mesh_context
        from repro.kernels.vht_stats.ops import stats_update
        from repro.kernels.vht_stats.ref import stats_update_ref

        mesh = make_stream_mesh("model")
        ks = jax.random.split(jax.random.PRNGKey(4), 4)
        stats = jnp.floor(jax.random.uniform(ks[0], (31, 16, 8, 2)) * 9)
        leaf = jax.random.randint(ks[1], (64,), 0, 31)
        xbin = jax.random.randint(ks[2], (64, 16), 0, 8)
        y = jax.random.randint(ks[3], (64,), 0, 2)
        w = jnp.ones((64,))
        sharded = jax.device_put(
            stats, NamedSharding(mesh, P(None, "model", None, None)))

        def step(s):
            out = stats_update(s, leaf, xbin, y, w, impl="pallas",
                               interpret=True)
            return jax.lax.with_sharding_constraint(out, sharded.sharding)

        with mesh_context(mesh):
            out = jax.jit(step)(sharded)
        _assert_partitioned(jnp.moveaxis(out, 1, 0), N_DEVICES, 16)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(stats_update_ref(stats, leaf, xbin,
                                                         y, w)))

    def test_chip_smoke_vertical_phase_on_the_mesh():
        """chip_smoke.py's four-chip phase at a tiny size on the 8-device
        mesh: statistics split 8 ways, bit-identical to one device."""
        import importlib.util
        path = os.path.join(_repo_root(), "chip_smoke.py")
        spec = importlib.util.spec_from_file_location("chip_smoke", path)
        smoke = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = smoke
        spec.loader.exec_module(smoke)
        dep = smoke.Deployment(n_attrs=16, max_nodes=63, n_min=50,
                               delta=0.05, tau=0.1, batch=64, chunk_len=4,
                               n_chunks=4)
        lines = smoke.vertical_parallel(dep)
        assert "partitioned 8 ways, 2 attributes per device" in lines[0]
