"""Smoke run of the main path on a TPU: the paper's dense-1000 VHT task.

One chip (no arguments): prequential evaluation of a Vertical Hoeffding
Tree through ``ChunkedPrequentialEvaluation`` on ``JitEngine`` with the
default pipelined driver -- a dense ``RandomTreeGenerator`` stream of
1000 attributes (500 categorical, 500 numeric) binned to 8 bins, 2
classes, micro-batches of 512 in chunks of 50 steps, generated on the
device chunk by chunk; a ``TreeConfig`` with 4095 nodes and SAMOA's VHT
defaults (n_min=200, delta=1e-7, tau=0.05); a mid-stream checkpoint; and
a ``ModelServer`` answering predict requests from the published snapshot
after training.  The run is checked against the same stream run with the
XLA implementations (``stats_impl="segment"``, ``route_impl="gather"``)
-- tree, statistics, prequential accuracy and the mid-stream checkpoint
must be equal -- and each Pallas kernel against its ``ref.py`` oracle at
the deployment's shapes.

Four chips (``--four-chip``): the paper's vertical parallelism only --
``build_vht_topology`` on ``ShardMapEngine(make_stream_mesh("model"))``
with the statistics' attribute axis split over the chips, against the
same topology on ``JitEngine`` on one chip; the statistics must really be
partitioned and every state leaf and output bit-identical.

Run from the repository root:

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # four chips of one host

Earlier lines report what ran (device kind, compile seconds, instances/s,
peak device bytes, state bytes) for information only; the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a
TPU, or when any check fails, the script exits non-zero and prints no
such line.  Compiled programs are cached in ``$JAX_COMPILATION_CACHE_DIR``
when set, else in ``.jax_cache`` next to this file.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SCRATCH = ROOT / ".smoke"          # checkpoints; removed after the run


@dataclasses.dataclass(frozen=True)
class Deployment:
    """The smoke deployment; the defaults are the chip run's sizes."""
    n_attrs: int = 1000            # half categorical, half numeric
    n_bins: int = 8
    n_classes: int = 2
    max_nodes: int = 4095
    n_min: int = 200               # SAMOA's VHT grace period,
    delta: float = 1e-7            # split confidence
    tau: float = 0.05              # and tie threshold
    batch: int = 512
    chunk_len: int = 50
    n_chunks: int = 20
    checkpoint_every: int = 12     # one checkpoint, mid-stream
    n_requests: int = 8
    seed: int = 0

    def tree(self, **impls):
        from repro.ml.htree import TreeConfig
        return TreeConfig(n_attrs=self.n_attrs, n_bins=self.n_bins,
                          n_classes=self.n_classes,
                          max_nodes=self.max_nodes, n_min=self.n_min,
                          delta=self.delta, tau=self.tau, **impls)

    def generator(self):
        from repro.data.generators import RandomTreeGenerator
        half = self.n_attrs // 2
        return RandomTreeGenerator(n_cat=half, n_num=self.n_attrs - half,
                                   n_classes=self.n_classes, seed=self.seed)


def log(msg: str):
    print(msg, flush=True)


def _require(ok: bool, what: str):
    if not ok:
        raise AssertionError(f"check failed: {what}")


def _equal(a, b) -> bool:
    import numpy as np
    return np.array_equal(np.asarray(a), np.asarray(b))


def _trees_equal(a, b) -> bool:
    import jax
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(_equal(x, y) for x, y in zip(la, lb))


def make_stream(dep: Deployment):
    """The chunked stream: chunk i is generated on the device from
    (seed, i), so every run of the deployment sees the same instances."""
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import ChunkedStream
    gen = dep.generator()
    key = jax.random.PRNGKey(dep.seed)

    @jax.jit
    def chunk(i):
        ks = jax.random.split(jax.random.fold_in(key, i), dep.chunk_len)
        x, y = jax.vmap(lambda k: gen.sample_binned(k, dep.batch,
                                                    dep.n_bins))(ks)
        return {"x": x, "y": y}

    return ChunkedStream.from_fn(lambda i: chunk(jnp.asarray(i)),
                                 dep.n_chunks, dep.chunk_len)


# ------------------------------------------------------------ kernels

def check_kernels(dep: Deployment, *, interpret: bool = False) -> list[str]:
    """Each Pallas kernel against its ref.py oracle at the deployment's
    shapes (interpret=True runs the kernel bodies off TPU).  Counts are
    f32 integers, so the counter kernels and the router must match
    exactly; the gain kernel, whose entropies round differently, to a
    tolerance.  Returns one report line per kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.rule_stats.ops import rule_stats_update
    from repro.kernels.rule_stats.ref import rule_stats_ref
    from repro.kernels.split_gain.ops import split_gain
    from repro.kernels.split_gain.ref import split_gain_ref
    from repro.kernels.tree_route.ops import tree_route
    from repro.kernels.tree_route.ref import tree_route_ref
    from repro.kernels.vht_stats.ops import stats_update
    from repro.kernels.vht_stats.ref import stats_update_ref

    N, m, nb, C, B = (dep.max_nodes, dep.n_attrs, dep.n_bins,
                      dep.n_classes, dep.batch)
    ks = jax.random.split(jax.random.PRNGKey(dep.seed + 1), 12)
    lines = []

    shape = (N, m, nb, C)
    stats = jnp.floor(jax.random.uniform(ks[0], shape) * 1000.0)
    leaf = jax.random.randint(ks[1], (B,), 0, N)
    xbin = jax.random.randint(ks[2], (B, m), 0, nb)
    y = jax.random.randint(ks[3], (B,), 0, C)
    w = jnp.where(jax.random.uniform(ks[4], (B,)) < 0.1, 0.0, 1.0)
    got = stats_update(stats, leaf, xbin, y, w, impl="pallas",
                       interpret=interpret)
    _require(_equal(got, stats_update_ref(stats, leaf, xbin, y, w)),
             f"vht_stats pallas == ref at {list(shape)}")
    lines.append(f"kernel vht_stats {list(shape)}: pallas == ref (exact)")

    # sparse counts up to 2^20: empty bins, empty leaves, large totals
    big = jnp.floor(jax.random.uniform(ks[5], shape) * 2.0 ** 20)
    sparse = jnp.where(jax.random.uniform(ks[6], shape) < 0.3, big, 0.0)
    got = np.asarray(split_gain(sparse, impl="pallas", interpret=interpret))
    ref = np.asarray(split_gain_ref(sparse))
    _require(np.array_equal(got <= -1e29, ref <= -1e29),
             "split_gain valid thresholds == ref")
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    lines.append(f"kernel split_gain {list(shape)}: pallas == ref "
                 f"(max abs diff {float(np.max(np.abs(got - ref))):.3g})")

    for M, n in ((1, N), (20, min(N, 255))):
        sa = jax.random.randint(ks[7], (M, n), -1, m)
        sb = jax.random.randint(ks[8], (M, n), 0, nb)
        ch = jax.random.randint(ks[9], (M, n, 2), 0, n)
        got = tree_route(sa, sb, ch, xbin, max_depth=24, impl="pallas",
                         interpret=interpret)
        _require(_equal(got, tree_route_ref(sa, sb, ch, xbin, 24)),
                 f"tree_route pallas == ref at M={M} N={n}")
        lines.append(f"kernel tree_route M={M} N={n} m={m}: pallas == ref "
                     "(exact)")

    rshape = (65, min(m, 40), nb, 3)              # AMRules benchmark width
    rst = jnp.floor(jax.random.uniform(ks[10], rshape) * 100.0)
    seg = jax.random.randint(ks[11], (B,), 0, rshape[0] + 1)
    mom = jnp.stack([jnp.ones(B), jnp.arange(B) % 7 - 3.0,
                     (jnp.arange(B) % 7 - 3.0) ** 2], -1)
    got = rule_stats_update(rst, seg, xbin[:, :rshape[1]], mom,
                            impl="pallas", interpret=interpret)
    _require(_equal(got, rule_stats_ref(rst, seg, xbin[:, :rshape[1]], mom)),
             f"rule_stats pallas == ref at {list(rshape)}")
    lines.append(f"kernel rule_stats {list(rshape)}: pallas == ref (exact)")
    return lines


# ------------------------------------------------------- one-chip path

def train(dep: Deployment, tc, *, engine, checkpoint_dir, publisher=None):
    """Prequential run of the deployment; returns (result, first-chunk
    seconds, state bytes, checkpoint)."""
    from repro.checkpoint.manager import CheckpointManager
    from repro.core.evaluation import ChunkedPrequentialEvaluation
    from repro.ml.vht import VHT, VHTConfig
    import jax
    shutil.rmtree(checkpoint_dir, ignore_errors=True)
    ckpt = CheckpointManager(checkpoint_dir, keep=0)
    learner = VHT(VHTConfig(tc))
    t0 = time.perf_counter()
    first = []

    def mark(outs, chunk, carry):
        if not first:
            first.append(time.perf_counter() - t0)

    res = ChunkedPrequentialEvaluation(
        learner, make_stream(dep), engine=engine, checkpoint=ckpt,
        checkpoint_every=dep.checkpoint_every, publisher=publisher,
        on_chunk=mark, key=jax.random.PRNGKey(dep.seed)).run(resume=False)
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(res.extra["carry"]))
    steps = ckpt.all_steps()
    _require(steps == [dep.checkpoint_every],
             f"one mid-stream checkpoint at chunk {dep.checkpoint_every}, "
             f"found {steps}")
    blob, _ = ckpt.restore_structured(steps[0])
    return learner, res, first[0], state_bytes, blob


def serve(dep: Deployment, learner, publisher) -> list[str]:
    """Answer predict requests from the published snapshot; every answer
    must equal ``reference_predict`` on that snapshot."""
    import jax
    import numpy as np
    from repro.serving import ModelServer, ServeConfig, reference_predict
    snap = publisher.current()
    _require(snap is not None, "a snapshot was published")
    x, _ = dep.generator().sample_binned(
        jax.random.PRNGKey(dep.seed + 2), dep.n_requests, dep.n_bins)
    x = np.asarray(x)
    server = ModelServer(learner, publisher, ServeConfig(
        max_batch=dep.n_requests, max_wait_ms=50.0,
        queue_limit=dep.n_requests, deadline_ms=600_000.0))
    try:
        reqs = [server.submit(row) for row in x]
        done = [r.result(timeout=600.0) for r in reqs]
    finally:
        server.stop()
    want = np.asarray(reference_predict(learner, snap.state, x))
    for i, r in enumerate(done):
        _require(r.status == "answered", f"request {i} answered "
                 f"(status {r.status}, {r.meta})")
        _require(int(r.pred) == int(want[i]),
                 f"request {i}: served {r.pred} == reference {want[i]}")
    lat = [r.meta["latency_ms"] for r in done]
    return [f"serve: {len(done)}/{len(done)} answered from snapshot chunk "
            f"{snap.chunk_index}, predictions == reference_predict "
            f"(latency ms max {max(lat):.1f}, includes compile)"]


def one_chip(dep: Deployment, *, interpret: bool = False,
             scratch: Path = SCRATCH) -> list[str]:
    """The one-chip phase: kernels against their oracles, the deployment
    on the default (Pallas on TPU) path against the XLA path, then
    serving.  Checkpoints go under ``scratch``, removed afterwards.
    Raises AssertionError on any mismatch."""
    import jax
    from repro.core.engines import JitEngine
    from repro.serving import SnapshotPublisher

    lines = check_kernels(dep, interpret=interpret)
    publisher = SnapshotPublisher()
    learner, res, first_s, state_bytes, ck = train(
        dep, dep.tree(), engine=JitEngine(), checkpoint_dir=scratch / "main",
        publisher=publisher)
    _, ref, _, _, ck_ref = train(
        dep, dep.tree(stats_impl="segment", route_impl="gather"),
        engine=JitEngine(donate=False), checkpoint_dir=scratch / "ref")
    shutil.rmtree(scratch, ignore_errors=True)

    st = res.extra["carry"]["states"]["vht"]
    st_ref = ref.extra["carry"]["states"]["vht"]
    for k in ("split_attr", "split_bin", "children", "n_nodes", "stats",
              "class_counts"):
        _require(_equal(st[k], st_ref[k]), f"{k}: default path == XLA path")
    n_nodes = int(st["n_nodes"])
    _require(n_nodes > 1, f"the tree grew (n_nodes={n_nodes})")
    _require(res.metric == ref.metric and res.curve == ref.curve,
             f"prequential accuracy {res.metric} == {ref.metric}")
    _require(_trees_equal(ck, ck_ref),
             f"checkpoint at chunk {dep.checkpoint_every} == XLA path's")
    seen = int(res.extra["seen"])
    lines += [
        f"tree: default path == XLA path (split_attr, split_bin, children, "
        f"stats, class_counts); n_nodes={n_nodes}",
        f"accuracy: default path == XLA path ({res.metric:.6f} over {seen} "
        "instances)",
        f"checkpoint: chunk {dep.checkpoint_every} snapshot == XLA path's",
    ]
    lines += serve(dep, learner, publisher)
    lines += [
        f"first_chunk_seconds (includes compile): {first_s:.3f}",
        f"instances_per_second (after the first chunk): "
        f"{res.throughput:.1f}",
        f"xla_path_instances_per_second: {ref.throughput:.1f}",
        f"state_bytes: {state_bytes}",
    ]
    mem = jax.devices()[0].memory_stats() or {}
    lines.append(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use')}")
    return lines


# ------------------------------------------------------ four-chip path

def vertical_parallel(dep: Deployment) -> list[str]:
    """The VHT topology with its statistics split over every device's
    'model' shard, against the same topology on one device: partitioned
    storage and bit-identical states and outputs."""
    import jax
    from repro.core.engines import JitEngine, ShardMapEngine
    from repro.launch.mesh import make_stream_mesh
    from repro.ml.vht import VHTConfig, build_vht_topology

    n_dev = jax.device_count()
    _require(dep.n_attrs % n_dev == 0,
             f"{dep.n_attrs} attributes split over {n_dev} devices")
    key = jax.random.PRNGKey(dep.seed)
    topo = build_vht_topology(VHTConfig(dep.tree()))
    runs = {}
    for name, eng in (("one", JitEngine()),
                      ("mesh", ShardMapEngine(make_stream_mesh("model")))):
        carry = eng.init(topo, key)
        t0 = time.perf_counter()
        carry, outs = eng.run_stream_chunked(topo, carry, make_stream(dep))
        jax.block_until_ready(carry)
        runs[name] = (carry, outs, time.perf_counter() - t0)

    one, outs1, t_one = runs["one"]
    mesh, outs4, t_mesh = runs["mesh"]
    stats = mesh["states"]["local-statistic"]["stats"]
    per = {s.data.shape for s in stats.addressable_shards}
    want = (dep.max_nodes, dep.n_attrs // n_dev, dep.n_bins, dep.n_classes)
    _require(len(stats.sharding.device_set) == n_dev and per == {want},
             f"stats partitioned over {n_dev} devices as {want}, got {per}")
    _require(_trees_equal(one["states"], mesh["states"]),
             "sharded states == one-device states")
    _require(_trees_equal(outs1, outs4),
             "sharded outputs == one-device outputs")
    n_nodes = int(one["states"]["model-aggregator"]["n_nodes"])
    _require(n_nodes > 1, f"the tree grew (n_nodes={n_nodes})")
    n = dep.n_chunks * dep.chunk_len * dep.batch
    return [
        f"vertical: stats {list(stats.shape)} partitioned {n_dev} ways, "
        f"{want[1]} attributes per device",
        f"vertical: states and outputs bit-identical to one device "
        f"(n_nodes={n_nodes})",
        f"vertical: seconds one device {t_one:.3f}, {n_dev} devices "
        f"{t_mesh:.3f} for {n} instances (include compile)",
    ]


# ---------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the vertical-parallelism phase over "
                         "four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chip else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime import compile_cache
    cache = compile_cache.enable(ROOT / ".jax_cache")

    dep = Deployment(seed=args.seed)
    log(f"device_kind: {devices[0].device_kind} x{len(devices)}")
    log(f"jax: {jax.__version__}; compile cache: {cache}")
    t0 = time.perf_counter()
    lines = vertical_parallel(dep) if args.four_chip else one_chip(dep)
    for line in lines:
        log(line)
    log(f"total_seconds: {time.perf_counter() - t0:.1f}")
    log(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
