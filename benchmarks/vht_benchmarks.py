"""VHT benchmarks: one function per paper table/figure (section 6.3).

Hardware adaptation note (EXPERIMENTS.md): the paper measures wall-clock on
a 24-core Storm cluster.  This container is one CPU core, so *scaling*
numbers are structural (per-shard work, message/statistics volume) while
*throughput* numbers are single-process wall-clock of the jit'd step --
honest measurements of this runtime, not projections.
"""

from __future__ import annotations

import dataclasses
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (acc_curve, make_stream, run_prequential,
                               run_prequential_scanned, state_bytes)
from repro.checkpoint.manager import CheckpointManager
from repro.core.engines import JitEngine
from repro.core.evaluation import ChunkedPrequentialEvaluation
from repro.data.generators import (CovtypeLikeGenerator,
                                   ElectricityLikeGenerator,
                                   RandomTreeGenerator, RandomTweetGenerator,
                                   bin_numeric)
from repro.data.pipeline import ChunkedStream
from repro.ml.htree import TreeConfig
from repro.ml.vht import VHT, VHTConfig, ShardingEnsemble, build_vht_topology

ROWS = []
BENCH = {}    # structured fig89 before/after numbers -> BENCH_vht.json


def emit(name, us_per_call, derived):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def _tc(m, n_classes=2, **kw):
    base = dict(n_attrs=m, n_bins=8, n_classes=n_classes, max_nodes=255,
                n_min=200)
    base.update(kw)
    return TreeConfig(**base)


def fig3_local_vs_moa(fast=True):
    """Fig. 3: VHT-local vs the sequential reference tree (MOA-equivalent).

    In our deterministic runtime both are the same algorithm at D=0; we
    verify accuracy parity between per-instance ('moa', batch=1 semantics
    approximated with batch=32) and micro-batched local execution."""
    n_b = 30 if fast else 120
    for tag, gen, m in [
        ("dense-10-10", RandomTreeGenerator(n_cat=10, n_num=10, depth=6), 20),
        ("sparse-100", RandomTweetGenerator(vocab=100), 100),
    ]:
        xs, ys = make_stream(gen, n_b, 512, 8)
        local = VHT(VHTConfig(_tc(m)))
        acc_l, thr_l, dt = run_prequential(local, xs, ys)
        # per-instance-like semantics: same stream in batches of 32
        xs2 = xs.reshape(-1, 32, xs.shape[-1])
        ys2 = ys.reshape(-1, 32)
        moa = VHT(VHTConfig(_tc(m, n_min=200)))
        acc_m, thr_m, _ = run_prequential(moa, xs2, ys2)
        emit(f"fig3.acc_parity.{tag}", dt / (n_b) * 1e6,
             f"local={acc_l:.3f};moa_like={acc_m:.3f};thr={thr_l:.0f}/s")


def fig45_parallel_accuracy(fast=True):
    """Fig. 4/5: local vs wok vs wk(z) vs sharding accuracy."""
    n_b = 40 if fast else 150
    streams = [
        ("dense-10-10", RandomTreeGenerator(n_cat=10, n_num=10, depth=6), 20),
        ("dense-100-100", RandomTreeGenerator(n_cat=100, n_num=100, depth=8), 200),
        ("sparse-1k", RandomTweetGenerator(vocab=1000), 1000),
    ]
    if fast:
        streams = streams[:2]
    for tag, gen, m in streams:
        xs, ys = make_stream(gen, n_b, 512, 8)
        results = {}
        for name, tc in [
            ("local", _tc(m)),
            ("wok", _tc(m, split_delay=4)),
            ("wk256", _tc(m, split_delay=4, buffer_size=256)),
        ]:
            v = VHT(VHTConfig(tc))
            acc, thr, dt = run_prequential(v, xs, ys)
            results[name] = acc
        sh = ShardingEnsemble(_tc(m), p=4)
        acc, thr, dt = run_prequential(sh, xs, ys)
        results["sharding4"] = acc
        emit(f"fig45.accuracy.{tag}", 0.0,
             ";".join(f"{k}={v:.3f}" for k, v in results.items()))


def _run_topology_scanned(cfg, xs, ys):
    """Time JitEngine.run_stream (whole-stream scan) on the VHT topology."""
    topo = build_vht_topology(cfg)
    eng = JitEngine()
    payloads = {"x": xs, "y": ys}
    key = jax.random.PRNGKey(0)
    eng.run_stream(topo, eng.init(topo, key), payloads)   # compile + warm
    carry = eng.init(topo, key)
    t0 = time.perf_counter()
    carry, outs = eng.run_stream(topo, carry, payloads)
    jax.block_until_ready(jax.tree.leaves(carry)[0])
    dt = time.perf_counter() - t0
    pred = np.asarray(outs["prediction"]["pred"])
    acc = float((pred == np.asarray(ys)).mean())
    return acc, ys.size / dt, dt


def fig89_speedup(fast=True):
    """Fig. 8/9: throughput of wok vs attribute count; per-shard work model.

    Vertical scaling structure: each LS shard holds m/p attribute columns;
    we report measured single-process throughput AND bytes/attr-shard at
    p in {2,4,8} (what each of p workers would hold/compute).

    Each arm is measured three ways so the perf trajectory is tracked from
    this PR on (-> BENCH_vht.json):
      before      -- pre-PR semantics: per-step jitted loop with host sync
                     per batch, dense one-hot statistics, ungated splits
      after       -- fused defaults: whole-stream lax.scan, segment/Pallas
                     statistics, lax.cond-gated split checks
      after_topo  -- the same stream through JitEngine.run_stream on the
                     MA/LS topology (the scanned engine path)
    """
    n_b = 20 if fast else 60
    dims = [20, 200, 1000]
    for m in dims:
        nb = n_b if m <= 200 else max(10, n_b // 2)
        half = m // 2
        gen = RandomTreeGenerator(n_cat=half, n_num=m - half, depth=8)
        xs, ys = make_stream(gen, nb, 512, 8)
        tc_before = _tc(m, split_delay=4, stats_impl="onehot",
                        gate_splits=False)
        acc0, thr0, dt0 = run_prequential(VHT(VHTConfig(tc_before)), xs, ys)
        cfg_after = VHTConfig(_tc(m, split_delay=4))
        acc1, thr1, dt1 = run_prequential_scanned(VHT(cfg_after), xs, ys)
        acc2, thr2, dt2 = _run_topology_scanned(cfg_after, xs, ys)
        v = VHT(cfg_after)
        st = v.init()
        total = state_bytes(st)
        shard = {p: state_bytes({"stats": st["stats"][:, : m // p]})
                 for p in (2, 4, 8)}
        BENCH[f"dense-{m}"] = {
            "n_batches": int(nb), "batch": int(ys.shape[1]),
            "before": {"us_per_batch": dt0 / nb * 1e6, "inst_per_s": thr0,
                       "acc": acc0,
                       "path": "per-step loop, one-hot stats, ungated"},
            "after": {"us_per_batch": dt1 / nb * 1e6, "inst_per_s": thr1,
                      "acc": acc1,
                      "path": "lax.scan stream, segment stats, gated"},
            "after_topology_scan": {
                "us_per_batch": dt2 / nb * 1e6, "inst_per_s": thr2,
                "acc": acc2,
                "path": "JitEngine.run_stream on MA/LS topology"},
            "speedup": dt0 / dt1,
            "speedup_topology": dt0 / dt2,
        }
        emit(f"fig89.speedup.dense-{m}", dt1 / nb * 1e6,
             f"thr={thr1:.0f}/s;before_us={dt0/nb*1e6:.0f};"
             f"after_us={dt1/nb*1e6:.0f};topo_us={dt2/nb*1e6:.0f};"
             f"speedup={dt0/dt1:.1f}x;state={total/2**20:.1f}MiB;"
             + ";".join(f"shard_p{p}={b/2**20:.1f}MiB" for p, b in shard.items()))


def chunked_long_stream(fast=True):
    """The chunked-runtime arm: a dense-200 VHT stream 2-3 orders of
    magnitude LONGER than the largest monolithic arm, run at flat device
    memory through the chunked driver.

    The stream is generator-backed (``ChunkedStream.from_fn``): no
    ``[T, ...]`` payload ever exists anywhere -- chunk k+1 is generated
    and device_put by the prefetch thread while chunk k's scan runs, and
    the (default) pipelined evaluation driver dispatches chunk k+1 before
    chunk k's result is read back.  Generation runs IN the loop here
    (unlike the pre-materialized monolithic arms), so it uses the
    packed-bits ``sample_binned`` path -- the float sampler would spend
    more time in RNG than the learner spends learning.  A
    memory ceiling guards the claim with a MEASUREMENT: the total bytes
    of live jax arrays (chunk double-buffer + learner state + temps),
    sampled at chunk boundaries during the timed run, must stay under
    1/10th of what stacking the stream would take, or the arm fails
    loudly instead of publishing a mislabeled number.  Metrics reduce
    per chunk (MetricAccumulator), a
    checkpoint is written at the midpoint chunk during the timed run,
    and a second evaluator resumes from it -- the arm records whether the
    resumed run reproduced the uninterrupted final metric exactly.
    """
    m, B, chunk_len = 200, 512, 50
    n_steps = 10_000 if fast else 20_000
    n_chunks = n_steps // chunk_len
    half = m // 2
    gen = RandomTreeGenerator(n_cat=half, n_num=m - half, depth=8)
    key = jax.random.PRNGKey(7)

    @jax.jit
    def chunk_payload(i):
        ks = jax.random.split(jax.random.fold_in(key, i), chunk_len)
        x, y = jax.vmap(lambda k: gen.sample_binned(k, B))(ks)
        return {"x": x, "y": y}

    probe = chunk_payload(0)
    chunk_bytes = state_bytes(probe)
    mono_bytes = chunk_bytes * n_chunks
    ceiling = mono_bytes // 10
    del probe

    # the guard MEASURES residency instead of deriving it: every few
    # chunks, sum the bytes of every live jax array in the process (chunk
    # double-buffer + learner state + compiled-program temps) -- a
    # refactor that quietly re-materializes the stream blows past the
    # ceiling here and the arm fails instead of publishing
    live_max = [0]

    def sample_live(outs, chunk, carry):
        if chunk.index % 10 == 0 or chunk.index == n_chunks - 1:
            live_max[0] = max(live_max[0],
                              sum(a.nbytes for a in jax.live_arrays()))

    stream = ChunkedStream.from_fn(
        lambda i: chunk_payload(jnp.asarray(i)), n_chunks, chunk_len,
        n_steps=n_steps)
    vht = VHT(VHTConfig(_tc(m, split_delay=4)))
    eng = JitEngine()

    kill_at = (3 * n_chunks) // 5        # mid-stream death point
    restore_from = n_chunks // 2         # newest checkpoint surviving it
    from repro.runtime import compile_cache
    with tempfile.TemporaryDirectory() as ckdir, \
            tempfile.TemporaryDirectory() as ccdir:
        # warm: compile the primed-first-chunk and steady-state chunk
        # programs.  The persistent compilation cache is part of the
        # recovery story, so it is enabled HERE: the warm/main compiles
        # populate it and the post-kill resume (fresh engine, fresh
        # traces) reloads the chunk programs from disk instead of
        # recompiling -- the recovery arm reports the hit/miss split
        t0 = time.perf_counter()
        ChunkedPrequentialEvaluation(
            vht, ChunkedStream.from_fn(
                lambda i: chunk_payload(jnp.asarray(i)), 2, chunk_len),
            engine=eng, compile_cache_dir=ccdir).run()
        compile_s = time.perf_counter() - t0

        mgr = CheckpointManager(ckdir, keep=0)
        res = ChunkedPrequentialEvaluation(
            vht, stream, engine=eng, checkpoint=mgr,
            checkpoint_every=n_chunks // 4,
            on_chunk=sample_live, compile_cache_dir=ccdir).run(resume=False)
        if live_max[0] >= ceiling:
            raise RuntimeError(
                f"chunked arm measured {live_max[0]} live device bytes "
                f">= ceiling {ceiling} (1/10th of the {mono_bytes}-byte "
                "monolithic stream): the runtime is materializing more "
                "than the chunk window")
        # simulate the kill at chunk `kill_at`: every checkpoint the dead
        # process would not have survived is dropped, then a FRESH engine
        # (cold caches -- recovery pays the recompile like a real restart)
        # resumes from what is left on disk
        import pathlib
        import shutil
        for s in mgr.all_steps():
            if s > restore_from:
                shutil.rmtree(pathlib.Path(ckdir) / f"step_{s:010d}")
        marks = {}

        def mark(outs, chunk, carry):
            jax.block_until_ready(jax.tree.leaves(carry)[0])
            marks[chunk.index] = time.perf_counter()

        cc0 = compile_cache.stats()
        resume_t0 = time.perf_counter()
        resumed = ChunkedPrequentialEvaluation(
            vht, stream, engine=JitEngine(),
            checkpoint=CheckpointManager(ckdir, keep=0),
            checkpoint_every=10 ** 9, on_chunk=mark,
            compile_cache_dir=ccdir).run(resume=True)
        cc1 = compile_cache.stats()
        # scope the cache to this arm: later arms time genuine compiles
        # (a cache placed from outside through the environment stays on)
        jax.config.update("jax_compilation_cache_dir",
                          os.environ.get(compile_cache.ENV_DIR))
    resume_cc = {k: cc1[k] - cc0[k] for k in cc1}
    resume_exact = (resumed.metric == res.metric
                    and resumed.curve == res.curve)
    # time-to-recover decomposition: restore+recompile+first replayed
    # chunk, catch-up through the kill point (the genuinely lost work),
    # and the full resumed tail
    dt = res.extra["wall_s"]
    t_first = marks[restore_from] - resume_t0
    t_recover = marks[kill_at] - resume_t0
    steady_per_chunk = dt / n_chunks
    largest_mono = max(v["n_batches"] for k, v in BENCH.items()
                       if k.startswith("dense-")) if BENCH else 0
    # the dispatch-gap headline: chunked-with-in-loop-generation vs the
    # monolithic pre-materialized dense-200 scan, us-per-batch over
    # us-per-batch (the ratio the pipelined driver + packed-bits
    # generation exist to hold down)
    mono_us = BENCH.get("dense-200", {}).get("after", {}).get("us_per_batch")
    vs_mono = (dt / n_steps * 1e6) / mono_us if mono_us else None
    BENCH[f"chunked.vht-dense200-c{chunk_len}"] = {
        "n_batches": int(n_steps), "batch": int(B),
        "chunk_len": int(chunk_len),
        "us_per_batch": dt / n_steps * 1e6,
        "inst_per_s": res.throughput,
        "acc": res.metric,
        "compile_s": compile_s,
        "resident_payload_bytes": int(live_max[0]),
        "monolithic_payload_bytes": int(mono_bytes),
        "memory_ceiling_bytes": int(ceiling),
        "stream_ratio_vs_largest_monolithic":
            (n_steps / largest_mono) if largest_mono else None,
        "vs_monolithic_dense200": vs_mono,
        "resume_exact": bool(resume_exact),
        "path": "generator-backed ChunkedStream (packed-bits generation), "
                "pipelined driver, per-chunk metric reduction, midpoint "
                "checkpoint + resume",
    }
    emit(f"chunked.vht-dense200-c{chunk_len}", dt / n_steps * 1e6,
         f"steps={n_steps};thr={res.throughput:.0f}/s;acc={res.metric:.3f};"
         f"resident={live_max[0]/2**20:.0f}MiB;"
         f"monolithic={mono_bytes/2**20:.0f}MiB;compile={compile_s:.1f}s;"
         + (f"vs_mono={vs_mono:.2f}x;" if vs_mono else "")
         + f"resume_exact={resume_exact}")

    # recovery arm: how long a mid-stream death actually costs.  t_first
    # is restore + recompile + the first replayed chunk; t_recover adds
    # the catch-up replay through the kill point (the work the dead
    # process genuinely lost); steady_per_chunk is the uninterrupted
    # run's per-chunk wall time for comparison.
    replayed = kill_at - restore_from + 1
    BENCH[f"recovery.vht-dense200-c{chunk_len}"] = {
        "killed_at_chunk": int(kill_at),
        "restored_from_chunk": int(restore_from),
        "replayed_chunks_to_kill_point": int(replayed),
        "time_to_first_replayed_chunk_s": t_first,
        "time_to_recover_s": t_recover,
        "steady_state_chunk_s": steady_per_chunk,
        "recovery_overhead_x": t_recover / (replayed * steady_per_chunk),
        "resumed_tail_s": resumed.extra["wall_s"],
        "resume_exact": bool(resume_exact),
        # the resume's persistent-cache split.  In-process, jax's global
        # in-memory compilation cache already dedupes the fresh engine's
        # recompiles (requests ~0 is EXPECTED); the persistent cache
        # earns its keep on process RESTART -- measured by the
        # multihost.compile-cache-restart arm
        "compile_cache_resume": resume_cc,
        "path": "drop post-kill checkpoints, fresh engine (traces cold; "
                "in-process compiles dedupe via jax's in-memory cache, "
                "process restarts reload from the persistent cache), "
                "restore newest intact checkpoint, replay to kill point",
    }
    emit(f"recovery.vht-dense200-c{chunk_len}", t_recover,
         f"killed_at={kill_at};restored_from={restore_from};"
         f"replayed={replayed};t_first={t_first:.2f}s;"
         f"t_recover={t_recover:.2f}s;"
         f"steady={steady_per_chunk*1e3:.0f}ms/chunk;"
         f"cache_hits={resume_cc['hits']}/{resume_cc['requests']};"
         f"resume_exact={resume_exact}")
    if not resume_exact:
        raise RuntimeError("checkpoint resume did not reproduce the "
                           "uninterrupted run's metrics")


OVERHEAD_GUARD = 1.35     # chunked/monolithic us-per-batch, same data


def chunked_overhead(fast=True):
    """Micro-arm: pure dispatch overhead of the chunked driver.

    The SAME pre-materialized dense-200 stream (generation excluded from
    both sides, unlike the long-stream arm) runs once as a single
    monolithic scan and once through the pipelined chunked evaluation;
    the published number is the chunked/monolithic us-per-batch ratio.
    This isolates what chunking itself costs -- per-chunk dispatch, the
    accumulator, the drain thread -- from generation and checkpointing.
    FAILS LOUDLY above ``OVERHEAD_GUARD`` so the dispatch gap cannot
    silently regress; part of the --fast CI smoke."""
    from benchmarks.common import best_of, run_prequential_engine
    m, B, chunk_len = 200, 512, 50
    n_steps = 300 if fast else 600
    half = m // 2
    gen = RandomTreeGenerator(n_cat=half, n_num=m - half, depth=8)
    key = jax.random.PRNGKey(11)

    @jax.jit
    def chunk_payload(i):
        ks = jax.random.split(jax.random.fold_in(key, i), chunk_len)
        x, y = jax.vmap(lambda k: gen.sample_binned(k, B))(ks)
        return {"x": x, "y": y}

    parts = [chunk_payload(jnp.asarray(i))
             for i in range(n_steps // chunk_len)]
    xs = jnp.concatenate([p["x"] for p in parts])
    ys = jnp.concatenate([p["y"] for p in parts])
    del parts
    vht = VHT(VHTConfig(_tc(m, split_delay=4)))
    eng = JitEngine()
    acc_m, _, dt_mono = best_of(
        lambda: run_prequential_engine(eng, vht, xs, ys), reps=2)

    def run_chunked():
        r = ChunkedPrequentialEvaluation(
            vht, ChunkedStream({"x": xs, "y": ys}, chunk_len),
            engine=eng).run(resume=False)
        return r.metric, r.throughput, r.extra["wall_s"]

    run_chunked()                       # warm the chunk programs
    acc_c, _, dt_chunk = best_of(run_chunked, reps=2)
    mono_us = dt_mono / n_steps * 1e6
    chunk_us = dt_chunk / n_steps * 1e6
    ratio = chunk_us / mono_us
    BENCH["chunked.overhead"] = {
        "n_batches": int(n_steps), "batch": int(B),
        "chunk_len": int(chunk_len),
        "monolithic_us_per_batch": mono_us,
        "chunked_us_per_batch": chunk_us,
        "ratio": ratio,
        "guard": OVERHEAD_GUARD,
        "path": "same pre-materialized stream; monolithic scan vs "
                "pipelined chunked driver",
    }
    emit("chunked.overhead", chunk_us,
         f"mono_us={mono_us:.0f};chunked_us={chunk_us:.0f};"
         f"ratio={ratio:.2f}x;guard={OVERHEAD_GUARD}x")
    if acc_c != acc_m:
        raise RuntimeError(
            f"chunked driver diverged from the monolithic scan on the "
            f"same stream: {acc_c} != {acc_m}")
    if ratio > OVERHEAD_GUARD:
        raise RuntimeError(
            f"chunked dispatch overhead {ratio:.2f}x exceeds the "
            f"{OVERHEAD_GUARD}x guard ({chunk_us:.0f} vs {mono_us:.0f} "
            "us/batch): the chunk pipeline regressed")


def tab34_realworld(fast=True):
    """Tab. 3/4: accuracy & time on real-data stand-ins (offline container:
    covtype-like / elec-like / phy-like synthetic streams)."""
    n_b = 30 if fast else 100
    streams = [
        ("elec", ElectricityLikeGenerator(), 12, 2),
        ("covtype", CovtypeLikeGenerator(), 54, 7),
        ("phy", RandomTreeGenerator(n_cat=0, n_num=78, depth=7), 78, 2),
    ]
    for tag, gen, m, C in streams:
        xs, ys = make_stream(gen, n_b, 512, 8)
        out = {}
        times = {}
        for name, mk in [
            ("local", lambda: VHT(VHTConfig(_tc(m, n_classes=C)))),
            ("wok2", lambda: VHT(VHTConfig(_tc(m, n_classes=C, split_delay=2)))),
            ("wk0", lambda: VHT(VHTConfig(_tc(m, n_classes=C, split_delay=2,
                                              buffer_size=32)))),
            ("shard2", lambda: ShardingEnsemble(_tc(m, n_classes=C), p=2)),
            ("shard4", lambda: ShardingEnsemble(_tc(m, n_classes=C), p=4)),
        ]:
            acc, thr, dt = run_prequential(mk(), xs, ys)
            out[name] = acc
            times[name] = dt
        emit(f"tab34.{tag}", 0.0,
             ";".join(f"{k}={v:.3f}/{times[k]:.1f}s" for k, v in out.items()))


def main(fast=True):
    fig3_local_vs_moa(fast)
    fig45_parallel_accuracy(fast)
    fig89_speedup(fast)
    chunked_long_stream(fast)      # after fig89: ratio vs largest mono arm
    chunked_overhead(fast)         # guarded chunked/monolithic micro-arm
    tab34_realworld(fast)
    return ROWS
