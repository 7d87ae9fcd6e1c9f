"""Stage counters and spans: the histogram helper, the chunk loop's stage
table, the ``repro.*`` spans in a profiler trace, and the server's and
publisher's histograms."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core.engines import JitEngine
from repro.core.evaluation import (PIPELINED_STAGES, SYNC_STAGES,
                                   ChunkedPrequentialEvaluation)
from repro.data.generators import RandomTreeGenerator, bin_numeric
from repro.data.pipeline import ChunkedStream
from repro.ml.htree import TreeConfig
from repro.ml.vht import VHT, VHTConfig
from repro.runtime import FaultInjector, telemetry
from repro.serving import ModelServer, ServeConfig, SnapshotPublisher

B, T, C = 64, 9, 3          # 9 micro-batches in chunks of 3: 3 chunks
TC = TreeConfig(n_attrs=12, n_bins=8, n_classes=2, max_nodes=63, n_min=20,
                delta=0.05, tau=0.1)
LEARNER = VHT(VHTConfig(TC))
ENGINE = JitEngine()

SPANS = ("repro.chunk.stream_wait", "repro.chunk.dispatch",
         "repro.chunk.backpressure", "repro.drain.wait", "repro.publish",
         "repro.checkpoint.save", "repro.stream.produce",
         "repro.serve.batch_open", "repro.serve.predict")


def _stream():
    gen = RandomTreeGenerator(n_cat=6, n_num=6, depth=5, seed=3)
    key = jax.random.PRNGKey(0)
    xs, ys = [], []
    for _ in range(T):
        key, k = jax.random.split(key)
        x, y = gen.sample(k, B)
        xs.append(bin_numeric(x, 8))
        ys.append(y)
    return ChunkedStream({"x": jnp.stack(xs), "y": jnp.stack(ys)}, C)


def _run(**kw):
    return ChunkedPrequentialEvaluation(
        LEARNER, _stream(), engine=ENGINE, **kw).run(resume=False)


# ------------------------------------------------------------ histogram

def test_histogram_counts_each_value_in_the_bucket_just_above_it():
    h = telemetry.Histogram()
    values = (0.0, 0.3, 1.0, 2.0, 3.0, 100.0)
    for v in values:
        h.add(v)
    s = h.snapshot()
    assert (s["count"], s["sum"], s["max"]) == (6, 106.3, 100.0)
    edges = [e for e, c in s["buckets"] for _ in range(c)]
    step = 2 ** (1 / telemetry.PER_OCTAVE)
    for v, e in zip(values, edges):
        assert v <= e < max(v * step, telemetry.LO * step), (v, e)
    # values past the last edge land in the last bucket
    h.add(1e12)
    assert h.counts[-1] == 1 and h.max == 1e12


def test_named_program_compiles_under_its_name():
    f = telemetry.program(lambda x: x + 1, "my_stage")
    assert "jit_my_stage" in f.lower(jnp.zeros(3)).as_text()


# ------------------------------------------------------------ chunk loop

@pytest.mark.parametrize("pipeline,stages", [(True, PIPELINED_STAGES),
                                             (False, SYNC_STAGES)],
                         ids=["pipelined", "sync"])
def test_run_reports_a_stage_table_with_one_count_per_chunk(pipeline,
                                                            stages):
    r = _run(pipeline=pipeline)
    table = r.extra["report"]["stages"]
    assert set(table) == set(stages)
    for name in stages:
        assert table[name]["count"] == T // C, name
        assert table[name]["total_s"] == pytest.approx(
            table[name]["sum"] / 1e3)
        assert table[name]["total_s"] >= 0
    # the newest finished run's table, for a reader in the process
    assert ChunkedPrequentialEvaluation.last_stages is table


def test_pipelined_run_writes_the_issued_chunk_to_the_publisher():
    pub = SnapshotPublisher()
    _run(publisher=pub)
    assert pub.issued_cursor == pub.train_cursor == T // C - 1
    assert pub.published == T // C


def test_profiler_trace_holds_every_repro_span(tmp_path):
    pub = SnapshotPublisher()
    ckpt = CheckpointManager(tmp_path / "ckpt", keep=0, async_write=False)
    srv = ModelServer(LEARNER, pub, ServeConfig(max_batch=4,
                                                 max_wait_ms=1.0))
    try:
        _run(publisher=pub)         # compile outside the trace
        xs = np.asarray(_stream()._fetch(0)["x"][0])
        srv.submit(xs[0]).result(300.0)
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            _run(publisher=pub, checkpoint=ckpt)
            reqs = [srv.submit(xs[i]) for i in range(4)]
            assert all(r.result(60.0).status == "answered" for r in reqs)
        finally:
            jax.profiler.stop_trace()
    finally:
        srv.stop()
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(tmp_path, "trace", "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert set(SPANS) <= names, sorted(set(SPANS) - names)


# --------------------------------------------------------------- server

def test_server_histograms_reconcile_with_its_counters():
    pub = SnapshotPublisher()
    _run(publisher=pub)
    srv = ModelServer(LEARNER, pub, ServeConfig(max_batch=4,
                                                 max_wait_ms=1.0))
    xs = np.asarray(_stream()._fetch(0)["x"][0])
    try:
        srv.submit(xs[0]).result(300.0)
        before = srv.status()
        # two chunks issued and not drained: every batch queues behind them
        pub.issued_cursor = pub.train_cursor + 2
        reqs = [srv.submit(xs[i % len(xs)]) for i in range(10)]
        assert all(r.result(60.0).status == "answered" for r in reqs)
        after = srv.status()
    finally:
        srv.stop()

    def grew(key, field):
        return after[key][field] - before[key][field]

    answered = after["answered"] - before["answered"]
    batches = after["batches"] - before["batches"]
    assert grew("queue_ms", "count") == answered == 10
    assert grew("device_ms", "count") == grew("chunks_ahead", "count") \
        == batches > 0
    assert grew("chunks_ahead", "sum") == 2 * batches
    assert before["chunks_ahead"]["sum"] == 0 \
        and before["chunks_ahead"]["count"] == 1
    # a request's queue wait is part of its latency
    assert 0 <= grew("queue_ms", "sum") \
        <= sum(r.meta["latency_ms"] for r in reqs)
    assert grew("device_ms", "sum") >= 0


def test_the_chaos_publisher_forwards_the_issued_chunk():
    pub = SnapshotPublisher()
    wrapped = FaultInjector(stall_publish_chunks=(1,)).wrap_publisher(pub)
    wrapped.issued_cursor = 3
    assert pub.issued_cursor == wrapped.issued_cursor == 3


def test_chunks_ahead_never_reads_below_zero():
    pub = SnapshotPublisher()
    _run(publisher=pub)
    pub.issued_cursor = -1          # a trainer that does not pipeline
    srv = ModelServer(LEARNER, pub, ServeConfig(max_batch=4))
    try:
        xs = np.asarray(_stream()._fetch(0)["x"][0])
        assert srv.submit(xs[0]).result(300.0).status == "answered"
        st = srv.status()["chunks_ahead"]
    finally:
        srv.stop()
    assert st["count"] == 1 and st["sum"] == 0 and st["max"] == 0
