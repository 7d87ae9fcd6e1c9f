"""The readers of the program's own counters and of the statistics
kernel's stable name: the right number from a hand-built run, None where
the source is absent, and numbers from a tiny traced run on the CPU."""

import sys

import pytest

from bench import harness
from bench.tests import tiny


def _read(name, info):
    return harness.load_module(harness.BENCH / "metrics"
                               / f"{name}.py").read(info)


def _hist(values, edges=(1.0, 2.0, 4.0, 8.0, 400.0)):
    """A snapshot in the program's format, with coarse buckets."""
    buckets = {}
    for v in values:
        e = min(e for e in edges if v <= e)
        buckets[e] = buckets.get(e, 0) + 1
    return {"count": len(values), "sum": float(sum(values)),
            "max": float(max(values, default=0.0)),
            "buckets": [[e, c] for e, c in sorted(buckets.items())]}


SERVER_READERS = ("predict_queue_ms_p95", "predict_device_ms_p95",
                  "predict_chunks_ahead_mean")
STAGE_READERS = ("chunk_dispatch_ms_p50", "chunk_backpressure_pct",
                 "chunk_stream_wait_pct")


def test_server_readers_count_only_the_window():
    before = {"queue_ms": _hist([300.0] * 50),      # set-up's answers
              "device_ms": _hist([300.0]),
              "chunks_ahead": _hist([0.0])}
    # the window: 100 answers, 95 waited 0.5 ms and 5 waited 7 ms; three
    # batches behind 1, 2 and 2 chunks
    after = {"queue_ms": _hist([300.0] * 50 + [0.5] * 95 + [7.0] * 5),
             "device_ms": _hist([300.0, 3.0, 3.0, 350.0]),
             "chunks_ahead": _hist([0.0, 1.0, 2.0, 2.0])}
    info = harness.RunInfo(server=after, server0=before)
    assert _read("predict_queue_ms_p95", info) == 1.0
    assert _read("predict_device_ms_p95", info) == 350.0     # the max
    assert _read("predict_chunks_ahead_mean", info) == pytest.approx(5 / 3)


@pytest.mark.parametrize("name", SERVER_READERS)
def test_server_readers_without_their_counter_read_none(name):
    assert _read(name, harness.RunInfo(server=None, server0=None)) is None
    # a server without the histograms (an older program)
    old = {"answered": 3, "batches": 1}
    assert _read(name, harness.RunInfo(server=old, server0=old)) is None


def _stage_table(monkeypatch, table):
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.core.evaluation import ChunkedPrequentialEvaluation
    monkeypatch.setattr(ChunkedPrequentialEvaluation, "last_stages", table)


def test_stage_readers_read_the_newest_runs_table(monkeypatch):
    _stage_table(monkeypatch, {
        "stream_wait": dict(_hist([1.0] * 10), total_s=0.5),
        "dispatch": dict(_hist([3.0] * 6 + [8.0] * 4), total_s=0.04),
        "backpressure": dict(_hist([300.0] * 10), total_s=9.0)})
    info = harness.RunInfo(window_s=10.0)
    assert _read("chunk_dispatch_ms_p50", info) == 4.0
    assert _read("chunk_backpressure_pct", info) == pytest.approx(90.0)
    assert _read("chunk_stream_wait_pct", info) == pytest.approx(5.0)


@pytest.mark.parametrize("name", STAGE_READERS)
def test_stage_readers_without_a_table_read_none(name, monkeypatch):
    _stage_table(monkeypatch, None)
    assert _read(name, harness.RunInfo(window_s=1.0)) is None
    # a program that keeps no table at all
    monkeypatch.setitem(sys.modules, "repro.core.evaluation", None)
    assert _read(name, harness.RunInfo(window_s=1.0)) is None


def test_stats_kernel_reader_finds_the_kernel_by_its_stable_name():
    ops = {"%vht_stats_update.3 = f32[4095,16000] custom-call(...)": 0.6,
           "vht_stats_update.4": 0.2, "%copy.100 = f32[] copy()": 1.0,
           "%split_gain.3 = f32[] custom-call()": 0.1}
    info = harness.RunInfo(trace={"devices": [{"op_self_time": ops}]},
                           steps=400)
    assert _read("stats_kernel_ms_per_step", info) == pytest.approx(2.0)
    rules = {"%rule_stats_update.6 = f32[] custom-call()": 0.05}
    info = harness.RunInfo(trace={"devices": [{"op_self_time": rules}]},
                           steps=100)
    assert _read("stats_kernel_ms_per_step", info) == pytest.approx(0.5)


def test_stats_kernel_reader_without_the_kernel_reads_none():
    assert _read("stats_kernel_ms_per_step",
                 harness.RunInfo(trace=None, steps=10)) is None
    # the parent's trace named the kernel after a Python function
    ops = {"_stats_update.3": 0.6}
    assert _read("stats_kernel_ms_per_step", harness.RunInfo(
        trace={"devices": [{"op_self_time": ops}]}, steps=10)) is None


def test_traced_serving_run_reads_every_counter_metric(monkeypatch):
    out = tiny.run("vht-dense1000.serve", monkeypatch, trace=True, chunks=4)
    assert out["correct"], out["checks"]
    for name in STAGE_READERS + SERVER_READERS:
        assert out["metrics"][name]["value"] >= 0, name
    assert out["metrics"]["predict_chunks_ahead_mean"]["value"] <= 2
