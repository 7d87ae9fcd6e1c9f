"""The reduction from a profiler trace to the per-layer metrics."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import harness, trace

DATA = Path(__file__).parent / "data"


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d)
                            for n, s, d in evs]) for ln, evs in lines])


def _run_info(summary, window_s, steps=10, work=(1000.0, 10.0)):
    return harness.RunInfo(
        trace=summary, window_s=window_s, steps=steps,
        cfg={"batch": 4, "n_nominal": 1, "n_numeric": 1},
        peaks={"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
        work=NS(step=lambda cfg: work))


def _read(name, info):
    return harness.load_module(harness.BENCH / "metrics"
                               / f"{name}.py").read(info)


def test_busy_idle_self_time_and_gaps_of_a_synthetic_trace():
    device = _plane("/device:TPU:0", [
        ("XLA Ops", [("%while = loop", 0, 100), ("%fusion.1 = f", 10, 30),
                     ("%custom.2 = k", 50, 40), ("%copy.3 = c", 300, 100)]),
        ("XLA Modules", [("jit_chunk_fn(1)", 0, 100),
                         ("jit_bench_stream_gen(2)", 300, 100)]),
    ])
    host = _plane("/host:CPU", [("python3", [("bench.window", 0, 500),
                                             ("Execute", 120, 50)])])
    s = trace.summarize([device, host])
    dev = s["devices"][0]
    assert s["window_s"] == pytest.approx(500e-9)
    assert dev["busy_s"] == pytest.approx(200e-9)
    assert dev["op_self_time"]["%while = loop"] == pytest.approx(30e-9)
    assert dev["module_time"]["jit_bench_stream_gen"] == pytest.approx(100e-9)
    # idle between the operations, then after the last one to the end
    assert dev["gaps"] == [pytest.approx(200e-9), pytest.approx(100e-9)]
    assert dev["gap_labels"] == ["bench.window / Execute", "bench.window"]
    info = _run_info(s, 1.0, work=(5.0, 0.0))
    assert _read("device_idle_pct", info) == pytest.approx(60.0)
    assert _read("stream_gen_pct", info) == pytest.approx(50.0)
    # 10 steps of at least 5 ns each over the program's 100 ns of device
    # time; the benchmark's own generator is not the program's
    assert _read("step_roofline_pct", info) == pytest.approx(50.0)
    bd = harness.breakdown(dev)
    assert bd["device_ops"][0] == ["copy.3", pytest.approx(100e-9)]


def test_collective_time_is_the_self_time_of_collectives():
    device = _plane("/device:TPU:0", [
        ("XLA Ops", [
            ("%while.1 = (f32[8]) while((f32[8]) %p), body=%b", 0, 300),
            ("%all-gather-start.1 = (f32[2]{0}, f32[8]{0}) "
             "all-gather-start(f32[2]{0} %p), dimensions={0}", 10, 20),
            ("%all-gather-done.1 = f32[8]{0} "
             "all-gather-done((f32[2]{0}, f32[8]{0}) %all-gather-start.1)",
             40, 30),
            ("%all-reduce.3 = f32[] all-reduce(f32[] %x), to_apply=%add",
             100, 50),
            ("%fusion.2 = f32[8]{0:T(128)S(1)} fusion(f32[8] %all-reduce.3),"
             " kind=kLoop, calls=%fused_computation", 200, 40),
            ("%copy.3 = f32[8] copy(f32[8] %all-gather-done.1), "
             "metadata={op_name=\"all-gather(\"}", 250, 10)]),
        ("XLA Modules", [("jit_chunk_fn(1)", 0, 300)])])
    host = _plane("/host:CPU", [("python3", [("bench.window", 0, 400)])])
    dev = trace.summarize([device, host])["devices"][0]
    assert dev["collective_s"] == pytest.approx(100e-9)
    assert dev["busy_s"] == pytest.approx(300e-9)


def test_only_the_window_is_counted():
    """Device work before and after the ``bench.window`` span (set-up, the
    answers to a serving cell's last requests) is cut off."""
    device = _plane("/device:TPU:0", [
        ("XLA Ops", [("%warm = w", 0, 100), ("%a = x", 150, 100),
                     ("%late = p", 480, 200)]),
        ("XLA Modules", [("jit_warm(1)", 0, 100), ("jit_chunk_fn(2)", 150, 100),
                         ("jit_predict(3)", 480, 200)]),
    ])
    host = _plane("/host:CPU", [("python3", [("bench.window", 100, 400)])])
    s = trace.summarize([device, host])
    dev = s["devices"][0]
    assert s["window_s"] == pytest.approx(400e-9)
    assert dev["busy_s"] == pytest.approx(120e-9)
    assert "%warm = w" not in dev["op_self_time"]
    assert dev["op_self_time"]["%late = p"] == pytest.approx(20e-9)
    assert dev["module_time"] == {"jit_chunk_fn": pytest.approx(100e-9),
                                  "jit_predict": pytest.approx(20e-9)}
    assert sorted(dev["gaps"]) == [pytest.approx(50e-9),
                                   pytest.approx(230e-9)]
    assert _read("device_idle_pct", _run_info(s, 1.0)) == pytest.approx(70.0)


def test_a_trace_without_the_window_span_is_refused():
    device = _plane("/device:TPU:0", [("XLA Ops", [("%a = x", 0, 10)])])
    with pytest.raises(ValueError):
        trace.summarize([device, _plane("/host:CPU", [])])


def test_readers_find_nothing_without_a_trace():
    info = _run_info(None, 1.0)
    for name in ("device_idle_pct", "stream_gen_pct", "step_roofline_pct"):
        mod = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
        assert mod.read(info) is None


def test_recorded_chip_trace_reduces_to_what_the_run_reported():
    """A two-chunk trace of vht-dense1000.train recorded on one v5e chip
    (``record_trace.py``) and the numbers that run reported from it."""
    import json
    from jax.profiler import ProfileData
    recorded = json.loads((DATA / "vht_2chunks.json").read_text())
    pd = ProfileData.from_file(str(DATA / "vht_2chunks.xplane.pb"))
    s = trace.summarize(pd.planes)
    dev = s["devices"][0]
    assert s["window_s"] == pytest.approx(recorded["device"]["window_s"])
    assert dev["busy_s"] == pytest.approx(recorded["device"]["busy_s"])
    assert 0 < dev["busy_s"] <= s["window_s"]
    cell = harness.Cell("vht-dense1000.train")
    info = harness.RunInfo(
        trace=s, steps=recorded["steps"], cfg=cell.cfg, work=cell.work(),
        peaks=harness.peaks(recorded["device"]["kind"]))
    for name in ("device_idle_pct", "stream_gen_pct", "step_roofline_pct"):
        assert _read(name, info) == pytest.approx(
            recorded["metrics"][name]["value"])
    bd = harness.breakdown(dev)
    assert bd == json.loads(json.dumps(recorded["breakdown"]))
    assert any(op.startswith("_stats_update") for op, _ in bd["device_ops"])
    # one chip: no collective among its operations
    assert dev["collective_s"] == 0
    assert "bench.window" in s["spans"]
