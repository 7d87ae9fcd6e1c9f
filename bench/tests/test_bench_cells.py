"""Each cell's set-up, window and correctness check through the harness,
at a tiny size on the CPU."""

import pytest

from bench.tests import tiny

CELLS = ["vht-dense1000.train", "vamr-waveform40.train",
         "vht-dense1000.serve"]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct(workload, monkeypatch):
    from bench import harness
    seen = {}
    compare = harness.compare

    def spy(fam, prog, ref):
        seen.update(ref)
        return compare(fam, prog, ref)

    monkeypatch.setattr(harness, "compare", spy)
    out = tiny.run(workload, monkeypatch)
    assert out["correct"], out["checks"]
    # the window did real work: the tree split, rules were created
    if "n_nodes" in seen:
        assert seen["n_nodes"] > 1
    else:
        assert seen["n_created"] > 0 and seen["n_feats"] > 0
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0
    for name in ("train_instances_per_s", "setup_s"):
        assert out["metrics"][name]["value"] > 0
    if workload.endswith(".serve"):
        assert out["metrics"]["predict_p95_ms"]["value"] > 0
        assert "served_wrong" in out["checks"]


def test_one_chip_cell_runs_a_jit_engine_and_enters_no_mesh(monkeypatch):
    from bench import harness
    from repro.core import engines
    made = []
    make_engine = harness.make_engine

    def spy(mesh):
        made.append((mesh, make_engine(mesh)))
        return made[-1][1]

    def no_mesh(mesh):
        raise AssertionError("a one-chip cell entered a mesh")

    monkeypatch.setattr(harness, "make_engine", spy)
    monkeypatch.setattr(engines, "mesh_context", no_mesh)
    out = tiny.run("vht-dense1000.train", monkeypatch, chunks=3)
    assert out["correct"], out["checks"]
    assert [(m, type(e)) for m, e in made] == [(None, engines.JitEngine)]
    assert "memory_peak_bytes_per_chip" not in out["report"]


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_per_layer_metrics(workload, monkeypatch):
    out = tiny.run(workload, monkeypatch, trace=True, chunks=4)
    assert out["correct"], out["checks"]
    assert out["metrics"]["window_compiles"]["value"] == 0
    assert out["device"]["window_s"] > 0

