"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and metric is found by name; the runner refuses to run
without a chip."""

import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = harness.ROOT
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    assert {"setup_s"} <= {m["name"] for m in BM["end_to_end"]}
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for text in [w["why"] for w in BM["workloads"]] + \
            [c["why"] for c in BM["configs"]] + \
            [m["layer"] for m in BM["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text


def test_every_name_finds_its_file():
    configs = {c["name"]: c for c in BM["configs"]}
    e2e = {m["name"] for m in BM["end_to_end"]}
    for w in BM["workloads"]:
        assert w["chips"] in (1, 4)
        cell = harness.Cell(w["name"])
        assert configs[w["config"]]["file"].startswith("bench/")
        assert hasattr(cell.work(), "step")
        for key in cell.cfg["reduced"]:
            assert key in cell.cfg["published"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        assert callable(harness.load_module(
            ROOT / "bench" / "metrics" / f"{m['name']}.py").read)
    for m in BM["end_to_end"]:
        assert callable(harness.load_module(
            ROOT / "bench" / "metrics" / f"{m['name']}.py").read)


def test_chips_are_what_the_configuration_is_laid_over():
    for w in BM["workloads"]:
        mesh = harness.Cell(w["name"]).cfg.get("mesh") or {}
        assert w["chips"] == math.prod(mesh.values())


def _scratch(chips, traffic="train"):
    bm = dict(BM, workloads=[{"name": "scratch", "config": "vht-dense1000",
                              "traffic": traffic, "chips": chips,
                              "why": "test"}])
    return lambda **kw: harness.Cell("scratch", benchmark=bm, **kw)


@pytest.mark.parametrize("chips, mesh", [(4, None), (4, {"model": 2}),
                                         (1, {"model": 4}),
                                         (4, {"model": 2, "data": 1})])
def test_cell_refuses_chips_its_mesh_does_not_make(chips, mesh):
    with pytest.raises(ValueError, match="laid over"):
        _scratch(chips)(overrides={"mesh": mesh})


def test_cell_takes_chips_its_mesh_makes():
    cell = _scratch(4)(overrides={"mesh": {"model": 2, "data": 2}})
    assert cell.chips == 4 and cell.mesh_shape == {"model": 2, "data": 2}


def test_serving_on_a_mesh_is_not_supported():
    with pytest.raises(NotImplementedError, match="not supported"):
        _scratch(4, "serve")(overrides={"mesh": {"model": 4}})


def test_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")


def _run_cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vht-dense1000.train",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_without_a_tpu_fails_and_prints_no_result():
    r = _run_cli(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_cli_in_a_bare_benchmark_directory_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cli(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
