"""A cell laid over four chips runs through the harness on the program's
multi-chip path: four virtual CPU devices, in a process of its own
(``mesh_runs.py``), since the device count is fixed when JAX starts."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import harness

SCRIPT = harness.BENCH / "tests" / "mesh_runs.py"


@pytest.fixture(scope="module")
def runs():
    from repro.launch.mesh import force_host_devices
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    force_host_devices(4, env)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(harness.ROOT / "src"), env.get("PYTHONPATH", ""))
        if p)
    r = subprocess.run([sys.executable, str(SCRIPT)], cwd=harness.ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        raise AssertionError(f"mesh_runs.py failed (rc={r.returncode}):\n"
                             f"{r.stdout[-4000:]}\n{r.stderr[-8000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_four_chip_cell_is_correct(runs):
    four = runs["four"]
    assert four["correct"], four["checks"]
    assert all(c["value"] == 0 for c in four["checks"].values())
    # the window did real work: the tree split
    assert four["prog"]["n_nodes"] > 1


def test_four_chip_cell_runs_on_a_shard_map_engine_over_its_mesh(runs):
    assert runs["four"]["engine"] == "ShardMapEngine"
    assert runs["four"]["engine_mesh"] == {"model": 4}
    assert runs["one"]["engine"] == "JitEngine"
    assert runs["one"]["engine_mesh"] is None


def test_chunks_are_made_replicated_on_the_mesh(runs):
    assert runs["four"]["chunk_devices"] == 4
    assert runs["four"]["chunk_replicated"]
    assert runs["one"]["chunk_devices"] == 1


def test_statistics_are_split_by_attribute_over_four_chips(runs):
    n_attrs = 16
    shard = [63, n_attrs // 4, 8, 2]
    assert runs["four"]["stats_shards"] == [shard] * 4
    # the reference is placed over the same chips, and compared part by part
    assert runs["four"]["ref_stats_shards"] == [shard] * 4
    assert runs["one"]["stats_shards"] == [[63, n_attrs, 8, 2]]


def test_four_chip_state_is_bit_identical_to_one_chip(runs):
    assert runs["one"]["correct"], runs["one"]["checks"]
    assert runs["four"]["prog"] == runs["one"]["prog"]


def test_four_chip_run_reports_each_chips_peak(runs):
    assert len(runs["four"]["report"]["memory_peak_bytes_per_chip"]) == 4
    assert "memory_peak_bytes_per_chip" not in runs["one"]["report"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_planted_fault_is_not_correct_on_four_chips(runs, fault):
    out = runs[fault]
    assert not out["correct"]
    assert out["checks"]["state_mismatch"]["value"] > 0


def test_part_by_part_compare_equals_whole_arrays(runs):
    for parts, whole in runs["sharded_compare"]:
        assert parts[0] == whole[0] > 0
        assert parts[1] == whole[1] > 0


def test_one_device_compare_equals_whole_arrays():
    import jax
    from bench.tests.mesh_runs import whole_compare
    rng = np.random.default_rng(3)
    fam = NS(EXACT=("a",), FLOAT=("f", "g"))
    ref = {"a": rng.integers(0, 5, (7, 3)).astype(np.int32),
           "f": rng.normal(size=(4, 6)).astype(np.float32),
           "g": rng.normal(size=(5,)).astype(np.float32)}
    prog = {k: v.copy() for k, v in ref.items()}
    prog["a"][2, 1] += 1
    prog["f"][3, 0] += 0.25
    on_device = {k: jax.device_put(v) for k, v in ref.items()}
    assert harness.compare(fam, prog, on_device) == whole_compare(fam, prog,
                                                                  ref)
    assert harness.compare(fam, prog, ref) == whole_compare(fam, prog, ref)


def test_memory_peak_is_the_fullest_chips():
    dev = lambda b: NS(memory_stats=lambda: {"peak_bytes_in_use": b})
    assert harness.memory_peak([dev(5), dev(9), dev(7)]) == (9, [5, 9, 7])
    assert harness.memory_peak([NS(memory_stats=lambda: None)]) == \
        (None, [None])
