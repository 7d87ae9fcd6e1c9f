"""chip_smoke.py's phases at a tiny size on the CPU.

The chip run uses the same functions at the deployment's full size; here
the Pallas kernels run in interpret mode and the "default" training path
resolves to the XLA implementations, so the checks exercise the control
flow (mid-stream checkpoint, snapshot serving, the comparisons) rather
than the kernels' lowering -- tests/test_tpu_compile.py covers that.
"""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod     # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    # a grace period and thresholds small enough that the tree grows
    # within 1536 instances
    return smoke.Deployment(n_attrs=16, max_nodes=63, n_min=50, delta=0.05,
                            tau=0.1, batch=64, chunk_len=4, n_chunks=6,
                            checkpoint_every=4, n_requests=3)


def test_one_chip_phase_at_tiny_size(smoke, tiny, tmp_path):
    lines = smoke.one_chip(tiny, interpret=True, scratch=tmp_path / "ck")
    text = "\n".join(lines)
    for kernel in ("vht_stats", "split_gain", "tree_route M=1",
                   "tree_route M=20", "rule_stats"):
        assert f"kernel {kernel}" in text
    assert "tree: default path == XLA path" in text
    assert "accuracy: default path == XLA path" in text
    assert "checkpoint: chunk 4 snapshot == XLA path's" in text
    assert "serve: 3/3 answered" in text
    assert not (tmp_path / "ck").exists()      # checkpoints cleaned up


def test_vertical_parallel_phase_at_tiny_size(smoke, tiny):
    lines = smoke.vertical_parallel(tiny)
    assert any("bit-identical to one device" in line for line in lines)


def test_kernel_check_fails_loudly_on_a_wrong_kernel(smoke, tiny,
                                                      monkeypatch):
    """A kernel that disagrees with its oracle must fail the phase."""
    from repro.kernels.vht_stats import ops
    real = ops._stats_update

    def off_by_one(*args, **kw):
        return real(*args, **kw) + (kw.get("impl") == "pallas")

    monkeypatch.setattr(ops, "_stats_update", off_by_one)
    with pytest.raises(AssertionError, match="vht_stats"):
        smoke.check_kernels(tiny, interpret=True)


def test_main_without_tpu_exits_nonzero_and_prints_no_result(smoke,
                                                             capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
