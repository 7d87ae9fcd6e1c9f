"""Tiny sizes of the benchmark's configurations, for tests on the CPU."""

import sys
import time

from bench import harness

if str(harness.ROOT / "src") not in sys.path:     # the program under test
    sys.path.insert(0, str(harness.ROOT / "src"))

SMALL = {
    "vht-dense1000": {"n_nominal": 8, "n_numeric": 8, "max_nodes": 63,
                      "batch": 128, "chunk_len": 5, "n_min": 20,
                      "concept_depth": 3},
    "vamr-waveform40": {"batch": 256, "chunk_len": 5, "n_min": 20},
}


def cell(workload: str) -> harness.Cell:
    return harness.Cell(workload, overrides=SMALL[workload.split(".")[0]])


def run(workload: str, monkeypatch, *, seed: int = 2**31 + 7,
        trace: bool = False, chunks: int = 8, seconds: float = 1.0) -> dict:
    """One run of a cell at its tiny size, without the chip and without
    the persistent compile cache."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    return harness.run(cell(workload), seed, seconds, trace,
                       t_start=time.time(), window_chunks=chunks,
                       check_device=False, log=lambda m: None)
