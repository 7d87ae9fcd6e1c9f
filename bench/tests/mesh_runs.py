"""Runs of a cell laid over four chips, on four virtual CPU devices.

    python3 bench/tests/mesh_runs.py

Start it in a fresh process with four CPU devices forced
(``repro.launch.mesh.force_host_devices``); ``test_bench_mesh.py`` does,
and reads the one JSON line it prints.  A scratch benchmark -- the tiny
VHT sizes of ``tiny.py`` laid over ``{"model": 4}``, with the program's
VHT topology and its reference (``topology_family.py``) -- runs through
``harness.run`` on four devices, the same on one device, and on four
devices again with a fault planted in the statistics' update.  Beside
them, the part-by-part comparison against a whole-array one on arrays
sharded over the four devices.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from bench.tests import tiny, topology_family  # noqa: E402

SEED = 2**31 + 11
CHUNKS = 10
FOUR = {"model": 4}


def scratch_benchmark() -> dict:
    bm = harness.load_json(harness.ROOT / "BENCHMARK.json")
    bm["workloads"] = [
        {"name": "vht-topology.four", "config": "vht-dense1000",
         "traffic": "train", "chips": 4, "why": "test"},
        {"name": "vht-topology.one", "config": "vht-dense1000",
         "traffic": "train", "chips": 1, "why": "test"}]
    return bm


def cell(workload: str, mesh: dict | None) -> harness.Cell:
    c = harness.Cell(workload, benchmark=scratch_benchmark(),
                     overrides=dict(tiny.SMALL["vht-dense1000"],
                                    mesh=mesh))
    c.family = topology_family
    return c


def run(c: harness.Cell, spy: dict) -> dict:
    """One run; ``spy`` gets the engine, the chunks' placement, the
    program's host state and the reference's parts as the harness made
    them."""
    make_engine, make_feed = harness.make_engine, harness.feed
    compare, program_state = harness.compare, topology_family.program_state

    def engine(mesh):
        e = make_engine(mesh)
        spy["engine"] = type(e).__name__
        spy["engine_mesh"] = dict(e.mesh.shape) if mesh is not None else None
        return e

    def feed(cell):
        mod = make_feed(cell)

        class Spied:
            @staticmethod
            def chunk_fn(cell, key, mesh=None):
                draw = mod.chunk_fn(cell, key, mesh)

                def spied(i):
                    out = draw(i)
                    x = out["x"]
                    spy["chunk_devices"] = len(x.sharding.device_set)
                    spy["chunk_replicated"] = x.sharding.is_fully_replicated
                    return out
                return spied
        return Spied

    def spied_state(states):
        spy["stats_shards"] = [tuple(s.data.shape) for s in
                               states["local-statistic"]["stats"]
                               .addressable_shards]
        return program_state(states)

    def spied_compare(fam, prog, ref):
        spy["prog"] = {k: v.tolist() for k, v in prog.items()}
        spy["ref_stats_shards"] = [
            tuple(s.data.shape) for s in ref["stats"].addressable_shards]
        return compare(fam, prog, ref)

    harness.make_engine, harness.feed = engine, feed
    harness.compare = spied_compare
    topology_family.program_state = spied_state
    try:
        return harness.run(c, SEED, 1.0, False, t_start=time.time(),
                           window_chunks=CHUNKS, check_device=False,
                           log=lambda m: None)
    finally:
        harness.make_engine, harness.feed = make_engine, make_feed
        harness.compare = compare
        topology_family.program_state = program_state


def planted(name: str):
    """A fault in the local statistics' update, which on a mesh runs on
    each device's attribute shard."""
    from repro.ml.vht import LocalStatisticProcessor
    update = LocalStatisticProcessor._update

    def unchanged(self, stats, ev):
        return stats

    def half_batch(self, stats, ev):
        h = ev["y"].shape[0] // 2
        return update(self, stats, {k: v[:h] for k, v in ev.items()})

    return LocalStatisticProcessor, update, {
        "unchanged": unchanged, "half_batch": half_batch}[name]


def sharded_compare() -> list:
    """(part by part, whole arrays) of ``harness.compare`` on arrays
    sharded over the four devices, with differences planted."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((4,), ("model",))
    rng = np.random.default_rng(5)

    class Fam:
        EXACT = ("a", "b", "c")
        FLOAT = ("f", "g")

    specs = {"a": P(None, "model"), "b": P(), "c": P("model"),
             "f": P("model", None), "g": P(None, "model")}
    shapes = {"a": (6, 8), "b": (5,), "c": (12, 3), "f": (8, 4), "g": (3, 8)}
    out = []
    for trial in range(3):
        ref = {k: rng.integers(0, 9, shapes[k]).astype(np.float32)
               for k in specs}
        prog = {k: v.copy() for k, v in ref.items()}
        for k in prog:
            flat = prog[k].reshape(-1)
            flat[rng.choice(flat.size, trial + 1, replace=False)] += \
                rng.uniform(0.5, 3.0, trial + 1).astype(np.float32)
        on_mesh = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                   for k, v in ref.items()}
        whole = whole_compare(Fam, prog, ref)
        out.append([list(harness.compare(Fam, prog, on_mesh)), list(whole)])
    return out


def whole_compare(fam, prog, ref):
    """The comparison on whole host arrays: the definition the part by
    part one has to meet."""
    import numpy as np
    mismatch = sum(int(np.count_nonzero(np.asarray(prog[k]) != ref[k]))
                   for k in fam.EXACT)
    scales = [float(np.max(np.abs(ref[k]))) for k in fam.FLOAT]
    floor = float(np.median(scales))
    gap = max(float(np.max(np.abs(np.asarray(prog[k], np.float64)
                                  - np.asarray(ref[k], np.float64))))
              / max(s, floor, 1e-30) for k, s in zip(fam.FLOAT, scales))
    return mismatch, gap


def main() -> int:
    import jax
    if jax.device_count() < 4:
        print(f"needs four devices, found {jax.device_count()}",
              file=sys.stderr)
        return 1
    harness.enable_compile_cache = lambda: None
    result = {}
    for name, workload, mesh in (("four", "vht-topology.four", FOUR),
                                 ("one", "vht-topology.one", None)):
        spy = {}
        out = run(cell(workload, mesh), spy)
        result[name] = {"correct": out["correct"], "checks": out["checks"],
                        "device": out["device"], "report": out["report"],
                        **spy}
    for fault in ("unchanged", "half_batch"):
        klass, original, broken = planted(fault)
        klass._update = broken
        try:
            out = run(cell("vht-topology.four", FOUR), {})
        finally:
            klass._update = original
        result[fault] = {"correct": out["correct"], "checks": out["checks"]}
    result["sharded_compare"] = sharded_compare()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
