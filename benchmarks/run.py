"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  --full runs paper-scale streams;
the default fast mode (also spellable --fast, for CI symmetry) keeps the
whole suite CPU-friendly.  The vht suite includes the chunked-runtime
long-stream smoke (``chunked.vht-dense200-c50``: 10k steps through the
bounded-memory chunked driver, memory-ceiling guarded, midpoint
checkpoint resumed and verified exact, publishing its us-per-batch ratio
vs the monolithic dense-200 arm) and the ``chunked.overhead`` micro-arm
(the same pre-materialized stream through the monolithic scan and the
pipelined chunked driver; fails loudly when the ratio exceeds its
guard).  Suites that track a
before/after perf trajectory additionally write structured numbers to
BENCH_<suite>.json
(vht -> BENCH_vht.json, amrules -> BENCH_amrules.json, clustream ->
BENCH_clustream.json, ensemble -> BENCH_ensemble.json; --bench-json
relocates the VHT file for backward compatibility) so the trajectory is
tracked PR over PR.

--sharded forces 8 virtual host devices (the flag must land before jax
initializes, which is why the suite modules are imported lazily below)
and runs ONLY the sharded arms -- VAMR with its rule axis over 'model'
and OzaBag with its member axis over 'data' -- merging the resulting
``sharded.*`` arms into the existing BENCH json instead of replacing it.

  PYTHONPATH=src python -m benchmarks.run [--full|--fast] [--sharded] \
      [--only vht|amrules|clustream|ensemble|lm|kernels|serving|fleet]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

SHARDED_DEVICES = 8


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--fast", action="store_true",
                    help="fast mode (the default; overrides --full)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--sharded", action="store_true",
                    help="run the multi-device sharded arms on "
                         f"{SHARDED_DEVICES} forced host devices")
    ap.add_argument("--bench-json", default="BENCH_vht.json",
                    help="where to write the structured VHT numbers")
    ap.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="enable jax's persistent compilation cache at DIR "
                         "for the whole run and print the hit/miss split "
                         "at the end (second runs of the same suite skip "
                         "the XLA compiles)")
    args = ap.parse_args()
    fast = args.fast or not args.full

    if args.sharded:
        from repro.launch.mesh import force_host_devices
        if not force_host_devices(SHARDED_DEVICES):
            sys.exit("--sharded must set XLA_FLAGS before jax initializes "
                     "its backends; run in a fresh process")

    if args.compile_cache:
        from repro.runtime import compile_cache
        args.compile_cache = compile_cache.enable(args.compile_cache)

    from benchmarks import (amrules_benchmarks, clustream_benchmarks,
                            ensemble_benchmarks, fleet_benchmarks,
                            kernel_benchmarks, lm_roofline,
                            multihost_benchmarks, serving_benchmarks,
                            vht_benchmarks)

    suites = {
        "vht": vht_benchmarks,
        "amrules": amrules_benchmarks,
        "clustream": clustream_benchmarks,
        "ensemble": ensemble_benchmarks,
        "lm": lm_roofline,
        "kernels": kernel_benchmarks,
        "serving": serving_benchmarks,
        "fleet": fleet_benchmarks,
        "multihost": multihost_benchmarks,
    }
    if args.sharded:
        suites = {k: v for k, v in suites.items()
                  if k in ("amrules", "ensemble")}
    elif args.only is None:
        # the multihost suite spawns its own 2-process worker groups (and
        # a 1x8 reference process); run it only when asked for explicitly
        suites.pop("multihost")
    if args.only:
        if args.only not in suites:
            sys.exit(f"unknown suite {args.only!r} "
                     f"(available: {', '.join(suites)})")
        suites = {args.only: suites[args.only]}
    print("name,us_per_call,derived")
    failed = set()
    for name, mod in suites.items():
        try:
            if args.sharded:
                mod.main(fast=fast, sharded=True)
            else:
                mod.main(fast=fast)
        except Exception as e:  # keep the harness going, flag the suite
            failed.add(name)
            print(f"{name}.SUITE_FAILED,0,{type(e).__name__}:{e}",
                  flush=True)
    if args.compile_cache:
        from repro.runtime import compile_cache
        st = compile_cache.stats()
        print(f"compile_cache,{st['requests']},hits={st['hits']};"
              f"misses={st['misses']};dir={args.compile_cache}", flush=True)
    mode = "fast" if fast else "full"
    for name, mod in suites.items():
        bench = getattr(mod, "BENCH", None)
        # a failed suite's BENCH may be half-filled -- don't publish a
        # partial trajectory that looks complete
        if not bench or name in failed:
            continue
        # the VHT file keeps its historical fig89 schema and --bench-json
        # override; the other suites write {"arms": ...}
        if name == "vht":
            path, payload = args.bench_json, {"fig89": bench, "mode": mode}
        else:
            path = f"BENCH_{name}.json"
            payload = {"arms": bench, "mode": mode}
            # the sharded and regular arms are produced by different
            # processes (the device-count flag must precede jax init), so
            # each write preserves the other family's arms
            if os.path.exists(path):
                try:
                    with open(path) as f:
                        old = json.load(f)
                    if args.sharded:
                        old.setdefault("arms", {}).update(bench)
                        payload = old
                    else:
                        for k, v in old.get("arms", {}).items():
                            if k.startswith("sharded."):
                                payload["arms"].setdefault(k, v)
                except (OSError, ValueError):
                    pass
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {path}", flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
