"""The control of the correctness check: the plain reference in a lower
precision, put in the program's place.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--chunks N]

The configurations state float32 counters and moments; the control runs
the same reference with them in bfloat16, over the same stream the cell's
window drives (``--chunks``, default the chunks of a window of
``run_seconds``), and compares it
with the float32 reference exactly as a run compares the program.  It
prints each compared number per seed: the readings that set the upper
end of each limit.  A control reading at or below a limit would mean the
limit cannot tell a float32 learner from a bfloat16 one.  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def readings(cell, seed: int, n_chunks: int) -> dict:
    """Compared numbers of the bfloat16 reference against the float32 one
    on the cell's stream (served answers: requests at the mix's rate,
    answered from each chunk's state)."""
    import jax.numpy as jnp
    import numpy as np

    from bench import harness
    fam, cfg = cell.family, cell.cfg
    L, B = cfg["chunk_len"], cfg["batch"]
    key = harness.seed_key(seed)
    chunk = harness.feed(cell).chunk_fn(cell, key)

    rows = None
    predict = cell.traffic.get("predict")
    if predict:
        rows = harness.request_rows(cell, key, 64)
    truth, ctrl = fam.ref_init(cfg), fam.ref_init(cfg, jnp.bfloat16)
    t_total = c_total = 0.0
    served_wrong = 0
    for c in range(n_chunks):
        p = chunk(c)
        truth, per_t = fam.ref_chunk(truth, p["x"], p["y"], cfg)
        ctrl, per_c = fam.ref_chunk(ctrl, p["x"], p["y"], cfg)
        t_total += np.asarray(per_t, np.float64).sum()
        c_total += np.asarray(per_c, np.float64).sum()
        if rows is not None:
            want = np.asarray(fam.ref_predict(truth, rows, cfg))
            got = np.asarray(fam.ref_predict(ctrl, rows, cfg))
            served_wrong += int(np.count_nonzero(got != want))
    view = lambda s: {k: np.asarray(v) for k, v in fam.ref_view(s).items()}
    mismatch, gap = harness.compare(fam, view(ctrl), view(truth))
    seen = float(n_chunks * L * B)
    name, mgap = fam.metric_gap(c_total / seen, t_total, seen)
    out = {"state_mismatch": mismatch, name: mgap}
    if fam.FLOAT:
        out["float_gap"] = gap
    if rows is not None:
        out["served_wrong"] = served_wrong
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--chunks", type=int, default=None)
    args = ap.parse_args(argv)
    from bench import harness
    harness.enable_compile_cache()
    harness.require_chips(1)
    cell = harness.Cell(args.workload)
    n = args.chunks or round(harness.load_json(harness.ROOT / "BENCHMARK.json")
                             ["run_seconds"] * cell.cfg["chunks_per_s"])
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(cell, seed, n)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "chunks": n, "control": r,
                          "limits": cell.cfg["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
