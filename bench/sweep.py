"""Find the serving cell's knee: the highest offered rate it sustains.

    python3 bench/sweep.py --workload vht-dense1000.serve --seed 1 \
        --seconds 10 --rates 40,60,80,100,120

Runs the cell once per rate in one process, each run exactly as
``bench/run.py`` would but with the traffic's ``rate_per_s`` replaced,
and prints one JSON line per rate: latency percentiles, the p95 of the
first and second half of the requests, and the server's counters.  A rate
is sustained when no request is rejected or shed and the second half's
p95 is no more than 1.5 times the first half's (no backlog growing
through the run).  The last line names the knee, the highest sustained
rate, and the cell's rate, 0.8 times the knee.  The cell's traffic file
keeps that number; this tool is run once, when the rate is set.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from bench import harness
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        cell = harness.Cell(args.workload,
                            predict_overrides={"rate_per_s": rate})
        out = harness.run(cell, args.seed, args.seconds, False,
                          t_start=time.time(),
                          log=lambda m: print(m, file=sys.stderr))
        srv, lat = out["report"]["server"], out["report"]["latency_ms"]
        rejected = (srv["rejected_overloaded"] + srv["shed"]
                    + srv["rejected_unavailable"])
        sustained = (rejected == 0 and out["failed"] == 0 and
                     lat["p95_second_half"] <= 1.5 * lat["p95_first_half"])
        if sustained:
            knee = rate
        print(json.dumps({"rate_per_s": rate, "sustained": sustained,
                          "correct": out["correct"], "latency_ms": lat,
                          "server": srv,
                          "train_instances_per_s": out["metrics"][
                              "train_instances_per_s"]["value"]}),
              flush=True)
    print(json.dumps({"knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else round(0.8 * knee, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
