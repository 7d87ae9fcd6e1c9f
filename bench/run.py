"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, its traffic
mix and its metrics are found by name through ``BENCHMARK.json`` (see
``bench/harness.py``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number the correctness check compared, beside its limit.  The same
numbers are the last lines of standard error.  Without the chips the cell
asks for, the run prints no result and exits with 1.
"""

import time

T_START = time.time()        # set-up is timed from here

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    # the TPU runtime's logs stay inside the checkout
    logs = harness.CACHE / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))
    cell = harness.Cell(args.workload)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START,
                          log=lambda m: print(m, file=sys.stderr, flush=True))
    except harness.NoDevice as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    print(f"correct: {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
