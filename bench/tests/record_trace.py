"""Record the small chip trace the trace tests read.

    python3 bench/tests/record_trace.py [out_dir]

Runs two chunks of ``vht-dense1000.train`` with the profiler on and keeps
the trace as ``bench/tests/data/vht_2chunks.xplane.pb`` with the
reduction's numbers beside it (``vht_2chunks.json``), for the tests to
check the reduction against.  Needs the chip.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402

DATA = Path(__file__).parent / "data"


def main() -> int:
    out_dir = Path(sys.argv[1]) if len(sys.argv) > 1 else DATA
    out_dir.mkdir(parents=True, exist_ok=True)
    out = harness.run(harness.Cell("vht-dense1000.train"), 11, 10.0, True,
                      t_start=time.time(), window_chunks=2,
                      keep_trace=out_dir / "vht_2chunks.xplane.pb")
    keep = {k: out[k] for k in ("correct", "metrics", "device", "breakdown")}
    keep["steps"] = out["report"]["window_chunks"] * harness.Cell(
        "vht-dense1000.train").cfg["chunk_len"]
    (out_dir / "vht_2chunks.json").write_text(json.dumps(keep, indent=1) + "\n")
    print(json.dumps(keep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
