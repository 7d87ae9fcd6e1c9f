"""Least work of one prequential VHT step, from shapes.

Per step of B instances of m attributes (x: B x m int32 bins, y: B int32
classes), any correct implementation must

* read the step's input once: 4 B (m + 1) bytes;
* increment, for every instance, one counter per attribute: B m
  additions; the counters live in HBM (the statistics, N x m x bins x C
  float32, do not fit in on-chip memory), and at least the m counters of
  one (leaf, class) pair change in a step, each read and written once:
  2 x 4 m bytes.

Everything else -- routing, the split check, the layout of the counters,
a batch's counters touching more than one leaf -- is left out, so the
count is a lower bound on what any implementation moves and computes.
"""


def step(cfg: dict) -> tuple[float, float]:
    """(bytes, operations) of one step."""
    B = cfg["batch"]
    m = cfg["n_nominal"] + cfg["n_numeric"]
    return 4.0 * B * (m + 1) + 8.0 * m, float(B * m)
