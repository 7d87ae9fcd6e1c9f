"""Least work of one prequential VAMR step, from shapes.

Per step of B instances of m attributes (x: B x m int32 bins, y: B
float32 targets), any correct implementation must

* read the step's input once: 4 B (m + 1) bytes;
* add each instance's (1, y, y^2) to the moments of one (rule, attribute,
  bin) per attribute: 3 B m additions.

The rule set and its statistics (R x m x bins x 3 float32, about 250 KB)
can stay in on-chip memory across steps, so no state traffic is counted:
the count is a lower bound on what any implementation moves and computes.
"""


def step(cfg: dict) -> tuple[float, float]:
    """(bytes, operations) of one step."""
    B = cfg["batch"]
    m = cfg["n_signal"] + cfg["n_noise"]
    return 4.0 * B * (m + 1), 3.0 * B * m
