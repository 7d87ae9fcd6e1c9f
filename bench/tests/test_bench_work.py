"""The least work of a step, from shapes, never exceeds what the plain
reference moves and computes for the same step."""

import math

import pytest

from bench import harness


@pytest.mark.parametrize("workload", ["vht-dense1000.train",
                                      "vamr-waveform40.train"])
def test_work_is_a_lower_bound_on_the_reference(workload):
    import jax
    import jax.numpy as jnp
    cell = harness.Cell(workload)
    cfg = cell.cfg
    nbytes, flops = cell.work().step(cfg)
    assert nbytes > 0 and flops > 0
    state = jax.eval_shape(lambda: cell.family.ref_init(cfg))
    state_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    B = cfg["batch"]
    m = cell.family.sizes(cfg)["n_attrs"]
    # the reference reads the step's input and reads and writes its state
    assert nbytes <= 4 * B * (m + 1) + 2 * state_bytes
    # it adds at least one one-hot product per (instance, attribute)
    assert flops <= 2 * B * m * 3 * max(
        cfg.get("max_nodes", 1) * cfg.get("n_classes", 1),
        cfg.get("max_rules", 1) + 1)
