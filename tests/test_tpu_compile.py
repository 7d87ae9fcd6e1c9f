"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e chip.

Interpret mode runs a kernel's body on the CPU but never shows it to the
TPU compiler, which is where block shapes that break the tiling rules,
unsupported primitives and over-sized VMEM use are refused.  Each test
lowers one kernel at a width the system runs and compiles it for a
described (not attached) v5e chip: the VHT counter and gain kernels at
the paper's dense-1000 deployment ([4095, 1000, 8, 2]) and the default
tree size ([255, 200, 8, 2]), the router for one deep tree and for an
ensemble of 20 members, and the rule-statistics kernel at the AMRules
benchmark width.  A test passes when the compiled program contains the
kernel (``tpu_custom_call``).

Describing the topology loads the TPU compiler library, so it happens
only inside the module fixture below, and the persistent compilation
cache is off while these compiles run (their entries could not be read
back without a chip).
"""

from functools import partial

import jax
import jax.numpy as jnp
import pytest

i32, f32 = jnp.int32, jnp.float32
B = 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


STATS = [(4095, 1000, 8, 2), (255, 200, 8, 2)]


@pytest.mark.parametrize("shape", STATS, ids=str)
def test_vht_stats_compiles_for_v5e(one_chip, shape):
    from repro.kernels.vht_stats.ops import stats_update
    N, m, nb, C = shape
    _compile(partial(stats_update, impl="pallas"), one_chip,
             (shape, f32), ((B,), i32), ((B, m), i32), ((B,), i32),
             ((B,), f32))


@pytest.mark.parametrize("shape", STATS, ids=str)
def test_split_gain_compiles_for_v5e(one_chip, shape):
    from repro.kernels.split_gain.ops import split_gain
    _compile(partial(split_gain, impl="pallas"), one_chip, (shape, f32))


@pytest.mark.parametrize("M,N,m", [(1, 4095, 1000), (20, 255, 200)],
                         ids=["one-tree", "ensemble-20"])
def test_tree_route_compiles_for_v5e(one_chip, M, N, m):
    from repro.kernels.tree_route.ops import tree_route
    _compile(partial(tree_route, max_depth=24, impl="pallas"), one_chip,
             ((M, N), i32), ((M, N), i32), ((M, N, 2), i32), ((B, m), i32))


@pytest.mark.parametrize("m", [40, 12])
def test_rule_stats_compiles_for_v5e(one_chip, m):
    from repro.kernels.rule_stats.ops import rule_stats_update
    shape = (65, m, 8, 3)        # max_rules=64 plus the default-rule row
    _compile(partial(rule_stats_update, impl="pallas"), one_chip,
             (shape, f32), ((B,), i32), ((B, m), i32), ((B, 3), f32))
