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
kernel (``tpu_custom_call``).  One more compiles the bare VHT's whole
chunk program at dense-1000, as the engine builds it, and checks that no
step of its scan relayouts the 262 MB statistics tensor.

Describing the topology loads the TPU compiler library, so it happens
only inside the module fixture below, and the persistent compilation
cache is off while these compiles run (their entries could not be read
back without a chip).
"""

import re
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


def _called(hlo: str, roots) -> dict[str, list[str]]:
    """The computations of a compiled HLO module reachable from ``roots``
    (while bodies, conditional branches, fusions, calls), by name."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
    seen, todo = {}, list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen[name] = comps[name]
        for line in comps[name]:
            todo += re.findall(r"(?:calls|to_apply|body|condition|"
                               r"true_computation|false_computation)"
                               r"=%?([\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                    line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def test_vht_chunk_program_keeps_stats_layout_for_v5e(one_chip,
                                                      monkeypatch):
    """The bare VHT's chunk program at dense-1000, with the Pallas paths
    the chip takes: inside the scan's while loop no copy, reshape or
    transpose produces a statistics-sized array -- the statistics are
    packed once at the program's entry and unpacked once at its exit."""
    from repro.core.engines import JitEngine
    from repro.ml.htree import TreeConfig
    from repro.ml.vht import VHT, VHTConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    N, m, T = 4095, 1000, 50
    vht = VHT(VHTConfig(TreeConfig(n_attrs=m, max_nodes=N, n_min=200)))
    eng = JitEngine()
    fn = eng._chunk_full_fn(eng._as_topology(vht), fused_boundary=False)
    carry = {"states": {"vht": jax.eval_shape(vht.init)}, "feedback": {}}
    payloads = {"x": jax.ShapeDtypeStruct((T, B, m), i32),
                "y": jax.ShapeDtypeStruct((T, B), i32)}
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    hlo = fn.lower(on_chip(carry), on_chip(payloads)).compile().as_text()
    assert "tpu_custom_call" in hlo
    bodies = re.findall(r" while\(.*body=%?([\w.\-]+)", hlo)
    assert bodies
    relayout = re.compile(r"= f32\[(4095,1000,8,2|4095,16000)\]\{[^}]*\} "
                          r"(copy|reshape|transpose)\(")
    inside = [line.strip()[:120]
              for lines in _called(hlo, bodies).values()
              for line in lines if relayout.search(line)]
    assert inside == []
