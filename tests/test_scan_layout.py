"""The VHT's scan layout: a chunk program carries the statistics packed 2-D
(``VHT.to_scan``) and restores them at its exit (``VHT.from_scan``).

Only the layout changes, so the chunked run must equal the per-step
``LocalEngine`` run bit for bit -- every state array and every step's
metrics -- on full chunks and on the masked tail chunk, for each variant
(local, wok, wk(z)) on the Pallas kernel (in interpret mode).  The run's
report counts the chunks that ran packed.  A width the kernel would pad,
and the XLA segment scatter, stay 4-D and count none.
"""

import dataclasses
import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engines import JitEngine, LocalEngine
from repro.core.evaluation import ChunkedPrequentialEvaluation, stack_outputs
from repro.data.generators import RandomTreeGenerator, bin_numeric
from repro.data.pipeline import ChunkedStream
from repro.ml import htree
from repro.ml.htree import TreeConfig
from repro.ml.vht import VHT, VHTConfig

B, CHUNK, STEPS = 256, 4, 11        # 2 full chunks and a tail of 3 steps
N_CHUNKS = -(-STEPS // CHUNK)

# name: (attributes, stats impl, split delay, wk(z) buffer, packed chunks)
CASES = {
    "pallas-local": (24, "pallas", 0, 0, N_CHUNKS),
    "pallas-wok": (24, "pallas", 3, 0, N_CHUNKS),
    "pallas-wkz": (24, "pallas", 3, 32, N_CHUNKS),
    "segment-local": (24, "segment", 0, 0, 0),
    "padded-width": (68, "pallas", 0, 0, 0),   # the kernel would pad 68
}


def _tree(m, impl, delay, buf):
    # a small check tile, so steps take both the gathered split check and
    # its full fallback
    return TreeConfig(n_attrs=m, max_nodes=63, n_min=30, split_delay=delay,
                      buffer_size=buf, stats_impl=impl, check_tile=2)


@functools.lru_cache(maxsize=None)
def _step_loop(m, delay, buf):
    """The per-step LocalEngine run with the segment scatter: every
    implementation gives the same counts, so the Pallas cases share it."""
    vht = VHT(VHTConfig(_tree(m, "segment", delay, buf)))
    eng = LocalEngine()
    states, outs = eng.run_stream(vht, eng.init(vht, jax.random.PRNGKey(0)),
                                  ChunkedStream(_stream(m), CHUNK))
    return states["vht"], stack_outputs(outs)["metrics"]


def _stream(m):
    gen = RandomTreeGenerator(n_cat=m // 2, n_num=m - m // 2, depth=3,
                              seed=2)
    xs, ys = [], []
    for k in jax.random.split(jax.random.PRNGKey(m), STEPS):
        x, y = gen.sample(k, B)
        xs.append(bin_numeric(x, 8))
        ys.append(y)
    return {"x": jnp.stack(xs), "y": jnp.stack(ys)}


def _assert_equal(a, b):
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree.leaves(b)
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=str(path))


@pytest.mark.parametrize("case", CASES)
def test_chunked_vht_with_packed_stats_equals_step_loop(case, monkeypatch):
    m, impl, delay, buf, packed = CASES[case]
    if impl == "pallas":
        from repro.kernels.vht_stats import ops
        monkeypatch.setattr(ops, "stats_update",
                            partial(ops.stats_update, interpret=True))
    want, want_metrics = _step_loop(m, delay, buf)
    assert int(want["n_splits"]) > 0

    # the evaluation drives JitEngine.run_stream_chunked chunk by chunk
    outs = []
    eng = JitEngine()
    res = ChunkedPrequentialEvaluation(
        VHT(VHTConfig(_tree(m, impl, delay, buf))),
        ChunkedStream(_stream(m), CHUNK), engine=eng,
        on_chunk=lambda o, chunk, carry: outs.append(o["metrics"]),
        key=jax.random.PRNGKey(0)).run(resume=False)
    got = res.extra["carry"]["states"]["vht"]
    assert got["stats"].shape == want["stats"].shape
    _assert_equal(got, want)
    _assert_equal(jax.tree.map(lambda *x: jnp.concatenate(x), *outs),
                  want_metrics)
    assert res.extra["report"]["packed_chunks"] == packed
    assert eng.packed_chunks == packed


def test_scan_layout_round_trips():
    tc = TreeConfig(n_attrs=24, max_nodes=15, stats_impl="pallas")
    vht = VHT(VHTConfig(tc))
    st = vht.init()
    st["stats"] = jnp.arange(st["stats"].size, dtype=jnp.float32).reshape(
        st["stats"].shape)
    packed = vht.to_scan(st)
    assert packed["stats"].shape == (15, 24 * 8 * 2)
    _assert_equal(vht.from_scan(packed), st)
    for other in (dataclasses.replace(tc, n_attrs=68),
                  dataclasses.replace(tc, stats_impl="segment")):
        learner = VHT(VHTConfig(other))
        assert learner.to_scan(learner.init())["stats"].ndim == 4


@pytest.mark.parametrize("n", [0, 1, 2, 5])
def test_packed_split_check_and_apply_equal_4d(n):
    """Split check and split apply on packed statistics against the same
    state 4-D, with n leaves due and splitting: none, within the check
    tile of 2 (gathered rows), and beyond it (every row)."""
    tc = TreeConfig(n_attrs=24, max_nodes=31, n_min=10, check_tile=2)
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    st = htree.init_tree(tc)
    st["stats"] = jnp.floor(jax.random.uniform(ks[0], st["stats"].shape)
                            * 20)
    st["class_counts"] = st["stats"][:, 0].sum(1)
    st["n_total"] = st["class_counts"].sum(1)
    st["n_nodes"] = jnp.asarray(11, jnp.int32)
    rows = jax.random.permutation(ks[1], 11)[:n]
    st["since_attempt"] = st["since_attempt"].at[rows].set(10.0)
    packed = {**st, "stats": st["stats"].reshape(31, -1)}

    decided = htree.decide_splits(st, tc)
    _assert_equal(htree.decide_splits(packed, tc), decided)
    mask = jnp.zeros((31,), bool).at[rows].set(True)
    attr = jax.random.randint(ks[2], (31,), 0, 24)
    want = htree.apply_splits(st, mask, attr, attr % 8, tc)
    got = htree.apply_splits(packed, mask, attr, attr % 8, tc)
    assert got[0]["stats"].shape == (31, 24 * 8 * 2)
    _assert_equal({**got[0], "stats": htree.unpacked(got[0]["stats"], tc)},
                  want[0])
    _assert_equal(got[1], want[1])
