"""Per-kernel allclose vs the pure-jnp oracle, swept over shapes/dtypes.

All Pallas kernels run in interpret=True on CPU (the kernel body executes
in Python); on TPU the same code lowers through Mosaic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.vht_stats.ops import stats_update
from repro.kernels.vht_stats.ref import stats_update_ref
from repro.kernels.split_gain.ops import split_gain
from repro.kernels.split_gain.ref import split_gain_ref
from repro.kernels.flash_attention.ops import flash_attention


# ------------------------------ vht_stats -----------------------------------

@pytest.mark.parametrize("N,m,nb,C,B", [
    (16, 8, 4, 2, 32),
    (32, 20, 8, 3, 64),
    (64, 33, 8, 7, 128),     # attr axis not a tile multiple
    (8, 5, 16, 2, 16),
])
def test_vht_stats_matches_ref(N, m, nb, C, B):
    key = jax.random.PRNGKey(N + m)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    stats = jax.random.uniform(k1, (N, m, nb, C)) * 5
    leaf = jax.random.randint(k2, (B,), 0, N)
    xbin = jax.random.randint(k3, (B, m), 0, nb)
    y = jax.random.randint(k4, (B,), 0, C)
    w = jnp.where(jnp.arange(B) % 3 == 0, 0.0, 1.0)  # mixed weights
    out = stats_update(stats, leaf, xbin, y, w, impl="pallas",
                       interpret=True)
    ref = stats_update_ref(stats, leaf, xbin, y, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_vht_stats_attr_tile_override():
    key = jax.random.PRNGKey(3)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    stats = jax.random.uniform(k1, (16, 12, 4, 2))
    leaf = jax.random.randint(k2, (32,), 0, 16)
    xbin = jax.random.randint(k3, (32, 12), 0, 4)
    y = jax.random.randint(k4, (32,), 0, 2)
    w = jnp.ones((32,))
    ref = stats_update_ref(stats, leaf, xbin, y, w)
    for tile in (4, 5, 12):      # including a non-divisor (padding path)
        out = stats_update(stats, leaf, xbin, y, w, impl="pallas",
                           attr_tile=tile, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


@pytest.mark.parametrize("impl", ["pallas", "segment", "onehot"])
def test_vht_stats_weight_zero_is_noop(impl):
    stats = jnp.ones((8, 4, 4, 2))
    out = stats_update(stats, jnp.zeros(16, jnp.int32),
                       jnp.zeros((16, 4), jnp.int32),
                       jnp.zeros(16, jnp.int32), jnp.zeros(16), impl=impl,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(stats))


# ------------------------------ split_gain ----------------------------------

@pytest.mark.parametrize("N,m,nb,C", [
    (16, 8, 4, 2),
    (33, 17, 8, 3),          # padding path
    (64, 32, 8, 7),
])
def test_split_gain_matches_ref(N, m, nb, C):
    key = jax.random.PRNGKey(N * m)
    stats = jax.random.uniform(key, (N, m, nb, C)) * 10
    out = split_gain(stats, impl="pallas", interpret=True)
    ref = split_gain_ref(stats)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_split_gain_empty_stats_invalid():
    g = split_gain(jnp.zeros((4, 3, 4, 2)), impl="pallas", interpret=True)
    assert float(g.max()) <= -1e29  # no valid threshold on empty stats


def test_split_gain_partial_blocks_match_ref():
    """Node and column tiles that do not divide the statistics: the
    out-of-range part of the last blocks is masked and never written."""
    from repro.kernels.split_gain.kernel import split_gain_pallas
    stats = jnp.floor(jax.random.uniform(jax.random.PRNGKey(5),
                                         (33, 17, 4, 2)) * 10)
    out = split_gain_pallas(stats, node_tile=8, col_tile=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(split_gain_ref(stats)),
                               atol=1e-5, rtol=1e-5)


def test_vht_stats_partial_node_block_is_exact():
    """More nodes than one node tile, not a tile multiple (the 4095-node
    deployment's shape class): integer counts stay exact."""
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    N, m, B = 600, 5, 32
    stats = jnp.floor(jax.random.uniform(ks[0], (N, m, 8, 2)) * 5)
    leaf = jax.random.randint(ks[1], (B,), 0, N).at[0].set(N - 1)
    xbin = jax.random.randint(ks[2], (B, m), 0, 8)
    y = jax.random.randint(ks[3], (B,), 0, 2)
    w = jnp.ones((B,))
    out = stats_update(stats, leaf, xbin, y, w, impl="pallas",
                       interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(stats_update_ref(stats, leaf, xbin, y, w)))


@pytest.mark.parametrize("moments,R,m,nb,C", [
    ("vht", 40, 24, 8, 2),        # class one-hot x weight
    ("amrules", 65, 32, 8, 3),    # (w, w*y, w*y^2), float moments
])
def test_rule_stats_packed_entry_equals_4d(moments, R, m, nb, C):
    """The packed entry ([R, m*bins*C] in and out, no reshape) computes
    exactly what the 4-D entry does, reshaped."""
    from repro.kernels.rule_stats.kernel import packs, rule_stats_pallas
    from repro.kernels.rule_stats.ops import rule_moments
    ks = jax.random.split(jax.random.PRNGKey(R + m), 5)
    B = 64
    stats = jnp.floor(jax.random.uniform(ks[0], (R, m, nb, C)) * 5)
    seg = jax.random.randint(ks[1], (B,), 0, R + 1)   # R = discarded
    xbin = jax.random.randint(ks[2], (B, m), 0, nb)
    w = jnp.where(jnp.arange(B) % 3 == 0, 0.0, 1.0)
    if moments == "vht":
        mom = jax.nn.one_hot(jax.random.randint(ks[3], (B,), 0, C), C) \
            * w[:, None]
    else:
        mom = rule_moments(jax.random.normal(ks[4], (B,)), w)
    assert packs(m, nb * C)
    want = rule_stats_pallas(stats, seg, xbin, mom, interpret=True)
    got = rule_stats_pallas(stats.reshape(R, -1), seg, xbin, mom,
                            interpret=True)
    assert got.shape == (R, m * nb * C)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want).reshape(R, -1))


def test_vht_stats_packed_entry_equals_4d():
    N, m, nb, C, B = 40, 24, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    stats = jnp.floor(jax.random.uniform(ks[0], (N, m, nb, C)) * 5)
    leaf = jax.random.randint(ks[1], (B,), 0, N)
    xbin = jax.random.randint(ks[2], (B, m), 0, nb)
    y = jax.random.randint(ks[3], (B,), 0, C)
    w = jnp.where(jnp.arange(B) % 3 == 0, 0.0, 1.0)
    want = stats_update(stats, leaf, xbin, y, w, impl="pallas",
                        interpret=True)
    got = stats_update(stats.reshape(N, -1), leaf, xbin, y, w,
                       impl="pallas", interpret=True, n_classes=C)
    assert got.shape == (N, m * nb * C)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want).reshape(N, -1))


def test_packed_entry_refuses_a_padded_width():
    """A width the kernel pads is not offered packed: padding every call
    would bring the relayout back."""
    from repro.kernels.rule_stats.kernel import packs, rule_stats_pallas
    N, m, nb, C, B = 8, 68, 8, 2, 64
    assert not packs(m, nb * C)
    with pytest.raises(ValueError, match="padding"):
        rule_stats_pallas(jnp.zeros((N, m * nb * C)),
                          jnp.zeros((B,), jnp.int32),
                          jnp.zeros((B, m), jnp.int32), jnp.zeros((B, C)),
                          interpret=True)


# --------------------------- flash_attention --------------------------------

@pytest.mark.parametrize("B,S,H,K,hd,dtype", [
    (2, 256, 4, 4, 64, jnp.float32),
    (2, 256, 4, 2, 64, jnp.float32),      # GQA
    (1, 512, 8, 1, 64, jnp.float32),      # MQA
    (2, 128, 4, 4, 128, jnp.bfloat16),
])
def test_flash_attention_matches_ref(B, S, H, K, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
    out = flash_attention(q, k, v, interpret=True, q_block=64, kv_block=64)
    ref = flash_attention(q, k, v, use_pallas=False)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64))
    k = jax.random.normal(ks[1], (2, 256, 2, 64))
    v = jax.random.normal(ks[2], (2, 256, 2, 64))
    out = flash_attention(q, k, v, interpret=True, q_block=64, kv_block=64, window=window)
    ref = flash_attention(q, k, v, use_pallas=False, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    out = flash_attention(q, k, v, interpret=True, q_block=64, kv_block=64, causal=False)
    ref = flash_attention(q, k, v, use_pallas=False, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)


# --------------------------- selective_scan ---------------------------------

from repro.kernels.selective_scan.ops import selective_scan


@pytest.mark.parametrize("B,c,dI,N", [
    (2, 32, 128, 16),
    (1, 16, 512, 16),
    (4, 64, 256, 8),
])
def test_selective_scan_matches_ref(B, c, dI, N):
    ks = jax.random.split(jax.random.PRNGKey(B * c), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, c, dI))) * 0.1
    x = jax.random.normal(ks[1], (B, c, dI))
    Bm = jax.random.normal(ks[2], (B, c, N)) * 0.5
    Cm = jax.random.normal(ks[3], (B, c, N)) * 0.5
    A = -jnp.exp(jax.random.normal(ks[4], (dI, N)) * 0.3)
    h0 = jax.random.normal(ks[5], (B, dI, N)) * 0.1
    y1, h1 = selective_scan(dt, x, Bm, Cm, A, h0, interpret=True)
    y2, h2 = selective_scan(dt, x, Bm, Cm, A, h0, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-4)


def test_selective_scan_state_chaining():
    """Scanning two half-chunks with state carry == one full chunk."""
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    B, c, dI, N = 2, 32, 64, 8
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, c, dI))) * 0.1
    x = jax.random.normal(ks[1], (B, c, dI))
    Bm = jax.random.normal(ks[2], (B, c, N)) * 0.5
    Cm = jax.random.normal(ks[3], (B, c, N)) * 0.5
    A = -jnp.exp(jax.random.normal(ks[4], (dI, N)) * 0.3)
    h0 = jnp.zeros((B, dI, N))
    y_full, h_full = selective_scan(dt, x, Bm, Cm, A, h0,
                                    interpret=True)
    h = h0
    ys = []
    for s in (slice(0, 16), slice(16, 32)):
        y, h = selective_scan(dt[:, s], x[:, s], Bm[:, s], Cm[:, s], A, h,
                              interpret=True)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(y_full), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full), atol=2e-4)
