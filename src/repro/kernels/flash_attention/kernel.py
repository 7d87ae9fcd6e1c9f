"""Pallas kernel: block-wise causal flash attention (TPU prefill path).

Grid = (batch*heads, q blocks).  Each program holds one q tile in VMEM and
streams kv tiles with an online-softmax running (max, sum, acc) -- the
probability tile NEVER touches HBM, which removes the ~10x memory-bound
elementwise traffic the XLA reference path pays (see EXPERIMENTS.md
section Perf).  Block sizes default to (512 q x 512 kv x hd), MXU-aligned.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

f32 = jnp.float32
NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, kv_block, causal, window,
            sm_scale, seq_k):
    qb = q_ref.shape[0]
    hd = q_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[...].astype(f32) * sm_scale
    qpos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kv_block), 0)

    nk = seq_k // kv_block

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(ki * kv_block, kv_block), :]
        v = v_ref[pl.ds(ki * kv_block, kv_block), :]
        s = jax.lax.dot_general(q, k.astype(f32), (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)  # [qb, kvb]
        kpos = ki * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (qb, kv_block), 1)
        mask = jnp.ones((qb, kv_block), bool)
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v.astype(f32), (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        return m_new, l_new, acc_new

    m0 = jnp.full((qb,), NEG, f32)
    l0 = jnp.zeros((qb,), f32)
    a0 = jnp.zeros((qb, hd), f32)
    if causal:
        # skip kv blocks strictly above the causal frontier of this q tile
        hi = jnp.minimum((qi + 1) * qb + kv_block - 1, seq_k) // kv_block
    else:
        hi = nk
    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=0,
                           q_block=512, kv_block=512, interpret=False):
    """q: [B,S,H,hd]; k/v: [B,T,H,hd] (GQA expanded by the wrapper)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    assert S % q_block == 0 and T % kv_block == 0, (S, T, q_block, kv_block)
    sm_scale = 1.0 / math.sqrt(hd)

    qr = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, T, hd)

    kern = functools.partial(
        _kernel, kv_block=kv_block, causal=causal, window=window,
        sm_scale=sm_scale, seq_k=T)
    out = pl.pallas_call(
        kern,
        grid=(B * H, S // q_block),
        in_specs=[
            pl.BlockSpec((None, q_block, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, T, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, T, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, q_block, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
