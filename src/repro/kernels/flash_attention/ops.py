"""Public jit'd wrapper: GQA expansion + Pallas flash attention."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "use_pallas",
                                   "interpret", "q_block", "kv_block"))
def flash_attention(q, k, v, *, causal=True, window=0, use_pallas=True,
                    interpret=False, q_block=512, kv_block=512):
    """q: [B,S,H,hd]; k/v: [B,T,K,hd] with H = K*G.

    The wrapper expands GQA kv heads (on TPU the kernel would index the
    shared kv head per q-head group instead of materializing; the
    expansion keeps the validation path simple).
    """
    K = k.shape[2]
    H = q.shape[2]
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if not use_pallas:
        return attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_block=q_block, kv_block=kv_block,
                                  interpret=interpret)
