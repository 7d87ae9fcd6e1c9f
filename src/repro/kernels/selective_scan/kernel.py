"""Pallas kernel: mamba-1 selective scan, TPU-native.

The GPU mamba kernel leans on warp shuffles and shared-memory scans; the
TPU adaptation (DESIGN.md hardware-adaptation): tile the INNER-CHANNEL axis
across the grid, keep the [dT, N] state resident in VMEM/VREGs, and walk
the time axis sequentially in-kernel -- the VPU retires the dA*h + dBx
update at full width while the discretization tensors (the 17 TB/step
blow-up of the XLA path at train_4k) never exist in HBM.

Grid = (batch, channel tiles); one kernel instance owns its [dT, N] state
for the whole chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

f32 = jnp.float32


def _kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, hT_ref):
    c_len = dt_ref.shape[0]
    A = a_ref[...].astype(f32)                        # [dT, N]

    def step(t, h):
        dt_t = dt_ref[t].astype(f32)                  # [dT]
        x_t = x_ref[t].astype(f32)                    # [dT]
        b_t = b_ref[t].astype(f32)                    # [N]
        c_t = c_ref[t].astype(f32)                    # [N]
        dA = jnp.exp(dt_t[:, None] * A)               # [dT, N]
        dBx = (dt_t * x_t)[:, None] * b_t[None, :]
        h = dA * h + dBx
        y = (h * c_t[None, :]).sum(-1)                # [dT]
        y_ref[pl.ds(t, 1), :] = y[None].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, c_len, step, h0_ref[...].astype(f32))
    hT_ref[...] = h.astype(hT_ref.dtype)


def selective_scan_pallas(dt, x, Bm, Cm, A, h0, *, channel_tile: int = 0,
                          interpret: bool = False):
    """dt/x: [B,c,dI]; Bm/Cm: [B,c,N]; A: [dI,N]; h0: [B,dI,N]."""
    B, c, dI = dt.shape
    N = A.shape[1]
    dT = channel_tile or min(dI, 512)
    assert dI % dT == 0, (dI, dT)

    y, hT = pl.pallas_call(
        _kernel,
        grid=(B, dI // dT),
        in_specs=[
            pl.BlockSpec((None, c, dT), lambda b, j: (b, 0, j)),   # dt
            pl.BlockSpec((None, c, dT), lambda b, j: (b, 0, j)),   # x
            pl.BlockSpec((None, c, N), lambda b, j: (b, 0, 0)),    # B
            pl.BlockSpec((None, c, N), lambda b, j: (b, 0, 0)),    # C
            pl.BlockSpec((dT, N), lambda b, j: (j, 0)),            # A
            pl.BlockSpec((None, dT, N), lambda b, j: (b, j, 0)),   # h0
        ],
        out_specs=[
            pl.BlockSpec((None, c, dT), lambda b, j: (b, 0, j)),   # y
            pl.BlockSpec((None, dT, N), lambda b, j: (b, j, 0)),   # hT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, c, dI), dt.dtype),
            jax.ShapeDtypeStruct((B, dI, N), f32),
        ],
        interpret=interpret,
    )(dt, x, Bm, Cm, A, h0)
    return y, hT
