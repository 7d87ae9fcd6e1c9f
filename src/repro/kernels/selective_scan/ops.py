"""Public jit'd wrapper for the selective-scan kernel."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.selective_scan.kernel import selective_scan_pallas
from repro.kernels.selective_scan.ref import selective_scan_ref


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def selective_scan(dt, x, Bm, Cm, A, h0, *, use_pallas: bool = True,
                   interpret: bool = False):
    if not use_pallas:
        return selective_scan_ref(dt, x, Bm, Cm, A, h0)
    return selective_scan_pallas(dt, x, Bm, Cm, A, h0, interpret=interpret)
