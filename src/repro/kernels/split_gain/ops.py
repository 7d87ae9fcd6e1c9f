"""Public dispatcher for the split-gain reduction.

impl="auto" routes through the fused Pallas kernel on TPU (prefix sums +
entropies + weighted gain in one VMEM-resident pass) and through the
pure-jnp reference elsewhere; the two agree to f32 rounding.  Off TPU
the kernel runs only in interpret mode, when the caller asks for it.
"""

from __future__ import annotations

from functools import partial

import jax

from repro.distributed.sharding import kernel_mesh, run_replicated
from repro.kernels.split_gain.kernel import split_gain_pallas
from repro.kernels.split_gain.ref import split_gain_ref


def split_gain(stats, *, impl: str = "auto", interpret: bool = False):
    """Information gain for every (node, attr, threshold-bin):
    [N, m, bins, C] -> [N, m, bins].  Under a multi-device mesh the
    kernel runs replicated inside a shard_map (``run_replicated``)."""
    return _split_gain(stats, impl=impl, interpret=interpret,
                       mesh=kernel_mesh())


@partial(jax.jit, static_argnames=("impl", "interpret", "mesh"))
def _split_gain(stats, *, impl, interpret, mesh):
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return split_gain_ref(stats)
    if impl != "pallas":
        raise ValueError(f"unknown split-gain impl {impl!r}")
    return run_replicated(partial(split_gain_pallas, interpret=interpret),
                          mesh, stats)
