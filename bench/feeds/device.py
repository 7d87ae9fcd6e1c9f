"""The device feed: every chunk is generated on the device.

``bench_stream_gen(key, i)`` draws chunk ``i`` (``chunk_len``
micro-batches of ``batch`` instances) from the run's key with the
configuration's own stream (``bench/gen/``).  The key is an argument, so
every seed runs the same compiled program, and the trace finds it by its
name.  On a cell's mesh every chunk is made there, replicated over its
chips (the instances of a key-grouped topology are replicated anyway),
so none is made on one chip and copied.  A traffic mix names its feed
with ``feed``; another feed (chunks made on the host, say) is another
module here with the same ``chunk_fn``.
"""

from __future__ import annotations


def chunk_fn(cell, key, mesh=None):
    """``chunk(i)``: chunk ``i`` of the run's stream, ``{"x", "y"}``
    stacked over the chunk's steps, on the device, or replicated over
    ``mesh``."""
    import jax
    import jax.numpy as jnp
    cfg = cell.cfg
    L, B, nb = cfg["chunk_len"], cfg["batch"], cfg["n_bins"]
    gen = cell.family.stream(cfg)

    def bench_stream_gen(key, i):
        ks = jax.random.split(jax.random.fold_in(key, i), L)
        x, y = jax.vmap(lambda k: gen.sample_binned(k, B, nb))(ks)
        return {"x": x, "y": y}

    if mesh is None:
        gen_fn = jax.jit(bench_stream_gen)
    else:
        from jax.sharding import NamedSharding, PartitionSpec
        gen_fn = jax.jit(bench_stream_gen,
                         out_shardings=NamedSharding(mesh, PartitionSpec()))
    return lambda i: gen_fn(key, jnp.asarray(i, jnp.int32))
