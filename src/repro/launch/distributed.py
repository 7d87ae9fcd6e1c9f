"""Multi-process (multi-host) process-group runtime.

SAMOA's core claim is that ONE streaming topology spans a cluster of
workers.  This module is the process-group wiring that makes the fused
chunk program actually span processes:

  * :func:`initialize` -- bootstrap ``jax.distributed`` for one worker
    (coordinator address, process index/count), forcing the host device
    count when asked and selecting the gloo cross-process collective
    backend for CPU devices BEFORE the jax backend initializes (both are
    read exactly once).  The platform is the caller's: the test launcher
    sets ``JAX_PLATFORMS=cpu`` in each worker's environment.
  * :func:`init_from_env` -- the same, driven by ``REPRO_DIST_*``
    environment variables, so a worker script needs no argument parsing.
  * :func:`make_global_stream_mesh` -- the global device mesh over EVERY
    process's devices: the LS attribute axis over ``'model'`` (key
    grouping) and the payload batch / member axis over ``'data'``
    (shuffle grouping), either of which may span processes.
  * :func:`payload_sharding` -- per-leaf NamedSharding factory for chunk
    payloads (``[chunk_len, B, ...]``): batch over ``'data'``, step axis
    replicated.  Feed it to ``ChunkedStream(sharding=...)`` so each
    process contributes only its addressable batch columns
    (``jax.make_array_from_process_local_data``).
  * :func:`launch_workers` -- the test/CI launcher: spawns N python
    subprocesses against a fresh localhost coordinator port, each with
    its own forced-host-device count, and fail-louds with both logs when
    any worker exits non-zero.

Everything here is functions (never import-time device state) for the
same reason as ``launch/mesh.py``: the flags must land before the first
jax initialization in the *target* process.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

from .mesh import force_host_devices

# Environment contract between launch_workers() and init_from_env().
ENV_COORD = "REPRO_DIST_COORDINATOR"
ENV_NPROC = "REPRO_DIST_NUM_PROCESSES"
ENV_PROC = "REPRO_DIST_PROCESS_ID"
ENV_LOCAL_DEVICES = "REPRO_DIST_LOCAL_DEVICES"


def free_port() -> int:
    """An OS-assigned free TCP port for the coordinator."""
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def initialize(coordinator_address: str, num_processes: int,
               process_id: int, *, local_devices: int | None = None):
    """Join the process group.  MUST run before any jax computation.

    Orders the three one-shot knobs correctly: forced host device count
    (XLA_FLAGS), the gloo CPU collectives implementation (without it the
    TFRT CPU client refuses cross-process programs), then
    ``jax.distributed.initialize``.  Returns ``(process_index,
    process_count, global_device_count)``.
    """
    if local_devices is not None:
        if not force_host_devices(int(local_devices)):
            raise RuntimeError(
                "initialize() must run before jax creates its backends; "
                "spawn a fresh process (see launch_workers)")
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes),
        process_id=int(process_id),
    )
    return jax.process_index(), jax.process_count(), jax.device_count()


def init_from_env(env=None):
    """Bootstrap from the ``REPRO_DIST_*`` contract (worker side).

    Returns ``None`` when the coordinator variable is absent -- the
    caller is a plain single-process run and should proceed without a
    process group.
    """
    env = os.environ if env is None else env
    coord = env.get(ENV_COORD)
    if not coord:
        return None
    local = env.get(ENV_LOCAL_DEVICES)
    return initialize(
        coord,
        int(env[ENV_NPROC]),
        int(env[ENV_PROC]),
        local_devices=int(local) if local else None,
    )


def make_global_stream_mesh(model: int | None = None,
                            data: int | None = None):
    """Global ``("model", "data")`` mesh over every process's devices.

    ``model`` carries the key-grouped learner state (VHT/LS attribute
    axis, AMRules rules); ``data`` carries the shuffle-grouped payload
    batch or the ensemble member axis, and is the axis that typically
    spans processes.  Unspecified factors are inferred; by default every
    device lands on 'data' (pure shuffle grouping).
    """
    import jax
    n = jax.device_count()
    if model is None and data is None:
        model, data = 1, n
    elif model is None:
        model = n // int(data)
    elif data is None:
        data = n // int(model)
    model, data = int(model), int(data)
    if model * data != n:
        raise ValueError(
            f"mesh {model}x{data} does not cover the {n} global devices")
    from repro.launch.mesh import auto_mesh
    return auto_mesh((model, data), ("model", "data"))


def payload_sharding(mesh, *, batch_axis: str = "data", batch_dim: int = 1):
    """Per-leaf sharding factory for chunk payload leaves.

    Chunk payloads are ``[chunk_len, B, ...]``: the step axis stays
    replicated, the batch axis shards over ``batch_axis``.  Returns a
    callable suitable for ``ChunkedStream(sharding=...)``; leaves with
    rank <= batch_dim replicate.
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def for_leaf(leaf):
        ndim = getattr(leaf, "ndim", 0)
        if ndim <= batch_dim:
            return NamedSharding(mesh, P())
        spec = [None] * ndim
        spec[batch_dim] = batch_axis
        return NamedSharding(mesh, P(*spec))

    return for_leaf


def worker_env(process_id: int, num_processes: int, coordinator: str, *,
               devices_per_process: int, base=None) -> dict:
    """The child-process environment for one worker."""
    env = dict(os.environ if base is None else base)
    env[ENV_COORD] = coordinator
    env[ENV_NPROC] = str(num_processes)
    env[ENV_PROC] = str(process_id)
    env[ENV_LOCAL_DEVICES] = str(devices_per_process)
    env["JAX_PLATFORMS"] = "cpu"
    # each worker forces its OWN host device count; scrub any inherited
    # count so force_host_devices in the child sees a clean slate
    env.pop("XLA_FLAGS", None)
    force_host_devices(devices_per_process, env)
    return env


def launch_workers(num_processes: int, argv, *, devices_per_process: int = 4,
                   env=None, timeout: float = 900.0,
                   coordinator: str | None = None):
    """Spawn ``num_processes`` copies of ``argv`` as one process group.

    Each child gets the ``REPRO_DIST_*`` contract (fresh localhost
    coordinator port unless given) plus its forced host device count, and
    must call :func:`init_from_env` before computing.  Blocks until all
    exit; raises RuntimeError carrying every worker's log tail when any
    exits non-zero (fail-loud: a hung collective surfaces as the timeout
    kill, not a silent pass).  Returns the list of worker stdouts.
    """
    coordinator = coordinator or f"127.0.0.1:{free_port()}"
    argv = [str(a) for a in argv]
    procs = []
    for pid in range(num_processes):
        wenv = worker_env(pid, num_processes, coordinator,
                          devices_per_process=devices_per_process, base=env)
        procs.append(subprocess.Popen(
            [sys.executable] + argv, env=wenv,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs, rcs = [], []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
            rcs.append(p.returncode)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        for p in procs:
            out, _ = p.communicate()
            outs.append(out)
        raise RuntimeError(
            f"multihost workers timed out after {timeout}s; logs:\n"
            + "\n".join(f"--- worker {i} ---\n{o[-4000:]}"
                        for i, o in enumerate(outs)))
    if any(rc != 0 for rc in rcs):
        raise RuntimeError(
            f"multihost workers failed (rcs={rcs}); logs:\n"
            + "\n".join(f"--- worker {i} (rc={rc}) ---\n{o[-4000:]}"
                        for i, (rc, o) in enumerate(zip(rcs, outs))))
    return outs
