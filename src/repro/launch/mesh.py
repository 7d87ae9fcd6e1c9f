"""Production mesh factory.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state -- required because the dry-run must set
XLA_FLAGS before the first jax initialization, while smoke tests and
benchmarks must see the real single CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    ``jax.make_mesh`` makes ``Explicit`` axes by default, under which
    GSPMD-style code (sharding constraints on the carry, propagation
    through gathers) must annotate every ``out_sharding`` by hand.  The
    engines rely on propagation, so every mesh in the repo is built
    here."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod = 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic mesh factory: any (pods, data, model) factorization of the
    currently visible devices (used by restart-after-failure paths)."""
    return auto_mesh(shape, axes)


def make_mesh_from_proposal(shape, axes):
    """Build a Mesh from ``Supervisor.propose_mesh`` output.

    Unlike ``jax.make_mesh`` (which insists on consuming EVERY visible
    device), this uses the FIRST prod(shape) devices -- a survivor mesh
    after host loss is by definition smaller than the full device set,
    and the dead hosts' devices are still visible to the single-process
    simulation."""
    import math

    import numpy as np
    from jax.sharding import Mesh

    n = math.prod(shape)
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(
            f"mesh proposal {tuple(shape)} needs {n} devices, "
            f"only {len(devs)} visible")
    return Mesh(np.asarray(devs[:n]).reshape(tuple(shape)), tuple(axes))


def make_local_mesh(model_parallel: int = 1):
    """Single-host mesh over whatever devices exist (tests/examples)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return auto_mesh((n // model_parallel, model_parallel), ("data", "model"))


def make_stream_mesh(axis: str = "model"):
    """All visible devices on ONE learner-sharding axis.

    The streaming learners shard state over a single named axis ('model'
    for key-grouped state: AMRules rules, CluStream micro-clusters; 'data'
    for the ensemble member axis), so the natural mesh for a sharded
    stream run puts every device on that axis and leaves the other at 1.
    """
    if axis not in ("model", "data"):
        raise ValueError(f"unknown stream axis {axis!r}")
    n = jax.device_count()
    shape = (n, 1) if axis == "model" else (1, n)
    return auto_mesh(shape, ("model", "data"))


FORCE_HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count"


def force_host_devices(n: int, env=None) -> bool:
    """Arrange for the CPU platform to expose `n` virtual devices.

    Mutates XLA_FLAGS in `env` (default os.environ).  MUST run before the
    first jax initialization in the target process -- the flag is read
    once; callers that already initialized jax get False back and should
    respawn (tests/benchmarks run their multi-device halves in a
    subprocess for exactly this reason).
    """
    import os
    import sys

    import re

    env = os.environ if env is None else env
    flag = f"{FORCE_HOST_DEVICES_FLAG}={n}"
    flags = env.get("XLA_FLAGS", "")
    have = re.search(f"{re.escape(FORCE_HOST_DEVICES_FLAG)}=(\\d+)", flags)
    if have is None:
        env["XLA_FLAGS"] = f"{flags} {flag}".strip()
    elif int(have.group(1)) < n:
        # a smaller pre-existing count would silently mis-label the run
        env["XLA_FLAGS"] = flags.replace(have.group(0), flag)
    if "jax" in sys.modules:
        # already-initialized backends ignore new XLA_FLAGS
        from jax._src import xla_bridge
        if not xla_bridge.backends_are_initialized():
            return True           # flag landed before first init
        return jax.device_count() >= n
    return True
