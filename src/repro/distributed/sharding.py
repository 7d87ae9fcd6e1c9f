"""Sharding policy: SAMOA groupings mapped onto GSPMD PartitionSpecs.

The paper distributes work with three *groupings*:

  * key grouping      -- route by key; in tensor form this is sharding an
                         axis of the state across workers.  VHT key-groups
                         the (leaf, attribute) statistics; the LM zoo
                         key-groups attention heads / FFN columns / experts.
                         All map to the ``model`` mesh axis here.
  * shuffle grouping  -- spread instances uniformly; this is batch sharding
                         over the ``data`` (and ``pod``) mesh axes.
  * all grouping      -- broadcast; replication + jax.lax collectives.

``param_spec`` below is the single place where a logical-axis-annotated
tensor is assigned mesh axes.  It implements two passes:

  1. *vertical parallelism* (the paper's technique): model-parallel axes
     (vocab / heads / ff / experts / kv_seq ...) go to ``model`` when the
     dimension is divisible by the axis size;
  2. *single-copy state* (the paper's memory argument, ==FSDP/ZeRO): the
     largest remaining eligible axis is sharded over the data axes so no
     worker holds a full replica -- the same argument the paper makes for
     why vertical statistics beat the ``sharding`` baseline's p-times
     memory blow-up.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# Logical axes handled by the vertical (tensor/model) parallel pass, tried in
# order.  Only applied when the dimension size is divisible by the mesh axis.
TP_RULES: dict[str, Any] = {
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "moe_ff": "model",
    "experts": "model",
    "experts_dp": ("data", "model"),  # expert-parallel over BOTH axes (one
                                      # expert per chip when E == data*model;
                                      # kills the FSDP weight gather at decode)
    "kv_seq": "model",      # decode-time KV cache sequence sharding
    "attr": "model",        # VHT: attribute axis == key grouping (leaf,attr)
    "rules": "model",       # AMRules: rule-id axis -> learner processors
    "d_inner": "model",     # SSM inner channels
    "d_rnn": "model",       # RG-LRU width
}

# Fallback vertical rules, tried only if no axis got a model assignment in the
# first pass (e.g. head counts not divisible by the mesh: qwen 20H, yi 56H).
TP_FALLBACK: dict[str, str] = {
    "head_dim": "model",
    "embed": "model",
}

# Axes eligible to absorb the FSDP (data-axes) shard of parameters.
FSDP_OK = ("embed", "ff", "moe_ff", "d_inner", "d_rnn", "vocab", "heads",
           "q_lora", "kv_lora", "attr", "rules")

# Axes that are *never* sharded.
NEVER = ("layers", "bins", "classes", "state", "conv", "pattern")


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """Data-parallel mesh axes: ('pod','data') on multi-pod, ('data',) else."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# --- active-mesh context: lets model code emit sharding constraints without
# --- threading the mesh through every call (no-op when no mesh is active)
import contextlib
import contextvars

_ACTIVE_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "repro_active_mesh", default=None)


@contextlib.contextmanager
def mesh_context(mesh: Mesh):
    """Activate `mesh` for constrain()/active_mesh() AND as jax's resource
    env (the Mesh context manager).  The contextvar is what model code
    must consult (active_mesh()): jax's own resource env is internal."""
    token = _ACTIVE_MESH.set(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _ACTIVE_MESH.reset(token)


def active_mesh() -> Mesh | None:
    return _ACTIVE_MESH.get()


def kernel_mesh() -> Mesh | None:
    """The active mesh when it spans more than one device, else None.

    GSPMD cannot partition a Mosaic (Pallas TPU) kernel: under such a
    mesh a kernel call must run inside a shard_map (``run_replicated``).
    The kernel dispatchers read this at trace time and pass it on as a
    static argument, so their compiled programs are keyed on it."""
    mesh = _ACTIVE_MESH.get()
    return mesh if mesh is not None and mesh.size > 1 else None


def run_per_shard(fn, mesh: Mesh, in_specs, out_specs, *args):
    """``fn`` on each device's shard of ``args`` (a shard_map over
    ``mesh``).  Kernel dispatchers traced inside see no active mesh: a
    shard is one device's problem, so they call their kernel directly."""
    token = _ACTIVE_MESH.set(None)
    try:
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(*args)
    finally:
        _ACTIVE_MESH.reset(token)


def run_replicated(fn, mesh: Mesh | None, *args):
    """``fn(*args)``; under a multi-device ``mesh`` inside a shard_map
    whose inputs and outputs are replicated.  Every device then runs the
    whole call on gathered inputs -- bit-identical to one device -- and
    GSPMD reshards the result to whatever layout the caller constrains."""
    if mesh is None:
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


_SUPPRESS_SPMD_GATHER: contextvars.ContextVar = contextvars.ContextVar(
    "repro_suppress_spmd_gather", default=False)


@contextlib.contextmanager
def suppress_spmd_member_gather():
    """Inside a fleet vmap the mesh's 'data' axis partitions TENANTS, not
    the member axis the inner learner sees, so a member-axis shard_map
    would bind the wrong physical axis.  LearnerFleet wraps its vmapped
    family calls in this context; mesh-aware member code (the ensemble's
    pooled split check) then keeps the single-shard formulation, which
    GSPMD batches per tenant."""
    token = _SUPPRESS_SPMD_GATHER.set(True)
    try:
        yield
    finally:
        _SUPPRESS_SPMD_GATHER.reset(token)


def spmd_member_gather_suppressed() -> bool:
    return _SUPPRESS_SPMD_GATHER.get()


def leading_axis_spec(axis: str, leaf) -> P | None:
    """P(axis, None, ..., None) matching the leaf's rank -- the learner
    ``state_sharding`` idiom (shard the leading state axis, replicate the
    rest).  Rank-0 leaves replicate (None)."""
    ndim = getattr(leaf, "ndim", 0)
    if ndim < 1:
        return None
    return P(axis, *([None] * (ndim - 1)))


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return math.prod(mesh.shape[a] for a in axes)


def param_spec(
    shape: Sequence[int],
    axes: Sequence[str | None],
    mesh: Mesh,
    *,
    fsdp: bool = True,
    tp: bool = True,
) -> P:
    """Assign mesh axes to a parameter from its logical-axis annotation."""
    assert len(shape) == len(axes), (shape, axes)
    assign: list[Any] = [None] * len(shape)
    used: set[str] = set()

    # batch axes (activations / caches): shuffle grouping over data(+pod)
    dp = dp_axes(mesh)
    dsize = _axis_size(mesh, dp)
    for i, (d, a) in enumerate(zip(shape, axes)):
        if (a == "batch" and dp and dsize > 1 and d % dsize == 0
                and not (set(dp) & used)):
            assign[i] = dp if len(dp) > 1 else dp[0]
            used.update(dp)

    if tp and "model" in mesh.axis_names:
        msize = mesh.shape["model"]
        for i, (d, a) in enumerate(zip(shape, axes)):
            rule = TP_RULES.get(a or "")
            if isinstance(rule, tuple):
                parts = tuple(r for r in rule if r in mesh.axis_names)
                size = math.prod(mesh.shape[r] for r in parts)
                if parts and not (set(parts) & used) and d % size == 0:
                    assign[i] = parts if len(parts) > 1 else parts[0]
                    used.update(parts)
                continue
            if rule and rule not in used and d % msize == 0:
                assign[i] = rule
                used.add(rule)
        if "model" not in used:
            for i, (d, a) in enumerate(zip(shape, axes)):
                rule = TP_FALLBACK.get(a or "")
                if rule and d % msize == 0:
                    assign[i] = rule
                    used.add(rule)
                    break

    if fsdp:
        dp = dp_axes(mesh)
        dsize = _axis_size(mesh, dp)
        if dp and dsize > 1 and not (set(dp) & used):
            order = sorted(range(len(shape)), key=lambda i: -shape[i])
            for i in order:
                if (
                    assign[i] is None
                    and (axes[i] or "") in FSDP_OK
                    and shape[i] % dsize == 0
                ):
                    assign[i] = dp if len(dp) > 1 else dp[0]
                    break
    return P(*assign)


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Bundles a mesh with grouping->PartitionSpec mapping decisions."""

    mesh: Mesh
    fsdp: bool = True
    tp: bool = True

    # ---- the three SAMOA groupings -------------------------------------
    def shuffle(self, *trailing: Any) -> P:
        """Shuffle grouping: batch axis over data(+pod)."""
        dp = dp_axes(self.mesh)
        lead = dp if len(dp) > 1 else (dp[0] if dp else None)
        return P(lead, *trailing)

    def key_group(self, ndim: int, axis: int) -> P:
        """Key grouping: shard dimension `axis` over the model mesh axis."""
        spec: list[Any] = [None] * ndim
        spec[axis] = "model"
        return P(*spec)

    def all_group(self, ndim: int) -> P:
        """All grouping: full replication."""
        return P(*([None] * ndim))

    # ---- parameter / activation helpers --------------------------------
    def param(self, shape, axes) -> NamedSharding:
        return NamedSharding(
            self.mesh, param_spec(shape, axes, self.mesh, fsdp=self.fsdp, tp=self.tp)
        )

    def spec(self, shape, axes) -> P:
        return param_spec(shape, axes, self.mesh, fsdp=self.fsdp, tp=self.tp)

    def activation(self, *logical: str | None) -> P:
        """Activations: batch over data(+pod); other axes replicated unless
        explicitly model-sharded (e.g. 'heads')."""
        out: list[Any] = []
        for name in logical:
            if name == "batch":
                dp = dp_axes(self.mesh)
                out.append(dp if len(dp) > 1 else (dp[0] if dp else None))
            elif name in TP_RULES:
                out.append("model")
            else:
                out.append(None)
        return P(*out)

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


def make_policy(mesh: Mesh, *, fsdp: bool = True, tp: bool = True) -> ShardingPolicy:
    return ShardingPolicy(mesh=mesh, fsdp=fsdp, tp=tp)


def constrain(x, *logical):
    """with_sharding_constraint from logical axis names, using the ambient
    mesh (``with mesh:`` / ``jax.sharding.use_mesh``).  No-op when no mesh
    is active (single-device tests) or when a dim doesn't divide its axis.

    logical names: "batch" -> data(+pod) axes, "model"/"experts"/"heads"/
    "ff"/"vocab"/"kv_seq" -> model axis, None -> unsharded.

    GSPMD propagates shardings poorly through scan bodies and reshapes;
    pinning activations at block boundaries is what keeps the batch axis
    partitioned instead of silently replicating the whole computation
    (a 16x FLOP/memory regression we hit in the dry-run -- see
    EXPERIMENTS.md section Perf).
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    spec: list[Any] = []
    for dim, name in zip(x.shape, logical):
        if name == "batch":
            dp = tuple(a for a in ("pod", "data") if a in names)
            size = math.prod(mesh.shape[a] for a in dp) if dp else 1
            if dp and size > 1 and dim % size == 0:
                spec.append(dp if len(dp) > 1 else dp[0])
            else:
                spec.append(None)
        elif name in TP_RULES or name == "model":
            rule = TP_RULES.get(name, "model")
            if isinstance(rule, tuple):
                parts = tuple(r for r in rule if r in names)
                size = math.prod(mesh.shape[r] for r in parts) if parts else 1
                if parts and dim % size == 0:
                    spec.append(parts if len(parts) > 1 else parts[0])
                else:
                    spec.append(None)
            elif "model" in names and dim % mesh.shape["model"] == 0:
                spec.append("model")
            else:
                spec.append(None)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


# --- process-spanning placement ---------------------------------------------
# On a multi-process mesh only the local shards of an array are
# addressable: host-local reads (np.asarray / jax.device_get) raise, and
# placement must go through per-process addressable shards.  These four
# helpers are the single chokepoint the engines / chunked pipeline /
# checkpointing route through, so the rest of the codebase never needs to
# know whether a sharding spans processes.

def spans_processes(sharding) -> bool:
    """True when `sharding` has shards this process cannot address."""
    try:
        return not sharding.is_fully_addressable
    except AttributeError:
        return False


def mesh_spans_processes(mesh: Mesh) -> bool:
    import numpy as np
    me = jax.process_index()
    return any(d.process_index != me for d in np.asarray(mesh.devices).flat)


def put_global(x, sharding):
    """Place a value onto `sharding`, which may span processes.

    The fully-addressable case is a plain ``jax.device_put``.  The
    process-spanning case assumes every process holds the same logical
    value (host-restored checkpoints, deterministic inits) and assembles
    the global array from this process's addressable shards only.
    """
    if sharding is None or not spans_processes(sharding):
        return jax.device_put(x) if sharding is None \
            else jax.device_put(x, sharding)
    import numpy as np
    host = x if isinstance(x, np.ndarray) else np.asarray(jax.device_get(x))
    return jax.make_array_from_callback(
        host.shape, sharding, lambda idx: host[idx])


def host_value(x):
    """The full logical value of `x` as a host numpy array.

    Fully-addressable arrays read directly; fully-replicated
    process-spanning arrays read their local replica; partitioned
    process-spanning arrays go through a cross-process all-gather (a
    COLLECTIVE -- every process must call this in the same order).
    """
    import numpy as np
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    if x.is_fully_addressable:
        return np.asarray(jax.device_get(x))
    if x.is_fully_replicated:
        return np.asarray(x)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def shardings_for(axes_tree, mesh: Mesh, *, fsdp: bool = True, tp: bool = True):
    """Map a pytree of (shape, logical-axes) leaves to NamedShardings.

    Leaves are ``AxisAnnotation`` (see models.params) or plain tuples of axis
    names paired with a shape-bearing twin tree via jax.eval_shape upstream.
    """
    def one(leaf):
        shape, axes = leaf
        return NamedSharding(mesh, param_spec(shape, axes, mesh, fsdp=fsdp, tp=tp))

    return jax.tree.map(one, axes_tree, is_leaf=lambda x: isinstance(x, tuple)
                        and len(x) == 2 and isinstance(x[0], tuple))
