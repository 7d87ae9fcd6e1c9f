"""Pluggable execution engines -- the DSPE-adapter layer of the paper.

The same Topology runs on three engines (the JAX analogue of the paper's
samoa-Storm / samoa-Flink / samoa-Samza / samoa-Apex adapters):

  LocalEngine     -- pure-Python event loop, one micro-batch at a time,
                     feedback delivered within the same step until
                     quiescence.  == the paper's 'local' sequential engine
                     (split feedback delay D = 0).
  JitEngine       -- the whole topology step is ONE jitted function;
                     feedback edges are carried state delivered at the
                     next step (delay D = 1 engine step).  This reproduces
                     the asynchronous split-delay of a real DSPE in a
                     deterministic, measurable way.
  ShardMapEngine  -- JitEngine + GSPMD: processor state sharded according
                     to each incoming stream's grouping (KEY -> 'model'
                     axis, SHUFFLE -> 'data' axis, ALL -> replicated).

Engines only require Processors to be pure; the same user code runs on all
three (the paper's flexibility goal).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.topology import (Grouping, Topology, build_learner_topology)
from repro.data.pipeline import Chunk, ChunkedStream
from repro.distributed.sharding import (leading_axis_spec, mesh_context,
                                        mesh_spans_processes, put_global)
from repro.runtime.telemetry import program


class Engine:
    def run_stream(self, topology, states, batches):  # pragma: no cover
        raise NotImplementedError

    _LEARNER_CACHE_MAX = 16

    def _evict_topology(self, topology: Topology):
        """Hook: subclasses drop any compiled programs keyed on the
        evicted wrapper so evictions free the executables too."""

    def _as_topology(self, topology) -> Topology:
        """Engines accept either a Topology or a bare functional learner
        (init/step): learners are wrapped in a single-processor topology
        (LRU-cached per learner, so the jit caches keyed on id() stay warm
        without pinning every learner an engine ever saw) -- run_stream
        then scan-compiles ensemble/AMRules/CluStream streams exactly like
        the hand-wired VHT graph."""
        if isinstance(topology, Topology):
            return topology
        cache = getattr(self, "_learner_topologies", None)
        if cache is None:
            cache = self._learner_topologies = {}
        entry = cache.get(id(topology))
        # the entry pins the learner, so its id cannot be recycled while
        # cached; the identity check guards the eviction race anyway
        if entry is not None and entry[0] is topology:
            cache[id(topology)] = cache.pop(id(topology))   # refresh recency
            return entry[1]
        if len(cache) >= self._LEARNER_CACHE_MAX:
            _, old_topo = cache.pop(next(iter(cache)))   # oldest entry
            self._evict_topology(old_topo)
        topo = build_learner_topology(topology)
        cache[id(topology)] = (topology, topo)
        return topo


def _init_states(topology: Topology, key):
    keys = jax.random.split(key, len(topology.processors))
    return {n: p.init_state(k)
            for (n, p), k in zip(topology.processors.items(), keys)}


def _stack_payloads(payloads):
    """A list (or iterator) is a per-step payload sequence and gets stacked
    on a new leading axis; any other pytree (dict, tuple, array) is taken
    as already stacked -- so a tuple-rooted stacked payload is never
    misread as a sequence of steps."""
    if hasattr(payloads, "__next__"):
        payloads = list(payloads)
    if isinstance(payloads, list):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)
    return payloads


def _unstack_payloads(payloads):
    if hasattr(payloads, "__next__"):
        payloads = list(payloads)
    if isinstance(payloads, list):
        return payloads
    n = jax.tree.leaves(payloads)[0].shape[0]
    return [jax.tree.map(lambda x: x[i], payloads) for i in range(n)]


def _require_no_boundaries(topology: Topology):
    """A topology with chunk-boundary hooks on a NON-chunked driver would
    silently never fire them (e.g. boundary-mode CluStream's macro
    centroids frozen at init forever) -- fail loudly instead."""
    names = [n for n, p in topology.processors.items()
             if p.boundary is not None]
    if names:
        raise ValueError(
            f"processors {names} have chunk-boundary hooks, which only "
            "fire on the chunked driver: pass a ChunkedStream or "
            "chunk_len= to run_stream (or use a boundary-free config, "
            "e.g. CluStream macro_impl='step')")


def _close_iter(it):
    """Release a chunk iterator deterministically: a ``ChunkedStream``
    iterator owns a producer thread whose shutdown is its generator
    ``finally`` -- on an abandoned iteration (a raising ``on_chunk``, a
    kill injected mid-stream) relying on GC would leak the thread and pin
    its prefetched device buffers until collection."""
    close = getattr(it, "close", None)
    if close is not None:
        close()


def _concat_outputs(segments):
    """The ONE output-stacking path: a list of output pytrees, each stacked
    on a leading step axis, becomes a single stacked pytree.  Both the
    monolithic scan (primed first step + scanned rest, including the n == 1
    stream where the scan segment is empty) and the chunked driver funnel
    through here, so there is exactly one concatenation semantics."""
    if not segments:
        return {}
    if len(segments) == 1:
        return segments[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *segments)


def _scan(layout, body, carry, xs):
    """``jax.lax.scan`` with the carry held in the processors' scan layout
    (``Processor.to_scan``) from the scan's entry to its exit; a plain scan
    when ``layout`` is None."""
    if layout is None:
        return jax.lax.scan(body, carry, xs)
    to_scan, from_scan = layout
    carry, outs = jax.lax.scan(body, to_scan(carry), xs)
    return from_scan(carry), outs


class LocalEngine(Engine):
    """Sequential reference engine (paper: the local execution engine).

    Feedback loops are iterated to quiescence inside each step: split
    decisions reach the model before the next micro-batch (delay 0).
    """

    def __init__(self, max_feedback_iters: int = 4):
        self.max_feedback_iters = max_feedback_iters

    def init(self, topology: Topology, key):
        return _init_states(self._as_topology(topology), key)

    def run_stream(self, topology: Topology, states, payloads):
        """Eager per-step loop: the reference semantics the scanned engines
        are tested against.  Returns (states, list of per-step outputs);
        ``repro.core.evaluation.stack_outputs`` normalizes the list to the
        scanned engines' stacked-pytree shape for parity checks.

        A ``ChunkedStream`` is accepted too: valid steps run eagerly and
        processor ``boundary`` hooks fire between chunks -- the eager
        oracle for the chunked drivers (boundary-phase semantics
        included)."""
        topology = self._as_topology(topology)
        outs = []
        if isinstance(payloads, ChunkedStream):
            it = iter(payloads)
            try:
                for chunk in it:
                    live = jax.tree.map(lambda x: x[:chunk.length],
                                        chunk.payload)
                    for payload in _unstack_payloads(live):
                        states, out = self.step(topology, states, payload)
                        outs.append(out)
                    states = self._apply_boundaries(topology, states)
            finally:
                _close_iter(it)
            return states, outs
        _require_no_boundaries(topology)
        for payload in _unstack_payloads(payloads):
            states, out = self.step(topology, states, payload)
            outs.append(out)
        return states, outs

    def _apply_boundaries(self, topology: Topology, states):
        hooks = {n: p.boundary for n, p in topology.processors.items()
                 if p.boundary is not None}
        if hooks:
            states = dict(states)
            for name, hook in hooks.items():
                states[name] = hook(states[name])
        return states

    def step(self, topology: Topology, states, source_payload):
        topology = self._as_topology(topology)
        order = topology.order()
        inboxes: dict[str, dict] = {n: {} for n in topology.processors}
        inboxes[topology.entry]["__source__"] = source_payload
        outputs: dict[str, Any] = {}
        for _ in range(self.max_feedback_iters):
            progressed = False
            for name in order:
                inbox = inboxes[name]
                if not inbox:
                    continue
                proc = topology.processors[name]
                states[name], emits = proc.process(states[name], inbox)
                inboxes[name] = {}
                progressed = True
                for stream_name, payload in (emits or {}).items():
                    if payload is None:
                        continue
                    stream = topology.streams.get(stream_name)
                    if stream is None:
                        outputs[stream_name] = payload  # task-level sink
                        continue
                    sunk = False
                    for dst, _ in stream.destinations:
                        inboxes[dst][stream_name] = payload
                        sunk = True
                    if not sunk:
                        outputs[stream_name] = payload
            if not progressed:
                break
        return states, outputs


class JitEngine(Engine):
    """Whole-topology step as one jitted function; feedback edges deliver
    next step (bounded staleness D=1 -- the deterministic analogue of DSPE
    queueing delay).  run_stream fuses the whole micro-batch stream into a
    single jax.lax.scan program with donated carries."""

    def __init__(self, donate: bool = True, fuse_boundary: bool = True):
        self.donate = donate
        # fuse_boundary=False keeps the chunk scan and the boundary hook as
        # two dispatches -- the oracle the fused epilogue is tested against
        self.fuse_boundary = fuse_boundary
        self._compiled: dict[int, Callable] = {}
        self._compiled_scan: dict[int, Callable] = {}
        self._compiled_chunk: dict[int, Callable] = {}
        self._compiled_chunk_full: dict[tuple, Callable] = {}
        self._compiled_boundary: dict[int, Callable | None] = {}
        self._packs: dict[int, bool] = {}
        # chunk programs run with a processor's state in a scan layout of
        # its own (see ``_make_scan_layout``), counted over the engine's life
        self.packed_chunks = 0

    def _evict_topology(self, topology: Topology):
        self._compiled.pop(id(topology), None)
        self._packs.pop(id(topology), None)
        self._compiled_scan.pop(id(topology), None)
        self._compiled_chunk.pop(id(topology), None)
        self._compiled_boundary.pop(id(topology), None)
        for k in [k for k in self._compiled_chunk_full
                  if k[0] == id(topology)]:
            del self._compiled_chunk_full[k]

    def init(self, topology: Topology, key):
        states = _init_states(self._as_topology(topology), key)
        return {"states": states, "feedback": None}

    def _mesh_ctx(self):
        return contextlib.nullcontext()

    def _make_step(self, topology: Topology):
        fb_edges = topology.feedback_edges()
        order = topology.order()

        def step(states, feedback, source_payload):
            inboxes: dict[str, dict] = {n: {} for n in topology.processors}
            inboxes[topology.entry]["__source__"] = source_payload
            # deliver last step's feedback first
            if feedback:
                for stream_name, payload in feedback.items():
                    stream = topology.streams[stream_name]
                    for dst, _ in stream.destinations:
                        inboxes[dst][stream_name] = payload
            outputs: dict[str, Any] = {}
            new_feedback: dict[str, Any] = {}
            for name in order:
                proc = topology.processors[name]
                states = dict(states)
                states[name], emits = proc.process(states[name], inboxes[name])
                for stream_name, payload in (emits or {}).items():
                    if payload is None:
                        continue
                    if stream_name in fb_edges:
                        new_feedback[stream_name] = payload
                        continue
                    stream = topology.streams.get(stream_name)
                    if stream is None or not stream.destinations:
                        outputs[stream_name] = payload
                        continue
                    for dst, _ in stream.destinations:
                        inboxes[dst][stream_name] = payload
            return states, new_feedback, outputs

        return step

    def step(self, topology: Topology, carry, source_payload):
        topology = self._as_topology(topology)
        key = id(topology)
        if key not in self._compiled:
            self._compiled[key] = program(self._make_step(topology),
                                          "engine_step")
        with self._mesh_ctx():
            states, feedback, outputs = self._compiled[key](
                carry["states"], carry["feedback"], source_payload)
        return {"states": states, "feedback": feedback}, outputs

    # ------------------------------------------------- whole-stream scan

    def _scan_fn(self, topology: Topology):
        key = id(topology)
        fn = self._compiled_scan.get(key)
        if fn is None:
            step = self._make_step(topology)
            layout = self._make_scan_layout(topology)

            def scan_fn(carry, payloads):
                def body(c, payload):
                    states, fb, outs = step(c["states"], c["feedback"],
                                            payload)
                    return {"states": states, "feedback": fb}, outs
                return _scan(layout, body, carry, payloads)

            donate = (0,) if self.donate and \
                jax.default_backend() != "cpu" else ()
            fn = program(scan_fn, "stream_scan", donate_argnums=donate)
            self._compiled_scan[key] = fn
        return fn

    def run_stream(self, topology: Topology, carry, payloads, *,
                   chunk_len: int | None = None, on_chunk=None,
                   collect_outputs: bool = True):
        """Fused prequential execution: the whole stream of micro-batches is
        ONE compiled program (jax.lax.scan over the topology step, carries
        donated), so N batches cost one dispatch instead of N.

        The first step runs through the plain jitted step to materialize the
        feedback-carry structure (engine.init starts with feedback=None);
        the remaining N-1 steps are scanned.  Accepts a list/iterator of
        payload pytrees or a pytree stacked on the leading axis; returns
        (carry, outputs stacked on the leading axis) and matches the
        per-step loop bit for bit.  Accepts a Topology or a bare learner
        (see Engine._as_topology).

        Passing a ``ChunkedStream`` (or ``chunk_len``, which wraps stacked
        payloads into one) routes through the chunked runtime instead: the
        same scanned step driven chunk by chunk at bounded memory -- see
        ``run_stream_chunked`` for the chunk-path semantics and knobs.
        """
        if chunk_len is not None and not isinstance(payloads, ChunkedStream):
            payloads = ChunkedStream(payloads, chunk_len)
        if isinstance(payloads, ChunkedStream):
            return self.run_stream_chunked(
                topology, carry, payloads, on_chunk=on_chunk,
                collect_outputs=collect_outputs)
        if on_chunk is not None or not collect_outputs:
            raise ValueError(
                "on_chunk / collect_outputs are chunked-runtime knobs: "
                "pass a ChunkedStream or chunk_len, or drop them -- the "
                "monolithic scan would silently ignore the reduction and "
                "materialize the full [T, ...] outputs")
        topology = self._as_topology(topology)
        _require_no_boundaries(topology)
        payloads = _stack_payloads(payloads)
        n = jax.tree.leaves(payloads)[0].shape[0]
        segments = []
        if carry["feedback"] is None:
            carry, seg0, payloads = self._prime_first_step(
                topology, carry, payloads)
            segments.append(seg0)
            n -= 1
        if n:
            with self._mesh_ctx():
                carry, outs = self._scan_fn(topology)(carry, payloads)
            segments.append(outs)
        return carry, _concat_outputs(segments)

    def _prime_first_step(self, topology: Topology, carry, payloads):
        """Run step 0 through the plain jitted step to materialize the
        feedback-carry structure (engine.init starts with feedback=None).
        Shared by the monolithic scan and the chunked driver's first
        chunk, so their priming semantics cannot diverge -- the
        chunked-vs-monolithic bit-identity depends on it.  Returns
        (carry, the primed output as a 1-step segment, remaining
        payloads)."""
        first = jax.tree.map(lambda x: x[0], payloads)
        carry, out0 = self.step(topology, carry, first)
        seg0 = jax.tree.map(lambda x: x[None], out0)
        return carry, seg0, jax.tree.map(lambda x: x[1:], payloads)

    # ------------------------------------------------ chunked stream path

    def _chunk_scan_fn(self, topology: Topology):
        """The masked chunk program: a scan whose step is lax.cond-gated on
        the chunk's validity mask, so the zero-padded tail of the last
        chunk is a carry-preserving no-op (outputs zeroed, trimmed by the
        driver).  Compiled once per chunk shape -- jit re-specializes on
        the (chunk_len-1)-step first chunk and the full-length steady
        state, and every subsequent chunk reuses those two executables."""
        key = id(topology)
        fn = self._compiled_chunk.get(key)
        if fn is None:
            step = self._make_step(topology)
            layout = self._make_scan_layout(topology)

            def chunk_fn(carry, payloads, valid):
                out_sd = jax.eval_shape(
                    lambda c, p: step(c["states"], c["feedback"], p),
                    carry, jax.tree.map(lambda x: x[0], payloads))[2]

                def body(c, xv):
                    payload, v = xv

                    def live(c):
                        states, fb, outs = step(c["states"], c["feedback"],
                                                payload)
                        return {"states": states, "feedback": fb}, outs

                    def dead(c):
                        zeros = jax.tree.map(
                            lambda s: jnp.zeros(s.shape, s.dtype), out_sd)
                        return c, zeros

                    return jax.lax.cond(v, live, dead, c)

                return _scan(layout, body, carry, (payloads, valid))

            donate = (0,) if self.donate and \
                jax.default_backend() != "cpu" else ()
            fn = program(chunk_fn, "chunk_masked", donate_argnums=donate)
            self._compiled_chunk[key] = fn
        return fn

    def _chunk_full_fn(self, topology: Topology, *, fused_boundary: bool,
                       reducer=None):
        """The UNMASKED chunk program: every step of a full (un-padded)
        chunk is real, so the lax.cond validity gate of ``_chunk_scan_fn``
        is dead weight -- this program scans the plain topology step
        (identical math, the same body the monolithic ``_scan_fn`` runs)
        and fuses the per-chunk epilogue into the same dispatch:

          * ``fused_boundary``: the processors' ``boundary()`` hooks run
            in the program's tail (one dispatch per chunk instead of two);
            ``fuse_boundary=False`` on the engine keeps the separate
            boundary dispatch as the bit-identity oracle.
          * ``reducer``: an output reduction compiled INTO the program, so
            only the reduced leaves (e.g. the ``[chunk_len]`` metric
            columns) are ever materialized -- XLA dead-code-eliminates
            whole unread output streams from the scan.  Must be a STABLE
            function (module-level, not a per-call lambda: the compiled
            program is cached on its identity) that commutes with
            concatenation along the step axis (selection / elementwise).
        """
        key = (id(topology), bool(fused_boundary),
               id(reducer) if reducer is not None else None)
        fn = self._compiled_chunk_full.get(key)
        if fn is None:
            step = self._make_step(topology)
            boundary = self._make_boundary(topology) if fused_boundary \
                else None
            layout = self._make_scan_layout(topology)

            def chunk_fn(carry, payloads):
                def body(c, payload):
                    states, fb, outs = step(c["states"], c["feedback"],
                                            payload)
                    return {"states": states, "feedback": fb}, outs

                carry, outs = _scan(layout, body, carry, payloads)
                if boundary is not None:
                    carry = boundary(carry)
                if reducer is not None:
                    outs = reducer(outs)
                return carry, outs

            donate = (0,) if self.donate and \
                jax.default_backend() != "cpu" else ()
            fn = program(chunk_fn, "chunk_program", donate_argnums=donate)
            self._compiled_chunk_full[key] = fn
        return fn

    def _make_scan_layout(self, topology: Topology):
        """``(to_scan, from_scan)`` over the carry: every processor's scan
        layout hooks applied to its state, which the scanned programs hold
        in that layout from their entry to their exit.  None when no
        processor has them: the programs are then the plain scan."""
        hooks = {n: (p.to_scan, p.from_scan)
                 for n, p in topology.processors.items()
                 if p.to_scan is not None}
        if not hooks:
            return None

        def apply(carry, side):
            states = dict(carry["states"])
            for name, pair in hooks.items():
                states[name] = pair[side](states[name])
            return {"states": states, "feedback": carry["feedback"]}

        return (lambda carry: apply(carry, 0)), (lambda carry: apply(carry, 1))

    def _packs_state(self, topology: Topology, carry) -> bool:
        """Whether the scan layout changes the form of any state leaf, so a
        chunk program of this topology runs with its state packed."""
        key = id(topology)
        if key not in self._packs:
            layout = self._make_scan_layout(topology)
            shapes = lambda t: [x.shape for x in jax.tree.leaves(t)]
            self._packs[key] = layout is not None and \
                shapes(jax.eval_shape(layout[0], carry)) != shapes(carry)
        return self._packs[key]

    def _make_boundary(self, topology: Topology):
        """The chunk-boundary phase: apply every processor's ``boundary``
        hook to its state.  Returns None when no processor has one (the
        common case -- zero per-chunk overhead)."""
        hooks = {n: p.boundary for n, p in topology.processors.items()
                 if p.boundary is not None}
        if not hooks:
            return None

        def boundary(carry):
            states = dict(carry["states"])
            for name, hook in hooks.items():
                states[name] = hook(states[name])
            return {"states": states, "feedback": carry["feedback"]}

        return boundary

    def _boundary_fn(self, topology: Topology):
        key = id(topology)
        if key not in self._compiled_boundary:
            fn = self._make_boundary(topology)
            self._compiled_boundary[key] = \
                program(fn, "chunk_boundary") if fn is not None else None
        return self._compiled_boundary[key]

    def run_stream_chunked(self, topology: Topology, carry, chunks, *,
                           on_chunk=None, collect_outputs: bool = True,
                           reduce_outputs=None):
        """Chunked stream runtime: drive the scanned topology step chunk by
        chunk, bit-identical to the monolithic scan but at bounded memory
        -- stream length is no longer capped by what fits on device.

        ``chunks`` is a ChunkedStream or any iterable of ``Chunk``s.  A
        full chunk runs through the unmasked chunk program with the
        ``boundary()`` hooks fused into its epilogue (one dispatch per
        chunk; ``fuse_boundary=False`` keeps the separate-dispatch
        oracle); the padded final chunk runs the masked scan program with
        its no-op tail trimmed.  Between chunks the driver calls
        ``on_chunk(outputs, chunk, carry)`` -- the streaming reduction
        point for per-chunk metrics and mid-stream checkpoints.
        ``collect_outputs=False`` drops the per-chunk outputs after
        ``on_chunk`` instead of concatenating a ``[T, ...]`` result, which
        is the whole point for long streams.  ``reduce_outputs`` is a
        STABLE function (see ``_chunk_full_fn``) applied to each chunk's
        stacked outputs INSIDE the compiled program where possible, so
        unread output streams never materialize.
        """
        topology = self._as_topology(topology)
        boundary = self._boundary_fn(topology)
        segments = []
        it = iter(chunks)
        try:
            for chunk in it:
                carry, outs, boundary_done = self._run_chunk(
                    topology, carry, chunk, reducer=reduce_outputs)
                if boundary is not None and not boundary_done:
                    with self._mesh_ctx():
                        carry = boundary(carry)
                if on_chunk is not None:
                    on_chunk(outs, chunk, carry)
                if collect_outputs:
                    segments.append(outs)
        finally:
            _close_iter(it)
        return carry, _concat_outputs(segments) if collect_outputs else None

    def _run_chunk(self, topology: Topology, carry, chunk: Chunk, *,
                   reducer=None):
        """One chunk through the compiled chunk program; the first chunk
        of a fresh stream primes the feedback-carry structure through the
        plain jitted step exactly like the monolithic path (bit-identity).
        Full chunks take the unmasked program with the boundary hooks
        fused (``fuse_boundary``); the padded tail chunk takes the masked
        scan with a separate boundary dispatch.  Returns ``(carry, outs,
        boundary_done)`` so the driver knows whether the epilogue already
        fired."""
        payloads, valid = chunk.payload, chunk.valid
        has_boundary = self._boundary_fn(topology) is not None
        segments = []
        if carry["feedback"] is None:
            carry, seg0, payloads = self._prime_first_step(
                topology, carry, payloads)
            if reducer is not None:
                seg0 = reducer(seg0)
            segments.append(seg0)
            valid = valid[1:]
        boundary_done = False
        if jax.tree.leaves(payloads)[0].shape[0]:
            with self._mesh_ctx():
                if not chunk.padded:
                    fused = self.fuse_boundary and has_boundary
                    carry, outs = self._chunk_full_fn(
                        topology, fused_boundary=fused, reducer=reducer)(
                        carry, payloads)
                    boundary_done = fused
                else:
                    carry, outs = self._chunk_scan_fn(topology)(
                        carry, payloads, valid)
                    if reducer is not None:
                        outs = reducer(outs)
            if self._packs_state(topology, carry):
                self.packed_chunks += 1
            segments.append(outs)
        outs = _concat_outputs(segments)
        if chunk.padded:
            outs = jax.tree.map(lambda x: x[:chunk.length], outs)
        return carry, outs, boundary_done


class ShardMapEngine(JitEngine):
    """JitEngine with GSPMD sharding derived from stream groupings.

    State leaves of processors fed by KEY-grouped streams get their leading
    axis sharded over 'model' (vertical parallelism); SHUFFLE-fed processor
    batches shard over 'data'; ALL-grouped streams replicate.  The jitted
    topology step is constrained accordingly -- XLA inserts the collectives
    that Storm/Samza would perform as network shuffles.  run_stream scans
    the whole stream inside the mesh context, so the collectives compile
    once for all N micro-batches.

    Processor `state_sharding` hints are enforced twice: `init` places the
    state per-shard (device_put), and every scanned step re-constrains the
    hinted leaves (with_sharding_constraint), so the carry cannot silently
    collapse to replicated mid-stream however XLA propagates the rest.
    Hints compose through the LearnerProcessor chain: packed sub-states
    such as a learner's DetectorBank publish their own leading-axis specs
    and partition with their owner (members -> 'data', rules -> 'model').
    Hints that do not fit the mesh (unknown axis, or a dimension the axis
    size does not divide) fall back to replication for that leaf instead of
    failing, so one learner config runs on any mesh shape.
    """

    def __init__(self, mesh, donate: bool = True,
                 fuse_boundary: bool = True):
        super().__init__(donate=donate, fuse_boundary=fuse_boundary)
        self.mesh = mesh
        self._spans = None

    @property
    def spans_processes(self) -> bool:
        """Whether this engine's mesh places shards on other processes
        (multi-host run) -- placement then goes through per-process
        addressable shards and EVERY carry leaf must live on the global
        mesh (a committed single-device leaf mixed into a global jit is a
        device-set error)."""
        if self._spans is None:
            self._spans = mesh_spans_processes(self.mesh)
        return self._spans

    def _spec_fits(self, shape, spec) -> bool:
        """A PartitionSpec is usable on `shape` iff every named axis exists
        in the mesh and its total size divides the dimension it shards."""
        for dim, part in zip(shape, spec):
            if part is None:
                continue
            parts = part if isinstance(part, tuple) else (part,)
            size = 1
            for p in parts:
                if p not in self.mesh.shape:
                    return False
                size *= self.mesh.shape[p]
            if size == 0 or dim % size:
                return False
        return True

    def _hint_leaf(self, x, spec, place):
        if spec is None or not hasattr(x, "shape") \
                or not self._spec_fits(x.shape, spec):
            return x
        sharding = NamedSharding(self.mesh, spec)
        if place:
            # committed-placement skip: a leaf the prefetch thread (or a
            # previous placement pass) already device_put with exactly
            # this sharding must not be transferred again -- the redundant
            # device_put would serialize a copy the pipeline already paid
            if isinstance(x, jax.Array) \
                    and getattr(x, "sharding", None) == sharding:
                return x
            # put_global degrades to device_put on a single-process mesh
            # and assembles from addressable shards when the mesh spans
            # processes (only the local shards can be written here)
            return put_global(x, sharding)
        return jax.lax.with_sharding_constraint(x, sharding)

    def _make_step(self, topology: Topology):
        base = super()._make_step(topology)
        if all(p.state_sharding() is None
               for p in topology.processors.values()):
            return base

        def step(states, feedback, source_payload):
            states, fb, outputs = base(states, feedback, source_payload)
            return self._apply_hints(topology, states, place=False), \
                fb, outputs

        return step

    def _make_scan_layout(self, topology: Topology):
        # no scan layout under a mesh: every step re-constrains the hinted
        # leaves (``_apply_hints``), and a packed leaf would meet hints
        # written for its usual form -- e.g. the VHT statistics' attribute
        # split P(None, "model", None, None) on a 2-D array
        return None

    def _mesh_ctx(self):
        # mesh_context also publishes the mesh through active_mesh(), which
        # learner code consults at trace time (e.g. CluStream's macro phase
        # replicates its k-means inputs only when tracing under a mesh)
        return mesh_context(self.mesh)

    def _apply_hints(self, topology: Topology, states, *, place: bool):
        out = dict(states)
        for name, proc in topology.processors.items():
            hint = proc.state_sharding()
            if hint is None:
                continue
            out[name] = jax.tree.map(
                lambda x, s: self._hint_leaf(x, s, place=place),
                out[name], hint,
                is_leaf=lambda v: v is None or isinstance(v, P))
        return out

    def _make_boundary(self, topology: Topology):
        """Chunk-boundary phase under a mesh: after the hooks run, the
        hinted leaves are re-constrained exactly like every scanned step,
        so the carry stays physically partitioned across chunk boundaries
        however the boundary computation (e.g. CluStream's replicated
        macro gather) was sharded."""
        base = super()._make_boundary(topology)
        if base is None:
            return None

        def boundary(carry):
            carry = base(carry)
            states = self._apply_hints(topology, carry["states"],
                                       place=False)
            return {"states": states, "feedback": carry["feedback"]}

        return boundary

    def init(self, topology: Topology, key):
        topology = self._as_topology(topology)
        carry = super().init(topology, key)
        carry["states"] = self._shard_states(topology, carry["states"])
        return carry

    def place_carry(self, topology, carry):
        """Re-place a host-restored carry (checkpoint resume) per-shard,
        through the SAME placement pass as ``init`` (sharding hints plus
        the KEY-grouping fallback), so a resumed chunked run is as
        physically partitioned as the run that wrote the checkpoint."""
        topology = self._as_topology(topology)
        carry = dict(carry)
        carry["states"] = self._shard_states(topology, carry["states"])
        if self.spans_processes and carry.get("feedback") is not None:
            # restored feedback leaves are host arrays; they must join the
            # states on the global mesh before the first post-resume step
            carry["feedback"] = self._globalize(carry["feedback"])
        return carry

    def _grouping_of(self, topology, proc_name) -> Grouping | None:
        for s in topology.streams.values():
            for dst, g in s.destinations:
                if dst == proc_name:
                    return g
        return None

    def _shard_states(self, topology, states):
        out = self._apply_hints(topology, states, place=True)
        for name, st in out.items():
            if topology.processors[name].state_sharding() is not None:
                continue
            if self._grouping_of(topology, name) is Grouping.KEY:
                out[name] = jax.tree.map(
                    lambda x: self._hint_leaf(
                        x, leading_axis_spec("model", x), place=True), st)
        if self.spans_processes:
            out = {name: self._globalize(st) for name, st in out.items()}
        return out

    def _globalize(self, tree):
        """On a process-spanning mesh, leaves without a (fitting) hint must
        STILL live on the global mesh: replicate them.  A jit that mixes
        global-mesh arrays with per-process committed arrays raises a
        device-set mismatch, so replicate-by-default is the only safe
        fallback.  Leaves already on a process-spanning sharding (a prior
        placement pass, or the restored-and-placed path) pass through."""
        rep = NamedSharding(self.mesh, P())

        def one(x):
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                return x
            return put_global(x, rep)

        return jax.tree.map(one, tree)
