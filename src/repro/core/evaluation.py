"""PrequentialEvaluation -- the paper's canonical Task (section 4).

"a classification task where each instance is used for testing first, and
then for training."  Wires a stream source, any learner exposing
``init``/``step``, and an evaluator that accumulates interleaved
test-then-train metrics; runs on any engine via the learner's jit'd step
(the default) or through an explicit Topology.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.topology import Task
from repro.distributed.sharding import host_value
from repro.runtime.telemetry import Histogram


def stack_outputs(outs):
    """Normalize engine ``run_stream`` outputs to ONE stacked pytree.

    ``LocalEngine`` returns a list of per-step output dicts (eager
    reference semantics); the scanned/chunked engines return a pytree
    stacked on a leading step axis.  Parity checks and metric reductions
    go through this helper instead of hand-rolling the conversion."""
    if isinstance(outs, list):
        if not outs:
            return {}
        return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return outs


def unstack_outputs(outs):
    """Inverse of ``stack_outputs``: a stacked pytree becomes the
    LocalEngine-shaped list of per-step output dicts."""
    if isinstance(outs, list):
        return outs
    leaves = jax.tree.leaves(outs)
    if not leaves:
        return []
    n = leaves[0].shape[0]
    return [jax.tree.map(lambda x: x[i], outs) for i in range(n)]


@dataclasses.dataclass
class PrequentialResult:
    metric: float            # accuracy (classification) or MAE (regression)
    throughput: float        # instances / second
    curve: list              # per-batch metric
    extra: dict


class PrequentialEvaluation(Task):
    def __init__(self, learner, stream, *, n_batches: int | None = None):
        self.learner = learner
        self.stream = stream
        self.n_batches = n_batches

    def run(self) -> PrequentialResult:
        init = self.learner.init
        try:
            state = init(jax.random.PRNGKey(0))
        except TypeError:
            state = init()
        step = jax.jit(self.learner.step)
        curve = []
        correct = abse = seen = 0.0
        t0 = None
        for i, (x, y) in enumerate(self.stream):
            if self.n_batches is not None and i >= self.n_batches:
                break
            state, m = step(state, x, y)
            if i == 0:
                jax.block_until_ready(m["seen"])
                t0 = time.perf_counter()    # exclude compile time
                continue
            c = float(m.get("correct", 0.0))
            a = float(m.get("abs_err", 0.0))
            s = float(m["seen"])
            correct += c
            abse += a
            seen += s
            curve.append((c or -a) / s if s else 0.0)
        dt = max(time.perf_counter() - (t0 or time.perf_counter()), 1e-9)
        metric = (correct / seen) if correct else (abse / seen)
        return PrequentialResult(
            metric=metric, throughput=seen / dt, curve=curve,
            extra={"state": state})


class MetricAccumulator:
    """Streaming prequential metric reduction with DEFERRED folding.

    The monolithic scan materializes ``[T, ...]`` metric outputs and
    reduces at the end; on an unbounded stream that is exactly the memory
    cliff the chunked runtime removes.  This accumulator consumes one
    chunk's stacked metrics at a time -- only ``[chunk_len]`` scalars ever
    cross to host -- and keeps running sums plus the per-batch curve.  Its
    state round-trips through ``state()``/``load()`` so a mid-stream
    checkpoint reproduces the uninterrupted run's final metrics exactly.

    ``update`` does NOT synchronize: the chunk's metric leaves are kept as
    (possibly still-executing) device arrays and folded lazily, in arrival
    order, the first time a reader needs the numbers (``metric`` /
    ``curve`` / ``seen`` / ``state()``).  The fold itself is the exact
    float64 numpy reduction it always was -- deferral changes WHEN the
    host pulls values, never WHAT it computes -- which is what lets the
    pipelined chunk driver dispatch chunk k+1 while chunk k's metrics are
    still on device.  Thread-safe: the driving loop appends while a drain
    thread flushes forks for checkpoints.
    """

    def __init__(self):
        # scalars for single-learner runs; [F] per-tenant columns when the
        # metrics carry a trailing fleet axis (LearnerFleet runs) -- one
        # column per tenant, so no tenant's metrics ever mix
        self._correct = 0.0
        self._abs_err = 0.0
        self._seen = 0.0
        self._curve: list = []
        self._pending: list = []       # unfolded per-chunk metric dicts
        self._lock = threading.Lock()

    def update(self, metrics):
        """Record one chunk's stacked metrics dict -- NO host sync here.

        Leaves are ``[steps]`` (single learner) or ``[steps, F]`` (fleet:
        one column per tenant); they stay device arrays until a reader
        forces the fold.  A step that contributes zero weight (an
        all-padding tail, an exhausted tenant) CARRIES THE PRIOR curve
        value forward instead of dividing by zero -- a spurious 0.0 dip
        would misreport a perfectly healthy stream."""
        with self._lock:
            self._pending.append(metrics)

    def _fold(self, metrics):
        # host_value: a direct read on single-process runs; on a
        # process-spanning mesh the metric columns come back replicated
        # from the chunk program's cross-process reduction and read their
        # LOCAL replica (partitioned leaves would gather -- a collective,
        # which is why the multi-process driver folds on the main thread)
        seen = np.asarray(host_value(metrics["seen"]), np.float64)
        zeros = np.zeros_like(seen)
        corr = np.asarray(host_value(metrics.get("correct", zeros)),
                          np.float64)
        abse = np.asarray(host_value(metrics.get("abs_err", zeros)),
                          np.float64)
        self._correct = self._correct + corr.sum(axis=0)
        self._abs_err = self._abs_err + abse.sum(axis=0)
        self._seen = self._seen + seen.sum(axis=0)
        signed = np.where(corr > 0, corr, -abse)
        prev = self._curve[-1] if self._curve \
            else np.zeros(seen.shape[1:], np.float64)
        for t in range(seen.shape[0]):
            val = np.where(seen[t] > 0,
                           signed[t] / np.maximum(seen[t], 1e-9), prev)
            prev = float(val) if val.ndim == 0 else val
            self._curve.append(prev)

    def flush(self):
        """Fold every pending chunk (in update order).  This is the one
        place device metric values cross to host."""
        with self._lock:
            for m in self._pending:
                self._fold(m)
            self._pending.clear()
        return self

    def fork(self):
        """A snapshot accumulator covering exactly the chunks updated so
        far, WITHOUT forcing a flush: folded state is shared by reference
        (folds rebind, never mutate in place) and the pending list is
        copied.  The pipelined driver hands forks to its drain thread so a
        checkpoint written chunks behind the dispatch frontier still
        records metrics up to ITS chunk only."""
        out = MetricAccumulator()
        with self._lock:
            out._correct = self._correct
            out._abs_err = self._abs_err
            out._seen = self._seen
            out._curve = list(self._curve)
            out._pending = list(self._pending)
        return out

    @property
    def correct(self):
        return self.flush()._correct

    @property
    def abs_err(self):
        return self.flush()._abs_err

    @property
    def seen(self):
        return self.flush()._seen

    @property
    def curve(self) -> list:
        return self.flush()._curve

    @property
    def metric(self):
        """Running metric: accuracy when correct-counts flowed, MAE
        otherwise.  A float for single-learner runs, an ``[F]`` vector for
        fleet runs; zero-weight (tenant) columns report 0.0, never NaN."""
        self.flush()
        if np.ndim(self._seen) == 0:
            if not self._seen:
                return 0.0
            return float(self._correct / self._seen) if self._correct \
                else float(self._abs_err / self._seen)
        num = np.where(np.asarray(self._correct) > 0,
                       self._correct, self._abs_err)
        return np.where(np.asarray(self._seen) > 0,
                        num / np.maximum(self._seen, 1e-9), 0.0)

    def state(self):
        """Checkpointable pytree of the accumulator."""
        self.flush()
        return {"correct": np.asarray(self._correct, np.float64),
                "abs_err": np.asarray(self._abs_err, np.float64),
                "seen": np.asarray(self._seen, np.float64),
                "curve": np.asarray(self._curve, np.float64)}

    def load(self, state):
        def _num(v):
            v = np.asarray(v, np.float64)
            return float(v) if v.ndim == 0 else v
        with self._lock:
            self._correct = _num(state["correct"])
            self._abs_err = _num(state["abs_err"])
            self._seen = _num(state["seen"])
            curve = np.asarray(state["curve"], np.float64)
            self._curve = [float(v) for v in curve] if curve.ndim <= 1 \
                else [row for row in curve]
            self._pending = []
        return self


def _metrics_only(outs):
    """Chunk-output reduction compiled into the chunk program (a STABLE
    module-level function: the engine caches the compiled chunk program on
    the reducer's identity).  Keeping only the metrics stream lets XLA
    dead-code-eliminate every unread output stream from the chunk scan --
    a topology emitting ``[chunk_len, B]`` predictions nobody reads stops
    materializing them entirely."""
    return {"metrics": outs["metrics"]}


@dataclasses.dataclass
class _ChunkTicket:
    """One dispatched-but-not-drained chunk: everything the drain thread
    needs to complete the chunk's host-side bookkeeping in order."""

    index: int
    done: Any             # small device leaf to await (chunk completion)
    flag: Any             # lazy finite scalar, or None when check is off
    carry: Any            # post-chunk carry (copied when donation is live)
    outs: Any             # full outputs, only when on_chunk needs them
    chunk: Any            # the Chunk, only when on_chunk needs it
    pub_state: Any        # model state to publish, or None
    acc_fork: Any         # MetricAccumulator fork for a due checkpoint
    t_start: float        # dispatch wall-clock (heartbeat duration)


class _ChunkDrain:
    """Ordered background completion for the pipelined chunk driver.

    The main loop dispatches chunk k+1 while the device executes chunk k;
    every per-chunk host obligation that used to stall the dispatch loop
    -- the finite check's sync, checkpoint save, snapshot publish, the
    ``on_chunk`` callback, supervisor heartbeats -- moves here, processed
    strictly in chunk order on one worker thread.  A semaphore sized
    ``max_inflight_chunks`` is the backpressure: ``submit`` blocks once
    that many chunks are dispatched but undrained, which also bounds the
    device-side queue and the prefetched payload buffers kept alive.

    Failure semantics mirror the synchronous driver exactly: a non-finite
    flag marks ``poisoned_at`` and every later ticket is discarded
    unprocessed (its checkpoint is never written, its snapshot never
    published), newly-dead hosts detected after a heartbeat latch into
    ``newly_dead`` for the main loop to act on at the next boundary, and
    a raising callback re-raises on the main loop at the next submit or
    flush."""

    def __init__(self, ev, report, check: bool, window: int,
                 known_dead: set):
        self.ev = ev
        self.report = report
        self.check = check
        self.poisoned_at: int | None = None
        self.known_dead = set(known_dead)
        self.newly_dead: set = set()
        self._lock = threading.Lock()
        self._q: queue.Queue = queue.Queue()
        self._sem = threading.Semaphore(max(1, window))
        self._error: BaseException | None = None
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------ main-loop side

    def submit(self, ticket: _ChunkTicket):
        """Enqueue one dispatched chunk; blocks on the in-flight window."""
        self._raise_pending()
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._work, name="chunk-drain", daemon=True)
            self._thread.start()
        self._sem.acquire()
        self._q.put(ticket)

    def flush(self):
        """Block until every submitted ticket is drained (or discarded)."""
        self._q.join()
        self._raise_pending()

    def stop(self):
        if self._thread is not None:
            self._q.put(None)
            self._thread.join()
            self._thread = None

    def take_newly_dead(self) -> set:
        with self._lock:
            out, self.newly_dead = self.newly_dead, set()
            return out

    def has_event(self) -> bool:
        with self._lock:
            return (self.poisoned_at is not None or bool(self.newly_dead)
                    or self._error is not None)

    def clear_poison(self):
        with self._lock:
            self.poisoned_at = None

    def _raise_pending(self):
        with self._lock:
            err, self._error = self._error, None
        if err is not None:
            raise err

    # --------------------------------------------------------- worker side

    def _work(self):
        while True:
            t = self._q.get()
            if t is None:
                self._q.task_done()
                return
            try:
                if self._error is None:
                    self._process(t)
            except BaseException as e:   # surfaced on the main loop
                with self._lock:
                    self._error = e
            finally:
                self._sem.release()
                self._q.task_done()

    def _process(self, t: _ChunkTicket):
        ev = self.ev
        if self.poisoned_at is not None:
            return                      # discard: the run is rolling back
        with TraceAnnotation("repro.drain.wait"):
            if t.flag is not None:      # the per-chunk sync, off hot path
                finite = bool(t.flag)
            else:
                finite = True
                jax.block_until_ready(t.done)
        if not finite:
            with self._lock:
                self.poisoned_at = t.index
            return
        if t.pub_state is not None:
            with TraceAnnotation("repro.publish"):
                ev.publisher.publish(t.index, t.pub_state)
        if t.acc_fork is not None:
            with TraceAnnotation("repro.checkpoint.save"):
                ev._save(t.index, t.carry, t.acc_fork)
        if ev.on_chunk is not None:
            ev.on_chunk(t.outs, t.chunk, t.carry)
        if ev.supervisor is not None:
            ev.supervisor.heartbeat(ev.host, t.index,
                                    time.perf_counter() - t.t_start)
            self.report["heartbeats"] += 1
            dead = ev._dead_hosts()
            with self._lock:
                newly = dead - self.known_dead
                if newly:
                    self.known_dead |= newly
                    self.newly_dead |= newly


#: the chunk loop's host stages: each is a ``repro.chunk.<stage>`` span and
#: a histogram of milliseconds per chunk in the run's ``report["stages"]``
#: -- taking the next chunk from the stream, the engine call through the
#: ticket, and the wait for a free in-flight slot (pipelined driver only)
PIPELINED_STAGES = ("stream_wait", "dispatch", "backpressure")
SYNC_STAGES = PIPELINED_STAGES[:2]


class ChunkedPrequentialEvaluation(Task):
    """Prequential task on the chunked stream runtime.

    Drives the engine's chunked scan one chunk at a time: metrics reduce
    per chunk through a ``MetricAccumulator`` (prequential curves stream
    to host incrementally; no ``[T, ...]`` output pytree is ever
    materialized), and an optional ``CheckpointManager`` snapshots the
    full resumable state -- engine carry (states + feedback), the chunk
    cursor, the stream RNG key, and the metric accumulator -- every
    ``checkpoint_every`` chunks.  ``run(resume=True)`` picks up a killed
    run mid-stream bit-identically: the resumed run's final carry and
    metrics equal the uninterrupted run's.

    Fault tolerance (all optional, zero overhead when off):

      * ``supervisor`` + ``host``: a per-chunk heartbeat (with the chunk's
        wall duration) feeds the ``Supervisor`` ledger, so dead-host and
        straggler detection run at chunk-boundary granularity.
      * elastic re-place: when the supervisor reports newly DEAD hosts at
        a chunk boundary and a ``remesh`` factory was given, the run
        snapshots its state, asks ``Supervisor.propose_mesh(chips_per_host,
        model_parallel)`` for the survivor mesh, builds a fresh engine via
        ``remesh(shape, axes)``, and re-enters the stream from the same
        cursor through ``restore_structured`` + ``place_carry`` -- the
        shrunken-mesh continuation is bit-identical to the uninterrupted
        run (the sharded==unsharded guarantee).
      * ``injector`` (``repro.runtime.chaos.FaultInjector``): kill /
        poison hooks fire at their scheduled chunks.
      * finite-check + rollback: ``check_finite`` (default: on whenever a
        checkpoint or injector is present) scans the carry for non-finite
        leaves after every chunk; on detection the run rolls back to the
        last checkpoint (or the pristine init) and, per ``poison_policy``,
        retries the poison chunk up to ``max_poison_retries`` times before
        skipping it.  Every decision lands in the run report
        (``result.extra["report"]``).

    The driving loop runs each chunk through its own
    ``engine.run_stream_chunked`` call -- same priming, same chunk
    program, same boundary-hook ordering as one fused call (the compiled
    chunk executables are cached per topology), so chunk-at-a-time
    control flow costs nothing and makes rollback/re-place possible.

    Pipelining (``pipeline``, default on): the dispatch loop is
    FREE-RUNNING -- the host dispatches chunk k+1 while the device still
    executes chunk k, and blocks only at stream end, at an explicit
    fence (rollback, elastic re-place, kill site), or on backpressure
    once ``max_inflight_chunks`` chunks are dispatched but undrained.
    Per-chunk host work (finite-check sync, checkpoint save, snapshot
    publish, ``on_chunk``, heartbeats) runs in chunk order on a drain
    thread (``_ChunkDrain``).  Results are bit-identical to
    ``pipeline=False`` -- same metrics, same curve, same carry, same
    checkpoint manifests, same kill/poison/elastic semantics -- the
    synchronous driver survives as the oracle and for debugging (see
    benchmarks/README.md).

    Each run's ``report["stages"]`` holds its stage table
    (``PIPELINED_STAGES``; the synchronous driver has no backpressure):
    a ``telemetry.Histogram`` snapshot of milliseconds per chunk and
    ``total_s``.  The newest finished run's
    table is also ``ChunkedPrequentialEvaluation.last_stages``, for a
    reader in the same process; each finished run replaces it.
    ``report["packed_chunks"]`` counts the chunk programs that ran with a
    processor's state in its scan layout (``Processor.to_scan``: the
    VHT's statistics packed 2-D), so a run shows whether that engaged.
    """

    last_stages: dict | None = None

    def __init__(self, learner, stream, *, engine=None,
                 checkpoint=None, checkpoint_every: int = 1, key=None,
                 on_chunk=None, supervisor=None, host="host0",
                 injector=None, publisher=None,
                 check_finite: bool | None = None,
                 poison_policy: str = "retry", max_poison_retries: int = 1,
                 remesh=None, chips_per_host: int = 1,
                 model_parallel: int = 1,
                 pipeline: bool | None = None,
                 max_inflight_chunks: int = 2,
                 compile_cache_dir=None):
        from repro.core.engines import JitEngine
        self.learner = learner
        self.stream = stream
        self.engine = engine if engine is not None else JitEngine()
        if not hasattr(self.engine, "run_stream_chunked"):
            raise TypeError(
                f"{type(self.engine).__name__} has no chunked driver; "
                "use JitEngine/ShardMapEngine (LocalEngine's eager "
                "ChunkedStream loop is a parity oracle, not an "
                "evaluation driver)")
        self.checkpoint = checkpoint
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.on_chunk = on_chunk     # optional extra per-chunk callback,
                                     # chained after the metric reduction
        self.supervisor = supervisor
        self.host = host
        self.injector = injector
        self.publisher = publisher   # serving SnapshotPublisher (or the
                                     # chaos-wrapped proxy); fed at chunk
                                     # boundaries on the healthy path only
        self.check_finite = check_finite
        if poison_policy not in ("retry", "skip"):
            raise ValueError(f"unknown poison_policy {poison_policy!r}")
        self.poison_policy = poison_policy
        self.max_poison_retries = max(0, int(max_poison_retries))
        self.remesh = remesh         # (shape, axes) -> engine factory
        self.chips_per_host = int(chips_per_host)
        self.model_parallel = int(model_parallel)
        self.pipeline = pipeline     # None -> pipelined (the default;
                                     # process-spanning meshes force the
                                     # synchronous driver, see run())
        self.max_inflight_chunks = max(1, int(max_inflight_chunks))
        self.compile_cache_dir = compile_cache_dir
        if compile_cache_dir is not None:
            # $JAX_COMPILATION_CACHE_DIR, when set, overrides the argument
            from repro.runtime import compile_cache
            self.compile_cache_dir = compile_cache.enable(compile_cache_dir)
        self.report: dict = {}

    def _save(self, chunk_index: int, carry, acc: MetricAccumulator):
        cursor = chunk_index + 1          # next chunk to run
        self.checkpoint.save(cursor, {
            "carry": carry,
            "cursor": np.int64(cursor),
            "key": self.key,
            "metrics": acc.state(),
        })

    def _restore(self):
        """(carry, cursor, acc) from the newest intact checkpoint, placed
        onto the current engine; None when nothing is on disk."""
        if self.checkpoint is None or self.checkpoint.latest_step() is None:
            return None
        blob, _ = self.checkpoint.restore_structured()
        carry = blob["carry"]
        place = getattr(self.engine, "place_carry", None)
        if place is not None:
            carry = place(self.learner, carry)
        self.key = jnp.asarray(blob["key"])
        acc = MetricAccumulator().load(blob["metrics"])
        return carry, int(blob["cursor"]), acc

    def _dead_hosts(self) -> set:
        if self.supervisor is None:
            return set()
        from repro.runtime.supervisor import HostStatus
        return {h for h, st in self.supervisor.hosts.items()
                if st.status is HostStatus.DEAD}

    def _rollback(self, poison_chunk: int, skip: set, retries: dict,
                  report: dict, key0):
        """Non-finite carry after `poison_chunk`: decide retry-vs-skip,
        then roll back to the last checkpoint (or the pristine initial
        state when none exists).  Returns (carry, cursor, acc)."""
        n = retries.get(poison_chunk, 0)
        if self.poison_policy == "retry" and n < self.max_poison_retries:
            retries[poison_chunk] = n + 1
            decision = "retry"
        else:
            skip.add(poison_chunk)
            report["skipped_chunks"].append(poison_chunk)
            decision = "skip"
        restored = self._restore()
        if restored is not None:
            carry, cursor, acc = restored
        else:
            self.key = key0
            carry = self.engine.init(self.learner, key0)
            cursor = self.stream.start_chunk
            acc = MetricAccumulator()
        report["rollbacks"] += 1
        report["events"].append(
            ("poison", poison_chunk, decision, cursor))
        return carry, cursor, acc

    def _elastic_replace(self, cursor: int, carry, acc, report: dict,
                         newly_dead: set):
        """Host loss at a chunk boundary: snapshot, shrink the mesh to the
        survivors (``propose_mesh``), rebuild the engine, and re-place the
        carry.  Metric/curve state lives on host already; only the carry
        crosses meshes (through the mesh-independent checkpoint)."""
        report["events"].append(("host_lost", tuple(sorted(newly_dead)),
                                 cursor))
        if self.remesh is None:
            return carry           # detection only; nothing to rebuild
        shape, axes = self.supervisor.propose_mesh(
            self.chips_per_host, model_parallel=self.model_parallel)
        if self.checkpoint is not None:
            # blocking snapshot: the re-place round-trips through the
            # checkpoint exactly like a real restart would
            self._save(cursor - 1, carry, acc)
            self.checkpoint.wait()
            self.engine = self.remesh(shape, axes)
            restored = self._restore()
            carry = restored[0]
        else:
            host_carry = jax.tree.map(host_value, carry)
            self.engine = self.remesh(shape, axes)
            carry = host_carry
            place = getattr(self.engine, "place_carry", None)
            if place is not None:
                carry = place(self.learner, carry)
        report["remeshes"] += 1
        report["events"].append(
            ("remesh", tuple(shape), tuple(axes), cursor))
        return carry

    def _prologue(self, resume: bool, report: dict):
        """Shared run setup: resume-or-init, restored-instance baseline,
        finite-check default.  Returns (carry, start, acc, seen0, check)."""
        acc = MetricAccumulator()
        carry = None
        start = self.stream.start_chunk
        if resume:
            restored = self._restore()
            if restored is not None:
                carry, start, acc = restored
                report["events"].append(("resume", start))
        if carry is None:
            carry = self.engine.init(self.learner, self.key)
        # restored instances: not processed now (summed over the fleet
        # axis when the accumulator keeps per-tenant columns)
        seen0 = float(np.sum(acc.seen))
        check = self.check_finite
        if check is None:       # default: on iff recovery can act on it
            check = self.checkpoint is not None or self.injector is not None
        return carry, start, acc, seen0, check

    def _epilogue(self, carry, acc, report, *, t0, timed, seen0, start,
                  end, stages) -> PrequentialResult:
        """Shared run teardown: final fence, throughput, pending-writer
        fences (checkpoint, async publisher), source-retry accounting, and
        the run's stage table (``report["stages"]``, also left in
        ``ChunkedPrequentialEvaluation.last_stages``)."""
        jax.block_until_ready(jax.tree.leaves(carry)[0])
        t_end = time.perf_counter()
        wall = max(t_end - t0, 1e-9)
        seen_total = float(np.sum(acc.seen))
        if len(timed) == 0 or seen_total == timed[0][1]:
            thr = (seen_total - seen0) / wall     # single-chunk stream
        else:
            thr = (seen_total - timed[0][1]) / max(t_end - timed[0][0], 1e-9)
        if self.checkpoint is not None:
            self.checkpoint.wait()
        report["source_retries"] = list(
            getattr(self.stream, "retry_events", []))
        # the events list is a capped ring buffer; the COUNT stays exact
        report["source_retry_count"] = int(
            getattr(self.stream, "retry_count",
                    len(report["source_retries"])))
        report["source_retries_dropped"] = int(
            getattr(self.stream, "retry_events_dropped", 0))
        if self.publisher is not None:
            flush = getattr(self.publisher, "flush", None)
            if callable(flush):
                flush()     # async publisher: settle counters for status
            status = getattr(self.publisher, "status", None)
            if callable(status):
                report["snapshots"] = status()
        if self.compile_cache_dir is not None:
            from repro.runtime import compile_cache
            report["compile_cache"] = dict(
                dir=str(self.compile_cache_dir), **compile_cache.stats())
        report["stages"] = {name: dict(h.snapshot(), total_s=h.sum / 1e3)
                            for name, h in stages.items()}
        ChunkedPrequentialEvaluation.last_stages = report["stages"]
        return PrequentialResult(
            metric=acc.metric, throughput=thr, curve=acc.curve,
            extra={"carry": carry, "seen": acc.seen,
                   "chunks": end - start, "wall_s": wall,
                   "report": report})

    def run(self, *, resume: bool = True) -> PrequentialResult:
        """Drive the stream.  ``pipeline=None``/``True`` uses the
        free-running async driver; ``pipeline=False`` the synchronous
        oracle.  Both produce bit-identical results.

        On a process-spanning mesh the synchronous driver is mandatory:
        cross-process collectives (the chunk programs, checkpoint
        gathers) must be issued in the SAME order on every process, and
        the pipelined driver's drain thread interleaves its host syncs
        with the dispatch loop nondeterministically per process."""
        if bool(getattr(self.engine, "spans_processes", False)):
            if self.pipeline:
                raise ValueError(
                    "pipeline=True is not supported on a process-spanning "
                    "mesh: the drain thread would issue cross-process "
                    "collectives out of order; use pipeline=None/False")
            return self._run_sync(resume=resume)
        if self.pipeline is None or self.pipeline:
            return self._run_pipelined(resume=resume)
        return self._run_sync(resume=resume)

    def _run_sync(self, *, resume: bool = True) -> PrequentialResult:
        learner = self.learner
        report = {"events": [], "skipped_chunks": [], "rollbacks": 0,
                  "remeshes": 0, "heartbeats": 0, "source_retries": [],
                  "packed_chunks": 0}
        self.report = report
        key0 = self.key
        carry, start, acc, seen0, check = self._prologue(resume, report)
        from repro.runtime.chaos import carry_all_finite

        every = self.checkpoint_every
        # throughput excludes the first chunk (where the chunk programs
        # compile), mirroring PrequentialEvaluation's compile exclusion;
        # timed[...] = (t after first chunk, instances seen by then)
        timed: list = []
        skip: set[int] = set()
        retries: dict[int, int] = {}
        known_dead = self._dead_hosts()
        end = self.stream.n_chunks
        cursor = start

        stages = {name: Histogram() for name in SYNC_STAGES}
        wait_h, dispatch_h = stages.values()

        t0 = time.perf_counter()
        while cursor < end:
            poisoned_at = None
            it = iter(self.stream.starting_at(cursor))
            t_free = time.perf_counter()
            try:
                while True:
                    with TraceAnnotation("repro.chunk.stream_wait"):
                        chunk = next(it, None)
                    if chunk is None:
                        break
                    if chunk.index in skip:
                        report["events"].append(("skip", chunk.index))
                        cursor = chunk.index + 1
                        continue
                    tc = time.perf_counter()
                    wait_h.add((tc - t_free) * 1e3)
                    with TraceAnnotation("repro.chunk.dispatch"):
                        if self.injector is not None:
                            # straggler injection: the sleep lands inside
                            # the timed region so the supervisor's
                            # heartbeat sees the slow chunk
                            self.injector.maybe_delay(chunk.index)
                        packed0 = self.engine.packed_chunks
                        carry, outs = self.engine.run_stream_chunked(
                            learner, carry, [chunk],
                            reduce_outputs=(_metrics_only
                                            if self.on_chunk is None
                                            else None))
                        report["packed_chunks"] += \
                            self.engine.packed_chunks - packed0
                        if self.injector is not None:
                            # models "this chunk's compute blew up": the
                            # NaN lands in the post-chunk carry, where the
                            # boundary finite-check must catch it
                            carry = self.injector.maybe_poison(chunk.index,
                                                               carry)
                        if check and not carry_all_finite(carry):
                            poisoned_at = chunk.index
                            break
                        if self.injector is not None:
                            self.injector.maybe_kill(chunk.index)
                        acc.update(outs["metrics"])
                    dispatch_h.add((time.perf_counter() - tc) * 1e3)
                    if not timed:
                        jax.block_until_ready(jax.tree.leaves(carry)[0])
                        timed.append((time.perf_counter(),
                                      float(np.sum(acc.seen))))
                    if self.publisher is not None:
                        # snapshot publication rides the same boundary as
                        # the metrics/checkpoint: only a carry that passed
                        # the finite check reaches here, and the publisher
                        # re-validates (finiteness + manifest structure
                        # round-trip) before readers see anything
                        from repro.serving.snapshot import model_state_of
                        with TraceAnnotation("repro.publish"):
                            self.publisher.publish(chunk.index,
                                                   model_state_of(carry))
                    if self.checkpoint is not None \
                            and (chunk.index + 1) % every == 0:
                        with TraceAnnotation("repro.checkpoint.save"):
                            self._save(chunk.index, carry, acc)
                    if self.on_chunk is not None:
                        self.on_chunk(outs, chunk, carry)
                    cursor = chunk.index + 1
                    if self.supervisor is not None:
                        self.supervisor.heartbeat(
                            self.host, chunk.index,
                            time.perf_counter() - tc)
                        report["heartbeats"] += 1
                        newly_dead = self._dead_hosts() - known_dead
                        if newly_dead:
                            known_dead |= newly_dead
                            carry = self._elastic_replace(
                                cursor, carry, acc, report, newly_dead)
                            break   # re-enter from cursor on the new mesh
                    t_free = time.perf_counter()
            finally:
                close = getattr(it, "close", None)
                if close is not None:
                    close()   # unblock the producer thread deterministically
            if poisoned_at is not None:
                carry, cursor, acc = self._rollback(
                    poisoned_at, skip, retries, report, key0)

        return self._epilogue(carry, acc, report, t0=t0, timed=timed,
                              seen0=seen0, start=start, end=end,
                              stages=stages)

    def _run_pipelined(self, *, resume: bool = True) -> PrequentialResult:
        """Free-running chunk driver: dispatch chunk k+1 while the device
        executes chunk k.  The host loop never blocks on a chunk's result
        -- the finite check becomes a lazy device flag, metrics enqueue as
        deferred device arrays, and checkpoint/publish/on_chunk/heartbeat
        obligations ride a ``_ChunkTicket`` to the drain thread, which
        completes them strictly in chunk order.  Blocking points: stream
        end, the first chunk (compile-exclusion timestamp), kill fences,
        rollback / elastic re-place boundaries, and backpressure once
        ``max_inflight_chunks`` tickets are undrained.  Bit-identical to
        ``_run_sync`` by construction: same chunk programs, same fold
        order, same failure ordering."""
        learner = self.learner
        report = {"events": [], "skipped_chunks": [], "rollbacks": 0,
                  "remeshes": 0, "heartbeats": 0, "source_retries": [],
                  "packed_chunks": 0}
        self.report = report
        key0 = self.key
        carry, start, acc, seen0, check = self._prologue(resume, report)
        from repro.runtime.chaos import carry_finite_flag
        from repro.serving.snapshot import model_state_of

        every = self.checkpoint_every
        inj = self.injector
        reducer = _metrics_only if self.on_chunk is None else None
        # donated buffers die at the NEXT dispatch; anything a ticket must
        # still read afterwards (checkpoint/publish/on_chunk carry) gets
        # copied first.  CPU never donates, so this is free there.
        donating = bool(getattr(self.engine, "donate", False)) \
            and jax.default_backend() != "cpu"
        timed: list = []
        skip: set[int] = set()
        retries: dict[int, int] = {}
        end = self.stream.n_chunks
        cursor = start

        stages = {name: Histogram() for name in PIPELINED_STAGES}
        wait_h, dispatch_h, backpressure_h = stages.values()

        t0 = time.perf_counter()
        drain = _ChunkDrain(self, report, check, self.max_inflight_chunks,
                            self._dead_hosts())
        try:
            while cursor < end:
                poisoned_local = None
                it = iter(self.stream.starting_at(cursor))
                t_free = time.perf_counter()
                try:
                    while True:
                        with TraceAnnotation("repro.chunk.stream_wait"):
                            chunk = next(it, None)
                        if chunk is None:
                            break
                        if drain.has_event():
                            break    # fence: rollback/re-place/error pending
                        if chunk.index in skip:
                            report["events"].append(("skip", chunk.index))
                            cursor = chunk.index + 1
                            continue
                        tc = time.perf_counter()
                        wait_h.add((tc - t_free) * 1e3)
                        with TraceAnnotation("repro.chunk.dispatch"):
                            if inj is not None:
                                inj.maybe_delay(chunk.index)
                            packed0 = self.engine.packed_chunks
                            carry, outs = self.engine.run_stream_chunked(
                                learner, carry, [chunk],
                                reduce_outputs=reducer)
                            report["packed_chunks"] += \
                                self.engine.packed_chunks - packed0
                            if self.publisher is not None:
                                self.publisher.issued_cursor = chunk.index
                            if inj is not None:
                                carry = inj.maybe_poison(chunk.index, carry)
                            flag = carry_finite_flag(carry) if check else None
                            if (inj is not None
                                    and inj.kill_at_chunk is not None
                                    and not inj.killed
                                    and int(chunk.index)
                                    == int(inj.kill_at_chunk)):
                                # kill fence: drain everything first so
                                # exactly the checkpoints a synchronous run
                                # would have issued are on disk, then
                                # replicate the sync ordering (earlier
                                # poison > own finite check > kill) before
                                # dying
                                drain.flush()
                                if drain.poisoned_at is not None:
                                    break
                                if flag is not None and not bool(flag):
                                    poisoned_local = chunk.index
                                    break
                                inj.maybe_kill(chunk.index)
                            acc.update(outs["metrics"])
                            save_due = (self.checkpoint is not None
                                        and (chunk.index + 1) % every == 0)
                            # fork BEFORE dispatching the next chunk: the
                            # snapshot covers exactly chunks <= this one,
                            # no matter when the drain's flush happens
                            acc_fork = acc.fork() if save_due else None
                            t_carry = carry
                            if donating and (save_due
                                             or self.on_chunk is not None
                                             or self.publisher is not None):
                                t_carry = jax.tree.map(jnp.array, carry)
                            on_chunk = self.on_chunk is not None
                            ticket = _ChunkTicket(
                                index=chunk.index,
                                done=jax.tree.leaves(outs["metrics"])[0],
                                flag=flag,
                                carry=t_carry,
                                outs=outs if on_chunk else None,
                                chunk=chunk if on_chunk else None,
                                pub_state=(model_state_of(t_carry)
                                           if self.publisher is not None
                                           else None),
                                acc_fork=acc_fork,
                                t_start=tc)
                        t_submit = time.perf_counter()
                        dispatch_h.add((t_submit - tc) * 1e3)
                        with TraceAnnotation("repro.chunk.backpressure"):
                            drain.submit(ticket)
                        t_free = time.perf_counter()
                        backpressure_h.add((t_free - t_submit) * 1e3)
                        cursor = chunk.index + 1
                        if not timed:
                            # compile-exclusion timestamp (same as sync):
                            # the only steady-state sync, and only once; no
                            # stage's time
                            jax.block_until_ready(jax.tree.leaves(carry)[0])
                            timed.append((time.perf_counter(),
                                          float(np.sum(acc.seen))))
                            t_free = timed[0][0]
                finally:
                    close = getattr(it, "close", None)
                    if close is not None:
                        close()   # unblock the producer deterministically
                drain.flush()
                poisoned = drain.poisoned_at
                if poisoned is None:
                    poisoned = poisoned_local
                if poisoned is not None:
                    # main-loop state past the poison chunk is garbage
                    # (dispatched blind); _rollback replaces carry, cursor
                    # and accumulator wholesale, so none of it survives
                    carry, cursor, acc = self._rollback(
                        poisoned, skip, retries, report, key0)
                    drain.clear_poison()
                    continue
                newly_dead = drain.take_newly_dead()
                if newly_dead:
                    carry = self._elastic_replace(
                        cursor, carry, acc, report, newly_dead)
        finally:
            drain.stop()

        return self._epilogue(carry, acc, report, t0=t0, timed=timed,
                              seen0=seen0, start=start, end=end,
                              stages=stages)
