"""The robust online model server: micro-batching, admission control,
deadlines, and truthful degradation.

``ModelServer`` answers predict requests against the newest snapshot a
``SnapshotPublisher`` has installed, while the training loop keeps
publishing.  The SAMOA topology (model aggregator feeding evaluators)
recast as a serving system, hardened the way PR 6 hardened training:

  * **micro-batching under a bounded wait** -- a dispatcher thread
    collects up to ``max_batch`` requests or until ``max_wait_ms`` has
    elapsed since the batch opened, whichever first, then answers them
    with ONE jitted predict call.  Batches are padded to exactly
    ``max_batch`` rows (repeating a real row, never NaN), so the predict
    program compiles once and tail latency never pays a recompile;
  * **admission control** -- the request queue is bounded at
    ``queue_limit``; when it is full ``submit`` returns an explicit
    ``overloaded`` rejection immediately instead of queueing into
    unbounded latency.  Requests submitted before any snapshot exists
    are rejected ``unavailable`` for the same reason;
  * **deadlines with on-expiry shedding** -- every request carries a
    deadline (default ``deadline_ms``); requests whose deadline passed
    while queued are shed at batch formation rather than wasting a
    predict slot on an answer nobody is waiting for;
  * **truthful accounting** -- every submitted request ends in exactly
    one of ``answered | shed | overloaded | unavailable`` and the
    counters must reconcile: ``status()["accounting_ok"]`` is the
    invariant ``submitted == answered + shed + rejected + pending``, and
    the serving BENCH arm fails loudly when it does not hold;
  * **graceful degradation** -- answers carry the snapshot version, its
    staleness in chunks, and the publisher's ``degraded`` flag, so a
    stalled or circuit-broken publisher yields stale-but-finite answers
    that SAY they are stale, never silence and never garbage;
  * **stage counters** -- ``status()`` carries three histograms
    (``repro.runtime.telemetry``): ``queue_ms``, each answered request's
    wait from submission to its batch's formation; ``device_ms``, each
    batch's time from formation to its answers on the host; and
    ``chunks_ahead``, the training chunks issued but not yet drained
    when each batch's predict was issued -- at most that many chunk
    programs are ahead of it on the device.  All come from clock reads
    the dispatcher makes anyway, and are added under the lock the
    accounting takes.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.runtime.telemetry import Histogram
from repro.serving.predict import make_predict_fn


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 32          # micro-batch flush size
    max_wait_ms: float = 2.0     # micro-batch flush age
    queue_limit: int = 128       # admission bound (pending requests)
    deadline_ms: float = 100.0   # default per-request deadline


#: terminal request states
ANSWERED, SHED, OVERLOADED, UNAVAILABLE = \
    "answered", "shed", "overloaded", "unavailable"


class Request:
    """One predict request: a handle the caller waits on.

    ``status`` is ``"pending"`` until the server resolves it to one of
    the four terminal states; ``result(timeout)`` blocks until then.
    Answered requests carry ``pred`` plus ``meta`` (snapshot version /
    chunk, staleness in chunks, degraded flag, latency)."""

    __slots__ = ("x", "deadline", "submitted_at", "status", "pred", "meta",
                 "tenant", "_done")

    def __init__(self, x, deadline: float, submitted_at: float,
                 tenant: int | None = None):
        self.x = x
        self.deadline = deadline
        self.submitted_at = submitted_at
        self.tenant = tenant
        self.status = "pending"
        self.pred: Any = None
        self.meta: dict = {}
        self._done = threading.Event()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: float | None = None) -> "Request":
        if not self._done.wait(timeout):
            raise TimeoutError("request not resolved within timeout")
        return self


class ModelServer:
    """Serve predictions from published snapshots; see module docstring."""

    def __init__(self, learner, publisher, config: ServeConfig = None, *,
                 start: bool = True, clock=time.monotonic):
        self.publisher = publisher
        self.cfg = config if config is not None else ServeConfig()
        if self.cfg.max_batch < 1 or self.cfg.queue_limit < 1:
            raise ValueError("max_batch and queue_limit must be >= 1")
        self._fn = make_predict_fn(learner)
        # fleet serving: requests carry a tenant id and the predict fn
        # routes each row to its tenant's packed model
        from repro.ml.fleet import LearnerFleet
        self._fleet = learner if isinstance(learner, LearnerFleet) else None
        self._clock = clock
        self._q: queue.Queue = queue.Queue(maxsize=self.cfg.queue_limit)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._closed = False     # admission gate; see stop()
        self._thread: threading.Thread | None = None
        # accounting: submitted == answered + shed + rejected_overloaded
        #             + rejected_unavailable + pending (queued or in batch)
        self.submitted = 0
        self.answered = 0
        self.shed = 0
        self.rejected_overloaded = 0
        self.rejected_unavailable = 0
        self.batches = 0
        self.max_queue_depth = 0
        self.degraded_answers = 0
        self.queue_ms = Histogram()
        self.device_ms = Histogram()
        self.chunks_ahead = Histogram()
        if start:
            self.start()

    # ------------------------------------------------------------ control

    def start(self):
        if self._thread is not None:
            return
        self._stop.clear()
        with self._lock:
            self._closed = False
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="repro-serve-dispatch")
        self._thread.start()

    def stop(self, *, drain: bool = True):
        """Stop dispatching.  ``drain=True`` serves what is queued first;
        otherwise queued requests resolve ``shed`` (never left pending)."""
        if self._thread is not None and drain:
            while not self._q.empty():
                time.sleep(0.001)
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        # close admission BEFORE the final drain: submit() enqueues under
        # the same lock, so a racing request either made it into the queue
        # (and is resolved by the drain below) or observes _closed and
        # resolves ``unavailable`` -- it can never land in the queue after
        # this drain and hang its caller's result() forever
        with self._lock:
            self._closed = True
        while True:      # resolve anything still queued: no silent drops
            try:
                r = self._q.get_nowait()
            except queue.Empty:
                break
            self._finish(r, SHED, reason="server_stopped")

    # ------------------------------------------------------------- submit

    def submit(self, x, *, deadline_ms: float | None = None,
               tenant: int | None = None) -> Request:
        """Admit one request (x: one instance's model input, no batch
        axis).  Never blocks: a full queue is an immediate ``overloaded``
        rejection, no snapshot yet an ``unavailable`` one, and a submit
        that races ``stop()``'s final drain resolves ``unavailable``
        instead of parking in the dead queue.  Serving a ``LearnerFleet``
        requires ``tenant`` (which tenant's model answers)."""
        if self._fleet is not None:
            if tenant is None:
                raise ValueError(
                    "this server serves a LearnerFleet: submit(..., "
                    "tenant=<id>) is required to route the request")
            if not 0 <= int(tenant) < self._fleet.n_tenants:
                raise ValueError(
                    f"tenant {tenant} outside [0, {self._fleet.n_tenants})")
            tenant = int(tenant)
        elif tenant is not None:
            raise ValueError("tenant routing requires a LearnerFleet")
        now = self._clock()
        dl = self.cfg.deadline_ms if deadline_ms is None else deadline_ms
        r = Request(np.asarray(x), now + dl / 1e3, now, tenant=tenant)
        with self._lock:
            self.submitted += 1
        if self.publisher.current() is None:
            self._finish(r, UNAVAILABLE, reason="no_snapshot")
            return r
        # the queue put and the closed-check must be ONE atomic step with
        # respect to stop(): a request that checked "not stopped" and was
        # then preempted could otherwise enqueue after the dispatcher's
        # final drain pass -- never finished, result() hangs forever, and
        # the accounting invariant breaks with a phantom pending request
        verdict = None
        with self._lock:
            if self._closed:
                verdict = (UNAVAILABLE, "server_stopped")
            else:
                try:
                    self._q.put_nowait(r)
                    self.max_queue_depth = max(self.max_queue_depth,
                                               self._q.qsize())
                except queue.Full:
                    verdict = (OVERLOADED, "queue_full")
        if verdict is not None:
            self._finish(r, verdict[0], reason=verdict[1])
        return r

    # ---------------------------------------------------------- dispatch

    def _loop(self):
        wait_s = self.cfg.max_wait_ms / 1e3
        while not self._stop.is_set():
            try:
                first = self._q.get(timeout=0.02)
            except queue.Empty:
                continue
            batch = [first]
            with TraceAnnotation("repro.serve.batch_open"):
                opened = self._clock()
                while len(batch) < self.cfg.max_batch:
                    left = wait_s - (self._clock() - opened)
                    if left <= 0:
                        break
                    try:
                        batch.append(self._q.get(timeout=left))
                    except queue.Empty:
                        break
            self._serve_batch(batch)

    def _serve_batch(self, batch):
        now = self._clock()
        live = []
        for r in batch:
            if now > r.deadline:
                self._finish(r, SHED, reason="deadline_expired")
            else:
                live.append(r)
        if not live:
            return
        snap = self.publisher.current()
        if snap is None:       # publisher never ran; reject explicitly
            for r in live:
                self._finish(r, UNAVAILABLE, reason="no_snapshot")
            return
        xs = np.stack([r.x for r in live])
        pad = self.cfg.max_batch - xs.shape[0]
        if pad:
            # pad with a REAL row (never zeros/NaN: padded rows go through
            # the same predict program and garbage could trip finiteness
            # asserts); padded outputs are simply dropped
            xs = np.concatenate([xs, np.repeat(xs[-1:], pad, axis=0)], 0)
        # chunks the training loop issued and has not drained: at most
        # what this predict queues behind (unlocked reads of two counters)
        ahead = max(0, self.publisher.issued_cursor
                    - self.publisher.train_cursor)
        with TraceAnnotation("repro.serve.predict"):
            if self._fleet is not None:
                ts = np.asarray([r.tenant for r in live], np.int32)
                if pad:
                    ts = np.concatenate([ts, np.repeat(ts[-1:], pad)], 0)
                preds = np.asarray(self._fn(snap.state, jnp.asarray(xs),
                                            jnp.asarray(ts)))
            else:
                preds = np.asarray(self._fn(snap.state, jnp.asarray(xs)))
        stale = max(0, self.publisher.train_cursor - snap.chunk_index)
        degraded = self.publisher.degraded()
        done = self._clock()
        with self._lock:
            self.batches += 1
            self.device_ms.add((done - now) * 1e3)
            self.chunks_ahead.add(ahead)
        for i, r in enumerate(live):
            r.pred = preds[i]
            r.meta = {
                "snapshot_version": snap.version,
                "snapshot_chunk": snap.chunk_index,
                "staleness_chunks": stale,
                "degraded": degraded,
                "latency_ms": (done - r.submitted_at) * 1e3,
                "batch_size": len(live),
            }
            if r.tenant is not None:
                r.meta["tenant"] = r.tenant
            self._finish(r, ANSWERED, queue_ms=(now - r.submitted_at) * 1e3)
            if degraded:
                with self._lock:
                    self.degraded_answers += 1

    def _finish(self, r: Request, status: str, *, reason: str | None = None,
                queue_ms: float = 0.0):
        r.status = status
        if reason is not None:
            r.meta = dict(r.meta, reason=reason)
        with self._lock:
            if status == ANSWERED:
                self.answered += 1
                self.queue_ms.add(queue_ms)
            elif status == SHED:
                self.shed += 1
            elif status == OVERLOADED:
                self.rejected_overloaded += 1
            elif status == UNAVAILABLE:
                self.rejected_unavailable += 1
        r._done.set()

    # ------------------------------------------------------------- status

    def status(self) -> dict:
        with self._lock:
            resolved = (self.answered + self.shed + self.rejected_overloaded
                        + self.rejected_unavailable)
            pending = self.submitted - resolved
            out = {
                "submitted": self.submitted,
                "answered": self.answered,
                "shed": self.shed,
                "rejected_overloaded": self.rejected_overloaded,
                "rejected_unavailable": self.rejected_unavailable,
                "pending": pending,
                "batches": self.batches,
                "max_queue_depth": self.max_queue_depth,
                "degraded_answers": self.degraded_answers,
                "queue_limit": self.cfg.queue_limit,
                "accounting_ok": pending >= 0,
                "queue_ms": self.queue_ms.snapshot(),
                "device_ms": self.device_ms.snapshot(),
                "chunks_ahead": self.chunks_ahead.snapshot(),
            }
        out.update({f"publisher_{k}": v
                    for k, v in self.publisher.status().items()})
        return out
