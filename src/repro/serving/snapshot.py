"""Snapshot publication: the train -> serve handoff.

The chunked training loop and the serving path share one model, but must
never share a MUTATING model: the engine carry is rewritten every scanned
step (and donated on accelerators), while a predict request may read it at
any moment.  ``SnapshotPublisher`` is the boundary between the two worlds:

  * the training loop calls ``publish(chunk_index, state)`` at chunk
    boundaries (``ChunkedPrequentialEvaluation(publisher=...)`` wires this
    into the same place the ``boundary()`` hooks fire);
  * ``publish`` VALIDATES the candidate before any reader can see it -- a
    snapshot is rejected when any inexact leaf is non-finite
    (``carry_all_finite``, the same check the training rollback uses) or
    when its manifest fails the checkpoint structure round-trip
    (``checkpoint.manager._encode_structure``, the machinery behind
    ``restore_structured``); rejected snapshots keep the last-good one
    visible and increment ``rejected_snapshots``, so a poison training
    step can never reach readers;
  * accepted snapshots are double-buffered: the candidate is deep-copied
    into a back buffer (readers are immune to later donation/mutation of
    the training carry) and installed with one atomic reference flip --
    readers holding the previous ``Snapshot`` keep a complete, immutable
    model for as long as they need it;
  * a circuit breaker trips after ``breaker_threshold`` CONSECUTIVE
    rejections (the training run is presumed sick, not unlucky) and heals
    on the next accepted snapshot;
  * staleness is tracked in chunks: ``observe`` advances the train cursor
    even when nothing is published, so a stalled publisher shows up as
    ``staleness()`` growing past ``max_staleness_chunks`` and the
    ``degraded`` readiness flag flipping -- the server keeps answering
    from last-good, it just stops claiming freshness;
  * the training loop also writes ``issued_cursor``, the newest chunk it
    has issued to the device; ``issued_cursor - train_cursor`` is the
    chunks issued but not yet drained, which the server reads to bound
    how many chunk programs a predict queues behind.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import _encode_structure
from repro.runtime.chaos import carry_all_finite


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """One immutable published model version."""

    state: Any          # model state pytree (deep copy of the carry slice)
    chunk_index: int    # chunk boundary this state was captured at
    version: int        # monotonically increasing publish counter
    published_at: float # time.monotonic() at install


def model_state_of(carry):
    """Extract the (single-processor) model state from an engine carry.

    The chunked engines carry ``{"states": {proc: state}, "feedback": ...}``
    for a bare learner wrapped in a ``LearnerProcessor``; serving wants the
    learner state itself.  Anything that is not that shape passes through
    unchanged (callers publishing a raw state directly)."""
    if isinstance(carry, dict) and isinstance(carry.get("states"), dict):
        states = carry["states"]
        if len(states) == 1:
            return next(iter(states.values()))
        return states
    return carry


def tenant_state_of(state, tenant: int):
    """One tenant's model out of a published FLEET snapshot.

    A ``LearnerFleet`` publishes its packed ``{"tenant": [F, ...],
    "cursor": [F]}`` state; readers that want a single tenant's model (a
    per-tenant export, the serving oracle) slice row ``tenant`` off every
    packed leaf.  Raises on non-fleet states rather than guessing."""
    if not (isinstance(state, dict) and "tenant" in state):
        raise TypeError(
            "not a fleet snapshot state (no packed 'tenant' leaves); "
            "single-learner snapshots ARE the model state already")
    return jax.tree.map(lambda leaf: leaf[int(tenant)], state["tenant"])


class SnapshotPublisher:
    """Validated, double-buffered snapshot publication with a circuit
    breaker and a staleness SLO.

    Thread-safety: one publisher thread (the training loop) and any number
    of reader threads.  All counter/flip mutations happen under one lock;
    ``current()`` returns the installed ``Snapshot`` object, which is
    immutable, so readers never hold the lock across a predict call.
    """

    def __init__(self, *, max_staleness_chunks: int = 4,
                 breaker_threshold: int = 3, copy: bool = True,
                 checkpoint=None, clock=time.monotonic,
                 async_publish: bool = False, max_pending: int = 2):
        self.max_staleness_chunks = int(max_staleness_chunks)
        self.breaker_threshold = max(1, int(breaker_threshold))
        self.copy = copy
        self.checkpoint = checkpoint   # optional spill of accepted snapshots
        self._clock = clock
        self._lock = threading.Lock()
        self._current: Snapshot | None = None
        self.train_cursor = -1         # newest chunk boundary observed
        self.issued_cursor = -1        # newest chunk the trainer issued
        self.published = 0
        self.rejected_snapshots = 0
        self.consecutive_rejections = 0
        self.breaker_open = False
        self.breaker_trips = 0
        self.events: list[tuple] = []
        # async mode: publish() only OBSERVES + enqueues; validation, the
        # back-buffer copy and the flip run on a worker thread, strictly
        # in submission order.  max_pending bounds the queue (each pending
        # entry pins a candidate state alive), matching the chunk
        # pipeline's bounded in-flight window.  flush() fences.
        self.async_publish = bool(async_publish)
        self.max_pending = max(1, int(max_pending))
        self._q: queue.Queue = queue.Queue()
        self._sem = threading.Semaphore(self.max_pending)
        self._worker: threading.Thread | None = None
        self._worker_error: BaseException | None = None

    # --------------------------------------------------------- validation

    @staticmethod
    def validate(state) -> str | None:
        """Rejection reason for `state`, or None when publishable."""
        leaves = jax.tree.leaves(state)
        if not leaves:
            return "empty"
        if _encode_structure(state, len(leaves)) is None:
            return "structure"      # manifest round-trip would fail
        if not carry_all_finite(state):
            return "non_finite"
        return None

    # -------------------------------------------------------------- write

    def observe(self, chunk_index: int):
        """Record that training finished chunk `chunk_index`, whether or
        not anything gets published -- this is what makes a stalled
        publisher visible as growing staleness."""
        with self._lock:
            self.train_cursor = max(self.train_cursor, int(chunk_index))

    def publish(self, chunk_index: int, state) -> bool:
        """Validate + install `state` as the serving snapshot for chunk
        boundary `chunk_index`.  Returns True when readers can see it.

        With ``async_publish`` the call is NON-BLOCKING (bar the bounded
        ``max_pending`` backpressure): the train cursor advances now --
        staleness semantics are unchanged -- while validation + flip land
        on the worker in submission order.  The optimistic True means
        "queued"; rejections still count and trip the breaker when the
        worker gets there, and ``flush()`` fences before reading
        counters."""
        self.observe(chunk_index)
        if self.async_publish:
            self._raise_worker_error()
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._drain, name="snapshot-publish", daemon=True)
                self._worker.start()
            self._sem.acquire()
            self._q.put((int(chunk_index), state))
            return True
        return self._publish_sync(chunk_index, state)

    def flush(self):
        """Block until every queued publication is validated + installed
        (or rejected).  No-op in synchronous mode."""
        if self.async_publish:
            self._q.join()
            self._raise_worker_error()

    def close(self):
        """flush + stop the worker thread (restartable: a later publish
        spawns a fresh worker)."""
        if self._worker is not None:
            self._q.put(None)
            self._worker.join()
            self._worker = None
        self._raise_worker_error()

    def _drain(self):
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            try:
                if self._worker_error is None:
                    self._publish_sync(*item)
            except BaseException as e:      # surfaced at next publish/flush
                with self._lock:
                    self._worker_error = e
            finally:
                self._sem.release()
                self._q.task_done()

    def _raise_worker_error(self):
        with self._lock:
            err, self._worker_error = self._worker_error, None
        if err is not None:
            raise err

    def _publish_sync(self, chunk_index: int, state) -> bool:
        reason = self.validate(state)
        if reason is not None:
            with self._lock:
                self.rejected_snapshots += 1
                self.consecutive_rejections += 1
                self.events.append(
                    ("reject", int(chunk_index), reason))
                if (self.consecutive_rejections >= self.breaker_threshold
                        and not self.breaker_open):
                    self.breaker_open = True
                    self.breaker_trips += 1
                    self.events.append(("breaker_open", int(chunk_index)))
            return False
        # back buffer: deep-copy OUTSIDE the lock (the copy is the slow
        # part; readers keep serving the old snapshot meanwhile)
        if self.copy:
            state = jax.tree.map(lambda x: jnp.array(x), state)
        with self._lock:
            version = self.published + 1
            snap = Snapshot(state=state, chunk_index=int(chunk_index),
                            version=version, published_at=self._clock())
            self._current = snap       # the atomic flip
            self.published = version
            self.consecutive_rejections = 0
            if self.breaker_open:
                self.breaker_open = False
                self.events.append(("breaker_close", int(chunk_index)))
        if self.checkpoint is not None:
            self.checkpoint.save(int(chunk_index), state)
        return True

    # --------------------------------------------------------------- read

    def current(self) -> Snapshot | None:
        with self._lock:
            return self._current

    def staleness(self) -> int:
        """Chunks of training progress the serving snapshot is behind.
        Infinite (a large sentinel is avoided: the caller gets the real
        count) only in the sense that with no snapshot at all every
        observed chunk is unserved."""
        with self._lock:
            if self._current is None:
                return self.train_cursor + 1
            return max(0, self.train_cursor - self._current.chunk_index)

    def degraded(self) -> bool:
        """True when the serving path should stop claiming freshness:
        no snapshot yet, staleness SLO blown, or breaker open."""
        with self._lock:
            if self.breaker_open or self._current is None:
                return True
            return (self.train_cursor - self._current.chunk_index
                    > self.max_staleness_chunks)

    def status(self) -> dict:
        with self._lock:
            cur = self._current
            stale = (self.train_cursor + 1 if cur is None
                     else max(0, self.train_cursor - cur.chunk_index))
            return {
                "published": self.published,
                "rejected_snapshots": self.rejected_snapshots,
                "consecutive_rejections": self.consecutive_rejections,
                "breaker_open": self.breaker_open,
                "breaker_trips": self.breaker_trips,
                "train_cursor": self.train_cursor,
                "snapshot_chunk": None if cur is None else cur.chunk_index,
                "snapshot_version": 0 if cur is None else cur.version,
                "pending_publishes": self._q.unfinished_tasks,
                "staleness_chunks": stale,
                "degraded": (self.breaker_open or cur is None
                             or stale > self.max_staleness_chunks),
            }
