"""Streaming data pipeline: generator -> micro-batches -> (sharded) device.

``StreamPipeline`` turns any generator into a prequential micro-batch
stream with host-side double-buffered prefetch and optional sharded
device_put (shuffle grouping over the data axis).  ``ChunkedStream`` is
the bounded-memory source for the chunked stream runtime: an iterator of
fixed-shape ``[chunk_len, ...]`` payload chunks (last chunk zero-padded
with an explicit validity mask) with the same double-buffered prefetch,
so streams longer than device memory run at flat footprint.
``TokenStream`` is the LM-side equivalent: an infinite deterministic
token stream for the training examples/benchmarks (synthetic LM data;
the real deployment would plug a tokenized corpus reader with identical
semantics).
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.data.generators import bin_numeric
from repro.distributed.sharding import spans_processes


def _already_placed(x, sharding) -> bool:
    """True when `x` is a device array whose placement already satisfies
    the requested `sharding` -- re-issuing ``jax.device_put`` would be a
    redundant transfer (the prefetch thread commits chunks to device; the
    consumer must not pay that copy twice).  With no sharding requested,
    any device array qualifies (it is already on a device); with one, the
    shardings must match exactly.  Process-spanning shardings compare the
    same way -- a global array built by a previous placement round-trips."""
    if not isinstance(x, jax.Array):
        return False
    if sharding is None:
        return True
    return getattr(x, "sharding", None) == sharding


def _place(x, sharding):
    """Commit one payload leaf to its requested placement.

    `sharding` may be a callable (leaf -> sharding), the idiom for chunk
    payloads whose leaves have different ranks (``launch.distributed.
    payload_sharding``).  When the resolved sharding spans processes, the
    leaf is this process's ADDRESSABLE PORTION of the global chunk (each
    process fetches only its own batch columns) and the global array is
    assembled via ``jax.make_array_from_process_local_data``; device_put
    would mis-read the local slab as the full logical value.
    """
    if callable(sharding):
        sharding = sharding(x)
    if _already_placed(x, sharding):
        return x
    if sharding is None:
        return jax.device_put(x)
    if spans_processes(sharding):
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(x))
    return jax.device_put(x, sharding)


class StreamPipeline:
    """Prequential micro-batch stream with background prefetch."""

    def __init__(self, gen, batch: int, n_batches: int, *, n_bins: int = 0,
                 seed: int = 0, classification: bool = True, prefetch: int = 2,
                 sharding=None):
        self.gen = gen
        self.batch = batch
        self.n_batches = n_batches
        self.n_bins = n_bins
        self.seed = seed
        self.classification = classification
        self.prefetch = prefetch
        self.sharding = sharding

    def _produce(self, q):
        key = jax.random.PRNGKey(self.seed)
        sample = getattr(self.gen, "sample_classification", None)
        if not self.classification or sample is None:
            sample = self.gen.sample
        sample = jax.jit(sample, static_argnums=(1,))
        for i in range(self.n_batches):
            key, sub = jax.random.split(key)
            x, y = sample(sub, self.batch)
            if self.n_bins:
                x = bin_numeric(x, self.n_bins)
            if self.sharding is not None:
                x = _place(x, self.sharding)
            q.put((x, y))
        q.put(None)

    def __iter__(self):
        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        t = threading.Thread(target=self._produce, args=(q,), daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is None:
                return
            yield item

    def materialize(self):
        """Stack the whole stream (for lax.scan-driven benchmarks)."""
        xs, ys = [], []
        for x, y in self:
            xs.append(x)
            ys.append(y)
        return jnp.stack(xs), jnp.stack(ys)


class TransientSourceError(RuntimeError):
    """A retryable stream-source failure (the streaming analogue of a
    dropped connection or a throttled broker): ``ChunkedStream`` retries
    the fetch with capped exponential backoff before declaring the chunk
    lost."""


class StreamSourceError(RuntimeError):
    """A chunk could not be produced: the transient-retry budget ran out.
    Carries the failing chunk index so the operator knows exactly where
    in the stream ingestion died."""

    def __init__(self, chunk_index: int, attempts: int, cause):
        super().__init__(
            f"stream source failed on chunk {chunk_index} after "
            f"{attempts} attempt{'s' if attempts != 1 else ''}: {cause!r}")
        self.chunk_index = int(chunk_index)
        self.attempts = int(attempts)


@dataclasses.dataclass
class Chunk:
    """One fixed-shape slice of a stream.

    ``payload`` leaves have leading dimension ``chunk_len`` (the last chunk
    of a stream whose length the chunk size does not divide is zero-padded
    up to it); ``valid`` is the ``[chunk_len]`` bool mask of real steps and
    ``length`` its static count, so drivers can trim outputs and run the
    padded tail through a masked no-op step.
    """

    index: int          # chunk position in the stream
    payload: Any        # pytree, leaves [chunk_len, ...]
    valid: Any          # [chunk_len] bool, True for real steps
    length: int         # number of valid (un-padded) steps

    @property
    def chunk_len(self) -> int:
        return int(jax.tree.leaves(self.payload)[0].shape[0])

    @property
    def padded(self) -> bool:
        return self.length < self.chunk_len


def _pad_chunk(index: int, payload, chunk_len: int) -> Chunk:
    """Zero-pad a raw (possibly short, final) payload up to chunk_len."""
    length = int(jax.tree.leaves(payload)[0].shape[0])
    if length > chunk_len:
        raise ValueError(f"chunk {index} has {length} steps > {chunk_len}")
    if length == 0:
        # an all-padding chunk would feed fabricated zeros through the
        # feedback-priming step of a fresh stream; require >= 1 real step
        raise ValueError(f"chunk {index} has 0 steps")
    if length < chunk_len:
        pad = chunk_len - length
        payload = jax.tree.map(
            lambda x: jnp.concatenate(
                [jnp.asarray(x),
                 jnp.zeros((pad,) + tuple(x.shape[1:]),
                           jnp.asarray(x).dtype)], 0), payload)
    valid = jnp.arange(chunk_len) < length
    return Chunk(index=index, payload=payload, valid=valid, length=length)


class ChunkedStream:
    """Bounded-memory stream source: fixed-shape payload chunks, prefetched.

    The SAMOA constraint is that streams are unbounded; materializing the
    whole stream as a stacked ``[T, ...]`` pytree caps T at device memory.
    A ChunkedStream instead yields ``Chunk``s of ``chunk_len`` steps; a
    background thread generates/slices chunk k+1 and starts its (async)
    ``jax.device_put`` while chunk k runs, so the device only ever holds a
    couple of chunks of payload (double-buffering).

    Two constructions:

      * ``ChunkedStream(payloads, chunk_len)`` -- split an already stacked
        payload pytree (or list of per-step payloads) into chunks; useful
        for parity tests and moderate streams.
      * ``ChunkedStream.from_fn(fn, n_chunks, chunk_len)`` -- ``fn(i)``
        produces chunk i's raw payload (leaves ``[<=chunk_len, ...]``) on
        demand, so the full stream never exists anywhere; this is the
        unbounded-stream path.

    ``starting_at(k)`` returns a view beginning at chunk k (mid-stream
    checkpoint resume).  Iteration is restartable: each ``__iter__`` spawns
    a fresh producer.
    """

    def __init__(self, payloads=None, chunk_len: int = 0, *,
                 fetch: Callable[[int], Any] | None = None,
                 n_chunks: int | None = None, n_steps: int | None = None,
                 start_chunk: int = 0, prefetch: int = 2, sharding=None,
                 to_device: bool = True, retries: int = 3,
                 retry_events_cap: int = 256,
                 backoff: float = 0.05, backoff_cap: float = 5.0,
                 transient: tuple = (TransientSourceError, ConnectionError,
                                     TimeoutError)):
        if chunk_len < 1:
            raise ValueError(f"chunk_len must be >= 1, got {chunk_len}")
        self.chunk_len = int(chunk_len)
        self.start_chunk = int(start_chunk)
        self.prefetch = prefetch
        self.sharding = sharding
        self.to_device = to_device
        self.retries = max(0, int(retries))
        self.backoff = float(backoff)
        self.backoff_cap = float(backoff_cap)
        self.transient = tuple(transient)
        # (chunk, attempt, slept_s, error) per retried fetch -- run reports
        # surface these so silent source flakiness stays visible.  A ring
        # buffer: a long-lived flaky stream would otherwise grow the list
        # without bound, so only the newest `retry_events_cap` events are
        # kept while `retry_count` stays exact (the dropped count is
        # `retry_events_dropped`)
        if retry_events_cap < 1:
            raise ValueError(
                f"retry_events_cap must be >= 1, got {retry_events_cap}")
        self.retry_events: collections.deque = collections.deque(
            maxlen=int(retry_events_cap))
        # shared mutable cell, NOT plain ints: ``starting_at`` views copy
        # __dict__, and retries observed through a resumed view must count
        # against the same stream (the deque is already shared by identity).
        # ``dropped`` lives HERE too -- deriving it per-view as
        # ``count - len(deque)`` reads two values that are updated
        # non-atomically, so a concurrent view could observe a torn
        # (negative / under-reported) drop count.  The lock makes the
        # append + both counters one atomic transition.
        self._retry_stats = {"count": 0, "dropped": 0}
        self._retry_lock = threading.Lock()
        if fetch is not None:
            if n_chunks is None:
                raise ValueError("from_fn streams need n_chunks")
            self._fetch = fetch
            self.n_chunks = int(n_chunks)
            self.n_steps = n_steps
        else:
            if hasattr(payloads, "__next__"):
                payloads = list(payloads)
            if isinstance(payloads, list):
                payloads = jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)
            t = int(jax.tree.leaves(payloads)[0].shape[0])
            self.n_steps = t
            self.n_chunks = -(-t // self.chunk_len)
            cl = self.chunk_len
            self._fetch = lambda i, _p=payloads: jax.tree.map(
                lambda x: x[i * cl:(i + 1) * cl], _p)
        if not (0 <= self.start_chunk <= self.n_chunks):
            raise ValueError(f"start_chunk {self.start_chunk} outside "
                             f"[0, {self.n_chunks}]")

    @classmethod
    def from_fn(cls, fn: Callable[[int], Any], n_chunks: int,
                chunk_len: int, **kw) -> "ChunkedStream":
        """Generator-backed stream: ``fn(chunk_index)`` -> raw payload of
        up to ``chunk_len`` steps.  Nothing is materialized beyond the
        prefetch window."""
        return cls(fetch=fn, n_chunks=n_chunks, chunk_len=chunk_len, **kw)

    def starting_at(self, chunk: int) -> "ChunkedStream":
        """A view of the same stream beginning at `chunk` (resume)."""
        out = ChunkedStream.__new__(ChunkedStream)
        out.__dict__.update(self.__dict__)
        if not (0 <= chunk <= self.n_chunks):
            raise ValueError(f"start chunk {chunk} outside "
                             f"[0, {self.n_chunks}]")
        out.start_chunk = int(chunk)
        return out

    def _fetch_retry(self, i: int):
        """Self-healing fetch: transient source errors (``transient``
        classes) retry with capped exponential backoff and DETERMINISTIC
        jitter -- the sleep for (chunk, attempt) is always the same, so a
        rerun of a flaky stream is reproducible.  After ``retries`` failed
        retries the chunk is declared lost via ``StreamSourceError`` with
        the failing chunk index; non-transient errors propagate at once."""
        attempt = 0
        while True:
            try:
                return self._fetch(i)
            except self.transient as e:
                attempt += 1
                if attempt > self.retries:
                    raise StreamSourceError(i, attempt, e) from e
                delay = min(self.backoff * (2 ** (attempt - 1)),
                            self.backoff_cap)
                rng = np.random.default_rng((int(i) + 1) * 1_000_003
                                            + attempt)
                delay *= float(rng.uniform(0.5, 1.0))
                with self._retry_lock:
                    if len(self.retry_events) == self.retry_events.maxlen:
                        self._retry_stats["dropped"] += 1
                    self.retry_events.append(
                        (int(i), attempt, delay, repr(e)))
                    self._retry_stats["count"] += 1
                time.sleep(delay)

    def _produce(self, q, stop):
        def put(item) -> bool:
            # bounded put that gives up when the consumer abandoned the
            # iterator (early break / error downstream): otherwise the
            # thread would block on the full queue forever, pinning the
            # prefetched device payload buffers
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        try:
            for i in range(self.start_chunk, self.n_chunks):
                with TraceAnnotation("repro.stream.produce"):
                    chunk = _pad_chunk(i, self._fetch_retry(i),
                                       self.chunk_len)
                    if self.to_device:
                        # async host->device copy of chunk k+1 overlaps
                        # chunk k's compute (device_put returns
                        # immediately); leaves a generator already
                        # committed with the right placement are passed
                        # through untouched
                        chunk = dataclasses.replace(
                            chunk, payload=jax.tree.map(
                                lambda x: _place(x, self.sharding),
                                chunk.payload))
                if not put(chunk):
                    return
            put(None)
        except Exception as e:  # surfaced on the consumer side
            put(e)

    def __iter__(self):
        q: queue.Queue = queue.Queue(maxsize=max(1, self.prefetch))
        stop = threading.Event()
        t = threading.Thread(target=self._produce, args=(q, stop),
                             daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is None:
                    return
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            stop.set()

    @property
    def retry_count(self) -> int:
        """Exact number of retried fetches (never capped)."""
        with self._retry_lock:
            return self._retry_stats["count"]

    @property
    def retry_events_dropped(self) -> int:
        """Retry events evicted from the ring buffer (count stays exact).

        Reads the explicit counter in the shared ``_retry_stats`` cell, so
        every ``starting_at`` view of the stream reports the same total
        and a read never races the append/count transition."""
        with self._retry_lock:
            return self._retry_stats["dropped"]

    def __len__(self):
        return self.n_chunks - self.start_chunk


class TokenStream:
    """Deterministic synthetic token stream for LM training drivers."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int = 0):
        self.vocab = vocab
        self.batch = batch
        self.seq = seq
        self.key = jax.random.PRNGKey(seed)
        # a fixed markov-ish structure so loss decreases measurably
        k1, self.key = jax.random.split(self.key)
        self._bigram = jax.random.randint(k1, (1024,), 0, vocab)

    def next(self):
        self.key, k1, k2 = jax.random.split(self.key, 3)
        base = jax.random.randint(k1, (self.batch, self.seq), 0, self.vocab)
        # inject predictable bigrams: token[t+1] = f(token[t]) half the time
        nxt = self._bigram[base[:, :-1] % 1024]
        mask = jax.random.bernoulli(k2, 0.5, nxt.shape)
        tokens = base.at[:, 1:].set(jnp.where(mask, nxt, base[:, 1:]))
        return {"tokens": tokens}

    def __iter__(self):
        while True:
            yield self.next()
