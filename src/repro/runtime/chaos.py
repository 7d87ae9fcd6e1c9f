"""Fault injection for the chunked streaming runtime (chaos layer).

A streaming runtime's recovery story is only credible if the failures are
actually exercised.  ``FaultInjector`` produces the four failure classes a
long-running SAMOA-style deployment sees, deterministically, so the chaos
suite can assert exact recovery semantics:

  * process death mid-chunk (``kill_at_chunk``): raised AFTER the chunk's
    compute but BEFORE its metrics/checkpoint land, so the work since the
    last checkpoint is genuinely lost and resume must replay it
    (``kill_mode="exit"`` uses ``os._exit`` for real-process round-trips:
    no atexit handlers, the async checkpoint writer dies mid-flight --
    exactly what the atomic tmp+rename protocol must survive);
  * transient stream-source errors (``flaky_chunks``): the wrapped fetch
    raises ``TransientSourceError`` a configured number of times per
    chunk, driving ``ChunkedStream``'s backoff/retry path;
  * non-finite carry (``poison_at_chunk``): one inexact leaf of the
    post-chunk engine carry gets a NaN, simulating numeric blow-up during
    that chunk's compute -- the evaluation's boundary finite-check must
    roll back and skip-or-retry;
  * on-disk checkpoint corruption (``corrupt_checkpoint``): flip tensor
    bytes / truncate the npz / break the manifest of a chosen step, so
    ``CheckpointManager``'s newest-intact fallback is tested against real
    bad bytes, not mocks.

Everything here is deliberately free of randomness: kill/poison sites are
explicit chunk indices and corruption is byte-deterministic, so a failing
chaos test reproduces byte-for-byte.
"""

from __future__ import annotations

import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import TransientSourceError


class SimulatedKill(RuntimeError):
    """Injected process death.  Deliberately NOT a subclass of anything the
    runtime catches: it must unwind through the evaluation like a real
    SIGKILL-adjacent crash would, leaving only the on-disk checkpoints."""

    def __init__(self, chunk_index: int):
        super().__init__(f"simulated kill at chunk {chunk_index}")
        self.chunk_index = int(chunk_index)


def carry_finite_flag(carry):
    """LAZY finiteness of `carry`: a device bool scalar, not a host bool.

    One fused all-reduce per inexact leaf, AND-combined ON DEVICE, so the
    caller gets a deferred scalar it can hold without synchronizing -- the
    pipelined chunk driver dispatches the check alongside chunk k+1 and
    only blocks on it from the drain thread.  Safe under a mesh (jnp.all
    over a sharded array lowers to the collective).  Integer/bool leaves
    are vacuously fine; a carry with no inexact leaves is finite."""
    flag = None
    for leaf in jax.tree.leaves(carry):
        x = jnp.asarray(leaf)
        if jnp.issubdtype(x.dtype, jnp.inexact) and x.size:
            ok = jnp.all(jnp.isfinite(x))
            flag = ok if flag is None else jnp.logical_and(flag, ok)
    return jnp.asarray(True) if flag is None else flag


def carry_all_finite(carry) -> bool:
    """True iff every inexact (float/complex) leaf of `carry` is finite.
    The BLOCKING form of ``carry_finite_flag`` (host sync)."""
    return bool(carry_finite_flag(carry))


def poison_carry(carry, value: float = float("nan")):
    """Return `carry` with `value` written into element 0 of the FIRST
    inexact leaf (tree order) -- the minimal non-finite perturbation, so a
    finite-check that misses any single leaf fails the chaos suite."""
    done = [False]

    def poison(x):
        x = jnp.asarray(x)
        if done[0] or not jnp.issubdtype(x.dtype, jnp.inexact) or not x.size:
            return x
        done[0] = True
        return x.reshape(-1).at[0].set(value).reshape(x.shape)

    out = jax.tree.map(poison, carry)
    if not done[0]:
        raise ValueError("carry has no inexact leaf to poison")
    return out


class FaultInjector:
    """Deterministic fault schedule for one evaluation run.

    Each fault fires AT MOST ONCE (``killed`` / ``poisoned`` latch), so a
    rolled-back or resumed run replays the failure site cleanly -- the
    injector models a fault that happened, not a cursed chunk.

    kill_at_chunk:  chunk index after whose compute the run dies.
    kill_mode:      "raise" -> ``SimulatedKill`` unwinds the evaluation
                    (in-process tests); "exit" -> ``os._exit(kill_exit_code)``
                    (subprocess round-trips; skips atexit/finally).
    poison_at_chunk: chunk index AFTER whose compute the carry gets a NaN
                    (the blow-up happened inside that chunk).
    flaky_chunks:   chunk indices whose source fetch fails transiently.
    flaky_failures: how many times each flaky chunk's fetch fails before
                    succeeding (> the stream's retry budget => fatal
                    ``StreamSourceError``).

    Serving-side faults (exercised through ``wrap_publisher``):

    stall_publish_chunks:    chunk indices whose snapshot publication is
                    silently dropped (the training loop ran, the publish
                    never landed) -- staleness grows and the staleness
                    SLO must flip the ``degraded`` flag;
    poison_snapshot_at_chunk: chunk index whose PUBLISHED snapshot (not
                    the training carry) gets a NaN before validation --
                    the publisher must reject it and keep last-good;
    delay_chunk(i, s):       sleep `s` seconds before chunk i's compute
                    (straggler / slow-pipeline injection; fires once).
    """

    def __init__(self, *, kill_at_chunk: int | None = None,
                 kill_mode: str = "raise", kill_exit_code: int = 113,
                 poison_at_chunk: int | None = None,
                 poison_value: float = float("nan"),
                 flaky_chunks=(), flaky_failures: int = 1,
                 stall_publish_chunks=(),
                 poison_snapshot_at_chunk: int | None = None,
                 poison_snapshot_value: float = float("nan")):
        if kill_mode not in ("raise", "exit"):
            raise ValueError(f"unknown kill_mode {kill_mode!r}")
        self.kill_at_chunk = kill_at_chunk
        self.kill_mode = kill_mode
        self.kill_exit_code = int(kill_exit_code)
        self.poison_at_chunk = poison_at_chunk
        self.poison_value = poison_value
        self.flaky_failures = {int(c): int(flaky_failures)
                               for c in flaky_chunks}
        self.stall_publish_chunks = {int(c) for c in stall_publish_chunks}
        self.poison_snapshot_at_chunk = poison_snapshot_at_chunk
        self.poison_snapshot_value = poison_snapshot_value
        self.killed = False
        self.poisoned = False
        self.snapshot_poisoned = False
        self.stalled_publishes = 0
        self.delay_chunks: dict[int, float] = {}
        self.delays_fired: set[int] = set()

    # ------------------------------------------------------------- hooks

    def maybe_kill(self, chunk_index: int):
        """Die after chunk `chunk_index`'s compute (before its checkpoint)."""
        if self.kill_at_chunk is None or self.killed \
                or int(chunk_index) != int(self.kill_at_chunk):
            return
        self.killed = True
        if self.kill_mode == "exit":
            os._exit(self.kill_exit_code)
        raise SimulatedKill(chunk_index)

    def maybe_poison(self, chunk_index: int, carry):
        """NaN the carry leaving chunk `chunk_index` (once)."""
        if self.poison_at_chunk is None or self.poisoned \
                or int(chunk_index) != int(self.poison_at_chunk):
            return carry
        self.poisoned = True
        return poison_carry(carry, self.poison_value)

    def delay_chunk(self, index: int, seconds: float):
        """Schedule a one-shot sleep before chunk `index`'s compute --
        the straggler injection.  Chainable; multiple chunks may be
        delayed (each fires once, same latch discipline as kill/poison)."""
        self.delay_chunks[int(index)] = float(seconds)
        return self

    def maybe_delay(self, chunk_index: int):
        """Sleep the scheduled delay for `chunk_index` (once)."""
        i = int(chunk_index)
        s = self.delay_chunks.get(i)
        if s is None or i in self.delays_fired:
            return
        self.delays_fired.add(i)
        time.sleep(s)

    def wrap_publisher(self, publisher):
        """Wrap a ``SnapshotPublisher`` with the serving-side faults:
        stalled publications (dropped, but the train cursor still
        advances -- exactly what a wedged publisher thread looks like to
        readers) and poisoned snapshots (NaN'd BEFORE validation, so the
        publisher's reject path is exercised against real bad state)."""
        return _ChaosPublisher(self, publisher)

    def wrap_fetch(self, fetch):
        """Wrap a ``ChunkedStream`` fetch fn: scheduled chunks raise
        ``TransientSourceError`` ``flaky_failures`` times, then recover."""
        remaining = dict(self.flaky_failures)

        def flaky(i):
            left = remaining.get(int(i), 0)
            if left > 0:
                remaining[int(i)] = left - 1
                raise TransientSourceError(
                    f"injected transient source failure on chunk {i} "
                    f"({left - 1} more to come)")
            return fetch(i)

        return flaky


class _ChaosPublisher:
    """Publisher proxy injecting stall / poison-snapshot faults (see
    ``FaultInjector.wrap_publisher``).  Everything except ``publish`` --
    ``current``/``status``/``degraded``/counters -- delegates to the real
    publisher, so the server under test reads true state."""

    def __init__(self, injector: FaultInjector, publisher):
        self._injector = injector
        self._publisher = publisher

    def publish(self, chunk_index: int, state) -> bool:
        inj = self._injector
        i = int(chunk_index)
        if i in inj.stall_publish_chunks:
            inj.stalled_publishes += 1
            # the training loop DID finish the chunk; only the publish is
            # lost.  observe() keeps the train cursor honest so staleness
            # grows exactly as it would with a wedged publisher thread.
            self._publisher.observe(i)
            return False
        if (inj.poison_snapshot_at_chunk is not None
                and i == int(inj.poison_snapshot_at_chunk)
                and not inj.snapshot_poisoned):
            inj.snapshot_poisoned = True
            state = poison_carry(state, inj.poison_snapshot_value)
        return self._publisher.publish(i, state)

    def __getattr__(self, name):
        return getattr(self._publisher, name)

    @property
    def issued_cursor(self) -> int:
        return self._publisher.issued_cursor

    @issued_cursor.setter
    def issued_cursor(self, chunk_index: int):
        self._publisher.issued_cursor = chunk_index


def request_burst(server, xs, *, deadline_ms: float | None = None):
    """Fire one request per row of `xs` back-to-back (no pacing) -- the
    burst injection.  Returns the list of request handles; the caller
    asserts the admission-control outcome (bounded queue, explicit
    ``overloaded`` rejections, exact accounting)."""
    return [server.submit(x, deadline_ms=deadline_ms) for x in xs]


def corrupt_checkpoint(directory, step: int | None = None, *,
                       mode: str = "tensor"):
    """Corrupt checkpoint `step` (default: newest) under `directory`.

    mode="tensor"    rewrite tensors.npz with one element flipped -- the
                     zip stays readable, the manifest md5 does not match
                     (the checksum-detection path);
    mode="truncate"  chop the npz in half -- unreadable archive (the
                     torn-write / bad-disk path);
    mode="manifest"  replace manifest.json with invalid JSON (metadata
                     loss).

    Returns the corrupted step."""
    d = Path(directory)
    steps = sorted(int(p.name.split("_")[1]) for p in d.glob("step_*"))
    if not steps:
        raise FileNotFoundError(f"no checkpoints in {d}")
    if step is None:
        step = steps[-1]
    target = d / f"step_{step:010d}"
    if mode == "tensor":
        npz = target / "tensors.npz"
        data = np.load(npz)
        arrs = {k: data[k].copy() for k in data.files}
        a = arrs["t0"].reshape(-1).view(np.uint8)
        a[0] ^= 0xFF
        np.savez(npz, **arrs)
    elif mode == "truncate":
        npz = target / "tensors.npz"
        raw = npz.read_bytes()
        npz.write_bytes(raw[:max(1, len(raw) // 2)])
    elif mode == "manifest":
        (target / "manifest.json").write_text("{corrupt")
    else:
        raise ValueError(f"unknown corruption mode {mode!r}")
    return step
