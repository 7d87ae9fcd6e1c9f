"""Persistent XLA compilation cache plumbing.

Warm restarts (kill/resume) and elastic remeshes recompile the same chunk
programs from scratch; jax's persistent compilation cache
(``jax_compilation_cache_dir``) makes the second process pay a disk read
instead.  :func:`enable` turns it on (idempotent; thresholds zeroed so
the small chunk programs qualify) and installs a monitoring listener, so
:func:`stats` can report hit/miss counts into run reports and the
recovery BENCH arm -- a cache that silently never hits is a perf claim
nobody verified.
"""

from __future__ import annotations

import os
import threading

_lock = threading.Lock()
_counts = {"requests": 0, "hits": 0}
_listening = False
_enabled_dir: str | None = None

_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
ENV_DIR = "JAX_COMPILATION_CACHE_DIR"


def _listener(event: str, **kw) -> None:
    with _lock:
        if event == _REQUEST_EVENT:
            _counts["requests"] += 1
        elif event == _HIT_EVENT:
            _counts["hits"] += 1


def enable(cache_dir) -> str:
    """Point jax's persistent compilation cache at ``cache_dir`` -- or at
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set: a cache placed from
    outside always wins over the caller's default.

    Zeroes the min-compile-time / min-entry-size gates (the chunk
    programs are small but recompiled constantly across restarts) and
    registers the hit/miss listener once.  Safe to call repeatedly; the
    last directory wins (jax reads the config per compile).  Returns the
    directory in use."""
    global _listening, _enabled_dir
    import jax
    cache_dir = os.environ.get(ENV_DIR) or str(cache_dir)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_listener)
            _listening = True
        _enabled_dir = cache_dir
    return cache_dir


def enabled_dir() -> str | None:
    with _lock:
        return _enabled_dir


def stats() -> dict:
    """{'requests', 'hits', 'misses'} since this process enabled the
    cache (misses derived: cacheable requests that read nothing)."""
    with _lock:
        req, hits = _counts["requests"], _counts["hits"]
    return {"requests": req, "hits": hits, "misses": req - hits}
