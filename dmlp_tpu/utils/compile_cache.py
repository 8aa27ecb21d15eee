"""Persistent XLA compilation cache — one rule, applied at every entry
point (engine CLI, serve daemon, train loop) before the
first compile:

- ``$JAX_COMPILATION_CACHE_DIR`` set: jax itself reads it at import and
  keeps the cache there; no directory is set in code — not from
  ``--compile-cache``, not from anything else. Whoever runs the program
  (a fleet launcher, the machine that holds the chip) places the cache
  from outside.
- not set: ``--compile-cache DIR`` when given, else the fixed
  ``<checkout>/.jax_cache`` (git-ignored). The directory is part of the
  cache key, so it is never derived from a temp name, a pid or a clock.
- one exception: on the cpu backend the unplaced default stays off.
  jaxlib 0.9.0's XLA:CPU loader writes an error block to stderr for
  every executable it takes from the cache (it rejects the
  ``+prefer-no-scatter`` pseudo-feature its own compiler recorded),
  stderr is the engine's ``Time taken`` contract channel, and a CPU
  compile costs seconds where a chip compile costs a minute. A cache
  someone placed (variable or flag) is honoured there too.

Compiling is a large part of a cold start, and every process of one run
(batch solve, then the daemon, then each fleet replica) compiles
overlapping programs; a cache that is always on is what lets the second
one reuse the first one's executables. :func:`stats` says what it did —
requests, hits, misses and backend compile time of this process, from
jax's own monitoring events — for the records the entry points write.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional

#: jax's own variable (read by jax.config at import)
JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed default: ``.jax_cache`` beside the package, i.e. in the
#: checkout root
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

# Process-wide like the jax config and the monitoring listeners it
# mirrors; compiles fire from any thread (the daemon's batcher compiles
# buckets), hence the lock.
_lock = threading.Lock()
_stats: Dict[str, Any] = {"dir": None, "requests": 0, "hits": 0,
                          "misses": 0, "backend_compile_ms": 0.0}
_listening = False


def resolve_cache_dir(flag: Optional[str] = None) -> str:
    """The directory the rule above picks. Read per call (no import-time
    snapshot) so spawned subprocesses and tests can flip the env."""
    return os.environ.get(JAX_ENV_VAR) or flag or DEFAULT_DIR


def _on_event(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        with _lock:
            _stats[key] += 1


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event == _BACKEND_COMPILE:
        with _lock:
            _stats["backend_compile_ms"] += secs * 1e3


def enable_compile_cache(flag: Optional[str] = None) -> Optional[str]:
    """Turn the persistent cache on by the module's rule; returns the
    directory in use, None when it stays off. Call before the first
    compile, from the process that solves (it may initialise the
    backend). An unwritable directory leaves the process running cold
    (``stats()["dir"]`` is then None): the cache is an optimization,
    not a dependency."""
    global _listening
    import jax
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True
    from_env = os.environ.get(JAX_ENV_VAR)
    if not (from_env or flag) and jax.default_backend() == "cpu":
        return None
    directory = resolve_cache_dir(flag)
    if not from_env:
        try:
            os.makedirs(directory, exist_ok=True)
        except OSError:
            return None
        jax.config.update("jax_compilation_cache_dir", directory)
    # The default threshold skips programs that compile "fast"; a warm
    # start's win is the SUM of many such programs, so cache them all.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with _lock:
        _stats["dir"] = directory
    return directory


def stats() -> Dict[str, Any]:
    """This process's cache use so far: ``dir`` (None = never enabled),
    ``requests`` / ``hits`` / ``misses`` (compiles that consulted the
    cache, were served from it, were written to it) and
    ``backend_compile_ms`` (time inside the backend compile call, cache
    retrieval included)."""
    with _lock:
        out = dict(_stats)
    out["backend_compile_ms"] = round(out["backend_compile_ms"], 1)
    return out
