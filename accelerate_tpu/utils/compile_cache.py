"""Persistent XLA compilation cache management.

The reference pays no compilation cost (torch eager): its 8.7 s GPT-J "load
time" (reference benchmarks/big_model_inference/README.md:31) is pure I/O.
Under XLA the first trace of a dispatched model costs tens of seconds, which
would dominate time-to-first-token. The persistent compilation cache makes
that a one-time cost per (program, topology): every later process — including
restarts after preemption (SURVEY §5 failure recovery) — deserializes the
executable instead of recompiling.

One directory, placed from outside: where ``JAX_COMPILATION_CACHE_DIR`` is
set the cache lives there and nothing in this package sets another; where
it is not, the cache is ``.xla_cache/`` at the root of the checkout — a
fixed path (the directory is part of what a cache entry is found by), never
a temporary name. ``ensure_persistent_compile_cache()`` is called by the
Accelerator, the serving engine, generation and the dispatch path, whose
jax.export artifacts live in ``exports/`` under the same directory.
``JAX_ENABLE_COMPILATION_CACHE=0`` (jax's own switch) runs uncached.

This module also owns the **compile-activity counters** the telemetry
session reads per step: ``install_compile_listeners()`` subscribes (once)
to ``jax.monitoring``'s event streams and tallies backend-compile events,
the seconds spent tracing, lowering and compiling, and persistent-cache hits. A step whose record shows
``compile_events > 0`` paid a trace/compile — the classic silent cause of
a 100x step-time outlier — and ``compile_cache_hits`` says whether the
persistent cache absorbed it.
"""

from __future__ import annotations

import os
import threading

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".xla_cache",
)


def ensure_persistent_compile_cache() -> str | None:
    """Idempotently point jax's persistent compilation cache at the one
    directory this process uses and return it (None when the user switched
    the cache off through ``jax_enable_compilation_cache``).

    ``JAX_COMPILATION_CACHE_DIR`` wins — re-applied through ``jax.config``
    because jax reads the variable only at import; else a directory the
    user's own code already configured stays; else :data:`REPO_CACHE_DIR`,
    caching everything that takes noticeable time. A directory that cannot
    be created raises: carrying on uncached would make every restart
    recompile with nothing to show for it."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    current = jax.config.jax_compilation_cache_dir
    cache_dir = env_dir or current or REPO_CACHE_DIR
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as e:
        raise OSError(
            f"persistent XLA compile cache dir {cache_dir} is not usable ({e}); "
            "point JAX_COMPILATION_CACHE_DIR at a writable path, or set "
            "JAX_ENABLE_COMPILATION_CACHE=0 to run uncached by choice"
        ) from e
    if cache_dir != current:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", cache_dir)
        if not env_dir:
            # our own directory, so our thresholds; entries are content-hashed
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # jax opens the cache once, at the directory configured then
        compilation_cache.reset_cache()
    return cache_dir


# ---------------------------------------------------------------------------
# compile-activity counters (consumed by telemetry at step cadence)
# ---------------------------------------------------------------------------

_counter_lock = threading.Lock()
_COMPILE_COUNTERS = {"count": 0, "seconds": 0.0, "cache_hits": 0}
_listeners_installed = False


def compile_event_counters() -> dict:
    """Monotonic process-wide counters: {count, seconds, cache_hits}.
    Consumers diff two snapshots to attribute activity to an interval."""
    with _counter_lock:
        return dict(_COMPILE_COUNTERS)


def record_compile_event(seconds: float = 0.0, cache_hit: bool = False):
    """Tally one compile (or cache-hit) observation. Public so tests and
    non-jax.monitoring paths can feed the same counters the listener does."""
    with _counter_lock:
        if cache_hit:
            _COMPILE_COUNTERS["cache_hits"] += 1
        else:
            _COMPILE_COUNTERS["count"] += 1
            _COMPILE_COUNTERS["seconds"] += float(seconds)


def _on_event_duration(event, duration, **_kw):
    """jax reports three durations per compiled program (trace, lowering,
    backend compile). All three add to ``seconds``; only the backend
    compile — which also fires when the persistent cache serves the
    executable — is a compile EVENT. Counting traces made "nothing compiles
    after warm-up" unprovable on the chip: every eager ``fold_in`` of an
    ``rbg`` key (the TPU default, two per train step) reports a ~15 us
    trace of its inner jit although nothing is compiled."""
    name = str(event)
    if "compile" in name and "cache" not in name:
        with _counter_lock:
            _COMPILE_COUNTERS["seconds"] += float(duration)
            if "backend_compile" in name:
                _COMPILE_COUNTERS["count"] += 1


def _on_event(event, **_kw):
    name = str(event)
    if "cache_hit" in name or ("cache" in name and "hit" in name):
        record_compile_event(cache_hit=True)


def install_compile_listeners() -> None:
    """Subscribe the counters to jax.monitoring (idempotent)."""
    global _listeners_installed
    if not _listeners_installed:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_event_duration)
        monitoring.register_event_listener(_on_event)
        _listeners_installed = True
