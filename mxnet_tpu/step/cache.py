"""Persistent XLA compilation cache: one rule for where it lives.

The fused train step's one weakness is its first call: a whole-model
forward+backward+optimizer XLA compile can take minutes. JAX ships a
persistent on-disk compilation cache; enabling it means warmup survives
process restarts (a preempted worker recompiles from disk in seconds —
the mxresil restart path), repeated bench/CI runs skip the multi-minute
first compile, and a fleet sharing a cache directory compiles each
program once.

The rule: when ``JAX_COMPILATION_CACHE_DIR`` is set in the environment
the cache is placed from outside — jax reads that variable itself, and
this module sets no cache configuration at all, it only listens. When
it is not set, the library user's ``MXNET_COMPILE_CACHE_DIR`` flag
(applied at import, config.py) or an entry point's fixed directory
(``chip_smoke.py`` passes ``<repo>/.jax_cache``) goes
through :func:`enable_compile_cache`. The directory is part of the
cache key, so it is never built from a temp name, a pid or the time.

Hits and misses are logged through the telemetry metrics registry via
jax's monitoring events, so ``tools/mxprof.py step`` and the
MXNET_METRICS_EXPORT stream show whether warmup actually came from
disk. The same listener hands jax's program-building events (trace,
lowering, compile or cache read) to :mod:`mxnet_tpu.trace.setup`; it is
registered at import whether or not a cache is placed.
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "maybe_enable_compile_cache"]

_LISTENER_ON = False

# jax monitoring event names of the persistent-cache path
# (jax/_src/compiler.py + compilation_cache.py)
_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": (
        "jax_compile_cache_hits_total",
        "persistent-compile-cache hits (programs loaded from disk)"),
    "/jax/compilation_cache/cache_misses": (
        "jax_compile_cache_misses_total",
        "persistent-compile-cache misses (programs compiled anew)"),
}


def _on_event(event: str, **kwargs):
    hit = _EVENT_COUNTERS.get(event)
    if hit is None:
        return
    from ..telemetry import metrics as _metrics
    _metrics.counter(*hit).inc()


def listen():
    """Register the listeners on jax's monitoring events: the cache's
    hit and miss counters, and the program-building events of
    :mod:`mxnet_tpu.trace.setup`. Idempotent."""
    global _LISTENER_ON
    if _LISTENER_ON:
        return
    import jax
    from ..trace import setup as _setup
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_setup.on_start)
    jax.monitoring.register_event_duration_secs_listener(_setup.on_duration)
    jax.monitoring.register_event_time_span_listener(_setup.on_time_span)
    _LISTENER_ON = True


def enable_compile_cache(directory: str,
                         min_compile_time_secs: float = 0.5) -> bool:
    """Point jax's persistent compilation cache at ``directory`` and
    wire its hit/miss monitoring events into the telemetry registry.
    ``JAX_COMPILATION_CACHE_DIR`` in the environment wins: the cache
    then stays where jax already put it and only the listener is
    registered. Returns True when a cache is active. Idempotent."""
    listen()
    placed_outside = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if not directory and not placed_outside:
        return False
    import jax
    if not placed_outside:
        jax.config.update("jax_compilation_cache_dir", directory)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_secs))
        # cache even tiny programs: CPU test models compile in <0.5 s
        # but the restart win is the same
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return True


def maybe_enable_compile_cache() -> bool:
    """Import-time hook (mxnet_tpu/__init__.py calls this once the flag
    registry is up): apply MXNET_COMPILE_CACHE_DIR, or just listen when
    the cache was placed by JAX_COMPILATION_CACHE_DIR; the listeners
    are registered either way."""
    from ..base import get_env
    return enable_compile_cache(get_env("MXNET_COMPILE_CACHE_DIR", ""))
