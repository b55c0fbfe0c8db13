"""Persistent XLA compilation cache + compile observability.

Two jobs:

1. **Turn on JAX's persistent compilation cache** (setup()) — at
   ``JAX_COMPILATION_CACHE_DIR`` where the environment names one, else
   at ``MXT_COMPILE_CACHE_DIR`` — with the thresholds dropped to zero
   so every program caches: on CPU tier-1 the compiles are small, and
   on the chip a whole-step compile is exactly what must never be paid
   twice. A second process compiling the same program deserializes from
   disk instead of running XLA.

2. **Count and time every compile** via ``jax.monitoring`` listeners:
   ``/jax/core/compile/*_duration`` duration events feed the
   ``mxt_compile_seconds{phase=trace|lower|compile}`` histogram and the
   ``mxt_compiles_total`` counter; ``/jax/compilation_cache/cache_hits``
   / ``cache_misses`` feed ``mxt_compile_cache_{hits,misses}_total``.
   ``compile_stats()`` snapshots all of it for bench deltas and the
   zero-JIT acceptance assert: on a warm start, the hot loop's
   cache_misses delta is 0. jax names the function of every such event
   (``fun_name``): ``by_function`` lists the ten that cost most, which is
   how a start-up's hundreds of one-operation programs get their names.

Listeners are installed once at package import (mxnet_tpu/__init__
imports tuning); they are passive counters — observability must never
take the process down, so every handler swallows its own errors.
"""
from __future__ import annotations

import os
import threading

_lock = threading.Lock()
_installed = False
_setup_dir = None

# module-level mirror of the telemetry counters: cheap consistent
# snapshots for compile_stats() deltas without walking the registry
_stats = {"compiles": 0, "compile_seconds": 0.0, "trace_seconds": 0.0,
          "lower_seconds": 0.0, "small_compiles": 0,
          "cache_hits": 0, "cache_misses": 0}
_SMALL_SECONDS = 1.0  # a backend compile under this is a "small" one
# fun_name -> [backend compiles, trace + lower + compile seconds]
_by_function = {}

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


def _telemetry():
    from .. import telemetry

    return telemetry


def _config():
    from .. import config

    return config


def _on_duration(name, secs, **kw):
    try:
        phase = _PHASES.get(name)
        if phase is None:
            return
        with _lock:
            if phase == "compile":
                _stats["compiles"] += 1
                _stats["compile_seconds"] += secs
                _stats["small_compiles"] += secs < _SMALL_SECONDS
            else:
                _stats[phase + "_seconds"] += secs
            fun = kw.get("fun_name") or "(unnamed)"
            if fun.startswith("jit(") and fun.endswith(")"):
                fun = fun[4:-1]  # lowering says jit(f) where tracing says f
            ent = _by_function.setdefault(fun, [0, 0.0])
            ent[0] += phase == "compile"
            ent[1] += secs
        _telemetry().record_compile(phase, secs)
    except Exception:  # noqa: BLE001 — never break a compile over metrics
        pass


def _on_event(name, **kw):
    try:
        if name == "/jax/compilation_cache/cache_hits":
            with _lock:
                _stats["cache_hits"] += 1
            _telemetry().record_compile_cache(hit=True)
        elif name == "/jax/compilation_cache/cache_misses":
            with _lock:
                _stats["cache_misses"] += 1
            _telemetry().record_compile_cache(hit=False)
    except Exception:  # noqa: BLE001
        pass


def install_listeners():
    """Register the jax.monitoring listeners (idempotent)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def setup(cache_dir=None):
    """Enable the persistent compilation cache; returns the active
    directory or None (feature off, nothing touched).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and that directory is the answer whatever else is set:
    no directory is set in code (the path is part of how a machine hands
    a warm cache from one run to the next). Otherwise ``cache_dir``
    defaults to ``MXT_COMPILE_CACHE_DIR``. Either way the thresholds
    drop so every program caches. Idempotent per directory."""
    global _setup_dir
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        cache_dir = env_dir
    elif cache_dir is None:
        cache_dir = _config().get("MXT_COMPILE_CACHE_DIR")
    if not cache_dir:
        return _setup_dir
    with _lock:
        if _setup_dir == cache_dir:
            return _setup_dir
    import jax

    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    # cache EVERYTHING: the default thresholds skip small/fast programs,
    # but tier-1 runs on CPU where every compile is small — and the
    # zero-JIT-resume contract is per program, not per expensive program
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with _lock:
        _setup_dir = str(cache_dir)
    return _setup_dir


def cache_dir():
    """The directory setup() activated (None = persistent cache off)."""
    return _setup_dir


def total_seconds():
    """Trace + lower + compile seconds so far (what a set-up phase of
    ``mx.profiler`` subtracts to say how much of it was compiling)."""
    with _lock:
        return (_stats["trace_seconds"] + _stats["lower_seconds"]
                + _stats["compile_seconds"])


def compile_stats():
    """One consistent snapshot: compiles, compile_seconds,
    trace_seconds, lower_seconds, small_compiles (backend compiles under a
    second), cache_hits, cache_misses (process totals — diff two snapshots
    to scope a window), and ``by_function``: the ten functions that cost
    most, as ``{"name", "compiles", "seconds"}`` (trace + lower + compile)."""
    with _lock:
        out = dict(_stats)
        top = sorted(_by_function.items(), key=lambda kv: -kv[1][1])[:10]
    out["by_function"] = [{"name": k, "compiles": v[0], "seconds": v[1]}
                          for k, v in top]
    return out
