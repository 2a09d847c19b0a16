"""JIT compile/retrace visibility via ``jax.monitoring``.

Retraces are the silent TPU performance killer (a closure scalar, a weak
dtype, a fresh shape — and suddenly every "cached" step recompiles). mxlint
catches the static cases before running; this hook measures the dynamic
truth: every jaxpr trace and every backend (XLA) compile the process
actually performs, counted and timed into the metrics registry.

jax emits named duration events through ``jax.monitoring``; we subscribe one
process-wide listener (idempotent install) and translate:

- ``/jax/core/compile/jaxpr_trace_duration``   → ``mxtpu_jit_traces_total``
- ``/jax/core/compile/backend_compile_duration`` →
  ``mxtpu_jit_backend_compiles_total`` + ``mxtpu_jit_compile_ms`` histogram
- ``/jax/compilation_cache/cache_hits``        → ``mxtpu_jit_cache_hits_total``
- ``/jax/compilation_cache/cache_retrieval_time_sec`` → a ``jit.cache_load``
  record, inside the ``jit.compile`` record of the same compile: a compile
  whose record encloses none ran the compiler, one that does was loaded

The listener respects the live ``MXNET_TELEMETRY`` switch, and registration
itself costs nothing between compiles.
"""
from __future__ import annotations

import threading
import time

from . import metrics as _metrics
from . import spans as _spans

__all__ = ["install", "installed", "last_compile_ms",
           "recent_compile_events", "JIT_TRACES", "JIT_COMPILES",
           "JIT_COMPILE_MS", "JIT_CACHE_HITS"]

JIT_TRACES = _metrics.counter(
    "mxtpu_jit_traces_total",
    "jaxpr traces performed (a growing count under a steady workload means "
    "the step function is retracing).")
JIT_COMPILES = _metrics.counter(
    "mxtpu_jit_backend_compiles_total", "XLA backend compiles performed.")
JIT_COMPILE_MS = _metrics.histogram(
    "mxtpu_jit_compile_ms", "XLA backend compile wall time.",
    buckets=(10, 50, 100, 500, 1000, 5000, 15000, 60000, 300000))
JIT_CACHE_HITS = _metrics.counter(
    "mxtpu_jit_cache_hits_total",
    "persistent compilation-cache hits (compiles avoided).")

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_installed = False
_last_compile_ms = None


def last_compile_ms():
    """Wall time of the most recent XLA backend compile this process
    performed (None before the first one) — the cost ledger attaches it to
    the row of the executable captured right after a compile event."""
    return _last_compile_ms


def recent_compile_events():
    """The span ring's ``jit.trace`` / ``jit.compile`` records as ``{"event",
    "t0", "dur_s"}`` dicts, ``t0`` in ``time.perf_counter`` seconds — the
    clock the profiler and the trace ring export against, so the compile
    that delayed a request lines up with its queue span."""
    return [{"event": r.name, "t0": r.t0, "dur_s": r.t1 - r.t0}
            for r in _spans.records() if r.name in ("jit.trace", "jit.compile")]


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if not _metrics.enabled():
        return
    now = time.perf_counter()
    if event == _TRACE_EVENT:
        JIT_TRACES.inc()
        _spans.record("jit.trace", now - duration_secs, now)
    elif event == _COMPILE_EVENT:
        global _last_compile_ms
        _last_compile_ms = duration_secs * 1000.0
        JIT_COMPILES.inc()
        JIT_COMPILE_MS.observe(duration_secs * 1000.0)
        _spans.record("jit.compile", now - duration_secs, now)
    elif event == _CACHE_LOAD_EVENT:
        _spans.record("jit.cache_load", now - duration_secs, now)


def _on_event(event: str, **kwargs) -> None:
    if not _metrics.enabled():
        return
    if event == _CACHE_HIT_EVENT:
        JIT_CACHE_HITS.inc()


def install() -> bool:
    """Register the jax.monitoring listeners once per process. Returns True
    when listeners are active (now or from an earlier call)."""
    global _installed
    with _lock:
        if _installed:
            return True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
        except Exception:
            return False
        _installed = True
        return True


def installed() -> bool:
    return _installed
