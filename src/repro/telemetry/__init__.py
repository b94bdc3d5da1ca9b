"""Execution telemetry: metrics, traces, fallback reporting, reports.

Lightweight and dependency-free (stdlib-only at module level — no jax/numpy
imports) so every layer of the stack can import it without cycles:

  metrics   -- process-global registry (counters, gauges, p50/p95/p99
               histograms), ``snapshot()`` exports one JSON-able dict
  trace     -- event tracer exporting Chrome-trace-format JSON
               (chrome://tracing, Perfetto) + ``validate_chrome_trace``
  span      -- :func:`span`, the one way the program records a span: a
               ``jax.profiler`` annotation always, and a histogram plus a
               tracer event when telemetry is enabled
  fallback  -- machine-readable fallback reason codes, one-time
               ``SparseFallbackWarning`` (always on), gated counters
  report    -- per-forward ``ExecutionReport``/``OpReport`` built by
               ``CnnEngine`` at dispatch time

The subsystem is **off by default** and records nothing when off: every
instrumentation site guards on :func:`is_enabled` — a single module-level
flag read — and nothing records from inside ``jax.jit``-traced code (all
sites sit at dispatch/trace time).  A :func:`span` still enters its
profiler annotation when off, so a profiler session names the host's time
on the device trace's clock whatever the flag says.  The one always-on signal is the
one-time fallback warning (see ``fallback.py``), which the issue requires
independent of telemetry state.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any

from repro.telemetry import metrics
from repro.telemetry.fallback import (REASONS, SparseFallbackWarning,
                                      record_fallback, reset_warnings)
from repro.telemetry.metrics import (REGISTRY, counter, gauge, histogram,
                                     snapshot)
from repro.telemetry.report import ExecutionReport, OpReport
from repro.telemetry.trace import TID_WALL, Tracer, validate_chrome_trace

__all__ = [
    "REASONS", "REGISTRY", "SparseFallbackWarning", "TID_WALL", "Tracer",
    "ExecutionReport", "OpReport", "counter", "disable", "enable", "enabled",
    "gauge", "get_tracer", "histogram", "is_enabled", "record_fallback",
    "reset", "reset_warnings", "snapshot", "span", "validate_chrome_trace",
]

_ENABLED = False
_TRACER = Tracer()


def is_enabled() -> bool:
    """The single flag every instrumentation site checks."""
    return _ENABLED


def enable() -> None:
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


@contextlib.contextmanager
def enabled():
    """Enable telemetry for the duration of a ``with`` block."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = True
    try:
        yield
    finally:
        _ENABLED = prev


def get_tracer() -> Tracer:
    """The process-global tracer (`--trace` exports it)."""
    return _TRACER


@contextlib.contextmanager
def span(name: str, **args: Any):
    """Record the ``with`` block as the span ``name``.

    Always enters ``jax.profiler.TraceAnnotation(name)``, so any profiler
    session records the span on the host plane of the same trace as the
    device's operations, on that trace's clock (costing about a
    microsecond when no session runs).  When telemetry is enabled at entry,
    it also observes the block's wall seconds into the histogram
    ``<name>_s`` and records one complete event, carrying ``args``, on the
    tracer's wall lane.
    """
    from jax.profiler import TraceAnnotation  # lazy: no jax at import time

    with TraceAnnotation(name):
        if not _ENABLED:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            metrics.histogram(name + "_s").observe(dt)
            _TRACER.complete(name, start_s=t0, dur_s=dt, cat="span",
                             args=args)


def reset() -> None:
    """Clear metrics, trace events, and fallback-warning dedup (tests)."""
    metrics.reset()
    _TRACER.clear()
    reset_warnings()
