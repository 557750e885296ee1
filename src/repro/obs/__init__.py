"""repro.obs — unified runtime telemetry (tracing + metrics).

The paper explains *why* tile-centric mixed precision wins with the PaRSEC
runtime's instrumentation (task traces, per-device utilization, message
volume); this package is our reproduction's equivalent lens:

* a process-local :class:`~repro.obs.metrics.MetricsRegistry` of labeled
  counters/gauges/histograms — always live, dict-increment cheap — that the
  tune dispatch layer, serve engine/scheduler, solver, and SUMMA record
  into instead of ad-hoc module-global dicts;
* a structured span/event :class:`~repro.obs.trace.Tracer` emitting
  JSON-lines that double as Chrome ``trace_event`` dicts (open the export
  in Perfetto or ``chrome://tracing``).  The default tracer writes no
  file; its spans are bare ``jax.profiler.TraceAnnotation``s;
* every span, JSONL on or off, lands on the JAX profiler's timeline as
  ``repro.<name>``, so a profiler session attributes device idle time to
  what the host was doing.

Facade::

    from repro import obs
    obs.configure(enabled=True, trace_path="run.jsonl")
    with obs.span("solve.sweep", "solve", sweep=3):
        ...
    obs.event("serve.admit", "serve", bucket="S16/default")
    obs.metrics_registry().counter("dispatch.calls", path="grouped").inc()
    obs.configure(enabled=False)          # back to profiler-only spans

Environment bootstrap: setting ``REPRO_OBS_TRACE=<path>`` (or
``REPRO_OBS=1`` for an in-memory tracer) enables tracing at import time,
so CI lanes and benchmarks turn the lens on without code changes.
"""
from __future__ import annotations

import atexit
import os

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               default_registry, label_key)
from repro.obs.trace import (CATEGORIES, NULL_TRACER, NullTracer, Tracer,
                             chrome_path_for, chrome_payload, export_chrome,
                             read_events, span_types)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "default_registry", "label_key", "metrics_registry",
    "CATEGORIES", "NullTracer", "Tracer", "chrome_payload",
    "chrome_path_for", "export_chrome", "read_events", "span_types",
    "configure", "is_enabled", "tracer", "span", "event",
]

_TRACER = NULL_TRACER


def configure(enabled: bool = True, trace_path: str | None = None,
              ) -> Tracer | NullTracer:
    """Install (or tear down) the process tracer.

    ``enabled=True`` with a ``trace_path`` streams JSONL events to that
    file; without a path, events collect in ``tracer().buffer`` (tests,
    short-lived tools).  ``enabled=False`` closes any active tracer and
    restores the JSONL-free singleton — the default state, under which no
    trace file is ever created and spans are profiler annotations only.
    """
    global _TRACER
    if _TRACER is not NULL_TRACER:
        _TRACER.close()
    _TRACER = Tracer(trace_path) if enabled else NULL_TRACER
    return _TRACER


def is_enabled() -> bool:
    return _TRACER.enabled


def tracer() -> Tracer | NullTracer:
    return _TRACER


def span(name: str, cat: str, **args):
    """Context manager tracing one complete span: always a profiler
    annotation ``repro.<name>``, and a JSONL event when enabled."""
    return _TRACER.span(name, cat, **args)


def event(name: str, cat: str, **args) -> None:
    """Instant event (no-op when disabled)."""
    _TRACER.event(name, cat, **args)


def metrics_registry() -> MetricsRegistry:
    """The process-global metrics registry (always live)."""
    return default_registry()


def _env_bootstrap() -> None:
    path = os.environ.get("REPRO_OBS_TRACE", "")
    if path:
        configure(enabled=True, trace_path=path)
    elif os.environ.get("REPRO_OBS", "") not in ("", "0"):
        configure(enabled=True)


@atexit.register
def _close_at_exit() -> None:
    _TRACER.close()


_env_bootstrap()
