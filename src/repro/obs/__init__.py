"""repro.obs — unified telemetry: metrics, request traces, phase spans.

Bottom-of-graph layer (beside ``errors``): imports nothing from the rest
of ``repro``, so every layer above — including ``robust`` — may publish
into it.  Three surfaces:

- :data:`REGISTRY` — the process-wide metrics registry; every counter
  island in the codebase (health table, fault seams, tuner, executor
  cache, serving stats, test hooks) records here.
- :data:`TRACES` — ring buffer of completed per-request traces from the
  serving layer and the ``repro.sparse`` facade (``SpmmConfig.telemetry``
  plans only).
- :class:`span` — the program's host phase spans (``repro.call``,
  ``repro.lookup``, ``repro.launch``, ``repro.flush``, ...), written on
  the profiler's clock whenever ``jax.profiler`` traces, with their recent
  host durations in :data:`SPAN_TIMES`.  Device time per stage of the
  fused body is on the same trace, under the ``jax.named_scope`` names of
  ``exec.pipeline.SCOPES``.

``snapshot()`` returns the whole state as JSON-serializable dicts;
``prometheus_text()`` emits the registry's Prometheus text exposition,
which ``metrics.parse_prometheus_text`` round-trips.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    format_sample,
    get_registry,
    instance_label,
    parse_prometheus_text,
)
from .trace import SPAN_TIMES, Span, SpanTimes, Trace, TraceStore, TRACES, span

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "REGISTRY",
    "format_sample",
    "get_registry",
    "instance_label",
    "parse_prometheus_text",
    "SPAN_TIMES",
    "Span",
    "SpanTimes",
    "Trace",
    "TraceStore",
    "TRACES",
    "span",
    "snapshot",
    "prometheus_text",
    "reset_for_tests",
]


def snapshot(*, trace_limit: Optional[int] = 64) -> Dict[str, Any]:
    """One JSON-serializable dict of all telemetry state."""
    return {
        "metrics": REGISTRY.snapshot(),
        "traces": TRACES.snapshot(trace_limit),
        "spans": SPAN_TIMES.snapshot(),
    }


def prometheus_text() -> str:
    """Prometheus text exposition of the registry's metrics."""
    return REGISTRY.to_prometheus()


def reset_for_tests() -> None:
    """Zero all metric series and drop traces and span durations.

    Metric *objects* (and their registrations) survive — modules register
    at import time; only values reset.
    """
    REGISTRY.reset_values()
    TRACES.reset()
    SPAN_TIMES.reset()
