"""Unified observability layer: structured tracing + typed metrics.

``obs.trace`` is the span/event tracer (Chrome-trace / Perfetto JSON
export); ``obs.metrics`` is the typed Counter/Gauge/Histogram registry
(JSON snapshot + Prometheus text exposition); ``obs.probes`` computes the
DC-specific gauges (diff-store occupancy, Bloom fill / false-positive
rate, governor ladder levels) from engine state.

Both the tracer and the registry have module-level defaults (logging-style)
so the engine/session/serving tiers record without threading handles
through every call site, and a zero-allocation no-op path when disabled so
the hot loop pays nothing by default.
"""

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    set_registry,
)
from repro.obs.trace import (
    Tracer,
    get_tracer,
    set_tracer,
    span,
    instant,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "set_registry",
    "Tracer",
    "get_tracer",
    "set_tracer",
    "span",
    "instant",
]
