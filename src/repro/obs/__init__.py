"""Unified observability layer: tracing, metrics, Chrome-trace export.

See `repro.obs.telemetry` for the facade the serving stack threads
through (`QueryService(telemetry=...)`), `repro.obs.trace` for the
span/timeline tracer and trace-event schema validator, and
`repro.obs.metrics` for the counter/gauge/histogram registry backing
`QueryService.stats()` and the Prometheus snapshot.
"""
from repro.obs.metrics import (
    HISTOGRAM_CAP,
    NULL_METRICS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullMetrics,
)
from repro.obs.telemetry import (
    NULL_TELEMETRY,
    Telemetry,
    get_telemetry,
    set_telemetry,
)
from repro.obs.trace import (
    LEAF_SPANS,
    MODEL_PID,
    NULL_TRACER,
    WALL_PID,
    NullTracer,
    Span,
    Tracer,
    validate_chrome_trace,
    write_chrome_trace,
)

__all__ = [
    "HISTOGRAM_CAP",
    "NULL_METRICS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullMetrics",
    "NULL_TELEMETRY",
    "Telemetry",
    "get_telemetry",
    "set_telemetry",
    "LEAF_SPANS",
    "MODEL_PID",
    "NULL_TRACER",
    "WALL_PID",
    "NullTracer",
    "Span",
    "Tracer",
    "validate_chrome_trace",
    "write_chrome_trace",
]
