"""Serving telemetry: span recorder (bounded ring, Perfetto trace-event
JSON, mirrored into the JAX profiler while it captures), metrics
registry (counters / gauges / percentile histograms) and structured
logging.  ``Telemetry`` is the facade the runtime takes; ``recorder()``
is the process-wide span recorder; everything here is import-free of
the runtime package so it can be used standalone."""
from .logger import StructLogger, as_logger
from .metrics import Counter, Gauge, Histogram, MetricsRegistry, percentile
from .telemetry import OFF_TELEMETRY, Telemetry
from .trace import (
    NULL_TRACER,
    PID_ENGINE,
    PID_FRONTEND,
    PID_PROCESS,
    PID_REQUESTS,
    Event,
    NullTracer,
    Tracer,
    recorder,
    validate_trace,
)

__all__ = [
    "Counter",
    "Event",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "OFF_TELEMETRY",
    "PID_ENGINE",
    "PID_FRONTEND",
    "PID_PROCESS",
    "PID_REQUESTS",
    "StructLogger",
    "Telemetry",
    "Tracer",
    "as_logger",
    "percentile",
    "recorder",
    "validate_trace",
]
