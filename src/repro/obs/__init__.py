"""Unified telemetry layer (ISSUE 8): clocks, spans, metrics, facade.

Import surface::

    from repro.obs import (Clock, ManualClock, MONOTONIC, as_clock,
                           Span, Tracer, NULL_TRACER,
                           MetricsRegistry, NULL_REGISTRY,
                           Telemetry, EventChannel, NULL_TELEMETRY,
                           make_telemetry, last_recording)

``repro.obs`` deliberately imports nothing from the rest of the repo,
so core and backend modules can depend on it without cycles.
"""
from repro.obs.clock import (Clock, ManualClock, MonotonicClock, MONOTONIC,
                             as_clock)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               NULL_REGISTRY, DEFAULT_BUCKETS)
from repro.obs.trace import Span, Tracer, NullTracer, NULL_TRACER
from repro.obs.telemetry import (Telemetry, EventChannel, NULL_TELEMETRY,
                                 make_telemetry, last_recording)

__all__ = [
    "Clock", "ManualClock", "MonotonicClock", "MONOTONIC", "as_clock",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NULL_REGISTRY",
    "DEFAULT_BUCKETS",
    "Span", "Tracer", "NullTracer", "NULL_TRACER",
    "Telemetry", "EventChannel", "NULL_TELEMETRY", "make_telemetry",
    "last_recording",
]
