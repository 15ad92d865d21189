"""Unified observability plane: tracing + metrics for the data plane.

Zero-dependency (stdlib only).  ``TRACER`` records per-query spans —
tier-ladder I/O, per-batch DMA, kernel stages, async-drain writes —
nested across threads and exportable as Chrome trace-event JSON
(Perfetto); ``METRICS`` is the process-global counter/histogram
registry the scattered per-layer accounting rolls up into.  Span /
event / metric names are cataloged in ``obs/names.py`` and documented
(CI-enforced) in ``docs/observability.md``.  While armed, every span
also enters a ``jax.profiler.TraceAnnotation`` of its name, so the
engine's phases share the profiler trace's clock with the device.
"""

from repro.obs.metrics import (DEFAULT_LATENCY_BOUNDS_S, METRICS, Counter,
                               Histogram, MetricsRegistry)
from repro.obs.names import (EVENT_NAMES, METRIC_NAMES, SPAN_NAMES,
                             SPAN_PREFIXES)
from repro.obs.trace import (NULL_SPAN, NullSpan, Span, SpanEvent, Tracer,
                             TraceSummary, TRACER)

__all__ = [
    "Counter", "Histogram", "MetricsRegistry", "METRICS",
    "DEFAULT_LATENCY_BOUNDS_S",
    "Span", "SpanEvent", "NullSpan", "NULL_SPAN", "Tracer", "TRACER",
    "TraceSummary",
    "SPAN_NAMES", "SPAN_PREFIXES", "EVENT_NAMES", "METRIC_NAMES",
]
