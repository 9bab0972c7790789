"""Observability layer: virtual-time span tracing, Chrome trace export,
the latency histogram, and wall-clock spans on the profiler's clock.

See docs/observability.md for the span taxonomy, the trace-event
schema, the two clocks, and the histogram error-bound derivation.
"""
from repro.obs.export import to_chrome_trace
from repro.obs.metrics import DEFAULT_GROWTH, DEFAULT_LO, Histogram
from repro.obs.spans import span
from repro.obs.tracer import PHASE_CATS, Span, Tracer, TraceSummary
from repro.obs.validate import CONSERVATION_TOL_US, validate_chrome_trace

__all__ = [
    "Histogram",
    "DEFAULT_GROWTH",
    "DEFAULT_LO",
    "Span",
    "Tracer",
    "TraceSummary",
    "PHASE_CATS",
    "to_chrome_trace",
    "validate_chrome_trace",
    "CONSERVATION_TOL_US",
    "span",
]
