"""Observability: metrics registry, span tracing, and profiler capture.

Every serving stage is spanned, and every query-aware distribution
(nprobe_eff, overflow, replica-dedup, batch shape, queue and head-of-line
wait) is a registry metric. An enabled ``Tracer`` also writes its spans into
``jax.profiler`` captures, on the same clock as the device operations. See
README "Observability".
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               default_registry, parse_exposition)
from repro.obs.profiling import profile_capture
from repro.obs.trace import NOOP, Span, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "default_registry", "parse_exposition",
    "Span", "Tracer", "NOOP",
    "profile_capture",
]
