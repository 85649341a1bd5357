"""Observability layer: tracing spans + metrics registry (DESIGN.md §9).

``repro.obs`` imports nothing from the rest of ``repro``, so every layer
(service, core, benchmarks) can import it without cycles; it imports JAX
only inside the functions that trace. See :mod:`repro.obs.trace` and
:mod:`repro.obs.metrics`.
"""
from .trace import (  # noqa: F401
    TRACE_ENV,
    Trace,
    chrome_trace_doc,
    enabled,
    span,
    trace_to,
    write_chrome_trace,
)
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Info,
    MetricsRegistry,
    REGISTRY,
    default_registry,
)

__all__ = [
    "TRACE_ENV",
    "Trace",
    "chrome_trace_doc",
    "enabled",
    "span",
    "trace_to",
    "write_chrome_trace",
    "Counter",
    "Gauge",
    "Histogram",
    "Info",
    "MetricsRegistry",
    "REGISTRY",
    "default_registry",
]
