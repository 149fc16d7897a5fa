"""repro.obs — tracing + metrics for the PH pipeline (ISSUE 8).

* :mod:`repro.obs.trace` — nested spans with device-lane attribution,
  Chrome ``trace_event`` export (Perfetto), the always-on :func:`stopwatch`
  timer, and the span-derived simulated critical path.
* :mod:`repro.obs.metrics` — the typed counter/gauge/histogram registry
  behind every ``stats`` dict the pipeline returns, with one documented
  schema (``docs/observability.md``).

Deliberately dependency-free (stdlib + nothing): importable from the
hottest core modules without cycles, and from environments without jax.
"""
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, MetricSpec,
                      SCHEMA, schema_markdown)
from .trace import (Span, Tracer, active_tracer, chrome_trace, coverage,
                    critical_path, span, stopwatch, tracing)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "MetricSpec",
    "SCHEMA", "schema_markdown",
    "Span", "Tracer", "active_tracer", "chrome_trace", "coverage",
    "critical_path", "span", "stopwatch", "tracing",
]
