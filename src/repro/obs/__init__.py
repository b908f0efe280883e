"""Zero-dependency observability: span tracer, metrics registry, and
Chrome/Perfetto export.

- ``obs.trace``   — nested thread-safe spans around plan / lower /
  compile, kernel launches (trace time), autotune probes, and the
  serving calls; opt-in with a no-op fast path.
- ``obs.metrics`` — registry-scoped counters / gauges / fixed-bucket
  histograms replacing module-level global tallies.
- ``obs.export``  — ``trace_events`` JSON (``serve.py --trace-out``)
  and plain-text metrics (``serve.py --metrics``, ``health()``).
"""
from repro.obs.trace import (Span, Tracer, current_tracer, event, set_tracer,
                             span, use_tracer)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               default_registry, registry, reset_metrics,
                               set_registry, use_registry)
from repro.obs.export import (chrome_trace_events, render_metrics,
                              write_chrome_trace)

__all__ = [
    "Span", "Tracer", "current_tracer", "event", "set_tracer", "span",
    "use_tracer",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "default_registry",
    "registry", "reset_metrics", "set_registry", "use_registry",
    "chrome_trace_events", "render_metrics", "write_chrome_trace",
]
