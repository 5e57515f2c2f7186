"""repro.obs — runtime self-observability.

Three pieces, one contract (non-interference with the 2-dispatch epoch
loop; CI-gated by ``benchmarks/run.py --obs``):

* :mod:`repro.obs.metrics` — labeled metrics registry (counters, gauges,
  bounded-bucket histograms).  ``core.runtime``'s ``DISPATCH_COUNTS`` /
  ``TRACE_COUNTS`` are :class:`~repro.obs.metrics.CounterDict` views over
  it, keeping the historical dict API and ``counting()`` semantics.
* :mod:`repro.obs.trace` — host-side span tracer with an injectable
  monotonic clock and a zero-allocation disabled mode; also the audited
  ``now_s`` / ``elapsed_s`` timing helpers the benchmarks use.
* :mod:`repro.obs.chrometrace` — Chrome trace-event JSON writer +
  ``pipelining_visible``, turning the pipelined record-sync proof into a
  timeline artifact.

See ``docs/observability.md`` for the span taxonomy and naming rules.
"""
from __future__ import annotations

from .metrics import (                                      # noqa: F401
    Counter, CounterDict, Gauge, Histogram, MetricFamily, MetricsRegistry,
    REGISTRY, DEFAULT_LATENCY_BUCKETS_S,
)
from .trace import (                                        # noqa: F401
    Clock, CLOCK, NOOP_SPAN, NULL_TRACER, NullTracer, Span, SpanTracer,
    disable, elapsed_s, enable, get_tracer, named_scope, now_s, set_tracer,
    tracing,
)
from .chrometrace import (                                  # noqa: F401
    chrome_trace_events, pipelining_visible, write_chrome_trace,
)

__all__ = [
    "Counter", "CounterDict", "Gauge", "Histogram", "MetricFamily",
    "MetricsRegistry", "REGISTRY", "DEFAULT_LATENCY_BUCKETS_S",
    "Clock", "CLOCK", "NOOP_SPAN", "NULL_TRACER", "NullTracer", "Span",
    "SpanTracer", "disable", "elapsed_s", "enable", "get_tracer",
    "named_scope", "now_s", "set_tracer", "tracing",
    "chrome_trace_events", "pipelining_visible", "write_chrome_trace",
]
