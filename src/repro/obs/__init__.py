"""repro.obs — structured observability for the serving stack.

Three pieces, woven through executor / engine / batcher / store / SLO /
resilience:

* :class:`Tracer` — span/event recording of the full batch lifecycle
  (submit → admission → formation → join/regroup/coalesce/split-retry →
  advances → finish/shed/fault), exported as Chrome trace-event JSON
  (:meth:`Tracer.save`) loadable in Perfetto.  Every span also lands on
  the profiler's timeline as a ``jax.profiler.TraceAnnotation``, the
  clock of the device trace.  Disabled is the shared
  :data:`NULL_TRACER`: profiler spans only, zero storage.
* :class:`MetricsRegistry` — named counters / gauges / histograms /
  ring-buffer time series behind ``ServerMetrics`` (now a view), with a
  JSON :meth:`~MetricsRegistry.snapshot` and a Prometheus-style
  :meth:`~MetricsRegistry.exposition`.
* :class:`CacheReport` — the per-request cache-decision explainer built
  from the fused loop's on-device decision/proxy traces at finish
  boundaries: zero extra host syncs, exact per row.

Layering: this package imports nothing from ``repro.serve`` /
``repro.slo`` / ``repro.resilience`` — they all import it.
"""
from repro.obs.registry import MetricsRegistry, TimeSeries  # noqa: F401
from repro.obs.report import (  # noqa: F401
    CacheReport, fused_cache_reports, run_cache_reports,
    schedule_cache_report)
from repro.obs.tracer import (  # noqa: F401
    NULL_TRACER, NullTracer, Tracer, profiling, validate_chrome_trace)
