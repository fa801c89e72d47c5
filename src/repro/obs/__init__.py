"""Runtime observability: tracing, metrics, compile-time attribution.

Zero-dependency (stdlib + numpy) and import-cycle free: this package
imports nothing from the rest of :mod:`repro`, while the runtime,
pipeline, CLIs and benchmarks all emit into it.  Three pillars:

* :mod:`repro.obs.trace` — span tree over the launch lifecycle
  (``submit → admit → queue-wait → pack → dep-resolve → dispatch
  (prepare → device-execute per group → counter-sync → to-results) →
  complete``) with Chrome-trace / Perfetto export, each span mirrored
  as a ``repro.<name>`` JAX profiler annotation while tracing is on.
  Process global: :data:`TRACER`; a ``RuntimeServer`` given its own
  ``tracer=`` passes it down to the executor, so one tracer per server
  sees the whole tree.
* :mod:`repro.obs.metrics` — counters / gauges / exact-quantile
  histograms; the landing pad for what used to live in ``TRANSFERS``,
  ``DrainStats`` and ad-hoc prints.  Process global: :data:`METRICS`;
  a server's own ``metrics=`` registry also receives its executor's
  compile counts (``jit.*``) and ``shard.dispatch_groups``.  The
  ``transfers.*`` counters stay process-wide (the ``TRANSFERS`` view).
* :mod:`repro.obs.jitprof` — cache-miss detection and wall-ms
  attribution around the two ``jax.jit`` seams
  (:func:`jit_call`, :func:`jit_summary`, :func:`jit_delta`).

Both globals are cheap no-ops until enabled (``TRACER.start()``) or
consulted (``METRICS`` is always on but recording is host-side only);
see ``docs/observability.md`` for the span and metric inventories.
"""
from .jitprof import delta as jit_delta
from .jitprof import jit_call
from .jitprof import summary as jit_summary
from .metrics import (METRICS, Counter, Gauge, Histogram, MetricsRegistry,
                      render_snapshot, safe_div)
from .trace import NULL_SPAN, TRACER, Span, Tracer

__all__ = [
    "METRICS", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "render_snapshot", "safe_div",
    "TRACER", "Tracer", "Span", "NULL_SPAN",
    "jit_call", "jit_summary", "jit_delta",
]
