"""`repro.obs` — the unified observability plane.

One :class:`Observability` object bundles the three measurement
surfaces the serving stack threads through every component:

* :class:`~repro.obs.registry.MetricsRegistry` — counters, gauges and
  fixed-bucket histograms. Components cache instrument handles at
  construction; the disabled registry hands out shared no-op
  instruments so the fused fast path pays one attribute load + one
  no-op call per record — near-zero, gated in
  ``benchmarks/routing_fastpath_bench.py`` (obs-on within 5% of
  obs-off at B=1024/K=100).
* :class:`~repro.obs.trace.Tracer` — request-scoped spans + events.
  The serving stack records at BATCH granularity (one event carries
  the request-id range it covers) so tracing stays O(batches) on the
  hot path; :func:`~repro.obs.export.request_timelines` re-expands the
  batch events into one ordered per-request timeline (dispatch →
  policy → admission spill → tier execute → complete). Every span,
  the disabled plane's included, is also a ``jax.profiler``
  annotation ``repro.<name>``, so a profiler trace shows the host
  stages beside the device operations; :func:`~repro.obs.trace.gc_spans`
  adds the garbage collector's pauses (``repro.gc``) while it is
  entered.
* exporters — :func:`~repro.obs.export.to_jsonl` event log and
  :func:`~repro.obs.export.prometheus_text` metrics snapshot, both
  byte-deterministic under a :class:`~repro.obs.clock.ManualClock`
  (golden-tested).

Observability is RUNTIME configuration, like ``runners=``: it is
passed to ``repro.api.build(spec, obs=...)``, never serialized into
the ``RouteSpec``. Metric VALUES ride the snapshot envelope's state
half (``state["obs"]``) when enabled; trace event history is local
measurement and never serializes (documented in api/session.py).
"""

from repro.obs.clock import Clock, ManualClock, MonotonicClock  # noqa: F401
from repro.obs.keys import int_keyed, str_keyed  # noqa: F401
from repro.obs.registry import (  # noqa: F401
    DEFAULT_TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullMetricsRegistry,
)
from repro.obs.trace import NullTracer, Span, Tracer, gc_spans  # noqa: F401
from repro.obs.plane import NULL_OBS, Observability  # noqa: F401
from repro.obs.export import (  # noqa: F401
    prometheus_text,
    request_timelines,
    span_tree,
    to_jsonl,
)

__all__ = [
    "Observability", "NULL_OBS",
    "MetricsRegistry", "NullMetricsRegistry",
    "Counter", "Gauge", "Histogram", "DEFAULT_TIME_BUCKETS",
    "Tracer", "NullTracer", "Span", "gc_spans",
    "Clock", "ManualClock", "MonotonicClock",
    "to_jsonl", "prometheus_text", "request_timelines", "span_tree",
    "str_keyed", "int_keyed",
]
