"""Observability: metrics registry, trace flight recorder, spans on the
profiler's clock, exporters.

  * ``obs.metrics`` — typed counters/gauges/histograms behind one
    :class:`MetricsRegistry`; the executor / admission queue / facade
    counters all live here, and their legacy dict views are read-only
    views over it.  The metric-name catalog and the ``svc.stats``
    schema constants are defined here too.
  * ``obs.trace``   — :class:`TraceRecorder`, a ring buffer + JSONL
    sink of protocol-granularity events (per-batch, per-voted-round
    wire bytes fed by the exact engine byte account, stage spans, the
    retry/bisect/quarantine/breaker/chaos ladder).
  * ``obs.spans``   — :func:`span`, the service's host steps as
    ``jax.profiler.TraceAnnotation`` spans (``svc.pump`` ... ``svc.seal``)
    that also observe their ``stage.seconds`` histogram, so the registry
    stage and the profiler's span are one measurement.
  * ``obs.export``  — Prometheus-style text + human table renderers.

Everything is off-hot-path (events are recorded host-side at dispatch
boundaries, never inside jit-traced code) and deterministic under an
injected clock, so traced runs replay byte-identically.  The device side
is named by the engine itself: every protocol stage runs under an
``agg.*`` ``jax.named_scope`` (``core.engine.STAGE_SCOPES``).
"""
from repro.obs.metrics import (DEFAULT_REGISTRY, MetricsRegistry,
                               SVC_STATS_DEPRECATED, SVC_STATS_KEYS,
                               SVC_STATS_VERSION)
from repro.obs.trace import (TickClock, TraceRecorder, record_batch_trace,
                             record_func_round)
from repro.obs.export import prometheus_text, stats_table
from repro.obs.spans import SERVICE_SPANS, span

__all__ = [
    "DEFAULT_REGISTRY", "MetricsRegistry", "SERVICE_SPANS",
    "SVC_STATS_DEPRECATED", "SVC_STATS_KEYS", "SVC_STATS_VERSION",
    "TickClock", "TraceRecorder", "prometheus_text", "record_batch_trace",
    "record_func_round", "span", "stats_table",
]
