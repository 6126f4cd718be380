"""Telemetry for the CrossStack serving stack (dependency-free).

Two tiers of ownership:

* **Global registry/tracer** (:func:`registry`, :func:`tracer`) —
  process-wide signals that exist below any one scheduler: engine
  dispatch counts (``crossstack_dispatch_total``), executor
  program/swap events, and jit trace/retrace counters
  (``serve_jit_traces_total`` / ``serve_jit_retraces_total``, bumped
  from inside jitted closure bodies, i.e. at trace time only).
* **Per-scheduler registry/tracer** (``BatchScheduler.metrics`` /
  ``.tracer``) — request lifecycle, window and head fill, QoS shares,
  and the Table-I model of array time/energy, scoped so concurrent
  schedulers in one process (every bench builds several) never
  cross-contaminate and a ``telemetry=False`` scheduler is a clean
  metrics-off baseline.

See ``docs/OBSERVABILITY.md`` for the metric/span catalog.
"""
from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    DEFAULT_LATENCY_BUCKETS,
    parse_prometheus,
)
from repro.obs.trace import Span, Tracer

_REGISTRY = MetricsRegistry()
_TRACER = Tracer()


def registry() -> MetricsRegistry:
    """The process-global registry (engine/executor/jit-trace events)."""
    return _REGISTRY


def tracer() -> Tracer:
    """The process-global tracer (executor-level swap spans)."""
    return _TRACER


def note_jit_trace(closure: str, tenant: str, retrace: bool) -> None:
    """Record one jit trace of a serving closure in the global registry.

    Called from *inside* jitted function bodies — host-side code there
    runs at trace time only, so each call is exactly one (re)trace.
    A ``retrace`` is any trace beyond the first for a given built
    closure; the serving invariant is that the retrace counter stays 0
    across `begin_hot_swap` windows (leak codes are traced operands,
    never trace constants).
    """
    reg = _REGISTRY
    reg.counter(
        "serve_jit_traces_total",
        help="jit traces of serving closures (decode/prefill), "
             "counted at trace time").inc(closure=closure, tenant=tenant)
    if retrace:
        reg.counter(
            "serve_jit_retraces_total",
            help="jit re-traces beyond the first per built closure; "
                 "must stay 0 across hot-swap windows",
        ).inc(closure=closure, tenant=tenant)


def reset() -> None:
    """Zero the global registry samples and drop global spans (for
    tests/benches that need a clean process-wide slate)."""
    _REGISTRY.reset()
    _TRACER.clear()


__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Span", "Tracer",
    "DEFAULT_LATENCY_BUCKETS", "parse_prometheus",
    "registry", "tracer", "note_jit_trace", "reset",
]
