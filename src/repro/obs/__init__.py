"""Engine-wide observability: trace spans, explain/calibrate, metrics.

Three cross-cutting pieces, wired through core and serve:

* ``trace``     — clock-injected, ring-buffered ``Tracer`` recording
  phase spans (resolve, layout build, dispatch with JAX's trace / lower
  / compile inside, device wait, disk load, serve flush), each also a
  profiler ``TraceAnnotation`` on the device trace's clock.  Attach
  with ``Engine(tracer=Tracer())``; with none attached, spans go to
  ``default_tracer()`` while the JAX profiler records, and cost one
  check otherwise.
* ``metrics``   — the unified ``MetricsRegistry`` (counters, gauges,
  log-spaced histograms, snapshot providers) every counting subsystem
  registers into; one ``snapshot()``, surfaced via ``--metrics-json``
  on the launchers and merged into ``Frontend.stats()``.
  ``LatencyHistogram`` lives here (``serve.metrics`` re-exports it).
* ``calibrate`` — predicted-vs-measured residuals per `auto` axis:
  ``Engine.explain(spec)`` reports every candidate's predicted cost
  without executing; ``Engine.run`` enriches ``Result.decision`` with
  measured counterparts; this module compares the two (and
  ``bench_delivery``'s regime table) in log2 space.
"""
from repro.obs.calibrate import (
    decision_residuals,
    delivery_calibration,
    delivery_traffic_pair,
    executed_supersteps,
    fused_traffic,
    reference_traffic,
    residual_log2,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    LatencyHistogram,
    MetricsRegistry,
    default_registry,
    reset_default_registry,
    weak_provider,
)
from repro.obs.trace import Span, Tracer, default_tracer, maybe_span

__all__ = [
    "Counter",
    "Gauge",
    "LatencyHistogram",
    "MetricsRegistry",
    "Span",
    "Tracer",
    "decision_residuals",
    "default_registry",
    "default_tracer",
    "delivery_calibration",
    "delivery_traffic_pair",
    "executed_supersteps",
    "fused_traffic",
    "maybe_span",
    "reference_traffic",
    "reset_default_registry",
    "residual_log2",
    "weak_provider",
]
