"""Flight recorder + unified telemetry (ISSUE 13).

Three pieces, all host-side pure (no jax import, no device fetches —
the graftlint GL002 fixture pins that the recorder never becomes a
hidden sync):

- ``recorder``: a bounded, preallocated ring of typed per-wave events
  (dispatch / harvest / fence-requeue / patch / bind-flush /
  degraded-transition / churn-op), wired through the engine's
  dispatch_waves/harvest_waves, the streaming loop, and both bind
  paths. Exact no-op when disabled; one lock + six scalar array writes
  per WAVE (not per pod) when on.
- ``registry``: the unified telemetry registry folding the span
  counters (utils/trace.py COUNTERS), SchedulerMetrics histograms,
  the ad-hoc service counter dicts, and live gauges (quantum, backlog,
  degraded state, commit/snapshot generations) into one labeled
  namespace with a single snapshot() and a single Prometheus render.
  Every introspection transport — HTTP ``/debug/vars``, the binary
  wire's STATS verb, ``VerdictService.debug_snapshot`` — serves THIS.
- ``perfetto``: a Chrome trace-event exporter rendering the recorder
  ring as host / fence / preempt / fastlane lanes, a loadable
  timeline (``python -m kubernetes_tpu.observability --trace out.json``
  then chrome://tracing or ui.perfetto.dev).

Pod-level black box (ISSUE 15), same host-pure discipline:

- ``podtrace``: head-sampled per-pod lifecycle timelines stamped at
  the queue/dispatch/harvest/fence/bind/preempt seams and joined
  across transports by a trace context; completion feeds a telescoping
  critical-path decomposition (phase sums == create->bound exactly)
  and a slowest-K tail-exemplar reservoir per window.
- ``slo``: the multiwindow burn-rate SLO engine over every bound pod's
  create->bound span — rolling p99, fast/slow burn gauges, alert flips
  on the flight-recorder ring. Both serve identically on every
  transport (HTTP /debug/pods + /debug/slo, the binary STATS verb,
  VerdictService.debug_snapshot) and fold into every registry
  snapshot.
"""

from kubernetes_tpu.observability.podtrace import TRACER, PodTracer
from kubernetes_tpu.observability.recorder import RECORDER, FlightRecorder
from kubernetes_tpu.observability.registry import TelemetryRegistry
from kubernetes_tpu.observability.slo import SLO, SLOMonitor

__all__ = ["FlightRecorder", "PodTracer", "RECORDER", "SLO",
           "SLOMonitor", "TRACER", "TelemetryRegistry"]
