"""paddle_tpu.observability — one telemetry substrate for every layer.

Three pieces (docs/OBSERVABILITY.md has the full guide):

- **Metrics registry** (``registry.py``): thread-safe ``Counter`` /
  ``Gauge`` / ``Histogram`` families with label sets and an injectable
  clock; Prometheus text exposition + JSON exporters. The process
  default (``default_registry()``) is what serving, jit, io, and
  distributed publish to.
- **Spans** (``tracing.py``): host annotations with structured args
  that reach any live JAX profiler session
  (``jax.profiler.TraceAnnotation``), the chrome trace of
  ``profiler.Profiler``, and an always-on process ring with parents
  and self times (``tracing.query``) — serving spans carry request
  ids, so one request is traceable across engine iterations.
- **Flight recorder** (``flight_recorder.py``): bounded ring of the
  last N step records (latency, occupancy, queue depth, compile
  events) dumped to disk when a step raises, the watchdog flags a dead
  peer, or an unhandled exception escapes; workers additionally spill
  the ring periodically so even a SIGKILL leaves a post-mortem.
- **Watchtower** (``watchtower.py``): the sensing layer over all of
  the above — multi-window SLO burn rates against declared objectives,
  EWMA + robust z-score anomaly detectors, stall/orphan/death
  detection, and deduped structured ``Incident`` records served from
  the front door's ``/healthz`` + ``/incidents`` endpoints and
  rendered by ``tools/ptpu_doctor.py``.
- **Cluster timeline** (``timeline.py``): merges per-process trace
  buffers and registry snapshots (scraped over the cluster
  ``telemetry`` RPC) into one chrome trace with per-request lanes, a
  per-request SLO attribution, and one cluster-wide Prometheus
  exposition (counters summed, gauges worker-labeled, histograms
  bucket-merged).

Instrumented out of the box: ``serving/engine.py`` (per-step spans,
queue/eviction/prefill counters, TTFT + inter-token + queue-wait
histograms), ``jit/static_function.py`` + ``jit/auto_capture.py``
(compile / cache-hit / graph-break / never-trace counters),
``distributed/watchdog.py`` (heartbeat-age gauge, failure counter,
dump hook), ``io/dataloader.py`` (batch-wait histogram), and
``profiler.Profiler.export_metrics`` (one chrome trace + one metrics
snapshot from the same run).
"""
from .registry import (Counter, Gauge, Histogram,  # noqa: F401
                       MetricError, MetricRegistry, default_registry)
from .tracing import (Span, span, TraceContext,  # noqa: F401
                      TraceBuffer, install_trace_buffer,
                      current_trace_buffer, bind_request,
                      unbind_request, clear_bindings, context_for,
                      active_context)
from .flight_recorder import FlightRecorder, default_recorder  # noqa: F401
from .timeline import ClusterTelemetry  # noqa: F401
from .watchtower import (Watchtower, Incident,  # noqa: F401
                         SLOObjective, DEFAULT_OBJECTIVES,
                         EwmaDetector, RobustZDetector,
                         render_diagnosis)

__all__ = ["Counter", "Gauge", "Histogram", "MetricError",
           "MetricRegistry", "default_registry", "Span", "span",
           "TraceContext", "TraceBuffer", "install_trace_buffer",
           "current_trace_buffer", "bind_request", "unbind_request",
           "clear_bindings", "context_for", "active_context",
           "FlightRecorder", "default_recorder", "ClusterTelemetry",
           "Watchtower", "Incident", "SLOObjective",
           "DEFAULT_OBJECTIVES", "EwmaDetector", "RobustZDetector",
           "render_diagnosis"]
