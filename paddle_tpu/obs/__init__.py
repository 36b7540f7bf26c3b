"""paddle_tpu.obs — the unified observability plane.

One substrate for every signal the framework emits, replacing the
reference's two disjoint generations (Fluid ``platform/profiler`` spans
vs the legacy v2 ``Stat`` counter registry) with three coordinated
pieces:

* :mod:`.metrics` — the process-wide :data:`~.metrics.REGISTRY` of named
  ``Counter``/``Gauge``/``Histogram`` families (stable
  ``paddle_tpu_<subsystem>_<name>`` naming, README metrics-table
  enforced) every subsystem's ad-hoc counters migrated into; scraped by
  ``RpcServer``'s built-in ``metrics`` method, aggregated fleet-wide by
  ``FleetSupervisor.fleet_metrics()`` / ``OnlineLearningLoop.stats()``,
  rendered by ``tools/metrics_dump.py`` (JSON or Prometheus text).
* :mod:`.trace` — cross-process trace-id propagation: ids generated at
  client edges, carried in the RPC header, restored server-side, so
  ``tools/merge_traces.py`` can stitch one request across processes.
* :mod:`.slo` — the ACTIONABLE layer: declarative SLO rules
  (metric selector, objective, multi-window burn-rate thresholds)
  evaluated by a background ``SloMonitor`` against registry snapshots or
  merged fleet views, emitting ``paddle_tpu_slo_*`` series and typed
  breach findings surfaced through every ``health()``/``stats()``.
* :mod:`.recorder` — the per-process flight recorder (bounded ring of
  structured lifecycle events, ``flight_dump`` RPC on every RpcServer)
  and the ``IncidentCollector`` that snapshots the whole fleet into one
  incident bundle on breach / canary-fail / child-restart triggers.
* :mod:`.perf` — performance introspection: compile telemetry (the
  ``paddle_tpu_compile_seconds`` histogram + bounded per-process
  :data:`~.perf.COMPILE_LOG` of ``CompileRecord``\\ s, ``compile``
  flight events), device-memory watermark gauges
  (``paddle_tpu_device_bytes_live``/``_peak``,
  :func:`~.perf.sample_device_memory` / ``MemorySampler``), and the
  cost-attribution API (:func:`~.perf.attribute` AOT HLO/cost-analysis
  merge) ``tools/hlo_report.py`` is a thin argument parser over.
* :func:`~.metrics.json_safe` — the wire-safety coercion every
  ``stats()``/``health()`` payload passes through.
"""

from . import metrics, perf, recorder, slo, trace
from .metrics import (Counter, Gauge, Histogram, REGISTRY, json_safe,
                      merge_snapshots, next_instance, prometheus_text,
                      scrape)
from .perf import COMPILE_LOG, CompileRecord, MemorySampler
from .recorder import (FlightRecorder, IncidentCollector, RECORDER,
                       capture_bundle, record)
from .slo import SloBreach, SloMonitor, SloRule
from .trace import (current_trace_id, new_trace_id, set_trace_id,
                    reset_trace_id, trace_context)

__all__ = [
    "metrics", "trace", "slo", "recorder", "perf", "REGISTRY", "Counter",
    "Gauge", "Histogram", "json_safe", "merge_snapshots", "next_instance",
    "prometheus_text", "scrape", "current_trace_id", "new_trace_id",
    "set_trace_id", "reset_trace_id", "trace_context", "SloRule",
    "SloMonitor", "SloBreach", "FlightRecorder", "IncidentCollector",
    "RECORDER", "record", "capture_bundle", "COMPILE_LOG", "CompileRecord",
    "MemorySampler",
]
