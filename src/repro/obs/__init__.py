"""Telemetry plane: request spans, metric registry, decision attribution,
and the SLO control plane built on top of them.

The paper's claim is that a lightweight latency manifest can *infer*
performance and interference; this package makes those inferences —
and the placements acted on them — visible:

* :mod:`repro.obs.trace` — per-request span tracer with trace ids that
  survive the session wire format, exportable as Chrome/Perfetto
  trace-event JSON (:class:`SpanTracer`; :data:`NULL_TRACER` default);
* :mod:`repro.obs.metrics` — counters/gauges/fixed-bucket histograms
  with Prometheus text exposition and JSON snapshot
  (:class:`MetricRegistry`);
* :mod:`repro.obs.attribution` — per-candidate, per-cost-model-term
  breakdown of every TraceTable search decision (:class:`DecisionLog`),
  fed by the ``SearchContext.attribution`` hook;
* :mod:`repro.obs.timeseries` — bounded ring-buffer samples of every
  registry series on the pump clock, with windowed rate/percentile
  derivation (:class:`TimeSeriesStore`);
* :mod:`repro.obs.slo` — multi-window burn-rate alerting over
  TTFT/TPOT/availability objectives (:class:`SLOMonitor`,
  :class:`Objective`, :class:`Alert`);
* :mod:`repro.obs.server` — a stdlib HTTP endpoint serving
  ``/metrics``, ``/timeseries``, ``/alerts``, ``/traces`` and
  ``/debug/decisions`` over real TCP (:class:`ObsServer`);
* :mod:`repro.obs.replay` — DecisionLog JSONL persistence plus a replay
  harness that re-scores recorded decisions under a modified cost model
  (:func:`dump_jsonl`, :func:`load_jsonl`, :func:`replay`).

All of it is opt-in: every instrumented class defaults to the null
tracer / no registry / no log, whose hot-path cost is one ``enabled``
check or one no-op call per span.  The tracer's context spans also land
in a running profiler session, on the device trace's clock.

``CANONICAL_STATS`` names the counter keys every scale's ``stats()``
facade agrees on (old per-scale keys remain as aliases for one release).
"""

from .attribution import DecisionLog, DecisionRecord
from .metrics import (BYTE_BUCKETS, LATENCY_BUCKETS, Counter, Gauge,
                      Histogram, MetricRegistry)
from .replay import (ReplayReport, dump_jsonl, load_jsonl, parse_cost,
                     record_to_json, replay, rescore)
from .server import ObsServer
from .slo import Alert, Objective, SLOMonitor
from .timeseries import TimeSeriesStore
from .trace import NULL_TRACER, TRACK_SCOPE, NullTracer, SpanTracer

#: Counter keys shared by ServeEngine.stats(), FleetGateway.stats(), and
#: RegionGateway.stats() — the unified naming the consistency test pins.
CANONICAL_STATS = ("requests_served", "requests_shed", "sessions_migrated",
                   "queue_depth")

__all__ = [
    "BYTE_BUCKETS", "LATENCY_BUCKETS", "CANONICAL_STATS",
    "Counter", "Gauge", "Histogram", "MetricRegistry",
    "DecisionLog", "DecisionRecord",
    "NULL_TRACER", "NullTracer", "SpanTracer", "TRACK_SCOPE",
    "TimeSeriesStore",
    "Alert", "Objective", "SLOMonitor",
    "ObsServer",
    "ReplayReport", "dump_jsonl", "load_jsonl", "parse_cost",
    "record_to_json", "replay", "rescore",
]
