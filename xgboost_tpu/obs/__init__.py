"""xgboost_tpu.obs — the unified observability layer (OBSERVABILITY.md).

Four pieces, one package:

- **tracing spans** (:mod:`~xgboost_tpu.obs.trace`): ``span(name,
  **attrs)`` with thread-local parent linkage and per-request /
  per-round trace ids, wired through ingest (``ingest.*``), the fused
  segment driver (``train.*``), the serving path (``X-Request-Id`` in
  -> batcher -> engine -> response header out) and checkpoint
  save/load; each also feeds the always-on span totals and, under a
  JAX profiler session, a ``TraceAnnotation`` on the device's clock;
- **structured event log** (:mod:`~xgboost_tpu.obs.events`): spans and
  discrete events (reload, drain, integrity failure, fault injection)
  append to a crash-safe JSONL file (``obs_log=`` / ``XGBTPU_OBS_LOG``)
  that ``tools/obs_report.py`` renders into a timeline;
- **metrics** (:mod:`~xgboost_tpu.obs.metrics`): one process-wide
  :class:`MetricsRegistry` that :class:`ServingMetrics`,
  :class:`ReliabilityMetrics`, :class:`TrainingMetrics` and the
  collective stats all register into, with one ``render()``;
- **training scrapeability + collective stats**
  (:mod:`~xgboost_tpu.obs.server`, :mod:`~xgboost_tpu.obs.comm`):
  ``metrics_port=`` serves ``/metrics`` + ``/healthz`` from a daemon
  thread during training, and the ``parallel/`` collective seam
  accounts each allreduce/allgather per round and per rank — the
  reference's ``report_stats`` (``allreduce_mock.h:52-56,87-95``).

Cost contract (OBSERVABILITY.md): spans, their always-on totals and
their profiler annotations cost microseconds and change nothing the
program does, with a log or a scrape port on or off; only the per-round
:class:`RoundProfiler` (``profile>=1``) puts a device barrier at each
phase and keeps the round loop on the host.
"""

from xgboost_tpu.obs import comm  # noqa: F401
from xgboost_tpu.obs.events import (EventLog, configure_log,  # noqa: F401
                                    get_log)
from xgboost_tpu.obs.metrics import (Counter, Gauge,  # noqa: F401
                                     Histogram, LabeledCounter,
                                     LabeledGauge, LaneMetrics,
                                     MetricsRegistry,
                                     PipelineMetrics, PredictMetrics,
                                     ReliabilityMetrics, ServingMetrics,
                                     TrainingMetrics, lane_metrics,
                                     pipeline_metrics,
                                     predict_metrics, registry,
                                     reliability_metrics, span_totals,
                                     training_metrics)
from xgboost_tpu.obs.profiler import RoundProfiler  # noqa: F401
from xgboost_tpu.obs.server import (get_metrics_server,  # noqa: F401
                                    start_metrics_server,
                                    stop_metrics_server)
from xgboost_tpu.obs.trace import (current_trace_id, event,  # noqa: F401
                                   span, trace_context)


__all__ = [
    "comm", "span", "event", "trace_context", "current_trace_id",
    "EventLog", "configure_log", "get_log",
    "Counter", "Gauge", "Histogram", "LabeledCounter", "LabeledGauge",
    "MetricsRegistry", "registry",
    "ServingMetrics", "ReliabilityMetrics", "TrainingMetrics",
    "PredictMetrics", "predict_metrics",
    "PipelineMetrics", "pipeline_metrics",
    "LaneMetrics", "lane_metrics",
    "reliability_metrics", "training_metrics", "span_totals",
    "RoundProfiler",
    "start_metrics_server", "get_metrics_server", "stop_metrics_server",
]
