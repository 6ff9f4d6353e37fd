"""Prometheus-style metric primitives and the process-wide registry.

This is the metrics half of the observability layer (OBSERVABILITY.md):
the :class:`Counter`/:class:`Gauge`/:class:`Histogram` primitives that
``xgboost_tpu.serving`` introduced, plus labeled families, plus ONE
process-wide :class:`MetricsRegistry` that every metric group —
:class:`ServingMetrics`, :class:`ReliabilityMetrics`, the training-side
:class:`TrainingMetrics`, and the collective-seam counters
(:mod:`xgboost_tpu.obs.comm`) — registers into, so a single
``render()`` covers the whole process regardless of which subsystems
are active.  The reference's analog is ``report_stats``
(``subtree/rabit/src/allreduce_mock.h:52-56,87-95``): one place that
accounts for allreduce time and checkpoint cost per version.

The always-on totals of every :func:`~xgboost_tpu.obs.trace.span`
(:class:`SpanTotals`) live here too: what an operator scrapes, and what
the benchmark reads for host work done before any profiler starts.
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# latency buckets in seconds: 0.5ms .. 5s, roughly x2 per step
_LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                    0.1, 0.25, 0.5, 1.0, 2.5, 5.0)
# batch-size buckets in rows: powers of two
_ROWS_BUCKETS = tuple(float(1 << i) for i in range(15))
# per-round wall-time buckets in seconds: 1ms .. 60s
_ROUND_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                  0.5, 1.0, 2.5, 5.0, 15.0, 60.0)


def _fmt(v: float) -> str:
    return f"{int(v)}" if float(v).is_integer() else repr(float(v))


def _escape_label(v: str) -> str:
    return (str(v).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


class Counter:
    """Monotonic counter (Prometheus ``counter``)."""

    def __init__(self, name: str, help_text: str = ""):
        self.name, self.help = name, help_text
        self._v = 0.0
        self._lock = threading.Lock()

    def inc(self, v: float = 1.0) -> None:
        with self._lock:
            self._v += v

    @property
    def value(self) -> float:
        return self._v

    def render(self) -> str:
        return (f"# HELP {self.name} {self.help}\n"
                f"# TYPE {self.name} counter\n"
                f"{self.name} {_fmt(self._v)}\n")


class Gauge:
    """Settable value (Prometheus ``gauge``)."""

    def __init__(self, name: str, help_text: str = ""):
        self.name, self.help = name, help_text
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._v = float(v)

    def inc(self, v: float = 1.0) -> None:
        with self._lock:
            self._v += v

    @property
    def value(self) -> float:
        return self._v

    def render(self) -> str:
        return (f"# HELP {self.name} {self.help}\n"
                f"# TYPE {self.name} gauge\n"
                f"{self.name} {_fmt(self._v)}\n")


class LabeledCounter:
    """One counter FAMILY with a single label dimension — e.g.
    ``xgbtpu_training_phase_seconds_total{phase="grow"}``.  The family
    renders one HELP/TYPE header and one sample per observed label
    value, which is what scrapers (and the exposition lint test)
    expect of labeled families."""

    def __init__(self, name: str, label: str, help_text: str = ""):
        self.name, self.label, self.help = name, label, help_text
        self._v: Dict[str, float] = {}
        self._lock = threading.Lock()

    def inc(self, label_value: str, v: float = 1.0) -> None:
        with self._lock:
            self._v[label_value] = self._v.get(label_value, 0.0) + v

    def value(self, label_value: str) -> float:
        return self._v.get(label_value, 0.0)

    def values(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._v)

    def render(self) -> str:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} counter"]
        with self._lock:
            items = sorted(self._v.items())
        for lv, v in items:
            lines.append(f'{self.name}{{{self.label}="{_escape_label(lv)}"}}'
                         f' {_fmt(v)}')
        return "\n".join(lines) + "\n"


class LabeledGauge:
    """Gauge family with one label dimension (e.g. eval scores keyed by
    ``set-metric``)."""

    def __init__(self, name: str, label: str, help_text: str = ""):
        self.name, self.label, self.help = name, label, help_text
        self._v: Dict[str, float] = {}
        self._lock = threading.Lock()

    def set(self, label_value: str, v: float) -> None:
        with self._lock:
            self._v[label_value] = float(v)

    def value(self, label_value: str) -> float:
        return self._v.get(label_value, 0.0)

    def values(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._v)

    def render(self) -> str:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} gauge"]
        with self._lock:
            items = sorted(self._v.items())
        for lv, v in items:
            lines.append(f'{self.name}{{{self.label}="{_escape_label(lv)}"}}'
                         f' {_fmt(v)}')
        return "\n".join(lines) + "\n"


class Histogram:
    """Fixed-bucket histogram (Prometheus ``histogram``) with quantile
    estimation by linear interpolation within the winning bucket —
    enough resolution for p50/p99 gauges on the metrics page."""

    def __init__(self, name: str, help_text: str = "",
                 buckets: Sequence[float] = _LATENCY_BUCKETS):
        self.name, self.help = name, help_text
        self.bounds = tuple(sorted(buckets))
        self._counts = [0] * (len(self.bounds) + 1)  # last = +Inf
        self._sum = 0.0
        self._n = 0
        self._lock = threading.Lock()

    def observe(self, x: float) -> None:
        i = bisect.bisect_left(self.bounds, x)
        with self._lock:
            self._counts[i] += 1
            self._sum += x
            self._n += 1

    @property
    def count(self) -> int:
        return self._n

    @property
    def sum(self) -> float:
        return self._sum

    def quantile(self, q: float) -> float:
        """Approximate q-quantile from the bucket counts.  Edge cases
        are exact: no observations -> 0.0; ``q<=0`` -> the lower edge of
        the first non-empty bucket; ``q>=1`` -> the upper edge of the
        last non-empty finite bucket (the top finite bound when the
        overflow bucket holds observations)."""
        with self._lock:
            n = self._n
            counts = list(self._counts)
        if n == 0:
            return 0.0
        if q <= 0.0:
            # lower edge of the first non-empty bucket (0.0 below the
            # first bound) — previously this returned bounds[0] even
            # when the first buckets were empty
            for i, c in enumerate(counts):
                if c > 0:
                    return self.bounds[i - 1] if i > 0 else 0.0
            return 0.0
        target = min(q, 1.0) * n
        cum = 0.0
        for i, c in enumerate(counts):
            prev = cum
            cum += c
            if cum >= target and c > 0:
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i] if i < len(self.bounds) else lo
                if hi <= lo:
                    return hi
                return lo + (hi - lo) * (target - prev) / c
        return self.bounds[-1]

    def render(self) -> str:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} histogram"]
        cum = 0
        with self._lock:
            counts = list(self._counts)
            total, s = self._n, self._sum
        for bound, c in zip(self.bounds, counts):
            cum += c
            lines.append(f'{self.name}_bucket{{le="{_fmt(bound)}"}} {cum}')
        lines.append(f'{self.name}_bucket{{le="+Inf"}} {total}')
        lines.append(f"{self.name}_sum {_fmt(s)}")
        lines.append(f"{self.name}_count {total}")
        return "\n".join(lines) + "\n"


# ---------------------------------------------------------------- registry
class MetricsRegistry:
    """Process-wide registry of named metric GROUPS.

    Groups (not individual metrics) register a render callable under a
    stable name; re-registering a name replaces the previous group (a
    test that builds several ``ServingMetrics`` keeps exactly one
    registered).  :meth:`render` concatenates every group — the body of
    the training ``/metrics`` endpoint, and the tail of the serving
    one."""

    def __init__(self):
        self._groups: Dict[str, Callable[[], str]] = {}
        self._lock = threading.Lock()

    def register(self, name: str, render_fn: Callable[[], str]) -> None:
        with self._lock:
            self._groups[name] = render_fn

    def unregister(self, name: str) -> None:
        with self._lock:
            self._groups.pop(name, None)

    def names(self) -> List[str]:
        with self._lock:
            return list(self._groups)

    def render(self, exclude: Sequence[str] = ()) -> str:
        with self._lock:
            groups = [(n, fn) for n, fn in self._groups.items()
                      if n not in exclude]
        return "".join(fn() for _, fn in groups)


_REGISTRY: Optional[MetricsRegistry] = None
_REGISTRY_LOCK = threading.Lock()


def registry() -> MetricsRegistry:
    """The process-wide MetricsRegistry singleton."""
    global _REGISTRY
    if _REGISTRY is None:
        with _REGISTRY_LOCK:
            if _REGISTRY is None:
                _REGISTRY = MetricsRegistry()
    return _REGISTRY


# ------------------------------------------------------------------ errors
_SWALLOWED: Optional[LabeledCounter] = None
_SWALLOWED_LOCK = threading.Lock()
_SWALLOW_EVENT_INTERVAL_S = 60.0
_swallow_last_event: Dict[str, float] = {}
_swallow_tls = threading.local()


def swallowed_errors() -> LabeledCounter:
    """The process-wide ``xgbtpu_swallowed_errors_total{site}`` family:
    every deliberately swallowed exception in the tree is counted here
    (the XGT004 lint rule enforces it), so "errors that vanish" become
    a scrapeable number instead of silence."""
    global _SWALLOWED
    if _SWALLOWED is None:
        with _SWALLOWED_LOCK:
            if _SWALLOWED is None:
                c = LabeledCounter(
                    "xgbtpu_swallowed_errors_total", "site",
                    "exceptions deliberately swallowed, by site")
                registry().register("errors", c.render)
                _SWALLOWED = c
    return _SWALLOWED


def swallowed_error(site: str, exc: Optional[BaseException] = None,
                    emit_event: bool = True) -> None:
    """Account a deliberately swallowed exception — the XGT004 fix
    recipe (ANALYSIS.md): increments
    ``xgbtpu_swallowed_errors_total{site=...}`` and, at most once per
    site per minute, emits a throttled ``error.swallowed`` obs event.

    NEVER raises: this runs inside ``except`` blocks on paths (the
    event log's own write failure, ``__del__`` at interpreter shutdown)
    where a second failure must not escape.  ``emit_event=False`` keeps
    callers that sit UNDER the event log (obs/events.py itself) from
    recursing into it; a thread-local guard backstops the same."""
    try:
        swallowed_errors().inc(site)
        if not emit_event or getattr(_swallow_tls, "active", False):
            return
        now = time.monotonic()
        with _SWALLOWED_LOCK:
            last = _swallow_last_event.get(site)
            if last is not None and now - last < _SWALLOW_EVENT_INTERVAL_S:
                return
            _swallow_last_event[site] = now
        _swallow_tls.active = True
        try:
            from xgboost_tpu.obs.trace import event
            event("error.swallowed", site=site,
                  error=f"{type(exc).__name__}: {exc}" if exc else "")
        finally:
            _swallow_tls.active = False
    except Exception:  # xgtpu: disable=XGT004 — accounting must not raise
        pass


# ------------------------------------------------------------ span totals
class SpanTotals:
    """Seconds and exits of every ``obs.span`` by name, ALWAYS on: no
    log, profiler or switch is needed, so these read the path a dark
    run takes (``xgbtpu_span_seconds_total{span}``,
    ``xgbtpu_span_total{span}``).  Durations are inclusive: a parent's
    seconds hold its children's."""

    def __init__(self):
        self.seconds = LabeledCounter(
            "xgbtpu_span_seconds_total", "span",
            "cumulative wall seconds inside obs.span(name), children "
            "included")
        self.count = LabeledCounter(
            "xgbtpu_span_total", "span", "obs.span(name) exits")
        self._lock = threading.Lock()
        registry().register("spans", self.render)

    def observe(self, name: str, seconds: float) -> None:
        # every span exit in the process comes through here: one lock
        # for both families, not one each (this is their only writer)
        secs, exits = self.seconds._v, self.count._v
        with self._lock:
            secs[name] = secs.get(name, 0.0) + seconds
            exits[name] = exits.get(name, 0.0) + 1.0

    def render(self) -> str:
        return self.seconds.render() + self.count.render()


_SPANS: Optional[SpanTotals] = None
_SPANS_LOCK = threading.Lock()


def span_totals() -> SpanTotals:
    """The process-wide SpanTotals singleton."""
    global _SPANS
    if _SPANS is None:
        with _SPANS_LOCK:
            if _SPANS is None:
                _SPANS = SpanTotals()
    return _SPANS


# ------------------------------------------------------------- reliability
class ReliabilityMetrics:
    """Process-wide failure-path accounting (RELIABILITY.md): how often
    the crash-safety machinery actually engaged.  One instance per
    process (:func:`reliability_metrics`), shared by the learner's
    model I/O, the CLI checkpoint ring, and the serving stack; rendered
    into every ``/metrics`` body via the registry."""

    def __init__(self, prefix: str = "xgbtpu_reliability"):
        p = prefix
        self.integrity_failures = Counter(
            f"{p}_integrity_failures_total",
            "persisted files that failed CRC/footer verification")
        self.ring_fallbacks = Counter(
            f"{p}_ckpt_ring_fallbacks_total",
            "checkpoint loads that fell back past a corrupt ring member")
        self.quarantines = Counter(
            f"{p}_quarantined_files_total",
            "corrupt files moved aside as *.corrupt")
        self.poisoned_reloads = Counter(
            f"{p}_poisoned_reload_skips_total",
            "reload polls skipped because the file content is known-bad")
        self.shed_requests = Counter(
            f"{p}_shed_requests_total",
            "abandoned (caller timed out) requests shed before dispatch")
        self.faults_injected = Counter(
            f"{p}_faults_injected_total",
            "chaos faults fired by the injection registry")
        self.drain_seconds = Gauge(
            f"{p}_drain_seconds",
            "duration of the last HTTP drain (SIGTERM to stopped)")
        # deadline discipline (reliability/deadline.py): requests turned
        # away BEFORE work because their budget was spent, and expired
        # batch entries dropped before device dispatch
        self.deadline_rejected = Counter(
            "xgbtpu_deadline_rejected_total",
            "requests rejected before device work because the deadline "
            "budget was spent or cannot cover observed service time")
        self.deadline_dropped = Counter(
            "xgbtpu_deadline_dropped_total",
            "expired requests dropped by the micro-batcher pre-dispatch")
        # gang-launcher stall/death accounting (parallel/launch.py):
        # RECOVERY.md recovery-cost bookkeeping, scrapeable like
        # everything else instead of stderr-only
        self.launch_worker_deaths = Counter(
            "xgbtpu_launch_worker_deaths_total",
            "worker processes observed dead nonzero by the gang "
            "launcher")
        self.launch_restarts = LabeledCounter(
            "xgbtpu_launch_restarts_total", "reason",
            "whole-gang restarts by the launcher, by reason "
            "(death = nonzero worker exit, stall = watchdog kill, "
            "fence = worker self-fenced, host_loss = permanent host "
            "death, growback = re-expansion to full size)")
        # elastic degraded-mesh recovery (RECOVERY.md degraded-mode
        # matrix): gang size re-planning, partition fencing, grow-back
        self.launch_mesh_size = Gauge(
            "xgbtpu_launch_mesh_size",
            "devices the launcher's current gang plan schedules "
            "(workers x local devices); drops on degrade, restores on "
            "grow-back")
        self.launch_degraded = Gauge(
            "xgbtpu_launch_degraded",
            "1 while the gang runs below its full planned size")
        self.launch_fences = Counter(
            "xgbtpu_launch_fence_total",
            "workers that self-fenced after the coordinator was "
            "unreachable past gang_partition_sec")
        self.launch_growbacks = Counter(
            "xgbtpu_launch_growbacks_total",
            "degraded gangs re-expanded to full size after a "
            "replacement worker registered")
        self._all = (self.integrity_failures, self.ring_fallbacks,
                     self.quarantines, self.poisoned_reloads,
                     self.shed_requests, self.faults_injected,
                     self.drain_seconds, self.deadline_rejected,
                     self.deadline_dropped, self.launch_worker_deaths,
                     self.launch_restarts, self.launch_mesh_size,
                     self.launch_degraded, self.launch_fences,
                     self.launch_growbacks)
        registry().register("reliability", self.render)

    def render(self) -> str:
        return "".join(m.render() for m in self._all)


_RELIABILITY: Optional[ReliabilityMetrics] = None
_RELIABILITY_LOCK = threading.Lock()


def reliability_metrics() -> ReliabilityMetrics:
    """The process-wide ReliabilityMetrics singleton.  Counters are
    cumulative for the process lifetime; tests read deltas."""
    global _RELIABILITY
    if _RELIABILITY is None:
        with _RELIABILITY_LOCK:
            if _RELIABILITY is None:
                _RELIABILITY = ReliabilityMetrics()
    return _RELIABILITY


# ---------------------------------------------------------------- training
class TrainingMetrics:
    """Training-side metric group (``xgbtpu_training_*``): live progress
    of a long run, scrapeable mid-run via the ``metrics_port=`` daemon
    (obs/server.py).  One instance per process
    (:func:`training_metrics`), fed by the round profiler
    (obs/profiler.py), the fused segment driver (learner.update_many),
    the eval path, and the CLI checkpoint loop."""

    def __init__(self, prefix: str = "xgbtpu_training"):
        p = prefix
        self.rounds = Counter(
            f"{p}_rounds_total", "boosting rounds completed")
        self.round = Gauge(
            f"{p}_round", "most recently completed boosting round index")
        self.round_seconds = Histogram(
            f"{p}_round_seconds", "wall time per boosting round",
            _ROUND_BUCKETS)
        self.phase_seconds = LabeledCounter(
            f"{p}_phase_seconds_total", "phase",
            "cumulative wall seconds per round phase "
            "(predict/gradient/grow/eval)")
        self.eval_score = LabeledGauge(
            f"{p}_eval_score", "key",
            "latest eval metric values, keyed set-metric")
        self.checkpoints = Counter(
            f"{p}_checkpoints_total", "model checkpoints written")
        self.checkpoint_seconds = Counter(
            f"{p}_checkpoint_seconds_total",
            "cumulative wall seconds spent writing checkpoints "
            "(the reference report_stats' checkpoint cost)")
        self.device_memory = Gauge(
            f"{p}_device_memory_bytes",
            "bytes in use on local device 0 (0 when the backend does "
            "not report memory stats)")
        # segmented round fusion (learner.update_many): one fused
        # dispatch covers a SEGMENT of rounds, so round_seconds goes
        # quiet on the fused path — these two carry the progress signal
        # instead (note the xgbtpu_train_ family, not xgbtpu_training_:
        # the dispatch is a device-launch unit, not a logical round)
        self.dispatch_seconds = Histogram(
            "xgbtpu_train_dispatch_seconds",
            "wall time per fused training dispatch (one scan over a "
            "segment of boosting rounds, device-blocked at the "
            "segment boundary)", _ROUND_BUCKETS)
        self.rounds_per_dispatch = Gauge(
            "xgbtpu_train_rounds_per_dispatch",
            "rounds covered by the most recent fused training dispatch "
            "(segment size; stays 0 on the per-round path)")
        # trace-time gauge (ops/pallas_hist._sum_chunks): 1 while a
        # job's rows fit one int32 accumulator block, 3 at 40M rows
        self.hist_row_chunks = Gauge(
            "xgbtpu_hist_row_chunks",
            "row chunks of the most recently traced Pallas level "
            "histogram: int8 sums at most 2^24 rows per int32 "
            "accumulator block and adds the chunks in float32 "
            "(0 until a Pallas histogram is traced)")
        # trace-time gauge (ops/pallas_hist._feature_dots): F, not the
        # padded f_pad, since the slots that only pad the last feature
        # tile run no dot
        self.hist_feature_dots = Gauge(
            "xgbtpu_hist_feature_dots",
            "per-feature one-hot dots one row tile runs at the most "
            "recently traced Pallas level histogram, summed over its "
            "feature tiles: the feature count F (0 until a Pallas "
            "histogram is traced)")
        # trace-time gauge (ops/pallas_hist._note_level): set at a
        # level of one node, added to at the others, so after a tree's
        # trace it holds the sum over the tree's levels
        self.hist_onehot_rows = Gauge(
            "xgbtpu_hist_onehot_rows",
            "one-hot rows one feature pushes through the MXU per row "
            "tile, summed over the Pallas level histograms of the most "
            "recently traced tree: 256 at depth 6 and 256 bins in int8, "
            "where a level past the first builds its left children only "
            "and a kernel of 1-32 nodes folds the bin id's high bits "
            "into idle lanes (352 with every node built, 1536 unfolded; "
            "0 until a Pallas histogram is traced)")
        # trace-time gauges of the level kernel's grid (_note_level too)
        self.hist_feature_tiles = Gauge(
            "xgbtpu_hist_feature_tiles",
            "feature tiles (f_pad // f_tile) of the most recently "
            "traced Pallas level histogram: 4 at 28 features and 256 "
            "bins, 2 at 13, 250 at 2,000 (0 until a Pallas histogram "
            "is traced)")
        self.hist_node_tiles = Gauge(
            "xgbtpu_hist_node_tiles",
            "node tiles (64 nodes each) of the Pallas level histograms "
            "of the most recently traced tree, summed over its levels: "
            "6 at depth 6, 8 at depth 8 in int8 (the 128-node level's "
            "64 left children are one; 9 with every node built: 0 "
            "until a Pallas histogram is traced)")
        # trace-time gauge (ops/pallas_hist._hist_pallas_derived)
        self.hist_derived_levels = Gauge(
            "xgbtpu_hist_derived_levels",
            "levels of the most recently traced tree whose Pallas "
            "histogram built the left children only and took each "
            "right child as parent - left in the kernel's int32 sums: "
            "5 at depth 6 and 7 at depth 8 in int8, 0 where every node "
            "is built (float modes, vmapped trees and lanes, scatter)")
        # loud fallback accounting: a multi-round train request that
        # took the per-round path instead of segmented fusion, by the
        # first failing eligibility reason (update_many's gate).  A
        # chaos or bench run that MEANT to measure the fused path
        # asserts this stays 0 (paired with the train.fused_fallback
        # obs event carrying the full reason list).
        self.fused_fallback = LabeledCounter(
            "xgbtpu_train_fused_fallback_total", "reason",
            "multi-round training runs that fell back from segmented "
            "round fusion to per-round dispatch, by first failing "
            "eligibility reason")
        self._all = (self.rounds, self.round, self.round_seconds,
                     self.phase_seconds, self.eval_score,
                     self.checkpoints, self.checkpoint_seconds,
                     self.device_memory, self.dispatch_seconds,
                     self.rounds_per_dispatch, self.hist_row_chunks,
                     self.hist_feature_dots, self.hist_onehot_rows,
                     self.hist_feature_tiles, self.hist_node_tiles,
                     self.hist_derived_levels, self.fused_fallback)
        registry().register("training", self.render)

    def observe_eval(self, scores: Dict[str, float]) -> None:
        """Record parsed eval-line scores (``{'train-error': 0.02}``)
        as gauges."""
        for k, v in scores.items():
            try:
                self.eval_score.set(k, float(v))
            except (TypeError, ValueError):
                pass

    def refresh_device_memory(self) -> None:
        """Best-effort device-memory gauge via
        ``jax.local_devices()[0].memory_stats()`` (TPU/GPU report it;
        CPU returns None — the gauge stays 0 there)."""
        try:
            import jax
            stats = jax.local_devices()[0].memory_stats()
            if stats:
                self.device_memory.set(float(stats.get("bytes_in_use", 0)))
        except Exception as e:
            # CPU backends report no memory stats; the gauge stays 0 —
            # but the miss is counted, not invisible
            swallowed_error("obs.metrics.device_memory", e,
                            emit_event=False)

    def render(self) -> str:
        self.refresh_device_memory()
        return "".join(m.render() for m in self._all)


_TRAINING: Optional[TrainingMetrics] = None
_TRAINING_LOCK = threading.Lock()


def training_metrics() -> TrainingMetrics:
    """The process-wide TrainingMetrics singleton."""
    global _TRAINING
    if _TRAINING is None:
        with _TRAINING_LOCK:
            if _TRAINING is None:
                _TRAINING = TrainingMetrics()
    return _TRAINING


# ---------------------------------------------------------------- predict
class PredictMetrics:
    """Prediction-path metric group (``xgbtpu_predict_*``): attributes
    the chunked tree-parallel traversal (models/tree.py) in /metrics.
    One instance per process (:func:`predict_metrics`), fed by
    ``Learner.predict`` and the serving ``PredictEngine``; rendered into
    every scrape via the registry."""

    def __init__(self, prefix: str = "xgbtpu_predict"):
        p = prefix
        self.rows = Counter(
            f"{p}_rows_total",
            "rows predicted through the gbtree traversal "
            "(Learner.predict + serving engine)")
        self.chunk_seconds = Histogram(
            f"{p}_chunk_seconds",
            "device traversal wall seconds per tree chunk "
            "(margin launch time / chunk count)", _LATENCY_BUCKETS)
        self.transfer_seconds = Histogram(
            f"{p}_transfer_seconds",
            "host→device feature upload wall seconds per transfer "
            "(prediction paths: learner blocks, engine batches, "
            "feature-store puts)", _LATENCY_BUCKETS)
        self.transfer_bytes = Counter(
            f"{p}_transfer_bytes_total",
            "host→device feature bytes uploaded on prediction paths "
            "(flat while the feature store serves resident entities)")
        self._all = (self.rows, self.chunk_seconds,
                     self.transfer_seconds, self.transfer_bytes)
        registry().register("predict", self.render)

    def observe_transfer(self, nbytes: int, seconds: float) -> None:
        """Account one host→device feature upload (the transfer-wall
        counters, round 7): every prediction-path upload feeds these, so
        'zero upload' claims (feature-store steady state) are assertable
        from /metrics instead of taken on faith."""
        self.transfer_bytes.inc(nbytes)
        self.transfer_seconds.observe(seconds)

    def render(self) -> str:
        return "".join(m.render() for m in self._all)


_PREDICT: Optional[PredictMetrics] = None
_PREDICT_LOCK = threading.Lock()


def predict_metrics() -> PredictMetrics:
    """The process-wide PredictMetrics singleton."""
    global _PREDICT
    if _PREDICT is None:
        with _PREDICT_LOCK:
            if _PREDICT is None:
                _PREDICT = PredictMetrics()
    return _PREDICT


def timed_device_put(arr, observe=None):
    """THE prediction-upload sequence: ``device_put`` + block + optional
    transfer accounting, in one place (learner blocks, the sparse
    host-binned path, engine batches, the prefetch pipeline's worker).
    ``observe`` is an ``(nbytes, seconds)`` callback — usually
    ``predict_metrics().observe_transfer``; ``None`` uploads without
    observing (engine warmup traffic).  The feature store times its own
    slab scatter separately (the write is upload + in-place update)."""
    import time

    import jax
    t0 = time.perf_counter()
    dev = jax.device_put(arr)
    jax.block_until_ready(dev)
    if observe is not None:
        observe(getattr(arr, "nbytes", 0), time.perf_counter() - t0)
    return dev


# ------------------------------------------------------------ feature store
class FeatureStoreMetrics:
    """Device-resident feature-store accounting (``xgbtpu_featurestore_*``,
    SERVING.md): the hit/miss economics of the predict-by-id fast path
    and the LRU's byte pressure.  One instance per process
    (:func:`featurestore_metrics`); rendered into every /metrics body via
    the registry."""

    def __init__(self, prefix: str = "xgbtpu_featurestore"):
        p = prefix
        self.hits = Counter(
            f"{p}_hits_total",
            "entity rows served from the device-resident store")
        self.misses = Counter(
            f"{p}_misses_total",
            "entity lookups that were not resident")
        self.evictions = Counter(
            f"{p}_evictions_total",
            "entity rows evicted by LRU byte-budget pressure")
        self.resident_bytes = Gauge(
            f"{p}_resident_bytes",
            "feature bytes currently resident on device")
        self._all = (self.hits, self.misses, self.evictions,
                     self.resident_bytes)
        registry().register("featurestore", self.render)

    def render(self) -> str:
        return "".join(m.render() for m in self._all)


_FEATURESTORE: Optional[FeatureStoreMetrics] = None
_FEATURESTORE_LOCK = threading.Lock()


def featurestore_metrics() -> FeatureStoreMetrics:
    """The process-wide FeatureStoreMetrics singleton."""
    global _FEATURESTORE
    if _FEATURESTORE is None:
        with _FEATURESTORE_LOCK:
            if _FEATURESTORE is None:
                _FEATURESTORE = FeatureStoreMetrics()
    return _FEATURESTORE


# ---------------------------------------------------------------- pipeline
class PipelineMetrics:
    """Continuous-training pipeline accounting (``xgbtpu_pipeline_*``,
    PIPELINE.md): the train→gate→publish cycle loop's health at a
    glance — cycles completed, gate verdicts, publish cost, trees
    shipped, and how stale the incumbent the fleet serves is.  One
    instance per process (:func:`pipeline_metrics`); rendered into
    every /metrics body via the registry."""

    def __init__(self, prefix: str = "xgbtpu_pipeline"):
        p = prefix
        self.cycles = Counter(
            f"{p}_cycles_total", "train→gate→publish cycles completed "
            "(any outcome: published, gate-failed, or idle)")
        self.cycle_seconds = Histogram(
            f"{p}_cycle_seconds", "wall time per pipeline cycle",
            _ROUND_BUCKETS)
        self.gate_pass = Counter(
            f"{p}_gate_pass_total", "candidates that passed the eval gate")
        self.gate_fail = Counter(
            f"{p}_gate_fail_total",
            "candidates rejected by the eval gate (incl. corrupt "
            "candidates failing CRC verification)")
        self.publishes = Counter(
            f"{p}_publishes_total",
            "gated models published to the serving path")
        self.publish_failures = Counter(
            f"{p}_publish_failures_total",
            "publish attempts that failed (I/O error or a rejected "
            "fleet canary rollout)")
        self.publish_seconds = Counter(
            f"{p}_publish_seconds_total",
            "cumulative wall seconds spent publishing gated models")
        self.trees_published = Counter(
            f"{p}_trees_published_total",
            "trees appended to the incumbent and published")
        self.quarantines = Counter(
            f"{p}_quarantines_total",
            "candidates quarantined (failed gate or failed verification)")
        self.resumes = Counter(
            f"{p}_resumes_total",
            "cycles resumed after a crash (checkpoint-ring mid-train "
            "resume or a re-gate of an already-trained candidate)")
        self.incumbent_age = Gauge(
            f"{p}_incumbent_age_seconds",
            "seconds since this pipeline last published (0 until the "
            "first publish)")
        self._published_at: Optional[float] = None
        self._all = (self.cycles, self.cycle_seconds, self.gate_pass,
                     self.gate_fail, self.publishes,
                     self.publish_failures, self.publish_seconds,
                     self.trees_published, self.quarantines,
                     self.resumes, self.incumbent_age)
        registry().register("pipeline", self.render)

    def note_publish(self) -> None:
        """Stamp the incumbent-age clock (monotonic — the gauge is a
        DURATION, XGT006)."""
        self._published_at = time.perf_counter()

    def render(self) -> str:
        if self._published_at is not None:
            self.incumbent_age.set(time.perf_counter()
                                   - self._published_at)
        return "".join(m.render() for m in self._all)


_PIPELINE: Optional[PipelineMetrics] = None
_PIPELINE_LOCK = threading.Lock()


def pipeline_metrics() -> PipelineMetrics:
    """The process-wide PipelineMetrics singleton."""
    global _PIPELINE
    if _PIPELINE is None:
        with _PIPELINE_LOCK:
            if _PIPELINE is None:
                _PIPELINE = PipelineMetrics()
    return _PIPELINE


class LaneMetrics:
    """Gang-batched tenant-lane accounting (``xgbtpu_lane_*``,
    PIPELINE.md "Gang-batched lanes"): how many tenants each stacked
    dispatch carried, how much of the stack was padding, how often a
    lane fell back to its own solo dispatch stream and why, and the
    shape-bucket population.  One instance per process
    (:func:`lane_metrics`); rendered into every /metrics body via the
    registry."""

    def __init__(self, prefix: str = "xgbtpu_lane"):
        p = prefix
        self.dispatches = Counter(
            f"{p}_dispatches_total",
            "stacked multi-tenant segment dispatches (one device launch "
            "each, regardless of how many lanes it carried)")
        self.stacked = Counter(
            f"{p}_stacked_total",
            "real tenant lane-segments advanced by stacked dispatches")
        self.padded = Counter(
            f"{p}_padded_total",
            "inactive pad lane-segments dispatched to round a bucket up "
            "to its power-of-two stack width")
        self.solo = LabeledCounter(
            f"{p}_solo_total", "reason",
            "lane cycles that ran the solo host-loop path instead of "
            "stacking, by first blocking reason")
        self.stack_width = Gauge(
            f"{p}_stack_width",
            "lane count (incl. padding) of the most recent stacked "
            "dispatch")
        self.buckets = Gauge(
            f"{p}_buckets",
            "distinct shape buckets in the most recent gang window")
        self.dispatch_seconds = Histogram(
            f"{p}_dispatch_seconds",
            "wall time per stacked segment dispatch (all lanes in the "
            "bucket advance together)", _ROUND_BUCKETS)
        self.restacks = Counter(
            f"{p}_restack_total",
            "bucket re-stacks: dispatches that rebuilt the stacked "
            "device columns instead of reusing the steady-bucket carry "
            "(lane churn, fresh data, or a first arrival)")
        self._all = (self.dispatches, self.stacked, self.padded,
                     self.solo, self.stack_width, self.buckets,
                     self.dispatch_seconds, self.restacks)
        registry().register("lanes", self.render)

    def render(self) -> str:
        return "".join(m.render() for m in self._all)


_LANES: Optional[LaneMetrics] = None
_LANES_LOCK = threading.Lock()


def lane_metrics() -> LaneMetrics:
    """The process-wide LaneMetrics singleton."""
    global _LANES
    if _LANES is None:
        with _LANES_LOCK:
            if _LANES is None:
                _LANES = LaneMetrics()
    return _LANES


class StreamMetrics:
    """Streaming continuous-learning accounting (``xgbtpu_stream_*``,
    PIPELINE.md streaming section): batch ingest, micro-cycle
    composition, the idle/collecting/ready/catch-up state machine,
    backpressure, and the drift→cut-refresh loop.  One instance per
    process (:func:`stream_metrics`); rendered into every /metrics
    body via the registry."""

    def __init__(self, prefix: str = "xgbtpu_stream"):
        p = prefix
        self.batches = Counter(
            f"{p}_batches_total",
            "spooled row batches claimed into micro-cycle manifests")
        self.rows = Counter(
            f"{p}_rows_total", "rows consumed across all micro-cycles")
        self.cycles = Counter(
            f"{p}_cycles_total",
            "micro-cycle manifests composed (each commits its batch "
            "set before any data is returned)")
        self.backlog = Gauge(
            f"{p}_backlog",
            "unclaimed spooled batches ahead of the consumer")
        self.backpressure = Counter(
            f"{p}_backpressure_total",
            "producer pushes refused because the unclaimed backlog hit "
            "max_backlog (StreamBacklogFull)")
        self.state = Gauge(
            f"{p}_state",
            "stream source state: 0=idle 1=collecting 2=ready "
            "3=catch_up")
        self.drift_score = Gauge(
            f"{p}_drift_score",
            "max per-feature PSI of the sliding window vs the "
            "reference distribution, as of the last cycle")
        self.drift_events = Counter(
            f"{p}_drift_events_total",
            "drift FIRE edges (a score crossing the threshold while "
            "not already fired; hysteresis suppresses repeats)")
        self.cut_refreshes = Counter(
            f"{p}_cut_refreshes_total",
            "online quantile-cut rebuilds (sketch proposal unioned "
            "with live thresholds, incumbent rebound exactly)")
        self.refresh_seconds = Histogram(
            f"{p}_refresh_seconds",
            "wall time per online cut refresh (propose + union + "
            "persist)", _ROUND_BUCKETS)
        self.kept_features = Gauge(
            f"{p}_kept_features",
            "features surviving the EMA-gain screen for the current "
            "cycle (the histogram working set's F; full width when "
            "screening is off)")
        self._all = (self.batches, self.rows, self.cycles, self.backlog,
                     self.backpressure, self.state, self.drift_score,
                     self.drift_events, self.cut_refreshes,
                     self.refresh_seconds, self.kept_features)
        registry().register("stream", self.render)

    def render(self) -> str:
        return "".join(m.render() for m in self._all)


_STREAM: Optional[StreamMetrics] = None
_STREAM_LOCK = threading.Lock()


def stream_metrics() -> StreamMetrics:
    """The process-wide StreamMetrics singleton."""
    global _STREAM
    if _STREAM is None:
        with _STREAM_LOCK:
            if _STREAM is None:
                _STREAM = StreamMetrics()
    return _STREAM


_TENANT_DEQUEUES: Optional[LabeledCounter] = None
_TENANT_DEQUEUES_LOCK = threading.Lock()


def tenant_dequeues() -> LabeledCounter:
    """The process-wide
    ``xgbtpu_batcher_tenant_dequeues_total{model}`` family: requests
    dequeued from the micro-batcher's accept queue per tenant — the
    observable side of weighted round-robin fairness (a heavy tenant's
    share of dequeues tracks its weight, not its queue depth)."""
    global _TENANT_DEQUEUES
    if _TENANT_DEQUEUES is None:
        with _TENANT_DEQUEUES_LOCK:
            if _TENANT_DEQUEUES is None:
                c = LabeledCounter(
                    "xgbtpu_batcher_tenant_dequeues_total", "model",
                    "micro-batcher dequeues per tenant (WRR fairness)")
                registry().register("batcher", c.render)
                _TENANT_DEQUEUES = c
    return _TENANT_DEQUEUES


# ------------------------------------------------------------------- fleet
class FleetMetrics:
    """Router-side fleet accounting (``xgbtpu_fleet_*``, SERVING.md
    fleet section): per-replica request/error attribution, the global
    admission budget's shed count, retry and breaker activity, and the
    membership gauge pair (registered vs in-rotation — their gap is the
    fleet's sick-replica count).  One instance per process
    (:func:`fleet_metrics`); rendered into every /metrics body via the
    registry."""

    def __init__(self, prefix: str = "xgbtpu_fleet"):
        p = prefix
        self.requests = LabeledCounter(
            f"{p}_requests_total", "replica",
            "requests dispatched by the router, by replica")
        self.errors = LabeledCounter(
            f"{p}_errors_total", "replica",
            "dispatches that failed (connect/5xx), by replica")
        self.latency = Histogram(
            f"{p}_latency_seconds",
            "router-side request latency, dispatch to response "
            "(includes the replica hop and any retry)")
        self.shed = Counter(
            f"{p}_shed_total",
            "requests shed with 503 by the router's in-flight budget")
        self.retries = Counter(
            f"{p}_retries_total",
            "requests retried on a second replica after a failure")
        self.breaker_trips = Counter(
            f"{p}_breaker_trips_total",
            "circuit breakers tripped open (consecutive failures)")
        self.breaker_open = LabeledGauge(
            f"{p}_breaker_open", "replica",
            "1 while a replica's circuit breaker is open/half-open")
        self.members = Gauge(
            f"{p}_members",
            "replicas currently in rotation (lease live + healthy + "
            "serving)")
        self.members_registered = Gauge(
            f"{p}_members_registered",
            "replicas currently registered (any state)")
        self.inflight = Gauge(
            f"{p}_inflight", "requests in flight through the router")
        self.rollouts = Counter(
            f"{p}_rollouts_total", "canary rollouts completed fleet-wide")
        self.rollbacks = Counter(
            f"{p}_rollbacks_total",
            "rollouts rolled back (gate failure or operator command)")
        # latency-aware ejection (fleet/membership.py): a slow-but-alive
        # replica sails under the failure-count breaker while wrecking
        # fleet p99 — these make the ejection state machine scrapeable
        self.slow_ejections = Counter(
            f"{p}_slow_ejections_total",
            "replicas ejected from least-loaded dispatch for latency "
            "(EWMA above k x the peers' median)")
        self.ejected = LabeledGauge(
            f"{p}_ejected", "replica",
            "1 while a replica is latency-ejected (awaiting its "
            "readmission probe)")
        self.replica_latency = LabeledGauge(
            f"{p}_replica_latency_ewma_seconds", "replica",
            "per-replica EWMA of router-observed dispatch latency")
        # heartbeat payload drift fix: every advertisement change the
        # membership table absorbs mid-lease (catalog delta, eviction)
        # is counted, so "how stale could the routing map have been"
        # is answerable from a scrape
        self.advert_updates = Counter(
            f"{p}_advert_updates_total",
            "heartbeats whose model/device advertisement differed "
            "from the membership table (map updated in place)")
        self._all = (self.requests, self.errors, self.latency, self.shed,
                     self.retries, self.breaker_trips, self.breaker_open,
                     self.members, self.members_registered, self.inflight,
                     self.rollouts, self.rollbacks, self.slow_ejections,
                     self.ejected, self.replica_latency,
                     self.advert_updates)
        registry().register("fleet", self.render)

    def render(self) -> str:
        return "".join(m.render() for m in self._all)


_FLEET: Optional[FleetMetrics] = None
_FLEET_LOCK = threading.Lock()


def fleet_metrics() -> FleetMetrics:
    """The process-wide FleetMetrics singleton."""
    global _FLEET
    if _FLEET is None:
        with _FLEET_LOCK:
            if _FLEET is None:
                _FLEET = FleetMetrics()
    return _FLEET


# ----------------------------------------------------------------- catalog
class CatalogMetrics:
    """Replica-side model-catalog accounting (``xgbtpu_catalog_*``,
    SERVING.md catalog section): how many models are configured vs
    actually resident, where the shared device budget stands, and the
    admission/eviction churn of the cold tail.  One instance per
    process (:func:`catalog_metrics`); rendered into every /metrics
    body via the registry."""

    def __init__(self, prefix: str = "xgbtpu_catalog"):
        p = prefix
        self.models_configured = Gauge(
            f"{p}_models_configured",
            "models named in this replica's catalog manifest")
        self.models_resident = Gauge(
            f"{p}_models_resident",
            "models with a live engine on device right now")
        self.bytes_used = Gauge(
            f"{p}_bytes_used",
            "estimated device bytes held by resident model engines")
        self.bytes_budget = Gauge(
            f"{p}_bytes_budget",
            "serve_catalog_mb budget in bytes (0 = unlimited)")
        self.admissions = Counter(
            f"{p}_admissions_total",
            "evicted models re-built and re-warmed on demand")
        self.evictions = Counter(
            f"{p}_evictions_total",
            "cold models' engines LRU-evicted to fit the budget")
        self.requests = LabeledCounter(
            f"{p}_requests_total", "model",
            "catalog resolves served, by model name")
        self.unknown_model = Counter(
            f"{p}_unknown_model_total",
            "requests naming a model the catalog does not hold (404)")
        self._all = (self.models_configured, self.models_resident,
                     self.bytes_used, self.bytes_budget, self.admissions,
                     self.evictions, self.requests, self.unknown_model)
        registry().register("catalog", self.render)

    def render(self) -> str:
        return "".join(m.render() for m in self._all)


_CATALOG: Optional[CatalogMetrics] = None
_CATALOG_LOCK = threading.Lock()


def catalog_metrics() -> CatalogMetrics:
    """The process-wide CatalogMetrics singleton."""
    global _CATALOG
    if _CATALOG is None:
        with _CATALOG_LOCK:
            if _CATALOG is None:
                _CATALOG = CatalogMetrics()
    return _CATALOG


# ------------------------------------------------------------------ tenant
class TenantMetrics:
    """Router-side per-tenant accounting (``xgbtpu_tenant_*``,
    SERVING.md catalog section): request/shed/latency per model name at
    the front door, so one tenant's overload is attributable — and
    provably isolated — at a glance.  Latency is a labeled
    milliseconds-sum counter; pair with ``requests_total`` for the
    per-tenant mean (per-tenant quantiles live in the bench/chaos
    reports, which sample client-side).  One instance per process
    (:func:`tenant_metrics`)."""

    def __init__(self, prefix: str = "xgbtpu_tenant"):
        p = prefix
        self.requests = LabeledCounter(
            f"{p}_requests_total", "model",
            "requests entering the router, by model name")
        self.shed = LabeledCounter(
            f"{p}_shed_total", "model",
            "requests shed by that tenant's quota (429 rate / "
            "503 in-flight)")
        self.latency_ms = LabeledCounter(
            f"{p}_latency_ms_total", "model",
            "cumulative router-side request milliseconds, by model")
        self.inflight = LabeledGauge(
            f"{p}_inflight", "model",
            "requests currently in flight through the router, by model")
        self._all = (self.requests, self.shed, self.latency_ms,
                     self.inflight)
        registry().register("tenant", self.render)

    def render(self) -> str:
        return "".join(m.render() for m in self._all)


_TENANT: Optional[TenantMetrics] = None
_TENANT_LOCK = threading.Lock()


def tenant_metrics() -> TenantMetrics:
    """The process-wide TenantMetrics singleton."""
    global _TENANT
    if _TENANT is None:
        with _TENANT_LOCK:
            if _TENANT is None:
                _TENANT = TenantMetrics()
    return _TENANT


# ------------------------------------------------------------------ placer
class PlacerMetrics:
    """Control-plane accounting for the autonomous placer
    (``xgbtpu_placer_*``, SERVING.md "Autonomous placement"): plan
    churn, manifest-delta pushes, convergence state, and the elastic
    supervisor's band/resize activity.  One instance per process
    (:func:`placer_metrics`); rendered into every /metrics body via
    the registry."""

    def __init__(self, prefix: str = "xgbtpu_placer"):
        p = prefix
        self.ticks = Counter(
            f"{p}_ticks_total",
            "placement control-loop iterations (lease held)")
        self.standby_ticks = Counter(
            f"{p}_standby_ticks_total",
            "iterations skipped because another placer holds the lease")
        self.plans = Counter(
            f"{p}_plans_total",
            "target assignments computed that differ from the last")
        self.moves = LabeledCounter(
            f"{p}_moves_total", "kind",
            "tenant placement deltas decided, kind=attach|detach")
        self.pushes = Counter(
            f"{p}_pushes_total",
            "manifest-delta pushes sent to replica admin surfaces")
        self.push_errors = Counter(
            f"{p}_push_errors_total",
            "manifest-delta pushes that failed (replica unreachable "
            "or rejected)")
        self.tenants = Gauge(
            f"{p}_tenants",
            "tenant models under placer management")
        self.tenants_placed = Gauge(
            f"{p}_tenants_placed",
            "managed tenants with >=1 in-rotation host advertising "
            "them")
        self.converged = Gauge(
            f"{p}_converged",
            "1 while the fleet's advertised hosting matches the "
            "target assignment")
        self.fleet_util = Gauge(
            f"{p}_fleet_utilization",
            "EWMA of fleet in-flight / (replica_slots * replicas), "
            "the elastic band signal")
        self.replicas_target = Gauge(
            f"{p}_replicas_target",
            "replica count the elastic supervisor is converging to")
        self.resizes = LabeledCounter(
            f"{p}_resizes_total", "direction",
            "elastic resizes executed, direction=up|down")
        self.resize_holds = Counter(
            f"{p}_resize_holds_total",
            "resizes deferred because a rollout/canary soak was in "
            "flight (path-group pinning)")
        self._all = (self.ticks, self.standby_ticks, self.plans,
                     self.moves, self.pushes, self.push_errors,
                     self.tenants, self.tenants_placed, self.converged,
                     self.fleet_util, self.replicas_target, self.resizes,
                     self.resize_holds)
        registry().register("placer", self.render)

    def render(self) -> str:
        return "".join(m.render() for m in self._all)


_PLACER: Optional[PlacerMetrics] = None
_PLACER_LOCK = threading.Lock()


def placer_metrics() -> PlacerMetrics:
    """The process-wide PlacerMetrics singleton."""
    global _PLACER
    if _PLACER is None:
        with _PLACER_LOCK:
            if _PLACER is None:
                _PLACER = PlacerMetrics()
    return _PLACER


# ----------------------------------------------------------------- serving
class ServingMetrics:
    """Metric registry for the serving subsystem (see SERVING.md for the
    full schema).  One instance is shared by engine + batcher + registry
    + HTTP front end; :meth:`render` produces the ``GET /metrics`` body.
    The instance registers into the process-wide registry as group
    ``"serving"`` (latest instance wins), and its own render appends
    every OTHER registered group, so one scrape covers steady-state,
    failure-path, and training-side behavior at once."""

    def __init__(self, prefix: str = "xgbtpu_serving"):
        self.prefix = prefix
        self._metrics: Dict[str, object] = {}
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()  # uptime is a DURATION (XGT006)
        p = prefix
        self.requests = self.counter(
            f"{p}_requests_total", "prediction requests received")
        self.rows = self.counter(
            f"{p}_rows_total", "real (caller-supplied) rows predicted")
        self.padded_rows = self.counter(
            f"{p}_padded_rows_total",
            "padding rows added to reach the shape bucket")
        self.rejected = self.counter(
            f"{p}_rejected_total", "requests rejected with QueueFull (503)")
        self.errors = self.counter(
            f"{p}_errors_total", "requests that raised during prediction")
        self.batches = self.counter(
            f"{p}_batches_total", "coalesced device batches executed")
        self.compiles = self.counter(
            f"{p}_compiles_total", "predict executables compiled")
        self.reloads = self.counter(
            f"{p}_reloads_total", "successful model hot-reloads")
        self.reload_errors = self.counter(
            f"{p}_reload_errors_total", "failed model reload attempts")
        self.queue_rows = self.gauge(
            f"{p}_queue_rows", "rows currently waiting in the batch queue")
        self.model_version = self.gauge(
            f"{p}_model_version", "monotonic version of the served model")
        self.batch_rows = self.histogram(
            f"{p}_batch_rows", "rows per coalesced device batch",
            _ROWS_BUCKETS)
        self.latency = self.histogram(
            f"{p}_latency_seconds",
            "request latency, submit to result (includes queueing)")
        # p50/p99 latency as plain gauges (scrapers that don't do
        # histogram_quantile still get the headline numbers); refreshed
        # from the histogram at render time
        self.latency_p50 = self.gauge(
            f"{p}_latency_p50_seconds", "p50 request latency")
        self.latency_p99 = self.gauge(
            f"{p}_latency_p99_seconds", "p99 request latency")
        registry().register("serving", self._render_own)

    # ------------------------------------------------------- constructors
    def counter(self, name: str, help_text: str = "") -> Counter:
        return self._register(Counter(name, help_text))

    def gauge(self, name: str, help_text: str = "") -> Gauge:
        return self._register(Gauge(name, help_text))

    def histogram(self, name: str, help_text: str = "",
                  buckets: Sequence[float] = _LATENCY_BUCKETS) -> Histogram:
        return self._register(Histogram(name, help_text, buckets))

    def _register(self, m):
        with self._lock:
            if m.name in self._metrics:
                return self._metrics[m.name]
            self._metrics[m.name] = m
            return m

    # ------------------------------------------------------------- render
    @property
    def uptime_seconds(self) -> float:
        return time.perf_counter() - self._t0

    def quantiles(self, qs: Tuple[float, ...] = (0.5, 0.99)
                  ) -> Dict[float, float]:
        return {q: self.latency.quantile(q) for q in qs}

    def _render_own(self) -> str:
        self.latency_p50.set(self.latency.quantile(0.5))
        self.latency_p99.set(self.latency.quantile(0.99))
        with self._lock:
            metrics = list(self._metrics.values())
        return "".join(m.render() for m in metrics)

    def render(self) -> str:
        # every other registered group rides along (reliability has
        # always been here; training/comm join when active) so one
        # scrape covers the whole process
        reliability_metrics()  # ensure the classic tail exists
        return self._render_own() + registry().render(exclude=("serving",))
