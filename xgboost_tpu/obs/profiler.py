"""Per-round phase timing: stderr lines, tracing spans, and training
metrics from ONE instrument.

This is ``profiling.RoundProfiler`` moved into the observability layer
and taught to feed it (the compat import path keeps working).  Three
consumers, all driven by the same phase boundaries:

- ``level>=1`` — the classic ``[prof]`` stderr lines per round plus the
  end-of-run summary (``profile=1``); ``level>=2`` additionally
  captures a ``jax.profiler`` trace (``profile=2``);
- event log — every phase and every round emit ``kind="span"`` records
  (name ``train.phase``/``train.round``) when ``obs_log=`` is
  configured beside ``profile>=1``, the round record carrying the phase
  breakdown and the round's collective tallies (obs/comm.py) so a dead
  run leaves a replayable timeline;
- metrics — rounds completed, per-phase seconds, round wall time and
  device memory land on :class:`~xgboost_tpu.obs.metrics.TrainingMetrics`
  for the ``metrics_port=`` scrape.

Phases force a device barrier at their boundaries (``.block``) so
async dispatch doesn't smear costs across phases — which is also why
the learner only instruments rounds under ``profile>=1`` (a barrier per
phase serializes host and device); a log or a scrape port alone keeps
the fused path and its ``train.*`` spans.
"""

from __future__ import annotations

import time
from collections import defaultdict
from typing import Optional

from xgboost_tpu.obs import comm, trace
from xgboost_tpu.obs.metrics import training_metrics


class RoundProfiler:
    """Collects per-phase wall time per boosting round.

    ``level=0`` keeps the spans/metrics but prints nothing (the
    timeline self-test's shape); ``level>=1`` adds the ``[prof]``
    stderr lines; ``level>=2`` adds the jax.profiler trace."""

    def __init__(self, level: int = 1, trace_dir: Optional[str] = None,
                 out=None):
        import sys
        self.level = level
        self.trace_dir = trace_dir or "./xgtpu_profile"
        self.out = out if out is not None else sys.stderr
        self.rounds = []
        self._current = None
        self._tracing = False
        self._round_t0: Optional[float] = None
        self._round_trace: Optional[str] = None

    # ------------------------------------------------------------ lifecycle
    def start(self):
        if self.level >= 2 and not self._tracing:
            import jax
            jax.profiler.start_trace(self.trace_dir)
            self._tracing = True

    def stop(self):
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False
            print(f"[prof] jax.profiler trace written to {self.trace_dir}",
                  file=self.out)

    # ---------------------------------------------------------- round phases
    def begin_round(self, iteration: int):
        self._current = {"round": iteration, "phases": {}, "t0": None}
        self._round_t0 = time.perf_counter()
        self._round_trace = trace.new_id()
        trace.set_round(iteration)

    def phase(self, name: str):
        """Context manager timing one phase of the current round.  Call
        ``.block(x)`` inside (or rely on the caller's own sync) to pin
        async device work to this phase."""
        return _Phase(self, name)

    def end_round(self):
        if self._current is None:
            return
        c = self._current
        total = sum(c["phases"].values())
        dur = (time.perf_counter() - self._round_t0
               if self._round_t0 is not None else total)
        tm = training_metrics()
        tm.rounds.inc()
        tm.round.set(c["round"])
        tm.round_seconds.observe(dur)
        from xgboost_tpu.obs import events
        if events.get_log() is not None:
            rec = {"ts": round(time.time(), 6), "kind": "span",
                   "name": "train.round", "trace": self._round_trace,
                   "span": trace.new_id(), "round": c["round"],
                   "dur_ms": round(dur * 1e3, 3),
                   "attrs": {"phases_ms": {
                       k: round(v * 1e3, 3)
                       for k, v in c["phases"].items()}}}
            cs = comm.round_stats(c["round"])
            if cs:
                rec["attrs"]["comm"] = cs
            events.emit(rec)
        if self.level >= 1:
            parts = " ".join(f"{k}={v * 1e3:.1f}ms"
                             for k, v in c["phases"].items())
            print(f"[prof] round {c['round']}: total={total * 1e3:.1f}ms "
                  f"{parts}", file=self.out)
        self.rounds.append(c)
        self._current = None
        trace.set_round(None)

    # ------------------------------------------------------------- summary
    def summary(self) -> str:
        if not self.rounds:
            return "[prof] no rounds recorded"
        agg = defaultdict(float)
        for r in self.rounds:
            for k, v in r["phases"].items():
                agg[k] += v
        total = sum(agg.values())
        n = len(self.rounds)
        lines = [f"[prof] {n} rounds, {total:.3f}s total, "
                 f"{total / n * 1e3:.1f}ms/round"]
        if not agg:
            # rounds recorded but no phases inside them (e.g. every
            # phase elided): nothing to break down, and no total to
            # divide by
            lines.append("[prof]   (no phases recorded)")
            return "\n".join(lines)
        for k, v in sorted(agg.items(), key=lambda kv: -kv[1]):
            # all-zero phase durations (clock granularity, empty
            # rounds) must yield a line, not a ZeroDivisionError
            pct = (v / total * 100) if total > 0 else 0.0
            lines.append(f"[prof]   {k:<10s} {v:8.3f}s  "
                         f"{pct:5.1f}%  {v / n * 1e3:8.1f}ms/round")
        return "\n".join(lines)

    def print_summary(self):
        if self.level >= 1:
            print(self.summary(), file=self.out)


class _Phase:
    def __init__(self, prof: RoundProfiler, name: str):
        self.prof = prof
        self.name = name
        self._blocked = None

    def block(self, x):
        """Record device arrays whose completion closes this phase."""
        self._blocked = x
        return x

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.ts = time.time()
        return self

    def __exit__(self, *exc):
        if self._blocked is not None and exc[0] is None:
            import jax
            jax.block_until_ready(self._blocked)
        dur = time.perf_counter() - self.t0
        cur = self.prof._current
        if cur is None and self.prof.rounds:
            # outside begin/end (e.g. eval after end_round): fold into
            # the most recent round
            cur = self.prof.rounds[-1]
        if cur is not None:
            cur["phases"][self.name] = (
                cur["phases"].get(self.name, 0.0) + dur)
        training_metrics().phase_seconds.inc(self.name, dur)
        from xgboost_tpu.obs import events
        if events.get_log() is not None:
            rnd = cur["round"] if cur is not None else None
            events.emit({
                "ts": round(self.ts, 6), "kind": "span",
                "name": "train.phase", "trace": self.prof._round_trace,
                "span": trace.new_id(), "round": rnd,
                "dur_ms": round(dur * 1e3, 3),
                "attrs": {"phase": self.name}})
        return False
