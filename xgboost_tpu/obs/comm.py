"""Per-worker collective statistics — the ``report_stats`` analog.

The reference's mock allreduce accounts per-version allreduce time and
checkpoint cost (``subtree/rabit/src/allreduce_mock.h:52-56,87-95``);
"GPU-acceleration for Large-scale Tree Boosting" (PAPERS.md) shows the
communication volume is the number that decides sharding strategy.
This module is where that accounting lives for the TPU port: every
host-side collective entry records ``(op, count, bytes, seconds)``
both cumulatively (Prometheus counters, group ``"comm"`` in the
registry) and per boosting round (consumed by the round profiler's
timeline events and the multi-worker tests).

Instrumented seams:

- ``parallel/mock.py collective()`` — one ``allreduce`` count (+payload
  estimate) per tree-growth launch, so ``xgbtpu_comm_allreduce_total``
  matches the mock seam's seqno count by construction;
- the per-round growth launches (``models/gbtree.py do_boost``) add
  wall seconds via :func:`timed`/:func:`record` with ``count=0`` —
  host-side launch time; the device-side collective is inside XLA and
  visible only to ``profile=2`` traces;
- the MESH-FUSED scan (``do_boost_fused`` under a data mesh) counts
  its real in-scan reductions as ``psum``: ``max_depth`` histogram
  psums per tree-growth step with the whole-tree payload estimate in
  ``xgbtpu_comm_psum_bytes_total``.  Its ``seconds`` counter stays 0
  by design — the psums execute inside ONE fused device program, so
  per-collective wall time is not observable host-side (the per-round
  psum cost on the chip is not measured, PERF.md §7 row 3); the
  dispatch wall goes to
  ``xgbtpu_train_dispatch_seconds``, never to a collective family;
- ``parallel/sharded.py`` eval collectives (``allsum``/``allgatherv``)
  and ``parallel/colsplit.py`` per-level split gathers record as
  ``allgather`` with real payload bytes.

Bytes for in-XLA reductions are ESTIMATES of the logical payload (what
the reference would have shipped over rabit), not wire bytes — ICI
topology and XLA fusion make wire truth unknowable host-side.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

OPS = ("allreduce", "allgather", "psum")

_lock = threading.Lock()
_metrics = None
_round: Optional[int] = None
# per-round tallies: round -> op -> {"count","bytes","seconds"}
_per_round: Dict[int, Dict[str, Dict[str, float]]] = {}
_MAX_ROUND_HISTORY = 4096


class CommMetrics:
    """Cumulative per-op counters, registered as registry group
    ``"comm"``."""

    def __init__(self, prefix: str = "xgbtpu_comm"):
        from xgboost_tpu.obs.metrics import Counter, registry
        self.count: Dict[str, object] = {}
        self.bytes: Dict[str, object] = {}
        self.seconds: Dict[str, object] = {}
        for op in OPS:
            self.count[op] = Counter(
                f"{prefix}_{op}_total",
                f"host-side {op} collective launches")
            self.bytes[op] = Counter(
                f"{prefix}_{op}_bytes_total",
                f"logical payload bytes moved by {op} collectives "
                "(estimate for in-XLA reductions)")
            self.seconds[op] = Counter(
                f"{prefix}_{op}_seconds_total",
                f"host-side wall seconds in {op} collective launches")
        registry().register("comm", self.render)

    def render(self) -> str:
        parts = []
        for op in OPS:
            parts += [self.count[op].render(), self.bytes[op].render(),
                      self.seconds[op].render()]
        return "".join(parts)


def metrics() -> CommMetrics:
    """The process-wide CommMetrics singleton."""
    global _metrics
    if _metrics is None:
        with _lock:
            if _metrics is None:
                _metrics = CommMetrics()
    return _metrics


# ----------------------------------------------------------------- record
def begin_round(version: int) -> None:
    """Open the per-round tally for ``version`` (called from the mock
    seam's ``begin_round``, i.e. once per boosting round)."""
    global _round
    with _lock:
        _round = int(version)
        _per_round.setdefault(_round, {})
        if len(_per_round) > _MAX_ROUND_HISTORY:
            for k in sorted(_per_round)[:len(_per_round) // 2]:
                del _per_round[k]


def record(op: str, nbytes: float = 0.0, seconds: float = 0.0,
           count: int = 1) -> None:
    """Record one (or ``count``) collective launches of ``op`` with a
    payload estimate and host wall seconds.  ``count=0`` adds
    bytes/seconds to an already-counted launch (the timing wrapper
    around a launch whose count the mock seam already took)."""
    m = metrics()
    if count:
        m.count[op].inc(count)
    if nbytes:
        m.bytes[op].inc(float(nbytes))
    if seconds:
        m.seconds[op].inc(float(seconds))
    with _lock:
        if _round is None:
            return
        tally = _per_round[_round].setdefault(
            op, {"count": 0.0, "bytes": 0.0, "seconds": 0.0})
        tally["count"] += count
        tally["bytes"] += float(nbytes)
        tally["seconds"] += float(seconds)


@contextmanager
def timed(op: str, nbytes: float = 0.0, count: int = 1):
    """Time a block as one collective launch (``count=0`` when the mock
    seam already counted it)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        record(op, nbytes=nbytes, seconds=time.perf_counter() - t0,
               count=count)


# ---------------------------------------------------------------- queries
def round_stats(version: Optional[int] = None
                ) -> Dict[str, Dict[str, float]]:
    """Per-op tallies of one round (default: the current round); empty
    dict when nothing was recorded."""
    with _lock:
        v = _round if version is None else int(version)
        if v is None or v not in _per_round:
            return {}
        return {op: dict(t) for op, t in _per_round[v].items()}


def all_round_stats() -> Dict[int, Dict[str, Dict[str, float]]]:
    with _lock:
        return {r: {op: dict(t) for op, t in per_op.items()}
                for r, per_op in _per_round.items()}


def totals() -> Dict[str, Dict[str, float]]:
    """Cumulative per-op totals for THIS worker."""
    m = metrics()
    return {op: {"count": m.count[op].value,
                 "bytes": m.bytes[op].value,
                 "seconds": m.seconds[op].value} for op in OPS}


def aggregate_across_workers() -> Dict[str, Dict[str, float]]:
    """Sum per-worker totals across all processes using the existing mesh
    collective (``ShardedDMatrix.allsum`` — a multihost allgather+sum);
    in single-process mode this is just :func:`totals`."""
    import numpy as np
    from xgboost_tpu.parallel.sharded import ShardedDMatrix
    mine = totals()
    vec = np.asarray([mine[op][k] for op in OPS
                      for k in ("count", "bytes", "seconds")], np.float64)
    summed = ShardedDMatrix.allsum(vec)
    out: Dict[str, Dict[str, float]] = {}
    i = 0
    for op in OPS:
        out[op] = {}
        for k in ("count", "bytes", "seconds"):
            out[op][k] = float(summed[i])
            i += 1
    return out


def reset_for_tests() -> None:
    """Drop per-round history (cumulative counters stay — tests read
    deltas, like the reliability counters)."""
    global _round
    with _lock:
        _per_round.clear()
        _round = None
