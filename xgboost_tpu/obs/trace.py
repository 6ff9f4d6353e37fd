"""Lightweight tracing spans with thread-local parent linkage.

The span model (OBSERVABILITY.md): a :func:`span` is a context manager
that times a named unit of work and, when the event log is configured
(obs/events.py), appends one ``kind="span"`` record at exit carrying

- ``trace`` — the request/round correlation id.  The serving front end
  seeds it from the ``X-Request-Id`` header (and echoes it back); the
  round profiler seeds one per boosting round; a span opened with no
  ambient trace id starts a fresh one;
- ``span``/``parent`` — random 64-bit ids linked through a
  thread-local stack, so nested spans reconstruct into a tree;
- ``dur_ms`` and the caller's attributes.

Every span also feeds two sinks that need no log: the always-on totals
``xgbtpu_span_seconds_total{span}`` / ``xgbtpu_span_total{span}``, and a
``jax.profiler.TraceAnnotation`` of the same name, which a running
profiler session records on the device events' clock (the record's own
``ts`` is ``time.time()``, another clock).  Spans are cheap when logging
is off: two ``perf_counter`` reads, one lock around two dict updates and
the annotation (half a microsecond with no session), about 3 us in all;
no id is generated and nothing is formatted or written.

:func:`event` appends a discrete (non-timed) record the same way —
fault injections, reloads, drains, integrity failures.  Both attach
the current boosting round (:func:`set_round`) when one is active, so
a chaos fault lands next to the round it hit in the timeline.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Optional

from xgboost_tpu.obs import events
from xgboost_tpu.obs.metrics import span_totals

_observe = span_totals().observe

_tls = threading.local()
_round_lock = threading.Lock()
_current_round: Optional[int] = None


def new_id() -> str:
    """Random 64-bit hex id (span/trace ids)."""
    return os.urandom(8).hex()


def current_trace_id() -> Optional[str]:
    return getattr(_tls, "trace", None)


def current_span_id() -> Optional[str]:
    stack = getattr(_tls, "spans", None)
    return stack[-1] if stack else None


@contextmanager
def trace_context(trace_id: Optional[str] = None):
    """Set the ambient trace id for this thread (e.g. from an incoming
    ``X-Request-Id``); restores the previous one on exit.  ``None``
    generates a fresh id."""
    prev = getattr(_tls, "trace", None)
    _tls.trace = trace_id or new_id()
    try:
        yield _tls.trace
    finally:
        _tls.trace = prev


def set_round(version: Optional[int]) -> None:
    """Record the boosting round in progress (profiler/mock seam), so
    discrete events correlate with the round that produced them."""
    global _current_round
    with _round_lock:
        _current_round = version


def current_round() -> Optional[int]:
    return _current_round


def _annotation(name: str, attrs: dict):
    """A ``jax.profiler.TraceAnnotation`` for the span, or None where
    this process never imported jax (fleet/, placer/): no profiler
    session can be running there.  With no session an annotation costs
    half a microsecond; with one, the span lands in the ``/host:CPU``
    plane on the device events' clock."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    if not attrs:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.TraceAnnotation(
        name, **{k: v for k, v in attrs.items()
                 if isinstance(v, (bool, int, float, str))})


class span:
    """``with span(name, **attrs) as sp:`` times one named unit of work.
    Exceptions propagate (recorded as ``status="error"`` in the log).
    ``sp.set(k, v)`` adds attributes after the span opened (row counts,
    status codes, ...); ``sp.seconds`` holds the duration once it has
    closed.  Three sinks, one instrument:

    - always: seconds and one exit added to the process-wide totals
      (``xgbtpu_span_seconds_total`` / ``xgbtpu_span_total``, obs/metrics.py);
    - whenever a JAX profiler session is running (``profile=2``, or any
      ``jax.profiler.start_trace`` around the caller): a
      ``TraceAnnotation`` of the same name, nested as opened, carrying
      the scalar attributes known at entry;
    - when the event log is configured: one ``kind="span"`` record at
      exit.  Ids, ``time.time()`` and formatting stay behind this check
      (a log enabled mid-span emits from the NEXT span on).

    A class, not a ``@contextmanager`` generator: with no log and no
    session this is the whole cost of a span on the serving path
    (tests/test_spans.py bounds it).
    """

    __slots__ = ("name", "attrs", "trace", "span_id", "parent", "seconds",
                 "_ts", "_t0", "_own_trace", "_note")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.trace = self.span_id = self.parent = self.seconds = None

    def set(self, key: str, value) -> None:
        self.attrs[key] = value

    def __enter__(self):
        stack = getattr(_tls, "spans", None)
        if stack is None:
            stack = _tls.spans = []
        self.trace = getattr(_tls, "trace", None)
        self._own_trace = False
        self._ts = None
        if events.get_log() is not None:
            self.parent = stack[-1] if stack else None
            if self.trace is None:
                self._own_trace = True
                self.trace = _tls.trace = new_id()
            self.span_id = new_id()
            self._ts = time.time()
        stack.append(self.span_id)
        note = self._note = _annotation(self.name, self.attrs)
        if note is not None:
            note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, err, tb):
        dur = self.seconds = time.perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(exc_type, err, tb)
        _tls.spans.pop()
        _observe(self.name, dur)
        if self._own_trace:
            _tls.trace = None
        if self._ts is not None and events.get_log() is not None:
            rec = {"ts": round(self._ts, 6), "kind": "span",
                   "name": self.name, "trace": self.trace,
                   "span": self.span_id, "dur_ms": round(dur * 1e3, 3)}
            if self.parent is not None:
                rec["parent"] = self.parent
            rnd = current_round()
            if rnd is not None:
                rec["round"] = rnd
            if err is not None:
                rec["status"] = "error"
                rec["error"] = f"{type(err).__name__}: {err}"
            if self.attrs:
                rec["attrs"] = self.attrs
            events.emit(rec)
        return False


def event(name: str, **fields) -> None:
    """Append one discrete (non-timed) event record (no-op when the log
    is off)."""
    if events.get_log() is None:
        return
    rec = {"ts": round(time.time(), 6), "kind": "event", "name": name}
    trace = current_trace_id()
    if trace is not None:
        rec["trace"] = trace
    rnd = current_round()
    if rnd is not None:
        rec["round"] = rnd
    if fields:
        rec["attrs"] = fields
    events.emit(rec)
