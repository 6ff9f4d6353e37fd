"""Dynamic checkers — the runtime half of xgtpu-lint (ANALYSIS.md).

Static rules catch patterns; these catch the behaviors the patterns
cause, in real executions under pytest:

- :class:`RecompileGuard` counts XLA ``backend_compile`` events via
  ``jax.monitoring``, generalizing the serving subsystem's
  zero-steady-state-recompile test so ANY test can assert a compile
  budget over a code region (``with guard.expect(0): ...``).
- :class:`LockRaceChecker` wraps an object's locks in instrumented
  shims that record per-thread held-lock sets, then watches writes to
  lock-guarded attributes: a write with the guarding lock not held is
  recorded as a violation (the dynamic twin of the static XGT005
  rule), and acquiring two instrumented locks in opposite orders on
  different call paths is recorded as a lock-order inversion (a latent
  deadlock no single run deadlocks on).  The static complement is
  XGT011 (analysis/contracts.py): the whole-repo nested-acquisition
  graph sees every LEXICAL order, not just the ones a test executed;
  tests/test_analysis_contracts.py cross-checks that runtime
  observations are a subset of that graph.
- :class:`DonationGuard` is the runtime twin of the static XGT013
  use-after-donate rule: it wraps a ``donate_argnums`` jitted callable
  and, after each call, DELETES the device buffers the caller handed
  over at donated positions — which is what a donation that the
  backend took does anyway, and what one it declined (JAX warns and
  copies) does not.  A caller that touches a donated buffer post-call
  then raises loudly under test on any backend, instead of reading
  garbage only on device.

All record violations instead of raising at the fault site, so a
stress test collects everything and fails once with the full report
(``checker.assert_clean()``).
"""

from __future__ import annotations

import dataclasses
import threading
import traceback
from contextlib import contextmanager
from typing import Dict, List, Sequence, Set, Tuple

# ---------------------------------------------------------------- compiles
# jax.monitoring offers no listener unregistration, so one process-wide
# counter is installed once and consumers read deltas of it.  A plain
# int (not an event list): a long-lived process compiles indefinitely,
# and every consumer only ever needs the count.
_compile_count = 0
_LISTENER_LOCK = threading.Lock()
_listener_installed = False


def _ensure_listener() -> None:
    global _listener_installed
    with _LISTENER_LOCK:
        if _listener_installed:
            return
        import jax

        def _on_event(*args, **kwargs):
            global _compile_count
            if args and "backend_compile" in str(args[0]):
                with _LISTENER_LOCK:
                    _compile_count += 1

        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listener_installed = True


class RecompileGuard:
    """Assert steady-state compile counts from XLA's own telemetry.

    ``backend_compile`` monitoring events are the ground truth the
    serving zero-recompile acceptance test pins (a Python-side cache
    counter can lie; the XLA event cannot).  Usage::

        def test_hot_path_is_compile_free(recompile_guard):
            f(x)                              # warmup compiles here
            with recompile_guard.expect(0):   # steady state
                for _ in range(100):
                    f(x)
    """

    def __init__(self):
        _ensure_listener()

    def count(self) -> int:
        """Total backend compiles observed process-wide so far."""
        return _compile_count

    def new_since(self, baseline: int) -> int:
        return _compile_count - baseline

    @contextmanager
    def expect(self, max_compiles: int = 0):
        """Fail if the region compiles more than ``max_compiles``
        XLA programs."""
        before = self.count()
        yield self
        new = self.count() - before
        if new > max_compiles:
            raise AssertionError(
                f"recompile_guard: region compiled {new} XLA program(s), "
                f"budget was {max_compiles} — a steady-state path is "
                "re-tracing (shape-varying args? Python scalars burned "
                "into the trace? see ANALYSIS.md XGT001)")


# ------------------------------------------------------------------- locks
@dataclasses.dataclass
class Violation:
    """One observed locking violation."""

    kind: str          # "unguarded-write" | "lock-order-inversion"
    detail: str
    thread: str
    stack: str

    def render(self) -> str:
        return (f"[{self.kind}] {self.detail} (thread {self.thread})\n"
                f"{self.stack}")


class InstrumentedLock:
    """Drop-in wrapper over a ``threading.Lock``/``RLock`` that reports
    acquire/release to its :class:`LockRaceChecker`."""

    def __init__(self, checker: "LockRaceChecker", name: str, inner=None):
        self._checker = checker
        self.name = name
        self._inner = inner if inner is not None else threading.Lock()

    def acquire(self, *args, **kwargs) -> bool:
        got = self._inner.acquire(*args, **kwargs)
        if got:
            self._checker._note_acquire(self.name)
        return got

    def release(self) -> None:
        self._checker._note_release(self.name)
        self._inner.release()

    def held_by_current_thread(self) -> bool:
        return self.name in self._checker._held()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False


class LockRaceChecker:
    """Instrumented-lock race/deadlock observer.

    :meth:`instrument` rewires one object: each named lock attribute is
    wrapped in an :class:`InstrumentedLock` (same underlying primitive,
    so real mutual exclusion is unchanged) and the object's class is
    subclassed with a ``__setattr__`` that records a violation whenever
    a guarded attribute is WRITTEN without any of the object's
    instrumented locks held.  Reads are not traced — the invariant this
    codebase documents (OBSERVABILITY.md, serving/) is writer-side
    locking with benign racy reads.

    Lock-order inversions are tracked globally across every lock the
    checker wrapped: first ``A then B`` on one path and ``B then A`` on
    another is recorded even though no single run deadlocks.
    """

    def __init__(self):
        self.violations: List[Violation] = []
        self._tls = threading.local()
        self._mu = threading.Lock()
        self._edges: Set[Tuple[str, str]] = set()
        self._inverted: Set[Tuple[str, str]] = set()
        self._n_instrumented = 0

    # ------------------------------------------------------------ held set
    def _held(self) -> List[str]:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    def _note_acquire(self, name: str) -> None:
        held = self._held()
        with self._mu:
            for h in held:
                if h == name:
                    continue
                self._edges.add((h, name))
                pair = tuple(sorted((h, name)))
                if (name, h) in self._edges and pair not in self._inverted:
                    self._inverted.add(pair)
                    self._record(
                        "lock-order-inversion",
                        f"{h} -> {name} here, but {name} -> {h} was "
                        "also observed — latent deadlock")
        held.append(name)

    def _note_release(self, name: str) -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):  # innermost acquisition
            if held[i] == name:
                del held[i]
                break

    def _record(self, kind: str, detail: str) -> None:
        stack = "".join(traceback.format_stack(limit=8)[:-2])
        self.violations.append(Violation(
            kind=kind, detail=detail,
            thread=threading.current_thread().name, stack=stack))

    # ---------------------------------------------------------- instrument
    def wrap_lock(self, name: str, inner=None) -> InstrumentedLock:
        """A standalone instrumented lock (for code that takes a lock
        as a dependency)."""
        return InstrumentedLock(self, name, inner)

    def instrument(self, obj, locks: Sequence[str],
                   guarded: Sequence[str]):
        """Instrument ``obj`` in place and return it.

        Args:
          locks: attribute names of the object's lock(s) to wrap
            (e.g. ``("_lock",)``).
          guarded: attribute names whose WRITES must happen with one of
            those locks held.
        """
        checker = self
        wrapped: Dict[str, InstrumentedLock] = {}
        with self._mu:
            self._n_instrumented += 1
            seq = self._n_instrumented
        for lock_attr in locks:
            inner = getattr(obj, lock_attr)
            # per-INSTANCE lock names: two instances of one class must
            # not satisfy each other's guard check (holding b1._lock
            # while writing b2.attr is exactly the race to catch)
            ilock = InstrumentedLock(
                self, f"{type(obj).__name__}#{seq}.{lock_attr}", inner)
            object.__setattr__(obj, lock_attr, ilock)
            wrapped[lock_attr] = ilock
        guarded_set = frozenset(guarded)
        base = type(obj)

        class _Watched(base):
            def __setattr__(self, key, value):
                if key in guarded_set and not any(
                        il.held_by_current_thread()
                        for il in wrapped.values()):
                    checker._record(
                        "unguarded-write",
                        f"{base.__name__}.{key} written without "
                        f"{'/'.join(il.name for il in wrapped.values())} "
                        "held")
                super().__setattr__(key, value)

        _Watched.__name__ = base.__name__ + "+lockcheck"
        _Watched.__qualname__ = _Watched.__name__
        obj.__class__ = _Watched
        return obj

    # -------------------------------------------------------------- report
    def assert_clean(self) -> None:
        if self.violations:
            report = "\n".join(v.render() for v in self.violations)
            raise AssertionError(
                f"LockRaceChecker: {len(self.violations)} violation(s)\n"
                + report)


# ---------------------------------------------------------------- donation
class DonationGuard:
    """Runtime use-after-donate detector (dynamic twin of XGT013).

    A backend may decline a ``donate_argnums`` donation (the pinned
    jax honours it on CPU as on TPU, and warns where it could not use
    one), and a suite can then pass while a donated dispatch reads
    freed memory elsewhere.  This guard makes every backend behave
    like one that reused the buffer: :meth:`wrap` returns a shim that,
    after each call completes, ``delete()``-s every jax-array leaf the
    caller passed at a donated position.  From then on any
    caller-side touch of that buffer raises JAX's own "Array has been
    deleted" — the runtime observation of exactly the reads XGT013
    flags statically.

    Two hazards are RECORDED rather than raised, so a multi-dispatch
    test collects everything and fails once via :meth:`assert_clean`:

    - ``donated-reuse``: an argument arriving at a donated position is
      already deleted — the caller re-passed a donated buffer instead
      of rebinding the carry (the loop form of use-after-donate);
    - ``non-donatable``: a donated position held a non-empty value
      with no deletable device array in it (donation silently
      pointless — e.g. a Python scalar burned into the trace).  An
      EMPTY pytree at a donated position is vacuously fine — gbtree
      donates ``tuple(eval_margins)`` unconditionally, and training
      without evals passes ``()`` there.

    Usage (the integration test drives the REAL fused dispatch)::

        guard = DonationGuard(donate_argnums=(1, 11))
        monkeypatch.setattr(gbtree, "_scan_rounds",
                            guard.wrap(gbtree._scan_rounds))
        ... run update_many ...
        assert guard.calls > 0
        guard.assert_clean()
    """

    def __init__(self, donate_argnums: Sequence[int]):
        self.donate_argnums = tuple(donate_argnums)
        self.calls = 0
        self.violations: List[Violation] = []

    def _record(self, kind: str, detail: str) -> None:
        stack = "".join(traceback.format_stack(limit=8)[:-2])
        self.violations.append(Violation(
            kind=kind, detail=detail,
            thread=threading.current_thread().name, stack=stack))

    @staticmethod
    def _array_leaves(value):
        import jax
        return [leaf for leaf in jax.tree_util.tree_leaves(value)
                if isinstance(leaf, jax.Array)]

    def wrap(self, fn):
        """``fn`` with device-faithful donation semantics appended."""
        import functools

        import jax

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            donated = []
            for i in self.donate_argnums:
                if i >= len(args):
                    continue
                leaves = self._array_leaves(args[i])
                if not leaves:
                    if jax.tree_util.tree_leaves(args[i]):
                        self._record(
                            "non-donatable",
                            f"donated position {i} of {fn.__name__} "
                            "holds no device array — donation is "
                            "silently a no-op there")
                    continue
                for leaf in leaves:
                    if leaf.is_deleted():
                        self._record(
                            "donated-reuse",
                            f"argument at donated position {i} of "
                            f"{fn.__name__} was ALREADY donated by an "
                            "earlier call — rebind the carry instead "
                            "of re-passing the dead buffer")
                    else:
                        donated.append(leaf)
            out = fn(*args, **kwargs)
            # the computation must have consumed its inputs before the
            # host frees them out from under an async dispatch
            jax.block_until_ready(out)
            for leaf in donated:
                leaf.delete()
            self.calls += 1
            return out

        return wrapper

    def assert_clean(self) -> None:
        if self.violations:
            report = "\n".join(v.render() for v in self.violations)
            raise AssertionError(
                f"DonationGuard: {len(self.violations)} violation(s)\n"
                + report)
