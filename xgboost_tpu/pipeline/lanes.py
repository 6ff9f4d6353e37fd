"""Gang-batched tenant lanes — N same-shape boosters, ONE dispatch.

The catalog trains thousands of small per-tenant models; the host-loop
``run_tenant_lanes`` gives each its own thread and its own device
dispatch stream, so lane count costs dispatches (ROADMAP: "the biggest
single lever for millions-of-users economics").  This module is the
training twin of the serving engine's power-of-two shape buckets: a
packer groups tenant lanes by their fused-scan compilation shape, pads
each bucket to a power-of-two stack width, and vmaps the whole bucket
through one ``_scan_rounds_lanes`` dispatch
(:func:`xgboost_tpu.models.gbtree._scan_rounds_lanes_impl`) — K rounds
for L tenants in a single device launch.

Contracts:

- **Bit-identity.**  A stacked lane's model bytes equal its solo run's,
  byte for byte (tests/test_lanes.py pins N ∈ {2, 8, 64}).  Each lane
  keeps its OWN ``PRNGKey(seed)`` (seeds derive from the lane NAME, not
  the stack index — ``run_pipeline``'s per-lane seed rule), its own
  dynamic ``first_iteration``, and its own label/margin slots; row pads
  ride at ``row_valid=False`` / ``pos = -1`` (the histogram kernel's
  inactive-row convention) and therefore never touch a neighbor's sums.
  A tenant joining or leaving a bucket changes ONLY the stack width.
- **Pad-lane semantics.**  A bucket of L real lanes pads to the next
  power of two with inactive lanes (lane 0's bins, all-False
  ``row_valid``, zero gradients): they grow degenerate zero trees the
  host discards.  Padding bounds compile count — tenants churn, the
  compiled program does not.
- **Per-tenant isolation.**  Only the boosting rounds stack; gate,
  publish, ledger, quarantine and checkpoints stay host-side per lane
  (zero-ungated-served holds PER TENANT).  A lane whose unpack or
  checkpoint callback raises keeps its error to itself.  A failure of
  the stacked dispatch itself (the scan refusing to compile or run) is
  NOT a tenant fault: every lane of the bucket fails with a
  :class:`~xgboost_tpu.pipeline.trainer.FatalCycleError`
  (``lanes.stack_error`` event) and the run reports them as errors —
  re-running them solo would end ``status: ok`` with the stacked
  program never having worked.
- **When the host loop still wins.**  Heterogeneous shapes (every lane
  its own bucket), ``subsample < 1`` with unequal row counts (N-shaped
  RNG draws forbid row padding), or one huge tenant dominating the
  stack: set ``XGBTPU_LANE_STACK=0`` for the A/B baseline.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from xgboost_tpu.obs import event, lane_metrics, span
from xgboost_tpu.pipeline.trainer import (ContinuousTrainer,
                                          FatalCycleError)

__all__ = ["LaneGang", "GangTrainer", "run_tenant_lanes_stacked"]


def _pow2_at_least(n: int, floor: int = 1) -> int:
    p = max(1, int(floor))
    while p < n:
        p *= 2
    return p


def _bucket_of(spec):
    """Shape-bucket key: everything that shapes the stacked scan's
    compiled program.  Static identities (cfg, split finder, gradient
    fn, pred_chunk) come straight from the LaneSpec — they are the jit
    static args of the scan itself, so key-equal lanes by construction
    compile (and cache) ONE program.  Rows pad to a power of two only
    when ``subsample == 1.0``: the subsample Bernoulli draw is N-shaped,
    so padded rows would shift a solo run's draws (bit-identity is the
    contract; exact-N buckets still stack equal-sized tenants)."""
    if spec.subsample >= 1.0:
        n_key = _pow2_at_least(spec.n_rows, 64)
    else:
        n_key = spec.n_rows
    w_key = _pow2_at_least(int(spec.cut_values.shape[1]), 8)
    return (n_key, spec.n_features, w_key, str(spec.binned.dtype),
            spec.K, spec.npar, spec.n_rounds, spec.seg_k, spec.cfg,
            spec.split_finder, spec.grad_fn, spec.pred_chunk)


def _pad_rows(x, n_pad: int, fill=0):
    """End-pad axis 0 to ``n_pad`` rows (identity when already there)."""
    n = x.shape[0]
    if n == n_pad:
        return x
    widths = [(0, n_pad - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


class _Arrival:
    """One lane's pending boost request at the gang rendezvous."""

    def __init__(self, name: str, spec, segment_callback):
        self.name = name
        self.spec = spec
        self.segment_callback = segment_callback
        self.done = False
        self.exc: Optional[BaseException] = None


class LaneGang:
    """Rendezvous + dispatcher for gang-batched lane training.

    Lanes call :meth:`boost` once per training cycle.  Arrivals collect
    until every registered lane is present or ``window_sec`` has passed
    since the first arrival, then ONE lane thread becomes the
    dispatcher: it groups arrivals into shape buckets, pads each bucket
    to a power-of-two stack width, and advances every bucket segment by
    segment through the lane-stacked scan.  Late lanes simply form the
    next batch — batch composition never changes any lane's bytes (see
    the module contract), only how much dispatch cost is shared.

    Lanes that finish (or error out) call :meth:`resign` so the
    rendezvous stops waiting for them; a lane whose spec is ineligible
    for stacking resigns implicitly and keeps its solo dispatch stream.
    """

    def __init__(self, expected: int, window_sec: float = 0.2):
        self._cv = threading.Condition()
        self._expected = int(expected)
        self._window = float(window_sec)
        self._arrivals: Dict[str, _Arrival] = {}
        self._t0: Optional[float] = None
        self._dispatching = False
        # steady-bucket carry: bucket key -> (identity tokens, strong
        # refs pinning those identities, stacked device columns, carried
        # margin stack).  When the same lanes re-arrive with the same
        # operand OBJECTS (static data, cached base key, the margin
        # views we handed back last dispatch), re-stacking is skipped
        # entirely and the scan consumes its own previous margin output
        # — the host cost of a steady cycle is one int stack plus the
        # dispatch itself.  Any identity change rebuilds the bucket
        # (counted by xgbtpu_lane_restack_total).
        self._carry: Dict[tuple, tuple] = {}

    # ------------------------------------------------------------ members
    def resign(self, name: str) -> None:
        """This lane will not arrive again (finished, errored, or
        permanently ineligible) — stop holding the rendezvous for it."""
        with self._cv:
            self._expected = max(0, self._expected - 1)
            self._cv.notify_all()

    # -------------------------------------------------------------- boost
    def boost(self, name: str, bst, dtrain, it0: int, n_rounds: int,
              segment_callback) -> None:
        """Advance one lane ``n_rounds`` rounds — stacked with whatever
        bucket peers rendezvous with it, or solo (loudly) when
        ineligible.  Blocks until the lane's rounds are fully absorbed
        (same contract as ``Booster.update_many``)."""
        spec, why = bst.fused_lane_spec(dtrain, it0, n_rounds)
        if spec is None:
            lane_metrics().solo.inc(why)
            event("lanes.solo", lane=name, reason=why)
            self.resign(name)  # permanent: eligibility is config-shaped
            bst.update_many(dtrain, it0, n_rounds,
                            segment_callback=segment_callback)
            return
        arr = _Arrival(name, spec, segment_callback)
        batch = None
        with self._cv:
            self._arrivals[name] = arr
            if self._t0 is None:
                self._t0 = time.monotonic()
            self._cv.notify_all()
            while not arr.done:
                full = len(self._arrivals) >= self._expected
                waited = (time.monotonic() - self._t0
                          if self._t0 is not None else 0.0)
                if ((full or waited >= self._window)
                        and not self._dispatching and not arr.done):
                    self._dispatching = True
                    batch = list(self._arrivals.values())
                    self._arrivals.clear()
                    self._t0 = None
                    break
                self._cv.wait(timeout=max(0.01, self._window / 4.0))
        if batch is not None:
            try:
                self._dispatch(batch)
            finally:
                with self._cv:
                    self._dispatching = False
                    for a in batch:
                        a.done = True
                    self._cv.notify_all()
        if arr.exc is not None:
            raise arr.exc

    # ----------------------------------------------------------- dispatch
    def _dispatch(self, batch: List[_Arrival]) -> None:
        buckets: Dict[tuple, List[_Arrival]] = {}
        for arr in batch:
            buckets.setdefault(_bucket_of(arr.spec), []).append(arr)
        lane_metrics().buckets.set(float(len(buckets)))
        for key, arrs in buckets.items():
            # deterministic lane order inside the stack (order cannot
            # change bytes — this only keeps dispatch logs stable)
            arrs.sort(key=lambda a: a.name)
            try:
                self._dispatch_bucket(key, arrs)
            except Exception as e:
                # the stacked program failed, not a tenant: fail every
                # lane of the bucket (other buckets still dispatch)
                event("lanes.stack_error", lanes=[a.name for a in arrs],
                      error=f"{type(e).__name__}: {e}")
                for arr in arrs:
                    arr.exc = FatalCycleError(
                        f"stacked dispatch failed: "
                        f"{type(e).__name__}: {e}")
                    arr.exc.__cause__ = e

    def _dispatch_bucket(self, key, arrs: List[_Arrival]) -> None:
        from xgboost_tpu.models.gbtree import (_scan_rounds_lanes,
                                               _unstack_lane_flats)
        n_pad, n_feat, w_pad = key[0], key[1], key[2]
        specs = [a.spec for a in arrs]
        s0 = specs[0]
        L_real = len(specs)
        L = _pow2_at_least(L_real)
        lm = lane_metrics()

        # steady-bucket carry: identical lane OBJECTS re-arriving means
        # the stacked columns are already on device and the carried
        # margin stack IS last dispatch's output (the views we handed
        # each lane are slices of its host copy).  Identity (not value)
        # comparison keeps this exact; the refs stored below pin every
        # tokenized object so a recycled id can never alias.
        tokens = tuple(
            (a.name, id(s.binned), id(s.label), id(s.weight),
             id(s.base_key), id(s.cut_values), id(s.n_cuts),
             None if s.row_valid is None else id(s.row_valid),
             id(s.margin))
            for a, s in zip(arrs, specs))
        carry = self._carry.get(key)
        if carry is not None and carry[0] == tokens:
            (binned_s, label_s, weight_s, key_s, cut_s, ncut_s,
             rv_s) = carry[2]
            margin_s = carry[3]
        else:
            lm.restacks.inc()

            def rows(x, fill=0):
                a = np.asarray(x)
                if a.shape[0] == n_pad:
                    return a
                w = [(0, n_pad - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
                return np.pad(a, w, constant_values=fill)

            def cuts(s):
                c = np.asarray(s.cut_values)
                if c.shape[1] < w_pad:
                    # +inf pad columns are inert: thresholds only read
                    # cut_values[f, i] at i < n_cuts[f] <= real width
                    c = np.pad(c, ((0, 0), (0, w_pad - c.shape[1])),
                               constant_values=np.inf)
                return c

            def valid(s):
                if s.row_valid is None:
                    return rows(np.ones(s.n_rows, np.bool_), fill=False)
                return rows(s.row_valid, fill=False)

            # stack host-side in numpy: ONE device put per column
            # instead of ~9 pad/stack dispatches per lane
            bcol = [rows(s.binned) for s in specs]
            mcol = [rows(s.margin) for s in specs]
            lcol = [rows(s.label) for s in specs]
            wcol = [rows(s.weight) for s in specs]
            kcol = [s.base_key for s in specs]
            ccol = [cuts(s) for s in specs]
            ncol = [np.asarray(s.n_cuts) for s in specs]
            rcol = [valid(s) for s in specs]
            if L > L_real:
                # inactive pad lanes: lane 0's bins/cuts (valid values,
                # zero cost after the stack copies either way), all rows
                # masked out — they grow degenerate zero trees the host
                # discards
                pads = L - L_real
                bcol += [bcol[0]] * pads
                mcol += [np.zeros_like(mcol[0])] * pads
                lcol += [np.zeros_like(lcol[0])] * pads
                wcol += [np.zeros_like(wcol[0])] * pads
                kcol += [jax.random.PRNGKey(0)] * pads
                ccol += [ccol[0]] * pads
                ncol += [ncol[0]] * pads
                rcol += [np.zeros(n_pad, np.bool_)] * pads
            binned_s = jnp.asarray(np.stack(bcol))
            margin_s = jnp.asarray(np.stack(mcol))
            label_s = jnp.asarray(np.stack(lcol))
            weight_s = jnp.asarray(np.stack(wcol))
            key_s = jnp.stack(kcol)  # keys may be typed: stack on device
            cut_s = jnp.asarray(np.stack(ccol))
            ncut_s = jnp.asarray(np.stack(ncol))
            rv_s = jnp.asarray(np.stack(rcol))

        first_s = jnp.asarray(np.asarray(
            [s.first_iteration for s in specs] + [0] * (L - L_real),
            np.int32))
        n_rounds, seg_k = s0.n_rounds, s0.seg_k
        done = 0
        views: List[Optional[np.ndarray]] = [None] * L_real
        while done < n_rounds:
            seg = min(seg_k, n_rounds - done)
            with span("lanes.dispatch", lanes=L_real, width=L,
                      n_rounds=seg, bucket_rows=n_pad):
                t0 = time.perf_counter()
                margin_s, stacks = _scan_rounds_lanes(
                    binned_s, margin_s, label_s, weight_s, key_s,
                    first_s + done, cut_s, ncut_s, rv_s,
                    n_rounds=seg, K=s0.K, npar=s0.npar, cfg=s0.cfg,
                    split_finder=s0.split_finder, grad_fn=s0.grad_fn,
                    pred_chunk=s0.pred_chunk)
                # block at the segment boundary: per-lane checkpoint
                # callbacks pull model bytes from this dispatch next,
                # and the histogram must record device wall time
                jax.block_until_ready(margin_s)
                dt = time.perf_counter() - t0
            lm.dispatches.inc()
            lm.dispatch_seconds.observe(dt)
            lm.stack_width.set(float(L))
            lm.stacked.inc(float(L_real))
            lm.padded.inc(float(L - L_real))
            # slice the lane axis in ONE launch, then per-tenant absorb;
            # margins fan out as views of ONE host copy (per-lane device
            # slicing would be a dispatch per lane per segment)
            lane_stacks = _unstack_lane_flats(stacks, L)
            margin_np = np.asarray(margin_s)  # xgtpu: disable=XGT002 — ONE batched pull per segment for ALL lanes
            for i, arr in enumerate(arrs):
                if arr.exc is not None:
                    continue  # this lane failed an earlier segment
                try:
                    spec = arr.spec
                    views[i] = margin_np[i, :spec.n_rows]
                    spec.booster.absorb_lane_segment(
                        spec, lane_stacks[i], views[i], seg)
                    arr.segment_callback(
                        spec.first_iteration + done + seg - 1)
                except Exception as e:  # isolation: keep it in-lane
                    arr.exc = e
            done += seg
        if all(a.exc is None for a in arrs):
            tokens_next = tuple(
                (a.name, id(s.binned), id(s.label), id(s.weight),
                 id(s.base_key), id(s.cut_values), id(s.n_cuts),
                 None if s.row_valid is None else id(s.row_valid),
                 id(views[i]))
                for i, (a, s) in enumerate(zip(arrs, specs)))
            self._carry[key] = (
                tokens_next,
                (specs, views),  # pin tokenized objects (id-reuse guard)
                (binned_s, label_s, weight_s, key_s, cut_s, ncut_s,
                 rv_s),
                margin_s)
        else:
            self._carry.pop(key, None)


class GangTrainer(ContinuousTrainer):
    """A :class:`ContinuousTrainer` whose boosting rounds route through
    a shared :class:`LaneGang` — everything else (resume, gate, publish,
    ledger) is the per-tenant base behavior, untouched."""

    def __init__(self, *args, gang: Optional[LaneGang] = None, **kw):
        super().__init__(*args, **kw)
        self._gang = gang

    def _boost_rounds(self, bst, dtrain, it0: int, n_rounds: int,
                      segment_callback) -> None:
        if self._gang is None:
            super()._boost_rounds(bst, dtrain, it0, n_rounds,
                                  segment_callback)
            return
        self._gang.boost(self.lane or self.publish_path, bst, dtrain,
                         it0, n_rounds, segment_callback)


def run_tenant_lanes_stacked(lanes: dict, quiet: bool = False,
                             window_sec: float = 0.2,
                             max_workers: Optional[int] = None) -> dict:
    """Stacked execution mode of
    :func:`xgboost_tpu.pipeline.run_tenant_lanes`: one thread per lane
    for the host-side phases (threads idle at the gang rendezvous while
    the device works), boosting rounds gang-batched through a shared
    :class:`LaneGang`.  Same call/return shape as the host loop."""
    import functools

    from xgboost_tpu.pipeline import run_pipeline

    gang = LaneGang(expected=len(lanes), window_sec=window_sec)
    results: dict = {}
    rlock = threading.Lock()
    names = list(lanes)
    if max_workers is None:
        max_workers = len(lanes)
    max_workers = max(1, min(int(max_workers), len(lanes))) if lanes else 0

    def _one(name: str, kw: dict) -> None:
        kw = dict(kw)
        kw.setdefault("lane", name)
        kw.setdefault("quiet", quiet)
        try:
            summary = run_pipeline(
                trainer_cls=functools.partial(GangTrainer, gang=gang),
                **kw)
            with rlock:
                results[name] = {"status": "ok", "summary": summary}
        except Exception as e:  # lane isolation: never kill siblings
            with rlock:
                results[name] = {"status": "error",
                                 "error": f"{type(e).__name__}: {e}"}
            event("pipeline.lane_error", lane=name,
                  error=f"{type(e).__name__}: {e}")
        finally:
            gang.resign(name)

    pending = list(names)
    plock = threading.Lock()

    def _worker() -> None:
        while True:
            with plock:
                if not pending:
                    return
                name = pending.pop(0)
            _one(name, lanes[name])

    threads = [threading.Thread(target=_worker, name=f"lane-worker-{i}",
                                daemon=True)
               for i in range(max_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results
