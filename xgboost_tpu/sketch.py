"""Weighted quantile sketch.

Re-implements the semantics of the reference's ``WQSummary`` /
``WQuantileSketch`` (reference ``src/utils/quantile.h:52-770``): bounded-size
weighted quantile summaries with associative ``merge`` (SetCombine,
``quantile.h:225-278``) and ``prune`` (SetPrune, ``quantile.h:189-219``),
plus the validity invariant of ``WQSummary::CheckValid``
(``quantile.h:165-173``).

This host-side (numpy) sketch is used to propose histogram cut points once
per training run (LightGBM-style global binning) — the TPU-native
replacement for the reference's per-round per-node sketches
(``updater_histmaker-inl.hpp:353-462``).  A fixed-size tensorized form of
the same summary (for on-device distributed merging over a mesh, replacing
rabit's ``SerializeReducer``) lives in ``parallel/sketch_device.py``.

The reference also ships a GK (Greenwald-Khanna, unweighted) sketch
(``quantile.h:383-525``) that nothing in its engine instantiates — the
weighted summary subsumes it (unweighted == all weights 1), so no
separate GK variant exists here.

Summary entries are (value, rmin, rmax, wmin):
  rmin = minimum possible rank of value  (sum of weights strictly below)
  rmax = maximum possible rank of value
  wmin = total weight of entries equal to value
Invariant: rmin + wmin <= rmax.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class QuantileSummary:
    """A weighted quantile summary (struct-of-arrays, sorted by value)."""

    value: np.ndarray  # (k,) float64
    rmin: np.ndarray   # (k,) float64
    rmax: np.ndarray   # (k,) float64
    wmin: np.ndarray   # (k,) float64

    @property
    def size(self) -> int:
        return len(self.value)

    @property
    def total_weight(self) -> float:
        return float(self.rmax[-1]) if self.size else 0.0

    # maximum rank error of this summary (reference WQSummary::MaxError)
    def max_error(self) -> float:
        if self.size == 0:
            return 0.0
        prev_rmax = np.concatenate([[0.0], self.rmax[:-1]])
        return float(np.max(np.maximum(
            self.rmin + self.wmin - prev_rmax,
            self.rmax - self.rmin - self.wmin)))

    def check_valid(self, eps: float = 1e-6) -> None:
        """Invariants of reference WQSummary::CheckValid (quantile.h:165-173)."""
        if self.size == 0:
            return
        assert np.all(self.rmin + self.wmin <= self.rmax + eps), "rmin+wmin > rmax"
        assert np.all(self.rmin >= -eps), "negative rmin"
        assert np.all(self.wmin >= -eps), "negative wmin"
        assert np.all(np.diff(self.value) > 0), "values not strictly increasing"
        assert np.all(np.diff(self.rmin) >= -eps), "rmin not monotone"
        assert np.all(np.diff(self.rmax) >= -eps), "rmax not monotone"

    # -- rank bounds helpers (reference Entry::RMinNext / RMaxPrev) --
    def _rmin_next(self) -> np.ndarray:
        return self.rmin + self.wmin

    def _rmax_prev(self) -> np.ndarray:
        return self.rmax - self.wmin


def empty_summary() -> QuantileSummary:
    z = np.zeros(0, dtype=np.float64)
    return QuantileSummary(z.copy(), z.copy(), z.copy(), z.copy())


def make_summary(values: np.ndarray, weights: np.ndarray | None = None) -> QuantileSummary:
    """Build an exact summary from raw weighted data (vectorized).

    Equivalent to pushing every element into the reference's
    WQuantileSketch and taking the unpruned summary.
    """
    if weights is None:
        # unweighted fast path: a plain value sort + run-length counts;
        # the general path's stable argsort + ufunc.at dominated
        # external-memory sketch ingest (~8x slower per column).
        # float32 input is sorted as it is and widened after: widening
        # is exact and monotone, so the sorted float64 array is the same
        values = np.asarray(values).ravel()
        if values.dtype != np.float32:
            values = values.astype(np.float64, copy=False)
        finite = np.isfinite(values)
        if not finite.all():
            values = values[finite]
        if values.size == 0:
            return empty_summary()
        v = np.sort(values).astype(np.float64, copy=False)
        edges = np.flatnonzero(
            np.concatenate([[True], v[1:] != v[:-1]]))
        gv = v[edges]
        gw = np.diff(np.concatenate(
            [edges, [v.size]])).astype(np.float64)
        rmax = np.cumsum(gw)
        return QuantileSummary(gv, rmax - gw, rmax, gw)
    values = np.asarray(values, dtype=np.float64).ravel()
    weights = np.asarray(weights, dtype=np.float64).ravel()
    mask = np.isfinite(values) & (weights > 0)
    values, weights = values[mask], weights[mask]
    if values.size == 0:
        return empty_summary()
    order = np.argsort(values, kind="stable")
    v, w = values[order], weights[order]
    # group duplicates
    boundary = np.concatenate([[True], v[1:] != v[:-1]])
    group_id = np.cumsum(boundary) - 1
    n_groups = group_id[-1] + 1
    gw = np.zeros(n_groups, dtype=np.float64)
    np.add.at(gw, group_id, w)
    gv = v[boundary]
    rmax = np.cumsum(gw)
    rmin = rmax - gw
    return QuantileSummary(gv, rmin, rmax, gw)


def merge_summaries(a: QuantileSummary, b: QuantileSummary) -> QuantileSummary:
    """Associative merge — semantics of WQSummary::SetCombine (quantile.h:225-278).

    Vectorized: for an entry of `a` at value v, its combined rank bounds add
    the rank bounds contributed by `b` at v: rmin += RMinNext of the last b
    entry with value < v; rmax += RMaxPrev of the first b entry with
    value > v (or b's total weight if none).  Entries with equal values
    combine directly.
    """
    if a.size == 0:
        return b
    if b.size == 0:
        return a

    def contrib(x: QuantileSummary, other: QuantileSummary):
        # index of first other-entry with value >= x.value
        lo = np.searchsorted(other.value, x.value, side="left")
        # index of first other-entry with value > x.value
        hi = np.searchsorted(other.value, x.value, side="right")
        exact = hi > lo  # other has an entry exactly at x.value
        rmin_next = np.concatenate([[0.0], other._rmin_next()])
        rmax_prev = np.concatenate([other._rmax_prev(),
                                    [other.total_weight]])
        add_rmin = np.where(exact, other.rmin[np.minimum(lo, other.size - 1)],
                            rmin_next[lo])
        add_rmax = np.where(exact, other.rmax[np.minimum(lo, other.size - 1)],
                            rmax_prev[hi])
        add_wmin = np.where(exact, other.wmin[np.minimum(lo, other.size - 1)], 0.0)
        return add_rmin, add_rmax, add_wmin

    a_rmin, a_rmax, a_wmin = contrib(a, b)
    b_rmin, b_rmax, b_wmin = contrib(b, a)

    allv = np.concatenate([a.value, b.value])
    allrmin = np.concatenate([a.rmin + a_rmin, b.rmin + b_rmin])
    allrmax = np.concatenate([a.rmax + a_rmax, b.rmax + b_rmax])
    allwmin = np.concatenate([a.wmin + a_wmin, b.wmin + b_wmin])
    order = np.argsort(allv, kind="stable")
    allv, allrmin, allrmax, allwmin = (allv[order], allrmin[order],
                                       allrmax[order], allwmin[order])
    # deduplicate equal values (each side already contains the other's mass)
    keep = np.concatenate([[True], allv[1:] != allv[:-1]])
    return QuantileSummary(allv[keep], allrmin[keep], allrmax[keep], allwmin[keep])


def prune_summary(s: QuantileSummary, maxsize: int) -> QuantileSummary:
    """Prune to <= maxsize entries — semantics of WQSummary::SetPrune
    (quantile.h:189-219): always keep the extreme entries; select interior
    entries nearest to evenly spaced ranks, using the (RMinNext, RMaxPrev)
    straddle test to bound rank error.
    """
    if s.size <= maxsize or maxsize < 2:
        return s
    begin = s.rmax[0]
    rng = s.rmin[-1] - begin
    n = maxsize - 2
    k = np.arange(1, n)
    dx2 = 2.0 * (k * rng / n + begin)
    mid = s.rmin + s.rmax  # 2 * midpoint rank of each entry
    # i(k): last entry with  mid[i+1] <= dx2  (scan pointer of the reference)
    i = np.searchsorted(mid, dx2, side="right") - 1
    i = np.clip(i, 0, s.size - 2)
    # choose entry i or i+1 by the straddle test
    rmin_next = s._rmin_next()
    rmax_prev = s._rmax_prev()
    use_i = dx2 < rmin_next[i] + rmax_prev[np.minimum(i + 1, s.size - 1)]
    sel = np.where(use_i, i, i + 1)
    sel = np.concatenate([[0], sel, [s.size - 1]])
    sel = np.unique(sel)
    return QuantileSummary(s.value[sel], s.rmin[sel], s.rmax[sel], s.wmin[sel])


def sketch_column(values: np.ndarray, weights: np.ndarray | None,
                  eps: float, sketch_ratio: float = 2.0,
                  chunk: int = 1 << 22) -> QuantileSummary:
    """Sketch one feature column to a bounded-size summary.

    max summary size = sketch_ratio / eps, mirroring
    TrainParam::max_sketch_size (reference ``src/tree/param.h:170-175``).
    Large inputs are processed in chunks and merged+pruned pairwise — the
    multi-level merge of the reference's quantile sketch engine
    (``quantile.h:621-709``) collapsed into a flat fold, which preserves
    the rank-error bound because merge is associative and prune is applied
    at bounded size.
    """
    maxsize = max(2, int(sketch_ratio / eps))
    values = np.asarray(values).ravel()
    if weights is not None:
        weights = np.asarray(weights)
    acc = empty_summary()
    for start in range(0, max(len(values), 1), chunk):
        # weights=None stays None: make_summary's unweighted branch
        # gives the summary its weighted one gives for all-one weights
        part = make_summary(values[start:start + chunk],
                            None if weights is None
                            else weights[start:start + chunk])
        part = prune_summary(part, maxsize)
        acc = prune_summary(merge_summaries(acc, part), maxsize)
    return acc


def query_quantile(s: QuantileSummary, rank: float) -> float:
    """Value whose rank interval is closest to `rank` (reference
    WQSummary::Query semantics, used for cut proposal)."""
    if s.size == 0:
        return 0.0
    mid = (s.rmin + s.rmax) * 0.5
    idx = int(np.argmin(np.abs(mid - rank)))
    return float(s.value[idx])


def propose_cuts(s: QuantileSummary, max_bin: int) -> np.ndarray:
    """Propose up to max_bin-1 strictly increasing cut values from a summary.

    The TPU binning scheme: a value v maps to bin 1+searchsorted(cuts, v,
    'right') (bin 0 is reserved for missing); a split at cut index j means
    "go left iff v < cuts[j]" — matching the reference's split condition
    semantics (``src/tree/model.h:555-566``).
    """
    if s.size == 0:
        return np.zeros(0, dtype=np.float32)
    total = s.total_weight
    n_cut = max_bin - 1
    if s.size <= n_cut:
        # few distinct values: every distinct value is a cut.  The cut AT the
        # minimum matters for sparse/one-hot features: "v < min" routes all
        # present values right while missing follows the learned default —
        # the split shape the reference finds on agaricus-style indicator
        # features (colmaker's missing-default enumeration,
        # updater_colmaker-inl.hpp:362-414).
        return np.unique(s.value.astype(np.float32))
    ranks = np.arange(1, n_cut + 1) * (total / (n_cut + 1))
    mid = (s.rmin + s.rmax) * 0.5
    idx = np.searchsorted(mid, ranks, side="left")
    idx = np.clip(idx, 1, s.size - 1)  # never cut below the min value
    cuts = np.unique(s.value[idx]).astype(np.float32)
    return cuts
