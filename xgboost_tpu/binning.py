"""Quantize a DMatrix into dense bin-id device arrays.

This is the TPU-native representational shift (SURVEY.md §7): instead of
the reference's CSR/CSC sorted-column scans
(``src/tree/updater_colmaker-inl.hpp:362-414``), data is quantized ONCE
per training run using the weighted quantile sketch and stored as a dense
``(n_rows, n_features)`` array of small-int bin ids in HBM.  All tree
growth then operates on bins (histogram method — the reference's own
scalable path, ``learner-inl.hpp:91-97``).

Binning scheme:
  - bin 0 is reserved for MISSING (absent CSR entries — the reference's
    missing-value semantics with learned default direction,
    ``model.h:555-566``).
  - a present value v maps to bin ``1 + searchsorted(cuts_f, v, 'right')``.
  - a split at cut index j of feature f sends rows left iff ``v < cuts_f[j]``
    ⇔ ``bin(v) <= j + 1``; missing rows follow the learned default.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

from xgboost_tpu.data import DMatrix
from xgboost_tpu.sketch import (QuantileSummary, make_summary, prune_summary,
                                propose_cuts, sketch_column)

# Auto bin alignment trims at most this many cuts (the measured win is
# landing on the sublane multiple just BELOW the proposed count; see
# align_cut_lists).  Single source of truth — the learner and
# compute_cuts both defer to this default.
DEFAULT_TRIM_MARGIN = 4

# A matrix that still holds the ndarray it was built from
# (``DMatrix.dense_source``) is read column by column (cut proposal)
# and row block by row block (bin ids) from that array: no CSR, no
# column cache.  Columns and blocks are independent and ``np.sort`` /
# ``np.searchsorted`` release the GIL, so they map over this many
# threads; the result is the serial loop's, byte for byte.
_THREADS = max(1, min(8, (os.cpu_count() or 2) - 1))
_BIN_BLOCK = 1 << 18    # rows of one binning task, at most, and
_BIN_CELLS = 1 << 25    # its cells: 2^18 rows up to 128 columns,
#                         16,777 at 2,000 (where 2^18 rows were two
#                         tasks for 400,000 rows).  Not fewer: a task
#                         bins column by column, and a column's
#                         searchsorted has to dwarf the interpreter's
#                         work between two of them or the threads queue
#                         on the GIL (3,670 rows x 2,000 on 7 threads:
#                         slower than one thread)
_LINE = 16              # float32 cells of a cache line: the columns of
#                         one cut-proposal task of a wide matrix
_GATHER_ROWS = 1 << 10  # rows of one gather step of such a task
_SKETCH_MIN = 1 << 16   # longer columns go through sketch_column


def _dense_source(dmat) -> Optional[tuple]:
    """``(arr, missing)`` of a matrix that holds its dense source, None
    for every other kind (CSR tuple, scipy, file, external, sharded)."""
    get = getattr(dmat, "dense_source", None)
    return None if get is None else get()


def holds_dense(dmat) -> int:
    """1 where ``compute_cuts`` / ``bin_matrix`` read ``dmat``'s dense
    source, 0 where they read CSR: the ``dense`` attribute of the
    ``ingest.cuts`` and ``ingest.bin`` spans."""
    return int(_dense_source(dmat) is not None)


def _block_rows(width: int) -> int:
    """Rows of one binning task of a matrix ``width`` columns wide."""
    return max(1, min(_BIN_BLOCK, _BIN_CELLS // max(width, 1)))


def _column_group(n_col: int) -> int:
    """Columns of one cut-proposal task: one, until there are columns
    enough to keep every thread in tasks of a cache line's worth."""
    return max(1, min(_LINE, n_col // (4 * _THREADS)))


def _map(fn, tasks) -> list:
    tasks = list(tasks)
    if _THREADS == 1 or len(tasks) < 2:
        return [fn(t) for t in tasks]
    with ThreadPoolExecutor(min(_THREADS, len(tasks))) as pool:
        return list(pool.map(fn, tasks))


def _dense_columns(arr: np.ndarray, missing: float, f0: int, f1: int):
    """Columns ``f0 .. f1-1`` of a dense source, each less its missing
    cells and in row order: what ``column_values(f)`` returns of the CSR
    built from it.  A group of columns is gathered in ONE pass over the
    rows, a block of rows at a time so that the block's cache lines are
    read once for all of them (a row's line holds 16 neighbouring
    cells: gathered one by one, each of 2,000 columns walks all N lines
    at a stride of 8 KB)."""
    n, width = arr.shape[0], max(0, min(f1, arr.shape[1]) - f0)
    if width == 1:
        got = np.ascontiguousarray(arr[:, f0])[None]
    else:
        got = np.empty((width, n), arr.dtype)
        for r in range(0, n, _GATHER_ROWS):
            got[:, r:r + _GATHER_ROWS] = arr[r:r + _GATHER_ROWS,
                                             f0:f0 + width].T
    for f in range(f0, f1):
        if f - f0 >= width:     # num_col= wider than the array
            yield np.zeros(0, np.float32)
            continue
        col = got[f - f0]
        present = ~np.isnan(col) if np.isnan(missing) else col != missing
        yield col if present.all() else col[present]


@dataclasses.dataclass
class CutMatrix:
    """Per-feature cut points, padded to a rectangle for device use.

    cut_values[f, j] for j < n_cuts[f] are strictly increasing; padding is
    +inf (so searchsorted against the padded row is still correct).
    """

    cut_values: np.ndarray  # (F, max_cuts) float32, +inf padded
    n_cuts: np.ndarray      # (F,) int32

    @property
    def num_feature(self) -> int:
        return self.cut_values.shape[0]

    @property
    def max_bin(self) -> int:
        # value bins 1..max_cuts+1 plus missing bin 0
        return self.cut_values.shape[1] + 2


def compute_cuts(dmat: DMatrix, max_bin: int = 256, sketch_eps: float = 0.03,
                 sketch_ratio: float = 2.0,
                 hess_weights: Optional[np.ndarray] = None,
                 bin_align: int = 0,
                 bin_align_margin: Optional[int] = DEFAULT_TRIM_MARGIN
                 ) -> CutMatrix:
    """Propose cut points for every feature via the weighted quantile sketch.

    Replaces the reference's per-round distributed sketch + cut proposal
    (``updater_histmaker-inl.hpp:353-462``) with one global pass; the
    summary machinery (merge/prune bounds) is identical.  ``bin_align``
    (learner-selected on TPU) aligns the bin count for the int8
    histogram kernel — see :func:`align_cut_lists`.
    """
    # dense input with no per-row weights never leaves its array
    src = None if hess_weights is not None else _dense_source(dmat)
    small = max(2, int(sketch_ratio / max(sketch_eps, 1.0 / max_bin)))

    def cuts_of(vals, w=None) -> np.ndarray:
        if len(vals) > _SKETCH_MIN:
            summary = sketch_column(vals, w, sketch_eps, sketch_ratio)
        else:
            summary = prune_summary(make_summary(vals, w), small)
        return propose_cuts(summary, max_bin - 1)  # room for missing bin

    def column_cuts(f: int) -> np.ndarray:
        rows, vals = dmat.column_values(f)
        return cuts_of(vals,
                       None if hess_weights is None else hess_weights[rows])

    def group_cuts(f0: int) -> list:
        return [cuts_of(v) for v in _dense_columns(*src, f0, min(f0 + group, F))]

    F = dmat.num_col
    if src is None:
        per_feature = [column_cuts(f) for f in range(F)]
    else:
        group = _column_group(F)
        tasks = range(0, F, group)
        groups = (_map(group_cuts, tasks) if src[0].shape[0] > _SKETCH_MIN
                  else [group_cuts(f0) for f0 in tasks])
        per_feature = [c for g in groups for c in g]
    return pack_cuts(align_cut_lists(per_feature, bin_align,
                                     bin_align_margin))


def align_cut_lists(per_feature, quantum: int = 32,
                    trim_margin: Optional[int] = DEFAULT_TRIM_MARGIN):
    """Trim the densest features' cut lists so the total bin count
    ``max_cuts + 2`` lands on a multiple of ``quantum``.

    The int8 MXU histogram kernel's one-hot operand tiles sublanes in
    32s: B = 67 bins occupy 96 physical sublanes, B = 64 occupy 64 —
    a measured ~19% round-rate difference at the bench shape for a
    3-cut resolution change (tools/hist_r5_ab.py; higgs-1M AUC is
    unchanged at the bench's precision).  Trimmed features keep evenly
    rank-spaced cuts (quantile-uniform coverage).  No-op when quantum
    is 0, when already aligned, or when the aligned count would drop
    below 8 cuts.

    ``trim_margin`` caps how many cuts may be trimmed: the win only
    exists when B sits just ABOVE a sublane multiple (67 -> 64 frees a
    whole 32-sublane tier for 3 cuts of resolution).  B = 63 is 31
    above the lower multiple — trimming to 32 would halve histogram
    resolution to save a single padded sublane, so alignment is
    skipped and the kernel pads instead (advisor finding, round 4).
    ``trim_margin=None`` removes the cap (explicit hist_bin_align>0
    opts into unconditional alignment).
    """
    if quantum <= 0 or not per_feature:
        return per_feature
    B = max((len(c) for c in per_feature), default=1) + 2
    excess = B % quantum
    if excess == 0:
        return per_feature
    if trim_margin is not None and excess > trim_margin:
        # Keeping all cuts costs quantum - excess padded sublanes in the
        # kernel — cheaper than losing `excess` cuts of resolution.
        return per_feature
    target = (B // quantum) * quantum - 2    # cuts so B % quantum == 0
    if target < 8:
        return per_feature
    out = []
    for cuts in per_feature:
        if len(cuts) > target:
            idx = np.unique(np.round(
                np.linspace(0, len(cuts) - 1, target)).astype(np.int64))
            cuts = np.asarray(cuts)[idx]
        out.append(cuts)
    return out


def _rank0() -> bool:
    """Rank-gate library-level warnings (the CLI silences rank != 0)."""
    try:
        import jax
        return jax.process_index() == 0
    except Exception as e:
        # no backend yet (or none at all): act as rank 0 so the warning
        # still prints somewhere; the probe failure itself is counted
        from xgboost_tpu.obs.metrics import swallowed_error
        swallowed_error("binning.rank0_probe", e, emit_event=False)
        return True


def pack_cuts(per_feature) -> CutMatrix:
    """Pack per-feature cut lists into an inf-padded rectangular CutMatrix."""
    F = len(per_feature)
    max_cuts = max(1, max((len(c) for c in per_feature), default=1))
    cut_values = np.full((F, max_cuts), np.inf, dtype=np.float32)
    n_cuts = np.zeros(F, dtype=np.int32)
    for f, cuts in enumerate(per_feature):
        cut_values[f, :len(cuts)] = cuts
        n_cuts[f] = len(cuts)
    return CutMatrix(cut_values, n_cuts)


def compute_cuts_exact(dmat: DMatrix, max_exact_bin: int = 4096) -> CutMatrix:
    """Cuts at EVERY distinct feature value — exact greedy as quantization.

    Enumerating a split before each distinct value is the same candidate
    set as the reference's sorted-column forward scan
    (``updater_colmaker-inl.hpp:362-414``); the sequential scan itself
    does not vectorize, but with cuts at all distinct values the
    histogram updater enumerates the identical partitions (only the
    recorded threshold differs: the reference stores a midpoint, we store
    the distinct value).  Features with more than ``max_exact_bin``
    distinct values fall back to that many quantile cuts.
    """
    F = dmat.num_col
    per_feature = []
    n_capped = 0
    for f in range(F):
        _, vals = dmat.column_values(f)
        uniq = np.unique(vals)
        if len(uniq) > max_exact_bin:
            n_capped += 1
            cuts = propose_cuts(
                prune_summary(make_summary(vals), 2 * max_exact_bin),
                max_exact_bin)
        else:
            # every distinct value is a cut, INCLUDING the minimum: the
            # "v < min" split separates nothing among present values but
            # with the learned default direction it is the
            # missing-vs-present split — essential for sparse indicator
            # features (all-ones columns in libsvm one-hot data)
            cuts = uniq.astype(np.float32)
        per_feature.append(cuts)
    if n_capped and _rank0():
        print(f"[grow_colmaker] {n_capped}/{F} features exceed "
              f"max_exact_bin={max_exact_bin} distinct values and were "
              "quantized to that many cuts — dsplit=row exact mode is "
              "approximate past the cap (single-controller AND "
              "dsplit=col training use the uncapped exact grower; the "
              "reference itself runs histmaker, not exact, under row "
              "split)", file=sys.stderr)
    return pack_cuts(per_feature)


def bin_matrix(dmat: DMatrix, cuts: CutMatrix) -> np.ndarray:
    """Quantize to a dense (n_rows, F) bin-id array (0 = missing)."""
    n, F = dmat.num_row, cuts.num_feature
    dtype = np.uint8 if cuts.max_bin <= 256 else np.uint16
    out = np.zeros((n, F), dtype=dtype)
    src = _dense_source(dmat)
    if src is not None:
        _bin_dense_into(out, src[0], cuts, src[1])
        return out
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(dmat.indptr))
    cols = dmat.indices
    # explicitly-stored NaNs are missing (bin 0) — same as an absent CSR
    # entry and as bin_dense_device's isnan mask (searchsorted would
    # otherwise send NaN to the LAST bin, routing the same data
    # differently depending on which quantizer ran; advisor, round 4)
    in_range = (cols < F) & ~np.isnan(dmat.values)
    rows, cols, vals = rows[in_range], cols[in_range], dmat.values[in_range]
    for f in range(F):
        m = cols == f
        if not m.any():
            continue
        b = 1 + np.searchsorted(cuts.cut_values[f, :cuts.n_cuts[f]],
                                vals[m], side="right")
        out[rows[m], f] = b.astype(dtype)
    return out


def bin_dense_device(X, cut_values):
    """Device-side quantization of a dense (N, F) float matrix (NaN =
    missing -> bin 0): ``1 + #{c: x >= cut[c]}`` — identical to the
    host ``searchsorted(side="right")`` since cut lists are sorted and
    inf-padded.  One fused (N, F, C) compare-reduce where the host loop
    takes seconds at 1M x 28 (prediction-time path)."""
    import jax
    import jax.numpy as jnp
    X = jnp.asarray(X, jnp.float32)
    cv = jnp.asarray(cut_values, jnp.float32)
    # the +inf PADDING columns must not count: x=+inf satisfies
    # inf >= inf, which would yield bin 1 + max_cuts instead of the
    # host searchsorted's 1 + n_cuts[f] (real cuts are finite — they
    # come from sketch summaries, which filter non-finite values)
    b = 1 + jnp.sum((X[:, :, None] >= cv[None, :, :])
                    & jnp.isfinite(cv)[None, :, :],
                    axis=2).astype(jnp.int32)
    b = jnp.where(jnp.isnan(X), 0, b)
    return b.astype(jnp.uint8 if cv.shape[1] + 2 <= 256 else jnp.uint16)


def _bin_dense_into(out: np.ndarray, X: np.ndarray, cuts: CutMatrix,
                    missing: float) -> None:
    """``out[i, f] = 1 + searchsorted(cuts_f, X[i, f], "right")`` for
    every present cell; missing cells and stored NaNs keep bin 0 (as an
    absent CSR entry and ``bin_dense_device``'s isnan mask do), ±inf
    goes where ``searchsorted`` sends it.  Row blocks, so that a
    block's columns are read from cache and tasks write apart."""
    width = min(X.shape[1], out.shape[1], cuts.num_feature)
    n_rows = _block_rows(width)

    def block(start: int) -> None:
        rows = slice(start, start + n_rows)
        for f in range(width):
            col, dst = X[rows, f], out[rows, f]
            present = ~np.isnan(col)
            if not np.isnan(missing):
                present &= col != missing
            cut = cuts.cut_values[f, :cuts.n_cuts[f]]
            if present.all():
                dst[:] = 1 + np.searchsorted(cut, col, side="right")
            else:
                dst[present] = 1 + np.searchsorted(cut, col[present],
                                                   side="right")

    _map(block, range(0, X.shape[0], n_rows))


def bin_dense(X: np.ndarray, cuts: CutMatrix, missing: float = np.nan) -> np.ndarray:
    """Quantize a dense float matrix directly (prediction-time fast path)."""
    n, F = X.shape
    dtype = np.uint8 if cuts.max_bin <= 256 else np.uint16
    out = np.zeros((n, F), dtype=dtype)
    _bin_dense_into(out, X, cuts, missing)
    return out
