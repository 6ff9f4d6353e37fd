"""Plain reference for histogram-method gradient boosting on dense data.

It imports nothing of the program and takes nothing the program made.
It implements, from their published descriptions:

* the cut proposal of xgboost's weighted quantile sketch (Chen &
  Guestrin 2016, appendix; `WQSummary` SetPrune / SetCombine / the
  equal-rank query) on unit weights, folded over chunks of rows;
* bin ids `1 + #{cuts <= v}` (bin 0 is the missing bin; the data here
  is dense and finite, so it stays empty);
* second-order boosting: `binary:logistic` gradients, level-wise exact
  greedy over the bins with gain `G^2 / (H + lambda)`,
  `min_child_weight` on both children, leaf `-eta * G / (H + lambda)`,
  ties to the lowest (feature, cut);
* logloss and tie-aware AUC.

Histograms are exact float32 sums: a one-hot matrix product whose
float32 gradient operand is split into three bfloat16 pieces, so every
product is exact and only the float32 accumulation rounds.  `levels`
turns the fit into the lower-precision control: gradients rounded to
`levels` steps of the per-round maximum, as an int8 (127) or int4 (7)
histogram would take them.  `drop_half` is the "half of the batch left
out" fault.  Neither is used by a benchmark run.
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional

import numpy as np

RT_EPS = 1e-6          # xgboost's rt_eps: a split must gain more than this
_THREADS = max(1, min(8, (os.cpu_count() or 2) - 1))


# ---------------------------------------------------------------- sketch

class Summary(NamedTuple):
    value: np.ndarray   # distinct values, ascending (float64)
    rmin: np.ndarray    # weight strictly below
    rmax: np.ndarray    # weight at or below
    wmin: np.ndarray    # weight at the value


def _exact_summary(col: np.ndarray) -> Summary:
    v = np.sort(col).astype(np.float64)
    first = np.flatnonzero(np.r_[True, v[1:] != v[:-1]])
    w = np.diff(np.r_[first, v.size]).astype(np.float64)
    rmax = np.cumsum(w)
    return Summary(v[first], rmax - w, rmax, w)


def _prune(s: Summary, maxsize: int) -> Summary:
    """Keep both ends and, for each of maxsize-2 equally spaced ranks,
    the entry on the nearer side of it (WQSummary::SetPrune)."""
    size = s.value.size
    if size <= maxsize or maxsize < 2:
        return s
    begin = s.rmax[0]
    rng = s.rmin[-1] - begin
    n = maxsize - 2
    k = np.arange(1, n)
    dx2 = 2.0 * (k * rng / n + begin)
    mid2 = s.rmin + s.rmax
    i = np.clip(np.searchsorted(mid2, dx2, side="right") - 1, 0, size - 2)
    rmin_next = s.rmin + s.wmin
    rmax_prev = s.rmax - s.wmin
    take_i = dx2 < rmin_next[i] + rmax_prev[i + 1]
    sel = np.unique(np.r_[0, np.where(take_i, i, i + 1), size - 1])
    return Summary(*(a[sel] for a in s))


def _merge(a: Summary, b: Summary) -> Summary:
    """Rank bounds of the union (WQSummary::SetCombine): a side that
    lacks a value bounds it by its neighbours there."""
    if a.value.size == 0:
        return b
    if b.value.size == 0:
        return a
    allv = np.union1d(a.value, b.value)

    def side(s: Summary):
        lo = np.searchsorted(s.value, allv, side="left")
        hi = np.searchsorted(s.value, allv, side="right")
        has = hi > lo
        at = np.minimum(lo, s.value.size - 1)
        below = np.r_[0.0, s.rmin + s.wmin]            # RMinNext of the entry before
        above = np.r_[s.rmax - s.wmin, s.rmax[-1]]     # RMaxPrev of the entry after
        return (np.where(has, s.rmin[at], below[lo]),
                np.where(has, s.rmax[at], above[hi]),
                np.where(has, s.wmin[at], 0.0))
    ra, rb = side(a), side(b)
    return Summary(allv, ra[0] + rb[0], ra[1] + rb[1], ra[2] + rb[2])


def _propose(s: Summary, max_bin: int) -> np.ndarray:
    """Up to max_bin-2 cuts at equally spaced ranks (never the minimum);
    a summary with no more entries than that gives every value."""
    n_cut = max_bin - 2
    if s.value.size == 0:
        return np.zeros(0, np.float32)
    if s.value.size <= n_cut:
        return np.unique(s.value.astype(np.float32))
    ranks = np.arange(1, n_cut + 1) * (s.rmax[-1] / (n_cut + 1))
    mid = (s.rmin + s.rmax) * 0.5
    idx = np.clip(np.searchsorted(mid, ranks, side="left"), 1, s.value.size - 1)
    return np.unique(s.value[idx]).astype(np.float32)


def column_cuts(col: np.ndarray, max_bin: int, sketch_eps: float,
                sketch_ratio: float, chunk: int, small: int) -> np.ndarray:
    if col.size <= small:
        # a short column is summarised exactly and pruned once
        size = max(2, int(sketch_ratio / max(sketch_eps, 1.0 / max_bin)))
        return _propose(_prune(_exact_summary(col), size), max_bin)
    size = max(2, int(sketch_ratio / sketch_eps))
    acc = Summary(*(np.zeros(0) for _ in range(4)))
    for s in range(0, col.size, chunk):
        part = _prune(_exact_summary(col[s:s + chunk]), size)
        acc = _prune(_merge(acc, part), size)
    return _propose(acc, max_bin)


def propose_cuts(X: np.ndarray, *, max_bin: int, sketch_eps: float,
                 sketch_ratio: float = 2.0, chunk: int = 1 << 22,
                 small: int = 1 << 16, bin_align: int = 0,
                 align_margin: int = 4) -> list:
    """Per-feature float32 cut lists of a dense finite matrix."""
    if not np.isfinite(X[:: max(1, X.shape[0] // 4096)]).all():
        raise ValueError("the reference is for dense finite data")

    def one(f):
        return column_cuts(np.ascontiguousarray(X[:, f]), max_bin,
                           sketch_eps, sketch_ratio, chunk, small)
    with ThreadPoolExecutor(_THREADS) as pool:
        cuts = list(pool.map(one, range(X.shape[1])))
    n_bin = max(len(c) for c in cuts) + 2
    if bin_align > 0 and 0 < n_bin % bin_align <= align_margin:
        # the configuration's bin alignment would trim cuts here; the
        # cells of this benchmark sit on a multiple and never get here
        raise NotImplementedError(
            f"{n_bin} bins are {n_bin % bin_align} over a multiple of "
            f"{bin_align}: cut trimming is not in the reference")
    return cuts


def bin_ids(X: np.ndarray, cuts: list) -> np.ndarray:
    """(N, F) uint8 bin ids, 1 + the number of cuts at or below the value."""
    out = np.empty(X.shape, np.uint8)

    def one(f):
        out[:, f] = 1 + np.searchsorted(cuts[f], X[:, f], side="right")
    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(one, range(X.shape[1])))
    return out


# --------------------------------------------------------------- metrics

def auc(pred, y) -> float:
    """Area under the ROC curve, ties at half credit (mean ranks)."""
    p = np.asarray(pred, np.float64)
    pos = np.asarray(y) > 0
    order = np.argsort(p, kind="stable")
    ps = p[order]
    first = np.flatnonzero(np.r_[True, ps[1:] != ps[:-1]])
    last = np.r_[first[1:], ps.size]
    mean_rank = (first + 1 + last) / 2.0                  # 1-based
    rank = np.repeat(mean_rank, last - first)
    n_pos = int(pos.sum())
    n_neg = pos.size - n_pos
    if n_pos == 0 or n_neg == 0:
        raise ValueError("AUC needs both classes")
    s = float(rank[pos[order]].sum())
    return (s - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg)


def logloss(pred, y) -> float:
    p = np.clip(np.asarray(pred, np.float64), 1e-16, 1.0 - 1e-16)
    y = np.asarray(y, np.float64)
    return float(np.mean(-(y * np.log(p) + (1.0 - y) * np.log(1.0 - p))))


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64)))


class Objective(NamedTuple):
    name: str
    metric: str

    def base_margin(self, base_score: float) -> float:
        return float(-np.log(1.0 / base_score - 1.0))

    def grad_np(self, margin, y):
        """float64 (g, h) on the host, for exact node sums."""
        p = sigmoid(margin)
        return p - np.asarray(y, np.float64), np.maximum(p * (1.0 - p), 1e-16)

    def evaluate(self, margin, y) -> float:
        return _METRICS[self.metric](sigmoid(margin), y)


_METRICS = {"auc": auc, "logloss": logloss}


def objective(name: str, metric: str) -> Objective:
    if name != "binary:logistic":
        raise ValueError(f"objective {name!r} is not in the reference")
    if metric not in _METRICS:
        raise ValueError(f"metric {metric!r} is not in the reference")
    return Objective(name, metric)


# ------------------------------------------------------------- tree walk

class Trees(NamedTuple):
    """T trees in heap order (children of k are 2k+1, 2k+2)."""
    feature: np.ndarray      # (T, nodes) int32
    cut_index: np.ndarray    # (T, nodes) int32: left iff bin <= cut_index + 1
    threshold: np.ndarray    # (T, nodes) float32: left iff value < threshold
    is_leaf: np.ndarray      # (T, nodes) bool
    leaf_value: np.ndarray   # (T, nodes) float32, eta applied; every live node
    sum_hess: np.ndarray     # (T, nodes) float32


def leaf_of(trees: Trees, t: int, X: np.ndarray, *, by: str, depth: int):
    """Heap index of the leaf tree t sends every row to.  `by` is
    "value" (X raw, left iff x < threshold) or "bin" (X bin ids)."""
    n, F = X.shape
    flat = np.ascontiguousarray(X).reshape(-1)
    at = np.arange(n, dtype=np.int64) * F
    node = np.zeros(n, np.int32)
    for _ in range(depth):
        f = trees.feature[t][node]
        stop = trees.is_leaf[t][node] | (f < 0)
        x = flat.take(at + np.maximum(f, 0))
        if by == "value":
            left = x < trees.threshold[t][node]
        else:
            left = x.astype(np.int32) <= trees.cut_index[t][node] + 1
        node = np.where(stop, node, 2 * node + 2 - left).astype(np.int32)
    return node


def margin_of(trees: Trees, X: np.ndarray, base: float, *, by: str,
              depth: int, n_trees: Optional[int] = None) -> np.ndarray:
    out = np.full(X.shape[0], base, np.float64)
    for t in range(trees.feature.shape[0] if n_trees is None else n_trees):
        out += trees.leaf_value[t][leaf_of(trees, t, X, by=by, depth=depth)]
    return out


# ------------------------------------------------------------------- fit

def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


@functools.lru_cache(maxsize=None)
def _level_fn(M: int, B: int, blk: int, lam: float, mcw: float, eta: float):
    """One level of one tree, jitted: histogram, best split per node,
    leaf weights, routing."""
    jax, jnp = _jnp()
    bf16, f32 = jnp.bfloat16, jnp.float32

    def split3(x):
        hi = x.astype(bf16)
        r = x - hi.astype(f32)
        mid = r.astype(bf16)
        lo = (r - mid.astype(f32)).astype(bf16)
        return jnp.stack([hi, mid, lo], axis=-1)

    def level(bins, gh, pos, n_cuts, row_val):
        N, F = bins.shape
        C = B - 2
        pieces = split3(gh)                                       # (N, 2, 3)
        nodes = jnp.arange(M, dtype=jnp.int32)
        bin_ids_ = jnp.arange(B, dtype=jnp.int32)

        def body(acc, i):
            b = jax.lax.dynamic_slice_in_dim(bins, i * blk, blk)
            p = jax.lax.dynamic_slice_in_dim(pieces, i * blk, blk)
            q = jax.lax.dynamic_slice_in_dim(pos, i * blk, blk)
            a = jnp.where((q[:, None] == nodes)[:, :, None, None],
                          p[:, None], jnp.zeros((), bf16)).reshape(blk, M * 6)
            oh = (b.astype(jnp.int32)[:, :, None] == bin_ids_
                  ).astype(bf16).reshape(blk, F * B)
            return acc + jax.lax.dot_general(
                a, oh, (((0,), (0,)), ((), ())),
                preferred_element_type=f32), None
        acc, _ = jax.lax.scan(body, jnp.zeros((M * 6, F * B), f32),
                              jnp.arange(N // blk))
        hist = acc.reshape(M, 2, 3, F, B).sum(axis=2)             # (M, 2, F, B)
        G, H = hist[:, 0], hist[:, 1]
        Gt, Ht = G[:, 0].sum(-1), H[:, 0].sum(-1)                 # node totals
        GL = jnp.cumsum(G[:, :, 1:], axis=-1)[:, :, :C]           # cut j: bins 1..j+1
        HL = jnp.cumsum(H[:, :, 1:], axis=-1)[:, :, :C]
        GR, HR = Gt[:, None, None] - GL, Ht[:, None, None] - HL

        def gain(g, h):
            return g * g / (h + lam)
        chg = gain(GL, HL) + gain(GR, HR) - gain(Gt, Ht)[:, None, None]
        ok = ((HL >= mcw) & (HR >= mcw)
              & (jnp.arange(C)[None, :] < n_cuts[:, None])[None])
        flat = jnp.where(ok, chg, -1e30).reshape(M, F * C)
        best = jnp.argmax(flat, axis=1)                           # first maximum
        best_gain = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]
        feat = (best // C).astype(jnp.int32)
        cut = (best % C).astype(jnp.int32)
        gl = jnp.take_along_axis(GL.reshape(M, -1), best[:, None], 1)[:, 0]
        hl = jnp.take_along_axis(HL.reshape(M, -1), best[:, None], 1)[:, 0]
        do_split = (best_gain > RT_EPS) & (Ht >= 2.0 * mcw)
        leaf_w = jnp.where(Ht < mcw, 0.0, -Gt / (Ht + lam)) * eta
        # rows: stop in a leaf, or go to a child.  Each row reads its
        # node's entries by a one-hot select (a gather of N rows is the
        # slow way on a TPU)
        active = pos >= 0
        mine = pos[:, None] == nodes                              # (N, M)

        def of_row(table):
            return jnp.where(mine, table[None, :], 0).sum(axis=1)
        stops = active & (of_row(do_split.astype(jnp.int32)) == 0)
        f_row = of_row(feat)
        b_row = jnp.where(f_row[:, None] == jnp.arange(F, dtype=jnp.int32),
                          bins.astype(jnp.int32), 0).sum(axis=1)
        left = b_row <= of_row(cut) + 1
        row_val = jnp.where(stops, of_row(leaf_w), row_val)
        pos = jnp.where(active & ~stops,
                        2 * pos + (~left).astype(jnp.int32), -1)
        # children's sums follow from the chosen split
        child_g = jnp.where(do_split[:, None],
                            jnp.stack([gl, Gt - gl], 1), 0.0).reshape(-1)
        child_h = jnp.where(do_split[:, None],
                            jnp.stack([hl, Ht - hl], 1), 0.0).reshape(-1)
        node = dict(feature=jnp.where(do_split, feat, -1), cut_index=cut,
                    is_leaf=~do_split & (Ht > 0), leaf_value=leaf_w,
                    sum_hess=Ht)
        return node, pos, row_val, child_g, child_h
    return jax.jit(level)


@functools.lru_cache(maxsize=None)
def _last_level(M: int):
    jax, jnp = _jnp()

    def last(pos, w, row_val):
        mine = pos[:, None] == jnp.arange(M, dtype=jnp.int32)
        return jnp.where(pos >= 0, jnp.where(mine, w[None, :], 0.0).sum(axis=1),
                         row_val)
    return jax.jit(last)


class Fit(NamedTuple):
    trees: Trees
    evals: list                 # held-out metric after each round
    train_delta: np.ndarray     # (N,) float64 margin - base after the last round
    held_delta: np.ndarray


def fit(bins_train: np.ndarray, y_train: np.ndarray, bins_held: np.ndarray,
        y_held: np.ndarray, cuts: list, obj: Objective, *, n_rounds: int,
        max_depth: int, n_bin: int, eta: float, reg_lambda: float = 1.0,
        min_child_weight: float = 1.0, base_score: float = 0.5,
        levels: Optional[int] = None, drop_half: bool = False,
        blk: int = 4096) -> Fit:
    jax, jnp = _jnp()
    N, F = bins_train.shape
    D = int(max_depth)
    blk = min(blk, 1 << max(8, int(np.ceil(np.log2(max(N, 2))))))
    n_pad = -(-N // blk) * blk
    pad = n_pad - N
    bins = jnp.asarray(np.pad(bins_train, ((0, pad), (0, 0))))
    y = jnp.asarray(np.pad(np.asarray(y_train, np.float32), (0, pad)))
    real = jnp.arange(n_pad) < N
    if drop_half:
        real = real & (jnp.arange(n_pad) % 2 == 0)
    n_cuts = jnp.asarray([len(c) for c in cuts], jnp.int32)
    base = obj.base_margin(base_score)
    margin = jnp.full(n_pad, base, jnp.float32)

    @jax.jit
    def grad(margin):
        p = 1.0 / (1.0 + jnp.exp(-margin))
        g, h = p - y, jnp.maximum(p * (1.0 - p), 1e-16)
        gh = jnp.stack([g, h], axis=-1) * real[:, None]
        if levels:
            scale = jnp.maximum(jnp.max(jnp.abs(gh), axis=0), 1e-30)
            gh = jnp.clip(jnp.round(gh / scale * levels),
                          -levels, levels) * (scale / levels)
        return gh

    n_nodes = (1 << (D + 1)) - 1
    fields = ("feature", "cut_index", "is_leaf", "leaf_value", "sum_hess")
    out = {k: [] for k in fields}
    thr_all, evals = [], []
    held_margin = np.full(bins_held.shape[0], base, np.float64)
    for r in range(n_rounds):
        gh = grad(margin)
        pos = jnp.where(jnp.arange(n_pad) < N, 0, -1).astype(jnp.int32)
        row_val = jnp.zeros(n_pad, jnp.float32)
        tree = {k: np.zeros(n_nodes, np.float32) for k in fields}
        tree["feature"] = np.full(n_nodes, -1, np.int32)
        tree["cut_index"] = np.zeros(n_nodes, np.int32)
        tree["is_leaf"] = np.zeros(n_nodes, bool)
        for d in range(D):
            M = 1 << d
            node, pos, row_val, cg, ch = _level_fn(
                M, int(n_bin), blk, float(reg_lambda),
                float(min_child_weight), float(eta))(
                    bins, gh, pos, n_cuts, row_val)
            for k in fields:
                tree[k][M - 1:2 * M - 1] = np.asarray(node[k])
        # the last level: every node that is still open is a leaf
        M = 1 << D
        cg, ch = np.asarray(cg), np.asarray(ch)
        w = np.where(ch < min_child_weight, 0.0,
                     -cg / (ch + np.float32(reg_lambda))) * np.float32(eta)
        tree["leaf_value"][M - 1:] = w
        tree["sum_hess"][M - 1:] = ch
        tree["is_leaf"][M - 1:] = ch > 0
        row_val = _last_level(M)(pos, jnp.asarray(w, jnp.float32), row_val)
        margin = margin + row_val
        for k in fields:
            out[k].append(tree[k])
        thr = np.zeros(n_nodes, np.float32)
        for k in np.flatnonzero(tree["feature"] >= 0):
            thr[k] = cuts[tree["feature"][k]][tree["cut_index"][k]]
        thr_all.append(thr)
        one = Trees(tree["feature"][None], tree["cut_index"][None], thr[None],
                    tree["is_leaf"][None], tree["leaf_value"][None],
                    tree["sum_hess"][None])
        held_margin = held_margin + one.leaf_value[0][
            leaf_of(one, 0, bins_held, by="bin", depth=D)]
        evals.append(obj.evaluate(held_margin, y_held))
    trees = Trees(np.stack(out["feature"]), np.stack(out["cut_index"]),
                  np.stack(thr_all), np.stack(out["is_leaf"]),
                  np.stack(out["leaf_value"]), np.stack(out["sum_hess"]))
    return Fit(trees, evals, np.asarray(margin, np.float64)[:N] - base,
               held_margin - base)
