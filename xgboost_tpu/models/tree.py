"""Struct-of-arrays regression trees: growth and traversal.

Replaces the reference's pointer-y ``TreeModel``/``RegTree``
(``src/tree/model.h:26-567``) with fixed-shape tensors: a tree of
``max_depth`` D occupies a perfect binary layout of ``2**(D+1)-1`` nodes
(node g has children 2g+1 / 2g+2), each field its own array.  Growth is
level-by-level — the strategy of the reference's histogram updaters
(``updater_histmaker-inl.hpp:124-147``) — with every level one
histogram + argmax + partition step on device.

The ``hist_reduce`` hook is the collective seam: single-chip it is the
identity; the data-parallel path passes ``lax.psum`` over the mesh axis,
which is exactly where the reference called ``rabit`` Allreduce
(``histmaker-inl.hpp:343-346``; SURVEY.md §5.8).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from xgboost_tpu.ops.histogram import (build_level_histogram,
                                       dequantize_hist, node_stats,
                                       stats_from_histogram)
from xgboost_tpu.ops.split import SplitConfig, calc_weight, find_best_splits


class TreeArrays(NamedTuple):
    """One regression tree (or a (T, ...) stack of them)."""
    feature: jax.Array       # (n_nodes,) int32, -1 if leaf/unused
    cut_index: jax.Array     # (n_nodes,) int32
    threshold: jax.Array     # (n_nodes,) f32 — raw-value cut (v < thr -> left)
    default_left: jax.Array  # (n_nodes,) bool
    is_leaf: jax.Array       # (n_nodes,) bool
    leaf_value: jax.Array    # (n_nodes,) f32 (eta-scaled)
    gain: jax.Array          # (n_nodes,) f32 loss_chg of the split (stat)
    sum_hess: jax.Array      # (n_nodes,) f32 node hessian sum (stat)

    @property
    def n_nodes(self) -> int:
        return self.feature.shape[-1]


class GrowConfig(NamedTuple):
    """Static configuration of the growth kernel."""
    split: SplitConfig
    max_depth: int
    n_bin: int               # histogram bins B (incl. missing bin 0)
    subsample: float = 1.0
    colsample_bytree: float = 1.0
    colsample_bylevel: float = 1.0
    hist_precision: str = "auto"  # auto | fp32 | bf16 | int8 | fixed
    # (named TrainParam; "fixed" = int32 fixed-point scatter — bitwise
    # deterministic across any data-mesh size, ops/histogram.FIXED_SCALE)
    # multi-root trees (reference TreeParam num_roots, data.h root_index):
    # the top ceil(log2 n_roots) levels of the perfect layout are root
    # slots; row i enters at node (2**d0 - 1) + root_index[i], matching
    # RegTree::GetLeafIndex(feat, root_id) semantics (model.h:534-543)
    n_roots: int = 1


class SplitDecision(NamedTuple):
    """Per-node chosen split for one level (hook-neutral: `feature` is in
    whatever id space the finder uses — local on one chip, global under
    column sharding — and `owner` names the shard holding the feature)."""
    gain: jax.Array          # (n_node,) f32
    feature: jax.Array       # (n_node,) int32
    cut_index: jax.Array     # (n_node,) int32
    default_left: jax.Array  # (n_node,) bool
    threshold: jax.Array     # (n_node,) f32 raw cut value
    valid: jax.Array         # (n_node,) bool
    owner: jax.Array         # (n_node,) int32 shard owning the feature
    # optional left-child (G, H) of the chosen split — finders that
    # provide them let the grower derive child node stats (terminal
    # level) instead of running a node_stats pass over all rows
    left_g: jax.Array = None
    left_h: jax.Array = None


def _wrap_best(best, cut_values) -> "SplitDecision":
    """BestSplit -> single-shard SplitDecision (threshold gather, local
    owner) — the one construction both histogram layouts share."""
    thr = cut_values[best.feature, best.cut_index]
    return SplitDecision(best.gain, best.feature, best.cut_index,
                         best.default_left, thr, best.valid,
                         jnp.zeros_like(best.feature),
                         best.left_g, best.left_h)


def _default_split_finder(hist, nst, n_cuts, cut_values, fmask, split_cfg):
    """Single-shard split finding: all features are local."""
    return _wrap_best(find_best_splits(hist, nst, n_cuts, split_cfg,
                                       fmask), cut_values)


def _onehot_select(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[..., idx]`` via broadcast-compare (no gather): table
    (..., M) indexed by idx (..., N) -> (..., N); M is small."""
    M = table.shape[-1]
    ids = jnp.arange(M, dtype=jnp.int32)
    sel = idx[..., :, None] == ids
    tb = table[..., None, :]
    if table.dtype == jnp.bool_:
        return (sel & tb).any(axis=-1)
    return jnp.where(sel, tb, jnp.zeros((), table.dtype)).sum(axis=-1)


from jax.custom_batching import custom_vmap  # noqa: E402 (used below)


@custom_vmap
def table_lookup(table: jax.Array, idx: jax.Array) -> jax.Array:
    """Per-row lookup in a small per-node table: ``table[idx]``.

    Broadcast-compare select, NOT a gather: measured on v5e (round 3,
    1M rows), XLA's dynamic gather costs 0.6-7.5 ms per launch for
    16-1023-entry tables while the O(N*M) compare-select fuses to
    0.05-0.9 ms — gathers only win past ~1024 entries (deep trees),
    where the fallback below applies.  The vmap rule (ensemble axis of
    vmapped growth) makes the same choice for batched lookups.
    """
    if table.shape[-1] > 1024:
        return table[idx]
    return _onehot_select(table, idx)


@table_lookup.def_vmap
def _table_lookup_vmap(axis_size, in_batched, table, idx):
    tb, ib = in_batched
    table_b = table if tb else jnp.broadcast_to(
        table, (axis_size,) + table.shape)
    idx_b = idx if ib else jnp.broadcast_to(idx, (axis_size,) + idx.shape)
    if table_b.shape[-1] > 1024:
        # the O(N*M) compare stops paying for big tables (deep trees,
        # CPU backends); the batched gather is the lesser evil there
        return jnp.take_along_axis(table_b, idx_b, axis=-1), True
    return _onehot_select(table_b, idx_b), True


def bin_of_feature(binned: jax.Array, f_row: jax.Array) -> jax.Array:
    """Per-row bin id of a per-row feature: ``binned[r, f_row[r]]``.

    Selected with a broadcast compare + masked sum over (N, F) instead of
    ``take_along_axis``: dynamic lane gathers serialize on TPU (~16 ms per
    level at 1M x 28) while this is a fused VPU pass (~1 ms).  Out-of-range
    ``f_row`` yields bin 0 (missing)."""
    f_ids = jnp.arange(binned.shape[1], dtype=jnp.int32)
    sel = f_ids[None, :] == f_row[:, None]               # (N, F)
    return jnp.where(sel, binned.astype(jnp.int32), 0).sum(axis=1)


def _default_router(best: SplitDecision, node_of_row, binned):
    """Row go-left decision when the split feature's bins are local.

    The (n_node,)-table lookups are cheap in-graph when unbatched (a
    gather-free MXU formulation measured no faster end-to-end), but
    catastrophic as vmap-batched gathers — :func:`table_lookup` picks
    the right lowering per context.  Only `take_along_axis`-style
    dynamic LANE gathers always serialize on TPU, hence the
    broadcast-compare :func:`bin_of_feature`.
    """
    f_row = table_lookup(best.feature, node_of_row)
    j_row = table_lookup(best.cut_index, node_of_row)
    dl_row = table_lookup(best.default_left, node_of_row)
    b = bin_of_feature(binned, f_row)
    return jnp.where(b == 0, dl_row, b <= j_row + 1)


def _default_feat_sampler(key, rate, binned):
    return _sample_features(key, binned.shape[1], rate)


def root_level(n_roots: int) -> int:
    """Depth of the level holding the root slots (0 for a single root)."""
    return max(n_roots - 1, 0).bit_length()


def tree_capacity(max_depth: int, n_roots: int = 1) -> int:
    return 2 ** (root_level(n_roots) + max_depth + 1) - 1


@functools.partial(jax.jit, static_argnames=(
    "cfg", "hist_reduce", "split_finder", "router", "feat_sampler"))
def grow_tree(key: jax.Array, binned: jax.Array, gh: jax.Array,
              cut_values: jax.Array, n_cuts: jax.Array, cfg: GrowConfig,
              row_valid: Optional[jax.Array] = None,
              hist_reduce: Callable[[jax.Array], jax.Array] = None,
              split_finder=None, router=None, feat_sampler=None,
              root: Optional[jax.Array] = None,
              binned_t: Optional[jax.Array] = None):
    """Grow one tree level-by-level.

    Args:
      key: PRNG key for row/column subsampling.
      binned: (N, F) bin ids (0 = missing); F may be a feature SHARD.
      gh: (N, 2) gradient pairs.
      cut_values: (F, C) padded raw cut values, n_cuts: (F,).
      row_valid: optional (N,) bool — rows that belong to this shard/set
        (padding rows excluded from both stats and leaf assignment).
      root: optional (N,) int32 per-row root slot in [0, cfg.n_roots)
        (reference BoosterInfo root_index, data.h:39-58); None = root 0.
      hist_reduce: collective reduction applied to every histogram and
        node-stat tensor (identity when None; psum over 'data' in DP mode).
      split_finder/router/feat_sampler: the collective seams for
        column-split training (parallel/colsplit.py); the defaults are
        the single-shard implementations.

    Returns (tree: TreeArrays, row_leaf: (N,) int32 global leaf node per
    row, row_val: (N,) f32 the row's leaf VALUE).  row_val is recorded
    AT PARKING TIME from the level's would-be leaf weights — the same
    numbers apply_level writes into leaf_value, so it bit-matches
    ``leaf_value[row_leaf]`` while replacing that post-growth
     127-entry per-row lookup (measured 0.84 ms/round at 1M rows —
    round-5 trace) with per-level selects that fuse into the routing
    pass.
    """
    N, F = binned.shape
    D = cfg.max_depth
    d0 = root_level(cfg.n_roots)  # growth starts at the root-slot level
    red = hist_reduce if hist_reduce is not None else (lambda x: x)
    default_finder = split_finder is None
    if split_finder is None:
        split_finder = _default_split_finder
    if router is None:
        router = _default_router
    if feat_sampler is None:
        feat_sampler = _default_feat_sampler

    key_rows, key_ftree, key_flevel = jax.random.split(key, 3)

    # row subsampling (reference TrainParam::subsample applied at gradient
    # level, updater_colmaker-inl.hpp:115-146): dropped rows contribute no
    # statistics but still flow to a leaf for the prediction cache.
    gh_used = gh
    if cfg.subsample < 1.0:
        keep = jax.random.uniform(key_rows, (N,)) < cfg.subsample
        gh_used = gh * keep[:, None].astype(gh.dtype)
    if row_valid is not None:
        gh_used = gh_used * row_valid[:, None].astype(gh.dtype)

    # column sampling bytree (colmaker-inl.hpp:148-160): boolean mask, no
    # replacement semantics approximated by per-feature bernoulli with a
    # guaranteed non-empty fallback.
    feat_mask_tree = feat_sampler(key_ftree, cfg.colsample_bytree, binned)

    tree = empty_tree(D, cfg.n_roots)

    # level-local position at depth d0; -1 = parked in a leaf.  With one
    # root this is all zeros; multi-root rows start in their root slot
    # (the reference initializes position from root_index,
    # updater_colmaker-inl.hpp:115-146 / basemaker InitData).
    if root is not None and d0 > 0:
        pos = jnp.clip(root.astype(jnp.int32), 0, cfg.n_roots - 1)
    else:
        pos = jnp.zeros(N, jnp.int32)
    if row_valid is not None:
        pos = jnp.where(row_valid, pos, -1)
    row_leaf = jnp.zeros(N, jnp.int32)
    row_val = jnp.zeros(N, jnp.float32)
    prev = None  # (best, nst, do_split) of the previous level
    raw = None   # its histogram's exact int32 block, where there is one

    # once-per-tree histogram precompute: the bins transpose and (int8
    # mode) gradient quantization hoisted out of the level loop —
    # re-materializing them per level cost ~9 ms/round at 1M x 28
    # (round-4 trace; ops/histogram.prepare_hist).  binned_t, when the
    # caller provides it (learner entries), is the RESIDENT
    # pre-transposed u8 operand: zero per-round transpose AND none of
    # the per-pallas-call layout copies an in-graph transpose incurs
    from xgboost_tpu.ops.histogram import (level_histogram_carried,
                                           prepare_hist)
    with jax.named_scope("grow.operand"):
        hist_prep = prepare_hist(binned, gh_used, cfg.n_bin,
                                 cfg.hist_precision, binned_t=binned_t)
    # kernel-NATIVE histogram layout (F, B, 2, n_node): the split
    # finder consumes the kernel's own output order, skipping the
    # per-level relayout transpose (~0.47 ms/round at 1M x 28 —
    # round-5 trace).  Default finder only (the colsplit/skmaker seams
    # speak the standard layout), single node tile.
    use_native = default_finder and hist_prep is not None

    from xgboost_tpu.ops.histogram import stats_from_histogram_native
    for depth in range(d0, d0 + D + 1):
        n_node = 1 << depth
        base = n_node - 1  # global index of first node at this level
        terminal = depth == d0 + D
        native = use_native and n_node <= 64
        # a level that builds a histogram past 32 nodes (the unfolded
        # 64-node tile, then node tiles) is set apart in a device trace:
        # its kernels, split finding and routing go under deep.*; every
        # other level, the terminal one included, stays under grow.*
        scope = "deep" if n_node > 32 and not terminal else "grow"

        if not terminal:
            with jax.named_scope(f"{scope}.hist"):
                if hist_prep is None:
                    hist = build_level_histogram(binned, gh_used, pos,
                                                 n_node, cfg.n_bin,
                                                 cfg.hist_precision)
                else:
                    # past the first level built, an int8 level builds
                    # its left children only and takes the right ones
                    # from the level above's raw sums (prev[2]: the
                    # parents that split)
                    hist, raw = level_histogram_carried(
                        pos, n_node, cfg.n_bin, cfg.hist_precision,
                        hist_prep, native,
                        parent=None if raw is None else (raw, prev[2]))
                hist = dequantize_hist(red(hist))

        with jax.named_scope(f"{scope}.split"):
            if terminal:
                # terminal level: everything still active becomes a
                # leaf.  Node stats DERIVE from the parent's chosen
                # split (left child = winner's left sums, right =
                # parent - left) when the finder provides them — a full
                # node_stats pass over the rows costs ~4.4 ms at 1M rows
                # (v5e, round 3)
                if prev is not None and prev[0].left_g is not None:
                    p_best, p_nst, p_split = prev
                    gl = jnp.where(p_split, p_best.left_g, 0.0)
                    hl = jnp.where(p_split, p_best.left_h, 0.0)
                    gr = jnp.where(p_split,
                                   p_nst[:, 0] - p_best.left_g, 0.0)
                    hr = jnp.where(p_split,
                                   p_nst[:, 1] - p_best.left_h, 0.0)
                    nst = jnp.stack(
                        [jnp.stack([gl, gr], 1).reshape(-1),
                         jnp.stack([hl, hr], 1).reshape(-1)], axis=1)
                else:
                    nst = dequantize_hist(red(node_stats(
                        gh_used, pos, n_node,
                        cfg.hist_precision)))  # (n_node, 2)
                make_leaf = jnp.ones(n_node, jnp.bool_)
                best = None
            else:
                # node totals fall out of the histogram (bin sums of any
                # one feature) — saves a per-level pass over all rows
                nst = (stats_from_histogram_native(hist) if native
                       else stats_from_histogram(hist))
                fmask = feat_mask_tree
                if cfg.colsample_bylevel < 1.0:
                    fmask = fmask & feat_sampler(
                        jax.random.fold_in(key_flevel, depth),
                        cfg.colsample_bylevel, binned)
                if native:
                    from xgboost_tpu.ops.split import \
                        find_best_splits_native
                    best = _wrap_best(
                        find_best_splits_native(hist, nst, n_cuts,
                                                cfg.split, fmask),
                        cut_values)
                else:
                    best = split_finder(hist, nst, n_cuts, cut_values,
                                        fmask, cfg.split)
                # cannot_split (param.h:174): too little hessian mass
                can_try = nst[:, 1] >= 2.0 * cfg.split.min_child_weight
                do_split = best.valid & can_try
                make_leaf = ~do_split
                prev = (best, nst, do_split)

            tree = apply_level(tree, depth, nst, best, make_leaf,
                               cfg.split)
            # the level's would-be leaf weights (same expression
            # apply_level writes — CSE'd, bitwise identical): parked rows
            # record their value here instead of a post-growth
            # leaf_value[row_leaf] pass
            leaf_w = calc_weight(nst[:, 0], nst[:, 1], cfg.split) \
                * cfg.split.eta

        # park rows whose node became a leaf; route the rest to children
        with jax.named_scope(f"{scope}.route"):
            active = pos >= 0
            node_of_row = jnp.clip(pos, 0, n_node - 1)
            if best is None:
                # terminal level: make_leaf is constant-true — no lookup
                row_is_leaf = active
                val_row = table_lookup(leaf_w, node_of_row)
            elif router is _default_router and n_node <= 1024:
                # ONE (N, n_node) one-hot compare serves all five
                # per-node channels (routing feature/cut/default + park
                # flag + leaf value): XLA multi-output-fuses the masked
                # sums over the shared compare, replacing 4 separate
                # lookup fusions
                ids = jnp.arange(n_node, dtype=jnp.int32)
                sel = node_of_row[:, None] == ids             # (N, M)

                def pick(v):
                    return jnp.where(sel, v[None, :], 0.0).sum(axis=1)
                f_row = pick(best.feature.astype(jnp.float32)
                             ).astype(jnp.int32)
                j1_row = pick(best.cut_index.astype(jnp.float32) + 1.0)
                dl_row = pick(
                    best.default_left.astype(jnp.float32)) != 0.0
                leaf_row = pick(make_leaf.astype(jnp.float32)) != 0.0
                val_row = pick(leaf_w)
                row_is_leaf = active & leaf_row
                b = bin_of_feature(binned, f_row)
                go_left = jnp.where(b == 0, dl_row,
                                    b.astype(jnp.float32) <= j1_row)
            else:
                row_is_leaf = active & table_lookup(make_leaf,
                                                    node_of_row)
                val_row = table_lookup(leaf_w, node_of_row)
                go_left = router(best, node_of_row, binned)
            row_leaf = jnp.where(row_is_leaf, base + pos, row_leaf)
            row_val = jnp.where(row_is_leaf, val_row, row_val)
            if best is not None:
                new_pos = 2 * pos + (~go_left).astype(jnp.int32)
                pos = jnp.where(active & ~row_is_leaf, new_pos, -1)

    return tree, row_leaf, row_val


def apply_level(tree: TreeArrays, depth: int, nst: jax.Array,
                best: Optional[SplitDecision], make_leaf: jax.Array,
                split_cfg) -> TreeArrays:
    """Write one level's decisions into the tree arrays (shared by the
    in-memory, distributed and paged growers)."""
    n_node = 1 << depth
    base = n_node - 1
    # node occupancy: a level node is "live" iff some ancestor path made
    # it; detect via sum_hess>0 OR it is the root.  Empty nodes get
    # is_leaf=False and are unreachable, which is fine.
    live = (nst[:, 1] > 0.0) | (jnp.arange(n_node) == 0) if depth == 0 \
        else (nst[:, 1] > 0.0)

    # the would-be leaf weight is recorded for EVERY live node (not just
    # leaves): the prune updater turns split nodes back into leaves and
    # needs their weight (reference keeps base_weight in RTreeNodeStat)
    leaf_w = calc_weight(nst[:, 0], nst[:, 1], split_cfg) * split_cfg.eta
    idx = base + jnp.arange(n_node)
    tree = tree._replace(
        sum_hess=tree.sum_hess.at[idx].set(nst[:, 1]),
        is_leaf=tree.is_leaf.at[idx].set(make_leaf & live),
        leaf_value=tree.leaf_value.at[idx].set(leaf_w),
    )
    if best is not None:
        keep_split = ~make_leaf
        tree = tree._replace(
            feature=tree.feature.at[idx].set(
                jnp.where(keep_split, best.feature, -1)),
            cut_index=tree.cut_index.at[idx].set(best.cut_index),
            threshold=tree.threshold.at[idx].set(best.threshold),
            default_left=tree.default_left.at[idx].set(best.default_left),
            gain=tree.gain.at[idx].set(
                jnp.where(keep_split, best.gain, 0.0)),
        )
    return tree


def empty_tree(max_depth: int, n_roots: int = 1) -> TreeArrays:
    """All-unused tree arrays for a depth-``max_depth`` perfect layout."""
    n_total = tree_capacity(max_depth, n_roots)
    return TreeArrays(
        feature=jnp.full(n_total, -1, jnp.int32),
        cut_index=jnp.zeros(n_total, jnp.int32),
        threshold=jnp.zeros(n_total, jnp.float32),
        default_left=jnp.zeros(n_total, jnp.bool_),
        is_leaf=jnp.zeros(n_total, jnp.bool_),
        leaf_value=jnp.zeros(n_total, jnp.float32),
        gain=jnp.zeros(n_total, jnp.float32),
        sum_hess=jnp.zeros(n_total, jnp.float32),
    )


def _sample_features(key: jax.Array, F: int, rate: float) -> jax.Array:
    if rate >= 1.0:
        return jnp.ones(F, jnp.bool_)
    mask = jax.random.uniform(key, (F,)) < rate
    # never allow an empty feature set (reference resamples until non-empty)
    fallback = jnp.zeros(F, jnp.bool_).at[
        jax.random.randint(key, (), 0, F)].set(True)
    return jnp.where(mask.any(), mask, fallback)


# ---------------------------------------------------------------- traversal

def _traverse_one(tree: TreeArrays, binned: jax.Array, max_depth: int,
                  root: Optional[jax.Array] = None, n_roots: int = 1):
    """Leaf index per row for one tree on binned data.

    Matches reference RegTree::GetLeafIndex / GetNext (model.h:534-566)
    including missing-value default direction; with ``root`` (the
    per-row root_index, data.h:39-58) traversal starts at that root
    slot instead of node 0.

    Level-LOCAL like the grower: at depth d a row can only sit in one
    of 2^d nodes, so the per-node lookups compare against a STATIC
    SLICE of the tree arrays (2^d wide) instead of the full perfect
    layout — sliced lookups total ~5 * n_nodes compare-selects per
    tree where full-table lookups cost ~5 * n_nodes * depth.  All
    five channels share one (N, 2^d) compare, as in growth.
    """
    N = binned.shape[0]
    d0 = root_level(n_roots)
    # level-local position within depth level d0 + d; parked rows keep
    # their GLOBAL leaf index in `leaf_node` and pos = -1
    if n_roots > 1 and root is not None:
        pos = jnp.clip(root.astype(jnp.int32), 0, n_roots - 1)
    else:
        pos = jnp.zeros_like(binned[:, 0], dtype=jnp.int32)
    leaf_node = jnp.zeros(N, jnp.int32)
    for d in range(d0, d0 + max_depth + 1):
        n_node = 1 << d
        base = n_node - 1
        sl = slice(base, base + n_node)
        active = pos >= 0
        node = jnp.clip(pos, 0, n_node - 1)
        if n_node <= 1024:
            ids = jnp.arange(n_node, dtype=jnp.int32)
            sel = node[:, None] == ids

            def pick(v):
                return jnp.where(sel, v[None, :], 0.0).sum(axis=1)
            f_row = pick(tree.feature[sl].astype(jnp.float32)
                         ).astype(jnp.int32)
            is_leaf_row = pick(tree.is_leaf[sl].astype(jnp.float32)) \
                != 0.0
        else:
            # very deep levels: compare-select stops paying (see
            # table_lookup) — per-level gathers on the slices
            def pick(v):
                return table_lookup(v, node)
            f_row = pick(tree.feature[sl])
            is_leaf_row = pick(tree.is_leaf[sl])
        stop = active & (is_leaf_row | (f_row < 0) | (d == d0 + max_depth))
        leaf_node = jnp.where(stop, base + pos, leaf_node)
        if d == d0 + max_depth:
            break
        if n_node <= 1024:
            j1_row = pick(tree.cut_index[sl].astype(jnp.float32) + 1.0)
            dl_row = pick(tree.default_left[sl].astype(jnp.float32)) \
                != 0.0
        else:
            j1_row = pick(tree.cut_index[sl]).astype(jnp.float32) + 1.0
            dl_row = pick(tree.default_left[sl])
        b = bin_of_feature(binned, jnp.maximum(f_row, 0))
        go_left = jnp.where(b == 0, dl_row,
                            b.astype(jnp.float32) <= j1_row)
        new_pos = 2 * pos + (~go_left).astype(jnp.int32)
        pos = jnp.where(active & ~stop, new_pos, -1)
    return leaf_node


def padded_tree_count(T: int, tree_chunk: int) -> int:
    """Padded ensemble size of the chunked traversal for ``T`` trees.

    The ladder bounds compilation count for GROWING ensembles while
    keeping padded-tree waste near zero for the small stacks the
    incremental per-round margin update traverses:

      - ``T <= tree_chunk``: next power of two >= T, capped at
        ``tree_chunk`` (a 1-tree round update pads to 1, not to a full
        chunk; the cap keeps a non-power-of-two chunk's promised vmap
        width — T=12 at chunk 12 pads to 12, not 16);
      - ``T > tree_chunk``: next multiple of ``tree_chunk``.

    Distinct padded sizes for T in [1, k*chunk] total at most
    ``log2(chunk) + k`` — the fixed compile budget the bounded-compile
    test pins (tests/test_predict_chunk.py)."""
    if tree_chunk <= 1:
        return T
    if T <= tree_chunk:
        return min(1 << max(T - 1, 0).bit_length(), tree_chunk)
    return -(-T // tree_chunk) * tree_chunk


def predict_chunk_layout(T: int, tree_chunk: int):
    """(T_padded, chunk_size, n_chunks) of the chunked traversal —
    shared by the traversal itself and by serving/observability code
    attributing per-chunk cost.  Below the chunk the whole (power-of-
    two-padded, chunk-capped) ensemble is one chunk."""
    if tree_chunk <= 1:
        return T, 1, T
    T_pad = padded_tree_count(T, tree_chunk)
    C = T_pad if T <= tree_chunk else tree_chunk
    return T_pad, C, T_pad // C


def pad_predict_stack(stack: TreeArrays, tree_group: jax.Array,
                      tree_chunk: int):
    """Pad a (T, ...) ensemble stack to the :func:`padded_tree_count`
    ladder with zero-leaf-value trees (feature -1 = immediate leaf at
    the root, contributing exactly 0 — and the traversal core skips
    them via ``n_valid`` anyway).

    Returns ``(stack_padded, group_padded, n_valid)``.  This is EAGER
    glue deliberately kept OUTSIDE the jitted traversal core: padding
    inside the jit would key the compiled program on the raw T and
    recompile the whole traversal per ensemble size; out here, growing
    T costs only byte-copy concat ops while the heavy program compiles
    once per ladder rung (tests/test_predict_chunk.py pins the
    budget)."""
    T = int(stack.feature.shape[0])
    T_pad = padded_tree_count(T, tree_chunk)
    if T_pad == T:
        return stack, tree_group, T

    def pad(x, fill=0):
        return jnp.concatenate(
            [x, jnp.full((T_pad - T,) + x.shape[1:], fill, x.dtype)])
    stack = stack._replace(
        **{f: pad(getattr(stack, f), -1 if f == "feature" else 0)
           for f in TreeArrays._fields})
    return stack, pad(tree_group), T


def _chunk_leaves(chunk: TreeArrays, binned, max_depth, root, n_roots):
    """(C, N) leaf indices of one tree chunk: ``_traverse_one`` vmapped
    over the tree axis.  The per-level one-hot compares batch into
    (C, N, 2^d) fused compare-select-sums — the same lowering vmapped
    ensemble GROWTH uses (table_lookup's custom_vmap rule)."""
    return jax.vmap(
        lambda tr: _traverse_one(tr, binned, max_depth, root, n_roots)
    )(chunk)


@functools.partial(jax.jit, static_argnames=("max_depth", "n_group",
                                             "n_roots"))
def _predict_margin_scan(stack: TreeArrays, tree_group: jax.Array,
                         binned: jax.Array, base: jax.Array,
                         max_depth: int, n_group: int,
                         root: Optional[jax.Array] = None,
                         n_roots: int = 1) -> jax.Array:
    """Sequential ``lax.scan`` over trees — the pre-chunking traversal,
    kept as the A/B baseline and the ``tree_chunk<=1`` path."""
    N = binned.shape[0]

    def body(margin, tg):
        tree, group = tg
        leaf = _traverse_one(tree, binned, max_depth, root, n_roots)
        contrib = table_lookup(tree.leaf_value, leaf)
        margin = margin + contrib[:, None] * jax.nn.one_hot(
            group, n_group, dtype=margin.dtype)
        return margin, None

    margin0 = jnp.broadcast_to(base, (N, n_group)).astype(jnp.float32)
    margin, _ = jax.lax.scan(body, margin0, (stack, tree_group))
    return margin


@functools.partial(jax.jit, static_argnames=("max_depth", "n_group",
                                             "n_roots", "tree_chunk"))
def _predict_margin_chunked(stack: TreeArrays, tree_group: jax.Array,
                            n_valid: jax.Array, binned: jax.Array,
                            base: jax.Array, max_depth: int, n_group: int,
                            root: Optional[jax.Array], n_roots: int,
                            tree_chunk: int) -> jax.Array:
    """Chunked tree-parallel traversal core.  ``stack`` is ALREADY
    padded to a ``tree_chunk`` multiple (:func:`pad_predict_stack`), so
    the compiled program is keyed on the ladder rung, not the raw
    ensemble size; ``n_valid`` (the real tree count) is a TRACED
    scalar, so growing within a rung never retraces.

    Bit-identity with the scan: contributions accumulate IN TREE ORDER
    through the same ``margin + contrib * one_hot`` expression (the
    per-tree one-hot compare-selects are exact — a single nonzero term
    summed over zeros), and padded trees leave the margin untouched via
    ``where(valid, updated, margin)`` rather than adding 0.0 (which
    would flip a -0.0 margin cell to +0.0)."""
    N = binned.shape[0]
    T_pad = stack.feature.shape[0]
    C = tree_chunk                 # layout-derived; always divides T_pad
    n_chunks = T_pad // C
    margin = jnp.broadcast_to(base, (N, n_group)).astype(jnp.float32)

    chunks = jax.tree.map(
        lambda x: x.reshape((n_chunks, C) + x.shape[1:]), stack)
    groups = tree_group.reshape(n_chunks, C)
    valid = (jnp.arange(T_pad, dtype=jnp.int32)
             < n_valid).reshape(n_chunks, C)

    def body(m, cgv):
        chunk, gs, vs = cgv
        leaves = _chunk_leaves(chunk, binned, max_depth, root, n_roots)
        contribs = jax.vmap(table_lookup)(chunk.leaf_value, leaves)

        def acc(mm, tgv):
            contrib, group, ok = tgv
            upd = mm + contrib[:, None] * jax.nn.one_hot(
                group, n_group, dtype=mm.dtype)
            return jnp.where(ok, upd, mm), None
        m, _ = jax.lax.scan(acc, m, (contribs, gs, vs))
        return m, None

    margin, _ = jax.lax.scan(body, margin, (chunks, groups, valid))
    return margin


def predict_margin_binned(stack: TreeArrays, tree_group: jax.Array,
                          binned: jax.Array, base: jax.Array,
                          max_depth: int, n_group: int,
                          root: Optional[jax.Array] = None,
                          n_roots: int = 1,
                          tree_chunk: int = 0) -> jax.Array:
    """Sum of leaf values over a (T, n_nodes) stacked ensemble.

    ``tree_chunk > 1`` selects the chunked TREE-PARALLEL traversal:
    the ensemble pads to the :func:`padded_tree_count` ladder with
    zero-leaf-value trees, ``tree_chunk`` trees traverse at once under
    ``vmap`` (each level one batched compare-select instead of a
    per-tree chain of dependent launches — the vmapped-growth
    formulation applied to inference), and per-tree leaf
    contributions reduce into the (N, n_group) margin in tree order —
    bit-identical to the sequential scan (tests/test_predict_chunk.py).
    One compilation serves every ensemble size on the same ladder rung
    (``recompile_guard``-enforced).

    ``tree_chunk <= 1`` keeps the original scan over trees
    (``XGBTPU_PREDICT_TREE_CHUNK=0`` forces it end to end).  Returns
    (N, n_group) margins.
    """
    if tree_chunk <= 1:
        return _predict_margin_scan(stack, tree_group, binned, base,
                                    max_depth, n_group, root, n_roots)
    _, C, _ = predict_chunk_layout(int(stack.feature.shape[0]),
                                   tree_chunk)
    stack, tree_group, n_valid = pad_predict_stack(stack, tree_group,
                                                   tree_chunk)
    return _predict_margin_chunked(stack, tree_group, jnp.int32(n_valid),
                                   binned, base, max_depth, n_group,
                                   root, n_roots, C)


# ---------------------------------------------------- fused quantize+traverse

def _quantize_in_graph(X: jax.Array, cut_values: jax.Array) -> jax.Array:
    """Device quantization as a traceable sub-graph: the EXACT expression
    of :func:`binning.bin_dense_device` (one function, imported — not a
    copy), so the fused program's bin ids are bit-identical to the
    two-step path's by construction.  Raw f32 rows in (NaN = missing),
    small-int bin ids out; the binned matrix exists only as an XLA
    intermediate — it never materializes host-side."""
    from xgboost_tpu.binning import bin_dense_device
    return bin_dense_device(X, cut_values)


@functools.partial(jax.jit, static_argnames=("max_depth", "n_group",
                                             "n_roots"))
def _predict_margin_fused_scan(stack: TreeArrays, tree_group: jax.Array,
                               X: jax.Array, cut_values: jax.Array,
                               base: jax.Array, max_depth: int,
                               n_group: int,
                               root: Optional[jax.Array] = None,
                               n_roots: int = 1) -> jax.Array:
    binned = _quantize_in_graph(X, cut_values)
    return _predict_margin_scan.__wrapped__(stack, tree_group, binned,
                                            base, max_depth, n_group,
                                            root, n_roots)


@functools.partial(jax.jit, static_argnames=("max_depth", "n_group",
                                             "n_roots", "tree_chunk"))
def _predict_margin_fused_chunked(stack: TreeArrays, tree_group: jax.Array,
                                  n_valid: jax.Array, X: jax.Array,
                                  cut_values: jax.Array, base: jax.Array,
                                  max_depth: int, n_group: int,
                                  root: Optional[jax.Array], n_roots: int,
                                  tree_chunk: int) -> jax.Array:
    binned = _quantize_in_graph(X, cut_values)
    return _predict_margin_chunked.__wrapped__(
        stack, tree_group, n_valid, binned, base, max_depth, n_group,
        root, n_roots, tree_chunk)


def predict_margin_fused(stack: TreeArrays, tree_group: jax.Array,
                         X: jax.Array, cut_values: jax.Array,
                         base: jax.Array, max_depth: int, n_group: int,
                         root: Optional[jax.Array] = None,
                         n_roots: int = 1,
                         tree_chunk: int = 0) -> jax.Array:
    """FUSED quantize+traverse: raw f32 feature rows (NaN = missing) go
    cut-compare → bin ids → margins inside ONE jitted program.

    The transfer-wall companion of :func:`predict_margin_binned` (round
    7): a one-off prediction uploads raw f32 blocks and never
    materializes the binned matrix outside the program — no second
    device buffer, no extra launch boundary, and where the upload
    dominates the quantize+traverse cost hides under the NEXT block's
    upload (learner's prefetch pipeline).

    Bit-parity contract: the quantize sub-graph IS
    ``binning.bin_dense_device`` (imported, not re-derived) and the
    traversal cores are the two-step path's own (``__wrapped__`` of the
    same jitted functions), so margins are bit-identical to
    quantize-then-:func:`predict_margin_binned` on the same rows
    (tests/test_predict_fused.py).  Same ladder/padding discipline:
    compiled programs are keyed on the ladder rung, not the raw T."""
    if tree_chunk <= 1:
        return _predict_margin_fused_scan(stack, tree_group, X, cut_values,
                                          base, max_depth, n_group, root,
                                          n_roots)
    _, C, _ = predict_chunk_layout(int(stack.feature.shape[0]),
                                   tree_chunk)
    stack, tree_group, n_valid = pad_predict_stack(stack, tree_group,
                                                   tree_chunk)
    return _predict_margin_fused_chunked(stack, tree_group,
                                         jnp.int32(n_valid), X, cut_values,
                                         base, max_depth, n_group, root,
                                         n_roots, C)


@functools.partial(jax.jit, static_argnames=("max_depth", "n_roots"))
def _predict_leaf_scan(stack: TreeArrays, binned: jax.Array,
                       max_depth: int, root: Optional[jax.Array] = None,
                       n_roots: int = 1) -> jax.Array:
    def body(_, tree):
        return None, _traverse_one(tree, binned, max_depth, root, n_roots)
    _, leaves = jax.lax.scan(body, None, stack)
    return leaves.T


@functools.partial(jax.jit, static_argnames=("max_depth", "n_roots",
                                             "tree_chunk"))
def _predict_leaf_chunked(stack: TreeArrays, binned: jax.Array,
                          max_depth: int, root: Optional[jax.Array],
                          n_roots: int, tree_chunk: int) -> jax.Array:
    """(T_pad, N) leaves of a padded stack, chunked like the margin
    core (padded columns are sliced off by the caller)."""
    T_pad = stack.feature.shape[0]
    C = tree_chunk                 # layout-derived; always divides T_pad
    n_chunks = T_pad // C
    chunks = jax.tree.map(
        lambda x: x.reshape((n_chunks, C) + x.shape[1:]), stack)

    def body(_, chunk):
        return None, _chunk_leaves(chunk, binned, max_depth, root,
                                   n_roots)
    _, leaves = jax.lax.scan(body, None, chunks)     # (n_chunks, C, N)
    return leaves.reshape(T_pad, -1)


def predict_leaf_binned(stack: TreeArrays, binned: jax.Array,
                        max_depth: int, root: Optional[jax.Array] = None,
                        n_roots: int = 1,
                        tree_chunk: int = 0) -> jax.Array:
    """(N, T) leaf node index per tree (reference PredictLeaf,
    gbtree-inl.hpp:355-385).  ``tree_chunk > 1`` traverses chunks of
    trees in parallel (same ladder/padding as
    :func:`predict_margin_binned`); leaf indices are integers, so
    parity with the scan is trivial."""
    if tree_chunk <= 1:
        return _predict_leaf_scan(stack, binned, max_depth, root, n_roots)
    T = int(stack.feature.shape[0])
    _, C, _ = predict_chunk_layout(T, tree_chunk)
    group = jnp.zeros(T, jnp.int32)          # layout only; groups unused
    stack, _, _ = pad_predict_stack(stack, group, tree_chunk)
    leaves = _predict_leaf_chunked(stack, binned, max_depth, root,
                                   n_roots, C)
    return leaves[:T].T
