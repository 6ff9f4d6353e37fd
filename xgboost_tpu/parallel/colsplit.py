"""Column-split distributed tree growth (``dsplit=col`` — the
reference's DistColMaker, ``src/tree/updater_distcol-inl.hpp``).

Model/feature parallelism: every device holds ALL rows but only a shard
of the features (the reference's per-worker column shard).  The growth
loop is the shared :func:`xgboost_tpu.models.tree.grow_tree`; this module
supplies its three collective hooks:

  - split finder: local best per shard, then all-gather + argmax — the
    analog of the ``Reducer<SplitEntry>`` allreduce with its
    deterministic lowest-feature-id tie-break
    (``distcol-inl.hpp:136-153``, ``param.h:335-405``);
  - router: the winning shard owns the split feature's bin column, so
    row left/right routing is a psum of owner-masked go-left bits — the
    analog of the BitOR bitmap allreduce (``distcol-inl.hpp:115-117``);
  - feature sampler: colsample masks are drawn over the GLOBAL (real)
    feature ids with a shared key so shards agree — the analog of
    broadcasting rank-0's sampled feature list
    (``basemaker-inl.hpp:79-88``).

Every shard ends each level with identical split decisions, so trees are
replicated without a TreeSyncher broadcast (``updater_sync-inl.hpp``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from xgboost_tpu.models.tree import (GrowConfig, SplitDecision,
                                     _sample_features, bin_of_feature,
                                     grow_tree,
                                     table_lookup)
from xgboost_tpu.ops.split import NEG, RT_EPS, find_best_splits

FEAT_AXIS = "feat"


def feature_parallel_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over devices, axis 'feat' (column shards)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    from xgboost_tpu.parallel.mesh import make_mesh
    return make_mesh((len(devs),), (FEAT_AXIS,), devices=devs)


def grow_tree_colsplit(mesh: Mesh, key, binned, gh, cut_values, n_cuts,
                       cfg: GrowConfig, row_valid=None, f_real=None):
    """Grow one tree with features sharded over mesh axis 'feat'.

    binned: (N, F) bin ids with F padded to a multiple of the mesh size
    (padding features have n_cuts == 0 and are never selected);
    gh: (N, 2) replicated; f_real: the unpadded feature count (defaults
    to F).  Returns (tree [replicated], row_leaf (N,), delta (N,) leaf
    contribution) — all replicated.
    """
    n_shard = mesh.shape[FEAT_AXIS]
    N, F = binned.shape
    assert F % n_shard == 0, "pad features to the mesh size first"
    f_local = F // n_shard

    if row_valid is None:
        row_valid = jnp.ones(N, jnp.bool_)
    fn = _colsplit_fn(mesh, cfg, f_local, n_shard,
                      F if f_real is None else int(f_real))
    # collective accounting (obs/comm.py, the report_stats analog):
    # each level all-gathers one SplitDecision per shard per node and
    # psums the (N,) routing bits — count one "allgather" per level
    # with the logical per-level payload (estimate; the launch itself
    # is one fused XLA program, so wall time covers the whole tree)
    from xgboost_tpu.obs import comm
    n_nodes = (1 << cfg.max_depth) - 1
    est_bytes = (cfg.max_depth * n_shard * 24     # SplitDecision fields
                 + n_nodes * 24                   # per-node candidates
                 + cfg.max_depth * N * 4)         # routing-bit psum
    with comm.timed("allgather", nbytes=float(est_bytes),
                    count=cfg.max_depth):
        return fn(key, binned, gh, cut_values, n_cuts, row_valid)


@functools.lru_cache(maxsize=64)
def _colsplit_fn(mesh: Mesh, cfg: GrowConfig, f_local: int, n_shard: int,
                 f_real: int):
    """Build + cache the jitted shard_map'd growth fn per (mesh, config).

    The three hooks are constructed HERE (once per cache key) so their
    identities are stable and grow_tree's jit cache is hit across calls.
    """
    split_finder = functools.partial(_colsplit_split_finder, f_local=f_local)
    router = functools.partial(_colsplit_router, f_local=f_local)
    feat_sampler = functools.partial(_colsplit_feat_sampler, f_local=f_local,
                                     n_shard=n_shard, f_real=f_real)

    def body(key, binned, gh, cut_values, n_cuts, row_valid):
        tree, row_leaf, row_val = grow_tree(
            key, binned, gh, cut_values, n_cuts, cfg, row_valid,
            split_finder=split_finder, router=router,
            feat_sampler=feat_sampler)
        delta = row_val * row_valid.astype(jnp.float32)
        return tree, row_leaf, delta

    # check_vma=False: every shard derives the SAME tree/row outputs from
    # all-gathered split candidates and psum'd routing bits, but the static
    # varying-manifest analysis cannot see through the argmax/gather chain.
    from jax import shard_map
    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, FEAT_AXIS), P(), P(FEAT_AXIS, None),
                  P(FEAT_AXIS), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    ))


def _colsplit_split_finder(hist, nst, n_cuts, cut_values, fmask, split_cfg,
                           *, f_local: int) -> SplitDecision:
    """Local best split per shard, merged by all-gather + argmax (the
    SplitEntry allreduce).  Shards are ordered by axis index = ordered by
    global feature id, and argmax takes the FIRST max, so the reference's
    lowest-fid tie-break is preserved."""
    shard = jax.lax.axis_index(FEAT_AXIS)
    local = find_best_splits(hist, nst, n_cuts, split_cfg, fmask)
    thr_local = cut_values[local.feature, local.cut_index]

    gains = jax.lax.all_gather(
        jnp.where(local.valid, local.gain, NEG), FEAT_AXIS)
    gfid = jax.lax.all_gather(shard * f_local + local.feature, FEAT_AXIS)
    cuts_g = jax.lax.all_gather(local.cut_index, FEAT_AXIS)
    dl_g = jax.lax.all_gather(local.default_left, FEAT_AXIS)
    thr_g = jax.lax.all_gather(thr_local, FEAT_AXIS)

    winner = jnp.argmax(gains, axis=0)                    # (n_node,)

    def take(a):
        return jnp.take_along_axis(a, winner[None], axis=0)[0]

    best_gain = take(gains)
    return SplitDecision(
        gain=best_gain, feature=take(gfid), cut_index=take(cuts_g),
        default_left=take(dl_g), threshold=take(thr_g),
        valid=best_gain > RT_EPS, owner=winner.astype(jnp.int32))


def _colsplit_router(best: SplitDecision, node_of_row, binned, *,
                     f_local: int):
    """Owner-shard routing + psum 'bitmap' exchange
    (distcol-inl.hpp:115-117)."""
    shard = jax.lax.axis_index(FEAT_AXIS)
    owner_row = best.owner[node_of_row]
    lf_row = best.feature[node_of_row] - owner_row * f_local
    i_own = owner_row == shard
    b = bin_of_feature(binned, jnp.clip(lf_row, 0, binned.shape[1] - 1))
    dl_row = best.default_left[node_of_row]
    j_row = best.cut_index[node_of_row]
    go_left_local = jnp.where(b == 0, dl_row, b <= j_row + 1)
    return jax.lax.psum(
        (go_left_local & i_own).astype(jnp.int32), FEAT_AXIS) > 0


def _colsplit_feat_sampler(key, rate, binned, *, f_local: int, n_shard: int,
                           f_real: int):
    """Sample a global colsample mask over the REAL features only (so
    padding features can never be the non-empty fallback and results
    match a single-device run over the same feature set), then slice the
    local shard's piece."""
    shard = jax.lax.axis_index(FEAT_AXIS)
    mask_real = _sample_features(key, f_real, rate)
    mask_global = jnp.zeros(f_local * n_shard, jnp.bool_
                            ).at[:f_real].set(mask_real)
    return jax.lax.dynamic_slice(mask_global, (shard * f_local,), (f_local,))


# ------------------------------------------------------- exact column-split

def grow_tree_exact_colsplit(mesh: Mesh, key, X, gh, cfg: GrowConfig,
                             row_valid=None, has_missing: bool = True,
                             rank_t=None, uniq=None, f_real=None):
    """TRUE exact-greedy growth with features sharded over 'feat' — the
    reference's DistColMaker running full exact enumeration on each
    worker's column shard at ANY cardinality
    (``updater_distcol-inl.hpp:136-153`` over ColMaker's scan
    ``updater_colmaker-inl.hpp:362-414``).

    The segment-sorted exact finder (models/colmaker.py) is
    feature-local by construction — its per-level (node, value) sorts
    and prefix scans never mix features — so each shard runs it
    unchanged on its own raw columns; the per-node winners then reduce
    through the same all-gather + argmax as the histogram column split
    (lowest-global-fid tie-break preserved: shards are ordered by axis
    index = global fid block, argmax takes the first max), and row
    routing is the owner-masked psum bitmap with RAW-value comparison
    (``x < thr``) instead of bin comparison.

    X: (N, F) raw values, F padded to a multiple of the mesh size with
    all-NaN columns (they sort into the trash segment and can never
    win); rank_t/uniq: optional (F, N) dense-rank structures
    (build_exact_ranks on the PADDED matrix).  Returns (tree, row_leaf,
    delta), all replicated.
    """
    n_shard = mesh.shape[FEAT_AXIS]
    N, F = X.shape
    assert F % n_shard == 0, "pad features to the mesh size first"
    f_local = F // n_shard
    if row_valid is None:
        row_valid = jnp.ones(N, jnp.bool_)
    fn = _colsplit_exact_fn(mesh, cfg, f_local, n_shard,
                            F if f_real is None else int(f_real),
                            bool(has_missing), rank_t is not None)
    if rank_t is None:
        rank_t = jnp.zeros((F, 0), jnp.int32)   # placeholder, unused
        uniq = jnp.zeros((F, 0), jnp.float32)
    return fn(key, X, gh, row_valid, rank_t, uniq)


@functools.lru_cache(maxsize=64)
def _colsplit_exact_fn(mesh: Mesh, cfg: GrowConfig, f_local: int,
                       n_shard: int, f_real: int, has_missing: bool,
                       ranked: bool):
    """Build + cache the jitted shard_map'd exact growth fn (stable hook
    identities, same pattern as _colsplit_fn)."""
    from xgboost_tpu.models.colmaker import grow_tree_exact

    split_merge = functools.partial(_colsplit_exact_merge, f_local=f_local)
    router = functools.partial(_colsplit_exact_router, f_local=f_local)
    feat_sampler = functools.partial(_colsplit_feat_sampler,
                                     f_local=f_local, n_shard=n_shard,
                                     f_real=f_real)

    def body(key, X, gh, row_valid, rank_t, uniq):
        tree, row_leaf = grow_tree_exact(
            key, X, gh, cfg, row_valid, has_missing=has_missing,
            rank_t=rank_t if ranked else None,
            uniq=uniq if ranked else None,
            split_merge=split_merge, router=router,
            feat_sampler=feat_sampler)
        delta = (table_lookup(tree.leaf_value, row_leaf)
                 * row_valid.astype(jnp.float32))
        return tree, row_leaf, delta

    # check_vma=False for the same reason as _colsplit_fn: every shard
    # derives identical outputs from the merged winners + psum'd bits
    from jax import shard_map
    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, FEAT_AXIS), P(), P(),
                  P(FEAT_AXIS, None), P(FEAT_AXIS, None)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    ))


def _colsplit_exact_merge(local: SplitDecision, *, f_local: int
                          ) -> SplitDecision:
    """Per-shard exact winners -> global winner by all-gather + argmax
    (the SplitEntry allreduce, distcol-inl.hpp:136-153).  Thresholds
    are already raw midpoints, so no cut table is consulted; left-child
    (G, H) ride along for the grower's terminal-level derivation."""
    shard = jax.lax.axis_index(FEAT_AXIS)
    gains = jax.lax.all_gather(
        jnp.where(local.valid, local.gain, NEG), FEAT_AXIS)
    gfid = jax.lax.all_gather(shard * f_local + local.feature, FEAT_AXIS)
    thr_g = jax.lax.all_gather(local.threshold, FEAT_AXIS)
    dl_g = jax.lax.all_gather(local.default_left, FEAT_AXIS)
    gl_g = jax.lax.all_gather(local.left_g, FEAT_AXIS)
    hl_g = jax.lax.all_gather(local.left_h, FEAT_AXIS)

    winner = jnp.argmax(gains, axis=0)                    # (n_node,)

    def take(a):
        return jnp.take_along_axis(a, winner[None], axis=0)[0]

    best_gain = take(gains)
    return SplitDecision(
        gain=best_gain, feature=take(gfid),
        cut_index=jnp.zeros_like(winner, dtype=jnp.int32),
        default_left=take(dl_g), threshold=take(thr_g),
        valid=best_gain > RT_EPS, owner=winner.astype(jnp.int32),
        left_g=take(gl_g), left_h=take(hl_g))


def _colsplit_exact_router(best: SplitDecision, node_of_row, X, x_missing,
                           *, f_local: int):
    """Owner-shard raw-value routing + psum 'bitmap' exchange
    (distcol-inl.hpp:115-117): only the shard holding the winning
    feature's raw column decides, everyone sums the masked bits."""
    shard = jax.lax.axis_index(FEAT_AXIS)
    owner_row = table_lookup(best.owner, node_of_row)
    lf_row = table_lookup(best.feature, node_of_row) - owner_row * f_local
    i_own = owner_row == shard
    sel = (jnp.arange(f_local, dtype=jnp.int32)[None, :]
           == jnp.clip(lf_row, 0, f_local - 1)[:, None])
    x_row = jnp.where(sel, jnp.nan_to_num(X), 0.0).sum(axis=1)
    miss = (sel & x_missing).any(axis=1)
    thr_row = table_lookup(best.threshold, node_of_row)
    dl_row = table_lookup(best.default_left, node_of_row)
    go_left_local = jnp.where(miss, dl_row, x_row < thr_row)
    return jax.lax.psum(
        (go_left_local & i_own).astype(jnp.int32), FEAT_AXIS) > 0


def pad_features(arr, multiple: int, axis: int, fill=0):
    """Pad the feature axis to a multiple of the mesh size."""
    F = arr.shape[axis]
    pad = (-F) % multiple
    if pad == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths, constant_values=fill)
