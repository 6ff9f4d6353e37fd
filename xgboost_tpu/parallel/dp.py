"""Row-split data-parallel tree growth (the reference's flagship
distributed mode, ``dsplit=row`` → grow_histmaker, SURVEY.md §2.4).

Each device holds a row shard; per level the local histograms and node
stats are ``psum``-reduced over the mesh ``data`` axis — exactly where
the reference called ``histred.Allreduce``
(``src/tree/updater_histmaker-inl.hpp:343-346``) and ``GetNodeStats``'
allreduce (``updater_basemaker-inl.hpp:266-306``).  After the reduction
every shard computes the identical argmax split (deterministic
tie-break), so trees need no broadcast step — the reference's
TreeSyncher (``updater_sync-inl.hpp:34-49``) is free.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from xgboost_tpu.models.tree import (GrowConfig, grow_tree,
                                     table_lookup)
from xgboost_tpu.parallel.mesh import DATA_AXIS


def _psum_data(x):
    return jax.lax.psum(x, DATA_AXIS)


def grow_tree_dp(mesh: Mesh, key, binned, gh, cut_values, n_cuts,
                 cfg: GrowConfig, row_valid, split_finder=None, root=None):
    """Grow one tree with rows sharded over mesh axis 'data'.

    binned: (N, F) with N divisible by mesh size; gh: (N, 2);
    row_valid: (N,) bool marking real (non-padding) rows;
    root: optional (N,) int32 per-row root slot (multi-root trees).
    Returns (tree [replicated], row_leaf (N,) [sharded]).
    """
    def body(key, binned, gh, cut_values, n_cuts, row_valid, root):
        tree, row_leaf, row_val = grow_tree(
            key, binned, gh, cut_values, n_cuts, cfg,
            row_valid, hist_reduce=_psum_data,
            split_finder=split_finder,
            root=root if cfg.n_roots > 1 else None)
        # the leaf value was recorded at parking time, inside the shard
        return tree, row_leaf, row_val

    if root is None:
        root = jnp.zeros(binned.shape[0], jnp.int32)
    # check_vma=False: the Pallas histogram kernel's out_shape carries no
    # vma annotation, and the psum'd tree outputs are replicated anyway
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS), P(), P(), P(DATA_AXIS),
                  P(DATA_AXIS)),
        out_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
        check_vma=False,
    )
    return fn(key, binned, gh, cut_values, n_cuts, row_valid, root)


def refresh_tree_dp(mesh: Mesh, tree, binned, gh, split_cfg, max_depth,
                    row_valid):
    """Refresh a tree's stats over row-sharded data: per-shard path
    accumulation + psum (exactly the reference TreeRefresher's lazy
    allreduce of all node stats, updater_refresh-inl.hpp:94-98)."""
    from xgboost_tpu.models.updaters import refresh_tree

    def body(tree, binned, gh, row_valid):
        return refresh_tree(tree, binned, gh, split_cfg, max_depth,
                            row_valid, hist_reduce=_psum_data)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(),
        check_vma=False,
    )
    if row_valid is None:
        row_valid = jnp.ones(binned.shape[0], jnp.bool_)
    return fn(tree, binned, gh, row_valid)


def shard_rows(mesh: Mesh, arr: jax.Array) -> jax.Array:
    """Place an array with rows sharded over the 'data' axis."""
    spec = P(DATA_AXIS, *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


def pad_rows(n: int, multiple: int) -> int:
    """Rows to add so n divides evenly across the mesh."""
    return (-n) % multiple
