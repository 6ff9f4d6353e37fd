"""Device-side distributed weighted quantile sketch.

The TPU replacement for rabit's ``SerializeReducer`` reduction of
serialized quantile summaries (reference
``src/tree/updater_histmaker-inl.hpp:417-424``,
``src/utils/quantile.h:587-593``): each shard of a row-sharded dataset
builds a bounded-size summary of every feature ON DEVICE, summaries are
``all_gather``-ed over the mesh axis and folded with the associative
merge+prune — no host ever needs a full column.

A summary is a fixed-shape padded tensor (jit/pjit friendly): four
``(K,)`` float32 arrays (value, rmin, rmax, wmin), sorted by value, with
padding slots at ``value=+inf, rmin=rmax=total_weight, wmin=0``.  That
padding is rank-consistent — a padded slot behaves like "an entry above
every real value" — so merge needs no masks beyond the representation.

Semantics mirror the host sketch (:mod:`xgboost_tpu.sketch`, itself the
reference's ``WQSummary`` SetCombine/SetPrune, ``quantile.h:189-278``);
the rank-error guarantee eps = O(1/K) carries over because merge is
exact on rank bounds and prune is applied at bounded size.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class DeviceSummary(NamedTuple):
    """Padded weighted quantile summary (per feature: each field (..., K))."""
    value: jax.Array
    rmin: jax.Array
    rmax: jax.Array
    wmin: jax.Array


def _pad_entry(total):
    """Rank-consistent padding slot: sits above every real value."""
    return jnp.inf, total, total, jnp.float32(0.0)


def _select_prune(value, rmin, rmax, wmin, last_idx, n_real, total, K: int):
    """SetPrune (quantile.h:189-219) on sorted, possibly duplicated
    entries: keep extremes, pick interior entries nearest evenly spaced
    ranks with the (RMinNext, RMaxPrev) straddle test.  Returns a (K,)
    padded deduplicated DeviceSummary."""
    L = value.shape[0]
    begin = rmax[0]
    rng = jnp.take(rmin, jnp.maximum(n_real - 1, 0)) - begin
    n = K - 2
    k = jnp.arange(1, max(n, 1), dtype=jnp.float32)
    dx2 = 2.0 * (k * rng / max(n, 1) + begin)
    mid = rmin + rmax  # 2x midpoint rank; pads have mid = 2*total (>= dx2)
    ii = jnp.clip(jnp.searchsorted(mid, dx2, side="right") - 1, 0, L - 1)
    rmin_next = rmin + wmin
    rmax_prev = rmax - wmin
    nxt = jnp.minimum(last_idx[ii] + 1, L - 1)  # first slot of next group
    use_i = dx2 < rmin_next[ii] + rmax_prev[nxt]
    sel = jnp.where(use_i, ii, nxt)
    sel = jnp.concatenate([jnp.zeros(1, sel.dtype), sel,
                           jnp.maximum(n_real - 1, 0)[None]])
    sel = jnp.clip(sel, 0, jnp.maximum(n_real - 1, 0))

    sv, srmin, srmax, swmin = value[sel], rmin[sel], rmax[sel], wmin[sel]
    # dedup (selection may hit one group twice); padded slots dedup too
    keep = jnp.concatenate([jnp.array([True]), sv[1:] != sv[:-1]])
    keep &= jnp.isfinite(sv) & (n_real > 0)
    pv, prmin, prmax, pwmin = _pad_entry(total)
    sv = jnp.where(keep, sv, pv)
    srmin = jnp.where(keep, srmin, prmin)
    srmax = jnp.where(keep, srmax, prmax)
    swmin = jnp.where(keep, swmin, pwmin)
    # restore sortedness (masked slots went to +inf mid-array); K is tiny
    order = jnp.argsort(sv, stable=True)
    out = DeviceSummary(sv[order], srmin[order], srmax[order], swmin[order])
    # pad from K-1 selected slots up to K
    pad = jnp.full(K - sv.shape[0], 1.0)
    return DeviceSummary(
        jnp.concatenate([out.value, pad * pv]),
        jnp.concatenate([out.rmin, pad * prmin]),
        jnp.concatenate([out.rmax, pad * prmax]),
        jnp.concatenate([out.wmin, pad * pwmin]))


def local_summary(values: jax.Array, weights: jax.Array, K: int
                  ) -> DeviceSummary:
    """Exact summary of one feature shard, pruned to K slots.

    values: (N,) raw feature values (NaN/inf = missing); weights: (N,)
    (zero-weight rows are dropped, matching host make_summary).
    """
    N = values.shape[0]
    valid = jnp.isfinite(values) & (weights > 0)
    v = jnp.where(valid, values, jnp.inf).astype(jnp.float32)
    w = jnp.where(valid, weights, 0.0).astype(jnp.float32)
    order = jnp.argsort(v, stable=True)
    vs, ws = v[order], w[order]
    cum = jnp.cumsum(ws)
    total = cum[-1]
    n_real = jnp.sum(valid)
    i = jnp.arange(N)
    neq = vs[1:] != vs[:-1]
    first_b = jnp.concatenate([jnp.array([True]), neq])
    last_b = jnp.concatenate([neq, jnp.array([True])])
    first_idx = jax.lax.cummax(jnp.where(first_b, i, 0))
    last_idx = jax.lax.cummin(jnp.where(last_b, i, N - 1), reverse=True)
    cum0 = jnp.concatenate([jnp.zeros(1, jnp.float32), cum])
    rmin = cum0[first_idx]          # weight strictly below the group
    rmax = cum[last_idx]            # weight at or below the group
    wmin = rmax - rmin
    # pads (missing rows sorted to +inf with w=0) get rank-consistent slots
    real = jnp.arange(N) < n_real
    vs = jnp.where(real, vs, jnp.inf)
    rmin = jnp.where(real, rmin, total)
    rmax = jnp.where(real, rmax, total)
    wmin = jnp.where(real, wmin, 0.0)
    return _select_prune(vs, rmin, rmax, wmin, last_idx, n_real, total, K)


def _total(s: DeviceSummary):
    """Total weight: pads carry it by construction; last slot is pad-or-max."""
    return s.rmax[..., -1]


def merge_summaries_dev(a: DeviceSummary, b: DeviceSummary, K: int
                        ) -> DeviceSummary:
    """Associative merge + prune back to K (SetCombine, quantile.h:225-278).

    Both inputs are (K,)-padded deduplicated summaries.
    """
    def contrib(x: DeviceSummary, other: DeviceSummary):
        L = other.value.shape[0]
        lo = jnp.searchsorted(other.value, x.value, side="left")
        hi = jnp.searchsorted(other.value, x.value, side="right")
        exact = hi > lo
        tot = _total(other)
        rmin_next = jnp.concatenate(
            [jnp.zeros(1, jnp.float32), other.rmin + other.wmin])
        rmax_prev = jnp.concatenate(
            [other.rmax - other.wmin, tot[None]])
        loc = jnp.minimum(lo, L - 1)
        add_rmin = jnp.where(exact, other.rmin[loc], rmin_next[lo])
        add_rmax = jnp.where(exact, other.rmax[loc], rmax_prev[hi])
        add_wmin = jnp.where(exact, other.wmin[loc], 0.0)
        return add_rmin, add_rmax, add_wmin

    ar, ax, aw = contrib(a, b)
    br, bx, bw = contrib(b, a)
    allv = jnp.concatenate([a.value, b.value])
    allrmin = jnp.concatenate([a.rmin + ar, b.rmin + br])
    allrmax = jnp.concatenate([a.rmax + ax, b.rmax + bx])
    allwmin = jnp.concatenate([a.wmin + aw, b.wmin + bw])
    order = jnp.argsort(allv, stable=True)
    allv, allrmin, allrmax, allwmin = (allv[order], allrmin[order],
                                       allrmax[order], allwmin[order])
    total = _total(a) + _total(b)
    # dedup equal values (each side already absorbed the other's mass);
    # re-pad with the merged total
    keep = jnp.concatenate([jnp.array([True]), allv[1:] != allv[:-1]])
    keep &= jnp.isfinite(allv)
    pv, prmin, prmax, pwmin = _pad_entry(total)
    allv = jnp.where(keep, allv, pv)
    allrmin = jnp.where(keep, allrmin, prmin)
    allrmax = jnp.where(keep, allrmax, prmax)
    allwmin = jnp.where(keep, allwmin, pwmin)
    order = jnp.argsort(allv, stable=True)
    allv, allrmin, allrmax, allwmin = (allv[order], allrmin[order],
                                       allrmax[order], allwmin[order])
    n_real = jnp.sum(jnp.isfinite(allv))
    L = allv.shape[0]
    return _select_prune(allv, allrmin, allrmax, allwmin,
                         jnp.arange(L), n_real, total, K)


def propose_cuts_dev(s: DeviceSummary, max_bin: int) -> jax.Array:
    """Padded cut proposal from a device summary: up to max_bin-1 strictly
    increasing cut values, +inf padded (host propose_cuts semantics)."""
    K = s.value.shape[-1]
    n_cut = max_bin - 1
    n_real = jnp.sum(jnp.isfinite(s.value))
    total = _total(s)
    # dense path: every distinct value is a cut (incl. the minimum — the
    # missing-vs-present split for one-hot features)
    dense = s.value  # already distinct + sorted + inf-padded
    # quantile path
    ranks = jnp.arange(1, n_cut + 1, dtype=jnp.float32) * (
        total / (n_cut + 1))
    mid = (s.rmin + s.rmax) * 0.5
    idx = jnp.searchsorted(mid, ranks, side="left")
    idx = jnp.clip(idx, 1, jnp.maximum(n_real - 1, 1))
    qv = s.value[idx]
    keep = jnp.concatenate([jnp.array([True]), qv[1:] != qv[:-1]])
    qv = jnp.sort(jnp.where(keep & jnp.isfinite(qv), qv, jnp.inf))
    use_dense = n_real <= n_cut
    out_len = max(n_cut, K)
    dense_p = jnp.full(out_len, jnp.inf).at[:K].set(dense)
    quant_p = jnp.full(out_len, jnp.inf).at[:n_cut].set(qv)
    return jnp.where(use_dense, dense_p, quant_p)[:n_cut]


@functools.partial(jax.jit, static_argnames=("K", "max_bin", "axis_name"))
def _sketch_shard(values, weights, K: int, max_bin: int, axis_name: str):
    """Per-shard: local summaries for all features, all-gather over the
    mesh axis, associative fold, cut proposal.  values: (n_local, F)."""
    summ = jax.vmap(lambda col: local_summary(col, weights, K),
                    in_axes=1, out_axes=0)(values)      # (F, K) fields
    gathered = jax.lax.all_gather(summ, axis_name)       # (n_shard, F, K)
    n_shard = gathered.value.shape[0]
    merge = jax.vmap(lambda a, b: merge_summaries_dev(a, b, K))
    # pairwise tree fold: O(log n_shard) dependent merge stages
    parts = [jax.tree.map(lambda x, r=r: x[r], gathered)
             for r in range(n_shard)]
    while len(parts) > 1:
        nxt = [merge(parts[i], parts[i + 1])
               for i in range(0, len(parts) - 1, 2)]
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    acc = parts[0]
    # host compute_cuts proposes max_bin-1 cuts from its summary arg of
    # max_bin, leaving room for the reserved missing bin (binning.py:73);
    # mirror that so CutMatrix.max_bin stays <= max_bin on both paths
    cuts = jax.vmap(lambda s: propose_cuts_dev(s, max_bin - 1))(acc)
    return cuts, acc


def sketch_cuts_global(mesh, values_dev, weights_dev,
                       max_bin: int = 256, sketch_eps: float = 0.03,
                       sketch_ratio: float = 2.0):
    """Propose cuts from GLOBAL device arrays already row-sharded over
    ``mesh``'s 'data' axis.

    This is the true multi-host entry point: with per-rank split loading
    (:class:`xgboost_tpu.parallel.sharded.ShardedDMatrix`) each process
    contributed only its local rows to ``values_dev``, so no host ever
    materializes a full feature column — the cut proposal happens
    entirely in the mesh (local summaries -> all_gather -> associative
    fold), exactly the SerializeReducer role (quantile.h:587-593).

    values_dev: (N_pad, F) float32, NaN = missing;
    weights_dev: (N_pad,) float32, 0 on padding rows.
    Returns a host CutMatrix (identical on every process — the fold is
    deterministic and the output is replicated).
    """
    from jax.sharding import PartitionSpec as P

    from xgboost_tpu.binning import pack_cuts

    K = max(8, int(sketch_ratio / max(sketch_eps, 1.0 / max_bin)))
    from jax import shard_map
    fn = shard_map(
        functools.partial(_sketch_shard, K=K, max_bin=max_bin,
                          axis_name="data"),
        mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False)
    cuts_padded, _ = jax.jit(fn)(values_dev, weights_dev)
    cuts_np = np.asarray(cuts_padded)  # replicated -> host pull is local
    per_feature = [c[np.isfinite(c)].astype(np.float32) for c in cuts_np]
    return pack_cuts(per_feature)


def sketch_cuts_mesh(mesh, values: np.ndarray, weights: np.ndarray | None,
                     max_bin: int = 256, sketch_eps: float = 0.03,
                     sketch_ratio: float = 2.0):
    """Propose cuts for all features with rows sharded over ``mesh``'s
    'data' axis — the dsplit=row cut proposal (every shard sketches only
    its own rows; merge rides the ICI all-gather).

    Returns a host :class:`xgboost_tpu.binning.CutMatrix` (identical on
    every shard — the fold is deterministic).

    Single-controller convenience wrapper: ``values`` here is the full
    dense matrix the controller already holds (the per-shard split
    happens at device-put).  Per-rank split loading goes through
    :func:`sketch_cuts_global` with each process contributing only its
    local rows — same merge, bit-identical cuts.
    """
    n_shard = mesh.devices.size
    N, F = values.shape
    pad = (-N) % n_shard
    # missing/padding marker is +inf, NOT NaN: the sketch treats any
    # non-finite as missing, and in multi-process mode the runtime
    # asserts replicated device_put inputs are value-equal across
    # processes — which NaN can never be (NaN != NaN)
    if np.isnan(values).any():  # avoid a full-matrix copy when dense
        values = np.where(np.isnan(values), np.inf, values)
    if pad:
        values = np.concatenate(
            [values, np.full((pad, F), np.inf, values.dtype)])
        w = np.ones(N + pad, np.float32)
        w[N:] = 0.0
    else:
        w = np.ones(N, np.float32)
    if weights is not None:
        w[:N] = weights
    return sketch_cuts_global(
        mesh, jnp.asarray(values, jnp.float32), jnp.asarray(w),
        max_bin, sketch_eps, sketch_ratio)
