"""Pallas TPU histogram kernel — the one custom kernel in the framework.

Replaces the reference's per-thread histogram accumulation
(``src/tree/updater_histmaker-inl.hpp:296-348``) for the hot path.  A
scatter-add over (node, feature, bin) cells serializes on TPU; this
kernel reformulates the histogram as MXU matmuls:

  For a row tile of R rows and one feature f:
      onehot[b, r]   = 1 iff binned[f, r] == b               (B, R)
      gh_exp[r, l]   = gh[r, l // M] * (pos[r] == l % M)     (R, 2M)
      hist_f        += onehot @ gh_exp                       (B, 2M)

  i.e. the per-node gradient/hessian sums of every bin fall out of a
  single (B x R) @ (R x 2M) matmul with the level's M nodes (and the
  grad/hess channel) packed into the MXU lane dimension.  At the deepest
  default level (depth 6, M = 64) the lane dim is exactly 128 — a full
  MXU pass.  Inactive rows (pos < 0, i.e. parked / padding /
  subsampled-out shards) contribute nothing because the node mask never
  matches.

  A level of few nodes leaves most of the 128 lanes idle, and what a
  feature costs is its one-hot's ROWS (built on the VPU and pushed
  through the MXU one by one), whatever the lanes hold.  Such a level
  FOLDS the bin id's high bits into the idle lanes (:func:`_fold_of`;
  the rows and lanes kernels): with b = hi * rows + lo,
      onehot[lo', r]  = 1 iff lo(binned[f, r]) == lo'         (rows, R)
      rhs_f[r, l]     = gh[r, (l % 2M) // M]
                        * (hi(binned[f, r]) * M + pos[r]
                           == (l // 2M) * M + l % M)          (R, n_hi*2M)
      hist_f         += onehot @ rhs_f                        (rows, n_hi*2M)
  and cell (lo, hi * 2M + c * M + n) is bin hi * rows + lo of channel c
  and node n: the caller unfolds it (:func:`_unfold`).  At 256 bins in
  int8 a level of 1-4 nodes pushes 32 one-hot rows per feature, one of
  8 or 16 nodes 64 and one of 32 nodes 128, where the unfolded kernel
  pushes 256; the right-hand operand is then built per feature (one
  compare, one select, one convert per element).  Every cell still
  sums the same rows in the same row-tile order, so the sums are the
  unfolded kernel's bit for bit.  From 64 nodes on (and in the
  tree-batched kernel, whose lanes the trees fill) nothing folds and
  the program is the one above.

  A feature whose bin ids all fall below 32 (:func:`narrow_features`:
  one-hot columns, small integer codes) leaves rows 32 ... B - 1 of its
  one-hot empty at every level.  Where a dataset has such features the
  level takes one call per WIDTH CLASS (:func:`width_classes`): the
  others at B bins, the narrow ones at 32, the unfolded program at every
  level with 64 features to a tile; the tail pads the narrow sums back
  to B bins and puts the features back in order.  Same rows, same
  row-tile order: the histogram bit for bit.  Where none is narrow the
  program is the one call it always was.

Bins are consumed feature-major ((F, N), int32) so every block satisfies
the TPU (8, 128) tile rule; the (N, F) -> (F, N) transpose happens once
per jit trace (CSE collapses the per-level copies inside one tree).

Grid: (feature_tiles, row_tiles), row tiles innermost so each feature
tile's output block accumulates across row tiles in VMEM.

The XLA scatter in :mod:`xgboost_tpu.ops.histogram` remains the portable
fallback (CPU mesh tests, interpret-free debugging).

Kernel names (each ``pallas_call`` passes ``name=``, which becomes the
HLO instruction name ``%<name>.<n>`` a device trace shows; a contract,
OBSERVABILITY.md): ``hist_level_rows`` — one tree, one dataset
(:func:`_hist_pallas_pre`, the training scan's kernel);
``hist_level_lanes`` — tenant lanes stacked along the row grid
(:func:`_hist_pallas_lanes_pre`); ``hist_level_trees`` — an ensemble
axis sharing one one-hot (:func:`_hist_pallas_batched_pre`);
``hist_level_node_stats`` — per-node (G, H) sums without bins
(:func:`node_stats_pallas`).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _mxu_mode(precision_mode: str) -> tuple:
    """``(operand dtype, accumulator dtype, dot precision)`` of a
    histogram mode.  TPU matmul default precision truncates f32
    operands to bf16; fp32 mode must request HIGHEST for exact
    (parity-testable) histograms (HIGH: unsupported by Mosaic).  In
    bf16 mode the operands are materialized in bf16 up front: the MXU
    would truncate them anyway, and halving the one-hot's VMEM
    footprint is a measured ~20% kernel win (tools/hist_microbench.py).
    int8 mode (gh arrives PRE-QUANTIZED as int32, one-hot is int8,
    products accumulate exactly in int32): the v5e MXU streams int8
    rows at 2x the bf16 rate — 1.88x measured on the level kernel
    (tools/hist_dots_probe.py, PR 32)."""
    if precision_mode == "int8":
        return jnp.int8, jnp.int32, jax.lax.Precision.DEFAULT
    if precision_mode == "fp32":
        return jnp.float32, jnp.float32, jax.lax.Precision.HIGHEST
    return jnp.bfloat16, jnp.float32, jax.lax.Precision.DEFAULT


def _fold_of(n_bin: int, m_pad: int, precision_mode: str) -> tuple:
    """``(rows, n_hi)``: the one-hot rows a level kernel pushes per
    feature and the bin-id groups it folds into the lane dim, from the
    level's shapes alone.  ``(n_bin, 1)`` is the unfolded program.

    A bin id is ``hi * rows + lo``.  The one-hot is over ``lo`` only and
    the right-hand operand, built per feature, puts a row's (g, h) in
    lane ``hi * 2M + channel * M + node``, so the high bits ride the
    lanes that 2M < 128 leaves idle.  ``rows`` is a power of two (so
    ``hi`` and ``lo`` are a shift and a mask), at least the one-hot
    dtype's sublane tile, and ``n_hi * 2 * m_pad <= 128``.  An ``n_bin``
    that is no power of two (67, 68) folds too: the last group is partly
    empty and the caller cuts it away (``[:n_bin]`` after the unfold).

    What a feature costs per row tile is linear in both (the shipped
    kernel at every fold, ``tools/hist_dots_probe.py``, PERF.md §7): a
    pushed one-hot row 1.3-1.4 ns in int8 (2.7 in bf16), a lane of the
    per-feature operand 0.9-1.0 ns (1.3): ``_LANE_COST`` of a row.  So
    ``(rows, n_hi)`` costs ``rows + _LANE_COST * n_hi * 2 * m_pad``
    against ``n_bin`` unfolded; the cheapest power of two is taken, and
    the level stays unfolded unless that saves ``_FOLD_PAYS`` of the
    cost.  At 256 bins in int8: 32 rows up to 4 nodes, 64 at 8 and 16,
    128 at 32 (measured 2.65 us a step of eight features against 3.11;
    the count says 218 against 256); a node-tiled deep level (m_pad =
    64, no idle lane) runs the unfolded program, as does 64 bins from
    16 nodes on (folded by two it measured 3.27 us a step of 28
    features against 2.77)."""
    floor = {"int8": 32, "bf16": 16}.get(precision_mode, 8)
    best, best_cost = (n_bin, 1), n_bin * (1.0 - _FOLD_PAYS)
    rows = floor
    while rows < n_bin:
        n_hi = -(-n_bin // rows)
        lanes = n_hi * 2 * m_pad
        cost = rows + _LANE_COST * lanes
        if lanes <= 128 and cost < best_cost:
            best, best_cost = (rows, n_hi), cost
        rows *= 2
    return best


# what a lane of the folded level's per-feature operand costs, in one-hot
# rows, and the share of an unfolded level's cost a fold has to save
# before the level is folded (both measured: see _fold_of)
_LANE_COST = 0.7
_FOLD_PAYS = 0.1


def _note_level(first: bool, rows: int, n_m_tiles: int, f_tiles: int,
                n_feat: int, narrow: bool = False,
                again: bool = False) -> None:
    """The trace-time gauges of a level histogram's grid.

    ``xgbtpu_hist_onehot_rows``: the one-hot rows one feature pushes
    through the MXU per row tile (``rows`` a node tile), summed over the
    levels of the tree last traced: set at the ``first`` level the
    tree builds, added to at the others (trace time, like its
    neighbours).  Depth 6 at 256 bins in int8, where a level past the
    first builds its left children only (:func:`_hist_pallas_derived`):
    32 + 32 + 32 + 32 + 64 + 64 = 256 (352 with every node built, 1,536
    unfolded).  ``xgbtpu_hist_node_tiles``: the node tiles of 64 nodes,
    summed the same way: 6 at depth 6, 8 at depth 8 (the 128-node
    level's 64 left children are one; 9 with every node built).
    ``xgbtpu_hist_feature_tiles``: the feature tiles ``f_pad // f_tile``
    of the level last traced (4 at 28 features and 256 bins, 250 at
    2,000): with the row tiles, the grid steps a node tile.
    ``xgbtpu_hist_feature_dots``: the real features of the level last
    traced, F: the one-hot dots one row tile runs over all its feature
    tiles (:func:`_feature_dots`).

    A level whose features take two kernel calls (:func:`width_classes`)
    notes the second with ``again``: its one-hot rows, feature tiles and
    dots add to the level's, its node tiles are the level's own already.
    ``xgbtpu_hist_narrow_features``: the features of the call that
    builds them at :data:`NARROW_BIN` bins (``narrow``), 0 where no
    call of the level does."""
    from xgboost_tpu.obs import training_metrics
    tm = training_metrics()
    tm.hist_narrow_features.set(float(n_feat) if narrow else 0.0)
    if again:
        for gauge, v in ((tm.hist_feature_tiles, f_tiles),
                         (tm.hist_feature_dots, n_feat),
                         (tm.hist_onehot_rows, rows * n_m_tiles)):
            gauge.inc(float(v))
        return
    tm.hist_feature_tiles.set(float(f_tiles))
    tm.hist_feature_dots.set(float(n_feat))
    if first:
        tm.hist_derived_levels.set(0.0)     # a new tree: none derived yet
    for gauge, v in ((tm.hist_onehot_rows, rows * n_m_tiles),
                     (tm.hist_node_tiles, n_m_tiles)):
        if first:
            gauge.set(float(v))
        else:
            gauge.inc(float(v))


def _feature_dots(bins, rhs_of, out_ref, lead: tuple, fi, *, n_feat: int,
                  rows: int, lo_mask, f_tile: int, precision_mode: str):
    """The per-feature loop of every level kernel: one ``(rows, R)``
    one-hot and one ``(rows, R) @ (R, lanes)`` dot per REAL feature of
    this feature tile, added into rows ``f * rows`` of ``out_ref[lead]``.
    ``rhs_of(b)`` gives the right-hand operand for a feature's bin ids
    ``b`` (1, R): the shared ``gh_exp`` on an unfolded level (``rows ==
    n_bin``, ``lo_mask`` None: the one-hot is over the bin id), the
    per-feature folded operand otherwise (the one-hot is over ``b &
    lo_mask``, :func:`_fold_of`).

    A slot that only pads the feature tile (``fi * f_tile + f >=
    n_feat``: 4 of 32 at 28 features and 256 bins, 3 of 16 at 13; its
    bin ids are the zeros :func:`transpose_bins` pads with) runs no
    one-hot and no MXU pass; its accumulator rows stay at the zeros
    ``_init`` wrote and the caller cuts them away (``[:F]``).  Such
    slots sit at the end of the LAST feature tile only, so they share
    one ``pl.when`` on the tile index and every other slot is the
    straight-line code it always was: where F fills its tiles (or
    ``f_tile == F``, the 64-bin jobs) no guard is in the program.

    bins: (f_tile, R) int32; fi: this step's index along the
    feature-tile grid axis."""
    hot_dtype, acc_dtype, prec = _mxu_mode(precision_mode)
    r_tile = bins.shape[1]
    bin_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, r_tile), 0)
    last = (n_feat - 1) // f_tile           # index of the last tile
    n_real = n_feat - last * f_tile         # its slots that hold a feature

    def slots(lo, hi):
        for f in range(lo, hi):
            b = bins[f:f + 1, :]
            lo_id = b if lo_mask is None else b & lo_mask
            onehot = (lo_id == bin_ids).astype(hot_dtype)    # (rows, R)
            acc = jax.lax.dot_general(
                onehot, rhs_of(b), (((1,), (1,)), ((), ())),
                precision=prec,
                preferred_element_type=acc_dtype)            # (rows, lanes)
            out_ref[lead + (slice(f * rows, (f + 1) * rows),
                            slice(None))] += acc

    slots(0, n_real)
    if n_real < f_tile:
        pl.when(fi < last)(lambda: slots(n_real, f_tile))


def _hist_kernel(binned_ref, pos_ref, gh_ref, out_ref, *,
                 m_pad: int, f_tile: int, n_feat: int,
                 precision_mode: str, rpl: int, rpa: int,
                 rows: int, n_hi: int):
    """One (node_tile, feature_tile, row_tile) grid step.

    binned_ref: (f_tile, R) u8|int32 bin ids, feature-major
    pos_ref:    (1, R) int32 node position (-1 = inactive)
    gh_ref:     (2, R) f32|int32 grad/hess
    out_ref:    (f_tile * rows, n_hi * 2 * m_pad) accumulator for the
                m_pad nodes of THIS node tile (grid dim 0) — deep levels
                (n_node > m_pad) tile the node dim so the block never
                outgrows VMEM.
    n_feat:     the real feature count F: slots past it in the last
                feature tile run no dot (:func:`_feature_dots`).
    rpl:        row tiles per lane.  The solo call passes its whole
                row-tile count; the LANE-stacked call (gang-batched
                multi-tenant training, _hist_pallas_lanes_pre) packs L
                tenants' rows end-to-end along the row grid.
    rpa:        row tiles per accumulator block (:func:`_acc_tiles`):
                the block is zeroed at each lane's first row tile and
                then every ``rpa`` tiles of that lane.  ``rpa == rpl``
                (one block per lane and node tile) in the float modes
                and wherever a lane's rows fit one int32 accumulator;
                an int8 job past 16.7M rows sums each CHUNK of ``rpa``
                tiles exactly into a block of its own, and the caller
                widens and adds the chunks (:func:`_sum_chunks`).
    rows, n_hi: the fold (:func:`_fold_of`).  ``n_hi == 1``: ``rows ==
                n_bin``, the one-hot is over the bin id and every
                feature's dot shares one ``gh_exp``.  ``n_hi > 1`` (a
                shallow level, single node tile): the one-hot is over
                the bin id's low bits and each feature's right-hand
                operand holds the row's (g, h) in the lane of its bin
                id's high bits, channel and node; every output cell
                still sums the same rows in the same row-tile order as
                unfolded, so the sums are the same bit for bit.

    EVERY per-row operand keeps rows in the LANE dim: TPU arrays tile
    to (8, 128), so (N, 1)/(N, 2) operands are physically inflated
    128x/64x — the per-level reshape copies of the old (R, 1) pos
    alone cost ~5 ms/round at 1M rows (round-4 trace).  gh_exp is
    therefore built (2M, R) and the dot contracts both operands' lane
    dim (the natural NT matmul).
    """
    r_tile = binned_ref.shape[1]
    m2 = 2 * m_pad
    m_base = pl.program_id(0) * m_pad  # first global node of this tile

    tile = pl.program_id(2) % rpl          # row tile within this lane
    if rpa < rpl:
        tile = tile % rpa

    @pl.when(tile == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    hot_dtype = _mxu_mode(precision_mode)[0]
    pos = pos_ref[0:1, :]                                    # (1, R)
    # lane l of the right-hand operand: bin-id group l // 2M (0 when
    # unfolded), channel (l % 2M) // M, node l % M of this node tile
    sub = jax.lax.broadcasted_iota(jnp.int32, (n_hi * m2, r_tile), 0)
    if n_hi == 1:
        # gh_exp[l, r] = gh[l // m_pad, r] masked by (pos[r] == l % m_pad)
        node_of_sub = m_base + jnp.where(sub < m_pad, sub, sub - m_pad)
        ghsel = jnp.where(sub < m_pad, gh_ref[0:1, :], gh_ref[1:2, :])
        active = (pos == node_of_sub)                        # (2M, R)
        gh_exp = jnp.where(active, ghsel, 0).astype(hot_dtype)

        def rhs_of(b):
            return gh_exp
    else:
        # one key per lane, hi * M + node, and per row and feature,
        # (b >> shift) * M + pos: one compare selects the lane.  A row
        # that is in no node of the level (pos < 0, or past the level's
        # nodes) takes a key below every lane's
        hi_of_sub = sub // m2
        within = sub - hi_of_sub * m2
        is_h = within >= m_pad
        lane_key = hi_of_sub * m_pad + jnp.where(is_h, within - m_pad,
                                                 within)
        ghsel = jnp.where(is_h, gh_ref[1:2, :], gh_ref[0:1, :])
        pos_key = jnp.where((pos < 0) | (pos >= m_pad), -n_hi * m_pad, pos)
        shift = rows.bit_length() - 1

        def rhs_of(b):
            key = (b >> shift) * m_pad + pos_key             # (1, R)
            return jnp.where(key == lane_key, ghsel,
                             0).astype(hot_dtype)            # (lanes, R)
    # bins may arrive u8 (the entry's resident pre-transposed operand —
    # zero per-round transpose/layout-copy cost) or int32 (the
    # in-graph transpose fallback); widen in-register either way
    bins = binned_ref[:].astype(jnp.int32)                   # (f_tile, R)
    _feature_dots(bins, rhs_of, out_ref, (0,), pl.program_id(1),
                  n_feat=n_feat, rows=rows,
                  lo_mask=None if n_hi == 1 else rows - 1, f_tile=f_tile,
                  precision_mode=precision_mode)


def _unfold(out: jax.Array, f_pad: int, n_bin: int, rows: int, n_hi: int,
            m_pad: int) -> jax.Array:
    """A folded level's ``(..., f_pad * rows, n_hi * 2 * m_pad)`` block
    back to the unfolded kernel's ``(..., f_pad * n_bin, 2 * m_pad)``:
    bin ``hi * rows + lo`` of a feature sits at row ``lo``, lanes
    ``hi * 2M ...``; the bins past ``n_bin`` of a partly empty last
    group are cut away.  ``n_hi == 1`` passes through: no op is added
    to the program."""
    if n_hi == 1:
        return out
    lead = out.shape[:-2]
    k = len(lead)
    out = out.reshape(lead + (f_pad, rows, n_hi, 2 * m_pad))
    out = out.transpose(tuple(range(k)) + (k, k + 2, k + 1, k + 3))
    out = out.reshape(lead + (f_pad, n_hi * rows, 2 * m_pad))[..., :n_bin, :]
    return out.reshape(lead + (f_pad * n_bin, 2 * m_pad))


def _rows_per_acc(r_tile: int) -> int:
    """Rows one int32 accumulator block may sum in int8 mode: every row
    adds at most 127 to a cell, so ``rows * 127 < 2^31``.  The largest
    power of two under that bound (2^24 = 16,777,216), in whole row
    tiles: 8192 tiles at the default ``r_tile`` of 2048."""
    bound = (2 ** 31 - 1) // 127
    return max(r_tile, (1 << (bound.bit_length() - 1)) // r_tile * r_tile)


def _acc_tiles(n_tiles: int, r_tile: int, precision: str,
               rows_per_acc=None) -> tuple:
    """``(rpa, n_chunks)``: row tiles per accumulator block and the
    blocks a lane's ``n_tiles`` take.  One block where the sums are
    float32; at most :func:`_rows_per_acc` rows' worth a block where
    they are int32.  No mode changes with the row count: int8 past
    16.7M rows takes more blocks, not another precision.
    ``rows_per_acc`` is for tests (a small job in several chunks)."""
    if precision != "int8":
        return n_tiles, 1
    if rows_per_acc is None:
        rows_per_acc = _rows_per_acc(r_tile)
    assert rows_per_acc % r_tile == 0 and rows_per_acc * 127 < 2 ** 31
    rpa = min(n_tiles, rows_per_acc // r_tile)
    return rpa, -(-n_tiles // rpa)


def _sum_chunks(out: jax.Array, n_chunks: int, axis: int) -> jax.Array:
    """Widen the exact int32 sums of ``n_chunks`` row chunks, laid along
    ``axis``, to float32 and add them; traced once per level, so the
    gauge ``xgbtpu_hist_row_chunks`` holds the last level's count.  One
    chunk (every job under 16.7M rows) passes through untouched: no op
    is added to the program."""
    from xgboost_tpu.obs import training_metrics
    training_metrics().hist_row_chunks.set(float(n_chunks))
    if n_chunks == 1:
        return out
    with jax.named_scope("grow.widen"):
        shape = out.shape[:axis] + (n_chunks, -1) + out.shape[axis + 1:]
        return out.reshape(shape).astype(jnp.float32).sum(axis=axis)


def _tiling(N: int, F: int, n_bin: int):
    """(r_tile, f_tile, n_pad, f_pad) — level-independent (f_tile's
    lane bound max(2M, 128) = 128 for every m_pad <= 64)."""
    # read at trace time: changing it after the first same-shape call
    # has no effect (jit cache) — set it before the first training
    # round.  2048 was the best size in the pre-round records (not
    # re-measured on this machine); 4096 and 8192 also compile and
    # verify on the installed Mosaic (chip_smoke.py --kernels, PR 22).
    r_tile = int(os.environ.get("XGBTPU_HIST_RTILE", "2048"))
    # feature tile sized so the output block (f_tile*B, 2M) f32 stays
    # ~<=1MB of VMEM
    f_tile = max(1, min(F, (256 * 1024) // (max(n_bin, 1) * 128)))
    # TPU tile rule: a block's sublane dim must be a multiple of 8 OR
    # equal the full array dim
    if f_tile < F:
        f_tile = max(8, (f_tile // 8) * 8)
    return (r_tile, f_tile, _round_up(max(N, 1), r_tile),
            _round_up(F, f_tile))


def quantize_gh(gh: jax.Array) -> tuple:
    """Symmetric per-channel int8 quantization of (..., N, 2) grad/hess
    (batched leading axes quantize per slice): (gh_q int32, scale f32).
    Quantize ONCE per round — g is fixed within a round; int8 products
    accumulate exactly in int32 so this is the only error source
    (~scale/254 per element, vs bf16's ~0.2% relative truncation)."""
    scale = jnp.maximum(jnp.max(jnp.abs(gh), axis=-2), 1e-30)
    gh_q = jnp.clip(jnp.round(gh / scale[..., None, :] * 127.0),
                    -127, 127).astype(jnp.int32)
    return gh_q, scale


def host_transpose_bins(binned_host, n_bin: int):
    """HOST-side (F, n_pad) u8 pre-transpose — built once per dataset
    and kept device-resident (standard layout) so the kernel pays zero
    per-round transpose and none of the per-pallas-call layout copies
    the in-graph transpose incurs (~7 ms/round at 1M x 28, round-4
    trace).  Returns None when the feature dim would be tiled (u8
    sublane tiles need 32-multiples; only the full-dim case is
    supported — F <= f_tile, true for the default bin counts)."""
    import numpy as np
    N, F = binned_host.shape
    r_tile, f_tile, n_pad, f_pad = _tiling(N, F, n_bin)
    if f_tile != F or n_bin > 256:
        # u8 can't hold >256 bin ids (binning emits uint16 there), and
        # a tiled feature dim would break the u8 (32, 128) tile rule
        return None
    bt = np.zeros((F, n_pad), np.uint8)
    bt[:, :N] = np.asarray(binned_host, np.uint8).T
    return bt


def transpose_bins(binned: jax.Array, n_bin: int) -> jax.Array:
    """(N, F) bins -> the kernel's padded (f_pad, n_pad) int32 operand.
    Compute ONCE per tree: left per level, XLA re-materializes the
    112 MB transpose+pad inside the fused round scan every level
    (measured ~7 ms/round of copies at 1M x 28 — round-4 trace)."""
    N, F = binned.shape
    r_tile, f_tile, n_pad, f_pad = _tiling(N, F, n_bin)
    binned_t = binned.astype(jnp.int32).T
    if n_pad != N or f_pad != F:
        binned_t = jnp.pad(binned_t, ((0, f_pad - F), (0, n_pad - N)))
    return binned_t


# A feature whose bin ids all fall below this is NARROW: its level
# histogram is built at this many bins.  The int8 one-hot's sublane tile
# (_fold_of's floor), so the narrow kernel call is the unfolded program
# with one shared gh_exp at every level, and _tiling's VMEM rule gives
# it 8x the features a tile of the 256-bin call
NARROW_BIN = 32


def narrow_features(n_cuts, n_bin: int) -> tuple:
    """The features whose largest bin id, ``n_cuts + 1`` (bin 0 is the
    missing bin), falls below :data:`NARROW_BIN`: static, from the cuts
    alone.  Empty at ``n_bin`` <= NARROW_BIN: no call is narrower."""
    import numpy as np
    if n_bin <= NARROW_BIN:
        return ()
    return tuple(int(f) for f in
                 np.flatnonzero(np.asarray(n_cuts) + 1 < NARROW_BIN))


def width_classes(F: int, n_bin: int, narrow: tuple):
    """The kernel calls a level histogram of ``F`` features takes, one
    per width class: ``((features, width), ...)``, ``features`` the
    class's feature ids in ascending order, or None for all F in order.
    None where no feature is narrow (or ``n_bin`` is no wider than
    :data:`NARROW_BIN`): ONE call at ``n_bin``, the program as it always
    was.  Otherwise the wide features at ``n_bin``, then the ``narrow``
    ones (:func:`narrow_features`) at NARROW_BIN bins, whose rows 32 ...
    n_bin - 1 no bin id reaches: the same sums, at 32 one-hot rows a
    feature in place of n_bin."""
    if not narrow or n_bin <= NARROW_BIN:
        return None
    assert max(narrow) < F, "narrow feature ids past the feature count"
    if len(narrow) == F:
        return ((None, NARROW_BIN),)
    ns = set(narrow)
    return ((tuple(f for f in range(F) if f not in ns), n_bin),
            (tuple(sorted(ns)), NARROW_BIN))


def _runs(ids) -> list:
    """Consecutive ids as ``[start, stop)`` runs, in order."""
    runs = []
    for i in ids:
        if runs and runs[-1][1] == i:
            runs[-1][1] = i + 1
        else:
            runs.append([i, i + 1])
    return runs


def transpose_classes(binned: jax.Array, classes: tuple) -> tuple:
    """One kernel operand per width class (:func:`width_classes`): the
    class's columns of ``binned`` in their order, as :func:`transpose_bins`
    lays them out at the class's width; runs of consecutive columns are
    slices, not a gather."""
    out = []
    for idx, width in classes:
        cols = binned if idx is None else jnp.concatenate(
            [binned[:, a:b] for a, b in _runs(idx)], axis=1)
        out.append(transpose_bins(cols, width))
    return tuple(out)


@functools.partial(jax.jit, static_argnames=(
    "n_node", "n_bin", "precision", "interpret"))
def build_level_histogram_pallas(binned: jax.Array, gh: jax.Array,
                                 pos: jax.Array, n_node: int, n_bin: int,
                                 precision: str = "fp32",
                                 interpret: bool = False) -> jax.Array:
    """Pallas drop-in for ``histogram.build_level_histogram``.

    Args match the XLA version; ``precision`` selects the MXU mode:
    "fp32" (HIGHEST, exact f32 — parity-testable against the scatter),
    "bf16" (DEFAULT, ~3x faster; operands truncated to bf16 inside the
    MXU, accumulation still f32), or "int8" (gradients quantized per
    call to 8 bits, int32-exact accumulation, ~9x the bf16 kernel —
    element error ~s/254 vs bf16's ~0.2% relative truncation).  The mode
    asked for is the mode that runs at ANY row count: past 16.7M rows
    int8 sums row chunks of at most 2^24 rows each exactly in int32 and
    adds the chunks in float32 (:func:`_acc_tiles`).

    Returns (n_node, F, n_bin, 2) float32.
    """
    N, F = binned.shape
    binned_t = transpose_bins(binned, n_bin)
    if precision == "int8":
        gh_in, scale = quantize_gh(gh)
    else:
        gh_in, scale = gh.astype(jnp.float32), None
    return _hist_pallas_pre(binned_t, gh_in, scale, pos, (N, F), n_node,
                            n_bin, precision, interpret)


def _hist_pallas_pre(binned_t, gh_in, scale, pos, nf, n_node: int,
                     n_bin: int, precision: str, interpret: bool,
                     native: bool = False, rows_per_acc=None,
                     classes=None) -> jax.Array:
    """Kernel invocation on PREPARED operands (transpose_bins /
    quantize_gh hoisted to once per tree/round by the grow loop): the
    kernel's raw sums (:func:`_level_raws`), then their tail
    (:func:`_hist_level_tail`).

    ``native=True`` returns the kernel's own ``(F, B, 2, n_node)``
    layout (node minor) without the relayout transpose — consumed by
    split.find_best_splits_native; callers gate on n_node <= 64
    (single node tile).  ``rows_per_acc`` (tests only) forces the int8
    row chunks of :func:`_acc_tiles` at a small size.  ``classes``
    (:func:`width_classes`): ``binned_t`` is :func:`transpose_classes`'
    tuple, one kernel call a class."""
    raw = _level_raws(binned_t, gh_in, pos, nf, n_node, n_bin, precision,
                      interpret, rows_per_acc, classes=classes)
    return _hist_level_tail(raw, scale, nf, n_node, n_bin, precision, native,
                            classes=classes)


def _level_raws(binned_t, gh_in, pos, nf, n_node: int, n_bin: int,
                precision: str, interpret: bool, rows_per_acc=None,
                first=None, classes=None):
    """The raw block of a level (:func:`_hist_level_raw`), or with
    ``classes`` (:func:`width_classes`) a tuple of one block per width
    class, each from its own kernel call on its class's operand (the
    tuple ``binned_t``, :func:`transpose_classes`) at its class's width:
    every cell a class holds sums the rows it sums in the one call at
    ``n_bin``, in the same row-tile order."""
    if classes is None:
        return _hist_level_raw(binned_t, gh_in, pos, nf, n_node, n_bin,
                               precision, interpret, rows_per_acc, first)
    N, F = nf
    return tuple(
        _hist_level_raw(bt, gh_in, pos, (N, F if idx is None else len(idx)),
                        n_node, width, precision, interpret, rows_per_acc,
                        first, narrow=width < n_bin, again=c > 0)
        for c, (bt, (idx, width)) in enumerate(zip(binned_t, classes)))


def _hist_level_raw(binned_t, gh_in, pos, nf, n_node: int, n_bin: int,
                    precision: str, interpret: bool, rows_per_acc=None,
                    first=None, narrow: bool = False,
                    again: bool = False) -> jax.Array:
    """The ``hist_level_rows`` kernel and nothing that changes a sum:
    ``(n_chunks * n_m_tiles, f_pad * n_bin, 2 * m_pad)``, block ``c *
    n_m_tiles + t`` the sums of row chunk ``c`` for the nodes of node
    tile ``t``, lane ``channel * m_pad + node``, unfolded
    (:func:`_unfold` is a permutation).  In int8 mode these are the
    accumulators' exact int32 sums; float32 otherwise, one chunk.
    ``first``: whether this is the first level its tree builds, for the
    gauges that sum over a tree's levels (:func:`_note_level`); a level
    of one node where the caller does not say.  ``narrow`` and
    ``again``: this call builds the narrow width class of the level,
    and is its second call (:func:`_level_raws`)."""
    N, F = nf
    r_tile, f_tile, n_pad, f_pad = _tiling(N, F, n_bin)
    n_tiles = n_pad // r_tile
    rpa, n_chunks = _acc_tiles(n_tiles, r_tile, precision, rows_per_acc)
    # deep levels tile the node dim at 64 (lane dim 2*64 = one full MXU
    # pass) so the accumulator block stays VMEM-bounded at any depth
    m_pad = min(n_node, 64)
    n_m_tiles = -(-n_node // m_pad)
    # rows ride the LANE dim of every per-row operand (see _hist_kernel)
    pos_t = jnp.pad(pos.astype(jnp.int32), (0, n_pad - N),
                    constant_values=-1)[None, :]             # (1, n_pad)
    gh_t = jnp.pad(gh_in.T, ((0, 0), (0, n_pad - N)))        # (2, n_pad)

    rows, n_hi = _fold_of(n_bin, m_pad, precision)
    _note_level(n_node == 1 if first is None else first, rows, n_m_tiles,
                f_pad // f_tile, F, narrow, again)

    out_dtype = jnp.int32 if precision == "int8" else jnp.float32
    kernel = functools.partial(_hist_kernel, m_pad=m_pad,
                               f_tile=f_tile, n_feat=F,
                               precision_mode=precision,
                               rpl=n_tiles, rpa=rpa, rows=rows, n_hi=n_hi)
    # a chunk's blocks follow the previous chunk's along the node-tile
    # axis; with one chunk the index map is the plain one
    if n_chunks == 1:
        def out_index(mi, fi, ri):
            return (mi, fi, 0)
    else:
        def out_index(mi, fi, ri):
            return (ri // rpa * n_m_tiles + mi, fi, 0)
    out = pl.pallas_call(
        kernel,
        grid=(n_m_tiles, f_pad // f_tile, n_tiles),
        in_specs=[
            pl.BlockSpec((f_tile, r_tile), lambda mi, fi, ri: (fi, ri)),
            pl.BlockSpec((1, r_tile), lambda mi, fi, ri: (0, ri)),
            pl.BlockSpec((2, r_tile), lambda mi, fi, ri: (0, ri)),
        ],
        out_specs=pl.BlockSpec((1, f_tile * rows, n_hi * 2 * m_pad),
                               out_index),
        out_shape=jax.ShapeDtypeStruct(
            (n_chunks * n_m_tiles, f_pad * rows, n_hi * 2 * m_pad),
            out_dtype),
        interpret=interpret,
        name="hist_level_rows",
    )(binned_t, pos_t, gh_t)
    return _unfold(out, f_pad, n_bin, rows, n_hi, m_pad)


def _hist_level_tail(raw, scale, nf, n_node: int, n_bin: int,
                     precision: str, native: bool = False,
                     slots: bool = False, classes=None) -> jax.Array:
    """A level's raw block (:func:`_hist_level_raw`'s, built or derived)
    to the histogram the finders read: the row chunks widened and added,
    the layout, the cut to the real nodes and features, the dequantize.
    ``(n_node, F, n_bin, 2)`` float32, or ``(F, n_bin, 2, n_node)`` when
    ``native``.  ``slots``: the block holds its nodes where
    :func:`_node_slot` puts them, not tile by tile in order.

    With ``classes`` (:func:`width_classes`) ``raw`` is one block per
    width class, and each class's histogram is padded with zero cells
    from its width to ``n_bin`` bins (the cells no bin id of its
    features reaches, zero in the one call too) and set back among the
    others in the features' own order, runs of consecutive features as
    slices, before the one dequantize: the same histogram, bit for
    bit."""
    N, F = nf
    if classes is None:
        out = _tail_layout(raw, F, n_node, n_bin, native, slots)
    else:
        axis = 0 if native else 1           # the feature axis
        parts = []
        for block, (idx, width) in zip(raw, classes):
            part = _tail_layout(block, F if idx is None else len(idx),
                                n_node, width, native, slots)
            if width < n_bin:
                pad = [(0, 0)] * part.ndim
                pad[axis + 1] = (0, n_bin - width)
                part = jnp.pad(part, pad)
            parts.append(part)
        out = _in_feature_order(parts, classes, axis)
    if precision == "int8":
        # dequantize the exact int32 sums back to f32 cell values
        s = scale / 127.0
        out = out.astype(jnp.float32) * (s[None, None, :, None] if native
                                         else s[None, None, None, :])
    return out


def _tail_layout(raw, F: int, n_node: int, n_bin: int, native: bool,
                 slots: bool) -> jax.Array:
    """:func:`_hist_level_tail` of one block up to the dequantize."""
    m_pad = min(n_node, 64)
    n_m_tiles = -(-n_node // m_pad)
    f_pad = raw.shape[1] // n_bin
    out = _sum_chunks(raw, raw.shape[0] // n_m_tiles, 0)
    if native:
        assert n_m_tiles == 1, "native layout needs a single node tile"
        return out.reshape(f_pad, n_bin, 2, m_pad)[:F, :, :, :n_node]
    # (m_tiles, f_pad*B, 2M) -> (m_tiles, F, B, 2, M) -> (m_tiles*M, F, B, 2),
    # node t * M + j from tile t, lane j; with ``slots`` node j * m_tiles + t
    out = out.reshape(n_m_tiles, f_pad, n_bin, 2, m_pad)
    out = out.transpose(((4, 0) if slots else (0, 4)) + (1, 2, 3)).reshape(
        n_m_tiles * m_pad, f_pad, n_bin, 2)
    return out[:n_node, :F, :, :]


def _in_feature_order(parts, classes, axis: int) -> jax.Array:
    """The width classes' histograms (``parts``, features along ``axis``
    in each class's order) as one, features in their own order: a
    concatenation of slices, one a run of features that are consecutive
    in one class and in the whole."""
    if len(parts) == 1:
        return parts[0]
    pieces = []                         # (first feature, class, position, n)
    for c, (idx, _) in enumerate(classes):
        p = 0
        for a, b in _runs(idx):
            pieces.append((a, c, p, b - a))
            p += b - a
    return jnp.concatenate(
        [jax.lax.slice_in_dim(parts[c], p, p + n, axis=axis)
         for _, c, p, n in sorted(pieces)], axis=axis)


def _node_slot(pos: jax.Array, n_node: int) -> jax.Array:
    """Where the int8 raw block of a level of ``n_node`` nodes holds node
    ``pos`` (-1, no node, stays -1).  Past one node tile of 64, node c of
    T = n_node / 64 tiles sits in tile c mod T, lane c div T: the order
    in which :func:`_derive_level` lays out a level's children without
    moving a lane (the children of the parent in tile u, lane j are in
    tiles 2u and 2u + 1, lane j).  Up to 64 nodes, node c itself."""
    T = n_node // 64
    if T <= 1:
        return pos
    return jnp.where(pos >= 0, (pos % T) * 64 + pos // T, -1)


def _derive_level(parent, left, parent_split, n_node: int) -> jax.Array:
    """The raw block of a level of ``n_node`` = 2M nodes from its
    parent level's raw block and the block of its LEFT children alone
    (both ``(n_chunks * T, f_pad * n_bin, 2 * m)``, M = T * m nodes, as
    :func:`_hist_level_raw` lays them out, nodes where
    :func:`_node_slot` puts them), chunk by chunk in int32:
    node 2p is ``left[p]``, node 2p + 1 is ``parent[p] - left[p]``
    where parent p split.  A parent that became a leaf parked its rows:
    both children are empty, and ``parent - 0`` would hand its whole
    mass to a node that does not exist, so the mask is not optional.
    Every row of a parent that split is in exactly one of its children,
    so the difference IS the sum the kernel would have made over the
    right child's rows: the same integer."""
    M = n_node // 2
    m = min(M, 64)                      # nodes a tile of parent / left
    T = M // m
    fb = parent.shape[1]
    shape = (-1, T, fb, 2, m)
    left = left.reshape(shape)
    # parent p = j * T + u is tile u's lane j
    split = parent_split.reshape(m, T).T.reshape(1, T, 1, 1, m)
    right = jnp.where(split, parent.reshape(shape) - left, 0)
    if 2 * m <= 64:                     # one tile of 2m nodes
        out = jnp.stack([left, right], axis=-1)  # (.., 2, m, child)
        return out.reshape(-1, fb, 4 * m)
    # m == 64: tile u's 128 children are the level's tiles 2u (the left
    # ones) and 2u + 1 (the right), each in its parent's lane
    # (_node_slot's order): tiles stacked, no lane moves
    return jnp.stack([left, right], axis=2).reshape(-1, fb, 128)


def _hist_pallas_derived(binned_t, gh_in, scale, pos, parent, parent_split,
                         nf, n_node: int, n_bin: int, interpret: bool,
                         native: bool = False, rows_per_acc=None,
                         classes=None) -> tuple:
    """``(histogram, raw block)`` of a level of ``n_node`` nodes in int8
    mode, the raw block kept for the level below.  With ``parent`` (the
    level above's raw block; ``parent_split`` (n_node / 2,) bool, the
    parents that split) the kernel builds the LEFT children only, at
    half the node lanes — ``pos`` even, as node ``pos >> 1`` of n_node /
    2; every other row in no node — and each right child is parent -
    left (:func:`_derive_level`): bit for bit the block the kernel
    builds at ``n_node`` nodes (each node where :func:`_node_slot` puts
    it, as the level below finds its parents), at the price of the
    level above, since
    the kernel's time goes with its node lanes and one-hot rows and not
    with the rows in a node (PERF.md section 7).  Without ``parent``
    (the first level a tree builds) every node is built.  With
    ``classes`` (:func:`width_classes`) the raw block, ``parent`` and
    the left children are each a tuple of one block per width class,
    and each class derives on its own."""
    if parent is None:
        raw = _level_raws(binned_t, gh_in, _node_slot(pos, n_node), nf,
                          n_node, n_bin, "int8", interpret, rows_per_acc,
                          first=True, classes=classes)
    else:
        pos_left = jnp.where((pos >= 0) & (pos & 1 == 0), pos >> 1, -1)
        left = _level_raws(binned_t, gh_in,
                           _node_slot(pos_left, n_node // 2), nf,
                           n_node // 2, n_bin, "int8", interpret,
                           rows_per_acc, first=False, classes=classes)
        raw = (_derive_level(parent, left, parent_split, n_node)
               if classes is None else
               tuple(_derive_level(p, lf, parent_split, n_node)
                     for p, lf in zip(parent, left)))
        from xgboost_tpu.obs import training_metrics
        training_metrics().hist_derived_levels.inc(1.0)
    return (_hist_level_tail(raw, scale, nf, n_node, n_bin, "int8", native,
                             slots=n_node > 64, classes=classes), raw)


def raw_block_shape(nf, n_node: int, n_bin: int, rows_per_acc=None) -> tuple:
    """Shape of the int8 raw block of a level (:func:`_hist_level_raw`)."""
    N, F = nf
    r_tile, _, n_pad, f_pad = _tiling(N, F, n_bin)
    n_chunks = _acc_tiles(n_pad // r_tile, r_tile, "int8", rows_per_acc)[1]
    m_pad = min(n_node, 64)
    return (n_chunks * -(-n_node // m_pad), f_pad * n_bin, 2 * m_pad)


def raw_block_zeros(nf, n_node: int, n_bin: int, classes=None):
    """int32 zeros in the structure of a level's int8 raw block: one
    block, or with ``classes`` a tuple of one per width class."""
    if classes is None:
        return jnp.zeros(raw_block_shape(nf, n_node, n_bin), jnp.int32)
    N, F = nf
    return tuple(jnp.zeros(raw_block_shape(
        (N, F if idx is None else len(idx)), n_node, width), jnp.int32)
        for idx, width in classes)


def _hist_pallas_lanes_pre(binned_t, gh_in, scale, pos, nf, n_node: int,
                           n_bin: int, precision: str, interpret: bool,
                           native: bool = False,
                           rows_per_acc=None) -> jax.Array:
    """LANE-stacked kernel invocation: a leading axis L batches WHOLE
    tenant datasets (gang-batched multi-tenant training — each lane has
    its own bins, so the tree-batched kernel's shared one-hot does not
    apply).  Lanes pack end-to-end along the ROW grid dimension at
    per-lane n_pad granularity, and the output index map gives every
    (lane, node tile) its own accumulator block: each lane's block sees
    exactly the row-tile sequence (content, order, and tile grouping)
    of that lane's solo :func:`_hist_pallas_pre` call, so per-lane
    results are BITWISE identical to solo — including signed zeros —
    in every precision mode.  One launch, L x the solo grid.  A lane
    past one int32 accumulator's rows takes the solo call's row chunks
    (:func:`_acc_tiles`): more blocks along the same axis.

    binned_t (L, f_pad, n_pad); gh_in (L, N, 2) f32|int32;
    scale (L, 2) f32 in int8 mode else None; pos (L, N) int32.
    Returns (L, n_node, F, B, 2) f32 — or (L, F, B, 2, n_node) when
    ``native`` (n_node <= 64, as solo)."""
    L = binned_t.shape[0]
    N, F = nf
    r_tile, f_tile, n_pad, f_pad = _tiling(N, F, n_bin)
    m_pad = min(n_node, 64)
    n_m_tiles = -(-n_node // m_pad)
    rpl = n_pad // r_tile  # row tiles per lane
    rpa, n_chunks = _acc_tiles(rpl, r_tile, precision, rows_per_acc)
    pos_t = jnp.pad(pos.astype(jnp.int32), ((0, 0), (0, n_pad - N)),
                    constant_values=-1).reshape(1, L * n_pad)
    gh_t = jnp.pad(gh_in, ((0, 0), (0, n_pad - N), (0, 0)))
    gh_t = gh_t.transpose(2, 0, 1).reshape(2, L * n_pad)
    bt = binned_t.transpose(1, 0, 2).reshape(f_pad, L * n_pad)

    rows, n_hi = _fold_of(n_bin, m_pad, precision)
    _note_level(n_node == 1, rows, n_m_tiles, f_pad // f_tile, F)

    out_dtype = jnp.int32 if precision == "int8" else jnp.float32
    kernel = functools.partial(_hist_kernel, m_pad=m_pad,
                               f_tile=f_tile, n_feat=F,
                               precision_mode=precision,
                               rpl=rpl, rpa=rpa, rows=rows, n_hi=n_hi)

    def acc_of(ri):                 # lane-major, a lane's chunks within
        if n_chunks == 1:
            return ri // rpl
        return ri // rpl * n_chunks + ri % rpl // rpa
    out = pl.pallas_call(
        kernel,
        grid=(n_m_tiles, f_pad // f_tile, L * rpl),
        in_specs=[
            pl.BlockSpec((f_tile, r_tile), lambda mi, fi, ri: (fi, ri)),
            pl.BlockSpec((1, r_tile), lambda mi, fi, ri: (0, ri)),
            pl.BlockSpec((2, r_tile), lambda mi, fi, ri: (0, ri)),
        ],
        out_specs=pl.BlockSpec(
            (1, f_tile * rows, n_hi * 2 * m_pad),
            lambda mi, fi, ri: (acc_of(ri) * n_m_tiles + mi, fi, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (L * n_chunks * n_m_tiles, f_pad * rows, n_hi * 2 * m_pad),
            out_dtype),
        interpret=interpret,
        name="hist_level_lanes",
    )(bt, pos_t, gh_t)

    out = _sum_chunks(
        out.reshape(L, n_chunks * n_m_tiles, -1, n_hi * 2 * m_pad),
        n_chunks, 1)
    out = _unfold(out, f_pad, n_bin, rows, n_hi, m_pad)
    out = out.reshape(L, n_m_tiles, f_pad, n_bin, 2, m_pad)
    if native:
        assert n_m_tiles == 1, "native layout needs a single node tile"
        out = out.reshape(L, f_pad, n_bin, 2, m_pad)[:, :F, :, :, :n_node]
        if precision == "int8":
            out = (out.astype(jnp.float32)
                   * (scale / 127.0)[:, None, None, :, None])
        return out
    out = out.transpose(0, 1, 5, 2, 3, 4).reshape(
        L, n_m_tiles * m_pad, f_pad, n_bin, 2)
    out = out[:, :n_node, :F, :, :]
    if precision == "int8":
        out = (out.astype(jnp.float32)
               * (scale / 127.0)[:, None, None, None, :])
    return out


@functools.partial(jax.jit, static_argnames=(
    "n_node", "n_bin", "precision", "interpret"))
def build_level_histogram_pallas_lanes(binned: jax.Array, gh: jax.Array,
                                       pos: jax.Array, n_node: int,
                                       n_bin: int, precision: str = "fp32",
                                       interpret: bool = False) -> jax.Array:
    """Lane-stacked histogram from RAW per-lane operands: binned
    (L, N, F), gh (L, N, 2), pos (L, N) -> (L, n_node, F, B, 2) f32,
    bitwise equal to stacking L solo
    :func:`build_level_histogram_pallas` calls.  Selected by the
    batched-bins branch of the histogram custom_vmap rules, i.e. by
    ``jax.vmap`` over tenant lanes (gang-batched multi-tenant
    training)."""
    L, N, F = binned.shape
    binned_t = jax.vmap(lambda b: transpose_bins(b, n_bin))(binned)
    if precision == "int8":
        gh_in, scale = quantize_gh(gh)               # per-lane (L, 2)
    else:
        gh_in, scale = gh.astype(jnp.float32), None
    return _hist_pallas_lanes_pre(binned_t, gh_in, scale, pos, (N, F),
                                  n_node, n_bin, precision, interpret)


def _batched_hist_kernel(binned_ref, pos_ref, gh_ref, out_ref, *,
                         n_bin: int, m_pad: int, f_tile: int, n_feat: int,
                         t_tile: int, precision_mode: str, rpa: int):
    """Tree-batched variant of :func:`_hist_kernel`: the (B, R) one-hot
    is built ONCE per (feature, row tile) and contracted against a
    (R, t_tile*2M) operand whose lane l encodes (tree, grad/hess, node):
    t = l // 2M, hess = (l % 2M) >= M, node = l % M.  Per-tree positions
    and gradients differ; the bins (and hence the one-hot — the VPU-
    bound part of the kernel) do not, so a K-class round's histogram
    cost approaches one class's instead of K's.

    The tree dim is grid-tiled (grid dim 1) so lanes and the output
    block stay VMEM-bounded at any ensemble width (num_parallel_tree
    forests): per step only ``t_tile`` trees' lanes are resident.

    binned_ref: (f_tile, R) int32;  pos_ref: (t_tile, R) int32;
    gh_ref: (2*t_tile, R) f32|int32, per-tree (g_t, h_t) sublane pairs.
    out_ref: (1, 1, f_tile*n_bin, t_tile*2*m_pad), zeroed every ``rpa``
    row tiles (one int8 row chunk, :func:`_acc_tiles`).
    Rows ride the LANE dim of every per-row operand and gh_exp is
    (lanes, R) with an NT dot, for the same physical-tiling reason as
    :func:`_hist_kernel`.
    """
    r_tile = binned_ref.shape[1]
    m2 = 2 * m_pad
    lanes = t_tile * m2
    m_base = pl.program_id(0) * m_pad

    @pl.when(pl.program_id(3) % rpa == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    sub = jax.lax.broadcasted_iota(jnp.int32, (lanes, r_tile), 0)
    t_of = sub // m2
    within = sub - t_of * m2
    node_of = m_base + jnp.where(within < m_pad, within, within - m_pad)
    is_h = within >= m_pad

    # per-sublane gh/pos selected by tree id via t_tile broadcast
    # compares (tiles are small; dynamic gathers would serialize)
    gh_dtype = jnp.int32 if precision_mode == "int8" else jnp.float32
    ghsel = jnp.zeros((lanes, r_tile), gh_dtype)
    possel = jnp.zeros((lanes, r_tile), jnp.int32)
    for t in range(t_tile):
        sel = t_of == t
        gval = jnp.where(is_h, gh_ref[2 * t + 1:2 * t + 2, :],
                         gh_ref[2 * t:2 * t + 1, :])
        ghsel = jnp.where(sel, gval, ghsel)
        possel = jnp.where(sel, pos_ref[t:t + 1, :], possel)

    gh_exp = jnp.where(possel == node_of, ghsel,
                       0).astype(_mxu_mode(precision_mode)[0])

    bins = binned_ref[:].astype(jnp.int32)
    _feature_dots(bins, lambda b: gh_exp, out_ref, (0, 0), pl.program_id(2),
                  n_feat=n_feat, rows=n_bin, lo_mask=None, f_tile=f_tile,
                  precision_mode=precision_mode)


@functools.partial(jax.jit, static_argnames=(
    "n_node", "n_bin", "precision", "interpret"))
def build_level_histogram_pallas_batched(binned: jax.Array, gh: jax.Array,
                                         pos: jax.Array, n_node: int,
                                         n_bin: int, precision: str = "fp32",
                                         interpret: bool = False) -> jax.Array:
    """Tree-batched histogram: gh (T, N, 2), pos (T, N), binned (N, F).

    Returns (T, n_node, F, n_bin, 2) f32, bitwise equal (in fp32 mode)
    to stacking T calls of :func:`build_level_histogram_pallas`.
    Selected by the custom_vmap rule of
    :func:`xgboost_tpu.ops.histogram.build_level_histogram`, i.e. by
    ``jax.vmap`` of tree growth over an ensemble axis.
    """
    T, N, _ = gh.shape
    F = binned.shape[1]
    if precision == "int8":
        gh, scale = quantize_gh(gh)                  # per-tree (T, 2)
    else:
        scale = None
    return _hist_pallas_batched_pre(
        transpose_bins_batched(binned, n_bin, T, min(n_node, 64),
                               precision), gh, scale,
        pos, (N, F), n_node, n_bin, precision, interpret)


def _hist_pallas_batched_prequant(binned, gh_in, scale, pos, n_node: int,
                                  n_bin: int, precision: str,
                                  interpret: bool,
                                  native: bool = False) -> jax.Array:
    """Batched kernel from RAW bins + pre-quantized gradients (the
    ensemble vmap rule of the prep path: batched tiling depends on the
    tree count, so the transpose happens here per call).  ``native``
    emits (T, F, B, 2, n_node) in the same single relayout pass the
    standard order takes."""
    T, N, _ = gh_in.shape
    F = binned.shape[1]
    return _hist_pallas_batched_pre(
        transpose_bins_batched(binned, n_bin, T, min(n_node, 64),
                               precision), gh_in,
        scale, pos, (N, F), n_node, n_bin, precision, interpret,
        native=native)


def transpose_bins_batched(binned, n_bin: int, T: int, m_pad: int,
                           precision: str):
    """Padded (f_pad, n_pad) int32 operand for the BATCHED kernel (its
    r/f tiling depends on the tree count, level and precision)."""
    N, F = binned.shape
    r_tile, f_tile, _, n_pad, f_pad, *_ = _tiling_batched(
        N, F, n_bin, T, m_pad, precision)
    binned_t = binned.astype(jnp.int32).T
    if n_pad != N or f_pad != F:
        binned_t = jnp.pad(binned_t, ((0, f_pad - F), (0, n_pad - N)))
    return binned_t


def _t_tile_of(T, m2, n_bin):
    """Trees per grid step: t_tile trees give lanes = t_tile*2M and an
    output block of f_tile*B x lanes f32, both VMEM-bounded at ANY
    ensemble width (num_parallel_tree forests)."""
    return max(1, min(T, max(1, 768 // m2),
                      (2 << 20) // (8 * max(n_bin, 1) * m2 * 4)))


def _tiling_batched(N, F, n_bin, T, m_pad, precision):
    """Per-LEVEL r/f tiling for the batched kernel (the batched path
    re-transposes its bins per call, so no cross-level layout sharing
    is needed).  Returns (r_tile, f_tile, t_tile, n_pad, f_pad,
    lanes)."""
    r_tile = int(os.environ.get("XGBTPU_HIST_RTILE", "2048"))
    m2 = 2 * m_pad
    t_tile = _t_tile_of(T, m2, n_bin)
    lanes = t_tile * m2
    # the (r_tile, lanes) gh_exp operand: cap at ~3MB of VMEM or Mosaic
    # fails to place the kernel (seen at fp32, lanes=768, r_tile=2048).
    # int8 mode's ghsel/possel INTERMEDIATES are int32, so it budgets
    # like fp32 (scoped-vmem OOM otherwise — seen at 6 trees, B=64)
    esize = 2 if precision == "bf16" else 4
    r_cap = max(512, ((3 << 20) // (max(lanes, 128) * esize))
                // 512 * 512)
    r_tile = min(r_tile, r_cap)
    # f_tile: multiple of 8 (or the whole feature dim), output block
    # f_tile*B x lanes f32 <= ~2MB
    f_tile = max(8, min(F, (512 * 1024) // (max(n_bin, 1) *
                                            max(lanes, 128))))
    if f_tile < F:
        f_tile = max(8, (f_tile // 8) * 8)
    return (r_tile, f_tile, t_tile, _round_up(max(N, 1), r_tile),
            _round_up(F, f_tile), lanes)


def _hist_pallas_batched_pre(binned_t, gh, scale, pos, nf, n_node: int,
                             n_bin: int, precision: str,
                             interpret: bool,
                             native: bool = False,
                             rows_per_acc=None) -> jax.Array:
    N, F = nf
    T = gh.shape[0]
    m_pad = min(n_node, 64)
    n_m_tiles = -(-n_node // m_pad)
    m2 = 2 * m_pad
    r_tile, f_tile, t_tile, n_pad, f_pad, lanes = _tiling_batched(
        N, F, n_bin, T, m_pad, precision)
    t_tiles = -(-T // t_tile)
    T_pad = t_tiles * t_tile
    n_tiles = n_pad // r_tile
    rpa, n_chunks = _acc_tiles(n_tiles, r_tile, precision, rows_per_acc)
    _note_level(n_node == 1, n_bin, n_m_tiles, f_pad // f_tile, F)  # no fold
    if n_pad != N or T_pad != T:
        gh = jnp.pad(gh, ((0, T_pad - T), (0, n_pad - N), (0, 0)))
        pos = jnp.pad(pos, ((0, T_pad - T), (0, n_pad - N)),
                      constant_values=-1)

    # per-tree (g, h) SUBLANE pairs, rows in lanes (see _hist_kernel's
    # physical-tiling note): (T, N, 2) -> (t_tiles, 2*t_tile, N).  The
    # tree-tile axis is a LEADING (squeezed) block dim so the block's
    # sublane dim is the whole t_tile axis: a (t_tile, R) block of a
    # flat (T_pad, N) array is refused by the TPU lowering whenever
    # t_tile < T_pad is not a multiple of 8 (9 classes at a 64-node
    # level: t_tile 6 of T_pad 12)
    gh_flat = gh.transpose(0, 2, 1).reshape(t_tiles, 2 * t_tile, n_pad)
    pos_t = pos.astype(jnp.int32).reshape(t_tiles, t_tile, n_pad)

    kernel = functools.partial(_batched_hist_kernel, n_bin=n_bin,
                               m_pad=m_pad, f_tile=f_tile, n_feat=F,
                               t_tile=t_tile, precision_mode=precision,
                               rpa=rpa)
    out_dtype = jnp.int32 if precision == "int8" else jnp.float32
    out = pl.pallas_call(
        kernel,
        grid=(n_m_tiles, t_tiles, f_pad // f_tile, n_tiles),
        in_specs=[
            pl.BlockSpec((f_tile, r_tile), lambda mi, ti, fi, ri: (fi, ri)),
            pl.BlockSpec((None, t_tile, r_tile),
                         lambda mi, ti, fi, ri: (ti, 0, ri)),
            pl.BlockSpec((None, 2 * t_tile, r_tile),
                         lambda mi, ti, fi, ri: (ti, 0, ri)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, f_tile * n_bin, lanes),
            lambda mi, ti, fi, ri: (ri // rpa * n_m_tiles + mi, ti, fi, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_chunks * n_m_tiles, t_tiles, f_pad * n_bin, lanes), out_dtype),
        interpret=interpret,
        name="hist_level_trees",
    )(binned_t, pos_t,
      gh_flat if precision == "int8" else gh_flat.astype(jnp.float32))
    out = _sum_chunks(out, n_chunks, 0)

    # (m_tiles, t_tiles, f_pad*B, t_tile*2M) -> (T, m_tiles*M, F, B, 2)
    out = out.reshape(n_m_tiles, t_tiles, f_pad, n_bin, t_tile, 2, m_pad)
    if native:
        # ONE relayout straight to (T, F, B, 2, m_tiles*M) — composing
        # the standard transpose with a to-native pass would copy the
        # whole histogram twice per level
        out = out.transpose(1, 4, 2, 3, 5, 0, 6).reshape(
            T_pad, f_pad, n_bin, 2, n_m_tiles * m_pad)
        out = out[:T, :F, :, :, :n_node]
        if precision == "int8":
            out = (out.astype(jnp.float32)
                   * (scale / 127.0)[:, None, None, :, None])
        return out
    out = out.transpose(1, 4, 0, 6, 2, 3, 5).reshape(
        T_pad, n_m_tiles * m_pad, f_pad, n_bin, 2)
    out = out[:T, :n_node, :F, :, :]
    if precision == "int8":
        out = (out.astype(jnp.float32)
               * (scale / 127.0)[:, None, None, None, :])
    return out


def _nst_kernel(pos_ref, gh_ref, out_ref, *, m_pad: int):
    """Per-node (G, H) sums for one row tile: ones @ gh_exp on the MXU."""
    r_tile = pos_ref.shape[0]
    m2 = 2 * m_pad

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    pos = pos_ref[:, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (r_tile, m2), 1)
    node_of_lane = jnp.where(lane < m_pad, lane, lane - m_pad)
    ghsel = jnp.where(lane < m_pad, gh_ref[:, 0:1], gh_ref[:, 1:2])
    gh_exp = jnp.where(pos[:, None] == node_of_lane, ghsel, 0.0)
    ones = jnp.ones((8, r_tile), jnp.float32)
    out_ref[:] += jax.lax.dot_general(
        ones, gh_exp, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_node", "interpret"))
def node_stats_pallas(gh: jax.Array, pos: jax.Array, n_node: int,
                      interpret: bool = False) -> jax.Array:
    """Pallas drop-in for ``histogram.node_stats``: (n_node, 2) f32.

    Exact (HIGHEST-precision dot against a ones matrix — sums of f32
    values, bit-comparable to the scatter up to addition order).
    """
    N = gh.shape[0]
    r_tile = 2048
    n_pad = _round_up(max(N, 1), r_tile)
    if n_pad != N:
        gh = jnp.pad(gh, ((0, n_pad - N), (0, 0)))
        pos = jnp.pad(pos, (0, n_pad - N), constant_values=-1)
    kernel = functools.partial(_nst_kernel, m_pad=n_node)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // r_tile,),
        in_specs=[
            pl.BlockSpec((r_tile, 1), lambda ri: (ri, 0)),
            pl.BlockSpec((r_tile, 2), lambda ri: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((8, 2 * n_node), lambda ri: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 2 * n_node), jnp.float32),
        interpret=interpret,
        name="hist_level_node_stats",
    )(pos.reshape(-1, 1).astype(jnp.int32), gh.astype(jnp.float32))
    return out[0].reshape(2, n_node).T  # (n_node, 2)
