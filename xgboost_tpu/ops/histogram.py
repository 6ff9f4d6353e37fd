"""Gradient/hessian histogram accumulation.

The TPU-native replacement for the reference's per-thread histogram
loops (``src/tree/updater_histmaker-inl.hpp:296-348``): one scatter-add
over ``(node, feature, bin)`` cells per tree level, executed on device.
Every (active) row contributes exactly one bin per feature — including
the reserved missing bin 0 — so the per-node totals equal the bin-sums
of any single feature.

A Pallas kernel variant lives in :mod:`xgboost_tpu.ops.pallas_hist`
(selected automatically on TPU); this XLA scatter is the portable path.
Selection happens in ONE place, :func:`hist_backend`: env
``XGBTPU_HIST`` = ``pallas`` | ``pallas_bf16`` | ``pallas_int8`` |
``scatter`` overrides; default is the Pallas kernel on TPU backends,
scatter elsewhere; a pallas impl off-TPU runs interpreted.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp


class HistBackend(NamedTuple):
    """What builds the level histograms in this process.  ``interpret``
    is True when a pallas impl was chosen off-TPU (tests,
    ``XGBTPU_HIST=pallas*`` on CPU): the kernels then run through the
    Pallas interpreter, not Mosaic."""
    impl: str        # scatter | pallas | pallas_bf16 | pallas_int8
    interpret: bool


def hist_backend(precision: str = "auto") -> HistBackend:
    """THE backend -> (impl, interpret) choice; every kernel call site
    below asks here.  A process whose TPU runtime failed to start falls
    to the CPU backend and therefore to scatter — correct for tests and
    CPU users, so a script that claims to run on the chip must assert
    on this result (chip_smoke.py does) instead of trusting that
    training finished."""
    on_tpu = jax.default_backend() == "tpu"
    if precision == "fixed":
        # deterministic fixed-point accumulation: always the scatter
        # path (on every backend) with int32 cells — see FIXED_SCALE.
        return HistBackend("scatter", False)
    forced = os.environ.get("XGBTPU_HIST", "")
    if forced:
        if forced not in ("pallas", "pallas_bf16", "pallas_int8",
                          "scatter"):
            raise ValueError(
                f"XGBTPU_HIST={forced!r}: expected one of "
                "'pallas', 'pallas_bf16', 'pallas_int8', 'scatter'")
        return HistBackend(forced, forced != "scatter" and not on_tpu)
    # evaluated at trace time; the default backend decides the kernel.
    # `precision` is the named TrainParam hist_precision (recorded in
    # saved models: accuracy-affecting precision must be a visible
    # parameter, not an env-var default): fp32 selects exact-f32
    # histograms; bf16 takes the bf16 MXU pass; int8 — the TPU auto
    # default — quantizes gradients to 8 bits per call with
    # int32-exact accumulation.  The row count changes none of this:
    # past 16.7M rows int8 sums row chunks of 2^24 rows in int32 blocks
    # of their own (pallas_hist._acc_tiles).  kernel_mode() names what
    # runs, for the spans.
    if not on_tpu:
        return HistBackend("scatter", False)
    return HistBackend({"fp32": "pallas", "bf16": "pallas_bf16"}.get(
        precision, "pallas_int8"), False)


_KERNEL_MODE = {"pallas": "fp32", "pallas_bf16": "bf16",
                "pallas_int8": "int8"}


def kernel_mode(precision: str = "auto") -> str:
    """The histogram mode that runs in this process for the TrainParam
    ``hist_precision``: ``int8`` | ``bf16`` | ``fp32`` (the Pallas
    kernel's), ``fixed`` or ``scatter`` (XLA scatter, int32 fixed-point
    or float32 cells).  The ``hist_mode`` attribute of ``train.launch``."""
    if precision == "fixed":
        return "fixed"
    return _KERNEL_MODE.get(hist_backend(precision).impl, "scatter")


@functools.lru_cache(maxsize=None)
def _pallas_hist_vmappable(n_node: int, n_bin: int, precision: str,
                           interpret: bool):
    """Pallas histogram wrapped in custom_vmap: ``jax.vmap`` over an
    ensemble axis (multiclass groups / num_parallel_tree forests,
    SURVEY.md §2.4.5) dispatches to the tree-batched kernel that builds
    the one-hot once and packs trees into MXU lanes, instead of vmap's
    default grid-prepend batching of the per-tree kernel (measured ~2x
    slower than even sequential launches).  lru-cached so the wrapped
    identity is stable for jit caches."""
    from jax.custom_batching import custom_vmap
    from xgboost_tpu.ops.pallas_hist import (
        build_level_histogram_pallas, build_level_histogram_pallas_batched,
        build_level_histogram_pallas_lanes)

    @custom_vmap
    def hist(binned, gh, pos):
        return build_level_histogram_pallas(
            binned, gh, pos, n_node, n_bin, precision=precision,
            interpret=interpret)

    @hist.def_vmap
    def _rule(axis_size, in_batched, binned, gh, pos):
        binned_b, gh_b, pos_b = in_batched
        if binned_b:
            # batched BINS = tenant lanes (gang-batched multi-tenant
            # training): no one-hot sharing possible, but the lane
            # kernel grid-packs L whole datasets into one launch with
            # per-lane accumulators — bitwise equal per lane to the
            # solo kernel (the stacked-vs-solo model byte contract)
            gg = gh if gh_b else jnp.broadcast_to(
                gh, (axis_size,) + gh.shape)
            pp = pos if pos_b else jnp.broadcast_to(
                pos, (axis_size,) + pos.shape)
            out = build_level_histogram_pallas_lanes(
                binned, gg, pp, n_node, n_bin, precision=precision,
                interpret=interpret)
            return out, True
        gg = gh if gh_b else jnp.broadcast_to(gh, (axis_size,) + gh.shape)
        pp = pos if pos_b else jnp.broadcast_to(pos, (axis_size,) + pos.shape)
        out = build_level_histogram_pallas_batched(
            binned, gg, pp, n_node, n_bin, precision=precision,
            interpret=interpret)
        return out, True

    return hist


# hist_precision="fixed": gradients are rounded to multiples of
# 1/FIXED_SCALE and accumulated in int32.  Integer addition is exactly
# associative, so the per-(node, feature, bin) sums — and therefore the
# grown trees — are bitwise identical for ANY grouping of the rows:
# single device, or row shards combined by `lax.psum` over a data mesh
# of any size (the mesh-fused parity contract,
# tests/test_mesh_fused.py).  Resolution: |g| <= 2^20/FIXED_SCALE per
# row before saturation matters; cells overflow at ~2^31/(FIXED_SCALE
# * max|g|) rows per (node, bin) — ~1M unit-scale rows at 2^11.
FIXED_SCALE = 2048.0


def dequantize_hist(hist: jax.Array) -> jax.Array:
    """Undo the "fixed" mode's int32 fixed-point encoding AFTER the
    cross-shard reduction (identity on float histograms/node stats)."""
    if jnp.issubdtype(hist.dtype, jnp.integer):
        return hist.astype(jnp.float32) * jnp.float32(1.0 / FIXED_SCALE)
    return hist


class HistPrep(NamedTuple):
    """Once-per-tree precompute for the level loop (prepare_hist):
    leaving these per level costs ~7 ms/round of re-materialized
    transposes + ~2 ms of re-quantization at 1M x 28 (round-4 trace).
    ``gh_in`` is f32 grad/hess, or int32 quantized with ``scale`` set
    in int8 mode."""
    binned: jax.Array            # the original (N, F) bins
    binned_t: jax.Array          # (f_pad, n_pad) int32 kernel operand
    gh_in: jax.Array             # (N, 2) f32 | int32
    scale: object                # (2,) f32 in int8 mode, else None
    precision: str               # the kernel mode hist_precision
    #                              resolved to: fp32 | bf16 | int8
    #                              (kernel_mode; never by the row count)


def prepare_hist(binned, gh, n_bin: int, precision: str = "auto",
                 binned_t=None):
    """Build a :class:`HistPrep` for the pallas path, or None when the
    scatter fallback is active (callers pass prep straight through to
    :func:`build_level_histogram`).  ``binned_t`` is an optional
    RESIDENT pre-transposed operand (pallas_hist.host_transpose_bins,
    built once per dataset by the learner entry)."""
    mode = _KERNEL_MODE.get(hist_backend(precision).impl)
    if mode is None:
        return None
    from xgboost_tpu.ops import pallas_hist as ph
    if mode == "int8":
        gh_in, scale = ph.quantize_gh(gh)
    else:
        gh_in, scale = gh.astype(jnp.float32), None
    if binned_t is None:
        binned_t = ph.transpose_bins(binned, n_bin)
    return HistPrep(binned, binned_t, gh_in, scale, mode)


@functools.lru_cache(maxsize=None)
def _pallas_hist_pre_vmappable(n_node: int, n_bin: int, precision: str,
                               interpret: bool, native: bool = False):
    """custom_vmap wrapper over PREPARED operands: the unbatched call
    runs the kernel on the hoisted transpose/quantization; a vmapped
    ensemble axis dispatches to the tree-batched kernel from the raw
    bins (its tiling depends on the tree count, so it re-transposes —
    cheap at ensemble workloads' row counts).

    ``hist(binned, binned_t, gh_in, pos)`` in the float modes;
    ``hist(binned, binned_t, gh_in, pos, scale, *parent)`` ->
    ``(histogram, raw)`` in int8, where the unbatched call also hands
    out the kernel's exact int32 block, and takes the level above's
    where it is given (``parent`` = its raw block and the parents that
    split) to build the left children only
    (pallas_hist._hist_pallas_derived).  The batched kernels build
    every node as they always did: their rules take no parent and hand
    out a block of zeros nothing reads (the level below is batched
    too), so what ``grow_tree`` carries has one shape either way.

    ``native`` returns the kernel's (F, B, 2, n_node) layout (see
    pallas_hist._hist_pallas_pre); the batched rule asks the batched
    kernel for the native order directly (its single relayout pass
    emits either order — no extra transpose either way)."""
    from jax.custom_batching import custom_vmap
    from xgboost_tpu.ops import pallas_hist as ph
    int8 = precision == "int8"

    def batched(axis_size, in_batched, binned, binned_t, gh_in, pos,
                scale=None):
        def bc(x, b):
            return x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
        gh_in, pos = bc(gh_in, in_batched[2]), bc(pos, in_batched[3])
        if int8:
            scale = bc(scale, in_batched[4])
        if in_batched[0]:
            # batched bins = tenant lanes: the lane kernel rides the
            # prepared operands straight through (per-lane int8 scales
            # dequantize per lane after the launch)
            return ph._hist_pallas_lanes_pre(
                bc(binned_t, in_batched[1]), gh_in, scale, pos,
                (binned.shape[1], binned.shape[2]), n_node, n_bin,
                precision, interpret, native=native)
        return ph._hist_pallas_batched_prequant(
            binned, gh_in, scale, pos, n_node, n_bin, precision,
            interpret, native=native)

    if not int8:
        @custom_vmap
        def hist(binned, binned_t, gh_in, pos):
            return ph._hist_pallas_pre(binned_t, gh_in, None, pos,
                                       binned.shape, n_node, n_bin,
                                       precision, interpret, native=native)

        @hist.def_vmap
        def _rule(axis_size, in_batched, *args):
            return batched(axis_size, in_batched, *args), True
        return hist

    @custom_vmap
    def hist(binned, binned_t, gh_in, pos, scale, *parent):
        return ph._hist_pallas_derived(
            binned_t, gh_in, scale, pos, *(parent or (None, None)),
            binned.shape, n_node, n_bin, interpret, native=native)

    @hist.def_vmap
    def _rule(axis_size, in_batched, binned, binned_t, gh_in, pos, scale,
              *parent):
        out = batched(axis_size, in_batched, binned, binned_t, gh_in, pos,
                      scale)
        from xgboost_tpu.obs import training_metrics
        training_metrics().hist_derived_levels.set(0.0)
        nf = binned.shape[-2:]
        return ((out, jnp.zeros(ph.raw_block_shape(nf, n_node, n_bin),
                                jnp.int32)), (True, False))
    return hist


def build_level_histogram(binned: jax.Array, gh: jax.Array, pos: jax.Array,
                          n_node: int, n_bin: int,
                          precision: str = "auto",
                          prep=None, native: bool = False) -> jax.Array:
    """Accumulate per-(node, feature, bin) grad/hess sums for one level.

    Args:
      binned: (N, F) integer bin ids (0 = missing).
      gh:     (N, 2) grad/hess per row (zeros for subsampled-out rows).
      pos:    (N,) level-local node position in [0, n_node), -1 = inactive.
      n_node: static number of nodes at this level (2**depth).
      n_bin:  static number of bins B.
      precision: hist_precision TrainParam (auto | fp32 | bf16 | int8 |
              fixed).  "fixed" returns INT32 fixed-point sums (see
              FIXED_SCALE) — callers apply :func:`dequantize_hist`
              after their cross-shard reduction.
      prep:   optional :class:`HistPrep` from :func:`prepare_hist` —
              the level loop hoists the bins transpose and gradient
              quantization to once per tree instead of once per level.

    Returns: (n_node, F, B, 2) float32 — or the kernel-native
    (F, B, 2, n_node) when ``native`` (prep path only, n_node <= 64).
    """
    if prep is not None:
        return level_histogram_carried(pos, n_node, n_bin, precision, prep,
                                       native)[0]
    assert not native, "native layout requires the pallas prep path"
    impl, interpret = hist_backend(precision)
    if impl in _KERNEL_MODE:
        fn = _pallas_hist_vmappable(n_node, n_bin, _KERNEL_MODE[impl],
                                    interpret)
        return fn(binned, gh, pos)
    N, F = binned.shape
    f_ids = jnp.arange(F, dtype=jnp.int32)[None, :]
    flat = (pos[:, None] * F + f_ids) * n_bin + binned.astype(jnp.int32)
    # inactive rows (pos < 0) -> out-of-bounds index, dropped by the scatter
    flat = jnp.where(pos[:, None] < 0, n_node * F * n_bin, flat)
    if precision == "fixed":
        q = jnp.round(gh * FIXED_SCALE).astype(jnp.int32)
        hist = jnp.zeros((n_node * F * n_bin, 2), dtype=jnp.int32)
        hist = hist.at[flat].add(q[:, None, :], mode="drop")
        return hist.reshape(n_node, F, n_bin, 2)
    hist = jnp.zeros((n_node * F * n_bin, 2), dtype=jnp.float32)
    hist = hist.at[flat].add(gh[:, None, :], mode="drop")
    return hist.reshape(n_node, F, n_bin, 2)


def level_histogram_carried(pos: jax.Array, n_node: int, n_bin: int,
                            precision: str, prep: HistPrep,
                            native: bool = False, parent=None) -> tuple:
    """``(histogram, raw)`` of one level on the Pallas prep path, for a
    level loop that carries ``raw`` to the level below (``grow_tree``).

    Where the kernel's sums are integers (``prep.precision == "int8"``)
    ``raw`` is their exact int32 block, and a level given ``parent`` =
    (the level above's ``raw``, the (n_node / 2,) bool of the parents
    that split) builds its LEFT children only, at half the node lanes,
    and takes each right child as parent - left in int32: the same
    integers the kernel sums at ``n_node`` nodes, so the same histogram
    bit for bit, at the kernel time of the level above.  In the float
    modes ``raw`` is None and every node is built: a float32
    difference is not the sum the kernel would have made.  Under
    ``jax.vmap`` (trees, lanes) the batched kernels build every node."""
    fn = _pallas_hist_pre_vmappable(n_node, n_bin, prep.precision,
                                    hist_backend(precision).interpret,
                                    native)
    if prep.scale is None:
        return fn(prep.binned, prep.binned_t, prep.gh_in, pos), None
    return fn(prep.binned, prep.binned_t, prep.gh_in, pos, prep.scale,
              *(parent or ()))


def node_stats(gh: jax.Array, pos: jax.Array, n_node: int,
               precision: str = "auto") -> jax.Array:
    """Per-node (G, H) sums via segment-sum (reference GetNodeStats,
    ``updater_basemaker-inl.hpp:266-306``).  Returns (n_node, 2) —
    int32 fixed-point under ``precision="fixed"`` (same contract as
    :func:`build_level_histogram`: reduce first, then
    :func:`dequantize_hist`)."""
    if precision == "fixed":
        idx = jnp.where(pos < 0, n_node, pos)
        q = jnp.round(gh * FIXED_SCALE).astype(jnp.int32)
        out = jnp.zeros((n_node, 2), dtype=jnp.int32)
        return out.at[idx].add(q, mode="drop")
    impl, interpret = hist_backend()
    if impl.startswith("pallas"):
        from xgboost_tpu.ops.pallas_hist import node_stats_pallas
        return node_stats_pallas(gh, pos, n_node, interpret=interpret)
    idx = jnp.where(pos < 0, n_node, pos)
    out = jnp.zeros((n_node, 2), dtype=jnp.float32)
    return out.at[idx].add(gh, mode="drop")


def stats_from_histogram_native(hist: jax.Array) -> jax.Array:
    """Per-node (G, H) totals from the NATIVE (F, B, 2, n_node) layout:
    bin sums of feature 0 (same identity as stats_from_histogram)."""
    return hist[0].sum(axis=0).T


def stats_from_histogram(hist: jax.Array) -> jax.Array:
    """Per-node (G, H) totals as the bin-sums of feature 0 — every active
    row lands in exactly one bin of every feature (missing included), so
    any single feature's bin sums are the node totals.  Reusing the level
    histogram saves a full pass over the rows and keeps totals bitwise
    consistent with the children's partial sums under reduced-precision
    histogram accumulation."""
    return hist[:, 0, :, :].sum(axis=1)
