"""BoostLearner: objective + booster + metrics orchestration, and the
``train``/``cv`` front-end API.

Mirrors the reference's learner layer (``src/learner/learner-inl.hpp``:
``BoostLearner::UpdateOneIter/EvalOneIter/Predict`` :274-346) and the
Python surface (``wrapper/xgboost.py``: ``Booster`` :246-530, ``train``
with early stopping :533-632, ``cv``/``mknfold``/``aggcv`` :635-740).

Prediction caching: each DMatrix a Booster has seen keeps a device-side
binned matrix and a running margin, advanced incrementally per round —
the reference's pred_buffer/pred_counter design
(``gbtree-inl.hpp:304-353``) without the per-row tree walk.
"""

from __future__ import annotations

import json
import math
import os
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from xgboost_tpu.binning import _rank0 as _is_rank0
from xgboost_tpu.binning import bin_matrix, compute_cuts, holds_dense
from xgboost_tpu.config import TrainParam
from xgboost_tpu.data import DMatrix, MetaInfo, upload
from xgboost_tpu.metrics import create_metric
from xgboost_tpu.objectives import create_objective
from xgboost_tpu.obs import span, training_metrics

_MAGIC = "xgbtpu001"


def _predict_upload_depth() -> int:
    """Prefetch depth of the one-off prediction upload pipeline: how
    many f32 row blocks stage ahead of the quantize+traverse consuming
    them (external._prefetch_to_device).  2 = double-buffered (block
    k+1 uploads while block k computes); 1 = single lookahead; 0 =
    synchronous.  ``XGBTPU_PREDICT_UPLOAD_DEPTH`` is the A/B seam."""
    try:
        return max(0, int(os.environ.get("XGBTPU_PREDICT_UPLOAD_DEPTH",
                                         "2")))
    except ValueError:
        return 2


class _CacheEntry:
    """Per-DMatrix device state (the reference's CacheEntry,
    learner-inl.hpp:495-512)."""

    def __init__(self, dmat: DMatrix, binned: jax.Array, base_margin: jax.Array,
                 info=None, row_valid: Optional[jax.Array] = None,
                 n_real: Optional[int] = None, external: bool = False):
        self.dmat = dmat                 # strong ref: id(dmat) keys the cache
        self.binned = binned
        self.base = base_margin          # (N_pad, K)
        self.info = info if info is not None else dmat.info
        self.row_valid = row_valid       # None, or (N_pad,) bool when padded
        self.n_real = n_real if n_real is not None else dmat.num_row
        self.margin: Optional[jax.Array] = None
        self.applied = 0                 # trees folded into margin
        self.external = external         # paged matrix: margin lives on host
        self.root: Optional[jax.Array] = None  # per-row root slots (N_pad,)
        self.info_version = dmat.info.version  # source-snapshot tracking
        # group-padded rank layout (rank_device.PadRankPrep): rows are
        # RELAID (label-sorted, lane-padded per group), so user-facing
        # outputs must unmap via user_rows() instead of [:n_real]
        self.rank_pad_prep = None

    def user_rows(self, x):
        """User-row view of a host per-row array (rows = first axis):
        a [:n_real] slice for end-padded layouts, the static unmap
        gather for group-padded rank entries."""
        if self.rank_pad_prep is not None:
            return x[self.rank_pad_prep.user_map]
        return x[:self.n_real]


class LaneSpec(NamedTuple):
    """One tenant's gang-batching contract (:meth:`Booster.fused_lane_spec`):
    everything the lane-stacking driver (``pipeline/lanes.py``) needs to
    vmap this booster's next fused rounds alongside its shape-bucket
    peers in ONE device dispatch.  The static fields (cfg, finder and
    gradient identities, K/npar, pred_chunk, shapes) form the bucket
    key — lanes stack only when every static matches, so the stacked
    scan's compiled program is exactly the solo scan's under ``vmap``.
    The device fields are the solo scan's own operands; margins are
    already synced (``_sync_margin``) when the spec is handed out."""
    booster: "Booster"
    entry: _CacheEntry
    n_rows: int              # device row count (N_pad of the entry)
    n_features: int
    n_rounds: int
    first_iteration: int
    seg_k: int               # resolved rounds-per-dispatch segment size
    K: int                   # num_output_group
    npar: int                # num_parallel_tree
    cfg: object              # GrowConfig (hashable; static scan arg)
    split_finder: object     # stable identity or None
    grad_fn: object          # Objective.fused_grad (stable identity)
    pred_chunk: int
    subsample: float         # < 1.0 forbids row padding (N-shaped draws)
    binned: jax.Array        # (N, F) device bins
    margin: jax.Array        # (N, K) synced margins
    label: jax.Array
    weight: jax.Array
    base_key: jax.Array      # PRNGKey(seed) — the solo scan's own key
    cut_values: jax.Array    # (F, W) f32
    n_cuts: jax.Array        # (F,) int32
    row_valid: Optional[jax.Array]   # (N,) bool or None (= all real)


class Booster:
    """Learner handle (reference wrapper/xgboost.py Booster + BoostLearner)."""

    def __init__(self, params: Optional[dict] = None,
                 cache: Sequence[DMatrix] = (), model_file: Optional[str] = None):
        self.param = TrainParam.from_dict(params or {})
        self.obj = None
        self.gbtree = None
        self.num_feature = 0
        self._cache: Dict[int, _CacheEntry] = {}
        self.best_iteration: int = -1
        self.best_score: float = float("nan")
        self.attributes: Dict[str, str] = {}
        # bumped on every whole-model replacement (load_model/load_raw):
        # cache entries stamp it so a margin can never fold trees of two
        # different loaded ensembles (the hot-reload window-mixing guard)
        self._model_gen = 0
        self._mesh = None                  # resolved at _lazy_init (dsplit=row)
        self._col_mesh = None              # resolved at _lazy_init (dsplit=col)
        # EMA-FS feature screen (set_feature_screen): ascending FULL-
        # space feature ids fused training restricts its histogram
        # working set to; None = off (the bit-identical default path)
        self._feature_screen = None
        self._pending_cache = list(cache)  # bound at _lazy_init (needs cuts)
        if model_file is not None:
            self.load_model(model_file)

    # ----------------------------------------------------------- parameters
    def set_param(self, name, value=None):
        if isinstance(name, (dict, list, tuple)):
            from xgboost_tpu.config import params_to_dict
            for k, v in params_to_dict(name).items():
                self.param.set_param(k, v)
        else:
            self.param.set_param(name, value)
        self._reconfigure()

    def _init_obj(self):
        self.obj = create_objective(self.param.objective)
        self.obj.set_param("scale_pos_weight", self.param.scale_pos_weight)
        self.obj.set_param("num_class", self.param.num_class)
        self.obj.set_param("num_pairsample", self.param.num_pairsample)
        self.obj.set_param("fix_list_weight", self.param.fix_list_weight)
        self.obj.set_param("rank_impl", self.param.rank_impl)
        self.obj.set_param("seed", self.param.seed)

    def _reconfigure(self):
        """Propagate changed params into live objective/booster state, so
        continued training (xgb_model=...) honors new hyperparameters."""
        if self.obj is not None:
            self._init_obj()
        if self.gbtree is not None and self.param.booster != "gblinear":
            from xgboost_tpu.models.gbtree import make_grow_config
            self.gbtree.param = self.param
            self.gbtree.cfg = make_grow_config(self.param,
                                               self.gbtree.cuts.max_bin)
            # updater / sketch params may have changed the split finder
            self.gbtree._split_finder_cache = None
            self.gbtree._base_key_cache = None  # seed may have changed

    def set_feature_screen(self, kept=None) -> None:
        """Restrict FUSED training's histogram working set to ``kept``
        full-space feature ids (EMA-FS, ``ema_fs`` > 0 — see
        xgboost_tpu.stream): the (C, N, F) histogram build touches only
        the surviving columns, and grown trees are remapped back to the
        full feature space so model bytes, prediction, and eval are
        screen-free.  ``None`` clears the screen (the default path,
        bit-identical to a build that never heard of screening).  The
        screen applies only where it is safe and profitable — single
        device, in-memory dense entries, fused segments; every other
        path ignores it."""
        if kept is None:
            self._feature_screen = None
            return
        ids = sorted({int(i) for i in kept})
        if not ids or ids[0] < 0:
            raise ValueError(
                "feature screen must keep >= 1 valid feature id")
        self._feature_screen = tuple(ids)

    def rebind_cuts(self, cuts) -> None:
        """Swap the quantile cut matrix under the live model (online
        cut refresh — xgboost_tpu.stream): delegates the exact
        threshold-preserving remap to :meth:`GBTree.rebind_cuts`, then
        invalidates every cached binned entry/margin exactly like a
        whole-model load (the ``_load_np`` discipline) — stale bin ids
        quantized under the old cuts must never feed a gradient."""
        if self.gbtree is None or self.param.booster == "gblinear":
            raise ValueError(
                "rebind_cuts needs an initialized gbtree model")
        self.gbtree.rebind_cuts(cuts)
        self._cache.clear()
        self._model_gen += 1

    # ------------------------------------------------------------- init
    def _lazy_init(self, dtrain: DMatrix):
        if self.obj is None:
            self._init_obj()
        if self.gbtree is None:
            if self.param.booster == "gblinear":
                from xgboost_tpu.models.gblinear import GBLinear
                self.num_feature = dtrain.num_col
                self.gbtree = GBLinear(self.param, dtrain.num_col)
            else:
                from xgboost_tpu.models.gbtree import GBTree
                self.num_feature = dtrain.num_col
                with span("ingest.cuts", features=dtrain.num_col,
                          max_bin=self.param.max_bin) as sp:
                    cuts = self._propose_cuts(dtrain)
                    sp.set("dense", holds_dense(dtrain))
                self.gbtree = GBTree(self.param, cuts)
                if getattr(dtrain, "is_external", False):
                    # paged matrices route through the binned pipeline
                    # regardless of updater (see the sketch branch above)
                    self.gbtree.exact_raw = False
        if getattr(dtrain, "is_sharded", False) and self._mesh is None:
            # continued training (loaded model) on a split-loaded matrix:
            # mesh resolution belongs HERE, not in the entry builder
            self._mesh = dtrain.mesh
        if self.param.booster == "gblinear":
            # distributed gblinear (dsplit=row): rows shard over the mesh,
            # Gf/Hf reductions psum (VERDICT r2 item 10)
            from xgboost_tpu.parallel import mesh as pmesh
            if self.param.dsplit == "row" and self._mesh is None:
                self._mesh = pmesh.get_mesh() or pmesh.data_parallel_mesh()
        if self.param.booster != "gblinear":
            from xgboost_tpu.parallel import mesh as pmesh
            if self.param.dsplit == "row" and self._mesh is None:
                self._mesh = pmesh.get_mesh() or pmesh.data_parallel_mesh()
            elif self.param.dsplit == "col" and self._col_mesh is None:
                from xgboost_tpu.parallel.colsplit import feature_parallel_mesh
                m = pmesh.get_mesh()
                self._col_mesh = (m if m is not None
                                  and "feat" in m.axis_names
                                  else feature_parallel_mesh())
        for d in self._pending_cache:
            self._entry(d)
        self._pending_cache = []

    def _propose_cuts(self, dtrain: DMatrix):
        """The ``CutMatrix`` of a fresh gbtree model: whichever cut
        proposal the matrix kind, the updater and the split mode call
        for (may resolve ``self._mesh`` on the way)."""
        from xgboost_tpu.models.updaters import parse_updaters
        if getattr(dtrain, "is_sharded", False):
            # per-rank split loading: no process holds full
            # columns, so the cut proposal MUST be the device
            # sketch over the global mesh (SURVEY.md §5.8)
            if self.param.dsplit == "col":
                raise NotImplementedError(
                    "ShardedDMatrix is row-block loaded; "
                    "dsplit=col needs feature-shard loading "
                    "(load replicated for column split)")
            if "grow_colmaker" in parse_updaters(self.param.updater):
                raise NotImplementedError(
                    "updater=grow_colmaker (exact greedy) needs "
                    "cuts at every distinct value, which no "
                    "process can propose from a row shard; load "
                    "replicated for exact-greedy training")
            if self.param.objective.startswith("rank:"):
                raise NotImplementedError(
                    "ranking objectives need global group "
                    "structure, which row-block split loading "
                    "cannot provide; load replicated for "
                    "rank:* training")
            from xgboost_tpu.parallel.sketch_device import \
                sketch_cuts_global
            self._mesh = dtrain.mesh
            vals, w = dtrain.device_raw()
            cuts = sketch_cuts_global(
                self._mesh, vals, w, self.param.max_bin,
                self.param.sketch_eps, self.param.sketch_ratio)
            del vals, w  # transient raw floats: free before binning
        elif getattr(dtrain, "is_external", False):
            # streaming sketch over raw pages (SURVEY.md §5.7);
            # paged matrices always use the histogram method, as
            # in the reference (learner-inl.hpp:263-267) — even
            # for updater=grow_colmaker (exact_raw is cleared
            # below: paged training is binned end to end)
            cuts = dtrain.sketch_cuts(self.param.max_bin,
                                      self.param.sketch_eps,
                                      self.param.sketch_ratio)
        elif ("grow_colmaker" in parse_updaters(self.param.updater)
                and self.param.dsplit == "row"):
            # dsplit=row exact: cuts at every distinct value up
            # to max_exact_bin (the reference itself switches
            # away from exact under row split,
            # learner-inl.hpp:91-93 — this quantized form is
            # already more than it offers there)
            from xgboost_tpu.binning import compute_cuts_exact
            cuts = compute_cuts_exact(dtrain,
                                      self.param.max_exact_bin)
        elif "grow_colmaker" in parse_updaters(self.param.updater):
            # TRUE exact-greedy (models/colmaker.py): bin-free —
            # sorted raw-value scans at ANY cardinality; the
            # CutMatrix is a placeholder (nothing is quantized).
            # Under dsplit=col each shard scans its own raw
            # columns (colsplit.grow_tree_exact_colsplit — the
            # DistColMaker analog, exact at any cardinality,
            # round 5; previously capped at max_exact_bin cuts)
            from xgboost_tpu.binning import CutMatrix
            cuts = CutMatrix(
                np.full((dtrain.num_col, 1), np.inf, np.float32),
                np.zeros(dtrain.num_col, np.int32))
        elif self.param.dsplit == "row" and (
                self.param.device_sketch > 0
                or (self.param.device_sketch < 0
                    and jax.process_count() > 1)):
            # distributed cut proposal: per-shard device sketches
            # merged over the mesh axis — no host needs a full
            # column (SerializeReducer analog, SURVEY.md §5.8)
            from xgboost_tpu.parallel import mesh as pmesh
            from xgboost_tpu.parallel.sketch_device import \
                sketch_cuts_mesh
            if self._mesh is None:
                self._mesh = (pmesh.get_mesh()
                              or pmesh.data_parallel_mesh())
            cuts = sketch_cuts_mesh(
                self._mesh, dtrain.to_dense(), dtrain.info.weight,
                self.param.max_bin, self.param.sketch_eps,
                self.param.sketch_ratio)
        else:
            # explicit hist_bin_align>0 lifts the trim-margin
            # cap (unconditional alignment); auto keeps
            # binning.DEFAULT_TRIM_MARGIN
            margin_kw = ({"bin_align_margin": None}
                         if int(self.param.hist_bin_align) > 0
                         else {})
            cuts = compute_cuts(dtrain, self.param.max_bin,
                                self.param.sketch_eps,
                                self.param.sketch_ratio,
                                bin_align=self._bin_align(),
                                **margin_kw)
        return cuts

    @property
    def _K(self) -> int:
        return max(1, self.param.num_output_group)

    def _base_margin_of(self, dmat: DMatrix, n: int) -> jax.Array:
        bm = dmat.info.base_margin
        if bm is not None:
            return upload(np.asarray(bm, np.float32).reshape(n, self._K))
        base = self.obj.prob_to_margin(self.param.base_score)
        return jnp.full((n, self._K), base, jnp.float32)

    def _entry(self, dmat: DMatrix) -> _CacheEntry:
        key = id(dmat)
        if (key in self._cache
                and getattr(self._cache[key], "model_gen", 0)
                != self._model_gen):
            # the whole model was replaced (registry hot-reload /
            # load_model) since this entry was built: its incremental
            # margin folds the OLD ensemble's trees — rebuild rather
            # than mix tree windows (load_raw also clears the cache;
            # this stamp is the belt for entries handed out earlier or
            # a gbtree swapped in directly)
            del self._cache[key]
        if (key in self._cache and self._cache[key].external
                and dmat._binned_cuts is not self.gbtree.cuts):
            # another model re-quantized this matrix meanwhile: re-bin and
            # rebuild our margins from scratch
            self._cache[key] = self._build_ext_entry(dmat)
            self._cache[key].model_gen = self._model_gen
        if (key in self._cache
                and self._cache[key].info is not dmat.info
                and self._cache[key].info_version != dmat.info.version):
            # sharded entries snapshot the MetaInfo; a set_label/set_weight
            # after caching must rebuild the snapshot (stale device labels
            # would silently feed the gradients otherwise)
            del self._cache[key]
        if (key in self._cache
                and self._cache[key].rank_pad_prep is not None
                and (self._cache[key].info_version != dmat.info.version
                     or self.obj is None
                     or not self.param.objective.startswith("rank:")
                     or getattr(self.obj, "rank_impl", None) != "device")):
            # the group-padded rank layout is DERIVED from labels +
            # group_ptr (any set_field invalidates the relayout) and
            # only the device rank gradient understands it (a set_param
            # switching objective/rank_impl must rebuild a plain entry)
            del self._cache[key]
        if key not in self._cache:
            if self.num_feature and dmat.num_col > self.num_feature:
                raise ValueError(
                    f"data has {dmat.num_col} features, model was trained "
                    f"with {self.num_feature}")
            if getattr(dmat, "is_sharded", False):
                if self.param.booster == "gblinear":
                    raise NotImplementedError(
                        "gblinear works on raw feature columns; per-rank "
                        "split loading currently supports gbtree only")
                self._cache[key] = self._make_shard_loaded_entry(dmat)
            elif getattr(dmat, "is_external", False):
                self._cache[key] = self._build_ext_entry(dmat)
            elif self.param.booster == "gblinear":
                if self._mesh is not None:
                    # dsplit=row: rows shard over the mesh (the dense X
                    # plays the role binned ids play for gbtree)
                    self._cache[key] = self._make_sharded_entry(
                        dmat, binned_np=self.gbtree.host_matrix(dmat))
                else:
                    binned = self.gbtree.device_matrix(dmat)
                    self._cache[key] = _CacheEntry(
                        dmat, binned, self._base_margin_of(dmat, dmat.num_row))
            elif self._mesh is not None:
                self._cache[key] = self._make_sharded_entry(dmat)
            elif getattr(self.gbtree, "exact_raw", False):
                # exact mode is bin-free: entries hold RAW values (NaN =
                # missing); trees route by value comparison.  Under
                # dsplit=col the feature axis pads to the mesh with
                # all-NaN columns ONCE per matrix, before the single
                # device upload (they sort into the finder's trash
                # segment regardless of has_missing and can never win
                # a split); the host copy pads too so the rank build
                # sees the sharded width
                raw, has_miss, raw_host = self._raw_dense(
                    dmat, pad_multiple=(self._col_mesh.devices.size
                                        if self._col_mesh is not None
                                        else 1))
                entry = _CacheEntry(
                    dmat, raw,
                    self._base_margin_of(dmat, dmat.num_row))
                # static per-dataset fact: lets the exact grower elide
                # the default-left scan + end-of-scan candidates
                entry.exact_has_missing = has_miss
                entry.exact_ranks = None  # built lazily on first boost
                entry.exact_host = raw_host  # dropped after rank build
                self._cache[key] = entry
            elif self._rank_pad_ok(dmat):
                self._cache[key] = self._make_rank_padded_entry(dmat)
            else:
                bin_span = dict(rows=dmat.num_row, features=dmat.num_col,
                                dense=holds_dense(dmat))
                with span("ingest.bin", **bin_span):
                    binned_host = bin_matrix(dmat, self.gbtree.cuts)
                binned = upload(binned_host)
                if self._col_mesh is not None:
                    # pad the feature axis ONCE per matrix (padding per
                    # boosting round would re-copy the whole matrix)
                    from xgboost_tpu.parallel.colsplit import pad_features
                    binned = pad_features(
                        binned, self._col_mesh.devices.size, axis=1)
                entry = _CacheEntry(
                    dmat, binned, self._base_margin_of(dmat, dmat.num_row))
                from xgboost_tpu.ops.histogram import hist_backend
                if (self._mesh is None and self._col_mesh is None
                        and hist_backend(self.param.hist_precision
                                         ).impl.startswith("pallas")):
                    # resident pre-transposed histogram operand (zero
                    # per-round transpose/layout-copy cost; see
                    # pallas_hist.host_transpose_bins) — single-chip
                    # pallas path only: sharded paths re-transpose and
                    # the scatter fallback never reads it.  After the
                    # put above, so that copy overlaps the transpose
                    from xgboost_tpu.ops.pallas_hist import \
                        host_transpose_bins
                    with span("ingest.bin", **bin_span):
                        bt = host_transpose_bins(binned_host,
                                                 self.gbtree.cfg.n_bin)
                    entry.binned_t = None if bt is None else upload(bt)
                self._cache[key] = entry
            self._attach_root(self._cache[key], dmat)
            self._cache[key].model_gen = self._model_gen
        entry = self._cache[key]
        if (entry.info is dmat.info
                and entry.info_version != dmat.info.version):
            # plain entries SHARE the MetaInfo: label/weight freshness
            # rides info._dev_cache invalidation, but root and base
            # margin are entry-level snapshots — refresh them (and the
            # margin built on base) on any set_field
            entry.root = None
            self._attach_root(entry, dmat)
            if entry.external:
                # streaming-external entries keep the base HOST-side
                entry.base = np.asarray(
                    self._base_margin_of(dmat, dmat.num_row))
            else:
                entry.base = self._base_margin_of(dmat, dmat.num_row)
            entry.margin = None
            entry.applied = 0
            entry.info_version = dmat.info.version
        return entry

    def _attach_root(self, entry: _CacheEntry, dmat) -> None:
        """Per-row root slots (multi-root trees, reference root_index
        data.h:39-58), padded to the entry's device row count."""
        ri = getattr(dmat.info, "root_index", None)
        if ri is None or max(1, self.param.num_roots) <= 1:
            return
        if getattr(dmat, "is_sharded", False):
            raise NotImplementedError(
                "root_index on split-loaded matrices is not supported "
                "(per-rank placement of the root vector is unwired); "
                "load replicated for multi-root training")
        if entry.external:
            raise NotImplementedError(
                "root_index on external-memory matrices is not supported")
        n_dev = entry.binned.shape[0]
        r = np.zeros(n_dev, np.int32)
        r[:len(ri)] = np.asarray(ri, np.int64).astype(np.int32)
        if self._mesh is not None and not getattr(dmat, "is_sharded", False):
            entry.root = upload(r, self._shard_rows)
        else:
            entry.root = upload(r)

    def _build_ext_entry(self, dmat) -> _CacheEntry:
        """Entry for an external-memory matrix (not necessarily cached)."""
        if getattr(self.gbtree, "exact_raw", False):
            raise NotImplementedError(
                "exact-mode (grow_colmaker) models route on raw values; "
                "external-memory matrices are binned — load this matrix "
                "in memory (DMatrix) for exact-mode predict/eval/train")
        if self._col_mesh is not None:
            raise NotImplementedError(
                "external-memory matrices do not support dsplit=col "
                "(the reference routes paged matrices to the histogram "
                "row-split path too, learner-inl.hpp:263-267)")
        # (re)quantize when the matrix was binned with a DIFFERENT
        # model's cuts — reusing a stale memmap would silently compare
        # this model's cut indices against another model's bins
        if dmat._binned_mm is None or dmat._binned_cuts is not self.gbtree.cuts:
            dmat.build_binned(self.gbtree.cuts)
        # when the whole binned matrix fits the device budget, external
        # memory has done its job (bounded INGEST/sketch/quantize memory)
        # and training can take the in-memory fast path — one launch per
        # tree (or per fused run) instead of per (level, batch).  The
        # reference's HalfRAM variant is the same idea one level down
        # (page_dmatrix-inl.hpp:230-245: rows on disk, working set in
        # RAM); here the working set is the binned matrix in HBM.
        if dmat.fits_device_budget():
            binned_np = np.asarray(dmat._binned_mm)
            if self._mesh is not None:
                return self._make_sharded_entry(dmat, binned_np=binned_np)
            return _CacheEntry(
                dmat, upload(binned_np),
                jnp.asarray(self._base_margin_of(dmat, dmat.num_row)))
        return _CacheEntry(
            dmat, None, np.asarray(self._base_margin_of(dmat, dmat.num_row)),
            external=True)

    def _make_sharded_entry(self, dmat: DMatrix,
                            binned_np: Optional[np.ndarray] = None
                            ) -> _CacheEntry:
        """Pad rows to the mesh size and shard over the 'data' axis (the
        reference's per-rank row-shard loading, simple_dmatrix-inl.hpp:89-96,
        realized as device placement under one controller).  ``binned_np``
        skips re-binning (in-budget external matrices pass their memmap)."""
        shard = self._shard_rows
        n = dmat.num_row
        pad = (-n) % self._mesh.size
        with span("ingest.bin", rows=n, features=dmat.num_col,
                  dense=0 if binned_np is not None else holds_dense(dmat)):
            if binned_np is None:
                binned_np = bin_matrix(dmat, self.gbtree.cuts)
            if pad:
                binned_np = np.pad(binned_np, ((0, pad), (0, 0)))
        # host numpy -> global sharding directly: in multi-process mode
        # every process holds the full (replicated) host copy and
        # device_put places only its addressable shards
        binned = upload(binned_np, shard)
        row_valid = upload(np.arange(n + pad) < n, shard)
        info = _pad_info(dmat.info, n, pad, self._K)
        # device-resident SHARDED gradient inputs (row-aligned with the
        # margin); also avoids re-uploading label/weight every round
        if info.label is not None:
            info._dev_cache["label"] = upload(
                np.asarray(info.label, np.float32), shard)
        info._dev_cache[("weight", n + pad)] = upload(
            np.asarray(info.get_weight(n + pad), np.float32), shard)
        base = np.broadcast_to(
            np.asarray(self._base_margin_of(dmat, n)), (n, self._K))
        base = np.concatenate(
            [base, np.zeros((pad, self._K), np.float32)]) if pad else base
        base = upload(np.asarray(base, np.float32), shard)
        return _CacheEntry(dmat, binned, base, info=info,
                           row_valid=row_valid, n_real=n)

    def _shard_rows(self, host):
        """Place a host array with its rows sharded over the mesh."""
        from xgboost_tpu.parallel.dp import shard_rows
        return shard_rows(self._mesh, host)

    def _make_shard_loaded_entry(self, dmat) -> _CacheEntry:
        """Entry for a per-rank split-loaded matrix: every process bins
        ONLY its local row block; the global arrays are assembled from
        process-local data (``jax.make_array_from_process_local_data``)
        — the reference's per-rank shard loading
        (simple_dmatrix-inl.hpp:89-96) without any replicated host copy.

        Bit-compatibility: the global (padded) row layout is identical
        to :meth:`_make_sharded_entry`'s device placement of a
        replicated load over the same mesh, so training produces
        byte-identical models (tested in tests/test_launch.py)."""
        if self.param.objective.startswith("rank:"):
            raise NotImplementedError(
                "ranking objectives need global group structure, which "
                "row-block split loading cannot provide; load "
                "replicated for rank:*")
        n_loc = dmat.local_num_row
        K = self._K
        with span("ingest.bin", rows=n_loc, features=dmat.num_col,
                  dense=holds_dense(dmat._local)):
            binned_local = dmat.pad_local(
                bin_matrix(dmat._local, self.gbtree.cuts))
        binned = upload(binned_local, dmat.make_global)
        row_valid = dmat.row_valid_global()

        # the entry's info snapshot holds LOCAL host metadata (for label
        # validation + local metric partials) and GLOBAL device arrays
        # for the gradient kernels
        info = MetaInfo()
        info.label = dmat.info.label
        info.weight = dmat.info.weight
        info.base_margin = dmat.info.base_margin
        if info.label is not None:
            info._dev_cache["label"] = upload(
                dmat.pad_local(np.asarray(info.label, np.float32)),
                dmat.make_global)
        info._dev_cache[("weight", dmat.padded_global_rows)] = upload(
            dmat.pad_local(
                np.asarray(dmat.info.get_weight(n_loc), np.float32)),
            dmat.make_global)

        if getattr(dmat, "_full_base_margin", None) is not None:
            # sidecar base_margin holds GLOBAL (N, K) values; slice rows
            # here where K is known (multiclass-safe)
            base_local = np.asarray(
                dmat._full_base_margin, np.float32).reshape(
                    dmat.global_num_row, K)[dmat.row_start:dmat.row_end]
        elif dmat.info.base_margin is not None:
            base_local = np.asarray(
                dmat.info.base_margin, np.float32).reshape(n_loc, K)
        else:
            base_local = np.full(
                (n_loc, K), self.obj.prob_to_margin(self.param.base_score),
                np.float32)
        base = upload(dmat.pad_local(base_local), dmat.make_global)
        entry = _CacheEntry(dmat, binned, base, info=info,
                            row_valid=row_valid, n_real=dmat.global_num_row)
        return entry

    def _bin_align(self) -> int:
        """Bin-count alignment quantum for the cut proposal (see
        binning.align_cut_lists): 32 when the pallas histogram kernel
        will consume the bins (its int8 one-hot tiles sublanes in 32s),
        else 0.  hist_bin_align overrides (0 = never, >0 = quantum)."""
        hba = int(self.param.hist_bin_align)
        if hba >= 0:
            return hba
        from xgboost_tpu.ops.histogram import hist_backend
        return 32 if hist_backend(self.param.hist_precision
                                  ).impl.startswith("pallas") else 0

    def _announce_rank_path(self, entry) -> None:
        """One stderr line (first boost only) naming the LambdaRank
        gradient path chosen for the TRAINING matrix.  The group-padded
        and sort-based device paths train numerically DIFFERENT models
        (bf16 partner dot + lane tie-breaks vs unstable sort order —
        metric-parity tested, bit divergence documented in
        rank_device.py); the gate that picks between them is a
        heuristic, so the choice must be visible without reading
        docstrings (advisor, round 4).  Called from the boost path —
        not the entry builder — so eval-set entries never announce and
        the mesh-sharded branch (always sort-based) is covered too.
        ``XGBTPU_RANK_PAD=0`` forces sort-based; ``silent=1`` mutes."""
        if (getattr(self, "_rank_path_told", False)
                or not self.param.objective.startswith("rank:")
                or getattr(self.obj, "rank_impl", None) != "device"):
            return
        self._rank_path_told = True
        path = ("group-padded" if entry.rank_pad_prep is not None
                else "sort-based")
        if int(getattr(self.param, "silent", 0)) == 0 and _is_rank0():
            print(f"[rank] LambdaRank gradient path: {path} "
                  "(set XGBTPU_RANK_PAD=0 to force sort-based; "
                  "see README 'Ranking')", file=sys.stderr)

    def _rank_pad_ok(self, dmat) -> bool:
        """Gate for the group-padded rank layout (rank_device round 4):
        device LambdaRank, single chip, in-memory gbtree, grouped data
        with modest group sizes and small integer labels (bf16-exact in
        the one-hot partner dot).  ``XGBTPU_RANK_PAD=0`` disables."""
        info = dmat.info
        if (os.environ.get("XGBTPU_RANK_PAD", "1") == "0"
                or self.obj is None
                or not self.param.objective.startswith("rank:")
                or getattr(self.obj, "rank_impl", None) != "device"
                or self._col_mesh is not None
                or self._K != 1
                or info.group_ptr is None or len(info.group_ptr) < 2
                or info.label is None
                or (getattr(info, "root_index", None) is not None
                    and max(1, self.param.num_roots) > 1)):
            return False
        gptr = np.asarray(info.group_ptr, np.int64)
        sizes = np.diff(gptr)
        if len(sizes) == 0 or sizes.min() <= 0:
            return False
        G = len(sizes)
        L = max(8, int(-(-sizes.max() // 8) * 8))
        n = dmat.num_row
        # clamped at 256: lane positions/counts up to L must stay exact
        # in the bf16 one-hot partner dot (256 = 2^8 is the last exact
        # odd-step integer; see rank_device._lane_select)
        max_lane = min(256, int(os.environ.get("XGBTPU_RANK_PAD_MAXLANE",
                                               "256")))
        la = np.asarray(info.label)
        # padding blow-up economics: extra rows cost grower time
        # (~14 ms per 1M-row round) against the ~7.7 ms/1M the padded
        # gradient saves (tools/rank_inv_ab.py) — breakeven ~1.45x.
        # Small datasets take the padded path more liberally (absolute
        # cost is negligible; one code path to exercise).
        blow = (G * L + (n - int(gptr[-1]))) / max(n, 1)
        return (L <= max_lane
                and G * L * L <= (1 << 28)       # (G, L, L) plane budget
                and (blow <= 1.4 or (n <= 200_000 and blow <= 3.0))
                and bool(np.all(la >= 0)) and bool(np.all(la < 32))
                and bool(np.all(la == np.round(la))))

    def _make_rank_padded_entry(self, dmat) -> _CacheEntry:
        """Entry in the group-padded rank layout: group g owns slots
        [g*L, (g+1)*L), rows label-sorted within the group (the
        reference's bucket-skipping partner draw becomes a pure lane
        formula), padding slots carry bin 0 / zero gradients.  The
        per-round LambdaRank gradient then runs sort-free and
        gather-free (rank_device.rank_gradient_padded; measured 3.2 vs
        10.9 ms at 1M rows / 10k groups — tools/rank_inv_ab.py)."""
        from xgboost_tpu.rank_device import build_pad_prep
        info = dmat.info
        tag = ("rank_pad_prep",)
        if tag not in info._dev_cache:
            info._dev_cache[tag] = build_pad_prep(
                np.asarray(info.label, np.float32),
                np.asarray(info.group_ptr, np.int64))
        prep = info._dev_cache[tag]
        n_slots = prep.G * prep.L + prep.n_tail
        occupied = prep.pad_map >= 0                      # (n_slots,)
        src = prep.pad_map[occupied]

        bin_span = dict(rows=dmat.num_row, features=dmat.num_col,
                        dense=holds_dense(dmat))
        with span("ingest.bin", **bin_span):
            binned_host = bin_matrix(dmat, self.gbtree.cuts)
            binned_pad = np.zeros((n_slots, binned_host.shape[1]),
                                  binned_host.dtype)
            binned_pad[occupied] = binned_host[src]
        base = np.asarray(self._base_margin_of(dmat, dmat.num_row))
        base_pad = np.full((n_slots, self._K),
                           float(base.reshape(-1)[0]) if base.size
                           else 0.0, np.float32)
        base_pad[occupied] = base.reshape(dmat.num_row, self._K)[src]
        entry = _CacheEntry(
            dmat, upload(binned_pad), upload(base_pad),
            row_valid=upload(occupied), n_real=dmat.num_row)
        entry.rank_pad_prep = prep
        from xgboost_tpu.ops.histogram import hist_backend
        if hist_backend(self.param.hist_precision
                        ).impl.startswith("pallas"):
            from xgboost_tpu.ops.pallas_hist import host_transpose_bins
            with span("ingest.bin", **bin_span):
                bt = host_transpose_bins(binned_pad, self.gbtree.cfg.n_bin)
            entry.binned_t = None if bt is None else upload(bt)
        return entry

    def _raw_dense(self, dmat, pad_multiple: int = 1):
        """Dense raw-value matrix for exact mode (NaN = missing),
        feature-padded/truncated to the model width.  Returns
        (device matrix, has_missing, host matrix) — has_missing is a
        static per-dataset fact the exact grower specializes on; the
        host copy feeds the one-off rank build for training matrices.
        ``pad_multiple`` additionally pads the feature axis with
        all-NaN columns to a multiple (exact column split's shard
        width) BEFORE the single host→device transfer; pad columns do
        not flip has_missing (they sort into the finder's trash
        segment regardless — see colmaker._find_exact_splits)."""
        X = dmat.to_dense(missing=np.nan)
        X = X[:, :self.num_feature]
        has_missing = bool(np.isnan(X).any())
        if X.shape[1] < self.num_feature:
            X = np.pad(X, ((0, 0), (0, self.num_feature - X.shape[1])),
                       constant_values=np.nan)
            has_missing = True
        pad = (-X.shape[1]) % max(1, pad_multiple)
        if pad:
            X = np.pad(X, ((0, 0), (0, pad)), constant_values=np.nan)
        return jnp.asarray(X), has_missing, X

    def _replicated(self, x):
        """Make a device value fully addressable for host pulls: in
        multi-process mode sharded arrays live partly on other hosts, so
        metric evaluation / prediction output all-gathers them first
        (rides ICI on real pods; the reference instead allreduces metric
        partial sums — same communication role)."""
        if (isinstance(x, jax.Array) and not x.is_fully_addressable
                and self._mesh is not None):
            if getattr(self, "_replicate_fn", None) is None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                self._replicate_fn = jax.jit(
                    lambda v: v,
                    out_shardings=NamedSharding(self._mesh, P()))
            x = self._replicate_fn(x)
        return x

    def _sync_margin(self, entry: _CacheEntry):
        """Fold not-yet-applied trees into the cached margin, one round's
        worth at a time (fixed shapes -> one compilation)."""
        if entry.external:
            self._sync_margin_ext(entry)
            return
        if (self.param.booster != "gblinear"
                and entry.applied > self.gbtree.num_trees):
            # the ensemble SHRANK under this entry (a reload to an
            # older model, or an ntree window raced a swap): the cached
            # margin folds trees that no longer exist — rebuild from
            # base instead of serving a mixed window
            entry.margin = None
            entry.applied = 0
        if self.param.booster == "gblinear":
            entry.margin = self.gbtree.predict_margin(entry.binned, entry.base)
            entry.applied = self.gbtree.version
            return
        if entry.margin is None:
            # a buffer of its own (copy=True): where base already has
            # the margin's shape and dtype, broadcast_to and astype hand
            # back base ITSELF, and the fused scan donates its margin —
            # base must outlive that (ntree_limit predictions and every
            # later rebuild read it)
            entry.margin = jnp.array(jnp.broadcast_to(
                entry.base, (entry.binned.shape[0], self._K)),
                jnp.float32, copy=True)
        per_round = self._K * max(1, self.param.num_parallel_tree)
        while entry.applied < self.gbtree.num_trees:
            chunk = self.gbtree.trees[entry.applied:entry.applied + per_round]
            first_group = self.gbtree.tree_group[entry.applied]
            entry.margin = self.gbtree.predict_incremental(
                entry.binned, entry.margin, chunk, first_group,
                root=entry.root)
            entry.applied += len(chunk)

    def _sync_margin_ext(self, entry: _CacheEntry):
        """Margin for an external-memory matrix, rebuilt by streaming
        binned batches through the not-yet-applied trees.

        The margin is DEVICE-resident (it is O(N), tiny next to the
        paged O(N*F) data), so no round pays a host round trip for
        it."""
        if entry.margin is None:
            entry.margin = jnp.broadcast_to(
                jnp.asarray(entry.base),
                (entry.n_real, self._K)).astype(jnp.float32)
            entry.applied = 0
        if entry.applied >= self.gbtree.num_trees:
            return
        from xgboost_tpu.models.tree import predict_margin_binned
        chunk_trees = self.gbtree.trees[entry.applied:]
        groups = self.gbtree.tree_group[entry.applied:]
        stack = jax.tree.map(lambda *xs: jnp.stack(xs), *chunk_trees)
        group = jnp.asarray(groups, jnp.int32)
        # batches are contiguous ordered row ranges: one concat + one
        # add instead of a full-margin scatter per batch
        parts = [predict_margin_binned(
                     stack, group, batch, jnp.zeros((), jnp.float32),
                     self.gbtree.cfg.max_depth, self._K,
                     tree_chunk=self.gbtree.pred_chunk)
                 for _, batch in entry.dmat.device_batches()]
        entry.margin = jnp.asarray(entry.margin) + jnp.concatenate(parts)
        entry.applied = self.gbtree.num_trees

    # ------------------------------------------------------------ profiling
    @property
    def profiler(self):
        """Lazily created RoundProfiler when param profile>=1 (the
        report_stats analog, SURVEY.md §5.1) — or, at level 0, when the
        observability layer is on (``obs_log=``/``metrics_port=``):
        phase spans, the event-log timeline and the
        live training metrics all need the per-phase boundaries, which
        also means per-round host control (no fused multi-round launch)
        and a device barrier per phase — the same cost contract as
        ``profile=1``."""
        if getattr(self, "_profiler", None) is not None:
            return self._profiler
        if self.param.profile <= 0:
            from xgboost_tpu import obs
            if not obs.phases_enabled():
                return None
        from xgboost_tpu.obs import RoundProfiler
        self._profiler = RoundProfiler(
            self.param.profile, self.param.profile_dir or None)
        self._profiler.start()
        return self._profiler

    # ------------------------------------------------------------- training
    def update(self, dtrain: DMatrix, iteration: int, fobj=None):
        """One boosting round (reference BoostLearner::UpdateOneIter,
        learner-inl.hpp:274-281; custom-objective path Booster.update,
        wrapper/xgboost.py:335-355)."""
        prof = self.profiler
        if prof is None:
            return self._update(dtrain, iteration, fobj)
        prof.begin_round(iteration)
        try:
            return self._update(dtrain, iteration, fobj, prof)
        finally:
            prof.end_round()

    def _update(self, dtrain: DMatrix, iteration: int, fobj=None, prof=None):
        from contextlib import nullcontext
        ph = (lambda name: prof.phase(name)) if prof else \
            (lambda name: nullcontext())
        self._lazy_init(dtrain)
        with ph("predict") as p:
            entry = self._entry(dtrain)
            self._announce_rank_path(entry)
            self._sync_margin(entry)
            if prof:
                p.block(entry.margin)
        if fobj is None:
            with ph("gradient") as p:
                margin = entry.margin
                if getattr(self.obj, "needs_host_margin", False):
                    # ranking objectives sample pairs host-side from the
                    # full margin; all-gather it in multi-process mode
                    margin = self._replicated(margin)
                if entry.rank_pad_prep is not None:
                    gh = self.obj.get_gradient(
                        jnp.asarray(margin), entry.info, iteration,
                        entry.margin.shape[0],
                        pad_prep=entry.rank_pad_prep)
                else:
                    gh = self.obj.get_gradient(
                        jnp.asarray(margin), entry.info,
                        iteration, entry.margin.shape[0])
                if prof:
                    p.block(gh)
        else:
            if getattr(dtrain, "is_sharded", False):
                raise NotImplementedError(
                    "custom objectives need the full prediction/gradient "
                    "vectors on each host; load replicated (DMatrix) for "
                    "custom-objective training")
            # custom objective sees only the real rows; gradients are
            # zero-padded back to the device row count below in boost()
            pred = np.asarray(self._replicated(
                self.obj.pred_transform(entry.margin)))
            pred = entry.user_rows(pred)
            if pred.shape[1] == 1:
                pred = pred[:, 0]
            grad, hess = fobj(pred, dtrain)
            return self.boost(dtrain, grad, hess)
        with ph("grow") as p:
            self._do_boost(dtrain, entry, gh, iteration)
            if prof and entry.margin is not None:
                p.block(entry.margin)

    # Rows at which auto-K reaches 1: 9 x the fixed cost of a dispatch
    # over the cost of a row, 9 * 4.46514 ms / 9.97417 ns.  Both come
    # from a pre-round fit at 64 bins (125k...1M rows x 28) on a machine
    # that is gone; ROADMAP S8(c) replaces the number with one measured
    # on the benchmark's cells.
    AUTO_DISPATCH_ROWS = 4_029_033.36

    def _resolve_rounds_per_dispatch(self, n_rows: int,
                                     override=None) -> int:
        """Segment size K for fused training dispatches: the explicit
        ``override`` (``update_many``'s keyword) if given, else the
        ``rounds_per_dispatch`` train param.  ``-1`` (auto) sizes the
        segment so the fixed per-dispatch cost amortizes to <=10% of
        the dispatch — ``K = ceil(AUTO_DISPATCH_ROWS / rows)`` —
        clamped to [1, 64] (past 64 the fixed term is noise and longer
        segments only delay eval lines / checkpoints).  ``0`` =
        per-round dispatch, the A/B baseline."""
        k = int(self.param.rounds_per_dispatch if override is None
                else override)
        if k >= 0:
            return k
        return max(1, min(64, math.ceil(
            self.AUTO_DISPATCH_ROWS / max(1, int(n_rows)))))

    def _fused_grad(self, entry):
        """The objective's jittable gradient for ``entry`` (None when
        it has none): the rank-padded relayout's, where the entry
        carries one."""
        if entry.rank_pad_prep is not None:
            return self.obj.fused_grad(entry.info,
                                       pad_prep=entry.rank_pad_prep)
        return self.obj.fused_grad(entry.info)

    def _fused_blockers(self, entry, ups, *, lanes: bool, fobj=None,
                        n_rounds: int, evals=(), feval=None) -> list:
        """Why this job may not run as fused segments: the reasons in
        table order, empty when it may (the first is the one
        ``xgbtpu_train_fused_fallback_total`` and a declined lane
        report).  ONE table for :meth:`update_many` (``lanes=False``)
        and :meth:`fused_lane_spec` (``lanes=True``); ``lanes`` only
        selects the rows that exist for one caller alone.  ``evals`` is
        ``update_many``'s ``(dmat, name, entry, is_train)`` watchlist.

        Fault injection (mock) does not block fusion — do_boost_fused
        replays the injector's round/seqno coordinates before each
        dispatch.  Sharded watchlist sets ride the scan carry like any
        mesh entry; their eval lines reduce metric partials via
        ShardedDMatrix.allsum (_eval_parts_sharded) — only a custom
        feval (needs the full vector on one host) excludes them.
        External-memory sets still page batches per round."""
        solo = not lanes
        checks = (
            ("custom_objective", solo and fobj is not None),
            ("single_round", solo and n_rounds <= 1),
            ("booster", solo and self.param.booster != "gbtree"),
            ("no_rounds", lanes and n_rounds < 1),
            ("external_train", bool(entry.external)),
            ("mesh", lanes and self._mesh is not None),
            ("col_split", self._col_mesh is not None),
            # escape hatch: sequential per-round launches (the fused
            # scan always grows the round's ensemble vmapped)
            ("seq_boost_env", bool(os.environ.get("XGBTPU_SEQ_BOOST"))),
            ("profiler", self.profiler is not None),
            ("prune", self.param.gamma > 0.0 and "prune" in ups),
            ("multi_root", max(1, self.param.num_roots) != 1),
            ("exact", bool(getattr(self.gbtree, "exact_raw", False))),
            ("refresh", "refresh" in ups),
            ("no_grow_updater",
             not any(u.startswith("grow") for u in ups)),
            # stacking-only: lanes share one rowwise gradient program
            ("rank_layout", lanes and entry.rank_pad_prep is not None),
            ("no_fused_grad", self._fused_grad(entry) is None),
            ("feature_screen", lanes and self.param.ema_fs > 0
             and self._feature_screen is not None),
            ("external_eval", solo and any(
                e.external for _, _, e, _ in evals)),
            ("sharded_eval_feval", solo and feval is not None and any(
                getattr(d, "is_sharded", False) for d, _, _, _ in evals)),
        )
        return [name for name, blocked in checks if blocked]

    def update_many(self, dtrain: DMatrix, first_iteration: int,
                    n_rounds: int, fobj=None, *, evals=None, feval=None,
                    eval_callback=None, round_callback=None,
                    segment_callback=None, plan_callback=None,
                    boundary_align: int = 0,
                    rounds_per_dispatch=None) -> None:
        """Run ``n_rounds`` boosting rounds in fused SEGMENTS: K rounds
        per ``_scan_rounds`` dispatch (``rounds_per_dispatch``; auto
        sizes K from the fitted round model), touching the host only at
        segment boundaries.  Watchlist evaluation runs device-resident
        inside the scan — eval lines print per round AFTER the segment's
        dispatch, byte-identical to the per-round path's — and the
        stacked per-round trees each dispatch returns keep checkpoint
        granularity at segment boundaries with per-round model bytes
        available.  The fused path bit-matches the sequential path
        (same per-round keys and kernels) — the reference's round loop
        is host-side by construction (xgboost_main.cpp:183-217); here
        it compiles into the program.

        Falls back to per-round :meth:`update` (same callbacks, one
        boundary per round) when fusion is ineligible — custom/host
        objective, pruning, refresh, column split, profiler/obs
        phases, external-memory matrices — or when the resolved
        segment size is 0 (the per-round A/B baseline).  Every
        fallback is LOUD: ``xgbtpu_train_fused_fallback_total`` and a
        ``train.fused_fallback`` event record the first blocking
        reason, so chaos/bench runs meant to measure the fused path
        can assert it never silently degraded.  Fault injection
        (``mock=``) no longer forces the fallback: the fused driver
        replays the injector's (version, seqno) coordinates at
        segment boundaries.

        Driver hooks (all optional; the CLI and ContinuousTrainer ride
        these instead of owning round loops):

        - ``evals``/``feval``: watchlist ``[(dmat, name), ...]`` and
          custom metric — eval lines are built per round on BOTH paths.
        - ``eval_callback(iteration, msg)``: one formatted eval line.
        - ``round_callback(iteration)``: per-round liveness, ONLY on
          the per-round path (a fused segment has no between-round
          host point by design).
        - ``segment_callback(last_iteration)``: a segment completed
          through ``last_iteration`` (per-round path: every round) —
          checkpoint/save hook.
        - ``plan_callback(k)``: the resolved segment size (0 =
          per-round), reported once before training.
        - ``boundary_align``: force segment boundaries at iteration
          multiples (periodic ``save_period`` saves need the model
          materialized exactly there).
        """
        from xgboost_tpu.models.updaters import parse_updaters

        self._lazy_init(dtrain)
        entry = self._entry(dtrain)
        self._announce_rank_path(entry)
        ups = parse_updaters(self.param.updater)
        # Watchlist entries are built HERE, before eligibility is
        # consulted: a zero-round call is how a caller asks for the
        # device entries of the training set and every watchlist member
        # (benchmark/run.py ends its ingest stopwatch on one), so this
        # is a stated step of every path below, fused or not.
        # (entry, is_train) per slot: a slot that IS the training
        # matrix reads the scan's grow-time margin (the
        # prediction-buffer shortcut) instead of carrying a second copy.
        evals = list(evals) if evals else []
        espec = []
        for dmat, name in evals:
            e = self._entry(dmat)
            espec.append((dmat, name, e, e is entry))
        # a fallback is LOUD: chaos/bench runs that mean to measure the
        # fused path verify the fused_fallback counter stayed 0
        blockers = self._fused_blockers(
            entry, ups, lanes=False, fobj=fobj, n_rounds=n_rounds,
            evals=espec, feval=feval)
        fused_ok = not blockers
        k = (self._resolve_rounds_per_dispatch(
            dtrain.num_row, rounds_per_dispatch) if fused_ok else 0)
        if plan_callback is not None:
            plan_callback(k)
        if not fused_ok or k <= 0:
            if n_rounds > 1 and self.param.booster == "gbtree":
                why = blockers or ["rounds_per_dispatch_0"]
                from xgboost_tpu.obs import trace
                training_metrics().fused_fallback.inc(why[0])
                trace.event("train.fused_fallback", reasons=why,
                            first_iteration=first_iteration,
                            n_rounds=n_rounds)
            from contextlib import nullcontext
            for i in range(first_iteration, first_iteration + n_rounds):
                if round_callback is not None:
                    round_callback(i)
                self.update(dtrain, i, fobj)
                if evals:
                    prof = self.profiler
                    with prof.phase("eval") if prof else nullcontext():
                        msg = self.eval_set(evals, i, feval)
                    if eval_callback is not None:
                        eval_callback(i, msg)
                if segment_callback is not None:
                    segment_callback(i)
            return
        self.obj.validate_labels(entry.info)  # host check, once per info
        self._sync_margin(entry)
        for _, _, e, is_train in espec:
            if not is_train:
                self._sync_margin(e)
        etransform = self.obj.fused_eval_transform() if espec else None
        # EMA-FS (ema_fs > 0 + set_feature_screen): fused segments grow
        # over the screened (C, N, F_kept) working set.  Confined to the
        # plain single-device dense path — meshes, paged matrices, exact
        # mode and rank relayouts keep the full feature set (the screen
        # is a throughput optimization, never a correctness dependency);
        # grown trees come back remapped to the full space.
        screen = None
        if (self.param.ema_fs > 0
                and self._feature_screen is not None
                and self._mesh is None
                and not entry.external
                and not getattr(self.gbtree, "exact_raw", False)
                and entry.rank_pad_prep is None
                and len(self._feature_screen) < int(entry.binned.shape[1])
                and all(not e.external and e.rank_pad_prep is None
                        and not getattr(d, "is_sharded", False)
                        for d, _, e, t in espec if not t)):
            screen = self._feature_screen
            kept_dev = jnp.asarray(screen, jnp.int32)

            def _screened(e):
                # per-entry screened-column cache, keyed on the kept
                # set: re-gathering (N, F_kept) columns every segment
                # would cancel the histogram win
                if getattr(e, "screen_key", None) != screen:
                    e.screen_binned = jnp.take(e.binned, kept_dev,
                                               axis=1)
                    e.screen_key = screen
                return e.screen_binned
        align = max(0, int(boundary_align))
        tm = training_metrics()
        done = 0
        while done < n_rounds:
            first = first_iteration + done
            seg = min(k, n_rounds - done)
            if align:
                # stop at the next aligned boundary so periodic saves
                # see the model at exactly that round (segment lengths
                # stay O(distinct) -> bounded scan compiles)
                seg = min(seg, align - first % align)
            with span("train.segment", first_round=first, n_rounds=seg):
                margin_f, emargins_f, eouts = self.gbtree.do_boost_fused(
                    _screened(entry) if screen is not None
                    else entry.binned,
                    entry.margin, entry.info, self._fused_grad(entry),
                    first, seg, row_valid=entry.row_valid,
                    mesh=self._mesh,
                    binned_t=(None if screen is not None
                              else getattr(entry, "binned_t", None)),
                    eval_binned=tuple(
                        (_screened(e) if screen is not None else e.binned)
                        for _, _, e, t in espec if not t),
                    eval_margins=tuple(e.margin for _, _, e, t in espec
                                       if not t),
                    eval_is_train=tuple(t for _, _, _, t in espec),
                    etransform=etransform,
                    rowwise_grad=entry.rank_pad_prep is None,
                    feature_screen=screen)
                entry.margin = margin_f
                entry.applied = self.gbtree.num_trees
                ei = 0
                for _, _, e, is_train in espec:
                    if is_train:
                        continue
                    e.margin = emargins_f[ei]
                    e.applied = self.gbtree.num_trees
                    ei += 1
                if espec:
                    with span("train.eval", slots=len(espec),
                              rows=sum(int(o.shape[1]) for o in eouts)):
                        self._segment_eval_lines(espec, eouts, first, seg,
                                                 feval, eval_callback)
                # live progress on the fused path too (the per-round
                # profiler, which used to be their only feeder, never
                # runs here)
                tm.rounds.inc(seg)
                tm.round.set(first + seg - 1)
            done += seg
            if segment_callback is not None:
                segment_callback(first + seg - 1)

    def _segment_eval_lines(self, espec, eouts, first: int, seg: int,
                            feval, eval_callback) -> None:
        """Eval lines for every round of one fused segment, from the ONE
        dispatch's stacked per-round outputs (device -> host here)."""
        for r in range(seg):
            parts = [f"[{first + r}]"]
            for si, (dmat, name, e, _) in enumerate(espec):
                if getattr(dmat, "is_sharded", False):
                    # split-loaded set: metric partials on the LOCAL
                    # shard of the round's transformed outputs, reduced
                    # via allsum — no process ever holds the full
                    # prediction vector
                    local = dmat.local_block_of(eouts[si][r])
                    self._eval_parts_sharded(
                        dmat, name, local[:dmat.local_num_row], parts)
                    continue
                tr = e.user_rows(np.asarray(self._replicated(
                    eouts[si][r])))
                self._eval_parts(dmat, name, tr, parts, feval)
            msg = "\t".join(parts)
            training_metrics().observe_eval(_parse_eval(msg))
            if eval_callback is not None:
                eval_callback(first + r, msg)

    def fused_lane_spec(self, dtrain: DMatrix, first_iteration: int,
                        n_rounds: int, rounds_per_dispatch=None):
        """Gang-batching eligibility + operand bundle for this booster's
        next ``n_rounds`` fused rounds (PIPELINE.md "Gang-batched
        lanes").  Returns ``(LaneSpec, None)`` when the lane-stacking
        driver may vmap this booster with same-bucket peers, else
        ``(None, reason)`` — the reasons are :meth:`_fused_blockers`'
        (``update_many``'s own table) with the stacking-only rows (any
        mesh, rank relayouts, an active feature screen): a declined lane runs
        solo through the normal :meth:`update_many` path, which decides
        its own fused-vs-per-round route.

        Side effects on eligibility match the fused path exactly:
        labels are host-validated once and the entry margin is synced,
        so the returned ``margin``/``binned`` are the solo scan's own
        operands and a stacked dispatch is bit-identical per lane.
        """
        from xgboost_tpu.models.updaters import parse_updaters
        if self.param.booster != "gbtree":
            return None, "booster"
        self._lazy_init(dtrain)
        entry = self._entry(dtrain)
        ups = parse_updaters(self.param.updater)
        blockers = self._fused_blockers(entry, ups, lanes=True,
                                        n_rounds=n_rounds)
        if blockers:
            return None, blockers[0]
        k = self._resolve_rounds_per_dispatch(dtrain.num_row,
                                              rounds_per_dispatch)
        if k <= 0:
            return None, "rounds_per_dispatch_0"
        self.obj.validate_labels(entry.info)  # host check, once per info
        self._sync_margin(entry)
        N = int(entry.binned.shape[0])
        return LaneSpec(
            booster=self, entry=entry, n_rows=N,
            n_features=int(entry.binned.shape[1]),
            n_rounds=int(n_rounds),
            first_iteration=int(first_iteration), seg_k=int(k),
            K=self._K, npar=max(1, self.param.num_parallel_tree),
            cfg=self.gbtree.cfg,
            split_finder=self.gbtree._split_finder(),
            grad_fn=self._fused_grad(entry),
            pred_chunk=self.gbtree.pred_chunk,
            subsample=float(self.param.subsample),
            binned=entry.binned, margin=entry.margin,
            label=entry.info.label_dev(),
            weight=entry.info.weight_dev(N),
            base_key=self.gbtree.base_key(),
            cut_values=self.gbtree.cut_values_dev,
            n_cuts=self.gbtree.n_cuts_dev,
            row_valid=entry.row_valid), None

    def absorb_lane_segment(self, spec: LaneSpec, stacks, margin,
                            n_rounds: int) -> None:
        """Install one gang segment's per-lane outputs back into this
        booster: the lane's flattened ``(n_rounds*K*npar, ...)`` tree
        stack joins the ensemble and the scanned margin replaces the
        entry's cached one (sliced back to the entry's own row count by
        the caller).  Mirrors what :meth:`update_many` does after
        ``do_boost_fused``."""
        self.gbtree.absorb_round_stacks(stacks, n_rounds)
        spec.entry.margin = margin
        spec.entry.applied = self.gbtree.num_trees

    def boost(self, dtrain: DMatrix, grad, hess):
        """Boost from user-supplied gradients (reference
        XGBoosterBoostOneIter, wrapper/xgboost_wrapper.cpp:310-317)."""
        if getattr(dtrain, "is_sharded", False):
            raise NotImplementedError(
                "boost() takes full gradient vectors; split-loaded "
                "matrices have no full-vector host view")
        self._lazy_init(dtrain)
        entry = self._entry(dtrain)
        self._sync_margin(entry)
        g = np.asarray(grad, np.float32).reshape(dtrain.num_row, self._K)
        h = np.asarray(hess, np.float32).reshape(dtrain.num_row, self._K)
        n_dev = (entry.binned.shape[0] if entry.binned is not None
                 else entry.margin.shape[0])  # external: no binned array
        if entry.rank_pad_prep is not None:
            # group-padded layout: user rows scatter to their slots
            gp = np.zeros((n_dev, self._K), np.float32)
            hp = np.zeros((n_dev, self._K), np.float32)
            gp[entry.rank_pad_prep.user_map] = g
            hp[entry.rank_pad_prep.user_map] = h
            g, h = gp, hp
        elif n_dev - dtrain.num_row:  # zero-gradient padding rows
            pad = n_dev - dtrain.num_row
            g = np.concatenate([g, np.zeros((pad, self._K), np.float32)])
            h = np.concatenate([h, np.zeros((pad, self._K), np.float32)])
        gh = jnp.stack([jnp.asarray(g), jnp.asarray(h)], axis=-1)
        self._do_boost(dtrain, entry, gh, self.gbtree.num_boosted_rounds
                       if self.param.booster != "gblinear"
                       else self.gbtree.version)

    def _do_boost(self, dtrain, entry, gh, iteration):
        # fault-injection seam (reference AllreduceMock, allreduce_mock.h:
        # 37-44): every boosting round is a "version"; each collective
        # launch inside it bumps the seqno (parallel/mock.py)
        from xgboost_tpu.parallel import mock
        mock.begin_round(iteration)
        # deterministic per-iteration seeding: the reference forces
        # seed_per_iteration in distributed mode for replayable recovery
        # (learner-inl.hpp:275-277); fold_in gives that always.
        key = jax.random.fold_in(
            jax.random.PRNGKey(self.param.seed), iteration)
        if self.param.booster == "gblinear":
            self.gbtree.do_boost(entry.binned, gh, dtrain.info,
                                 mesh=self._mesh)
            entry.applied = self.gbtree.version  # recompute on next sync
            entry.margin = None
            self._sync_margin(entry)
            return
        from xgboost_tpu.models.updaters import parse_updaters
        ups = parse_updaters(self.param.updater)
        if entry.external:
            if "refresh" in ups:
                raise NotImplementedError(
                    "updater=refresh is not supported on external-memory "
                    "matrices")
            deltas = self.gbtree.do_boost_paged(entry.dmat, gh, key,
                                                mesh=self._mesh)
            entry.margin = jnp.asarray(entry.margin) + deltas
            entry.applied = self.gbtree.num_trees
            return
        grows = any(u.startswith("grow") or u == "distcol" for u in ups)
        if grows and getattr(self.gbtree, "exact_raw", False) \
                and getattr(entry, "exact_ranks", None) is None:
            # one-off: dense-rank structures for the single-key sort
            # (colmaker.build_exact_ranks; host argsort on the matrix
            # _raw_dense already densified, then resident on device
            # for every subsequent round)
            from xgboost_tpu.models.colmaker import build_exact_ranks
            rk, uq = build_exact_ranks(entry.exact_host)
            entry.exact_ranks = (jnp.asarray(rk), jnp.asarray(uq))
            entry.exact_host = None
        if grows:
            _, delta = self.gbtree.do_boost(
                entry.binned, gh, key, row_valid=entry.row_valid,
                mesh=self._mesh, col_mesh=self._col_mesh,
                root=entry.root,
                exact_has_missing=getattr(entry, "exact_has_missing",
                                          True),
                exact_ranks=getattr(entry, "exact_ranks", None),
                binned_t=getattr(entry, "binned_t", None))
            entry.margin = entry.margin + delta
            entry.applied = self.gbtree.num_trees
        if "refresh" in ups:
            # refresh pass (reference updater=refresh): recompute stats +
            # leaf values of ALL trees on this data.  In a mixed pipeline
            # ("grow_histmaker,refresh") it runs after growth on the same
            # gradient snapshot, like the reference's sequential updaters.
            self.gbtree.do_refresh(entry.binned, gh,
                                   row_valid=entry.row_valid,
                                   mesh=self._mesh, root=entry.root)
            if "prune" in ups and self.param.gamma > 0.0 and not grows:
                # "refresh,prune": prune against the refreshed gains
                from xgboost_tpu.models.updaters import prune_tree
                for i, t in enumerate(self.gbtree.trees):
                    self.gbtree.trees[i], _ = prune_tree(
                        t, self.param.gamma, self.gbtree.cfg.n_roots)
                self.gbtree._stack_cache = None
            # leaf values changed: every cached margin is stale
            for e in self._cache.values():
                e.margin = None
                e.applied = 0
            self._sync_margin(entry)

    def _predict_block_rows(self, data) -> int:
        """Row-block size for one-off dense prediction uploads: whole
        matrix while under the ``2^31``-byte single-buffer guard, else
        256 MB f32 blocks (thousands of rows even at wide F; with the
        depth-2 prefetch queue at most ~4 blocks are in flight
        device-side).  ``XGBTPU_BIN_BLOCK_BYTES`` overrides (test
        seam)."""
        Fm = self.gbtree.cuts.num_feature
        N = data.num_row
        budget = int(os.environ.get("XGBTPU_BIN_BLOCK_BYTES", 0))
        if not budget and N * Fm * 4 <= (1 << 31):
            return max(N, 1)
        return max(1, (budget or (1 << 28)) // (4 * max(Fm, 1)))

    def _dense_block_fn(self, data):
        """``(s, e) -> (e-s, Fm) f32`` dense row blocks (NaN = missing).

        When ``Booster.predict`` wrapped a plain C-contiguous f32
        ndarray of model width, blocks are zero-copy VIEWS of the
        caller's own buffer — the CSR round-trip and the per-block
        densify copy are skipped entirely and the caller's memory
        uploads directly (round-7 satellite; NaN is the missing marker
        on both paths, so blocks are value-identical).  Otherwise
        blocks densify straight from the CSR arrays: the host working
        set is ONE f32 block, never a full N x F densify."""
        Fm = self.gbtree.cuts.num_feature
        src = getattr(data, "_predict_dense_src", None)
        if src is None and hasattr(data, "predict_dense_src"):
            # a lazily-CSR DMatrix built straight from a dense ndarray
            # (data.py): the caller's buffer is the upload source and
            # the CSR arrays never materialize for this predict
            src = data.predict_dense_src()
        if src is not None and src.shape[1] == Fm:
            return lambda s, e: src[s:e]

        def dense_block(s, e):
            Xb = np.full((e - s, Fm), np.nan, np.float32)
            lo, hi = data.indptr[s], data.indptr[e]
            rows = np.repeat(np.arange(e - s),
                             np.diff(data.indptr[s:e + 1]))
            cols = data.indices[lo:hi]
            keep = cols < Fm
            Xb[rows[keep], cols[keep]] = data.values[lo:hi][keep]
            return Xb

        return dense_block

    def _bin_dense_blocked(self, data: DMatrix):
        """Device-side quantization of a dense-enough matrix, chunked
        over row blocks past the ``2^31``-byte single-buffer guard (a
        20M x 28 one-off prediction used to silently fall back to the
        seconds-long host ``searchsorted`` loop).

        This is the TWO-STEP path (binned matrix materialized in HBM):
        ``pred_leaf`` and the ``XGBTPU_PREDICT_FUSED=0`` baseline use
        it; the margin fast path fuses quantize into the traversal
        program instead (:meth:`_predict_fused_blocked`).  Blocks stage
        through :func:`external._prefetch_to_device` at the
        ``XGBTPU_PREDICT_UPLOAD_DEPTH`` lookahead, and every upload
        feeds the ``xgbtpu_predict_transfer_*`` counters."""
        from xgboost_tpu.binning import bin_dense_device
        from xgboost_tpu.obs.metrics import predict_metrics
        cv = self.gbtree.cuts.cut_values
        N = data.num_row
        block = self._predict_block_rows(data)
        blk = self._dense_block_fn(data)
        pm = predict_metrics()
        if N <= block:
            from xgboost_tpu.obs.metrics import timed_device_put
            return bin_dense_device(
                timed_device_put(blk(0, N), pm.observe_transfer), cv)
        from xgboost_tpu.external import _prefetch_to_device

        def host_blocks():
            for s in range(0, N, block):
                yield s, blk(s, min(s + block, N))

        parts = [bin_dense_device(xb, cv)
                 for _, xb in _prefetch_to_device(
                     host_blocks(), depth=_predict_upload_depth(),
                     observe=pm.observe_transfer)]
        return jnp.concatenate(parts, axis=0)

    def _fused_predict_ok(self, data, pred_leaf: bool) -> bool:
        """Gate for the fused one-off margin path: margins only
        (pred_leaf needs the leaf matrix), non-empty input (the block
        pipeline has nothing to concatenate at N=0; the two-step path
        already returns the (0,) result), single-device placement (the
        mesh path keeps the two-step upload), no multi-root routing
        (root vectors would need per-block slicing), and the
        ``XGBTPU_PREDICT_FUSED`` A/B seam (0 = two-step baseline)."""
        return (not pred_leaf
                and data.num_row > 0
                and os.environ.get("XGBTPU_PREDICT_FUSED", "1") != "0"
                and self._mesh is None and self._col_mesh is None
                and not (getattr(data.info, "root_index", None) is not None
                         and max(1, self.param.num_roots) > 1))

    def _predict_fused_blocked(self, data, ntree_limit: int = 0):
        """One-off dense prediction margins through the FUSED
        quantize+traverse program (round 7 — the transfer wall): raw
        f32 row blocks upload through the
        ``XGBTPU_PREDICT_UPLOAD_DEPTH``-deep prefetch pipeline (block
        k+1's upload overlaps block k's quantize+traverse), margins
        come out of ONE compiled program per block, and the binned
        matrix never exists outside it — no second HBM buffer, no extra
        launch boundary.  Every upload feeds the
        ``xgbtpu_predict_transfer_*`` counters.  Bit-identical to the
        two-step path: the quantize sub-graph is
        ``binning.bin_dense_device`` itself and traversal is
        row-independent, so per-block margins concatenate to exactly
        the whole-matrix result (tests/test_predict_fused.py)."""
        from xgboost_tpu.external import _prefetch_to_device
        from xgboost_tpu.obs.metrics import predict_metrics
        N = data.num_row
        K = self._K
        block = self._predict_block_rows(data)
        blk = self._dense_block_fn(data)
        bm = data.info.base_margin
        if bm is None:
            base_all = None
            base0 = jnp.full((), self.obj.prob_to_margin(
                self.param.base_score), jnp.float32)
        else:
            base_all = np.asarray(bm, np.float32).reshape(N, K)
            base0 = None
        pm = predict_metrics()
        if N <= block:
            # single block (virtually all under-guard predicts): skip
            # the prefetch worker thread/queue — inline timed upload,
            # one fused program call (mirrors _bin_dense_blocked)
            from xgboost_tpu.obs.metrics import timed_device_put
            xd = timed_device_put(blk(0, N), pm.observe_transfer)
            base = (base0 if base_all is None
                    else jnp.asarray(base_all))
            return self.gbtree.predict_margin_fused(xd, base, ntree_limit)

        def host_blocks():
            for s in range(0, N, block):
                yield s, blk(s, min(s + block, N))

        parts = []
        for s, xd in _prefetch_to_device(host_blocks(),
                                         depth=_predict_upload_depth(),
                                         observe=pm.observe_transfer):
            base = (base0 if base_all is None
                    else jnp.asarray(base_all[s:s + xd.shape[0]]))
            parts.append(self.gbtree.predict_margin_fused(
                xd, base, ntree_limit))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                                axis=0)

    # ------------------------------------------------------------ inference
    def predict(self, data: DMatrix, output_margin: bool = False,
                ntree_limit: int = 0, pred_leaf: bool = False) -> np.ndarray:
        """(reference BoostLearner::Predict, learner-inl.hpp:332-346 and
        Booster.predict, wrapper/xgboost.py:422-450).

        ``data`` may also be a plain 2-D ndarray / jax.Array / nested
        list (NaN = missing): it is wrapped into a transient DMatrix
        here, so callers (serving engine, sklearn wrapper) don't each
        re-implement the wrapping."""
        assert self.gbtree is not None, "model not trained/loaded"
        if not hasattr(data, "num_row"):  # any DMatrix flavor has it
            arr = np.asarray(data, dtype=np.float32)
            data = DMatrix(arr)
            if arr.ndim == 2 and arr.flags.c_contiguous:
                # upload the caller's own buffer: the UPLOAD path skips
                # the CSR→dense densify copy per block and ships views
                # of arr instead (NaN is the missing marker on both
                # paths; see _dense_block_fn).  The DMatrix above is
                # CSR-LAZY (data.py): this one-off predict reads only
                # num_nonmissing() + these views, so the ~2x
                # values/indices/indptr copy is never built at all
                data._predict_dense_src = arr

        def _counted(out):
            """Attribute prediction traffic in /metrics by the rows
            actually RETURNED: sharded ranks count their local shard
            (num_row is the global count), and a predict that raises
            counts nothing.  The serving engine feeds the same
            family."""
            if self.param.booster != "gblinear":
                from xgboost_tpu.obs.metrics import predict_metrics
                predict_metrics().rows.inc(out.shape[0])
            return out
        if getattr(data, "is_sharded", False):
            # split-loaded matrix: each process returns predictions for
            # ITS OWN rows only (no host holds the full output)
            if self.param.booster == "gblinear":
                raise NotImplementedError(
                    "gblinear works on raw feature columns; per-rank "
                    "split loading currently supports gbtree only")
            entry = self._cache.get(id(data))
            if entry is None:
                # transient, NOT registered (the buffer_offset=-1 path —
                # registering every served matrix would grow the cache
                # unboundedly)
                entry = self._make_shard_loaded_entry(data)
            if pred_leaf:
                leaves = self.gbtree.predict_leaf(entry.binned, ntree_limit)
                return _counted(
                    data.local_block_of(leaves)[:data.local_num_row])
            if ntree_limit == 0:
                self._sync_margin(entry)
                margin = entry.margin
            else:
                margin = self.gbtree.predict_margin(
                    entry.binned, entry.base, ntree_limit)
            out = data.local_block_of(self.obj.pred_transform(
                margin, output_margin=output_margin))[:data.local_num_row]
            if out.ndim == 2 and out.shape[1] == 1:
                out = out[:, 0]
            return _counted(out)
        cached = self._cache.get(id(data))
        if cached is None and getattr(data, "is_external", False):
            # one-off external prediction: build a transient entry WITHOUT
            # registering it (the buffer_offset=-1 path — registering every
            # served matrix would grow the cache unboundedly)
            cached = self._build_ext_entry(data)
        if cached is not None and cached.external:
            if pred_leaf:
                leaves = [np.asarray(self.gbtree.predict_leaf(
                    batch, ntree_limit))
                    for _, batch in data.device_batches()]
                return _counted(np.concatenate(leaves, axis=0))
            if ntree_limit == 0:
                self._sync_margin(cached)
                margin = cached.margin
            else:
                margin = np.concatenate(
                    [np.asarray(self.gbtree.predict_margin(
                        batch,
                        np.asarray(cached.base)[s:s + batch.shape[0]],
                        ntree_limit))
                     for s, batch in data.device_batches()], axis=0)
            out = np.asarray(self.obj.pred_transform(
                jnp.asarray(margin), output_margin=output_margin))
            if out.ndim == 2 and out.shape[1] == 1:
                out = out[:, 0]
            return _counted(out)
        fused = False
        if cached is None:
            # one-off prediction: no cache registration (the reference's
            # buffer_offset = -1 path, learner-inl.hpp:332-346)
            if self.num_feature and data.num_col > self.num_feature:
                raise ValueError(
                    f"data has {data.num_col} features, model was trained "
                    f"with {self.num_feature}")
            # the density gate counts actual non-missing values, even
            # for ndarray inputs carrying _predict_dense_src: a
            # mostly-NaN ndarray must keep the O(nnz) host-binning
            # path (u8 upload), not ship the full f32 matrix — the
            # direct-buffer view is an UPLOAD optimization for inputs
            # that are dense anyway, not a routing override.
            # num_nonmissing() == len(data.values) bit for bit, but a
            # lazily-CSR dense DMatrix answers it WITHOUT building the
            # ~2x values/indices/indptr copy this gate alone would
            # otherwise force (data.py)
            nnz = (data.num_nonmissing()
                   if hasattr(data, "num_nonmissing")
                   else len(data.values))
            dense_enough = (nnz
                            >= 0.25 * data.num_row * max(data.num_col, 1))
            if self.param.booster == "gblinear":
                binned = self.gbtree.device_matrix(data)
            elif getattr(self.gbtree, "exact_raw", False):
                # exact mode routes on RAW values (no bins exist)
                binned = self._raw_dense(data)[0]
            elif dense_enough and self._fused_predict_ok(data, pred_leaf):
                # FUSED quantize+traverse (round 7): raw f32 blocks
                # upload (prefetch-overlapped) and margins come out of
                # one compiled program per block — the binned matrix
                # never exists outside it.  The margin branch below
                # routes to the fused block pipeline.
                binned = None
                fused = True
            elif dense_enough:
                # quantize ON DEVICE: the host searchsorted loop costs
                # seconds at 1M rows where the fused compare-reduce is
                # ~2 ms (binning.bin_dense_device); the per-block f32
                # densify is the only host work left.  Sparse inputs
                # (<25% dense) keep the O(nnz) bin_matrix path —
                # densifying them host-side costs more memory/transfer
                # than the device quantize saves (advisor, round 4).
                # Matrices past the 2^31-byte single-buffer guard no
                # longer cliff to the seconds-long host path: they
                # quantize in CSR-densified row blocks (prefetch-
                # staged, upload overlapping quantize, bounded host +
                # device working set)
                binned = self._bin_dense_blocked(data)
            else:
                from xgboost_tpu.obs.metrics import (predict_metrics,
                                                     timed_device_put)
                binned = timed_device_put(
                    bin_matrix(data, self.gbtree.cuts),
                    predict_metrics().observe_transfer)
            base = (None if fused
                    else self._base_margin_of(data, data.num_row))
        else:
            binned, base = cached.binned, cached.base
        if cached is not None:
            root = cached.root
        elif (getattr(data.info, "root_index", None) is not None
                and max(1, self.param.num_roots) > 1):
            root = jnp.asarray(
                np.asarray(data.info.root_index, np.int64), jnp.int32)
        else:
            root = None
        if pred_leaf:
            leaves = np.asarray(self._replicated(
                self.gbtree.predict_leaf(binned, ntree_limit, root=root)))
            return _counted(cached.user_rows(leaves)
                            if cached is not None else leaves)
        if cached is not None and ntree_limit == 0:
            self._sync_margin(cached)
            margin = cached.margin
        elif fused:
            margin = self._predict_fused_blocked(data, ntree_limit)
        else:
            margin = self.gbtree.predict_margin(binned, base, ntree_limit,
                                                root=root)
        out = self.obj.pred_transform(margin, output_margin=output_margin)
        out = np.asarray(self._replicated(out))
        if cached is not None:
            out = cached.user_rows(out)
        if out.ndim == 2 and out.shape[1] == 1:
            out = out[:, 0]
        return _counted(out)

    # ----------------------------------------------------------- evaluation
    def _metrics(self, feval=None) -> List:
        names = list(self.param.eval_metric)
        if not names and feval is None:
            names = [self.obj.default_metric]
        return [create_metric(n) for n in names]

    def _eval_parts(self, dmat, name: str, tr, parts: List[str],
                    feval) -> None:
        """Append one watchlist set's ``name-metric:value`` fields to
        ``parts`` from its transformed predictions ``tr`` (user rows,
        host numpy) — shared by the per-round eval path (:meth:`eval_set`)
        and the segmented fused driver (:meth:`update_many`), which
        computes a ``tr`` per round of a segment from ONE stacked
        dispatch output.  Same host float64 metric math on the same f32
        values -> byte-identical eval text on both paths."""
        labels = np.asarray(dmat.get_label())
        weights = np.asarray(dmat.get_weight())
        gptr = dmat.info.group_ptr
        for m in self._metrics(feval):
            p = tr if tr.shape[1] > 1 else tr[:, 0]
            if getattr(m, "needs_fold_index", False):
                val = m(p, labels, weights, gptr,
                        fold_index=dmat.info.fold_index)
            else:
                val = m(p, labels, weights, gptr)
            parts.append(f"{name}-{m.metric_name}:{val:.6f}")
        if feval is not None:
            # feval comes LAST so early stopping tracks it (reference
            # wrapper/xgboost.py appends custom eval after built-ins)
            preds = tr[:, 0] if tr.shape[1] == 1 else tr
            mname, val = feval(preds, dmat)
            parts.append(f"{name}-{mname}:{val:.6f}")

    def eval_set(self, evals: Sequence[Tuple[DMatrix, str]], iteration: int = 0,
                 feval=None) -> str:
        """Formatted eval line (reference EvalSet::Eval, evaluation.h:62-95:
        ``[iter]\\tname-metric:value``)."""
        parts = [f"[{iteration}]"]
        for dmat, name in evals:
            entry = self._entry(dmat)
            self._sync_margin(entry)
            if getattr(dmat, "is_sharded", False):
                self._eval_sharded(dmat, entry, name, parts, feval)
                continue
            tr = entry.user_rows(np.asarray(self._replicated(
                self.obj.eval_transform(entry.margin))))
            self._eval_parts(dmat, name, tr, parts, feval)
        msg = "\t".join(parts)
        # latest eval scores ride the training metrics as gauges
        # (xgbtpu_training_eval_score{key="train-error"}), scrapeable
        # mid-run via metrics_port= (OBSERVABILITY.md)
        training_metrics().observe_eval(_parse_eval(msg))
        return msg

    def _eval_sharded(self, dmat, entry, name: str, parts: List[str],
                      feval) -> None:
        """Distributed evaluation for a split-loaded matrix: each process
        computes metric partials on ITS shard only, then partial sums
        reduce across processes — the reference's rabit::Allreduce of
        (sum, wsum) in EvalEWiseBase (evaluation-inl.hpp:45) instead of
        the all-gather the replicated path uses."""
        if feval is not None:
            raise NotImplementedError(
                "custom feval needs the full prediction vector on one "
                "host; load the eval set replicated (DMatrix) instead")
        local = dmat.local_block_of(self.obj.eval_transform(entry.margin))
        self._eval_parts_sharded(dmat, name, local[:dmat.local_num_row],
                                 parts)

    def _eval_parts_sharded(self, dmat, name: str, preds,
                            parts: List[str]) -> None:
        """The partial-sum metric core shared by the per-round sharded
        eval path (:meth:`_eval_sharded`) and the mesh-fused driver
        (:meth:`update_many`, which hands in the LOCAL user rows of one
        round's transformed scan outputs).  ``preds`` is this process's
        (local_num_row, K) transformed prediction block."""
        labels = np.asarray(dmat.info.label)
        weights = np.asarray(dmat.info.get_weight(dmat.local_num_row))
        for m in self._metrics():
            if not hasattr(m, "partial_fn"):
                from xgboost_tpu.metrics import _DIST_METRICS
                raise NotImplementedError(
                    f"metric {m.metric_name!r} has no distributed "
                    "partial-sum form; supported on split-loaded data: "
                    f"{sorted(_DIST_METRICS)}")
            p = preds if preds.shape[1] > 1 else preds[:, 0]
            if (m.metric_name == "auc"
                    and self.param.dist_auc != "approx"):
                # EXACT global AUC: allgather per-shard value runs and
                # merge.  Payload is one 24-byte run per DISTINCT
                # predicted value — for continuous margins that is
                # ~local_rows runs (24 MB/shard at 1M rows), fine as
                # an end-of-training eval, heavy as an every-round
                # one; past dist_auc_max_runs the reference's
                # mean-of-shards approximation kicks in with a loud
                # one-time warning (it is also always available
                # explicitly via dist_auc=approx).
                from xgboost_tpu.metrics import (auc_compress,
                                                 auc_exact_from_runs)
                runs = auc_compress(p, labels, weights)
                limit = int(getattr(self.param, "dist_auc_max_runs",
                                    1 << 22))
                # the exact-vs-approx decision must be GLOBAL: ranks
                # branching on shard-local run counts would execute
                # mismatched collectives (allsum vs allgatherv) and
                # hang — decide on the summed run count, which is also
                # the actual gathered payload
                total_runs = int(dmat.allsum(
                    np.array([float(len(runs))]))[0])
                if total_runs > limit:
                    if not getattr(self, "_warned_auc_runs", False):
                        self._warned_auc_runs = True
                        print(f"[dist-auc] {total_runs} distinct-value "
                              f"runs across shards exceeds "
                              f"dist_auc_max_runs={limit}; falling "
                              "back to the reference's approximate "
                              "mean-of-shards AUC", file=sys.stderr)
                    partial = m.partial_fn(p, labels, weights, None)
                    val = m.finalize_fn(dmat.allsum(partial))
                else:
                    val = auc_exact_from_runs(dmat.allgatherv(runs))
            else:
                partial = m.partial_fn(p, labels, weights, None)
                val = m.finalize_fn(dmat.allsum(partial))
            parts.append(f"{name}-{m.metric_name}:{val:.6f}")

    def eval(self, data: DMatrix, name: str = "eval", iteration: int = 0) -> str:
        return self.eval_set([(data, name)], iteration)

    # ---------------------------------------------------------- model store
    def save_model(self, path: str, save_base64: bool = False):
        """Save the model; ``save_base64`` writes the text-safe encoding
        (the reference's ``bs64`` mode, learner-inl.hpp:240-252, which
        survives text-only channels).

        File writes are crash-safe: the payload (plus its CRC32
        integrity footer, reliability/integrity.py) goes through
        ``atomic_write``, so a watcher of ``path`` — the serving
        ModelRegistry, the checkpoint ring — can never observe a torn
        file.  ``stdout`` streams the bare payload (no footer: the
        reader of a pipe already owns the transport)."""
        assert self.gbtree is not None, "nothing to save"
        header = {
            "magic": _MAGIC,
            "param": _jsonable(self.param.to_dict()),
            "objective": self.param.objective,
            "booster": self.param.booster,
            "num_feature": self.num_feature,
            "attributes": self.attributes,
            "best_iteration": self.best_iteration,
        }
        state = self.gbtree.get_state()
        import io
        buf = io.BytesIO()
        np.savez(buf, header=np.frombuffer(
            json.dumps(header).encode(), dtype=np.uint8), **state)
        payload = buf.getvalue()
        if save_base64 or path == "stdout":
            # stdout is always base64, like the reference
            # (learner-inl.hpp:240-243)
            import base64
            payload = b"bs64\t" + base64.b64encode(payload) + b"\n"
            if path == "stdout":
                import sys
                sys.stdout.buffer.write(payload)
                sys.stdout.buffer.flush()
                return
        from xgboost_tpu.reliability.integrity import (add_footer,
                                                       atomic_write)
        with span("model.save", path=path, bytes=len(payload)):
            atomic_write(path, add_footer(payload))

    def load_model(self, path: str):
        from xgboost_tpu.reliability.integrity import (read_file,
                                                       verify_model_bytes)
        with span("model.load", path=path):
            raw = read_file(path)
            # strips + checks the CRC footer; raises ModelIntegrityError
            # on torn/bit-flipped files, warns once on footer-less
            # legacy files
            self.load_raw(verify_model_bytes(raw, name=path), name=path)

    def load_raw(self, raw: bytes, name: str = "<buffer>"):
        """Load a model from an in-memory buffer (reference
        XGBoosterLoadModelFromBuffer, wrapper/xgboost_wrapper.cpp:338-341).
        Sniffs the same formats as load_model: our npz, base64 text-safe
        (bs64), or the reference binary stream (binf / reference bs64)."""
        import io
        head = raw[:5]
        if head[:4] in (b"binf", b"bs64") and head != b"bs64\t":
            # reference binary format: delegate to the compat reader
            self._load_reference(raw)
            return
        if head == b"bs64\t":
            import base64
            try:
                dec = base64.b64decode(b"".join(raw[5:].split()),
                                       validate=True)
            except Exception as e:
                from xgboost_tpu.reliability.integrity import \
                    ModelIntegrityError
                raise ModelIntegrityError(
                    f"{name}: torn/invalid bs64 payload: {e}")
            if not dec.startswith(b"PK"):  # not our npz: reference stream
                self._load_reference(dec)
                return
            raw = dec
        self._load_np(io.BytesIO(raw), name)

    def _load_np(self, src, path):
        from xgboost_tpu.reliability.integrity import ModelIntegrityError
        try:
            z = np.load(src, allow_pickle=False)
        except Exception as e:
            # unparseable npz: for a footer-less file this is the only
            # torn-write signal there is — type it so recovery paths
            # (checkpoint-ring fallback, registry poisoning) can react
            from xgboost_tpu.obs import reliability_metrics
            reliability_metrics().integrity_failures.inc()
            raise ModelIntegrityError(
                f"{path} is not an xgboost_tpu model file: {e}")
        with z:
            header = json.loads(bytes(z["header"]).decode())
            assert header.get("magic") == _MAGIC, "not an xgboost_tpu model"
            self.param = TrainParam.from_dict(header["param"])
            self.num_feature = header["num_feature"]
            self.attributes = header.get("attributes", {})
            self.best_iteration = header.get("best_iteration", -1)
            state = {k: z[k] for k in z.files if k != "header"}
        self._init_obj()
        if self.param.booster == "gblinear":
            from xgboost_tpu.models.gblinear import GBLinear
            self.gbtree = GBLinear.from_state(self.param, state)
        else:
            from xgboost_tpu.models.gbtree import GBTree
            self.gbtree = GBTree.from_state(self.param, state)
        self._cache.clear()
        self._model_gen += 1

    def _load_reference(self, src):
        """Adopt the state of a reference-format model (path or bytes)."""
        from xgboost_tpu.compat import load_reference_model
        other = load_reference_model(src)
        self.param = other.param
        self.obj = other.obj
        self.gbtree = other.gbtree
        self.num_feature = other.num_feature
        self._cache.clear()
        self._model_gen += 1

    def save_raw(self) -> bytes:
        import io
        buf = io.BytesIO()
        header = {"magic": _MAGIC, "param": _jsonable(self.param.to_dict()),
                  "num_feature": self.num_feature,
                  "attributes": self.attributes,
                  "best_iteration": self.best_iteration}
        np.savez(buf, header=np.frombuffer(
            json.dumps(header).encode(), dtype=np.uint8),
            **self.gbtree.get_state())
        return buf.getvalue()

    # --------------------------------------------------------------- dumps
    def get_dump(self, fmap: str = "", with_stats: bool = False) -> List[str]:
        from xgboost_tpu.dump import dump_trees
        return dump_trees(self, fmap, with_stats)

    def dump_model(self, fout: str, fmap: str = "", with_stats: bool = False):
        dumps = self.get_dump(fmap, with_stats)
        from xgboost_tpu.reliability.integrity import atomic_write
        atomic_write(fout, "".join(
            f"booster[{i}]:\n{s}" for i, s in enumerate(dumps)).encode())

    def get_fscore(self, fmap: str = "") -> Dict[str, int]:
        """Split-count feature importance (wrapper/xgboost.py:512-530)."""
        from xgboost_tpu.dump import feature_importance
        return feature_importance(self, fmap)


def _pad_info(info: MetaInfo, n: int, pad: int, k: int = 1) -> MetaInfo:
    """Row-pad metadata with zero-weight rows so padded rows produce zero
    gradients (group_ptr is left untouched: rows past gptr[-1] are
    group-less and get no ranking pairs)."""
    if pad == 0:
        # still a fresh MetaInfo (sharing the arrays): the caller
        # populates _dev_cache with mesh-sharded device arrays, which
        # must not leak into the user's DMatrix
        out = MetaInfo()
        for f in ("label", "weight", "base_margin", "root_index",
                  "fold_index", "group_ptr"):
            setattr(out, f, getattr(info, f))
        return out
    out = MetaInfo()
    if info.label is not None:
        out.label = np.concatenate(
            [info.label, np.zeros(pad, np.float32)])
    out.weight = np.concatenate(
        [info.get_weight(n), np.zeros(pad, np.float32)])
    if info.base_margin is not None:
        # base_margin may arrive flat (n,), raveled (n*k,) or (n, k):
        # pad along ROWS so a later reshape(n_pad, k) stays valid
        bm = np.asarray(info.base_margin, np.float32).reshape(n, k)
        out.base_margin = np.concatenate(
            [bm, np.zeros((pad, k), np.float32)])
    if info.group_ptr is None:
        # one explicit group over the real rows, so ranking objectives never
        # pair padding rows
        out.group_ptr = np.array([0, n], dtype=np.int64)
    else:
        out.group_ptr = info.group_ptr
    return out


def _jsonable(d: dict) -> dict:
    out = {}
    for k, v in d.items():
        if isinstance(v, (np.integer,)):
            v = int(v)
        elif isinstance(v, (np.floating,)):
            v = float(v)
        elif isinstance(v, tuple):
            v = list(v)
        out[k] = v
    return out


_MAXIMIZE_METRICS = ("auc", "ams", "ndcg", "map", "pre")


def train(params: dict, dtrain: DMatrix, num_boost_round: int = 10,
          evals: Sequence[Tuple[DMatrix, str]] = (), obj=None, feval=None,
          maximize: Optional[bool] = None,
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[dict] = None, verbose_eval: bool = True,
          xgb_model=None, init_model=None) -> Booster:
    """Train a booster (reference wrapper/xgboost.py:533-632, including the
    early-stopping protocol: best_score/best_iteration attributes, stop
    after `early_stopping_rounds` non-improving rounds on the LAST metric
    of the LAST eval set).

    ``init_model``/``xgb_model`` (aliases; a Booster or a model path)
    warm-start continuation: the new rounds APPEND to the existing
    ensemble, and their iteration indices continue the existing round
    numbering — so per-iteration seeding (``fold_in(seed, iteration)``,
    subsample draws) matches what one uninterrupted run of
    ``existing + num_boost_round`` rounds would have used, and the
    continued model is bit-identical to it (the continuous-training
    pipeline's resume contract, PIPELINE.md)."""
    if init_model is not None and xgb_model is not None:
        raise ValueError("pass init_model or xgb_model, not both "
                         "(they are aliases)")
    xgb_model = xgb_model if xgb_model is not None else init_model
    start_round = 0
    if xgb_model is not None:
        bst = xgb_model if isinstance(xgb_model, Booster) else Booster(
            params, model_file=xgb_model)
        bst.set_param(params or {})
        # continuation rounds keep counting where the loaded ensemble
        # stopped (ntree accounting): round i of this call is global
        # iteration start_round + i
        if bst.gbtree is not None:
            start_round = bst.gbtree.num_boosted_rounds
    else:
        bst = Booster(params, cache=[dtrain] + [d for d, _ in evals])

    best_score = None
    best_iter = 0
    best_msg = ""

    if not evals and early_stopping_rounds is None:
        # nothing runs on the host between rounds: fuse the whole round
        # loop into one device launch where eligible (update_many falls
        # back to per-round updates otherwise)
        bst.update_many(dtrain, start_round, num_boost_round, fobj=obj)
        rounds = ()
    else:
        rounds = range(num_boost_round)

    for i in rounds:
        bst.update(dtrain, start_round + i, fobj=obj)
        if not evals:
            continue
        from contextlib import nullcontext
        prof = bst.profiler
        with prof.phase("eval") if prof else nullcontext():
            msg = bst.eval_set(evals, i, feval)  # folds into ended round
        # bool => on/off; int N > 1 => print every N rounds (and the
        # last), the newer reference wrappers' print-period idiom
        if verbose_eval and (
                verbose_eval is True or int(verbose_eval) <= 1
                or i % int(verbose_eval) == 0
                or i == num_boost_round - 1):
            print(msg)
        scores = _parse_eval(msg)
        if evals_result is not None:
            for k, v in scores.items():
                evals_result.setdefault(k, []).append(v)
        if early_stopping_rounds is not None:
            last_key = list(scores)[-1]
            score = scores[last_key]
            mx = maximize
            if mx is None:
                metric = last_key.split("-", 1)[1]
                mx = any(metric.startswith(m) for m in _MAXIMIZE_METRICS)
            improved = (best_score is None or
                        (score > best_score if mx else score < best_score))
            if improved:
                best_score, best_iter, best_msg = score, i, msg
            elif i - best_iter >= early_stopping_rounds:
                if verbose_eval:
                    print(f"Stopping. Best iteration:\n{best_msg}")
                break
    if early_stopping_rounds is not None and best_score is not None:
        bst.best_score = best_score
        bst.best_iteration = best_iter
    if getattr(bst, "_profiler", None) is not None:
        bst._profiler.print_summary()
        bst._profiler.stop()
    return bst


def _parse_eval(msg: str) -> Dict[str, float]:
    out = {}
    for part in msg.split("\t")[1:]:
        k, _, v = part.rpartition(":")
        out[k] = float(v)
    return out


class CVPack:
    """One fold's (train, test, booster) bundle (wrapper/xgboost.py:635-650)."""

    def __init__(self, dtrain: DMatrix, dtest: DMatrix, params: dict):
        self.dtrain, self.dtest = dtrain, dtest
        self.bst = Booster(params, cache=[dtrain, dtest])
        self.watchlist = [(dtrain, "train"), (dtest, "test")]

    def update(self, i, fobj):
        self.bst.update(self.dtrain, i, fobj)

    def eval(self, i, feval):
        return self.bst.eval_set(self.watchlist, i, feval)


def mknfold(dall: DMatrix, nfold: int, params: dict, seed: int,
            evals=(), fpreproc=None) -> List[CVPack]:
    """Random nfold partition (reference wrapper/xgboost.py:652-674)."""
    from xgboost_tpu.config import params_to_dict
    rng = np.random.RandomState(seed)
    idx = rng.permutation(dall.num_row)
    folds = np.array_split(idx, nfold)
    packs = []
    for k in range(nfold):
        test_idx = folds[k]
        train_idx = np.concatenate([folds[j] for j in range(nfold) if j != k])
        dtrain = dall.slice(np.sort(train_idx))
        dtest = dall.slice(np.sort(test_idx))
        p = params_to_dict(params)
        if fpreproc is not None:
            dtrain, dtest, p = fpreproc(dtrain, dtest, p)
        packs.append(CVPack(dtrain, dtest, p))
    return packs


def aggcv(rlist: List[str], show_stdv: bool = True) -> str:
    """Aggregate per-fold eval lines into cv mean+std (wrapper
    xgboost.py:676-695)."""
    cvmap: Dict[str, List[float]] = {}
    ret = rlist[0].split("\t")[0]
    for line in rlist:
        for part in line.split("\t")[1:]:
            k, _, v = part.rpartition(":")
            cvmap.setdefault(k, []).append(float(v))
    for k, vals in cvmap.items():
        v = np.asarray(vals)
        if show_stdv:
            ret += f"\tcv-{k}:{v.mean():.6f}+{v.std():.6f}"
        else:
            ret += f"\tcv-{k}:{v.mean():.6f}"
    return ret


def cv(params: dict, dtrain: DMatrix, num_boost_round: int = 10,
       nfold: int = 3, metrics=(), obj=None, feval=None, fpreproc=None,
       show_stdv: bool = True, seed: int = 0,
       verbose_eval: bool = True) -> List[str]:
    """k-fold cross validation (reference wrapper/xgboost.py:697-740)."""
    from xgboost_tpu.config import params_to_dict
    params = params_to_dict(params)
    if metrics:
        params["eval_metric"] = list(metrics)
    packs = mknfold(dtrain, nfold, params, seed, fpreproc=fpreproc)
    results = []
    for i in range(num_boost_round):
        for p in packs:
            p.update(i, obj)
        line = aggcv([p.eval(i, feval) for p in packs], show_stdv)
        if verbose_eval:
            print(line)
        results.append(line)
    return results
