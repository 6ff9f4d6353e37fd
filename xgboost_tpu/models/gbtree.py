"""GBTree: gradient-boosted tree ensemble booster.

The reference's ``GBTree`` (``src/gbm/gbtree-inl.hpp``): per-class tree
groups (:102-121), ``num_parallel_tree`` boosted-random-forest mode
(:393-396), prediction buffers keyed by leaf positions (:258-303), and
model commit per boosting round.  Here trees are fixed-shape tensor
stacks; the prediction "buffer" is an incrementally maintained margin
per cached DMatrix, updated from grow-time leaf positions — the same
fast path as the reference's ``GetLeafPosition`` shortcut
(``updater_distcol-inl.hpp:40-42``).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from xgboost_tpu.binning import CutMatrix
from xgboost_tpu.config import TrainParam
from xgboost_tpu.models.tree import (GrowConfig, TreeArrays, grow_tree,
                                     predict_leaf_binned,
                                     predict_margin_binned,
                                     predict_margin_fused, table_lookup,
                                     tree_capacity)
from xgboost_tpu.ops.histogram import kernel_mode
from xgboost_tpu.ops.split import SplitConfig


def make_grow_config(p: TrainParam, n_bin: int) -> GrowConfig:
    split = SplitConfig(
        reg_lambda=p.reg_lambda, reg_alpha=p.reg_alpha,
        max_delta_step=p.max_delta_step, min_child_weight=p.min_child_weight,
        gamma=p.gamma, eta=p.eta, default_direction=p.default_direction)
    return GrowConfig(split=split, max_depth=p.max_depth, n_bin=n_bin,
                      subsample=p.subsample,
                      colsample_bytree=p.colsample_bytree,
                      colsample_bylevel=p.colsample_bylevel,
                      hist_precision=p.hist_precision,
                      n_roots=max(1, p.num_roots))


@functools.partial(jax.jit, static_argnames=("t",))
def _unstack_trees(stacked, t: int):
    """Slice a (T, ...) tree stack into a tuple of per-tree pytrees in
    ONE device launch.  Doing this as T x n_fields eager ops costs a
    dispatch each."""
    return tuple(jax.tree.map(lambda x: x[i], stacked) for i in range(t))


@functools.partial(jax.jit, static_argnames=("t",))
def _unstack_lane_flats(stacked, t: int):
    """Slice the lane axis of a (L, n_rounds, K*npar, ...) gang-scan
    tree output into per-lane FLAT (n_rounds*K*npar, ...) stacks, all
    in ONE device launch.  The flatten rides inside the same program:
    reshaping eagerly per lane costs a dispatch per lane per tree field
    and dominated the stacked cycle."""
    flat = jax.tree.map(
        lambda x: x.reshape((x.shape[0], -1) + x.shape[3:]), stacked)
    return tuple(jax.tree.map(lambda x: x[i], flat) for i in range(t))


def _scan_rounds_impl(binned, margin, label, weight, base_key,
                      first_iteration, cut_values, n_cuts, row_valid,
                      binned_t, eval_binned, eval_margins, *,
                      n_rounds: int, K: int,
                      npar: int, cfg: GrowConfig, split_finder, grad_fn,
                      mesh, eval_is_train, etransform, pred_chunk: int,
                      hist_reduce=None):
    """``lax.scan`` over whole boosting rounds (one device launch for
    n_rounds x K x npar trees).  Module-level so the jit cache is shared
    across Booster instances: all static arguments (cfg, grad_fn,
    split_finder, etransform) carry stable identities.

    Device-resident eval (segmented round fusion): ``eval_binned``
    carries one binned matrix per non-train watchlist set and the
    corresponding ``eval_margins`` ride the scan carry; each round adds
    the round's tree contributions through the SAME
    ``predict_margin_binned`` expression the per-round margin sync uses
    (same ``pred_chunk``), then applies ``etransform``
    (Objective.eval_transform) — so the per-round transformed outputs
    the scan stacks are bit-identical to what the per-round eval path
    would have pulled, with zero host dispatches between rounds.
    ``eval_is_train`` marks watchlist slots that ARE the training
    matrix: those read the grow-time margin directly (the per-round
    path's prediction-buffer shortcut) instead of re-traversing.

    Returns ``(final margin (N, K), final eval margins,
    stacked trees (n_rounds, K*npar, ...),
    per-round transformed eval outputs (one (n_rounds, N_e, K) per
    watchlist slot))``.

    The round's parts carry ``jax.named_scope`` names — ``round.gradient``,
    ``round.margin``, ``round.eval`` here, ``grow.*`` in
    :func:`~xgboost_tpu.models.tree.grow_tree` — which land in the
    compiled module's ``op_name`` metadata and nowhere else: the names
    are a contract with whoever reads a device trace (OBSERVABILITY.md).
    """
    T_pr = K * npar
    group_pr = jnp.asarray([j // npar for j in range(T_pr)], jnp.int32)

    def grow_one(tkey, gh2):
        if mesh is not None:
            from xgboost_tpu.parallel.dp import grow_tree_dp
            rv = (row_valid if row_valid is not None
                  else jnp.ones(binned.shape[0], jnp.bool_))
            tree, row_leaf, d = grow_tree_dp(
                mesh, tkey, binned, gh2, cut_values, n_cuts, cfg, rv,
                split_finder=split_finder)
        else:
            tree, row_leaf, d = grow_tree(
                tkey, binned, gh2, cut_values, n_cuts, cfg, row_valid,
                hist_reduce=hist_reduce,
                split_finder=split_finder, binned_t=binned_t)
        if row_valid is not None:
            d = d * row_valid.astype(d.dtype)
        return tree, d

    def body(carry, i):
        margin, emargins = carry
        key = jax.random.fold_in(base_key, i)
        with jax.named_scope("round.gradient"):
            gh = grad_fn(margin, label, weight, i)       # (N, K, 2)
        if T_pr > 1:
            # ensemble axis vmapped: the batched shared-onehot histogram
            # kernel + broadcast-compare lookups make this the fast path
            # (same per-tree keys as the sequential loop — bit-matched)
            tkeys = jnp.stack([jax.random.fold_in(key, j)
                               for j in range(T_pr)])
            gh_t = jnp.take(gh, jnp.asarray(
                [j // npar for j in range(T_pr)], jnp.int32),
                axis=1).transpose(1, 0, 2)               # (T, N, 2)
            stacked, ds = jax.vmap(grow_one)(tkeys, gh_t)
            with jax.named_scope("round.margin"):
                delta = jnp.zeros_like(margin)
                for j in range(T_pr):
                    delta = delta.at[:, j // npar].add(ds[j])
                margin = margin + delta
        else:
            tree, d = grow_one(jax.random.fold_in(key, 0), gh[:, 0, :])
            stacked = jax.tree.map(lambda x: x[None], tree)
            with jax.named_scope("round.margin"):
                margin = margin + d[:, None]
        eouts, new_em = [], []
        ei = 0
        with jax.named_scope("round.eval"):
            for is_train in eval_is_train:
                if is_train:
                    eouts.append(etransform(margin))
                    continue
                em = (predict_margin_binned(
                    stacked, group_pr, eval_binned[ei],
                    jnp.zeros((), jnp.float32), cfg.max_depth, K,
                    root=None, n_roots=cfg.n_roots,
                    tree_chunk=pred_chunk) + emargins[ei])
                new_em.append(em)
                eouts.append(etransform(em))
                ei += 1
        return (margin, tuple(new_em)), (stacked, tuple(eouts))

    iters = first_iteration + jnp.arange(n_rounds)
    (margin, eval_margins), (stacks, eouts) = jax.lax.scan(
        body, (margin, eval_margins), iters)
    return margin, eval_margins, stacks, eouts


def _scan_rounds_mesh_impl(binned, margin, label, weight, base_key,
                           first_iteration, cut_values, n_cuts, row_valid,
                           binned_t, eval_binned, eval_margins, *,
                           n_rounds: int, K: int,
                           npar: int, cfg: GrowConfig, split_finder,
                           grad_fn, mesh, eval_is_train, etransform,
                           pred_chunk: int):
    """The K-round scan under ONE ``shard_map`` over the 'data' axis.

    Where :func:`_scan_rounds_impl` with ``mesh`` nests a per-tree
    ``grow_tree_dp`` shard_map INSIDE the scan (a shard_map entry/exit
    per tree-growth step, and GSPMD left to infer the sharding of the
    margin/eval carries between them), this wraps the WHOLE scan body
    in a single shard_map: rows stay shard-resident for the entire
    segment, the per-level histogram/node-stat psums
    (``dp._psum_data`` via grow_tree's ``hist_reduce`` seam) are the
    ONLY collectives in the program, watchlist eval margins accumulate
    per shard, and the host is contacted exactly once per segment.
    Tree stacks replicate for free — after each level's psum every
    shard computes the identical argmax split (the reference's
    TreeSyncher no-op, updater_sync-inl.hpp:34-49).

    Gradients must be rowwise (reg/softmax ``fused_grad``): the
    LambdaRank pad path needs global group structure, so its mesh runs
    keep the nested-``grow_tree_dp`` scan (update_many routes by
    ``entry.rank_pad_prep``).  Same per-round fold_in keys as every
    other boost path — with an exactly-associative histogram mode
    (``hist_precision=fixed``) the model bytes are invariant to the
    mesh device count (tests/test_mesh_fused.py).
    """
    from jax.sharding import PartitionSpec as P
    from xgboost_tpu.parallel.dp import _psum_data
    from jax import shard_map
    from xgboost_tpu.parallel.mesh import DATA_AXIS

    D = P(DATA_AXIS)
    R = P()

    def body(binned, margin, label, weight, base_key, first_iteration,
             cut_values, n_cuts, row_valid, eval_binned, eval_margins):
        return _scan_rounds_impl(
            binned, margin, label, weight, base_key, first_iteration,
            cut_values, n_cuts, row_valid, None, eval_binned,
            eval_margins, n_rounds=n_rounds, K=K, npar=npar, cfg=cfg,
            split_finder=split_finder, grad_fn=grad_fn, mesh=None,
            eval_is_train=eval_is_train, etransform=etransform,
            pred_chunk=pred_chunk, hist_reduce=_psum_data)

    # check_vma=False + out_specs P() for the tree stacks: replicated
    # by the psum'd split argmax (the grow_tree_dp convention).  The
    # per-round transformed eval outputs stack rounds on axis 0 with
    # rows still sharded on axis 1.
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(D, D, D, D, R, R, R, R, D, D, D),
        out_specs=(D, D, R, P(None, DATA_AXIS)),
        check_vma=False)
    return fn(binned, margin, label, weight, base_key, first_iteration,
              cut_values, n_cuts, row_valid, eval_binned, eval_margins)


# Jit wrappings of the round-scan implementations, one each: the
# margin (arg 1) and eval-margin (arg 11) carries' buffers are donated
# to XLA so segment k+1 updates segment k's output in place — no
# per-segment device copy of the O(N*K) state.  Every backend the repo
# runs on honours the donation (the CPU one too: a donated input is
# deleted), so a caller never reads an array it passed in those
# positions (lint rule XGT013 checks the call sites).
# ``_scan_rounds_mesh`` compiles the whole-scan shard_map (mesh-fused
# training); ``_scan_rounds`` keeps ``mesh`` for the legacy
# nested-grow_tree_dp scan (rank objectives).
_SCAN_STATIC = ("n_rounds", "K", "npar", "cfg", "split_finder",
                "grad_fn", "mesh", "eval_is_train", "etransform",
                "pred_chunk")
_scan_rounds = functools.partial(
    jax.jit, static_argnames=_SCAN_STATIC + ("hist_reduce",),
    donate_argnums=(1, 11))(_scan_rounds_impl)
_scan_rounds_mesh = functools.partial(
    jax.jit, static_argnames=_SCAN_STATIC,
    donate_argnums=(1, 11))(_scan_rounds_mesh_impl)


def _scan_rounds_lanes_impl(binned, margin, label, weight, base_key,
                            first_iteration, cut_values, n_cuts,
                            row_valid, *, n_rounds: int, K: int,
                            npar: int, cfg: GrowConfig, split_finder,
                            grad_fn, pred_chunk: int):
    """Lane-stacked round scan: ``jax.vmap`` of :func:`_scan_rounds_impl`
    over a leading LANE axis — L same-shape tenant boosters advance
    ``n_rounds`` rounds in ONE device dispatch (PIPELINE.md
    "Gang-batched lanes").  Every operand carries the lane axis:
    (L, N, F) bins, (L, N, K) margins/labels, (L,) first iterations,
    (L, 2) RNG keys, (L, F, W) cut values, (L, N) row-validity masks.
    Inactive pad rows/lanes are all-False ``row_valid`` — grow_tree
    zeroes their gradients and parks them at ``pos = -1`` (the
    histogram's existing inactive-row convention), so a pad lane grows
    degenerate zero trees the host discards and a padded row never
    touches a real lane's sums.  Watchlist eval stays HOST-side
    (per-tenant gating needs per-tenant metrics anyway), so the eval
    carry is empty.  ``first_iteration`` is dynamic and per-lane:
    tenants at different incumbent rounds share one compiled dispatch.

    Returns ``(final margins (L, N, K),
    stacked trees (L, n_rounds, K*npar, ...))``.
    """
    def one(binned, margin, label, weight, base_key, first_iteration,
            cut_values, n_cuts, row_valid):
        m, _, stacks, _ = _scan_rounds_impl(
            binned, margin, label, weight, base_key, first_iteration,
            cut_values, n_cuts, row_valid, None, (), (),
            n_rounds=n_rounds, K=K, npar=npar, cfg=cfg,
            split_finder=split_finder, grad_fn=grad_fn, mesh=None,
            eval_is_train=(), etransform=None, pred_chunk=pred_chunk)
        return m, stacks

    return jax.vmap(one)(binned, margin, label, weight, base_key,
                         first_iteration, cut_values, n_cuts, row_valid)


_LANE_STATIC = ("n_rounds", "K", "npar", "cfg", "split_finder",
                "grad_fn", "pred_chunk")
_scan_rounds_lanes = functools.partial(
    jax.jit, static_argnames=_LANE_STATIC,
    donate_argnums=(1,))(_scan_rounds_lanes_impl)


class GBTree:
    """Tree ensemble state + boosting step (reference IGradBooster: DoBoost /
    Predict / PredictLeaf / DumpModel, src/gbm/gbm.h:19-125)."""

    def __init__(self, param: TrainParam, cuts: CutMatrix):
        self.param = param
        self.cuts = cuts
        self.cfg = make_grow_config(param, cuts.max_bin)
        # TRUE exact-greedy mode (models/colmaker.py): bin-free raw-value
        # pipeline.  Covers single-controller AND dsplit=col (the
        # DistColMaker analog runs the same finder per feature shard —
        # colsplit.grow_tree_exact_colsplit); only dsplit=row keeps the
        # quantized form (the reference switches away from exact there,
        # learner-inl.hpp:91-93)
        from xgboost_tpu.models.updaters import parse_updaters
        self.exact_raw = ("grow_colmaker" in parse_updaters(param.updater)
                          and param.dsplit != "row")
        self._split_finder_cache = None  # stable identity (jit static arg)
        self._trees_list: List[TreeArrays] = []  # materialized per-tree pytrees
        # stacked trees not yet sliced into _trees_list (fused rounds /
        # model load keep the ensemble stacked; slicing T trees eagerly
        # costs a T-output jit per distinct T and duplicates the stack).
        # Held as a LIST of flat (t_i, ...) stacks so absorbing a scan
        # segment is a pure host append — concatenation is deferred to
        # the first _stack()/trees read (the gang-batched lane driver
        # absorbs N tenants per dispatch; N*leaves tiny device concats
        # per segment would swamp the stacked scan it just saved)
        self._pending: Optional[Tuple[List[TreeArrays], int]] = None
        self.tree_group: List[int] = []
        self._stack_cache: Optional[Tuple[int, TreeArrays, jax.Array]] = None
        self.cut_values_dev = jnp.asarray(cuts.cut_values)
        self.n_cuts_dev = jnp.asarray(cuts.n_cuts)
        # PRNGKey(seed), built once: a stable OBJECT, not just a stable
        # value — the lane-stacking driver's steady-bucket carry keys on
        # identity, and a per-cycle PRNGKey would be one device dispatch
        # per lane per cycle for a constant
        self._base_key_cache: Optional[jax.Array] = None
        self._col_pad_cache = None  # (n_shard, cut_values, n_cuts)
        # (kept_ids, cut_values, n_cuts, kept_dev) of the EMA-FS
        # feature screen (do_boost_fused feature_screen=); rebuilding
        # the screened cut arrays every segment would be wasted traffic
        self._screen_cut_cache = None
        # chunked tree-parallel traversal width (models/tree.py); 0/1 =
        # the sequential scan baseline; -1 auto = 32 on TPU, scan on
        # CPU (the batched compare-select kernel loses to the scan's
        # cache locality there; the TPU width is not measured on this
        # machine).  The env override is the A/B seam.
        env_chunk = os.environ.get("XGBTPU_PREDICT_TREE_CHUNK")
        if env_chunk not in (None, ""):
            self.pred_chunk = max(0, int(env_chunk))
        else:
            pc = int(param.predict_tree_chunk)
            if pc < 0:
                pc = 32 if jax.default_backend() == "tpu" else 0
            self.pred_chunk = pc

    @property
    def trees(self) -> List[TreeArrays]:
        """Per-tree pytree list; materializes any stacked pending trees
        on first access (prediction/save after fused training go through
        the stack cache and never pay this)."""
        if self._pending is not None:
            flats, t = self._pending
            self._pending = None
            flat = flats[0] if len(flats) == 1 else jax.tree.map(
                lambda *xs: jnp.concatenate(xs), *flats)
            self._trees_list.extend(_unstack_trees(flat, t))
        return self._trees_list

    def base_key(self) -> jax.Array:
        """The booster's root ``PRNGKey(seed)`` (cached; see __init__)."""
        if self._base_key_cache is None:
            self._base_key_cache = jax.random.PRNGKey(self.param.seed)
        return self._base_key_cache

    def col_arrays(self, n_shard: int):
        """Cut arrays feature-padded to the column mesh (cached: padding
        the same arrays every boosting round is wasted HBM traffic)."""
        if self._col_pad_cache is None or self._col_pad_cache[0] != n_shard:
            from xgboost_tpu.parallel.colsplit import pad_features
            self._col_pad_cache = (
                n_shard,
                pad_features(self.cut_values_dev, n_shard, axis=0,
                             fill=jnp.inf),
                pad_features(self.n_cuts_dev, n_shard, axis=0))
        return self._col_pad_cache[1], self._col_pad_cache[2]

    def _grow_cfg(self, kept=None) -> GrowConfig:
        """``cfg`` with the features the cuts make narrow
        (``GrowConfig.hist_narrow``: their level histograms take a
        kernel call of their own), for a grower handed every column or
        the screened columns ``kept`` (ascending full-space ids).  The
        column-split and lane-stacked growers keep ``cfg`` as it is."""
        from xgboost_tpu.ops.pallas_hist import narrow_features
        narrow = narrow_features(self.cuts.n_cuts, self.cfg.n_bin)
        if kept is not None:
            ns = set(narrow)
            narrow = tuple(p for p, f in enumerate(kept) if int(f) in ns)
        return self.cfg._replace(hist_narrow=narrow)

    def _split_finder(self):
        """The pluggable split finder: skmaker's 3-way sketch selection
        when updater=grow_skmaker, else None (= histogram argmax).
        Cached so the jitted growers see a stable static identity."""
        if self._split_finder_cache is None:
            from xgboost_tpu.models.updaters import parse_updaters
            if "grow_skmaker" in parse_updaters(self.param.updater):
                from xgboost_tpu.models.skmaker import skmaker_split_finder
                K = max(4, int(self.param.sketch_ratio
                               / max(self.param.sketch_eps, 1e-6)))
                self._split_finder_cache = skmaker_split_finder(
                    min(K, self.cfg.n_bin))
            else:
                self._split_finder_cache = False
        return self._split_finder_cache or None

    def rebind_cuts(self, cuts: CutMatrix) -> None:
        """Swap the quantile cut matrix under the live ensemble — the
        online cut-refresh seam (xgboost_tpu.stream): every node's
        ``cut_index`` is re-derived from its RAW ``threshold`` in the
        new per-feature cut row, so future BINNED training routes rows
        through the exact same "v < threshold" boundaries while fresh
        splits draw from drift-tracking cuts.  The swap is EXACT when
        every live threshold appears in its feature's new row — callers
        build the new cuts as (sketch proposal ∪ live thresholds,
        ``stream.drift.propose_refreshed_cuts``); a missing threshold
        raises ValueError with the model untouched."""
        cv = np.asarray(cuts.cut_values)
        nc = np.asarray(cuts.n_cuts)
        if self.num_trees:
            stack, group = self._stack(0)
            feat = np.asarray(stack.feature)          # (T, n_nodes)
            thr = np.asarray(stack.threshold)
            ci = np.array(stack.cut_index)
            m = feat >= 0
            if m.any():
                f = feat[m]
                th = thr[m]
                if int(f.max()) >= cv.shape[0]:
                    raise ValueError(
                        f"rebind_cuts: model splits feature {int(f.max())}"
                        f" but the new cuts cover only {cv.shape[0]}")
                rows = cv[f]                          # (M, max_cuts)
                idx = (rows < th[:, None]).sum(axis=1)
                at = rows[np.arange(len(f)),
                          np.minimum(idx, rows.shape[1] - 1)]
                ok = (idx < nc[f]) & (at == th)
                if not ok.all():
                    bad = int(f[~ok][0])
                    raise ValueError(
                        f"rebind_cuts: live split threshold "
                        f"{float(th[~ok][0])!r} of feature {bad} is "
                        "absent from the new cuts — refreshed cuts must "
                        "include every live threshold")
                ci[m] = idx
            stack = stack._replace(
                cut_index=jnp.asarray(ci, jnp.int32))
            T = int(stack.feature.shape[0])
            self._trees_list = []
            self._pending = ([stack], T)
            self._stack_cache = (T, stack, group)
        self.cuts = cuts
        self.cfg = make_grow_config(self.param, cuts.max_bin)
        self.cut_values_dev = jnp.asarray(cuts.cut_values)
        self.n_cuts_dev = jnp.asarray(cuts.n_cuts)
        self._col_pad_cache = None
        self._screen_cut_cache = None

    def _comm_bytes(self, n_feat: int, mesh=None) -> float:
        """Logical HISTOGRAM-allreduce payload estimate per tree-growth
        launch (the report_stats bytes analog, obs/comm.py): each level
        reduces per-node (F, n_bin, 2) f32 histogram partials and the
        node count doubles per level.  0 when no row mesh is active —
        single-chip runs reduce nothing, and column split never
        allreduces histograms (its SplitDecision gathers are accounted
        by colsplit.py itself as "allgather").  An estimate of what the
        reference would have shipped over rabit — ICI wire bytes are
        not observable host-side."""
        if mesh is None:
            return 0.0
        return float(((1 << self.cfg.max_depth) - 1)
                     * n_feat * self.cfg.n_bin * 2 * 4)

    @property
    def num_trees(self) -> int:
        return len(self._trees_list) + (
            self._pending[1] if self._pending is not None else 0)

    @property
    def num_boosted_rounds(self) -> int:
        k = max(1, self.param.num_output_group) * max(
            1, self.param.num_parallel_tree)
        return self.num_trees // k

    # ---------------------------------------------------------------- boost
    def do_boost(self, binned: jax.Array, gh: jax.Array, key: jax.Array,
                 row_valid: Optional[jax.Array] = None,
                 mesh=None, col_mesh=None,
                 root: Optional[jax.Array] = None,
                 exact_has_missing: bool = True,
                 exact_ranks=None,
                 binned_t: Optional[jax.Array] = None
                 ) -> Tuple[List[TreeArrays], jax.Array]:
        """One boosting round: grows num_output_group × num_parallel_tree
        trees (reference BoostNewTrees, gbtree-inl.hpp:238-273), then runs
        the prune updater if configured (reference updater pipeline
        "grow_histmaker,prune", gbtree-inl.hpp:218-236).

        gh: (N, K, 2).  Returns (new_trees, leaf_contrib (N, K) margin delta)
        computed from grow-time leaf positions — the prediction-buffer fast
        path (gbtree-inl.hpp:258-303).  With `mesh`, rows are sharded over
        the 'data' axis and histograms psum-reduced (SURVEY.md §5.8); with
        `col_mesh`, features are sharded over 'feat' (DistColMaker).
        """
        from xgboost_tpu.models.updaters import parse_updaters, prune_tree

        do_prune = ("prune" in parse_updaters(self.param.updater)
                    and self.param.gamma > 0.0)
        K = max(1, self.param.num_output_group)
        npar = max(1, self.param.num_parallel_tree)
        new_trees: List[TreeArrays] = []
        deltas = []
        from xgboost_tpu.parallel import mock
        import os
        # ensemble parallelism (SURVEY.md §2.4.5): all class-group x
        # parallel trees of the round grow in ONE vmapped launch:
        # (a) jax.vmap of the level histogram dispatches to the
        # tree-batched shared-onehot kernel via custom_vmap
        # (ops/histogram.py) and (b) the per-row small-table lookups
        # batch as broadcast-compare selects instead of gathers
        # (tree.table_lookup).  XGBTPU_SEQ_BOOST=1 restores sequential
        # launches.
        if root is not None and (col_mesh is not None
                                 or self.cfg.n_roots <= 1):
            raise NotImplementedError(
                "root_index needs num_roots > 1 (and dsplit != col): set "
                "num_roots to the number of tree roots")
        if self.exact_raw:
            return self._do_boost_exact(binned, gh, key, row_valid,
                                        do_prune, K, npar,
                                        exact_has_missing, exact_ranks,
                                        col_mesh=col_mesh)
        if (col_mesh is None and K * npar > 1
                and not os.environ.get("XGBTPU_SEQ_BOOST")):
            return self._do_boost_vmapped(binned, gh, key, row_valid, mesh,
                                          K, npar, do_prune, root)
        from xgboost_tpu.obs import comm
        comm_nbytes = self._comm_bytes(binned.shape[1], mesh)
        for k in range(K):
            delta_k = None
            for t in range(npar):
                # one "seqno" per tree-growth launch (the collective unit:
                # psum histograms / split reduce happen inside); the seam
                # also counts it into the per-round collective stats, and
                # the timed() wrapper below adds the launch wall seconds
                mock.collective(nbytes=comm_nbytes)
                tkey = jax.random.fold_in(key, k * npar + t)
                _t_launch = time.perf_counter()
                if col_mesh is not None:
                    if self._split_finder() is not None:
                        raise NotImplementedError(
                            "updater=grow_skmaker is not supported under "
                            "dsplit=col (the column-split grower reduces "
                            "SplitEntry tuples, not summaries)")
                    from xgboost_tpu.parallel.colsplit import (
                        grow_tree_colsplit, pad_features)
                    n_shard = col_mesh.devices.size
                    cv, nc = self.col_arrays(n_shard)
                    if binned.shape[1] % n_shard:  # caller didn't pre-pad
                        binned = pad_features(binned, n_shard, axis=1)
                    tree, row_leaf, d = grow_tree_colsplit(
                        col_mesh, tkey, binned, gh[:, k, :], cv, nc,
                        self.cfg, row_valid,
                        f_real=self.cuts.num_feature)
                elif mesh is not None:
                    from xgboost_tpu.parallel.dp import grow_tree_dp
                    rv = row_valid if row_valid is not None else \
                        jnp.ones(binned.shape[0], jnp.bool_)
                    tree, row_leaf, d = grow_tree_dp(
                        mesh, tkey, binned, gh[:, k, :], self.cut_values_dev,
                        self.n_cuts_dev, self._grow_cfg(), rv,
                        split_finder=self._split_finder(), root=root)
                else:
                    tree, row_leaf, d = grow_tree(
                        tkey, binned, gh[:, k, :], self.cut_values_dev,
                        self.n_cuts_dev, self._grow_cfg(), row_valid,
                        split_finder=self._split_finder(), root=root,
                        binned_t=binned_t)
                # host-side launch wall time of the collective unit the
                # seam counted above (count=0: no double count).  Under
                # column split the launch is already timed inside
                # grow_tree_colsplit as "allgather" — adding it here too
                # would double the total comm seconds.
                if col_mesh is None:
                    comm.record("allreduce", count=0,
                                seconds=time.perf_counter() - _t_launch)
                if do_prune:
                    tree, resolve = prune_tree(tree, self.param.gamma,
                                               self.cfg.n_roots)
                    d = table_lookup(tree.leaf_value[jnp.asarray(resolve)],
                                     row_leaf)
                if row_valid is not None:
                    # padding rows land on node 0, which carries the root's
                    # would-be leaf weight; zero their delta so their cached
                    # margin stays at the entry's (zero-padded) base value
                    d = d * row_valid.astype(d.dtype)
                new_trees.append(tree)
                self.trees.append(tree)
                self.tree_group.append(k)
                delta_k = d if delta_k is None else delta_k + d
            deltas.append(delta_k)
        self._stack_cache = None
        return new_trees, jnp.stack(deltas, axis=1)

    def _do_boost_exact(self, X, gh, key, row_valid, do_prune: bool,
                        K: int, npar: int, has_missing: bool = True,
                        exact_ranks=None, col_mesh=None):
        """Exact-greedy round: sequential per-tree growth (the exact
        scans don't share a one-hot, so there is nothing to batch).
        With ``col_mesh``, each shard scans its own raw columns and
        winners reduce over the mesh — TRUE exact column split at any
        cardinality (colsplit.grow_tree_exact_colsplit)."""
        from xgboost_tpu.models.colmaker import grow_tree_exact
        from xgboost_tpu.models.updaters import prune_tree
        from xgboost_tpu.parallel import mock
        if self.cfg.n_roots > 1:
            raise NotImplementedError(
                "num_roots > 1 is not supported by the exact grower")
        new_trees: List[TreeArrays] = []
        deltas = []
        from xgboost_tpu.obs import comm
        for k in range(K):
            delta_k = None
            for t in range(npar):
                # exact mode reduces SplitEntry tuples + routing
                # bitmaps, not histograms: count the launch, skip the
                # payload estimate
                mock.collective()
                tkey = jax.random.fold_in(key, k * npar + t)
                _t_launch = time.perf_counter()
                rk, uq = exact_ranks if exact_ranks is not None \
                    else (None, None)
                if col_mesh is not None:
                    from xgboost_tpu.parallel.colsplit import \
                        grow_tree_exact_colsplit
                    tree, row_leaf, _ = grow_tree_exact_colsplit(
                        col_mesh, tkey, X, gh[:, k, :], self.cfg,
                        row_valid, has_missing=has_missing,
                        rank_t=rk, uniq=uq,
                        f_real=self.cuts.num_feature)
                else:
                    tree, row_leaf = grow_tree_exact(
                        tkey, X, gh[:, k, :], self.cfg, row_valid,
                        has_missing=has_missing, rank_t=rk, uniq=uq)
                comm.record("allreduce", count=0,
                            seconds=time.perf_counter() - _t_launch)
                if do_prune:
                    tree, resolve = prune_tree(tree, self.param.gamma)
                    d = table_lookup(tree.leaf_value[jnp.asarray(resolve)],
                                     row_leaf)
                else:
                    d = table_lookup(tree.leaf_value, row_leaf)
                if row_valid is not None:
                    d = d * row_valid.astype(d.dtype)
                new_trees.append(tree)
                self.trees.append(tree)
                self.tree_group.append(k)
                delta_k = d if delta_k is None else delta_k + d
            deltas.append(delta_k)
        self._stack_cache = None
        return new_trees, jnp.stack(deltas, axis=1)

    def _do_boost_vmapped(self, binned, gh, key, row_valid, mesh,
                          K: int, npar: int, do_prune: bool, root=None):
        """Grow the round's K*npar trees in a single vmapped launch
        (reference: one tree per class group per round,
        gbtree-inl.hpp:104-117, num_parallel_tree :247-253 — here the
        ensemble axis is a batch axis over the same histograms kernel).

        Bit-matches the sequential path: per-tree keys, subsampling and
        histograms are identical; only the launch is batched.
        """
        from xgboost_tpu.models.updaters import prune_tree
        from xgboost_tpu.parallel import mock
        # keep the seqno space identical to the sequential path (one per
        # tree) so mock fault coordinates fire regardless of backend; a
        # hit kills the round before the batched launch, which recovery
        # treats the same as a mid-round death (partial state discarded).
        # The comm stats inherit the same count space (one logical
        # allreduce per tree, even though the launch is batched).
        from xgboost_tpu.obs import comm
        comm_nbytes = self._comm_bytes(binned.shape[1], mesh)
        for _ in range(K * npar):
            mock.collective(nbytes=comm_nbytes)
        _t_launch = time.perf_counter()

        T = K * npar
        keys = jnp.stack([jax.random.fold_in(key, i) for i in range(T)])
        kk = jnp.asarray([i // npar for i in range(T)], jnp.int32)
        gh_t = jnp.take(gh, kk, axis=1).transpose(1, 0, 2)   # (T, N, 2)

        if mesh is not None:
            from xgboost_tpu.parallel.dp import grow_tree_dp
            rv = row_valid if row_valid is not None else \
                jnp.ones(binned.shape[0], jnp.bool_)

            def one(tkey, gh2):
                return grow_tree_dp(mesh, tkey, binned, gh2,
                                    self.cut_values_dev, self.n_cuts_dev,
                                    self.cfg, rv,
                                    split_finder=self._split_finder(),
                                    root=root)
            stacked, row_leafs, ds = jax.vmap(one)(keys, gh_t)
        else:
            def one(tkey, gh2):
                return grow_tree(tkey, binned, gh2, self.cut_values_dev,
                                 self.n_cuts_dev, self.cfg, row_valid,
                                 split_finder=self._split_finder(),
                                 root=root)
            stacked, row_leafs, ds = jax.vmap(one)(keys, gh_t)
        comm.record("allreduce", count=0,
                    seconds=time.perf_counter() - _t_launch)

        new_trees = list(_unstack_trees(stacked, T))
        if do_prune:
            # pruning is host-side per tree; the delta re-gather stays
            # eager (prune runs only when gamma > 0)
            deltas = jnp.zeros((binned.shape[0], K), jnp.float32)
            for i in range(T):
                tree, resolve = prune_tree(new_trees[i], self.param.gamma,
                                           self.cfg.n_roots)
                d = table_lookup(tree.leaf_value[jnp.asarray(resolve)],
                                 row_leafs[i])
                if row_valid is not None:
                    d = d * row_valid.astype(d.dtype)
                new_trees[i] = tree
                deltas = deltas.at[:, i // npar].add(d)
        else:
            deltas = jnp.zeros((binned.shape[0], K), jnp.float32)
            for i in range(T):
                d = ds[i]
                if row_valid is not None:
                    d = d * row_valid.astype(d.dtype)
                deltas = deltas.at[:, i // npar].add(d)
        for i, tree in enumerate(new_trees):
            self.trees.append(tree)
            self.tree_group.append(i // npar)
        self._stack_cache = None
        return new_trees, deltas

    # ------------------------------------------------------------ fused boost
    def do_boost_fused(self, binned, margin, info, grad_fn,
                       first_iteration: int, n_rounds: int,
                       row_valid=None, mesh=None, binned_t=None,
                       eval_binned=(), eval_margins=(),
                       eval_is_train=(), etransform=None,
                       rowwise_grad: bool = True, feature_screen=None):
        """Scan ``n_rounds`` whole boosting rounds in ONE device launch.

        Per-round host dispatch (gradient launch + growth launch + margin
        update) has a cost per launch (not measured on this machine);
        folding the round loop into ``lax.scan`` removes it entirely
        and lets XLA pipeline rounds back-to-back.  The round
        body replays the sequential path exactly — same per-round
        ``fold_in`` keys, same kernels — so the resulting model
        bit-matches ``do_boost`` called ``n_rounds`` times (tested).

        The reference has no analog (its round loop is inherently
        host-side, ``xgboost_main.cpp:183-217``); this is the TPU-native
        shape of "the round loop is itself a compiled program".

        Restrictions (callers fall back to per-round ``do_boost``):
        no pruning (``gamma > 0`` pruning is a host-side pass), no
        refresh, no column split, and a jittable gradient function
        (standard reg/softmax objectives).  Fault injection IS
        compatible: the per-round injector coordinates replay host-side
        BEFORE the segment dispatches (same round/seqno space as the
        per-round path), so a simulated death or stall fires at a
        segment boundary and resume from the checkpoint ring replays
        the whole segment bit-identically.

        Args:
          margin: (N, K) current margins (device).  DONATED, like
            ``eval_margins``: the caller replaces its own references
            with the returned arrays and reads the passed ones no more.
          info: MetaInfo supplying device-cached label/weight.
          grad_fn: pure ``(margin, label, weight, iteration) -> (N, K, 2)``
            gradient with stable identity (Objective.fused_grad).
          row_valid: optional (N,) bool mask of real rows.
          mesh: optional data-parallel mesh (rows sharded over 'data').
          rowwise_grad: ``grad_fn`` is a pure per-row map (standard
            reg/softmax fused gradients) — with ``mesh`` this selects
            the whole-scan shard_map driver
            (:func:`_scan_rounds_mesh_impl`); group-structured
            gradients (LambdaRank pad path) keep the legacy
            nested-``grow_tree_dp`` scan.
          eval_binned / eval_margins / eval_is_train / etransform:
            device-resident watchlist evaluation (see
            :func:`_scan_rounds_impl`) — per-round transformed eval
            outputs come back stacked, one launch for the whole segment.
          feature_screen: optional ascending FULL-space feature ids the
            caller screened ``binned``/``eval_binned`` down to (EMA-FS,
            xgboost_tpu.stream): the scan grows trees over the screened
            (C, N, F_kept) working set using matching screened cut
            arrays, and grown trees' feature ids are remapped back to
            the full space before they join the ensemble — model bytes
            and prediction never see the screen.

        Returns ``(final margin (N, K), final eval margins tuple,
        stacked per-round transformed eval outputs tuple)``; grown
        trees are appended.
        """
        K = max(1, self.param.num_output_group)
        npar = max(1, self.param.num_parallel_tree)
        mesh_scan = mesh is not None and rowwise_grad
        # the fused scan still performs the per-round collectives; keep
        # the comm/seqno count space identical to the per-round path by
        # replaying one injector-seam entry per tree-growth step BEFORE
        # the dispatch (an armed die/stall fires here, at the segment
        # boundary — the checkpoint ring then replays the segment).
        # The mesh-fused driver counts its REAL collectives: one
        # histogram psum per level per tree into the xgbtpu_comm_psum_*
        # families (max_depth per growth step; the terminal level's
        # node stats derive from the parent's split — no reduction).
        # Single-device/legacy launches keep the per-round path's
        # logical "allreduce" accounting; NOTHING charges the dispatch
        # wall time to a collective family — that wall time is device
        # compute and belongs to xgbtpu_train_dispatch_seconds alone.
        from xgboost_tpu.obs import span, training_metrics
        from xgboost_tpu.parallel import mock
        with span("train.dispatch", first_round=first_iteration,
                  n_rounds=n_rounds,
                  mesh_fused=bool(mesh_scan)) as dispatch:
            with span("train.launch",
                      hist_mode=kernel_mode(self.cfg.hist_precision)):
                label = info.label_dev()
                weight = info.weight_dev(margin.shape[0])
                cut_vals, cut_ns = self.cut_values_dev, self.n_cuts_dev
                kept_dev = None
                if feature_screen is not None:
                    kept = tuple(int(i) for i in feature_screen)
                    cache = self._screen_cut_cache
                    if cache is None or cache[0] != kept:
                        kidx = jnp.asarray(kept, jnp.int32)
                        cache = (kept,
                                 jnp.take(self.cut_values_dev, kidx,
                                          axis=0),
                                 jnp.take(self.n_cuts_dev, kidx), kidx)
                        self._screen_cut_cache = cache
                    _, cut_vals, cut_ns, kept_dev = cache
                comm_nbytes = self._comm_bytes(binned.shape[1], mesh)
                for r in range(n_rounds):
                    mock.begin_round(first_iteration + r)
                    for _ in range(K * npar):
                        if mesh_scan:
                            mock.collective("psum", nbytes=comm_nbytes,
                                            count=self.cfg.max_depth)
                        else:
                            mock.collective(nbytes=comm_nbytes)
                scan = _scan_rounds_mesh if mesh_scan else _scan_rounds
                margin_f, emargins_f, stacks, eouts = scan(
                    binned, margin, label, weight,
                    self.base_key(),
                    jnp.int32(first_iteration), cut_vals,
                    cut_ns, row_valid, binned_t,
                    tuple(eval_binned), tuple(eval_margins),
                    n_rounds=n_rounds, K=K, npar=npar,
                    cfg=self._grow_cfg(feature_screen),
                    split_finder=self._split_finder(), grad_fn=grad_fn,
                    mesh=mesh, eval_is_train=tuple(eval_is_train),
                    etransform=etransform, pred_chunk=self.pred_chunk)
            with span("train.wait"):
                # block at the segment boundary: the driver pulls eval
                # lines / checkpoint bytes from this dispatch next, and
                # the histogram must record device wall time, not async
                # dispatch
                jax.block_until_ready(margin_f)
        tm = training_metrics()
        tm.dispatch_seconds.observe(dispatch.seconds)
        tm.rounds_per_dispatch.set(float(n_rounds))
        with span("train.absorb"):
            # flatten (n_rounds, K*npar, ...) -> (T_new, ...) and install
            # the full-ensemble stack cache directly: prediction then
            # reuses the scan's own output instead of re-stacking T
            # per-tree slices
            flat = jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), stacks)
            if kept_dev is not None:
                # grown trees speak the SCREENED feature space; remap
                # split ids back to the full space before anything
                # concatenates, persists or predicts (thresholds/cut
                # indices already match the full space: screened rows
                # are whole full-space rows)
                f = flat.feature
                flat = flat._replace(feature=jnp.where(
                    f >= 0,
                    jnp.take(kept_dev,
                             jnp.clip(f, 0, kept_dev.shape[0] - 1)),
                    f))
            self._append_flat_trees(flat, n_rounds)
        return margin_f, emargins_f, eouts

    def _append_flat_trees(self, flat, n_rounds: int) -> None:
        """Append a flattened ``(n_rounds*K*npar, ...)`` tree stack grown
        by a fused or lane-stacked scan: a pure host-side list append —
        zero device dispatches.  Concatenation into the full-ensemble
        stack is deferred to the next :meth:`_stack` read (one concat
        per leaf, however many segments accumulated).  The gang-batched
        lane driver absorbs N tenants per dispatch; eager per-lane
        concat + cache rebuild here used to cost ~25 tiny device ops
        per lane and swamped the stacked scan it had just saved."""
        K = max(1, self.param.num_output_group)
        npar = max(1, self.param.num_parallel_tree)
        group_new = [j // npar for _ in range(n_rounds)
                     for j in range(K * npar)]
        T_new = n_rounds * K * npar
        # keep the new trees STACKED (ADVICE r2: eager unstack compiles a
        # T-output program per distinct T and duplicates the cached
        # stack); the trees property slices lazily if anything needs
        # per-tree objects
        if self._pending is not None:
            flats, old_t = self._pending
            flats.append(flat)
            self._pending = (flats, old_t + T_new)
        elif self._trees_list:
            # per-tree objects already materialized (paged/refresh
            # paths): fold them back into the pending list so _stack()
            # never re-slices
            self._pending = ([jax.tree.map(lambda *xs: jnp.stack(xs),
                                           *self._trees_list), flat],
                             len(self._trees_list) + T_new)
            self._trees_list = []
        else:
            self._pending = ([flat], T_new)
        self.tree_group.extend(group_new)
        self._stack_cache = None

    def absorb_round_stacks(self, flat, n_rounds: int) -> None:
        """Install one lane's flattened ``(n_rounds*K*npar, ...)`` tree
        stack as this booster's newest trees — the lane-stacked
        driver's per-tenant unpack (pipeline/lanes.py): the gang
        dispatch grew every lane's trees in one launch and
        ``_unstack_lane_flats`` pre-flattened the round axis device-
        side; each tenant absorbs its own slice exactly as
        :meth:`do_boost_fused` would have (a pure host append)."""
        self._append_flat_trees(flat, n_rounds)

    # ----------------------------------------------------------- paged boost
    def do_boost_paged(self, dmat, gh, key: jax.Array,
                       mesh=None) -> jax.Array:
        """One boosting round over an external-memory matrix: histograms
        accumulate batch-by-batch (SURVEY.md §5.7); gradients, margins
        and deltas are O(N) and stay DEVICE-side (no host round trip
        per round).  With ``mesh``, each batch
        additionally shards over the 'data' axis with psum'd partials
        (distributed external memory).
        gh: (N, K, 2).  Returns the (N, K) margin delta (device)."""
        from xgboost_tpu.external import _paged_leaf_delta, grow_tree_paged
        from xgboost_tpu.models.updaters import parse_updaters, prune_tree

        if self.cfg.n_roots > 1:
            raise NotImplementedError(
                "num_roots > 1 is not supported on external-memory "
                "matrices (root_index routing is in-memory only)")
        do_prune = ("prune" in parse_updaters(self.param.updater)
                    and self.param.gamma > 0.0)
        K = max(1, self.param.num_output_group)
        npar = max(1, self.param.num_parallel_tree)
        from xgboost_tpu.obs import comm
        from xgboost_tpu.parallel import mock
        gh = jnp.asarray(gh)
        comm_nbytes = self._comm_bytes(dmat.num_col, mesh)
        deltas = jnp.zeros((dmat.num_row, K), jnp.float32)
        for k in range(K):
            for t in range(npar):
                mock.collective(nbytes=comm_nbytes)
                tkey = jax.random.fold_in(key, k * npar + t)
                _t_launch = time.perf_counter()
                tree = grow_tree_paged(tkey, dmat, gh[:, k, :],
                                       self.cut_values_dev, self.n_cuts_dev,
                                       self.cfg, mesh=mesh,
                                       split_finder=self._split_finder())
                comm.record("allreduce", count=0,
                            seconds=time.perf_counter() - _t_launch)
                if do_prune:
                    tree, _ = prune_tree(tree, self.param.gamma)
                d_k = jnp.concatenate(
                    [_paged_leaf_delta(tree, batch, self.cfg.max_depth)
                     for _, batch in dmat.device_batches()])
                deltas = deltas.at[:, k].add(d_k)
                self.trees.append(tree)
                self.tree_group.append(k)
        self._stack_cache = None
        return deltas

    # --------------------------------------------------------------- refresh
    def do_refresh(self, binned: jax.Array, gh: jax.Array,
                   row_valid: Optional[jax.Array] = None, mesh=None,
                   root: Optional[jax.Array] = None) -> None:
        """Refresh all trees' stats/leaf values on (new) data — the
        reference's ``updater=refresh`` continued-training mode
        (updater_refresh-inl.hpp:19-151)."""
        from xgboost_tpu.models.updaters import refresh_tree

        if mesh is not None:
            from xgboost_tpu.parallel.dp import refresh_tree_dp
            if root is not None:
                raise NotImplementedError(
                    "refresh with root_index under dsplit=row is not "
                    "wired; refresh single-device or drop root_index")
        for i, tree in enumerate(self.trees):
            k = self.tree_group[i]
            if mesh is not None:
                self.trees[i] = refresh_tree_dp(
                    mesh, tree, binned, gh[:, k, :], self.cfg.split,
                    self.cfg.max_depth, row_valid)
            else:
                self.trees[i] = refresh_tree(
                    tree, binned, gh[:, k, :], self.cfg.split,
                    self.cfg.max_depth, row_valid,
                    root=root, n_roots=self.cfg.n_roots)
        self._stack_cache = None

    # -------------------------------------------------------------- predict
    def _stack(self, ntree_limit: int = 0):
        """Stack trees (optionally first ntree_limit) into (T, ...) arrays.

        ``ntree_limit`` is CLAMPED to [0, num_trees] rather than
        validated: a hot-reloaded smaller model can race a stale request
        parameter (serving registry swap), and the reference likewise
        treats out-of-range limits as "all trees"."""
        T = self.num_trees if ntree_limit <= 0 else min(
            int(ntree_limit), self.num_trees)
        if self._stack_cache is not None and self._stack_cache[0] == T:
            return self._stack_cache[1], self._stack_cache[2]
        assert T > 0, "model is empty"
        if self._pending is not None and T == self.num_trees:
            # full-ensemble read with pending flat segments: concat the
            # segments directly (one op per leaf) instead of slicing T
            # per-tree pytrees and re-stacking them.  Collapse the
            # pending list so repeated appends stay O(segments-since-
            # last-read), not O(all-segments-ever).
            parts = ([jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *self._trees_list)]
                     if self._trees_list else [])
            parts.extend(self._pending[0])
            stack = parts[0] if len(parts) == 1 else jax.tree.map(
                lambda *xs: jnp.concatenate(xs), *parts)
            if not self._trees_list:
                self._pending = ([stack], T)
        else:
            stack = jax.tree.map(lambda *xs: jnp.stack(xs),
                                 *self.trees[:T])
        group = jnp.asarray(self.tree_group[:T], dtype=jnp.int32)
        self._stack_cache = (T, stack, group)
        return stack, group

    def predict_margin(self, binned: jax.Array, base: jax.Array,
                       ntree_limit: int = 0,
                       root: Optional[jax.Array] = None) -> jax.Array:
        stack, group = self._stack(ntree_limit)
        K = max(1, self.param.num_output_group)
        if self.exact_raw:
            from xgboost_tpu.models.colmaker import predict_margin_raw
            return predict_margin_raw(stack, group, binned, base,
                                      self.cfg.max_depth, K)
        return predict_margin_binned(
            stack, group, binned, base, self.cfg.max_depth, K,
            root=root, n_roots=self.cfg.n_roots,
            tree_chunk=self.pred_chunk)

    def predict_margin_fused(self, X: jax.Array, base: jax.Array,
                             ntree_limit: int = 0,
                             root: Optional[jax.Array] = None) -> jax.Array:
        """Margins straight from RAW f32 feature rows (NaN = missing):
        the fused quantize+traverse program (models/tree.py, round 7).
        Bit-identical to ``predict_margin(bin_dense_device(X, cuts), ...)``
        — the quantize sub-graph is the same function.  ``X`` must be
        width-matched to the model's cut matrix (callers NaN-pad)."""
        if self.exact_raw:
            raise NotImplementedError(
                "exact-mode models route on raw values already; the "
                "fused quantize+traverse applies to binned models only")
        stack, group = self._stack(ntree_limit)
        K = max(1, self.param.num_output_group)
        return predict_margin_fused(
            stack, group, X, self.cut_values_dev, base,
            self.cfg.max_depth, K, root=root, n_roots=self.cfg.n_roots,
            tree_chunk=self.pred_chunk)

    def predict_incremental(self, binned: jax.Array, margin: jax.Array,
                            new_trees: List[TreeArrays],
                            first_group: int = 0,
                            root: Optional[jax.Array] = None) -> jax.Array:
        """Add the contribution of freshly grown trees to a cached margin
        (fixed shapes per round -> single compilation).  An empty
        ``new_trees`` is a no-op (a stale caller can observe zero fresh
        trees when racing a model swap)."""
        if not new_trees:
            return margin
        K = max(1, self.param.num_output_group)
        npar = max(1, self.param.num_parallel_tree)
        stack = jax.tree.map(lambda *xs: jnp.stack(xs), *new_trees)
        group = jnp.asarray(
            [first_group + i // npar for i in range(len(new_trees))],
            dtype=jnp.int32)
        if self.exact_raw:
            from xgboost_tpu.models.colmaker import predict_margin_raw
            return predict_margin_raw(
                stack, group, binned, jnp.zeros((), jnp.float32),
                self.cfg.max_depth, K) + margin
        return predict_margin_binned(
            stack, group, binned, jnp.zeros((), jnp.float32),
            self.cfg.max_depth, K,
            root=root, n_roots=self.cfg.n_roots,
            tree_chunk=self.pred_chunk) + margin

    def predict_leaf(self, binned: jax.Array, ntree_limit: int = 0,
                     root: Optional[jax.Array] = None) -> jax.Array:
        stack, _ = self._stack(ntree_limit)
        if self.exact_raw:
            from xgboost_tpu.models.colmaker import traverse_raw

            def body(_, tree):
                return None, traverse_raw(tree, binned, self.cfg.max_depth)
            _, leaves = jax.lax.scan(body, None, stack)
            return leaves.T
        return predict_leaf_binned(stack, binned, self.cfg.max_depth,
                                   root=root, n_roots=self.cfg.n_roots,
                                   tree_chunk=self.pred_chunk)

    # ------------------------------------------------------------ serialize
    def get_state(self) -> dict:
        stack, group = self._stack(0)
        state = {f"tree_{f}": np.asarray(getattr(stack, f))
                 for f in TreeArrays._fields}
        state["tree_group_arr"] = np.asarray(group)
        state["cut_values"] = self.cuts.cut_values
        state["cut_n"] = self.cuts.n_cuts
        return state

    @classmethod
    def from_state(cls, param: TrainParam, state: dict) -> "GBTree":
        cuts = CutMatrix(state["cut_values"], state["cut_n"])
        gbt = cls(param, cuts)
        stack = TreeArrays(**{f: jnp.asarray(state[f"tree_{f}"])
                              for f in TreeArrays._fields})
        T = stack.feature.shape[0]
        # stay stacked: prediction/save go through the stack cache; only
        # dump/refresh/prune-style per-tree access slices lazily
        gbt._pending = ([stack], T)
        gbt.tree_group = [int(g) for g in state["tree_group_arr"]]
        gbt._stack_cache = (T, stack,
                            jnp.asarray(state["tree_group_arr"],
                                        dtype=jnp.int32))
        return gbt
