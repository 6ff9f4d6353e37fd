"""External-memory training: datasets larger than device HBM.

The reference streams 64MB CSR pages from disk with a prefetch thread
and routes all paged training through the histogram updater
(``src/io/page_dmatrix-inl.hpp``, ``learner-inl.hpp:263-267``).  The
TPU-native shape of the same idea (SURVEY.md §5.7):

  1. ingest once into raw CSR pages on disk (native page store,
     ``native/xgtpu_io.cpp``; in-RAM fallback);
  2. one streaming pass builds per-feature quantile sketches
     (merge/prune bounds identical to the in-RAM path) → cuts;
  3. one streaming pass quantizes to a binned ``(N, F)`` small-int
     **memmap** — the only O(N·F) artifact, living on disk/page cache,
     never fully resident;
  4. per tree level, batches of binned rows are staged host→device,
     positions recomputed by partial traversal, and partial histograms
     accumulated — working set is a handful of page_rows batches (one
     synchronously; up to four with the default prefetcher — see
     ``device_batches``), never the data size (the reference builds
     histograms col-batch by col-batch for the same reason,
     ``updater_histmaker-inl.hpp:296-348``).

Margins, gradients and deltas are (N,)-sized — tiny next to the paged
O(N·F) data — and stay DEVICE-resident (no host round trip per
round).  When the whole binned matrix fits
the device budget (``fits_device_budget``), the learner skips streaming
entirely and trains through the in-memory fast path; only genuinely
over-budget matrices stream batches host→device.
"""

from __future__ import annotations

import functools
import os
import tempfile
from typing import Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from xgboost_tpu.data import DMatrix, MetaInfo, load_meta_sidecars
from xgboost_tpu.models.tree import (GrowConfig, TreeArrays, _traverse_one,
                                     apply_level, bin_of_feature, empty_tree,
                                     table_lookup)
from xgboost_tpu.ops.histogram import build_level_histogram, node_stats
from xgboost_tpu.ops.split import find_best_splits
from xgboost_tpu.sketch import (QuantileSummary, empty_summary, make_summary,
                                merge_summaries, prune_summary, propose_cuts)
from xgboost_tpu.binning import CutMatrix

DEFAULT_PAGE_ROWS = 1 << 16


class ExtMemDMatrix:
    """Paged data matrix (reference DMatrixPage, magic 0xffffab02).

    Construct from a libsvm path (``ExtMemDMatrix("big.svm#cache")`` or
    ``DMatrix("ext:big.svm#cache")``) or from an iterator of
    ``(X_dense, y)`` chunks.  Raw CSR pages are spilled to
    ``<cache>.pages``; after binning, a ``<cache>.binned`` memmap holds
    the quantized matrix.

    A ``!`` path prefix (or ``half_ram=True``) selects the HalfRAM
    variant (reference ``DMatrixHalfRAM``, magic 0xffffab03, selected by
    ``!`` at ``io.cpp:70-73``): raw CSR rows stay paged on disk but the
    compact working set — here the quantized bin matrix — is held in
    host RAM instead of a memmap, trading RAM for batch-access speed.
    """

    is_external = True

    def __init__(self, data, label=None, weight=None,
                 cache: Optional[str] = None,
                 page_rows: int = DEFAULT_PAGE_ROWS, missing: float = np.nan,
                 silent: bool = True, half_ram: bool = False):
        self.info = MetaInfo()
        self.page_rows = page_rows
        self._binned_path: Optional[str] = None
        self._binned_mm: Optional[np.memmap] = None
        self._binned_cuts: Optional[CutMatrix] = None
        self._binned_dtype = np.uint8
        self.feature_names = None
        self._col_cache = None

        self.half_ram = half_ram
        if isinstance(data, str):
            if data.startswith("!"):
                self.half_ram = True
                data = data[1:]
            path, _, cachesuffix = data.partition("#")
            if cache is None:
                cache = cachesuffix or path + ".extcache"
            self.cache_prefix = cache
            self._ingest_libsvm(path, missing, silent)
            load_meta_sidecars(self, path)
        else:
            if cache is None:
                cache = os.path.join(
                    tempfile.mkdtemp(prefix="xgbtpu_ext_"), "m")
            self.cache_prefix = cache
            self._ingest_chunks(iter(data), missing)
        if label is not None:
            self.info.set_field("label", label)
        if weight is not None:
            self.info.set_field("weight", weight)

    # ------------------------------------------------------------- ingest
    def _pages_path(self) -> str:
        return self.cache_prefix + ".pages"

    def _ingest_libsvm(self, path: str, missing: float, silent: bool,
                       chunk_lines: int = 0):
        """Stream-parse text into the page store chunk by chunk.

        The reference never holds a whole text source in memory
        (``libsvm_parser.h`` ThreadedParser streams chunks); parsing
        bounded line blocks keeps host RAM at one chunk + one page, so
        external memory relieves host RAM as well as HBM."""
        from xgboost_tpu.data import iter_libsvm_chunks
        from xgboost_tpu import native
        chunk_lines = chunk_lines or self.page_rows
        # moderate files: the native multithreaded parser is an order of
        # magnitude faster and its whole-file buffering is affordable;
        # past the threshold, stream bounded python chunks instead
        fast_limit = int(os.environ.get("XGTPU_NATIVE_INGEST_LIMIT",
                                        str(1 << 29)))  # 512 MB
        if native.available() and os.path.getsize(path) <= fast_limit:
            indptr, indices, values, labels = native.parse_libsvm_native(
                path) or (None,) * 4
            if indptr is not None:
                writer = self._page_writer()
                n = len(indptr) - 1
                for start in range(0, n, self.page_rows):
                    stop = min(start + self.page_rows, n)
                    self._push_page(writer, indptr[start:stop + 1],
                                    indices, values)
                self._close_writer(writer)
                self._num_col = (int(indices.max()) + 1 if len(indices)
                                 else 0)
                self.info.set_field("label", labels)
                self._num_row = n
                return
        writer = self._page_writer()
        all_labels: List[np.ndarray] = []
        num_col = 0
        n_rows = 0
        for indptr, indices, values, labels in iter_libsvm_chunks(
                path, chunk_lines):
            self._push_page(writer, indptr, indices, values)
            all_labels.append(labels)
            if len(indices):
                num_col = max(num_col, int(indices.max()) + 1)
            n_rows += len(labels)
        self._close_writer(writer)
        self._num_col = num_col
        self.info.set_field(
            "label", np.concatenate(all_labels) if all_labels
            else np.zeros(0, np.float32))
        self._num_row = n_rows

    def _ingest_chunks(self, chunks: Iterator[Tuple[np.ndarray, np.ndarray]],
                       missing: float):
        labels: List[np.ndarray] = []
        writer = self._page_writer()
        n_rows = 0
        num_col = 0
        for X, y in chunks:
            X = np.asarray(X, np.float32)
            num_col = max(num_col, X.shape[1])
            present = ~np.isnan(X) if np.isnan(missing) else X != missing
            counts = present.sum(axis=1)
            indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
            rows, cols = np.nonzero(present)
            self._push_page(writer, indptr, cols.astype(np.int32),
                            X[rows, cols].astype(np.float32))
            labels.append(np.asarray(y, np.float32))
            n_rows += X.shape[0]
        self._close_writer(writer)
        self._num_row = n_rows
        self._num_col = num_col
        if labels:
            self.info.set_field("label", np.concatenate(labels))

    def _write_pages_from_csr(self, indptr, indices, values):
        writer = self._page_writer()
        n = len(indptr) - 1
        for start in range(0, n, self.page_rows):
            stop = min(start + self.page_rows, n)
            self._push_page(writer, indptr[start:stop + 1],
                            indices, values)
        self._close_writer(writer)

    # page-store backends: native lib, or an in-RAM list fallback
    def _page_writer(self):
        from xgboost_tpu import native
        if native.available():
            return native.PageWriter(self._pages_path())
        self._ram_pages: List[tuple] = []
        return None

    def _push_page(self, writer, indptr, indices, values):
        if writer is not None:
            writer.push(indptr, indices, values)
        else:
            base = indptr[0]
            self._ram_pages.append(
                (np.asarray(indptr) - base,
                 np.asarray(indices[base:indptr[-1]], np.int32),
                 np.asarray(values[base:indptr[-1]], np.float32)))

    def _close_writer(self, writer):
        if writer is not None:
            writer.close()

    def iter_raw_pages(self):
        """Yield (indptr, indices, values) CSR pages."""
        from xgboost_tpu import native
        if native.available() and os.path.exists(self._pages_path()):
            with native.PageReader(self._pages_path()) as r:
                for page in r:
                    yield page
        else:
            yield from self._ram_pages

    # ---------------------------------------------------- DMatrix protocol
    @property
    def num_row(self) -> int:
        return self._num_row

    @property
    def num_col(self) -> int:
        return self._num_col

    def get_label(self):
        return self.info.label

    def get_weight(self):
        return self.info.get_weight(self.num_row)

    def get_base_margin(self):
        return self.info.base_margin

    def set_label(self, label):
        self.info.set_field("label", label)

    def set_weight(self, weight):
        self.info.set_field("weight", weight)

    def set_group(self, group):
        self.info.set_field("group", group)

    def set_base_margin(self, margin):
        self.info.set_field("base_margin", margin)

    def slice(self, rindex):
        raise NotImplementedError(
            "slice() is not supported on external-memory matrices")

    # ------------------------------------------------------------- sketch
    def sketch_cuts(self, max_bin: int = 256, sketch_eps: float = 0.03,
                    sketch_ratio: float = 2.0) -> CutMatrix:
        """Streaming per-feature quantile sketch over raw pages (the
        reference's per-batch sketch push, basemaker-inl.hpp:307-385)."""
        F = self.num_col
        maxsize = max(2, int(sketch_ratio / max(sketch_eps, 1.0 / max_bin)))
        summaries: List[QuantileSummary] = [empty_summary() for _ in range(F)]
        for indptr, indices, values in self.iter_raw_pages():
            order = np.argsort(indices, kind="stable")
            sorted_cols = indices[order]
            starts = np.searchsorted(sorted_cols, np.arange(F + 1))
            for f in range(F):
                sel = order[starts[f]:starts[f + 1]]
                if len(sel) == 0:
                    continue
                s = prune_summary(make_summary(values[sel]), maxsize)
                summaries[f] = prune_summary(
                    merge_summaries(summaries[f], s), maxsize)
        from xgboost_tpu.binning import pack_cuts
        return pack_cuts([propose_cuts(s, max_bin - 1) for s in summaries])

    # ------------------------------------------------------------ binning
    def build_binned(self, cuts: CutMatrix) -> None:
        """Quantize raw pages into the on-disk binned memmap.

        Width is the MODEL's feature count (like the in-RAM bin_matrix):
        a matrix whose max observed feature index is below the model's
        num_feature still gets columns for every model feature, so tree
        traversal never gathers out of bounds."""
        width = max(self.num_col, cuts.num_feature)
        self._binned_dtype = np.uint8 if cuts.max_bin <= 256 else np.uint16
        if self.half_ram:
            mm = np.zeros((self.num_row, width), dtype=self._binned_dtype)
        else:
            self._binned_path = self.cache_prefix + ".binned"
            mm = np.memmap(self._binned_path, dtype=self._binned_dtype,
                           mode="w+", shape=(self.num_row, width))
        f_lim = min(self.num_col, cuts.num_feature)
        row0 = 0
        for indptr, indices, values in self.iter_raw_pages():
            n = len(indptr) - 1
            page = np.zeros((n, width), dtype=self._binned_dtype)
            rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
            # one argsort groups entries by feature; each feature then
            # costs O(nnz_f log C) — NOT the O(F x nnz) of scanning a
            # boolean `indices == f` mask per feature (VERDICT r2 item 8:
            # wide datasets crawled through ingest)
            order = np.argsort(indices, kind="stable")
            starts = np.searchsorted(indices[order], np.arange(f_lim + 1))
            bins = np.zeros(len(indices), dtype=np.int64)
            for f in range(f_lim):
                sel = order[starts[f]:starts[f + 1]]
                if len(sel) == 0:
                    continue
                bins[sel] = 1 + np.searchsorted(
                    cuts.cut_values[f, :cuts.n_cuts[f]], values[sel],
                    side="right")
            in_lim = indices < f_lim
            page[rows[in_lim], indices[in_lim]] = \
                bins[in_lim].astype(self._binned_dtype)
            mm[row0:row0 + n] = page
            row0 += n
        if self.half_ram:
            self._binned_mm = mm
        else:
            mm.flush()
            self._binned_mm = np.memmap(self._binned_path,
                                        dtype=self._binned_dtype, mode="r",
                                        shape=(self.num_row, width))
        self._binned_cuts = cuts  # identity-tracked: see Booster._entry

    def binned_batches(self, batch_rows: Optional[int] = None):
        """Yield (row_start, binned_np) batches of the quantized matrix."""
        assert self._binned_mm is not None, "call build_binned first"
        step = batch_rows or self.page_rows
        for start in range(0, self.num_row, step):
            yield start, np.asarray(self._binned_mm[start:start + step])

    def fits_device_budget(self) -> bool:
        """True when the whole binned matrix fits the device budget.
        The learner then trains through the in-memory fast path —
        external memory has done its job bounding INGEST/sketch/quantize
        memory — and only genuinely over-budget matrices stream batches
        (the out-of-HBM guarantee: working set is a few page_rows
        batches — up to four with the default prefetcher, one with
        ``XGBTPU_EXT_PREFETCH=0``).

        Budget: ``XGBTPU_EXT_DEVICE_CACHE_MB`` when set; otherwise HALF
        of the device's currently-free memory (ADVICE r2: a fixed
        default can overcommit small-HBM devices — the other half covers
        the working set: histograms, margins, int32 upcasts of bin ids),
        falling back to 2048MB when the backend reports no stats (CPU)."""
        assert self._binned_mm is not None, "call build_binned first"
        # canonical XGBTPU_ prefix; the pre-round-8 XGTPU_ spelling is
        # still honored (older A/B scripts set it)
        env = os.environ.get("XGBTPU_EXT_DEVICE_CACHE_MB",
                             os.environ.get("XGTPU_EXT_DEVICE_CACHE_MB"))
        if env is not None:
            budget = int(env) << 20
        else:
            budget = _default_device_budget()
        total = (self.num_row * self._binned_mm.shape[1]
                 * self._binned_mm.dtype.itemsize)
        return total <= budget

    def device_batches(self):
        """Yield (row_start, binned_device) batches (streaming; the
        in-budget case never reaches here — see fits_device_budget).

        Batches are staged by a background prefetch thread (depth-2
        queue): the memmap read + host→device upload of batch i+1
        overlaps the device compute on batch i — the reference's
        ThreadBuffer idea (``utils/thread_buffer.h``) at the device
        boundary.  The streamed working set is then up to FOUR batches
        device-resident (yielded + 2 queued + 1 in-flight put) instead
        of one — still bounded by page_rows, never by data size; the
        default budget's free-HBM halving covers it
        (:func:`_default_device_budget`).  ``XGBTPU_EXT_PREFETCH=0``
        restores synchronous single-batch staging (the A/B seam and
        the fallback for batches sized near free HBM; the prefetch
        depth is not measured on this machine; the legacy XGTPU_
        spelling still works)."""
        if os.environ.get("XGBTPU_EXT_PREFETCH",
                          os.environ.get("XGTPU_EXT_PREFETCH", "1")) == "0":
            for start, b in self.binned_batches():
                yield start, jnp.asarray(b)
            return
        yield from _prefetch_to_device(self.binned_batches())


def _prefetch_to_device(batches, depth: int = 2, observe=None):
    """Stage (start, np_batch) pairs to the device from a worker thread,
    ``depth`` batches ahead (``depth=0`` degrades to synchronous inline
    staging — the A/B baseline).  jax.device_put is thread-safe; the
    consumer's compute dispatches interleave with the worker's uploads
    on the host side, and the device runtime orders them on its stream.
    Exceptions propagate to the consumer.

    Shared upload/compute-overlap seam: paged training and prediction
    consume it through :meth:`ExtMemDMatrix.device_batches`, and the
    learner's blocked one-off prediction (``Learner._predict_fused_
    blocked`` / ``_bin_dense_blocked``) reuses it so row-block f32
    uploads overlap the device quantize+traverse of the previous block
    instead of serializing behind it
    (``XGBTPU_PREDICT_UPLOAD_DEPTH`` picks the prediction-path depth).

    ``observe``, when given, is called with ``(nbytes, seconds)`` per
    upload (the prediction transfer counters); timing then blocks the
    WORKER on upload completion — the consumer still overlaps, and the
    number measures transfer, not dispatch."""
    import queue
    import threading

    def _put(b):
        if observe is None:
            return jax.device_put(b)
        from xgboost_tpu.obs.metrics import timed_device_put
        return timed_device_put(b, observe)

    if depth <= 0:
        def _sync():
            for start, b in batches:
                yield start, _put(b)
        return _sync()

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    _END = object()
    stop = threading.Event()

    def worker():
        try:
            for start, b in batches:
                if stop.is_set():
                    return
                q.put((start, _put(b)))
            q.put(_END)
        except BaseException as e:  # noqa: BLE001 - relayed to consumer
            q.put(e)

    def _piped():
        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is _END:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            # early-closed generator: unblock + retire the worker so its
            # memmap reads don't outlive the matrix
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=5.0)

    return _piped()


_budget_cache: Optional[int] = None


def _default_device_budget() -> int:
    """Deterministic per-process device budget: half the free device
    memory sampled ONCE (repeated queries would let allocation state
    flip identical matrices between streamed and in-memory paths), and
    the fixed 2048MB default in multi-process jobs — ranks computing
    different budgets would pick different collective sequences."""
    global _budget_cache
    if _budget_cache is None:
        budget = 2048 << 20
        if jax.process_count() == 1:
            try:
                stats = jax.devices()[0].memory_stats() or {}
                limit = stats.get("bytes_limit")
                if limit:
                    free = limit - stats.get("bytes_in_use", 0)
                    budget = max(free // 2, 0)
            except Exception as e:
                # backends without memory_stats keep the default
                from xgboost_tpu.obs.metrics import swallowed_error
                swallowed_error("external.memory_budget", e,
                                emit_event=False)
        _budget_cache = budget
    return _budget_cache


# ------------------------------------------------------------- paged grow
@functools.partial(jax.jit, static_argnames=("depth", "n_bin",
                                              "precision"))
def _paged_level_hist(tree: TreeArrays, binned: jax.Array, gh: jax.Array,
                      depth: int, n_bin: int, precision: str = "auto"):
    """Partial histogram + node stats for one batch at one level: row
    positions are recomputed by traversing the partial tree."""
    node = jnp.zeros_like(binned[:, 0], dtype=jnp.int32)
    alive = jnp.ones(binned.shape[0], jnp.bool_)
    for _ in range(depth):
        f = table_lookup(tree.feature, node)
        at_leaf = table_lookup(tree.is_leaf, node) | (f < 0)
        b = bin_of_feature(binned, jnp.maximum(f, 0))
        go_left = jnp.where(b == 0, table_lookup(tree.default_left, node),
                            b <= table_lookup(tree.cut_index, node) + 1)
        nxt = jnp.where(go_left, 2 * node + 1, 2 * node + 2)
        alive = alive & ~at_leaf
        node = jnp.where(at_leaf, node, nxt)
    n_node = 1 << depth
    pos = jnp.where(alive, node - (n_node - 1), -1)
    hist = build_level_histogram(binned, gh, pos, n_node, n_bin, precision)
    return hist, node_stats(gh, pos, n_node, precision)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def _paged_leaf_delta(tree: TreeArrays, binned: jax.Array, max_depth: int):
    return table_lookup(tree.leaf_value,
                        _traverse_one(tree, binned, max_depth))


@functools.partial(jax.jit, static_argnames=("depth", "n_bin", "mesh",
                                              "precision"))
def _paged_level_hist_dp(mesh, tree: TreeArrays, binned: jax.Array,
                         gh: jax.Array, depth: int, n_bin: int,
                         precision: str = "auto"):
    """Distributed batch histogram: rows of one streamed batch shard over
    the mesh 'data' axis, partial histograms psum across shards (the
    reference's paged matrices participating in dsplit=row training,
    learner-inl.hpp:263-267 + histmaker's histred.Allreduce).

    Padding rows carry gh == 0, so they contribute nothing to any cell.
    """
    from jax.sharding import PartitionSpec as P

    def shard_fn(tree, binned, gh):
        hist, nst = _paged_level_hist.__wrapped__(tree, binned, gh,
                                                  depth, n_bin, precision)
        return (jax.lax.psum(hist, "data"), jax.lax.psum(nst, "data"))

    from jax import shard_map
    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(), P("data"), P("data")),
                   out_specs=(P(), P()), check_vma=False)
    return fn(tree, binned, gh)


def grow_tree_paged(key, dmat: ExtMemDMatrix, gh: np.ndarray,
                    cut_values: jax.Array, n_cuts: jax.Array,
                    cfg: GrowConfig, mesh=None,
                    split_finder=None) -> TreeArrays:
    """Level-by-level growth streaming binned batches host→device.

    With ``mesh``, each batch's rows shard over the 'data' axis and
    partial histograms psum across shards before accumulating across
    batches (distributed external memory: SURVEY.md §5.7 item 2 composed
    with §2.4.2).

    gh: (N, 2) gradients (device or host).  Row subsampling uses a
    deterministic device-side draw.  Returns the grown tree (delta is
    computed by the caller via :func:`_paged_leaf_delta` batch by batch).
    """
    from xgboost_tpu.models.tree import (_default_split_finder,
                                         _sample_features)

    if split_finder is None:
        split_finder = _default_split_finder

    key_rows, key_ftree, key_flevel = jax.random.split(key, 3)
    # gradients are O(N) (not O(N*F)) and stay device-resident
    # instead of re-uploading per batch
    gh_dev = jnp.asarray(gh, jnp.float32)
    if cfg.subsample < 1.0:
        keep = jax.random.uniform(key_rows, (dmat.num_row,)) < cfg.subsample
        gh_dev = gh_dev * keep[:, None].astype(jnp.float32)

    F = int(n_cuts.shape[0])
    fmask_tree = _sample_features(key_ftree, F, cfg.colsample_bytree)

    tree = empty_tree(cfg.max_depth)
    for depth in range(cfg.max_depth + 1):
        n_node = 1 << depth
        hist = None
        nst = None
        for start, batch in dmat.device_batches():
            bgh = gh_dev[start:start + batch.shape[0]]
            if mesh is not None:
                pad = (-batch.shape[0]) % mesh.devices.size
                if pad:
                    batch = jnp.pad(batch, ((0, pad), (0, 0)))
                    bgh = jnp.pad(bgh, ((0, pad), (0, 0)))
                h, s = _paged_level_hist_dp(
                    mesh, tree, batch, bgh, depth, cfg.n_bin,
                    cfg.hist_precision)
            else:
                h, s = _paged_level_hist(tree, batch, bgh, depth,
                                         cfg.n_bin, cfg.hist_precision)
            hist = h if hist is None else hist + h
            nst = s if nst is None else nst + s
        # "fixed" mode batches accumulate exact int32; decode once per
        # level after the cross-batch/cross-shard sums
        from xgboost_tpu.ops.histogram import dequantize_hist
        hist = dequantize_hist(hist)
        nst = dequantize_hist(nst)
        if depth == cfg.max_depth:
            make_leaf = jnp.ones(n_node, jnp.bool_)
            best = None
        else:
            fmask = fmask_tree
            if cfg.colsample_bylevel < 1.0:
                fmask = fmask & _sample_features(
                    jax.random.fold_in(key_flevel, depth), F,
                    cfg.colsample_bylevel)
            best = split_finder(hist, nst, n_cuts, cut_values,
                                fmask, cfg.split)
            can_try = nst[:, 1] >= 2.0 * cfg.split.min_child_weight
            make_leaf = ~(best.valid & can_try)
        tree = apply_level(tree, depth, nst, best, make_leaf, cfg.split)
    return tree
