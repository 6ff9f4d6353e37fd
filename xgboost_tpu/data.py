"""DMatrix: data container for xgboost_tpu.

Covers the reference's data layer (SURVEY.md §2.1 L2):
  - ``MetaInfo`` — labels/weights/groups/base_margin/root_index/fold_index
    (reference ``src/learner/dmatrix.h:18-145``), including sidecar file
    loading (``train.txt.group`` etc., ``dmatrix.h:108-137``).
  - CSR storage + libsvm text parsing with optional rank/npart split
    loading for distributed training (reference
    ``src/io/simple_dmatrix-inl.hpp:69-117``).
  - binary save/load cache (reference magic 0xffffab01,
    ``simple_dmatrix-inl.hpp:154-251``) — here an ``.npz`` container, with
    the same ``path#cachefile`` / auto ``.buffer`` conventions handled in
    :mod:`xgboost_tpu.io.dispatch`.
  - ``slice``/``mknfold`` support (reference ``wrapper/xgboost_wrapper.cpp:200-245``).

TPU-native difference: downstream training never iterates CSR — the
matrix is quantized once into a dense (n_rows, n_features) bin-id array
(:mod:`xgboost_tpu.binning`), the analog of the reference's decision to
route all distributed/external training through histogram updaters
(``learner-inl.hpp:91-97,263-267``).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Optional, Sequence

import numpy as np


def upload(host, put=None):
    """Hand one host array to the device under an ``ingest.upload`` span
    [bytes].  The span covers the host side of the put only: no barrier
    is added, the copy may still be in flight when it returns.  ``put``
    places the array where ``jnp.asarray`` would not (a mesh sharding)."""
    from xgboost_tpu.obs import span
    with span("ingest.upload", bytes=int(getattr(host, "nbytes", 0))):
        if put is None:
            import jax.numpy as jnp
            put = jnp.asarray
        return put(host)


class MetaInfo:
    """Per-row (and per-group) metadata (reference src/learner/dmatrix.h:18-145)."""

    __slots__ = ("label", "weight", "group_ptr", "base_margin",
                 "root_index", "fold_index", "_dev_cache", "version")

    def __init__(self):
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.group_ptr: Optional[np.ndarray] = None  # (n_groups+1,) int
        self.base_margin: Optional[np.ndarray] = None
        self.root_index: Optional[np.ndarray] = None
        self.fold_index: Optional[np.ndarray] = None
        # device copies + validation marks, reused across boosting rounds
        # (re-uploading label/weight every round costs more host<->device
        # time than the gradient computation itself)
        self._dev_cache: dict = {}
        self.version = 0  # bumped on set_field: snapshot invalidation

    def get_weight(self, n_rows: int) -> np.ndarray:
        if self.weight is None:
            return np.ones(n_rows, dtype=np.float32)
        return self.weight

    def label_dev(self):
        """Device-resident label, cached until the field changes."""
        if "label" not in self._dev_cache:
            self._dev_cache["label"] = upload(self.label)
        return self._dev_cache["label"]

    def weight_dev(self, n_rows: int):
        """Device-resident per-row weight (ones when unset), cached."""
        key = ("weight", n_rows)
        if key not in self._dev_cache:
            self._dev_cache[key] = upload(self.get_weight(n_rows))
        return self._dev_cache[key]

    def check_once(self, mark: str, fn) -> None:
        """Run a host-side validation once per (info, mark); cleared when
        any field is re-set."""
        if mark not in self._dev_cache:
            fn()
            self._dev_cache[mark] = True

    def set_field(self, name: str, value) -> None:
        self._dev_cache.clear()
        self.version += 1
        if value is None:
            setattr(self, name if name != "group" else "group_ptr", None)
            return
        arr = np.asarray(value)
        if name == "group":
            # group sizes -> cumulative pointer (reference MetaInfo::SetInfo)
            self.group_ptr = np.concatenate(
                [[0], np.cumsum(arr.astype(np.int64))])
        elif name in ("label", "weight", "base_margin"):
            setattr(self, name, arr.astype(np.float32).ravel())
        elif name in ("root_index", "fold_index"):
            # uint32: full reference XGDMatrixSetUIntInfo range
            setattr(self, name, arr.astype(np.uint32).ravel())
        else:
            raise ValueError(f"unknown meta field {name!r}")

    def get_field(self, name: str):
        if name == "group":
            return self.group_ptr
        return getattr(self, name)

    def slice(self, rindex: np.ndarray) -> "MetaInfo":
        out = MetaInfo()
        for f in ("label", "weight", "base_margin", "root_index", "fold_index"):
            v = getattr(self, f)
            if v is not None:
                setattr(out, f, v[rindex])
        # group structure does not survive arbitrary row slicing (same as
        # reference XGDMatrixSliceDMatrix, which drops group_ptr)
        return out


class DMatrix:
    """Sparse (CSR) data matrix with metadata.

    Accepts: libsvm text path, dense numpy array (with ``missing`` marker),
    scipy CSR/CSC, or a (indptr, indices, values, num_col) CSR tuple.

    Dense ndarray input is held by REFERENCE and CSR is built lazily on
    first ``values``/``indices``/``indptr`` access (histogram training
    and a one-off predict never build it: cuts and bin ids are read
    column by column from the array, ``dense_source``, and the fused
    predict path uploads views of the caller's buffer).  Consequence:
    mutating the source array between construction and first use
    changes what this matrix sees — and only
    for float32 input (``np.asarray`` copies while converting any other
    dtype); snapshot with ``DMatrix(arr.copy())`` when the buffer will
    be reused.
    """

    def __new__(cls, data: Any = None, *args, **kwargs):
        # "ext:path" / "!path#cache" URIs construct the paged matrix
        # (reference io.cpp routes paged magics and the '!' HalfRAM
        # prefix the same way, io.cpp:36-81); ExtMemDMatrix is not a
        # subclass, so __init__ below is skipped for it.  The '!' prefix
        # is only honored TOGETHER with a '#cache' suffix, matching the
        # reference's routing (io.cpp:70-73 checks '!' inside the
        # cache-file branch only; a bare '!file' is a plain file load).
        if cls is DMatrix and isinstance(data, str) and (
                data.startswith("ext:")
                or (data.startswith("!") and "#" in data)):
            from xgboost_tpu.external import ExtMemDMatrix
            path = data[4:] if data.startswith("ext:") else data
            names = ("label", "weight", "missing", "base_margin", "group",
                     "num_col", "silent", "feature_names")
            for name, val in zip(names, args):
                kwargs.setdefault(name, val)
            unsupported = [k for k in ("base_margin", "group", "num_col",
                                       "feature_names")
                           if kwargs.get(k) is not None]
            if unsupported:
                raise ValueError(
                    f"DMatrix({data!r}): {unsupported} not supported on "
                    "external-memory matrices; construct ExtMemDMatrix and "
                    "use set_base_margin/set_group instead")
            return ExtMemDMatrix(
                path, label=kwargs.get("label"),
                weight=kwargs.get("weight"),
                missing=kwargs.get("missing", np.nan),
                silent=kwargs.get("silent", True))
        return super().__new__(cls)

    def __init__(self, data: Any, label=None, weight=None, missing: float = np.nan,
                 base_margin=None, group=None, num_col: Optional[int] = None,
                 silent: bool = True, feature_names: Optional[Sequence[str]] = None):
        self.info = MetaInfo()
        self.feature_names = list(feature_names) if feature_names else None
        self._col_cache = None
        # CSR storage is LAZY for dense ndarray input: a one-off
        # ``DMatrix(arr)`` predict never touches values/indices/indptr
        # (the fused path uploads views of ``arr`` itself and the
        # density gate reads num_nonmissing()), and cut proposal and
        # binning read the array's columns (dense_source()), so the ~2x
        # host copy is only built when something actually iterates CSR
        # (slicing, save_binary, gblinear, exact mode...).  The
        # properties below materialize on first access — transparent
        # to every consumer.
        self._indptr = self._indices = self._values = None
        self._lazy_dense: Optional[tuple] = None  # (arr, missing)
        self._lazy_lock = threading.Lock()
        self._nnz: Optional[int] = None

        from xgboost_tpu.obs import span
        with span("ingest.dmatrix") as sp:
            if isinstance(data, str):
                from xgboost_tpu.io.dispatch import load_dmatrix_into
                load_dmatrix_into(self, data, silent=silent)
            elif isinstance(data, tuple) and len(data) == 4:
                self.indptr, self.indices, self.values, self._num_col = data
                self.indptr = np.asarray(self.indptr, dtype=np.int64)
                self.indices = np.asarray(self.indices, dtype=np.int32)
                self.values = np.asarray(self.values, dtype=np.float32)
            elif _is_scipy_sparse(data):
                csr = data.tocsr()
                self.indptr = csr.indptr.astype(np.int64)
                self.indices = csr.indices.astype(np.int32)
                self.values = csr.data.astype(np.float32)
                self._num_col = csr.shape[1]
            else:
                arr = np.asarray(data, dtype=np.float32)
                if arr.ndim != 2:
                    raise ValueError("expected 2D array")
                self._lazy_dense = (arr, missing)
                self._num_col = arr.shape[1]

            if num_col is not None:
                self._num_col = max(num_col, getattr(self, "_num_col", 0))
            elif not hasattr(self, "_num_col") or self._num_col is None:
                self._num_col = int(self.indices.max()) + 1 if len(self.indices) else 0

            if label is not None:
                self.info.set_field("label", label)
            if weight is not None:
                self.info.set_field("weight", weight)
            if base_margin is not None:
                self.info.set_field("base_margin", base_margin)
            if group is not None:
                self.info.set_field("group", group)
            sp.set("rows", self.num_row)
            sp.set("cols", self._num_col)

    # ------------------------------------------------------------------
    def _from_dense_locked(self, arr: np.ndarray, missing: float) -> None:
        # called with _lazy_lock held (lazy materialization) — the one
        # CSR-building path since dense __init__ went lazy
        if np.isnan(missing):
            present = ~np.isnan(arr)
        else:
            present = arr != missing
        counts = present.sum(axis=1)
        self.indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        rows, cols = np.nonzero(present)
        self.indices = cols.astype(np.int32)
        self.values = arr[rows, cols].astype(np.float32)
        self._num_col = arr.shape[1]

    # ------------------------------------------------------- lazy CSR
    def _materialize(self) -> None:
        """Build CSR from the pending dense source, once, thread-safely
        (an eagerly-built DMatrix was always shareable across predict
        threads; lazy construction must not regress that).  Writes land
        in order — arrays first, the ``_lazy_dense = None`` "done" mark
        last — so a lock-free property read that sees the mark cleared
        also sees complete arrays (GIL ordering)."""
        if self._lazy_dense is None:
            return
        from xgboost_tpu.obs import span
        with self._lazy_lock:
            if self._lazy_dense is None:
                return  # another thread won the race
            arr, missing = self._lazy_dense
            # the dense -> CSR half of ingest.dmatrix, deferred to here
            with span("ingest.dmatrix", rows=int(arr.shape[0]),
                      cols=int(arr.shape[1])) as sp:
                nc = self._num_col  # num_col= widening must survive
                self._from_dense_locked(arr, missing)
                self._num_col = max(nc, self._num_col)
                sp.set("nnz", len(self._values))
            self._lazy_dense = None

    @property
    def indptr(self) -> np.ndarray:
        if self._indptr is None:
            self._materialize()
        return self._indptr

    @indptr.setter
    def indptr(self, v) -> None:
        self._indptr = v

    @property
    def indices(self) -> np.ndarray:
        if self._indices is None:
            self._materialize()
        return self._indices

    @indices.setter
    def indices(self, v) -> None:
        self._indices = v

    @property
    def values(self) -> np.ndarray:
        if self._values is None:
            self._materialize()
        return self._values

    @values.setter
    def values(self, v) -> None:
        self._values = v

    def num_nonmissing(self) -> int:
        """Count of stored (non-missing) entries — ``len(values)``
        without forcing a lazy dense matrix to materialize CSR: the
        predict-path density gate (learner.py) reads ONLY this, so a
        dense one-off ``DMatrix(arr)`` routes straight to the fused
        upload of ``arr`` itself.  Counted in bounded row blocks (the
        boolean temp stays ~16 MB however large the matrix is);
        bit-identical to ``len(self.values)`` by construction."""
        src = self._lazy_dense  # one read: may be cleared concurrently
        if self._values is not None or src is None:
            return len(self.values)
        if self._nnz is None:
            arr, missing = src
            block = max(1, (1 << 24) // max(arr.shape[1], 1))
            total = 0
            for s in range(0, arr.shape[0], block):
                chunk = arr[s:s + block]
                if np.isnan(missing):
                    total += int(np.count_nonzero(~np.isnan(chunk)))
                else:
                    total += int(np.count_nonzero(chunk != missing))
            self._nnz = total
        return self._nnz

    def dense_source(self) -> Optional[tuple]:
        """``(arr, missing)`` while this matrix still holds the 2-D
        float32 array it was built from and has built no CSR from it,
        else None.  Cut proposal and binning read their columns from
        it (binning.py), so training from an ndarray never builds CSR
        or the column cache; sparse, file and tuple input have none."""
        return self._lazy_dense  # one read: may be cleared concurrently

    def predict_dense_src(self) -> Optional[np.ndarray]:
        """The dense f32 NaN-missing buffer this matrix wraps, when CSR
        is still pending — the zero-copy upload source for the fused
        predict path (learner._dense_block_fn).  None once CSR exists
        or when the missing marker / dtype / layout would change the
        uploaded values."""
        src = self._lazy_dense  # one read: may be cleared concurrently
        if src is None:
            return None
        arr, missing = src
        if (np.isnan(missing) and arr.dtype == np.float32
                and arr.flags.c_contiguous):
            return arr
        return None

    # ------------------------------------------------------------------
    @property
    def num_row(self) -> int:
        src = self._lazy_dense  # one read: may be cleared concurrently
        if self._indptr is None and src is not None:
            return int(src[0].shape[0])
        return len(self.indptr) - 1

    @property
    def num_col(self) -> int:
        return self._num_col

    def set_label(self, label):
        self.info.set_field("label", label)

    def set_weight(self, weight):
        self.info.set_field("weight", weight)

    def set_group(self, group):
        self.info.set_field("group", group)

    def set_base_margin(self, margin):
        self.info.set_field("base_margin", margin)

    # generic typed field accessors (reference wrapper/xgboost.py:166-183:
    # get/set_float_info for label/weight/base_margin; get/set_uint_info
    # for root_index/fold_index, plus read-only group_ptr)
    _FLOAT_FIELDS = ("label", "weight", "base_margin")
    _UINT_FIELDS = ("root_index", "fold_index")

    def set_float_info(self, field: str, data) -> None:
        if field not in self._FLOAT_FIELDS:
            raise ValueError(f"unknown float field {field!r}")
        self.info.set_field(field, np.asarray(data, dtype=np.float32))

    def get_float_info(self, field: str) -> np.ndarray:
        """Unset fields return an EMPTY array (reference parity: callers
        detect unset weights via size == 0 — unlike get_weight(), which
        materializes the implicit all-ones weights)."""
        if field not in self._FLOAT_FIELDS:
            raise ValueError(f"unknown float field {field!r}")
        v = self.info.get_field(field)
        return (np.zeros(0, np.float32) if v is None
                else np.asarray(v, np.float32).copy())

    def set_uint_info(self, field: str, data) -> None:
        if field not in self._UINT_FIELDS:
            raise ValueError(f"unknown uint field {field!r}")
        arr = np.asarray(data)
        if arr.size and (not np.issubdtype(arr.dtype, np.integer)
                         or int(arr.min()) < 0
                         or int(arr.max()) > np.iinfo(np.uint32).max):
            raise ValueError(
                f"set_uint_info({field!r}): values must fit uint32 "
                "(reference XGDMatrixSetUIntInfo range)")
        self.info.set_field(field, arr)

    def get_uint_info(self, field: str) -> np.ndarray:
        if field == "group_ptr":  # read-only: set via set_group (sizes)
            v = self.info.group_ptr
        elif field in self._UINT_FIELDS:
            v = self.info.get_field(field)
        else:
            raise ValueError(f"unknown uint field {field!r}")
        return (np.zeros(0, np.uint32) if v is None
                else np.asarray(v, np.uint32).copy())

    def get_label(self):
        # a copy: in-place mutation of the returned array would bypass
        # MetaInfo's device-cache invalidation (set via set_field only)
        return None if self.info.label is None else self.info.label.copy()

    def get_weight(self):
        w = self.info.get_weight(self.num_row)
        # copy only stored arrays: the unset case is already a fresh ones()
        return w.copy() if self.info.weight is not None else w

    def get_base_margin(self):
        return (None if self.info.base_margin is None
                else self.info.base_margin.copy())

    # ------------------------------------------------------------------
    def column_values(self, col: int):
        """(row_ids, values) of one column — used by sketch/binning and
        gblinear (the reference's ColBatch access, src/data.h:92-118)."""
        if self._col_cache is None:
            order = np.argsort(self.indices, kind="stable")
            sorted_cols = self.indices[order]
            starts = np.searchsorted(sorted_cols, np.arange(self._num_col + 1))
            row_of_entry = np.repeat(np.arange(self.num_row, dtype=np.int64),
                                     np.diff(self.indptr))
            self._col_cache = (order, starts, row_of_entry)
        order, starts, row_of_entry = self._col_cache
        sel = order[starts[col]:starts[col + 1]]
        return row_of_entry[sel], self.values[sel]

    def to_dense(self, missing: float = np.nan) -> np.ndarray:
        out = np.full((self.num_row, self._num_col), missing, dtype=np.float32)
        rows = np.repeat(np.arange(self.num_row), np.diff(self.indptr))
        out[rows, self.indices] = self.values
        return out

    def slice(self, rindex) -> "DMatrix":
        """Row-slice (reference XGDMatrixSliceDMatrix, xgboost_wrapper.cpp:200-245)."""
        rindex = np.asarray(rindex, dtype=np.int64)
        counts = np.diff(self.indptr)[rindex]
        new_indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        sel = np.concatenate(
            [np.arange(self.indptr[r], self.indptr[r + 1]) for r in rindex]
        ) if len(rindex) else np.zeros(0, dtype=np.int64)
        out = DMatrix((new_indptr, self.indices[sel], self.values[sel],
                       self._num_col))
        out.info = self.info.slice(rindex)
        out.feature_names = self.feature_names
        return out

    # ------------------------------------------------------------------
    def save_binary(self, path: str, silent: bool = True) -> None:
        """Binary cache (the reference's 0xffffab01 .buffer format,
        simple_dmatrix-inl.hpp:154-251 — here an npz container)."""
        fields = {"indptr": self.indptr, "indices": self.indices,
                  "values": self.values,
                  "num_col": np.int64(self._num_col)}
        for f in ("label", "weight", "base_margin", "root_index", "fold_index"):
            v = getattr(self.info, f)
            if v is not None:
                fields["meta_" + f] = v
        if self.info.group_ptr is not None:
            fields["meta_group_ptr"] = self.info.group_ptr
        # write through a file object: np.savez(str) appends ".npz",
        # which would break the reference's name.buffer convention.
        # Streamed into the tmp+rename staging file (XGT003): a crash
        # mid-save must not leave a torn cache that every later run
        # trusts blindly — and the cache can be the biggest file this
        # process writes, so no in-memory copy of the archive either
        from xgboost_tpu.reliability.integrity import atomic_writer
        with atomic_writer(path) as f:
            np.savez(f, **fields)

    @classmethod
    def load_binary(cls, path: str) -> "DMatrix":
        with np.load(path) as z:
            dm = cls((z["indptr"], z["indices"], z["values"],
                      int(z["num_col"])))
            for f in ("label", "weight", "base_margin", "root_index",
                      "fold_index"):
                if "meta_" + f in z:
                    setattr(dm.info, f, z["meta_" + f])
            if "meta_group_ptr" in z:
                dm.info.group_ptr = z["meta_group_ptr"]
        return dm


def _is_scipy_sparse(data) -> bool:
    try:
        import scipy.sparse as sp  # noqa: deferred optional dependency
        return sp.issparse(data)
    except ImportError:
        return False


# ----------------------------------------------------------------------
def parse_libsvm(path: str, rank: int = 0, nparts: int = 1):
    """Parse libsvm text into CSR; optional round-robin row sharding.

    The reference splits a text source across workers at load time
    (``simple_dmatrix-inl.hpp:89-96``); here ``rank``/``nparts`` select a
    row shard (row i kept iff i % nparts == rank).
    Returns (indptr, indices, values, labels).

    Uses the native multithreaded parser (native/xgtpu_io.cpp — the
    reference's OMP chunk parser, ``src/io/libsvm_parser.h``) when
    available; the pure-Python path below is the fallback.
    """
    from xgboost_tpu.native import parse_libsvm_native
    out = parse_libsvm_native(path, rank, nparts)
    if out is not None:
        return out
    return parse_libsvm_python(path, rank, nparts)


def iter_libsvm_chunks(path: str, chunk_rows: int, rank: int = 0,
                       nparts: int = 1):
    """Stream a libsvm text file as bounded CSR chunks.

    Yields (indptr, indices, values, labels) per ``chunk_rows`` rows —
    host memory stays at one chunk regardless of file size (the
    reference's ThreadedParser streaming, ``src/io/libsvm_parser.h``).
    Shared by the whole-file parser below and external-memory ingest.
    """
    labels: list = []
    indptr: list = [0]
    indices: list = []
    values: list = []

    def emit():
        out = (np.asarray(indptr, dtype=np.int64),
               np.asarray(indices, dtype=np.int32),
               np.asarray(values, dtype=np.float32),
               np.asarray(labels, dtype=np.float32))
        labels.clear(), indices.clear(), values.clear()
        indptr.clear(), indptr.append(0)
        return out

    with open(path, "rb") as f:
        for i, raw in enumerate(f):
            if nparts > 1 and i % nparts != rank:
                continue
            parts = raw.split()
            if not parts:
                continue
            labels.append(float(parts[0]))
            for tok in parts[1:]:
                k, _, v = tok.partition(b":")
                indices.append(int(k))
                values.append(float(v))
            indptr.append(len(indices))
            if len(labels) >= chunk_rows:
                yield emit()
    if labels:
        yield emit()


def parse_libsvm_python(path: str, rank: int = 0, nparts: int = 1):
    """Pure-Python libsvm parser (fallback + parity oracle for the
    native parser's tests)."""
    chunks = list(iter_libsvm_chunks(path, 1 << 62, rank, nparts))
    if not chunks:
        return (np.zeros(1, np.int64), np.zeros(0, np.int32),
                np.zeros(0, np.float32), np.zeros(0, np.float32))
    return chunks[0]


def load_meta_sidecars(dmat: DMatrix, path: str) -> None:
    """Load ``path.group`` / ``path.weight`` / ``path.base_margin`` sidecar
    files if present (reference MetaInfo::TryLoadGroup/TryLoadFloatInfo,
    src/learner/dmatrix.h:108-137)."""
    if os.path.exists(path + ".group"):
        dmat.info.set_field(
            "group", np.loadtxt(path + ".group", dtype=np.int64, ndmin=1))
    for name in ("weight", "base_margin"):
        if os.path.exists(path + "." + name):
            dmat.info.set_field(
                name, np.loadtxt(path + "." + name, dtype=np.float32, ndmin=1))
