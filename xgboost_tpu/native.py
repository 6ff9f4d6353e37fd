"""ctypes binding to the native IO runtime (native/xgtpu_io.cpp).

Loads ``libxgtpu_io.so`` — but only one built from the ``native/``
sources as they are NOW: the library is ignored by git and survives in
a working tree across checkouts, so a build stamp (digest of its
sources) sits beside it and a missing or stale stamp means rebuild with
the repo Makefile (when a toolchain is available).  Exposes:

  - :func:`parse_libsvm_native` — multithreaded libsvm parsing
    (reference ``src/io/libsvm_parser.h``'s OMP chunk parser);
  - :class:`PageWriter` / :class:`PageReader` — external-memory sparse
    page spill files with a background prefetch thread (reference
    ``src/io/sparse_batch_page.h`` + ``src/utils/thread_buffer.h``).

Everything degrades to pure-Python equivalents when the library cannot
be built (``available()`` returns False).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

# module-level import so __del__ can still account failures during
# interpreter shutdown, when function-local imports start failing
from xgboost_tpu.obs.metrics import swallowed_error

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libxgtpu_io.so")
_STAMP_PATH = _LIB_PATH + ".stamp"
_LIB_SOURCES = ("xgtpu_io.cpp", "Makefile")

_lib = None
_lib_lock = threading.Lock()
_load_failed = False

i64p = ctypes.POINTER(ctypes.c_int64)
i32p = ctypes.POINTER(ctypes.c_int32)
f32p = ctypes.POINTER(ctypes.c_float)


def _source_digest() -> str:
    h = hashlib.sha256()
    for name in _LIB_SOURCES:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _lib_is_current() -> bool:
    """The library exists AND its stamp matches the present sources."""
    try:
        with open(_STAMP_PATH) as f:
            return (os.path.exists(_LIB_PATH)
                    and f.read().strip() == _source_digest())
    except OSError:
        return False


def _build() -> bool:
    if os.environ.get("XGTPU_NO_NATIVE_BUILD"):
        return False
    import sys
    print("xgboost_tpu: building native IO library (no build of the "
          "present sources found; set XGTPU_NO_NATIVE_BUILD=1 to skip "
          "and use the Python parser)", file=sys.stderr)
    try:
        # -B: a library left by another checkout can be NEWER than the
        # sources it was not built from
        subprocess.run(["make", "-B", "-C", _NATIVE_DIR, "lib"],
                       check=True, capture_output=True, timeout=120)
        if not os.path.exists(_LIB_PATH):
            return False
        # atomic: a torn stamp must read as "stale", never as a match
        from xgboost_tpu.reliability.integrity import atomic_write
        atomic_write(_STAMP_PATH, (_source_digest() + "\n").encode())
        return True
    except Exception as e:
        # no toolchain -> pure-Python fallback; the degradation is
        # counted so a fleet silently parsing at 1/8 speed shows up
        swallowed_error("native.build", e)
        return False


def _declare(lib) -> None:
    lib.XGTParseLibSVM.restype = ctypes.c_void_p
    lib.XGTParseLibSVM.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                   ctypes.c_int, ctypes.c_int,
                                   ctypes.POINTER(ctypes.c_int)]
    lib.XGTCSRSizes.argtypes = [ctypes.c_void_p, i64p, i64p]
    lib.XGTCSRCopy.argtypes = [ctypes.c_void_p, i64p, i32p, f32p, f32p]
    lib.XGTCSRFree.argtypes = [ctypes.c_void_p]
    lib.XGTPageWriterCreate.restype = ctypes.c_void_p
    lib.XGTPageWriterCreate.argtypes = [ctypes.c_char_p]
    lib.XGTPageWriterPush.restype = ctypes.c_int
    lib.XGTPageWriterPush.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                      i64p, i32p, f32p]
    lib.XGTPageWriterClose.argtypes = [ctypes.c_void_p]
    lib.XGTPageReaderCreate.restype = ctypes.c_void_p
    lib.XGTPageReaderCreate.argtypes = [ctypes.c_char_p]
    lib.XGTPageReaderNext.restype = ctypes.c_int
    lib.XGTPageReaderNext.argtypes = [ctypes.c_void_p, i64p, i64p]
    lib.XGTPageReaderCopy.argtypes = [ctypes.c_void_p, i64p, i32p, f32p]
    lib.XGTPageReaderReset.argtypes = [ctypes.c_void_p]
    lib.XGTPageReaderFree.argtypes = [ctypes.c_void_p]


def get_lib():
    """The loaded native library, or None when unavailable."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _load_failed:
            return _lib
        if not _lib_is_current() and not _build():
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            _declare(lib)
            _lib = lib
        except OSError:
            _load_failed = True
    return _lib


def available() -> bool:
    return get_lib() is not None


def _as_i64p(a): return a.ctypes.data_as(i64p)
def _as_i32p(a): return a.ctypes.data_as(i32p)
def _as_f32p(a): return a.ctypes.data_as(f32p)


def parse_libsvm_native(path: str, rank: int = 0, nparts: int = 1,
                        nthread: int = 0
                        ) -> Optional[Tuple[np.ndarray, np.ndarray,
                                            np.ndarray, np.ndarray]]:
    """(indptr, indices, values, labels) or None if native unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    status = ctypes.c_int(0)
    h = lib.XGTParseLibSVM(path.encode(), nthread, rank, nparts,
                           ctypes.byref(status))
    if not h:
        if status.value == 2:
            # match the pure-Python fallback, which raises ValueError
            # from int()/float() on malformed tokens
            raise ValueError(f"malformed libsvm input in {path!r}")
        import errno
        raise FileNotFoundError(errno.ENOENT, "cannot open libsvm file",
                                path)
    try:
        n_rows = ctypes.c_int64()
        nnz = ctypes.c_int64()
        lib.XGTCSRSizes(h, ctypes.byref(n_rows), ctypes.byref(nnz))
        indptr = np.empty(n_rows.value + 1, np.int64)
        indices = np.empty(nnz.value, np.int32)
        values = np.empty(nnz.value, np.float32)
        labels = np.empty(n_rows.value, np.float32)
        lib.XGTCSRCopy(h, _as_i64p(indptr), _as_i32p(indices),
                       _as_f32p(values), _as_f32p(labels))
    finally:
        lib.XGTCSRFree(h)
    return indptr, indices, values, labels


class PageWriter:
    """Spill CSR row pages to a binary page file."""

    def __init__(self, path: str):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native IO runtime unavailable")
        self._lib = lib
        self._h = lib.XGTPageWriterCreate(path.encode())
        if not self._h:
            raise IOError(f"cannot create {path!r}")

    def push(self, indptr: np.ndarray, indices: np.ndarray,
             values: np.ndarray) -> None:
        indptr = np.ascontiguousarray(indptr, np.int64)
        indices = np.ascontiguousarray(indices, np.int32)
        values = np.ascontiguousarray(values, np.float32)
        if len(indptr) < 1:
            raise ValueError("indptr must have at least one element")
        rc = self._lib.XGTPageWriterPush(
            self._h, len(indptr) - 1, _as_i64p(indptr), _as_i32p(indices),
            _as_f32p(values))
        if rc != 0:
            raise IOError("page write failed")

    def close(self) -> None:
        if self._h:
            self._lib.XGTPageWriterClose(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()  # flush the C++ stream even without close()
        except Exception as e:
            swallowed_error("native.page_writer_del", e, emit_event=False)


class PageReader:
    """Iterate (indptr, indices, values) pages with background prefetch."""

    def __init__(self, path: str):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native IO runtime unavailable")
        self._lib = lib
        self._h = lib.XGTPageReaderCreate(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path!r} (bad magic?)")

    def __iter__(self):
        return self

    def __next__(self):
        n_rows = ctypes.c_int64()
        nnz = ctypes.c_int64()
        if not self._lib.XGTPageReaderNext(self._h, ctypes.byref(n_rows),
                                           ctypes.byref(nnz)):
            raise StopIteration
        indptr = np.empty(n_rows.value + 1, np.int64)
        indices = np.empty(nnz.value, np.int32)
        values = np.empty(nnz.value, np.float32)
        self._lib.XGTPageReaderCopy(self._h, _as_i64p(indptr),
                                    _as_i32p(indices), _as_f32p(values))
        return indptr, indices, values

    def reset(self) -> None:
        self._lib.XGTPageReaderReset(self._h)

    def close(self) -> None:
        if self._h:
            self._lib.XGTPageReaderFree(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception as e:
            swallowed_error("native.page_reader_del", e, emit_event=False)
