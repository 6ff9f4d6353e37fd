"""Dense input stays dense through ingest (ISSUE 30): a ``DMatrix``
built from an ndarray proposes its cuts and bins its rows column by
column from that array, and gives the bytes the CSR path gives on the
CSR tuple of the same array; training from it builds no CSR."""

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu import binning
from xgboost_tpu.binning import bin_dense, bin_matrix, compute_cuts
from xgboost_tpu.data import DMatrix
from xgboost_tpu.obs import span_totals
from xgboost_tpu.sketch import sketch_column


def _normal(n, f, seed=0):
    return np.random.default_rng(seed).normal(size=(n, f)).astype(np.float32)


def _no_missing():
    return _normal(3000, 5), {}


def _nan_missing():
    X = _normal(3000, 5, 1)
    X[np.random.default_rng(2).random(X.shape) < 0.1] = np.nan
    X[:, 3] = np.nan                        # a wholly missing column
    return X, {}


def _zero_missing_with_nans():
    X = _normal(3000, 5, 3)
    r = np.random.default_rng(4).random(X.shape)
    X[r < 0.2] = 0.0                        # the marker
    X[r > 0.95] = np.nan                    # stored, stays bin 0
    return X, {"missing": 0.0}


def _inf_cells():
    X = _normal(3000, 4, 5)
    X[::7, 0], X[3::11, 1], X[5::13, 1] = np.inf, -np.inf, np.inf
    X[::2, 2] = np.inf
    return X, {}


def _few_distinct():
    rng = np.random.default_rng(6)
    X = rng.integers(0, 12, size=(3000, 4)).astype(np.float32)
    X[:, 1] = rng.integers(0, 2, size=3000)
    X[:, 2] = rng.integers(-100, 100, size=3000) / 4
    return X, {}


def _constant_column():
    X = _normal(3000, 4, 7)
    X[:, 0], X[:, 2] = 2.5, 0.0
    return X, {}


def _wider_num_col():
    return _normal(2000, 3, 8), {"num_col": 6}


def _fortran_order():
    X = np.asfortranarray(_normal(3000, 5, 9))
    X[::5, 1] = np.nan
    return X, {}


def _float64_input():
    X = np.random.default_rng(10).normal(size=(3000, 4))
    X[::9, 2] = np.nan
    return X, {}


def _long_column():
    # over 2^16 rows: the sketch_column branch, one chunk; the second
    # column is shorter than that once its missing cells are out
    X = _normal((1 << 16) + 4000, 3, 11)
    X[np.random.default_rng(12).random(len(X)) < 0.2, 1] = np.nan
    return X, {}


def _two_chunks():
    # over 2^22 rows: two chunks and a merge; column 1's chunks fall on
    # other rows than column 0's because its missing cells are left out
    X = _normal((1 << 22) + 30000, 2, 13)
    X[::3, 1] = np.nan
    return X, {}


SMALL = (_no_missing, _nan_missing, _zero_missing_with_nans, _inf_cells,
         _few_distinct, _constant_column, _wider_num_col, _fortran_order,
         _float64_input)
CASES = ([(make, max_bin) for make in SMALL for max_bin in (16, 256)]
         + [(_long_column, 16), (_long_column, 256), (_two_chunks, 256)])


def _csr_twin(X, **kw):
    """The matrix the CSR tuple of ``X`` builds: the path every input
    without a dense source takes."""
    d = DMatrix(X, **kw)
    return DMatrix((d.indptr, d.indices, d.values, d.num_col))


def _quantize(dmat, max_bin):
    cuts = compute_cuts(dmat, max_bin=max_bin, sketch_eps=1.0 / max_bin)
    return cuts, bin_matrix(dmat, cuts)


@pytest.mark.parametrize(
    "make,max_bin", CASES,
    ids=[f"{m.__name__.lstrip('_')}-{b}" for m, b in CASES])
def test_dense_source_gives_the_csr_path_s_cuts_and_bins(make, max_bin):
    X, kw = make()
    dense, twin = DMatrix(X, **kw), _csr_twin(X, **kw)
    assert twin.dense_source() is None
    cuts, bins = _quantize(dense, max_bin)
    want_cuts, want_bins = _quantize(twin, max_bin)
    assert dense.dense_source() is not None     # no CSR was built
    assert dense._col_cache is None
    np.testing.assert_array_equal(cuts.n_cuts, want_cuts.n_cuts)
    np.testing.assert_array_equal(cuts.cut_values, want_cuts.cut_values)
    assert bins.dtype == want_bins.dtype
    np.testing.assert_array_equal(bins, want_bins)
    if "num_col" not in kw:
        np.testing.assert_array_equal(
            bin_dense(np.asarray(X, np.float32), cuts,
                      kw.get("missing", np.nan)), bins)


def test_hess_weights_keep_the_weighted_csr_path():
    X, _ = _nan_missing()
    w = np.random.default_rng(0).random(len(X)) + 0.5
    dense, twin = DMatrix(X), _csr_twin(X)
    got = compute_cuts(dense, max_bin=32, hess_weights=w)
    want = compute_cuts(twin, max_bin=32, hess_weights=w)
    np.testing.assert_array_equal(got.cut_values, want.cut_values)
    assert dense.dense_source() is None         # it asked for CSR


@pytest.mark.parametrize("threads", [2, 8])
def test_thread_pool_gives_the_serial_loop_s_bytes(monkeypatch, threads):
    X = _normal((1 << 16) + 5000, 6, 20)
    X[np.random.default_rng(21).random(X.shape) < 0.05] = np.nan
    monkeypatch.setattr(binning, "_BIN_BLOCK", 1 << 13)   # nine blocks
    monkeypatch.setattr(binning, "_THREADS", 1)
    cuts, bins = _quantize(DMatrix(X), 64)
    monkeypatch.setattr(binning, "_THREADS", threads)
    pooled_cuts, pooled_bins = _quantize(DMatrix(X), 64)
    assert pooled_cuts.cut_values.tobytes() == cuts.cut_values.tobytes()
    assert pooled_bins.tobytes() == bins.tobytes()


@pytest.mark.parametrize("F,rows,group", [(28, 1 << 18, 1), (13, 1 << 18, 1),
                                          (2000, 16777, 16)])
def test_tasks_follow_the_matrix_s_width(monkeypatch, F, rows, group):
    """A binning block is sized in cells and a cut-proposal task in
    columns: what they were at 28 and 13 columns (2^18 rows, one
    column), 24 + 6 blocks and 125 groups of 16 at 400,000 + 100,000 x
    2,000, where 2^18 rows were two tasks and one."""
    monkeypatch.setattr(binning, "_THREADS", 8)
    assert binning._block_rows(F) == rows
    assert binning._column_group(F) == group


@pytest.mark.parametrize("F", [28, 2000])
def test_blocks_and_column_groups_give_the_serial_loop_s_bytes(monkeypatch, F):
    n = 20000
    X = _normal(n, F, 36)
    X[np.random.default_rng(37).random(X.shape) < 0.01] = np.nan
    X[:, F // 2] = np.round(X[:, F // 2])           # a column of ties
    with monkeypatch.context() as m:
        # one column a task, one block, one thread
        m.setattr(binning, "_THREADS", 1)
        m.setattr(binning, "_LINE", 1)
        m.setattr(binning, "_BIN_CELLS", 1 << 40)
        cuts, bins = _quantize(DMatrix(X), 256)
    monkeypatch.setattr(binning, "_SKETCH_MIN", 1 << 12)    # pooled
    monkeypatch.setattr(binning, "_THREADS", 8)
    monkeypatch.setattr(binning, "_BIN_BLOCK", 1 << 13)
    assert -(-n // binning._block_rows(F)) == 3
    assert binning._column_group(F) == (16 if F == 2000 else 1)
    got_cuts, got_bins = _quantize(DMatrix(X), 256)
    assert got_cuts.cut_values.tobytes() == cuts.cut_values.tobytes()
    assert got_cuts.n_cuts.tobytes() == cuts.n_cuts.tobytes()
    assert got_bins.tobytes() == bins.tobytes()
    # and a matrix narrower than num_col= still pads with empty columns
    wide = compute_cuts(DMatrix(X[:, :F - 3], num_col=F), max_bin=256,
                        sketch_eps=1.0 / 256)
    assert wide.n_cuts[F - 3:].tolist() == [0, 0, 0]
    np.testing.assert_array_equal(wide.cut_values[:F - 3, :cuts.cut_values.shape[1]],
                                  cuts.cut_values[:F - 3])


@pytest.mark.parametrize("n,chunk", [(5000, 1 << 22), (20000, 3000)])
def test_unweighted_sketch_is_the_weighted_one_s_twin(n, chunk):
    rng = np.random.default_rng(n)
    v = rng.normal(size=n).astype(np.float32)
    v[::50] = v[1::50]                       # ties
    v[::97], v[5::101] = np.nan, np.inf      # left out by both
    a = sketch_column(v, None, 1.0 / 256, chunk=chunk)
    b = sketch_column(v, np.ones_like(v), 1.0 / 256, chunk=chunk)
    c = sketch_column(v.astype(np.float64), None, 1.0 / 256, chunk=chunk)
    for other in (b, c):
        for field in ("value", "rmin", "rmax", "wmin"):
            got, want = getattr(a, field), getattr(other, field)
            assert got.dtype == want.dtype == np.float64
            np.testing.assert_array_equal(got, want)


PARAMS = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
          "max_bin": 64, "sketch_eps": 1.0 / 64, "silent": 1}


def _labelled(n=1200, f=5, seed=30):
    X = _normal(n, f, seed)
    X[np.random.default_rng(seed + 1).random(X.shape) < 0.05] = np.nan
    return X, (np.nan_to_num(X[:, 0]) + X[:, 1] > 0).astype(np.float32)


def test_training_from_an_ndarray_builds_no_csr_and_slice_still_does():
    X, y = _labelled()
    dmat = DMatrix(X, label=y)
    exits0 = span_totals().count.values().get("ingest.dmatrix", 0)
    bst = xgb.Booster(dict(PARAMS), cache=[dmat])
    for i in range(2):
        bst.update(dmat, i)
    assert dmat.dense_source() is not None and dmat._indptr is None
    assert span_totals().count.values()["ingest.dmatrix"] == exits0
    part = dmat.slice(np.arange(0, 1200, 3))    # asks for CSR: built now
    assert dmat.dense_source() is None
    assert span_totals().count.values()["ingest.dmatrix"] == exits0 + 2
    np.testing.assert_array_equal(part.to_dense(), X[::3])
    dmat.slice([0, 1])
    dmat.to_dense()
    assert span_totals().count.values()["ingest.dmatrix"] == exits0 + 3
    # and the booster's cached entry still serves the same matrix
    np.testing.assert_array_equal(bst.predict(dmat), bst.predict(DMatrix(X)))


def test_model_bytes_from_an_ndarray_equal_those_from_its_csr_tuple():
    X, y = _labelled(seed=40)
    raws = []
    for dmat in (DMatrix(X, label=y), _csr_twin(X)):
        dmat.set_label(y)
        bst = xgb.Booster(dict(PARAMS), cache=[dmat])
        for i in range(3):
            bst.update(dmat, i)
        raws.append(bst.save_raw())
    assert raws[0] == raws[1]
