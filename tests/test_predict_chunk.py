"""Chunked tree-parallel prediction (models/tree.py ``tree_chunk``).

Parity suite: the chunked-vmap traversal must be BIT-identical to the
sequential scan-over-trees baseline across every layout the ladder can
produce (T not a chunk multiple, T < chunk, ntree_limit windows,
n_group > 1, n_roots > 1), plus a ``recompile_guard`` budget proving
the padding ladder bounds compilation for growing ensembles.
"""

import numpy as np
import pytest


def _train(params=None, n=400, f=8, rounds=7, seed=0, num_class=0):
    import xgboost_tpu as xgb

    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    if num_class:
        y = (X[:, 0] * num_class).astype(np.int64) % num_class
        y = y.astype(np.float32)
        p = {"objective": "multi:softmax", "num_class": num_class}
    else:
        y = (X[:, 0] + 0.3 * X[:, 1] > 0.6).astype(np.float32)
        p = {"objective": "binary:logistic"}
    p.update({"max_depth": 4, "eta": 0.3, "silent": 1})
    p.update(params or {})
    d = xgb.DMatrix(X, label=y)
    return xgb.train(p, d, rounds), X, d


def _binned_of(bst, X):
    import jax.numpy as jnp
    import xgboost_tpu as xgb
    from xgboost_tpu.binning import bin_matrix
    return jnp.asarray(bin_matrix(xgb.DMatrix(X), bst.gbtree.cuts))


def _margins(bst, binned, chunk, ntree_limit=0):
    """(N, K) margins with the traversal width forced to ``chunk``
    (0 = the scan baseline)."""
    gbt = bst.gbtree
    saved = gbt.pred_chunk
    gbt.pred_chunk = chunk
    try:
        import jax.numpy as jnp
        return np.asarray(gbt.predict_margin(
            binned, jnp.zeros((), jnp.float32), ntree_limit))
    finally:
        gbt.pred_chunk = saved


def _leaves(bst, binned, chunk):
    gbt = bst.gbtree
    saved = gbt.pred_chunk
    gbt.pred_chunk = chunk
    try:
        return np.asarray(gbt.predict_leaf(binned))
    finally:
        gbt.pred_chunk = saved


def test_chunk_parity_binary_all_layouts():
    """T=7 against chunks exercising: T not a chunk multiple (4),
    non-power-of-two chunks (3, 6 — incl. the pow2-pad chunk cap),
    T < chunk (32), chunk == 2."""
    from xgboost_tpu.models.tree import padded_tree_count
    # the pow2 pad below the chunk is CAPPED at the chunk width (the
    # knob's promised vmap width): 12@12 -> 12, 5@6 -> 6, not 8/16
    assert padded_tree_count(12, 12) == 12
    assert padded_tree_count(5, 6) == 6
    assert padded_tree_count(7, 6) == 12
    bst, X, _ = _train(rounds=7)
    binned = _binned_of(bst, X)
    ref_m = _margins(bst, binned, 0)
    ref_l = _leaves(bst, binned, 0)
    for chunk in (2, 3, 4, 6, 32):
        assert np.array_equal(ref_m, _margins(bst, binned, chunk)), chunk
        assert np.array_equal(ref_l, _leaves(bst, binned, chunk)), chunk


def test_chunk_parity_multiclass():
    """n_group > 1: per-tree groups route contributions through the
    one-hot accumulation; 4 rounds x 3 classes = 12 trees, chunk 5
    (partial final chunk with mixed groups)."""
    bst, X, _ = _train(rounds=4, num_class=3)
    binned = _binned_of(bst, X)
    ref = _margins(bst, binned, 0)
    assert ref.shape[1] == 3
    for chunk in (5, 12, 32):
        assert np.array_equal(ref, _margins(bst, binned, chunk)), chunk


def test_chunk_parity_ntree_limit_windows():
    """ntree_limit re-stacks a PREFIX of the ensemble: every window
    size must hit the same ladder pad and stay bit-identical."""
    bst, X, _ = _train(rounds=9)
    binned = _binned_of(bst, X)
    for lim in (1, 2, 3, 5, 8, 9):
        ref = _margins(bst, binned, 0, ntree_limit=lim)
        assert np.array_equal(
            ref, _margins(bst, binned, 4, ntree_limit=lim)), lim


def test_chunk_parity_multi_root():
    """n_roots > 1: the per-row root slot flows through the vmapped
    traversal unbatched; end-to-end booster predict is bit-identical."""
    import xgboost_tpu as xgb

    rng = np.random.RandomState(7)
    n = 600
    X = rng.rand(n, 3).astype(np.float32)
    regime = (rng.rand(n) > 0.5).astype(np.uint32)
    y = np.where(regime == 0, X[:, 0] > 0.5, X[:, 0] <= 0.5).astype(
        np.float32)
    d = xgb.DMatrix(X, label=y)
    d.set_uint_info("root_index", regime)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 2,
                     "eta": 1.0, "num_roots": 2, "silent": 1}, d, 3)
    gbt = bst.gbtree
    d2 = xgb.DMatrix(X, label=y)
    d2.set_uint_info("root_index", regime)
    gbt.pred_chunk = 0
    ref = bst.predict(d2)
    d3 = xgb.DMatrix(X, label=y)
    d3.set_uint_info("root_index", regime)
    gbt.pred_chunk = 4
    assert np.array_equal(ref, bst.predict(d3))
    # leaves route through root slots too
    gbt.pred_chunk = 0
    ref_l = bst.predict(d2, pred_leaf=True)
    gbt.pred_chunk = 4
    assert np.array_equal(ref_l, bst.predict(d3, pred_leaf=True))


def test_incremental_margin_matches_full_traversal():
    """The cached incremental margin (predict_incremental windows per
    round) must equal a cold full-model prediction under chunking —
    the training predict phase and one-off serving agree bitwise."""
    import xgboost_tpu as xgb
    bst, X, d = _train(rounds=6)
    cached = bst.predict(d)                  # incremental margin cache
    cold = bst.predict(xgb.DMatrix(X))       # fresh full traversal
    assert np.array_equal(cached, cold)


def test_chunk_compile_budget(recompile_guard):
    """Growing an ensemble T = 1..3*chunk recompiles the TRAVERSAL only
    when the ladder rung changes: the distinct-pad count (log2(chunk)
    + 3 here) is the fixed budget — NOT one compile per T.  The eager
    padding glue (byte-copy concats, deliberately outside the jitted
    core — see pad_predict_stack) is warmed in setup so the guarded
    region counts exactly the heavy traversal programs."""
    import jax
    import jax.numpy as jnp
    from xgboost_tpu.models.tree import (pad_predict_stack,
                                         padded_tree_count,
                                         predict_margin_binned)
    bst, X, _ = _train(rounds=12)            # 3 * chunk trees
    binned = _binned_of(bst, X)
    chunk = 4
    stack, group = bst.gbtree._stack(0)
    base = jnp.zeros((), jnp.float32)
    windows = []
    for T in range(1, 13):
        win = (jax.tree.map(lambda x: x[:T], stack), group[:T])
        windows.append(win)
        jax.block_until_ready(pad_predict_stack(win[0], win[1], chunk)[:2])
    jax.block_until_ready(jnp.int32(1))
    expected = len({padded_tree_count(T, chunk) for T in range(1, 13)})
    assert expected == 5  # {1, 2, 4, 8, 12}
    with recompile_guard.expect(expected):
        for st, gr in windows:
            jax.block_until_ready(
                predict_margin_binned(st, gr, binned, base, 4, 1,
                                      tree_chunk=chunk))
    # second pass over the same growing windows: zero compiles
    with recompile_guard.expect(0):
        for st, gr in windows:
            jax.block_until_ready(
                predict_margin_binned(st, gr, binned, base, 4, 1,
                                      tree_chunk=chunk))


def test_bin_dense_blocked_matches_single_shot(monkeypatch):
    """The row-blocked device quantize (learner size-cliff fix) is
    bit-identical to the single-buffer call and to the host
    searchsorted path, NaNs included — and densifies per CSR block
    (never a full N x F f32 host copy)."""
    import xgboost_tpu as xgb
    from xgboost_tpu.binning import bin_dense_device, bin_matrix

    bst, X, _ = _train(rounds=2, n=300, f=6)
    Xd = X.copy()
    Xd[::7, 2] = np.nan                      # missing -> bin 0
    d = xgb.DMatrix(Xd)
    one = np.asarray(bin_dense_device(
        d.to_dense(missing=np.nan), bst.gbtree.cuts.cut_values))
    # force ~5 blocks: 300 rows * 6 cols * 4B / 5
    monkeypatch.setenv("XGBTPU_BIN_BLOCK_BYTES", str(300 * 6 * 4 // 5))
    blocked = np.asarray(bst._bin_dense_blocked(d))
    assert np.array_equal(one, blocked)
    host = bin_matrix(d, bst.gbtree.cuts)
    assert np.array_equal(host, blocked)


def test_predict_over_guard_keeps_device_path(monkeypatch):
    """A dense matrix past the (shrunk) byte guard still predicts
    bit-identically through the blocked device-quantize path."""
    import xgboost_tpu as xgb
    bst, X, _ = _train(rounds=3, n=500, f=6)
    ref = bst.predict(xgb.DMatrix(X))
    monkeypatch.setenv("XGBTPU_BIN_BLOCK_BYTES", str(500 * 6 * 4 // 3))
    assert np.array_equal(ref, bst.predict(xgb.DMatrix(X)))


def test_predict_rows_metric_counts():
    """xgbtpu_predict_rows_total counts Learner.predict traffic."""
    import xgboost_tpu as xgb
    from xgboost_tpu.obs.metrics import predict_metrics
    bst, X, _ = _train(rounds=2, n=123)
    before = predict_metrics().rows.value
    bst.predict(xgb.DMatrix(X))
    assert predict_metrics().rows.value == before + 123


def test_engine_reports_chunk_layout_and_observes_seconds():
    """The serving engine carries the chunk layout in describe() and
    feeds the per-chunk traversal histogram on every predict — and a
    CHUNKED model serves bit-identically to Learner.predict through
    the AOT per-bucket executables."""
    import xgboost_tpu as xgb
    from xgboost_tpu.obs.metrics import predict_metrics
    from xgboost_tpu.serving import PredictEngine
    bst, X, _ = _train({"predict_tree_chunk": 8}, rounds=5)
    assert bst.gbtree.pred_chunk == 8
    eng = PredictEngine(bst, min_bucket=8, max_bucket=64)
    desc = eng.describe()
    assert desc["tree_chunk"] == 8
    assert desc["tree_chunks"] == 1          # 5 trees pad to one chunk
    pm = predict_metrics()
    n0, r0 = pm.chunk_seconds.count, pm.rows.value
    out = eng.predict(X[:10])
    assert out.shape[0] == 10
    assert pm.chunk_seconds.count == n0 + 1
    assert pm.rows.value == r0 + 10
    # bitwise parity engine (padded bucket, chunked) vs learner
    assert np.array_equal(eng.predict(X[:10]),
                          bst.predict(xgb.DMatrix(X[:10])))


def test_chunk_knob_resolution(monkeypatch):
    """XGBTPU_PREDICT_TREE_CHUNK is the end-to-end A/B seam; the -1
    auto default resolves per backend (scan on CPU); an explicit param
    forces."""
    import jax
    monkeypatch.setenv("XGBTPU_PREDICT_TREE_CHUNK", "8")
    bst, X, _ = _train(rounds=3)
    assert bst.gbtree.pred_chunk == 8
    monkeypatch.delenv("XGBTPU_PREDICT_TREE_CHUNK")
    bst2, _, _ = _train(rounds=3)            # auto
    expect = 32 if jax.default_backend() == "tpu" else 0
    assert bst2.gbtree.pred_chunk == expect
    bst3, _, _ = _train({"predict_tree_chunk": 16}, rounds=3)
    assert bst3.gbtree.pred_chunk == 16
    import xgboost_tpu as xgb
    p = bst.predict(xgb.DMatrix(X))
    assert np.array_equal(p, bst2.predict(xgb.DMatrix(X)))
    assert np.array_equal(p, bst3.predict(xgb.DMatrix(X)))
