"""Data-parallel training tests on an 8-virtual-device CPU mesh.

The analog of the reference's local multi-process harness
(``subtree/rabit/tracker/rabit_demo.py`` + ``multi-node/`` scripts,
SURVEY.md §4.2): same training code, collectives over a real mesh.

Key property: row-split distributed training produces EXACTLY the same
model as single-device training (histogram psum is a sum either way and
the argmax tie-break is deterministic) — stronger than the reference,
which only guarantees consistent distributed state.
"""

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.parallel.mesh import data_parallel_mesh, set_mesh


@pytest.fixture
def mesh8():
    m = data_parallel_mesh(8)
    set_mesh(m)
    yield m
    set_mesh(None)


def make_data(n=4096, f=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = ((X[:, 0] > 0.5) ^ (X[:, 1] > 0.3) |
         (X[:, 2] > 0.9)).astype(np.float32)
    return X, y


def test_dp_matches_single_device(mesh8):
    X, y = make_data()
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.5}

    d1 = xgb.DMatrix(X, label=y)
    bst_single = xgb.train(params, d1, 5, verbose_eval=False)
    p_single = bst_single.predict(d1)

    d2 = xgb.DMatrix(X, label=y)
    bst_dp = xgb.train({**params, "dsplit": "row"}, d2, 5, verbose_eval=False)
    p_dp = bst_dp.predict(d2)

    np.testing.assert_allclose(p_single, p_dp, rtol=2e-4, atol=2e-5)


def test_dp_padding_odd_rows(mesh8):
    X, y = make_data(n=4091)  # not divisible by 8
    d = xgb.DMatrix(X, label=y)
    res = {}
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "dsplit": "row"}, d, 3, evals=[(d, "train")],
                    evals_result=res, verbose_eval=False)
    assert bst.predict(d).shape == (4091,)
    assert res["train-error"][-1] < 0.3


def test_dp_multiclass(mesh8):
    rng = np.random.RandomState(1)
    X = rng.randn(2048, 6).astype(np.float32)
    y = np.argmax(X[:, :3] + 0.2 * rng.randn(2048, 3), axis=1).astype(
        np.float32)
    d = xgb.DMatrix(X, label=y)
    res = {}
    xgb.train({"objective": "multi:softmax", "num_class": 3, "max_depth": 4,
               "dsplit": "row"}, d, 5, evals=[(d, "train")],
              evals_result=res, verbose_eval=False)
    assert res["train-merror"][-1] < 0.2


def test_dp_multiclass_base_margin_odd_rows(mesh8):
    """K>1 + base_margin + dsplit=row with padding: the raveled (n*K,)
    margin must pad per-row, and the model must match single-device."""
    rng = np.random.RandomState(5)
    n = 2043  # not divisible by 8
    X = rng.randn(n, 6).astype(np.float32)
    y = np.argmax(X[:, :3] + 0.2 * rng.randn(n, 3), axis=1).astype(np.float32)
    margin = rng.randn(n, 3).astype(np.float32) * 0.1
    params = {"objective": "multi:softprob", "num_class": 3, "max_depth": 3,
              "eta": 0.5}

    d_dp = xgb.DMatrix(X, label=y)
    d_dp.set_base_margin(margin.ravel())
    bst_dp = xgb.train({**params, "dsplit": "row"}, d_dp, 3,
                       verbose_eval=False)
    p_dp = bst_dp.predict(d_dp)
    assert p_dp.shape == (n, 3)

    d1 = xgb.DMatrix(X, label=y)
    d1.set_base_margin(margin.ravel())
    p1 = xgb.train(params, d1, 3, verbose_eval=False).predict(d1)
    np.testing.assert_allclose(p1, p_dp, rtol=2e-4, atol=2e-5)


def test_dp_padding_margin_invariant(mesh8):
    """Cached margins of padding rows must stay at base across rounds
    (they feed get_gradient; garbage would leak via prune's node-0 value)."""
    X, y = make_data(n=4091)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 4,
                     "eta": 0.5, "gamma": 0.2, "dsplit": "row"}, d, 3,
                    verbose_eval=False)
    entry = bst._cache[id(d)]
    margin = np.asarray(entry.margin).reshape(-1)
    valid = np.asarray(entry.row_valid).reshape(-1)
    base = np.asarray(entry.base).reshape(-1)
    np.testing.assert_allclose(margin[~valid], base[~valid], atol=1e-6)


def test_dp_deterministic(mesh8):
    X, y = make_data(n=2048)
    params = {"objective": "binary:logistic", "max_depth": 4,
              "subsample": 0.8, "seed": 11, "dsplit": "row"}
    d1 = xgb.DMatrix(X, label=y)
    p1 = xgb.train(params, d1, 3, verbose_eval=False).predict(d1)
    d2 = xgb.DMatrix(X, label=y)
    p2 = xgb.train(params, d2, 3, verbose_eval=False).predict(d2)
    np.testing.assert_array_equal(p1, p2)


def test_graft_entry_dryrun():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)


def test_graft_entry_forward():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as ge
    import jax
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert np.all(np.isfinite(np.asarray(out)))


def test_dp_custom_objective_odd_rows(mesh8):
    """Custom-objective (fobj) path under row sharding with padding:
    predictions seen by fobj must have exactly num_row entries and the
    padded gradient rows must not perturb the model."""
    X, y = make_data(n=4091)
    d = xgb.DMatrix(X, label=y)

    def logistic_obj(preds, dmat):
        labels = dmat.get_label()
        assert preds.shape == (4091,)
        grad = preds - labels
        hess = preds * (1.0 - preds)
        return grad, hess

    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.5,
              "dsplit": "row"}
    bst = xgb.train(params, d, 3, obj=logistic_obj, verbose_eval=False)
    bst_builtin = xgb.train(params, xgb.DMatrix(X, label=y), 3,
                            verbose_eval=False)
    np.testing.assert_allclose(bst.predict(d),
                               bst_builtin.predict(xgb.DMatrix(X, label=y)),
                               rtol=2e-4, atol=2e-5)


def test_dp_pred_leaf_truncates_padding(mesh8):
    X, y = make_data(n=4091)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "dsplit": "row"}, d, 2, verbose_eval=False)
    leaves = bst.predict(d, pred_leaf=True)
    assert leaves.shape[0] == 4091


# ---------------------------------------------------------------- distcol
def test_colsplit_matches_single_device():
    """dsplit=col (DistColMaker analog): feature-sharded growth must
    reproduce the single-device model exactly — the SplitEntry argmax
    reduce and psum position bitmap change nothing numerically."""
    from xgboost_tpu.parallel.colsplit import feature_parallel_mesh

    X, y = make_data(n=2048, f=10)
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.5}

    d1 = xgb.DMatrix(X, label=y)
    bst_single = xgb.train(params, d1, 4, verbose_eval=False)
    p_single = bst_single.predict(d1)

    d2 = xgb.DMatrix(X, label=y)
    bst_col = xgb.train({**params, "dsplit": "col"}, d2, 4,
                        verbose_eval=False)
    p_col = bst_col.predict(d2)
    np.testing.assert_allclose(p_single, p_col, rtol=2e-4, atol=2e-5)

    # identical tree structure, not just predictions (cut_index is only
    # meaningful on real split nodes; elsewhere it holds argmax noise)
    for t1, t2 in zip(bst_single.gbtree.trees, bst_col.gbtree.trees):
        f1, f2 = np.asarray(t1.feature), np.asarray(t2.feature)
        np.testing.assert_array_equal(f1, f2)
        split = f1 >= 0
        np.testing.assert_array_equal(np.asarray(t1.cut_index)[split],
                                      np.asarray(t2.cut_index)[split])


def test_colsplit_feature_count_not_divisible():
    """F=13 features over 8 shards exercises the feature-padding path."""
    X, y = make_data(n=1024, f=13, seed=3)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "eta": 0.5, "dsplit": "col"}, d, 3,
                    evals=[(d, "train")], verbose_eval=False)
    err = ((bst.predict(d) > 0.5) != (y > 0.5)).mean()
    assert err < 0.1


def test_colsplit_with_gamma_prune():
    X, y = make_data(n=1024, f=10, seed=4)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 4,
                     "eta": 0.5, "gamma": 0.3, "dsplit": "col"}, d, 2,
                    verbose_eval=False)
    d_s = xgb.DMatrix(X, label=y)
    bst_s = xgb.train({"objective": "binary:logistic", "max_depth": 4,
                       "eta": 0.5, "gamma": 0.3}, d_s, 2, verbose_eval=False)
    np.testing.assert_allclose(bst.predict(d), bst_s.predict(d_s),
                               rtol=2e-4, atol=2e-5)


def test_sharded_dmatrix_single_process_bitmatch(mesh8, tmp_path):
    """ShardedDMatrix (per-rank split loading) in single-process mode:
    degenerates to loading everything, and training bit-matches the
    replicated device-sketch path — covering the block-split math,
    make_array_from_process_local_data assembly, distributed metric
    partials and local-shard prediction without subprocesses (the real
    2-process case lives in test_launch.py)."""
    rng = np.random.RandomState(13)
    N = 1003  # not divisible by the 8-device mesh
    X = rng.rand(N, 5)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0.7).astype(int)
    path = tmp_path / "t.libsvm"
    with open(path, "w") as fh:
        for i in range(N):
            # sparse: drop feature 2 on odd rows (missing-value handling)
            cols = [j for j in range(5) if not (j == 2 and i % 2)]
            feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in cols)
            fh.write(f"{y[i]} {feats}\n")

    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.7,
              "max_bin": 32, "dsplit": "row"}
    dm_s = xgb.ShardedDMatrix(str(path))
    assert dm_s.num_row == N and dm_s.local_num_row == N
    res_s = {}
    bst_s = xgb.train(params, dm_s, 5, evals=[(dm_s, "train")],
                      evals_result=res_s, verbose_eval=False)

    dm_r = xgb.DMatrix(str(path))
    res_r = {}
    bst_r = xgb.train(dict(params, device_sketch=1), dm_r, 5,
                      evals=[(dm_r, "train")], evals_result=res_r,
                      verbose_eval=False)

    s_s, s_r = bst_s.gbtree.get_state(), bst_r.gbtree.get_state()
    for k in s_s:
        np.testing.assert_array_equal(s_s[k], s_r[k], err_msg=k)
    # distributed (partial-sum) metrics agree with the host metrics
    assert res_s["train-error"][-1] == pytest.approx(
        res_r["train-error"][-1], abs=1e-6)

    # local predictions cover exactly the local rows
    p = bst_s.predict(dm_s)
    assert p.shape == (dm_s.local_num_row,)
    assert float(np.mean((p > 0.5) != y)) < 0.05

    # auc partials reduce to the reference's mean-of-shards form
    res_auc = {}
    xgb.train(dict(params, eval_metric=["auc", "logloss"]),
              xgb.ShardedDMatrix(str(path)), 3,
              evals=[(dm_s, "train")], evals_result=res_auc,
              verbose_eval=False)
    assert 0.9 < res_auc["train-auc"][-1] <= 1.0
    assert res_auc["train-logloss"][-1] < 0.3

    # unsupported-in-sharded-mode surfaces are loud, not silent
    with pytest.raises(NotImplementedError):
        xgb.train(dict(params, objective="rank:pairwise"),
                  xgb.ShardedDMatrix(str(path)), 1, verbose_eval=False)
    with pytest.raises(NotImplementedError):
        dm_s.slice(np.arange(4))


def test_dp_gblinear_matches_single_device(mesh8):
    """Distributed gblinear (VERDICT r2 item 10): rows sharded over the
    mesh, Gf/Hf reductions psum'd — matches single-device coordinate
    descent to float tolerance, padding rows inert."""
    rng = np.random.RandomState(3)
    n = 1021  # not divisible by 8: exercises zero-padding rows
    X = rng.rand(n, 6).astype(np.float32)
    w_true = np.array([1.5, -2.0, 0.0, 0.7, 0.0, 0.3], np.float32)
    y = (X @ w_true + 0.1 * rng.randn(n) > 0.5).astype(np.float32)
    params = {"booster": "gblinear", "objective": "binary:logistic",
              "eta": 0.5, "lambda": 0.1, "alpha": 0.05}

    d1 = xgb.DMatrix(X, label=y)
    bst1 = xgb.train(params, d1, 8, verbose_eval=False)
    p1 = bst1.predict(d1)

    d2 = xgb.DMatrix(X, label=y)
    res = {}
    bst2 = xgb.train({**params, "dsplit": "row"}, d2, 8,
                     evals=[(d2, "train")], evals_result=res,
                     verbose_eval=False)
    p2 = bst2.predict(d2)

    assert p2.shape == (n,)
    np.testing.assert_allclose(p1, p2, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(bst1.gbtree.weight),
                               np.asarray(bst2.gbtree.weight),
                               rtol=2e-4, atol=2e-5)
    assert res["train-error"][-1] < 0.2


def test_hlo_collectives_parser_forms():
    """The payload parser must not double-count: operand names that
    contain the opcode ('%all-reduce.3'), async -start tuple results
    (operand-alias + produced buffer), and -done ops all tripped a
    looser regex (review round 4)."""
    from xgboost_tpu.parallel.commcost import hlo_collectives
    hlo = """
  %all-reduce.4 = f32[16,28,64,2] all-reduce(f32[16,28,64,2] %all-reduce.3), replica_groups={{}}
  %ar-start = (f32[32,28,64,2], f32[32,28,64,2]) all-reduce-start(f32[32,28,64,2] %p), to_apply=%add
  %ar-done = f32[32,28,64,2] all-reduce-done((f32[32,28,64,2], f32[32,28,64,2]) %ar-start)
  %ag = (f32[8,4], f32[16,4]) all-gather-start(f32[8,4] %x), dimensions={0}
  %cps = (f32[8,4], f32[8,4], u32[], u32[]) collective-permute-start(f32[8,4] %y), source_target_pairs={{0,1}}
  ROOT %t = (f32[4], f32[8]) all-reduce(f32[4] %a, f32[8] %b), to_apply=%add
"""
    out = hlo_collectives(hlo)
    assert [(op, b) for op, _, b in out] == [
        ("all-reduce", 16 * 28 * 64 * 2 * 4),    # operand NOT counted
        ("all-reduce", 32 * 28 * 64 * 2 * 4),    # -start: buffer only
        ("all-gather", 16 * 4 * 4),              # -start: produced buf
        ("collective-permute", 8 * 4 * 4),       # context u32[]s not
        ("all-reduce", 4 * 4 + 8 * 4),           # fused tuple: both
    ], out


def test_dp_collectives_in_compiled_program(mesh8):
    """Multi-chip claim strengthener (VERDICT r2 weak #7): lower the
    bench-shaped distributed training step over the 8-device mesh and
    assert the COMPILED program contains the expected collectives — the
    histogram psum (the reference's histred.Allreduce role) — and that
    an actual step executes with the bench depth/bins."""
    import jax
    import jax.numpy as jnp
    from xgboost_tpu.binning import bin_dense, compute_cuts
    from xgboost_tpu.config import TrainParam
    from xgboost_tpu.models.gbtree import make_grow_config
    from xgboost_tpu.models.tree import grow_tree
    from xgboost_tpu.parallel.dp import grow_tree_dp, shard_rows

    rng = np.random.RandomState(0)
    N, F = 80_000, 28  # bench feature count; rows scaled for CPU CI
    X = rng.rand(N, F).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    cuts = compute_cuts(xgb.DMatrix(X, label=y), max_bin=64)
    cfg = make_grow_config(TrainParam(max_depth=6, eta=0.1, max_bin=64),
                           cuts.max_bin)
    gh = np.stack([0.5 - y, np.full(N, 0.25)], 1).astype(np.float32)

    mesh = mesh8
    args = (jax.random.PRNGKey(0),
            shard_rows(mesh, jnp.asarray(bin_dense(X, cuts))),
            shard_rows(mesh, jnp.asarray(gh)),
            jnp.asarray(cuts.cut_values), jnp.asarray(cuts.n_cuts),
            shard_rows(mesh, jnp.ones(N, bool)))

    fn = jax.jit(lambda k, b, g, cv, nc, rv: grow_tree_dp(
        mesh, k, b, g, cv, nc, cfg, rv))
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    n_allreduce = hlo.count("all-reduce")
    # one histogram psum per non-terminal level (depths 0..5); terminal
    # node stats DERIVE from the split's child sums (no collective)
    assert n_allreduce >= 6, n_allreduce
    # the deepest histogram psum (depth 5, 32 nodes x 28 features x
    # n_bin bins x 2) rides the wire — the reference's histred.Allreduce
    # payload shape (TStats x bins x features x nodes, SURVEY §5.8)
    B = cfg.n_bin
    assert f"f32[32,{F},{B},2]" in hlo, "deepest histogram psum missing"

    # collective PAYLOAD accounting (VERDICT r3 item 2): the bytes on
    # the wire per round must match the analytic model
    # (commcost.hist_psum_bytes = the reference's histred.Allreduce
    # payload role, updater_histmaker-inl.hpp:343-346) — a payload
    # regression (extra collectives, wider stats, un-derived terminal
    # node_stats) fails here
    from xgboost_tpu.parallel.commcost import (hist_psum_bytes,
                                               hlo_collectives)
    colls = hlo_collectives(hlo)
    model = hist_psum_bytes(cfg.max_depth, F, B)
    ar_bytes = sum(b for op, _, b in colls if op == "all-reduce")
    for d, expect in model.items():
        shape = f"f32[{1 << d},{F},{B},2]"
        level = [b for op, s, b in colls
                 if op == "all-reduce" and shape in s]
        assert level and level[0] == expect, (d, shape, level)
    total = sum(model.values())
    assert total <= ar_bytes <= int(total * 1.05), (
        f"all-reduce bytes {ar_bytes} vs model {total}: "
        f"unexpected collective payload\n{colls}")

    tree, row_leaf, deltas = fn(*args)
    assert np.asarray(tree.feature).shape[0] == 127
    assert np.isfinite(np.asarray(deltas)).all()

    # and the distributed step matches single-device growth exactly
    t1, _, _ = grow_tree(args[0], jnp.asarray(bin_dense(X, cuts)),
                         jnp.asarray(gh), args[3], args[4], cfg)
    for f in tree._fields:
        np.testing.assert_allclose(np.asarray(getattr(tree, f)),
                                   np.asarray(getattr(t1, f)),
                                   rtol=1e-5, atol=1e-6, err_msg=f)


def test_exact_colsplit_bit_matches_single_device():
    """dsplit=col + grow_colmaker: TRUE exact column split at any
    cardinality (round 5 — the DistColMaker analog,
    updater_distcol-inl.hpp:136-153 over colmaker's scan
    :362-414; previously capped at max_exact_bin=4096 quantized cuts
    with a warning).  Each shard runs the segment-sorted exact finder
    on its own raw columns; winners reduce by all-gather + argmax and
    rows route by owner-masked raw-value psum.  The grown model must
    BIT-match the single-device exact grower — dumps equal,
    predictions equal — on ~50k distinct values per feature, with and
    without missing values, and no cap warning may fire."""
    import contextlib
    import io

    rng = np.random.RandomState(5)
    N = 50_000
    for nan_frac in (0.0, 0.08):
        X = rng.randn(N, 5).astype(np.float32)  # ~N distinct per feature
        if nan_frac:
            X[rng.rand(N, 5) < nan_frac] = np.nan
        y = ((np.nan_to_num(X[:, 0]) > 0.3)
             ^ (np.nan_to_num(X[:, 1]) < -0.2)).astype(np.float32)

        def run(extra):
            d = xgb.DMatrix(X, label=y)
            p = dict({"objective": "binary:logistic", "max_depth": 4,
                      "eta": 0.5, "updater": "grow_colmaker,prune",
                      "silent": 1}, **extra)
            err = io.StringIO()
            with contextlib.redirect_stderr(err):
                bst = xgb.train(p, d, 2)
            return bst, d, err.getvalue()

        b1, d1, _ = run({})
        b2, d2, log2 = run({"dsplit": "col"})
        assert b2.gbtree.exact_raw
        assert "max_exact_bin" not in log2, log2  # no cap warning
        assert b1.get_dump() == b2.get_dump()
        np.testing.assert_array_equal(np.asarray(b1.predict(d1)),
                                      np.asarray(b2.predict(d2)))
