"""Updater tests: exact colmaker, prune, refresh, distcol (reference
updater registry src/tree/updater.cpp:18-31)."""

import os

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.models.updaters import parse_updaters, prune_tree


def make_data(n=2000, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = ((X[:, 0] > 0.5) ^ (X[:, 1] > 0.3)).astype(np.float32)
    return X, y


def test_parse_updaters_rejects_unknown():
    assert parse_updaters("grow_histmaker,prune") == ("grow_histmaker",
                                                     "prune")
    with pytest.raises(ValueError):
        parse_updaters("grow_gpu")


# ------------------------------------------------------------ exact greedy
def test_colmaker_exact_beats_coarse_hist():
    """With very coarse quantile bins a fine threshold is unfindable;
    exact enumeration of all distinct values must find it."""
    rng = np.random.RandomState(5)
    n = 3000
    X = np.round(rng.rand(n, 3), 3).astype(np.float32)
    y = (X[:, 0] > 0.777).astype(np.float32)
    params_exact = {"objective": "binary:logistic", "max_depth": 2,
                    "eta": 1.0, "updater": "grow_colmaker,prune"}
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train(params_exact, d, 2, verbose_eval=False)
    err = ((bst.predict(d) > 0.5) != (y > 0.5)).mean()
    assert err < 0.005
    # the exact split threshold is a distinct data value near 0.777
    dump = bst.get_dump()[0]
    first_cond = float(dump.split("<")[1].split("]")[0])
    assert abs(first_cond - 0.777) < 0.002


def test_colmaker_matches_histmaker_on_binary_features():
    """On 0/1 features, 256-bin histogram == exact enumeration: the two
    updaters must produce identical models."""
    rng = np.random.RandomState(6)
    X = (rng.rand(1000, 10) > 0.5).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0.5).astype(np.float32)
    p = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.7}
    d1, d2 = xgb.DMatrix(X, label=y), xgb.DMatrix(X, label=y)
    bst_h = xgb.train({**p, "updater": "grow_histmaker,prune"}, d1, 3,
                      verbose_eval=False)
    bst_c = xgb.train({**p, "updater": "grow_colmaker,prune"}, d2, 3,
                      verbose_eval=False)
    np.testing.assert_allclose(bst_h.predict(d1), bst_c.predict(d2),
                               rtol=1e-5)


# ------------------------------------------------------------------- prune
def test_prune_tree_removes_weak_leaf_pair():
    X, y = make_data()
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 5,
                     "eta": 0.5}, d, 1, verbose_eval=False)
    tree = bst.gbtree.trees[0]
    gains = np.asarray(tree.gain)
    pos_gains = gains[gains > 0]
    gamma = float(np.percentile(pos_gains, 60))
    pruned, resolve = prune_tree(tree, gamma)
    n_splits_before = int((np.asarray(tree.feature) >= 0).sum())
    n_splits_after = int((np.asarray(pruned.feature) >= 0).sum())
    assert n_splits_after < n_splits_before
    # surviving split nodes that kept both children as leaves have
    # gain >= gamma
    f = np.asarray(pruned.feature)
    il = np.asarray(pruned.is_leaf)
    g = np.asarray(pruned.gain)
    n = len(f)
    for nid in range(n):
        if f[nid] >= 0 and not il[nid]:
            l, r = 2 * nid + 1, 2 * nid + 2
            def leaflike(c):
                return c >= n or il[c] or f[c] < 0
            if leaflike(l) and leaflike(r):
                assert g[nid] >= gamma
    # resolve maps pruned descendants to their surviving ancestor
    for nid in range(1, n):
        assert il[resolve[nid]] or f[resolve[nid]] < 0 or resolve[nid] == nid


def test_gamma_post_prune_keeps_strong_grandchildren():
    """XOR data: the root split has ~zero gain but its children's splits
    are strong.  Post-pruning (reference semantics) must KEEP the tree;
    pre-pruning would collapse it to a stump."""
    rng = np.random.RandomState(7)
    X = (rng.rand(4000, 2) > 0.5).astype(np.float32)
    y = (X[:, 0] != X[:, 1]).astype(np.float32)  # pure XOR
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 2,
                     "eta": 1.0, "gamma": 0.5}, d, 1, verbose_eval=False)
    err = ((bst.predict(d) > 0.5) != (y > 0.5)).mean()
    assert err < 0.01  # gamma=0.5 did not destroy the XOR tree


def test_gamma_prunes_noise_splits():
    rng = np.random.RandomState(8)
    X = rng.rand(2000, 5).astype(np.float32)
    y = rng.rand(2000).astype(np.float32)  # pure noise
    d = xgb.DMatrix(X, label=y)
    bst_free = xgb.train({"objective": "reg:linear", "max_depth": 5,
                          "eta": 0.3}, d, 1, verbose_eval=False)
    d2 = xgb.DMatrix(X, label=y)
    bst_g = xgb.train({"objective": "reg:linear", "max_depth": 5,
                       "eta": 0.3, "gamma": 10.0}, d2, 1, verbose_eval=False)
    splits_free = int((np.asarray(bst_free.gbtree.trees[0].feature) >= 0).sum())
    splits_g = int((np.asarray(bst_g.gbtree.trees[0].feature) >= 0).sum())
    assert splits_g < splits_free


# ----------------------------------------------------------------- refresh
def test_refresh_recomputes_leaves_on_new_data():
    X1, y1 = make_data(seed=1)
    X2 = X1.copy()
    y2 = 1.0 - y1  # flipped labels: leaf values must flip sign
    d1 = xgb.DMatrix(X1, label=y1)
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.5}
    bst = xgb.train(params, d1, 3, verbose_eval=False)
    structure_before = [np.asarray(t.feature).copy()
                        for t in bst.gbtree.trees]
    acc_before = (((bst.predict(xgb.DMatrix(X2)) > 0.5) == (y2 > 0.5))
                  .mean())

    d2 = xgb.DMatrix(X2, label=y2)
    bst.set_param("updater", "refresh")
    # each refresh is one damped Newton replacement of all leaf values at
    # the current margin (all trees share one gradient snapshot, like the
    # reference); iterate to converge on the flipped labels
    for i in range(8):
        bst.update(d2, i)
    # structure unchanged
    for t, f_before in zip(bst.gbtree.trees, structure_before):
        np.testing.assert_array_equal(np.asarray(t.feature), f_before)
    acc_after = ((bst.predict(xgb.DMatrix(X2)) > 0.5) == (y2 > 0.5)).mean()
    assert acc_before < 0.5 and acc_after > 0.85


def test_refresh_stats_are_exact():
    """Refreshed node stats must equal the data's gradient statistics at
    the pre-refresh margin: root sum_hess == sum of p(1-p), and each
    refreshed root-level leaf weight follows -G/(H+lambda) * eta."""
    X, y = make_data(seed=2)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "eta": 0.5}, d, 2, verbose_eval=False)
    p = bst.predict(xgb.DMatrix(X))  # pre-refresh probabilities
    bst.set_param("updater", "refresh")
    bst.update(d, 0)
    expected_hess = float(np.sum(p * (1.0 - p)))
    for t in bst.gbtree.trees:
        root_hess = float(np.asarray(t.sum_hess)[0])
        np.testing.assert_allclose(root_hess, expected_hess, rtol=1e-4)
    # root would-be leaf weight: -G/(H+lambda) * eta with G = sum(p - y)
    G = float(np.sum(p - y))
    w = -G / (expected_hess + 1.0) * 0.5
    np.testing.assert_allclose(
        float(np.asarray(bst.gbtree.trees[0].leaf_value)[0]), w, rtol=1e-4)


def test_skmaker_trains_and_differs_from_histmaker():
    """grow_skmaker (models/skmaker.py): per-node 3-way sketch split
    selection — must train to a good model (lossier than histograms is
    acceptable, reference skmaker is approximate by design) and must
    actually use the sketch finder (distinct trees from histmaker with
    a coarse sketch; guards against silently falling through to the
    histogram path)."""
    import xgboost_tpu as xgb

    rng = np.random.RandomState(0)
    X = rng.rand(3000, 8).astype(np.float32)
    y = ((X[:, 0] > 0.5) ^ (X[:, 1] > 0.3)).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.5,
              "sketch_eps": 0.1}
    res = {}
    bst = xgb.train({**params, "updater": "grow_skmaker,refresh"},
                    xgb.DMatrix(X, label=y), 8,
                    evals=[(xgb.DMatrix(X, label=y), "train")],
                    evals_result=res, verbose_eval=False)
    # sketch_eps=0.1 coarsens both binning (~20 cuts) and candidates
    assert float(res["train-error"][-1]) < 0.08
    assert bst.gbtree._split_finder() is not None  # sketch finder active
    state = bst.gbtree.get_state()
    feats = state["tree_feature"]
    assert (feats >= -1).all() and (feats < 8).all()

    bst_h = xgb.train(params, xgb.DMatrix(X, label=y), 8,
                      verbose_eval=False)
    state_h = bst_h.gbtree.get_state()
    assert bst_h.gbtree._split_finder() is None
    # with a 20-candidate sketch vs 67-bin histograms, at least one
    # split decision must differ somewhere in the ensemble
    assert not (np.array_equal(state["tree_cut_index"],
                               state_h["tree_cut_index"])
                and np.array_equal(state["tree_feature"],
                                   state_h["tree_feature"]))


def test_skmaker_coarse_sketch_still_learns():
    """With a very coarse sketch (few candidate cuts) skmaker still
    finds usable splits — the candidate set shrinks, accuracy degrades
    gracefully."""
    import xgboost_tpu as xgb

    rng = np.random.RandomState(1)
    X = rng.rand(2000, 5).astype(np.float32)
    y = (X[:, 2] > 0.6).astype(np.float32)
    res = {}
    xgb.train({"objective": "binary:logistic", "max_depth": 3, "eta": 1.0,
               "updater": "grow_skmaker", "sketch_eps": 0.25},
              xgb.DMatrix(X, label=y), 5,
              evals=[(xgb.DMatrix(X, label=y), "train")],
              evals_result=res, verbose_eval=False)
    assert float(res["train-error"][-1]) < 0.1


def test_multi_root_trees_route_by_root_index():
    """Multi-root trees (reference TreeParam num_roots + BoosterInfo
    root_index, data.h:39-58, model.h:534-543): rows enter the tree at
    their per-row root; each root subtree learns its own regime."""
    import xgboost_tpu as xgb

    rng = np.random.RandomState(7)
    n = 2000
    X = rng.rand(n, 3).astype(np.float32)
    regime = (rng.rand(n) > 0.5).astype(np.uint32)
    # opposite relationships per regime: a single shallow tree cannot
    # capture both, two roots trivially can
    y = np.where(regime == 0, X[:, 0] > 0.5, X[:, 0] <= 0.5).astype(
        np.float32)

    d = xgb.DMatrix(X, label=y)
    d.set_uint_info("root_index", regime)
    params = {"objective": "binary:logistic", "max_depth": 2, "eta": 1.0,
              "num_roots": 2}
    res = {}
    bst = xgb.train(params, d, 3, evals=[(d, "train")], evals_result=res,
                    verbose_eval=False)
    assert res["train-error"][-1] < 0.02, res

    # root routing matters: same features, different root, different leaf
    d2 = xgb.DMatrix(X, label=y)
    d2.set_uint_info("root_index", 1 - regime)  # flip every row's root
    p_flip = bst.predict(d2)
    p_orig = bst.predict(xgb.DMatrix(X, label=y))  # no root -> root 0
    assert float(np.mean((p_flip > 0.5) == y)) < 0.2  # flipped = wrong

    # save/load keeps the multi-root layout working
    import tempfile, os
    fd, path = tempfile.mkstemp(suffix=".model")
    os.close(fd)
    try:
        bst.save_model(path)
        bst2 = xgb.Booster(model_file=path)
        d3 = xgb.DMatrix(X, label=y)
        d3.set_uint_info("root_index", regime)
        p2 = bst2.predict(d3)
        assert float(np.mean((p2 > 0.5) != y)) < 0.02
    finally:
        os.remove(path)

    # dump shows each root's subtree
    dumps = bst.get_dump()
    assert dumps[0].count(":[") >= 2  # at least one split under each root


def test_exact_mode_presence_only():
    """Exact mode proposes missing-vs-present splits (the reference's
    end-of-scan candidates) — the ONLY split on presence-only one-hot
    columns."""
    import xgboost_tpu as xgb

    rng = np.random.RandomState(0)
    n = 2000
    present = rng.rand(n) < 0.5
    X = np.full((n, 2), np.nan, np.float32)
    X[present, 0] = 1.0
    X[:, 1] = rng.rand(n)
    y = present.astype(np.float32)
    r = {}
    xgb.train({"objective": "binary:logistic", "max_depth": 2, "eta": 1.0,
               "updater": "grow_colmaker,prune"},
              xgb.DMatrix(X, label=y), 3,
              evals=[(xgb.DMatrix(X, label=y), "train")], evals_result=r,
              verbose_eval=False)
    assert r["train-error"][-1] < 0.01, r


AGARICUS_TRAIN = "/root/reference/demo/data/agaricus.txt.train"
AGARICUS_TEST = "/root/reference/demo/data/agaricus.txt.test"


@pytest.mark.skipif(not os.path.exists(AGARICUS_TRAIN),
                    reason="the reference's demo data is not here")
def test_exact_mode_agaricus_canonical():
    """Exact mode reproduces the reference's canonical exact-greedy
    agaricus numbers."""
    import xgboost_tpu as xgb

    dtrain = xgb.DMatrix(AGARICUS_TRAIN)
    dtest = xgb.DMatrix(AGARICUS_TEST,
                        num_col=dtrain.num_col)
    r = {}
    xgb.train({"objective": "binary:logistic", "max_depth": 3, "eta": 1.0,
               "updater": "grow_colmaker,prune"}, dtrain, 2,
              evals=[(dtrain, "train"), (dtest, "test")], evals_result=r,
              verbose_eval=False)
    # the reference CLI's exact-greedy numbers for this config
    assert r["train-error"][0] == pytest.approx(0.014433, abs=2e-6)
    assert r["test-error"][0] == pytest.approx(0.016139, abs=2e-6)
    assert r["train-error"][1] == pytest.approx(0.001228, abs=2e-6)
    assert r["test-error"][1] == 0.0
