"""Segmented round fusion (Booster.update_many driver): K rounds per
dispatch must be BIT-identical to the per-round path — model bytes,
margins, and eval-line text — at every segment size, including sizes
that do not divide the round count, warm starts, and mid-segment
checkpoint resume."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import xgboost_tpu as xgb  # noqa: E402
from xgboost_tpu.learner import Booster  # noqa: E402


def make_data(n=1500, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = ((X[:, 0] + 0.3 * X[:, 1] > 0.6) ^ (X[:, 2] > 0.7)).astype(
        np.float32)
    return X, y


PARAMS = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.4}


def _run(params, n_rounds, k, evals_names=("eval", "train"),
         seed_data=0, init_model=None, n=1500):
    """Train with segment size ``k`` (0 = per-round baseline) and return
    (booster, eval_lines, dtrain)."""
    X, y = make_data(n=n, seed=seed_data)
    Xe, ye = make_data(n=500, seed=seed_data + 100)
    dtrain = xgb.DMatrix(X, label=y)
    deval = xgb.DMatrix(Xe, label=ye)
    named = {"train": dtrain, "eval": deval}
    evals = [(named[nm], nm) for nm in evals_names]
    bst = Booster(params, cache=[dtrain, deval], model_file=init_model)
    first = bst.gbtree.num_boosted_rounds if bst.gbtree is not None else 0
    lines = []
    bst.update_many(dtrain, first, n_rounds, evals=evals or None,
                    eval_callback=lambda i, msg: lines.append(msg),
                    rounds_per_dispatch=k)
    return bst, lines, dtrain


def _assert_bitwise_equal(ba, la, bb, lb, d):
    assert la == lb                       # eval-line TEXT, not approx
    np.testing.assert_array_equal(np.asarray(ba.predict(d)),
                                  np.asarray(bb.predict(d)))
    assert bytes(ba.save_raw()) == bytes(bb.save_raw())


@pytest.mark.parametrize("k", [1, 3, 4, 64])
def test_segmented_bit_parity_vs_per_round(k):
    """K ∈ {divides, does-not-divide, exceeds} 7 rounds: model bytes,
    margins and eval lines all byte-match the per-round baseline."""
    params = {**PARAMS, "eval_metric": "logloss"}
    b0, l0, d = _run(params, 7, 0)
    bk, lk, _ = _run(params, 7, k)
    assert len(lk) == 7 and lk[0].startswith("[0]")
    _assert_bitwise_equal(b0, l0, bk, lk, d)


def test_warm_start_subsample_bit_parity(tmp_path):
    """init_model continuation with subsampling: the fused path must
    replay the same fold_in(seed, iteration) keys from the warm-start
    offset, not restart the key schedule."""
    params = {**PARAMS, "subsample": 0.7, "colsample_bytree": 0.8,
              "seed": 11, "eval_metric": "error"}
    base, _, _ = _run(params, 3, 0)
    mf = str(tmp_path / "warm.model")
    base.save_model(mf)
    b0, l0, d = _run(params, 5, 0, init_model=mf)
    b4, l4, _ = _run(params, 5, 4, init_model=mf)
    assert l4[0].startswith("[3]") and l4[-1].startswith("[7]")
    _assert_bitwise_equal(b0, l0, b4, l4, d)


def test_checkpoint_resume_mid_segment(tmp_path):
    """Kill-at-a-segment-boundary resume: bytes captured by the
    segment_callback restore a booster that finishes bit-identical to
    the uninterrupted run (deterministic per-iteration seeding)."""
    X, y = make_data()
    params = {**PARAMS, "subsample": 0.8, "seed": 5}

    d_ref = xgb.DMatrix(X, label=y)
    ref = Booster(params, cache=[d_ref])
    ref.update_many(d_ref, 0, 10, rounds_per_dispatch=4)

    # interrupted run: segments of 4 -> boundaries after rounds 4, 8;
    # capture the ring write at round 8 and stop there (mid final
    # segment of the 10-round plan)
    snaps = {}
    d1 = xgb.DMatrix(X, label=y)
    b1 = Booster(params, cache=[d1])

    def seg_cb(last_i):
        snaps[last_i + 1] = bytes(b1.save_raw())

    b1.update_many(d1, 0, 8, segment_callback=seg_cb,
                   rounds_per_dispatch=4)
    assert sorted(snaps) == [4, 8]

    d2 = xgb.DMatrix(X, label=y)
    b2 = Booster(params, cache=[d2])
    b2.load_raw(snaps[8])
    assert b2.gbtree.num_boosted_rounds == 8
    b2.update_many(d2, 8, 2, rounds_per_dispatch=4)
    assert bytes(b2.save_raw()) == bytes(ref.save_raw())


def test_watchlist_metrics_multiclass_multi_metric():
    """Device-resident eval with several metrics and a train-as-eval
    slot: line text matches the per-round path character for character."""
    rng = np.random.RandomState(3)
    X = rng.rand(900, 6).astype(np.float32)
    y = (X[:, 0] * 3).astype(np.int32).clip(0, 2).astype(np.float32)
    params = {"objective": "multi:softprob", "num_class": 3,
              "max_depth": 3, "eta": 0.3,
              "eval_metric": ["merror", "mlogloss"]}
    d0 = xgb.DMatrix(X, label=y)
    b0 = Booster(params, cache=[d0])
    l0 = []
    b0.update_many(d0, 0, 5, evals=[(d0, "train")],
                   eval_callback=lambda i, m: l0.append(m),
                   rounds_per_dispatch=0)
    d3 = xgb.DMatrix(X, label=y)
    b3 = Booster(params, cache=[d3])
    l3 = []
    b3.update_many(d3, 0, 5, evals=[(d3, "train")],
                   eval_callback=lambda i, m: l3.append(m),
                   rounds_per_dispatch=3)
    assert l0 == l3
    assert "train-merror" in l3[0] and "train-mlogloss" in l3[0]
    assert bytes(b0.save_raw()) == bytes(b3.save_raw())


@pytest.mark.parametrize("keyword,plan", [(0, 0), (None, 8), (3, 3)])
def test_keyword_beats_parameter(keyword, plan):
    """K has two sources: ``update_many``'s keyword, else the train
    parameter.  The keyword wins (0 = the per-round A/B switch); left
    out, the parameter decides; the plan is reported once."""
    X, y = make_data(n=400)
    d = xgb.DMatrix(X, label=y)
    bst = Booster({**PARAMS, "rounds_per_dispatch": 8}, cache=[d])
    plans = []
    bst.update_many(d, 0, 3, plan_callback=plans.append,
                    rounds_per_dispatch=keyword)
    assert plans == [plan]
    assert bst.gbtree.num_trees == 3


@pytest.mark.parametrize("rows,k", [
    (400, 64), (63_952, 64), (63_953, 63), (100_000, 41), (1_000_000, 5),
    (2_014_516, 3), (2_014_517, 2), (4_029_033, 2), (4_029_034, 1),
    (8_400_000, 1), (40_000_000, 1)])
def test_auto_k_by_rows(rows, k):
    """rounds_per_dispatch=-1 (the default) is
    clamp(ceil(AUTO_DISPATCH_ROWS / rows), 1, 64): the values the
    retired round-model file gave, at each boundary and at the
    benchmark cells' row counts.  No data of that size: the resolver
    reads a row count."""
    bst = Booster(PARAMS)
    assert bst.param.rounds_per_dispatch == -1
    assert bst._resolve_rounds_per_dispatch(rows) == k


@pytest.mark.parametrize("rows,call,plan,dispatches", [
    (400_000, 10, 11, 1), (400_000, 25, 11, 3), (8_400_000, 10, 1, 10)])
def test_auto_k_at_a_cell_s_rows_is_one_dispatch_and_the_same_trees(
        monkeypatch, rows, call, plan, dispatches):
    """A call of 10 rounds on 400,000 rows is ONE dispatch of a ten-round
    scan (auto-K = ceil(4,029,033 / 400,000) = 11), where 8.4M rows take
    ten; the trees, margins and eval lines are those of K = 1, byte for
    byte, at a depth whose levels pass 32 nodes.  The data is 1,500
    rows: the resolver is given the stated row count."""
    from xgboost_tpu.obs import span_totals, training_metrics
    params = {**PARAMS, "max_depth": 7, "eval_metric": "logloss"}
    assert Booster(params)._resolve_rounds_per_dispatch(rows) == plan
    resolve = Booster._resolve_rounds_per_dispatch
    monkeypatch.setattr(
        Booster, "_resolve_rounds_per_dispatch",
        lambda self, n_rows, override=None: resolve(
            self, rows if override is None else n_rows, override))
    b1, l1, d = _run(params, call, 1)
    before = span_totals().count.values().get("train.dispatch", 0)
    bk, lk, _ = _run(params, call, None)
    assert (span_totals().count.values()["train.dispatch"] - before
            == dispatches)
    assert training_metrics().rounds_per_dispatch.value == min(
        plan, call - (dispatches - 1) * plan)
    assert len(lk) == call
    _assert_bitwise_equal(b1, l1, bk, lk, d)


def test_auto_plan_reported_once():
    X, y = make_data(n=400)
    d = xgb.DMatrix(X, label=y)
    bst = Booster(PARAMS, cache=[d])
    plans = []
    bst.update_many(d, 0, 2, plan_callback=plans.append)
    assert plans == [64]
    assert bst.gbtree.num_trees == 2


def _block(bst, d, reason, monkeypatch):
    """Put ``bst`` into the state that raises ``reason`` alone (rows
    that are parameters are set by the caller)."""
    bst._lazy_init(d)
    if reason == "col_split":
        bst._col_mesh = object()
    elif reason == "seq_boost_env":
        monkeypatch.setenv("XGBTPU_SEQ_BOOST", "1")
    elif reason == "exact":
        bst.gbtree.exact_raw = True
    elif reason == "no_fused_grad":
        monkeypatch.setattr(bst.obj, "fused_grad",
                            lambda info=None, **kw: None)


@pytest.mark.parametrize("reason,extra", [
    ("external_train", {}), ("col_split", {}), ("seq_boost_env", {}),
    ("profiler", {"profile": 1}), ("prune", {"gamma": 0.5}),
    ("multi_root", {"num_roots": 2}), ("exact", {}),
    ("refresh", {"updater": "grow_histmaker,refresh"}),
    ("no_grow_updater", {"updater": "prune"}), ("no_fused_grad", {})])
def test_solo_and_lane_decline_for_the_same_reason(reason, extra,
                                                   monkeypatch, tmp_path):
    """The ten eligibility rows a solo run and a gang lane share: the
    lane is declined with, and the solo run's fallback counter gains,
    the same first reason.  The per-round fallback itself is stubbed:
    the decision is under test, not the training it routes to."""
    from xgboost_tpu.obs import training_metrics
    monkeypatch.delenv("XGBTPU_SEQ_BOOST", raising=False)
    X, y = make_data(n=300)
    if reason == "external_train":
        from xgboost_tpu.external import ExtMemDMatrix
        monkeypatch.setenv("XGTPU_EXT_DEVICE_CACHE_MB", "0")  # paged
        d = ExtMemDMatrix(iter([(X, y)]), cache=str(tmp_path / "c"),
                          page_rows=128)
    else:
        d = xgb.DMatrix(X, label=y)
    bst = Booster({**PARAMS, **extra}, cache=[d])
    _block(bst, d, reason, monkeypatch)
    assert bst.fused_lane_spec(d, 0, 3) == (None, reason)
    monkeypatch.setattr(Booster, "update", lambda self, *a, **k: None)
    fb = training_metrics().fused_fallback
    before = dict(fb.values())
    plans = []
    bst.update_many(d, 0, 3, plan_callback=plans.append)
    grew = {r: v - before.get(r, 0) for r, v in fb.values().items()
            if v != before.get(r, 0)}
    assert grew == {reason: 1} and plans == [0]


def test_zero_round_call_builds_the_watchlist_entries():
    """``update_many(d, 0, 0, evals=...)`` is how a caller asks for the
    device entries of the training set AND of every watchlist member
    (benchmark/run.py ends its ingest stopwatch on it): both are in
    ``Booster._cache`` afterwards, binned, and no tree was grown."""
    X, y = make_data(n=400)
    Xh, yh = make_data(n=200, seed=9)
    d, held = xgb.DMatrix(X, label=y), xgb.DMatrix(Xh, label=yh)
    bst = Booster(PARAMS, cache=[d])
    assert id(held) not in bst._cache
    bst.update_many(d, 0, 0, evals=[(held, "t")])
    assert id(d) in bst._cache and id(held) in bst._cache
    assert bst._cache[id(held)].binned.shape == (200, 8)
    assert bst.gbtree.num_trees == 0


def test_base_margin_outlives_the_donated_dispatch():
    """The fused scan donates the entry's margin; the entry's BASE
    margin has to be another buffer, since an ntree_limit prediction on
    the cached training matrix (and any margin rebuild) reads it after
    training.  (They were one array until PR 33: deleted by the first
    dispatch on a backend that honours donation.)"""
    X, y = make_data(n=400)
    d = xgb.DMatrix(X, label=y)
    bst = Booster(PARAMS, cache=[d])
    bst.update_many(d, 0, 4, rounds_per_dispatch=2)
    assert not bst._cache[id(d)].base.is_deleted()
    np.testing.assert_array_equal(
        np.asarray(bst.predict(d, ntree_limit=1)),
        np.asarray(bst.predict(xgb.DMatrix(X), ntree_limit=1)))


def test_segment_compile_budget(recompile_guard):
    """The fused scan compiles once per DISTINCT segment length and its
    statics are instance-independent: a second 10-round K=3 run (segment
    lengths {3, 1}, eval included) with a FRESH booster and fresh
    matrices compiles zero XLA programs.  (Tree-count-dependent host
    stack concatenates — shared with the per-round path — are the only
    shape-varying programs, so the round count must match across the
    warm and guarded runs.)"""
    params = {**PARAMS, "eval_metric": "logloss"}
    _run(params, 10, 3)         # warm: segment lengths {3, 1} + eval
    with recompile_guard.expect(0):
        _run(params, 10, 3)     # fresh booster, same shapes -> no XLA
