"""Reference binary model format: reader + cross-check fixtures.

The committed fixtures in ``tests/data/`` were produced by the reference
CLI built from ``/root/reference`` (demo/binary_classification
mushroom.conf, 2 rounds, depth 3):

- ``ref_agaricus.model``  — ``binf`` binary model (with pred buffer)
- ``ref_agaricus.bs64``   — the same model in base64 text mode
  (``model_out=stdout``)
- ``ref_agaricus.pred``   — the reference CLI's own predictions on
  agaricus.txt.test (``%g`` precision)

so the round-trip bar is: load the reference's bytes, predict, match the
reference's numbers (SURVEY.md M2).
"""

import os

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.compat import load_reference_model, parse_reference_model

DATA = os.path.join(os.path.dirname(__file__), "data")
AGARICUS_TEST = "/root/reference/demo/data/agaricus.txt.test"
AGARICUS_TRAIN = "/root/reference/demo/data/agaricus.txt.train"
needs_agaricus = pytest.mark.skipif(
    not os.path.exists(AGARICUS_TRAIN),
    reason="the reference's demo data is not on this container")


@pytest.fixture(scope="module")
def ref_model_path():
    return os.path.join(DATA, "ref_agaricus.model")


def test_parse_reference_model(ref_model_path):
    with open(ref_model_path, "rb") as f:
        parsed = parse_reference_model(f.read())
    assert parsed["objective"] == "binary:logistic"
    assert parsed["gbm"] == "gbtree"
    assert parsed["num_feature"] == 126
    assert len(parsed["trees"]) == 2
    assert list(parsed["tree_info"]) == [0, 0]
    nodes, stats = parsed["trees"][0]
    assert (nodes["cleft"] == -1).sum() > 0          # has leaves
    assert (stats["sum_hess"] > 0).all()


@needs_agaricus
def test_reference_model_predictions_match(ref_model_path):
    """Predictions from the loaded reference model must equal the
    reference CLI's own pred output."""
    bst = load_reference_model(ref_model_path)
    dtest = xgb.DMatrix(AGARICUS_TEST, num_col=126)
    preds = bst.predict(dtest)
    ref = np.loadtxt(os.path.join(DATA, "ref_agaricus.pred"))
    assert preds.shape == ref.shape
    np.testing.assert_allclose(preds, ref, rtol=1e-4, atol=1e-5)


@needs_agaricus
def test_reference_bs64_matches_binf():
    b1 = load_reference_model(os.path.join(DATA, "ref_agaricus.model"))
    b2 = load_reference_model(os.path.join(DATA, "ref_agaricus.bs64"))
    dtest = xgb.DMatrix(AGARICUS_TEST, num_col=126)
    np.testing.assert_array_equal(b1.predict(dtest), b2.predict(dtest))


@needs_agaricus
def test_booster_load_model_autodetects_reference(ref_model_path):
    """Booster(model_file=...) must transparently read reference files."""
    bst = xgb.Booster(model_file=ref_model_path)
    dtest = xgb.DMatrix(AGARICUS_TEST, num_col=126)
    ref = np.loadtxt(os.path.join(DATA, "ref_agaricus.pred"))
    np.testing.assert_allclose(bst.predict(dtest), ref, rtol=1e-4, atol=1e-5)


@needs_agaricus
def test_save_base64_roundtrip(tmp_path):
    """Our own bs64 text-safe mode: save -> load -> bit-identical preds,
    and the file must be single-line printable text after the magic."""
    dtrain = xgb.DMatrix(AGARICUS_TRAIN)
    bst = xgb.train({"eta": 1.0, "max_depth": 3,
                     "objective": "binary:logistic"}, dtrain, 2,
                    verbose_eval=False)
    p = str(tmp_path / "m.bs64")
    bst.save_model(p, save_base64=True)
    with open(p, "rb") as f:
        raw = f.read()
    assert raw[:5] == b"bs64\t"
    body = raw[5:].rstrip(b"\n")
    assert all(32 <= c < 127 for c in body)  # survives text channels
    bst2 = xgb.Booster(model_file=p)
    dtest = xgb.DMatrix(AGARICUS_TEST, num_col=dtrain.num_col)
    np.testing.assert_array_equal(bst.predict(dtest), bst2.predict(dtest))


@needs_agaricus
def test_cli_save_base64(tmp_path):
    """CLI save_base64=1 writes the text-safe encoding."""
    from xgboost_tpu.cli import BoostLearnTask
    model = str(tmp_path / "cli.bs64")
    rc = BoostLearnTask().run([
        f"data={AGARICUS_TRAIN}", "num_round=1", "max_depth=3",
        "objective=binary:logistic", "silent=2", "save_base64=1",
        f"model_out={model}"])
    assert rc == 0
    with open(model, "rb") as f:
        assert f.read(5) == b"bs64\t"
    bst = xgb.Booster(model_file=model)
    assert bst.predict(xgb.DMatrix(AGARICUS_TEST, num_col=126)).shape == (1611,)


# ----------------------------------------------------------------- writer

@needs_agaricus
def test_reference_writer_self_roundtrip(tmp_path):
    """save_reference_model -> our own reference reader reproduces the
    predictions exactly (format-level self-consistency)."""
    from xgboost_tpu.compat import save_reference_model

    dtrain = xgb.DMatrix(AGARICUS_TRAIN)
    dtest = xgb.DMatrix(AGARICUS_TEST, num_col=dtrain.num_col)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "eta": 1.0}, dtrain, 2, verbose_eval=False)
    want = np.asarray(bst.predict(dtest))

    path = str(tmp_path / "ours.refmodel")
    raw = save_reference_model(bst, path)
    assert raw[:4] == b"binf"
    # loads through the generic loader (magic autodetect)
    b2 = xgb.Booster(model_file=path)
    got = np.asarray(b2.predict(xgb.DMatrix(AGARICUS_TEST,
                                            num_col=dtrain.num_col)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)

    # bs64 text mode round-trips too
    raw64 = save_reference_model(bst, str(tmp_path / "ours.bs64"),
                                 base64_mode=True)
    assert raw64.startswith(b"bs64\t")
    b3 = xgb.Booster(model_file=str(tmp_path / "ours.bs64"))
    got64 = np.asarray(b3.predict(xgb.DMatrix(AGARICUS_TEST,
                                              num_col=dtrain.num_col)))
    np.testing.assert_allclose(got64, want, rtol=1e-6, atol=1e-7)


def test_reference_writer_multiclass_and_linear(tmp_path):
    from xgboost_tpu.compat import save_reference_model

    rng = np.random.RandomState(0)
    X = rng.rand(300, 5).astype(np.float32)
    y = (X[:, 0] * 3).astype(int).clip(0, 2).astype(np.float32)
    bst = xgb.train({"objective": "multi:softmax", "num_class": 3,
                     "max_depth": 3, "eta": 0.5},
                    xgb.DMatrix(X, label=y), 3, verbose_eval=False)
    p = save_reference_model(bst, str(tmp_path / "mc.refmodel"))
    b2 = xgb.Booster(model_file=str(tmp_path / "mc.refmodel"))
    np.testing.assert_allclose(b2.predict(xgb.DMatrix(X)),
                               bst.predict(xgb.DMatrix(X)),
                               rtol=1e-6, atol=1e-7)
    # the prediction-buffer sections are num_pbuffer * num_output_group
    # entries EACH (gbtree-inl.hpp PredBufferSize) — a K=1-shaped counter
    # would make multiclass models unreadable by reference tooling
    from xgboost_tpu.compat import _GBTREE_PARAM, _LEARNER_PARAM
    raw = p[4:]  # skip binf
    off = _LEARNER_PARAM.size
    from xgboost_tpu.compat import _read_str
    _, off = _read_str(raw, off)
    _, off = _read_str(raw, off)
    _, _, _, npb, nog, _ = _GBTREE_PARAM.unpack_from(raw, off)
    assert npb == 300 and nog == 3
    assert raw.endswith(b"\x00" * (8 * 300 * 3))  # buffer + counter

    yl = (X[:, 0] > 0.5).astype(np.float32)
    bl = xgb.train({"booster": "gblinear", "objective": "binary:logistic",
                    "eta": 0.5}, xgb.DMatrix(X, label=yl), 4,
                   verbose_eval=False)
    save_reference_model(bl, str(tmp_path / "lin.refmodel"))
    b3 = xgb.Booster(model_file=str(tmp_path / "lin.refmodel"))
    np.testing.assert_allclose(b3.predict(xgb.DMatrix(X)),
                               bl.predict(xgb.DMatrix(X)),
                               rtol=1e-5, atol=1e-6)


@pytest.fixture(scope="module")
def reference_cli():
    """The reference C++ CLI, built from /root/reference (cached)."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        from tools.parity import build_reference
        return build_reference("/tmp/xgbtpu_parity")
    except Exception as e:  # no compiler / build breakage
        pytest.skip(f"reference binary unavailable: {e}")


def test_reference_cli_consumes_our_model(tmp_path, reference_cli):
    """THE round-trip (VERDICT r2 item 6): a model trained HERE, saved in
    the reference format, fed to the reference CLI ``task=pred`` —
    its predictions must match ours on agaricus."""
    import subprocess
    from xgboost_tpu.compat import save_reference_model

    dtrain = xgb.DMatrix(AGARICUS_TRAIN)
    dtest = xgb.DMatrix(AGARICUS_TEST, num_col=dtrain.num_col)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "eta": 1.0}, dtrain, 2, verbose_eval=False)
    ours = np.asarray(bst.predict(dtest))

    model = str(tmp_path / "ours.refmodel")
    save_reference_model(bst, model)
    conf = tmp_path / "pred.conf"
    conf.write_text("task = pred\n")
    pred_out = str(tmp_path / "pred.txt")
    r = subprocess.run(
        [reference_cli, str(conf), f"model_in={model}",
         f"test:data={AGARICUS_TEST}", f"name_pred={pred_out}",
         "use_buffer=0", "silent=1"],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    ref_pred = np.loadtxt(pred_out)
    assert ref_pred.shape == ours.shape
    # the reference prints %g (6 significant digits)
    np.testing.assert_allclose(ref_pred, ours, rtol=2e-5, atol=2e-6)

    # CONTINUED TRAINING with the default prediction buffer: the writer
    # bakes num_pbuffer = our cached rows + a zeroed buffer, matching
    # what the reference itself writes.  (Adding NEW eval sets at
    # continue time overflows num_pbuffer for reference-trained models
    # too — a brittleness of the format, verified, not of this writer.)
    tconf = tmp_path / "cont.conf"
    tconf.write_text("task = train\n")
    cont = str(tmp_path / "cont.model")
    r2 = subprocess.run(
        [reference_cli, str(tconf), f"data={AGARICUS_TRAIN}",
         "objective=binary:logistic", "max_depth=3", "eta=1.0",
         "num_round=1", f"model_in={model}", "silent=1",
         f"model_out={cont}"],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path))
    assert r2.returncode == 0, (r2.stdout + r2.stderr)[-2000:]
    # the continued model loads back here and extends the ensemble
    b3 = xgb.Booster(model_file=cont)
    assert b3.gbtree.num_trees == bst.gbtree.num_trees + 1


def test_exact_colmaker_matches_reference_splits(tmp_path, reference_cli):
    """TRUE exact mode (VERDICT r2 item 5): on a continuous dataset with
    ~50k distinct values per feature — far past the old 4096-bin cap —
    our grow_colmaker must match the reference CLI's exact greedy
    split-for-split (same features, same gains) and prediction-for-
    prediction."""
    import subprocess
    rng = np.random.RandomState(5)
    N = 50_000
    X = rng.randn(N, 3).astype(np.float32)  # ~N distinct values/feature
    y = ((X[:, 0] > 0.3) ^ (X[:, 1] < -0.2)).astype(np.float32)
    train = tmp_path / "exact.train"
    with open(train, "w") as f:
        for i in range(N):
            f.write(f"{y[i]:g} " + " ".join(
                f"{j}:{X[i, j]:.6f}" for j in range(3)) + "\n")

    conf = tmp_path / "t.conf"
    conf.write_text("task = train\n")
    ref_model = str(tmp_path / "ref.model")
    r = subprocess.run(
        [reference_cli, str(conf), f"data={train}",
         "objective=binary:logistic", "max_depth=3", "eta=0.5",
         "num_round=2", "use_buffer=0", "silent=1",
         f"model_out={ref_model}"],
        capture_output=True, text=True, timeout=600, cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]

    d = xgb.DMatrix(str(train))
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "eta": 0.5, "updater": "grow_colmaker,prune"},
                    d, 2, verbose_eval=False)
    assert bst.gbtree.exact_raw

    # the DISTRIBUTED exact path (dsplit=col, round 5) bit-matches the
    # single-device model, so the split-for-split check below covers it
    # transitively — asserted here against the same reference run
    d_col = xgb.DMatrix(str(train))
    bst_col = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                         "eta": 0.5, "updater": "grow_colmaker,prune",
                         "dsplit": "col"}, d_col, 2, verbose_eval=False)
    assert bst_col.gbtree.exact_raw
    assert bst_col.get_dump() == bst.get_dump()

    # split-for-split on SIGNAL nodes (gain > 20 on 50k rows): both
    # sides' float accumulation orders differ in the last bits, so
    # near-zero-gain noise nodes can legitimately tie-break apart
    parsed = parse_reference_model(open(ref_model, "rb").read())
    n_checked = 0
    for t, (nodes, stats) in enumerate(parsed["trees"]):
        ours = bst.gbtree.trees[t]
        of = np.asarray(ours.feature)
        og = np.asarray(ours.gain)
        frontier = [(0, 0)]  # (reference nid, our slot)
        while frontier:
            nid, slot = frontier.pop()
            if nodes["cleft"][nid] == -1 or stats["loss_chg"][nid] <= 20:
                continue
            rf = int(nodes["sindex"][nid] & 0x7FFFFFFF)
            assert of[slot] == rf, (t, slot, of[slot], rf)
            np.testing.assert_allclose(og[slot], stats["loss_chg"][nid],
                                       rtol=2e-3, atol=1e-3)
            n_checked += 1
            frontier.append((int(nodes["cleft"][nid]), 2 * slot + 1))
            frontier.append((int(nodes["cright"][nid]), 2 * slot + 2))
    assert n_checked >= 6, n_checked  # both trees' signal structure

    # prediction-for-prediction on the training data (noise-leaf drift
    # bounded by eta * small weights)
    ref_loaded = xgb.Booster(model_file=ref_model)
    p_ours = np.asarray(bst.predict(d))
    p_ref = np.asarray(ref_loaded.predict(d))
    assert float(np.abs(p_ours - p_ref).mean()) < 1e-3
    assert float(np.abs(p_ours - p_ref).max()) < 0.05
