"""The Epsilon-shape configuration of the benchmark (ISSUE 36), held on
the CPU at test sizes: the shipped files, the generator at 2,000
columns, the program's cuts and bin ids over 33 feature tiles' worth of
columns against the benchmark's plain reference, and one rehearsal of
the whole run on the tiny cell beside the benchmark's test cells:
depth 8, ten rounds to a call, one dispatch."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELLS = os.path.join(BENCH, "tests", "cells")
sys.path.insert(0, BENCH)

import reference as ref  # noqa: E402
import run  # noqa: E402
from datagen import synth_tabular  # noqa: E402

import xgboost_tpu as xgb  # noqa: E402
from xgboost_tpu.binning import bin_matrix, compute_cuts  # noqa: E402

CELL, CONFIG = "epsilon-shape-synth.train_logloss", "epsilon-shape-synth-d8-b256"
TINY, TINY_CONFIG = "tiny-epsilon.train_logloss", "tiny-epsilon-shape"
NEW_METRICS = {"hist_feature_tiles": "hist_feature_tiles",
               "hist_node_tiles": "hist_node_tiles",
               "hist_derived_levels": "hist_derived_levels",
               "deep_hist_ms_per_round": "deep.hist",
               "deep_route_ms_per_round": "deep.route",
               "deep_split_ms_per_round": "deep.split"}
COMPARED = ("cuts_maxdiff", "bins_mismatch", "loss_r0", "loss_r1", "loss_r2",
            "cover_nodes", "grad_nodes", "dmargin_train", "dmargin_held",
            "eval_vs_trees")


def test_shipped_files_name_each_other_and_nothing_is_cut():
    bench, entry, cell, cfg = run.find_cell(CELL)
    assert entry["config"] == cfg["name"] == CONFIG and entry["chips"] == 1
    listed = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert listed["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert listed["reduced"] == cfg["reduced"] == [] and cfg["reduced_why"]
    pub = cfg["published"]
    assert (cfg["rows"], cfg["n_train"], cfg["n_held"], cfg["features"]) == (
        pub["rows"], pub["n_train"], pub["n_held"], pub["features"]) == (
        500_000, 400_000, 100_000, 2000)
    assert cfg["params"]["max_depth"] == pub["max_depth"] == 8
    assert cfg["params"]["max_bin"] == pub["max_bin"] == 256
    assert cfg["params"]["eta"] == pub["eta"] == 0.1
    assert cfg["params"]["lambda"] == pub["lambda"] == 1.0
    assert cfg["params"]["hist_precision"] == "auto"
    assert "rounds_per_dispatch" not in cfg["params"]       # left at auto
    assert cfg["generator"] == "synth_tabular" and cfg["bin_align"] == 32
    assert cell["job"] == {"rounds_per_call": 10, "watchlist_name": "test",
                           "params": {"eval_metric": "logloss"}}
    assert cell["limits"]["cuts_maxdiff"] == cell["limits"]["bins_mismatch"] == 0
    assert set(cell["limits"]) <= set(COMPARED)
    # the parameters are the other two cells', but for the depth
    _, _, _, higgs = run.find_cell("higgs-shape-synth.train_logloss")
    assert {**higgs["params"], "max_depth": 8} == cfg["params"]


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metrics_use_the_readers_the_benchmark_has(metric):
    bench = run.load_json(REPO, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    spec = run.load_json(BENCH, "metrics", f"{metric}.json")
    everywhere = metric.startswith("hist_")
    assert entry["workloads"] == (
        [w["name"] for w in bench["workloads"]] if everywhere else [CELL])
    assert spec["reader"] == ("program_gauge" if everywhere
                              else "trace_scope_time")
    assert NEW_METRICS[metric] in (spec["args"].get("gauge"),
                                   spec["args"].get("scope"))
    assert (spec["unit"], spec["layer"], spec["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])


def test_every_metric_listed_for_the_cell_has_its_files():
    bench = run.load_json(REPO, "BENCHMARK.json")
    mine = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    assert len(mine) == 33 and "widen_ms_per_round" not in mine
    for name in mine:
        spec = run.load_json(BENCH, "metrics", f"{name}.json")
        assert os.path.exists(os.path.join(
            BENCH, "readers", f"{spec['reader']}.py")), name


def test_new_gauges_are_read_or_nothing_is():
    from readers import program_gauge
    from xgboost_tpu.obs import training_metrics
    training_metrics().hist_feature_tiles.set(250.0)
    training_metrics().hist_node_tiles.set(8.0)
    training_metrics().hist_derived_levels.set(7.0)
    for name, want in (("hist_feature_tiles", 250.0), ("hist_node_tiles", 8.0),
                       ("hist_derived_levels", 7.0)):
        args = run.load_json(BENCH, "metrics", f"{name}.json")["args"]
        assert program_gauge.read({}, **args) == want


def test_generator_at_2000_columns_is_a_function_of_the_seed_alone():
    _, _, _, cfg = run.find_cell(CELL)
    args = cfg["generator_args"]
    a = synth_tabular.generate(3600003601, 1500, 500, 2000, **args)
    b = synth_tabular.generate(3600003601, 1500, 500, 2000, **args)
    c = synth_tabular.generate(3600003602, 1500, 500, 2000, **args)
    for k, v in a.items():
        assert v.dtype == np.float32 and v.flags.c_contiguous, k
        assert np.array_equal(v, b[k]) and v.shape == c[k].shape, k
    assert a["X_train"].shape == (1500, 2000) and a["X_held"].shape == (500, 2000)
    assert not np.array_equal(a["X_train"], c["X_train"])
    assert 0.4 < a["y_train"].mean() < 0.6
    # the first n_informative columns carry the signal, the rest none
    corr = np.abs(np.corrcoef(a["X_train"].T, a["y_train"])[-1, :-1])
    k = args["n_informative"]
    assert corr[:k].mean() > 2 * corr[k:].mean()


def test_cuts_and_bins_over_33_feature_tiles_equal_the_reference():
    """70,000 x 264 continuous columns (past the sketch's 65,536 rows,
    so columns go through the pooled sketch in groups of several): the
    program's cuts and bin ids are the reference's, cell for cell."""
    X = synth_tabular.generate(3600003603, 70_000, 0, 264,
                               task="classification")["X_train"]
    want = ref.propose_cuts(X, max_bin=256, sketch_eps=1 / 256,
                            sketch_ratio=2.0, bin_align=32)
    dtrain = xgb.DMatrix(X)
    cuts = compute_cuts(dtrain, max_bin=256, sketch_eps=1 / 256,
                        sketch_ratio=2.0, bin_align=32)
    assert cuts.max_bin == 256
    for f, w in enumerate(want):
        assert np.array_equal(cuts.cut_values[f, :cuts.n_cuts[f]], w), f
    assert np.array_equal(bin_matrix(dtrain, cuts), ref.bin_ids(X, want))


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """run.py's phases on the CPU over the tiny cell beside the
    benchmark's test cells: 4,096 + 1,024 x 264, 32 bins, depth 8, ten
    rounds to a call.  It writes no device metric."""
    root = tmp_path_factory.mktemp("epsilon_root")
    (root / "BENCHMARK.json").write_text(json.dumps(
        {"workloads": [{"name": TINY, "config": TINY_CONFIG}]}))
    args = run.parse(["--workload", TINY, "--seed", "3600003604",
                      "--seconds", "0.5"])
    from xgboost_tpu.obs import span_totals, training_metrics
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("XGBTPU_NO_JITCACHE", "1")        # no cache from a test
        before = run.program_failures()
        calls0 = span_totals().count.values().get("train.dispatch", 0)
        result = run.run_cell(args, rehearse=True, bench_dir=CELLS,
                              root=str(root))
        calls = span_totals().count.values()["train.dispatch"] - calls0
    return result, before, calls, training_metrics().rounds_per_dispatch.value


def test_rehearsal_runs_ten_rounds_to_a_dispatch(rehearsal):
    result, before, calls, per_dispatch = rehearsal
    assert result["failed"] == before and result["correct"] == (before == 0)
    assert result["metrics"] == {} and result["attempted"] >= 10
    assert result["attempted"] % 10 == 0
    assert result["notes"]["rounds_per_call"] == 10 and per_dispatch == 10
    assert calls == 1 + result["attempted"] // 10   # one dispatch a call
    assert result["counts"]["compiles_in_window"] == 0
    assert len(result["compared"]) == 10


@pytest.mark.parametrize("number", COMPARED)
def test_rehearsal_holds_each_compared_number_under_its_limit(rehearsal,
                                                              number):
    held = rehearsal[0]["compared"][number]
    assert held["value"] <= held["limit"], held
    if number in ("cuts_maxdiff", "bins_mismatch"):
        assert held["value"] == 0
    cell = run.load_json(CELLS, "workloads", f"{TINY}.json")
    assert any(number in k for k in cell["limits_why"]), number
