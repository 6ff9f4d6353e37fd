"""GBM-perf's one-hot Airline job as a benchmark configuration (ISSUE
39), held on the CPU at test sizes: the shipped files, the generator's
one-hot groups, the program's cuts and bin ids of two-valued columns
against the benchmark's plain reference, a depth-10 tree (levels of 256
and 512 nodes, 1,024 leaves) against `reference.fit`, the scopes that
set the levels of 256 nodes and more apart, the bin-fill gauge, and one
rehearsal of the whole run on a tiny cell beside the benchmark's test
cells."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELLS = os.path.join(BENCH, "tests", "cells")
sys.path.insert(0, BENCH)

import compare  # noqa: E402
import reference as ref  # noqa: E402
import run  # noqa: E402
from datagen import airline_onehot  # noqa: E402

import xgboost_tpu as xgb  # noqa: E402
from xgboost_tpu.binning import bin_matrix, compute_cuts  # noqa: E402
from xgboost_tpu.obs import training_metrics  # noqa: E402

CELL, CONFIG = ("airline-onehot-synth.train_logloss",
                "airline-onehot-synth-d10-b256")
TINY, TINY_CONFIG = ("tiny-airline-onehot.train_logloss",
                     "tiny-airline-onehot")
NEW_METRICS = {"deeper_hist_ms_per_round": "deeper.hist",
               "deeper_split_ms_per_round": "deeper.split",
               "deeper_route_ms_per_round": "deeper.route",
               "hist_bin_fill_pct": "hist_bin_fill"}
COMPARED = ("cuts_maxdiff", "bins_mismatch", "loss_r0", "loss_r1", "loss_r2",
            "cover_nodes", "grad_nodes", "dmargin_train", "dmargin_held",
            "eval_vs_trees")
GROUPS = [(airline_onehot.OFFSET[g], n) for g, n in airline_onehot.GROUPS
          if n is not None]


def test_shipped_files_name_each_other_and_nothing_is_cut():
    bench, entry, cell, cfg = run.find_cell(CELL)
    assert entry["config"] == cfg["name"] == CONFIG and entry["chips"] == 1
    listed = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert listed["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert listed["reduced"] == cfg["reduced"] == [] and cfg["reduced_why"]
    pub = cfg["published"]
    assert (cfg["rows"], cfg["n_train"], cfg["n_held"], cfg["features"]) == (
        pub["rows"], pub["n_train"], pub["n_held"], pub["features"]) == (
        1_100_000, 1_000_000, 100_000, 695)
    assert cfg["params"]["max_depth"] == pub["max_depth"] == 10
    assert cfg["params"]["max_bin"] == pub["max_bin"] == 256
    assert cfg["params"]["eta"] == pub["eta"] == 0.1
    assert cfg["generator"] == "airline_onehot" and cfg["bin_align"] == 32
    assert "generator_args" not in cfg
    # the departure from the source is stated, with the cardinalities
    said = " ".join(cfg["assumed"])
    assert "MISSING" in said and "dense 0.0" in said
    for name, n in airline_onehot.GROUPS:
        if n is not None:
            assert f"{name} {n}" in said, name
    assert cell["job"] == {"rounds_per_call": 10, "watchlist_name": "test",
                           "params": {"eval_metric": "logloss"}}
    assert cell["limits"]["cuts_maxdiff"] == cell["limits"]["bins_mismatch"] == 0
    assert set(cell["limits"]) <= set(COMPARED)
    why = " ".join(cell["limits_why"])
    for k in cell["limits"]:
        assert k in why, k                  # every limit has its reason
    # the parameters are the other cells', but for the depth
    _, _, _, eps = run.find_cell("epsilon-shape-synth.train_logloss")
    assert {**eps["params"], "max_depth": 10} == cfg["params"]


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metrics_use_the_readers_the_benchmark_has(metric):
    bench = run.load_json(REPO, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    spec = run.load_json(BENCH, "metrics", f"{metric}.json")
    everywhere = metric == "hist_bin_fill_pct"
    assert entry["workloads"] == (
        [w["name"] for w in bench["workloads"]] if everywhere else [CELL])
    assert spec["reader"] == ("program_gauge" if everywhere
                              else "trace_scope_time")
    assert NEW_METRICS[metric] in (spec["args"].get("gauge"),
                                   spec["args"].get("scope"))
    assert (spec["unit"], spec["layer"], spec["moves"], spec["source"]) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])


def test_every_metric_but_widen_is_listed_for_the_cell_and_has_its_files():
    bench = run.load_json(REPO, "BENCHMARK.json")
    mine = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    assert len(mine) == len(bench["per_layer"]) - 1 == 40
    assert "widen_ms_per_round" not in mine
    assert bench["workloads"][-1]["name"] == CELL       # appended, last
    for name in mine:
        spec = run.load_json(BENCH, "metrics", f"{name}.json")
        assert os.path.exists(os.path.join(
            BENCH, "readers", f"{spec['reader']}.py")), name


def test_generator_is_a_function_of_the_seed_alone_one_1_per_group():
    a = airline_onehot.generate(3900000101, 3000, 500, 695)
    b = airline_onehot.generate(3900000101, 3000, 500, 695)
    c = airline_onehot.generate(3900000102, 3000, 500, 695)
    for k, v in a.items():
        assert v.dtype == np.float32 and v.flags.c_contiguous, k
        assert np.array_equal(v, b[k]) and v.shape == c[k].shape, k
    assert a["X_train"].shape == (3000, 695) and a["X_held"].shape == (500, 695)
    assert not np.array_equal(a["X_train"], c["X_train"])
    X = np.concatenate([a["X_train"], a["X_held"]])
    assert sum(n for _, n in GROUPS) == 693
    for off, n in GROUPS:
        block = X[:, off:off + n]
        assert set(np.unique(block)) == {0.0, 1.0}
        assert np.array_equal(block.sum(axis=1), np.ones(len(X))), off
    dep, dist = X[:, airline_onehot.OFFSET["DepTime"]], X[:, -1]
    assert np.all(np.mod(dep, 100) < 60) and dep.max() < 2400
    assert dist.min() >= 11 and dist.max() <= 4962
    assert 0.15 < a["y_train"].mean() < 0.23
    with pytest.raises(ValueError):
        airline_onehot.generate(1, 10, 10, 694)


def test_cuts_and_bins_of_two_valued_columns_equal_the_reference():
    """70,000 rows (past the sketch's 65,536, so the columns go through
    the pooled sketch in groups) x 695: the program's cuts and bin ids
    are the reference's, cell for cell; a two-valued column has the two
    cuts 0 and 1 (one where its code never came up), so 0.0 lands in
    bin 2 and 1.0 in bin 3."""
    X = airline_onehot.generate(3900000103, 70_000, 0, 695)["X_train"]
    want = ref.propose_cuts(X, max_bin=256, sketch_eps=1 / 256,
                            sketch_ratio=2.0, bin_align=32)
    dtrain = xgb.DMatrix(X)
    cuts = compute_cuts(dtrain, max_bin=256, sketch_eps=1 / 256,
                        sketch_ratio=2.0, bin_align=32)
    assert cuts.max_bin == 256
    for f, w in enumerate(want):
        assert np.array_equal(cuts.cut_values[f, :cuts.n_cuts[f]], w), f
    binned = bin_matrix(dtrain, cuts)
    assert np.array_equal(binned, ref.bin_ids(X, want))
    onehot = np.concatenate([np.arange(o, o + n) for o, n in GROUPS])
    seen = X[:, onehot].max(axis=0) > 0
    assert np.array_equal(cuts.n_cuts[onehot], np.where(seen, 2, 1))
    assert set(np.unique(binned[:, onehot])) == {2, 3}


def _onehot_case(n=20_000, seed=3900000104):
    """20,000 x 41: 38 one-hot columns in groups of 12 and 26, three
    numeric ones; the label leans on both kinds."""
    rng = np.random.default_rng(seed)
    X = np.zeros((n, 41), np.float32)
    a, b = rng.integers(0, 12, n), rng.zipf(1.3, n) % 26
    X[np.arange(n), a] = 1.0
    X[np.arange(n), 12 + b] = 1.0
    X[:, 38:] = rng.normal(size=(n, 3)).astype(np.float32)
    score = (rng.normal(size=12)[a] + rng.normal(size=26)[b] + X[:, 38]
             + X[:, 39] * X[:, 40] + rng.logistic(size=n))
    return X, (score > np.quantile(score, 0.8)).astype(np.float32)


def test_depth_10_trees_against_the_reference():
    """Three depth-10 trees (levels of 256 and 512 nodes, 1,024 leaves)
    through the public entry, against `reference.fit` on the same data:
    every compared number under the cell's limits; the trees reach the
    levels the cell is there for."""
    X, y = _onehot_case()
    n_train = 16_000
    data = {"X_train": X[:n_train], "y_train": y[:n_train],
            "X_held": X[n_train:], "y_held": y[n_train:]}
    _, _, cell, big = run.find_cell(CELL)
    cfg = {"params": {**big["params"], "max_bin": 32, "sketch_eps": 1 / 32,
                      "eval_metric": "logloss"}, "bin_align": 0}
    dtrain = xgb.DMatrix(data["X_train"], label=data["y_train"])
    dheld = xgb.DMatrix(data["X_held"], label=data["y_held"])
    bst = xgb.Booster(dict(cfg["params"]))
    lines = {}
    bst.update_many(dtrain, 0, 3, evals=[(dheld, "test")],
                    eval_callback=lines.__setitem__)
    out = run.program_outputs(bst, dtrain, lines, 41)
    assert int(np.max(np.flatnonzero(out.trees.feature[0] >= 0))) >= 511
    side = compare.reference_side(data, cfg)
    fit = compare.reference_fit(side, data, cfg)
    got = compare.compare(out, side, fit, data, cfg, cell["limits"])
    assert compare.is_correct(got), compare.lines(got)
    assert got["cuts_maxdiff"]["value"] == got["bins_mismatch"]["value"] == 0
    for k in ("cover_nodes", "grad_nodes"):
        assert got[k]["value"] <= cell["limits"][k], (k, got[k])


def _scopes_of(monkeypatch, depth, n_rows):
    """(kernel output shapes by scope, every scope) of ``grow_tree``
    traced (never run) at ``depth`` on the int8 Pallas path, read off
    the jaxpr's name stacks.  A row count of its own for each depth: a
    jit reuses the trace of the same shapes."""
    import jax
    import jax.numpy as jnp

    from xgboost_tpu.models.tree import GrowConfig, grow_tree
    from xgboost_tpu.ops import histogram as H
    from xgboost_tpu.ops.split import SplitConfig
    monkeypatch.setattr(H, "hist_backend", lambda precision="auto":
                        H.HistBackend("pallas_int8", False))
    S = jax.ShapeDtypeStruct
    F = 11
    cfg = GrowConfig(split=SplitConfig(eta=0.1), max_depth=depth, n_bin=256,
                     hist_precision="int8")
    traced = grow_tree.trace(
        S((2,), jnp.uint32), S((n_rows, F), jnp.uint8),
        S((n_rows, 2), jnp.float32), S((F, 254), jnp.float32),
        S((F,), jnp.int32), cfg)

    def walk(j, prefix):
        for e in getattr(j, "jaxpr", j).eqns:
            ns = f"{prefix}/{e.source_info.name_stack}"
            yield e, ns
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                        yield from walk(sub, ns)
    kernels, scopes = {}, set()
    for e, ns in walk(traced.jaxpr, ""):
        names = [p for p in ns.split("/") if "." in p and p.split(".")[0]
                 in ("grow", "deep", "deeper")]
        if names:
            scopes.add(names[-1])
        if e.primitive.name == "pallas_call":
            kernels.setdefault(names[-1], []).append(e.outvars[0].aval.shape)
    return kernels, scopes


@pytest.mark.parametrize("depth,n_rows", [(10, 4099), (8, 4097)])
def test_deeper_scopes_hold_the_levels_past_128_nodes(monkeypatch, depth,
                                                      n_rows):
    """Depth 10: the level kernels of 1-32 nodes under grow.hist, of 64
    and 128 nodes under deep.hist (one node tile each, built as 32 and 64
    left children), of 256 and 512 under deeper.hist (2 and 4 tiles of
    64 left children); split finding and routing follow.  Depth 8 has no
    deeper.* at all and its deep.* are the same two levels."""
    kernels, scopes = _scopes_of(monkeypatch, depth, n_rows)
    assert [s[0] for s in kernels["grow.hist"]] == [1] * 6
    # one node tile each: 32 left children folded by two, then 64
    assert [s[0] for s in kernels["deep.hist"]] == [1, 1]
    for part in ("split", "route"):
        assert {f"grow.{part}", f"deep.{part}"} <= scopes
    if depth == 8:
        assert "deeper.hist" not in kernels
        assert not {s for s in scopes if s.startswith("deeper.")}
        return
    assert [s[0] for s in kernels["deeper.hist"]] == [2, 4]
    assert {"deeper.hist", "deeper.split", "deeper.route"} <= scopes


def test_bin_fill_gauge_on_a_known_layout():
    """Three two-valued columns (3 bins each can reach), one of 200
    distinct values (200 cuts, 201 bins), one of 12 with a missing cell
    (13 bins and bin 0): (3 x 3 + 201 + 13 + 1) / (5 x n_bin)."""
    rng = np.random.default_rng(3900000105)
    n = 4000
    X = np.zeros((n, 5), np.float32)
    X[np.arange(n), rng.integers(0, 3, n)] = 1.0
    X[:, 3] = rng.integers(0, 200, n)
    X[:, 4] = rng.integers(0, 12, n)
    X[7, 4] = np.nan
    bst = xgb.Booster({"max_bin": 256, "sketch_eps": 1 / 256,
                       "objective": "binary:logistic", "max_depth": 2})
    d = xgb.DMatrix(X, label=(X[:, 3] > 100).astype(np.float32))
    bst.update_many(d, 0, 0)                    # builds the entry
    assert list(bst.gbtree.cuts.n_cuts) == [2, 2, 2, 200, 12]
    n_bin = bst.gbtree.cfg.n_bin                # 202, or 224 aligned
    want = 100.0 * (3 * 3 + 201 + 13 + 1) / (5 * n_bin)
    assert training_metrics().hist_bin_fill.value == pytest.approx(want)
    from xgboost_tpu import obs
    assert "xgbtpu_hist_bin_fill " in obs.registry().render()
    from readers import program_gauge
    args = run.load_json(BENCH, "metrics", "hist_bin_fill_pct.json")["args"]
    assert program_gauge.read({}, **args) == pytest.approx(want)


def test_bin_fill_counts_bin_0_of_a_sparse_column():
    """CSR input: a column with absent entries reaches bin 0 too."""
    import scipy.sparse as sp
    rng = np.random.default_rng(3900000106)
    dense = np.where(rng.random((3000, 4)) < 0.5, 1.0, 0.0).astype(np.float32)
    dense[:, 0] = 1.0                           # stored everywhere
    m = sp.csr_matrix(dense)                    # zeros are absent entries
    bst = xgb.Booster({"max_bin": 64, "objective": "binary:logistic",
                       "max_depth": 2})
    bst.update_many(xgb.DMatrix(m, label=dense[:, 1]), 0, 0)
    n_cuts = bst.gbtree.cuts.n_cuts
    n_bin = bst.gbtree.cfg.n_bin
    want = 100.0 * (int(n_cuts.sum()) + 4 + 3) / (4 * n_bin)
    assert training_metrics().hist_bin_fill.value == pytest.approx(want)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """run.py's phases on the CPU over the tiny cell beside the
    benchmark's test cells: 2,048 + 512 x 695 from the cell's own
    generator, 16 bins, depth 10, ten rounds to a call.  It writes no
    device metric."""
    root = tmp_path_factory.mktemp("airline_onehot_root")
    (root / "BENCHMARK.json").write_text(json.dumps(
        {"workloads": [{"name": TINY, "config": TINY_CONFIG}]}))
    args = run.parse(["--workload", TINY, "--seed", "3900000107",
                      "--seconds", "0.5"])
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("XGBTPU_NO_JITCACHE", "1")        # no cache from a test
        before = run.program_failures()
        result = run.run_cell(args, rehearse=True, bench_dir=CELLS,
                              root=str(root))
    return result, before, training_metrics().hist_bin_fill.value


def test_rehearsal_runs_the_cell(rehearsal):
    result, before, fill = rehearsal
    assert result["failed"] == before and result["correct"] == (before == 0)
    assert result["metrics"] == {} and result["attempted"] >= 10
    assert result["notes"]["rounds_per_call"] == 10
    assert result["counts"]["compiles_in_window"] == 0
    assert len(result["compared"]) == 10
    # 693 columns of 3 reachable bins or fewer beside two of 15: a few %
    assert 10.0 < fill < 20.0


@pytest.mark.parametrize("number", COMPARED)
def test_rehearsal_holds_each_compared_number_under_its_limit(rehearsal,
                                                              number):
    held = rehearsal[0]["compared"][number]
    assert held["value"] <= held["limit"], held
    if number in ("cuts_maxdiff", "bins_mismatch"):
        assert held["value"] == 0
