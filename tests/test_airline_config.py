"""The Airline-shape configuration of the benchmark (ISSUE 31), held on
the CPU at test sizes: its generator, the program's cuts and bin ids on
its tied columns against the benchmark's plain reference, the shipped
files, and one rehearsal of the whole run at a tiny size."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)

import reference as ref  # noqa: E402
import run  # noqa: E402
from datagen import airline_like  # noqa: E402

import xgboost_tpu as xgb  # noqa: E402
from xgboost_tpu.binning import bin_matrix, compute_cuts  # noqa: E402

CELL, CONFIG = "airline-shape-synth.train_logloss", "airline-shape-synth-d6-b256"
ROWS, HELD = 200_000, 10_000


@pytest.fixture(scope="module")
def data():
    return airline_like.generate(3100003101, ROWS, HELD, 13)


def test_generator_is_a_function_of_the_seed_alone(data):
    again = airline_like.generate(3100003101, ROWS, HELD, 13)
    other = airline_like.generate(3100003102, ROWS, HELD, 13)
    for k, v in data.items():
        assert v.dtype == np.float32 and v.flags.c_contiguous, k
        assert np.array_equal(v, again[k]), k
        assert v.shape == other[k].shape, k
    assert data["X_train"].shape == (ROWS, 13)
    assert data["X_held"].shape == (HELD, 13)
    assert not np.array_equal(data["X_train"], other["X_train"])
    with pytest.raises(ValueError):
        airline_like.generate(1, 100, 10, 28)


@pytest.mark.parametrize("column,distinct", [
    ("Year", 22), ("Month", 12), ("DayofMonth", 31), ("DayOfWeek", 7),
    ("CRSDepTime", 1440), ("CRSArrTime", 1440), ("UniqueCarrier", 29),
    ("Origin", 350), ("Dest", 350), ("Diverted", 2)])
def test_generator_columns_hold_the_stated_values(data, column, distinct):
    col = data["X_train"][:, airline_like.COLUMNS.index(column)]
    assert np.array_equal(col, np.rint(col))          # integer-coded
    assert len(np.unique(col)) == distinct
    if column.startswith("CRS"):                      # valid HHMM
        assert col.min() >= 0 and col.max() <= 2359 and (col % 100).max() <= 59


def test_generator_flag_codes_lengths_and_label(data):
    X, y = data["X_train"], data["y_train"]
    col = {c: X[:, j] for j, c in enumerate(airline_like.COLUMNS)}
    assert 0.0015 < col["Diverted"].mean() < 0.0025
    n_flight = len(np.unique(col["FlightNum"]))       # a Zipf tail: rare codes
    assert 5000 < n_flight <= 8000 and col["FlightNum"].max() < 8000
    top = np.sort(np.bincount(col["FlightNum"].astype(int)))[::-1]
    assert 0.08 < top[0] / ROWS < 0.13                # exponent 1: 1 / H(8000)
    for c in ("ActualElapsedTime", "Distance"):
        assert np.array_equal(col[c], np.rint(col[c])) and col[c].min() > 0
    assert len(np.unique(col["Distance"])) > 2000
    assert set(np.unique(y)) == {0.0, 1.0} and 0.43 < y.mean() < 0.47
    # a diverted flight arrives late, and the rest can be learned
    assert y[col["Diverted"] == 1].mean() > 0.8


def test_cuts_and_bins_of_tied_columns_equal_the_reference(data):
    X = data["X_train"]
    want = ref.propose_cuts(X, max_bin=256, sketch_eps=1 / 256,
                            sketch_ratio=2.0, bin_align=32)
    dtrain = xgb.DMatrix(X, label=data["y_train"])
    cuts = compute_cuts(dtrain, max_bin=256, sketch_eps=1 / 256,
                        sketch_ratio=2.0, bin_align=32)
    n = {c: int(cuts.n_cuts[j]) for j, c in enumerate(airline_like.COLUMNS)}
    assert (n["DayOfWeek"], n["Month"], n["Year"], n["DayofMonth"],
            n["UniqueCarrier"], n["Diverted"]) == (7, 12, 22, 31, 29, 2)
    assert cuts.max_bin == 256
    for f, w in enumerate(want):
        assert np.array_equal(cuts.cut_values[f, :cuts.n_cuts[f]], w), f
    assert np.array_equal(bin_matrix(dtrain, cuts), ref.bin_ids(X, want))


def test_shipped_files_name_each_other():
    bench, entry, cell, cfg = run.find_cell(CELL)
    assert entry["config"] == cfg["name"] == CONFIG and entry["chips"] == 1
    listed = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert listed["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert listed["reduced"] == cfg["reduced"] == ["rows"]
    assert cfg["features"] == cfg["published"]["features"] == 13
    assert cfg["n_train"] == 20 * cfg["n_held"] >= 28_000_000
    assert cfg["params"]["max_bin"] == cfg["published"]["max_bin"] == 256
    assert cfg["params"]["max_depth"] == cfg["published"]["max_depth"] == 6
    assert cfg["params"]["hist_precision"] == "auto"
    assert cell["limits"]["cuts_maxdiff"] == cell["limits"]["bins_mismatch"] == 0
    assert len(cell["limits"]) == 10
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            spec = run.load_json(BENCH, "metrics", f"{m['name']}.json")
            assert os.path.exists(os.path.join(
                BENCH, "readers", f"{spec['reader']}.py")), m["name"]


def test_program_gauge_reader_reads_the_gauge_or_nothing():
    from readers import program_gauge
    from xgboost_tpu.obs import training_metrics
    training_metrics().hist_row_chunks.set(3.0)
    args = run.load_json(BENCH, "metrics", "hist_row_chunks.json")["args"]
    assert program_gauge.read({}, **args) == 3.0
    assert program_gauge.read({}, group="training_metrics",
                              gauge="not_there") is None
    assert program_gauge.read({}, group="not_there", gauge="x") is None


def test_rehearsal_of_the_configuration_at_a_tiny_size(tmp_path, monkeypatch):
    """run.py's phases on the CPU with the shipped files cut to 60k rows,
    32 bins and depth 4 in a temporary cells directory: files found by
    name, the generator, ingest, the fused calls, the reference and the
    comparison.  It writes no device metric."""
    monkeypatch.setenv("XGBTPU_NO_JITCACHE", "1")   # no cache from a test
    _, _, cell, _ = run.find_cell(CELL)
    cfg = run.load_json(BENCH, "configs", f"{CONFIG}.json")
    cfg.update(n_train=60_000, n_held=6_000, rows=66_000, bin_align=0)
    cfg["params"].update(max_depth=4, max_bin=32, sketch_eps=1 / 32)
    cell["job"]["rounds_per_call"] = 4
    for sub, name, body in (("configs", CONFIG, cfg), ("workloads", CELL, cell)):
        os.makedirs(tmp_path / sub)
        (tmp_path / sub / f"{name}.json").write_text(json.dumps(body))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(
        {"workloads": [{"name": CELL, "config": CONFIG}]}))
    args = run.parse(["--workload", CELL, "--seed", "3100003103",
                      "--seconds", "0.5"])
    # the program's failure counters are the process's: earlier tests of
    # this worker may have left some, and `correct` then says false
    before = run.program_failures()
    result = run.run_cell(args, rehearse=True, bench_dir=str(tmp_path),
                          root=str(tmp_path))
    held = result["compared"]
    assert len(held) == 10 and all(
        c["value"] <= c["limit"] for c in held.values()), held
    assert held["cuts_maxdiff"]["value"] == held["bins_mismatch"]["value"] == 0
    assert result["failed"] == before and result["correct"] == (before == 0)
    assert result["metrics"] == {} and result["attempted"] >= 4
    assert result["counts"]["compiles_in_window"] == 0
