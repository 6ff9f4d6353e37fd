"""Each fused dispatch's wait against the quickest of its kind
(OBSERVABILITY.md, ``train.wait``):

(a) the least-wait bookkeeping on given durations: the first of a kind
    and a new least add nothing, the 1 % rule, kinds kept apart;
(b) a dispatch held 0.2 s inside ``train.wait`` (``block_until_ready``
    patched) is counted: the excess counter, the stalled counter and
    that dispatch's log record;
(c) a log or a scrape port leaves training on the fused path, and the
    log holds one ``train.wait`` record per dispatch, with the host's
    readings and none of the device allocator's on the CPU;
(d) the benchmark's two metric files read the counters, or nothing on
    a program without them.

No test here asserts that a CPU run holds no stall: the CPU's timings
are not the chip's.
"""

import importlib.util
import json
import os
import time

import jax
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu import obs
from xgboost_tpu.obs import events, span_totals, training_metrics
from xgboost_tpu.obs.metrics import (DEVICE_READINGS, HOST_READINGS,
                                     WaitFloor, swallowed_errors)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARAMS = {"objective": "binary:logistic", "max_depth": 2, "eta": 0.3,
          "silent": 1}


@pytest.mark.parametrize("waits,kinds,excess,stalled", [
    # the first of a kind adds nothing
    ([0.5], "a", [0.0], [False]),
    # a new least adds nothing, and lowers the floor for the next
    ([0.5, 0.4, 0.45], "aaa", [0.0, 0.0, 0.05], [False, False, True]),
    # equal to the least: nothing
    ([0.5, 0.5], "aa", [0.0, 0.0], [False, False]),
    # the 1 % rule: 1 % of 0.5 s is 5 ms; 3.9 ms over is no stall,
    # 7.8 ms is (sums exact in binary)
    ([0.5, 0.50390625, 0.5078125], "aaa", [0.0, 0.00390625, 0.0078125],
     [False, False, True]),
    # kinds kept apart: a slow kind is no stall of a quick one
    ([0.1, 7.0, 0.1, 7.2], "abab", [0.0, 0.0, 0.0, 0.2],
     [False, False, False, True]),
])
def test_wait_floor(waits, kinds, excess, stalled):
    floor = WaitFloor()
    got = [floor.observe(k, w) for k, w in zip(kinds, waits)]
    assert [e for e, _ in got] == pytest.approx(excess, abs=1e-12)
    assert [s for _, s in got] == stalled


def test_observe_wait_feeds_both_counters():
    tm = training_metrics()
    e0, s0 = tm.wait_excess_seconds.value, tm.stalled_dispatches.value
    kind = ("test_observe_wait_feeds_both_counters",)
    assert tm.observe_wait(kind, 2.0) == (0.0, False)
    assert tm.observe_wait(kind, 2.01) == pytest.approx((0.01, False))
    assert tm.observe_wait(kind, 2.5) == (0.5, True)
    assert tm.wait_excess_seconds.value - e0 == pytest.approx(0.51)
    assert tm.stalled_dispatches.value - s0 == 1


def _matrix(n, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 5)).astype(np.float32)
    return xgb.DMatrix(X, label=(X[:, 0] > 0).astype(np.float32))


def _wait_records(path):
    with open(path) as f:
        return [r for r in map(json.loads, f)
                if r["kind"] == "span" and r["name"] == "train.wait"]


def test_a_dispatch_held_in_its_wait_is_counted(tmp_path, monkeypatch):
    dtrain = _matrix(1601)             # a row count, so a kind, of its own
    bst = xgb.Booster(dict(PARAMS))
    bst.update_many(dtrain, 0, 0)      # the entry, before the patch
    real = jax.block_until_ready
    calls = []

    def held(x):
        out = real(x)
        if len(calls) == 4:
            time.sleep(0.2)
        calls.append(1)
        return out
    monkeypatch.setattr(jax, "block_until_ready", held)
    tm = training_metrics()
    e0, s0 = tm.wait_excess_seconds.value, tm.stalled_dispatches.value
    path = str(tmp_path / "obs.jsonl")
    obs.configure_log(path)
    try:
        bst.update_many(dtrain, 0, 6, rounds_per_dispatch=1)
    finally:
        obs.configure_log(None)
    assert len(calls) == 6             # one block a dispatch, no other
    waits = [r["attrs"] for r in _wait_records(path)]
    assert len(waits) == 6
    least = min(w["wait_s"] for w in waits[:4])
    held_one = waits[4]
    assert held_one["wait_s"] >= 0.2
    assert held_one["excess_s"] == pytest.approx(held_one["wait_s"] - least)
    assert held_one["stalled"] is True
    rise = tm.wait_excess_seconds.value - e0
    assert rise >= 0.2 - least
    assert rise == pytest.approx(sum(w["excess_s"] for w in waits))
    assert tm.stalled_dispatches.value - s0 >= 1
    assert tm.stalled_dispatches.value - s0 == sum(
        w["stalled"] for w in waits)


@pytest.mark.parametrize("switch", ["obs_log", "metrics_port"])
def test_a_log_or_a_scrape_port_keeps_the_fused_path(tmp_path, switch):
    dtrain, dheld = _matrix(1602), _matrix(300, seed=1)
    tm = training_metrics()
    fallbacks0 = dict(tm.fused_fallback.values())
    swallowed0 = sum(swallowed_errors().values().values())
    waits0 = span_totals().count.value("train.wait")
    path = str(tmp_path / "obs.jsonl")
    bst = xgb.Booster(dict(PARAMS))
    if switch == "obs_log":
        obs.configure_log(path)
    else:
        obs.start_metrics_server(port=0)
    try:
        bst.update_many(dtrain, 0, 4, evals=[(dheld, "test")],
                        rounds_per_dispatch=2)
        text = obs.registry().render()
    finally:
        obs.configure_log(None)
        obs.stop_metrics_server()
    assert getattr(bst, "_profiler", None) is None
    assert dict(tm.fused_fallback.values()) == fallbacks0
    assert span_totals().count.value("train.wait") - waits0 == 2
    assert sum(swallowed_errors().values().values()) == swallowed0
    for family in ("xgbtpu_train_wait_excess_seconds_total",
                   "xgbtpu_train_stalled_dispatches_total",
                   "xgbtpu_train_launch_bytes_in_use",
                   "xgbtpu_train_launch_bytes_reserved"):
        assert f"\n{family} " in text, family
    if switch != "obs_log":
        assert events.get_log() is None
        return
    waits = [r["attrs"] for r in _wait_records(path)]
    assert len(waits) == 2
    for w in waits:
        assert {"wait_s", "excess_s", "stalled", *HOST_READINGS} <= set(w)
        assert w["wait_s"] >= 0 and w["cpu_s"] >= 0 and w["nivcsw"] >= 0
        # a CPU backend reports no allocator: those keys are absent
        assert not [k for k in w if k.endswith(DEVICE_READINGS)]


def test_profile_1_still_takes_the_per_round_path(tmp_path):
    tm = training_metrics()
    before = tm.fused_fallback.value("profiler")
    path = str(tmp_path / "obs.jsonl")
    obs.configure_log(path)
    try:
        xgb.Booster({**PARAMS, "profile": 1}).update_many(_matrix(1603),
                                                          0, 2)
    finally:
        obs.configure_log(None)
    assert tm.fused_fallback.value("profiler") - before == 1
    with open(path) as f:
        names = [r["name"] for r in map(json.loads, f)]
    assert names.count("train.round") == 2 and "train.phase" in names
    assert "train.wait" not in names


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "benchmark", "readers", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("metric,reader,attr", [
    ("wait_excess_ms_per_round", "program_counter_per_round",
     "wait_excess_seconds"),
    ("stalled_dispatches", "program_gauge", "stalled_dispatches")])
def test_the_metric_files_read_the_counters(metric, reader, attr):
    with open(os.path.join(REPO, "benchmark", "metrics",
                           f"{metric}.json")) as f:
        spec = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert (spec["reader"], spec["layer"], spec["moves"]) == (
        reader, "round driver", "train_rounds_per_s")
    assert (entry["layer"], entry["moves"], entry["unit"], entry["source"]) \
        == (spec["layer"], spec["moves"], spec["unit"], spec["source"])
    value = getattr(training_metrics(), attr).value
    ctx = {"counts": {"rounds_in_window": 40}}
    got = _reader(reader).read(ctx, **spec["args"])
    assert got == pytest.approx(
        value * 1000 / 40 if metric == "wait_excess_ms_per_round"
        else value)
    missing = dict(spec["args"], **{
        "counter" if "counter" in spec["args"] else "gauge": "no_such"})
    assert _reader(reader).read(ctx, **missing) is None
