"""Spans and named scopes inside the fused trainer and the ingest path
(ISSUE 28, OBSERVABILITY.md "Span model"):

(a) under a JAX profiler session the program's spans are
    ``TraceAnnotation``s in the ``/host:CPU`` plane, nested as opened;
(b) with no profiler and no log the always-on totals advance by the
    same counts, the fused path is taken and the round counter moves;
(c) the compiled scan carries every scope name in its ``op_name``
    metadata, and neither spans nor scopes change the model;
(d) a span with no log and no session generates no id and writes
    nothing, and costs microseconds (bounded here, on the CPU).
"""

import contextlib
import glob
import os
import re
import time

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu import obs
from xgboost_tpu.obs import events, span, span_totals, training_metrics

INGEST = ("ingest.dmatrix", "ingest.cuts", "ingest.bin", "ingest.upload")
SEGMENT = ("train.segment", "train.dispatch", "train.launch", "train.wait",
           "train.absorb", "train.eval")
SCOPES = ("round.gradient", "grow.operand", "grow.hist", "grow.split",
          "grow.route", "round.margin", "round.eval")
PARAMS = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
          "eval_metric": "logloss", "silent": 1}


def _data(n, n_held=300, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n + n_held, f)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    return X[:n], y[:n], X[n:], y[n:]


def _train(n, rounds=4, k=2, params=PARAMS):
    """A fresh booster on fresh matrices: `rounds` rounds in fused
    segments of `k`, watched on a held-out set."""
    X, y, Xh, yh = _data(n)
    dtrain, dheld = xgb.DMatrix(X, label=y), xgb.DMatrix(Xh, label=yh)
    bst = xgb.Booster(dict(params))
    lines = {}
    bst.update_many(dtrain, 0, rounds, evals=[(dheld, "test")],
                    eval_callback=lines.__setitem__, rounds_per_dispatch=k)
    assert sorted(lines) == list(range(rounds))
    return bst


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Host-plane events (name, start, end) of a two-segment fused run
    under `jax.profiler.start_trace`, read back through ProfileData."""
    import jax
    from jax.profiler import ProfileData
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        _train(1501)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("train.", "ingest.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def _inside(events_, name, outer):
    return [e for e in events_ if e[0] == name
            and outer[1] <= e[1] and e[2] <= outer[2]]


@pytest.mark.parametrize("name", INGEST)
def test_ingest_spans_are_profiler_annotations(profiled, name):
    assert [e for e in profiled if e[0] == name], name


def test_fused_segments_are_profiler_annotations_nested_as_opened(profiled):
    segments = [e for e in profiled if e[0] == "train.segment"]
    assert len(segments) == 2
    for seg in segments:
        (dispatch,) = _inside(profiled, "train.dispatch", seg)
        (launch,) = _inside(profiled, "train.launch", dispatch)
        (wait,) = _inside(profiled, "train.wait", dispatch)
        (absorb,) = _inside(profiled, "train.absorb", seg)
        (ev,) = _inside(profiled, "train.eval", seg)
        assert launch[2] <= wait[1] and wait[2] <= absorb[1] <= ev[1]
        assert not _inside(profiled, "train.absorb", dispatch)


def test_totals_advance_with_no_profiler_and_no_log():
    assert events.get_log() is None
    tm = training_metrics()
    before = span_totals().count.values()
    secs0 = span_totals().seconds.values()
    rounds0, dispatches0 = tm.rounds.value, tm.dispatch_seconds.count
    fallbacks0 = sum(tm.fused_fallback.values().values())
    _train(1502)
    after = span_totals().count.values()
    grew = {k: after[k] - before.get(k, 0) for k in after}
    assert {k: grew[k] for k in SEGMENT} == dict.fromkeys(SEGMENT, 2)
    # two matrices: their constructors, and no CSR built from the
    # ndarrays; one sketch; two entries binned; bin ids x 2, labels and
    # weights of the train set
    assert grew["ingest.dmatrix"] == 2 and grew["ingest.cuts"] == 1
    assert grew["ingest.bin"] == 2 and grew["ingest.upload"] == 4
    secs = span_totals().seconds.values()
    assert all(secs[k] > secs0.get(k, 0.0) for k in INGEST + SEGMENT)
    assert sum(tm.fused_fallback.values().values()) == fallbacks0
    assert tm.rounds.value - rounds0 == 4 and tm.round.value == 3
    # the dispatch histogram is fed from the train.dispatch span
    assert tm.dispatch_seconds.count - dispatches0 == 2
    text = obs.registry().render()
    assert 'xgbtpu_span_total{span="train.segment"}' in text
    assert 'xgbtpu_span_seconds_total{span="ingest.bin"}' in text


@pytest.mark.parametrize("kind,dense", [("ndarray", 1), ("csr_tuple", 0)])
def test_cuts_and_bin_spans_say_whether_the_dense_source_was_read(
        tmp_path, kind, dense):
    import json
    X, y, _, _ = _data(1504)
    path = str(tmp_path / "spans.jsonl")
    obs.configure_log(path)
    try:
        dtrain = xgb.DMatrix(X, label=y)
        if kind == "csr_tuple":
            dtrain = xgb.DMatrix((dtrain.indptr, dtrain.indices,
                                  dtrain.values, dtrain.num_col), label=y)
        xgb.Booster(dict(PARAMS), cache=[dtrain]).update(dtrain, 0)
    finally:
        obs.configure_log(None)
    with open(path) as f:
        spans = [r for r in map(json.loads, f) if r["kind"] == "span"]
    ingest = [r for r in spans if r["name"] in INGEST]
    for name in ("ingest.cuts", "ingest.bin"):
        found = [r["attrs"]["dense"] for r in ingest if r["name"] == name]
        assert found and set(found) == {dense}, (name, found)
    # the deferred CSR build is nobody's child: the four never nest
    ids = {r["span"] for r in ingest}
    assert {r["name"] for r in ingest} == set(INGEST)
    assert not [r for r in ingest if r.get("parent") in ids]
    built = [r for r in ingest if r["name"] == "ingest.dmatrix"]
    assert len(built) == (1 if dense else 3)


def _live_scans() -> dict:
    """Text of every live compiled scan, by its hash."""
    import jax.extend
    return {hash(t): t
            for ex in jax.extend.backend.get_backend().live_executables()
            for m in ex.hlo_modules() if "_scan_rounds_impl" in m.name
            for t in [m.to_string()]}


@pytest.fixture(scope="module", params=[3, 6, 7])
def depth_and_op_names(request):
    """(max_depth, op_name metadata of the compiled scan) of a run whose
    histograms go through the Pallas path (interpreted here), so that
    the operand it builds in-graph is there to be named.  A row count
    of its own for each depth: the scan it compiles is a new one."""
    depth = request.param
    before = _live_scans()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("XGBTPU_HIST", "pallas_int8")
        _train(257 + 2 * depth, rounds=2, k=2,
               params={**PARAMS, "max_depth": depth})
    new = [t for h, t in _live_scans().items() if h not in before]
    assert new, "the run compiled no scan of its own"
    return depth, [n for t in new for n in re.findall(r'op_name="([^"]*)"', t)]


@pytest.mark.parametrize("scope", SCOPES)
def test_compiled_scan_carries_the_scope(depth_and_op_names, scope):
    _, names = depth_and_op_names
    assert any(f"/{scope}" in n for n in names), scope


DEEP = ("deep.hist", "deep.split", "deep.route")


@pytest.mark.parametrize("scope", DEEP)
def test_levels_past_32_nodes_carry_their_own_scopes(depth_and_op_names,
                                                     scope):
    """Depth 7: the level of 64 nodes is under deep.*, the levels of
    1-32 nodes and the terminal one under grow.*; a tree whose levels
    stop at 32 nodes (depth 3; depth 6: 32, then the terminal 64) never
    enters them.  The benchmark's reader finds a scope as the innermost
    ``[a-z_]+\\.[a-z_]+`` component of an op's name."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    from readers import trace_scope_time
    depth, names = depth_and_op_names
    hits = [n for n in names if f"/{scope}" in n]
    if depth < 7:
        assert not hits
        return
    assert hits and all(trace_scope_time.innermost(n) == scope
                        or "grow.widen" in n for n in hits)
    twin = scope.replace("deep.", "grow.")
    assert any(trace_scope_time.innermost(n) == twin for n in names)


def test_spans_and_scopes_leave_the_model_bytes_alone(monkeypatch):
    import jax

    import xgboost_tpu.learner
    with_them = _train(1503).save_raw()

    class no_span(contextlib.nullcontext):
        seconds = 0.0

        def __init__(self, name, **attrs):
            super().__init__(self)

        def set(self, key, value):
            pass
    monkeypatch.setattr(obs, "span", no_span)
    monkeypatch.setattr(xgboost_tpu.learner, "span", no_span)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    # new traces of the scan and of the jitted grower inside it, not
    # the cached ones that have the scopes
    jax.clear_caches()
    before = span_totals().count.values()
    without = _train(1503).save_raw()
    after = span_totals().count.values()
    jax.clear_caches()
    assert {k: after[k] - before[k] for k in SEGMENT + INGEST} == \
        dict.fromkeys(SEGMENT + INGEST, 0)
    assert with_them == without


def test_span_with_no_log_and_no_session_makes_no_id(monkeypatch):
    assert events.get_log() is None
    made = []
    monkeypatch.setattr(obs.trace, "new_id", lambda: made.append(1) or "x")
    monkeypatch.setattr(events, "emit", lambda rec: made.append(rec))
    n0 = span_totals().count.value("test.dark")
    with span("test.dark", rows=3) as outer:
        with span("test.dark") as inner:
            assert obs.trace.current_span_id() is None
    assert made == []
    assert (outer.span_id, outer.trace, inner.parent) == (None, None, None)
    assert span_totals().count.value("test.dark") - n0 == 2


def test_span_cost_with_no_log_and_no_session_is_microseconds():
    """The whole cost of a span where nobody watches (the serving path
    opens four a request): about 3 us on this container's CPU against
    2.4 us before PR 28; the bound leaves room for a loaded host."""
    assert events.get_log() is None

    def per_span(n=2000):
        t0 = time.perf_counter()
        for _ in range(n):
            with span("test.cost", rows=3):
                pass
        return (time.perf_counter() - t0) / n
    assert min(per_span() for _ in range(5)) < 30e-6
