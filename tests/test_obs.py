"""Observability layer tests (xgboost_tpu.obs; design in
OBSERVABILITY.md).

Acceptance criteria covered here:
(a) a CLI training run with ``metrics_port=`` exposes live
    ``xgbtpu_training_*`` metrics (scraped over HTTP by a test) and
    with ``obs_log=`` leaves a JSONL timeline that
    ``tools/obs_report.py`` renders into a per-round phase view;
(b) a serving request carrying ``X-Request-Id`` gets the id echoed in
    the response header and appears as a span in the event log;
(c) collective stats exported per rank across real processes
    (``mp_comm_worker.py``), the allreduce count matching the mock
    seam's collective-call count;
(d) the Prometheus exposition output lints (HELP/TYPE per family,
    cumulative histogram buckets ending at ``+Inf == _count``);
(e) ``xgboost_tpu.obs`` is the one import path of the primitives.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu import obs
from xgboost_tpu.obs import comm, trace
from xgboost_tpu.obs.metrics import Histogram, LabeledCounter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def obs_log(tmp_path):
    """Configure a temp event log; always unconfigure (the log is
    process-global and must not leak into other tests)."""
    path = str(tmp_path / "obs.jsonl")
    obs.configure_log(path)
    try:
        yield path
    finally:
        obs.configure_log(None)


def _records(path):
    out = []
    with open(path) as f:
        for line in f:
            if line.strip():
                out.append(json.loads(line))
    return out


def _train(seed=0, rounds=3, n=300, **params):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 6).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    p = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.4,
         "silent": 1, "seed": seed, **params}
    return xgb.train(p, xgb.DMatrix(X, label=y), rounds), X, y


# ---------------------------------------------------------- primitives
def test_histogram_quantile_edge_cases():
    h = Histogram("t", buckets=(1.0, 2.0, 4.0))
    # empty: every quantile is 0.0, exactly
    for q in (0.0, 0.5, 1.0):
        assert h.quantile(q) == 0.0
    # single bucket occupied (values in (2.0, 4.0])
    h.observe(3.0)
    h.observe(3.5)
    assert h.quantile(0.0) == 2.0   # lower edge of first nonempty bucket
    assert h.quantile(1.0) == 4.0   # upper edge of last nonempty bucket
    assert 2.0 < h.quantile(0.5) < 4.0
    # q beyond [0,1] clamps to the edges
    assert h.quantile(-1.0) == 2.0
    assert h.quantile(2.0) == 4.0
    # overflow-bucket observations: q=1 reports the top finite bound
    h2 = Histogram("t2", buckets=(1.0,))
    h2.observe(50.0)
    assert h2.quantile(1.0) == 1.0
    assert h2.quantile(0.0) == 1.0  # lower edge of the +Inf bucket


def test_round_profiler_summary_no_division_by_zero():
    from xgboost_tpu.obs import RoundProfiler
    prof = RoundProfiler(level=0)
    # a round whose phases all measured 0.0s must not raise
    prof.rounds.append({"round": 0, "phases": {"grow": 0.0}, "t0": None})
    s = prof.summary()
    assert "1 rounds" in s and "grow" in s and "0.0%" in s
    # a round with NO phases at all
    prof2 = RoundProfiler(level=0)
    prof2.rounds.append({"round": 0, "phases": {}, "t0": None})
    s2 = prof2.summary()
    assert "1 rounds" in s2 and "no phases" in s2
    # empty profiler
    assert "no rounds" in RoundProfiler(level=0).summary()


def test_labeled_counter_render_and_escaping():
    c = LabeledCounter("x_total", "phase", "help text")
    c.inc("grow", 1.5)
    c.inc('we"ird\nname', 1)
    text = c.render()
    assert '# HELP x_total help text' in text
    assert '# TYPE x_total counter' in text
    assert 'x_total{phase="grow"} 1.5' in text
    assert r'we\"ird\nname' in text


# ----------------------------------------------------- exposition lint
def _lint_exposition(text):
    """promtool-style lint: every sample belongs to a family that
    declared HELP and TYPE; histogram buckets are cumulative and end at
    +Inf == _count; no family is declared twice."""
    helps, types = {}, {}
    samples = []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name = line.split()[2]
            assert name not in helps, f"duplicate HELP for {name}"
            helps[name] = line
        elif line.startswith("# TYPE "):
            name = line.split()[2]
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = line.split()[3]
        else:
            m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)$",
                         line)
            assert m, f"unparseable sample line: {line!r}"
            samples.append((m.group(1), m.group(2), float(m.group(3))))
    for name, labels, _ in samples:
        family = re.sub(r"_(bucket|sum|count)$", "", name) \
            if re.sub(r"_(bucket|sum|count)$", "", name) in types else name
        assert family in types, f"sample {name} has no TYPE"
        assert family in helps, f"sample {name} has no HELP"
    # histogram bucket discipline
    hists = [n for n, t in types.items() if t == "histogram"]
    for h in hists:
        buckets = [(labels, v) for n, labels, v in samples
                   if n == f"{h}_bucket"]
        counts = [v for n, _, v in samples if n == f"{h}_count"]
        assert buckets and len(counts) == 1, h
        vals = [v for _, v in buckets]
        assert vals == sorted(vals), f"{h} buckets not cumulative"
        assert buckets[-1][0] == '{le="+Inf"}', h
        assert vals[-1] == counts[0], f"{h} +Inf bucket != _count"
    return types


def test_exposition_lint_full_registry():
    # make every group exist and carry data
    obs.training_metrics().phase_seconds.inc("grow", 0.5)
    obs.training_metrics().round_seconds.observe(0.01)
    obs.reliability_metrics()
    comm.record("allreduce", nbytes=10, seconds=0.1)
    types = _lint_exposition(obs.registry().render(exclude=("serving",)))
    for fam in ("xgbtpu_training_rounds_total",
                "xgbtpu_training_phase_seconds_total",
                "xgbtpu_training_round_seconds",
                "xgbtpu_comm_allreduce_total",
                "xgbtpu_comm_allreduce_bytes_total",
                "xgbtpu_comm_allreduce_seconds_total",
                "xgbtpu_reliability_integrity_failures_total"):
        assert fam in types, f"{fam} missing"


def test_exposition_lint_serving_metrics():
    from xgboost_tpu.obs import ServingMetrics
    m = ServingMetrics()
    m.latency.observe(0.003)
    m.latency.observe(0.3)
    m.batch_rows.observe(4)
    types = _lint_exposition(m.render())
    assert types["xgbtpu_serving_latency_seconds"] == "histogram"
    assert "xgbtpu_reliability_integrity_failures_total" in types


# ------------------------------------------------------- spans + events
def test_span_nesting_and_trace_propagation(obs_log):
    with trace.trace_context("req-42"):
        with obs.span("outer", a=1) as sp:
            sp.set("b", 2)
            with obs.span("inner"):
                pass
    recs = _records(obs_log)
    inner = next(r for r in recs if r["name"] == "inner")
    outer = next(r for r in recs if r["name"] == "outer")
    assert outer["trace"] == inner["trace"] == "req-42"
    assert inner["parent"] == outer["span"]
    assert "parent" not in outer
    assert outer["attrs"] == {"a": 1, "b": 2}
    assert outer["dur_ms"] >= inner["dur_ms"] >= 0


def test_span_error_status(obs_log):
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("nope")
    rec = _records(obs_log)[-1]
    assert rec["status"] == "error" and "nope" in rec["error"]


def test_span_is_noop_without_log():
    # no log configured: no ids generated, nesting depth still
    # consistent, nothing written
    assert obs.get_log() is None
    with obs.span("quiet") as sp:
        assert sp.span_id is None
        with obs.span("inner"):
            pass
        assert trace.current_span_id() is None  # sentinel, not an id
    assert not getattr(trace._tls, "spans", [])


def test_event_log_rotation(tmp_path):
    path = str(tmp_path / "rot.jsonl")
    log = obs.configure_log(path, rotate_bytes=512)
    try:
        for i in range(100):
            log.emit({"i": i, "pad": "x" * 32})
        assert os.path.exists(path + ".1"), "no rotation happened"
        # both generations parse line-by-line
        for p in (path, path + ".1"):
            assert all(json.loads(l) for l in open(p) if l.strip())
    finally:
        obs.configure_log(None)


def test_faults_emit_obs_events(obs_log, tmp_path):
    from xgboost_tpu.reliability import faults, integrity
    f = tmp_path / "victim.bin"
    f.write_bytes(b"payload")
    faults.inject("read_flip", 0, path_sub="victim")
    try:
        integrity.read_file(str(f))
    finally:
        faults.clear_faults()
    evs = [r for r in _records(obs_log) if r["kind"] == "event"
           and r["name"] == "fault.injected"]
    assert len(evs) == 1
    assert evs[0]["attrs"]["kind"] == "read_flip"
    assert evs[0]["attrs"]["seam"] == "read"
    assert "victim" in evs[0]["attrs"]["path"]


def test_integrity_failure_emits_event(obs_log):
    from xgboost_tpu.reliability.integrity import (ModelIntegrityError,
                                                   add_footer,
                                                   verify_model_bytes)
    raw = bytearray(add_footer(b"model-bytes"))
    raw[3] ^= 0x40
    with pytest.raises(ModelIntegrityError):
        verify_model_bytes(bytes(raw), name="flipped.bin")
    evs = [r for r in _records(obs_log)
           if r["name"] == "integrity.failure"]
    assert evs and evs[0]["attrs"]["file"] == "flipped.bin"


# ------------------------------------------------------------ training
def test_training_rounds_emit_timeline_and_metrics(obs_log):
    comm.reset_for_tests()
    from xgboost_tpu.parallel import mock
    rounds0 = obs.training_metrics().rounds.value
    ar0 = comm.metrics().count["allreduce"].value
    calls0 = mock.collective_calls()
    # per-round phases are profile>=1's: a log alone keeps the fused path
    _train(rounds=3, profile=1)
    assert obs.training_metrics().rounds.value - rounds0 == 3
    # comm allreduce count matches the mock seam's collective calls
    assert (comm.metrics().count["allreduce"].value - ar0
            == mock.collective_calls() - calls0 == 3)
    for r in range(3):
        rs = comm.round_stats(r)
        assert rs["allreduce"]["count"] == 1
        assert rs["allreduce"]["seconds"] > 0
    recs = _records(obs_log)
    rounds = [r for r in recs if r["name"] == "train.round"]
    phases = [r for r in recs if r["name"] == "train.phase"]
    assert [r["round"] for r in rounds] == [0, 1, 2]
    assert {p["attrs"]["phase"] for p in phases} >= {"predict", "grow"}
    assert all(r["attrs"]["phases_ms"] for r in rounds)
    assert all(r["attrs"]["comm"]["allreduce"]["count"] >= 1
               for r in rounds)


def test_eval_scores_exported_as_gauges():
    rng = np.random.RandomState(3)
    X = rng.rand(200, 4).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    xgb.train({"objective": "binary:logistic", "silent": 1}, d, 2,
              evals=[(d, "train")], verbose_eval=False)
    vals = obs.training_metrics().eval_score.values()
    assert any(k.startswith("train-") for k in vals)


def test_cli_train_scrape_and_timeline(tmp_path):
    """Acceptance: CLI train with metrics_port= is scrapeable over HTTP
    while running, and obs_log= leaves a timeline obs_report renders
    (per-round phase lines under profile=1, which keeps the round loop
    on the host)."""
    rng = np.random.RandomState(11)
    X = rng.rand(300, 5).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    train = tmp_path / "train.svm"
    with open(train, "w") as f:
        for row, label in zip(X, y):
            feats = " ".join(f"{j}:{v:.6f}" for j, v in enumerate(row))
            f.write(f"{label:g} {feats}\n")
    log = str(tmp_path / "run.jsonl")
    model = str(tmp_path / "m.model")
    from xgboost_tpu.cli import main as cli_main
    rc = {}

    def run():
        rc["rc"] = cli_main([
            f"data={train}", "task=train", "num_round=40",
            "objective=binary:logistic", "max_depth=3", "silent=1",
            f"eval[train]={train}", f"model_out={model}",
            f"obs_log={log}", "metrics_port=0", "profile=1"])

    t = threading.Thread(target=run)
    t.start()
    try:
        # the server comes up before the first round; scrape it LIVE
        srv = None
        for _ in range(2000):
            srv = obs.get_metrics_server()
            if srv is not None:
                break
            time.sleep(0.005)
        assert srv is not None, "metrics server never started"
        base = f"http://{srv.host}:{srv.port}"
        mid_run, text = False, ""
        while t.is_alive():
            r = urllib.request.urlopen(base + "/metrics", timeout=5)
            assert r.headers["Content-Type"] == \
                "text/plain; version=0.0.4; charset=utf-8"
            text = r.read().decode()
            m = re.search(r"^xgbtpu_training_rounds_total (\d+)", text,
                          re.M)
            if m and int(m.group(1)) > 0:
                mid_run = True
                break
            time.sleep(0.002)
        t.join(120)
        assert rc.get("rc") == 0
        if not mid_run:  # training beat the poll loop: scrape post-run
            text = urllib.request.urlopen(
                base + "/metrics", timeout=5).read().decode()
        for fam in ("xgbtpu_training_rounds_total",
                    "xgbtpu_training_phase_seconds_total",
                    "xgbtpu_training_eval_score",
                    "xgbtpu_comm_allreduce_total"):
            assert fam in text, f"{fam} missing from scrape"
        h = json.load(urllib.request.urlopen(base + "/healthz", timeout=5))
        assert h["status"] == "ok" and h["rounds_completed"] >= 40
        assert h["uptime_seconds"] >= 0
    finally:
        t.join(120)
        obs.stop_metrics_server()
        obs.configure_log(None)
    # the timeline renders per-round phase lines
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         log, "--rounds"], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "== training: 40 rounds ==" in out.stdout
    assert "round   39" in out.stdout
    assert "grow=" in out.stdout


# ------------------------------------------------------------- serving
def test_request_id_echo_and_span(tmp_path, obs_log):
    from xgboost_tpu.serving import run_server
    bst, X, _ = _train(seed=5)
    path = str(tmp_path / "m.bin")
    bst.save_model(path)
    srv = run_server(path, port=0, min_bucket=8, max_bucket=32,
                     max_wait_ms=1, poll_sec=0, warmup=False,
                     quiet=True, block=False)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = ",".join("0.5" for _ in range(6)).encode()
        req = urllib.request.Request(base + "/predict", data=body,
                                     method="POST")
        req.add_header("X-Request-Id", "trace-me-123")
        resp = urllib.request.urlopen(req)
        assert resp.headers["X-Request-Id"] == "trace-me-123"
        json.load(resp)
        # a request WITHOUT the header still gets a generated id echoed
        resp2 = urllib.request.urlopen(urllib.request.Request(
            base + "/predict", data=body, method="POST"))
        assert resp2.headers["X-Request-Id"]
        # prometheus content type on serving /metrics too
        m = urllib.request.urlopen(base + "/metrics")
        assert m.headers["Content-Type"] == \
            "text/plain; version=0.0.4; charset=utf-8"
        h = json.load(urllib.request.urlopen(base + "/healthz"))
        assert h["uptime_seconds"] >= 0 and h["model_version"] == 1
    finally:
        srv.shutdown()
    recs = _records(obs_log)
    spans = [r for r in recs if r["name"] == "serve.request"
             and r["trace"] == "trace-me-123"]
    assert len(spans) == 1
    sp = spans[0]
    assert sp["attrs"]["request_id"] == "trace-me-123"
    assert sp["attrs"]["status"] == 200 and sp["attrs"]["rows"] == 1
    # the device batch span names the request it coalesced
    batches = [r for r in recs if r["name"] == "serve.batch"]
    assert any("trace-me-123" in b["attrs"].get("request_ids", [])
               for b in batches)


# ------------------------------------------------------------- tooling
def test_obs_report_selftest():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--selftest"], capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "obs_report selftest: OK" in out.stdout


def test_obs_is_the_one_import_path():
    """The ``xgboost_tpu.profiling`` shim is gone (PR 28): the package
    boundary of the metric primitives and the profiler is ``obs``."""
    from xgboost_tpu.obs import (Counter, Gauge,  # noqa: F401
                                 Histogram, ReliabilityMetrics,
                                 RoundProfiler, ServingMetrics,
                                 reliability_metrics)
    from xgboost_tpu.obs.profiler import RoundProfiler as ObsRP
    assert RoundProfiler is ObsRP
    assert reliability_metrics() is obs.reliability_metrics()
    with pytest.raises(ImportError):
        import xgboost_tpu.profiling  # noqa: F401


# ------------------------------------------------------ multi-process
@pytest.mark.skipif(
    not hasattr(__import__("jax").sharding, "AxisType"),
    reason="jax too old for mesh axis types (all mesh paths unavailable)")
def test_mp_comm_stats_per_rank(tmp_path):
    """Acceptance: per-rank collective stats across REAL processes —
    nonzero allreduce count/bytes/seconds per round, the count matching
    the mock seam, and cross-worker aggregation via the mesh
    collective."""
    rng = np.random.RandomState(0)
    X = rng.rand(512, 6).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0.8).astype(np.float32)
    train = tmp_path / "train.svm"
    with open(train, "w") as f:
        for row, label in zip(X, y):
            feats = " ".join(f"{j}:{v:.6f}" for j, v in enumerate(row))
            f.write(f"{label:g} {feats}\n")
    prefix = str(tmp_path / "comm")
    n_rounds = 3
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "xgboost_tpu.launch", "-n", "2",
           "--local-devices", "2", "--",
           sys.executable, os.path.join(REPO, "tests",
                                        "mp_comm_worker.py"),
           str(train), prefix, str(n_rounds)]
    r = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    reports = []
    for rank in (0, 1):
        with open(f"{prefix}.rank{rank}.json") as f:
            reports.append(json.load(f))
    for rep in reports:
        tot = rep["totals"]["allreduce"]
        # count matches the number of collective calls the mock seam
        # recorded in that process
        assert tot["count"] == rep["mock_calls"] == n_rounds
        assert tot["bytes"] > 0 and tot["seconds"] > 0
        for rnd in range(n_rounds):
            per = rep["per_round"][str(rnd)]["allreduce"]
            assert per["count"] == 1
            assert per["bytes"] > 0 and per["seconds"] > 0
        # per-rank export: the rendered registry carries the families
        for fam in ("xgbtpu_comm_allreduce_total",
                    "xgbtpu_comm_allreduce_bytes_total",
                    "xgbtpu_comm_allreduce_seconds_total"):
            assert fam in rep["metrics_text"]
    # aggregation across workers used the mesh collective and sums the
    # per-rank totals
    agg = reports[0]["aggregated"]["allreduce"]
    assert agg["count"] == sum(
        rep["totals"]["allreduce"]["count"] for rep in reports)
