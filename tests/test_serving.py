"""Serving subsystem tests (xgboost_tpu.serving; design in SERVING.md).

Acceptance criteria covered here:
(a) engine predictions bitwise-equal to ``Learner.predict`` for every
    shape bucket (and at bucket boundaries / beyond the top bucket);
(b) after warmup, serving 100 mixed-size requests triggers ZERO new
    compiles — asserted via the engine's own compile counter AND via
    ``jax.monitoring`` backend-compile events (the XLA-level truth);
(c) hot-reload swaps models without dropping or corrupting in-flight
    requests (every concurrent response bit-matches exactly one of the
    two models — never a mixture).
"""

import json
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.serving import (MicroBatcher, ModelRegistry, PredictEngine,
                                 QueueFull, power_of_two_buckets, run_server)

# one process-global compile-event collector: jax.monitoring has no
# unregister, so tests read deltas of this list instead
_COMPILE_EVENTS = []
jax.monitoring.register_event_duration_secs_listener(
    lambda *a, **k: _COMPILE_EVENTS.append(a[0])
    if "backend_compile" in a[0] else None)


def _n_compiles() -> int:
    return len(_COMPILE_EVENTS)


def _train(seed=0, rounds=5, **params):
    rng = np.random.RandomState(seed)
    X = rng.rand(300, 6).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    p = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.4,
         "silent": 1, "seed": seed, **params}
    bst = xgb.train(p, xgb.DMatrix(X, label=y), rounds)
    return bst, X, y


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    bst, X, y = _train()
    path = str(tmp_path_factory.mktemp("serving") / "model.bin")
    bst.save_model(path)
    return bst, X, y, path


# --------------------------------------------------------------- engine
def test_engine_bitwise_parity_all_buckets(model):
    bst, X, _, path = model
    eng = PredictEngine(path, min_bucket=8, max_bucket=64)
    assert eng.buckets == [8, 16, 32, 64]
    rng = np.random.RandomState(1)
    # every bucket size, both boundaries, plus 1 row and a non-boundary
    sizes = sorted({1, 5, 7, 8, 9, 15, 16, 31, 32, 63, 64})
    for n in sizes:
        Xq = rng.rand(n, 6).astype(np.float32)
        ref = bst.predict(xgb.DMatrix(Xq))
        got = eng.predict(Xq)
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert np.array_equal(got, ref), f"n={n} diverged"
        # margins too (output_margin skips the transform)
        refm = bst.predict(xgb.DMatrix(Xq), output_margin=True)
        assert np.array_equal(eng.predict(Xq, output_margin=True), refm)


def test_engine_chunks_beyond_top_bucket(model):
    bst, _, _, path = model
    eng = PredictEngine(path, min_bucket=8, max_bucket=32)
    rng = np.random.RandomState(2)
    Xq = rng.rand(101, 6).astype(np.float32)  # 32+32+32+5 chunks
    assert np.array_equal(eng.predict(Xq), bst.predict(xgb.DMatrix(Xq)))


def test_engine_multiclass_parity():
    rng = np.random.RandomState(3)
    X = rng.rand(120, 5).astype(np.float32)
    y = rng.randint(0, 3, 120).astype(np.float32)
    bst = xgb.train({"objective": "multi:softprob", "num_class": 3,
                     "max_depth": 3, "silent": 1}, xgb.DMatrix(X, label=y), 3)
    eng = PredictEngine(bst, min_bucket=8, max_bucket=32)
    Xq = rng.rand(11, 5).astype(np.float32)
    ref = bst.predict(xgb.DMatrix(Xq))
    got = eng.predict(Xq)
    assert got.shape == (11, 3)
    assert np.array_equal(got, ref)
    # empty batches keep the objective's output shape (softprob: (0, K))
    assert eng.predict(np.zeros((0, 5), np.float32)).shape == (0, 3)


def test_engine_empty_batch_shapes(model):
    bst, _, _, path = model
    eng = PredictEngine(path, min_bucket=8, max_bucket=32)
    empty = np.zeros((0, 6), np.float32)
    assert eng.predict(empty).shape == (0,)  # binary: squeezed like n>0
    assert eng.predict(empty, output_margin=True).shape == (0,)


def test_engine_missing_and_narrow_rows(model):
    """NaN features and fewer-columns-than-model inputs bin like the
    learner path (missing -> bin 0)."""
    bst, _, _, path = model
    eng = PredictEngine(path, min_bucket=8, max_bucket=32)
    rng = np.random.RandomState(4)
    Xq = rng.rand(10, 6).astype(np.float32)
    Xq[Xq < 0.2] = np.nan
    assert np.array_equal(eng.predict(Xq), bst.predict(xgb.DMatrix(Xq)))
    narrow = rng.rand(6, 4).astype(np.float32)  # model has 6 features
    assert np.array_equal(eng.predict(narrow),
                          bst.predict(xgb.DMatrix(narrow, num_col=6)))


def test_zero_recompiles_after_warmup(model):
    """Acceptance (b): 100 mixed-size requests after warmup compile
    NOTHING — engine counter and XLA backend-compile events both."""
    _, _, _, path = model
    eng = PredictEngine(path, min_bucket=8, max_bucket=64, warmup=True)
    assert eng.num_compiled == len(eng.buckets)
    rng = np.random.RandomState(5)
    sizes = rng.randint(1, 65, size=100)
    c0, e0 = eng.compile_count, _n_compiles()
    for n in sizes:
        eng.predict(rng.rand(n, 6).astype(np.float32))
    assert eng.compile_count - c0 == 0
    assert _n_compiles() - e0 == 0, "steady-state request recompiled"


def test_recompile_guard_reproduces_zero_steady_state(model,
                                                      recompile_guard):
    """The generalized checker (xgboost_tpu.analysis.runtime, surfaced
    as the conftest ``recompile_guard`` fixture) reproduces acceptance
    (b) without this module's bespoke listener plumbing — the form any
    future test should use to pin a compile budget."""
    _, _, _, path = model
    eng = PredictEngine(path, min_bucket=8, max_bucket=64, warmup=True)
    rng = np.random.RandomState(11)
    queries = [rng.rand(n, 6).astype(np.float32)
               for n in rng.randint(1, 65, size=50)]
    with recompile_guard.expect(0):
        for Xq in queries:
            eng.predict(Xq)


def test_warmup_does_not_pollute_row_counters(model):
    """Warmup rows are synthetic: rows_total/padded_rows_total must stay
    at zero (dashboards count caller-supplied rows), while
    compiles_total records the warmup's compiles."""
    from xgboost_tpu.obs import ServingMetrics
    _, _, _, path = model
    m = ServingMetrics()
    eng = PredictEngine(path, min_bucket=8, max_bucket=32, metrics=m,
                        warmup=True)
    assert m.rows.value == 0
    assert m.padded_rows.value == 0
    assert m.compiles.value == len(eng.buckets)
    eng.predict(np.zeros((3, 6), np.float32))
    assert m.rows.value == 3
    assert m.padded_rows.value == 5  # padded up to the 8-row bucket


def test_engine_rejects_gblinear():
    rng = np.random.RandomState(6)
    X = rng.rand(60, 4).astype(np.float32)
    bst = xgb.train({"booster": "gblinear", "objective": "reg:linear",
                     "silent": 1}, xgb.DMatrix(X, label=X[:, 0]), 2)
    with pytest.raises(NotImplementedError):
        PredictEngine(bst)


def test_bucket_ladder():
    assert power_of_two_buckets(8, 64) == [8, 16, 32, 64]
    assert power_of_two_buckets(1, 1) == [1]
    # max_bucket is a HARD memory cap: never exceeded
    assert power_of_two_buckets(8, 100) == [8, 16, 32, 64]
    assert power_of_two_buckets(9, 10) == [10]  # no pow2 fits the range
    with pytest.raises(ValueError):
        power_of_two_buckets(16, 8)


# -------------------------------------------------------------- batcher
def test_batcher_coalesces_concurrent_requests():
    calls = []

    def predict_fn(X, output_margin=False):
        calls.append(X.shape[0])
        time.sleep(0.01)
        return X[:, 0].copy()

    b = MicroBatcher(predict_fn, max_batch_rows=100, max_wait_ms=50,
                     max_queue_rows=1000)
    try:
        results = [None] * 6
        barrier = threading.Barrier(6)

        def worker(i):
            barrier.wait()
            Xi = np.full((2, 3), float(i), np.float32)
            results[i] = b.submit(Xi)

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # every caller got ITS OWN rows back, in order
        for i, r in enumerate(results):
            assert np.array_equal(r, np.full(2, float(i), np.float32))
        # 6 near-simultaneous requests coalesced into fewer device calls
        assert len(calls) < 6, f"no coalescing: {calls}"
        assert sum(calls) == 12
    finally:
        b.close()


def test_batcher_backpressure_queuefull():
    from xgboost_tpu.obs import ServingMetrics
    release = threading.Event()
    metrics = ServingMetrics()

    def predict_fn(X, output_margin=False):
        release.wait(5.0)
        return np.zeros(X.shape[0], np.float32)

    b = MicroBatcher(predict_fn, max_batch_rows=4, max_wait_ms=1,
                     max_queue_rows=10, metrics=metrics)
    try:
        t = threading.Thread(target=lambda: b.submit(np.zeros((4, 2))))
        t.start()
        time.sleep(0.05)  # worker picked up the first batch and blocked
        t2 = threading.Thread(target=lambda: b.submit(np.zeros((8, 2))))
        t2.start()
        time.sleep(0.05)  # 8 rows queued
        with pytest.raises(QueueFull):
            b.submit(np.zeros((5, 2)))  # 8 + 5 > 10 -> reject, not buffer
        # "requests received" includes the rejected one (reject ratio =
        # rejected/requests must stay <= 1)
        assert metrics.requests.value == 3
        assert metrics.rejected.value == 1
        release.set()
        t.join(5.0)
        t2.join(5.0)
    finally:
        release.set()
        b.close()


def test_batcher_admits_oversized_request_when_idle():
    """A single request bigger than max_queue_rows must not 503 forever:
    it is admitted when nothing is queued (the engine chunks it)."""
    def predict_fn(X, output_margin=False):
        return X[:, 0].copy()

    b = MicroBatcher(predict_fn, max_batch_rows=8, max_wait_ms=1,
                     max_queue_rows=10)
    try:
        big = np.arange(50, dtype=np.float32).reshape(25, 2)
        assert np.array_equal(b.submit(big), big[:, 0])
    finally:
        b.close()


def test_batcher_error_propagates_to_all_callers():
    def predict_fn(X, output_margin=False):
        raise RuntimeError("boom")

    b = MicroBatcher(predict_fn, max_wait_ms=1)
    try:
        with pytest.raises(RuntimeError, match="boom"):
            b.submit(np.zeros((2, 2)))
    finally:
        b.close()


def test_batcher_tenant_weighted_round_robin():
    """A heavy tenant's queued burst no longer serves ahead of a light
    tenant that arrived later: dequeues interleave by smooth WRR
    (weight 2 vs 1 → exactly 2:1), and the per-tenant dequeue counter
    accounts every pop."""
    gate = threading.Event()
    order = []

    def predict_fn(X, output_margin=False):
        gate.wait(5.0)
        return X[:, 0].copy()

    # max_batch_rows == one request's rows: every dequeue is its own
    # batch, so the service order IS the dequeue order
    b = MicroBatcher(predict_fn, max_batch_rows=2, max_wait_ms=1,
                     max_queue_rows=1000)
    b.set_tenant_weight("heavy", 2.0)
    orig = b._next_request

    def spy():
        req = orig()
        order.append(req.tenant)
        return req

    b._next_request = spy
    try:
        def worker(tenant):
            b.submit(np.zeros((2, 3), np.float32), tenant=tenant,
                     timeout=10)

        warm = threading.Thread(target=worker, args=("warm",))
        warm.start()
        time.sleep(0.1)  # worker now blocked in the warm flush
        ts = [threading.Thread(target=worker, args=("heavy",))
              for _ in range(6)]
        for t in ts:
            t.start()
        time.sleep(0.1)  # the whole heavy burst queued first...
        tl = [threading.Thread(target=worker, args=("light",))
              for _ in range(3)]
        for t in tl:
            t.start()
        time.sleep(0.1)  # ...then the light one, all behind the gate
        gate.set()
        for t in [warm] + ts + tl:
            t.join()
    finally:
        b.close()
    assert order[0] == "warm"
    # smooth WRR at weights (2, 1): heavy, light, heavy, heavy, ...
    assert order[1:] == ["heavy", "light", "heavy", "heavy", "light",
                         "heavy", "heavy", "light", "heavy"]
    from xgboost_tpu.obs.metrics import tenant_dequeues
    rendered = tenant_dequeues().render()
    assert 'xgbtpu_batcher_tenant_dequeues_total{model="heavy"}' in rendered
    assert 'xgbtpu_batcher_tenant_dequeues_total{model="light"}' in rendered


# ------------------------------------------------------------- registry
def test_hot_reload_swap_and_rollback(model, tmp_path):
    bst_a, X, _, _ = model
    path = str(tmp_path / "m.bin")
    bst_a.save_model(path)
    reg = ModelRegistry(path, keep_versions=2, warmup=False, poll_sec=0,
                        min_bucket=8, max_bucket=32)
    Xq = X[:10]
    ref_a = bst_a.predict(xgb.DMatrix(Xq))
    assert reg.version == 1
    assert np.array_equal(reg.predict(Xq), ref_a)
    # byte-identical rewrite is NOT a reload
    bst_a.save_model(path)
    assert reg.check_reload() is False
    assert reg.version == 1
    # a different model IS
    bst_b, _, _ = _train(seed=9, rounds=7, max_depth=2)
    bst_b.save_model(path)
    ref_b = bst_b.predict(xgb.DMatrix(Xq))
    assert reg.check_reload() is True
    assert reg.version == 2
    assert np.array_equal(reg.predict(Xq), ref_b)
    # instant rollback to the still-warm previous engine
    assert reg.rollback() is True
    assert np.array_equal(reg.predict(Xq), ref_a)
    # the rollback sticks: the unchanged on-disk file does not re-load
    assert reg.check_reload() is False
    # rollback is reversible: the swapped-out engine went onto the ring,
    # so a second rollback toggles back to model B
    assert reg.rollback() is True
    assert np.array_equal(reg.predict(Xq), ref_b)
    # keep_versions=0 disables the ring entirely
    reg0 = ModelRegistry(path, keep_versions=0, warmup=False, poll_sec=0,
                         min_bucket=8, max_bucket=32)
    assert reg0.rollback() is False


def test_hot_reload_under_concurrent_requests(model, tmp_path):
    """Acceptance (c): requests racing a model swap each get a response
    bit-matching exactly ONE model — old or new, never a mixture, never
    an error."""
    bst_a, X, _, _ = model
    path = str(tmp_path / "m.bin")
    bst_a.save_model(path)
    reg = ModelRegistry(path, warmup=False, poll_sec=0,
                        min_bucket=8, max_bucket=32)
    batcher = MicroBatcher(reg.predict, max_batch_rows=64, max_wait_ms=1,
                           max_queue_rows=100_000)
    bst_b, _, _ = _train(seed=11, rounds=6, max_depth=2)
    Xq = X[:7]
    ref_a = bst_a.predict(xgb.DMatrix(Xq))
    ref_b = bst_b.predict(xgb.DMatrix(Xq))
    assert not np.array_equal(ref_a, ref_b)

    stop = threading.Event()
    outputs, errors = [], []

    def hammer():
        while not stop.is_set():
            try:
                outputs.append(batcher.submit(Xq, timeout=10.0))
            except BaseException as e:  # noqa: BLE001 — recorded, asserted
                errors.append(e)

    try:
        ts = [threading.Thread(target=hammer) for _ in range(4)]
        for t in ts:
            t.start()
        time.sleep(0.2)           # requests flowing on model A
        bst_b.save_model(path)
        assert reg.check_reload()  # load + warm + swap, mid-traffic
        # a submit AFTER the swap must see model B (batches resolve the
        # engine at flush time) — deterministic, no timing window
        post_swap = batcher.submit(Xq, timeout=30.0)
        stop.set()
        for t in ts:
            t.join(10.0)
    finally:
        stop.set()
        batcher.close()
    assert not errors, f"in-flight requests failed: {errors[:3]}"
    assert np.array_equal(post_swap, ref_b)
    assert post_swap.model_version == 2  # tagged with the model that RAN
    assert len(outputs) > 3
    n_a = sum(bool(np.array_equal(o, ref_a)) for o in outputs)
    n_b = sum(bool(np.array_equal(o, ref_b)) for o in outputs)
    assert n_a + n_b == len(outputs), "a response matched NEITHER model"
    assert n_a > 0, "no request was served by the old model"
    # every response's version tag names the model that produced it
    for o in outputs:
        expect = 1 if np.array_equal(o, ref_a) else 2
        assert o.model_version == expect


# ----------------------------------------------------------------- http
def test_http_roundtrip_ephemeral_port(model, tmp_path):
    bst, X, _, _ = model
    path = str(tmp_path / "m.bin")
    bst.save_model(path)
    srv = run_server(path, port=0, min_bucket=8, max_bucket=32,
                     max_wait_ms=1, poll_sec=0, warmup=False,
                     quiet=True, block=False)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        Xq = np.round(X[:5], 6)
        body = "\n".join(",".join(f"{v:.6f}" for v in row)
                         for row in Xq).encode()
        resp = json.load(urllib.request.urlopen(urllib.request.Request(
            base + "/predict", data=body, method="POST")))
        ref = bst.predict(xgb.DMatrix(Xq))
        assert resp["rows"] == 5 and resp["model_version"] == 1
        assert np.allclose(resp["predictions"], ref, atol=1e-6)
        # libsvm body, same rows -> same predictions
        lib = "\n".join(
            "1 " + " ".join(f"{j}:{v:.6f}" for j, v in enumerate(row))
            for row in Xq).encode()
        resp2 = json.load(urllib.request.urlopen(urllib.request.Request(
            base + "/predict?format=libsvm", data=lib, method="POST")))
        assert resp2["predictions"] == resp["predictions"]
        # output_margin passthrough
        respm = json.load(urllib.request.urlopen(urllib.request.Request(
            base + "/predict?output_margin=1", data=body, method="POST")))
        refm = bst.predict(xgb.DMatrix(Xq), output_margin=True)
        assert np.allclose(respm["predictions"], refm, atol=1e-6)
        # healthz + metrics
        h = json.load(urllib.request.urlopen(base + "/healthz"))
        assert h["status"] == "ok" and h["model_version"] == 1
        mtext = urllib.request.urlopen(base + "/metrics").read().decode()
        for metric in ("xgbtpu_serving_requests_total",
                       "xgbtpu_serving_batch_rows_bucket",
                       "xgbtpu_serving_padded_rows_total",
                       "xgbtpu_serving_queue_rows",
                       "xgbtpu_serving_latency_seconds_bucket",
                       "xgbtpu_serving_latency_p99_seconds",
                       "xgbtpu_serving_model_version"):
            assert metric in mtext, f"{metric} missing from /metrics"
        # bad request -> 400, unknown route -> 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/predict", data=b"", method="POST"))
        assert ei.value.code == 400
        # client-input error (too many columns) -> 400, not 500
        wide = ",".join(["0.5"] * 9).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/predict", data=wide, method="POST"))
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/nope")
        assert ei.value.code == 404
        # keep-alive hygiene: a POST with a body on a side route must
        # not desync the reused connection (body fully drained)
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", srv.port)
        conn.request("POST", "/-/reload", body=b'{"force": true}')
        r1 = conn.getresponse()
        r1.read()
        assert r1.status == 200
        conn.request("POST", "/predict", body=body)
        r2 = conn.getresponse()
        out = json.loads(r2.read())
        assert r2.status == 200 and out["rows"] == 5
        conn.close()
    finally:
        srv.shutdown()


# ----------------------------------------------------- satellite fixes
def test_predict_accepts_plain_ndarray(model):
    """Satellite: Learner.predict auto-wraps 2-D arrays (and jax arrays
    and nested lists) into a transient DMatrix."""
    bst, X, _, _ = model
    ref = bst.predict(xgb.DMatrix(X[:20]))
    assert np.array_equal(bst.predict(X[:20]), ref)
    import jax.numpy as jnp
    assert np.array_equal(bst.predict(jnp.asarray(X[:20])), ref)
    assert np.array_equal(bst.predict([list(map(float, r))
                                       for r in X[:20]]), ref)


def test_sklearn_predict_uses_autowrap():
    rng = np.random.RandomState(8)
    X = rng.rand(100, 4).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(int)
    clf = xgb.XGBClassifier(n_estimators=3, silent=True).fit(X, y)
    assert clf._predict_data(X) is X  # no DMatrix re-wrap on the hot path
    assert (clf.predict(X) == y).mean() > 0.9
    # a non-NaN missing marker still wraps explicitly
    clf2 = xgb.XGBClassifier(n_estimators=3, silent=True, missing=-999.0)
    clf2.fit(X, y)
    assert isinstance(clf2._predict_data(X), xgb.DMatrix)


def test_ntree_limit_clamps_not_raises(model):
    """Satellite: ntree_limit beyond the trained rounds clamps to the
    full ensemble (hot-reloaded smaller model vs stale request param)."""
    bst, X, _, _ = model
    full = bst.predict(xgb.DMatrix(X[:10]))
    over = bst.predict(xgb.DMatrix(X[:10]), ntree_limit=10_000)
    assert np.array_equal(over, full)
    # direct model-layer call too
    stack, group = bst.gbtree._stack(10_000)
    assert stack.feature.shape[0] == bst.gbtree.num_trees
    # negative clamps to "all trees" instead of producing an empty stack
    stack_neg, _ = bst.gbtree._stack(-3)
    assert stack_neg.feature.shape[0] == bst.gbtree.num_trees


def test_predict_incremental_empty_is_noop(model):
    bst, X, _, _ = model
    import jax.numpy as jnp
    margin = jnp.zeros((4, 1), jnp.float32)
    out = bst.gbtree.predict_incremental(jnp.zeros((4, 6), jnp.uint8),
                                         margin, [])
    assert out is margin


def test_cli_usage_lists_serve_params(capsys):
    from xgboost_tpu.cli import main as cli_main
    assert cli_main([]) == 0
    out = capsys.readouterr().out
    assert "serve" in out
    for name in ("serve_port", "serve_max_batch_rows", "serve_max_wait_ms",
                 "serve_poll_sec", "serve_keep_versions"):
        assert name in out, f"{name} missing from CLI usage"


def test_serving_main_parser_builds():
    from xgboost_tpu.serving.__main__ import _build_parser
    args = _build_parser().parse_args(
        ["--model", "m.bin", "--port", "0", "--max-wait-ms", "5"])
    assert args.model == "m.bin" and args.port == 0
    assert args.max_wait_ms == 5.0
