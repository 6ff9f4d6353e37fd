"""End-to-end deadline discipline (reliability/deadline.py; ISSUE 10).

Acceptance criteria covered here:
(a) an EXPIRED request is rejected before any device work, at both the
    router and the replica, counter-verified on
    ``xgbtpu_deadline_rejected_total``;
(b) the router and replica share ONE ``X-Deadline-Ms`` contract: the
    router stamps the REMAINING budget onto the replica hop (never the
    original), and restamps on the retry;
(c) the MicroBatcher drops expired entries pre-dispatch
    (``xgbtpu_deadline_dropped_total``) and the caller sees the typed
    :class:`DeadlineExceeded` (HTTP 504), never a late result;
(d) replica admission-by-service-time: a budget below the bucket's
    observed service EWMA is 504'd up front;
(e) the retry path spends the remaining budget with jittered backoff
    instead of arming a fresh timeout.

All tests are mesh-free (stdlib HTTP + tiny CPU models) — no
``sharding.AxisType`` dependency.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.fleet import FleetRouter
from xgboost_tpu.obs import reliability_metrics
from xgboost_tpu.reliability.deadline import (DEADLINE_HEADER, Deadline,
                                              DeadlineExceeded,
                                              backoff_delay, jittered)
from xgboost_tpu.serving import run_server
from xgboost_tpu.serving.batcher import MicroBatcher


# ------------------------------------------------------------------ unit
def test_deadline_budget_spends_down():
    dl = Deadline(10_000)
    assert not dl.expired()
    assert 0 < dl.remaining() <= 10.0
    r0 = dl.remaining()
    time.sleep(0.02)
    assert dl.remaining() < r0  # monotonic spend-down
    assert Deadline(0).expired()


def test_deadline_header_roundtrip_carries_remaining():
    dl = Deadline(5_000)
    time.sleep(0.05)
    hop = Deadline.from_header(dl.header_value())
    # the hop sees the REMAINING budget, not the original
    assert hop is not None
    assert hop.remaining_ms() <= dl.remaining_ms() + 1.0 < 5_000


@pytest.mark.parametrize("bad", [None, "", "nan-ish", "-5"])
def test_deadline_unparseable_header_means_no_deadline(bad):
    assert Deadline.from_header(bad) is None


def test_jittered_stays_in_band():
    vals = [jittered(1.0) for _ in range(200)]
    assert all(0.8 <= v <= 1.2 for v in vals)
    assert len({round(v, 6) for v in vals}) > 1, "no jitter at all"


def test_backoff_delay_bounded_by_deadline():
    assert backoff_delay(1) <= 0.05
    # an almost-spent budget caps the sleep at a quarter of what's left
    dl = Deadline(40)
    assert backoff_delay(1, deadline=dl) <= dl.remaining() * 0.25 + 1e-6
    assert backoff_delay(3, base=10.0, cap=2.0) <= 2.0


# --------------------------------------------------------------- batcher
def test_batcher_flush_drops_expired_entry_pre_dispatch():
    """(c) the worker's flush skips an expired-deadline entry BEFORE
    dispatch: its rows never reach the predict fn, its caller gets the
    typed error, the drop counts — while live batch-mates still run."""
    rm = reliability_metrics()
    seen_rows = []

    def predict(X, output_margin=False):
        seen_rows.append(int(X.shape[0]))
        return np.zeros(X.shape[0], np.float32)

    b = MicroBatcher(predict, max_wait_ms=1.0, max_batch_rows=8)
    from xgboost_tpu.serving.batcher import _Request
    try:
        base_dropped = rm.deadline_dropped.value
        live = _Request(np.zeros((1, 3), np.float32), False)
        dead = _Request(np.zeros((2, 3), np.float32), False,
                        deadline=Deadline(0))
        with b._lock:
            b._queued_rows += 3
        b._flush([live, dead])
        assert live.done.is_set() and live.error is None
        assert live.result.shape == (1,)
        assert dead.done.is_set()
        assert isinstance(dead.error, DeadlineExceeded)
        assert rm.deadline_dropped.value == base_dropped + 1
        assert seen_rows == [1], "expired rows reached the device fn"
    finally:
        b.close()


def test_batcher_caller_sees_typed_error_when_budget_dies_queued():
    """(c) integration: a caller whose budget dies while its request
    waits behind a parked batch gets DeadlineExceeded (504 upstream),
    never a late result."""
    gate = threading.Event()

    def predict(X, output_margin=False):
        gate.wait(5.0)  # first batch parks the worker
        return np.zeros(X.shape[0], np.float32)

    b = MicroBatcher(predict, max_wait_ms=1.0, max_batch_rows=4)
    try:
        t1 = threading.Thread(
            target=lambda: b.submit(np.zeros((1, 3), np.float32)))
        t1.start()
        time.sleep(0.05)  # worker is now parked inside predict()
        with pytest.raises(DeadlineExceeded):
            b.submit(np.zeros((2, 3), np.float32), deadline=Deadline(80))
        gate.set()
        t1.join(5.0)
    finally:
        gate.set()
        b.close()


def test_batcher_submit_within_budget_succeeds():
    b = MicroBatcher(lambda X, output_margin=False:
                     np.ones(X.shape[0], np.float32), max_wait_ms=0.5)
    try:
        out = b.submit(np.zeros((3, 2), np.float32),
                       deadline=Deadline(10_000))
        assert out.shape == (3,)
    finally:
        b.close()


# --------------------------------------------------------------- replica
def _train_model(path, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(200, 5).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "eta": 0.4, "silent": 1},
                    xgb.DMatrix(X, label=y), 3)
    bst.save_model(path)
    return X


def _post(url, data=b"", headers=None):
    req = urllib.request.Request(url, data=data, method="POST",
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            return e.code, json.loads(raw)
        except ValueError:
            return e.code, {}


@pytest.fixture(scope="module")
def replica(tmp_path_factory):
    d = tmp_path_factory.mktemp("deadline")
    path = str(d / "m.bin")
    X = _train_model(path)
    srv = run_server(path, port=0, min_bucket=8, max_bucket=32,
                     max_wait_ms=1.0, poll_sec=0, warmup=False,
                     quiet=True, block=False)
    yield srv, X
    srv.shutdown()


def _csv(rows):
    return "\n".join(",".join(f"{v:.6f}" for v in row)
                     for row in rows).encode()


def test_replica_rejects_expired_before_any_work(replica):
    """(a) X-Deadline-Ms: 0 -> 504 up front: no rows parsed, no batch
    submitted, counter bumped."""
    srv, X = replica
    rm = reliability_metrics()
    base = rm.deadline_rejected.value
    batches = srv.metrics.batches.value
    st, js = _post(f"http://{srv.host}:{srv.port}/predict",
                   data=_csv(X[:2]), headers={DEADLINE_HEADER: "0"})
    assert st == 504 and js["deadline_exceeded"] is True
    assert rm.deadline_rejected.value == base + 1
    assert srv.metrics.batches.value == batches, "device work was paid"
    # same discipline on the by-id route
    st, js = _post(f"http://{srv.host}:{srv.port}/predict_by_id",
                   data=b'{"ids": ["x"]}', headers={DEADLINE_HEADER: "0"})
    assert st == 504 and js["deadline_exceeded"] is True


def test_replica_generous_deadline_serves_normally(replica):
    srv, X = replica
    st, js = _post(f"http://{srv.host}:{srv.port}/predict",
                   data=_csv(X[:2]),
                   headers={DEADLINE_HEADER: "30000"})
    assert st == 200 and js["rows"] == 2


def test_replica_admission_by_observed_service_time(replica):
    """(d) remaining budget below the bucket's service EWMA -> 504
    BEFORE submit; the estimate recovers as real traffic lands."""
    srv, X = replica
    rm = reliability_metrics()
    # poison the 2-row bucket's estimate: pretend it takes ~10 s
    # (folded in repeatedly — earlier tests seeded a fast EWMA)
    for _ in range(8):
        srv.observe_service(2, 10.0)
    assert srv.service_estimate(2) >= 5.0
    base = rm.deadline_rejected.value
    st, js = _post(f"http://{srv.host}:{srv.port}/predict",
                   data=_csv(X[:2]),
                   headers={DEADLINE_HEADER: "200"})
    assert st == 504 and "service time" in js["error"]
    assert rm.deadline_rejected.value == base + 1
    # deadline-less traffic is never admission-gated, and its real
    # latency pulls the EWMA back down
    for _ in range(40):
        st, _ = _post(f"http://{srv.host}:{srv.port}/predict",
                      data=_csv(X[:2]))
        assert st == 200
    assert srv.service_estimate(2) < 1.0
    st, _ = _post(f"http://{srv.host}:{srv.port}/predict",
                  data=_csv(X[:2]), headers={DEADLINE_HEADER: "5000"})
    assert st == 200


def test_service_estimate_bucketing():
    from xgboost_tpu.serving.http import PredictServer
    assert PredictServer._svc_bucket(1) == 1
    assert PredictServer._svc_bucket(2) == 2
    assert PredictServer._svc_bucket(3) == 4
    assert PredictServer._svc_bucket(1000) == 1024


# ---------------------------------------------------------------- router
class _EchoStub:
    """Stub replica recording the deadline header of every /predict it
    receives; optionally fails the first N requests (retry testing)."""

    def __init__(self):
        self.headers_seen = []
        self.fail_next = 0
        self.delay = 0.0
        stub = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            disable_nagle_algorithm = True

            def log_message(self, *a):
                pass

            def _send(self, code, obj):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                self._send(200, {"status": "ok", "state": "serving",
                                 "model_hash": "stub"})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                stub.headers_seen.append(
                    self.headers.get(DEADLINE_HEADER))
                if stub.delay:
                    time.sleep(stub.delay)
                if stub.fail_next > 0:
                    stub.fail_next -= 1
                    self._send(500, {"error": "injected"})
                    return
                self._send(200, {"predictions": [0.5], "rows": 1,
                                 "model_version": 1})

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.httpd.daemon_threads = True
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def _register(base, rid, stub):
    st, js = _post(base + "/fleet/register",
                   data=json.dumps({"replica_id": rid,
                                    "url": stub.url}).encode())
    assert st == 200, js


def test_router_rejects_expired_and_stamps_remaining_budget():
    """(a)+(b) the router 504s an expired request before any dispatch,
    and stamps the REMAINING (shrunken) budget onto the replica hop."""
    rt = FleetRouter(port=0, hc_sec=0, quiet=True).start()
    base = f"http://{rt.host}:{rt.port}"
    stub = _EchoStub()
    rm = reliability_metrics()
    try:
        _register(base, "r1", stub)
        n_rejected = rm.deadline_rejected.value
        st, js = _post(base + "/predict", data=b"0.5",
                       headers={DEADLINE_HEADER: "0"})
        assert st == 504 and js["deadline_exceeded"] is True
        assert rm.deadline_rejected.value == n_rejected + 1
        assert stub.headers_seen == [], "expired request was dispatched"
        # a live budget is forwarded, shrunk by router time
        st, js = _post(base + "/predict", data=b"0.5",
                       headers={DEADLINE_HEADER: "5000"})
        assert st == 200
        assert len(stub.headers_seen) == 1
        fwd = float(stub.headers_seen[0])
        assert 0 < fwd <= 5000
        # no client deadline + no fleet_deadline_ms default -> no stamp
        st, _ = _post(base + "/predict", data=b"0.5")
        assert st == 200 and stub.headers_seen[1] is None
    finally:
        stub.close()
        rt.shutdown()


def test_router_default_deadline_and_budgeted_retry():
    """(e) fleet_deadline_ms stamps a default budget, and the
    retry-once hop is restamped with what REMAINS after the failed
    first attempt + backoff."""
    rt = FleetRouter(port=0, hc_sec=0, deadline_ms=2000.0,
                     quiet=True).start()
    base = f"http://{rt.host}:{rt.port}"
    ok = _EchoStub()
    bad = _EchoStub()
    bad.fail_next = 10_000
    try:
        _register(base, "a-bad", bad)
        _register(base, "b-ok", ok)
        # both stubs idle -> least-loaded picks "a-bad" first (id
        # tiebreak), fails, retries on "b-ok" with a restamped budget
        st, js = _post(base + "/predict", data=b"0.5")
        assert st == 200, js
        assert len(ok.headers_seen) == 1
        first = float(bad.headers_seen[0])
        second = float(ok.headers_seen[0])
        assert 0 < first <= 2000.0
        assert 0 < second < first, (
            "retry hop did not spend the remaining budget")
    finally:
        ok.close()
        bad.close()
        rt.shutdown()


def test_budget_cut_hop_is_504_and_never_charges_the_breaker():
    """A hop cut short by the request's own budget (deadline-shrunk
    socket timeout) is the REQUEST running out of money, not a replica
    failure: the router answers 504 and the breaker stays closed —
    tight-budget clients must not 503 a healthy replica for everyone
    else."""
    rt = FleetRouter(port=0, hc_sec=0, breaker_failures=2,
                     quiet=True).start()
    base = f"http://{rt.host}:{rt.port}"
    slow = _EchoStub()
    slow.delay = 0.4  # healthy, just slower than the clients' budgets
    try:
        _register(base, "r1", slow)
        for _ in range(4):
            st, js = _post(base + "/predict", data=b"0.5",
                           headers={DEADLINE_HEADER: "120"})
            assert st == 504, js
            assert js["deadline_exceeded"] is True
        members = _get(base + "/fleet/members")["replicas"]
        r1 = [m for m in members if m["replica_id"] == "r1"][0]
        assert r1["breaker"] == "closed", \
            "tight-budget timeouts tripped the breaker"
        assert r1["consecutive_failures"] == 0
        assert r1["outstanding"] == 0  # neutral releases balanced
        # a patient client is still served by the same replica
        st, js = _post(base + "/predict", data=b"0.5",
                       headers={DEADLINE_HEADER: "20000"})
        assert st == 200, js
    finally:
        slow.close()
        rt.shutdown()


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def test_router_deadline_spent_mid_retry_is_504():
    """A first attempt that eats the whole budget leaves nothing to
    retry with: the router answers 504, not a fresh-timeout retry."""
    rt = FleetRouter(port=0, hc_sec=0, quiet=True).start()
    base = f"http://{rt.host}:{rt.port}"
    slow_bad = _EchoStub()
    slow_bad.delay = 0.3
    slow_bad.fail_next = 10_000
    ok = _EchoStub()
    try:
        _register(base, "a-slowbad", slow_bad)
        _register(base, "b-ok", ok)
        st, js = _post(base + "/predict", data=b"0.5",
                       headers={DEADLINE_HEADER: "150"})
        assert st == 504, js
        assert js["deadline_exceeded"] is True
        assert ok.headers_seen == [], "retry fired with a dead budget"
    finally:
        slow_bad.close()
        ok.close()
        rt.shutdown()
