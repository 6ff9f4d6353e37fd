"""Serving micro-benchmark: requests/s and latency quantiles through
the full engine + micro-batcher stack at fixed row counts.

CPU-only (``JAX_PLATFORMS=cpu``), same output shape as the
``BENCH_r*.json`` files::

    python tools/bench_serving.py            # writes BENCH_serving.json

The headline metric is single-row requests/s after warmup (the
latency-bound serving shape); per-size throughput and p50/p99 ride
along, plus a concurrent-clients run that exercises coalescing.
"""

import json
import os
import sys
import threading
import time

# CPU-only harness, pinned — not a default: this process and every
# child it starts inherit the pin.  On a machine whose environment names
# the TPU the parent would otherwise hold the chip that every child
# then wants (one process per chip; ROADMAP S1/R5 bring this to the
# chip one process per device).
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import xgboost_tpu as xgb  # noqa: E402
from xgboost_tpu.obs import ServingMetrics  # noqa: E402
from xgboost_tpu.serving import MicroBatcher, PredictEngine  # noqa: E402

ROWS_PER_REQ = (1, 8, 64, 512)
REQS_PER_SIZE = int(os.environ.get("BENCH_SERVING_REQS", "300"))
N_TRAIN, N_FEAT, ROUNDS = 20_000, 28, 20
CONCURRENT_CLIENTS = 8


def _train_model():
    rng = np.random.RandomState(0)
    X = rng.rand(N_TRAIN, N_FEAT).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.25 * X[:, 2]
         + 0.1 * rng.randn(N_TRAIN) > 0.65).astype(np.float32)
    return xgb.train({"objective": "binary:logistic", "max_depth": 6,
                      "eta": 0.3, "silent": 1},
                     xgb.DMatrix(X, label=y), ROUNDS)


def bench_direct(engine, rng):
    """Engine-only path: one request at a time, per-size stats.  The
    p50/p99 come from the unified metric registry's latency histogram
    (one fresh ``ServingMetrics`` per size), not an ad-hoc sorted-list
    recompute — the bench reports exactly what a scrape would."""
    per_size = {}
    for n in ROWS_PER_REQ:
        metrics = ServingMetrics()
        Xs = [rng.rand(n, N_FEAT).astype(np.float32) for _ in range(32)]
        engine.predict(Xs[0])  # bucket already warm; prime np caches
        t0 = time.perf_counter()
        for i in range(REQS_PER_SIZE):
            s = time.perf_counter()
            engine.predict(Xs[i % len(Xs)])
            metrics.latency.observe(time.perf_counter() - s)
        wall = time.perf_counter() - t0
        q = metrics.quantiles((0.5, 0.99))
        per_size[n] = {
            "requests_per_sec": round(REQS_PER_SIZE / wall, 1),
            "rows_per_sec": round(REQS_PER_SIZE * n / wall, 1),
            "p50_ms": round(q[0.5] * 1e3, 3),
            "p99_ms": round(q[0.99] * 1e3, 3),
        }
    return per_size


def bench_concurrent(engine, rng):
    """Batched path: N client threads hammering one MicroBatcher with
    single-row requests (the coalescing win over bench_direct[1])."""
    metrics = ServingMetrics()
    batcher = MicroBatcher(engine.predict, max_batch_rows=1024,
                           max_wait_ms=1.0, max_queue_rows=1 << 20,
                           metrics=metrics)
    reqs_per_client = REQS_PER_SIZE // 2
    Xs = [rng.rand(1, N_FEAT).astype(np.float32) for _ in range(64)]
    barrier = threading.Barrier(CONCURRENT_CLIENTS + 1)

    def client():
        barrier.wait()
        for i in range(reqs_per_client):
            # the batcher observes each request's latency into
            # metrics.latency; quantiles below read the same histogram
            # the /metrics endpoint renders
            batcher.submit(Xs[i % len(Xs)])

    ts = [threading.Thread(target=client)
          for _ in range(CONCURRENT_CLIENTS)]
    for t in ts:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    total = reqs_per_client * CONCURRENT_CLIENTS
    batcher.close()
    q = metrics.quantiles((0.5, 0.99))
    return {
        "clients": CONCURRENT_CLIENTS,
        "requests_per_sec": round(total / wall, 1),
        "p50_ms": round(q[0.5] * 1e3, 3),
        "p99_ms": round(q[0.99] * 1e3, 3),
        "batches": int(metrics.batches.value),
        "mean_batch_rows": round(total / max(metrics.batches.value, 1), 2),
    }


def main():
    bst = _train_model()
    engine = PredictEngine(bst, min_bucket=8, max_bucket=1024)
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0
    rng = np.random.RandomState(1)

    # traversal-only latency comes from the unified registry's
    # per-chunk histogram (engine times the margin launch into it) —
    # snapshot the count so the bench reports only its own traffic
    from xgboost_tpu.obs.metrics import predict_metrics
    pm = predict_metrics()
    chunk_n0 = pm.chunk_seconds.count

    c0 = engine.compile_count
    per_size = bench_direct(engine, rng)
    concurrent = bench_concurrent(engine, rng)
    assert engine.compile_count == c0, "steady state recompiled!"

    desc = engine.describe()
    out = {
        "metric": "serving_1row_requests_per_sec",
        "value": per_size[1]["requests_per_sec"],
        "unit": (f"req/s (1-row requests, depth6 x {ROUNDS} trees, "
                 f"{N_FEAT} feats, CPU; p99="
                 f"{per_size[1]['p99_ms']}ms)"),
        "backend": os.environ["JAX_PLATFORMS"],  # the pin above
        "warmup_sec": round(warmup_s, 2),
        "buckets": engine.buckets,
        "compile_count": engine.compile_count,
        "steady_state_compiles": engine.compile_count - c0,
        "per_request_rows": {str(k): v for k, v in per_size.items()},
        "concurrent": concurrent,
        # device traversal time per tree chunk (xgbtpu_predict_chunk
        # _seconds), separated from the request latency above — the
        # queueing/transform/HTTP share is the difference
        "traversal": {
            "tree_chunk": desc["tree_chunk"],
            "tree_chunks": desc["tree_chunks"],
            "chunk_p50_ms": round(
                pm.chunk_seconds.quantile(0.5) * 1e3, 3),
            "chunk_p99_ms": round(
                pm.chunk_seconds.quantile(0.99) * 1e3, 3),
            "launches": pm.chunk_seconds.count - chunk_n0,
        },
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_serving.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
