"""Capture + summarize a device trace of the fused binary round.

Trains the bench workload (higgs-1M, depth 6) for a warmup + a traced
30-round fused launch, then parses the xplane protobuf with
tensorboard_plugin_profile and prints the top device ops by self time.
This is the measurement tool behind the pre-round "where do the
milliseconds go" tables (deleted in PR 22; ROADMAP S1 replaces it).

Usage: python tools/trace_round.py [workload]   (binary | multiclass | rank)
"""
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np  # noqa: E402
import jax  # noqa: E402

import xgboost_tpu as xgb  # noqa: E402
from bench import make_higgs_like  # noqa: E402

N_R = 30


def build(workload):
    if workload == "binary":
        X, y = make_higgs_like(1_000_000)
        d = xgb.DMatrix(X, label=y)
        params = {"objective": "binary:logistic", "max_depth": 6,
                  "eta": 0.1}
    elif workload == "multiclass":
        rng = np.random.RandomState(0)
        X = rng.rand(200_000, 28).astype(np.float32)
        y = (X[:, 0] * 6).astype(np.int32) % 6
        d = xgb.DMatrix(X, label=y)
        params = {"objective": "multi:softmax", "num_class": 6,
                  "max_depth": 6, "eta": 0.1}
    else:
        rng = np.random.RandomState(0)
        n, gs = 1_000_000, 100
        X = rng.rand(n, 28).astype(np.float32)
        y = (rng.rand(n) * 4).astype(np.int32).astype(np.float32)
        d = xgb.DMatrix(X, label=y, group=[gs] * (n // gs))
        params = {"objective": "rank:ndcg", "max_depth": 6, "eta": 0.1}
    return d, params


def barrier(b, d):
    m = b._cache[id(d)].margin
    jax.block_until_ready(m)


def main():
    workload = sys.argv[1] if len(sys.argv) > 1 else "binary"
    d, params = build(workload)
    bst = xgb.Booster(params, cache=[d])
    bst.update(d, 0)
    bst.update_many(d, 1, N_R - 1)
    barrier(bst, d)

    trace_dir = tempfile.mkdtemp(prefix="xgtpu_trace_")
    bst2 = xgb.Booster(params, cache=[d])
    bst2.update(d, 0)
    barrier(bst2, d)
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    bst2.update_many(d, 1, N_R - 1)
    barrier(bst2, d)
    dt = time.perf_counter() - t0
    jax.profiler.stop_trace()
    print(f"{workload}: {(N_R - 1) / dt:.2f} rounds/s "
          f"({dt / (N_R - 1) * 1e3:.2f} ms/round traced)")

    xs = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                   recursive=True)
    assert xs, f"no xplane under {trace_dir}"
    # NOTE use the xprof package, NOT tensorboard_plugin_profile (its
    # generated protos predate the installed protobuf and crash)
    from xprof.convert import raw_to_tool_data
    data, _ = raw_to_tool_data.xspace_to_tool_data(xs, "hlo_stats", {})
    tbl = json.loads(data) if isinstance(data, (str, bytes)) else data
    t = tbl[0] if isinstance(tbl, list) else tbl
    cols = [c["id"] for c in t["cols"]]
    rows = [dict(zip(cols, [c.get("v") for c in r["c"]]))
            for r in t["rows"]]
    rows.sort(key=lambda r: -float(r.get("total_self_time") or 0))
    tot = sum(float(r.get("total_self_time") or 0) for r in rows)
    print(f"device self-time total: {tot / 1e3:.1f} ms "
          f"({tot / 1e3 / (N_R - 1):.2f} ms/round)")
    for r in rows[:25]:
        us = float(r.get("total_self_time") or 0)
        print(f"  {us / (N_R - 1):8.1f} us/round  {us / tot * 100:5.1f}%  "
              f"{str(r.get('category'))[:14]:14s} "
              f"{str(r.get('hlo_op_expression'))[:110]}")
    print("trace dir:", trace_dir)


if __name__ == "__main__":
    main()
