"""Round-throughput harness: best-of-3 fused 50-round run on higgs-1M.

Used for separate-process A/B of grower formulations: check out / edit
the variant under test, run this once per arm, compare rounds/s
(separate processes — a jitted variant choice inside one process hits
the first compilation's cache).  Historical result (pre-round record,
another machine): an MXU one-hot router tied the default gather router,
ruling routing gathers out as a bottleneck; the experimental branch was
deleted rather than committed.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax  # noqa: E402
from bench import make_higgs_like  # noqa: E402
import xgboost_tpu as xgb  # noqa: E402

label = sys.argv[1] if len(sys.argv) > 1 else "default"
X, y = make_higgs_like(1_000_000)
dtrain = xgb.DMatrix(X, label=y)
params = {"objective": "binary:logistic", "max_depth": 6, "eta": 0.1}


def barrier(b):
    m = b._cache[id(dtrain)].margin
    jax.block_until_ready(m)


N_R = 50
w = xgb.Booster(params, cache=[dtrain])
w.update(dtrain, 0)
w.update_many(dtrain, 1, N_R - 1)
barrier(w)
del w
best = 1e9
for _ in range(3):
    b = xgb.Booster(params, cache=[dtrain])
    b.update(dtrain, 0)
    barrier(b)
    t0 = time.perf_counter()
    b.update_many(dtrain, 1, N_R - 1)
    barrier(b)
    best = min(best, time.perf_counter() - t0)
print(f"{label:12s}: {(N_R - 1) / best:6.2f} rounds/s (best of 3)")
