"""A/B: streaming external-memory training with vs without prefetch.

VERDICT r4 Missing #4: the over-budget streaming path (the actual
point of external.py) had no measured throughput and no evidence the
host→device batch staging overlaps compute.  This tool forces the
bench config over budget (XGBTPU_EXT_DEVICE_CACHE_MB=16) and times
rounds/s with the depth-2 background prefetcher
(external._prefetch_to_device — the reference's ThreadBuffer idea,
utils/thread_buffer.h, at the device boundary) against synchronous
staging (XGBTPU_EXT_PREFETCH=0).  A second, larger shape (2M x 100)
scales the streamed volume ~7x to confirm the staging-bound rate
holds at scale.

Run on the real chip: ``python tools/ext_stream_ab.py``.
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def run_case(n, f, rounds, seed, prefetch: bool):
    import xgboost_tpu as xgb
    from xgboost_tpu.external import ExtMemDMatrix
    import bench as B

    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0.65).astype(np.float32)
    cache = os.path.join(tempfile.mkdtemp(prefix="xgbtpu_ab_ext_"), "m")

    def chunks():
        for s in range(0, n, 1 << 18):
            yield X[s:s + (1 << 18)], y[s:s + (1 << 18)]

    d = ExtMemDMatrix(chunks(), cache=cache, page_rows=1 << 18)
    saved = {k: os.environ.get(k) for k in ("XGBTPU_EXT_DEVICE_CACHE_MB",
                                            "XGBTPU_EXT_PREFETCH")}
    os.environ["XGBTPU_EXT_DEVICE_CACHE_MB"] = "16"
    os.environ["XGBTPU_EXT_PREFETCH"] = "1" if prefetch else "0"
    try:
        bst = xgb.Booster({"objective": "binary:logistic", "max_depth": 6,
                           "eta": 0.1, "max_bin": 64}, cache=[d])
        bst.update(d, 0)
        B._barrier_entry(bst, d)
        t0 = time.perf_counter()
        for i in range(1, rounds):
            bst.update(d, i)
        B._barrier_entry(bst, d)
        dt = (time.perf_counter() - t0) / (rounds - 1)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        del d, bst
        import shutil
        shutil.rmtree(os.path.dirname(cache), ignore_errors=True)
    staged_mb = n * f * 7 / 1e6          # 6 levels + delta pass
    return {"rows": n, "feat": f, "s_per_round": dt,
            "rounds_per_sec": 1 / dt,
            "staged_mb_per_sec": staged_mb / dt,
            "prefetch": prefetch}


def main():
    out = []
    for n, f, rounds in ((1_000_000, 28, 4), (2_000_000, 100, 3)):
        for prefetch in (False, True):
            r = run_case(n, f, rounds, seed=3, prefetch=prefetch)
            print(f"{n:>9,} x {f:>3}  prefetch={int(prefetch)}  "
                  f"{r['s_per_round']*1e3:8.1f} ms/round  "
                  f"({r['staged_mb_per_sec']:7.1f} MB/s staged)",
                  file=sys.stderr)
            out.append(r)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
