"""Self-check of the trace reduction (`tracered.py` and the trace
readers) against a small trace recorded on the chip and kept in
`testdata/`: PR 26's probe, two boosting rounds of 200,000 x 28 at 64
bins in one `jit__scan_rounds_impl` dispatch on a TPU v5 lite, followed
by the host-side AUC.  The expected numbers were read from the trace by
hand (sums of `device_duration_ps` over the `XLA Ops` line).  Runs on
the CPU and uses no chip:

    python3 benchmark/selfcheck_trace.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
import tracered  # noqa: E402

TRACE = os.path.join(HERE, "testdata", "train_2rounds_200k_x28.xplane.pb")
SCAN = "jit__scan_rounds_impl"
EXPECT = {
    "window_s": 0.016283048, "busy_s": 0.004581597,
    "kernel_s": 0.003297576, "other_s": 0.001276047, "scan_modules": 1,
    "top_op": "%grow_tree.47 s32[1,1792,64] [pallas]",
    "top_gap": "$metrics.py:87 _value_runs",
    # through the readers, as two rounds in the window
    "hist_ms_per_round": 1.648788, "xla_ms_per_round": 0.6380235,
    "host_gap_ms_per_round": 5.8507255, "device_idle_pct": 71.8628,
    "rounds_per_dispatch": 2.0,
}


def close(a, b, rel=1e-5):
    return abs(a - b) <= rel * max(abs(a), abs(b))


def main() -> int:
    tr = tracered.load(TRACE)
    got = {
        "window_s": tracered.window_s(tr), "busy_s": tracered.busy_s(tr),
        "kernel_s": tracered.op_seconds(tr, inside=SCAN, kernels=True),
        "other_s": tracered.op_seconds(tr, inside=SCAN, kernels=False),
        "scan_modules": len(tracered.module_events(tr, SCAN)),
        "top_op": tracered.top_device_ops(tr, 1)[0][0],
        "top_gap": tracered.idle_gaps(tr, 1)[0][0],
    }
    ctx = {"trace": tr, "counts": {"rounds_in_window": 2}, "spans": {},
           "notes": {}, "memory": {}, "shape": {}, "peaks": None}
    bench = {"per_layer": [{"name": n, "unit": ""} for n in EXPECT
                           if os.path.exists(os.path.join(
                               HERE, "metrics", f"{n}.json"))]}
    got.update({k: v["value"] for k, v in
                run.read_metrics(bench, "per_layer", "", ctx).items()})
    bad = 0
    for k, want in EXPECT.items():
        ok = (got.get(k) == want if isinstance(want, (str, int))
              else k in got and close(got[k], want))
        print(f"{'ok  ' if ok else 'FAIL'} {k}: {got.get(k)!r} (expected {want!r})")
        bad += not ok
    # nearly all ops ran inside the scan, and the containers were dropped
    every = tracered.op_seconds(tr)
    assert 0.99 * every <= got["kernel_s"] + got["other_s"] <= every
    assert got["busy_s"] <= got["window_s"]
    print("trace reduction:", "FAILED" if bad else "ok")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
