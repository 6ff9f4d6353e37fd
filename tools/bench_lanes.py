#!/usr/bin/env python
"""Gang-batched tenant-lane micro-benchmark: stacked vs host loop.

Measures the TRAINING-STEP throughput the lane stacker optimizes
(PIPELINE.md "Gang-batched lanes"): N same-shape tenant boosters each
advancing ``rounds`` boosting rounds per cycle, either as N solo fused
dispatches (the ``XGBTPU_LANE_STACK=0`` host loop's boost path) or as
ONE ``_scan_rounds_lanes`` dispatch through the real ``LaneGang``
bucket dispatcher — rendezvous, carry cache, unpack and per-tenant
absorb included.  Gate/publish/ledger fan-out is identical host-side
work in both modes and is deliberately outside the timed region; the
catalog regime this targets is thousands of SMALL tenants, where
per-lane dispatch overhead — not device FLOPs — is the bill.

Writes ``BENCH_lanes.json``::

    JAX_PLATFORMS=cpu python tools/bench_lanes.py

Cells (per lane count N in ``--lanes``):

- ``solo``    — N sequential ``update_many`` calls per cycle (warm).
- ``stacked`` — one ``LaneGang`` bucket dispatch per cycle (warm).

Every cell pins BIT-identity: after the timed cycles, each stacked
booster's ``save_raw()`` bytes must equal its solo twin's, and the
stacked dispatch count per cycle must be 1 regardless of N (the
dispatch-independence acceptance claim).  The committed N=64 cell must
show ``speedup >= 3``; the driver re-checks this in the same container
the numbers were measured in.

Like BENCH_fleet.json, the host ``cpu`` block is recorded: this
container is CPU-only, so the stacked win measured here is the
dispatch-amortization floor — on a TPU the per-dispatch overhead the
stack removes is larger, not smaller.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# CPU-only harness, pinned — not a default: this process and every
# child it starts inherit the pin.  On a machine whose environment names
# the TPU the parent would otherwise hold the chip that every child
# then wants (one process per chip; ROADMAP S1/R5 bring this to the
# chip one process per device).
os.environ["JAX_PLATFORMS"] = "cpu"
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

import numpy as np  # noqa: E402

N_ROWS, N_FEAT, DEPTH, ROUNDS = 64, 4, 2, 2
PARAMS = {"objective": "binary:logistic", "max_depth": DEPTH,
          "eta": 0.3, "silent": 1}


def make_boosters(n):
    import xgboost_tpu as xgb
    out = []
    for i in range(n):
        rng = np.random.RandomState(1000 + i)
        X = rng.rand(N_ROWS, N_FEAT).astype(np.float32)
        y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
        d = xgb.DMatrix(X, label=y)
        out.append((xgb.Booster(dict(PARAMS, seed=1000 + i), [d]), d))
    return out


def bench_solo(n, cycles, warmup):
    lanes = make_boosters(n)
    ts = []
    for c in range(warmup + cycles):
        t0 = time.perf_counter()
        for b, d in lanes:
            b.update_many(d, c * ROUNDS, ROUNDS)
        dt = time.perf_counter() - t0
        if c >= warmup:
            ts.append(dt)
    return lanes, ts


def bench_stacked(n, cycles, warmup):
    from xgboost_tpu.obs import lane_metrics
    from xgboost_tpu.pipeline.lanes import LaneGang, _Arrival, _bucket_of

    lanes = make_boosters(n)
    gang = LaneGang(expected=0)
    lm = lane_metrics()
    ts, dispatches = [], []
    for c in range(warmup + cycles):
        d0 = lm.dispatches.value
        t0 = time.perf_counter()
        arrs = []
        for i, (b, d) in enumerate(lanes):
            spec, why = b.fused_lane_spec(d, c * ROUNDS, ROUNDS)
            assert spec is not None, f"lane {i} declined stacking: {why}"
            arrs.append(_Arrival(f"lane{i:03d}", spec, lambda it: None))
        gang._dispatch_bucket(_bucket_of(arrs[0].spec), arrs)
        dt = time.perf_counter() - t0
        for a in arrs:
            assert a.exc is None, a.exc
        if c >= warmup:
            ts.append(dt)
            dispatches.append(lm.dispatches.value - d0)
    return lanes, ts, dispatches


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", default="8,64",
                    help="comma-separated lane counts (cells)")
    ap.add_argument("--cycles", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(_HERE), "BENCH_lanes.json"))
    args = ap.parse_args(argv)

    import jax
    try:
        affinity = len(os.sched_getaffinity(0))
    except AttributeError:
        affinity = None
    out = {
        "backend": jax.default_backend(),
        "rows": N_ROWS, "features": N_FEAT, "max_depth": DEPTH,
        "rounds_per_cycle": ROUNDS, "cycles": args.cycles,
        "warmup_cycles": args.warmup,
        "cpu": {"cpu_count": os.cpu_count(), "affinity": affinity},
        "cells": {},
    }
    for n in [int(x) for x in args.lanes.split(",") if x]:
        solo_lanes, solo_ts = bench_solo(n, args.cycles, args.warmup)
        stacked_lanes, st_ts, disp = bench_stacked(
            n, args.cycles, args.warmup)
        # bit-identity pin: every stacked tenant == its solo twin
        mismatched = [i for i, ((bs, _), (bh, _))
                      in enumerate(zip(stacked_lanes, solo_lanes))
                      if bs.save_raw() != bh.save_raw()]
        assert not mismatched, \
            f"N={n}: stacked bytes != solo bytes for lanes {mismatched}"
        # dispatch independence: one stacked launch per cycle, any N
        assert all(d == 1 for d in disp), \
            f"N={n}: expected 1 dispatch/cycle, saw {disp}"
        solo_med = float(np.median(solo_ts))
        st_med = float(np.median(st_ts))
        cell = {
            "solo_cycle_seconds": round(solo_med, 5),
            "stacked_cycle_seconds": round(st_med, 5),
            "solo_lanes_per_s": round(n / solo_med, 2),
            "stacked_lanes_per_s": round(n / st_med, 2),
            "speedup": round(solo_med / st_med, 2),
            "dispatches_per_cycle": 1,
            "bit_identical": True,
        }
        out["cells"][f"n{n}"] = cell
        print(f"N={n:4d}  solo {solo_med*1e3:8.2f} ms/cycle   "
              f"stacked {st_med*1e3:8.2f} ms/cycle   "
              f"speedup {cell['speedup']:.2f}x")
    n64 = out["cells"].get("n64")
    if n64 is not None and n64["speedup"] < 3.0:
        print(f"FAIL: N=64 speedup {n64['speedup']} < 3.0",
              file=sys.stderr)
        return 1
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
