"""Fit the per-round compute model from a single-chip row sweep.

The multi-chip projection (``parallel/commcost.project_round_time``)
models per-chip compute as ``fixed_round_s + per_row_s * rows_per_chip``.
Round 4 ASSUMED ``fixed_round_s = 0.004`` — 79% of the projected 8-chip
round — with no measurement behind it (VERDICT r4, Missing #2).  This
tool replaces the assumption with a measurement: it times the bench's
binary workload (depth 6, max_bin 64, F=28 — the exact config the
projection speaks about) at 1M, 1M/2, 1M/4 and 1M/8 rows on the real
chip, least-squares fits the affine model, and writes ``ROUND_MODEL.json``
at the repo root, which ``project_round_time`` then loads as its
calibrated defaults.

The row sweep measures exactly the quantity the projection needs:
per-chip round time at N/k rows is the single-chip round time at that
row count (the level structure — launches, split finding, routing — is
identical; only the row-proportional kernels shrink), plus the psum
term, which is modeled separately and test-pinned byte-for-byte
(tests/test_distributed.py).

Run on the real chip (default env): ``python tools/fit_round_model.py``.
Reference counterpart: the network boundary being modeled is
``updater_histmaker-inl.hpp:343-346`` (per-level histogram allreduce);
the reference validated its distributed mode with real multi-node runs
(``multi-node/col-split/mushroom-col-rabit.sh``), which this image's
single chip cannot — the fit makes the projection as anchored as the
hardware allows.

MESH CELL (``FIT_MESH=1``): measures — rather than projects — the
mesh-fused scan (round 6).  Trains the bench workload through the
shard_map'd segmented scan (``dsplit=row``, ``hist_precision=fixed``)
on every visible device and again on ONE device at the sharded
per-device row count; the delta is the measured per-round psum +
shard_map overhead the ring model only estimated.  Writes
``MULTICHIP_r06.json`` (measured rounds/s, per-round psum seconds,
measured-vs-projected error against a host-local affine fit) and does
NOT touch ``ROUND_MODEL.json`` — the committed fit there is from the
real chip and a CPU bench host must never clobber it.
``FIT_MESH_DEVICES=N`` forces N in-process virtual CPU devices (the
live multi-device target on hosts whose backend cannot run
multi-process programs); ``FIT_MESH_ROWS``/``FIT_MESH_ROUNDS`` size
the workload.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _sweep(B, xgb, params, X, y, rows_list, rounds, tag):
    points = []
    for n in rows_list:
        d = xgb.DMatrix(X[:n], label=y[:n])
        t0 = time.perf_counter()
        dt, _ = B._time_training(xgb, params, d, rounds)
        s_round = dt / (rounds - 1)
        points.append({"rows": n, "s_per_round": s_round})
        print(f"[{tag}] rows={n:>9,}  {s_round*1e3:7.3f} ms/round  "
              f"({1/s_round:6.1f} r/s; wall {time.perf_counter()-t0:.0f}s)",
              file=sys.stderr)
    rows = np.array([p["rows"] for p in points], np.float64)
    t = np.array([p["s_per_round"] for p in points], np.float64)
    A = np.stack([np.ones_like(rows), rows], axis=1)
    (fixed, slope), res, *_ = np.linalg.lstsq(A, t, rcond=None)
    pred = A @ np.array([fixed, slope])
    rel_err = np.abs(pred - t) / t
    return float(fixed), float(slope), points, float(rel_err.max())


def mesh_cell():
    """The round-6 measurement: multi-device mesh-fused rounds/s and
    per-round psum seconds (delta method), written to
    MULTICHIP_r06.json beside the r05 projection (see module
    docstring)."""
    import bench as B
    import jax

    import xgboost_tpu as xgb
    from xgboost_tpu.obs.metrics import training_metrics
    from xgboost_tpu.parallel import commcost
    from xgboost_tpu.parallel import mesh as pmesh

    D = len(jax.devices())
    rows = int(os.environ.get("FIT_MESH_ROWS", 262144))
    rounds = int(os.environ.get("FIT_MESH_ROUNDS", 20))
    rows -= rows % D  # mesh-divisible, so no padding skews the delta
    params = {"objective": "binary:logistic", "max_depth": 6,
              "eta": 0.1, "max_bin": 64, "dsplit": "row",
              "hist_precision": "fixed"}
    X, y = B.make_higgs_like(rows)
    fb0 = dict(training_metrics().fused_fallback.values())

    def timed(n_dev, n_rows, tag):
        pmesh.set_mesh(pmesh.data_parallel_mesh(n_dev))
        try:
            d = xgb.DMatrix(X[:n_rows], label=y[:n_rows])
            dt, _ = B._time_training(xgb, params, d, rounds)
        finally:
            pmesh.set_mesh(None)
        s = dt / (rounds - 1)
        print(f"[mesh] {tag}: devices={n_dev} rows={n_rows:>9,}  "
              f"{s*1e3:7.3f} ms/round ({1/s:6.1f} r/s)", file=sys.stderr)
        return s

    # single-device anchors: the per-device compute at the sharded row
    # count (what each mesh device grinds per round), plus two more
    # points for the host-local affine fit
    s_shard = timed(1, rows // D, "1dev@rows/D")
    s_half = timed(1, rows // 2, "1dev@rows/2")
    s_full = timed(1, rows, "1dev@rows")
    # the measurement the projection only modeled
    s_mesh = timed(D, rows, f"{D}dev fused")

    fb1 = dict(training_metrics().fused_fallback.values())
    fallbacks = sum(fb1.values()) - sum(fb0.values())

    # host-local affine fit from the three single-device points — NOT
    # the committed ROUND_MODEL.json, which is chip-fitted
    pts_r = np.array([rows // D, rows // 2, rows], np.float64)
    pts_t = np.array([s_shard, s_half, s_full], np.float64)
    A = np.stack([np.ones_like(pts_r), pts_r], axis=1)
    (fixed, slope), *_ = np.linalg.lstsq(A, pts_t, rcond=None)
    fixed, slope = float(fixed), float(slope)
    proj = commcost.project_round_time(
        rows=rows, max_depth=6, n_feat=28, n_bin=64, n_chips=D,
        single_chip_round_s=s_full, single_chip_rows=rows,
        fixed_round_s=fixed, per_row_s=slope)
    psum_measured = s_mesh - s_shard
    rel_err = (s_mesh - proj["round_s"]) / proj["round_s"]

    r05 = None
    r05_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "MULTICHIP_r05.json")
    if os.path.exists(r05_path):
        with open(r05_path) as f:
            r05 = json.load(f).get("tail", "").strip()

    report = {
        "mode": "mesh_fused_measurement",
        "n_devices": D,
        "rows": rows,
        "rounds": rounds,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
        "config": {k: v for k, v in params.items()},
        "single_device_round_s_at_shard_rows": s_shard,
        "single_device_round_s_at_half_rows": s_half,
        "single_device_round_s_at_full_rows": s_full,
        "mesh_round_s": s_mesh,
        "measured_rounds_per_sec": 1.0 / s_mesh,
        "measured_psum_s_per_round": psum_measured,
        "host_fit": {"fixed_round_s": fixed, "per_row_s": slope},
        "projected": proj,
        "measured_vs_projected_rel_err": rel_err,
        "scaling_efficiency_vs_full": s_full / (D * s_mesh),
        "fused_fallbacks": fallbacks,
        "r05_projection": r05,
        "note": ("virtual CPU devices share the host's physical cores, "
                 "so the delta (mesh_round_s - "
                 "single_device_round_s_at_shard_rows) bundles real "
                 "psum/shard_map overhead WITH core contention — an "
                 "upper bound on the collective cost.  On a real "
                 "multi-chip mesh each device has its own silicon and "
                 "the delta isolates the interconnect term the ring "
                 "model projects."),
        "fitted_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "MULTICHIP_r06.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    print(f"[mesh] {D}-device fused: {1/s_mesh:.1f} rounds/s measured "
          f"(projected {proj['rounds_per_sec']:.1f}; rel err "
          f"{rel_err:+.1%}); per-round psum+overhead "
          f"{psum_measured*1e3:.3f} ms (ring model projected "
          f"{proj['psum_s']*1e3:.3f} ms); {fallbacks} fused "
          f"fallbacks -> {out}", file=sys.stderr)
    print(json.dumps(report))
    if fallbacks:
        raise SystemExit("mesh cell fell back to per-round dispatch — "
                         "the measurement above is NOT the fused path")


def main():
    if os.environ.get("FIT_MESH", "") not in ("", "0"):
        nd = os.environ.get("FIT_MESH_DEVICES")
        if nd:
            # must precede the first jax import (bench imports jax)
            flags = os.environ.get("XLA_FLAGS", "")
            if "host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + f" --xla_force_host_platform_device_count"
                    f"={nd}").strip()
            os.environ["JAX_PLATFORMS"] = "cpu"
        mesh_cell()
        return

    import bench as B
    import xgboost_tpu as xgb
    import jax

    rounds = int(os.environ.get("FIT_ROUNDS", 50))
    rows_list = [int(r) for r in os.environ.get(
        "FIT_ROWS", "125000,250000,500000,1000000").split(",")]
    params = {"objective": "binary:logistic", "max_depth": 6,
              "eta": 0.1, "max_bin": 64}

    X, y = B.make_higgs_like(max(rows_list))
    fixed, slope, points, max_rel = _sweep(
        B, xgb, params, X, y, rows_list, rounds, "fused")

    # round 8: the primary sweep rides update_many's segmented fusion
    # (auto-K, or XGBTPU_ROUNDS_PER_DISPATCH in the env); a second
    # sweep at K=0 measures the per-round dispatch floor the fusion
    # removes, so the json carries that A/B.
    # FIT_PER_ROUND_BASELINE=0 skips it.
    baseline = None
    if os.environ.get("FIT_PER_ROUND_BASELINE", "1") != "0":
        old = os.environ.get("XGBTPU_ROUNDS_PER_DISPATCH")
        os.environ["XGBTPU_ROUNDS_PER_DISPATCH"] = "0"
        try:
            bfixed, bslope, bpoints, bmax_rel = _sweep(
                B, xgb, params, X, y, rows_list, rounds, "per-round")
        finally:
            if old is None:
                os.environ.pop("XGBTPU_ROUNDS_PER_DISPATCH", None)
            else:
                os.environ["XGBTPU_ROUNDS_PER_DISPATCH"] = old
        baseline = {"fixed_round_s": bfixed, "per_row_s": bslope,
                    "points": bpoints, "fit_max_rel_err": bmax_rel,
                    "fixed_drop_vs_fused": (bfixed / fixed)
                    if fixed > 0 else None}

    model = {
        "fixed_round_s": fixed,
        "per_row_s": slope,
        "config": {"max_depth": 6, "n_feat": 28, "n_bin": 64,
                   "max_bin": 64, "eta": 0.1,
                   "objective": "binary:logistic", "rounds": rounds,
                   "rounds_per_dispatch": os.environ.get(
                       "XGBTPU_ROUNDS_PER_DISPATCH", "auto")},
        "points": points,
        "fit_max_rel_err": max_rel,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
        "fitted_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if baseline is not None:
        model["per_round_baseline"] = baseline
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "ROUND_MODEL.json")
    with open(out, "w") as f:
        json.dump(model, f, indent=1)
    print(json.dumps(model))


if __name__ == "__main__":
    main()
