#!/usr/bin/env python
"""Cycle-latency smoke bench for the continuous-training pipeline.

Drives an in-process :class:`~xgboost_tpu.pipeline.ContinuousTrainer`
over the deterministic synthetic source for a few cycles and reports
the cycle-loop economics: wall seconds per cycle, the publish's share
of it, and the gate verdict mix.  This is a SMOKE bench (is the cycle
loop sanely fast, did a change regress it 10x), not a training bench —
bench.py owns rows/sec.

Usage::

    JAX_PLATFORMS=cpu python tools/bench_pipeline.py --cycles 4

Emits ``BENCH_pipeline.json``.

``--stream`` benches the streaming layer instead (PIPELINE.md
streaming section): a pre-spooled drifting batch stream is consumed by
an in-process :class:`~xgboost_tpu.stream.StreamTrainer` twice — once
with the EMA-FS feature screen on, once off — reporting micro-cycle
latency, claimed batches/s, the online drift-refresh cost
(propose ∪ live thresholds ∪ rebind wall seconds), and the screened
(C, N, F) histogram working-set reduction.  Emits
``BENCH_stream.json``.  Numbers from the 1-core CPU container are
cycle-loop SMOKE economics, not accelerator truth.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

# CPU-only harness, pinned — not a default: this process and every
# child it starts inherit the pin.  On a machine whose environment names
# the TPU the parent would otherwise hold the chip that every child
# then wants (one process per chip; ROADMAP S1/R5 bring this to the
# chip one process per device).
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def stream_bench(args) -> int:
    """The ``--stream`` cell: micro-cycle economics of the streaming
    layer, with and without the EMA-FS feature screen, over the same
    pre-spooled drifting batch stream."""
    import jax
    import numpy as np

    from xgboost_tpu.obs.metrics import stream_metrics
    from xgboost_tpu.pipeline import EvalGate
    from xgboost_tpu.stream import StreamDataSource, StreamTrainer

    work = args.workdir or tempfile.mkdtemp(prefix="xgbtpu_benchstream_")
    n_batches = args.cycles * 2
    batch_rows = max(args.rows // 2, 1)

    def spool(stream_dir):
        # identical batch content for both runs; the distribution
        # shifts halfway so one drift episode (and its cut refresh)
        # lands inside the measured window
        src = StreamDataSource(stream_dir, min_batches=1, max_batches=2)
        for i in range(n_batches):
            r = np.random.RandomState(100 + i)
            shift = 0.35 if i >= n_batches // 2 else 0.0
            X = (r.rand(batch_rows, args.features) + shift).astype(
                np.float32)
            y = (X[:, 0] + 0.25 * X[:, 1]
                 > 0.6 + 1.25 * shift).astype(np.float32)
            src.push(X, y)
        return src

    sm = stream_metrics()

    def run(tag, ema_fs):
        src = spool(os.path.join(work, f"stream-{tag}"))
        wd = os.path.join(work, f"wd-{tag}")
        trainer = StreamTrainer(
            os.path.join(work, f"published-{tag}.model"), src, wd,
            rounds_per_cycle=args.rounds,
            params={"objective": "binary:logistic", "max_depth": 4,
                    "eta": 0.3, "ema_fs": ema_fs, "silent": 1},
            gate=EvalGate(max_regression=0.5), quiet=True)
        base = (sm.refresh_seconds.sum, sm.cut_refreshes.value)
        cycle_s = []
        batches = 0
        for c in range(args.cycles):
            t0 = time.perf_counter()
            trainer.run_cycle()
            cycle_s.append(time.perf_counter() - t0)
            batches += len(src.batches_for(c))
            print(f"[bench-stream] {tag}: cycle {c} in "
                  f"{cycle_s[-1]:.3f}s", file=sys.stderr)
        total = sum(cycle_s)
        kept = None
        try:
            with open(os.path.join(
                    wd, "plans",
                    f"plan-{args.cycles - 1:06d}.json")) as f:
                kept = json.load(f).get("kept")
        except (OSError, ValueError):
            pass
        return {
            "ema_fs": ema_fs,
            "cycle_seconds": [round(s, 4) for s in cycle_s],
            "cycle_seconds_mean": round(total / len(cycle_s), 4),
            "cycle_seconds_steady": round(
                sum(cycle_s[1:]) / max(len(cycle_s) - 1, 1), 4),
            "batches_claimed": batches,
            "batches_per_sec": round(batches / total, 3),
            "rows_per_cycle": batch_rows * 2,
            "cut_refreshes": sm.cut_refreshes.value - base[1],
            "refresh_seconds_total": round(
                sm.refresh_seconds.sum - base[0], 4),
            "kept_features": len(kept) if kept else args.features,
        }

    off = run("off", 0.0)
    on = run("ema", args.ema_fs)
    f_kept = on["kept_features"]
    report = {
        "backend": jax.default_backend(),
        "caveat": "1-core CPU container smoke numbers — cycle-loop "
                  "economics only, not accelerator truth",
        "cycles": args.cycles,
        "rounds_per_cycle": args.rounds,
        "features": args.features,
        "stream_off": off,
        "stream_ema_fs": on,
        "working_set": {
            "full_F": args.features,
            "screened_F": f_kept,
            "fraction": round(f_kept / args.features, 4),
            "note": "fused histogram working set is (C, N, F); C and "
                    "N unchanged, F shrinks to the EMA-FS kept set",
        },
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"[bench-stream] off {off['cycle_seconds_steady']}s/cycle, "
          f"ema_fs {on['cycle_seconds_steady']}s/cycle, "
          f"F {args.features}->{f_kept}, "
          f"{on['cut_refreshes']:.0f} refresh(es) in "
          f"{on['refresh_seconds_total']}s -> {args.out}",
          file=sys.stderr)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cycles", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--out", default=None)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--stream", action="store_true",
                    help="bench the streaming layer instead "
                         "(BENCH_stream.json; see module docstring)")
    ap.add_argument("--ema-fs", type=float, default=0.9,
                    help="--stream: ema_fs fraction for the screened "
                         "run")
    args = ap.parse_args(argv)
    if args.out is None:
        args.out = "BENCH_stream.json" if args.stream \
            else "BENCH_pipeline.json"
    if args.stream:
        return stream_bench(args)

    import jax

    from xgboost_tpu.obs.metrics import pipeline_metrics
    from xgboost_tpu.pipeline import (ContinuousTrainer, EvalGate,
                                      SyntheticDataSource)

    work = args.workdir or tempfile.mkdtemp(prefix="xgbtpu_benchpipe_")
    publish = os.path.join(work, "published.model")
    trainer = ContinuousTrainer(
        publish, SyntheticDataSource(n_rows=args.rows,
                                     n_features=args.features, seed=0),
        os.path.join(work, "wd"), rounds_per_cycle=args.rounds,
        params={"objective": "binary:logistic", "max_depth": 4,
                "eta": 0.3, "silent": 1},
        gate=EvalGate(max_regression=0.1), quiet=True)

    pm = pipeline_metrics()
    base = {"publish_s": pm.publish_seconds.value,
            "pass": pm.gate_pass.value, "fail": pm.gate_fail.value,
            "published": pm.publishes.value}
    cycle_s = []
    statuses = []
    for _ in range(args.cycles):
        t0 = time.perf_counter()
        out = trainer.run_cycle()
        cycle_s.append(time.perf_counter() - t0)
        statuses.append(out["status"])
        print(f"[bench-pipe] cycle {out['cycle']}: {out['status']} "
              f"in {cycle_s[-1]:.3f}s", file=sys.stderr)

    report = {
        "backend": jax.default_backend(),
        "cycles": args.cycles,
        "rounds_per_cycle": args.rounds,
        "rows_per_cycle": args.rows,
        "features": args.features,
        "statuses": statuses,
        "cycle_seconds": [round(s, 4) for s in cycle_s],
        "cycle_seconds_mean": round(sum(cycle_s) / len(cycle_s), 4),
        "cycle_seconds_steady": round(
            sum(cycle_s[1:]) / max(len(cycle_s) - 1, 1), 4),
        "publish_seconds_total": round(
            pm.publish_seconds.value - base["publish_s"], 4),
        "gate_pass": pm.gate_pass.value - base["pass"],
        "gate_fail": pm.gate_fail.value - base["fail"],
        "published": pm.publishes.value - base["published"],
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"[bench-pipe] steady-state cycle "
          f"{report['cycle_seconds_steady']}s "
          f"({report['published']:.0f} published) -> {args.out}",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
