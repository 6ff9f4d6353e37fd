"""Attribute the higgs250k parity gap (VERDICT r2 item 2).

Round-2 parity left small unattributed AUC deltas vs the reference CLI
(train-auc -0.00203, test-auc -0.00077 on higgs250k).  This sweep runs
BOTH sides on several seeds of the same generator and sweeps the
quantization/precision knobs on our side:

  - default: eps-driven global sketch (~66 bins)
  - bf16 vs fp32 histogram accumulation (hist_precision)
  - fine cuts: max_bin=1024 + sketch_eps=0.003 (~600 bins)
  - near-exact cuts: max_bin=4096 + sketch_eps=0.0008

If the delta shrinks to seed-noise at fine cuts, the gap is
quantization resolution (the reference re-proposes cuts per node per
round — updater_histmaker-inl.hpp:353-462 — which adapts resolution
where the data is); if not, something else is unaccounted.

Writes PARITY_SWEEP.json and appends a summary table to PARITY.md.

Usage: python tools/parity_sweep.py [--seeds 3] [--rounds 20]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.parity import (_parse_evals, _write_libsvm, build_reference,
                          run_reference)  # noqa: E402


def make_data(workdir: str, seed: int, n: int = 250_000, n_test: int = 50_000):
    import numpy as np
    train = os.path.join(workdir, f"sweep_s{seed}.train")
    test = os.path.join(workdir, f"sweep_s{seed}.test")
    if os.path.exists(train) and os.path.exists(test):
        return train, test
    from chip_smoke import make_higgs_like
    X, y = make_higgs_like(n + n_test, seed=seed * 977 + 42)
    _write_libsvm(train, X[:n], y[:n])
    _write_libsvm(test, X[n:], y[n:])
    return train, test


REF_ARGS = ["objective=binary:logitraw", "max_depth=6", "eta=0.1",
            "eval_metric=auc", "use_buffer=0"]

OUR_CONFIGS = {
    "default_fp32": {"hist_precision": "fp32"},
    "default_bf16": {"hist_precision": "bf16"},
    "fine_fp32": {"hist_precision": "fp32", "max_bin": 1024,
                  "sketch_eps": 0.003, "sketch_ratio": 2.0},
    "xfine_fp32": {"hist_precision": "fp32", "max_bin": 4096,
                   "sketch_eps": 0.0008, "sketch_ratio": 2.0},
}


def run_ours_api(train, test, rounds, extra, workdir):
    """Run our side in a SUBPROCESS (fresh backend per config keeps jit
    caches separate and lets hist_precision/bins vary freely)."""
    script = os.path.join(workdir, "_run_ours.py")
    with open(script, "w") as f:
        f.write(f"""
import sys, json
sys.path.insert(0, {REPO!r})
import xgboost_tpu as xgb
params = {{"objective": "binary:logitraw", "max_depth": 6, "eta": 0.1,
          "eval_metric": "auc"}}
params.update({extra!r})
dtrain = xgb.DMatrix({train!r})
dtest = xgb.DMatrix({test!r}, num_col=dtrain.num_col)
res = {{}}
xgb.train(params, dtrain, {rounds},
          evals=[(dtest, "test"), (dtrain, "train")],
          evals_result=res, verbose_eval=False)
print(json.dumps({{k: v[-1] for k, v in res.items()}}))
""")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(f"ours failed: {r.stderr[-800:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--workdir", default="/tmp/xgbtpu_parity")
    args = ap.parse_args()
    os.makedirs(args.workdir, exist_ok=True)
    ref_bin = build_reference(args.workdir)

    results = {"rounds": args.rounds, "seeds": {}}
    for seed in range(args.seeds):
        train, test = make_data(args.workdir, seed)
        print(f"[sweep] seed {seed}: reference ...", flush=True)
        r_ev, _, _ = run_reference(
            ref_bin, [f"data={train}", f"eval[test]={test}", "eval_train=1",
                      "model_out=NONE", f"num_round={args.rounds}"]
            + REF_ARGS, args.workdir)
        entry = {"reference": {"train-auc": r_ev["train-auc"][-1],
                               "test-auc": r_ev["test-auc"][-1]}}
        for name, extra in OUR_CONFIGS.items():
            print(f"[sweep] seed {seed}: ours {name} ...", flush=True)
            entry[name] = run_ours_api(train, test, args.rounds, extra,
                                       args.workdir)
        results["seeds"][str(seed)] = entry
        print(json.dumps(entry, indent=1), flush=True)

    with open(os.path.join(REPO, "PARITY_SWEEP.json"), "w") as f:
        json.dump(results, f, indent=1)

    # summary: mean +/- std of (ours - reference) per config/metric
    import numpy as np
    lines = ["", "## Parity attribution sweep (round 3, "
             f"{args.seeds} seeds x {args.rounds} rounds, higgs250k "
             "generator)", "",
             "Delta = ours - reference (same data both sides).", "",
             "| config | train-auc delta | test-auc delta |",
             "|---|---|---|"]
    for name in OUR_CONFIGS:
        row = [name]
        for m in ("train-auc", "test-auc"):
            ds = [results["seeds"][s][name][m]
                  - results["seeds"][s]["reference"][m]
                  for s in results["seeds"]]
            row.append(f"{np.mean(ds):+.5f} ± {np.std(ds):.5f}")
        lines.append("| " + " | ".join(row) + " |")
    with open(os.path.join(REPO, "PARITY.md"), "a") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))


if __name__ == "__main__":
    main()
