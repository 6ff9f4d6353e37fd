"""Readings the limits of `correct` are set from, without the program:
for each seed, the reference at full float32 against itself put in the
program's place at lower precision (gradients in 127 steps as the int8
path takes them: where sound runs should read; in 7 steps, int4: the
control that has to fail), with half of the rows left out of the
gradients, and with every leaf weight 1 % too large (faults that have to
fail).  One process, any number of seeds, no ingest.  The program's own readings come from benchmark runs.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 [--out file.jsonl]
"""

import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import compare  # noqa: E402
import run  # noqa: E402

STAND_INS = {"int8": {"levels": 127}, "int4_control": {"levels": 7},
             "half_fault": {"drop_half": True}}


def leaf_altered(fit, data, cfg, obj, factor: float = 1.01):
    """The full-precision fit with every leaf weight `factor` too large,
    and the eval lines such trees would print."""
    import reference as ref
    p = cfg["params"]
    trees = fit.trees._replace(leaf_value=fit.trees.leaf_value * np.float32(factor))
    base = obj.base_margin(p.get("base_score", 0.5))
    evals = [obj.evaluate(ref.margin_of(trees, data["X_held"], base, by="value",
                                        depth=int(p["max_depth"]), n_trees=r + 1),
                          data["y_held"]) for r in range(len(fit.evals))]
    return fit._replace(trees=trees, evals=evals)


def readings(cell_name: str, seed: int, bench_dir=HERE, root=run.ROOT) -> dict:
    _, _, cell, cfg = run.find_cell(cell_name, bench_dir, root)
    gen = importlib.import_module(f"datagen.{cfg['generator']}")
    t0 = time.perf_counter()
    data = gen.generate(seed, cfg["n_train"], cfg["n_held"], cfg["features"],
                        **cfg.get("generator_args", {}))
    side = compare.reference_side(data, cfg, run.say)
    t1 = time.perf_counter()
    fit = compare.reference_fit(side, data, cfg)
    t2 = time.perf_counter()
    out = {"workload": cell_name, "seed": seed, "ref_evals": fit.evals,
           "cuts_bins_s": t1 - t0, "fit_s": t2 - t1}
    others = {name: compare.reference_fit(side, data, cfg, **kw)
              for name, kw in STAND_INS.items()}
    others["leaf_fault"] = leaf_altered(fit, data, cfg, side["obj"])
    for name, other in others.items():
        c = compare.compare(
            compare.stand_in(other, side["cuts"], side["bins_train"]),
            side, fit, data, cfg, cell["limits"])
        out[name] = {k: x["value"] for k, x in c.items()}
        out[name + "_correct"] = compare.is_correct(c)
    out["total_s"] = time.perf_counter() - t0
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--out", default="")
    a = p.parse_args()
    import jax
    d = jax.devices()[0]
    for seed in (int(s) for s in a.seeds.split(",")):
        r = readings(a.workload, seed)
        r["device"] = f"{d.platform}/{d.device_kind}"
        line = json.dumps(r)
        print(line, flush=True)
        if a.out:
            os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
            with open(a.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
