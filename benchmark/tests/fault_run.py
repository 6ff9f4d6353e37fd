"""One rehearsal run of a tiny cell with the timed path broken underneath
(`test_faults.py` starts one process per fault, because jit caches the
first trace).  Prints the result line.

    python3 benchmark/tests/fault_run.py <none|stale_state|half_batch|leaf_altered|eval_altered> [cell]
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XGBTPU_NO_JITCACHE"] = "1"       # a fault must not reach a cache
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import json  # noqa: E402

import run  # noqa: E402


def plant(fault: str) -> None:
    import xgboost_tpu as xgb
    real = xgb.Booster.update_many
    if fault == "stale_state":
        # after its first call the step returns its state unchanged, and
        # repeats the eval line it had
        seen = {}

        def update_many(self, dtrain, first, n, *a, eval_callback=None, **kw):
            if first == 0 or n == 0:
                def keep(i, msg):
                    seen["last"] = msg
                    eval_callback(i, msg)
                return real(self, dtrain, first, n, *a, eval_callback=(
                    keep if eval_callback else None), **kw)
            for i in range(first, first + n):
                eval_callback(i, f"[{i}]" + seen["last"].split("]", 1)[1])
        xgb.Booster.update_many = update_many
    elif fault == "half_batch":
        # half of the rows are left out of every round's gradients
        import jax.numpy as jnp
        from xgboost_tpu import objectives
        grad = objectives._regloss_grad

        def half(margin, label, weight, loss, spw):
            keep = (jnp.arange(margin.shape[0]) % 2 == 0)
            return grad(margin, label, weight, loss, spw) * keep[:, None, None]
        objectives._regloss_grad = half
    elif fault == "leaf_altered":
        # every leaf weight comes out 1% too large
        from xgboost_tpu.models import tree
        from xgboost_tpu.ops import split
        calc = split.calc_weight

        def off(G, H, cfg):
            return calc(G, H, cfg) * 1.01
        split.calc_weight = tree.calc_weight = off
    elif fault == "eval_altered":
        # the last eval line of every call is 0.1% off
        def update_many(self, dtrain, first, n, *a, eval_callback=None, **kw):
            def alter(i, msg):
                if i == first + n - 1:
                    head, val = msg.rsplit(":", 1)
                    msg = f"{head}:{float(val) * 1.001:.6f}"
                eval_callback(i, msg)
            return real(self, dtrain, first, n, *a, eval_callback=(
                alter if eval_callback else None), **kw)
        xgb.Booster.update_many = update_many
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    fault = sys.argv[1]
    cell = sys.argv[2] if len(sys.argv) > 2 else "tiny.train_logloss"
    plant(fault)
    cells = os.path.join(HERE, "cells")
    args = run.parse(["--workload", cell, "--seed", "2147483777",
                      "--seconds", "1"])
    print(json.dumps(run.run_cell(args, rehearse=True, bench_dir=cells,
                                  root=cells)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
