"""Worker for test_launch.py: PER-RANK SPLIT LOADING end to end.

Each process parses only its row block of the libsvm file
(ShardedDMatrix), assembles the global binned array from process-local
data, trains over the global mesh, and — in the same job — trains a
second Booster from a fully replicated load (DMatrix + device_sketch)
to prove the models are BYTE-IDENTICAL: split loading changes where
bytes live, not the math (reference property:
simple_dmatrix-inl.hpp:89-96).
Usage: mp_shard_worker.py <libsvm_path> <out_prefix>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from xgboost_tpu.parallel.launch import init_worker  # noqa: E402

assert init_worker(local_device_count=2)

import jax  # noqa: E402
import numpy as np  # noqa: E402


def main():
    path, out_prefix = sys.argv[1], sys.argv[2]
    rank = jax.process_index()
    assert jax.device_count() == 4

    import xgboost_tpu as xgb

    params = {"objective": "binary:logistic", "max_depth": 3,
              "eta": 0.7, "max_bin": 32, "dsplit": "row"}

    dm_s = xgb.ShardedDMatrix(path)
    # the whole point: this process's host arrays cover only ~N/2 rows
    with open(f"{out_prefix}.rank{rank}.rows", "w") as f:
        f.write(f"{dm_s.local_num_row} {dm_s.num_row}\n")

    # fused (no-evals) split-loaded training
    bst_s = xgb.train(params, dm_s, 5, verbose_eval=False)
    bst_s.save_model(f"{out_prefix}.rank{rank}.model")

    # same job, replicated load over the same mesh: the ensemble state
    # must be byte-identical (save_raw differs only in the param header:
    # the replicated run spells device_sketch explicitly)
    bst_r = xgb.train(dict(params, device_sketch=1), xgb.DMatrix(path), 5,
                      verbose_eval=False)
    s_s, s_r = bst_s.gbtree.get_state(), bst_r.gbtree.get_state()
    bitmatch = int(all(np.array_equal(s_s[k], s_r[k]) for k in s_s))

    # per-round path with DISTRIBUTED metric evaluation (partial sums)
    res = {}
    bst_e = xgb.train(params, xgb.ShardedDMatrix(path), 5,
                      evals=[(dm_s, "train")], evals_result=res,
                      verbose_eval=False)
    err = float(res["train-error"][-1])
    s_e = bst_e.gbtree.get_state()
    bitmatch_e = int(all(np.array_equal(s_e[k], s_s[k]) for k in s_s))

    # local-shard prediction comes back with local row count
    preds = bst_s.predict(dm_s)
    assert preds.shape == (dm_s.local_num_row,), preds.shape

    # EXACT distributed AUC (dist_auc=exact, the default) must equal
    # the replicated-load AUC; the reference-compat approximation
    # (mean of per-shard AUCs, evaluation-inl.hpp:405-414) is kept
    # behind dist_auc=approx
    auc_params = dict(params, eval_metric="auc")
    r_exact, r_approx, r_repl = {}, {}, {}
    xgb.train(auc_params, xgb.ShardedDMatrix(path), 3,
              evals=[(dm_s, "train")], evals_result=r_exact,
              verbose_eval=False)
    xgb.train(dict(auc_params, dist_auc="approx"),
              xgb.ShardedDMatrix(path), 3, evals=[(dm_s, "train")],
              evals_result=r_approx, verbose_eval=False)
    xgb.train(dict(auc_params, device_sketch=1), xgb.DMatrix(path), 3,
              evals=[(xgb.DMatrix(path), "train")], evals_result=r_repl,
              verbose_eval=False)

    with open(f"{out_prefix}.rank{rank}.result", "w") as f:
        f.write(f"{bitmatch} {bitmatch_e} {err:.6f}\n")
    with open(f"{out_prefix}.rank{rank}.auc", "w") as f:
        f.write(f"{r_exact['train-auc'][-1]:.9f} "
                f"{r_approx['train-auc'][-1]:.9f} "
                f"{r_repl['train-auc'][-1]:.9f}\n")
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("done")


if __name__ == "__main__":
    main()
