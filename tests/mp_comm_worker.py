"""Worker for test_obs.py: per-rank collective stats across processes.

Trains a few dsplit=row rounds over the global (cross-process) mesh and
writes one JSON file per rank with:

- ``totals``        — this rank's cumulative comm stats (obs/comm.py)
- ``mock_calls``    — the mock seam's collective-call count (the number
  ``xgbtpu_comm_allreduce_total`` must match)
- ``per_round``     — per-round (count, bytes, seconds) tallies
- ``aggregated``    — totals summed ACROSS workers via the existing
  mesh collective (ShardedDMatrix.allsum)
- ``metrics_text``  — the rank's rendered /metrics registry body

Usage: mp_comm_worker.py <libsvm_path> <out_prefix> <n_rounds>
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from xgboost_tpu.parallel.launch import init_worker  # noqa: E402

assert init_worker(local_device_count=2)

import jax  # noqa: E402


def main():
    path, out_prefix, n_rounds = sys.argv[1], sys.argv[2], int(sys.argv[3])
    rank = jax.process_index()

    import xgboost_tpu as xgb
    from xgboost_tpu.obs import comm, registry
    from xgboost_tpu.parallel import mock

    params = {"objective": "binary:logistic", "max_depth": 3,
              "eta": 0.7, "max_bin": 32, "dsplit": "row"}
    dtrain = xgb.DMatrix(path)
    # per-round updates (evals force the non-fused path, so each round
    # has its own begin_round + collective launch)
    xgb.train(params, dtrain, n_rounds, evals=[(dtrain, "train")],
              verbose_eval=False)

    out = {
        "rank": rank,
        "totals": comm.totals(),
        "mock_calls": mock.collective_calls(),
        "per_round": {str(k): v for k, v in comm.all_round_stats().items()},
        "aggregated": comm.aggregate_across_workers(),
        "metrics_text": registry().render(),
    }
    with open(f"{out_prefix}.rank{rank}.json", "w") as f:
        json.dump(out, f)
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("done")


if __name__ == "__main__":
    main()
