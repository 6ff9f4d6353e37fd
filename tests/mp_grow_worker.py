"""Worker script for test_launch.py: multi-process distributed growth.

Each process loads its row shard of a deterministic dataset, assembles
the global row-sharded arrays via make_array_from_process_local_data,
grows one tree with the dp path (psum over the 4-device global mesh),
and rank 0 writes the tree arrays to OUT_PATH.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from xgboost_tpu.parallel.launch import init_worker  # noqa: E402

assert init_worker(local_device_count=2)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def main():
    out_path = sys.argv[1]
    rank = jax.process_index()
    nproc = jax.process_count()
    # 2 local devices per process; the GLOBAL mesh spans all processes
    assert jax.device_count() == 2 * nproc

    from xgboost_tpu.binning import bin_dense, compute_cuts
    from xgboost_tpu.data import DMatrix
    from xgboost_tpu.models.gbtree import make_grow_config
    from xgboost_tpu.config import TrainParam
    from xgboost_tpu.parallel.dp import grow_tree_dp
    from xgboost_tpu.parallel.mesh import data_parallel_mesh

    # deterministic dataset; every process derives the same cuts from the
    # full data ONLY to keep the test self-contained (cut proposal across
    # hosts is parallel/sketch_device.py's job, tested separately)
    rng = np.random.RandomState(0)
    X = rng.rand(1024, 6).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0.8).astype(np.float32)
    cuts = compute_cuts(DMatrix(X, label=y), max_bin=16)
    param = TrainParam(max_depth=3, eta=0.5)
    cfg = make_grow_config(param, cuts.max_bin)

    # block row shard per process (the global array is the concatenation)
    n_local = X.shape[0] // nproc
    sl = slice(rank * n_local, (rank + 1) * n_local)
    binned_local = bin_dense(X[sl], cuts)
    margin_local = np.zeros(n_local, np.float32)
    p = 1.0 / (1.0 + np.exp(-margin_local))
    gh_local = np.stack([p - y[sl], p * (1 - p)], axis=1).astype(np.float32)

    mesh = data_parallel_mesh()

    def globalize(a):
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("data", *[None] * (a.ndim - 1))), a)

    binned = globalize(binned_local)
    gh = globalize(gh_local)
    rv = globalize(np.ones(n_local, bool))

    tree, row_leaf, delta = grow_tree_dp(
        mesh, jax.random.PRNGKey(7), binned, gh,
        jnp.asarray(cuts.cut_values), jnp.asarray(cuts.n_cuts), cfg, rv)

    if rank == 0:
        state = {f: np.asarray(getattr(tree, f)) for f in tree._fields}
        np.savez(out_path, **state)
    # all processes finish cleanly
    jax.experimental.multihost_utils.sync_global_devices("done")


if __name__ == "__main__":
    import jax.experimental.multihost_utils  # noqa: F401
    main()
