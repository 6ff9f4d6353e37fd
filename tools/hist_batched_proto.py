"""Benchmark: tree-batched histogram kernel vs per-tree launches.

The kernel itself lives in the package now
(:func:`xgboost_tpu.ops.pallas_hist.build_level_histogram_pallas_batched`,
dispatched by vmap via the custom_vmap rule in ops/histogram.py); this
script reproduces the measurement that motivated it.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from tools.hist_microbench import timeit  # noqa: E402
from xgboost_tpu.ops.pallas_hist import (  # noqa: E402
    build_level_histogram_pallas, build_level_histogram_pallas_batched)


def main():
    n, f, b = 200_000, 28, 67
    T, n_node = 6, 64
    rng = np.random.RandomState(0)
    binned = jnp.asarray(rng.randint(0, b, size=(n, f)), jnp.int32)
    gh = jnp.asarray(rng.randn(T, n, 2), jnp.float32)
    pos = jnp.asarray(rng.randint(0, n_node, size=(T, n)), jnp.int32)

    # parity on dyadic grads (f32 sums order-independent)
    ghd = jnp.asarray(rng.randint(-512, 512, (T, 4096, 2)) / 256.0,
                      jnp.float32)
    got = np.asarray(build_level_histogram_pallas_batched(
        binned[:4096], ghd, pos[:, :4096], n_node, b, precision="fp32"))
    for t in range(T):
        ref = np.asarray(build_level_histogram_pallas(
            binned[:4096], ghd[t], pos[t, :4096], n_node, b,
            precision="fp32"))
        np.testing.assert_array_equal(got[t], ref)
    print("fp32 bitwise parity ok")

    def per_tree(binned, gh, pos):
        outs = [build_level_histogram_pallas(binned, gh[t], pos[t],
                                             n_node, b, precision="bf16")
                for t in range(T)]
        return jnp.stack(outs)

    seq = jax.jit(per_tree)
    ms = timeit(seq, binned, gh, pos)
    print(f"per-tree x{T} (sequential kernels): {ms:7.2f} ms")
    ms = timeit(build_level_histogram_pallas_batched, binned, gh, pos,
                n_node, b, "bf16")
    print(f"batched shared-onehot           : {ms:7.2f} ms")


if __name__ == "__main__":
    main()
