"""A/B: exact-mode per-LEVEL sort vs per-TREE sort + partition apply.

VERDICT r4 Weak #3 / next-round #7: the segment-sorted exact grower
spends ~14 of ~21 ms/level on the packed-key bitonic sort
(models/colmaker.py).  Row positions refine monotonically within a
level order, so one sort per TREE suffices mathematically: after the
level-d sort, each node segment splits stably into left/right child
blocks, i.e. the level-(d+1) order is a PERMUTATION computable from
routing bits without comparing values again.

The catch is applying that permutation: the sorted layout carries 3
operands (packed key, g, h) that all must move, and on TPU a
row-granular (F, N) take_along_axis / scatter is the known-serializing
dynamic lane gather (pre-round record: one operand cost more than the
whole 3-operand sort).  This tool
measures the actual alternatives at the exact-bench shape:

  A. lax.sort of (packed int32 key, g, h), num_keys=1 — the shipped
     per-level path;
  B. destination-index computation + 3x take_along_axis — the
     per-tree-sort inner step (destination math itself is cheap
     segmented-cumsum work, also timed);
  C. destination-index + 3x scatter (.at[dest].set) — the same
     permutation, scatter-form.

If B or C beats A by >=1.5x, per-tree sort pays and the grower should
adopt it; otherwise this file is the committed negative result (like
pack2/in-kernel routing in earlier rounds; PERF.md "Carried over").
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp

    F, N = 28, 250_000
    rng = np.random.RandomState(0)
    key_np = rng.randint(0, 1 << 22, (F, N)).astype(np.int32)
    g_np = rng.randn(F, N).astype(np.float32)
    h_np = rng.rand(F, N).astype(np.float32)
    perm_np = np.stack([rng.permutation(N) for _ in range(F)]).astype(
        np.int32)

    key_d = jnp.asarray(key_np)
    g_d = jnp.asarray(g_np)
    h_d = jnp.asarray(h_np)
    perm_d = jnp.asarray(perm_np)

    @jax.jit
    def sort3(k, g, h):
        return jax.lax.sort((k, g, h), dimension=1, num_keys=1,
                            is_stable=False)

    @jax.jit
    def gather3(perm, k, g, h):
        return (jnp.take_along_axis(k, perm, axis=1),
                jnp.take_along_axis(g, perm, axis=1),
                jnp.take_along_axis(h, perm, axis=1))

    @jax.jit
    def scatter3(perm, k, g, h):
        z = jnp.zeros_like
        return (z(k).at[jnp.arange(F)[:, None], perm].set(k),
                z(g).at[jnp.arange(F)[:, None], perm].set(g),
                z(h).at[jnp.arange(F)[:, None], perm].set(h))

    @jax.jit
    def dest_math(go_left, seg_lo, key):
        # the per-tree-sort bookkeeping: destination = child segment
        # base + stable within-child rank, via two segmented cumsums
        # (approximated here by their global-cumsum cost shape)
        gl = go_left.astype(jnp.int32)
        c_left = jnp.cumsum(gl, axis=1)
        c_right = jnp.cumsum(1 - gl, axis=1)
        return jnp.where(go_left, c_left, c_right) + seg_lo

    go_left = jnp.asarray(rng.rand(F, N) < 0.5)
    seg_lo = jnp.zeros((F, N), jnp.int32)

    def bench(fn, *args):
        r = fn(*args)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(10):
            r = fn(*args)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / 10 * 1e3

    t_sort = bench(sort3, key_d, g_d, h_d)
    t_gather = bench(gather3, perm_d, key_d, g_d, h_d)
    t_scatter = bench(scatter3, perm_d, key_d, g_d, h_d)
    t_dest = bench(dest_math, go_left, seg_lo, key_d)
    print(f"A per-level sort3          : {t_sort:7.2f} ms")
    print(f"B permutation via gather3  : {t_gather:7.2f} ms (+ dest "
          f"{t_dest:.2f} ms)")
    print(f"C permutation via scatter3 : {t_scatter:7.2f} ms (+ dest "
          f"{t_dest:.2f} ms)")
    best_alt = min(t_gather, t_scatter) + t_dest
    print(f"verdict: per-tree sort {'PAYS' if best_alt * 1.5 <= t_sort else 'does NOT pay'} "
          f"(best alternative {best_alt:.2f} vs sort {t_sort:.2f} ms; "
          f"adoption bar 1.5x)")


if __name__ == "__main__":
    main()
