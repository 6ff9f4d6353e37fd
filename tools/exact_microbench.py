"""Microbench the primitive costs behind exact-mode redesign candidates.

Round-4 exact grower (VERDICT item 1): the round-3 design materializes
~10 (N, n_node) f32 intermediates per (feature, level).  The candidate
redesign sorts rows by (node, value) per (feature, level) so per-node
prefix sums become O(N) *segmented* scans.  This tool measures, on the
real chip, the primitives that decide between the candidates:

  a) batched int32 key sort (28, N)        -- full re-sort per level
  b) batched scatter-permutation (28, N)   -- incremental 1-bit partition
  c) segmented cumsum via associative_scan -- the per-level scan body
  d) plain (28, N) cumsum                  -- lower bound for (c)
  e) current dense (N, M) cumsum x4        -- round-3 status quo cost

All timings amortized inside one lax.scan launch of ITERS iterations
(the fixed dispatch cost divides out).
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

ITERS = 30


def timed(fn, *args, iters=ITERS):
    """Run fn in a lax.scan of `iters` iterations; return ms/iter."""

    @jax.jit
    def loop(args):
        def body(c, _):
            out = fn(*args, c)
            # fold output into carry so nothing is dead-code-eliminated
            leaves = jax.tree_util.tree_leaves(out)
            acc = sum(jnp.sum(l.astype(jnp.float32)) % 7.0 for l in leaves)
            return c + acc * 1e-20, None

        c, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=iters)
        return c

    r = loop(args)
    jax.block_until_ready(r)
    float(r)  # true barrier (host pull)
    t0 = time.perf_counter()
    r = loop(args)
    jax.block_until_ready(r)
    float(r)
    dt = time.perf_counter() - t0
    return dt / iters * 1e3


def main():
    N = 250_000
    F = 28
    M = 64
    rng = np.random.RandomState(0)
    key = jnp.asarray(rng.randint(0, M, (F, N)).astype(np.int32))
    payload = jnp.asarray(rng.randint(0, N, (F, N)).astype(np.int32))
    gh = jnp.asarray(rng.randn(F, N).astype(np.float32))
    perm = jnp.asarray(
        np.stack([rng.permutation(N) for _ in range(F)]).astype(np.int32))

    # (a) batched sort: composite int32 key (node*N + slot keeps stability)
    def sort_composite(key, payload, c):
        comp = key * N + jnp.arange(N, dtype=jnp.int32)[None, :]
        k, p = jax.lax.sort((comp + c.astype(jnp.int32) * 0, payload),
                            dimension=1, num_keys=1)
        return k, p

    print(f"sort (F={F},N={N}) int32 composite + payload: "
          f"{timed(sort_composite, key, payload):8.2f} ms")

    # (b) batched scatter-permutation: out[perm[i]] = payload[i]
    def scatter_perm(perm, payload, c):
        return jnp.zeros_like(payload).at[
            jnp.arange(F)[:, None], perm].set(payload + c.astype(jnp.int32) * 0)

    print(f"scatter-permutation (F={F},N={N}) int32:      "
          f"{timed(scatter_perm, perm, payload):8.2f} ms")

    # (b2) gather-permutation, for comparison
    def gather_perm(perm, payload, c):
        return jnp.take_along_axis(payload + c.astype(jnp.int32) * 0, perm,
                                   axis=1)

    print(f"gather-permutation (F={F},N={N}) int32:       "
          f"{timed(gather_perm, perm, payload):8.2f} ms")

    # (c) segmented cumsum via associative_scan over (F, N)
    seg_start = jnp.asarray(
        (rng.rand(F, N) < (M / N)).astype(np.bool_))

    def seg_cumsum(gh, seg_start, c):
        def comb(a, b):
            av, af = a
            bv, bf = b
            return jnp.where(bf, bv, av + bv), af | bf

        v, _ = jax.lax.associative_scan((gh + c, seg_start), axis=1)

        return v

    # associative_scan with custom op:
    def seg_cumsum2(gh, seg_start, c):
        def comb(a, b):
            av, af = a
            bv, bf = b
            return jnp.where(bf, bv, av + bv), af | bf

        v, _ = jax.lax.associative_scan(comb, (gh + c, seg_start), axis=1)
        return v

    print(f"segmented cumsum assoc_scan (F={F},N={N}):    "
          f"{timed(seg_cumsum2, gh, seg_start):8.2f} ms")

    # (d) plain cumsum (F, N)
    def plain_cumsum(gh, c):
        return jnp.cumsum(gh + c, axis=1)

    print(f"plain cumsum (F={F},N={N}):                   "
          f"{timed(plain_cumsum, gh):8.2f} ms")

    # (e) the round-3 dense formulation: one feature's 2 cumsums + cummax
    #     + reverse cummin over (N, M)  [x F features for a level]
    pos = jnp.asarray(rng.randint(0, M, N).astype(np.int32))
    ghn = jnp.asarray(rng.randn(N, 2).astype(np.float32))
    vs = jnp.asarray(np.sort(rng.randn(N).astype(np.float32)))

    def dense_level(pos, ghn, vs, c):
        onehot = pos[:, None] == jnp.arange(M, dtype=jnp.int32)[None, :]
        oh = onehot.astype(jnp.float32)
        cg = jnp.cumsum(oh * (ghn[:, 0:1] + c), axis=0)
        ch = jnp.cumsum(oh * ghn[:, 1:2], axis=0)
        vm = jnp.where(onehot, vs[:, None], -jnp.inf)
        a_run = jax.lax.cummax(vm, axis=0)
        bm = jnp.where(onehot, vs[:, None], jnp.inf)
        b_rev = jax.lax.cummin(bm, axis=0, reverse=True)
        return cg, ch, a_run, b_rev

    ms = timed(dense_level, pos, ghn, vs)
    print(f"dense (N,{M}) 2cumsum+cummax+cummin (1 feat): {ms:8.2f} ms"
          f"  -> x{F} = {ms * F:7.1f} ms/level")

    # (f) segment max via scatter-max (F, N) -> (F, M)
    def seg_max(key, gh, c):
        return jnp.full((F, M), -jnp.inf).at[
            jnp.arange(F)[:, None], key].max(gh + c)

    print(f"segment-max scatter (F={F},N={N})->(F,{M}):   "
          f"{timed(seg_max, key, gh):8.2f} ms")


if __name__ == "__main__":
    main()
