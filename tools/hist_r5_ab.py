"""Round-5 histogram-kernel A/B: the one-hot build is the bound.

hlo_stats of the fused round showed the int8
kernel at ~1.84 ms/level FLAT in node count — the MXU floor is ~0.6 ms
and the rest is VPU one-hot construction (B x R compares + i8 convert
per feature).  Variants:

  prod      — production int8 kernel (bins widened to i32, i32 iota
              compare, select -> i8)
  u8cmp     — compare in the u8 domain (u8 bins vs u8 iota, no widen);
              tests whether Mosaic vectorizes sub-word compares
  b64       — n_bin=64 instead of 67: the i8 one-hot tile pads
              sublanes to 96 for B=67 but 64 for B=64 (~33% fewer
              physical VPU elements)
  shared6   — ONE one-hot per (feature, row tile) contracted against
              6 levels' gh_exp operands (the per-round floor IF levels
              could share the build; they can't today — sequential
              splits — this measures what a restructure would buy)
  gh32      — gh_exp kept i32, dot in i32?? (not supported; skipped)

All timed amortized in a lax.scan (dispatch cost divides out).
"""
import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from xgboost_tpu.ops.pallas_hist import _round_up  # noqa: E402

N, F, M = 1_000_000, 28, 64
R_TILE = 2048


def make_kernel(mode, n_bin, n_levels=1):
    def kernel(binned_ref, pos_ref, gh_ref, out_ref):
        r_tile = binned_ref.shape[1]
        m2 = 2 * M

        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        sub = jax.lax.broadcasted_iota(jnp.int32, (m2, r_tile), 0)
        node_of_sub = jnp.where(sub < M, sub, sub - M)
        ghsel = jnp.where(sub < M, gh_ref[0:1, :], gh_ref[1:2, :])
        pos = pos_ref[0:1, :]
        gh_exps = []
        for lv in range(n_levels):
            act = (pos + lv) % M == node_of_sub if n_levels > 1 else \
                pos == node_of_sub
            gh_exps.append(jnp.where(act, ghsel, 0).astype(jnp.int8))

        if mode == "u8cmp":
            bins = binned_ref[:]                      # stay u8
            # u8 iota is unsupported; build once from i32 (hoisted out
            # of the feature loop — the per-feature compares stay u8)
            bin_ids = jax.lax.broadcasted_iota(
                jnp.int32, (n_bin, r_tile), 0).astype(jnp.uint8)
        else:
            bins = binned_ref[:].astype(jnp.int32)
            bin_ids = jax.lax.broadcasted_iota(
                jnp.int32, (n_bin, r_tile), 0)
        for f in range(F):
            onehot = (bins[f:f + 1, :] == bin_ids).astype(jnp.int8)
            for lv, ghe in enumerate(gh_exps):
                acc = jax.lax.dot_general(
                    onehot, ghe, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32)
                out_ref[lv, f * n_bin:(f + 1) * n_bin, :] += acc

    return kernel


def build(mode, n_bin, n_levels=1):
    @jax.jit
    def fn(binned_t, pos, gh):
        n_pad = binned_t.shape[1]
        kernel = make_kernel(mode, n_bin, n_levels)
        return pl.pallas_call(
            kernel,
            grid=(n_pad // R_TILE,),
            in_specs=[
                pl.BlockSpec((F, R_TILE), lambda ri: (0, ri)),
                pl.BlockSpec((1, R_TILE), lambda ri: (0, ri)),
                pl.BlockSpec((2, R_TILE), lambda ri: (0, ri)),
            ],
            out_specs=pl.BlockSpec((n_levels, F * n_bin, 2 * M),
                                   lambda ri: (0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((n_levels, F * n_bin, 2 * M),
                                           jnp.int32),
        )(binned_t, pos, gh)

    return fn


def timed(fn, binned_t, pos, gh, iters=40):
    @jax.jit
    def loop(b, p, g):
        def body(c, _):
            out = fn(b, p, g + c)
            return c + out[0, 0, 0] % 3, None
        c, _ = jax.lax.scan(body, jnp.int32(0), None, length=iters)
        return c

    r = loop(binned_t, pos, gh); jax.block_until_ready(r); int(r)
    t0 = time.perf_counter()
    int(loop(binned_t, pos, gh))
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    rng = np.random.RandomState(0)
    n_pad = _round_up(N, R_TILE)
    pos = jnp.asarray(np.pad(
        rng.randint(0, M, N).astype(np.int32), (0, n_pad - N),
        constant_values=-1))[None, :]
    gh = jnp.asarray(rng.randint(-127, 127, (2, n_pad)).astype(np.int32))

    # NOTE u8cmp fails Mosaic compilation twice over: u8 iota is "not
    # implemented" and so is cmpi on vector<8x128x4xi8> — though the
    # 4-per-lane vector type confirms a packed compare WOULD be 4x.
    # Negative result recorded; the i32-domain compare is the floor.
    for n_bin in (67, 64, 32):
        bt = jnp.asarray(rng.randint(0, n_bin, (F, n_pad)).astype(np.uint8))
        t = timed(build("prod", n_bin), bt, pos, gh)
        print(f"prod    B={n_bin}: {t:7.2f} ms/level")
    bt = jnp.asarray(rng.randint(0, 64, (F, n_pad)).astype(np.uint8))
    t6 = timed(build("prod", 64, n_levels=6), bt, pos, gh, iters=20)
    print(f"shared6 B=64: {t6:7.2f} ms for 6 levels "
          f"({t6 / 6:.2f} ms/level-equivalent)")


if __name__ == "__main__":
    main()
