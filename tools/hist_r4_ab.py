"""Round-4 histogram-kernel A/B (VERDICT r3 item 7).

Variants at the bench shape (1M x 28, B=64, deep level M=64), all
timed amortized inside one lax.scan launch (the fixed dispatch cost
divides out):

  prod      — production kernel, bf16 mode (the 33 r/s bench path)
  dotfloor  — same dots, one-hot replaced by a constant bf16 tile
              (isolates the one-hot build: prod - dotfloor = VPU cost)
  u8bins    — bins stored uint8 in HBM, widened in-kernel (4x less
              kernel input bandwidth)
  i16hot    — one-hot built by int16-select of 0x3F80 + bitcast to
              bf16 (the "int8/int16 compare via bitcast" candidate:
              avoids the int->float convert on the select)
  rtile=K   — r_tile sweep around the production 2048

Prints per-variant ms/level-equivalent and the implied bench celling.
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

N, F, B, M = 1_000_000, 28, 64, 64


def make_kernel(mode):
    def kernel(binned_ref, pos_ref, gh_ref, out_ref, *,
               n_bin, m_pad, f_tile):
        r_tile = binned_ref.shape[1]
        m2 = 2 * m_pad
        m_base = pl.program_id(0) * m_pad

        @pl.when(pl.program_id(2) == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        pos = pos_ref[:, 0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (r_tile, m2), 1)
        node_of_lane = m_base + jnp.where(lane < m_pad, lane,
                                          lane - m_pad)
        ghsel = jnp.where(lane < m_pad, gh_ref[:, 0:1], gh_ref[:, 1:2])
        gh_exp = jnp.where(pos[:, None] == node_of_lane, ghsel,
                           0.0).astype(jnp.bfloat16)

        bins = binned_ref[:]
        if mode == "u8bins":
            bins = bins.astype(jnp.int32)
        bin_ids = jax.lax.broadcasted_iota(jnp.int32, (n_bin, r_tile), 0)
        for f in range(f_tile):
            if mode == "dotfloor":
                onehot = (bin_ids < 1).astype(jnp.bfloat16)
            elif mode == "i16hot":
                eq = bins[f:f + 1, :] == bin_ids
                onehot = jax.lax.bitcast_convert_type(
                    jnp.where(eq, jnp.int16(0x3F80), jnp.int16(0)),
                    jnp.bfloat16)
            else:
                onehot = (bins[f:f + 1, :] == bin_ids).astype(
                    jnp.bfloat16)
            acc = jax.lax.dot_general(
                onehot, gh_exp, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)
            out_ref[0, f * n_bin:(f + 1) * n_bin, :] += acc

    return kernel


def build(mode, r_tile):
    bins_dtype = jnp.uint8 if mode == "u8bins" else jnp.int32

    @jax.jit
    def fn(binned_t, pos, gh):
        f_tile = F
        n_pad = binned_t.shape[1]
        kernel = functools.partial(make_kernel(mode), n_bin=B, m_pad=M,
                                   f_tile=f_tile)
        return pl.pallas_call(
            kernel,
            grid=(1, 1, n_pad // r_tile),
            in_specs=[
                pl.BlockSpec((f_tile, r_tile), lambda mi, fi, ri: (fi, ri)),
                pl.BlockSpec((r_tile, 1), lambda mi, fi, ri: (ri, 0)),
                pl.BlockSpec((r_tile, 2), lambda mi, fi, ri: (ri, 0)),
            ],
            out_specs=pl.BlockSpec((1, f_tile * B, 2 * M),
                                   lambda mi, fi, ri: (mi, fi, 0)),
            out_shape=jax.ShapeDtypeStruct((1, f_tile * B, 2 * M),
                                           jnp.float32),
        )(binned_t, pos, gh)

    return fn, bins_dtype


def timed(fn, binned_t, pos, gh, iters=30):
    @jax.jit
    def loop(b, p, g):
        def body(c, _):
            out = fn(b, p, g + c * 1e-20)
            return c + jnp.sum(out[0, :2, :2]) % 7.0 * 1e-20, None
        c, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=iters)
        return c

    r = loop(binned_t, pos, gh); jax.block_until_ready(r); float(r)
    t0 = time.perf_counter()
    float(loop(binned_t, pos, gh))
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    rng = np.random.RandomState(0)
    r_tile0 = 2048
    n_pad = _round_up(N, 8192)
    binned = rng.randint(0, B, (F, n_pad)).astype(np.int32)
    pos = rng.randint(0, M, (n_pad, 1)).astype(np.int32)
    gh = rng.randn(n_pad, 2).astype(np.float32)

    results = {}
    for mode in ("prod", "dotfloor", "u8bins", "i16hot"):
        for r_tile in ((1024, 2048, 4096) if mode == "prod"
                       else (r_tile0,)):
            fn, bdt = build(mode, r_tile)
            bt = jnp.asarray(binned.astype(np.uint8) if mode == "u8bins"
                             else binned)
            try:
                ms = timed(fn, bt, jnp.asarray(pos), jnp.asarray(gh))
                tag = f"{mode}@r{r_tile}"
                results[tag] = ms
                print(f"{tag:18s} {ms:7.2f} ms/level "
                      f"(x6 = {ms*6:6.1f} ms/round-equiv)")
            except Exception as e:
                print(f"{mode}@r{r_tile}: FAILED {type(e).__name__}: "
                      f"{str(e)[:200]}")
    if "prod@r2048" in results and "dotfloor@r2048" in results:
        p, d = results["prod@r2048"], results["dotfloor@r2048"]
        print(f"\none-hot build cost: {p - d:.2f} ms/level "
              f"({(p - d) / p * 100:.0f}% of kernel); dot floor "
              f"{d:.2f} ms/level -> floor bench ceiling ~"
              f"{1000 / (d * 6 + 7):.0f} r/s (with ~7 ms non-hist round)")


if __name__ == "__main__":
    main()
