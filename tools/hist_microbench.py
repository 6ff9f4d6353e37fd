"""Microbenchmark of Pallas histogram kernel variants on the real chip.

Measures build_level_histogram_pallas-style kernels at the bench shape
(1M x 28, B=67, depth-6 level M=64) to guide kernel tuning.  Variants:

  base      — production kernel (f32 one-hot, selected precision)
  bf16hot   — one-hot built directly in bf16 (halves VMEM write traffic)
  i16cmp    — bin ids held as int16 in VMEM (halves compare read traffic)

Usage: python tools/hist_microbench.py [n_rows] [n_feat] [n_bin]
"""
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

sys.path.insert(0, ".")
from xgboost_tpu.ops.pallas_hist import (  # noqa: E402
    _round_up, build_level_histogram_pallas)


def _variant_kernel(binned_ref, pos_ref, gh_ref, out_ref, *,
                    n_bin, m_pad, f_tile, precision_mode, hot_dtype):
    r_tile = binned_ref.shape[1]
    m2 = 2 * m_pad
    m_base = pl.program_id(0) * m_pad

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    pos = pos_ref[:, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (r_tile, m2), 1)
    node_of_lane = m_base + jnp.where(lane < m_pad, lane, lane - m_pad)
    g = gh_ref[:, 0:1]
    h = gh_ref[:, 1:2]
    ghsel = jnp.where(lane < m_pad, g, h)
    active = (pos[:, None] == node_of_lane)
    gh_exp = jnp.where(active, ghsel, 0.0).astype(hot_dtype)

    prec = (jax.lax.Precision.HIGHEST if precision_mode == "fp32"
            else jax.lax.Precision.DEFAULT)
    bins = binned_ref[:]
    bin_ids = jax.lax.broadcasted_iota(bins.dtype, (n_bin, r_tile), 0)
    for f in range(f_tile):
        onehot = (bins[f:f + 1, :] == bin_ids).astype(hot_dtype)
        acc = jax.lax.dot_general(
            onehot, gh_exp, (((1,), (0,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32)
        out_ref[0, f * n_bin:(f + 1) * n_bin, :] += acc


@functools.partial(jax.jit, static_argnames=(
    "n_node", "n_bin", "precision", "hot_dtype", "bin_dtype", "r_tile"))
def variant(binned, gh, pos, n_node, n_bin, precision="bf16",
            hot_dtype=jnp.float32, bin_dtype=jnp.int32, r_tile=1024):
    N, F = binned.shape
    m_pad = min(n_node, 64)
    n_m_tiles = -(-n_node // m_pad)
    f_tile = max(1, min(F, (256 * 1024) // (max(n_bin, 1)
                                            * max(2 * m_pad, 128))))
    if f_tile < F:
        f_tile = max(8, (f_tile // 8) * 8)
    n_pad = _round_up(max(N, 1), r_tile)
    f_pad = _round_up(F, f_tile)
    binned_t = binned.astype(bin_dtype).T
    if n_pad != N or f_pad != F:
        binned_t = jnp.pad(binned_t, ((0, f_pad - F), (0, n_pad - N)))
        gh = jnp.pad(gh, ((0, n_pad - N), (0, 0)))
        pos = jnp.pad(pos, (0, n_pad - N), constant_values=-1)
    kernel = functools.partial(_variant_kernel, n_bin=n_bin, m_pad=m_pad,
                               f_tile=f_tile, precision_mode=precision,
                               hot_dtype=hot_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(n_m_tiles, f_pad // f_tile, n_pad // r_tile),
        in_specs=[
            pl.BlockSpec((f_tile, r_tile), lambda mi, fi, ri: (fi, ri)),
            pl.BlockSpec((r_tile, 1), lambda mi, fi, ri: (ri, 0)),
            pl.BlockSpec((r_tile, 2), lambda mi, fi, ri: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((1, f_tile * n_bin, 2 * m_pad),
                               lambda mi, fi, ri: (mi, fi, 0)),
        out_shape=jax.ShapeDtypeStruct((n_m_tiles, f_pad * n_bin, 2 * m_pad),
                                       jnp.float32),
    )(binned_t, pos.reshape(-1, 1).astype(jnp.int32),
      gh.astype(jnp.float32))
    out = out.reshape(n_m_tiles, f_pad, n_bin, 2, m_pad)
    out = out.transpose(0, 4, 1, 2, 3).reshape(
        n_m_tiles * m_pad, f_pad, n_bin, 2)
    return out[:n_node, :F, :, :]


def barrier(x):
    jax.block_until_ready(x)


def timeit(fn, *args, reps=20, **kw):
    out = fn(*args, **kw)
    barrier(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    barrier(out)
    return (time.perf_counter() - t0) / reps * 1e3


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    f = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    b = int(sys.argv[3]) if len(sys.argv) > 3 else 67
    n_node = 64
    rng = np.random.RandomState(0)
    binned = jnp.asarray(rng.randint(0, b, size=(n, f)), jnp.int32)
    gh = jnp.asarray(rng.randn(n, 2), jnp.float32)
    pos = jnp.asarray(rng.randint(0, n_node, size=n), jnp.int32)

    ms = timeit(build_level_histogram_pallas, binned, gh, pos, n_node, b,
                precision="bf16")
    print(f"production bf16        : {ms:7.2f} ms")
    for name, kw in [
        ("base f32hot bf16mm", dict(precision="bf16",
                                    hot_dtype=jnp.float32)),
        ("bf16hot bf16mm", dict(precision="bf16", hot_dtype=jnp.bfloat16)),
        ("i16cmp f32hot", dict(precision="bf16", hot_dtype=jnp.float32,
                               bin_dtype=jnp.int16)),
        ("i16cmp bf16hot", dict(precision="bf16", hot_dtype=jnp.bfloat16,
                                bin_dtype=jnp.int16)),
        ("bf16hot r2048", dict(precision="bf16", hot_dtype=jnp.bfloat16,
                               r_tile=2048)),
        ("f32 HIGHEST (exact)", dict(precision="fp32",
                                     hot_dtype=jnp.float32)),
    ]:
        try:
            ms = timeit(variant, binned, gh, pos, n_node, b, **kw)
            print(f"{name:22s} : {ms:7.2f} ms")
        except Exception as e:
            print(f"{name:22s} : FAILED {type(e).__name__}: {str(e)[:90]}")


if __name__ == "__main__":
    main()
