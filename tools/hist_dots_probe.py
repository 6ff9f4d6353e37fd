"""What sets the level kernel's cost per feature (ISSUE 32's probe).

The production level kernel (``ops/pallas_hist._hist_kernel``) runs, per
row tile of 2,048 rows and per feature, a ``(256, R)`` one-hot and one
``(256, R) @ (R, 2M)`` dot, and takes 0.34-0.39 us for it whatever M is.
This times the same body at 8.4M rows x 8 features (one feature tile),
M = 1 and M = 32, int8 and bf16, with

* the one-hot cut to 256, 128, 64 and 32 rows (WRONG sums: timing only):
  the slope over the rows is what a pushed one-hot row costs (its VPU
  compare and select, and its pass through the MXU), the intercept what
  a feature costs besides (loading the right-hand operand into the MXU,
  the accumulate, the step);
* the right-hand operand either the shared ``gh_exp`` of the production
  kernel, built once per row tile, or a ``(128, R)`` operand built PER
  FEATURE from the feature's bin ids (what folding the bin id's high
  bits into the lanes that 2M <= 64 leaves idle would need);

and, beside them, the forms of the padded-slot guard at 28 and 13
features (none = the parent's program; tile = one ``pl.when`` on the
tile index around the trailing slots; slot = one ``pl.when`` per slot;
split = two whole loops, eight slots under ``fi < last`` and the real
ones under ``fi == last``).

    chiprun -- python tools/hist_dots_probe.py            # on the chip
    JAX_PLATFORMS=cpu python tools/hist_dots_probe.py --rehearse

The table goes to stdout and to ``chiprun_out/hist_dots_probe.json``.
Nothing imports this file.
"""
import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from xgboost_tpu.ops.pallas_hist import _round_up  # noqa: E402

B, R_TILE, F_TILE = 256, 2048, 8


def make_kernel(mode, m_pad, hot_rows, rhs, n_feat, guard):
    """The ``hist_level_rows`` body with the probe's three knobs."""
    hot_dtype, acc_dtype = ((jnp.int8, jnp.int32) if mode == "int8"
                            else (jnp.bfloat16, jnp.float32))
    lanes = 2 * m_pad if rhs == "shared" else 128
    fold = lanes // (2 * m_pad)          # bin-id values folded into lanes
    shift = (B // fold).bit_length() - 1

    def kernel(binned_ref, pos_ref, gh_ref, out_ref):
        r_tile = binned_ref.shape[1]

        @pl.when(pl.program_id(1) == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        sub = jax.lax.broadcasted_iota(jnp.int32, (lanes, r_tile), 0)
        within = sub % (2 * m_pad)
        node_of_sub = jnp.where(within < m_pad, within, within - m_pad)
        ghsel = jnp.where(within < m_pad, gh_ref[0:1, :], gh_ref[1:2, :])
        active = pos_ref[0:1, :] == node_of_sub
        zero = jnp.zeros((), ghsel.dtype)
        gh_exp = jnp.where(active, ghsel, zero).astype(hot_dtype)
        bins = binned_ref[:].astype(jnp.int32)
        bin_ids = jax.lax.broadcasted_iota(jnp.int32, (hot_rows, r_tile), 0)
        hi_of_sub = sub // (2 * m_pad)

        def slot(f):
            b = bins[f:f + 1, :]
            if rhs == "shared":
                rhs_f = gh_exp
            else:       # lane l takes the rows whose bin id's high bits
                rhs_f = jnp.where(          # are l // 2M: built per feature
                    active & (b >> shift == hi_of_sub),
                    ghsel, zero).astype(hot_dtype)
            # bin ids past hot_rows match no row: fewer ones, same work
            onehot = (b == bin_ids).astype(hot_dtype)
            acc = jax.lax.dot_general(
                onehot, rhs_f, (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dtype)
            out_ref[f * hot_rows:(f + 1) * hot_rows, :] += acc

        fi = pl.program_id(0)
        last = (n_feat - 1) // F_TILE
        n_real = n_feat - last * F_TILE     # real slots of the last tile

        def slots(lo, hi):
            for f in range(lo, hi):
                slot(f)

        if guard == "split":        # two whole straight-line bodies
            pl.when(fi < last)(lambda: slots(0, F_TILE))
            pl.when(fi == last)(lambda: slots(0, n_real))
            return
        slots(0, F_TILE if guard == "none" else n_real)
        if guard == "tile":
            pl.when(fi < last)(lambda: slots(n_real, F_TILE))
        elif guard == "slot":
            for f in range(n_real, F_TILE):
                pl.when(fi * F_TILE + f < n_feat)(functools.partial(slot, f))
    return kernel, lanes, acc_dtype


def build(mode, m_pad, hot_rows, rhs, n_feat, guard, interpret):
    kernel, lanes, acc_dtype = make_kernel(mode, m_pad, hot_rows, rhs,
                                           n_feat, guard)
    f_pad = _round_up(n_feat, F_TILE)

    @jax.jit
    def fn(binned_t, pos, gh):
        n_pad = binned_t.shape[1]
        return pl.pallas_call(
            kernel,
            grid=(f_pad // F_TILE, n_pad // R_TILE),
            in_specs=[
                pl.BlockSpec((F_TILE, R_TILE), lambda fi, ri: (fi, ri)),
                pl.BlockSpec((1, R_TILE), lambda fi, ri: (0, ri)),
                pl.BlockSpec((2, R_TILE), lambda fi, ri: (0, ri)),
            ],
            out_specs=pl.BlockSpec((F_TILE * hot_rows, lanes),
                                   lambda fi, ri: (fi, 0)),
            out_shape=jax.ShapeDtypeStruct((f_pad * hot_rows, lanes),
                                           acc_dtype),
            interpret=interpret,
            name="hist_dots_probe",
        )(binned_t[:f_pad], pos, gh)
    return fn


def operands(n_rows, f_pad, m_pad, mode, seed=32):
    n_pad = _round_up(n_rows, R_TILE)
    rng = np.random.RandomState(seed)
    binned_t = rng.randint(0, B, (f_pad, n_pad)).astype(np.int32)
    pos = rng.randint(0, m_pad, (1, n_pad)).astype(np.int32)
    gh = rng.randint(-127, 128, (2, n_pad))
    gh = gh.astype(np.int32 if mode == "int8" else np.float32)
    return jnp.asarray(binned_t), jnp.asarray(pos), jnp.asarray(gh)


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))            # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), float(min(times))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true",
                    help="interpret mode, 4,096 rows: control flow only")
    ap.add_argument("--rows", type=int, default=8_400_000)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--only-guards", action="store_true")
    args = ap.parse_args()
    interp = args.rehearse
    n_rows = 4096 if interp else args.rows
    reps = 1 if interp else args.reps
    if not interp and jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU (or --rehearse)")
    n_tiles = _round_up(n_rows, R_TILE) // R_TILE
    rows = []

    def run(mode, m_pad, hot_rows, rhs, n_feat, guard, ops):
        fn = build(mode, m_pad, hot_rows, rhs, n_feat, guard, interp)
        med, low = timed(fn, ops, reps)
        real_dots = n_feat if guard != "none" else _round_up(n_feat, F_TILE)
        row = {"mode": mode, "M": m_pad, "hot_rows": hot_rows, "rhs": rhs,
               "F": n_feat, "guard": guard, "ms": med * 1e3,
               "ms_min": low * 1e3,
               "us_per_dot": med * 1e6 / (n_tiles * real_dots),
               "us_per_step": med * 1e6
               / (n_tiles * _round_up(n_feat, F_TILE) // F_TILE)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    # one feature tile of eight real features: rows of the one-hot and
    # the right-hand operand
    for mode in () if args.only_guards else ("int8", "bf16"):
        for m_pad in (1, 32):
            ops = operands(n_rows, F_TILE, m_pad, mode)
            for rhs in ("shared", "per_feature"):
                for hot_rows in (256, 128, 64, 32):
                    run(mode, m_pad, hot_rows, rhs, F_TILE, "none", ops)
    # the guard's forms where the last tile has padded slots
    for n_feat in (28, 13):
        for mode, m_pad in (("int8", 32), ("int8", 1), ("bf16", 32)):
            ops = operands(n_rows, _round_up(n_feat, F_TILE), m_pad, mode)
            for guard in ("none", "tile", "slot", "split"):
                run(mode, m_pad, B, "shared", n_feat, guard, ops)

    out = {"device": str(jax.devices()[0].device_kind), "rows": n_rows,
           "row_tiles": n_tiles, "reps": reps, "rehearsal": interp,
           "table": rows}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = ("hist_dots_probe_guards.json" if args.only_guards
            else "hist_dots_probe.json")
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"ok": True, "cases": len(rows),
                      "device": out["device"], "rehearsal": interp}))


if __name__ == "__main__":
    main()
