"""What sets the level kernel's cost per feature (ISSUE 32's probe).

The production level kernel (``ops/pallas_hist._hist_kernel``) runs, per
row tile of 2,048 rows and per feature, a ``(256, R)`` one-hot and one
``(256, R) @ (R, 2M)`` dot, and takes 0.34-0.39 us for it whatever M is.
This times the same body at 8.4M rows x 8 features (one feature tile),
M = 1 and M = 32, int8 and bf16, with

* the one-hot cut to 256, 128, 64 and 32 rows against the shared
  ``gh_exp`` (WRONG sums: timing only): the slope over the rows is what
  a pushed one-hot row costs (its VPU compare and select, and its pass
  through the MXU), the intercept what a feature costs besides (loading
  the right-hand operand into the MXU, the accumulate, the step);
* the SHIPPED kernel (``ops/pallas_hist._hist_pallas_pre``, right sums)
  with its fold (``_fold_of``: the bin id's high bits in the lanes that
  2M < 128 leaves idle, the right-hand operand built per feature) forced
  to each ``(rows, n_hi)`` that fits 128 lanes, M = 1 ... 32, beside the
  one ``_fold_of`` ships (ISSUE 34); kernel time from a device trace,
  so the call's XLA prologue and unfold are not in it.  ``--rehearse``
  checks every forced fold against the unfolded sums, bit for bit;
* the SHIPPED kernel as it ships at M = 1, 16, 32, 64 nodes with every
  row in a node against a random HALF of the rows parked (``pos`` -1),
  at the first cell's shape and the third's (``--only halves``, ISSUE
  38): a level that builds its left children only is the second; the
  kernel is dense, so the two should cost the same;

and, beside them, the forms of the padded-slot guard at 28 and 13
features (none = the parent's program; tile = one ``pl.when`` on the
tile index around the trailing slots; slot = one ``pl.when`` per slot;
split = two whole loops, eight slots under ``fi < last`` and the real
ones under ``fi == last``).

    chiprun -- python tools/hist_dots_probe.py            # on the chip
    chiprun -- python tools/hist_dots_probe.py --only folds
    chiprun -- python tools/hist_dots_probe.py --only halves
    JAX_PLATFORMS=cpu python tools/hist_dots_probe.py --rehearse

The table goes to stdout and to ``chiprun_out/hist_dots_probe.json``.
Nothing imports this file.
"""
import argparse
import functools
import glob
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from xgboost_tpu.ops import pallas_hist as ph  # noqa: E402
from xgboost_tpu.ops.pallas_hist import _round_up  # noqa: E402

B, R_TILE, F_TILE = 256, 2048, 8


def make_kernel(mode, m_pad, hot_rows, n_feat, guard):
    """The unfolded ``hist_level_rows`` body with the probe's knobs."""
    hot_dtype, acc_dtype = ((jnp.int8, jnp.int32) if mode == "int8"
                            else (jnp.bfloat16, jnp.float32))
    lanes = 2 * m_pad

    def kernel(binned_ref, pos_ref, gh_ref, out_ref):
        r_tile = binned_ref.shape[1]

        @pl.when(pl.program_id(1) == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        sub = jax.lax.broadcasted_iota(jnp.int32, (lanes, r_tile), 0)
        node_of_sub = jnp.where(sub < m_pad, sub, sub - m_pad)
        ghsel = jnp.where(sub < m_pad, gh_ref[0:1, :], gh_ref[1:2, :])
        active = pos_ref[0:1, :] == node_of_sub
        zero = jnp.zeros((), ghsel.dtype)
        gh_exp = jnp.where(active, ghsel, zero).astype(hot_dtype)
        bins = binned_ref[:].astype(jnp.int32)
        bin_ids = jax.lax.broadcasted_iota(jnp.int32, (hot_rows, r_tile), 0)

        def slot(f):
            # bin ids past hot_rows match no row: fewer ones, same work
            onehot = (bins[f:f + 1, :] == bin_ids).astype(hot_dtype)
            acc = jax.lax.dot_general(
                onehot, gh_exp, (((1,), (1,)), ((), ())),
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


def build(mode, m_pad, hot_rows, n_feat, guard, interpret):
    kernel, lanes, acc_dtype = make_kernel(mode, m_pad, hot_rows, n_feat,
                                           guard)
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


def bin_ids(n_rows, f_pad, n_bin=B, seed=32):
    """Made on the device: 8.4M x 32 bin ids are 1.1 GB."""
    return jax.random.randint(jax.random.PRNGKey(seed),
                              (f_pad, _round_up(n_rows, R_TILE)), 0, n_bin,
                              jnp.int32)


def row_operands(n_rows, m_pad, mode, seed=33, parked=0.0):
    """``(pos, gh)``: every row in one of the level's ``m_pad`` nodes,
    but for a random share ``parked`` of them, in none (-1)."""
    n_pad = _round_up(n_rows, R_TILE)
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    pos = jax.random.randint(k[0], (1, n_pad), 0, m_pad, jnp.int32)
    if parked:
        pos = jnp.where(jax.random.uniform(k[2], (1, n_pad)) < parked,
                        -1, pos)
    gh = jax.random.randint(k[1], (2, n_pad), -127, 128, jnp.int32)
    return pos, gh.astype(jnp.int32 if mode == "int8" else jnp.float32)


def operands(n_rows, f_pad, m_pad, mode):
    return (bin_ids(n_rows, f_pad),) + row_operands(n_rows, m_pad, mode)


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))            # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), float(min(times))


def kernel_timed(calls, reps):
    """Per ``(fn, args)`` of ``calls``: median and least device time (s)
    of its ``hist_level_rows`` kernel over ``reps`` calls, from ONE
    profiler trace of all of them in turn (starting a trace costs far
    more than the calls); the call's wall time where there is no device
    plane (``--rehearse``)."""
    from jax.profiler import ProfileData
    wall = [timed(fn, args, 1) for fn, args in calls]   # compiles, warms
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            for fn, args in calls:
                for _ in range(reps):
                    out = fn(*args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        data = ProfileData.from_file(files[-1])
        # an event is named by its HLO text, and the kernel's consumer
        # names the kernel too: match the op's own name, which comes first
        times = sorted((e.start_ns, e.duration_ns * 1e-9)
                       for plane in data.planes
                       if plane.name == "/device:TPU:0"
                       for line in plane.lines if line.name == "XLA Ops"
                       for e in line.events
                       if e.name.startswith("%hist_level_rows"))
    if len(times) != reps * len(calls):
        if times:                   # else: no device plane (--rehearse)
            print("# trace:", len(times), "kernel events for", reps, "x",
                  len(calls), "calls; wall times instead",
                  file=sys.stderr, flush=True)
        return wall
    times = [t for _, t in times]
    return [(float(np.median(times[i:i + reps])),
             float(min(times[i:i + reps])))
            for i in range(0, len(times), reps)]


_SHIPPED_FOLD_OF = ph._fold_of


def shipped_level(mode, m_pad, n_feat, n_bin, fold, interpret):
    """One level of the shipped kernel on prepared operands, its fold
    forced (``ph._fold_of`` is read at trace time): the kernel's native
    ``(F, B, 2, M)`` histogram."""
    def fn(binned_t, pos, gh):
        ph._fold_of = lambda *a: fold
        try:
            scale = jnp.ones((2,), jnp.float32) if mode == "int8" else None
            return ph._hist_pallas_pre(
                binned_t, gh.T, scale, pos[0], (binned_t.shape[1], n_feat),
                m_pad, n_bin, mode, interpret, native=True)
        finally:
            ph._fold_of = _SHIPPED_FOLD_OF
    return jax.jit(fn)


def folds_of(n_bin, m_pad, mode):
    """Unfolded, then every power-of-two fold that fits 128 lanes."""
    floor = {"int8": 32, "bf16": 16}[mode]
    out, rows = [(n_bin, 1)], n_bin // 2
    while rows >= floor and (n_bin // rows) * 2 * m_pad <= 128:
        out.append((rows, n_bin // rows))
        rows //= 2
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true",
                    help="interpret mode, 4,096 rows: control flow, and "
                    "every forced fold against the unfolded sums")
    ap.add_argument("--rows", type=int, default=8_400_000)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--only", choices=("rows", "folds", "guards", "halves"),
                    action="append", help="default: the first three")
    args = ap.parse_args()
    parts = args.only or ["rows", "folds", "guards"]
    interp = args.rehearse
    n_rows = 4096 if interp else args.rows
    reps = 1 if interp else args.reps
    if not interp and jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU (or --rehearse)")
    n_tiles = _round_up(n_rows, R_TILE) // R_TILE
    rows = []

    def report(row, med, low, n_feat, real_dots, tiles=n_tiles,
               f_tile=F_TILE):
        row.update(ms=med * 1e3, ms_min=low * 1e3,
                   us_per_dot=med * 1e6 / (tiles * real_dots),
                   us_per_step=med * 1e6
                   / (tiles * _round_up(n_feat, f_tile) // f_tile))
        rows.append(row)
        print(json.dumps(row), flush=True)

    def run(mode, m_pad, hot_rows, n_feat, guard, ops):
        fn = build(mode, m_pad, hot_rows, n_feat, guard, interp)
        med, low = timed(fn, ops, reps)
        report({"part": "guards" if n_feat != F_TILE else "rows",
                "mode": mode, "M": m_pad, "hot_rows": hot_rows,
                "F": n_feat, "guard": guard}, med, low, n_feat,
               n_feat if guard != "none" else _round_up(n_feat, F_TILE))

    def run_folds(n_feat, n_bin, n, levels, part="folds"):
        """``levels``: (mode, m_pad, folds[, parked share]) on one set
        of bin ids; one trace for the lot."""
        t0 = time.perf_counter()
        f_tile = ph._tiling(n, n_feat, n_bin)[1]
        f_pad = _round_up(n_feat, f_tile)
        binned_t = bin_ids(n, f_pad, n_bin)
        calls, heads = [], []
        for mode, m_pad, folds, *parked in levels:
            parked = parked[0] if parked else 0.0
            ops = (binned_t,) + row_operands(n, m_pad, mode, parked=parked)
            fns = [shipped_level(mode, m_pad, n_feat, n_bin, fold, interp)
                   for fold in folds]
            if interp:
                # sums only: every fold against the unfolded program
                want = np.asarray(shipped_level(
                    mode, m_pad, n_feat, n_bin, (n_bin, 1), interp)(*ops))
                assert want.any() and all(
                    np.array_equal(np.asarray(fn(*ops)), want)
                    for fn in fns), (mode, m_pad, n_bin, parked)
            calls += [(fn, ops) for fn in fns]
            ships = ph._fold_of(n_bin, m_pad, mode)
            heads += [{"part": part, "mode": mode, "M": m_pad,
                       "B": n_bin, "F": n_feat, "N": n, "rows": fold[0],
                       "n_hi": fold[1], "ships": fold == ships,
                       "parked": parked}
                      for fold in folds]
        for head, (med, low) in zip(heads, kernel_timed(calls, reps)):
            report(head, med, low, n_feat, n_feat,
                   tiles=_round_up(n, R_TILE) // R_TILE, f_tile=f_tile)
        print(f"# {len(calls)} kernels in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)

    # one feature tile of eight real features: rows of the one-hot
    if "rows" in parts:
        for mode in ("int8", "bf16"):
            for m_pad in (1, 32):
                ops = operands(n_rows, F_TILE, m_pad, mode)
                for hot_rows in (256, 128, 64, 32):
                    run(mode, m_pad, hot_rows, F_TILE, "none", ops)
    # the shipped kernel, its fold forced; then the cells' shapes and
    # the 64-bin smoke's, unfolded against what ships
    if "folds" in parts:
        levels = (1, 2, 4, 8, 16, 32)
        run_folds(F_TILE, B, n_rows,
                  [(mode, m, folds_of(B, m, mode))
                   for mode in ("int8", "bf16") for m in levels])
        run_folds(28, B, n_rows,
                  [("int8", m, [(B, 1), ph._fold_of(B, m, "int8")])
                   for m in levels])
        run_folds(28, 64, n_rows, [("int8", m, folds_of(64, m, "int8"))
                                   for m in levels[:5]])
    # what ships at M nodes, every row in a node against half of them
    # parked: the first cell's shape, then the third's (3.2 GB of bin ids)
    if "halves" in parts:
        for n_feat, n in ((28, n_rows), (2000, 400_000)):
            run_folds(n_feat, B, 4096 if interp else n,
                      [("int8", m, [ph._fold_of(B, m, "int8")], parked)
                       for m in (1, 16, 32, 64) for parked in (0.0, 0.5)],
                      part="halves")
    # the guard's forms where the last tile has padded slots
    if "guards" in parts:
        for n_feat in (28, 13):
            for mode, m_pad in (("int8", 32), ("int8", 1), ("bf16", 32)):
                ops = operands(n_rows, _round_up(n_feat, F_TILE), m_pad,
                               mode)
                for guard in ("none", "tile", "slot", "split"):
                    run(mode, m_pad, B, n_feat, guard, ops)

    out = {"device": str(jax.devices()[0].device_kind), "rows": n_rows,
           "row_tiles": n_tiles, "reps": reps, "rehearsal": interp,
           "parts": parts, "table": rows}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = "hist_dots_probe_" + "_".join(parts) + ".json"
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"ok": True, "cases": len(rows),
                      "device": out["device"], "rehearsal": interp}))


if __name__ == "__main__":
    main()
