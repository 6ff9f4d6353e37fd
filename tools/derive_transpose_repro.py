"""PR 38's derivation against the one shipped since PR 39, where the chip
showed the fault: one round of ``Booster.update_many`` at the fourth
cell's shape (the one-hot Airline rows, 695 columns, depth 10, int8),
and each level of tree 0 with the hessian sums the tree recorded beside
exact sums over the rows the tree sends there.

PR 38's ``_derive_level`` interleaved a tile's 128 children into two
tiles of the level by a 6-D transpose, every block in node order.  In
that program the chip read the 128-node level's sums as ZERO (each of
its nodes a leaf of weight 0; PERF.md section 6, "PR 39"); on the CPU
both forms give the same bits.  ``--forms transpose`` puts that form
back for one tree beside the shipped one.  ``--chain`` also runs
``chip_smoke.py --kernels``' scan case (the levels of 64 ... 512 nodes
alone in one ``lax.scan``) with each form: on the chip both were right
there, at 8,750 and 1,000,000 rows, so the fault needs more of the
grower's program than the histograms.  On the chip; ``--rows`` small
to rehearse here.

    python3 tools/derive_transpose_repro.py [--rows 1000000] \\
        [--forms transpose,shipped] [--seed 3900003901] [--chain]
"""

import argparse
import contextlib
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path[:0] = [ROOT, BENCH]


def _pr38_derive(parent, left, parent_split, n_node):
    """PR 38's ``_derive_level``, as it was (nodes in order)."""
    import jax.numpy as jnp
    M = n_node // 2
    m = min(M, 64)
    T = M // m
    fb = parent.shape[1]
    shape = (-1, T, fb, 2, m)
    left = left.reshape(shape)
    split = parent_split.reshape(1, T, 1, 1, m)
    right = jnp.where(split, parent.reshape(shape) - left, 0)
    out = jnp.stack([left, right], axis=-1)
    if 2 * m <= 64:
        return out.reshape(-1, fb, 4 * m)
    out = out.reshape(-1, T, fb, 2, 2, 64).transpose(0, 1, 4, 2, 3, 5)
    return out.reshape(-1, fb, 128)


@contextlib.contextmanager
def form(name):
    """``transpose``: PR 38's derivation, every block in node order."""
    import jax

    from xgboost_tpu.ops import pallas_hist as ph
    saved = ph._derive_level, ph._node_slot, ph._hist_level_tail
    if name == "transpose":
        tail = ph._hist_level_tail
        ph._derive_level = _pr38_derive
        ph._node_slot = lambda pos, n_node: pos
        ph._hist_level_tail = (lambda *a, slots=False, **k: tail(*a, **k))
    jax.clear_caches()              # the grower is traced anew
    try:
        yield
    finally:
        ph._derive_level, ph._node_slot, ph._hist_level_tail = saved


def tree_levels(rows: int, seed: int) -> list:
    """Per level of tree 0: (recorded hessian sum, exact sum)."""
    import compare
    import run
    import xgboost_tpu as xgb
    from datagen import airline_onehot
    cfg = run.load_json(BENCH, "configs", "airline-onehot-synth-d10-b256.json")
    depth = int(cfg["params"]["max_depth"])
    data = airline_onehot.generate(seed, rows, max(rows // 10, 1000),
                                   cfg["features"])
    X, y = data["X_train"], data["y_train"]
    dtrain = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(dict(cfg["params"]))
    bst.update_many(dtrain, 0, 1)
    trees = run.program_outputs(bst, dtrain, {}, cfg["features"]).trees
    g, h = 0.5 - y.astype(np.float64), np.full(rows, 0.25)
    _, H, _ = compare.node_sums(trees, 0, X, g, h, depth)
    Hp = trees.sum_hess[0].astype(np.float64)
    return [(float(Hp[(1 << d) - 1:(2 << d) - 1].sum()),
             float(H[(1 << d) - 1:(2 << d) - 1].sum()))
            for d in range(depth + 1)]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=1_000_000)
    p.add_argument("--forms", default="transpose,shipped")
    p.add_argument("--seed", type=int, default=3900003901)
    p.add_argument("--chain", action="store_true")
    a = p.parse_args()
    import jax

    import chip_smoke
    from xgboost_tpu.compile_cache import configure_compile_cache
    configure_compile_cache()
    bad = 0
    for f in a.forms.split(","):
        with form(f):
            t0 = time.perf_counter()
            levels = tree_levels(a.rows, a.seed)
            wrong = [d for d, (hp, hx) in enumerate(levels)
                     if abs(hp - hx) > 1e-6 * max(hx, 1.0)]
            bad += bool(wrong)
            print(f"{f:9s} tree 0: levels {wrong or 'none'} wrong "
                  f"({time.perf_counter() - t0:.1f} s); recorded / exact "
                  "hessian by level: " + ", ".join(
                      f"{hp:.6g}/{hx:.6g}" for hp, hx in levels), flush=True)
            if a.chain:
                name, build = next(
                    c for c in chip_smoke.kernel_cases(8 * a.rows)
                    if c[0].startswith("scan "))
                fn, args, verify = build(jax.default_backend() != "tpu")
                try:
                    verify(jax.jit(fn)(*args))
                    verdict = "right"
                except SystemExit as e:
                    verdict = f"WRONG: {e}"
                print(f"{f:9s} {name}: {verdict}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
