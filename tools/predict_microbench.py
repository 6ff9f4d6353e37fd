"""Prediction-traversal microbenchmark: sequential scan-over-trees vs
chunked tree-parallel vmap (models/tree.py ``tree_chunk``) at several
(T, N, depth) shapes, on whatever backend is active.

Synthetic random ensembles (uniform features/cuts, leaf values at the
bottom level) traverse identically to trained ones — the kernel cost
is shape-driven.  Every A/B cell first asserts the chunked margins are
BIT-identical to the scan's, then reports best-of-reps wall ms and the
speedup.  JSON output like ``tools/bench_serving.py``::

    python tools/predict_microbench.py [PREDICT_MICROBENCH.json]

Round 7 adds END-TO-END cells (``e2e_cells``): raw f32 row blocks
upload through ``external._prefetch_to_device`` and predict, A/B-ing
upload depth (0 = synchronous, 1, 2 = double-buffered) × fused
quantize+traverse vs the two-step quantize-then-traverse — the
transfer-wall knobs, with a per-cell bitwise
assert that fused margins equal two-step margins.

Env knobs: ``PRED_MB_SHAPES`` ("T,N,depth;..." cells),
``PRED_MB_CHUNKS`` (comma list), ``PRED_MB_REPS`` (default 5),
``PRED_MB_E2E_SHAPES`` (e2e "T,N,depth;..." cells),
``PRED_MB_E2E_DEPTHS`` (upload depths, default "1,2").
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from xgboost_tpu.models.tree import (  # noqa: E402
    TreeArrays, predict_margin_binned, predict_margin_fused,
    tree_capacity)

N_FEAT = 28
N_BIN = 64
DEFAULT_SHAPES = "100,1000000,6;100,100000,6;20,100000,6;100,100000,10"
DEFAULT_CHUNKS = "8,32"
DEFAULT_E2E_SHAPES = "100,200000,6;100,1000000,6"
DEFAULT_E2E_DEPTHS = "1,2"
E2E_BLOCKS = 4  # raw f32 row blocks per end-to-end prediction


def synth_ensemble(T, depth, n_feat, n_bin, seed=0):
    """(stack, group) of T random depth-``depth`` trees: every node
    above the bottom level splits, the bottom level is all leaves —
    the worst-case (deepest) traversal for the layout."""
    rng = np.random.RandomState(seed)
    n_nodes = tree_capacity(depth)
    bottom = (1 << depth) - 1
    feature = rng.randint(0, n_feat, size=(T, n_nodes)).astype(np.int32)
    feature[:, bottom:] = -1
    is_leaf = np.zeros((T, n_nodes), bool)
    is_leaf[:, bottom:] = True
    stack = TreeArrays(
        feature=jnp.asarray(feature),
        cut_index=jnp.asarray(
            rng.randint(0, n_bin - 2, size=(T, n_nodes)), jnp.int32),
        threshold=jnp.zeros((T, n_nodes), jnp.float32),
        default_left=jnp.asarray(rng.rand(T, n_nodes) < 0.5),
        is_leaf=jnp.asarray(is_leaf),
        leaf_value=jnp.asarray(
            rng.randn(T, n_nodes).astype(np.float32) * 0.1),
        gain=jnp.zeros((T, n_nodes), jnp.float32),
        sum_hess=jnp.ones((T, n_nodes), jnp.float32),
    )
    return stack, jnp.zeros(T, jnp.int32)


def barrier(x):
    jax.block_until_ready(x)


def timeit(fn, reps):
    out = fn()
    barrier(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        barrier(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3, out


def synth_raw(N, n_feat, n_bin, seed=3):
    """Raw f32 rows (with some NaN missing) + a sorted finite cut
    matrix: the end-to-end cells quantize these on device, so the
    two-step and fused paths start from identical host bytes."""
    rng = np.random.RandomState(seed)
    X = rng.rand(N, n_feat).astype(np.float32)
    X[:: 13, 0] = np.nan
    cuts = np.sort(rng.rand(n_feat, n_bin - 2).astype(np.float32),
                   axis=1)
    return X, cuts


def run_e2e(X, cuts, stack, group, depth, tree_chunk, upload_depth,
            fused):
    """One end-to-end prediction: raw f32 blocks → prefetch upload →
    (quantize →) traverse → concatenated margins.  This is the
    learner's one-off pipeline with the learner stripped away."""
    from xgboost_tpu.binning import bin_dense_device
    from xgboost_tpu.external import _prefetch_to_device
    N = X.shape[0]
    block = -(-N // E2E_BLOCKS)
    base = jnp.zeros((), jnp.float32)
    cuts_dev = jnp.asarray(cuts)

    def blocks():
        for s in range(0, N, block):
            yield s, X[s:s + block]

    parts = []
    for _, xd in _prefetch_to_device(blocks(), depth=upload_depth):
        if fused:
            parts.append(predict_margin_fused(
                stack, group, xd, cuts_dev, base, depth, 1,
                tree_chunk=tree_chunk))
        else:
            parts.append(predict_margin_binned(
                stack, group, bin_dense_device(xd, cuts_dev), base,
                depth, 1, tree_chunk=tree_chunk))
    return jnp.concatenate(parts, axis=0)


def e2e_main(reps, chunk):
    """End-to-end (upload+quantize+traverse) A/B grid: upload depth ×
    fused-vs-two-step, per shape.  Margins are bit-asserted equal
    across every variant of a cell."""
    shapes = [tuple(int(v) for v in cell.split(","))
              for cell in os.environ.get(
                  "PRED_MB_E2E_SHAPES", DEFAULT_E2E_SHAPES).split(";")
              if cell]
    depths = [int(d) for d in os.environ.get(
        "PRED_MB_E2E_DEPTHS", DEFAULT_E2E_DEPTHS).split(",")]
    cells = []
    for T, N, depth in shapes:
        X, cuts = synth_raw(N, N_FEAT, N_BIN)
        stack, group = synth_ensemble(T, depth, N_FEAT, N_BIN)
        cell = {"T": T, "N": N, "depth": depth, "blocks": E2E_BLOCKS,
                "tree_chunk": chunk}
        ref = None
        for fused in (False, True):
            for d in depths:
                ms, m = timeit(lambda: run_e2e(
                    X, cuts, stack, group, depth, chunk, d, fused),
                    reps)
                key = f"{'fused' if fused else 'twostep'}_depth{d}"
                cell[f"{key}_ms"] = round(ms, 2)
                cell[f"{key}_rows_per_sec"] = round(N / (ms / 1e3), 1)
                if ref is None:
                    ref = np.asarray(m)
                else:
                    bit = bool(np.array_equal(ref, np.asarray(m)))
                    cell[f"{key}_bit_identical"] = bit
                    assert bit, f"e2e margins diverged at {key} T={T}"
        cells.append(cell)
        print(json.dumps(cell))
    return cells


def main():
    shapes = [tuple(int(v) for v in cell.split(","))
              for cell in os.environ.get(
                  "PRED_MB_SHAPES", DEFAULT_SHAPES).split(";") if cell]
    chunks = [int(c) for c in os.environ.get(
        "PRED_MB_CHUNKS", DEFAULT_CHUNKS).split(",")]
    reps = int(os.environ.get("PRED_MB_REPS", "5"))
    base = jnp.zeros((), jnp.float32)
    cells = []
    for T, N, depth in shapes:
        rng = np.random.RandomState(1)
        binned = jnp.asarray(
            rng.randint(0, N_BIN, size=(N, N_FEAT)), jnp.uint8)
        stack, group = synth_ensemble(T, depth, N_FEAT, N_BIN)
        ms_scan, m_scan = timeit(
            lambda: predict_margin_binned(stack, group, binned, base,
                                          depth, 1, tree_chunk=0), reps)
        cell = {"T": T, "N": N, "depth": depth,
                "scan_ms": round(ms_scan, 2),
                "scan_rows_per_sec": round(N / (ms_scan / 1e3), 1)}
        for c in chunks:
            ms, m = timeit(
                lambda: predict_margin_binned(stack, group, binned, base,
                                              depth, 1, tree_chunk=c),
                reps)
            bit = bool(np.array_equal(np.asarray(m_scan), np.asarray(m)))
            cell[f"chunk{c}_ms"] = round(ms, 2)
            cell[f"chunk{c}_speedup"] = round(ms_scan / ms, 2)
            cell[f"chunk{c}_bit_identical"] = bit
            assert bit, f"chunked margins diverged at T={T} chunk={c}"
        cells.append(cell)
        print(json.dumps(cell))
    # e2e cells traverse at the auto-gate chunk (32 on TPU, scan on
    # CPU — gbtree.pred_chunk's own resolution), so the committed
    # numbers reflect what Learner.predict actually runs per backend
    e2e = e2e_main(reps, 32 if jax.default_backend() == "tpu" else 0)
    out = {"metric": "predict_traversal_scan_vs_chunked_ms",
           "backend": jax.default_backend(),
           "reps_best_of": reps, "n_feat": N_FEAT, "n_bin": N_BIN,
           "cells": cells, "e2e_cells": e2e}
    if len(sys.argv) > 1:
        with open(sys.argv[1], "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
