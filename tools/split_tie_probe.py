"""Where tree 0 of the program and of `benchmark/reference.py` part, and
what each side's arithmetic says of the two candidates (PERF.md §6, "PR
36": why `loss_r0` has no limit in the widest cell).

One cell, one seed: the cell's data, one round of the program through
the public entry, one round of the reference on the program's cuts and
bins.  For every node at which the two trees choose another (feature,
cut) while all its ancestors agree, it prints the node's rows, both
candidates' exact left counts (round 0: g = 0.5 - y, h = 0.25, so every
sum is a count), and the loss change of both candidates as each side
computes it: the reference's float32 expression on the exact sums, and
the program's, read out of its own level histogram and split finder at
the shape the program runs (`ops.split._first_max` is watched, nothing
is re-derived); and the reference's own level function on the same
rows, beside a copy of its lines that hands out what its argmax saw.
Run it on the chip:

    chiprun -- python3 tools/split_tie_probe.py \
        --workload epsilon-shape-synth.train_logloss --seed 3600003612
"""

import argparse
import gc
import importlib
import io
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path[:0] = [ROOT, BENCH]


def hexf(x) -> str:
    return float(np.float32(x)).hex()


def positions(tree, bins: np.ndarray, depth: int) -> np.ndarray:
    """Level-local node of every row at `depth` of tree 0, -1 where the
    row stopped in a leaf above it."""
    n = bins.shape[0]
    rows = np.arange(n)
    node, alive = np.zeros(n, np.int64), np.ones(n, bool)
    for _ in range(depth):
        f = tree.feature[0][node]
        alive &= ~(tree.is_leaf[0][node] | (f < 0))
        left = bins[rows, np.maximum(f, 0)] <= tree.cut_index[0][node] + 1
        node = np.where(alive, 2 * node + 2 - left, node)
    return np.where(alive, node - ((1 << depth) - 1), -1).astype(np.int32)


def program_side(bins, y, pos, depth, j, cands, n_cuts, n_bin, params):
    """The program's level histogram and finder at `depth`, on the rows
    as `pos` places them: the finder's pick at node j, and the left sums
    and loss change it computed for each candidate (default right)."""
    import jax
    import jax.numpy as jnp
    from xgboost_tpu.ops import histogram as oh, split
    prec = params.get("hist_precision", "auto")
    cfg = split.SplitConfig(reg_lambda=float(params.get("lambda", 1.0)),
                            min_child_weight=float(
                                params.get("min_child_weight", 1.0)),
                            eta=float(params["eta"]))
    n_node = 1 << depth
    seen = {}
    real = split._first_max

    def watched(loss_chg, GL, HL, f_ax, c_ax, d_ax):
        seen.update(loss=loss_chg, GL=GL, HL=HL, axes=(f_ax, c_ax, d_ax))
        return real(loss_chg, GL, HL, f_ax, c_ax, d_ax)

    def level(binned, gh, pos, n_cuts):
        prep = oh.prepare_hist(binned, gh, n_bin, prec)
        native = prep is not None and n_node <= 64
        hist = oh.dequantize_hist(oh.build_level_histogram(
            binned, gh, pos, n_node, n_bin, prec, prep=prep, native=native))
        nst = (oh.stats_from_histogram_native(hist) if native
               else oh.stats_from_histogram(hist))
        finder = (split.find_best_splits_native if native
                  else split.find_best_splits)
        best = finder(hist, nst, n_cuts, cfg)
        f_ax, c_ax, d_ax = seen["axes"]

        def at(x, f, c):
            idx = [j] * x.ndim          # the one axis left is the node's
            idx[f_ax], idx[c_ax], idx[d_ax] = f, c, 0
            return x[tuple(idx)]
        return (best.feature[j], best.cut_index[j], best.gain[j], nst[j],
                jnp.stack([jnp.stack([at(seen[k], f, c) for k in
                                      ("GL", "HL", "loss")])
                           for f, c in cands]))

    gh = np.stack([0.5 - y, np.full(len(y), 0.25)], 1).astype(np.float32)
    split._first_max = watched
    try:
        out = jax.jit(level)(jnp.asarray(bins), jnp.asarray(gh),
                             jnp.asarray(pos), jnp.asarray(n_cuts))
    finally:
        split._first_max = real
    f, c, gain, nst, per = (np.asarray(x) for x in out)
    return int(f), int(c), np.float32(gain), nst.astype(np.float32), per


def reference_side(sums, lam: float):
    """The reference's float32 loss change (`reference._level_fn`) of
    candidates whose exact sums are (GL, HL, Gt, Ht), on this device."""
    import jax
    import jax.numpy as jnp

    def chg(GL, HL, Gt, Ht):
        def gain(g, h):
            return g * g / (h + lam)
        return gain(GL, HL) + gain(Gt - GL, Ht - HL) - gain(Gt, Ht)
    cols = [jnp.asarray(np.asarray(c, np.float32)) for c in zip(*sums)]
    return np.asarray(jax.jit(chg)(*cols))


def reference_level(bins, y, pos, depth, j, cands, n_cuts, n_bin, lam, mcw,
                    eta, blk: int = 4096) -> dict:
    """What `reference._level_fn` itself returns for node j at `depth`
    on these rows, beside a copy of its lines that also hands out the
    candidates' loss change before and after the `(M, F, C) -> (M, F *
    C)` reshape its argmax runs over."""
    import jax
    import jax.numpy as jnp
    import reference as ref
    M, B, C = 1 << depth, n_bin, n_bin - 2
    N, F = bins.shape
    pad = -(-N // blk) * blk - N
    bins_d = jnp.asarray(np.pad(bins, ((0, pad), (0, 0))))
    gh = jnp.asarray(np.pad(np.stack([0.5 - y, np.full(N, 0.25)], 1)
                            .astype(np.float32), ((0, pad), (0, 0))))
    pos_d = jnp.asarray(np.pad(pos, (0, pad), constant_values=-1))
    cuts_d = jnp.asarray(n_cuts)
    node = ref._level_fn(M, B, blk, lam, mcw, eta)(
        bins_d, gh, pos_d, cuts_d, jnp.zeros(N + pad, jnp.float32))[0]
    bf16, f32 = jnp.bfloat16, jnp.float32

    def level(bins, gh, pos, n_cuts):       # reference._level_fn, to `best`
        hi = gh.astype(bf16)
        r = gh - hi.astype(f32)
        mid = r.astype(bf16)
        lo = (r - mid.astype(f32)).astype(bf16)
        pieces = jnp.stack([hi, mid, lo], axis=-1)
        nodes = jnp.arange(M, dtype=jnp.int32)
        bin_ids_ = jnp.arange(B, dtype=jnp.int32)

        def body(acc, i):
            b = jax.lax.dynamic_slice_in_dim(bins, i * blk, blk)
            p = jax.lax.dynamic_slice_in_dim(pieces, i * blk, blk)
            q = jax.lax.dynamic_slice_in_dim(pos, i * blk, blk)
            a = jnp.where((q[:, None] == nodes)[:, :, None, None],
                          p[:, None], jnp.zeros((), bf16)).reshape(blk, M * 6)
            oh = (b.astype(jnp.int32)[:, :, None] == bin_ids_
                  ).astype(bf16).reshape(blk, F * B)
            return acc + jax.lax.dot_general(
                a, oh, (((0,), (0,)), ((), ())),
                preferred_element_type=f32), None
        acc, _ = jax.lax.scan(body, jnp.zeros((M * 6, F * B), f32),
                              jnp.arange((N + pad) // blk))
        hist = acc.reshape(M, 2, 3, F, B).sum(axis=2)
        G, H = hist[:, 0], hist[:, 1]
        Gt, Ht = G[:, 0].sum(-1), H[:, 0].sum(-1)
        GL = jnp.cumsum(G[:, :, 1:], axis=-1)[:, :, :C]
        HL = jnp.cumsum(H[:, :, 1:], axis=-1)[:, :, :C]
        GR, HR = Gt[:, None, None] - GL, Ht[:, None, None] - HL

        def gain(g, h):
            return g * g / (h + lam)
        chg = gain(GL, HL) + gain(GR, HR) - gain(Gt, Ht)[:, None, None]
        ok = ((HL >= mcw) & (HR >= mcw)
              & (jnp.arange(C)[None, :] < n_cuts[:, None])[None])
        kept = jnp.where(ok, chg, -1e30)
        flat = kept.reshape(M, F * C)
        best = jnp.argmax(flat, axis=1)
        return (best[j], flat[j, best[j]], flat[j], kept[j],
                jnp.stack([Gt[j], Ht[j]]),
                jnp.stack([jnp.stack([GL[j, f, c], HL[j, f, c]])
                           for f, c in cands]),
                jnp.stack([hist[j, :, f] for f, _ in cands]))
    best, best_gain, flat, kept, tot, sums, cells = (
        np.asarray(x) for x in jax.jit(level)(bins_d, gh, pos_d, cuts_d))
    # the two features' histogram cells against counts taken here
    mine = pos == j
    off = []
    for (f, _), got in zip(cands, cells):
        for ch, w in enumerate((0.5 - y[mine], np.full(mine.sum(), 0.25))):
            want = np.bincount(bins[mine, f], weights=w, minlength=B)
            off += [[f, int(b), "gh"[ch], float(got[ch, b]), float(want[b])]
                    for b in np.flatnonzero(got[ch] != want)]
    moved = np.flatnonzero(flat != kept.reshape(-1))
    top = int(np.argmax(kept.reshape(-1)))
    return {
        "level_fn_picks": [int(node["feature"][j]), int(node["cut_index"][j])],
        "copy_argmax_of_flat": [int(best) // C, int(best) % C],
        "copy_gain_at_argmax": float(best_gain),
        "numpy_argmax_of_unreshaped": [top // C, top % C],
        "numpy_max_of_unreshaped": float(kept.reshape(-1)[top]),
        "total": tot.tolist(),
        "histogram_cells_off": len(off), "first_cells_off": off[:12],
        "cells_the_reshape_moved": int(moved.size),
        "first_moved": [[int(i) // C, int(i) % C, float(flat[i]),
                         float(kept.reshape(-1)[i])] for i in moved[:8]],
        "candidates": [{"feature": f, "cut": c, "GL": float(s[0]),
                        "HL": float(s[1]), "unreshaped": float(kept[f, c]),
                        "flat": float(flat[f * C + c])}
                       for (f, c), s in zip(cands, sums)]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cells", default="", help="a directory laid out as "
                    "benchmark/tests/cells, for a rehearsal on the CPU")
    ap.add_argument("--node", type=int, default=-1, help="report this node "
                    "(heap index) too, whether or not the trees part there")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    import reference as ref
    import run
    where = (a.cells, a.cells) if a.cells else (BENCH, ROOT)
    _, _, _, cfg = run.find_cell(a.workload, *where)
    p = cfg["params"]
    import jax
    import xgboost_tpu as xgb
    dev = jax.devices()[0]
    gen = importlib.import_module(f"datagen.{cfg['generator']}")
    data = gen.generate(a.seed, cfg["n_train"], cfg["n_held"],
                        cfg["features"], **cfg.get("generator_args", {}))
    dtrain = xgb.DMatrix(data["X_train"], label=data["y_train"])
    dheld = xgb.DMatrix(data["X_held"], label=data["y_held"])
    bst = xgb.Booster(dict(p))
    lines = {}
    bst.update_many(dtrain, 0, 1, evals=[(dheld, "test")],
                    eval_callback=lines.__setitem__)
    out = run.program_outputs(bst, dtrain, lines, cfg["features"])
    raw = np.load(io.BytesIO(bst.save_raw()))
    gain_rec = np.asarray(raw["tree_gain"])[0]
    bins = np.ascontiguousarray(out.bins_train)
    del bst, dtrain, dheld
    gc.collect()
    run.say(f"program: {lines}")

    D = int(p["max_depth"])
    lam = float(p.get("lambda", 1.0))
    n_cuts = np.asarray([len(c) for c in out.cuts], np.int32)
    n_bin = int(n_cuts.max()) + 2
    fit = ref.fit(bins, data["y_train"], ref.bin_ids(data["X_held"], out.cuts),
                  data["y_held"], out.cuts,
                  ref.objective(p["objective"], p["eval_metric"]),
                  n_rounds=1, max_depth=D, n_bin=n_bin, eta=float(p["eta"]),
                  reg_lambda=lam,
                  min_child_weight=float(p.get("min_child_weight", 1.0)),
                  base_score=float(p.get("base_score", 0.5)))
    P, R = out.trees, fit.trees
    run.say(f"reference: {fit.evals}")

    differ = lambda k: (P.feature[0][k] != R.feature[0][k] or (  # noqa: E731
        P.feature[0][k] >= 0 and P.cut_index[0][k] != R.cut_index[0][k]))
    inner = (1 << D) - 1
    parted = [k for k in range(inner) if differ(k)]
    first = [k for k in parted
             if not any(differ(q) for q in _ancestors(k))]
    y = np.asarray(data["y_train"], np.float32)
    C = n_bin - 2
    report = {"workload": a.workload, "seed": a.seed,
              "device": f"{dev.platform}/{dev.device_kind}",
              "eval_r0": {"program": lines.get(0), "reference": fit.evals[0]},
              "split_nodes": int(np.sum(P.feature[0][:inner] >= 0)),
              "nodes_that_differ": len(parted), "first_to_differ": first,
              "nodes": []}
    for k in first[:4] + [a.node] * (0 <= a.node < inner):
        depth = int(np.log2(k + 1))
        j = k - ((1 << depth) - 1)
        pos = positions(P, bins, depth)
        mine = pos == j
        cands = [(int(t.feature[0][k]), int(t.cut_index[0][k]))
                 for t in (P, R)]
        n, n1 = int(mine.sum()), int(y[mine].sum())
        sums, rows = [], []
        for f, c in cands:
            left = mine & (bins[:, f] <= c + 1)
            nl, n1l = int(left.sum()), int(y[left].sum())
            rows.append({"feature": f, "cut": c, "flat_index": f * C + c,
                         "rows_left": nl, "label1_left": n1l,
                         "int8_sum_g_left": 127 * (nl - 2 * n1l),
                         "int8_sum_h_left": 127 * nl})
            sums.append((0.5 * (nl - 2 * n1l), 0.25 * nl,
                         0.5 * (n - 2 * n1), 0.25 * n))
        ref_chg = reference_side(sums, lam)
        pf, pc, pgain, nst, per = program_side(
            bins, y, pos, depth, j, cands, n_cuts, n_bin, p)
        for r, s, rc, (GL, HL, chg) in zip(rows, sums, ref_chg, per):
            GR, HR = nst[0] - GL, nst[1] - HL
            r["reference"] = {"GL": s[0], "HL": s[1], "GR": s[2] - s[0],
                              "HR": s[3] - s[1], "loss_chg": float(rc),
                              "loss_chg_hex": hexf(rc)}
            r["program"] = {"GL": float(GL), "HL": float(HL),
                            "GR": float(GR), "HR": float(HR),
                            "GL_hex": hexf(GL), "GR_hex": hexf(GR),
                            "loss_chg": float(chg), "loss_chg_hex": hexf(chg)}
        ref_own = reference_level(
            bins, y, pos, depth, j, cands, n_cuts, n_bin, lam,
            float(p.get("min_child_weight", 1.0)), float(p["eta"]))
        a_, b_ = rows
        la, lb = ((r["rows_left"], r["label1_left"]) for r in rows)
        counts = ("the same candidate" if cands[0] == cands[1] else
                  "the same left counts" if la == lb else
                  "mirrored: one's left counts are the other's right"
                  if la == (n - lb[0], n1 - lb[1]) else "different counts")
        report["nodes"].append({
            "node": k, "depth": depth, "rows": n, "label1": n1,
            "program_total": [float(nst[0]), float(nst[1])],
            "program_picks": a_, "reference_picks": b_,
            "counts": counts, "reference_level": ref_own,
            "reference_sum_hess": float(R.sum_hess[0][k]),
            "program_sum_hess": float(P.sum_hess[0][k]),
            "reference_says": _verdict(ref_chg[0], ref_chg[1]),
            "program_says": _verdict(per[0][2], per[1][2]),
            "checks": {
                "finder_rerun_picks_the_tree_s_split": (pf, pc) == cands[0],
                "rerun_gain_is_the_recorded_gain_bit_for_bit":
                    hexf(pgain) == hexf(gain_rec[k]) == hexf(per[0][2])}})
    text = json.dumps(report, indent=1)
    print(text)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            f.write(text + "\n")
    return 0


def _ancestors(k: int):
    while k:
        k = (k - 1) // 2
        yield k


def _verdict(own, other) -> str:
    """The program's pick against the reference's, by one side's
    float32 loss change."""
    own, other = np.float32(own), np.float32(other)
    if own == other:
        return "tie: equal bit for bit, the lower (feature, cut) wins"
    ulp = abs(float(own) - float(other)) / float(np.spacing(max(own, other)))
    return (f"program's pick {'higher' if own > other else 'lower'} "
            f"by {ulp:.1f} ulp")


if __name__ == "__main__":
    sys.exit(main())
