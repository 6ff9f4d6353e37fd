"""The comparison that decides `correct`.

`Outputs` is what the timed object produced, as anyone holding the
booster can read it: the cuts and trees of the model bytes it returned,
the eval lines it printed, and the bin ids of its training entry.
`compare` runs the plain reference on the same data and returns every
number compared beside its limit:

  cuts_maxdiff    largest |cut - reference cut| over all features
  bins_mismatch   share of training cells whose bin id differs
  loss_r0..r2     held-out metric the program printed for rounds 0..2
                  against the reference's own three rounds (relative)
  cover_nodes     trees 0..2, the worst: node hessian sums the program
                  recorded against exact sums over the rows the tree
                  sends there, over sum h
  grad_nodes      the same for the node gradient sums, as the split
                  finder got them from the histograms (recovered from
                  each node's recorded weight), over sum |g|; a round's
                  gradient is taken at the margin of the program's own
                  earlier trees, so a flipped split does not enter
  dmargin_train   norm of the margin change of the first three trees on
  dmargin_held    the training / held-out rows against the reference's
                  (gap of norms over the reference's norm)
  eval_vs_trees   the last eval line against the metric of the returned
                  trees, walked by the reference on the raw held-out rows

A run is correct when every value that the cell's file gives a limit is
at or under it.  A number that has a limit and could not be taken (the
bin ids not found on the booster, a NaN) is written as 1e300 and fails.
A number with no limit there is read and printed, not compared.
"""

from __future__ import annotations

import time
from typing import NamedTuple, Optional

import numpy as np

import reference as ref


class Outputs(NamedTuple):
    cuts: list                      # per-feature float32 cut arrays
    bins_train: Optional[np.ndarray]
    evals: dict                     # iteration -> printed held-out metric
    trees: ref.Trees


def stand_in(fit: ref.Fit, cuts: list, bins_train: np.ndarray) -> Outputs:
    """The reference put in the program's place (controls and faults)."""
    return Outputs(cuts, bins_train, dict(enumerate(fit.evals)), fit.trees)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def node_sums(trees: ref.Trees, t: int, X, g, h, depth: int):
    """Exact float64 (G, H) of every node of tree t over the rows it
    sends there, and each row's leaf: sums at the leaves, then every
    node is itself plus its children."""
    n_nodes = trees.feature.shape[1]
    leaf = ref.leaf_of(trees, t, X, by="value", depth=depth)
    G = np.bincount(leaf, weights=g, minlength=n_nodes)
    H = np.bincount(leaf, weights=h, minlength=n_nodes)
    for k in range(n_nodes - 1, 0, -1):
        G[(k - 1) // 2] += G[k]
        H[(k - 1) // 2] += H[k]
    return G, H, leaf


def reference_side(data: dict, cfg: dict, log=lambda *_: None) -> dict:
    """Everything the reference computes from the data alone."""
    p = cfg["params"]
    t0 = time.perf_counter()
    cuts = ref.propose_cuts(
        data["X_train"], max_bin=p["max_bin"], sketch_eps=p["sketch_eps"],
        sketch_ratio=p.get("sketch_ratio", 2.0),
        bin_align=cfg.get("bin_align", 0))
    t1 = time.perf_counter()
    bins_train = ref.bin_ids(data["X_train"], cuts)
    bins_held = ref.bin_ids(data["X_held"], cuts)
    t2 = time.perf_counter()
    log(f"reference: cuts {t1 - t0:.2f} s, bins {t2 - t1:.2f} s")
    return dict(cuts=cuts, bins_train=bins_train, bins_held=bins_held,
                n_bin=max(len(c) for c in cuts) + 2,
                obj=ref.objective(p["objective"], p["eval_metric"]))


def reference_fit(side: dict, data: dict, cfg: dict, *, n_rounds: int = 3,
                  levels=None, drop_half=False) -> ref.Fit:
    p = cfg["params"]
    return ref.fit(side["bins_train"], data["y_train"], side["bins_held"],
                   data["y_held"], side["cuts"], side["obj"],
                   n_rounds=n_rounds, max_depth=p["max_depth"],
                   n_bin=side["n_bin"], eta=p["eta"],
                   reg_lambda=p.get("lambda", 1.0),
                   min_child_weight=p.get("min_child_weight", 1.0),
                   base_score=p.get("base_score", 0.5),
                   levels=levels, drop_half=drop_half)


def compare(out: Outputs, side: dict, fit: ref.Fit, data: dict, cfg: dict,
            limits: dict) -> dict:
    p = cfg["params"]
    D, eta = int(p["max_depth"]), float(p["eta"])
    lam = float(p.get("lambda", 1.0))
    mcw = float(p.get("min_child_weight", 1.0))
    obj = side["obj"]
    base = obj.base_margin(p.get("base_score", 0.5))
    nan = float("nan")
    v = {}

    same_shape = (len(out.cuts) == len(side["cuts"]) and all(
        len(a) == len(b) for a, b in zip(out.cuts, side["cuts"])))
    v["cuts_maxdiff"] = max(
        (float(np.max(np.abs(np.asarray(a, np.float64) - b), initial=0.0))
         for a, b in zip(out.cuts, side["cuts"])),
        default=0.0) if same_shape else float("inf")
    if out.bins_train is None:
        v["bins_mismatch"] = nan        # not taken: fails where it has a limit
    else:
        v["bins_mismatch"] = (
            float(np.mean(np.asarray(out.bins_train) != side["bins_train"]))
            if np.shape(out.bins_train) == side["bins_train"].shape
            else float("inf"))

    n_ref = len(fit.evals)
    for r in range(n_ref):
        v[f"loss_r{r}"] = (_rel(out.evals[r], fit.evals[r])
                           if r in out.evals else nan)

    T = out.trees.feature.shape[0]
    if T >= n_ref:
        # walk the program's first trees: each round's gradient is exact
        # (float64) at the margin the program's own earlier trees give
        margin = np.full(len(data["y_train"]), base, np.float64)
        cover = grad = 0.0
        for t in range(n_ref):
            g, h = obj.grad_np(margin, data["y_train"])
            G, H, leaf = node_sums(out.trees, t, data["X_train"], g, h, D)
            Hp = out.trees.sum_hess[t].astype(np.float64)
            Gp = -out.trees.leaf_value[t].astype(np.float64) * (Hp + lam) / eta
            live = Hp >= mcw
            cover = max(cover, float(np.sqrt(np.sum((Hp - H) ** 2)) / h.sum()))
            grad = max(grad, float(np.sqrt(np.sum((Gp - G)[live] ** 2))
                                   / np.abs(g).sum()))
            margin += out.trees.leaf_value[t][leaf]
        v["cover_nodes"], v["grad_nodes"] = cover, grad
        d_held = ref.margin_of(out.trees, data["X_held"], 0.0, by="value",
                               depth=D, n_trees=n_ref)
        v["dmargin_train"] = _rel(float(np.linalg.norm(margin - base)),
                                  float(np.linalg.norm(fit.train_delta)))
        v["dmargin_held"] = _rel(float(np.linalg.norm(d_held)),
                                 float(np.linalg.norm(fit.held_delta)))
    else:
        for k in ("cover_nodes", "grad_nodes", "dmargin_train", "dmargin_held"):
            v[k] = nan
    last = max(out.evals) if out.evals else -1
    if last >= 0 and T == last + 1:
        m = ref.margin_of(out.trees, data["X_held"], base, by="value", depth=D)
        v["eval_vs_trees"] = _rel(out.evals[last],
                                  obj.evaluate(m, data["y_held"]))
    else:
        v["eval_vs_trees"] = nan      # trees and eval lines do not line up
    for k in limits:
        v.setdefault(k, nan)          # a limit on a number nobody took
    # a number that could not be taken (NaN, inf) is written as 1e300: it
    # fails any limit and the result line stays plain JSON
    return {k: {"value": float(x) if np.isfinite(x) else 1e300,
                "limit": float(limits[k]) if k in limits else None}
            for k, x in v.items()}


def held(compared: dict) -> dict:
    """The numbers that have a limit: the ones `correct` rests on."""
    return {k: c for k, c in compared.items() if c["limit"] is not None}


def is_correct(compared: dict) -> bool:
    h = held(compared)
    return bool(h) and all(c["value"] <= c["limit"] for c in h.values())


def lines(compared: dict) -> list:
    return [f"{k} {c['value']:.6g} " + (
                "read, not compared" if c["limit"] is None else
                f"limit {c['limit']:.6g} "
                f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
            for k, c in sorted(compared.items(),
                               key=lambda kc: kc[1]["limit"] is not None)]
