"""Rank-gradient op A/B.

The device LambdaRank gradient (rank_device.rank_gradient) is down to
one unstable 2-key sort + one inverse-permutation scatter + two
gathers; the inv-scatter (~7 ms at 1M) is the biggest single op left.
This tool times, at the bench shape (1M rows, 10k groups of 100), the
candidate replacements amortized inside one lax.scan launch:

  sort3        — the 2-key sort alone (floor for any sort-based path)
  scatter_inv  — sort + at[order].set(iota)       (production today)
  sort_inv     — sort + SECOND sort of (order, iota) (payload = inv)
  grad_now     — full rank_gradient(ndcg, 1 pairsample) as shipped
  pad_posn     — group-PADDED formulation: pred laid out (G, L) with
                 lane padding, per-row pred-rank by an L-wide
                 broadcast-compare count (no sort, no scatter)
  pad_partner  — padded partner read: one-hot select of C=4 channels
                 over lanes as a (G, L, L) x (G, L, C) batched MXU dot
  pad_full     — pad_posn + pad_partner + the ndcg weight/sigmoid
                 math = the padded gradient candidate end-to-end

Uniform groups here let the padded layout be a literal reshape; the
real entry would pad each group to the lane boundary at ingestion
(static index maps, built once).
"""
import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N, G = 1_000_000, 10_000
GS = N // G          # true group size
L = 128              # padded lane width


def timed(fn, *args, iters=50):
    @jax.jit
    def loop(*a):
        def body(c, _):
            out = fn(a[0] + c * 1e-20, *a[1:])
            leaf = jax.tree.leaves(out)[0]
            return c + (leaf.reshape(-1)[0].astype(jnp.float32) % 7.0
                        ) * 1e-20, None
        c, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=iters)
        return c

    r = loop(*args); jax.block_until_ready(r); float(r)
    t0 = time.perf_counter()
    float(loop(*args))
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    rng = np.random.RandomState(0)
    pred = jnp.asarray(rng.randn(N).astype(np.float32))
    labels = rng.randint(0, 5, N).astype(np.float32)
    gptr = np.arange(0, N + 1, GS)

    from xgboost_tpu.rank_device import build_prep, rank_gradient
    prep = build_prep(labels, gptr, N)
    rows = jnp.arange(N, dtype=jnp.int32)
    gkey = jnp.where(prep.group_of < 0, jnp.int32(2**31 - 1),
                     prep.group_of)

    def sort3(p):
        _, _, order = jax.lax.sort((gkey, -p, rows), dimension=0,
                                   num_keys=2, is_stable=False)
        return order

    def scatter_inv(p):
        order = sort3(p)
        return jnp.zeros(N, jnp.int32).at[order].set(rows)

    def sort_inv(p):
        order = sort3(p)
        _, inv = jax.lax.sort((order, rows), dimension=0, num_keys=1,
                              is_stable=False)
        return inv

    def grad_now(p, key):
        return rank_gradient(p, key, prep, "ndcg", 1)

    # ---- padded formulation (uniform groups -> literal reshape) ----
    lab_pad = jnp.pad(jnp.asarray(labels).reshape(G, GS),
                      ((0, 0), (0, L - GS)))
    valid_pad = jnp.pad(jnp.ones((G, GS), jnp.bool_),
                        ((0, 0), (0, L - GS)))
    lane = jnp.arange(L, dtype=jnp.int32)

    def to_pad(p):
        P = p.reshape(G, GS)
        return jnp.pad(P, ((0, 0), (0, L - GS)),
                       constant_values=-jnp.inf)

    def pad_posn(p):
        P = to_pad(p)                      # (G, L)
        # pred-rank within group: count of strictly-better peers
        gt = (P[:, None, :] > P[:, :, None]) | (
            (P[:, None, :] == P[:, :, None]) & (lane[None, None, :]
                                                < lane[None, :, None]))
        gt = gt & valid_pad[:, None, :]
        return gt.sum(axis=2).astype(jnp.int32)   # (G, L)

    # static partner index per (g, i) in [0, L): drawn once here; the
    # real path draws per round from fold_in, same shape/cost class
    partner_idx = jnp.asarray(
        rng.randint(0, GS, (G, L)).astype(np.int32))

    def pad_partner(p):
        P = to_pad(p)
        posn = pad_posn(p).astype(jnp.float32)
        n_other = jnp.broadcast_to(jnp.float32(GS), (G, L))
        tab = jnp.stack([lab_pad, P, posn, n_other], axis=2)  # (G, L, C)
        onehot = (partner_idx[:, :, None] == lane[None, None, :]
                  ).astype(jnp.bfloat16)                      # (G, L, L)
        part = jax.lax.dot_general(
            onehot, tab.astype(jnp.bfloat16),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # (G, L, C)
        return part

    def pad_full(p, key):
        P = to_pad(p)
        posn = pad_posn(p).astype(jnp.float32)
        n_other = jnp.broadcast_to(jnp.float32(GS), (G, L))
        tab = jnp.stack([lab_pad, P, posn, n_other], axis=2)
        u = jax.random.randint(key, (G, L), 0, 1 << 30) % GS
        onehot = (u[:, :, None] == lane[None, None, :]
                  ).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            onehot, tab.astype(jnp.bfloat16),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        lab_p, pred_p, posn_p = part[..., 0], part[..., 1], part[..., 2]
        hi = lab_pad > lab_p
        p_pos = jnp.where(hi, posn, posn_p)
        p_neg = jnp.where(hi, posn_p, posn)
        lab_hi = jnp.maximum(lab_pad, lab_p)
        lab_lo = jnp.minimum(lab_pad, lab_p)
        pos_li = 1.0 / jnp.log(p_pos + 2.0)
        neg_li = 1.0 / jnp.log(p_neg + 2.0)
        pg = 2.0 ** lab_hi - 1.0
        ng = 2.0 ** lab_lo - 1.0
        w = jnp.abs((pg * pos_li + ng * neg_li)
                    - (ng * pos_li + pg * neg_li))
        s = jax.nn.sigmoid(jnp.where(hi, P - pred_p, pred_p - P))
        g = (s - 1.0) * w
        h = jnp.maximum(s * (1.0 - s), 1e-16) * 2.0 * w
        g = jnp.where(valid_pad, jnp.where(hi, g, -g) * 2.0, 0.0)
        h = jnp.where(valid_pad, h * 2.0, 0.0)
        return jnp.stack([g, h], axis=2)

    # ragged pad/unpad gathers: if cheap, the padded gradient can run
    # on the EXISTING row layout (pad per round); if they cost like the
    # random 1M gathers (~5-8 ms), the entry must relayout at ingestion
    pad_idx = jnp.asarray(
        (np.arange(G)[:, None] * GS
         + np.minimum(np.arange(L)[None, :], GS - 1)).astype(np.int32))
    unpad_idx = jnp.asarray(
        (np.arange(N, dtype=np.int64) // GS * L
         + np.arange(N, dtype=np.int64) % GS).astype(np.int32))

    def pad_gather(p):
        return p[pad_idx]

    def unpad_gather(p):
        big = jnp.tile(p, 2)[:G * L]
        return big[unpad_idx]

    key = jax.random.PRNGKey(7)
    out = {}
    out["pad_gather"] = timed(pad_gather, pred)
    out["unpad_gather"] = timed(unpad_gather, pred)
    out["sort3"] = timed(sort3, pred)
    out["scatter_inv"] = timed(scatter_inv, pred)
    out["sort_inv"] = timed(sort_inv, pred)
    out["grad_now"] = timed(grad_now, pred, key)
    out["pad_posn"] = timed(pad_posn, pred)
    out["pad_partner"] = timed(pad_partner, pred)
    out["pad_full"] = timed(pad_full, pred, key)
    for k, v in out.items():
        print(f"{k:12s} {v:8.2f} ms")


if __name__ == "__main__":
    main()
