"""``ops.split._first_max``, the tail of both split finders, against a
numpy oracle: per node the FIRST maximum of the candidates flattened as
``(f * C + c) * 2 + d`` (lowest feature, then lowest cut, then
default-right), which is what the finders took by reshape + argmax
before the reduction went axis by axis.  Ties are forced across every
axis; a node with no candidate and a node with a NaN cell are among
the nodes of every case."""

import jax.numpy as jnp
import numpy as np
import pytest

from xgboost_tpu.ops import split
from xgboost_tpu.ops.split import (NEG, RT_EPS, SplitConfig,
                                   find_best_splits, find_best_splits_native)

LAYOUTS = {"standard": ((1, 2, 3), (0, 1, 2, 3)),     # (M, F, C, 2)
           "native": ((0, 1, 2), (1, 2, 3, 0))}       # (F, C, 2, M)


def _oracle(loss, GL, HL):
    """(gain, feature, cut, default_left, valid, left_g, left_h) of
    every node of ``(M, F, C, 2)`` arrays, by numpy's flat argmax (the
    first maximum; the first NaN where there is one)."""
    M, F, C, _ = loss.shape
    flat = loss.reshape(M, -1)
    k = np.argmax(flat, axis=1)
    rows = np.arange(M)
    gain = flat[rows, k]
    return (gain, k // (2 * C), (k // 2) % C, (k % 2).astype(bool),
            gain > RT_EPS, GL.reshape(M, -1)[rows, k],
            HL.reshape(M, -1)[rows, k])


def _tied_candidates(rng, F, C):
    """Ten nodes of candidates from four values, so that nearly every
    maximum is tied, the ties laid across each axis in turn."""
    loss = rng.integers(0, 4, (10, F, C, 2)).astype(np.float32)
    f, c = F // 2, C // 2
    loss[0, f, c, :] = 9.0                  # across direction only
    loss[1, f, [c, C - 1], 1] = 9.0         # across cut, default-left
    loss[2, [f, F - 1], c, 0] = 9.0         # across feature
    loss[3, f, c, 1] = loss[3, F - 1, 0, 0] = 9.0   # later d, earlier f
    loss[4, f, C - 1, 0] = loss[4, f, c, 1] = 9.0   # later d, earlier c
    loss[5] = NEG                           # no candidate at all
    loss[6, f, c, 1] = np.nan               # a NaN cell behind and before
    loss[6, 0, 0, 0] = loss[6, F - 1, C - 1, 1] = 9.0   # larger values
    loss[7, F - 1, C - 1, 1] = 9.0          # the very last cell
    loss[8] = 3.0                           # everything tied
    return loss


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("C", [254, 5])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_first_max_is_the_flat_argmax(layout, C, seed):
    rng = np.random.default_rng(1000 * C + seed)
    F = int(rng.integers(3, 9))
    loss = _tied_candidates(rng, F, C)
    GL = rng.normal(size=loss.shape).astype(np.float32)
    HL = rng.random(loss.shape).astype(np.float32)
    axes, perm = LAYOUTS[layout]
    got = split._first_max(*(jnp.asarray(x.transpose(perm))
                             for x in (loss, GL, HL)), *axes)
    for name, g, w in zip(got._fields, got, _oracle(loss, GL, HL)):
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)


def _flat_first_max(loss_chg, GL, HL, f_ax, c_ax, d_ax):
    """The finders' old tail: candidates moved to (node, F * C * 2)."""
    def flat(x):
        x = jnp.moveaxis(x, (f_ax, c_ax, d_ax), (-3, -2, -1))
        return x.reshape(-1, np.prod(x.shape[-3:]))
    C = loss_chg.shape[c_ax]
    lc = flat(loss_chg)
    k = jnp.argmax(lc, axis=1)

    def take(x):
        return jnp.take_along_axis(flat(x), k[:, None], axis=1)[:, 0]
    gain = take(loss_chg)
    return split.BestSplit(gain, (k // (2 * C)).astype(jnp.int32),
                           ((k // 2) % C).astype(jnp.int32),
                           (k % 2).astype(jnp.bool_), gain > RT_EPS,
                           take(GL), take(HL))


@pytest.mark.parametrize("cfg", [
    SplitConfig(), SplitConfig(min_child_weight=4.0),
    SplitConfig(default_direction=1), SplitConfig(default_direction=2)],
    ids=["learn", "min_child_weight", "forced_left", "forced_right"])
@pytest.mark.parametrize("B", [256, 7])
@pytest.mark.parametrize("native", [False, True], ids=["standard", "native"])
def test_finders_pick_what_the_flat_argmax_picked(monkeypatch, native, B, cfg):
    """Count-valued histograms (g = +-0.5, h = 0.25 on few rows: equal
    gains everywhere, features that repeat each other, empty missing
    bins so that both directions tie) through either finder, with the
    tail as it is and as it was."""
    rng = np.random.default_rng(B)
    M, F, n = 6, 5, 300
    bins = rng.integers(1, B, (n, F))
    bins[:, 3] = bins[:, 1]                     # a feature twice
    bins[:, 4] = B - bins[:, 1]                 # and mirrored
    bins[: n // 3, 2] = 0                       # one with missing rows
    node = rng.integers(0, M - 1, n)            # the last node stays empty
    g = np.where(rng.random(n) < 0.5, 0.5, -0.5)
    hist = np.zeros((M, F, B, 2), np.float32)
    for f in range(F):
        np.add.at(hist[:, f, :, 0], (node, bins[:, f]), g)
        np.add.at(hist[:, f, :, 1], (node, bins[:, f]), 0.25)
    nst = jnp.asarray(hist[:, 0].sum(axis=1))
    n_cuts = jnp.asarray(rng.integers(2, B - 1, F).astype(np.int32))

    def run():
        if native:
            return find_best_splits_native(
                jnp.asarray(hist.transpose(1, 2, 3, 0)), nst, n_cuts, cfg)
        return find_best_splits(jnp.asarray(hist), nst, n_cuts, cfg)
    got = run()
    monkeypatch.setattr(split, "_first_max", _flat_first_max)
    want = run()
    assert np.asarray(got.valid)[:-1].any() and not np.asarray(got.valid)[-1]
    for name, a, b in zip(got._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
