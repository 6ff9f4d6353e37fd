"""Split gain math and vectorized best-split search.

Gain/weight formulas re-implement reference ``TrainParam::CalcGain`` /
``CalcWeight`` (``src/tree/param.h:109-152``) including the L1 soft
threshold and the max_delta_step variant.  Split enumeration replaces the
reference's per-feature forward/backward sorted scans
(``updater_colmaker-inl.hpp:362-414``) and histogram scans
(``updater_histmaker-inl.hpp:175-258``) with one vectorized argmax over
``(feature, cut, default_direction)`` per node, with the reference's
deterministic lowest-feature-wins tie-break (``param.h:335-405``) falling
out of argmax-first-occurrence over a feature-major layout.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# plain float, not jnp: a module-level device constant would initialize
# the XLA backend at import time, breaking jax.distributed.initialize()
# (which must run first in multi-host workers — parallel/launch.py)
NEG = -1e30
RT_EPS = 1e-6  # reference rt_eps accept threshold


class SplitConfig(NamedTuple):
    """Static split hyperparameters (subset of TrainParam used on device)."""
    reg_lambda: float = 1.0
    reg_alpha: float = 0.0
    max_delta_step: float = 0.0
    min_child_weight: float = 1.0
    gamma: float = 0.0
    eta: float = 0.3
    default_direction: int = 0  # 0=learn, 1=left, 2=right


def _threshold_l1(w, alpha):
    return jnp.sign(w) * jnp.maximum(jnp.abs(w) - alpha, 0.0)


def calc_weight(G, H, cfg: SplitConfig):
    """Leaf weight (reference CalcWeight, param.h:138-152)."""
    dw = -_threshold_l1(G, cfg.reg_alpha) / (H + cfg.reg_lambda)
    if cfg.max_delta_step != 0.0:
        dw = jnp.clip(dw, -cfg.max_delta_step, cfg.max_delta_step)
    return jnp.where(H < cfg.min_child_weight, 0.0, dw)


def calc_gain(G, H, cfg: SplitConfig):
    """Node objective reduction (reference CalcGain, param.h:109-126).

    Note: unlike CalcWeight, the plain-gain path has no min_child_weight
    zeroing here — the reference's histogram updaters enforce
    min_child_weight explicitly on both children (histmaker-inl.hpp:230-239),
    which find_best_splits replicates.
    """
    if cfg.max_delta_step == 0.0:
        t = _threshold_l1(G, cfg.reg_alpha) if cfg.reg_alpha != 0.0 else G
        return t * t / (H + cfg.reg_lambda)
    w = calc_weight(G, H, cfg)
    ret = G * w + 0.5 * (H + cfg.reg_lambda) * w * w
    if cfg.reg_alpha != 0.0:
        ret = ret + cfg.reg_alpha * jnp.abs(w)
    return -2.0 * ret


class BestSplit(NamedTuple):
    gain: jax.Array          # (n_node,) loss_chg of best split (f32)
    feature: jax.Array       # (n_node,) int32
    cut_index: jax.Array     # (n_node,) int32  (left iff bin <= cut_index+1)
    default_left: jax.Array  # (n_node,) bool
    valid: jax.Array         # (n_node,) bool — accept split?
    # chosen split's left-child sums (incl. the default-direction missing
    # mass): lets the grower DERIVE the next level's node stats instead
    # of a full node_stats pass over the rows (right child = node - left)
    left_g: jax.Array = None  # (n_node,) f32
    left_h: jax.Array = None  # (n_node,) f32


def _first_max(loss_chg: jax.Array, GL: jax.Array, HL: jax.Array,
               f_ax: int, c_ax: int, d_ax: int) -> BestSplit:
    """Per node, the FIRST maximum of ``loss_chg`` over (feature, cut,
    direction) in that order (the lowest feature wins a tie, then the
    lowest cut, then default-right: what ``argmax`` over the flattened
    ``(f * C + c) * 2 + d`` gives), and the winner's left sums.

    Taken axis by axis, innermost first, and never flattened: C = B - 2
    is no multiple of the sublane tile, so collapsing ``(F, C, 2)`` into
    one axis is a physical relayout, which the TPU compiler emits
    feature by feature (at F = 2,000: 29 MB of code and 3.5 minutes of
    compile time for each level's finder).  The first maximum over d
    for every (f, c), then the first c among those maxima for every f,
    then the first f: the same cell as the flat argmax, NaN included."""
    m_d = loss_chg.max(axis=d_ax, keepdims=True)
    a_d = jnp.argmax(loss_chg, axis=d_ax, keepdims=True)
    m_c = m_d.max(axis=c_ax, keepdims=True)
    a_c = jnp.argmax(m_d, axis=c_ax, keepdims=True)
    best_gain = m_c.max(axis=f_ax, keepdims=True)
    a_f = jnp.argmax(m_c, axis=f_ax, keepdims=True)

    def ids(ax):
        return jax.lax.broadcasted_iota(
            jnp.int32, tuple(n if i == ax else 1
                             for i, n in enumerate(loss_chg.shape)), ax)
    # the winner's cut and direction, gather-free: one-hot selects over
    # the small per-feature tables (batched gathers serialize on TPU)
    sel_f = ids(f_ax) == a_f
    cut = jnp.where(sel_f, a_c, 0).sum(axis=f_ax, keepdims=True)
    sel_fc = sel_f & (ids(c_ax) == cut)
    d = jnp.where(sel_fc, a_d, 0).sum(axis=(f_ax, c_ax), keepdims=True)
    sel = (sel_fc & (ids(d_ax) == d)).astype(jnp.float32)

    def node(x):
        return x.reshape(-1)            # every other axis is 1
    axes = (f_ax, c_ax, d_ax)
    best_gain = node(best_gain)
    return BestSplit(
        best_gain, node(a_f).astype(jnp.int32),
        node(cut).astype(jnp.int32), node(d).astype(jnp.bool_),
        # accept: positive reduction (reference loss_chg > rt_eps,
        # histmaker-inl.hpp:253).  gamma is NOT applied here: the prune
        # updater post-prunes loss_chg < min_split_loss bottom-up
        # (updater_prune-inl.hpp:42-72), which keeps a weak split whose
        # descendants are strong — pre-pruning would not.
        best_gain > RT_EPS,
        # winner's left-child sums (one-hot contraction, as above)
        (GL * sel).sum(axis=axes), (HL * sel).sum(axis=axes))


def find_best_splits(hist: jax.Array, nstats: jax.Array, n_cuts: jax.Array,
                     cfg: SplitConfig, feature_mask: jax.Array | None = None
                     ) -> BestSplit:
    """Vectorized best split per node from a level histogram.

    Args:
      hist:    (n_node, F, B, 2) grad/hess histogram (bin 0 = missing).
      nstats:  (n_node, 2) per-node (G, H) totals.
      n_cuts:  (F,) number of valid cut indices per feature.
      feature_mask: optional (F,) bool — colsample mask.
    """
    n_node, F, B, _ = hist.shape
    C = B - 2  # number of candidate cut positions (splits after bins 1..C)
    cum = jnp.cumsum(hist, axis=2)              # (n_node, F, B, 2)
    miss = hist[:, :, 0, :]                     # (n_node, F, 2)
    total = nstats[:, None, None, :]            # (n_node, 1, 1, 2)

    # left sums excluding missing, for cut j: bins 1..j+1  -> cum[.., j+1] - miss
    left_excl = cum[:, :, 1:C + 1, :] - miss[:, :, None, :]  # (n_node, F, C, 2)
    # default right: missing goes right;  default left: missing joins left
    left_dr = left_excl
    left_dl = left_excl + miss[:, :, None, :]
    left = jnp.stack([left_dr, left_dl], axis=3)     # (n_node, F, C, 2dir, 2)
    right = total[:, :, :, None, :] - left

    GL, HL = left[..., 0], left[..., 1]
    GR, HR = right[..., 0], right[..., 1]
    root_gain = calc_gain(nstats[:, 0], nstats[:, 1], cfg)  # (n_node,)
    loss_chg = (calc_gain(GL, HL, cfg) + calc_gain(GR, HR, cfg)
                - root_gain[:, None, None, None])

    ok = (HL >= cfg.min_child_weight) & (HR >= cfg.min_child_weight)
    cut_ids = jnp.arange(C, dtype=jnp.int32)
    ok &= (cut_ids[None, :, None] < n_cuts[:, None, None])[None]
    if feature_mask is not None:
        ok &= feature_mask[None, :, None, None]
    if cfg.default_direction == 1:    # forced left
        ok &= jnp.array([False, True])[None, None, None, :]
    elif cfg.default_direction == 2:  # forced right
        ok &= jnp.array([True, False])[None, None, None, :]
    loss_chg = jnp.where(ok, loss_chg, NEG)

    return _first_max(loss_chg, GL, HL, 1, 2, 3)


def find_best_splits_native(hist: jax.Array, nstats: jax.Array,
                            n_cuts: jax.Array, cfg: SplitConfig,
                            feature_mask: jax.Array | None = None
                            ) -> BestSplit:
    """:func:`find_best_splits` on the histogram kernel's NATIVE layout
    ``(F, B, 2, n_node)`` — node minor, exactly how the pallas kernel
    writes it.  Skipping the (n_node, F, B, 2) relayout saves ~0.47
    ms/round at the bench shape (round-5 trace), and the cumsum runs
    along a sublane dim with nodes riding the lanes.  Candidate order,
    tie-breaks and math are identical to the standard layout (same
    (feature, cut, dir) flattening, argmax-first tie-break) — pinned
    bitwise by
    tests/test_pallas_hist.py::test_native_split_finder_matches_standard.
    """
    F, B, _, n_node = hist.shape
    C = B - 2
    cum = jnp.cumsum(hist, axis=1)               # (F, B, 2, M)
    miss = hist[:, 0, :, :]                      # (F, 2, M)
    total = nstats.T[None, None, None, :, :]     # (1, 1, 1, 2, M)

    left_excl = cum[:, 1:C + 1, :, :] - miss[:, None, :, :]  # (F, C, 2, M)
    left = jnp.stack([left_excl, left_excl + miss[:, None, :, :]],
                     axis=2)                     # (F, C, 2dir, 2, M)
    right = total - left

    GL, HL = left[..., 0, :], left[..., 1, :]    # (F, C, 2dir, M)
    GR, HR = right[..., 0, :], right[..., 1, :]
    root_gain = calc_gain(nstats[:, 0], nstats[:, 1], cfg)   # (M,)
    loss_chg = (calc_gain(GL, HL, cfg) + calc_gain(GR, HR, cfg)
                - root_gain[None, None, None, :])

    ok = (HL >= cfg.min_child_weight) & (HR >= cfg.min_child_weight)
    cut_ids = jnp.arange(C, dtype=jnp.int32)
    ok &= (cut_ids[None, :] < n_cuts[:, None])[:, :, None, None]
    if feature_mask is not None:
        ok &= feature_mask[:, None, None, None]
    if cfg.default_direction == 1:    # forced left
        ok &= jnp.array([False, True])[None, None, :, None]
    elif cfg.default_direction == 2:  # forced right
        ok &= jnp.array([True, False])[None, None, :, None]
    loss_chg = jnp.where(ok, loss_chg, NEG)

    return _first_max(loss_chg, GL, HL, 0, 1, 2)
