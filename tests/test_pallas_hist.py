"""Parity tests: Pallas histogram/node-stat kernels vs the XLA scatter.

Gradients are dyadic rationals (multiples of 1/256, bounded), so f32
summation is exact in ANY order — bitwise equality between the MXU
matmul formulation and the scatter-add is required, not just allclose.
Runs in Pallas interpret mode on the CPU test platform; the same kernels
compile on TPU (exercised by chip_smoke.py --kernels and benchmark/).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from xgboost_tpu.ops.histogram import (build_level_histogram,  # noqa: E402
                                       node_stats, stats_from_histogram)
from xgboost_tpu.ops import pallas_hist as ph  # noqa: E402
from xgboost_tpu.ops.pallas_hist import (  # noqa: E402
    build_level_histogram_pallas, node_stats_pallas)


def _case(N, F, B, M, seed=0, frac_inactive=0.2):
    rng = np.random.RandomState(seed)
    binned = rng.randint(0, B, (N, F)).astype(np.uint8)
    gh = (rng.randint(-512, 512, (N, 2)) / 256.0).astype(np.float32)
    pos = rng.randint(0, M, N).astype(np.int32)
    pos[rng.rand(N) < frac_inactive] = -1
    return jnp.asarray(binned), jnp.asarray(gh), jnp.asarray(pos)


@pytest.mark.parametrize("N,F,B,M", [
    (1000, 13, 32, 8),     # generic odd sizes
    (513, 7, 67, 64),      # non-aligned rows, bench-like bin count
    (100, 3, 8, 4),        # smaller than one row tile
    (1024, 5, 16, 1),      # root level (single node)
    (7, 1, 4, 2),          # tiny
])
def test_pallas_histogram_bitwise_parity(N, F, B, M):
    binned, gh, pos = _case(N, F, B, M)
    want = np.asarray(build_level_histogram(binned, gh, pos, M, B))
    got = np.asarray(build_level_histogram_pallas(
        binned, gh, pos, M, B, interpret=True))
    assert got.shape == (M, F, B, 2)
    np.testing.assert_array_equal(got, want)


def test_pallas_histogram_all_inactive():
    binned, gh, pos = _case(64, 2, 8, 4)
    pos = jnp.full_like(pos, -1)
    got = np.asarray(build_level_histogram_pallas(
        binned, gh, pos, 4, 8, interpret=True))
    np.testing.assert_array_equal(got, np.zeros_like(got))


def test_pallas_histogram_bf16_mode_runs():
    """bf16 mode materializes the matmul operands in bf16 (the MXU would
    truncate them anyway; halving one-hot VMEM traffic is a measured
    kernel win).  The one-hot is 0/1 (exact in bf16), so the result must
    bitwise equal the exact histogram of bf16-rounded gradients —
    accumulation stays f32 in both formulations."""
    binned, gh, pos = _case(2000, 6, 32, 8, seed=3)
    gh_b = gh.astype(jnp.bfloat16).astype(jnp.float32)
    want = np.asarray(build_level_histogram(binned, gh_b, pos, 8, 32))
    got = np.asarray(build_level_histogram_pallas(
        binned, gh, pos, 8, 32, precision="bf16", interpret=True))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("N,M", [(1000, 8), (513, 64), (100, 1), (8, 2)])
def test_pallas_node_stats_parity(N, M):
    _, gh, pos = _case(N, 1, 4, M, seed=5)
    want = np.asarray(node_stats(gh, pos, M))
    got = np.asarray(node_stats_pallas(gh, pos, M, interpret=True))
    assert got.shape == (M, 2)
    np.testing.assert_array_equal(got, want)


def test_stats_from_histogram_matches_node_stats():
    binned, gh, pos = _case(800, 4, 16, 8, seed=9)
    hist = build_level_histogram(binned, gh, pos, 8, 16)
    np.testing.assert_allclose(np.asarray(stats_from_histogram(hist)),
                               np.asarray(node_stats(gh, pos, 8)),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("N,F,B,M", [
    (600, 34, 40, 64),    # f_tile < F with F not a multiple of 8
    (600, 34, 256, 512),  # deep level forces f_tile rounding to 8s
])
def test_pallas_histogram_odd_feature_tiling(N, F, B, M):
    """Block sublane dims must be multiples of 8 or the full feature dim
    (regression: F=34 with a budget tile of 30 failed Mosaic lowering)."""
    binned, gh, pos = _case(N, F, B, M, seed=11)
    want = np.asarray(build_level_histogram(binned, gh, pos, M, B))
    got = np.asarray(build_level_histogram_pallas(
        binned, gh, pos, M, B, interpret=True))
    np.testing.assert_array_equal(got, want)


def _spy_on_pallas_call(monkeypatch):
    """Collect what every ``pallas_call`` of ops/pallas_hist returns: the
    kernel's own block, before the caller cuts the padding away."""
    from xgboost_tpu.ops import pallas_hist as ph
    raw, real = [], ph.pl.pallas_call

    def pallas_call(*args, **kwargs):
        call = real(*args, **kwargs)

        def run(*operands):
            raw.append(call(*operands))
            return raw[-1]
        return run
    monkeypatch.setattr(ph.pl, "pallas_call", pallas_call)
    return raw


@pytest.mark.parametrize("precision", ["int8", "bf16", "fp32"])
@pytest.mark.parametrize("F,B", [(13, 256), (28, 256), (34, 40)])
@pytest.mark.parametrize("kernel", ["rows", "lanes", "trees"])
def test_padded_feature_slots_run_no_dot(kernel, F, B, precision,
                                         monkeypatch):
    """The feature slots that only pad the last feature tile (3 of 16 at
    13 features and 256 bins, 4 of 32 at 28) run no one-hot and no dot:
    (a) the histogram is the XLA scatter's, bitwise; (b) whatever bin
    ids stand in the padded rows of the prepared operand, the kernel's
    UNCUT block is zero in the padded slots (the parent's held bin 0's
    sums there, cut away afterwards) and the cut histogram does not
    move; (c) the gauge reads the dots one row tile runs: F.  At 40 bins
    the rows and lanes kernels have ``f_tile == F`` and nothing to skip;
    the trees kernel's own tiling pads 34 to 48."""
    from xgboost_tpu import obs
    from xgboost_tpu.ops import pallas_hist as ph

    N = 300
    M = 64 if (kernel == "trees" or B == 40) else 4
    K = 6 if (kernel == "trees" and B == 40) else 2     # lanes / trees
    rng = np.random.RandomState(32)
    binned = rng.randint(0, B, (K, N, F)).astype(np.uint8)
    gh = (rng.randint(-512, 512, (K, N, 2)) / 256.0).astype(np.float32)
    pos = rng.randint(0, M, (K, N)).astype(np.int32)
    pos[rng.rand(K, N) < 0.2] = -1
    if kernel != "lanes":               # one dataset
        binned = binned[:1]
    if kernel == "rows":
        gh, pos = gh[:1], pos[:1]
    gh_j = jnp.asarray(gh)
    if precision == "int8":
        gh_in, scale = ph.quantize_gh(gh_j)
        gh_ref = np.asarray(gh_in, np.float32)      # exact integer sums
    else:
        gh_in, scale = gh_j, None
        gh_ref = np.asarray(gh_j.astype(jnp.bfloat16).astype(jnp.float32)
                            if precision == "bf16" else gh_j)

    if kernel == "trees":
        bt = ph.transpose_bins_batched(jnp.asarray(binned[0]), B, K, M,
                                       precision)
    else:
        bt = jax.vmap(lambda b: ph.transpose_bins(b, B))(
            jnp.asarray(binned))
        bt = bt[0] if kernel == "rows" else bt
    f_pad = bt.shape[-2]
    assert f_pad > F or (B == 40 and kernel != "trees")
    junk = jnp.asarray(rng.randint(0, B, bt.shape).astype(np.int32))
    is_pad = (jnp.arange(f_pad) >= F)[:, None]
    bt_junk = jnp.where(is_pad, junk, bt)

    def run(operand):
        nf = (N, F)
        if kernel == "rows":
            return ph._hist_pallas_pre(
                operand, gh_in[0], None if scale is None else scale[0],
                jnp.asarray(pos[0]), nf, M, B, precision, True)[None]
        pre = (ph._hist_pallas_lanes_pre if kernel == "lanes"
               else ph._hist_pallas_batched_pre)
        return pre(operand, gh_in, scale, jnp.asarray(pos), nf, M, B,
                   precision, True)

    raw = _spy_on_pallas_call(monkeypatch)
    feature_dots = obs.training_metrics().hist_feature_dots
    feature_dots.set(0.0)
    got = np.asarray(run(bt))
    assert feature_dots.value == F                              # (c)
    got_junk = np.asarray(run(bt_junk))
    assert len(raw) == 2
    # a feature's rows in the block: B, or the fold's (M = 4 folds at
    # 256 bins in the rows and lanes kernels; the trees kernel never)
    rows = B if kernel == "trees" else ph._fold_of(B, M, precision)[0]
    for block in raw:                                           # (b)
        block = np.asarray(block)
        assert block.shape[-2] == f_pad * rows
        assert not block[..., F * rows:, :].any()
        assert block[..., :F * rows, :].any()
    np.testing.assert_array_equal(got_junk, got)

    assert got.shape == (gh.shape[0], M, F, B, 2)               # (a)
    for k in range(gh.shape[0]):
        want = np.asarray(build_level_histogram(
            jnp.asarray(binned[0 if kernel == "trees" else k]),
            jnp.asarray(gh_ref[k]), jnp.asarray(pos[k]), M, B))
        if precision == "int8":
            want = want * np.asarray(scale[k] / 127.0)
        np.testing.assert_array_equal(got[k], want)


@pytest.mark.parametrize("T,N,F,B,M", [
    (3, 500, 5, 16, 8),
    (6, 257, 4, 67, 64),   # bench-like bins, node-tiled level
    (2, 64, 3, 8, 1),      # root level
])
def test_batched_histogram_parity(T, N, F, B, M):
    """Tree-batched kernel == stacked per-tree kernels, bitwise (fp32)."""
    from xgboost_tpu.ops.pallas_hist import (
        build_level_histogram_pallas_batched)
    rng = np.random.RandomState(11)
    binned = jnp.asarray(rng.randint(0, B, (N, F)).astype(np.uint8))
    gh = jnp.asarray((rng.randint(-512, 512, (T, N, 2)) / 256.0)
                     .astype(np.float32))
    pos = rng.randint(0, M, (T, N)).astype(np.int32)
    pos[rng.rand(T, N) < 0.2] = -1
    pos = jnp.asarray(pos)
    got = np.asarray(build_level_histogram_pallas_batched(
        binned, gh, pos, M, B, interpret=True))
    assert got.shape == (T, M, F, B, 2)
    for t in range(T):
        want = np.asarray(build_level_histogram_pallas(
            binned, gh[t], pos[t], M, B, interpret=True))
        np.testing.assert_array_equal(got[t], want)


def test_vmap_dispatches_to_batched_kernel(monkeypatch):
    """jax.vmap of build_level_histogram over (gh, pos) must hit the
    custom_vmap rule (tree-batched kernel) and match per-tree results.

    The CPU test platform defaults to the scatter impl, which vmap
    handles natively — force the pallas impl (interpret mode) so this
    actually executes the custom_vmap wrapper and its def_vmap rule.
    """
    monkeypatch.setenv("XGBTPU_HIST", "pallas")
    rng = np.random.RandomState(12)
    T, N, F, B, M = 4, 300, 6, 32, 8
    binned = jnp.asarray(rng.randint(0, B, (N, F)).astype(np.uint8))
    gh = jnp.asarray((rng.randint(-512, 512, (T, N, 2)) / 256.0)
                     .astype(np.float32))
    pos = jnp.asarray(rng.randint(0, M, (T, N)).astype(np.int32))
    got = np.asarray(jax.vmap(
        lambda g, p: build_level_histogram(binned, g, p, M, B))(gh, pos))
    for t in range(T):
        want = np.asarray(build_level_histogram(binned, gh[t], pos[t],
                                                M, B))
        np.testing.assert_array_equal(got[t], want)


def test_vmap_batched_binned_falls_back_to_map(monkeypatch):
    """The custom_vmap rule's batched-binned branch (per-shard bins, no
    one-hot sharing) must also produce per-example results."""
    monkeypatch.setenv("XGBTPU_HIST", "pallas")
    rng = np.random.RandomState(13)
    T, N, F, B, M = 3, 120, 4, 16, 4
    binned = jnp.asarray(rng.randint(0, B, (T, N, F)).astype(np.uint8))
    gh = jnp.asarray((rng.randint(-512, 512, (T, N, 2)) / 256.0)
                     .astype(np.float32))
    pos = jnp.asarray(rng.randint(0, M, (T, N)).astype(np.int32))
    got = np.asarray(jax.vmap(
        lambda b, g, p: build_level_histogram(b, g, p, M, B))(
            binned, gh, pos))
    for t in range(T):
        want = np.asarray(build_level_histogram(binned[t], gh[t], pos[t],
                                                M, B))
        np.testing.assert_array_equal(got[t], want)


def test_native_split_finder_matches_standard():
    """find_best_splits_native on the kernel-native (F, B, 2, M) layout
    must equal find_best_splits on (M, F, B, 2) EXACTLY (same candidate
    order, tie-breaks, gains and winner sums)."""
    from xgboost_tpu.ops.split import (SplitConfig, find_best_splits,
                                       find_best_splits_native)
    rng = np.random.RandomState(0)
    M, F, B = 16, 7, 12
    hist = jnp.asarray(rng.rand(M, F, B, 2).astype(np.float32))
    hist = hist.at[..., 1].set(hist[..., 1] * 3)
    nst = hist[:, 0, :, :].sum(axis=1)
    n_cuts = jnp.asarray(rng.randint(3, B - 2, F).astype(np.int32))
    fmask = jnp.asarray(rng.rand(F) > 0.2)
    for cfg in (SplitConfig(min_child_weight=0.5),
                SplitConfig(reg_alpha=0.1, default_direction=1),
                SplitConfig(max_delta_step=0.7)):
        a = find_best_splits(hist, nst, n_cuts, cfg, fmask)
        b = find_best_splits_native(hist.transpose(1, 2, 3, 0), nst,
                                    n_cuts, cfg, fmask)
        for f in a._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
                err_msg=f)


def test_native_grow_matches_scatter(monkeypatch):
    """grow_tree through the native-layout prep path (pallas fp32,
    interpret on CPU) grows the EXACT tree the scatter path grows."""
    import jax.random
    from xgboost_tpu.binning import bin_dense, compute_cuts
    from xgboost_tpu.config import TrainParam
    from xgboost_tpu.data import DMatrix
    from xgboost_tpu.models.gbtree import make_grow_config
    from xgboost_tpu.models.tree import grow_tree
    from xgboost_tpu.ops.pallas_hist import host_transpose_bins

    rng = np.random.RandomState(3)
    X = rng.rand(2048, 5).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0.8).astype(np.float32)
    cuts = compute_cuts(DMatrix(X, label=y), max_bin=16)
    cfg = make_grow_config(TrainParam(max_depth=4, eta=0.5), cuts.max_bin)
    binned = bin_dense(X, cuts)
    bt = host_transpose_bins(binned, cuts.max_bin)
    gh = np.stack([0.5 - y, np.full_like(y, 0.25)], axis=1)
    args = (jax.random.PRNGKey(7), jnp.asarray(binned), jnp.asarray(gh),
            jnp.asarray(cuts.cut_values), jnp.asarray(cuts.n_cuts), cfg)

    monkeypatch.setenv("XGBTPU_HIST", "pallas")
    t_n, rl_n, rv_n = jax.jit(
        lambda *a: grow_tree.__wrapped__(*a, binned_t=jnp.asarray(bt)),
        static_argnums=(5,))(*args)
    monkeypatch.setenv("XGBTPU_HIST", "scatter")
    t_s, rl_s, rv_s = jax.jit(
        lambda *a: grow_tree.__wrapped__(*a), static_argnums=(5,))(*args)
    for f in t_n._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(t_n, f)), np.asarray(getattr(t_s, f)),
            err_msg=f)
    np.testing.assert_array_equal(np.asarray(rl_n), np.asarray(rl_s))
    np.testing.assert_array_equal(np.asarray(rv_n), np.asarray(rv_s))


def test_native_vmapped_multiclass_matches_scatter(monkeypatch):
    """The ensemble (vmapped) native path — batched kernel emitting
    (T, F, B, 2, M) in one relayout — must train the same multiclass
    model as the scatter path (fp32 pallas, interpret on CPU)."""
    import xgboost_tpu as xgb

    rng = np.random.RandomState(5)
    X = rng.rand(3000, 5).astype(np.float32)
    yc = (X[:, 0] * 3).astype(np.int32) % 3
    params = {"objective": "multi:softmax", "num_class": 3,
              "max_depth": 3, "eta": 0.5, "max_bin": 16}

    preds = {}
    for impl in ("pallas", "scatter"):
        monkeypatch.setenv("XGBTPU_HIST", impl)
        d = xgb.DMatrix(X, label=yc)
        bst = xgb.Booster(params, cache=[d])
        bst.update(d, 0)
        bst.update(d, 1)
        preds[impl] = np.asarray(bst.predict(d, output_margin=True))
    np.testing.assert_allclose(preds["pallas"], preds["scatter"],
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------ the fold (ISSUE 34)
def _unfolded(n_bin, m_pad, precision):
    return n_bin, 1


def _fold_case(N, F, B, M, precision, lanes=None, seed=34):
    """Prepared operands with 20 % inactive rows and one column that is
    0 in 99.8 % of rows; ``gh_ref`` is what the scatter has to sum to
    give the kernel's cells exactly (the quantized integers in int8)."""
    rng = np.random.RandomState(seed)
    lead = () if lanes is None else (lanes,)
    binned = rng.randint(0, B, lead + (N, F)).astype(np.uint8)
    binned[..., F // 2] *= rng.rand(*lead, N) >= 0.998
    gh = (rng.randint(-512, 512, lead + (N, 2)) / 256.0).astype(np.float32)
    pos = rng.randint(0, M, lead + (N,)).astype(np.int32)
    pos[rng.rand(*lead, N) < 0.2] = -1
    gh_j = jnp.asarray(gh)
    if precision == "int8":
        gh_in, scale = ph.quantize_gh(gh_j)
        gh_ref = np.asarray(gh_in, np.float32)
    else:
        gh_in, scale, gh_ref = gh_j, None, gh
    tb = lambda b: ph.transpose_bins(b, B)                      # noqa: E731
    bt = tb(jnp.asarray(binned)) if lanes is None else jax.vmap(tb)(
        jnp.asarray(binned))
    return binned, bt, gh_in, scale, gh_ref, jnp.asarray(pos)


@pytest.mark.parametrize("precision", ["int8", "fp32"])
@pytest.mark.parametrize("F", [28, 13, 8])
@pytest.mark.parametrize("M", [1, 2, 8, 16, 32, 64])
@pytest.mark.parametrize("B", [256, 64, 67])
def test_folded_level_equals_scatter_and_unfolded(B, M, F, precision,
                                                  monkeypatch):
    """The level kernel with the bin id's high bits folded into the
    lanes a shallow level leaves idle (``_fold_of``) gives the XLA
    scatter's histogram and the unfolded program's, bit for bit, in the
    standard and the kernel-native layout; where ``_fold_of`` leaves the
    level unfolded the two programs are one.  F = 28 and 13 leave padded
    slots in the last feature tile at 256 bins (PR 32's guard)."""
    N = 700
    binned, bt, gh_in, scale, gh_ref, pos = _fold_case(N, F, B, M, precision)

    def run(native):
        return np.asarray(ph._hist_pallas_pre(
            bt, gh_in, scale, pos, (N, F), M, B, precision, True,
            native=native))
    raw = _spy_on_pallas_call(monkeypatch)
    std, nat = run(False), run(True)
    rows, n_hi = ph._fold_of(B, M, precision)
    f_pad = bt.shape[0]
    assert raw[0].shape == (1, f_pad * rows, n_hi * 2 * M)
    monkeypatch.setattr(ph, "_fold_of", _unfolded)
    std_1 = run(False)
    assert raw[2].shape == (1, f_pad * B, 2 * M)
    want = np.asarray(build_level_histogram(
        jnp.asarray(binned), jnp.asarray(gh_ref), pos, M, B))
    if precision == "int8":
        want = want * np.asarray(scale / 127.0)
    assert std.shape == (M, F, B, 2) and nat.shape == (F, B, 2, M)
    assert np.abs(want).max() > 0
    np.testing.assert_array_equal(std, want)
    np.testing.assert_array_equal(std, std_1)
    np.testing.assert_array_equal(nat, std.transpose(1, 2, 3, 0))


@pytest.mark.parametrize("precision", ["int8", "fp32"])
@pytest.mark.parametrize("B,M", [(256, 1), (256, 8), (256, 16), (256, 32),
                                 (64, 2), (67, 4)])
def test_folded_lanes_equal_solo_bit_for_bit(B, M, precision, monkeypatch):
    """The lanes kernel shares ``_hist_kernel`` and folds with it: each
    lane's histogram is the solo call's on that lane, bit for bit, in
    both layouts, and the unfolded lanes program's."""
    N, F, L = 700, 13, 2
    binned, bt, gh_in, scale, _, pos = _fold_case(N, F, B, M, precision,
                                                  lanes=L)

    def run(native):
        return np.asarray(ph._hist_pallas_lanes_pre(
            bt, gh_in, scale, pos, (N, F), M, B, precision, True,
            native=native))
    raw = _spy_on_pallas_call(monkeypatch)
    std, nat = run(False), run(True)
    rows, n_hi = ph._fold_of(B, M, precision)
    assert raw[0].shape == (L, bt.shape[1] * rows, n_hi * 2 * M)
    for lane in range(L):
        for native, got in ((False, std), (True, nat)):
            solo = ph._hist_pallas_pre(
                bt[lane], gh_in[lane], None if scale is None else scale[lane],
                pos[lane], (N, F), M, B, precision, True, native=native)
            np.testing.assert_array_equal(got[lane], np.asarray(solo))
    monkeypatch.setattr(ph, "_fold_of", _unfolded)
    np.testing.assert_array_equal(std, run(False))


@pytest.mark.parametrize("n_bin,m_pad,precision,want", [
    # 256 bins, int8: the one-hot dtype's sublane tile is 32 rows
    (256, 1, "int8", (32, 8)), (256, 2, "int8", (32, 8)),
    (256, 4, "int8", (32, 8)),
    (256, 8, "int8", (64, 4)),          # 64 + 64 lanes beat 32 + 128
    (256, 16, "int8", (64, 4)),
    (256, 32, "int8", (128, 2)),        # measured -14 % on the chip
    (256, 64, "int8", (256, 1)),        # a node tile: no idle lane
    # bf16 and fp32 tiles are 16 and 8 rows
    (256, 1, "bf16", (16, 16)), (256, 1, "fp32", (16, 16)),
    (256, 2, "bf16", (32, 8)), (256, 8, "fp32", (64, 4)),
    (256, 16, "bf16", (64, 4)), (256, 32, "bf16", (128, 2)),
    (256, 32, "fp32", (128, 2)), (256, 64, "fp32", (256, 1)),
    # 64 bins (the smoke): a fold of two while it pays
    (64, 1, "int8", (32, 2)), (64, 2, "int8", (32, 2)),
    (64, 4, "int8", (32, 2)), (64, 8, "int8", (32, 2)),
    (64, 16, "int8", (64, 1)),          # measured +17 % folded: stays
    (64, 32, "int8", (64, 1)), (64, 64, "int8", (64, 1)),
    (64, 1, "fp32", (8, 8)), (64, 8, "bf16", (32, 2)),
    # no power of two: the last group partly empty
    (67, 1, "int8", (32, 3)), (68, 2, "int8", (32, 3)),
    (67, 4, "int8", (32, 3)), (67, 2, "fp32", (16, 5)),
    (67, 8, "int8", (67, 1)), (67, 16, "int8", (67, 1)),
    (67, 32, "fp32", (67, 1)), (67, 64, "int8", (67, 1)),
    # too few bins to halve
    (32, 1, "int8", (32, 1)), (16, 1, "bf16", (16, 1)),
    (8, 4, "fp32", (8, 1)), (2, 1, "fp32", (2, 1)),
])
def test_fold_of_table(n_bin, m_pad, precision, want):
    rows, n_hi = ph._fold_of(n_bin, m_pad, precision)
    assert (rows, n_hi) == want
    assert n_hi * rows >= n_bin and n_hi * 2 * m_pad <= max(128, 2 * m_pad)
    if n_hi > 1:
        assert rows & (rows - 1) == 0 and (n_hi - 1) * rows < n_bin


def _trace_tree_levels(F, depth):
    """Trace (never run) the level histograms of one tree at 256 bins in
    int8, kernel-native up to 64 nodes as the grower asks for them.  A
    fresh function each call: eval_shape caches traces."""
    def tree(binned, gh, pos):
        prep_bt = ph.transpose_bins(binned, 256)
        q, scale = ph.quantize_gh(gh)
        return [ph._hist_pallas_pre(prep_bt, q, scale, pos, binned.shape,
                                    1 << d, 256, "int8", False,
                                    native=(1 << d) <= 64)
                for d in range(depth)]
    jax.eval_shape(lambda *a: tree(*a),
                   jax.ShapeDtypeStruct((4096, F), jnp.uint8),
                   jax.ShapeDtypeStruct((4096, 2), jnp.float32),
                   jax.ShapeDtypeStruct((4096,), jnp.int32))


def test_onehot_rows_gauge_after_a_depth_6_trace(monkeypatch):
    """Six levels of one tree at 256 bins, traced (never run): the gauge
    holds the one-hot rows a feature pushes per row tile summed over the
    levels, 32 + 32 + 32 + 64 + 64 + 128; the unfolded program's 6 x
    256; a second tree starts again at its root."""
    from xgboost_tpu import obs
    gauge = obs.training_metrics().hist_onehot_rows

    def trace():
        _trace_tree_levels(28, 6)
    trace()
    assert gauge.value == 352
    assert "xgbtpu_hist_onehot_rows 352" in obs.registry().render()
    trace()
    assert gauge.value == 352
    monkeypatch.setattr(ph, "_fold_of", _unfolded)
    trace()
    assert gauge.value == 1536


# ------------------------- wide F and node tiles (ISSUE 36, the Epsilon shape)
@pytest.mark.parametrize("M", [32, 64, 128])
@pytest.mark.parametrize("F,N", [(264, 4200), (2000, 2100)])
def test_wide_level_equals_scatter_in_int8(F, N, M, monkeypatch):
    """33 and 250 feature tiles of 8 at 256 bins, a few row tiles, 20 %
    inactive rows: the level kernel's int32 sums are the XLA scatter's
    of the same quantized gradients, bit for bit, at 32 nodes (folded by
    two), 64 (one unfolded 64-node tile) and 128 (two node tiles, the
    relayout of the standard layout)."""
    binned, bt, gh_in, scale, gh_ref, pos = _fold_case(N, F, 256, M, "int8")
    raw = _spy_on_pallas_call(monkeypatch)
    got = np.asarray(ph._hist_pallas_pre(bt, gh_in, scale, pos, (N, F), M,
                                         256, "int8", True))
    rows, n_hi = ph._fold_of(256, min(M, 64), "int8")
    assert (rows, n_hi) == ((128, 2) if M == 32 else (256, 1))
    assert bt.shape[0] == F and F % 8 == 0          # no padded slot
    assert raw[0].shape == (-(-M // 64), F * rows, n_hi * 2 * min(M, 64))
    want = np.asarray(build_level_histogram(
        jnp.asarray(binned), jnp.asarray(gh_ref), pos, M, 256))
    assert got.shape == (M, F, 256, 2) and np.abs(want).max() > 0
    np.testing.assert_array_equal(got, want * np.asarray(scale / 127.0))


@pytest.mark.parametrize("depth,F,tiles,node_tiles,rows", [
    (6, 28, 4, 6, 352), (6, 13, 2, 6, 352), (8, 2000, 250, 9, 1120),
    (8, 264, 33, 9, 1120)])
def test_grid_gauges_after_a_traced_tree(depth, F, tiles, node_tiles, rows):
    """A tree's level histograms at 256 bins, traced (never run): the
    gauges hold the feature tiles of the last level and the node tiles
    and one-hot rows summed over the levels (the 128-node level counts
    two tiles and 2 x 256 rows), and the registry renders them."""
    from xgboost_tpu import obs
    tm = obs.training_metrics()
    _trace_tree_levels(F, depth)
    assert tm.hist_feature_tiles.value == tiles
    assert tm.hist_node_tiles.value == node_tiles
    assert tm.hist_onehot_rows.value == rows
    text = obs.registry().render()
    assert f"xgbtpu_hist_feature_tiles {tiles}" in text
    assert f"xgbtpu_hist_node_tiles {node_tiles}" in text
