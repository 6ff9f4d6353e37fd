"""int8 histograms at any row count (ISSUE 31): the Pallas kernels sum
row CHUNKS of at most 2^24 rows exactly in int32 blocks of their own and
add the chunks in float32, where ``resolve_precision`` used to turn int8
into bf16 past 16.9M rows without telling anyone.

(a) chunked int8 equals one-block int8 bit for bit (interpret mode,
    ``rows_per_acc`` forced to two tiles over five tiles of rows: a
    partial last chunk), native and relayout, one node tile and two,
    solo, lanes and trees;
(b) the accumulator bound, and three chunks at 40M rows;
(c) the mode ``prepare_hist`` resolves at an abstract 40M rows is the
    one asked for, the traced level has three chunks and a
    ``grow.widen`` scope, a small job has neither;
(d) ``train.launch`` says which mode ran, in the event log.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu import obs
from xgboost_tpu.ops import histogram as hs
from xgboost_tpu.ops import pallas_hist as ph

R_TILE = 2048
N, F, B = 5 * R_TILE - 100, 13, 32          # five row tiles, the last short
FORCED = 2 * R_TILE                          # chunks of 2, 2 and 1 tiles


def _case(M, seed=0, lanes=None):
    rng = np.random.RandomState(seed)
    lead = () if lanes is None else (lanes,)
    binned = rng.randint(0, B, lead + (N, F)).astype(np.uint8)
    gh = rng.randn(*lead, N, 2).astype(np.float32)
    pos = rng.randint(-1, M, lead + (N,)).astype(np.int32)
    return jnp.asarray(binned), jnp.asarray(gh), jnp.asarray(pos)


def _solo(M, native, rows_per_acc):
    binned, gh, pos = _case(M)
    q, scale = ph.quantize_gh(gh)
    return ph._hist_pallas_pre(
        ph.transpose_bins(binned, B), q, scale, pos, (N, F), M, B, "int8",
        True, native=native, rows_per_acc=rows_per_acc)


def _lanes(M, native, rows_per_acc):
    binned, gh, pos = _case(M, lanes=2)
    q, scale = ph.quantize_gh(gh)
    bt = jax.vmap(lambda b: ph.transpose_bins(b, B))(binned)
    return ph._hist_pallas_lanes_pre(
        bt, q, scale, pos, (N, F), M, B, "int8", True, native=native,
        rows_per_acc=rows_per_acc)


def _trees(M, native, rows_per_acc):
    binned, _, _ = _case(M)
    _, gh, pos = _case(M, seed=1, lanes=3)
    q, scale = ph.quantize_gh(gh)
    bt = ph.transpose_bins_batched(binned, B, 3, min(M, 64), "int8")
    return ph._hist_pallas_batched_pre(
        bt, q, scale, pos, (N, F), M, B, "int8", True, native=native,
        rows_per_acc=rows_per_acc)


@pytest.mark.parametrize("call", [_solo, _lanes, _trees])
@pytest.mark.parametrize("M,native", [(4, True), (4, False), (128, False)])
def test_chunked_int8_equals_one_block_bit_for_bit(call, M, native):
    one = np.asarray(call(M, native, None))
    chunks = obs.training_metrics().hist_row_chunks
    assert chunks.value == 1
    three = np.asarray(call(M, native, FORCED))
    assert chunks.value == 3
    assert one.dtype == three.dtype == np.float32
    assert np.abs(one).max() > 0
    assert np.array_equal(one, three)


@pytest.mark.parametrize("kernel", ["solo", "lanes"])
@pytest.mark.parametrize("n_bin,M", [(256, 1), (256, 8), (256, 16),
                                     (64, 2), (67, 4)])
def test_folded_chunked_int8_equals_one_block_and_unfolded(kernel, n_bin, M,
                                                           monkeypatch):
    """A folded level (ISSUE 34: the bin id's high bits in idle lanes,
    ``_fold_of``) takes the row chunks as the unfolded one does: three
    forced chunks equal one block, and both equal the unfolded program's
    three chunks, bit for bit."""
    assert ph._fold_of(n_bin, M, "int8")[1] > 1
    rng = np.random.RandomState(34)
    lead = (2,) if kernel == "lanes" else ()
    binned = jnp.asarray(rng.randint(0, n_bin, lead + (N, F)).astype(np.uint8))
    gh = jnp.asarray(rng.randn(*lead, N, 2).astype(np.float32))
    pos = jnp.asarray(rng.randint(-1, M, lead + (N,)).astype(np.int32))
    q, scale = ph.quantize_gh(gh)
    tb = lambda b: ph.transpose_bins(b, n_bin)                  # noqa: E731
    bt = jax.vmap(tb)(binned) if lead else tb(binned)
    pre = ph._hist_pallas_lanes_pre if lead else ph._hist_pallas_pre

    def run(rows_per_acc):
        return np.asarray(pre(bt, q, scale, pos, (N, F), M, n_bin, "int8",
                              True, native=True, rows_per_acc=rows_per_acc))
    chunks = obs.training_metrics().hist_row_chunks
    one = run(None)
    assert chunks.value == 1 and np.abs(one).max() > 0
    three = run(FORCED)
    assert chunks.value == 3
    assert np.array_equal(one, three)
    monkeypatch.setattr(ph, "_fold_of", lambda n_bin, m_pad, p: (n_bin, 1))
    assert np.array_equal(run(FORCED), three)


def test_lane_chunks_equal_the_solo_call_of_each_lane():
    binned, gh, pos = _case(4, lanes=2)
    q, scale = ph.quantize_gh(gh)
    stacked = np.asarray(_lanes(4, False, FORCED))
    for lane in range(2):
        solo = ph._hist_pallas_pre(
            ph.transpose_bins(binned[lane], B), q[lane], scale[lane],
            pos[lane], (N, F), 4, B, "int8", True, rows_per_acc=FORCED)
        assert np.array_equal(stacked[lane], np.asarray(solo)), lane


@pytest.mark.parametrize("r_tile", [512, 2048, 4096, 8192])
def test_an_accumulator_block_cannot_overflow(r_tile):
    rows = ph._rows_per_acc(r_tile)
    assert rows % r_tile == 0 and rows * 127 < 2 ** 31 <= 2 * rows * 127
    n_tiles = -(-40_000_000 // r_tile)
    rpa, n_chunks = ph._acc_tiles(n_tiles, r_tile, "int8")
    assert rpa * r_tile == rows == 16_777_216 and n_chunks == 3
    # float32 cells have no such bound, and a small job is one block
    assert ph._acc_tiles(n_tiles, r_tile, "bf16") == (n_tiles, 1)
    assert ph._acc_tiles(n_tiles, r_tile, "fp32") == (n_tiles, 1)
    assert ph._acc_tiles(7, r_tile, "int8") == (7, 1)


def _abstract_level(n_rows):
    """Trace and lower for the TPU (never run) prepare_hist and one
    32-node level histogram at `n_rows`; returns (mode, output, chunks
    gauge, module text with its scopes)."""
    seen = {}

    def level(binned, gh, pos):
        prep = hs.prepare_hist(binned, gh, 256, "auto")
        seen["mode"] = prep.precision
        return hs.build_level_histogram(binned, gh, pos, 32, 256, "auto",
                                        prep=prep, native=True)
    args = (jax.ShapeDtypeStruct((n_rows, 13), jnp.uint8),
            jax.ShapeDtypeStruct((n_rows, 2), jnp.float32),
            jax.ShapeDtypeStruct((n_rows,), jnp.int32))
    lowered = jax.jit(level).trace(*args).lower(lowering_platforms=("tpu",))
    return (seen["mode"], lowered.out_info,
            obs.training_metrics().hist_row_chunks.value,
            lowered.as_text(debug_info=True))


@pytest.mark.parametrize("forced,mode", [
    ("pallas_int8", "int8"), ("pallas_bf16", "bf16"), ("pallas", "fp32")])
def test_the_mode_at_40m_rows_is_the_one_asked_for(monkeypatch, forced, mode):
    assert not hasattr(ph, "resolve_precision")
    monkeypatch.setenv("XGBTPU_HIST", forced)
    assert hs.kernel_mode("auto") == mode
    got, out, chunks, text = _abstract_level(40_000_000)
    assert got == mode
    assert (out.shape, out.dtype) == ((13, 256, 2, 32), jnp.float32)
    assert chunks == (3 if mode == "int8" else 1)
    assert ("grow.widen" in text) == (mode == "int8")
    # under one accumulator's rows: one chunk, nothing widened
    _, _, chunks, text = _abstract_level(8_400_000)
    assert chunks == 1 and "grow.widen" not in text


def test_kernel_mode_names_the_scatter_paths(monkeypatch):
    monkeypatch.delenv("XGBTPU_HIST", raising=False)
    assert hs.kernel_mode("fixed") == "fixed"
    assert hs.kernel_mode("auto") == "scatter"      # the CPU backend


def test_train_launch_says_which_mode_ran(tmp_path, monkeypatch):
    monkeypatch.setenv("XGBTPU_HIST", "pallas_int8")
    rng = np.random.default_rng(0)
    X = rng.normal(size=(600, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    path = str(tmp_path / "spans.jsonl")
    obs.configure_log(path)
    try:
        dtrain = xgb.DMatrix(X, label=y)
        xgb.Booster({"objective": "binary:logistic", "max_depth": 2,
                     "silent": 1}).update_many(dtrain, 0, 2,
                                               rounds_per_dispatch=2)
    finally:
        obs.configure_log(None)
    with open(path) as f:
        launches = [r for r in map(json.loads, f)
                    if r["kind"] == "span" and r["name"] == "train.launch"]
    assert launches and all(r["attrs"]["hist_mode"] == "int8"
                            for r in launches)
    assert "xgbtpu_hist_row_chunks 1" in obs.registry().render()
