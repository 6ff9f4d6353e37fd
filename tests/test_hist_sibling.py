"""Sibling derivation in the level kernel's own int32 sums (ISSUE 38):
past the first level a tree builds, an int8 level builds its LEFT
children only, at half the node lanes, and takes each right child as
parent - left before anything is widened or scaled
(``ops/pallas_hist._hist_pallas_derived``; ``grow_tree`` carries the
raw block through ``ops/histogram.level_histogram_carried``).

The contract: a derived level IS the built level, bit for bit, raw
int32 block and dequantized histogram, so whole trees come out equal to
every bit, and every path that builds every node (float modes, scatter,
``fixed``, vmapped trees and lanes) keeps every contract it has with one
that derives.  Interpret mode under ``XGBTPU_HIST=pallas_int8``; the
chip's own Mosaic kernel runs the same comparison in ``chip_smoke.py
--kernels``.
"""

import contextlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from xgboost_tpu import obs  # noqa: E402
from xgboost_tpu.models.tree import GrowConfig, grow_tree  # noqa: E402
from xgboost_tpu.ops import histogram as H  # noqa: E402
from xgboost_tpu.ops import pallas_hist as ph  # noqa: E402
from xgboost_tpu.ops.split import SplitConfig  # noqa: E402

N_ROWS = 4200                   # three row tiles of 2,048


# ------------------------------------------------------------- one level
def _level_case(N, F, n_bin, n_node, seed=0, parked=0.2, split=0.7):
    """A level of ``n_node`` nodes under its parent level: ``(bt, q,
    scale, parent pos, parent_split, pos)``.  A share of the rows is in
    no parent; a share of the parents did not split (their rows are
    parked); the other parents' rows go left or right."""
    rng = np.random.default_rng(seed)
    M = n_node // 2
    binned = jnp.asarray(rng.integers(0, n_bin, (N, F)), jnp.int32)
    q, scale = ph.quantize_gh(
        jnp.asarray(rng.normal(size=(N, 2)), jnp.float32))
    ppos = np.where(rng.random(N) < parked, -1, rng.integers(0, M, N))
    did = rng.random(M) < split
    did[0] = True
    pos = np.where((ppos >= 0) & did[np.clip(ppos, 0, None)],
                   2 * ppos + rng.integers(0, 2, N), -1)
    return (ph.transpose_bins(binned, n_bin), q, scale,
            jnp.asarray(ppos, jnp.int32), jnp.asarray(did),
            jnp.asarray(pos, jnp.int32))


def _built_and_derived(F, n_bin, n_node, native, rows_per_acc, **kw):
    """The level built whole (raw block and histogram) and the same
    level derived from its parent level's raw block."""
    bt, q, scale, ppos, did, pos = _level_case(N_ROWS, F, n_bin, n_node,
                                               **kw)
    nf = (N_ROWS, F)
    parent = ph._hist_level_raw(bt, q, ppos, nf, n_node // 2, n_bin, "int8",
                                True, rows_per_acc)
    raw_built = ph._hist_level_raw(bt, q, pos, nf, n_node, n_bin, "int8",
                                   True, rows_per_acc)
    built = ph._hist_pallas_pre(bt, q, scale, pos, nf, n_node, n_bin, "int8",
                                True, native=native,
                                rows_per_acc=rows_per_acc)
    derived, raw = ph._hist_pallas_derived(
        bt, q, scale, pos, parent, did, nf, n_node, n_bin, True,
        native=native, rows_per_acc=rows_per_acc)
    return (np.asarray(raw_built), np.asarray(raw), np.asarray(built),
            np.asarray(derived), np.asarray(did))


# rows_per_acc -> the int32 blocks the three row tiles take
_CHUNKS = {None: 1, 4096: 2, 2048: 3}

# n_node: every level of a depth-8 tree past its root: the kernel runs
# the programs of 1 ... 64 nodes (folded up to 32; 64 -> 128 is two node
# tiles made from ONE); both layouts up to 64 nodes (the grower asks for
# the native one there); 256 bins at F = 13 (3 of 16 slots of the last
# feature tile are padding), one int32 block and three
_LEVELS = [(n, 256, 13, nat, rpa)
           for n in (2, 4, 8, 16, 32, 64, 128) for nat in (True, False)
           for rpa in (None, 2048) if not (nat and n > 64)]
# two blocks (the last one a single row tile) at the fold boundaries
_LEVELS += [(n, 256, 13, n <= 64, 4096) for n in (2, 16, 64, 128)]
# 64 bins (no fold from 16 nodes on, one feature tile) and 67 (no power
# of two: the last bin-id group is partly empty and cut away)
_LEVELS += [(n, b, 13, nat, rpa) for b in (64, 67) for n in (4, 32, 128)
            for nat, rpa in ((n <= 64, None), (False, 2048),
                             (n <= 64, 4096))]
# 28 of 32 slots in four feature tiles; 264: every slot a feature
_LEVELS += [(n, 256, F, n <= 64, rpa) for n in (8, 128) for F in (28, 264)
            for rpa in (None, 2048) if not (F == 264 and rpa)]


@pytest.mark.parametrize("n_node,n_bin,F,native,rows_per_acc", _LEVELS)
def test_derived_level_is_the_built_level(n_node, n_bin, F, native,
                                          rows_per_acc):
    raw_built, raw, built, derived, _ = _built_and_derived(
        F, n_bin, n_node, native, rows_per_acc)
    f_pad = ph._tiling(N_ROWS, F, n_bin)[3]
    assert raw.dtype == np.int32 and np.abs(raw_built).max() > 0
    assert raw.shape == ph.raw_block_shape((N_ROWS, F), n_node, n_bin,
                                           rows_per_acc)
    assert raw.shape == (_CHUNKS[rows_per_acc] * -(-n_node // 64),
                         f_pad * n_bin, 2 * min(n_node, 64))
    # chunk by chunk: the int32 blocks themselves, not only their sum
    np.testing.assert_array_equal(raw, raw_built)
    assert derived.dtype == np.float32
    assert derived.shape == ((F, n_bin, 2, n_node) if native
                             else (n_node, F, n_bin, 2))
    # to the bit, signed zeros included
    np.testing.assert_array_equal(derived.view(np.uint32),
                                  built.view(np.uint32))


@pytest.mark.parametrize("n_node", [2, 16, 128])
def test_a_parent_that_did_not_split_has_two_empty_children(n_node):
    """Its rows are parked (pos -1), so the left child is empty, and
    the right one must not inherit parent - 0."""
    raw_built, raw, built, derived, did = _built_and_derived(
        5, 64, n_node, False, None, split=0.5)
    assert (~did).any() or n_node == 2
    children = np.repeat(~did, 2)
    assert not derived[children].any()
    assert derived[~children].any()
    np.testing.assert_array_equal(raw, raw_built)
    np.testing.assert_array_equal(derived, built)


def test_no_parent_builds_every_node():
    bt, q, scale, _, _, pos = _level_case(N_ROWS, 5, 64, 8)
    hist, raw = ph._hist_pallas_derived(bt, q, scale, pos, None, None,
                                        (N_ROWS, 5), 8, 64, True)
    np.testing.assert_array_equal(
        np.asarray(raw), np.asarray(ph._hist_level_raw(
            bt, q, pos, (N_ROWS, 5), 8, 64, "int8", True)))
    np.testing.assert_array_equal(
        np.asarray(hist), np.asarray(ph._hist_pallas_pre(
            bt, q, scale, pos, (N_ROWS, 5), 8, 64, "int8", True)))


def _trace_tree(F, depth):
    """Trace (never run) a tree's level histograms at 256 bins in int8
    as the grower asks for them: the root built, every level below it
    derived.  A fresh function each call: eval_shape caches traces."""
    def tree(binned, gh, pos):
        bt = ph.transpose_bins(binned, 256)
        q, scale = ph.quantize_gh(gh)
        raw = None
        for d in range(depth):
            _, raw = ph._hist_pallas_derived(
                bt, q, scale, pos, raw,
                None if raw is None else jnp.ones(1 << d >> 1, jnp.bool_),
                binned.shape, 1 << d, 256, False, native=(1 << d) <= 64)
        return raw
    jax.eval_shape(lambda *a: tree(*a),
                   jax.ShapeDtypeStruct((4096, F), jnp.uint8),
                   jax.ShapeDtypeStruct((4096, 2), jnp.float32),
                   jax.ShapeDtypeStruct((4096,), jnp.int32))


@pytest.mark.parametrize("depth,F,node_tiles,rows", [
    (6, 28, 6, 256), (6, 13, 6, 256), (8, 2000, 8, 640), (8, 264, 8, 640)])
def test_gauges_after_a_traced_tree(depth, F, node_tiles, rows):
    """The three gauges that sum over the levels of the tree last
    traced, counted for the nodes the kernel BUILDS: 32 + 32 + 32 + 32 +
    64 + 64 one-hot rows at depth 6 (352 with every node built), + 128 +
    256 at depth 8, where the 128-node level is ONE tile of 64 left
    children (1,120 rows and 9 tiles with every node built); a second
    tree starts again at its root."""
    tm = obs.training_metrics()
    for _ in range(2):
        _trace_tree(F, depth)
        assert tm.hist_derived_levels.value == depth - 1
        assert tm.hist_onehot_rows.value == rows
        assert tm.hist_node_tiles.value == node_tiles
    text = obs.registry().render()
    assert f"xgbtpu_hist_derived_levels {depth - 1}" in text
    assert f"xgbtpu_hist_onehot_rows {rows}" in text
    assert f"xgbtpu_hist_node_tiles {node_tiles}" in text


# ------------------------------------------------------------ whole trees
@contextlib.contextmanager
def _every_node_built():
    """The same ``grow_tree`` with no level derived: the seam is handed
    no parent.  The test's switch, not the program's."""
    carried = H.level_histogram_carried

    def direct(*args, parent=None, **kw):
        return carried(*args, **kw)
    jax.clear_caches()
    H.level_histogram_carried = direct
    try:
        yield
    finally:
        H.level_histogram_carried = carried
        jax.clear_caches()


def _tree_case(N=3000, F=5, n_bin=32, seed=0):
    rng = np.random.default_rng(seed)
    binned = jnp.asarray(rng.integers(0, n_bin, (N, F)), jnp.uint8)
    y = (np.asarray(binned[:, 0], np.float32) / n_bin
         + 0.3 * rng.normal(size=N) > 0.5)
    gh = jnp.stack([jnp.asarray(0.5 - y, jnp.float32),
                    jnp.full(N, 0.25, jnp.float32)], 1)
    cut_values = jnp.tile(jnp.arange(n_bin - 2, dtype=jnp.float32), (F, 1))
    n_cuts = jnp.full(F, n_bin - 2, jnp.int32)
    return binned, gh, cut_values, n_cuts


def _cfg(depth, n_bin=32, precision="auto", **kw):
    split = SplitConfig(**{k: kw.pop(k) for k in list(kw)
                           if k in SplitConfig._fields})
    return GrowConfig(split=split, max_depth=depth, n_bin=n_bin,
                      hist_precision=precision, **kw)


def _grow(cfg, case, **kw):
    binned, gh, cut_values, n_cuts = case
    out = grow_tree(jax.random.PRNGKey(3), binned, gh, cut_values, n_cuts,
                    cfg, **kw)
    return jax.tree_util.tree_map(np.asarray, out)


def _assert_same_trees(got, want):
    """TreeArrays, row_leaf and row_val, byte for byte."""
    got, want = (jax.tree_util.tree_leaves(x) for x in (got, want))
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


@pytest.mark.parametrize("depth,extra,levels", [
    (6, {}, 5),
    (8, {}, 7),
    # parents that stay leaves early (their children must stay empty),
    # rows dropped by subsample (zeros in gh_used at every level) and
    # rows under row_valid=False (never in a node)
    (6, {"min_child_weight": 12.0, "subsample": 0.6}, 5),
    # two roots: the first level built is the 2-node one, built whole
    (3, {"n_roots": 2}, 2),
])
def test_whole_tree_equals_the_direct_build(monkeypatch, depth, extra,
                                            levels):
    monkeypatch.setenv("XGBTPU_HIST", "pallas_int8")
    cfg = _cfg(depth, **extra)
    case = _tree_case()
    kw = {}
    if "subsample" in extra:
        kw["row_valid"] = jnp.arange(3000) % 7 != 0
    if "n_roots" in extra:
        kw["root"] = jnp.arange(3000, dtype=jnp.int32) % 2
    gauge = obs.training_metrics().hist_derived_levels
    with _every_node_built():
        want = _grow(cfg, case, **kw)
        assert gauge.value == 0
    got = _grow(cfg, case, **kw)
    assert gauge.value == levels
    tree = got[0]
    assert (tree.feature >= 0).sum() > (2 if "n_roots" in extra else 8)
    if "subsample" in extra:
        # some parent of a level that was derived did stay a leaf
        assert tree.is_leaf[:31].any()
    _assert_same_trees(got, want)


def _equations(jaxpr):
    """Every equation of a jaxpr, in program order, through whatever
    wraps a sub-program (pjit, custom_vmap_call)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _kernel_calls(jaxpr):
    """Output shapes of every ``pallas_call``."""
    return [tuple(v.aval.shape) for eqn in _equations(jaxpr.jaxpr)
            if eqn.primitive.name == "pallas_call" for v in eqn.outvars]


def _int_subtractions(jaxpr):
    """``sub`` equations on int32 blocks of a histogram's rank."""
    return sum(eqn.primitive.name == "sub"
               and eqn.outvars[0].aval.dtype == jnp.int32
               and eqn.outvars[0].aval.ndim >= 3
               for eqn in _equations(jaxpr.jaxpr))


def _level_block(n_node, n_bin, F, mode):
    """The block the level kernel writes when it builds ``n_node``
    nodes of a one-chunk job: (node tiles, f_pad * rows, lanes)."""
    m_pad = min(n_node, 64)
    rows, n_hi = ph._fold_of(n_bin, m_pad, mode)
    f_pad = ph._tiling(2100, F, n_bin)[3]
    return (-(-n_node // m_pad), f_pad * rows, n_hi * 2 * m_pad)


@pytest.mark.parametrize("env,mode", [
    ("pallas", "fp32"), ("pallas_bf16", "bf16"), ("pallas_int8", "int8")])
def test_float_modes_hold_no_subtraction(monkeypatch, env, mode):
    """float32 accumulators are not associative: parent - left is not
    the sum the kernel would have made.  The traced program of fp32 and
    bf16 has one kernel call per level at the level's FULL node count,
    no int32 subtraction, and the gauge reads 0; the int8 program, on
    the same inputs, builds 1, 1, 2, 4 nodes and subtracts three
    times."""
    monkeypatch.setenv("XGBTPU_HIST", env)
    binned, gh, cut_values, n_cuts = _tree_case(N=2100)
    cfg = _cfg(4)
    gauge = obs.training_metrics().hist_derived_levels
    gauge.set(9.0)
    jaxpr = jax.make_jaxpr(
        lambda b, g: grow_tree.__wrapped__(
            jax.random.PRNGKey(3), b, g, cut_values, n_cuts, cfg))(binned, gh)
    calls = [s for s in _kernel_calls(jaxpr) if len(s) == 3]
    built = [1, 2, 4, 8] if mode != "int8" else [1, 1, 2, 4]
    assert calls == [_level_block(n, 32, 5, mode) for n in built]
    assert gauge.value == (0 if mode != "int8" else 3)
    assert _int_subtractions(jaxpr) == (0 if mode != "int8" else 3)


@pytest.mark.parametrize("env,precision", [
    ("pallas", "auto"), ("pallas_bf16", "auto"), ("scatter", "auto"),
    ("", "fixed")])
def test_other_modes_build_every_node(monkeypatch, env, precision):
    """Run, not only traced: the float modes, the XLA scatter and
    ``fixed`` ask the kernel, where there is one, for every node of
    every level, and grow a tree."""
    monkeypatch.setenv("XGBTPU_HIST", env)
    asked = []
    raw_of = ph._hist_level_raw

    def spy(*args, **kw):
        asked.append(args[4])
        return raw_of(*args, **kw)
    monkeypatch.setattr(ph, "_hist_level_raw", spy)
    tm = obs.training_metrics()
    tm.hist_derived_levels.set(0.0)
    jax.clear_caches()
    tree = _grow(_cfg(4, precision=precision), _tree_case(N=2100))[0]
    jax.clear_caches()
    assert (tree.feature >= 0).sum() > 4
    assert tm.hist_derived_levels.value == 0
    assert asked == ([1, 2, 4, 8] if env.startswith("pallas") else [])


def test_vmapped_trees_and_lanes_equal_their_solo_trees(monkeypatch):
    """Two trees on one dataset (multiclass groups) and two datasets
    (tenant lanes) under ``jax.vmap``: the batched kernels build every
    node, the solo tree derives, and they are the same trees."""
    monkeypatch.setenv("XGBTPU_HIST", "pallas_int8")
    cfg = _cfg(4)
    a, b = _tree_case(N=2100, seed=1), _tree_case(N=2100, seed=2)
    key = jax.random.PRNGKey(3)
    gauge = obs.training_metrics().hist_derived_levels
    solo = [_grow(cfg, (a[0], gh) + a[2:]) for gh in (a[1], b[1])]
    solo_b = _grow(cfg, b)
    assert gauge.value == 3             # trace-time: the first call's
    trees = jax.vmap(
        lambda gh: grow_tree(key, a[0], gh, a[2], a[3], cfg))(
            jnp.stack([a[1], b[1]]))
    assert gauge.value == 0
    for t in range(2):
        _assert_same_trees(
            jax.tree_util.tree_map(lambda x: np.asarray(x[t]), trees),
            solo[t])
    gauge.set(3.0)
    lanes = jax.vmap(
        lambda binned, gh: grow_tree(key, binned, gh, a[2], a[3], cfg))(
            jnp.stack([a[0], b[0]]), jnp.stack([a[1], b[1]]))
    assert gauge.value == 0
    for t, want in enumerate((solo[0], solo_b)):
        _assert_same_trees(
            jax.tree_util.tree_map(lambda x: np.asarray(x[t]), lanes), want)


@pytest.mark.parametrize("shards", [2, 4])
def test_row_split_mesh_equals_the_direct_build(monkeypatch, shards):
    """``parallel/dp.py``, rows over the CPU mesh, int8: each shard
    subtracts in its own int32 block before the dequantize and the
    ``psum``, so every shard sends what it sent when it built every
    node, and the trees are those."""
    from xgboost_tpu.parallel.dp import grow_tree_dp
    from xgboost_tpu.parallel.mesh import data_parallel_mesh, mesh_available
    if not mesh_available(shards):
        pytest.skip(f"needs >= {shards} devices")
    monkeypatch.setenv("XGBTPU_HIST", "pallas_int8")
    cfg = _cfg(4)
    binned, gh, cut_values, n_cuts = _tree_case(N=4200)
    mesh = data_parallel_mesh(shards)

    def grow():
        out = grow_tree_dp(mesh, jax.random.PRNGKey(3), binned, gh,
                           cut_values, n_cuts, cfg,
                           jnp.arange(4200) % 11 != 0)
        return jax.tree_util.tree_map(np.asarray, out)
    gauge = obs.training_metrics().hist_derived_levels
    with _every_node_built():
        want = grow()
        assert gauge.value == 0
    got = grow()
    assert gauge.value == 3
    assert (got[0].feature >= 0).sum() > 4
    _assert_same_trees(got, want)


def test_the_metric_file_reads_the_gauge():
    """``benchmark/metrics/hist_derived_levels.json`` through the
    ``program_gauge`` reader: the gauge's value, and nothing on a
    program without it (the parent commit)."""
    import importlib.util
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "metrics",
                           "hist_derived_levels.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "program_gauge"
    assert spec["layer"] == "histogram kernels"
    assert spec["moves"] == "train_rounds_per_s"
    assert spec["args"] == {"group": "training_metrics",
                            "gauge": "hist_derived_levels"}
    mod = importlib.util.spec_from_file_location(
        "program_gauge", os.path.join(root, "benchmark", "readers",
                                      "program_gauge.py"))
    reader = importlib.util.module_from_spec(mod)
    mod.loader.exec_module(reader)
    obs.training_metrics().hist_derived_levels.set(7.0)
    assert reader.read({}, **spec["args"]) == 7.0
    assert reader.read({}, **dict(spec["args"], gauge="no_such")) is None
