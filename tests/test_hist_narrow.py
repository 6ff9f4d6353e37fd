"""Width classes of the level kernel: a feature whose bin ids
all fall below ``NARROW_BIN`` = 32 (``n_cuts + 1 < 32``, from the cuts)
is built in a ``hist_level_rows`` call of its own at 32 bins, beside the
call for the other features at ``n_bin``; the tail pads the narrow
histogram back to ``n_bin`` bins and puts the features back in their
order (``ops/pallas_hist.width_classes``, ``transpose_classes``,
``_hist_level_tail``).

The contract: the histogram is the one full-width call's, bit for bit,
in every mode, layout, row chunking and derived level, so trees come out
equal to every byte; where no feature is narrow the program is the one
it was (one call a level, one operand); the tree-batched and lane
kernels build every feature at ``n_bin`` as before.  Interpret mode;
``chip_smoke.py --kernels`` runs the same comparison on the chip's own
Mosaic kernel (its "two width classes" cases).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from xgboost_tpu import obs  # noqa: E402
from xgboost_tpu.models.tree import GrowConfig, grow_tree  # noqa: E402
from xgboost_tpu.ops import pallas_hist as ph  # noqa: E402
from xgboost_tpu.ops.split import SplitConfig  # noqa: E402

N_ROWS = 4200                   # three row tiles of 2,048
B = 256
# largest bin id n_cuts + 1: narrow up to 31 (n_cuts 30), wide from 32
CUTS = {
    "interleaved": [254, 2, 30, 31, 1, 254, 29, 2, 100, 2, 2, 254, 5],
    "all": [2, 1, 30, 5, 2, 2, 17],
    "none": [254, 31, 100, 254, 40],
}


def _case(n_cuts, n_node, seed=0, missing=0.1, N=N_ROWS):
    """Bin ids of ``len(n_cuts)`` features, feature f's in 1 .. n_cuts[f]
    + 1 with a share ``missing`` in bin 0, dyadic gradients, positions
    in ``n_node`` nodes with a tenth of the rows in none."""
    rng = np.random.default_rng(seed)
    F = len(n_cuts)
    binned = np.stack([rng.integers(1, c + 2, N) for c in n_cuts], 1)
    binned[rng.random((N, F)) < missing] = 0
    gh = (rng.integers(-512, 512, (N, 2)) / 256.0).astype(np.float32)
    pos = np.where(rng.random(N) < 0.1, -1, rng.integers(0, n_node, N))
    return (jnp.asarray(binned, jnp.uint8), jnp.asarray(gh),
            jnp.asarray(pos, jnp.int32))


def _grad(gh, precision):
    if precision == "int8":
        return ph.quantize_gh(gh)
    return gh, None


def _one_and_two(n_cuts, n_node, precision="int8", native=False,
                 rows_per_acc=None, **kw):
    """The level through the one full-width call, and through one call
    a width class: ``(one, two, classes)``."""
    binned, gh, pos = _case(n_cuts, n_node, **kw)
    nf = binned.shape
    classes = ph.width_classes(nf[1], B, ph.narrow_features(n_cuts, B))
    gh_in, scale = _grad(gh, precision)
    one = ph._hist_pallas_pre(ph.transpose_bins(binned, B), gh_in, scale,
                              pos, nf, n_node, B, precision, True,
                              native=native, rows_per_acc=rows_per_acc)
    if classes is None:
        return np.asarray(one), None, classes
    two = ph._hist_pallas_pre(ph.transpose_classes(binned, classes), gh_in,
                              scale, pos, nf, n_node, B, precision, True,
                              native=native, rows_per_acc=rows_per_acc,
                              classes=classes)
    return np.asarray(one), np.asarray(two), classes


def _same_bits(a, b):
    assert a.shape == b.shape and a.dtype == b.dtype
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


# ------------------------------------------------------------ the classes
def test_width_classes_of_the_cuts():
    cuts = CUTS["interleaved"]
    narrow = ph.narrow_features(cuts, B)
    assert narrow == (1, 2, 4, 6, 7, 9, 10, 12)
    wide, nar = ph.width_classes(len(cuts), B, narrow)
    assert wide == ((0, 3, 5, 8, 11), B) and nar == (narrow, ph.NARROW_BIN)
    assert ph.width_classes(7, B, ph.narrow_features(CUTS["all"], B)) == (
        (None, ph.NARROW_BIN),)
    assert ph.narrow_features(CUTS["none"], B) == ()
    assert ph.width_classes(5, B, ()) is None
    # no narrower call where the configured width is no wider
    assert ph.narrow_features([2, 1], ph.NARROW_BIN) == ()
    assert ph.width_classes(2, 16, (0, 1)) is None


@pytest.mark.parametrize("n_cuts,narrow", [(29, True), (30, True),
                                           (31, False), (32, False)])
def test_the_class_boundary(n_cuts, narrow):
    """Largest bin id 31 (n_cuts 30) is narrow, 32 is wide; a narrow
    feature's id 31 lands in the narrow call's last row, and the level
    is the one call's either way."""
    cuts = [254, n_cuts, 3]
    assert (1 in ph.narrow_features(cuts, B)) == narrow
    one, two, classes = _one_and_two(cuts, 8, missing=0.0)
    _same_bits(one, two)
    top = n_cuts + 1
    assert one[:, 1, top].any() and not one[:, 1, top + 1:].any()


# ---------------------------------------------------- one level, bit for bit
_LEVELS = [(layout, n, native, "int8") for layout in ("interleaved", "all")
           for n, native in ((1, True), (16, False), (64, True),
                             (128, False))]
_LEVELS += [("interleaved", 8, False, "fp32"), ("all", 4, True, "fp32"),
            ("interleaved", 64, True, "bf16"),
            ("interleaved", 128, False, "bf16")]


@pytest.mark.parametrize("layout,n_node,native,precision", _LEVELS)
def test_two_calls_equal_one_call(layout, n_node, native, precision):
    """Narrow and wide features interleaved (runs of both, the narrow
    ones at 32 bins), or every feature narrow (one call at 32 bins and
    the pad): the histogram the finders read, in both layouts and every
    mode, to the bit, signed zeros included."""
    one, two, classes = _one_and_two(CUTS[layout], n_node, precision,
                                     native)
    assert classes is not None and one.any()
    assert len(classes) == (2 if layout == "interleaved" else 1)
    _same_bits(one, two)


def test_no_narrow_feature_is_the_one_call():
    one, two, classes = _one_and_two(CUTS["none"], 16)
    assert classes is None and two is None and one.any()


def test_a_narrow_feature_with_a_missing_cell():
    """Bin 0 of a narrow feature (its missing rows) is built in the
    narrow call and read where the one call has it."""
    one, two, _ = _one_and_two([254, 2, 5], 4, missing=0.3)
    _same_bits(one, two)
    assert one[:, 1, 0].any() and one[:, 2, 0].any()
    assert not one[:, 1, 4:].any()


@pytest.mark.parametrize("rows_per_acc,n_node", [(2048, 8), (4096, 128)])
def test_int8_row_chunks(rows_per_acc, n_node):
    """Each class's int32 blocks in row chunks (three of one row tile,
    and two), widened and added per class before the merge."""
    one, two, _ = _one_and_two(CUTS["interleaved"], n_node,
                               native=n_node <= 64,
                               rows_per_acc=rows_per_acc)
    _same_bits(one, two)


def _parents(n_node, rng):
    """Positions of a level and of its parent level, a share of the
    parents not split (their rows parked)."""
    M = n_node // 2
    ppos = np.where(rng.random(N_ROWS) < 0.1, -1,
                    rng.integers(0, M, N_ROWS))
    did = rng.random(M) < 0.75
    did[0] = True
    pos = np.where((ppos >= 0) & did[np.clip(ppos, 0, None)],
                   2 * ppos + rng.integers(0, 2, N_ROWS), -1)
    return (jnp.asarray(ppos, jnp.int32), jnp.asarray(did),
            jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("rows_per_acc", [None, 2048])
def test_derived_chain_64_to_512(rows_per_acc):
    """64 -> 128 -> 256 -> 512 nodes as the grower derives them at depth
    10, each level's blocks carried to the next, a quarter of the
    parents not split (their rows parked): every class derives on its
    own, and every level is the one-call chain's and the level built
    whole, bit for bit."""
    cuts = CUTS["interleaved"]
    binned, gh, _ = _case(cuts, 1)
    nf = binned.shape
    classes = ph.width_classes(nf[1], B, ph.narrow_features(cuts, B))
    q, scale = ph.quantize_gh(gh)
    bt = ph.transpose_bins(binned, B)
    operands = ((bt, None), (ph.transpose_classes(binned, classes), classes))
    rng = np.random.default_rng(5)
    raws = [None, None]
    pos = np.where(rng.random(N_ROWS) < 0.1, -1, rng.integers(0, 64, N_ROWS))
    did = None
    for n_node in (64, 128, 256, 512):
        if n_node > 64:
            did = rng.random(n_node // 2) < 0.75
            did[0] = True
            pos = np.where((pos >= 0) & did[np.clip(pos, 0, None)],
                           2 * pos + rng.integers(0, 2, N_ROWS), -1)
        p = jnp.asarray(pos, jnp.int32)
        native = n_node <= 64
        outs = [np.asarray(ph._hist_pallas_pre(
            bt, q, scale, p, nf, n_node, B, "int8", True, native=native,
            rows_per_acc=rows_per_acc))]
        for k, (op, c) in enumerate(operands):
            hist, raws[k] = ph._hist_pallas_derived(
                op, q, scale, p, raws[k],
                None if did is None else jnp.asarray(did), nf, n_node, B,
                True, native=native, rows_per_acc=rows_per_acc, classes=c)
            outs.append(np.asarray(hist))
        assert outs[0].any()
        _same_bits(outs[0], outs[1])
        _same_bits(outs[0], outs[2])
        assert isinstance(raws[1], tuple) and len(raws[1]) == 2
        assert raws[1][1].shape[1] == 8 * ph.NARROW_BIN   # 8 narrow slots


# ---------------------------------------------------------- whole trees
def _onehot_case(N=2100, groups=(7, 12, 4), seed=1):
    """The one-hot Airline shape, small: one-hot 0/1 groups (bins 2 and
    3: two cuts each) beside two numeric columns at 256 bins, the second
    numeric the last column; a label from a code and the numerics."""
    rng = np.random.default_rng(seed)
    cols, n_cuts = [], []
    for g in groups:
        code = rng.integers(0, g, N)
        cols += [2 + (code == j) for j in range(g)]
        n_cuts += [2] * g
        if len(cols) == groups[0]:      # DepTime after the first group
            cols.append(rng.integers(1, B, N))
            n_cuts.append(B - 2)
    cols.append(rng.integers(1, B, N))
    n_cuts.append(B - 2)
    binned = np.stack(cols, 1).astype(np.uint8)
    y = (binned[:, 1] == 3) ^ (binned[:, groups[0]] > 140) \
        ^ (rng.random(N) < 0.2)
    gh = np.stack([0.5 - y, np.full(N, 0.25)], 1).astype(np.float32)
    F = binned.shape[1]
    cut_values = jnp.tile(jnp.arange(B - 2, dtype=jnp.float32), (F, 1))
    return (jnp.asarray(binned), jnp.asarray(gh), cut_values,
            jnp.asarray(n_cuts, jnp.int32), np.asarray(n_cuts))


def _cfg(depth, narrow=(), **kw):
    return GrowConfig(split=SplitConfig(min_child_weight=0.5), max_depth=depth,
                      n_bin=B, hist_narrow=narrow, **kw)


def _grow(cfg, case, **kw):
    binned, gh, cut_values, n_cuts, _ = case
    out = grow_tree(jax.random.PRNGKey(3), binned, gh, cut_values, n_cuts,
                    cfg, **kw)
    return jax.tree_util.tree_map(np.asarray, out)


def _assert_same_trees(got, want):
    got, want = (jax.tree_util.tree_leaves(x) for x in (got, want))
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


def test_grow_tree_on_the_onehot_shape_equals_the_one_call(monkeypatch):
    """Depth 7 (levels up to 64 nodes, the last built as 32 left
    children), 23 one-hot columns of 25 narrow: the trees, the rows'
    leaves and values, byte for byte."""
    monkeypatch.setenv("XGBTPU_HIST", "pallas_int8")
    case = _onehot_case()
    narrow = ph.narrow_features(case[4], B)
    assert len(narrow) == 23 and 7 not in narrow and 24 not in narrow
    tm = obs.training_metrics()
    want = _grow(_cfg(7), case)
    assert tm.hist_narrow_features.value == 0
    got = _grow(_cfg(7, narrow), case)
    assert tm.hist_narrow_features.value == 23
    assert (got[0].feature >= 0).sum() > 20
    assert set(np.asarray(got[0].feature)) & set(narrow)   # narrow splits
    _assert_same_trees(got, want)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _kernel_calls(narrow, depth=4):
    """Output shapes of the level kernel calls of a traced tree."""
    binned, gh, cut_values, n_cuts, _ = _onehot_case()
    cfg = _cfg(depth, narrow)
    jaxpr = jax.make_jaxpr(
        lambda b, g: grow_tree.__wrapped__(
            jax.random.PRNGKey(3), b, g, cut_values, n_cuts, cfg))(binned, gh)
    return [tuple(v.aval.shape) for eqn in _equations(jaxpr.jaxpr)
            if eqn.primitive.name == "pallas_call" for v in eqn.outvars]


@pytest.mark.parametrize("some", [False, True])
def test_one_kernel_call_a_level_without_narrow_features(monkeypatch, some):
    """No narrow feature: one call a level, the full-width block of all
    25 features, as the program always traced it.  23 narrow: two calls
    a level, the 2 wide features at 256 bins and the 23 narrow at 32."""
    monkeypatch.setenv("XGBTPU_HIST", "pallas_int8")
    narrow = ph.narrow_features(_onehot_case()[4], B) if some else ()
    calls = _kernel_calls(narrow)
    built = (1, 1, 2, 4)                # depth 4: the root, then left halves

    def block(F, n_bin, m):
        rows, n_hi = ph._fold_of(n_bin, m, "int8")
        f_pad = ph._tiling(2100, F, n_bin)[3]
        return (1, f_pad * rows, n_hi * 2 * m)
    if not some:
        assert calls == [block(25, B, m) for m in built]
    else:
        assert calls == [b for m in built
                         for b in (block(2, B, m), block(23, 32, m))]


def test_vmapped_trees_and_lanes_keep_the_full_width_kernels(monkeypatch):
    """Under ``jax.vmap`` (two trees on one dataset; two datasets as
    tenant lanes) the rules build every feature at ``n_bin`` in the
    batched kernels, whatever ``hist_narrow`` says, and the trees are
    the solo ones, which take two calls a level."""
    monkeypatch.setenv("XGBTPU_HIST", "pallas_int8")
    a, b = _onehot_case(seed=1), _onehot_case(seed=2)
    narrow = ph.narrow_features(a[4], B)
    cfg = _cfg(4, narrow)
    key = jax.random.PRNGKey(3)
    gauge = obs.training_metrics().hist_narrow_features
    solo = [_grow(cfg, (a[0], gh) + a[2:]) for gh in (a[1], b[1])]
    solo_b = _grow(cfg, b)
    assert gauge.value == 23
    trees = jax.vmap(
        lambda gh: grow_tree(key, a[0], gh, a[2], a[3], cfg))(
            jnp.stack([a[1], b[1]]))
    assert gauge.value == 0
    for t in range(2):
        _assert_same_trees(
            jax.tree_util.tree_map(lambda x: np.asarray(x[t]), trees),
            solo[t])
    lanes = jax.vmap(
        lambda binned, gh: grow_tree(key, binned, gh, a[2], a[3], cfg))(
            jnp.stack([a[0], b[0]]), jnp.stack([a[1], b[1]]))
    for t, want in enumerate((solo[0], solo_b)):
        _assert_same_trees(
            jax.tree_util.tree_map(lambda x: np.asarray(x[t]), lanes), want)


# ---------------------------------------------------------------- gauges
def _trace_tree(n_cuts, depth):
    """Trace (never run) a tree's level histograms as the grower asks
    for them, width classes from ``n_cuts``."""
    F = len(n_cuts)
    classes = ph.width_classes(F, B, ph.narrow_features(n_cuts, B))

    def tree(binned, gh, pos):
        bt = (ph.transpose_bins(binned, B) if classes is None
              else ph.transpose_classes(binned, classes))
        q, scale = ph.quantize_gh(gh)
        raw = None
        for d in range(depth):
            _, raw = ph._hist_pallas_derived(
                bt, q, scale, pos, raw,
                None if raw is None else jnp.ones(1 << d >> 1, jnp.bool_),
                binned.shape, 1 << d, B, False, native=(1 << d) <= 64,
                classes=classes)
        return raw
    jax.eval_shape(tree, jax.ShapeDtypeStruct((4096, F), jnp.uint8),
                   jax.ShapeDtypeStruct((4096, 2), jnp.float32),
                   jax.ShapeDtypeStruct((4096,), jnp.int32))


@pytest.mark.parametrize("layout,narrow,tiles,rows", [
    ("none", 0, 1, 256), ("interleaved", 8, 1 + 1, 256 + 6 * 32),
    ("onehot", 693, 1 + 11, 2176 + 14 * 32)])
def test_the_gauges_of_a_traced_tree(layout, narrow, tiles, rows):
    """``xgbtpu_hist_narrow_features`` reads the narrow class, 0 with
    none; the feature dots still read F, the feature tiles and one-hot
    rows add the narrow call's to the wide call's, the node tiles count
    each level once: depth 6 (depth 10 at the one-hot cell's 695)."""
    if layout == "onehot":
        cuts, depth = [2] * 50 + [254] + [2] * 643 + [254], 10
    else:
        cuts, depth = CUTS[layout], 6
    _trace_tree(cuts, depth)
    tm = obs.training_metrics()
    assert tm.hist_narrow_features.value == narrow
    assert tm.hist_feature_dots.value == len(cuts)
    assert tm.hist_feature_tiles.value == tiles
    assert tm.hist_onehot_rows.value == rows
    assert tm.hist_node_tiles.value == (6 if depth == 6 else 14)
    assert tm.hist_derived_levels.value == depth - 1
    assert f"xgbtpu_hist_narrow_features {narrow}" in obs.registry().render()


def test_the_booster_takes_the_narrow_set_from_its_cuts():
    """The narrow set from the cuts, on the paths that hand the grower
    every column; a feature screen maps it to the kept columns'
    positions; ``cfg`` itself (column split, lanes) stays without it;
    new cuts, a new set."""
    from xgboost_tpu.binning import CutMatrix
    from xgboost_tpu.config import TrainParam
    from xgboost_tpu.models.gbtree import GBTree
    cuts = CUTS["interleaved"]
    cv = np.full((len(cuts), 254), np.inf, np.float32)
    for f, c in enumerate(cuts):
        cv[f, :c] = np.arange(c)
    gbt = GBTree(TrainParam(), CutMatrix(cv, np.asarray(cuts, np.int32)))
    assert gbt.cfg.n_bin == B and gbt.cfg.hist_narrow == ()
    assert gbt._grow_cfg().hist_narrow == (1, 2, 4, 6, 7, 9, 10, 12)
    assert gbt._grow_cfg((0, 2, 3, 9, 11)).hist_narrow == (1, 3)
    cv2 = cv.copy()
    cv2[1, :100] = np.arange(100)
    gbt.rebind_cuts(CutMatrix(cv2, np.asarray([254, 100] + cuts[2:],
                                              np.int32)))
    assert gbt._grow_cfg().hist_narrow == (2, 4, 6, 7, 9, 10, 12)


@pytest.mark.parametrize("k", [0, 3])
def test_training_takes_the_narrow_calls_and_the_same_model(monkeypatch, k):
    """``Booster.update_many`` per round (k 0) and in fused segments of 3
    rounds on the one-hot shape at 256 bins: the cuts make 23 of 25
    columns narrow, the kernel builds them in their own call, and the
    model bytes and eval lines are those of a booster whose cuts named
    none narrow."""
    import xgboost_tpu as xgb
    from xgboost_tpu.learner import Booster
    monkeypatch.setenv("XGBTPU_HIST", "pallas_int8")
    binned, _, _, _, n_cuts = _onehot_case(N=1200)
    X = np.asarray(binned, np.float32)
    y = ((X[:, 1] == 3) ^ (X[:, 7] > 140)).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3,
              "max_bin": 256, "sketch_eps": 1 / 256.0,
              "eval_metric": "logloss"}

    def train():
        jax.clear_caches()
        d = xgb.DMatrix(X, label=y)
        bst = Booster(params, cache=[d])
        lines = []
        bst.update_many(d, 0, 4, evals=[(d, "train")],
                        eval_callback=lambda i, msg: lines.append(msg),
                        rounds_per_dispatch=k)
        return bst, lines
    gauge = obs.training_metrics().hist_narrow_features
    bst, lines = train()
    assert bst.gbtree._grow_cfg().hist_narrow == ph.narrow_features(n_cuts,
                                                                    B)
    assert gauge.value == 23
    monkeypatch.setattr(ph, "narrow_features", lambda n_cuts, n_bin: ())
    ref, ref_lines = train()
    assert gauge.value == 0
    assert lines == ref_lines and len(lines) == 4
    assert bytes(bst.save_raw()) == bytes(ref.save_raw())
    jax.clear_caches()


def test_the_metric_file_reads_the_gauge():
    """``benchmark/metrics/hist_narrow_features.json`` through the
    ``program_gauge`` reader, listed for every cell; nothing on a
    program without the gauge (the parent commit)."""
    import importlib.util
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "metrics",
                           "hist_narrow_features.json")) as f:
        spec = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "hist_narrow_features")
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert (spec["reader"], spec["layer"], spec["moves"], spec["unit"]) == (
        "program_gauge", "histogram kernels", "train_rounds_per_s", "count")
    assert (entry["layer"], entry["moves"], entry["unit"]) == (
        spec["layer"], spec["moves"], spec["unit"])
    assert spec["args"] == {"group": "training_metrics",
                            "gauge": "hist_narrow_features"}
    mod = importlib.util.spec_from_file_location(
        "program_gauge", os.path.join(root, "benchmark", "readers",
                                      "program_gauge.py"))
    reader = importlib.util.module_from_spec(mod)
    mod.loader.exec_module(reader)
    obs.training_metrics().hist_narrow_features.set(693.0)
    assert reader.read({}, **spec["args"]) == 693.0
    assert reader.read({}, **dict(spec["args"], gauge="no_such")) is None
