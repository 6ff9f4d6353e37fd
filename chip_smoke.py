"""chip_smoke.py — does the system still start on the chip?

Drives the main path ONCE, through the entry points a user calls, at
the full width of the one shape the repo has chip history for
(:func:`make_higgs_like` 1M x 28, ``binary:logistic``, depth 6,
``max_bin=64``, ``hist_precision`` auto), in ONE process, every stage
fatal:

  device   a TPU or nothing; versions, compile-cache dir, HBM limit;
           ``block_until_ready`` is a real barrier
  train    ``Booster.update_many`` with a watchlist, as ``python -m
           xgboost_tpu`` runs it: >= 3 fused segments, compiled int8
           Pallas histograms, no fused fallback, no swallowed error,
           held-out AUC within 0.002 of the same run at fp32
  kernel   compiled fp32 histogram / node-stats kernels == XLA scatter,
           bitwise, on dyadic gradients
  predict  fused ndarray predict == two-step == the margin the scan
           carried == reloaded model == a libsvm round trip, bitwise
  serve    the saved model behind ``run_server`` (port 0, warm-up on):
           HTTP /predict at three bucket sizes == ``Booster.predict``
           bitwise, no compile after warm-up
  mesh     (``--chips N``) the same training ``dsplit=row`` over N chips
  kernels  (``--kernels``) every ``pallas_call`` site compiled by Mosaic
           over the shape matrix of :func:`kernel_cases`

Stage wall times are printed for orientation — smoke, not a benchmark.
The last line of stdout on success is the pass line::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU the script exits 2 before any training and prints no
result.  ``--rehearse-cpu ROWS`` is the only way to run it elsewhere: a
tiny-size rehearsal of the control flow (Pallas interpreted), marked on
every line, that can never print the pass line.

It sets no ``JAX_PLATFORMS``.  The server it starts is a thread it
drains before exiting; the only process it can start is the ``make``
of the native IO library (native.py, first use), which it waits for.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import functools
import json
import os
import sys
import tempfile
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

FULL_ROWS, HELD_ROWS, ROUNDS = 1_000_000, 100_000, 100
PARAMS = {"objective": "binary:logistic", "max_depth": 6, "eta": 0.1,
          "max_bin": 64, "eval_metric": "auc", "silent": 1}
AUC_BAND = 0.002

_TAG = "[smoke]"
_EVENTS: collections.Counter = collections.Counter()


def say(msg: str) -> None:
    print(f"{_TAG} {msg}", flush=True)


@contextlib.contextmanager
def stage(name: str):
    say(f"{name}: start")
    t0 = time.perf_counter()
    yield
    say(f"{name}: PASSED in {time.perf_counter() - t0:.1f} s wall "
        "(smoke, not a benchmark)")


def check(cond, what: str) -> None:
    """A stage assertion that survives ``python -O``."""
    if not cond:
        raise SystemExit(f"{_TAG} FAILED: {what}")


@contextlib.contextmanager
def env(name: str, value: str):
    """Set one of the repo's own A/B env seams for the enclosed call."""
    old = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if old is None:
            del os.environ[name]
        else:
            os.environ[name] = old


def _compiles() -> int:
    return sum(v for k, v in _EVENTS.items() if "backend_compile" in k)


# ------------------------------------------------------------------ device
def stage_device(args):
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}, {len(devs)} "
              "device(s)).  Nothing was run.  (--rehearse-cpu ROWS "
              "rehearses the control flow off-chip; it cannot pass.)",
              file=sys.stderr)
        sys.exit(2)
    check(len(devs) >= args.chips,
          f"--chips {args.chips} but JAX found {len(devs)} device(s)")
    if not args.rehearse_cpu:
        check(all(d.platform == "tpu" for d in devs[:args.chips]),
              "a non-TPU device among the first --chips devices")

    jax.monitoring.register_event_listener(
        lambda name, **kw: _EVENTS.update([name]))
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: _EVENTS.update([name]))
    from xgboost_tpu.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()

    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # not installed here (CPU rehearsal)
        libtpu = "not installed"
    stats = dev.memory_stats() or {}
    say(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devs)} using={args.chips}")
    say(f"device: jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    say("device: compile cache "
        + ("off (XGBTPU_NO_JITCACHE)" if cache_dir is None else
           f"dir={cache_dir} (" + (
               "from JAX_COMPILATION_CACHE_DIR, no directory set in code"
               if os.environ.get("JAX_COMPILATION_CACHE_DIR")
               else "set by compile_cache.py") + ")"))
    say(f"device: memory_stats bytes_limit={stats.get('bytes_limit')} "
        "(external.py's 2 GB default is used only when this is None)")

    # block_until_ready must be a barrier: everything that times or
    # orders device work in this repo rests on it alone
    import jax.numpy as jnp

    @jax.jit
    def burn(x):
        return jax.lax.fori_loop(
            0, 200, lambda i, a: jnp.tanh(a @ a) * 0.5 + 0.1, x)

    n = 256 if args.rehearse_cpu else 4096
    x = jnp.full((n, n), 0.01, jnp.float32)
    np.asarray(burn(x)[:1, :1])         # compile + warm both programs
    t0 = time.perf_counter()
    y = burn(x)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(y)
    t_block = time.perf_counter() - t0
    np.asarray(y[:1, :1])                               # host pull
    t_pull = time.perf_counter() - t0 - t_block
    say(f"device: barrier — dispatch returned after "
        f"{t_dispatch * 1e3:.2f} ms, block_until_ready after "
        f"{t_block * 1e3:.2f} ms, a one-element pull after it took "
        f"{t_pull * 1e3:.2f} ms more")
    check(t_pull < 0.05 * t_block + 0.005,
          "work was still running after block_until_ready returned")
    return dev, devs, cache_dir


# ------------------------------------------------------------------- train
def make_higgs_like(n, f=28, seed=42):
    """Deterministic Higgs-like binary task: kinematic-ish features with a
    nonlinear decision surface and ~30% bayes noise."""
    rng = np.random.RandomState(seed)
    X = np.empty((n, f), dtype=np.float32)
    # mix of exponential (pT-like), gaussian (eta-like) and uniform features
    X[:, : f // 3] = rng.exponential(1.0, (n, f // 3))
    X[:, f // 3: 2 * f // 3] = rng.randn(n, f - 2 * (f // 3) + f // 3)[:, : f // 3]
    X[:, 2 * (f // 3):] = rng.rand(n, f - 2 * (f // 3))
    score = (np.sin(3 * X[:, 0]) + X[:, 1] * X[:, 2] - 0.5 * X[:, 3] ** 2
             + 2.0 * (X[:, 4] > 1.0) + 0.8 * rng.randn(n))
    y = (score > np.median(score)).astype(np.float32)
    return X, y


def _data(rows: int, held: int):
    X, y = make_higgs_like(rows + held)
    return X[:rows], y[:rows], X[rows:], y[rows:]


def _train(xgb, params, X, y, Xh, yh, rounds, k=None):
    """One ``update_many`` run the way cli.py drives it.  Returns
    (booster, dtrain, dheld, last held-out AUC, segment size K)."""
    dtrain = xgb.DMatrix(X, label=y)
    dheld = xgb.DMatrix(Xh, label=yh)
    bst = xgb.Booster(params, cache=[dtrain, dheld])
    lines, plan = [], []
    bst.update_many(dtrain, 0, rounds,
                    evals=[(dheld, "eval"), (dtrain, "train")],
                    eval_callback=lambda i, msg: lines.append(msg),
                    plan_callback=plan.append, rounds_per_dispatch=k)
    check(len(lines) == rounds, f"{len(lines)} eval lines, not {rounds}")
    from xgboost_tpu.learner import _parse_eval
    return bst, dtrain, dheld, _parse_eval(lines[-1])["eval-auc"], plan[0]


def _counters():
    from xgboost_tpu.obs import training_metrics
    from xgboost_tpu.obs.metrics import swallowed_errors
    return (sum(training_metrics().fused_fallback.values().values()),
            dict(swallowed_errors().values()))


def stage_train(args, xgb, data):
    from xgboost_tpu.ops.histogram import hist_backend
    X, y, Xh, yh = data
    rounds = args.rounds
    # auto-K (rounds_per_dispatch=-1) at full size; the rehearsal pins
    # K so that it too crosses segment boundaries
    k = 4 if args.rehearse_cpu else None
    chosen = hist_backend(PARAMS.get("hist_precision", "auto"))
    say(f"train: histogram backend = {chosen.impl}, "
        f"{'INTERPRETED' if chosen.interpret else 'compiled'}")
    if not args.rehearse_cpu:
        check(chosen == ("pallas_int8", False),
              f"expected compiled pallas_int8 on the chip, got {chosen}")
    fb0, sw0 = _counters()
    c0 = _compiles()
    t0 = time.perf_counter()
    bst, dtrain, dheld, auc, seg_k = _train(xgb, PARAMS, X, y, Xh, yh,
                                            rounds, k)
    say(f"train: {rounds} rounds at {X.shape[0]} x {X.shape[1]} in "
        f"segments of {seg_k} ({-(-rounds // max(seg_k, 1))} dispatches), "
        f"{time.perf_counter() - t0:.1f} s wall incl. "
        f"{_compiles() - c0} compilations; held-out auc={auc:.6f}")
    check(seg_k > 0 and -(-rounds // seg_k) >= 3,
          f"needs >= 3 fused segments, got K={seg_k}")
    entry = bst._cache[id(dtrain)]
    check(getattr(entry, "binned_t", None) is not None
          or args.rehearse_cpu,
          "the resident u8 histogram operand was not built")
    check(bool(np.isfinite(np.asarray(entry.margin)).all()),
          "non-finite training margins")
    check(bst.gbtree.num_trees == rounds, "tree count != rounds")

    t0 = time.perf_counter()
    _, _, _, auc32, _ = _train(
        xgb, dict(PARAMS, hist_precision="fp32"), X, y, Xh, yh, rounds, k)
    say(f"train: same run at hist_precision=fp32 — auc={auc32:.6f} "
        f"(|diff|={abs(auc - auc32):.6f}, band {AUC_BAND}), "
        f"{time.perf_counter() - t0:.1f} s wall")
    check(abs(auc - auc32) <= AUC_BAND, "int8 AUC left the fp32 band")
    fb1, sw1 = _counters()
    say(f"train: fused_fallback_total={fb1 - fb0} swallowed_errors="
        f"{ {k: v - sw0.get(k, 0) for k, v in sw1.items() if v - sw0.get(k, 0)} }")
    check(fb1 == fb0, "training fell off the fused path")
    check(sw1 == sw0, f"swallowed errors were counted: {sw1}")
    return bst, dtrain, dheld, auc


# ------------------------------------------------------------ kernel truth
def _dyadic_case(N, F, B, M, seed, frac_inactive):
    """Gradients are multiples of 1/256: their f32 sums are exact in any
    order, so kernel-vs-scatter equality is bitwise, not approximate
    (tests/test_pallas_hist.py's construction)."""
    rng = np.random.RandomState(seed)
    binned = rng.randint(0, B, (N, F)).astype(np.uint8 if B <= 256
                                              else np.uint16)
    gh = (rng.randint(-512, 512, (N, 2)) / 256.0).astype(np.float32)
    pos = rng.randint(0, M, N).astype(np.int32)
    pos[rng.rand(N) < frac_inactive] = -1
    return binned, gh, pos


def stage_kernel_truth(args):
    import jax.numpy as jnp
    from xgboost_tpu.ops.histogram import build_level_histogram, node_stats
    from xgboost_tpu.ops.pallas_hist import (build_level_histogram_pallas,
                                             node_stats_pallas)
    interp = bool(args.rehearse_cpu)
    # the XLA scatter reference serializes on the TPU (about a minute
    # at 200k rows in the first chip run): 32 row tiles are enough
    big = 4096 if args.rehearse_cpu else 65_536
    for name, (N, F, B, M, inact) in {
            "bench-like": (big, 28, 64, 64, 0.0),
            "pos=-1 rows": (5000, 13, 32, 8, 0.2)}.items():
        binned, gh, pos = map(jnp.asarray,
                              _dyadic_case(N, F, B, M, 7, inact))
        with env("XGBTPU_HIST", "scatter"):
            want = np.asarray(build_level_histogram(binned, gh, pos, M, B))
            want_ns = np.asarray(node_stats(gh, pos, M))
        got = np.asarray(build_level_histogram_pallas(
            binned, gh, pos, M, B, precision="fp32", interpret=interp))
        check(np.array_equal(got, want),
              f"fp32 kernel != XLA scatter ({name}, {N}x{F} B={B} M={M})")
        got_ns = np.asarray(node_stats_pallas(gh, pos, M, interpret=interp))
        check(np.array_equal(got_ns, want_ns),
              f"node_stats kernel != XLA scatter ({name})")
        say(f"kernel: {name} {N}x{F} B={B} M={M}: histogram and "
            "node-stats kernels == XLA scatter, bitwise")


# ----------------------------------------------------------------- predict
def stage_predict(args, xgb, bst, dheld, Xh, yh, auc_eval, workdir):
    from xgboost_tpu import metrics, native
    Xh = np.ascontiguousarray(Xh)
    p_fused = bst.predict(Xh)                   # ndarray: fused path
    check(p_fused.shape == (Xh.shape[0],) and p_fused.dtype == np.float32
          and bool(np.isfinite(p_fused).all()), "bad fused predictions")
    with env("XGBTPU_PREDICT_FUSED", "0"):
        p_two = bst.predict(xgb.DMatrix(Xh))    # quantize, then traverse
    check(np.array_equal(p_fused, p_two), "fused != two-step predict")
    p_carry = bst.predict(dheld)                # margin the scan carried
    check(np.array_equal(p_fused, p_carry),
          "one-off predict != the eval margin carried through training")
    auc = metrics.auc(p_fused, yh, np.ones_like(yh))
    check(abs(auc - auc_eval) < 1e-6,
          f"predict auc {auc} != last eval line {auc_eval}")
    path = os.path.join(workdir, "smoke.model")
    bst.save_model(path)
    loaded = xgb.Booster(model_file=path)
    check(np.array_equal(loaded.predict(Xh), p_fused),
          "reloaded model predicts differently")
    # libsvm text round trip (%.9g is exact for f32) through DMatrix(path)
    n = 1000
    svm = os.path.join(workdir, "held.libsvm")
    with open(svm, "w") as f:
        for row, lab in zip(Xh[:n], yh[:n]):
            f.write(f"{int(lab)} " + " ".join(
                f"{j}:{v:.9g}" for j, v in enumerate(row)) + "\n")
    p_svm = loaded.predict(xgb.DMatrix(svm, silent=True))
    check(np.array_equal(p_svm, p_fused[:n]),
          "libsvm round trip predicts differently")
    say(f"predict: {Xh.shape[0]} held-out rows — fused == two-step == "
        f"scan-carried == reloaded, bitwise; libsvm parser that ran: "
        f"{'native (built from the present sources)' if native.available() else 'python'}; "
        f"tree chunk={bst.gbtree.pred_chunk}; auc={auc:.6f}")
    return path, loaded


# ------------------------------------------------------------------- serve
def _metric(text: str, name: str) -> float:
    for line in text.splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            return float(line.rsplit(" ", 1)[1])
    raise SystemExit(f"{_TAG} FAILED: /metrics has no {name}")


def stage_serve(args, model_path, loaded, Xh):
    from xgboost_tpu.serving import run_server
    t0 = time.perf_counter()
    srv = run_server(model_path, port=0, min_bucket=1, max_bucket=512,
                     poll_sec=0, warmup=True, quiet=True, block=False)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        say(f"serve: up on port {srv.port}, buckets "
            f"{srv.registry.engine.buckets} warmed in "
            f"{time.perf_counter() - t0:.1f} s wall")

        def metrics_text():
            return urllib.request.urlopen(base + "/metrics").read().decode()
        compiled = _metric(metrics_text(), "xgbtpu_serving_compiles_total")
        c0 = _compiles()
        start = 0
        for n in (1, 7, 300):
            rows = Xh[start:start + n]
            start += n
            body = "\n".join(",".join(f"{v:.9g}" for v in r)
                             for r in rows).encode()
            resp = json.load(urllib.request.urlopen(urllib.request.Request(
                base + "/predict", data=body, method="POST")))
            got = np.asarray(resp["predictions"], np.float32)
            check(resp["rows"] == n and np.array_equal(
                got, loaded.predict(rows)),
                f"served {n}-row answer != Booster.predict")
        # Booster.predict above compiles its own one-off programs;
        # the server's count is its /metrics counter
        after = _metric(metrics_text(), "xgbtpu_serving_compiles_total")
        check(after == compiled,
              f"server compiled after warm-up ({compiled} -> {after})")
        say(f"serve: 1, 7 and 300 rows over HTTP == Booster.predict, "
            f"bitwise; xgbtpu_serving_compiles_total={int(after)} "
            f"before and after ({_compiles() - c0} process-wide "
            "compilations meanwhile were Booster.predict's own)")
    finally:
        srv.drain(grace=10.0)


# -------------------------------------------------------------------- mesh
def stage_mesh(args, xgb, data, auc_one):
    from xgboost_tpu.parallel.mesh import data_parallel_mesh, set_mesh
    X, y, Xh, yh = data
    n = args.chips
    k = 4 if args.rehearse_cpu else None
    set_mesh(data_parallel_mesh(n))
    try:
        fb0, sw0 = _counters()
        bst, dtrain, _, auc, seg_k = _train(
            xgb, dict(PARAMS, dsplit="row"), X, y, Xh, yh, args.rounds, k)
        entry = bst._cache[id(dtrain)]
        where = {s.device for s in entry.binned.addressable_shards}
        say(f"mesh: dsplit=row over {bst._mesh.size} devices, binned "
            f"sharding {entry.binned.sharding}, shards on "
            f"{sorted(str(d) for d in where)}; segments of {seg_k}; "
            f"held-out auc={auc:.6f} (one chip {auc_one:.6f})")
        check(bst._mesh.size == n and len(where) == n,
              f"rows are not sharded over {n} distinct devices")
        check(args.rehearse_cpu
              or all(d.platform == "tpu" for d in where),
              "a shard sits on a non-TPU device")
        check(abs(auc - auc_one) <= AUC_BAND,
              "mesh AUC left the one-chip band")
        check(_counters() == (fb0, sw0),
              "mesh training fell off the fused path or swallowed an error")

        # exactly-associative histograms: the model must not depend on
        # the device count (tests/test_mesh_fused.py, on real silicon).
        # Small, because this mode is XLA scatter (ROADMAP S2).
        rows = min(131_072, X.shape[0])
        fixed = dict(PARAMS, hist_precision="fixed", dsplit="row")
        raws = {}
        for nd in (n, 1):
            set_mesh(data_parallel_mesh(nd))
            b, *_ = _train(xgb, fixed, X[:rows], y[:rows], Xh, yh,
                           10 if not args.rehearse_cpu else 6, 4)
            raws[nd] = b.save_raw()
        check(raws[n] == raws[1],
              f"hist_precision=fixed: {n}-device model bytes != 1-device")
        say(f"mesh: hist_precision=fixed at {rows} rows — {n}-device "
            f"model bytes == 1-device ({len(raws[1])} bytes)")
    finally:
        set_mesh(None)


# ----------------------------------------------------------- kernel matrix
def _ref_hist(binned, gh, pos, n_node, n_bin):
    """(n_node, F, B, 2) by float64 bincount — exact for dyadic sums."""
    N, F = binned.shape
    rows = np.nonzero(pos >= 0)[0]
    out = np.zeros((n_node, F, n_bin, 2), np.float64)
    for f in range(F):
        idx = pos[rows].astype(np.int64) * n_bin + binned[rows, f]
        for c in range(2):
            out[:, f, :, c] = np.bincount(
                idx, weights=gh[rows, c].astype(np.float64),
                minlength=n_node * n_bin).reshape(n_node, n_bin)
    return out.astype(np.float32)


def _expect(gh, precision):
    """The gradients the kernel really sums, by precision mode
    (pallas_hist.quantize_gh's rounding for int8)."""
    if precision == "bf16":
        import jax.numpy as jnp
        return np.asarray(jnp.asarray(gh).astype(jnp.bfloat16)
                          .astype(jnp.float32))
    if precision == "int8":
        scale = np.maximum(np.abs(gh).max(axis=0), 1e-30)
        q = np.clip(np.round(gh / scale * 127.0), -127, 127)
        return (q * (scale / 127.0)).astype(np.float32)
    return gh


def _close(got, want, precision, what, gh=None):
    if precision == "int8":
        # int32-exact sums of the quantized gradients; a device divide
        # may round a handful of rows to the neighbouring step, so
        # allow a few steps (scale/127), not a share of the sum
        step = float(np.abs(gh).max()) / 127.0
        err = float(np.abs(got.astype(np.float64) - want).max())
        tol = 4 * step + 1e-5 * float(np.abs(want).max())
        check(bool(np.isfinite(got).all()) and err <= tol,
              f"{what}: int8 histogram off by {err:.4g} (tol {tol:.4g})")
    else:
        check(np.array_equal(got, want), f"{what}: != reference, bitwise")


def kernel_cases(n_rows: int = 70_000):
    """Every ``pallas_call`` site of ops/pallas_hist.py at the shapes the
    system can reach: ``(name, build)`` pairs where ``build(interpret)``
    returns ``(fn, args, verify)`` — ``jax.jit(fn)(*args)`` runs the
    kernel and ``verify(out)`` checks it.  tests/test_chip_contract.py
    cross-lowers the same list for TPU on the CPU; ``--kernels`` compiles
    and runs it on the chip."""
    import jax
    import jax.numpy as jnp
    from xgboost_tpu.ops import pallas_hist as ph

    cases = []

    def solo(N, F, B, M, precision, operand, native, chunks=1):
        # chunks > 1: the int8 row chunks a job past 16.7M rows takes,
        # forced at this size through the kernel's private rows_per_acc
        r_tile, _, n_pad, _ = ph._tiling(N, F, B)
        rows_per_acc = (None if chunks == 1 else
                        -(-(n_pad // r_tile) // chunks) * r_tile)

        def build(interpret):
            binned, gh, pos = _dyadic_case(N, F, B, M, 3, 0.1)
            bt = None
            if operand == "u8":
                bt = ph.host_transpose_bins(binned, B)
                assert bt is not None, "u8 operand needs f_tile == F"
                bt = jnp.asarray(bt)

            def fn(binned, bt, gh, pos):
                if precision == "int8":
                    gh_in, scale = ph.quantize_gh(gh)
                else:
                    gh_in, scale = gh.astype(jnp.float32), None
                if bt is None:
                    bt = ph.transpose_bins(binned, B)
                return ph._hist_pallas_pre(
                    bt, gh_in, scale, pos, (N, F), M, B, precision,
                    interpret, native=native, rows_per_acc=rows_per_acc)

            def verify(out):
                out = np.asarray(out)
                if native:                      # (F, B, 2, M)
                    out = out.transpose(3, 0, 1, 2)
                _close(out, _ref_hist(binned, _expect(gh, precision),
                                      pos, M, B), precision, name, gh)
            return fn, (jnp.asarray(binned), bt, jnp.asarray(gh),
                        jnp.asarray(pos)), verify
        name = (f"solo N={N} F={F} B={B} M={M} {precision} "
                f"{operand}-operand {'native' if native else 'standard'}"
                + (f" {chunks} row chunks" if chunks > 1 else ""))
        cases.append((name, build))

    # the training path: every level of depth 6, resident u8 operand,
    # kernel-native layout, int8
    for M in (1, 2, 4, 8, 16, 32, 64):
        solo(n_rows, 28, 64, M, "int8", "u8", True)
    # the other precisions / operand / layout (mesh and ensemble paths
    # transpose in-graph to int32 and use the standard layout)
    for M in (1, 8, 64):
        for precision in ("fp32", "bf16", "int8"):
            solo(n_rows, 28, 64, M, precision, "int32", False)
    solo(n_rows, 28, 64, 64, "fp32", "u8", True)
    # depth 8 and 10: node tiles (n_m_tiles 4 and 16)
    for M in (256, 1024):
        solo(n_rows, 28, 64, M, "int8", "u8", False)
        solo(n_rows, 28, 64, M, "fp32", "int32", False)
    # wide F: f_tile=32 < F=93 (u8 path off)
    for precision in ("int8", "fp32"):
        solo(n_rows, 93, 64, 64, precision, "int32", False)
    # fine bins: f_tile=8 < F=28 with the u8 path off
    for precision in ("int8", "fp32", "bf16"):
        solo(n_rows, 28, 256, 64, precision, "int32", False)
    # the folded levels (ops/pallas_hist._fold_of: the bin id's high bits
    # in the lanes a shallow level leaves idle; at 256 bins a one-hot of
    # 32, 64, 64, 128 rows at 1, 8, 16, 32 nodes in int8, of 16 at one
    # node in bf16 and fp32; 64 bins fold by two up to 8 nodes (by 8 at
    # one node in fp32) and stay unfolded from 16 on)
    for M in (1, 8, 16, 32):
        for precision in ("int8", "bf16", "fp32"):
            solo(n_rows, 28, 256, M, precision, "int32",
                 precision == "int8")
    for precision in ("int8", "bf16", "fp32"):
        solo(n_rows, 28, 64, 16, precision, "int32", False)
    # the Airline shape past one int32 accumulator's rows: F=13 in two
    # feature tiles of 8, three row chunks, both layouts
    solo(n_rows, 13, 256, 32, "int8", "int32", True, chunks=3)
    solo(n_rows, 13, 256, 128, "int8", "int32", False, chunks=3)
    # the Epsilon shape: F=2000 in 250 feature tiles of 8 with no padded
    # slot; a folded shallow level, the 32-node level folded by two, the
    # unfolded 64-node tile (native layout) and the 128-node level's two
    # node tiles (standard layout, the relayout); fewer rows, the grid's
    # feature axis is what is new
    wide_rows = max(n_rows // 8, 2500)
    for M in (1, 32, 64):
        solo(wide_rows, 2000, 256, M, "int8", "int32", True)
    solo(wide_rows, 2000, 256, 128, "int8", "int32", False)

    def derived(N, F, B, M, chunks=1):
        # a level of M nodes built whole against the same level built as
        # its M/2 left children and derived from its parent level's raw
        # int32 block (ph._hist_pallas_derived): equal to every bit
        r_tile, _, n_pad, _ = ph._tiling(N, F, B)
        rows_per_acc = (None if chunks == 1 else
                        -(-(n_pad // r_tile) // chunks) * r_tile)

        def build(interpret):
            rng = np.random.RandomState(17)
            binned, gh, ppos = _dyadic_case(N, F, B, M // 2, 17, 0.1)
            did = rng.rand(M // 2) < 0.7      # parents that split
            did[0] = True
            pos = np.where((ppos >= 0) & did[np.maximum(ppos, 0)],
                           2 * ppos + rng.randint(0, 2, N), -1
                           ).astype(np.int32)

            def fn(binned, gh, ppos, did, pos):
                gh_in, scale = ph.quantize_gh(gh)
                bt = ph.transpose_bins(binned, B)
                raw = functools.partial(
                    ph._hist_level_raw, bt, gh_in, nf=(N, F), n_bin=B,
                    precision="int8", interpret=interpret,
                    rows_per_acc=rows_per_acc)
                native = M <= 64
                return (raw(pos=pos, n_node=M),
                        ph._hist_pallas_pre(
                            bt, gh_in, scale, pos, (N, F), M, B, "int8",
                            interpret, native=native,
                            rows_per_acc=rows_per_acc),
                        ph._hist_pallas_derived(
                            bt, gh_in, scale, pos,
                            raw(pos=ppos, n_node=M // 2), did, (N, F), M, B,
                            interpret, native=native,
                            rows_per_acc=rows_per_acc))

            def verify(out):
                raw_built, built, (got, raw) = jax.tree_util.tree_map(
                    np.asarray, out)
                check(raw.dtype == np.int32 and np.abs(raw).max() > 0
                      and np.array_equal(raw, raw_built),
                      f"{name}: derived int32 block != built")
                check(np.array_equal(got.view(np.uint32),
                                     built.view(np.uint32)),
                      f"{name}: derived histogram != built, bitwise")
            return fn, (jnp.asarray(binned), jnp.asarray(gh),
                        jnp.asarray(ppos), jnp.asarray(did),
                        jnp.asarray(pos)), verify
        name = (f"solo N={N} F={F} B={B} M={M} int8 derived from "
                f"{M // 2} left children"
                + (f" {chunks} row chunks" if chunks > 1 else ""))
        cases.append((name, build))

    # a level past the first as the grower builds it in int8 (ISSUE 38):
    # the 2-node level from the root, the 32- and 64-node levels (built
    # as the folded 16- and 32-node programs), the 128-node level as ONE
    # 64-node tile; one int32 block and three; and at the Epsilon width
    for M in (2, 32, 64, 128):
        for chunks in (1, 3):
            derived(n_rows, 28, 256, M, chunks)
    for M in (64, 128):
        derived(wide_rows, 2000, 256, M)

    def batched(T, N, F, B, M, precision):
        def build(interpret):
            rng = np.random.RandomState(11)
            binned = rng.randint(0, B, (N, F)).astype(np.uint8)
            gh = (rng.randint(-512, 512, (T, N, 2)) / 256.0
                  ).astype(np.float32)
            pos = rng.randint(0, M, (T, N)).astype(np.int32)
            pos[rng.rand(T, N) < 0.1] = -1

            def fn(binned, gh, pos):
                return ph.build_level_histogram_pallas_batched(
                    binned, gh, pos, M, B, precision=precision,
                    interpret=interpret)

            def verify(out):
                out = np.asarray(out)
                for t in range(T):
                    _close(out[t],
                           _ref_hist(binned, _expect(gh[t], precision),
                                     pos[t], M, B),
                           precision, f"{name} tree {t}", gh[t])
            return fn, (jnp.asarray(binned), jnp.asarray(gh),
                        jnp.asarray(pos)), verify
        name = f"batched T={T} N={N} F={F} B={B} M={M} {precision}"
        cases.append((name, build))

    for M in (1, 64):
        batched(6, n_rows, 28, 64, M, "int8")       # 6-class softmax
        batched(9, n_rows, 93, 64, M, "int8")       # otto: 9 x 93
    batched(6, n_rows, 28, 64, 64, "fp32")
    batched(9, n_rows, 93, 64, 64, "fp32")
    # 256 bins: f_tile=8 < F=28, the last tile's 4 padded slots guarded
    batched(6, n_rows, 28, 256, 64, "int8")

    def lanes(L, N, F, B, M, precision):
        def build(interpret):
            rng = np.random.RandomState(13)
            binned = rng.randint(0, B, (L, N, F)).astype(np.uint8)
            gh = (rng.randint(-512, 512, (L, N, 2)) / 256.0
                  ).astype(np.float32)
            pos = rng.randint(0, M, (L, N)).astype(np.int32)
            pos[rng.rand(L, N) < 0.1] = -1

            def fn(binned, gh, pos):
                return ph.build_level_histogram_pallas_lanes(
                    binned, gh, pos, M, B, precision=precision,
                    interpret=interpret)

            def verify(out):
                out = np.asarray(out)
                for lane in range(L):
                    _close(out[lane],
                           _ref_hist(binned[lane],
                                     _expect(gh[lane], precision),
                                     pos[lane], M, B), precision,
                           f"{name} lane {lane}", gh[lane])
            return fn, (jnp.asarray(binned), jnp.asarray(gh),
                        jnp.asarray(pos)), verify
        name = f"lanes L={L} N={N} F={F} B={B} M={M} {precision}"
        cases.append((name, build))

    # 64-row tenants: per-lane n_pad = 2048, 97% padding
    for L in (2, 8, 64):
        for M in (1, 4):
            lanes(L, 64, 4, 32, M, "int8")
        lanes(L, 64, 4, 32, 4, "fp32")
    # 256 bins: F=13 in two feature tiles of 8, 3 padded slots guarded;
    # the lanes kernel folds as the rows kernel does
    for B in (256, 64):
        for M in (1, 8, 16):
            for precision in ("int8", "bf16", "fp32"):
                lanes(2, 3000, 13, B, M, precision)

    def nstats(N, M):
        def build(interpret):
            _, gh, pos = _dyadic_case(N, 1, 4, M, 5, 0.1)

            def fn(gh, pos):
                return ph.node_stats_pallas(gh, pos, M,
                                            interpret=interpret)

            def verify(out):
                want = _ref_hist(np.zeros((N, 1), np.uint8), gh, pos,
                                 M, 1)[:, 0, 0, :]
                check(np.array_equal(np.asarray(out), want),
                      f"{name}: != reference, bitwise")
            return fn, (jnp.asarray(gh), jnp.asarray(pos)), verify
        name = f"node-stats N={N} M={M}"
        cases.append((name, build))

    nstats(n_rows, 1)
    nstats(n_rows, 64)
    return cases


def stage_kernels(args):
    """Mosaic compiles every ``pallas_call`` site at every shape of
    :func:`kernel_cases`, and what runs is right.  All cases are tried;
    any failure fails the stage."""
    import jax
    interp = bool(args.rehearse_cpu)

    def run(build):
        fn, fargs, verify = build(interp)
        t0 = time.perf_counter()
        exe = jax.jit(fn).lower(*fargs).compile()
        t_c = time.perf_counter() - t0
        verify(jax.block_until_ready(exe(*fargs)))
        return t_c

    failed = []
    cases = kernel_cases(3000 if interp else 70_000)
    for name, build in cases:
        try:
            say(f"kernels: ok   {name} (compiled in {run(build):.1f} s)")
        except (Exception, SystemExit) as e:
            msg = f"{type(e).__name__}: {e}".strip()
            failed.append(name)
            say(f"kernels: FAIL {name}: {msg[:2000]}")
    say(f"kernels: {len(cases) - len(failed)} of {len(cases)} cases "
        "compiled and verified")
    # XGBTPU_HIST_RTILE: 2048 is the default every case above used;
    # >= 8192 was once recorded as refused by Mosaic — say what holds
    # now (informational), on the deepest training-path case
    name, build = cases[6]
    for rt in ("4096", "8192"):
        with env("XGBTPU_HIST_RTILE", rt):
            try:
                run(build)
                say(f"kernels: note XGBTPU_HIST_RTILE={rt} compiles "
                    f"and verifies ({name})")
            except (Exception, SystemExit) as e:
                say(f"kernels: note XGBTPU_HIST_RTILE={rt} refused: "
                    f"{type(e).__name__}: {str(e)[:300]}")
    check(not failed, f"{len(failed)} kernel case(s) failed: "
          + "; ".join(failed))


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    global _TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="also train dsplit=row over this many chips "
                         "(fails if fewer)")
    ap.add_argument("--kernels", action="store_true",
                    help="also compile + verify the full kernel shape "
                         "matrix (kernel_cases)")
    ap.add_argument("--rehearse-cpu", type=int, default=0, metavar="ROWS",
                    help="rehearse the control flow off-chip at ROWS "
                         "training rows; can never pass")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        _TAG = "[REHEARSAL off-chip, not a chip run]"
    args.rounds = 12 if args.rehearse_cpu else ROUNDS
    t_all = time.perf_counter()

    with stage("device"):
        dev, devs, cache_dir = stage_device(args)
    import xgboost_tpu as xgb
    rows = args.rehearse_cpu or FULL_ROWS
    held = min(HELD_ROWS, max(rows // 10, 2000))
    data = _data(rows, held)
    with tempfile.TemporaryDirectory(prefix="xgbtpu_smoke_") as workdir:
        with stage("train"):
            bst, dtrain, dheld, auc = stage_train(args, xgb, data)
        with stage("kernel"):
            stage_kernel_truth(args)
        with stage("predict"):
            model_path, loaded = stage_predict(
                args, xgb, bst, dheld, data[2], data[3], auc, workdir)
        with stage("serve"):
            stage_serve(args, model_path, loaded, data[2])
        if args.chips > 1:
            with stage("mesh"):
                stage_mesh(args, xgb, data, auc)
        if args.kernels:
            with stage("kernels"):
                stage_kernels(args)
    say(f"all stages passed in {time.perf_counter() - t_all:.1f} s wall "
        f"(smoke, not a benchmark); compilations={_compiles()} "
        f"persistent-cache hits="
        f"{_EVENTS['/jax/compilation_cache/cache_hits']} misses="
        f"{_EVENTS['/jax/compilation_cache/cache_misses']} "
        f"(cache dir {cache_dir})")
    if args.rehearse_cpu:
        say("rehearsal complete — this is NOT a pass")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
