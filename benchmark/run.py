"""One cell of the benchmark, once, in a fresh process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its metrics and their readers are found by
name: `BENCHMARK.json` -> `workloads/<cell>.json` ->
`configs/<config>.json` -> `datagen/<generator>.py`, and
`metrics/<metric>.json` -> `readers/<reader>.py`.  This file holds no
cell's name.  It needs a TPU whose kind is in `peaks.json`; without one
it exits 2 and prints no result.

Phases (seconds of each go to stderr):
  set-up   process start, data from --seed, ingest (`DMatrix(...)` to the
           booster's device entries, by a zero-round `update_many`), the
           first `update_many` call (compiles, rounds 0..k-1, untimed)
  window   whole `update_many` calls of the same booster until --seconds
           have passed; with --trace 1 under the JAX profiler
  after    device memory read, model bytes and eval lines taken, the
           program's state freed, then the plain reference and the
           comparison that decides `correct` (compare.py); not in setup_s

The last line of stdout is the result; the numbers compared, each
beside its limit, are the last lines of stderr and the last key of the
result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(name: str, bench_dir: str = HERE, root: str = ROOT):
    """(BENCHMARK.json, its workload entry, the cell's file, its config)."""
    bench = load_json(root, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"workload {name!r} is not in BENCHMARK.json")
    cell = load_json(bench_dir, "workloads", f"{name}.json")
    cfg = load_json(bench_dir, "configs", f"{entry['config']}.json")
    # the job's own parameters (what the user watches) join the booster's
    cfg["params"] = {**cfg["params"], **cell["job"].get("params", {})}
    return bench, entry, cell, cfg


def device_or_exit(chips: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        say(f"no accelerator: {e}")
        raise SystemExit(2)
    import required
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        say(f"needs {chips} TPU chip(s); found {len(devs)} x {d.platform}/"
            f"{d.device_kind}")
        raise SystemExit(2)
    try:
        peak = required.peaks(d.device_kind)
    except KeyError as e:
        say(str(e))
        raise SystemExit(2)
    return devs[:chips], peak


class Compiles:
    """jax.monitoring's backend-compile events, with the time of each."""

    def __init__(self):
        from jax import monitoring
        self.events = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == _COMPILE_EVENT:
            self.events.append((time.perf_counter(), float(secs)))

    def seconds(self) -> float:
        return sum(s for _, s in self.events)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)


def largest_program_temp():
    """(bytes, module name) of the compiled program with the most scratch."""
    import jax.extend
    best = (0, "")
    for ex in jax.extend.backend.get_backend().live_executables():
        try:
            temp = int(ex.get_compiled_memory_stats().temp_size_in_bytes)
            name = ex.hlo_modules()[0].name
        except Exception:                      # an executable with no stats
            continue
        if temp > best[0]:
            best = (temp, name)
    return best


def training_bins(bst, dtrain):
    """The bin ids of the booster's training entry.  Where this version
    of the program keeps them elsewhere: None, and `bins_mismatch` then
    fails its limit (compare.py), so the check cannot be lost unseen."""
    import numpy as np
    try:
        return np.asarray(bst._cache[id(dtrain)].binned)
    except (AttributeError, KeyError, TypeError) as e:
        say(f"bins_mismatch cannot be taken: the booster's entry is not at "
            f"_cache[id(dtrain)].binned ({e!r})")
        return None


def program_outputs(bst, dtrain, eval_lines: dict, n_features: int):
    import numpy as np
    import compare
    import reference
    raw = np.load(io.BytesIO(bst.save_raw()))
    trees = reference.Trees(*(np.asarray(raw[f"tree_{k}"]) for k in
                              reference.Trees._fields))
    cuts = [np.asarray(raw["cut_values"][f, :raw["cut_n"][f]])
            for f in range(n_features)]
    evals = {i: float(m.rsplit(":", 1)[1]) for i, m in eval_lines.items()}
    return compare.Outputs(cuts, training_bins(bst, dtrain), evals, trees)


def program_failures() -> int:
    """Fused fallbacks and swallowed errors the program counted."""
    from xgboost_tpu.obs import training_metrics
    from xgboost_tpu.obs.metrics import swallowed_errors
    counted = {"fused_fallback": training_metrics().fused_fallback.values(),
               "swallowed_errors": swallowed_errors().values()}
    n = int(sum(sum(v.values()) for v in counted.values()))
    if n:
        say(f"program counters: {counted}")
    return n


def read_metrics(bench: dict, kind: str, cell_name: str, ctx: dict) -> dict:
    out = {}
    for m in bench[kind]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        spec = load_json(HERE, "metrics", f"{m['name']}.json")
        reader = importlib.import_module(f"readers.{spec['reader']}")
        ctx["metric"] = m["name"]
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(args, *, rehearse: bool = False, bench_dir: str = HERE,
             root: str = ROOT) -> dict:
    """Drive one run and return the result.  `rehearse` skips the look
    for a chip (tests and the CPU rehearsal); nothing else changes."""
    bench, entry, cell, cfg = find_cell(args.workload, bench_dir, root)
    import jax
    if rehearse:
        devs, peak = jax.devices()[:1], None
    else:
        devs, peak = device_or_exit(int(entry["chips"]))
    import xgboost_tpu as xgb
    from xgboost_tpu.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    compiles = Compiles()
    d0 = devs[0]
    say(f"{args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={d0.platform}/{d0.device_kind}x{len(devs)} "
        f"cache={cache_dir}" + (" REHEARSAL, no device metric" if rehearse else ""))

    spans, counts, notes = {}, {}, {}
    # ---- set-up: data
    t = time.perf_counter()
    gen = importlib.import_module(f"datagen.{cfg['generator']}")
    data = gen.generate(args.seed, cfg["n_train"], cfg["n_held"],
                        cfg["features"], **cfg.get("generator_args", {}))
    spans["generate_s"] = time.perf_counter() - t
    # ---- set-up: ingest, through the public constructor and entry
    job = cell["job"]
    watch = job.get("watchlist_name", "test")
    t = time.perf_counter()
    dtrain = xgb.DMatrix(data["X_train"], label=data["y_train"])
    dheld = xgb.DMatrix(data["X_held"], label=data["y_held"])
    bst = xgb.Booster(dict(cfg["params"]))
    evals = [(dheld, watch)]
    bst.update_many(dtrain, 0, 0, evals=evals)      # builds both entries
    jax.block_until_ready([a for a in jax.live_arrays() if not a.is_deleted()])
    spans["ingest_s"] = time.perf_counter() - t
    counts["rows_ingested"] = cfg["n_train"] + cfg["n_held"]
    # ---- set-up: the first call of the object the window will drive
    k = int(job["rounds_per_call"])
    eval_lines = {}
    done = 0

    def call():
        nonlocal done
        bst.update_many(dtrain, done, k, evals=evals,
                        eval_callback=eval_lines.__setitem__)
        done += k
    t = time.perf_counter()
    call()
    spans["first_call_s"] = time.perf_counter() - t
    spans["compile_s"] = compiles.seconds()
    counts["compiles_in_setup"] = len(compiles.events)
    stats0 = d0.memory_stats() or {}
    spans["setup_s"] = time.perf_counter() - _T0
    # ---- the window
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    rounds0 = done
    try:
        with jax.profiler.TraceAnnotation("bench_window"):
            w0 = w1 = time.perf_counter()
            call_s = []
            while w1 - w0 < args.seconds:
                call()
                call_s.append(time.perf_counter() - w1)
                w1 += call_s[-1]
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    spans["window_s"] = w1 - w0
    counts["rounds_in_window"] = done - rounds0
    counts["compiles_in_window"] = compiles.between(w0, w1)
    # ---- after: memory, before anything else touches the device
    stats = d0.memory_stats() or {}
    temp, temp_of = largest_program_temp()
    live = int(stats.get("bytes_in_use", 0))
    alloc_peak = int(stats.get("peak_bytes_in_use", 0))
    reserved = int(stats.get("peak_bytes_reserved", 0))
    # this runtime keeps a running program's scratch out of
    # peak_bytes_in_use and under bytes_reserved (PERF.md section 4):
    # the peak is the allocator's plus that scratch, which is
    # the largest program's temp where the runtime does not say
    memory = {"allocator_peak_bytes": alloc_peak, "live_bytes_in_window": live,
              "live_bytes_before_window": int(stats0.get("bytes_in_use", 0)),
              "peak_bytes_reserved": reserved,
              "largest_program_temp_bytes": temp,
              "largest_program_temp_of": temp_of,
              "bytes_limit": int(stats.get("bytes_limit", 0)),
              "memory_peak_bytes": alloc_peak + (reserved or temp)}
    say("memory " + json.dumps(memory))
    out = program_outputs(bst, dtrain, eval_lines, cfg["features"])
    failed = program_failures()
    missing = [i for i in range(done) if i not in eval_lines]
    notes.update(last_eval_line=eval_lines.get(done - 1, ""),
                 rounds_per_call=k, call_s=[round(c, 4) for c in call_s],
                 hist_precision=str(getattr(getattr(bst, "param", None),
                                            "hist_precision", "")))
    del bst, dtrain, dheld, evals
    gc.collect()
    # ---- after: the trace, while nothing else runs
    import tracered
    trace = None
    if args.trace:
        t = time.perf_counter()
        try:
            trace = tracered.load(tracered.find_xplane(trace_dir))
        except ValueError as e:
            if not rehearse:
                raise
            say(f"rehearsal: {e}")          # a CPU trace has no device plane
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        spans["trace_read_s"] = time.perf_counter() - t
    # ---- after: the plain reference and the comparison
    import compare
    t = time.perf_counter()
    side = compare.reference_side(data, cfg, say)
    fit = compare.reference_fit(side, data, cfg)
    spans["reference_s"] = time.perf_counter() - t
    t = time.perf_counter()
    compared = compare.compare(out, side, fit, data, cfg, cell["limits"])
    spans["compare_s"] = time.perf_counter() - t
    correct = (compare.is_correct(compared) and not missing and failed == 0
               and counts["compiles_in_window"] == 0)
    notes["n_bin"] = side["n_bin"]

    ctx = {"spans": spans, "counts": counts, "notes": notes, "memory": memory,
           "trace": trace, "peaks": peak,
           "shape": {"N": cfg["n_train"], "F": cfg["features"],
                     "B": side["n_bin"], "depth": cfg["params"]["max_depth"],
                     "n_held": cfg["n_held"]}}
    if rehearse:
        metrics = {}            # a CPU run writes no device metric
    else:
        metrics = read_metrics(
            bench, "per_layer" if args.trace else "end_to_end",
            args.workload, ctx)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs),
              "memory_peak_bytes": memory["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": counts["rounds_in_window"],
              "failed": failed + len(missing), "metrics": metrics,
              "device": device}
    if trace is not None and not rehearse:
        device["busy_s"] = tracered.busy_s(trace)
        device["window_s"] = tracered.window_s(trace)
        result["breakdown"] = {"device_ops": tracered.top_device_ops(trace),
                               "idle_gaps": tracered.idle_gaps(trace)}
    spans["total_s"] = time.perf_counter() - _T0
    result["phases"] = spans
    result["counts"] = counts
    notes["read_not_compared"] = {k: c["value"] for k, c in compared.items()
                                  if c["limit"] is None}
    result["notes"] = notes
    result["compared"] = compare.held(compared)
    say("phases " + json.dumps(spans))
    say("counts " + json.dumps(counts))
    say("notes " + json.dumps(notes))
    say("metrics " + json.dumps(metrics))
    say(f"correct={correct} failed={failed} missing_eval_lines={len(missing)} "
        f"compiles_in_window={counts['compiles_in_window']}; compared "
        "(value <= limit):")
    for line in compare.lines(compared):
        say("  " + line)
    return result


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    result = run_cell(parse(argv))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
