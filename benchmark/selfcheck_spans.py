"""Self-check of the three span and scope readers (`readers/program_span.py`,
`trace_span_idle.py`, `trace_scope_time.py`) against a second small
trace recorded on the chip from the program as PR 28 left it, kept in
`testdata/` with its op -> scope map beside it: two fused segments of
one boosting round each, 200,000 x 28 at 256 bins and depth 6 (the
in-graph int32 operand, as in the benchmark's cell), 20,000 rows held
out and watched by logloss, on a TPU v5 lite.  The expected numbers were
read from the trace by hand: sums over the `XLA Ops` line by instruction
name, and the gaps between them laid against the `train.*` annotations
of the `/host:CPU` plane.  Runs on the CPU and uses no chip:

    python3 benchmark/selfcheck_spans.py

`--record DIR` makes such a trace and map anew (needs the chip):

    chiprun -- python3 benchmark/selfcheck_spans.py --record chiprun_out/spans
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

STEM = "spans_2rounds_200k_x28"
TRACE = os.path.join(HERE, "testdata", f"{STEM}.xplane.pb")
SCOPES = os.path.join(HERE, "testdata", f"{STEM}.scopes.json")
OLD_TRACE = os.path.join(HERE, "testdata", "train_2rounds_200k_x28.xplane.pb")
SCAN = "jit__scan_rounds_impl"
GAPS = ["launch_gap_ms_per_round", "dispatch_gap_ms_per_round",
        "absorb_gap_ms_per_round", "eval_gap_ms_per_round",
        "unspanned_gap_ms_per_round"]
SCOPED = ["operand_ms_per_round", "split_ms_per_round", "route_ms_per_round",
          "eval_dev_ms_per_round"]
EXPECT = {
    # device-idle ns of the window (30,647,999 ns; 14,372,797 idle) under
    # each span, over two rounds, the spans laid 1,694,983 ns earlier:
    # the lesser of the two `train.wait` ends less its scan module's end
    # (1,826,454 and 1,694,983).  Read on a 1 ns grid, busy painted from
    # the `XLA Ops` line and the innermost span over it.
    "launch_gap_ms_per_round": 0.8544085, "dispatch_gap_ms_per_round": 0.7373805,
    "absorb_gap_ms_per_round": 2.445745, "eval_gap_ms_per_round": 2.0514885,
    "unspanned_gap_ms_per_round": 1.097376,
    # non-kernel ops inside the two scan modules, by innermost scope
    "operand_ms_per_round": 0.020043, "split_ms_per_round": 0.2573695,
    "route_ms_per_round": 0.327141, "eval_dev_ms_per_round": 0.030941,
    "hist_kernels_ms_per_round": 7.266039, "kernel_names": ["hist_level_rows"],
}
# the rest under none of the four, by enclosing span: "" holds the 1.69 ms
# of the window's end that the shift lays bare (two rounds: 0.85 a round;
# a sixtieth of that in a window of the benchmark)
EXPECT_UNSPANNED = {"train.segment": 0.178425, "train.dispatch": 0.0521595,
                    "": 0.8667915}
EXPECT_OFFSET_MS = 1.694983
# the scopes that are no metric of their own, in the notes
EXPECT_NOTED = {"": 0.158469, "grow.hist": 0.032009, "round.gradient": 0.021104,
                "round.margin": 0.018379}


def close(a, b, rel=1e-5):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-12)


def through_readers(trace_path, scope_map, rounds=2):
    """({metric: value}, notes) of every trace metric, old and new,
    through `run.read_metrics`, as `rounds` rounds in the window."""
    import run
    import tracered
    ctx = {"trace": tracered.load(trace_path), "scope_map": scope_map,
           "counts": {"rounds_in_window": rounds}, "spans": {}, "notes": {},
           "memory": {}, "shape": {}, "peaks": None}
    names = GAPS + SCOPED + ["host_gap_ms_per_round", "xla_ms_per_round",
                             "hist_ms_per_round"]
    bench = {"per_layer": [{"name": n, "unit": ""} for n in names]}
    got = run.read_metrics(bench, "per_layer", "", ctx)
    return {k: v["value"] for k, v in got.items()}, ctx["notes"]


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tracered
    with open(SCOPES) as f:
        scope_map = json.load(f)
    got, notes = through_readers(TRACE, scope_map)
    tr = tracered.load(TRACE)
    kernels = {tracered.short_name(o.name).split(".")[0].lstrip("%")
               for o in tr.ops[0] if o.kernel}
    got["kernel_names"] = sorted(kernels)
    got["hist_kernels_ms_per_round"] = notes["kernel_ms_by_scope"]["grow.hist"]
    bad = 0
    for k, want in EXPECT.items():
        ok = (got.get(k) == want if isinstance(want, list)
              else k in got and close(got[k], want))
        print(f"{'ok  ' if ok else 'FAIL'} {k}: {got.get(k)!r} "
              f"(expected {want!r})")
        bad += not ok
    for k, want in EXPECT_NOTED.items():
        ok = close(notes["xla_ms_by_scope"].get(k, -1.0), want)
        print(f"{'ok  ' if ok else 'FAIL'} noted scope {k!r}: "
              f"{notes['xla_ms_by_scope'].get(k)!r} (expected {want!r})")
        bad += not ok
    for k, want in EXPECT_UNSPANNED.items():
        have = notes["unspanned_gap_ms_by_span"].get(k, -1.0)
        ok = close(have, want)
        print(f"{'ok  ' if ok else 'FAIL'} unspanned under {k!r}: {have!r} "
              f"(expected {want!r})")
        bad += not ok
    ok = close(notes["span_clock_offset_ms"], EXPECT_OFFSET_MS)
    print(f"{'ok  ' if ok else 'FAIL'} span_clock_offset_ms: "
          f"{notes['span_clock_offset_ms']!r} (expected {EXPECT_OFFSET_MS!r})")
    bad += not ok
    # the five gaps are the idle time; the scopes are the non-kernel time;
    # the kernels under grow.hist are the kernels
    sums = {
        "gaps = host_gap_ms_per_round":
            (sum(got[k] for k in GAPS), got["host_gap_ms_per_round"]),
        "scopes = xla_ms_per_round":
            (sum(notes["xla_ms_by_scope"].values()), got["xla_ms_per_round"]),
        "kernels under grow.hist = hist_ms_per_round":
            (got["hist_kernels_ms_per_round"], got["hist_ms_per_round"]),
    }
    for what, (a, b) in sums.items():
        ok = close(a, b, 1e-9)
        print(f"{'ok  ' if ok else 'FAIL'} {what}: {a!r} against {b!r}")
        bad += not ok
    # a program without spans or scopes gives None, never 0
    old, _ = through_readers(OLD_TRACE, {})
    absent = [k for k in GAPS + SCOPED if k in old]
    print(f"{'FAIL' if absent else 'ok  '} the trace of the program before "
          f"its spans reports none of the new metrics: {absent}")
    bad += bool(absent)
    print("span readers:", "FAILED" if bad else "ok")
    return 1 if bad else 0


def record(out_dir: str) -> int:
    """Two fused one-round segments under the profiler, on the chip."""
    import shutil
    import tempfile

    import jax

    import tracered
    import xgboost_tpu as xgb
    from datagen import synth_tabular
    from readers import trace_scope_time
    if jax.devices()[0].platform != "tpu":
        print("recording needs the chip", file=sys.stderr)
        return 2
    data = synth_tabular.generate(28, 200_000, 20_000, 28,
                                  task="classification")
    cfg = json.load(open(os.path.join(
        HERE, "configs", "higgs-shape-synth-d6-b256.json")))
    dtrain = xgb.DMatrix(data["X_train"], label=data["y_train"])
    dheld = xgb.DMatrix(data["X_held"], label=data["y_held"])
    bst = xgb.Booster(dict(cfg["params"], eval_metric="logloss"))
    evals = [(dheld, "test")]
    bst.update_many(dtrain, 0, 2, evals=evals, rounds_per_dispatch=1)
    trace_dir = tempfile.mkdtemp(prefix="spans_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # keeps the file small
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_window"):
        bst.update_many(dtrain, 2, 2, evals=evals, rounds_per_dispatch=1)
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    trace_path = os.path.join(out_dir, f"{STEM}.xplane.pb")
    shutil.copy(tracered.find_xplane(trace_dir), trace_path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    scopes = {k: trace_scope_time.innermost(v)
              for k, v in trace_scope_time.scope_map(SCAN).items()}
    scopes = {k: v for k, v in scopes.items() if v}
    with open(os.path.join(out_dir, f"{STEM}.scopes.json"), "w") as f:
        json.dump(scopes, f, indent=0, sort_keys=True)
    got, notes = through_readers(trace_path, scopes)
    print(json.dumps({"metrics": got, "notes": notes,
                      "bytes": os.path.getsize(trace_path),
                      "scoped_instructions": len(scopes)}))
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--record":
        sys.exit(record(sys.argv[2]))
    sys.exit(main())
