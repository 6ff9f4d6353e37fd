#!/usr/bin/env python
"""Fleet micro-benchmark: aggregate req/s and p99 through the router
at 1 vs 3 replicas, plus shed rate under overload.

Real topology: replica SUBPROCESSES (own interpreters, own jax
runtimes) behind the in-process router, driven by concurrent keep-alive
HTTP clients posting 1-row CSV predicts — the latency-bound
millions-of-users shape.  Writes ``BENCH_fleet.json`` in the
``BENCH_r*.json`` shape::

    JAX_PLATFORMS=cpu python tools/bench_fleet.py

Cells:

- ``direct_1proc``   — clients -> one replica, no router (the
  single-process serving baseline measured over the SAME wire).
- ``router_1`` / ``router_3`` — clients -> router -> fleet.
- ``overload``       — router in-flight budget dropped to force load
  shedding; reports the shed rate and asserts zero NON-shed failures.
- ``catalog_1`` / ``catalog_4`` (``--catalog-only``) — one replica
  serving a 1-entry vs a 4-entry model catalog
  (``task=serve catalog=...``, xgboost_tpu.catalog) over the same
  wire, the 4-entry cell hammered by all four tenants CONCURRENTLY
  with per-tenant req/s and p99.

Note this container is 1-CPU: replica parallelism cannot exceed one
core, so ``router_3`` measures dispatch/retry overhead and shedding
correctness more than parallel speedup — on a multi-core host the
3-replica aggregate scales with cores.  Every cell records the host's
``cpu`` block (``os.cpu_count()`` + the per-process scheduler
affinity) so a reader can tell which regime a committed number was
measured under, and ``--multicore-only`` re-measures the
parallel-speedup cells (``router_3``, ``catalog_1``/``catalog_4``) and
drops the scarce-core caveats when ≥4 effective cores are available —
on a scarce-core host it is a deliberate no-op and the caveats stay.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

# CPU-only harness, pinned — not a default: this process and every
# child it starts inherit the pin.  On a machine whose environment names
# the TPU the parent would otherwise hold the chip that every child
# then wants (one process per chip; ROADMAP S1/R5 bring this to the
# chip one process per device).
os.environ["JAX_PLATFORMS"] = "cpu"
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root: xgboost_tpu
sys.path.insert(0, _HERE)                   # tools/: launch_fleet

import numpy as np  # noqa: E402

from launch_fleet import FleetLauncher, RetryingPredictClient  # noqa: E402

N_TRAIN, N_FEAT, ROUNDS = 20_000, 28, 20
CLIENTS = int(os.environ.get("BENCH_FLEET_CLIENTS", "16"))
REQS = int(os.environ.get("BENCH_FLEET_REQS", "1500"))
# deadline cells: the end-to-end budgets stamped on every request.
# FEASIBLE sits above the loaded p50 (most requests can finish; the
# tail shows the late/rejected split), TIGHT sits below it (the
# overload case the discipline exists for: the win is rejected-early
# ≫ completed-late — the fleet stops paying for answers nobody reads)
DEADLINE_FEASIBLE_MS = float(
    os.environ.get("BENCH_FLEET_DEADLINE_MS", "25"))
DEADLINE_TIGHT_MS = float(
    os.environ.get("BENCH_FLEET_DEADLINE_TIGHT_MS", "12"))
SERVE_ARGS = ["serve_min_bucket=8", "serve_max_bucket=64",
              "serve_max_wait_ms=1.0"]


def _train_model(path: str) -> None:
    import xgboost_tpu as xgb
    rng = np.random.RandomState(0)
    X = rng.rand(N_TRAIN, N_FEAT).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.25 * X[:, 2]
         + 0.1 * rng.randn(N_TRAIN) > 0.65).astype(np.float32)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 6,
                     "eta": 0.3, "silent": 1},
                    xgb.DMatrix(X, label=y), ROUNDS)
    bst.save_model(path)


def _bodies(n: int = 64):
    rng = np.random.RandomState(1)
    return [(",".join(f"{v:.6f}" for v in rng.rand(N_FEAT))).encode()
            for _ in range(n)]


def _cpu_info() -> dict:
    """The compute regime a cell was measured under: logical core
    count plus the per-process scheduler affinity (cgroup/taskset caps
    make these differ — affinity is what the replicas actually get)."""
    info = {"cpu_count": os.cpu_count() or 1}
    if hasattr(os, "sched_getaffinity"):
        aff = sorted(os.sched_getaffinity(0))
        info["affinity"] = aff
        info["effective_cores"] = len(aff)
    else:
        info["effective_cores"] = info["cpu_count"]
    return info


def _effective_cores() -> int:
    return _cpu_info()["effective_cores"]


def hammer(base_url: str, total_reqs: int, clients: int,
           deadline_ms=None, path: str = "/predict"):
    """``clients`` threads, keep-alive connections, 1-row posts
    (retry-once semantics live in launch_fleet.RetryingPredictClient).
    Returns aggregate stats + per-request outcome counts.

    ``deadline_ms`` stamps every request with that ``X-Deadline-Ms``
    budget and splits the outcome accounting into completed-in-budget /
    completed-late / rejected-up-front (504): the deadline cell's
    claim is that under a tight budget, rejected-early ≫
    completed-late — the fleet stops paying for answers nobody reads."""
    bodies = _bodies()
    per_client = total_reqs // clients
    lat: list = []
    counts = {"ok": 0, "shed": 0, "fail": 0,
              "in_budget": 0, "late": 0, "rejected_early": 0}
    headers = ({"X-Deadline-Ms": str(deadline_ms)}
               if deadline_ms is not None else None)
    fail_details: list = []
    lock = threading.Lock()
    barrier = threading.Barrier(clients + 1)

    def client(ci: int):
        conn = RetryingPredictClient(base_url, path=path)
        mine = dict.fromkeys(counts, 0)
        mylat = []
        details = []
        barrier.wait()
        for i in range(per_client):
            t0 = time.perf_counter()
            status, detail = conn.post(bodies[(ci + i) % len(bodies)],
                                       headers=headers)
            wall = time.perf_counter() - t0
            if status == 200:
                mine["ok"] += 1
                mylat.append(wall)
                if deadline_ms is not None:
                    key = ("in_budget" if wall * 1e3 <= deadline_ms
                           else "late")
                    mine[key] += 1
            elif status == 503:
                mine["shed"] += 1
            elif status == 504 and deadline_ms is not None:
                mine["rejected_early"] += 1
            else:
                mine["fail"] += 1
                details.append(detail if status is None
                               else f"status {status}: {detail}")
        conn.close()
        with lock:
            lat.extend(mylat)
            fail_details.extend(details)
            for k in counts:
                counts[k] += mine[k]

    ts = [threading.Thread(target=client, args=(i,))
          for i in range(clients)]
    for t in ts:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    arr = np.asarray(lat) if lat else np.zeros(1)
    done = per_client * clients
    cell = {
        "clients": clients,
        "requests": done,
        "requests_per_sec": round(done / wall, 1),
        "ok_per_sec": round(counts["ok"] / wall, 1),
        "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(arr, 99)) * 1e3, 3),
        "ok": counts["ok"], "shed": counts["shed"],
        "failures": counts["fail"],
        "shed_rate": round(counts["shed"] / max(done, 1), 4),
        "cpu": _cpu_info(),
    }
    if deadline_ms is not None:
        cell.update({
            "deadline_ms": deadline_ms,
            "completed_in_budget": counts["in_budget"],
            "completed_late": counts["late"],
            "rejected_early": counts["rejected_early"],
            "in_budget_rate": round(counts["in_budget"] / max(done, 1), 4),
            "rejected_early_vs_late": (
                round(counts["rejected_early"] / counts["late"], 2)
                if counts["late"] else counts["rejected_early"]),
        })
    if fail_details:
        cell["failure_detail"] = fail_details[:5]
    return cell


def _bench_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_fleet.json")


def deadline_only() -> int:
    """Run ONLY the deadline cell against a fresh 3-replica fleet and
    merge it into the committed BENCH_fleet.json (the other cells'
    numbers — measured under their own settings — stay untouched)."""
    import tempfile
    work = tempfile.mkdtemp(prefix="xgbtpu_benchdl_")
    model = os.path.join(work, "model.bin")
    print("[bench_fleet] training model...", file=sys.stderr)
    _train_model(model)
    fl = FleetLauncher(model, replicas=3,
                       workdir=os.path.join(work, "f3"),
                       serve_args=SERVE_ARGS, quiet=True)
    fl.start()
    fl.wait_ready()
    hammer(fl.url, min(REQS, 400), CLIENTS)  # warm the service EWMAs
    feasible = hammer(fl.url, REQS, CLIENTS,
                      deadline_ms=DEADLINE_FEASIBLE_MS)
    tight = hammer(fl.url, REQS, CLIENTS, deadline_ms=DEADLINE_TIGHT_MS)
    fl.stop()
    try:
        with open(_bench_path()) as f:
            out = json.load(f)
    except OSError:
        out = {}
    out["deadline_feasible"] = feasible
    out["deadline"] = tight
    out["backend"] = os.environ["JAX_PLATFORMS"]  # the pin above
    with open(_bench_path(), "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps({"deadline_feasible": feasible, "deadline": tight}))
    return 0 if feasible["failures"] + tight["failures"] == 0 else 1


def catalog_only() -> int:
    """Run ONLY the catalog cells — one replica serving a 1-entry vs a
    4-entry model catalog over the same wire — and merge them into the
    committed BENCH_fleet.json (the other cells stay untouched).  The
    4-entry cell drives all four tenants concurrently: the number that
    matters is how much a busy multi-tenant replica costs each tenant
    vs having the replica to itself."""
    import shutil
    import socket
    import subprocess
    import tempfile
    import urllib.request

    work = tempfile.mkdtemp(prefix="xgbtpu_benchcat_")
    print("[bench_fleet] training model...", file=sys.stderr)
    names = ["m0", "m1", "m2", "m3"]
    paths = {n: os.path.join(work, f"{n}.bin") for n in names}
    _train_model(paths["m0"])
    for n in names[1:]:
        shutil.copyfile(paths["m0"], paths[n])
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def replica(manifest):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        log = open(os.path.join(work, f"replica-{port}.log"), "ab")
        proc = subprocess.Popen(
            [sys.executable, "-m", "xgboost_tpu", "task=serve",
             f"catalog={manifest}", f"serve_port={port}",
             "serve_host=127.0.0.1", "silent=1"] + SERVE_ARGS,
            stdout=log, stderr=log, cwd=repo,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        log.close()
        url = f"http://127.0.0.1:{port}"
        deadline = time.perf_counter() + 300.0
        while time.perf_counter() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"catalog replica died rc={proc.returncode} "
                    f"(see {work}/replica-{port}.log)")
            try:
                with urllib.request.urlopen(url + "/healthz",
                                            timeout=2) as r:
                    json.load(r)
                return proc, url
            except (OSError, ValueError):
                time.sleep(0.25)
        proc.kill()
        raise TimeoutError("catalog replica never became healthy")

    print("[bench_fleet] catalog_1 (one resident model)...",
          file=sys.stderr)
    proc, url = replica(f"m0={paths['m0']}")
    cat1 = hammer(url, REQS, CLIENTS, path="/predict?model=m0")
    proc.terminate()
    proc.wait()

    print("[bench_fleet] catalog_4 (four resident models, "
          "concurrent tenants)...", file=sys.stderr)
    proc, url = replica(",".join(f"{n}={paths[n]}" for n in names))
    per = {}
    lock = threading.Lock()

    def tenant(n):
        cell = hammer(url, REQS // len(names),
                      max(2, CLIENTS // len(names)),
                      path=f"/predict?model={n}")
        with lock:
            per[n] = cell

    ts = [threading.Thread(target=tenant, args=(n,)) for n in names]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    proc.terminate()
    proc.wait()

    cat4 = {
        "tenants": len(names),
        "requests": sum(c["requests"] for c in per.values()),
        "requests_per_sec": round(
            sum(c["requests"] for c in per.values()) / wall, 1),
        "ok": sum(c["ok"] for c in per.values()),
        "failures": sum(c["failures"] for c in per.values()),
        "p99_ms_worst_tenant": max(c["p99_ms"] for c in per.values()),
        "per_tenant": per,
        "cpu": _cpu_info(),
    }
    if _effective_cores() <= 2:
        cat4["note"] = (
            f"{_effective_cores()}-effective-core container: all four "
            "tenant engines "
            "share one core, so catalog_4 measures multi-model "
            "interleaving fairness and per-tenant isolation overhead, "
            "not parallel speedup — aggregate req/s stays near "
            "catalog_1 while per-tenant p99 grows with the sharing")
    try:
        with open(_bench_path()) as f:
            out = json.load(f)
    except OSError:
        out = {}
    out["catalog_1"] = cat1
    out["catalog_4"] = cat4
    out["backend"] = os.environ["JAX_PLATFORMS"]  # the pin above
    with open(_bench_path(), "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps({"catalog_1": cat1, "catalog_4": cat4}))
    return 0 if cat1["failures"] + cat4["failures"] == 0 else 1


def multicore_only() -> int:
    """Re-measure the parallel-speedup cells — ``router_3`` and the
    catalog pair — and merge them into the committed BENCH_fleet.json,
    dropping the scarce-core caveats.  The committed numbers were taken
    on a 1-core container where those cells measure dispatch/isolation
    correctness, not speedup; on a host with ≥4 effective cores this
    replaces them with numbers the replica processes can actually
    scale into.  On a scarce-core host it is a deliberate NO-OP: the
    caveats stay because they are still true."""
    import tempfile
    cores = _effective_cores()
    if cores < 4:
        print(f"[bench_fleet] --multicore-only: {cores} effective "
              "core(s) (cpu_count="
              f"{os.cpu_count()}) — skipping the re-run; the committed "
              "scarce-core caveats remain accurate for this host",
              file=sys.stderr)
        return 0
    work = tempfile.mkdtemp(prefix="xgbtpu_benchmc_")
    model = os.path.join(work, "model.bin")
    print("[bench_fleet] training model...", file=sys.stderr)
    _train_model(model)
    print(f"[bench_fleet] router_3 re-run on {cores} cores...",
          file=sys.stderr)
    fl = FleetLauncher(model, replicas=3,
                       workdir=os.path.join(work, "f3"),
                       serve_args=SERVE_ARGS, quiet=True)
    fl.start()
    fl.wait_ready()
    hammer(fl.url, min(REQS, 400), CLIENTS)  # warm the service EWMAs
    r3 = hammer(fl.url, REQS, CLIENTS)
    fl.stop()
    try:
        with open(_bench_path()) as f:
            out = json.load(f)
    except OSError:
        out = {}
    out["router_3"] = r3
    out["value"] = r3["requests_per_sec"]
    out["unit"] = (f"req/s aggregate (1-row CSV via router, 3 "
                   f"subprocess replicas, {CLIENTS} clients, "
                   f"{cores} effective cores; p99={r3['p99_ms']}ms)")
    out.pop("note", None)   # the scarce-core caveat no longer applies
    out["backend"] = os.environ["JAX_PLATFORMS"]  # the pin above
    with open(_bench_path(), "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps({"router_3": r3}))
    rc_cat = catalog_only()   # refreshes catalog_1/catalog_4 + caveat
    return rc_cat if r3["failures"] == 0 else 1


def main():
    import tempfile
    if "--deadline-only" in sys.argv[1:]:
        return deadline_only()
    if "--catalog-only" in sys.argv[1:]:
        return catalog_only()
    if "--multicore-only" in sys.argv[1:]:
        return multicore_only()
    work = tempfile.mkdtemp(prefix="xgbtpu_benchfleet_")
    model = os.path.join(work, "model.bin")
    print("[bench_fleet] training model...", file=sys.stderr)
    _train_model(model)
    out = {"metric": "fleet_3replica_requests_per_sec",
           "clients": CLIENTS, "requests_per_cell": REQS}

    # ---- 1 replica: direct (no router) vs via router ----
    print("[bench_fleet] 1-replica fleet...", file=sys.stderr)
    fl = FleetLauncher(model, replicas=1,
                       workdir=os.path.join(work, "f1"),
                       serve_args=SERVE_ARGS, quiet=True)
    fl.start()
    fl.wait_ready()
    rep_url = fl.members()["replicas"][0]["url"]
    out["direct_1proc"] = hammer(rep_url, REQS, CLIENTS)
    out["router_1"] = hammer(fl.url, REQS, CLIENTS)
    fl.stop()

    # ---- 3 replicas via router; then overload with a tiny budget ----
    print("[bench_fleet] 3-replica fleet...", file=sys.stderr)
    fl = FleetLauncher(model, replicas=3,
                       workdir=os.path.join(work, "f3"),
                       serve_args=SERVE_ARGS, quiet=True)
    fl.start()
    fl.wait_ready()
    out["router_3"] = hammer(fl.url, REQS, CLIENTS)
    # overload: shrink the global in-flight budget far below the client
    # concurrency — admission control must shed with 503, fast, and
    # everything ADMITTED must still succeed
    fl.router.inflight_budget = 4
    out["overload"] = hammer(fl.url, REQS, CLIENTS)
    out["overload"]["inflight_budget"] = 4
    # deadline: full admission again, but every request carries an
    # X-Deadline-Ms budget — feasible first, then the tight overload
    # case where the win is rejected-early ≫ completed-late
    # (reliability/deadline.py; 504s are the deadline discipline
    # working, not failures)
    fl.router.inflight_budget = 256
    out["deadline_feasible"] = hammer(fl.url, REQS, CLIENTS,
                                      deadline_ms=DEADLINE_FEASIBLE_MS)
    out["deadline"] = hammer(fl.url, REQS, CLIENTS,
                             deadline_ms=DEADLINE_TIGHT_MS)
    fl.stop()

    out["value"] = out["router_3"]["requests_per_sec"]
    out["unit"] = (f"req/s aggregate (1-row CSV via router, 3 "
                   f"subprocess replicas, {CLIENTS} clients, CPU "
                   f"{os.cpu_count()}-core; p99="
                   f"{out['router_3']['p99_ms']}ms)")
    if _effective_cores() <= 2:
        out["note"] = (
            f"{_effective_cores()}-effective-core container: the 3 "
            "replica processes "
            "share one core, so router_3 measures dispatch/retry/shed "
            "correctness rather than parallel speedup — replica "
            "scaling needs cores to scale onto (compare router_1 vs "
            "direct_1proc for the router hop overhead instead)")
    try:
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "BENCH_serving.json")) as f:
            bs = json.load(f)
        out["bench_serving_baseline"] = {
            "headline_1row_req_per_sec": bs.get("value"),
            "concurrent_req_per_sec":
                bs.get("concurrent", {}).get("requests_per_sec"),
        }
    except OSError as e:
        out["bench_serving_baseline"] = f"unavailable: {e}"

    out["backend"] = os.environ["JAX_PLATFORMS"]  # the pin above
    with open(_bench_path(), "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out))
    ok = (out["overload"]["failures"] == 0
          and out["router_3"]["failures"] == 0)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
