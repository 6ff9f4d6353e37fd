#!/usr/bin/env python
"""Chaos loop: repeated kill-at-random-fault-point train/resume driver.

Each run arms a RANDOM failure combination against the real training
CLI — a worker death at a random (version, seqno) collective coordinate
(``mock=`` / parallel/mock.py) plus, half the time, a torn-write or
bit-flip fault on a random checkpoint-ring member at a random byte
offset (``reliability/faults.py``) — then lets the keepalive restart
recover through the checkpoint ring and asserts the finished model is
BIT-identical to an uninterrupted reference run.

Emits ``CHAOS.json``::

    {"runs": N, "recoveries": n, "bit_identical": n, "mismatches": 0,
     "deaths": total_kills, "corruptions_armed": n,
     "ring_fallbacks": n, "quarantines": n, "integrity_failures": n}

Usage::

    JAX_PLATFORMS=cpu python tools/chaos_loop.py --runs 10 --seed 0

``--fleet`` switches to the SERVING-tier chaos mode (SERVING.md fleet
section): a local fleet (tools/launch_fleet.py — router + N replica
subprocesses) serves live traffic while a killer SIGKILLs a random
replica every few seconds and keepalive restarts it.  The assertion is
the fleet contract: ZERO failed non-shed requests — every client
request either succeeds (the router's retry-once path absorbs replica
deaths) or is an explicit 503 shed.  Emits ``CHAOS_fleet.json``.

``--pipeline`` switches to the CONTINUOUS-TRAINING chaos mode
(PIPELINE.md): a shared-model fleet (every replica polls the pipeline's
publish path) serves live traffic while ``task=pipeline`` subprocesses
train→gate→publish fresh cycles — and the driver SIGKILLs the pipeline
process at random moments and randomly arms bit-flip/torn-write faults
on the candidate, the checkpoint ring, and the publish path.  A hash
watcher scrapes every replica's ``/healthz`` ``model_hash``
continuously; the contract asserted is **zero unverified or ungated
models ever observed by a serving replica**: every hash a replica
serves must be the initial seed model or a hash recorded in the
pipeline's fsync'd ``gated.log`` ledger BEFORE its publish began.
Emits ``PIPELINE_CHAOS.json``.

``--catalog`` switches to the MULTI-TENANT catalog chaos mode
(SERVING.md catalog section): two width-divergent tenant models share a
catalog fleet (``task=serve catalog=a=...,b=...``) behind a router
subprocess running with ``fleet_state_path``; per-tenant ``task=
pipeline`` lanes train→gate→publish against each tenant's publish
path while per-tenant clients drive ``/predict?model=...`` and the
killer SIGKILLs lane trainers at random — and the ROUTER itself, whose
replacement must restore membership from the CRC-footered snapshot
with zero non-shed client failures.  Per-tenant hash watchers scrape
``/healthz`` ``models`` rows straight off every replica; the contract
is the pipeline mode's zero-ungated-models invariant enforced PER
TENANT (each tenant against its OWN ``gated.log``), plus isolation:
killing one tenant's trainer never stalls the other's lane.  Emits
``CATALOG_CHAOS.json``.

``--stream`` switches to the STREAMING chaos mode (PIPELINE.md
streaming section): a producer thread spools row batches into a
``StreamDataSource`` directory (shifting the feature distribution
halfway through, so drift fires and an online cut refresh lands
mid-chaos) while ``task=stream`` subprocesses consume micro-cycles —
and the driver SIGKILLs the stream trainer at random moments
(mid-compose, mid-train, mid-gate, mid-publish).  SIGKILL-only: the
stream contract under test is replay determinism, not media faults.
A watcher hashes the publish path continuously; asserted are (a) the
zero-ungated invariant — every observed publish-path hash is the seed
or in ``gated.log`` — and (b) bit-identical replay: a FRESH workdir
consuming the SAME spool re-publishes the identical per-cycle hash
sequence and identical final model bytes.  Emits
``STREAM_CHAOS.json``.

``--placer`` switches to the AUTONOMOUS-PLACEMENT chaos mode
(SERVING.md "Autonomous placement"): a router + N default-only catalog
replicas + a ``task=placer`` subprocess managing a 4-tenant manifest
(``placer_replication=2``).  Once the placer has attached every tenant,
per-tenant clients drive ``/predict?model=...`` through the router
while the killer (a) SIGKILLs a replica mid-rebalance (keepalive
restarts it under a FRESH identity, so the placer must re-home, not
wait), (b) SIGKILLs the placer itself mid-push and restarts it on the
same ``placer_plan_path``, and (c) repeats the placer kill in a quiet
window to pin plan-resume determinism.  A watcher samples
``/fleet/members`` continuously; the contract is (1) zero non-shed
client failures, (2) no tenant ever orphaned — every sample shows ≥1
in-rotation replica advertising each tenant — and (3) the resumed
placer reports the SAME target assignment it snapshotted before the
kill.  Emits ``PLACER_CHAOS.json``.

``--train`` switches to the STALL-failure training mode (RELIABILITY.md
stall matrix): each run arms a ``stall`` mock coordinate (the hang twin
of worker death, parallel/mock.py) — and, half the time, a death
coordinate on the NEXT trial — against the real CLI supervised by the
gang launcher's heartbeat watchdog (``--watchdog-stall-sec``).  The
wedged worker stops touching its per-rank heartbeat file, the watchdog
kills and restarts the gang, the restarted trial sails past the
coordinate (ntrial semantics) and resumes from the checkpoint ring; the
assertion is the same bit-identical-final-model contract as the death
suite.  Two cells run per invocation: ``baseline`` (single-device
segmented fused dispatch) and ``fused_mesh`` (``dsplit=row`` +
``hist_precision=fixed`` over ``--local-devices`` in-process devices —
the mesh-fused scan), both verified fallback-free via the obs event
log (``train.fused_fallback`` must never appear).  Emits
``TRAIN_CHAOS.json``.

``--train --degrade`` additionally runs the ELASTIC DEGRADED-MESH
cells (RECOVERY.md degraded-mode matrix) against the real CLI under
the gang launcher:

- ``host_loss_growback`` — a permanent host death mid-run
  (``host_loss`` gang fault) forces an immediate re-plan at half the
  device count; once degraded, the driver touches the ``grow`` signal
  (a replacement registered) and the launcher re-expands to full size
  at the next segment boundary.  Asserted: the finished model is
  BIT-identical to an uninterrupted run (PR 12 mesh-size invariance is
  the oracle) and the ``gang.host_loss`` / ``launch.degrade`` /
  ``launch.growback`` events all fired.
- ``coord_sigkill_adopt`` — SIGKILL the COORDINATOR mid-restart (right
  after a worker death triggered a gang restart); a replacement
  launcher started on the same ``--state-path`` re-ADOPTS the live
  workers (``launch.adopt``) instead of orphaning or re-spawning them,
  and the job finishes bit-identical with no leaked pids.
- ``partition_fence`` — a ``partition`` window straddling the ring
  writes: the worker self-fences (``gang.fence``, rc 143) once the
  coordinator beacon is stale past ``--gang-partition-sec``, the gang
  restarts and resumes from the ring.  A watcher thread samples every
  checkpoint-ring member THROUGHOUT; the split-brain assertion is that
  every observed member CRC-verifies (atomic_write: no torn reads) and
  every version slot ever observed holds exactly ONE payload hash
  across all attempts — one attempt lineage, no second writer.

Cell results merge into the same ``TRAIN_CHAOS.json`` under
``degrade``.  ``--runs 0`` skips the stall cells (degrade cells only).

``--selftest`` runs the fast, subprocess-free logic checks (partition
clock, degrade ladder, coordinator-state roundtrip, fail-loud fault
parsing, ring-lineage scanner) and prints ``selftest: OK`` — wired as
a tier-1 test (tests/test_chaos_selftest.py).

``--fleet --slow`` arms ``slow_replica`` (a wedged-but-alive replica:
every predict sleeps, lease and /healthz stay green) instead of kills:
the router's latency-aware ejection must take the replica out of
rotation and traffic must keep flowing with ZERO non-shed failures.
Emits ``CHAOS_fleet_slow.json``.

Also runs as a slow-marked test
(tests/test_reliability.py::test_chaos_loop_driver).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _write_libsvm(path: str, n: int = 300, f: int = 5, seed: int = 0) -> None:
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = (X[:, 0] > 0.5).astype(int)
    with open(path, "w") as fh:
        for i in range(n):
            feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(f))
            fh.write(f"{y[i]} {feats}\n")


def _state(path: str):
    import xgboost_tpu as xgb
    return xgb.Booster(model_file=path).gbtree.get_state()


def _states_equal(a, b) -> bool:
    if set(a) != set(b):
        return False
    return all(np.array_equal(a[k], b[k]) for k in a)


def _scan_obs_events(prefix: str, name: str) -> int:
    """Count ``name`` events across the obs JSONL file(s) a run wrote
    (``prefix`` plus per-rank suffixes).  Append-only across gang
    restarts, so a fallback from ANY trial stays visible."""
    import glob
    hits = 0
    for path in glob.glob(prefix + "*"):
        try:
            with open(path) as f:
                for line in f:
                    if f'"name": "{name}"' in line or \
                            f'"name":"{name}"' in line:
                        hits += 1
        except OSError:
            pass
    return hits


def train_stall_mode(args) -> int:
    """Stall-failure training chaos: wedge the worker at a random
    collective coordinate, let the watchdog kill+restart the gang, and
    assert bit-identical resume — composed with a death on the restart
    trial half the time (see module docstring).

    Runs TWO cells per seed: ``baseline`` (single-device, segmented
    fused dispatch) and ``fused_mesh`` (``dsplit=row`` over
    ``--local-devices`` in-process devices with
    ``hist_precision=fixed``, the mesh-fused scan).  Both ride the
    fused driver — coordinates replay at segment boundaries — and both
    assert ZERO silent per-round fallbacks by scanning the run's obs
    event log for ``train.fused_fallback`` (counter-backed: the same
    events increment ``xgbtpu_train_fused_fallback_total``)."""
    import subprocess

    from xgboost_tpu.cli import main as cli_main

    work = args.workdir or tempfile.mkdtemp(prefix="xgbtpu_chaostrain_")
    os.makedirs(work, exist_ok=True)
    data = os.path.join(work, "train.libsvm")
    _write_libsvm(data, seed=args.seed)
    # rounds_per_dispatch=2: several segments per run, so the stall /
    # death coordinates land BETWEEN ring checkpoints and the restart
    # genuinely resumes mid-training (auto-K would fuse this tiny
    # workload into one segment and every restart would retrain from 0)
    common = [f"data={data}", "task=train", f"num_round={args.rounds}",
              "silent=2", "objective=binary:logistic", "max_depth=3",
              "eta=0.5", "max_bin=16", "rounds_per_dispatch=2"]
    cells = [
        ("baseline", [], []),
        ("fused_mesh", ["dsplit=row", "hist_precision=fixed"],
         ["--local-devices", str(args.local_devices)]),
    ]

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report = {"mode": "train_stall", "runs_per_cell": args.runs,
              "local_devices": args.local_devices,
              "stalls_armed": 0, "deaths_armed": 0,
              "watchdog_kills": 0, "restarts": 0,
              "bit_identical": 0, "mismatches": 0,
              "fused_fallbacks": 0, "run_log": []}
    if args.runs == 0:
        cells = []  # --runs 0: degrade cells only (see --degrade)
    for cell, extra, launch_extra in cells:
        # uninterrupted reference per cell (checkpointing ON: identical
        # code path; the mesh cell's params change the model)
        ref_model = os.path.join(work, f"ref_{cell}.model")
        rc = cli_main(common + extra + [
            f"model_out={ref_model}",
            f"checkpoint_dir={os.path.join(work, f'ck_ref_{cell}')}"])
        if rc != 0:
            print(f"[chaos-train] {cell} reference run failed (rc={rc})",
                  file=sys.stderr)
            return 1
        ref = _state(ref_model)

        rng = np.random.RandomState(args.seed)
        for run in range(args.runs):
            out = os.path.join(work, f"m_{cell}_{run:03d}.model")
            obs_log = os.path.join(work, f"obs_{cell}_{run:03d}.jsonl")
            vs = int(rng.randint(1, args.rounds))  # stall round (trial 0)
            mock = f"stall:{vs},0,0"
            report["stalls_armed"] += 1
            entry = {"cell": cell, "run": run, "mock": mock}
            if run % 2 == 1 or rng.rand() < 0.5:
                # compose stall with DEATH on (at least) every odd run:
                # the restarted trial (1) dies at a later coordinate,
                # exercising watchdog-kill followed by plain keepalive
                # restart in one recovery chain
                vd = int(rng.randint(1, args.rounds))
                mock += f";die:{vd},0,1"
                entry["mock"] = mock
                report["deaths_armed"] += 1
            cmd = [sys.executable, "-m", "xgboost_tpu.launch", "-n", "1",
                   "--standalone", "--keepalive", *launch_extra,
                   "--watchdog-stall-sec", str(args.stall_window),
                   "--restart-backoff-sec", "0.2", "--",
                   sys.executable, "-m", "xgboost_tpu", *common, *extra,
                   f"model_out={out}",
                   f"checkpoint_dir={os.path.join(work, f'ck_{cell}_{run:03d}')}",
                   f"mock={mock}"]
            # the event log witnesses the run; it no longer moves the
            # run off the fused path (the fallback asserted against)
            r = subprocess.run(cmd, cwd=repo, capture_output=True,
                               text=True, timeout=600,
                               env=dict(os.environ, JAX_PLATFORMS="cpu",
                                        XGBTPU_OBS_LOG=obs_log))
            entry["rc"] = r.returncode
            entry["watchdog_kills"] = r.stderr.count("[launch] STALL")
            entry["restarts"] = r.stderr.count("[launch] restarting")
            # the LOUD-fallback contract: every trial of every run must
            # have taken the fused driver (per-round fallback emits a
            # train.fused_fallback event + counter)
            entry["fused_fallbacks"] = _scan_obs_events(
                obs_log, "train.fused_fallback")
            report["watchdog_kills"] += entry["watchdog_kills"]
            report["restarts"] += entry["restarts"]
            report["fused_fallbacks"] += entry["fused_fallbacks"]
            if (r.returncode == 0 and _states_equal(ref, _state(out))
                    and entry["fused_fallbacks"] == 0):
                report["bit_identical"] += 1
                entry["result"] = "bit_identical"
            else:
                report["mismatches"] += 1
                entry["result"] = (
                    f"rc={r.returncode}" if r.returncode
                    else "FUSED_FALLBACK" if entry["fused_fallbacks"]
                    else "MISMATCH")
                entry["stderr_tail"] = r.stderr[-1500:]
            report["run_log"].append(entry)
            print(f"[chaos-train] {cell} run {run}: mock={mock} -> "
                  f"{entry['result']} ({entry['watchdog_kills']} "
                  f"watchdog kill(s), {entry['restarts']} restart(s), "
                  f"{entry['fused_fallbacks']} fused fallback(s))",
                  file=sys.stderr)
    degrade_ok = True
    if args.degrade:
        degrade_ok = degrade_cells(args, work, repo, report)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    total = args.runs * len(cells)
    print(f"[chaos-train] {report['bit_identical']}/{total} "
          f"bit-identical across {report['watchdog_kills']} watchdog "
          f"kills / {report['restarts']} restarts "
          f"({report['fused_fallbacks']} fused fallbacks) -> {args.out}",
          file=sys.stderr)
    ok = (report["mismatches"] == 0 and report["fused_fallbacks"] == 0
          and (args.runs == 0
               or (report["watchdog_kills"] >= 1
                   and report["restarts"] >= report["watchdog_kills"])))
    return 0 if (ok and degrade_ok) else 1


def _ckpt_lineage_violations(lineage) -> list:
    """Ring slots observed with MORE than one distinct payload hash —
    the split-brain witness: a resumed attempt rewriting a version slot
    must reproduce the identical bytes (deterministic recovery), so a
    second hash means a second, diverged writer touched the ring."""
    return sorted(name for name, hashes in lineage.items()
                  if len(hashes) > 1)


def degrade_cells(args, work, repo, report) -> bool:
    """The elastic degraded-mesh chaos cells (see module docstring,
    ``--train --degrade``): host-loss degrade + grow-back, coordinator
    SIGKILL + re-adoption, and a partition self-fence with the ring
    split-brain assertion.  Results land in ``report['degrade']``."""
    import hashlib
    import re
    import signal
    import subprocess
    import threading

    from xgboost_tpu.cli import main as cli_main
    from xgboost_tpu.reliability.integrity import (read_file,
                                                   verify_model_bytes)

    data = os.path.join(work, "train.libsvm")
    mesh = ["dsplit=row", "hist_precision=fixed"]

    def common(rounds):
        return [f"data={data}", "task=train", f"num_round={rounds}",
                "silent=2", "objective=binary:logistic", "max_depth=3",
                "eta=0.5", "max_bin=16", "rounds_per_dispatch=2"]

    def reference(tag, rounds, extra):
        # uninterrupted single-device reference: PR 12 mesh-size
        # invariance (dsplit=row + hist_precision=fixed) makes it the
        # oracle for EVERY size the elastic gang passes through
        ref_model = os.path.join(work, f"ref_{tag}.model")
        rc = cli_main(common(rounds) + extra + [
            f"model_out={ref_model}",
            f"checkpoint_dir={os.path.join(work, f'ck_ref_{tag}')}"])
        if rc != 0:
            raise RuntimeError(f"degrade reference {tag} failed rc={rc}")
        return _state(ref_model)

    def launch(tag, rounds, extra, launch_extra, env_extra,
               watch=None, timeout=420.0):
        """Run one launcher attempt; ``watch(proc, paths)`` is polled
        every 100ms for driver-side chaos (grow signals, SIGKILLs)."""
        out = os.path.join(work, f"{tag}.model")
        obs_log = os.path.join(work, f"obs_{tag}.jsonl")
        gang_dir = os.path.join(work, f"gang_{tag}")
        os.makedirs(gang_dir, exist_ok=True)
        ck = os.path.join(work, f"ck_{tag}")
        worker = [sys.executable, "-m", "xgboost_tpu", *common(rounds),
                  *extra, f"model_out={out}", f"checkpoint_dir={ck}"]
        cmd = [sys.executable, "-m", "xgboost_tpu.launch", "-n", "1",
               "--standalone", "--keepalive",
               "--restart-backoff-sec", "0.2",
               "--gang-dir", gang_dir, *launch_extra, "--", *worker]
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XGBTPU_OBS_LOG=obs_log, **env_extra)
        log = open(os.path.join(work, f"{tag}.log"), "ab")
        paths = {"out": out, "obs": obs_log, "gang_dir": gang_dir,
                 "ck": ck, "log": log.name,
                 "state": os.path.join(gang_dir, "coord-state.json")}
        p = subprocess.Popen(cmd, cwd=repo, env=env,
                             stdout=log, stderr=log)
        deadline = time.perf_counter() + timeout
        try:
            while p.poll() is None and time.perf_counter() < deadline:
                if watch is not None:
                    stop = watch(p, paths)
                    if stop:
                        break
                time.sleep(0.1)
            if p.poll() is None and (watch is None
                                     or time.perf_counter() >= deadline):
                p.kill()
        finally:
            p.wait()
            log.close()
        return p.returncode, paths

    results = {}
    ok = True

    # ---- cell 1: permanent host loss mid-run -> immediate degrade to
    # half the devices, then a grow-back once the driver (standing in
    # for a replacement host registering) touches the grow signal
    ref = reference("degrade", args.rounds, mesh)
    state_seen = {"grown": False}

    def grow_when_degraded(p, paths):
        if not state_seen["grown"]:
            try:
                with open(paths["state"], errors="replace") as f:
                    if '"degraded": true' in f.read():
                        open(os.path.join(paths["gang_dir"], "grow"),
                             "w").close()
                        state_seen["grown"] = True
                        print("[chaos-degrade] degraded snapshot seen; "
                              "touched grow signal", file=sys.stderr)
            except OSError:
                pass
        return False

    rc, paths = launch(
        "d1", args.rounds, mesh,
        ["--local-devices", "2", "--degrade-after", "3"],
        {"XGBTPU_FAULTS": "host_loss@t0.r0.v2."},
        watch=grow_when_degraded)
    cell = {"rc": rc,
            "grow_signal_sent": state_seen["grown"],
            "host_loss_events": _scan_obs_events(paths["obs"],
                                                 "gang.host_loss"),
            "degrades": _scan_obs_events(paths["obs"], "launch.degrade"),
            "growbacks": _scan_obs_events(paths["obs"],
                                          "launch.growback"),
            "bit_identical": (rc == 0
                              and _states_equal(ref,
                                                _state(paths["out"])))}
    cell["pass"] = bool(rc == 0 and cell["bit_identical"]
                        and cell["host_loss_events"] >= 1
                        and cell["degrades"] >= 1
                        and cell["growbacks"] >= 1)
    results["host_loss_growback"] = cell
    ok &= cell["pass"]
    print(f"[chaos-degrade] host_loss_growback: {cell}", file=sys.stderr)

    # ---- cell 2: coordinator SIGKILL mid-restart; the replacement
    # launcher on the same --state-path re-adopts the live gang
    ref2 = reference("adopt", args.rounds, [])
    killed = {"at": None}

    def kill_mid_restart(p, paths):
        # wait for the worker-death restart (the mock die fires at v3),
        # give trial 1 a second to be live mid-compile, then SIGKILL
        # the coordinator — the gang must survive it
        if killed["at"] is None and \
                _scan_obs_events(paths["obs"], "launch.restart") >= 1:
            killed["at"] = time.perf_counter() + 1.0
        if killed["at"] is not None \
                and time.perf_counter() >= killed["at"]:
            p.send_signal(signal.SIGKILL)
            print("[chaos-degrade] SIGKILLed coordinator mid-restart",
                  file=sys.stderr)
            return True
        return False

    state_path = os.path.join(work, "d2-coord-state.json")
    rc, paths = launch("d2", args.rounds, ["mock=die:3,0,0"],
                       ["--state-path", state_path], {},
                       watch=kill_mid_restart)
    orphans = []
    try:
        with open(state_path, errors="replace") as f:
            orphans = [int(m) for m in
                       re.findall(r'"pid": (\d+)', f.read())]
    except OSError:
        pass
    # the replacement coordinator: same state path, same command
    rc2, paths2 = launch("d2", args.rounds, ["mock=die:3,0,0"],
                         ["--state-path", state_path], {})
    time.sleep(1.0)  # adopted workers exit right after their done mark
    leaked = [pid for pid in orphans
              if os.path.exists(f"/proc/{pid}")]
    cell = {"coordinator_sigkilled": rc != 0 or killed["at"] is not None,
            "worker_pids_at_kill": orphans, "relaunch_rc": rc2,
            "adoptions": _scan_obs_events(paths2["obs"], "launch.adopt"),
            "leaked_pids": leaked,
            "bit_identical": (rc2 == 0
                              and _states_equal(ref2,
                                                _state(paths2["out"])))}
    cell["pass"] = bool(rc2 == 0 and cell["bit_identical"]
                        and cell["adoptions"] >= 1
                        and cell["coordinator_sigkilled"]
                        and not leaked)
    results["coord_sigkill_adopt"] = cell
    ok &= cell["pass"]
    print(f"[chaos-degrade] coord_sigkill_adopt: {cell}", file=sys.stderr)

    # ---- cell 3: partition window straddling the ring writes -> the
    # worker self-fences, the gang restarts and resumes from the ring;
    # a watcher samples every ring member throughout for the
    # split-brain assertion (CRC + one-lineage-per-slot)
    fence_rounds = 400  # ~8ms/segment: the window must outlast beacons
    ref3 = reference("fence", fence_rounds, [])
    lineage = {}
    crc_failures = []
    stop_watch = threading.Event()
    ck3 = os.path.join(work, "ck_d3")

    def ring_watcher():
        while not stop_watch.is_set():
            try:
                names = [n for n in os.listdir(ck3)
                         if re.fullmatch(r"ckpt-\d{6}\.model", n)]
            except OSError:
                names = []
            for n in names:
                try:
                    payload = verify_model_bytes(
                        read_file(os.path.join(ck3, n)), name=n)
                except OSError:
                    continue  # rotated away mid-read: not an observation
                except ValueError:
                    crc_failures.append(n)
                    continue
                lineage.setdefault(n, set()).add(
                    hashlib.sha256(payload).hexdigest())
            time.sleep(0.01)

    wt = threading.Thread(target=ring_watcher)
    wt.start()
    try:
        rc, paths = launch(
            "d3", fence_rounds, [],
            ["--gang-partition-sec", "0.5"],
            {"XGBTPU_FAULTS": "partition=20.0@t0.r0.v6."})
    finally:
        stop_watch.set()
        wt.join(10.0)
    cell = {"rc": rc,
            "fences": _scan_obs_events(paths["obs"], "gang.fence"),
            "partition_windows": _scan_obs_events(paths["obs"],
                                                  "gang.partition"),
            "restarts": _scan_obs_events(paths["obs"], "launch.restart"),
            "ring_slots_observed": len(lineage),
            "ring_crc_failures": sorted(set(crc_failures)),
            "ring_lineage_violations":
                _ckpt_lineage_violations(lineage),
            "bit_identical": (rc == 0
                              and _states_equal(ref3,
                                                _state(paths["out"])))}
    cell["pass"] = bool(rc == 0 and cell["bit_identical"]
                        and cell["fences"] >= 1
                        and cell["restarts"] >= 1
                        and cell["ring_slots_observed"] >= 2
                        and not cell["ring_crc_failures"]
                        and not cell["ring_lineage_violations"])
    results["partition_fence"] = cell
    ok &= cell["pass"]
    print(f"[chaos-degrade] partition_fence: {cell}", file=sys.stderr)

    report["degrade"] = results
    report["degrade_pass"] = bool(ok)
    return bool(ok)


def selftest() -> int:
    """Fast, subprocess-free logic checks for the elastic-gang pieces
    (wired as a tier-1 test; the heavyweight cells above are the real
    chaos proof).  Prints ``selftest: OK`` on success."""
    from xgboost_tpu.parallel.gang import PartitionClock
    from xgboost_tpu.parallel.launch import (_read_state, _write_state,
                                             plan_degrade)
    from xgboost_tpu.reliability import faults

    # -- partition clock: fence past threshold, heal on fresh beacon
    now = [0.0]
    clk = PartitionClock(partition_sec=0.5, monotonic=lambda: now[0])
    assert clk.observe(1.0) == "ok"          # grace starts
    now[0] = 0.1
    assert clk.observe(2.0) == "ok"          # beacon advanced
    clk.open_window(5.0)
    now[0] = 0.3
    assert clk.observe(3.0) == "partitioned"  # read dropped
    now[0] = 0.7
    assert clk.observe(4.0) == "fence"       # stale past 0.5s
    # heal path: window expired, a fresh beacon mtime lands
    now[0] = 6.0
    assert clk.observe(5.0) == "ok"
    # no spurious fence: boundaries every 50ms, beacon only every 200ms
    clk2 = PartitionClock(partition_sec=0.5, monotonic=lambda: now[0])
    mtime = 0.0
    for i in range(40):
        now[0] = 10.0 + i * 0.05
        if i % 4 == 0:
            mtime += 1.0
        assert clk2.observe(mtime) == "ok", f"spurious fence at {i}"
    # fencing disabled: stale forever still never fences
    clk3 = PartitionClock(partition_sec=0.0, monotonic=lambda: now[0])
    clk3.observe(1.0)
    now[0] += 1000.0
    assert clk3.observe(1.0) == "ok"

    # -- degrade ladder: devices halve first, then workers shed, and
    # min_workers floors the ladder
    assert plan_degrade(4, 4) == (4, 2)
    assert plan_degrade(4, 2) == (4, 1)
    assert plan_degrade(4, 1) == (3, 1)
    assert plan_degrade(2, None) == (1, None)
    assert plan_degrade(1, None) is None
    assert plan_degrade(2, None, min_workers=2) is None

    # -- coordinator-state snapshot: roundtrip + corrupt rejection
    with tempfile.TemporaryDirectory() as d:
        sp = os.path.join(d, "state.json")
        st = {"full_n": 2, "cur_n": 1, "degraded": True, "trial": 3,
              "workers": [{"rank": 0, "pid": 123}]}
        _write_state(sp, st, "pid42")
        got = _read_state(sp)
        assert got is not None and got["holder"] == "pid42"
        assert got["cur_n"] == 1 and got["degraded"] is True
        with open(sp, "r+b") as f:   # flip a byte: CRC must reject it
            f.seek(5)
            b = f.read(1)
            f.seek(5)
            f.write(bytes([b[0] ^ 0xFF]))
        assert _read_state(sp) is None

    # -- fail-loud fault specs: arm-time typed errors, nothing armed
    for bad in ("bogus_kind@ckpt", "torn_write=abc@ckpt",
                "torn_write=128@ckpt*0", "bit_flip@ckpt*zz", "=3@x"):
        try:
            faults.install_spec(bad)
        except faults.FaultSpecError:
            pass
        else:
            raise AssertionError(f"spec {bad!r} did not fail loud")
        finally:
            faults.clear_faults()
    # a trailing typo arms NOTHING (two-phase parse)
    try:
        faults.install_spec("torn_write=128@ckpt;bogus@x")
    except faults.FaultSpecError:
        pass
    assert not faults.gang_fault("t0.r0.v0.")
    faults.install_spec("host_loss@t0.r0.v2.;partition=3.5@t0.r0.v4.")
    assert faults.gang_fault("t0.r0.v2.") == [("host_loss", None)]
    assert faults.gang_fault("t0.r0.v4.") == [("partition", 3.5)]
    assert not faults.gang_fault("t1.r0.v2.")  # trial-scoped
    faults.clear_faults()

    # -- ring-lineage scanner: one hash per slot is clean, two is a
    # split brain
    clean = {"ckpt-000002.model": {"aa"}, "ckpt-000004.model": {"bb"}}
    split = {"ckpt-000002.model": {"aa", "cc"}}
    assert _ckpt_lineage_violations(clean) == []
    assert _ckpt_lineage_violations(split) == ["ckpt-000002.model"]

    print("selftest: OK")
    return 0


def fleet_mode(args) -> int:
    """Replica-kill chaos against a live local fleet: random SIGKILLs
    mid-traffic + keepalive restarts; asserts zero non-shed request
    failures (the router retry contract).  With ``--slow``, the chaos
    is a ``slow_replica`` wedge instead of kills: one replica stays
    alive and healthy-looking but answers every predict late, and the
    router's latency-aware ejection must route around it — same
    zero-non-shed-failures contract, plus at least one ejection."""
    import threading

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from launch_fleet import FleetLauncher, RetryingPredictClient

    import xgboost_tpu as xgb

    work = args.workdir or tempfile.mkdtemp(prefix="xgbtpu_chaosfleet_")
    os.makedirs(work, exist_ok=True)
    rng = np.random.RandomState(args.seed)
    X = rng.rand(400, 6).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "eta": 0.4, "silent": 1},
                    xgb.DMatrix(X, label=y), 4)
    model = os.path.join(work, "model.bin")
    bst.save_model(model)

    wedged = args.fleet_replicas - 1  # highest-numbered replica
    replica_faults = None
    if args.slow:
        # arm the wedge in the replica subprocess's env: every predict
        # on r<wedged> sleeps, while lease + /healthz stay green —
        # invisible to the breaker, fatal to the fleet p99
        replica_faults = {wedged: f"slow_replica={args.slow_delay}"
                                  f"@r{wedged}*1000000"}
    fl = FleetLauncher(
        model, replicas=args.fleet_replicas,
        workdir=os.path.join(work, "fleet"),
        serve_args=["serve_min_bucket=8", "serve_max_bucket=32",
                    "serve_max_wait_ms=1.0"],
        # short lease + fast health checks: a killed replica leaves
        # rotation quickly even before its breaker trips
        router_kwargs={"lease_sec": 3.0, "hc_sec": 0.5},
        replica_faults=replica_faults,
        quiet=True)
    fl.start()
    try:
        print(f"[chaos-fleet] waiting for {args.fleet_replicas} "
              "replicas...", file=sys.stderr)
        fl.wait_ready()
    except BaseException:
        # a failed bring-up must not orphan the router thread + N
        # replica subprocesses
        fl.stop()
        raise

    body = ",".join(f"{v:.6f}" for v in X[0]).encode()
    counts = {"ok": 0, "shed": 0, "fail": 0}
    lock = threading.Lock()
    stop = threading.Event()

    def client():
        # retry-once keep-alive client (launch_fleet): a second
        # transport failure counts as a REAL failure — the router is
        # up throughout, only replicas get killed
        conn = RetryingPredictClient(fl.url)
        mine = {"ok": 0, "shed": 0, "fail": 0}
        while not stop.is_set():
            status, _detail = conn.post(body)
            if status == 200:
                mine["ok"] += 1
            elif status == 503:
                mine["shed"] += 1
            else:
                mine["fail"] += 1
        conn.close()
        with lock:
            for k in counts:
                counts[k] += mine[k]

    clients = [threading.Thread(target=client) for _ in range(4)]
    for t in clients:
        t.start()

    kills = 0
    t_end = time.perf_counter() + args.fleet_secs
    next_kill = time.perf_counter() + args.kill_every
    try:
        while time.perf_counter() < t_end:
            time.sleep(0.25)
            fl.reap_and_restart()  # keepalive
            if args.slow:
                continue  # the wedge IS the chaos; no kills
            if time.perf_counter() >= next_kill:
                # victims come from the IN-ROTATION set (the router's
                # view — an alive-but-still-warming restart is not a
                # serving replica), and only while at least two are in
                # rotation: the contract under test is "replica deaths
                # cost nothing" — killing the LAST serving replica
                # (restarts take seconds) is a whole-fleet outage,
                # where 5xx is the only honest answer
                try:
                    rotation = [m["replica_id"]
                                for m in fl.members()["replicas"]
                                if m["in_rotation"]]
                except OSError:
                    rotation = []
                if len(rotation) >= 2:
                    victim = int(
                        rotation[rng.randint(len(rotation))][1:])
                    if fl.kill_replica(victim) is not None:
                        kills += 1
                        print(f"[chaos-fleet] killed replica r{victim}",
                              file=sys.stderr)
                next_kill = time.perf_counter() + args.kill_every
    finally:
        stop.set()
        for t in clients:
            t.join(30.0)
        restarts = fl.restarts
        ejections = 0.0
        wedged_desc = {}
        if args.slow:
            # the ejection evidence, read from the router's own state
            # + metrics before teardown
            try:
                import urllib.request

                import xgboost_tpu.fleet as fleet_pkg
                mtext = urllib.request.urlopen(
                    fl.url + "/metrics", timeout=5).read().decode()
                ejections = fleet_pkg.scrape_samples(mtext).get(
                    "xgbtpu_fleet_slow_ejections_total", 0.0)
                wedged_desc = [m for m in fl.members()["replicas"]
                               if m["replica_id"] == f"r{wedged}"][0]
            except (OSError, ValueError, IndexError) as e:
                print(f"[chaos-fleet] metric scrape failed: {e}",
                      file=sys.stderr)
        fl.stop()

    report = {"mode": "fleet_slow" if args.slow else "fleet",
              "replicas": args.fleet_replicas,
              "duration_sec": args.fleet_secs, "kills": kills,
              "keepalive_restarts": restarts, **counts,
              "non_shed_failures": counts["fail"]}
    if args.slow:
        report.update({
            "wedged_replica": f"r{wedged}",
            "slow_delay_sec": args.slow_delay,
            "slow_ejections": ejections,
            "wedged_final": {k: wedged_desc.get(k)
                             for k in ("ejected", "latency_ewma_ms",
                                       "breaker", "in_rotation")},
        })
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    if args.slow:
        print(f"[chaos-fleet] SLOW mode: {counts['ok']} ok, "
              f"{counts['shed']} shed, {counts['fail']} FAILED; "
              f"{ejections:.0f} ejection(s), wedged final "
              f"{report['wedged_final']} -> {args.out}", file=sys.stderr)
        if counts["fail"] or ejections < 1 or not counts["ok"]:
            return 1
        return 0
    print(f"[chaos-fleet] {counts['ok']} ok, {counts['shed']} shed, "
          f"{counts['fail']} FAILED across {kills} kills / "
          f"{restarts} restarts -> {args.out}", file=sys.stderr)
    if counts["fail"] or kills == 0 or not counts["ok"]:
        return 1
    return 0


def pipeline_mode(args) -> int:
    """Continuous-training chaos: SIGKILL/corrupt the train→gate→
    publish→reload boundary under live fleet traffic (see module
    docstring).  Contract: zero unverified or ungated models ever
    observed by a serving replica."""
    import hashlib
    import subprocess
    import threading
    import urllib.request

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from launch_fleet import FleetLauncher, RetryingPredictClient

    import xgboost_tpu as xgb

    work = args.workdir or tempfile.mkdtemp(prefix="xgbtpu_chaospipe_")
    os.makedirs(work, exist_ok=True)
    rng = np.random.RandomState(args.seed)
    cycles = args.pipe_cycles

    # fresh data per cycle + the fixed holdout window
    holdout = os.path.join(work, "holdout.libsvm")
    _write_libsvm(holdout, n=400, f=6, seed=999)
    for c in range(cycles):
        _write_libsvm(os.path.join(work, f"fresh-{c}.libsvm"),
                      n=400, f=6, seed=100 + c)

    # seed incumbent, published before the fleet boots
    publish = os.path.join(work, "published.model")
    X0 = np.random.RandomState(7).rand(400, 6).astype(np.float32)
    y0 = (X0[:, 0] > 0.5).astype(np.float32)
    xgb.train({"objective": "binary:logistic", "max_depth": 3,
               "eta": 0.4, "silent": 1},
              xgb.DMatrix(X0, label=y0), 3).save_model(publish)
    with open(publish, "rb") as f:
        initial_hash = hashlib.sha256(f.read()).hexdigest()
    wd = os.path.join(work, "wd")

    fl = FleetLauncher(
        publish, replicas=args.fleet_replicas, shared_model=True,
        workdir=os.path.join(work, "fleet"),
        serve_args=["serve_min_bucket=8", "serve_max_bucket=32",
                    "serve_max_wait_ms=1.0", "serve_poll_sec=0.25"],
        router_kwargs={"lease_sec": 3.0, "hc_sec": 0.5}, quiet=True)
    fl.start()
    try:
        print(f"[chaos-pipe] waiting for {args.fleet_replicas} "
              "replicas...", file=sys.stderr)
        fl.wait_ready()
        replica_urls = [m["url"] for m in fl.members()["replicas"]]
    except BaseException:
        fl.stop()
        raise

    observed = set()
    counts = {"ok": 0, "shed": 0, "fail": 0}
    lock = threading.Lock()
    stop = threading.Event()

    def watcher():
        # the contract's witness: what hash is each replica SERVING,
        # sampled continuously across every reload boundary
        while not stop.is_set():
            for u in replica_urls:
                try:
                    with urllib.request.urlopen(u + "/healthz",
                                                timeout=2) as r:
                        h = json.load(r).get("model_hash")
                except (OSError, ValueError):
                    continue
                if h:
                    with lock:
                        observed.add(h)
            time.sleep(0.05)

    body = ",".join(f"{v:.6f}" for v in X0[0]).encode()

    def client():
        conn = RetryingPredictClient(fl.url)
        mine = {"ok": 0, "shed": 0, "fail": 0}
        while not stop.is_set():
            status, _ = conn.post(body)
            key = ("ok" if status == 200
                   else "shed" if status == 503 else "fail")
            mine[key] += 1
        conn.close()
        with lock:
            for k in counts:
                counts[k] += mine[k]

    threads = [threading.Thread(target=watcher)] + [
        threading.Thread(target=client) for _ in range(2)]
    for t in threads:
        t.start()

    def cursor() -> int:
        try:
            with open(os.path.join(wd, "state.json")) as f:
                return int(json.load(f).get("cycle", 0))
        except (OSError, ValueError):
            return 0

    # the chaos menu: faults armed (via env) on a random subset of the
    # train→gate→publish boundary's write/read seams
    fault_menu = [None, None,  # half the attempts run fault-free
                  "bit_flip=256@candidate.model",
                  "torn_write=128@candidate.model",
                  "bit_flip=300@published.model",
                  "torn_write=200@ckpt-",
                  "read_flip=64@published.model"]
    pipe_cmd_base = [
        sys.executable, "-m", "xgboost_tpu", "task=pipeline",
        f"pipeline_publish_path={publish}", f"pipeline_dir={wd}",
        f"pipeline_data={os.path.join(work, 'fresh-{cycle}.libsvm')}",
        f"pipeline_holdout={holdout}", "pipeline_rounds_per_cycle=3",
        "pipeline_max_regression=0.2", "objective=binary:logistic",
        "max_depth=3", "eta=0.4", "silent=1"]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    kills = faults_armed = attempts = 0
    log = open(os.path.join(work, "pipeline.log"), "ab")
    try:
        while cursor() < cycles and attempts < cycles * 5:
            attempts += 1
            remaining = cycles - cursor()
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            fault = fault_menu[rng.randint(len(fault_menu))]
            if fault:
                env["XGBTPU_FAULTS"] = fault
                faults_armed += 1
            p = subprocess.Popen(
                pipe_cmd_base + [f"pipeline_cycles={remaining}"],
                stdout=log, stderr=log, cwd=repo, env=env)
            # SIGKILL at a random moment inside the attempt — startup,
            # mid-train, mid-gate, mid-publish, mid-reload all get hit
            # across runs
            deadline = time.perf_counter() + float(rng.uniform(4.0, 25.0))
            while time.perf_counter() < deadline and p.poll() is None:
                time.sleep(0.25)
            if p.poll() is None:
                p.kill()
                p.wait()
                kills += 1
                print(f"[chaos-pipe] SIGKILL attempt {attempts} "
                      f"(fault={fault}, cursor={cursor()})",
                      file=sys.stderr)
            else:
                print(f"[chaos-pipe] attempt {attempts} exited "
                      f"rc={p.returncode} (fault={fault}, "
                      f"cursor={cursor()})", file=sys.stderr)
        # let the pollers observe the final publish before teardown
        time.sleep(1.5)
    finally:
        stop.set()
        for t in threads:
            t.join(30.0)
        fl.stop()
        log.close()

    gated = set()
    try:
        with open(os.path.join(wd, "gated.log")) as f:
            # a SIGKILL can tear the final ledger line (the append-only
            # contract); a one-token tail is expected, not a crash
            gated = {parts[1] for parts in
                     (line.split() for line in f) if len(parts) >= 2}
    except OSError:
        pass
    allowed = gated | {initial_hash}
    violations = sorted(observed - allowed)
    report = {
        "mode": "pipeline", "cycles": cycles,
        "cycles_completed": cursor(), "attempts": attempts,
        "kills": kills, "faults_armed": faults_armed,
        "replicas": args.fleet_replicas,
        "gated_hashes": len(gated),
        "observed_hashes": len(observed),
        "published_observed": len(observed & gated),
        "ungated_or_unverified_observed": len(violations),
        "violations": violations, **counts,
        "non_shed_failures": counts["fail"],
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"[chaos-pipe] {report['cycles_completed']}/{cycles} cycles, "
          f"{kills} kills, {faults_armed} faults, "
          f"{len(observed)} hashes observed "
          f"({len(violations)} VIOLATIONS), {counts['ok']} ok / "
          f"{counts['fail']} failed requests -> {args.out}",
          file=sys.stderr)
    ok = (not violations and counts["fail"] == 0
          and report["cycles_completed"] >= cycles
          and report["published_observed"] >= 1 and kills >= 1)
    return 0 if ok else 1


def stream_mode(args) -> int:
    """Streaming chaos: SIGKILL ``task=stream`` trainers mid-micro-
    cycle while a producer keeps the spool moving and the feature
    distribution shifts mid-run (see module docstring).  SIGKILL-only
    — the stream contract under test is replay determinism.
    Contracts: zero ungated publish-path hashes, and a fresh-workdir
    replay over the same spool is bit-identical."""
    import hashlib
    import subprocess
    import threading

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import xgboost_tpu as xgb
    from xgboost_tpu.stream import StreamBacklogFull, StreamDataSource

    work = args.workdir or tempfile.mkdtemp(prefix="xgbtpu_chaosstream_")
    os.makedirs(work, exist_ok=True)
    rng = np.random.RandomState(args.seed)
    cycles = args.stream_cycles
    stream_dir = os.path.join(work, "stream-in")
    # bit-identity across the chaos run and the fresh-workdir replay
    # requires IDENTICAL command strings: the CLI cascades every param
    # into the learner (reference xgboost_main.cpp behavior) and the
    # model header serializes the param dict, so a differing
    # stream_workdir= path would differ the published bytes.  Each run
    # therefore gets its own cwd holding relative wd/ + published.model
    # and a symlink to the one shared spool.
    run_chaos = os.path.join(work, "run-chaos")
    run_replay = os.path.join(work, "run-replay")
    os.makedirs(stream_dir, exist_ok=True)
    for d in (run_chaos, run_replay):
        os.makedirs(d, exist_ok=True)
        link = os.path.join(d, "stream-in")
        if not os.path.lexists(link):
            os.symlink(os.path.join("..", "stream-in"), link)
    wd = os.path.join(run_chaos, "wd")
    publish = os.path.join(run_chaos, "published.model")

    # seed incumbent at the publish path — the warm-start lineage the
    # replay later reproduces from the same bytes
    X0 = np.random.RandomState(7).rand(400, 6).astype(np.float32)
    y0 = (X0[:, 0] + 0.25 * X0[:, 1] > 0.6).astype(np.float32)
    xgb.train({"objective": "binary:logistic", "max_depth": 3,
               "eta": 0.4, "silent": 1},
              xgb.DMatrix(X0, label=y0), 3).save_model(publish)
    with open(publish, "rb") as f:
        seed_bytes = f.read()
    initial_hash = hashlib.sha256(seed_bytes).hexdigest()

    stop = threading.Event()
    pushed = [0]

    def producer():
        # batch CONTENT is deterministic (seeded by the producer's own
        # counter); batch→cycle composition is timing-dependent, which
        # is the point — the manifests pin it for replay.  The
        # distribution shifts a third of the way in so drift fires and
        # a cut refresh lands under chaos.
        src = StreamDataSource(stream_dir)
        i = 0
        while not stop.is_set() and i < 400:
            r = np.random.RandomState(1000 + i)
            shift = 0.35 if i >= 6 else 0.0
            X = (r.rand(160, 6) + shift).astype(np.float32)
            y = (X[:, 0] + 0.25 * X[:, 1]
                 > 0.6 + 1.25 * shift).astype(np.float32)
            try:
                src.push(X, y)
            except StreamBacklogFull:
                time.sleep(0.5)
                continue
            i += 1
            pushed[0] = i
            time.sleep(0.15)

    observed = set()

    def watcher():
        # the contract's witness: every complete byte-state the publish
        # path ever holds (atomic_write => never a torn file)
        while not stop.is_set():
            try:
                with open(publish, "rb") as f:
                    observed.add(hashlib.sha256(f.read()).hexdigest())
            except OSError:
                pass
            time.sleep(0.05)

    threads = [threading.Thread(target=producer),
               threading.Thread(target=watcher)]
    for t in threads:
        t.start()

    def cursor(d=None) -> int:
        try:
            with open(os.path.join(d or wd, "state.json")) as f:
                return int(json.load(f).get("cycle", 0))
        except (OSError, ValueError):
            return 0

    def cmd():
        # relative paths, and the SAME string every attempt (chaos and
        # replay): the CLI cascades every param into the learner and
        # the model header records the param dict, so a per-attempt
        # stream_cycles=remaining would make otherwise-identical
        # models hash differently.  The driver, not the arg, decides
        # when a run is done — it SIGKILLs the trainer once the cycle
        # cursor reaches the target.
        return [
            sys.executable, "-m", "xgboost_tpu", "task=stream",
            "stream_publish_path=published.model", "stream_workdir=wd",
            "stream_dir=stream-in", f"stream_cycles={cycles}",
            "stream_rounds_per_cycle=3", "stream_min_batches=1",
            "stream_max_batches=2", "stream_max_regression=0.5",
            "stream_sleep_sec=0.1", "objective=binary:logistic",
            "max_depth=3", "eta=0.4", "ema_fs=0.9", "silent=1"]

    def ledger(workdir):
        """(all gated hashes, cycle -> LAST gated hash).  A killed-
        then-resumed cycle re-gates, so the raw ledger may hold
        several lines per cycle; the last one is the publish."""
        all_hashes, last = set(), {}
        try:
            with open(os.path.join(workdir, "gated.log")) as f:
                # a SIGKILL can tear the final line; skip short tails
                for parts in (line.split() for line in f):
                    if len(parts) >= 2:
                        try:
                            last[int(parts[0])] = parts[1]
                        except ValueError:
                            continue
                        all_hashes.add(parts[1])
        except OSError:
            pass
        return all_hashes, last

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    kills = attempts = 0
    log = open(os.path.join(work, "stream.log"), "ab")
    target = cycles  # extended until the kill quota is met
    try:
        while (cursor() < target or kills < 3) and attempts < 30:
            if cursor() >= target:
                target += 2
                print(f"[chaos-stream] kill quota unmet, extending "
                      f"target to {target} cycles", file=sys.stderr)
            attempts += 1
            p = subprocess.Popen(cmd(), stdout=log, stderr=log,
                                 cwd=run_chaos, env=env)
            # short deadlines until the kill quota is met (startup +
            # the first cycle run longer than this, so SIGKILLs land
            # inside live micro-cycle work), generous afterwards
            lo, hi = (5.0, 12.0) if kills < 3 else (8.0, 25.0)
            deadline = time.perf_counter() + float(rng.uniform(lo, hi))
            reached = False
            while time.perf_counter() < deadline and p.poll() is None:
                if cursor() >= target:
                    reached = True
                    break
                time.sleep(0.25)
            if p.poll() is None:
                p.kill()
                p.wait()
                if reached:
                    print(f"[chaos-stream] attempt {attempts} reached "
                          f"target {target}, stopped", file=sys.stderr)
                else:
                    kills += 1
                    print(f"[chaos-stream] SIGKILL attempt {attempts} "
                          f"(cursor={cursor()}, pushed={pushed[0]})",
                          file=sys.stderr)
            else:
                print(f"[chaos-stream] attempt {attempts} exited "
                      f"rc={p.returncode} (cursor={cursor()})",
                      file=sys.stderr)
        time.sleep(0.5)  # let the watcher observe the final publish
        stop.set()
        for t in threads:
            t.join(30.0)
        completed = cursor()
        gated, chaos_last = ledger(wd)

        # bit-identical replay: a FRESH run dir + publish path seeded
        # with the same incumbent bytes, consuming the SAME spool with
        # the IDENTICAL command string
        wd2 = os.path.join(run_replay, "wd")
        pub2 = os.path.join(run_replay, "published.model")
        with open(pub2, "wb") as f:
            f.write(seed_bytes)
        replay_rc = None
        if completed > 0:
            print(f"[chaos-stream] replaying {completed} cycles in a "
                  "fresh workdir...", file=sys.stderr)
            guard = 0
            while cursor(wd2) < completed and guard < 10:
                guard += 1
                p = subprocess.Popen(cmd(), stdout=log, stderr=log,
                                     cwd=run_replay, env=env)
                t0 = time.perf_counter()
                while (p.poll() is None
                       and time.perf_counter() - t0 < 300.0):
                    if cursor(wd2) >= completed:
                        break
                    time.sleep(0.25)
                if p.poll() is None:
                    p.kill()
                p.wait()
                replay_rc = p.returncode
    finally:
        stop.set()
        for t in threads:
            t.join(30.0)
        log.close()

    # per-cycle published-candidate hashes, both runs restricted to the
    # cycles the chaos run completed (either side may have started one
    # cycle past its stop point — that tail is not part of the
    # contract)
    _, replay_last = ledger(wd2)
    chaos_map = {c: h for c, h in chaos_last.items() if c < completed}
    replay_map = {c: h for c, h in replay_last.items() if c < completed}
    seq_identical = bool(chaos_map) and replay_map == chaos_map
    last_cycle = max(chaos_map) if chaos_map else None
    final_identical = (last_cycle is not None
                       and replay_map.get(last_cycle)
                       == chaos_map[last_cycle])

    drift_fires = refreshes = 0
    plans_dir = os.path.join(wd, "plans")
    if os.path.isdir(plans_dir):
        for fn in sorted(os.listdir(plans_dir)):
            if fn.startswith("plan-") and fn.endswith(".json"):
                try:
                    with open(os.path.join(plans_dir, fn)) as f:
                        plan = json.load(f)
                except (OSError, ValueError):
                    continue
                drift_fires += bool(plan.get("fired"))
                refreshes += bool(plan.get("refresh"))

    allowed = gated | {initial_hash}
    violations = sorted(observed - allowed)
    report = {
        "mode": "stream", "cycles": cycles,
        "cycles_target_final": target,
        "cycles_completed": completed, "attempts": attempts,
        "kills": kills, "batches_pushed": pushed[0],
        "gated_hashes": len(gated),
        "observed_hashes": len(observed),
        "published_observed": len(observed & gated),
        "ungated_or_unverified_observed": len(violations),
        "violations": violations,
        "drift_fires": drift_fires, "cut_refreshes": refreshes,
        "replay_rc": replay_rc,
        "replay_cycles": cursor(wd2),
        "replay_gated_sequence_identical": seq_identical,
        "replay_final_bytes_identical": final_identical,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"[chaos-stream] {completed}/{cycles} cycles, {kills} kills, "
          f"{len(observed)} hashes observed "
          f"({len(violations)} VIOLATIONS), {drift_fires} drift fires / "
          f"{refreshes} cut refreshes, replay identical="
          f"{seq_identical and final_identical} -> {args.out}",
          file=sys.stderr)
    ok = (not violations and completed >= cycles and kills >= 3
          and seq_identical and final_identical
          and report["published_observed"] >= 1)
    return 0 if ok else 1


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def catalog_mode(args) -> int:
    """Multi-tenant catalog chaos: two width-divergent tenants share a
    catalog fleet while per-tenant training lanes publish, a killer
    SIGKILLs lane trainers at random AND the router itself (which must
    restart from its membership snapshot with zero non-shed client
    failures).  Contract: the zero-ungated-models invariant holds PER
    TENANT, and killing one tenant's trainer never stalls the other."""
    import hashlib
    import subprocess
    import threading
    import urllib.error
    import urllib.request

    import xgboost_tpu as xgb

    work = args.workdir or tempfile.mkdtemp(prefix="xgbtpu_chaoscat_")
    os.makedirs(work, exist_ok=True)
    rng = np.random.RandomState(args.seed)
    cycles = args.pipe_cycles
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    # width-DIVERGENT tenants: different feature counts force different
    # compiled buckets, so cross-tenant bleed would be loud
    tenants = {"a": (6, 7), "b": (4, 21)}  # name -> (features, seed)
    pub, wd, init_hash, body = {}, {}, {}, {}
    for t, (nf, seed) in tenants.items():
        _write_libsvm(os.path.join(work, f"holdout-{t}.libsvm"),
                      n=400, f=nf, seed=900 + nf)
        for c in range(cycles):
            _write_libsvm(os.path.join(work, f"fresh-{t}-{c}.libsvm"),
                          n=400, f=nf, seed=seed * 100 + c)
        X0 = np.random.RandomState(seed).rand(400, nf).astype(np.float32)
        y0 = (X0[:, 0] > 0.5).astype(np.float32)
        pub[t] = os.path.join(work, f"published-{t}.model")
        xgb.train({"objective": "binary:logistic", "max_depth": 3,
                   "eta": 0.4, "silent": 1},
                  xgb.DMatrix(X0, label=y0), 3).save_model(pub[t])
        with open(pub[t], "rb") as f:
            init_hash[t] = hashlib.sha256(f.read()).hexdigest()
        wd[t] = os.path.join(work, f"wd-{t}")
        body[t] = ",".join(f"{v:.6f}" for v in X0[0]).encode()

    # the router is a SUBPROCESS here (unlike the other fleet modes):
    # the chaos menu includes SIGKILLing it, and the restart must
    # rebuild membership from the CRC-footered fleet_state_path snapshot
    port = _free_port()
    url = f"http://127.0.0.1:{port}"
    state_path = os.path.join(work, "router.state")
    router_cmd = [sys.executable, "-m", "xgboost_tpu",
                  "task=fleet_router", "fleet_host=127.0.0.1",
                  f"fleet_port={port}", "fleet_lease_sec=3.0",
                  "fleet_hc_sec=0.5", f"fleet_state_path={state_path}",
                  "silent=1"]

    def spawn_router():
        log = open(os.path.join(work, "router.log"), "ab")
        p = subprocess.Popen(router_cmd, stdout=log, stderr=log,
                             cwd=repo, env=env)
        log.close()
        return p

    manifest = ",".join(f"{t}={pub[t]}" for t in tenants)
    replicas = {}

    def spawn_replica(i):
        log = open(os.path.join(work, f"replica-{i}.log"), "ab")
        replicas[i] = subprocess.Popen(
            [sys.executable, "-m", "xgboost_tpu", "task=serve",
             f"catalog={manifest}", "serve_port=0",
             "serve_host=127.0.0.1", f"serve_router_url={url}",
             f"serve_replica_id=c{i}", "serve_min_bucket=8",
             "serve_max_bucket=32", "serve_max_wait_ms=1.0",
             "serve_poll_sec=0.25", "silent=1"],
            stdout=log, stderr=log, cwd=repo, env=env)
        log.close()

    def wait_members(n, timeout=180.0):
        deadline = time.perf_counter() + timeout
        got = 0
        while time.perf_counter() < deadline:
            try:
                with urllib.request.urlopen(url + "/fleet/members",
                                            timeout=5) as r:
                    mem = json.load(r)
                got = mem["in_rotation"]
                if got >= n:
                    return mem
            except (OSError, ValueError):
                pass
            time.sleep(0.25)
        raise TimeoutError(f"catalog fleet not ready: {got}/{n} "
                           f"(see {work}/replica-*.log)")

    router = spawn_router()
    n_reps = args.fleet_replicas
    for i in range(n_reps):
        spawn_replica(i)
    try:
        print(f"[chaos-cat] waiting for {n_reps} catalog replicas...",
              file=sys.stderr)
        replica_urls = [m["url"]
                        for m in wait_members(n_reps)["replicas"]]
    except BaseException:
        for p in list(replicas.values()) + [router]:
            p.kill()
        raise

    observed = {t: set() for t in tenants}
    counts = {t: {"ok": 0, "shed": 0, "fail": 0} for t in tenants}
    lock = threading.Lock()
    stop = threading.Event()

    def watcher():
        # per-tenant witness: which hash is each replica serving FOR
        # EACH MODEL, sampled straight off the replicas (router-down
        # windows must not blind the contract)
        while not stop.is_set():
            for u in replica_urls:
                try:
                    with urllib.request.urlopen(u + "/healthz",
                                                timeout=2) as r:
                        rows = json.load(r).get("models", {})
                except (OSError, ValueError):
                    continue
                with lock:
                    for t in tenants:
                        h = (rows.get(t) or {}).get("model_hash")
                        if h:
                            observed[t].add(h)
            time.sleep(0.05)

    def post(path, data, patience=60.0):
        # transport failures retry until the patience deadline: a
        # SIGKILL'd router is allowed a restart window, but every
        # request must STILL end in a 200 or an explicit shed
        deadline = time.perf_counter() + patience
        while True:
            req = urllib.request.Request(url + path, data=data)
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    r.read()
                    return 200
            except urllib.error.HTTPError as e:
                e.read()
                return e.code
            except OSError:
                if time.perf_counter() >= deadline:
                    return None
                time.sleep(0.2)

    def client(t):
        mine = {"ok": 0, "shed": 0, "fail": 0}
        while not stop.is_set():
            status = post(f"/predict?model={t}", body[t])
            mine["ok" if status == 200
                 else "shed" if status in (429, 503, 504)
                 else "fail"] += 1
        with lock:
            for k in mine:
                counts[t][k] += mine[k]

    threads = [threading.Thread(target=watcher)] + [
        threading.Thread(target=client, args=(t,)) for t in tenants]
    for t_ in threads:
        t_.start()

    def cursor(t):
        try:
            with open(os.path.join(wd[t], "state.json")) as f:
                return int(json.load(f).get("cycle", 0))
        except (OSError, ValueError):
            return 0

    def lane_cmd(t, remaining):
        data = os.path.join(work, "fresh-" + t + "-{cycle}.libsvm")
        return [sys.executable, "-m", "xgboost_tpu", "task=pipeline",
                f"pipeline_publish_path={pub[t]}",
                f"pipeline_dir={wd[t]}", f"pipeline_data={data}",
                f"pipeline_holdout={os.path.join(work, f'holdout-{t}.libsvm')}",
                "pipeline_rounds_per_cycle=3",
                "pipeline_max_regression=0.2",
                f"pipeline_cycles={remaining}",
                "objective=binary:logistic", "max_depth=3", "eta=0.4",
                "silent=1"]

    fault_menu = [None, None, None,
                  "bit_flip=256@candidate.model",
                  "torn_write=128@candidate.model",
                  "read_flip=64@published-"]
    lanes = {}
    lane_logs = {t: open(os.path.join(work, f"pipeline-{t}.log"), "ab")
                 for t in tenants}
    kills = router_kills = attempts = faults_armed = 0
    router_restart_sec = None
    max_attempts = 8 + cycles * 6
    try:
        while (attempts < max_attempts
               and any(cursor(t) < cycles for t in tenants)):
            for t in tenants:
                p = lanes.get(t)
                if cursor(t) >= cycles or (p is not None
                                           and p.poll() is None):
                    continue
                attempts += 1
                lenv = dict(env)
                fault = fault_menu[rng.randint(len(fault_menu))]
                if fault:
                    lenv["XGBTPU_FAULTS"] = fault
                    faults_armed += 1
                lanes[t] = subprocess.Popen(
                    lane_cmd(t, cycles - cursor(t)),
                    stdout=lane_logs[t], stderr=lane_logs[t],
                    cwd=repo, env=lenv)
                print(f"[chaos-cat] lane {t} attempt (fault={fault}, "
                      f"cursor={cursor(t)})", file=sys.stderr)
            time.sleep(float(rng.uniform(8.0, 20.0)))
            live = [t for t, p in lanes.items()
                    if p is not None and p.poll() is None]
            if live and (kills == 0 or rng.rand() < 0.7):
                # first opportunity always kills (the lane-kill leg is
                # part of the contract); later windows roll the dice
                t = live[rng.randint(len(live))]
                lanes[t].kill()
                lanes[t].wait()
                kills += 1
                print(f"[chaos-cat] SIGKILL lane {t} "
                      f"(cursor={cursor(t)})", file=sys.stderr)
            if router_kills == 0 and attempts >= 2:
                # the router restart leg: SIGKILL the front door under
                # live traffic; the replacement restores membership
                # from the snapshot and clients ride through on retry
                router.kill()
                router.wait()
                router_kills += 1
                t0 = time.perf_counter()
                router = spawn_router()
                wait_members(n_reps)
                router_restart_sec = round(time.perf_counter() - t0, 2)
                print(f"[chaos-cat] router SIGKILL -> restored "
                      f"{n_reps} members in {router_restart_sec}s",
                      file=sys.stderr)
        # let the replica pollers observe the final publishes
        time.sleep(1.5)
    finally:
        stop.set()
        for t_ in threads:
            t_.join(90.0)
        for p in list(lanes.values()) + list(replicas.values()):
            if p.poll() is None:
                p.terminate()
        if router.poll() is None:
            router.terminate()
        for p in list(lanes.values()) + list(replicas.values()) + [router]:
            try:
                p.wait(20.0)
            except subprocess.TimeoutExpired:
                p.kill()
        for f in lane_logs.values():
            f.close()

    per_tenant = {}
    total_fail = 0
    for t in tenants:
        gated = set()
        try:
            with open(os.path.join(wd[t], "gated.log")) as f:
                gated = {parts[1] for parts in
                         (line.split() for line in f) if len(parts) >= 2}
        except OSError:
            pass
        violations = sorted(observed[t] - (gated | {init_hash[t]}))
        total_fail += counts[t]["fail"]
        per_tenant[t] = {
            "cycles_completed": cursor(t),
            "gated_hashes": len(gated),
            "observed_hashes": len(observed[t]),
            "published_observed": len(observed[t] & gated),
            "ungated_observed": len(violations),
            "violations": violations, **counts[t]}
    report = {
        "mode": "catalog", "cycles": cycles,
        "replicas": n_reps, "attempts": attempts, "kills": kills,
        "router_kills": router_kills,
        "router_restart_sec": router_restart_sec,
        "faults_armed": faults_armed,
        "tenants": per_tenant, "non_shed_failures": total_fail}
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    done = all(per_tenant[t]["cycles_completed"] >= cycles
               for t in tenants)
    clean = all(not per_tenant[t]["violations"]
                and per_tenant[t]["ok"] > 0
                and per_tenant[t]["published_observed"] >= 1
                for t in tenants)
    print(f"[chaos-cat] cycles "
          + "/".join(f"{t}:{per_tenant[t]['cycles_completed']}"
                     for t in tenants)
          + f", {kills} lane kills, {router_kills} router kills, "
          f"{total_fail} non-shed failures -> {args.out}",
          file=sys.stderr)
    ok = (done and clean and total_fail == 0
          and kills >= 1 and router_kills >= 1)
    return 0 if ok else 1


def placer_mode(args) -> int:
    """Autonomous-placement chaos (see module docstring, ``--placer``):
    SIGKILL replicas mid-rebalance AND the placer mid-push; assert zero
    non-shed failures, no tenant ever orphaned, and that a resumed
    placer converges to the target it snapshotted."""
    import hashlib
    import subprocess
    import threading
    import urllib.error
    import urllib.request

    import xgboost_tpu as xgb
    from xgboost_tpu.reliability.integrity import verify_model_bytes

    work = args.workdir or tempfile.mkdtemp(prefix="xgbtpu_chaosplc_")
    os.makedirs(work, exist_ok=True)
    rng = np.random.RandomState(args.seed)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    # one model file, four tenant names: placement chaos is about WHERE
    # entries live, not what they predict
    model = os.path.join(work, "model.bin")
    X0 = np.random.RandomState(7).rand(300, 6).astype(np.float32)
    y0 = (X0[:, 0] + X0[:, 1] > 1.0).astype(np.float32)
    xgb.train({"objective": "binary:logistic", "max_depth": 3,
               "eta": 0.4, "silent": 1},
              xgb.DMatrix(X0, label=y0), 3).save_model(model)
    tenants = [f"t{i}" for i in range(1, 5)]
    manifest = ",".join(f"{t}={model}" for t in tenants)
    body = ",".join(f"{v:.6f}" for v in X0[0]).encode()

    port = _free_port()
    url = f"http://127.0.0.1:{port}"
    state_path = os.path.join(work, "router.state")
    plan_path = os.path.join(work, "placer.plan")

    rlog = open(os.path.join(work, "router.log"), "ab")
    router = subprocess.Popen(
        [sys.executable, "-m", "xgboost_tpu", "task=fleet_router",
         "fleet_host=127.0.0.1", f"fleet_port={port}",
         "fleet_lease_sec=3.0", "fleet_hc_sec=0.5",
         f"fleet_state_path={state_path}", "silent=1"],
        stdout=rlog, stderr=rlog, cwd=repo, env=env)
    rlog.close()

    n_reps = args.fleet_replicas
    replicas = {}
    next_idx = [0]

    def spawn_replica():
        # a FRESH identity per spawn: a SIGKILL'd replica's lease must
        # EXPIRE (no re-register under the old id), so re-homing is the
        # placer's job, not the tracker recover path's
        i = next_idx[0]
        next_idx[0] += 1
        log = open(os.path.join(work, f"replica-{i}.log"), "ab")
        replicas[i] = subprocess.Popen(
            [sys.executable, "-m", "xgboost_tpu", "task=serve",
             f"model_in={model}", "serve_port=0", "serve_host=127.0.0.1",
             f"serve_router_url={url}", f"serve_replica_id=p{i}",
             "serve_catalog_mb=64", "serve_min_bucket=8",
             "serve_max_bucket=32", "serve_max_wait_ms=1.0",
             "serve_poll_sec=0", "serve_warmup=0", "silent=1"],
            stdout=log, stderr=log, cwd=repo, env=env)
        log.close()
        return i

    placer = [None]

    def spawn_placer():
        log = open(os.path.join(work, "placer.log"), "ab")
        placer[0] = subprocess.Popen(
            [sys.executable, "-m", "xgboost_tpu", "task=placer",
             f"placer_router_url={url}", f"placer_catalog={manifest}",
             f"placer_plan_path={plan_path}", "placer_tick_sec=0.4",
             "placer_lease_sec=3.0", "placer_replication=2",
             "silent=1"],
            stdout=log, stderr=log, cwd=repo, env=env)
        log.close()

    def members(timeout=5.0):
        with urllib.request.urlopen(url + "/fleet/members",
                                    timeout=timeout) as r:
            return json.load(r)

    def hosted_counts(mem):
        out = {t: 0 for t in tenants}
        for d in mem.get("replicas", []):
            if not d.get("in_rotation"):
                continue
            for t in tenants:
                if t in (d.get("models") or []):
                    out[t] += 1
        return out

    def wait_placed(min_hosts, timeout=180.0):
        deadline = time.perf_counter() + timeout
        last = {}
        while time.perf_counter() < deadline:
            try:
                last = hosted_counts(members())
                if all(last.get(t, 0) >= min_hosts for t in tenants):
                    return last
            except (OSError, ValueError):
                pass
            time.sleep(0.25)
        raise TimeoutError(f"placement never converged: {last} "
                           f"(see {work}/placer.log)")

    def read_plan_snapshot():
        with open(plan_path, "rb") as f:
            state = json.loads(verify_model_bytes(f.read(), plan_path))
        return state["target"]

    def router_plan(timeout=5.0):
        with urllib.request.urlopen(url + "/placer/status",
                                    timeout=timeout) as r:
            return json.load(r).get("plan") or {}

    counts = {t: {"ok": 0, "shed": 0, "fail": 0} for t in tenants}
    orphan_windows = []
    lock = threading.Lock()
    stop = threading.Event()
    watch = threading.Event()   # set once initial placement landed

    def orphan_watcher():
        # the availability contract: from first placement on, every
        # sample of the router's view shows >=1 in-rotation advertiser
        # per tenant (router-down windows don't blind the watcher —
        # there is no router kill leg in this mode)
        while not stop.is_set():
            if watch.is_set():
                try:
                    mem = members(timeout=2.0)
                except (OSError, ValueError):
                    time.sleep(0.1)
                    continue
                counts_now = hosted_counts(mem)
                bad = sorted(t for t, n in counts_now.items() if n < 1)
                if bad:
                    with lock:
                        orphan_windows.append(
                            {"t": round(time.perf_counter(), 2),
                             "orphaned": bad})
            time.sleep(0.05)

    def post(path, data, patience=60.0):
        deadline = time.perf_counter() + patience
        while True:
            req = urllib.request.Request(url + path, data=data)
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    r.read()
                    return 200
            except urllib.error.HTTPError as e:
                e.read()
                return e.code
            except OSError:
                if time.perf_counter() >= deadline:
                    return None
                time.sleep(0.2)

    def client(t):
        mine = {"ok": 0, "shed": 0, "fail": 0}
        while not stop.is_set():
            if not watch.is_set():
                time.sleep(0.1)
                continue
            status = post(f"/predict?model={t}", body)
            mine["ok" if status == 200
                 else "shed" if status in (429, 503, 504)
                 else "fail"] += 1
        with lock:
            for k in mine:
                counts[t][k] += mine[k]

    threads = [threading.Thread(target=orphan_watcher)] + [
        threading.Thread(target=client, args=(t,)) for t in tenants]
    for t_ in threads:
        t_.start()

    replica_kills = placer_kills = 0
    resume_checks = []
    for _ in range(n_reps):
        spawn_replica()
    spawn_placer()
    try:
        print(f"[chaos-placer] waiting for initial placement "
              f"({n_reps} replicas x 4 tenants, replication=2)...",
              file=sys.stderr)
        wait_placed(min_hosts=2)
        watch.set()
        time.sleep(2.0)                      # traffic under steady state

        # ---- leg 1: SIGKILL a replica mid-rebalance, placer re-homes.
        # The restart uses a FRESH replica id, so the placer sees a
        # genuinely changed fleet both times.
        victim = sorted(replicas)[int(rng.randint(len(replicas)))]
        replicas[victim].kill()
        replicas[victim].wait()
        replicas.pop(victim)
        replica_kills += 1
        print(f"[chaos-placer] SIGKILL replica #{victim}",
              file=sys.stderr)
        spawn_replica()                      # keepalive replacement
        # ---- leg 2: SIGKILL the placer MID-PUSH — right inside the
        # re-homing window the replica kill just opened
        time.sleep(float(rng.uniform(0.3, 0.9)))
        placer[0].kill()
        placer[0].wait()
        placer_kills += 1
        print("[chaos-placer] SIGKILL placer mid-push", file=sys.stderr)
        spawn_placer()
        wait_placed(min_hosts=2)             # resumed placer converges
        time.sleep(2.0)

        # ---- leg 3: quiet-window placer kill pins resume determinism:
        # same fleet + snapshotted plan -> the resumed placer must
        # record the SAME target on the router
        before_snapshot = read_plan_snapshot()
        before_plan = router_plan().get("target") or {}
        placer[0].kill()
        placer[0].wait()
        placer_kills += 1
        print("[chaos-placer] SIGKILL placer (quiet window)",
              file=sys.stderr)
        spawn_placer()
        deadline = time.perf_counter() + 60.0
        after_plan = {}
        while time.perf_counter() < deadline:
            try:
                after_plan = router_plan().get("target") or {}
            except (OSError, ValueError):
                after_plan = {}
            if after_plan:
                break
            time.sleep(0.25)
        resume_checks.append({
            "snapshot_equals_recorded": before_snapshot == before_plan,
            "resumed_equals_snapshot": after_plan == before_snapshot})
        wait_placed(min_hosts=2)
        time.sleep(2.0)                      # post-chaos steady traffic
    finally:
        stop.set()
        for t_ in threads:
            t_.join(90.0)
        procs = list(replicas.values()) + [router]
        if placer[0] is not None:
            procs.append(placer[0])
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(20.0)
            except subprocess.TimeoutExpired:
                p.kill()

    total_fail = sum(c["fail"] for c in counts.values())
    total_ok = sum(c["ok"] for c in counts.values())
    resumed_plan_equal = bool(resume_checks) and all(
        rc["resumed_equals_snapshot"] for rc in resume_checks)
    report = {
        "mode": "placer", "replicas": n_reps, "tenants": len(tenants),
        "replication": 2, "replica_kills": replica_kills,
        "placer_kills": placer_kills,
        "per_tenant": counts, "non_shed_failures": total_fail,
        "orphan_windows": orphan_windows[:20],
        "orphan_window_count": len(orphan_windows),
        "resume_checks": resume_checks,
        "resumed_plan_equal": resumed_plan_equal,
        "model_sha256": hashlib.sha256(
            open(model, "rb").read()).hexdigest(),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"[chaos-placer] {replica_kills} replica kills, "
          f"{placer_kills} placer kills, {total_ok} ok / "
          f"{total_fail} non-shed failures, "
          f"{len(orphan_windows)} orphan windows, resumed_plan_equal="
          f"{resumed_plan_equal} -> {args.out}", file=sys.stderr)
    ok = (total_fail == 0 and not orphan_windows and total_ok > 0
          and replica_kills >= 1 and placer_kills >= 2
          and resumed_plan_equal
          and all(c["ok"] > 0 for c in counts.values()))
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--workdir", default=None,
                    help="scratch dir (default: a fresh tempdir)")
    ap.add_argument("--fleet", action="store_true",
                    help="serving-tier mode: kill/restart replicas "
                         "under live traffic (see module docstring)")
    ap.add_argument("--fleet-replicas", type=int, default=3)
    ap.add_argument("--fleet-secs", type=float, default=20.0,
                    help="--fleet: how long to drive traffic")
    ap.add_argument("--kill-every", type=float, default=4.0,
                    help="--fleet: seconds between replica kills")
    ap.add_argument("--slow", action="store_true",
                    help="--fleet variant: wedge one replica with the "
                         "slow_replica fault instead of killing any; "
                         "asserts latency ejection routes around it "
                         "with zero non-shed failures")
    ap.add_argument("--slow-delay", type=float, default=0.6,
                    help="--slow: seconds each wedged predict sleeps")
    ap.add_argument("--train", action="store_true",
                    help="stall-failure training mode: stall mock "
                         "coordinates + heartbeat-watchdog gang "
                         "restarts, bit-identical resume "
                         "(TRAIN_CHAOS.json; see module docstring)")
    ap.add_argument("--stall-window", type=float, default=4.0,
                    help="--train: launcher --watchdog-stall-sec; must "
                         "cover startup + one fused segment dispatch "
                         "(compile included on the first trial)")
    ap.add_argument("--local-devices", type=int, default=2,
                    help="--train: in-process device count for the "
                         "fused_mesh cell (dsplit=row over an "
                         "N-virtual-CPU-device mesh)")
    ap.add_argument("--degrade", action="store_true",
                    help="--train addition: run the elastic degraded-"
                         "mesh cells (host_loss degrade + grow-back, "
                         "coordinator SIGKILL + re-adoption, partition "
                         "self-fence with the ring split-brain "
                         "assertion); merged into TRAIN_CHAOS.json "
                         "under 'degrade'.  --runs 0 skips the stall "
                         "cells and runs only these.")
    ap.add_argument("--selftest", action="store_true",
                    help="fast subprocess-free logic checks (partition "
                         "clock, degrade ladder, state roundtrip, "
                         "fail-loud fault parsing, lineage scanner); "
                         "prints 'selftest: OK'")
    ap.add_argument("--pipeline", action="store_true",
                    help="continuous-training mode: SIGKILL/corrupt "
                         "the train→gate→publish→reload boundary under "
                         "live fleet traffic (see module docstring)")
    ap.add_argument("--pipe-cycles", type=int, default=4,
                    help="--pipeline/--catalog: cycles each pipeline "
                         "(lane) must complete")
    ap.add_argument("--stream", action="store_true",
                    help="streaming mode: SIGKILL task=stream "
                         "trainers mid-micro-cycle while a producer "
                         "spools drifting batches; zero-ungated + "
                         "bit-identical fresh-workdir replay "
                         "(STREAM_CHAOS.json; see module docstring)")
    ap.add_argument("--stream-cycles", type=int, default=6,
                    help="--stream: micro-cycles the trainer must "
                         "complete")
    ap.add_argument("--catalog", action="store_true",
                    help="multi-tenant catalog mode: two width-"
                         "divergent tenants on a catalog fleet, "
                         "per-tenant training lanes, SIGKILLs of lane "
                         "trainers AND the router (snapshot restart); "
                         "per-tenant zero-ungated contract "
                         "(CATALOG_CHAOS.json; see module docstring)")
    ap.add_argument("--placer", action="store_true",
                    help="autonomous-placement mode: router + default-"
                         "only replicas + task=placer subprocess; "
                         "SIGKILLs replicas mid-rebalance and the "
                         "placer mid-push; zero non-shed failures, no "
                         "tenant ever orphaned, resumed placer "
                         "converges to its snapshotted plan "
                         "(PLACER_CHAOS.json; see module docstring)")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if args.degrade and not args.train:
        ap.error("--degrade composes with --train "
                 "(use --train --degrade, optionally --runs 0)")
    if args.out is None:
        args.out = ("STREAM_CHAOS.json" if args.stream
                    else "PLACER_CHAOS.json" if args.placer
                    else "CATALOG_CHAOS.json" if args.catalog
                    else "PIPELINE_CHAOS.json" if args.pipeline
                    else "CHAOS_fleet_slow.json"
                    if args.fleet and args.slow
                    else "CHAOS_fleet.json" if args.fleet
                    else "TRAIN_CHAOS.json" if args.train
                    else "CHAOS.json")
    if args.stream:
        return stream_mode(args)
    if args.placer:
        return placer_mode(args)
    if args.catalog:
        return catalog_mode(args)
    if args.pipeline:
        return pipeline_mode(args)
    if args.fleet:
        return fleet_mode(args)
    if args.train:
        return train_stall_mode(args)

    from xgboost_tpu.cli import main as cli_main
    from xgboost_tpu.obs import reliability_metrics
    from xgboost_tpu.reliability import faults

    work = args.workdir or tempfile.mkdtemp(prefix="xgbtpu_chaos_")
    os.makedirs(work, exist_ok=True)
    data = os.path.join(work, "train.libsvm")
    _write_libsvm(data, seed=args.seed)
    common = [f"data={data}", "task=train", f"num_round={args.rounds}",
              "silent=2", "objective=binary:logistic", "max_depth=3",
              "eta=0.5", "max_bin=16"]

    # the uninterrupted reference (checkpointing ON so the code path is
    # identical up to the injected failures)
    ref_model = os.path.join(work, "ref.model")
    rc = cli_main(common + [f"model_out={ref_model}",
                            f"checkpoint_dir={os.path.join(work, 'ck_ref')}"])
    if rc != 0:
        print(f"reference run failed (rc={rc})", file=sys.stderr)
        return 1
    ref = _state(ref_model)

    rng = np.random.RandomState(args.seed)
    rm = reliability_metrics()
    base = {"ring_fallbacks": rm.ring_fallbacks.value,
            "quarantines": rm.quarantines.value,
            "integrity_failures": rm.integrity_failures.value}
    report = {"runs": args.runs, "recoveries": 0, "bit_identical": 0,
              "mismatches": 0, "deaths": 0, "corruptions_armed": 0,
              "run_log": []}

    for run in range(args.runs):
        ck = os.path.join(work, f"ck_{run:03d}")
        out = os.path.join(work, f"m_{run:03d}.model")
        # 1-2 deaths at random round boundaries (distinct versions so
        # the second coordinate is reachable after the first restart)
        versions = sorted(rng.choice(
            np.arange(1, args.rounds), size=int(rng.randint(1, 3)),
            replace=False))
        mock = ";".join(f"{int(v)},0,{i}" for i, v in enumerate(versions))
        entry = {"run": run, "mock": mock, "fault": None}
        faults.clear_faults()
        if rng.rand() < 0.5:
            # corrupt the ring member the restart will want: the one
            # written just before the (first) death
            kind = "torn_write" if rng.rand() < 0.5 else "bit_flip"
            at = int(rng.randint(16, 1000))
            target = f"ckpt-{int(versions[0]):06d}"
            faults.inject(kind, at, path_sub=target)
            entry["fault"] = f"{kind}={at}@{target}"
            report["corruptions_armed"] += 1
        try:
            rc = cli_main(common + [f"model_out={out}",
                                    f"checkpoint_dir={ck}",
                                    f"mock={mock}", "keepalive=1"])
        except BaseException as e:  # noqa: BLE001 — recorded in the report
            entry["error"] = f"{type(e).__name__}: {e}"
            rc = -1
        finally:
            faults.clear_faults()
        report["deaths"] += len(versions)
        if rc == 0:
            report["recoveries"] += 1
            got = _state(out)
            if _states_equal(ref, got):
                report["bit_identical"] += 1
                entry["result"] = "bit_identical"
            else:
                report["mismatches"] += 1
                entry["result"] = "MISMATCH"
        else:
            report["mismatches"] += 1
            entry["result"] = f"rc={rc}"
        report["run_log"].append(entry)
        print(f"[chaos] run {run}: mock={mock} fault={entry['fault']} "
              f"-> {entry['result']}", file=sys.stderr)

    report["ring_fallbacks"] = rm.ring_fallbacks.value - base["ring_fallbacks"]
    report["quarantines"] = rm.quarantines.value - base["quarantines"]
    report["integrity_failures"] = (rm.integrity_failures.value
                                    - base["integrity_failures"])
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"[chaos] {report['bit_identical']}/{args.runs} bit-identical, "
          f"{report['ring_fallbacks']:.0f} ring fallbacks, "
          f"{report['quarantines']:.0f} quarantines -> {args.out}",
          file=sys.stderr)
    return 0 if report["mismatches"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
