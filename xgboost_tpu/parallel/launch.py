"""Multi-host job launcher — the tracker/submitter analog.

The reference's cluster layer is a Python rendezvous tracker plus
submitters that spawn workers with rank/world env vars
(``subtree/rabit/tracker/rabit_tracker.py:125-309``,
``tracker/rabit_demo.py`` local multi-process with keepalive restart,
``rabit_mpi/sge/yarn``).  Under JAX the tracker itself disappears — the
JAX distributed runtime owns rendezvous — so what remains is exactly
this launcher: assign (coordinator, num_processes, process_id), spawn,
optionally restart dead workers (keepalive), and a worker-side
``init_worker()`` that calls ``jax.distributed.initialize``.

Local usage (the rabit_demo.py equivalent — N processes on one host):

    python -m xgboost_tpu.launch -n 4 [--keepalive] \
        python my_worker.py ...

One host, several chips: that is ONE process, not this launcher.
A JAX process takes every chip of its host, so ``dsplit=row`` in a
single ``python -m xgboost_tpu`` already builds its mesh over all of
them (``data_parallel_mesh()`` over ``jax.devices()``;
``chip_smoke.py --chips 4`` is the standing check).  N
default-backend workers started here on one accelerator host would
each reach for all its chips and fail or hang; ``--local-devices``
instead pins every worker to N VIRTUAL CPU devices (testing).  The
launcher is for one worker per HOST.

Cluster usage: run the same worker command on every host with
``XGBTPU_COORD`` (host:port of process 0), ``XGBTPU_NUM_WORKER`` and
``XGBTPU_WORKER_ID`` exported by the scheduler; ``init_worker()`` picks
them up.  Workers load only their row shard (``parse_libsvm`` rank /
nparts modulo split — reference ``simple_dmatrix-inl.hpp:89-96``) and
assemble global arrays with ``jax.make_array_from_process_local_data``.

The FULL stack is multi-process capable (tests/test_launch.py proves
2-process x 2-device jobs end to end): launcher + ``init_worker``
rendezvous, the global data-parallel mesh, the distributed growth /
sketch kernels, and the high-level ``Booster``/CLI training loop —
each process holds the replicated host copy of the data, compute
shards over the global mesh, host pulls (metrics/predictions)
all-gather first (``Booster._replicated``), and ranks produce
byte-identical models (rank 0 saves, like the reference).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

COORD_ENV = "XGBTPU_COORD"
NWORKER_ENV = "XGBTPU_NUM_WORKER"
RANK_ENV = "XGBTPU_WORKER_ID"
TRIAL_ENV = "XGBTPU_NUM_TRIAL"

#: exit codes (registry: reliability/rc.py, lint rule XGT016) —
#: STALL_RC for an unrecovered stall (no keepalive / restart budget
#: exhausted), COORD_FENCED_RC for a coordinator superseded by a
#: standby takeover (it must stop supervising and report neither
#: success nor worker failure); re-exported here for callers that
#: import them from the launcher
from xgboost_tpu.reliability.rc import (COORD_FENCED_RC,  # noqa: F401
                                        STALL_RC)
#: grow-back signal file in the gang dir: a replacement worker (or the
#: operator) touches it to ask a DEGRADED gang to re-expand to full
#: size at the next segment boundary (= checkpoint resume point)
GROW_SIGNAL = "grow"


def init_worker(local_device_count: Optional[int] = None) -> bool:
    """Initialize this process as a distributed JAX worker when the
    launcher env is present.  Returns True iff distributed mode is on.

    Call BEFORE any other jax API touches the backend.  After it,
    ``jax.devices()`` spans all workers and
    :func:`xgboost_tpu.parallel.mesh.data_parallel_mesh` builds the
    global mesh (collectives ride ICI within a slice, DCN across).
    """
    coord = os.environ.get(COORD_ENV)
    if local_device_count is None and os.environ.get("XGBTPU_LOCAL_DEVICES"):
        local_device_count = int(os.environ["XGBTPU_LOCAL_DEVICES"])
    if not coord:
        # standalone gang worker (launch_local(standalone=True) exports
        # no coordinator): still honor the virtual-device request so a
        # single-controller worker can run the mesh-fused scan over an
        # in-process multi-device mesh — the live multi-device target
        # on hosts whose backend cannot execute multi-process programs
        if local_device_count is not None:
            _force_local_devices(local_device_count)
        return False
    if RANK_ENV in os.environ:
        n = int(os.environ[NWORKER_ENV])
        rank = int(os.environ[RANK_ENV])
    else:
        # scheduler-launched worker (mpirun/srun/qsub via
        # parallel/submit.py): rank/world come from the scheduler's env
        from xgboost_tpu.parallel.submit import scheduler_rank
        rw = scheduler_rank()
        if rw is None:
            raise RuntimeError(
                f"{COORD_ENV} is set but no rank source found: export "
                f"{RANK_ENV}/{NWORKER_ENV} or launch under a scheduler "
                "(OpenMPI/PMI/Slurm/SGE)")
        rank, sched_n = rw
        n = int(os.environ.get(NWORKER_ENV, sched_n))
    if local_device_count is not None:
        _force_local_devices(local_device_count)
    import jax
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=n, process_id=rank)
    return True


def _force_local_devices(local_device_count: int) -> None:
    """Give this process a fixed virtual CPU device count and pin the
    platform.  Must run before any jax API touches the backend."""
    # CPU workers: give each process a fixed virtual device count.  An
    # explicit request REPLACES any inherited count (a parent test
    # harness or launcher may have exported its own) — the operator
    # asked for exactly this many devices.
    import re
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"--xla_force_host_platform_device_count={local_device_count}"
    if "host_platform_device_count" in flags:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", want, flags)
    else:
        flags = (flags + " " + want).strip()
    os.environ["XLA_FLAGS"] = flags
    import jax
    # N virtual devices exist on the CPU platform only: pin it, so on
    # a machine whose default backend is an accelerator the request
    # does not land there (and default_backend() does not steer
    # backend-conditional code — the histogram kernel choice — at a
    # CPU-device mesh).  A config update, not the env var:
    # JAX_PLATFORMS is read when jax is first imported, which may
    # already have happened.
    jax.config.update("jax_platforms", "cpu")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _reap(procs: List[Optional[subprocess.Popen]],
          grace: float = 3.0) -> None:
    """Terminate-then-kill every live child and wait() them all.  A
    survivor blocked in a collective of a doomed gang ignores SIGTERM
    (it is inside the coordination-service wait), so the grace is
    short: these processes are about to be replaced by the restart and
    their state is reconstructed from the checkpoint ring anyway."""
    for q in procs:
        if q is not None and q.poll() is None:
            q.terminate()
    for q in procs:
        if q is None:
            continue
        try:
            q.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            q.kill()
            q.wait()


def _latest_heartbeat(hb_dir: str) -> Optional[float]:
    """Newest heartbeat-file mtime across ranks (monotonic-comparable
    only against other mtimes from the same filesystem), or None when
    no rank has beaten yet."""
    latest = None
    try:
        names = os.listdir(hb_dir)
    except OSError:
        return None
    for name in names:
        if not name.startswith("hb-"):
            continue
        try:
            m = os.stat(os.path.join(hb_dir, name)).st_mtime
        except OSError:
            continue  # racing a rewrite; the next poll sees it
        if latest is None or m > latest:
            latest = m
    return latest


def plan_degrade(n: int, local_devices: Optional[int],
                 min_workers: int = 1
                 ) -> Optional[Tuple[int, Optional[int]]]:
    """The largest viable smaller gang plan, or None when already
    minimal.  Device counts HALVE (the mesh-size-invariance family PR 12
    proved bit-identical is the power-of-two ladder 8/4/2/1); worker
    counts step down by one (the rank/nparts modulo row split re-shards
    at any count).  Pure — the chaos selftest drives it directly."""
    if local_devices is not None and local_devices > 1:
        return n, local_devices // 2
    if n > max(1, min_workers):
        return n - 1, local_devices
    return None


def _write_state(state_path: str, state: dict, holder: str) -> None:
    """Snapshot coordinator state (gang roster, attempt counter, plan)
    atomically with the standard CRC footer — the same discipline as a
    ring member, because a restarted coordinator re-adopting live
    workers off a torn snapshot would be its own split brain."""
    from xgboost_tpu.reliability.integrity import add_footer, atomic_write
    payload = json.dumps(dict(state, holder=holder),
                         sort_keys=True).encode()
    atomic_write(state_path, add_footer(payload))


def _read_state(state_path: str) -> Optional[dict]:
    """Load + CRC-verify a coordinator snapshot; None when missing or
    unusable (a corrupt snapshot means fresh-start, not crash)."""
    from xgboost_tpu.reliability.integrity import (read_file,
                                                   verify_model_bytes)
    try:
        raw = read_file(state_path)
    except OSError:
        return None
    try:
        payload = verify_model_bytes(raw, name=state_path, warn=False)
        return json.loads(payload.decode())
    except ValueError as e:
        from xgboost_tpu.obs import event
        event("launch.state_corrupt", path=state_path, error=str(e))
        print(f"[launch] coordinator state {state_path} unusable "
              f"({e}); starting fresh", file=sys.stderr)
        return None


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # alive, just not ours to signal


def _reap_pids(pids: List[int], grace: float = 3.0) -> None:
    """The :func:`_reap` discipline for ADOPTED workers — non-children
    this coordinator cannot ``wait()``: SIGTERM, poll for death within
    the grace, then SIGKILL."""
    for pid in pids:
        if _pid_alive(pid):
            try:
                os.kill(pid, signal.SIGTERM)
            except OSError:
                pass  # died between the check and the signal
    deadline = time.monotonic() + grace
    while time.monotonic() < deadline:
        if not any(_pid_alive(p) for p in pids):
            return
        time.sleep(0.1)
    for pid in pids:
        if _pid_alive(pid):
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
    while any(_pid_alive(p) for p in pids):
        time.sleep(0.05)


def _touch(path: str) -> None:
    """mtime-bump a beacon file (created on first touch); never raises
    — a beacon failure must not kill a healthy coordinator loop."""
    try:
        os.utime(path, None)
    except OSError:
        try:
            with open(path, "a"):  # xgtpu: disable=XGT003 — liveness beacon
                pass
        except OSError as e:
            from xgboost_tpu.obs.metrics import swallowed_error
            swallowed_error("parallel.launch.beacon", e, emit_event=False)


def _wait_for_stale_lease(state_path: str, lease_sec: float,
                          poll: float = 0.25) -> None:
    """Standby-coordinator wait (the placer's single-holder-lease idea
    on a file): the primary renews its lease by mtime-bumping the state
    snapshot every poll tick; block until that stops for ``lease_sec``
    (or the file never appears for that long) — then the primary is
    dead and this process may take over."""
    last_mtime: Optional[float] = None
    last_change = time.monotonic()
    while True:
        try:
            m = os.stat(state_path).st_mtime
        except OSError:
            m = None
        if m is not None and m != last_mtime:
            last_mtime = m
            last_change = time.monotonic()
        elif time.monotonic() - last_change > lease_sec:
            return
        time.sleep(poll)


def launch_local(n: int, cmd: List[str], keepalive: bool = False,
                 local_devices: Optional[int] = None,
                 max_restarts: int = 10,
                 watchdog_stall_sec: float = 0.0,
                 restart_backoff_sec: float = 0.5,
                 standalone: bool = False,
                 degrade_after: int = 0,
                 min_workers: int = 1,
                 gang_partition_sec: float = 0.0,
                 gang_dir: Optional[str] = None,
                 state_path: Optional[str] = None,
                 standby: bool = False,
                 coord_lease_sec: float = 10.0) -> int:
    """Spawn ``n`` local worker processes running ``cmd`` (the
    rabit_demo.py submitter).

    With ``keepalive``, any nonzero worker death restarts the WHOLE gang
    with a bumped trial counter and a fresh coordinator port: a single
    restarted process cannot rejoin a live ``jax.distributed`` job, so
    recovery is whole-job restart + resume from ``checkpoint_dir`` —
    exactly the per-round-checkpoint fault model (SURVEY.md §5.3 TPU
    mapping).  The fresh port per attempt also sidesteps the
    free_port() probe/bind race.

    ``watchdog_stall_sec > 0`` extends keepalive from death-detection
    to STALL-detection (the reference's allreduce_robust timeout
    recovery, RELIABILITY.md stall matrix): every worker touches a
    per-rank heartbeat file at each round boundary
    (``mock.begin_round``), and when ALL ranks stop advancing for that
    long — a gang wedged in a collective, a worker hung in device code
    — the launcher kills and restarts the gang exactly as it would for
    a death.  The window must cover startup + the slowest single round
    (data load and jit compilation count against it until the first
    round lands).  Restarts draw from one ``max_restarts`` budget with
    jittered exponential backoff between trials
    (``restart_backoff_sec`` doubling per trial, capped at 30 s).

    ``standalone=True`` supervises WITHOUT distributed rendezvous: no
    ``XGBTPU_COORD`` is exported, so workers run single-controller and
    the launcher contributes only keepalive + the stall watchdog —
    process supervision for jobs (or containers) where the
    ``jax.distributed`` mesh path is unavailable.

    **Elastic degraded-mesh recovery** (RECOVERY.md degraded-mode
    matrix) arms when any of the gang knobs is set:

    - ``degrade_after > 0``: after that many consecutive failed
      attempts at the current size — or IMMEDIATELY on a permanent
      host loss (worker rc ``HOST_LOSS_RC`` / ``lost-<rank>``
      tombstone) — the gang is re-planned at the largest viable
      smaller size (:func:`plan_degrade`) and resumes from the last
      segment-boundary ring member; mesh-size invariance (PR 12) makes
      the finished model bit-identical to an uninterrupted run.
    - While degraded, a ``grow`` file appearing in the gang dir (a
      replacement worker registered) re-expands the gang to full size
      at the next segment boundary — the restart resumes from the last
      boundary's checkpoint, which IS the boundary.
    - ``gang_partition_sec > 0``: the launcher maintains a ``coord``
      beacon in the gang dir; a worker that cannot see it advance for
      that long self-fences (``parallel/gang.py``) — it stops writing
      checkpoints/heartbeats and dies ``FENCE_RC``, so a healed
      partition can never put two writers on the ring.
    - ``state_path`` (default ``<gang_dir>/coord-state.json``):
      coordinator state (gang roster + pids, attempt counter, current
      plan) snapshots via ``atomic_write``+CRC at every attempt
      boundary; a SIGKILL'd coordinator restarted with the same path
      RE-ADOPTS the live workers (pid-polled, clean exits visible via
      ``done-<rank>`` markers) instead of orphaning them.
    - ``standby=True``: warm-standby coordinator (the placer's
      single-holder-lease pattern on a file): block until the
      primary's lease — the state-file mtime it bumps every poll tick
      — goes stale for ``coord_lease_sec``, then take over and adopt.
      A superseded primary notices the holder change and exits
      ``COORD_FENCED_RC`` without touching the workers.
    """
    from xgboost_tpu.obs import event
    from xgboost_tpu.parallel import gang as gangmod
    from xgboost_tpu.obs import reliability_metrics
    from xgboost_tpu.reliability.deadline import backoff_delay

    rm = reliability_metrics()
    gang_on = bool(degrade_after or gang_partition_sec > 0 or gang_dir
                   or state_path or standby)
    own_gang_dir = False
    if gang_on:
        if gang_dir is None:
            gang_dir = tempfile.mkdtemp(prefix="xgbtpu_gang_")
            own_gang_dir = True
        else:
            os.makedirs(gang_dir, exist_ok=True)
        if state_path is None:
            state_path = os.path.join(gang_dir, "coord-state.json")
    holder = f"pid{os.getpid()}"

    if standby:
        print(f"[launch] standby coordinator: watching {state_path} "
              f"(lease {coord_lease_sec}s)", file=sys.stderr)
        _wait_for_stale_lease(state_path, coord_lease_sec)
        event("launch.standby_takeover", state_path=state_path,
              holder=holder)
        print(f"[launch] standby takeover: lease stale, {holder} is "
              "now the coordinator", file=sys.stderr)

    hb_root = None
    if watchdog_stall_sec > 0:
        hb_root = tempfile.mkdtemp(prefix="xgbtpu_hb_")

    # the gang plan: full size is what the caller asked for; the
    # current size shrinks on degrade and restores on grow-back
    cur_n, cur_devices = n, local_devices
    degraded = False
    trial = 0
    fails_at_size = 0

    # coordinator failover: a previous holder's snapshot with every
    # worker pid still alive means ADOPT, not respawn — a SIGKILL'd
    # coordinator must not orphan (or needlessly kill) a healthy gang
    adopt_pids: Optional[Dict[int, int]] = None
    adopt_hb_dir: Optional[str] = None
    if gang_on and os.path.exists(state_path):
        st = _read_state(state_path)
        if st and int(st.get("full_n", -1)) == n:
            trial = int(st.get("trial", 0))
            cur_n = int(st.get("cur_n", n))
            cd = st.get("cur_devices")
            cur_devices = int(cd) if cd is not None else None
            degraded = bool(st.get("degraded"))
            workers = {int(w["rank"]): int(w["pid"])
                       for w in st.get("workers", [])}
            live = {r: p for r, p in workers.items() if _pid_alive(p)}
            done_marks = {r for r in workers
                          if os.path.exists(os.path.join(
                              gang_dir, f"done-{r}"))}
            if workers and all(r in live or r in done_marks
                               for r in workers):
                adopt_pids = workers
                adopt_hb_dir = st.get("hb_dir")
            elif live:
                # partial gang: the stragglers are doomed (their gang
                # is broken) — reap them and restart normally
                _reap_pids(list(live.values()))

    try:
        while True:
            rm.launch_mesh_size.set(cur_n * (cur_devices or 1))
            rm.launch_degraded.set(1 if degraded else 0)
            t_attempt = time.perf_counter()  # duration anchor (XGT006)
            adopted = adopt_pids is not None
            grow_path = (os.path.join(gang_dir, GROW_SIGNAL)
                         if gang_on else None)

            if adopted:
                live_pids = dict(adopt_pids)
                adopt_pids = None
                hb_dir = adopt_hb_dir
                event("launch.adopt", trial=trial,
                      workers=sorted(live_pids.values()))
                print(f"[launch] re-adopting live gang "
                      f"{sorted(live_pids.items())} (trial {trial})",
                      file=sys.stderr)
                _write_state(state_path, {
                    "full_n": n, "cur_n": cur_n,
                    "cur_devices": cur_devices, "degraded": degraded,
                    "trial": trial, "hb_dir": hb_dir,
                    "gang_dir": gang_dir,
                    "workers": [{"rank": r, "pid": p}
                                for r, p in live_pids.items()],
                }, holder)
                procs = []
            else:
                coord = f"localhost:{free_port()}"
                hb_dir = None
                if hb_root is not None:
                    # fresh beacon dir per attempt: a stale heartbeat
                    # from the previous trial must not vouch for this
                    hb_dir = os.path.join(hb_root, f"t{trial}")
                    os.makedirs(hb_dir, exist_ok=True)
                if gang_on:
                    # stale completion markers must not vouch for the
                    # ranks of THIS attempt
                    for name in os.listdir(gang_dir):
                        if name.startswith("done-"):
                            try:
                                os.remove(os.path.join(gang_dir, name))
                            except OSError:
                                pass  # racing a concurrent cleaner
                    _touch(os.path.join(gang_dir, gangmod.BEACON_NAME))

                def spawn(rank: int) -> subprocess.Popen:
                    env = dict(os.environ)
                    if not standalone:
                        env[COORD_ENV] = coord
                    env[NWORKER_ENV] = str(cur_n)
                    env[RANK_ENV] = str(rank)
                    env[TRIAL_ENV] = str(trial)
                    if hb_dir is not None:
                        env["XGBTPU_HEARTBEAT_DIR"] = hb_dir
                    if cur_devices is not None:
                        env["XGBTPU_LOCAL_DEVICES"] = str(cur_devices)
                    if gang_on:
                        env[gangmod.GANG_DIR_ENV] = gang_dir
                        if gang_partition_sec > 0:
                            env[gangmod.PARTITION_SEC_ENV] = str(
                                gang_partition_sec)
                        if degraded:
                            env[gangmod.DEGRADED_ENV] = "1"
                        else:
                            env.pop(gangmod.DEGRADED_ENV, None)
                    return subprocess.Popen(cmd, env=env)

                procs = [spawn(r) for r in range(cur_n)]
                live_pids = {}
                if gang_on:
                    # attempt-boundary snapshot: everything a restarted
                    # coordinator needs to re-adopt this exact gang
                    _write_state(state_path, {
                        "full_n": n, "cur_n": cur_n,
                        "cur_devices": cur_devices,
                        "degraded": degraded, "trial": trial,
                        "hb_dir": hb_dir, "gang_dir": gang_dir,
                        "workers": [{"rank": r, "pid": p.pid}
                                    for r, p in enumerate(procs)],
                    }, holder)

            procs_left: List[Optional[subprocess.Popen]] = list(procs)
            done_ranks: set = set()
            # stall clock: progress = the newest heartbeat mtime CHANGED
            # since the last poll (mtimes are wall-clock, so they are
            # only ever compared with each other; the silence DURATION
            # is measured on the monotonic clock, XGT006)
            last_progress = time.monotonic()
            last_hb_seen: Optional[float] = None
            failed_rc = None
            host_lost = False
            stalled = False
            grow = False
            superseded = False
            tick = 0

            def gang_alive() -> bool:
                if adopted:
                    return any(r not in done_ranks for r in live_pids)
                return any(p is not None for p in procs_left)

            while gang_alive() and failed_rc is None:
                time.sleep(0.2)
                tick += 1
                if gang_on:
                    # coordinator liveness beacon (workers fence off
                    # its staleness) + lease renewal for any standby
                    _touch(os.path.join(gang_dir, gangmod.BEACON_NAME))
                    _touch(state_path)
                    if tick % 10 == 0:
                        st = _read_state(state_path)
                        if st is not None and st.get("holder") != holder:
                            superseded = True
                            break
                    if degraded and os.path.exists(grow_path):
                        grow = True
                        try:
                            os.remove(grow_path)
                        except OSError:
                            pass  # signal already consumed either way
                        break
                if adopted:
                    for r, pid in live_pids.items():
                        if r in done_ranks or _pid_alive(pid):
                            continue
                        if os.path.exists(os.path.join(
                                gang_dir, f"done-{r}")):
                            done_ranks.add(r)
                            continue
                        failed_rc = 1  # unwaitable: rc unknowable
                        rm.launch_worker_deaths.inc()
                        event("launch.worker_death", rank=r, rc=None,
                              trial=trial, adopted=True)
                        print(f"[launch] adopted worker {r} (pid {pid})"
                              f" died without a done marker "
                              f"(trial {trial})", file=sys.stderr)
                        break
                else:
                    for r, p in enumerate(procs_left):
                        if p is None or p.poll() is None:
                            continue
                        if p.returncode == 0:
                            procs_left[r] = None
                        else:
                            failed_rc = p.returncode
                            if p.returncode == gangmod.HOST_LOSS_RC:
                                host_lost = True
                            rm.launch_worker_deaths.inc()
                            event("launch.worker_death", rank=r,
                                  rc=p.returncode, trial=trial)
                            print(f"[launch] worker {r} died "
                                  f"(rc={p.returncode}, trial {trial})",
                                  file=sys.stderr)
                            break
                if (failed_rc is None and hb_dir is not None
                        and gang_alive()):
                    # stall watchdog: progress = a NEW heartbeat from
                    # any rank since the last poll (spawn time until
                    # the first one lands — startup counts against the
                    # window, so it must cover compile time)
                    hb = _latest_heartbeat(hb_dir)
                    if hb is not None and hb != last_hb_seen:
                        last_hb_seen = hb
                        last_progress = time.monotonic()
                    silent = time.monotonic() - last_progress
                    if silent > watchdog_stall_sec:
                        stalled = True
                        event("launch.stall", trial=trial,
                              silent_sec=round(silent, 2),
                              stall_window_sec=watchdog_stall_sec)
                        print(f"[launch] STALL: no rank advanced for "
                              f"{silent:.1f}s (> {watchdog_stall_sec}s"
                              f", trial {trial}); killing the gang",
                              file=sys.stderr)
                        break

            if superseded:
                # a standby took the lease: the workers are THEIRS now
                # — touching them (or the beacon, or the state file)
                # from here would be exactly the two-coordinator race
                # the single-holder lease exists to prevent
                event("launch.coord_fenced", trial=trial, holder=holder)
                print(f"[launch] coordinator fenced: state holder "
                      f"changed under {holder}; exiting "
                      f"rc={COORD_FENCED_RC} without touching the "
                      "gang", file=sys.stderr)
                return COORD_FENCED_RC
            if failed_rc is None and not stalled and not grow:
                if gang_on:
                    try:
                        os.remove(state_path)  # job done: nothing to adopt
                    except OSError:
                        pass  # never written / already gone
                return 0
            t_detect = time.perf_counter()
            if adopted:
                _reap_pids([p for r, p in live_pids.items()
                            if r not in done_ranks])
            else:
                _reap(procs_left)

            if grow:
                trial += 1
                prev = (cur_n, cur_devices)
                cur_n, cur_devices = n, local_devices
                degraded = False
                fails_at_size = 0
                rm.launch_growbacks.inc()
                rm.launch_restarts.inc("growback")
                event("launch.growback", trial=trial,
                      from_size=prev[0] * (prev[1] or 1),
                      to_size=cur_n * (cur_devices or 1))
                print(f"[launch] GROW-BACK: replacement registered; "
                      f"re-expanding {prev[0]}x{prev[1] or 1} -> "
                      f"{cur_n}x{cur_devices or 1} from the last "
                      f"segment boundary (trial {trial})",
                      file=sys.stderr)
                continue  # a healthy gang was cut: restart immediately

            if not keepalive or trial >= max_restarts:
                return STALL_RC if stalled else failed_rc
            trial += 1
            fails_at_size += 1
            tombs = gangmod.live_tombstones(gang_dir) if gang_on else []
            reason = ("stall" if stalled
                      else "host_loss" if host_lost or tombs
                      else "fence" if failed_rc == gangmod.FENCE_RC
                      else "death")
            rm.launch_restarts.inc(reason)
            event("launch.restart", reason=reason, trial=trial,
                  attempt_sec=round(t_detect - t_attempt, 2))

            # degraded-mode re-plan: immediately on permanent host
            # loss, or after degrade_after consecutive same-size
            # failures; the resume point is the last segment-boundary
            # ring member, and PR 12's mesh-size invariance keeps the
            # finished model bit-identical at the smaller size
            if gang_on and (host_lost or tombs
                            or (degrade_after > 0
                                and fails_at_size >= degrade_after)):
                plan = plan_degrade(cur_n, cur_devices, min_workers)
                if plan is not None:
                    prev = (cur_n, cur_devices)
                    cur_n, cur_devices = plan
                    degraded = True
                    fails_at_size = 0
                    event("launch.degrade", trial=trial,
                          reason=("host_loss" if host_lost or tombs
                                  else "restart_budget"),
                          from_size=prev[0] * (prev[1] or 1),
                          to_size=cur_n * (cur_devices or 1))
                    print(f"[launch] DEGRADE: re-planning "
                          f"{prev[0]}x{prev[1] or 1} -> "
                          f"{cur_n}x{cur_devices or 1} "
                          f"({'host loss' if host_lost or tombs else 'restart budget'}"
                          f", trial {trial}); resuming from the last "
                          "segment boundary", file=sys.stderr)
                    for t in tombs:  # consumed: no longer scheduled
                        try:
                            os.remove(os.path.join(gang_dir, f"lost-{t}"))
                        except OSError:
                            pass
                else:
                    print("[launch] cannot degrade below "
                          f"{cur_n}x{cur_devices or 1}; retrying at "
                          "the same size", file=sys.stderr)

            # jittered exponential backoff between trials (the shared
            # reliability helper): a crash loop (bad input, wedged
            # device) must not hot-spin the host it is supposed to be
            # recovering on
            delay = backoff_delay(trial, base=restart_backoff_sec,
                                  cap=30.0)
            # recovery-cost accounting (RECOVERY.md): attempt wall time
            # up to detection, plus the reap (SIGTERM the survivors)
            print(f"[launch] restarting all {cur_n} workers, trial "
                  f"{trial} (reason {reason}, attempt ran "
                  f"{t_detect - t_attempt:.2f}s, "
                  f"reap {time.perf_counter() - t_detect:.2f}s, "
                  f"backoff {delay:.2f}s)",
                  file=sys.stderr)
            time.sleep(delay)
    finally:
        if hb_root is not None:
            shutil.rmtree(hb_root, ignore_errors=True)
        if own_gang_dir:
            shutil.rmtree(gang_dir, ignore_errors=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m xgboost_tpu.launch",
        description="spawn N distributed workers (rabit_demo.py analog)")
    ap.add_argument("-n", "--nworker", type=int, required=True)
    ap.add_argument("--keepalive", action="store_true",
                    help="restart workers that die nonzero (and gangs "
                         "the stall watchdog kills)")
    ap.add_argument("--local-devices", type=int, default=None,
                    help="virtual CPU devices per worker (testing)")
    ap.add_argument("--watchdog-stall-sec", type=float, default=0.0,
                    help="kill+restart the gang when ALL ranks stop "
                         "advancing (heartbeats at round boundaries) "
                         "for this long; must cover startup + the "
                         "slowest round (0 = off)")
    ap.add_argument("--max-restarts", type=int, default=10,
                    help="total gang restarts (death + stall) before "
                         "giving up")
    ap.add_argument("--restart-backoff-sec", type=float, default=0.5,
                    help="base backoff between gang restarts "
                         "(doubles per trial, jittered, capped 30s)")
    ap.add_argument("--standalone", action="store_true",
                    help="supervise without distributed rendezvous "
                         "(no XGBTPU_COORD): keepalive + watchdog only")
    ap.add_argument("--degrade-after", type=int, default=0,
                    help="after this many consecutive failed attempts "
                         "at the current size (or immediately on a "
                         "permanent host loss), re-plan the gang at "
                         "the largest viable smaller size and resume "
                         "from the last segment boundary (0 = off)")
    ap.add_argument("--min-workers", type=int, default=1,
                    help="never degrade below this many workers")
    ap.add_argument("--gang-partition-sec", type=float, default=0.0,
                    help="workers self-fence (stop checkpoint/beacon "
                         "writes, exit 143) after this long without a "
                         "fresh coordinator beacon (0 = off)")
    ap.add_argument("--gang-dir", default=None,
                    help="shared gang-protocol directory (beacon, "
                         "tombstones, grow signal); default: a fresh "
                         "tempdir, removed on exit")
    ap.add_argument("--state-path", default=None,
                    help="coordinator-state snapshot (CRC-footered "
                         "JSON, atomic): restart with the same path to "
                         "re-adopt a live gang after coordinator death "
                         "(default: <gang-dir>/coord-state.json)")
    ap.add_argument("--standby", action="store_true",
                    help="warm-standby coordinator: block until the "
                         "primary's lease on --state-path goes stale, "
                         "then take over and adopt its workers")
    ap.add_argument("--coord-lease-sec", type=float, default=10.0,
                    help="coordinator lease: the primary bumps the "
                         "state-file mtime every poll tick; a standby "
                         "takes over after this long without a bump")
    ap.add_argument("cmd", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if args.cmd and args.cmd[0] == "--":
        args.cmd = args.cmd[1:]
    if not args.cmd:
        ap.error("missing worker command")
    return launch_local(args.nworker, args.cmd, keepalive=args.keepalive,
                        local_devices=args.local_devices,
                        max_restarts=args.max_restarts,
                        watchdog_stall_sec=args.watchdog_stall_sec,
                        restart_backoff_sec=args.restart_backoff_sec,
                        standalone=args.standalone,
                        degrade_after=args.degrade_after,
                        min_workers=args.min_workers,
                        gang_partition_sec=args.gang_partition_sec,
                        gang_dir=args.gang_dir,
                        state_path=args.state_path,
                        standby=args.standby,
                        coord_lease_sec=args.coord_lease_sec)


if __name__ == "__main__":
    sys.exit(main())
