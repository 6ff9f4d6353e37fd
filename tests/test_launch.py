"""Multi-host launcher (parallel/launch.py) — the tracker/submitter
analog (reference ``tracker/rabit_demo.py`` + ``rabit_tracker.py``).

Spawns REAL separate processes that rendezvous through
``jax.distributed.initialize`` and run the dp growth path over a global
(cross-process) mesh; the grown tree must match an in-process run on
the same global mesh shape.
"""

import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "mp_grow_worker.py")


def _clean_env():
    env = dict(os.environ)
    # the pytest process forced an 8-device CPU platform; workers set up
    # their own 2-device platforms
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_two_process_growth_matches_in_process(tmp_path):
    out = tmp_path / "tree.npz"
    cmd = [sys.executable, "-m", "xgboost_tpu.launch", "-n", "2",
           "--local-devices", "2", "--",
           sys.executable, WORKER, str(out)]
    r = subprocess.run(cmd, cwd=REPO, env=_clean_env(),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert out.exists(), r.stderr[-3000:]
    got = dict(np.load(str(out)))

    # in-process reference: same data, same 4-device mesh shape
    import jax
    import jax.numpy as jnp
    from xgboost_tpu.binning import bin_dense, compute_cuts
    from xgboost_tpu.config import TrainParam
    from xgboost_tpu.data import DMatrix
    from xgboost_tpu.models.gbtree import make_grow_config
    from xgboost_tpu.parallel.dp import grow_tree_dp, shard_rows
    from xgboost_tpu.parallel.mesh import data_parallel_mesh

    rng = np.random.RandomState(0)
    X = rng.rand(1024, 6).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0.8).astype(np.float32)
    cuts = compute_cuts(DMatrix(X, label=y), max_bin=16)
    cfg = make_grow_config(TrainParam(max_depth=3, eta=0.5), cuts.max_bin)
    p = np.float32(0.5)
    gh = np.stack([p - y, np.full_like(y, p * (1 - p))], axis=1)

    mesh = data_parallel_mesh(4)
    tree, _, _ = grow_tree_dp(
        mesh, jax.random.PRNGKey(7), shard_rows(mesh, jnp.asarray(
            bin_dense(X, cuts))), shard_rows(mesh, jnp.asarray(gh)),
        jnp.asarray(cuts.cut_values), jnp.asarray(cuts.n_cuts), cfg,
        shard_rows(mesh, jnp.ones(1024, bool)))

    for f in tree._fields:
        np.testing.assert_allclose(
            got[f], np.asarray(getattr(tree, f)), rtol=1e-5, atol=1e-6,
            err_msg=f)


def test_four_process_growth_matches_in_process(tmp_path):
    """The same dp growth over FOUR processes x 2 devices (8-device
    global mesh): the launcher, rendezvous and collective layout must
    hold beyond the 2-process case (VERDICT r3 weak #6 — wider gang
    coverage), and the tree must match an in-process 8-device run."""
    out = tmp_path / "tree4.npz"
    cmd = [sys.executable, "-m", "xgboost_tpu.launch", "-n", "4",
           "--local-devices", "2", "--",
           sys.executable, WORKER, str(out)]
    r = subprocess.run(cmd, cwd=REPO, env=_clean_env(),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert out.exists(), r.stderr[-3000:]
    got = dict(np.load(str(out)))

    import jax
    import jax.numpy as jnp
    from xgboost_tpu.binning import bin_dense, compute_cuts
    from xgboost_tpu.config import TrainParam
    from xgboost_tpu.data import DMatrix
    from xgboost_tpu.models.gbtree import make_grow_config
    from xgboost_tpu.parallel.dp import grow_tree_dp, shard_rows
    from xgboost_tpu.parallel.mesh import data_parallel_mesh

    rng = np.random.RandomState(0)
    X = rng.rand(1024, 6).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0.8).astype(np.float32)
    cuts = compute_cuts(DMatrix(X, label=y), max_bin=16)
    cfg = make_grow_config(TrainParam(max_depth=3, eta=0.5), cuts.max_bin)
    p = np.float32(0.5)
    gh = np.stack([p - y, np.full_like(y, p * (1 - p))], axis=1)

    mesh = data_parallel_mesh(8)
    tree, _, _ = grow_tree_dp(
        mesh, jax.random.PRNGKey(7), shard_rows(mesh, jnp.asarray(
            bin_dense(X, cuts))), shard_rows(mesh, jnp.asarray(gh)),
        jnp.asarray(cuts.cut_values), jnp.asarray(cuts.n_cuts), cfg,
        shard_rows(mesh, jnp.ones(1024, bool)))

    for f in tree._fields:
        np.testing.assert_allclose(
            got[f], np.asarray(getattr(tree, f)), rtol=1e-5, atol=1e-6,
            err_msg=f)


def test_launcher_keepalive_restarts(tmp_path):
    """A worker that dies nonzero on trial 0 is restarted with a bumped
    XGBTPU_NUM_TRIAL (the rabit_demo keepalive loop)."""
    script = tmp_path / "flaky.py"
    script.write_text(
        "import os, sys\n"
        "if int(os.environ.get('XGBTPU_NUM_TRIAL', '0')) == 0:\n"
        "    sys.exit(3)\n"
        "print('survived trial', os.environ['XGBTPU_NUM_TRIAL'])\n")
    from xgboost_tpu.parallel.launch import launch_local
    rc = launch_local(1, [sys.executable, str(script)], keepalive=True,
                      restart_backoff_sec=0.05)
    assert rc == 0


# a worker that heartbeats once, then wedges forever on trial 0 and
# exits clean on any later trial — the launcher-watchdog test double
# (mesh-free: no jax anywhere)
_STALL_SCRIPT = """\
import os, sys, time
trial = int(os.environ.get("XGBTPU_NUM_TRIAL", "0"))
hb = os.environ.get("XGBTPU_HEARTBEAT_DIR")
rank = os.environ.get("XGBTPU_WORKER_ID", "0")
if hb:
    with open(os.path.join(hb, f"hb-{rank}"), "w") as f:
        f.write("0")
if trial == 0:
    time.sleep(600)  # wedged: no further heartbeats
sys.exit(0)
"""


def test_watchdog_kills_and_restarts_stalled_gang(tmp_path, capfd):
    """ISSUE 10 tentpole (2): a gang that stops advancing (heartbeats
    stale for watchdog_stall_sec) is killed and restarted on a bumped
    trial — stall-detection keepalive, the allreduce_robust timeout
    analog.  Counter- and event-verified."""
    from xgboost_tpu.parallel.launch import launch_local
    from xgboost_tpu.obs import reliability_metrics
    script = tmp_path / "staller.py"
    script.write_text(_STALL_SCRIPT)
    rm = reliability_metrics()
    base_stall = rm.launch_restarts.value("stall")
    rc = launch_local(1, [sys.executable, str(script)], keepalive=True,
                      watchdog_stall_sec=1.2, restart_backoff_sec=0.05,
                      standalone=True)
    assert rc == 0
    assert rm.launch_restarts.value("stall") == base_stall + 1
    err = capfd.readouterr().err
    assert "[launch] STALL" in err
    assert "restarting all 1 workers, trial 1 (reason stall" in err


def test_watchdog_no_keepalive_kills_and_returns_stall_rc(tmp_path):
    """Without keepalive the watchdog still UNWEDGES the job — the
    gang is killed and the distinct stall exit code surfaces."""
    from xgboost_tpu.parallel.launch import STALL_RC, launch_local
    script = tmp_path / "staller.py"
    script.write_text(_STALL_SCRIPT)
    rc = launch_local(1, [sys.executable, str(script)], keepalive=False,
                      watchdog_stall_sec=1.0, standalone=True)
    assert rc == STALL_RC


def test_stall_mock_watchdog_resume_bit_identical_composed_with_death(
        tmp_path):
    """Satellite: the `stall` mock kind composed with death, end to end
    through the REAL CLI (the local_recover.cc analog for hangs,
    mesh-free via --standalone): the worker wedges at (version 2,
    seqno 0, trial 0), the watchdog kills+restarts the gang, the
    restarted trial dies at (version 3, trial 1), keepalive restarts
    again, and the final model is BIT-identical to an uninterrupted
    run.  ``rounds_per_dispatch=2`` keeps two fused segments in the
    4-round job (mock replay no longer blocks fusion), so the stall
    lands mid-segment-1 (no checkpoint yet) and the death lands after
    the segment-boundary ring write — the second restart must resume
    from round 2, not from scratch."""
    data = tmp_path / "train.libsvm"
    rng = np.random.RandomState(5)
    X = rng.rand(300, 5)
    y = (X[:, 0] > 0.5).astype(int)
    with open(data, "w") as fh:
        for i in range(300):
            feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(5))
            fh.write(f"{y[i]} {feats}\n")
    common = [f"data={data}", "task=train", "num_round=4", "silent=2",
              "objective=binary:logistic", "max_depth=3", "eta=0.5",
              "max_bin=16", "rounds_per_dispatch=2"]
    ref = tmp_path / "ref.model"
    chaos = tmp_path / "chaos.model"
    env = _clean_env()
    r = subprocess.run(
        [sys.executable, "-m", "xgboost_tpu", *common,
         f"model_out={ref}", f"checkpoint_dir={tmp_path / 'ck_ref'}"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    r = subprocess.run(
        [sys.executable, "-m", "xgboost_tpu.launch", "-n", "1",
         "--standalone", "--keepalive", "--watchdog-stall-sec", "4",
         "--restart-backoff-sec", "0.2", "--",
         sys.executable, "-m", "xgboost_tpu", *common,
         f"model_out={chaos}", f"checkpoint_dir={tmp_path / 'ck'}",
         "mock=stall:2,0,0;die:3,0,1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "[mock] stall at version=2" in r.stderr
    assert "[launch] STALL" in r.stderr
    assert "reason stall" in r.stderr
    assert "die at version=3" in r.stderr
    assert "reason death" in r.stderr
    assert "[ckpt] resume at round 2" in r.stderr

    import xgboost_tpu as xgb
    a = xgb.Booster(model_file=str(ref)).gbtree.get_state()
    b = xgb.Booster(model_file=str(chaos)).gbtree.get_state()
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# a worker that runs the REAL gang protocol mesh-free (no jax): round
# boundaries call gang.on_round (fault firing, beacon observation,
# self-fencing) and gate the heartbeat exactly like mock.begin_round
_GANG_SCRIPT = """\
import os, sys, time
sys.path.insert(0, {repo!r})
from xgboost_tpu.parallel import gang
hb = os.environ.get("XGBTPU_HEARTBEAT_DIR")
rank = os.environ.get("XGBTPU_WORKER_ID", "0")
for v in range({rounds}):
    beat = gang.on_round(v)
    if hb and beat:
        with open(os.path.join(hb, f"hb-{{rank}}"), "w") as f:
            f.write(str(v))
    time.sleep({sleep})
gang.mark_done()
sys.exit(0)
"""


def _gang_worker(tmp_path, rounds, sleep=0.15):
    script = tmp_path / "gang_worker.py"
    script.write_text(
        _GANG_SCRIPT.format(repo=REPO, rounds=rounds, sleep=sleep))
    return [sys.executable, str(script)]


def test_plan_degrade_prefers_device_halving():
    """The re-plan ladder: halve local devices down the PR 12
    invariance ladder first, then shed workers one at a time, floored
    at min_workers; a minimal gang cannot degrade."""
    from xgboost_tpu.parallel.launch import plan_degrade
    assert plan_degrade(4, 4) == (4, 2)
    assert plan_degrade(4, 2) == (4, 1)
    assert plan_degrade(4, 1) == (3, 1)
    assert plan_degrade(2, None) == (1, None)
    assert plan_degrade(1, None) is None
    assert plan_degrade(1, 1) is None
    assert plan_degrade(2, None, min_workers=2) is None


def test_coordinator_state_roundtrip_and_corruption(tmp_path, capfd):
    """Coordinator snapshots carry the ring's CRC discipline: a clean
    roundtrip restores the plan + roster, and a flipped byte makes the
    snapshot unusable (fresh start), never a silently-wrong adoption."""
    from xgboost_tpu.parallel.launch import _read_state, _write_state
    p = str(tmp_path / "coord-state.json")
    st = {"full_n": 2, "cur_n": 1, "cur_devices": None, "degraded": True,
          "trial": 3, "hb_dir": None, "gang_dir": str(tmp_path),
          "workers": [{"rank": 0, "pid": 12345}]}
    _write_state(p, st, "pid777")
    got = _read_state(p)
    assert got == dict(st, holder="pid777")
    raw = bytearray(open(p, "rb").read())
    raw[10] ^= 0x20
    open(p, "wb").write(bytes(raw))
    capfd.readouterr()
    assert _read_state(p) is None
    assert "unusable" in capfd.readouterr().err


def test_host_loss_degrades_gang_and_grow_back_restores(
        tmp_path, monkeypatch, capfd):
    """ISSUE 17 tentpole (1) at the launcher seam: a permanent host
    loss (worker exits HOST_LOSS_RC + tombstone) immediately re-plans
    the gang one worker smaller; while degraded, a ``grow`` file in
    the gang dir re-expands to full size on the next restart."""
    import threading

    from xgboost_tpu.parallel.launch import launch_local
    from xgboost_tpu.obs import reliability_metrics
    monkeypatch.setenv("XGBTPU_FAULTS", "host_loss@t0.r0.v1.")
    gang_dir = tmp_path / "gang"
    gang_dir.mkdir()
    rm = reliability_metrics()
    base = {k: rm.launch_restarts.value(k)
            for k in ("host_loss", "growback")}
    base_grow = rm.launch_growbacks.value

    stop = threading.Event()

    def grow_when_degraded():
        state = gang_dir / "coord-state.json"
        while not stop.is_set():
            try:
                if b'"degraded": true' in state.read_bytes():
                    (gang_dir / "grow").touch()
                    return
            except OSError:
                pass
            time.sleep(0.05)

    t = threading.Thread(target=grow_when_degraded, daemon=True)
    t.start()
    try:
        rc = launch_local(2, _gang_worker(tmp_path, rounds=12, sleep=0.2),
                          keepalive=True, standalone=True,
                          degrade_after=3, restart_backoff_sec=0.05,
                          gang_dir=str(gang_dir))
    finally:
        stop.set()
        t.join(timeout=5)
    assert rc == 0
    assert rm.launch_restarts.value("host_loss") == base["host_loss"] + 1
    assert rm.launch_restarts.value("growback") == base["growback"] + 1
    assert rm.launch_growbacks.value == base_grow + 1
    # the final attempt ran at FULL size, not degraded
    assert rm.launch_mesh_size.value == 2
    assert rm.launch_degraded.value == 0
    err = capfd.readouterr().err
    assert "[gang] HOST LOSS" in err
    assert "[launch] DEGRADE: re-planning 2x1 -> 1x1 (host loss" in err
    assert "[launch] GROW-BACK" in err


def test_partition_window_self_fence_and_restart(
        tmp_path, monkeypatch, capfd):
    """ISSUE 17 tentpole (3): a worker partitioned from the
    coordinator beacon past gang_partition_sec self-fences (exit
    FENCE_RC, no further writes) and keepalive restarts the gang —
    reason ``fence``, counter-verified on the launcher side."""
    from xgboost_tpu.parallel.launch import launch_local
    from xgboost_tpu.obs import reliability_metrics
    monkeypatch.setenv("XGBTPU_FAULTS", "partition=30.0@t0.r0.v1.")
    rm = reliability_metrics()
    base = rm.launch_restarts.value("fence")
    rc = launch_local(1, _gang_worker(tmp_path, rounds=10, sleep=0.15),
                      keepalive=True, standalone=True,
                      gang_partition_sec=0.45,
                      restart_backoff_sec=0.05)
    assert rc == 0
    assert rm.launch_restarts.value("fence") == base + 1
    err = capfd.readouterr().err
    assert "[gang] partition window" in err
    assert "[gang] FENCED" in err
    assert "reason fence" in err


def test_coordinator_restart_adopts_live_gang(tmp_path, capfd):
    """ISSUE 17 tentpole (2): a coordinator restarted against a prior
    holder's state snapshot whose workers are all still alive ADOPTS
    them (no respawn), observes their clean exits via done markers,
    and removes the snapshot on success."""
    from xgboost_tpu.parallel.launch import _write_state, launch_local
    gang_dir = tmp_path / "gang"
    gang_dir.mkdir()
    state = str(gang_dir / "coord-state.json")
    sentinel = tmp_path / "respawned"
    # double-fork: the orphaned worker must NOT be our child, or its
    # exit leaves a zombie that os.kill(pid, 0) still sees as alive —
    # in the real failover it reparents to init and is reaped
    spawner = subprocess.run(
        [sys.executable, "-c",
         "import subprocess, sys\n"
         "p = subprocess.Popen([sys.executable, '-c', "
         "\"import os, sys, time; time.sleep(1.2); \"\n"
         "    \"open(os.path.join(sys.argv[1], 'done-0'), 'w')"
         ".write('done')\", sys.argv[1]])\n"
         "print(p.pid)\n",
         str(gang_dir)],
        capture_output=True, text=True, timeout=30)
    assert spawner.returncode == 0, spawner.stderr
    pid = int(spawner.stdout.strip())
    try:
        _write_state(state, {
            "full_n": 1, "cur_n": 1, "cur_devices": None,
            "degraded": False, "trial": 2, "hb_dir": None,
            "gang_dir": str(gang_dir),
            "workers": [{"rank": 0, "pid": pid}]}, "pid-dead")
        rc = launch_local(
            1, [sys.executable, "-c",
                f"open({str(sentinel)!r}, 'w').write('x')"],
            standalone=True, gang_dir=str(gang_dir), state_path=state)
    finally:
        try:
            os.kill(pid, 9)
        except OSError:
            pass
    assert rc == 0
    assert not sentinel.exists(), "adoption must not respawn the gang"
    assert not os.path.exists(state), "success removes the snapshot"
    assert "[launch] re-adopting live gang" in capfd.readouterr().err


def test_superseded_coordinator_exits_without_reaping(tmp_path, capfd):
    """The single-holder lease: a coordinator that sees the state-file
    holder change under it exits COORD_FENCED_RC WITHOUT touching the
    workers — they belong to the new holder now."""
    import threading

    from xgboost_tpu.parallel.launch import (COORD_FENCED_RC,
                                             _pid_alive, _read_state,
                                             _write_state, launch_local)
    gang_dir = tmp_path / "gang"
    gang_dir.mkdir()
    state = str(gang_dir / "coord-state.json")
    out = {}

    def run():
        out["rc"] = launch_local(
            1, [sys.executable, "-c", "import time; time.sleep(60)"],
            standalone=True, gang_dir=str(gang_dir), state_path=state)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    deadline = time.monotonic() + 10
    st = None
    while time.monotonic() < deadline:
        st = _read_state(state)
        if st and st.get("workers"):
            break
        time.sleep(0.05)
    assert st and st.get("workers"), "launcher never snapshotted"
    pid = int(st["workers"][0]["pid"])
    _write_state(state, st, "intruder")
    t.join(timeout=15)
    assert not t.is_alive()
    assert out["rc"] == COORD_FENCED_RC
    try:
        # the worker was NOT reaped: the new holder owns it
        assert _pid_alive(pid)
    finally:
        try:
            os.kill(pid, 9)
        except OSError:
            pass
    assert "[launch] coordinator fenced" in capfd.readouterr().err


def test_stale_lease_wait_blocks_until_renewals_stop(tmp_path):
    """Standby-side half of the lease: _wait_for_stale_lease must NOT
    return while the primary keeps bumping the state-file mtime, and
    must return shortly after the bumps stop."""
    import threading

    from xgboost_tpu.parallel.launch import _wait_for_stale_lease
    state = tmp_path / "coord-state.json"
    state.write_text("{}")

    def renew():
        for _ in range(8):
            os.utime(state, None)
            time.sleep(0.15)

    t = threading.Thread(target=renew, daemon=True)
    t0 = time.monotonic()
    t.start()
    _wait_for_stale_lease(str(state), 0.6, poll=0.05)
    elapsed = time.monotonic() - t0
    t.join()
    assert elapsed > 1.2, "took over while the primary was renewing"
    assert elapsed < 8.0


def test_two_process_full_booster_training(tmp_path):
    """FULL Booster training across 2 processes x 2 devices: both ranks
    must produce byte-identical models with good training error, and the
    model must be loadable for local prediction."""
    data = tmp_path / "train.libsvm"
    rng = np.random.RandomState(4)
    X = rng.rand(800, 6)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0.7).astype(int)
    with open(data, "w") as fh:
        for i in range(800):
            feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(6))
            fh.write(f"{y[i]} {feats}\n")

    out = tmp_path / "mp"
    cmd = [sys.executable, "-m", "xgboost_tpu.launch", "-n", "2",
           "--local-devices", "2", "--",
           sys.executable, os.path.join(REPO, "tests", "mp_train_worker.py"),
           str(data), str(out)]
    r = subprocess.run(cmd, cwd=REPO, env=_clean_env(),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]

    m0 = (tmp_path / "mp.rank0.model").read_bytes()
    m1 = (tmp_path / "mp.rank1.model").read_bytes()
    assert m0 == m1, "ranks diverged"
    err = float((tmp_path / "mp.rank0.err").read_text())
    assert err < 0.05, err

    import xgboost_tpu as xgb

    # the fused (no-evals) multi-process run produced the same model
    b_seq = xgb.Booster(model_file=str(tmp_path / "mp.rank0.model"))
    b_fus = xgb.Booster(
        model_file=str(tmp_path / "mp.rank0.fused.model"))
    s1, s2 = b_seq.gbtree.get_state(), b_fus.gbtree.get_state()
    for k in s1:
        np.testing.assert_array_equal(s1[k], s2[k], err_msg=k)
    mf0 = (tmp_path / "mp.rank0.fused.model").read_bytes()
    mf1 = (tmp_path / "mp.rank1.fused.model").read_bytes()
    assert mf0 == mf1, "fused ranks diverged"

    # the multi-process model predicts locally like any other model
    bst = xgb.Booster(model_file=str(tmp_path / "mp.rank0.model"))
    p = np.asarray(bst.predict(xgb.DMatrix(str(data))))
    assert float(np.mean((p > 0.5) != y)) < 0.05


def test_two_process_split_loading_bitmatches_replicated(tmp_path):
    """VERDICT r2 #1: per-rank split loading end to end.  Each process
    parses ONLY its row block (~N/2 host rows), assembles global device
    arrays from process-local data, and the resulting model is
    BYTE-IDENTICAL to a replicated-load run in the same job — both for
    the fused scan and the per-round path with distributed (partial-sum)
    metric evaluation."""
    data = tmp_path / "train.libsvm"
    rng = np.random.RandomState(11)
    N = 801  # deliberately not divisible by the 4-device mesh
    X = rng.rand(N, 6)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0.7).astype(int)
    with open(data, "w") as fh:
        for i in range(N):
            feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(6))
            fh.write(f"{y[i]} {feats}\n")

    out = tmp_path / "sh"
    cmd = [sys.executable, "-m", "xgboost_tpu.launch", "-n", "2",
           "--local-devices", "2", "--",
           sys.executable, os.path.join(REPO, "tests", "mp_shard_worker.py"),
           str(data), str(out)]
    r = subprocess.run(cmd, cwd=REPO, env=_clean_env(),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]

    # each process held only ~N/2 host rows (the scaling property)
    tot = 0
    for rank in range(2):
        loc, glob = map(int, (tmp_path / f"sh.rank{rank}.rows"
                              ).read_text().split())
        assert glob == N
        assert loc <= -(-N // 4) * 2, (rank, loc)  # <= 2 device shards
        tot += loc
    assert tot == N

    for rank in range(2):
        bitmatch, bitmatch_e, err = (
            tmp_path / f"sh.rank{rank}.result").read_text().split()
        assert bitmatch == "1", "split-loaded model != replicated model"
        assert bitmatch_e == "1", "per-round model != fused model"
        assert float(err) < 0.05, err

    # exact distributed AUC (VERDICT r3 item 6): the sharded
    # allgather-runs value equals the replicated value to f64
    # summation order; the reference-compat approximation is close on
    # iid shards but not required (or expected) to match exactly
    for rank in range(2):
        exact, approx, repl = map(float, (
            tmp_path / f"sh.rank{rank}.auc").read_text().split())
        assert abs(exact - repl) < 1e-6, (exact, repl)
        assert abs(approx - repl) < 0.05, (approx, repl)

    # ranks agree and the model is locally usable
    m0 = (tmp_path / "sh.rank0.model").read_bytes()
    m1 = (tmp_path / "sh.rank1.model").read_bytes()
    assert m0 == m1
    import xgboost_tpu as xgb
    bst = xgb.Booster(model_file=str(tmp_path / "sh.rank0.model"))
    p = np.asarray(bst.predict(xgb.DMatrix(str(data))))
    assert float(np.mean((p > 0.5) != y)) < 0.05


def test_two_process_rank_specific_death_gang_restart(tmp_path):
    """mock=rank,version,seqno,ntrial under the launcher: only the named
    rank dies, the launcher restarts the whole gang (single processes
    cannot rejoin a live jax.distributed job), and training resumes from
    the checkpoint to a saved model."""
    data = tmp_path / "train.libsvm"
    rng = np.random.RandomState(9)
    X = rng.rand(400, 5)
    y = (X[:, 0] > 0.5).astype(int)
    with open(data, "w") as fh:
        for i in range(400):
            feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(5))
            fh.write(f"{y[i]} {feats}\n")

    model = tmp_path / "ft.model"
    cmd = [sys.executable, "-m", "xgboost_tpu.launch", "-n", "2",
           "--local-devices", "2", "--keepalive", "--",
           sys.executable, "-m", "xgboost_tpu",
           f"data={data}", "objective=binary:logistic", "max_depth=3",
           "eta=1.0", "num_round=4", "silent=2", "mock=1,2,0,0",
           f"checkpoint_dir={tmp_path / 'ck'}", f"model_out={model}"]
    # two full gang attempts (each pays jit compiles) plus the
    # coordination-service error propagation make this the slowest test
    r = subprocess.run(cmd, cwd=REPO, env=_clean_env(),
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    # the death fired on rank 1 only, and the gang restarted once
    assert "die at version=2" in r.stderr
    assert "restarting all 2 workers, trial 1" in r.stderr
    assert model.exists()
    # recovery-cost instrumentation (RECOVERY.md): the launcher
    # reports attempt/reap timing and the restarted rank 0 reports the
    # time to its checkpoint-resume point.  The dying worker must exit
    # HARD: normal interpreter teardown hangs ~minutes in the
    # jax.distributed client, which this wall-clock bound catches.
    m = re.search(r"attempt ran ([0-9.]+)s, reap ([0-9.]+)s", r.stderr)
    assert m, r.stderr[-2000:]
    assert float(m.group(2)) < 30.0, "reap took too long"
    m2 = re.search(r"\[ckpt\] resume at round 2 \(([0-9.]+)s", r.stderr)
    assert m2, r.stderr[-2000:]
    assert float(m2.group(1)) < 60.0, "resume point took too long"

    import xgboost_tpu as xgb
    bst = xgb.Booster(model_file=str(model))
    p = np.asarray(bst.predict(xgb.DMatrix(str(data))))
    assert float(np.mean((p > 0.5) != y)) < 0.05
