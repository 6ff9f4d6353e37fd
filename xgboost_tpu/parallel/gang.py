"""Worker-side gang protocol: partition fencing, host loss, liveness.

The elastic gang recovery design (RECOVERY.md degraded-mode matrix)
splits responsibilities: the LAUNCHER (``parallel/launch.py``) owns
detection of death/stall, size re-planning and coordinator-state
snapshots; the WORKER owns the two decisions only it can make —

- **self-fencing**: a worker that cannot see a fresh coordinator
  beacon for ``XGBTPU_GANG_PARTITION_SEC`` seconds must assume it has
  been declared dead and REPLACED.  It stops writing heartbeats and
  checkpoints and dies with :data:`FENCE_RC`, so a healed partition
  can never produce two writers racing the checkpoint ring
  (split-brain).  The launcher restarts/readmits it like any other
  death — a fenced worker re-joins cleanly as a grow-back candidate.
- **host-loss reporting**: the ``host_loss`` chaos fault
  (``reliability/faults.py`` gang seam) models a permanently dead
  host: the worker writes a ``lost-<rank>`` tombstone and dies with
  :data:`HOST_LOSS_RC`, and because the env-armed spec re-fires in
  every respawn, the "host" stays dead until the launcher re-plans the
  gang without it (degraded attempts export ``XGBTPU_GANG_DEGRADED``
  and skip the check — the lost host is no longer scheduled).

The coordinator's liveness beacon is the ``coord`` file in
``XGBTPU_GANG_DIR``, touched by the launcher every poll tick; a worker
observes it at round boundaries (``parallel/mock.py:begin_round`` →
:func:`on_round`) exactly the way the launcher observes worker
heartbeats — mtime CHANGES on the observer's monotonic clock, never
wall-clock arithmetic (XGT006).  ``done-<rank>`` markers
(:func:`mark_done`) let a restarted coordinator that re-ADOPTED
non-child workers distinguish their clean exits from crashes.
"""

from __future__ import annotations

import os
import sys
import time
from typing import List, Optional, Tuple

#: shared gang-protocol directory (beacon, tombstones, done markers,
#: grow-back signal), exported by the launcher when elastic features
#: are on; unset = the whole protocol is a no-op
GANG_DIR_ENV = "XGBTPU_GANG_DIR"
#: seconds of coordinator unreachability after which a worker
#: self-fences (0/unset = fencing off)
PARTITION_SEC_ENV = "XGBTPU_GANG_PARTITION_SEC"
#: exported by the launcher on attempts running at REDUCED size: the
#: host_loss fault no longer fires (the lost host is not scheduled)
DEGRADED_ENV = "XGBTPU_GANG_DEGRADED"

#: worker exit codes (registry: reliability/rc.py, lint rule XGT016):
#: FENCE_RC for a self-fence (coordinator unreachable too long),
#: HOST_LOSS_RC for a simulated permanent host death; re-exported here
#: for the launcher and tests, which read them off this module
from xgboost_tpu.reliability.rc import (FENCE_RC,  # noqa: F401
                                        HOST_LOSS_RC)

#: beacon file the launcher touches every poll tick
BEACON_NAME = "coord"
#: default partition-window seconds when the fault spec gives no arg
DEFAULT_WINDOW_SEC = 5.0


class PartitionClock:
    """Coordinator-reachability tracker for one worker.

    Pure logic with an injectable monotonic clock (the chaos selftest
    drives it with a mock clock): :meth:`open_window` starts a
    message-drop window (the ``partition`` fault), :meth:`observe`
    folds in the latest beacon mtime and classifies the round:

    - ``"ok"`` — coordinator reachable; beacons/heartbeats flow;
    - ``"partitioned"`` — messages dropping (window open) or the beacon
      has gone stale, but not yet for ``partition_sec``;
    - ``"fence"`` — unreachable past ``partition_sec``: the worker must
      stop writing and die (``partition_sec <= 0`` disables fencing, so
      this state is never returned then).

    Beacon freshness is mtime CHANGE observed on this clock — wall
    mtimes are only ever compared with each other, the launcher's own
    heartbeat-watchdog discipline.
    """

    def __init__(self, partition_sec: float = 0.0, monotonic=None):
        self.partition_sec = float(partition_sec)
        self._mono = monotonic if monotonic is not None else time.monotonic
        self._window_until = 0.0
        self._last_mtime: Optional[float] = None
        self._last_change: Optional[float] = None

    def open_window(self, sec: float) -> None:
        """Open (or extend) a both-directions message-drop window."""
        self._window_until = max(self._window_until,
                                 self._mono() + float(sec))

    def window_open(self) -> bool:
        return self._mono() < self._window_until

    def observe(self, beacon_mtime: Optional[float]) -> str:
        now = self._mono()
        if self._last_change is None:
            self._last_change = now  # grace starts at first observation
        dropped = self.window_open()
        if not dropped and beacon_mtime is not None \
                and beacon_mtime != self._last_mtime:
            # a beacon read only lands when the link is up: reads
            # during an open window are dropped like everything else
            self._last_mtime = beacon_mtime
            self._last_change = now
            return "ok"
        unreachable = now - self._last_change
        if self.partition_sec > 0 and unreachable > self.partition_sec:
            return "fence"
        return "partitioned" if dropped else "ok"


_clock: Optional[PartitionClock] = None
_fenced = False


def _reset() -> None:
    """Forget all per-process gang state (test isolation)."""
    global _clock, _fenced
    _clock = None
    _fenced = False


def fenced() -> bool:
    """True once this worker has self-fenced: checkpoint writers must
    refuse to touch the ring (cli._save_checkpoint gate)."""
    return _fenced


def _get_clock(partition_sec: float) -> PartitionClock:
    global _clock
    if _clock is None:
        _clock = PartitionClock(partition_sec)
    return _clock


def _rank_trial() -> Tuple[str, str]:
    return (os.environ.get("XGBTPU_WORKER_ID", "0"),
            os.environ.get("XGBTPU_NUM_TRIAL", "0"))


def _die(rc: int) -> None:
    # die HARD (RECOVERY.md "die hard"): the obs event log flushes per
    # line, and a normal interpreter exit can hang in distributed
    # teardown — the launcher needs to see this pid dead NOW
    sys.stderr.flush()
    os._exit(rc)


def on_round(version: int) -> bool:
    """Round-boundary gang hook (called by ``mock.begin_round``).

    Fires armed gang faults at the ``t<trial>.r<rank>.v<version>.``
    coordinate, tracks coordinator reachability, and self-fences when
    unreachable past the threshold (this call then never returns).
    Returns False when the heartbeat beacon must be SUPPRESSED this
    round (messages to the coordinator are dropping)."""
    global _fenced
    rank, trial = _rank_trial()
    gang_dir = os.environ.get(GANG_DIR_ENV)
    partition_sec = float(os.environ.get(PARTITION_SEC_ENV) or 0.0)

    if not os.environ.get(DEGRADED_ENV):
        from xgboost_tpu.reliability import faults
        coord = f"t{trial}.r{rank}.v{version}."
        for kind, arg in faults.gang_fault(coord):
            if kind == "host_loss":
                _host_loss(gang_dir, rank, trial, version)  # no return
            elif kind == "partition":
                sec = float(arg) if arg is not None else DEFAULT_WINDOW_SEC
                _get_clock(partition_sec).open_window(sec)
                from xgboost_tpu.obs import trace
                trace.event("gang.partition", rank=rank, trial=trial,
                            window_sec=sec)
                print(f"[gang] partition window {sec}s open at "
                      f"version={version} trial={trial} (beacons drop "
                      "both ways)", file=sys.stderr)

    if _clock is None and partition_sec <= 0:
        return True  # no window ever opened, fencing off: fast path
    clock = _get_clock(partition_sec)
    mtime = None
    if gang_dir:
        try:
            mtime = os.stat(os.path.join(gang_dir, BEACON_NAME)).st_mtime
        except OSError:
            mtime = None  # unreadable beacon counts as unreachable
    elif partition_sec > 0:
        return True  # threshold armed but no gang dir: nothing to watch
    status = clock.observe(mtime)
    if status == "fence":
        _fenced = True
        from xgboost_tpu.obs import trace
        from xgboost_tpu.obs import reliability_metrics
        reliability_metrics().launch_fences.inc()
        trace.event("gang.fence", rank=rank, trial=trial,
                    version=version, partition_sec=partition_sec)
        print(f"[gang] FENCED: coordinator unreachable > "
              f"{partition_sec}s at version={version} trial={trial}; "
              "no further checkpoint/beacon writes, exiting "
              f"rc={FENCE_RC}", file=sys.stderr)
        _die(FENCE_RC)
    return status == "ok"


def _host_loss(gang_dir: Optional[str], rank: str, trial: str,
               version: int) -> None:
    from xgboost_tpu.obs import trace
    trace.event("gang.host_loss", rank=rank, trial=trial,
                version=version)
    if gang_dir:
        try:
            # a tombstone, not durable state: the launcher also keys off
            # HOST_LOSS_RC, so a torn marker costs nothing
            with open(os.path.join(gang_dir, f"lost-{rank}"),  # xgtpu: disable=XGT003
                      "w") as f:
                f.write(f"v{version} t{trial}\n")
        except OSError as e:
            from xgboost_tpu.obs.metrics import swallowed_error
            swallowed_error("parallel.gang.tombstone", e)
    print(f"[gang] HOST LOSS at version={version} trial={trial} "
          f"rank={rank}: permanent, exiting rc={HOST_LOSS_RC} (the "
          "launcher must re-plan without this host)", file=sys.stderr)
    _die(HOST_LOSS_RC)


def mark_done() -> None:
    """Touch this rank's ``done-<rank>`` marker on clean exit, so a
    coordinator that re-adopted this (non-child, thus unwaitable)
    worker can tell success from a crash.  No-op without a gang dir;
    never raises."""
    gang_dir = os.environ.get(GANG_DIR_ENV)
    if not gang_dir or _fenced:
        return
    rank, _ = _rank_trial()
    try:
        with open(os.path.join(gang_dir, f"done-{rank}"),  # xgtpu: disable=XGT003
                  "w") as f:
            f.write("done\n")
    except OSError as e:
        from xgboost_tpu.obs.metrics import swallowed_error
        swallowed_error("parallel.gang.mark_done", e)


def live_tombstones(gang_dir: str) -> List[str]:
    """Ranks with a ``lost-<rank>`` tombstone in the gang dir (launcher
    side: hosts declared permanently dead this job)."""
    try:
        names = os.listdir(gang_dir)
    except OSError:
        return []
    return sorted(n[len("lost-"):] for n in names if n.startswith("lost-"))
