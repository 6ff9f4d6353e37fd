"""Command-line driver: ``python -m xgboost_tpu <config> [name=value ...]``.

Mirrors the reference CLI (``src/xgboost_main.cpp:19-323``): a config
file of ``name = value`` pairs plus command-line overrides, dispatching
``task=train|pred|eval|dump|serve``.  Parameter names are kept identical
(``num_round``, ``save_period``, ``model_in``, ``model_out``,
``model_dir``, ``eval[name]=path``, ``test:data``, ``name_pred``,
``pred_margin``, ``ntree_limit``, ``fmap``, ``name_dump``,
``dump_stats``, ``eval_train``, ``dsplit``).

Fault tolerance: where the reference wraps the round loop in rabit
checkpoints (``xgboost_main.cpp:175-229``, two versions per round), this
driver checkpoints the model to ``checkpoint_dir`` at every fused
SEGMENT boundary (per round when fusion is ineligible or
``rounds_per_dispatch=0``) and resumes from the newest VERIFIABLE
checkpoint on restart (SURVEY.md §5.3 TPU mapping: model checkpoint +
restartable loop keyed by round version; deterministic per-iteration
seeding makes the re-trained tail bit-identical, so coarser write
granularity trades only recompute, never correctness; collectives
themselves are not elastically recoverable mid-step under XLA).  Checkpoint writes are atomic +
CRC-footered, a corrupt newest member is quarantined and the older
ring replica used instead (RELIABILITY.md), and ``faults=`` arms I/O
chaos injection the way ``mock=`` arms collective-seam deaths.
"""

from __future__ import annotations

import os
import re
import sys
import time
from typing import Dict, List, Optional, Tuple

from xgboost_tpu.config import (CATALOG_PARAMS, FLEET_PARAMS,
                                LANE_PARAMS, PIPELINE_PARAMS,
                                PLACER_PARAMS, SERVE_PARAMS,
                                STREAM_PARAMS, parse_config_file)

# process start, for recovery-cost accounting.  perf_counter, not
# wall-clock: these readings are only ever subtracted (XGT006)
_T0 = time.perf_counter()

_USAGE = """\
Usage: python -m xgboost_tpu <config> [name=value ...]

Tasks (task=...):
  train   train a model (data=..., num_round=..., model_out=...)
  pred    write predictions (model_in=..., test:data=..., name_pred=...)
  eval    print eval metrics (model_in=..., eval[name]=path)
  dump    dump trees as text (model_in=..., name_dump=...)
  serve   HTTP prediction service (model_in=...; see parameters below,
          or `python -m xgboost_tpu.serving --help`)
  fleet_router
          fleet front door (xgboost_tpu.fleet, SERVING.md): replicas
          started with serve_router_url=... register here; dispatch is
          least-loaded (/predict) or consistent-hash (/predict_by_id),
          with circuit breakers, load shedding, and canary rollout
          (quickstart: tools/launch_fleet.py)
  pipeline
          continuous training (xgboost_tpu.pipeline, PIPELINE.md):
          warm-start from the published model, append
          pipeline_rounds_per_cycle trees on fresh data, gate the
          candidate against the incumbent on a holdout, and atomically
          publish to the path the serving tier polls — directly or
          through the fleet canary lane (pipeline_router_url=)
  lanes   gang-batched multi-tenant continuous training
          (xgboost_tpu.pipeline.lanes, PIPELINE.md "Gang-batched
          lanes"): one pipeline per lanes= tenant, same-shape lanes
          vmap-stacked into ONE device dispatch per round segment
          (XGBTPU_LANE_STACK=0 for the independent host-loop
          baseline); per-lane gate/publish knobs ride the pipeline_*
          table
  placer  autonomous catalog placement (xgboost_tpu.placer, SERVING.md
          "Autonomous placement"): watch the router's per-tenant load,
          bin-pack placer_catalog models onto in-rotation replicas
          within their device budgets, and converge the fleet by
          pushing manifest deltas (elastic resizing rides
          tools/launch_fleet.py --supervise)

Observability (OBSERVABILITY.md): obs_log=PATH appends a crash-safe
JSONL timeline (render: tools/obs_report.py); metrics_port=N serves
live /metrics + /healthz during task=train (0 = ephemeral, -1 = off).

task=serve parameters:
{serve_params}

task=fleet_router parameters:
{fleet_params}

task=pipeline parameters:
{pipeline_params}

task=stream parameters (streaming drift-aware continuous learning):
{stream_params}

catalog parameters (multi-tenant serving, task=serve + task=fleet_router):
{catalog_params}

task=lanes parameters (gang-batched multi-tenant training):
{lane_params}

task=placer parameters (autonomous placement + elastic fleet):
{placer_params}
"""


class BoostLearnTask:
    """Training/prediction task state (reference BoostLearnTask)."""

    def __init__(self):
        self.silent = 0
        self.use_buffer = 1
        self.num_round = 10
        self.save_period = 0
        self.eval_train = 0
        self.pred_margin = 0
        self.ntree_limit = 0
        self.dump_stats = 0
        self.task = "train"
        self.train_path = ""
        self.test_path = ""
        self.model_in: Optional[str] = None
        self.model_out: Optional[str] = None
        self.save_final = True  # model_out=NONE disables the final save
        self.model_dir = "./"
        self.name_fmap = ""
        self.name_pred = "pred.txt"
        self.name_dump = "dump.txt"
        self.checkpoint_dir: Optional[str] = None
        self.save_base64 = 0  # text-safe model files (reference bs64 mode)
        self.shard_load = 1  # per-rank split loading in distributed mode
        self.mock_spec: List[Tuple[int, int, int]] = []  # fault injection
        self.faults_spec: Optional[str] = None  # I/O chaos (faults=...)
        self.keepalive = 0  # restart-on-WorkerFailure (rabit_demo keepalive)
        self.rank = 0  # process index under multi-host launch
        self._distributed = False
        self.eval_names: List[str] = []
        self.eval_paths: List[str] = []
        self.learner_params: List[Tuple[str, str]] = []
        # task=serve / task=fleet_router knobs, seeded from the config
        # tables (single source of truth for both CLI surfaces)
        self.serve_params = {k: v for k, (v, _) in SERVE_PARAMS.items()}
        self.fleet_params = {k: v for k, (v, _) in FLEET_PARAMS.items()}
        self.pipeline_params = {k: v
                                for k, (v, _) in PIPELINE_PARAMS.items()}
        self.stream_params = {k: v
                              for k, (v, _) in STREAM_PARAMS.items()}
        self.catalog_params = {k: v
                               for k, (v, _) in CATALOG_PARAMS.items()}
        self.placer_params = {k: v
                              for k, (v, _) in PLACER_PARAMS.items()}
        self.lane_params = {k: v for k, (v, _) in LANE_PARAMS.items()}

    # ------------------------------------------------------------- params
    _OWN = {
        "silent": int, "use_buffer": int, "num_round": int,
        "save_period": int, "eval_train": int, "pred_margin": int,
        "ntree_limit": int, "dump_stats": int, "save_base64": int,
        "shard_load": int,
    }

    def set_param(self, name: str, val: str) -> None:
        if name in self._OWN:
            setattr(self, name, self._OWN[name](val))
        elif name == "task":
            self.task = val
        elif name == "data":
            self.train_path = val
        elif name == "test:data":
            self.test_path = val
        elif name == "model_in":
            self.model_in = None if val == "NULL" else val
        elif name == "model_out":
            # NULL -> save numbered file; NONE -> skip the final save
            # (reference xgboost_main.cpp:218-224)
            self.model_out = None if val in ("NULL", "NONE") else val
            self.save_final = val != "NONE"
        elif name == "model_dir":
            self.model_dir = val
        elif name == "fmap":
            self.name_fmap = "" if val == "NULL" else val
        elif name == "name_dump":
            self.name_dump = val
        elif name == "name_pred":
            self.name_pred = val
        elif name == "checkpoint_dir":
            self.checkpoint_dir = val
        elif name == "mock":
            # reference AllreduceMock spec "rank,version,seqno,ntrial"
            # (allreduce_mock.h:57-63).  Stored with the rank; under the
            # multi-host launcher only the matching worker installs the
            # coordinate (single-controller: rank 0 == the process).
            # 3-field specs apply to every rank.  Multiple coordinates:
            # semicolon-separated.  A "stall:" prefix makes the
            # coordinate HANG instead of die (parallel/mock.py stall
            # kind — detectable only by the gang launcher's
            # --watchdog-stall-sec heartbeat watchdog, never by the
            # in-process keepalive loop).
            for part in val.split(";"):
                kind = "die"
                if ":" in part:
                    k, _, part = part.partition(":")
                    kind = k.strip()
                    if kind not in ("die", "stall"):
                        raise ValueError(
                            f"mock={part!r}: unknown kind {kind!r} "
                            "(die|stall)")
                nums = [int(x) for x in part.split(",") if x.strip() != ""]
                if len(nums) == 3:
                    nums = [-1] + nums  # any rank
                if len(nums) != 4:
                    raise ValueError(
                        f"mock={part!r}: expected "
                        "[kind:][rank,]version,seqno,ntrial")
                self.mock_spec.append(tuple(nums) + (kind,))
        elif name == "keepalive":
            self.keepalive = int(val)
        elif name == "faults":
            # I/O + serving chaos injection (reliability/faults.py):
            # "kind[=arg][@path][#times];..." — the file-system sibling
            # of the collective-seam mock= parameter
            self.faults_spec = val
        elif name in self.serve_params:
            self.serve_params[name] = type(SERVE_PARAMS[name][0])(val)
        elif name in self.fleet_params:
            self.fleet_params[name] = type(FLEET_PARAMS[name][0])(val)
        elif name in self.pipeline_params:
            self.pipeline_params[name] = type(PIPELINE_PARAMS[name][0])(val)
        elif name in self.stream_params:
            self.stream_params[name] = type(STREAM_PARAMS[name][0])(val)
        elif name in self.catalog_params:
            self.catalog_params[name] = type(CATALOG_PARAMS[name][0])(val)
        elif name in self.placer_params:
            self.placer_params[name] = type(PLACER_PARAMS[name][0])(val)
        elif name in self.lane_params:
            self.lane_params[name] = type(LANE_PARAMS[name][0])(val)
        else:
            m = re.match(r"eval\[([^\]]+)\]", name)
            if m:
                self.eval_names.append(m.group(1))
                self.eval_paths.append(val)
                return
        # every param also cascades into the learner (reference
        # xgboost_main.cpp:95 "learner.SetParam(name, val)")
        self.learner_params.append((name, val))

    # --------------------------------------------------------------- run
    def run(self, argv: List[str]) -> int:
        if not argv:
            from xgboost_tpu.config import (catalog_params_help,
                                            fleet_params_help,
                                            lane_params_help,
                                            pipeline_params_help,
                                            placer_params_help,
                                            serve_params_help,
                                            stream_params_help)
            print(_USAGE.format(serve_params=serve_params_help(),
                                fleet_params=fleet_params_help(),
                                pipeline_params=pipeline_params_help(),
                                stream_params=stream_params_help(),
                                catalog_params=catalog_params_help(),
                                lane_params=lane_params_help(),
                                placer_params=placer_params_help()))
            return 0
        if os.path.exists(argv[0]) or "=" not in argv[0]:
            for name, val in parse_config_file(argv[0]):
                self.set_param(name, val)
            rest = argv[1:]
        else:
            rest = argv
        for arg in rest:
            name, eq, val = arg.partition("=")
            if eq:
                self.set_param(name, val)
        if self.model_out == "stdout" or self.name_pred == "stdout":
            self.set_param("silent", "1")
            self.save_period = 0
        if self.faults_spec:
            from xgboost_tpu.reliability import faults
            faults.install_spec(self.faults_spec)

        # multi-host worker mode (launched by xgboost_tpu.launch or a
        # scheduler exporting XGBTPU_COORD): initialize the distributed
        # runtime BEFORE any backend use, train dsplit=row over the
        # global mesh, auto-silence nonzero ranks and save from rank 0
        # only (reference xgboost_main.cpp:48-50, :242-245)
        from xgboost_tpu.parallel.launch import init_worker
        self._distributed = init_worker()
        if self._distributed:
            import jax
            self.rank = jax.process_index()
            if not any(k == "dsplit" for k, _ in self.learner_params):
                self.set_param("dsplit", "row")
            if self.rank != 0:
                self.silent = max(self.silent, 2)
                if self.task != "train":
                    # pred/eval/dump are process-local: one rank suffices
                    # (and concurrent writes to shared output would race)
                    return 0

        if self._distributed:
            # die HARD on ANY fatal error (rabit workers just die):
            # normal interpreter exit hangs ~minutes in the
            # jax.distributed client teardown trying to reach the
            # coordinator, and the gang launcher cannot restart the job
            # until this process is seen dead — measured 330 s vs
            # sub-second detection (RECOVERY.md).  Covers real failures
            # (bad input, OOM, metric errors), not just the injector.
            try:
                return self._dispatch_marked()
            except SystemExit:
                raise
            except BaseException:
                import traceback

                from xgboost_tpu.reliability.rc import WORKER_CRASH_RC
                traceback.print_exc()
                sys.stderr.flush()
                os._exit(WORKER_CRASH_RC)
        return self._dispatch_marked()

    def _dispatch_marked(self) -> int:
        """Dispatch, then touch the gang ``done-<rank>`` marker on
        success — a re-adopting coordinator cannot ``wait()`` a worker
        it did not spawn, so clean exit must be visible on disk
        (parallel/gang.py)."""
        rc = self._dispatch()
        if rc == 0:
            from xgboost_tpu.parallel import gang
            gang.mark_done()
        return rc

    def _setup_obs(self) -> None:
        """Arm the observability layer (OBSERVABILITY.md) from params:
        ``obs_log=`` opens the JSONL event log (per-rank suffix under
        the multi-host launcher, so timelines never interleave), and
        ``metrics_port=`` serves live ``/metrics`` + ``/healthz`` from
        a daemon thread (rank r binds port+r — per-rank export of the
        collective stats).  Env equivalent: XGBTPU_OBS_LOG.
        """
        from xgboost_tpu import obs
        params = self._params_dict()
        obs_path = params.get("obs_log") or os.environ.get("XGBTPU_OBS_LOG")
        if obs_path:
            if self._distributed and self.rank != 0:
                obs_path = f"{obs_path}.rank{self.rank}"
            obs.configure_log(obs_path)
        port = int(params.get("metrics_port", -1))
        if port >= 0 and self.task in ("train", "pipeline"):
            srv = obs.start_metrics_server(
                port=port + self.rank if port > 0 else 0,
                rank=self.rank)
            if self.silent < 2:
                print(f"[obs] training metrics on "
                      f"http://{srv.host}:{srv.port}/metrics "
                      f"(rank {self.rank})", file=sys.stderr)

    def _dispatch(self) -> int:
        """Task dispatch after param parsing + distributed init."""
        self._setup_obs()
        if self.task == "train":
            if not self.mock_spec:
                return self.task_train()
            # fault-injection mode: install the injector; with keepalive,
            # restart from the checkpoint ring on simulated death (the
            # rabit_demo.py:26-40 keepalive wrapper, in-process).  In a
            # multi-host job the gang launcher owns restarts (a single
            # process cannot rejoin a live jax.distributed job), so the
            # failure propagates as a nonzero exit instead.
            from xgboost_tpu.parallel import mock
            trial = int(os.environ.get("XGBTPU_NUM_TRIAL", "0"))
            mine = [spec[1:] for spec in self.mock_spec
                    if spec[0] in (-1, self.rank)]
            while True:
                mock.set_fault_injection(mine, trial)
                try:
                    return self.task_train()
                except mock.WorkerFailure as e:
                    restart = self.keepalive and not self._distributed
                    print(f"{e}; "  # message carries the [mock] tag
                          + ("restarting" if restart else "dead"),
                          file=sys.stderr)
                    if not restart:
                        # distributed: the run() wrapper os._exit()s
                        raise
                    trial += 1
                finally:
                    mock.clear_fault_injection()
        if self.task == "pred":
            return self.task_pred()
        if self.task == "eval":
            return self.task_eval()
        if self.task == "dump":
            return self.task_dump()
        if self.task == "serve":
            return self.task_serve()
        if self.task == "fleet_router":
            return self.task_fleet_router()
        if self.task == "pipeline":
            return self.task_pipeline()
        if self.task == "stream":
            return self.task_stream()
        if self.task == "lanes":
            return self.task_lanes()
        if self.task == "placer":
            return self.task_placer()
        raise ValueError(f"unknown task {self.task!r}")

    # ------------------------------------------------------------- helpers
    def _params_dict(self) -> Dict[str, str]:
        from xgboost_tpu.config import params_to_dict
        return params_to_dict(self.learner_params)

    def _load_data(self, path: str):
        # "ext:" (paged, io.cpp:20-29) and "!" (HalfRAM, io.cpp:70-73)
        # URIs are routed by DMatrix.__new__ itself
        from xgboost_tpu.data import DMatrix
        return DMatrix(path, silent=self.silent != 0)

    def _load_train_data(self):
        """Training data: per-rank SPLIT loading in distributed dsplit=row
        mode (the reference routes distributed text loads through
        rank/npart partitioning, io.cpp:56-61 ->
        simple_dmatrix-inl.hpp:89-96); every other case loads the full
        matrix.  ``shard_load=0`` opts out."""
        path = self.train_path
        params = self._params_dict()
        from xgboost_tpu.metrics import _DIST_METRICS
        metrics = params.get("eval_metric", [])
        metrics = [metrics] if isinstance(metrics, str) else list(metrics)
        eligible = (
            self._distributed and self.shard_load
            and params.get("dsplit", "row") == "row"
            and params.get("booster", "gbtree") != "gblinear"
            and not str(params.get("objective", "")).startswith("rank:")
            and "grow_colmaker" not in str(params.get("updater", ""))
            # eval_train evaluates ON the training matrix: every metric
            # needs a distributed partial-sum form there
            and (not self.eval_train
                 or all(m.partition("@")[0] in _DIST_METRICS
                        for m in metrics))
            and not path.startswith(("ext:", "!")) and "#" not in path
            and path != "stdin" and os.path.exists(path)
            and _looks_like_text(path))
        if eligible:
            try:
                from xgboost_tpu.parallel.sharded import ShardedDMatrix
                return ShardedDMatrix(path, silent=self.silent != 0)
            except (NotImplementedError, ValueError) as e:
                # ValueError: mesh shape unsuitable for block split
                # (non-contiguous per-process devices) — replicated
                # loading still works there
                if self.silent < 2:
                    print(f"[shard_load] replicated-load fallback: {e}",
                          file=sys.stderr)
        return self._load_data(path)

    def _make_booster(self, cache=()):
        from xgboost_tpu.learner import Booster
        bst = Booster(self._params_dict(), cache=list(cache))
        if self.model_in:
            bst.load_model(self.model_in)
            bst.set_param(self._params_dict())
        return bst

    def _save(self, bst, i: Optional[int] = None) -> None:
        if self.rank != 0:  # rank-0-only saves (xgboost_main.cpp:242-245)
            return
        if i is None:
            assert self.model_out is not None
            path = self.model_out
        else:
            path = os.path.join(self.model_dir, f"{i + 1:04d}.model")
        bst.save_model(path, save_base64=bool(self.save_base64))

    # ------------------------------------------------------------- train
    def _train_rounds(self, bst, data, evals, start_round: int,
                      start: float) -> None:
        """The training round driver (reference TaskTrain round loop,
        xgboost_main.cpp:175-229), riding ``Booster.update_many``'s
        segmented fused dispatches: eval lines and numbered saves keep
        per-round granularity/bit-identity, checkpoints write at
        segment boundaries (a mid-segment SIGKILL resumes from the last
        boundary's ring member and retrains bit-identically — per-round
        fold_in seeding).  Ineligible configs (pruning, external
        memory, profiler/obs phases, ...) and rounds_per_dispatch=0
        run the same hooks one round at a time; ``mock=`` faults ride
        the fused path (coordinates replay at segment boundaries)."""

        def plan_cb(k: int) -> None:
            if self.silent or not k:
                return
            n = self.num_round - start_round
            print(f"fusing rounds {start_round}..{self.num_round - 1} "
                  f"in segments of {k} "
                  f"({-(-n // k)} device dispatches)", file=sys.stderr)

        def round_cb(i: int) -> None:
            if not self.silent:
                print(f"boosting round {i}, "
                      f"{time.perf_counter() - start:.0f} sec "
                      "elapsed", file=sys.stderr)

        def eval_cb(i: int, msg: str) -> None:
            if self.silent < 2:
                print(msg, file=sys.stderr)

        def seg_cb(last_i: int) -> None:
            if self.save_period != 0 \
                    and (last_i + 1) % self.save_period == 0:
                self._save(bst, last_i)
            if self.checkpoint_dir and self.rank == 0:
                from xgboost_tpu.obs import event
                from xgboost_tpu.parallel import gang
                if gang.fenced():
                    # split-brain interlock (RECOVERY.md): a fenced
                    # worker must never race the ring with its
                    # replacement.  The fence path exits the process at
                    # the round boundary, so this gate is a second
                    # lock on the same door — kept because the ring is
                    # the one artifact two writers must never share
                    event("ckpt.fenced_skip", version=last_i + 1)
                    return
                _save_checkpoint(self.checkpoint_dir, bst, last_i + 1)

        bst.update_many(data, start_round, self.num_round - start_round,
                        evals=evals or None, plan_callback=plan_cb,
                        round_callback=round_cb, eval_callback=eval_cb,
                        segment_callback=seg_cb,
                        boundary_align=self.save_period)

    def task_train(self) -> int:
        import xgboost_tpu  # noqa: F401  (ensure package import works early)

        data = self._load_train_data()
        evals = [(self._load_data(p), n)
                 for p, n in zip(self.eval_paths, self.eval_names)]
        if self.eval_train:
            evals.append((data, "train"))

        bst = self._make_booster(cache=[data] + [d for d, _ in evals])
        start_round = 0
        if self.checkpoint_dir:
            if self._distributed and self.rank != 0:
                pass  # rank 0's checkpoint is broadcast below
            else:
                bst, start_round = _load_checkpoint(
                    self.checkpoint_dir, bst, self._params_dict())
            if self._distributed:
                # rabit::LoadCheckPoint semantics: the recovered state is
                # broadcast so every rank resumes at the same round even
                # without a shared checkpoint filesystem
                bst, start_round = _broadcast_checkpoint(
                    bst, start_round, self.rank, self._params_dict())
            if start_round and self.rank == 0:
                # recovery-cost accounting (RECOVERY.md): time from
                # process start to the resume point — data reload +
                # distributed re-init + checkpoint load; the jit
                # recompile cost lands inside the first resumed round
                # (or not, with the persistent jit cache below)
                print(f"[ckpt] resume at round {start_round} "
                      f"({time.perf_counter() - _T0:.2f}s from process "
                      "start)", file=sys.stderr)

        start = time.perf_counter()
        # every config drives the segmented fused dispatcher: eval
        # lines, save_period and checkpoint_dir land at per-round /
        # segment-boundary granularity WITHOUT forcing per-round device
        # dispatches (update_many falls back per-round when fusion is
        # ineligible — pruning, external memory, profiler, ...)
        self._train_rounds(bst, data, evals, start_round, start)
        # save final round unless a periodic numbered save already covered
        # it (reference xgboost_main.cpp:219-225: no final save when
        # save_period divides num_round, even with model_out set)
        if self.save_final and (self.save_period == 0
                                or self.num_round % self.save_period != 0):
            if self.model_out is not None:
                self._save(bst)
            else:
                self._save(bst, self.num_round - 1)
        if getattr(bst, "_profiler", None) is not None:
            bst._profiler.print_summary()
            bst._profiler.stop()
        if not self.silent:
            print(f"\nupdating end, "
                  f"{time.perf_counter() - start:.0f} sec in all",
                  file=sys.stderr)
        return 0

    # -------------------------------------------------------------- pred
    def task_pred(self) -> int:
        data = self._load_data(self.test_path)
        bst = self._make_booster()
        assert self.model_in, "model_in not specified"
        if not self.silent:
            print("start prediction...")
        preds = bst.predict(data, output_margin=self.pred_margin != 0,
                            ntree_limit=self.ntree_limit)
        if not self.silent:
            print(f"writing prediction to {self.name_pred}")
        if self.name_pred == "stdout":
            for p in preds.reshape(-1):
                sys.stdout.write(f"{p:g}\n")
        else:
            # streamed into the tmp+rename staging file (XGT003): a
            # killed pred job leaves the previous complete output or
            # the new one, never a torn prefix a downstream consumer
            # would half-read — and a multi-million-row output is never
            # materialized in memory (no CRC footer: text output, not
            # a model file)
            from xgboost_tpu.reliability.integrity import atomic_writer
            with atomic_writer(self.name_pred) as f:
                for p in preds.reshape(-1):
                    f.write(f"{p:g}\n".encode())
        return 0

    # -------------------------------------------------------------- eval
    def task_eval(self) -> int:
        assert self.model_in, "model_in not specified"
        evals = [(self._load_data(p), n)
                 for p, n in zip(self.eval_paths, self.eval_names)]
        bst = self._make_booster(cache=[d for d, _ in evals])
        print(bst.eval_set(evals, 0), file=sys.stderr)
        return 0

    # -------------------------------------------------------------- serve
    def task_serve(self) -> int:
        """Run the HTTP prediction service on model_in (the serving
        subsystem; quickstart in README 'Serving', design in SERVING.md)
        — or on a multi-model catalog manifest (catalog=...,
        xgboost_tpu.catalog), where bare /predict serves the default
        model and ?model=NAME picks a tenant.
        """
        sp = self.serve_params
        cp = self.catalog_params
        assert self.model_in or cp["catalog"], \
            "model_in not specified (or pass catalog=name=path,...)"
        from xgboost_tpu.serving import run_server
        run_server(
            self.model_in or "",
            host=sp["serve_host"], port=sp["serve_port"],
            min_bucket=sp["serve_min_bucket"],
            max_bucket=sp["serve_max_bucket"],
            max_batch_rows=sp["serve_max_batch_rows"],
            max_wait_ms=sp["serve_max_wait_ms"],
            max_queue_rows=sp["serve_queue_rows"],
            poll_sec=sp["serve_poll_sec"],
            keep_versions=sp["serve_keep_versions"],
            warmup=bool(sp["serve_warmup"]),
            drain_sec=sp["serve_drain_sec"],
            max_body_mb=sp["serve_max_body_mb"],
            featurestore_mb=sp["serve_featurestore_mb"],
            catalog=cp["catalog"],
            catalog_default=cp["catalog_default"],
            catalog_mb=cp["serve_catalog_mb"],
            catalog_hysteresis_sec=cp["catalog_hysteresis_sec"],
            router_url=sp["serve_router_url"],
            replica_id=sp["serve_replica_id"],
            advertise_url=sp["serve_advertise_url"],
            quiet=self.silent != 0, block=True)
        return 0

    # ------------------------------------------------------- fleet_router
    def task_fleet_router(self) -> int:
        """Run the fleet routing front door (xgboost_tpu.fleet,
        SERVING.md fleet section).  Replicas join with
        ``task=serve serve_router_url=http://host:port``."""
        from xgboost_tpu.fleet import run_router
        fp = self.fleet_params
        cp = self.catalog_params
        run_router(
            host=fp["fleet_host"], port=fp["fleet_port"],
            lease_sec=fp["fleet_lease_sec"], hc_sec=fp["fleet_hc_sec"],
            inflight_budget=fp["fleet_inflight"],
            breaker_failures=fp["fleet_breaker_failures"],
            breaker_cooldown_sec=fp["fleet_breaker_cooldown_sec"],
            retry=bool(fp["fleet_retry"]),
            forward_timeout=fp["fleet_timeout_sec"],
            max_body_mb=fp["fleet_max_body_mb"],
            deadline_ms=fp["fleet_deadline_ms"],
            slow_eject_factor=fp["fleet_slow_eject_factor"],
            slow_eject_cooldown_sec=fp["fleet_slow_eject_cooldown_sec"],
            state_path=fp["fleet_state_path"],
            tenant_inflight=cp["tenant_inflight"],
            tenant_rate=cp["tenant_rate"],
            tenant_burst=cp["tenant_burst"],
            rollout_defaults={
                "canaries": fp["fleet_canaries"],
                "soak_sec": fp["fleet_soak_sec"],
                "gate_error_rate": fp["fleet_gate_error_rate"],
                "gate_p99_ms": fp["fleet_gate_p99_ms"],
            },
            quiet=self.silent != 0, block=True)
        return 0

    # ------------------------------------------------------------- placer
    def task_placer(self) -> int:
        """Run the autonomous placement controller (xgboost_tpu.placer,
        SERVING.md "Autonomous placement") against a fleet router:
        watch per-tenant load, bin-pack the ``placer_catalog`` models
        onto in-rotation replicas, push manifest deltas until the fleet
        converges.  Loops until SIGTERM/Ctrl-C."""
        from xgboost_tpu.catalog import parse_manifest
        from xgboost_tpu.placer import run_placer
        pp = self.placer_params
        router_url = pp["placer_router_url"]
        if not router_url:
            raise ValueError("task=placer requires placer_router_url=")
        if not pp["placer_catalog"]:
            raise ValueError("task=placer requires placer_catalog= "
                             "(name=path,... or a manifest file)")
        manifest = parse_manifest(pp["placer_catalog"])
        if self.silent < 2:
            print(f"[placer] managing {len(manifest)} tenant(s) on "
                  f"{router_url}", file=sys.stderr)
        run_placer(
            router_url, manifest,
            plan_path=pp["placer_plan_path"],
            placer_id=pp["placer_id"],
            tick_sec=pp["placer_tick_sec"],
            lease_sec=pp["placer_lease_sec"],
            replication=pp["placer_replication"],
            hot_replication=pp["placer_hot_replication"],
            hot_fraction=pp["placer_hot_fraction"],
            load_alpha=pp["placer_load_alpha"],
            block=True)
        return 0

    # ----------------------------------------------------------- pipeline
    def task_pipeline(self) -> int:
        """Run the continuous-training loop (xgboost_tpu.pipeline,
        PIPELINE.md): train → gate → publish against the model file the
        serving tier polls.  ``pipeline_data`` falls back to ``data=``;
        learner hyperparameters (objective, max_depth, ...) pass
        through like ``task=train``."""
        from xgboost_tpu.pipeline import run_pipeline
        pp = self.pipeline_params
        summary = run_pipeline(
            pp["pipeline_publish_path"],
            workdir=pp["pipeline_dir"],
            data=pp["pipeline_data"] or self.train_path,
            holdout=pp["pipeline_holdout"],
            rounds_per_cycle=pp["pipeline_rounds_per_cycle"],
            cycles=pp["pipeline_cycles"],
            metric=pp["pipeline_metric"],
            min_delta=pp["pipeline_min_delta"],
            max_regression=pp["pipeline_max_regression"],
            router_url=pp["pipeline_router_url"],
            publish_timeout_sec=pp["pipeline_publish_timeout_sec"],
            sleep_sec=pp["pipeline_sleep_sec"],
            params=self._params_dict(),
            quiet=self.silent != 0)
        if self.silent < 2:
            print(f"[pipeline] done: {summary}", file=sys.stderr)
        return 0 if summary.get("errors", 0) == 0 else 1

    # -------------------------------------------------------------- lanes
    def task_lanes(self) -> int:
        """Gang-batched multi-tenant continuous training
        (xgboost_tpu.pipeline.lanes, PIPELINE.md "Gang-batched lanes"):
        one train -> gate -> publish pipeline per ``lanes=`` tenant,
        with same-shape lanes vmap-stacked into one device dispatch per
        round segment.  Per-lane gate/publish knobs (metric, deltas,
        router, sleep) come from the pipeline_* table; learner
        hyperparameters pass through like ``task=train``."""
        from xgboost_tpu.catalog import parse_manifest
        from xgboost_tpu.pipeline import run_tenant_lanes
        lp = self.lane_params
        pp = self.pipeline_params
        if not lp["lanes"]:
            raise ValueError("task=lanes requires lanes= "
                             "(name=publish_path,... or a manifest "
                             "file)")
        manifest = parse_manifest(lp["lanes"])
        data = lp["lane_data"] or self.train_path
        holdout = lp["lane_holdout"]
        lanes = {}
        for name, publish_path in manifest.items():
            lanes[name] = dict(
                publish_path=publish_path,
                workdir=os.path.join(lp["lanes_dir"], name),
                data=data.replace("{lane}", name),
                holdout=holdout.replace("{lane}", name),
                rounds_per_cycle=lp["lane_rounds_per_cycle"],
                cycles=lp["lane_cycles"],
                metric=pp["pipeline_metric"],
                min_delta=pp["pipeline_min_delta"],
                max_regression=pp["pipeline_max_regression"],
                router_url=pp["pipeline_router_url"],
                publish_timeout_sec=pp["pipeline_publish_timeout_sec"],
                sleep_sec=pp["pipeline_sleep_sec"],
                params=self._params_dict())
        stacked = (None if lp["lane_stack"] < 0
                   else bool(lp["lane_stack"]))
        if self.silent < 2:
            print(f"[lanes] training {len(lanes)} tenant lane(s) "
                  f"(stacked={'auto' if stacked is None else stacked})",
                  file=sys.stderr)
        out = run_tenant_lanes(
            lanes, quiet=self.silent != 0, stacked=stacked,
            max_workers=lp["lane_max_workers"] or None,
            window_sec=lp["lane_window_ms"] / 1000.0)
        errors = sum(1 for v in out.values() if v.get("status") != "ok")
        if self.silent < 2:
            for name in sorted(out):
                print(f"[lanes] {name}: {out[name]}", file=sys.stderr)
        return 0 if errors == 0 else 1

    # ------------------------------------------------------------- stream
    def task_stream(self) -> int:
        """Run the streaming drift-aware loop (xgboost_tpu.stream,
        PIPELINE.md streaming section): consume row batches from the
        ``stream_dir`` spool as micro-cycles, track per-feature drift,
        refresh cuts online, and publish gated candidates.  Learner
        hyperparameters (objective, ema_fs, ...) pass through like
        ``task=train``."""
        from xgboost_tpu.stream import run_stream
        sp = self.stream_params
        summary = run_stream(
            sp["stream_publish_path"],
            workdir=sp["stream_workdir"],
            stream_dir=sp["stream_dir"],
            rounds_per_cycle=sp["stream_rounds_per_cycle"],
            cycles=sp["stream_cycles"],
            min_batches=sp["stream_min_batches"],
            max_batches=sp["stream_max_batches"],
            catchup_backlog=sp["stream_catchup_backlog"],
            max_backlog=sp["stream_max_backlog"],
            holdout_cycles=sp["stream_holdout_cycles"],
            metric=sp["stream_metric"],
            min_delta=sp["stream_min_delta"],
            max_regression=sp["stream_max_regression"],
            router_url=sp["stream_router_url"],
            sleep_sec=sp["stream_sleep_sec"],
            drift_threshold=sp["stream_drift_threshold"],
            drift_clear=sp["stream_drift_clear"],
            drift_window=sp["stream_drift_window"],
            sketch_size=sp["stream_sketch_size"],
            params=self._params_dict(),
            quiet=self.silent != 0,
            lane=sp["stream_lane"])
        if self.silent < 2:
            print(f"[stream] done: {summary}", file=sys.stderr)
        return 0 if summary.get("errors", 0) == 0 else 1

    # -------------------------------------------------------------- dump
    def task_dump(self) -> int:
        assert self.model_in, "model_in not specified"
        bst = self._make_booster()
        dumps = bst.get_dump(self.name_fmap, with_stats=self.dump_stats != 0)
        from xgboost_tpu.reliability.integrity import atomic_write
        text = "".join(f"booster[{i}]:\n{s}" for i, s in enumerate(dumps))
        atomic_write(self.name_dump, text.encode())
        return 0


def _looks_like_text(path: str) -> bool:
    """Cheap libsvm-text sniff: binary caches (npz/npy magics, NUL bytes)
    route to the magic-sniffing replicated loader."""
    try:
        with open(path, "rb") as f:
            head = f.read(256)
    except OSError:
        return False
    return bool(head) and b"\x00" not in head and not head.startswith(b"PK")


# -------------------------------------------------------- checkpointing
def _ckpt_path(ckpt_dir: str, version: int) -> str:
    return os.path.join(ckpt_dir, f"ckpt-{version:06d}.model")


def _save_checkpoint(ckpt_dir: str, bst, version: int) -> None:
    """Per-round checkpoint (the rabit::CheckPoint analog — the model
    is tiny, so a full save per round is cheap; SURVEY.md §5.3).
    ``save_model`` itself is atomic + CRC-footered (reliability/
    integrity.py), so a crash mid-save can never tear a ring member.
    Cost is accounted like the reference's report_stats checkpoint
    line: a ``ckpt.save`` span in the event log and the
    ``xgbtpu_training_checkpoint_*`` counters."""
    from xgboost_tpu.obs import span, training_metrics
    t0 = time.perf_counter()
    with span("ckpt.save", version=version):
        os.makedirs(ckpt_dir, exist_ok=True)
        bst.save_model(_ckpt_path(ckpt_dir, version))
        # keep only the two most recent checkpoints (ring replica analog)
        kept = sorted(f for f in os.listdir(ckpt_dir)
                      if re.fullmatch(r"ckpt-\d{6}\.model", f))
        for stale in kept[:-2]:
            os.remove(os.path.join(ckpt_dir, stale))
    tm = training_metrics()
    tm.checkpoints.inc()
    tm.checkpoint_seconds.inc(time.perf_counter() - t0)


def _load_checkpoint(ckpt_dir: str, bst, params: dict):
    """Resume from the newest VERIFIABLE checkpoint (rabit's two-replica
    ring made real): when the newest member fails verification — torn
    write, bit flip, unparseable — it is quarantined as ``*.corrupt``
    and the older replica is used instead; version 0 when nothing
    loads (reference xgboost_main.cpp:176-183)."""
    if not os.path.isdir(ckpt_dir):
        return bst, 0
    from xgboost_tpu.obs import event, span
    found = sorted(f for f in os.listdir(ckpt_dir)
                   if re.fullmatch(r"ckpt-\d{6}\.model", f))
    for name in reversed(found):
        path = os.path.join(ckpt_dir, name)
        # ONE read, verified, probed on a THROWAWAY booster, and only
        # then loaded into the real one from the SAME buffer: a failed
        # load can leave its target half-mutated (param/objective
        # adopted from a corrupt header before the state arrays
        # raised), and the real booster must keep the caller's config
        # when the whole ring is bad.  Re-reading between probe and
        # load would let the file change under us after verification.
        try:
            from xgboost_tpu.learner import Booster
            from xgboost_tpu.reliability.integrity import (
                read_file, verify_model_bytes)
            payload = verify_model_bytes(read_file(path), name=path)
            Booster().load_raw(payload, name=path)
        except OSError as e:
            # transient I/O (EIO, EMFILE, permission blip): the bytes
            # may be fine — do NOT quarantine; fall back for THIS
            # restart and let the next one retry the member
            print(f"[ckpt] {name} unreadable ({e}); trying the older "
                  "ring member (file left in place)", file=sys.stderr)
            continue
        except Exception as e:
            from xgboost_tpu.obs import reliability_metrics
            from xgboost_tpu.reliability.integrity import quarantine
            try:
                qpath = quarantine(path)
                q_msg = f"quarantined as {os.path.basename(qpath)}"
            except OSError as qe:
                # a failed rename must not abort the restart the ring
                # exists to survive
                q_msg = f"quarantine failed ({qe}); left in place"
            reliability_metrics().ring_fallbacks.inc()
            event("ckpt.ring_fallback", member=name, error=str(e))
            print(f"[ckpt] {name} failed verification ({e}); {q_msg}, "
                  "falling back to the older ring member",
                  file=sys.stderr)
            continue
        with span("ckpt.load", member=name, version=int(name[5:11])):
            bst.load_raw(payload, name=path)  # the verified buffer
            bst.set_param(params)
        return bst, int(name[5:11])
    return bst, 0


def _broadcast_checkpoint(bst, start_round: int, rank: int, params: dict):
    """Broadcast rank 0's recovered model + round to every rank
    (rabit::LoadCheckPoint, subtree/rabit/include/rabit.h:166-186)."""
    import numpy as np
    from jax.experimental import multihost_utils as mhu

    raw = bst.save_raw() if (rank == 0 and start_round > 0) else b""
    hdr = mhu.broadcast_one_to_all(
        np.array([len(raw), start_round], np.int64))
    n, rounds = int(hdr[0]), int(hdr[1])
    if n == 0:
        return bst, 0
    buf = np.zeros(n, np.uint8)
    if rank == 0:
        buf[:] = np.frombuffer(raw, np.uint8)
    buf = mhu.broadcast_one_to_all(buf)
    if rank != 0:
        bst.load_raw(buf.tobytes())
        bst.set_param(params)
    return bst, rounds


def main(argv: Optional[List[str]] = None) -> int:
    if argv is None:
        # process entry point (``python -m xgboost_tpu``): place the
        # persistent jit cache where it does not move between runs
        # (compile_cache.py), so a gang restart after a worker failure
        # (RECOVERY.md) or a repeated train/pred reloads compiled
        # executables instead of re-compiling.  Before any backend use.
        # An embedder calling ``main([...])`` keeps its own JAX
        # settings.
        from xgboost_tpu.compile_cache import configure_compile_cache
        configure_compile_cache()
    task = BoostLearnTask()
    task.set_param("seed", "0")
    return task.run(list(sys.argv[1:] if argv is None else argv))


if __name__ == "__main__":
    sys.exit(main())
