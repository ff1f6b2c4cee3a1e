"""Training entry points ``train`` and ``cv``.

Mirrors ``python-package/lightgbm/engine.py`` (train :18-229, cv :230-460):
callback-driven boosting loop, early stopping, evaluation recording,
stratified / grouped cross-validation folds.
"""
from __future__ import annotations

import collections
import copy
import os
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

from . import callback as callback_mod
from . import checkpoint as checkpoint_mod
from .basic import Booster, Dataset
from .config import canonicalize_params
from .utils import faults as faults_mod
from .utils import log
from .utils.cache import enable_persistent_cache


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj: Optional[Callable] = None, feval: Optional[Callable] = None,
          init_model: Optional[Union[str, Booster]] = None,
          feature_name: Union[str, List[str]] = "auto",
          categorical_feature: Union[str, List] = "auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[Dict] = None,
          verbose_eval: Union[bool, int] = True,
          learning_rates: Optional[Union[List[float], Callable]] = None,
          keep_training_booster: bool = True,
          callbacks: Optional[List[Callable]] = None,
          resume: Optional[Union[bool, str]] = None) -> Booster:
    """engine.py:18-229 analogue.

    ``resume`` (also the ``snapshot_resume`` param): ``True`` auto-detects
    the latest *valid* ``<output_model>.snapshot_iter_N`` checkpoint (a
    torn tail falls back to the previous good one) and continues training
    from it with bit-exact state — final model byte-identical to an
    uninterrupted run; a string resumes from that explicit checkpoint
    file.  See docs/ROBUSTNESS.md.
    """
    params = canonicalize_params(params)
    if "num_iterations" in params:
        num_boost_round = int(params.pop("num_iterations"))
    if "early_stopping_round" in params and params["early_stopping_round"]:
        early_stopping_rounds = int(params.pop("early_stopping_round"))
    enable_persistent_cache()
    # structured telemetry (lightgbm_tpu.obs): trace_path writes a
    # Chrome-trace span file; telemetry=true enables counters/spans without
    # a file.  The counter registry is reset per training so two runs in
    # one process never blur their kernel-identity evidence; the phase and
    # compile counters describe the process (a Dataset binned before this
    # call, a program an earlier booster compiled) and are kept.
    from .obs import devprof as obs_devprof
    from .obs import memory as obs_memory
    from .obs import trace as obs_trace
    from .obs.counters import PROCESS_COUNTERS, counters as obs_counters
    trace_path = str(params.get("trace_path", "") or "")
    # device-time attribution (obs/devprof.py): implies telemetry — its
    # device_profile block rides the trace file (the lgb: phase windows it
    # attributes by are in every profiler capture, switch or no switch)
    devprof_on = str(params.get("device_profile", "")).strip().lower() \
        in ("true", "1", "yes", "on", "+")
    telemetry_on = bool(trace_path) or devprof_on or str(
        params.get("telemetry", "")).strip().lower() in ("true", "1", "yes",
                                                         "on", "+")
    if telemetry_on:
        obs_counters.reset(keep=PROCESS_COUNTERS)
        obs_trace.start(trace_path or None)
        # device-memory accounting rides the same switch: per-iteration /
        # per-phase samples are host-side reads (memory_stats on TPU, a
        # live-array census on CPU) — zero added device synchronizations
        obs_memory.start()
    if devprof_on:
        obs_devprof.start(
            profile_iters=int(params.get("profile_iters", 2) or 2))
    # deterministic fault injection (utils/faults.py): a param-armed plan is
    # scoped to THIS training; an env-armed plan stays process-wide
    fault_spec = str(params.get("fault_inject", "") or "")
    prev_faults = faults_mod.get_faults()
    if fault_spec:
        faults_mod.install(fault_spec)
    # host_lost fault, startup leg: in a RELAUNCHED incarnation (the
    # supervisor stamps its attempt counter into child env) the lost
    # rank dies again BEFORE its first heartbeat — the repeatable
    # startup failure the supervisor's world_shrink_after counter is
    # defined over.  targets() (not fire()) so the @K pin stays armed
    # for the mid-run death of attempt 0.
    try:
        _sup_attempt = int(
            os.environ.get("LGBM_TPU_SUPERVISOR_ATTEMPT", "0") or 0)
    except ValueError:
        _sup_attempt = 0
    if _sup_attempt > 0:
        _fi = faults_mod.get_faults()
        if _fi.enabled and _fi.targets("host_lost",
                                       faults_mod.current_rank()):
            log.warning("host_lost fault: rank %d's host never comes "
                        "back — dying at startup of attempt %d (before "
                        "the first heartbeat)",
                        faults_mod.current_rank(), _sup_attempt)
            os._exit(70)
    # host-object collective budget (parallel/sync.py recovery ladder)
    from .parallel import sync as sync_mod
    if params.get("collective_timeout") or params.get("collective_retries") \
            is not None:
        sync_mod.configure(
            timeout=float(params["collective_timeout"])
            if params.get("collective_timeout") else None,
            retries=int(params["collective_retries"])
            if params.get("collective_retries") is not None else None)
    # elastic relaunch override: after a degraded-world shrink the
    # supervisor stamps the CURRENT world size into child env; the
    # user-level num_machines still describes the LAUNCH topology, so
    # reduce it here (a world of 1 then skips distributed bring-up — and
    # its dead-peer rendezvous — entirely)
    _env_world = os.environ.get("LGBM_TPU_WORLD", "")
    if _env_world.strip():
        try:
            _w = int(_env_world)
        except ValueError:
            _w = 0
        if _w >= 1 and _w != int(params.get("num_machines", 1) or 1):
            log.info("LGBM_TPU_WORLD=%d overrides num_machines=%s "
                     "(elastic relaunch at a shrunk world)", _w,
                     params.get("num_machines", 1))
            params["num_machines"] = _w
    if int(params.get("num_machines", 1)) > 1:
        # multi-host bring-up from config (application.cpp:190-224 analogue)
        from .config import config_from_params
        from .parallel.mesh import init_distributed_from_config
        init_distributed_from_config(config_from_params(params))
    if fobj is not None:
        params.setdefault("objective", "regression")

    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    booster = Booster(params=params, train_set=train_set)
    if init_model is not None:
        # continued training: load old model, use it as init scores
        prev = init_model if isinstance(init_model, Booster) \
            else Booster(model_file=str(init_model), params=params)
        raw = train_set.ensure_raw()
        if raw is None:
            log.fatal("Continued training requires raw data "
                      "(set free_raw_data=False)")
        init_scores = prev.inner.predictor().predict_raw(np.asarray(raw))
        booster.inner.scores = booster.inner.scores + np.asarray(
            init_scores, np.float32)
        booster.inner.num_init_iteration = prev.inner.current_iteration()
        booster.inner.models = list(prev.inner.models) + booster.inner.models
        booster.inner.boost_from_average_ = prev.inner.boost_from_average_

    valid_sets = valid_sets or []
    if isinstance(valid_sets, Dataset):
        valid_sets = [valid_sets]    # bare Dataset (python-guide examples)
    valid_names = valid_names or [f"valid_{i}" for i in range(len(valid_sets))]
    is_valid_contain_train = False
    train_data_name = "training"
    for vs, name in zip(valid_sets, valid_names):
        if vs is train_set:
            is_valid_contain_train = True
            train_data_name = name
            continue
        booster.add_valid(vs, name)

    cbs = list(callbacks or [])
    if verbose_eval is True:
        cbs.append(callback_mod.print_evaluation())
    elif isinstance(verbose_eval, int) and verbose_eval > 0:
        cbs.append(callback_mod.print_evaluation(verbose_eval))
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.append(callback_mod.early_stopping(early_stopping_rounds,
                                               bool(verbose_eval)))
    if learning_rates is not None:
        # per-iteration schedule, list or function(iter) (reference
        # engine.py:167-168 routes it through reset_parameter)
        cbs.append(callback_mod.reset_parameter(learning_rate=learning_rates))
    if evals_result is not None:
        cbs.append(callback_mod.record_evaluation(evals_result))
    cbs_before = [cb for cb in cbs if getattr(cb, "before_iteration", False)]
    cbs_after = [cb for cb in cbs if not getattr(cb, "before_iteration", False)]
    cbs_before.sort(key=lambda cb: getattr(cb, "order", 0))
    cbs_after.sort(key=lambda cb: getattr(cb, "order", 0))

    snapshot_freq = int(params.get("snapshot_freq", -1) or -1)
    snapshot_keep = int(params.get("snapshot_keep", -1) or -1)
    snapshot_out = str(params.get("output_model", "LightGBM_model.txt"))
    world = sync_mod.process_count()
    # single-process identity: a supervisor may run several INDEPENDENT
    # single-process workers under one prefix (LGBM_TPU_RANK env), whose
    # liveness artifacts — heartbeats, crash reports, flight streams —
    # must stay per-rank; distributed runs keep the jax process index
    rank = sync_mod.process_index() if world > 1 \
        else faults_mod.current_rank()
    single_process = world == 1
    # ---- the live telemetry plane (docs/OBSERVABILITY.md) ----
    # Both legs are scoped to THIS training (armed here, disarmed in the
    # finally) and both are pure host-side observers: the flight recorder
    # appends unsynced JSONL lines, the exporter serves scrapes off a
    # daemon thread — zero added collectives / device syncs (pinned).
    from .obs import flight as obs_flight
    from .obs import metrics as obs_metrics
    obs_stream = str(params.get("obs_stream_path", "") or "")
    flight_armed = False
    if obs_stream:
        obs_flight.start(obs_flight.stream_path(obs_stream, rank), rank=rank)
        flight_armed = True
    metrics_port = int(params.get("metrics_port", 0) or 0)
    exporter_armed = False
    if metrics_port > 0:
        obs_metrics.start_exporter(metrics_port + rank)
        exporter_armed = True
    # ---- the model-quality plane (split audit + importance gauges) ----
    # A pure host-side fold over arrays the boosting loop has ALREADY
    # fetched (the tree finalize drain), so arming it adds zero device
    # syncs and zero collectives (pinned).  model_quality=auto follows
    # the telemetry switch; on/off force it.
    from .obs import model_quality as obs_model_quality
    mq_armed = obs_model_quality.resolve_armed(
        booster.inner.config.model_quality, telemetry_on)
    if mq_armed:
        obs_model_quality.start(list(booster.inner.feature_names))
    ckpt_callbacks = cbs_before + cbs_after   # stable capture/restore order
    # elastic groups (docs/ROBUSTNESS.md): opt-in acceptance of committed
    # sets written at a DIFFERENT process count
    elastic = str(params.get("elastic_resume", "")).strip().lower() \
        in ("true", "1", "yes", "on", "+")
    _elastic_cache: List[Optional[Dict[str, Any]]] = [None]

    def _elastic_meta() -> Dict[str, Any]:
        """Partition metadata each shard ships through the existing commit
        barrier so the manifest carries GLOBAL row boundaries.  Cached:
        the partition cannot change mid-training, so the offset exchange
        is one extra allgather per TRAINING, not per snapshot."""
        if _elastic_cache[0] is None:
            ts = booster.inner.train_set
            n_local = int(ts.num_data)
            views = sorted(
                sync_mod.allgather_object({"rank": rank,
                                           "num_data": n_local}),
                key=lambda v: int(v["rank"]))
            off = sum(int(v["num_data"]) for v in views
                      if int(v["rank"]) < rank)
            _elastic_cache[0] = {
                "num_data": n_local,
                "valid_num_data": [int(vs.data.num_data)
                                   for vs in booster.inner.valid_sets],
                "fp_partial": checkpoint_mod.elastic_fingerprint_partial(
                    np.asarray(ts.binned), n_local, off),
                "num_features": int(np.asarray(ts.binned).shape[1]),
                "num_class": int(booster.inner.num_class),
                # model-shape knobs for the supervisor's W-1 mesh
                # pre-flight (plan_mesh sizes the histogram pool from
                # leaves x bins)
                "num_leaves": int(booster.inner.config.num_leaves),
                "max_bin": int(booster.inner.config.max_bin),
            }
        return _elastic_cache[0]

    def _write_checkpoint(iteration: int) -> None:
        """One atomic snapshot at an iteration boundary: the single-file
        checkpoint when alone, the coordinated shard-set protocol (shards
        -> CRC barrier -> rank-0 manifest commit) across processes."""
        if single_process:
            checkpoint_mod.write_snapshot(
                checkpoint_mod.snapshot_path(snapshot_out, iteration),
                booster, iteration, ckpt_callbacks, evals_result)
            if snapshot_keep > 0:
                checkpoint_mod.prune_snapshots(snapshot_out, snapshot_keep)
            return
        state = checkpoint_mod.capture_state(booster, iteration,
                                             ckpt_callbacks, evals_result)
        checkpoint_mod.write_group_snapshot(
            snapshot_out, iteration,
            booster.model_to_string(-1) if rank == 0 else "", state,
            rank=rank, world=world,
            fingerprint=booster.inner.data_fingerprint(),
            elastic_meta=_elastic_meta())
        if snapshot_keep > 0 and rank == 0:
            # only after the manifest commit, and only on rank 0: the
            # barrier guarantees every shard of the new set is durable, so
            # pruning can never race a peer's in-flight write
            checkpoint_mod.prune_snapshots(snapshot_out, snapshot_keep)

    # ---- resume from the latest valid snapshot (docs/ROBUSTNESS.md) ----
    if resume is None:
        resume = params.get("snapshot_resume", False)
    if isinstance(resume, str):
        s = resume.strip().lower()
        if s in ("false", "0", "no", "off", "-", ""):
            resume = False
        elif s in ("true", "1", "yes", "on", "+", "auto"):
            resume = True
    start_iter = 0
    if resume:
        if elastic:
            # the ELASTIC resume barrier (docs/ROBUSTNESS.md "Elastic
            # groups"): agree on the newest committed artifact at ANY
            # topology this group can reassemble — a W-rank set spliced
            # at global row boundaries, or a plain snapshot as a 1-rank
            # set (W->1 and 1->W are first-class)
            ts = booster.inner.train_set

            def _fp_partial(global_offset: int) -> int:
                return checkpoint_mod.elastic_fingerprint_partial(
                    np.asarray(ts.binned), int(ts.num_data),
                    int(global_offset))

            found = checkpoint_mod.find_latest_valid_elastic(
                snapshot_out, rank=rank, world=world,
                num_data=int(ts.num_data),
                valid_num_data=[int(vs.data.num_data)
                                for vs in booster.inner.valid_sets],
                fingerprint_partial_fn=_fp_partial,
                only_iteration=(checkpoint_mod.iteration_from_path(resume)
                                if isinstance(resume, str) else None))
        elif single_process:
            if isinstance(resume, str):    # explicit checkpoint file
                _, state = checkpoint_mod.load_snapshot(resume)
                found = (int(state["iteration"]), resume, state)
            else:                          # auto-detect; torn tails skipped
                found = checkpoint_mod.find_latest_valid(snapshot_out)
        else:
            # the resume barrier: ranks agree on the newest set valid on
            # EVERY rank (a torn shard anywhere demotes the whole group);
            # topology/partition mismatches raise a CheckpointError on all
            # ranks together instead of hanging the fleet
            found = checkpoint_mod.find_latest_valid_group(
                snapshot_out, rank=rank, world=world,
                fingerprint=booster.inner.data_fingerprint(),
                only_iteration=(checkpoint_mod.iteration_from_path(resume)
                                if isinstance(resume, str) else None))
        if found is None:
            log.info("snapshot_resume: no valid snapshot for %s; "
                     "training from scratch", snapshot_out)
        else:
            _, ck_path, state = found
            start_iter = checkpoint_mod.restore_state(
                booster, state, ckpt_callbacks, evals_result)
            obs_counters.event(
                "checkpoint_resume", iteration=start_iter, path=ck_path,
                kind="single" if single_process else "group")
            log.info("Resumed training from %s (continuing at "
                     "iteration %d)", ck_path, start_iter)

    # jax.profiler trace of the boosting loop (the reference's TIMETAG deep
    # profile becomes an xprof trace; lightweight counters are always on)
    profile_dir = params.get("profile_dir")
    import contextlib
    profile_ctx = contextlib.nullcontext()
    if profile_dir:
        import jax
        profile_ctx = jax.profiler.trace(str(profile_dir))

    # preemption safety (docs/ROBUSTNESS.md): SIGTERM/SIGINT request a
    # coordinated checkpoint at the next iteration boundary + a clean
    # exit.  Installed HERE, immediately before the try whose finally
    # restores the previous handlers, so they can never leak.
    preempt_watch = checkpoint_mod.PreemptionWatch(
        str(params.get("preempt_signal", "") or "")).install()
    preempt_armed = preempt_watch.armed or \
        faults_mod.get_faults().has_point("preempt")

    # liveness heartbeats (docs/ROBUSTNESS.md "Self-healing training"):
    # stamp iteration + wall-time into <output_model>.heartbeat.rank_R at
    # each boundary — pure host-side file writes on the happy path (the
    # zero-collectives pin of PR 6 extends over this), read by the
    # supervisor's hang detection.  Arming heartbeats also arms the
    # per-rank crash report on abnormal exit.
    heartbeat_interval = float(params.get("heartbeat_interval", 0) or 0)
    heartbeat = None
    if heartbeat_interval > 0:
        heartbeat = checkpoint_mod.Heartbeat(
            checkpoint_mod.heartbeat_path(snapshot_out, rank),
            heartbeat_interval)
        heartbeat.stamp(start_iter, force=True)

    def _boundary_liveness(iteration: int) -> None:
        """Once per iteration boundary: the supervisor-matrix fault points
        (a hard rank death / a wedged rank), then the heartbeat stamp."""
        fi = faults_mod.get_faults()
        if fi.enabled and fi.fire("rank_crash", iteration):
            log.warning("rank_crash fault: rank %d dying hard at "
                        "iteration %d (os._exit, no checkpoint, no "
                        "goodbye)", rank, iteration)
            os._exit(70)
        if fi.enabled and fi.fire("host_lost", iteration):
            log.warning("host_lost fault: rank %d dying hard at iteration "
                        "%d — and its host will NOT come back (every "
                        "relaunched incarnation dies again at startup)",
                        rank, iteration)
            os._exit(70)
        if fi.enabled and fi.fire("rank_hang", iteration):
            log.warning("rank_hang fault: rank %d wedging at iteration %d "
                        "(stand-in for a stuck device collective; "
                        "heartbeats stop now)", rank, iteration)
            import time as _time
            while True:          # only SIGKILL — or the supervisor — ends this
                _time.sleep(3600)
        if heartbeat is not None:
            heartbeat.stamp(iteration)

    train_span = obs_trace.phase("train", num_boost_round=num_boost_round)
    try:
        with profile_ctx, train_span:
            for i in range(start_iter, num_boost_round):
                for cb in cbs_before:
                    cb(callback_mod.CallbackEnv(
                        model=booster, params=params,
                        iteration=i, begin_iteration=0,
                        end_iteration=num_boost_round,
                        evaluation_result_list=None))
                finished = booster.update(fobj=fobj)

                evaluation_result_list = []
                if valid_sets:
                    if is_valid_contain_train:
                        evaluation_result_list.extend(
                            (train_data_name, m, v, hib)
                            for (_, m, v, hib) in booster.eval_train(feval))
                    evaluation_result_list.extend(booster.eval_valid(feval))
                try:
                    for cb in cbs_after:
                        cb(callback_mod.CallbackEnv(
                            model=booster, params=params, iteration=i,
                            begin_iteration=0, end_iteration=num_boost_round,
                            evaluation_result_list=evaluation_result_list))
                except callback_mod.EarlyStopException as es:
                    booster.best_iteration = es.best_iteration + 1
                    for item in (es.best_score or []):
                        booster.best_score.setdefault(
                            item[0], {})[item[1]] = item[2]
                    break
                # BEFORE the snapshot block: a rank_crash/rank_hang at
                # boundary K dies with iterations since the last committed
                # set genuinely lost — the shape of a real mid-run death
                _boundary_liveness(i + 1)
                wrote_snapshot = False
                if snapshot_freq > 0 and (i + 1) % snapshot_freq == 0:
                    # gbdt.cpp:456-460's snapshot cadence, upgraded to an
                    # atomic resumable checkpoint (coordinated shard set
                    # across processes).  AFTER the callbacks so the
                    # captured eval/early-stop state matches iteration i.
                    _write_checkpoint(i + 1)
                    wrote_snapshot = True
                if preempt_armed:
                    fi = faults_mod.get_faults()
                    want = preempt_watch.requested or \
                        (fi.enabled and fi.fire("preempt", i + 1))
                    if not single_process:
                        # a preemption notice may land on ONE rank only;
                        # the group must agree before anyone checkpoints
                        # or exits (hardened ladder: a dead peer surfaces
                        # as a named CollectiveError, not a hang)
                        want = any(sync_mod.allgather_object(bool(want)))
                    if want:
                        if not wrote_snapshot:
                            _write_checkpoint(i + 1)
                        obs_counters.event("preempt_checkpoint",
                                           iteration=i + 1)
                        log.info("Preemption requested: coordinated "
                                 "checkpoint written at iteration %d; "
                                 "exiting the training loop cleanly "
                                 "(snapshot_resume continues from here)",
                                 i + 1)
                        break
                if finished:
                    break
        # drain pipelined tree materialization NOW: deferred guard trips
        # (non-finite raise) and late no-split rewinds must surface from
        # train() itself, not from a later .models access
        booster.inner.models
        if booster.best_iteration <= 0:
            booster.best_iteration = booster.current_iteration()
        booster.inner.timers.report("training phase timers")
        if heartbeat is not None:
            heartbeat.stamp(booster.current_iteration(), force=True)
    except BaseException as e:
        # abnormal exit with heartbeats armed (i.e. a supervised rank):
        # flush a per-rank crash report — exception, every thread's stack,
        # the obs event-ring tail — so the supervisor can say WHY this
        # rank died without anyone re-running under a debugger.
        # EarlyStopException never reaches here (handled at the boundary);
        # SystemExit from the double-signal path and SimulatedCrash from
        # the fault matrix are exactly the deaths worth a report.
        if heartbeat is not None:
            checkpoint_mod.write_crash_report(snapshot_out, rank, exc=e)
        raise
    finally:
        preempt_watch.restore()   # handlers are scoped to THIS training
        if devprof_on:
            # finalize BEFORE the trace writes: the device_profile block
            # rides the trace as a telemetry.summary event so one file
            # carries the host spans AND the device attribution
            dp_summary = obs_devprof.stop()
            if dp_summary is not None:
                obs_trace.get_tracer().summary("device_profile", dp_summary)
        if telemetry_on:
            # recompile evidence: how many distinct (shape, donation)
            # entries the grower jit accumulated this training — a number
            # above the expected pow2-bucket count means buffer-identity
            # churn forced recompiles
            grow = getattr(booster.inner, "grow", None)
            cache_size = getattr(grow, "_cache_size", None)
            if callable(cache_size):
                try:
                    obs_counters.gauge("grower_jit_entries",
                                       int(cache_size()))
                except (TypeError, ValueError) as e:
                    # a gauge is best-effort, but anything beyond a size
                    # that won't coerce to int is a real bug — let it raise
                    log.debug("grower_jit_entries gauge unavailable: %s", e)
            # GSPMD trainings: record the compiled-HLO collective census
            # (compiler-inserted collectives never hit a call-site
            # counter) so the trace's final snapshot carries the real
            # communication story (docs/DISTRIBUTED.md).  The lowering
            # re-hits the persistent compilation cache, so this is a
            # read, not a second compile, on any warm run.
            if getattr(booster.inner, "_gspmd_mesh", None) is not None:
                try:
                    booster.inner.grow_hlo_census()
                except Exception as e:   # telemetry is best-effort
                    log.debug("grow HLO census unavailable: %s", e)
            # flush the memory summary (peak gauge + top residents event)
            # BEFORE the trace writes its final counter snapshot, so the
            # trace file carries the whole memory story
            obs_memory.stop()
            if mq_armed:
                # model-quality summary (top features by gain, gain-decay
                # curve) rides the trace like the device_profile block so
                # one file carries the whole training story
                obs_trace.get_tracer().summary(
                    "model_quality",
                    obs_model_quality.get_tracker().summary())
            obs_trace.stop()
        if mq_armed:
            # cache the training bin distribution on the booster while
            # the plane is still armed — later model saves embed it for
            # the serving drift monitor (one host bincount pass)
            try:
                booster.inner._training_distribution()
            except Exception as e:   # telemetry is best-effort
                log.debug("training distribution unavailable: %s", e)
            # after the trace summary (needs the live tracker) but before
            # the flight stop — the tracker itself never writes at stop
            obs_model_quality.stop()
        if exporter_armed:
            obs_metrics.stop_exporter()
        if flight_armed:
            # after memory/trace teardown so their final events (the
            # memory_summary, late checkpoint events) still stream
            obs_flight.stop()
        if fault_spec:
            faults_mod.restore(prev_faults)
    return booster


class CVBooster:
    """All per-fold boosters of a cv run (reference engine.py:230-252):
    unknown attribute access dispatches the call to every fold's booster
    and returns the list of results."""

    def __init__(self, boosters=None):
        self.boosters = list(boosters or [])
        self.best_iteration = -1

    def append(self, booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)

        def handler(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs)
                    for b in self.boosters]
        return handler


def _make_n_folds(full_data: Dataset, nfold: int, params: Dict,
                  seed: int, stratified: bool, shuffle: bool,
                  group_info: Optional[np.ndarray]):
    full_data.construct()
    num_data = full_data.num_data()
    rng = np.random.RandomState(seed)
    if group_info is not None:
        # group-aware folds: split whole queries
        group_sizes = np.asarray(group_info, dtype=np.int64)
        ngroups = len(group_sizes)
        gidx = np.arange(ngroups)
        if shuffle:
            rng.shuffle(gidx)
        folds_groups = np.array_split(gidx, nfold)
        bounds = np.concatenate([[0], np.cumsum(group_sizes)])
        for fg in folds_groups:
            test_idx = np.concatenate(
                [np.arange(bounds[g], bounds[g + 1]) for g in fg]) \
                if len(fg) else np.empty(0, dtype=np.int64)
            yield np.setdiff1d(np.arange(num_data), test_idx), test_idx, fg
        return
    if stratified:
        label = full_data.get_label().astype(np.int64)
        folds = [[] for _ in range(nfold)]
        for cls in np.unique(label):
            idx = np.nonzero(label == cls)[0]
            if shuffle:
                rng.shuffle(idx)
            for f, part in enumerate(np.array_split(idx, nfold)):
                folds[f].append(part)
        for f in range(nfold):
            test_idx = np.concatenate(folds[f])
            yield np.setdiff1d(np.arange(num_data), test_idx), test_idx, None
        return
    idx = np.arange(num_data)
    if shuffle:
        rng.shuffle(idx)
    for part in np.array_split(idx, nfold):
        yield np.setdiff1d(np.arange(num_data), part), part, None


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics: Optional[Union[str, List[str]]] = None,
       fobj=None, feval=None, init_model=None,
       feature_name="auto", categorical_feature="auto",
       early_stopping_rounds: Optional[int] = None,
       verbose_eval=None, seed: int = 0,
       callbacks: Optional[List[Callable]] = None,
       eval_train_metric: bool = False) -> Dict[str, List[float]]:
    """engine.py:230-460 analogue; returns {metric-mean: [...], metric-stdv: [...]}."""
    params = canonicalize_params(params)
    if "num_iterations" in params:
        num_boost_round = int(params.pop("num_iterations"))
    if metrics is not None:
        params["metric"] = metrics
    if params.get("objective", "").startswith(("binary",)) is False \
            and params.get("objective") not in ("binary", "multiclass",
                                                "multiclassova"):
        stratified = False if params.get("objective") else stratified

    train_set.construct()
    raw = train_set.ensure_raw()
    if raw is None:
        log.fatal("cv requires raw data (set free_raw_data=False)")
    label = train_set.get_label()
    weight = train_set.get_weight()
    group = train_set.get_group()

    if folds is None:
        folds = list(_make_n_folds(train_set, nfold, params, seed,
                                   stratified and group is None, shuffle, group))
    else:
        folds = [(tr, te, None) if len(f) == 2 else f
                 for f in (tuple(f) for f in folds)]

    boosters: List[Booster] = []
    for train_idx, test_idx, fold_groups in folds:
        tr = Dataset(raw[train_idx], label=label[train_idx],
                     weight=None if weight is None else weight[train_idx],
                     params=dict(params))
        te_ref = tr.create_valid(
            raw[test_idx], label=label[test_idx],
            weight=None if weight is None else weight[test_idx])
        if group is not None:
            # recompute per-fold group sizes
            gsizes = np.asarray(group, dtype=np.int64)
            gid = np.repeat(np.arange(len(gsizes)), gsizes)
            tr.group = np.bincount(gid[train_idx])[np.unique(gid[train_idx])]
            te_ref.group = np.bincount(gid[test_idx])[np.unique(gid[test_idx])]
        booster = Booster(params=dict(params), train_set=tr)
        booster.add_valid(te_ref, "valid")
        boosters.append(booster)

    results: Dict[str, List[float]] = collections.defaultdict(list)
    es_cb = (callback_mod.early_stopping(early_stopping_rounds, False)
             if early_stopping_rounds else None)
    for i in range(num_boost_round):
        all_evals = []
        for booster in boosters:
            booster.update(fobj=fobj)
            evals = booster.eval_valid(feval)
            if eval_train_metric:
                evals = list(booster.eval_train(feval)) + list(evals)
            all_evals.append(evals)
        # aggregate across folds
        agg: Dict[tuple, List[float]] = collections.defaultdict(list)
        order: List[tuple] = []
        for evals in all_evals:
            for name, metric, value, hib in evals:
                key = (name, metric, hib)
                if key not in agg:
                    order.append(key)
                agg[key].append(value)
        merged = []
        for key in order:
            name, metric, hib = key
            vals = agg[key]
            mean, std = float(np.mean(vals)), float(np.std(vals))
            results[f"{metric}-mean"].append(mean)
            results[f"{metric}-stdv"].append(std)
            merged.append((f"cv_agg {name}", metric, mean, hib, std))
        if verbose_eval:
            log.info("[%d]\t%s", i + 1,
                     "\t".join(f"{m[1]}: {m[2]:g} + {m[4]:g}" for m in merged))
        if es_cb is not None:
            try:
                es_cb(callback_mod.CallbackEnv(
                    model=CVBooster(boosters), params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=merged))
            except callback_mod.EarlyStopException as es:
                for k in results:
                    results[k] = results[k][:es.best_iteration + 1]
                break
    return dict(results)
