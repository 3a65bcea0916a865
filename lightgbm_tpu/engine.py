"""Training entry points: train() and cv() (ref: python-package/lightgbm/engine.py)."""

from __future__ import annotations

import copy
import json
import os
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

from .basic import Booster, Dataset
from .callback import (CallbackEnv, EarlyStopException, checkpoint,
                       early_stopping, log_evaluation, record_metrics)
from .config import Config
from .reliability import CheckpointManager, NonFiniteError
from .utils import atomic_write_text, log
from .utils.timer import global_timer


def _check_finite(booster: Booster, evals, iteration: int,
                  check_scores: bool) -> None:
    """Non-finite sentinel (reliability pillar 3): NaN gradients or eval
    scores mean every subsequent tree is garbage — fail fast instead of
    silently training on.  Both device-side flags (gradients and the
    FULL score buffer, not the old 256-row host sample) ride the eval
    tick's packed fetch when device metrics are on — the sentinel costs
    no extra host sync (docs/Performance.md)."""
    for name, metric, value, _ in evals:
        if value != value:  # NaN
            raise NonFiniteError(
                f"Evaluation metric {name} {metric} is NaN at iteration "
                f"{iteration + 1}. The model scores are corrupt — check the "
                "objective/labels for invalid values (or resume from a "
                "checkpoint). Set nonfinite_check_freq=0 to disable this "
                "sentinel.")
    if check_scores:
        if not booster._gbdt.gradients_finite():
            raise NonFiniteError(
                f"Non-finite gradients detected at (or before) iteration "
                f"{iteration + 1}: the split program masks NaN gains to "
                "zero, so every tree since the corruption is garbage. "
                "Check the objective/labels for invalid values (or resume "
                "from a checkpoint). Set nonfinite_check_freq=0 to disable "
                "this sentinel.")
        if not booster._gbdt.scores_finite():
            raise NonFiniteError(
                f"Non-finite training scores detected at iteration "
                f"{iteration + 1}: the gradients or tree outputs contain "
                "NaN/Inf. Check the objective, labels and learning_rate "
                "(or resume from a checkpoint). Set nonfinite_check_freq=0 "
                "to disable this sentinel.")


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          feval=None, init_model: Optional[Union[str, Booster]] = None,
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None,
          fobj=None,
          checkpoint_dir: Optional[str] = None,
          checkpoint_freq: Optional[int] = None,
          resume: Optional[bool] = None,
          metrics_dir: Optional[str] = None) -> Booster:
    """ref: engine.py:66 train.

    Reliability extensions (docs/Reliability.md): `checkpoint_dir`
    enables periodic atomic checkpoints every `checkpoint_freq`
    iterations; with `resume` (default True) a run restarted with the
    same directory continues from the newest checkpoint instead of from
    zero, reproducing the uninterrupted run byte-for-byte.  All three
    can also be given as params (`checkpoint_dir=...` etc.).

    Observability extensions (docs/Observability.md): `metrics_dir`
    (also a param) appends a structured JSONL event per iteration —
    phase timings, eval results, tree stats, checkpoint/fault/retry
    events — to `<metrics_dir>/events-rank<r>.jsonl`; the `profile_dir`
    param brackets the run with jax.profiler.start_trace/stop_trace for
    TensorBoard device timelines."""
    params = dict(params or {})
    cfg = Config(params)
    # an explicitly-passed num_iterations (or alias) wins over the function
    # default, matching the reference alias resolution (ref: engine.py:145-152)
    if "num_iterations" in cfg.raw_params:
        num_boost_round = cfg.num_iterations

    if checkpoint_dir is None:
        checkpoint_dir = cfg.checkpoint_dir or None
    if checkpoint_freq is None:
        checkpoint_freq = cfg.checkpoint_freq
    if resume is None:
        resume = cfg.resume
    if metrics_dir is None:
        metrics_dir = cfg.metrics_dir or None

    # ---- degradation ladder (docs/Reliability.md) ----
    # a previous attempt that HUNG left a stall-rank<r>.json in
    # metrics_dir; with auto_degrade this restart consumes it, disables
    # the next risky knob (donation -> compile cache -> async_host_io ->
    # device_eval) and resumes from the checkpoint instead of re-hanging
    degrade_info = {"applied": [], "new": [], "stall": None}
    if cfg.auto_degrade:
        from .observability import process_rank
        from .reliability.guard import apply_auto_degrade
        degrade_info = apply_auto_degrade(cfg, params, metrics_dir,
                                          rank=process_rank())
    # async host services (docs/Performance.md): one bounded writer
    # thread drains event-log appends and checkpoint serialization so
    # the training loop never blocks on host I/O; `async_host_io=false`
    # restores synchronous writes (byte-identical output either way)
    writer = None
    if cfg.async_host_io and (checkpoint_dir or metrics_dir):
        from .observability import AsyncWriter
        writer = AsyncWriter()
    ckpt_mgr = (CheckpointManager(checkpoint_dir,
                                  keep_last=cfg.checkpoint_keep,
                                  params=params, writer=writer)
                if checkpoint_dir else None)
    if writer is not None or metrics_dir or ckpt_mgr is not None:
        # a supervisor SIGTERM must flush the queued events/checkpoints
        # before the process dies — the log tail is the diagnosis; with
        # a checkpoint dir the handler additionally saves an on-demand
        # checkpoint (preemption notice, docs/Reliability.md)
        from .observability import install_sigterm_flush
        install_sigterm_flush()
    # ---- preemption checkpoint-on-demand (docs/Reliability.md) ----
    # `_progress` is the handler's view of the run: the live booster,
    # the last COMPLETED iteration, and whether the main thread is
    # inside booster.update() right now — mid-update, model text /
    # scores / iteration are not a consistent triple, so the save is
    # deferred to the iteration boundary (`preempt_pending`)
    _progress: Dict[str, Any] = {"booster": None, "iteration": 0,
                                 "in_update": False,
                                 "preempt_pending": False}
    if ckpt_mgr is not None and cfg.preempt_ckpt_grace_s > 0:
        import time as _time

        from .observability import set_preemption_hook

        def _preempt_save():
            if _progress["in_update"]:
                # signal landed mid-update: queue it; the loop saves at
                # the iteration boundary and finishes the termination
                _progress["preempt_pending"] = True
                return False
            booster = _progress["booster"]
            it = int(_progress["iteration"])
            if booster is None or it <= 0:
                return True
            from .observability import emit_event, global_registry
            t0 = _time.monotonic()
            saved = False
            try:
                saved = ckpt_mgr.save_now(
                    booster, it, grace_s=cfg.preempt_ckpt_grace_s) is not None
            except OSError as e:
                log.warning(f"Preemption checkpoint at iteration {it} "
                            f"failed: {e}")
            if saved:
                global_registry.inc("preempt_ckpt_saved")
            emit_event("preempt", iteration=it, saved=saved,
                       elapsed_s=round(_time.monotonic() - t0, 3),
                       grace_s=cfg.preempt_ckpt_grace_s)
            return True

        set_preemption_hook(_preempt_save)

    # ---- observability setup (docs/Observability.md) ----
    profile_dir = cfg.profile_dir or None
    event_logger = None
    timer_was_syncing = global_timer.sync
    cost_was_enabled = None
    metrics_srv = None
    if metrics_dir:
        from .observability import EventLogger, set_event_logger
        event_logger = EventLogger(metrics_dir,
                                   rotate_mb=cfg.metrics_rotate_mb,
                                   writer=writer)
        set_event_logger(event_logger)
        # the per-iteration phase breakdown diffs global_timer snapshots;
        # a metrics run syncs at every phase boundary so that each phase
        # is charged its own device work (restored afterwards)
        global_timer.sync = True
        if cfg.roofline:
            # compiled-cost accounting: per-phase measured MFU +
            # roofline classification in the iteration events
            # (observability/costmodel.py; restored afterwards)
            from .observability import enable_cost_model
            cost_was_enabled = enable_cost_model(True)
        # flight recorder bound + SIGUSR2 on-demand dump: `kill -USR2`
        # writes <metrics_dir>/flight-rank<r>.json from the live run
        from .observability import process_rank as _prank
        from .observability.flightrec import flight_recorder
        from .reliability.faults import register_flight_dump_signal
        flight_recorder.resize(cfg.flight_recorder_size)
        register_flight_dump_signal(metrics_dir, rank=_prank())
        event_logger.emit("train_start", num_boost_round=num_boost_round,
                          params=cfg.changed_params())
        if degrade_info["new"]:
            # one `degrade` event per ladder step, right at the top of
            # the restarted run's log
            event_logger.emit("degrade", knobs=degrade_info["new"],
                              active=degrade_info["applied"],
                              stall_iteration=(degrade_info["stall"] or {})
                              .get("last_iteration"))
    if cfg.metrics_port >= 0:
        # the trainer exports the same registry snapshot the serving
        # daemon scrapes: counters, gauges, cost totals — GET /metrics
        # (observability/prom.py), shut down with the run
        from .observability import start_metrics_http
        metrics_srv = start_metrics_http(cfg.metrics_port)
    profiling = False
    if profile_dir:
        try:
            import jax
            jax.profiler.start_trace(profile_dir)
            profiling = True
            log.info(f"jax profiler trace started; timeline will be "
                     f"written to {profile_dir}")
        except Exception as e:  # profiling must never block training
            log.warning(f"Could not start the jax profiler trace in "
                        f"{profile_dir}: {e}")

    start_iteration = 0
    resume_ckpt = None
    if ckpt_mgr is not None and resume:
        ck = ckpt_mgr.resumable(params)
        if ck is not None:
            if init_model is not None:
                log.warning("Both init_model and a resumable checkpoint "
                            "were given; the checkpoint wins")
            init_model = ck.model_path
            start_iteration = min(ck.iteration, num_boost_round)
            resume_ckpt = ck
            log.info(f"Resuming from checkpoint at iteration {ck.iteration} "
                     f"({ck.model_path})")

    user_callbacks = list(callbacks or [])

    def _build_booster() -> Booster:
        booster = Booster(params=params, train_set=train_set)
        booster._train_in_valid = False
        valid_wrappers: List[Dataset] = []
        if valid_sets:
            for i, vs in enumerate(valid_sets):
                if vs is train_set:
                    booster._train_in_valid = True
                    continue
                name = (valid_names[i] if valid_names and i < len(valid_names)
                        else f"valid_{i}")
                booster.add_valid(vs, name)
                valid_wrappers.append(vs)

        if init_model is not None:
            # continued training (ref: engine.py init_model ->
            # _InnerPredictor; the previous model's trees are adopted and
            # its predictions seed the scores, so the returned booster
            # contains old + new trees)
            import os
            if isinstance(init_model, Booster):
                prev = init_model
            elif isinstance(init_model, (str, bytes, os.PathLike)):
                prev = Booster(model_file=os.fspath(init_model))
            else:
                log.fatal(f"Unknown init_model type: {type(init_model)}")

            def _raw_of(ds):
                d = getattr(ds, "data", None)
                if d is None or isinstance(d, (str, bytes)):
                    return None
                return d.values if hasattr(d, "values") else np.asarray(d)

            booster._gbdt.continue_from(
                prev._gbdt, train_raw=_raw_of(train_set),
                valid_raws=[_raw_of(vs) for vs in valid_wrappers])
            if resume_ckpt is not None:
                # checkpoint resume goes beyond init_model: restore the
                # EXACT score buffer and RNG streams so training continues
                # as if never interrupted (byte-identical final model)
                booster._gbdt.restore_train_state(resume_ckpt.load_state())
        return booster

    # ---- stall watchdog (reliability/guard.py) ----
    # active when there is somewhere for the diagnosis to land: the run's
    # metrics_dir, or the directory the distributed supervisor provided
    # (LGBM_TPU_STALL_DIR / the heartbeat file's directory)
    run_guard = None
    hb_path = os.environ.get("LGBM_TPU_HEARTBEAT_FILE") or None
    guard_dir = (metrics_dir or os.environ.get("LGBM_TPU_STALL_DIR")
                 or (os.path.dirname(hb_path) if hb_path else None))
    if cfg.stall_floor_s > 0 and guard_dir:
        from .observability import process_rank
        from .reliability.guard import RunGuard
        run_guard = RunGuard(
            guard_dir, rank=process_rank(),
            stall_floor_s=cfg.stall_floor_s,
            stall_factor=cfg.stall_factor,
            knobs={"tpu_donate_buffers": cfg.tpu_donate_buffers,
                   "async_host_io": cfg.async_host_io,
                   "compile_cache_dir": cfg.compile_cache_dir,
                   "device_eval": cfg.device_eval,
                   "sharded_wave": False,
                   "auto_degrade": cfg.auto_degrade,
                   "degraded_knobs": list(degrade_info["applied"])},
            heartbeat_path=hb_path, writer=writer)
        run_guard.start()

    rollbacks = 0
    try:
        while True:
            booster = _build_booster()
            _progress["booster"] = booster
            _progress["iteration"] = start_iteration
            if run_guard is not None:
                # the mesh (sharded wave) engages only once the booster
                # exists — refresh the risky-knob fingerprint
                gbdt = getattr(booster, "_gbdt", None)
                run_guard.update_knobs(
                    sharded_wave=bool(getattr(gbdt, "mesh", None)
                                      is not None),
                    growth_strategy=getattr(gbdt, "growth_strategy", None))
            callbacks = list(user_callbacks)
            if cfg.early_stopping_round > 0 and valid_sets:
                callbacks.append(early_stopping(
                    cfg.early_stopping_round, cfg.first_metric_only,
                    verbose=cfg.verbosity >= 1,
                    min_delta=cfg.early_stopping_min_delta))
            if cfg.verbosity >= 1 and cfg.metric_freq > 0:
                callbacks.append(log_evaluation(cfg.metric_freq))
            if ckpt_mgr is not None and checkpoint_freq \
                    and checkpoint_freq > 0:
                callbacks.append(checkpoint(checkpoint_dir,
                                            frequency=checkpoint_freq,
                                            manager=ckpt_mgr))
            if event_logger is not None:
                callbacks.append(record_metrics(logger=event_logger))
            callbacks_before = [cb for cb in callbacks
                                if getattr(cb, "before_iteration", False)]
            callbacks_after = [cb for cb in callbacks
                               if not getattr(cb, "before_iteration", False)]
            callbacks_before.sort(key=lambda cb: getattr(cb, "order", 0))
            callbacks_after.sort(key=lambda cb: getattr(cb, "order", 0))

            booster.best_iteration = -1
            train_has_metric = (bool(cfg.is_provide_training_metric)
                                or booster._train_in_valid)
            sentinel_freq = max(int(cfg.nonfinite_check_freq), 0)
            try:
                for i in range(start_iteration, num_boost_round):
                    env = CallbackEnv(model=booster, params=params,
                                      iteration=i,
                                      begin_iteration=start_iteration,
                                      end_iteration=num_boost_round,
                                      evaluation_result_list=[])
                    for cb in callbacks_before:
                        cb(env)
                    _progress["in_update"] = True
                    stopped = booster.update(fobj=fobj)
                    # the model/scores now describe iteration i+1 —
                    # publish that BEFORE clearing in_update so a
                    # preemption landing here saves a consistent triple
                    _progress["iteration"] = i + 1
                    _progress["in_update"] = False
                    if _progress["preempt_pending"]:
                        # a SIGTERM arrived mid-update; save at this
                        # boundary, then finish the termination the
                        # handler suppressed
                        _progress["preempt_pending"] = False
                        _preempt_save()
                        from .observability.hostio import finish_preemption
                        finish_preemption()
                    if stopped:
                        break
                    evals = []
                    with global_timer.scope("GBDT::eval"):
                        if train_has_metric:
                            evals.extend(booster.eval_train(feval))
                        evals.extend(booster.eval_valid(feval))
                    if sentinel_freq > 0:
                        if (i + 1) % sentinel_freq == 0:
                            # device-memory watchdog rides the sentinel
                            # tick: the HBM gauges land in the registry
                            # and thus in the next iteration event
                            from .observability import update_memory_gauges
                            update_memory_gauges()
                        # always check right before a checkpoint write, so
                        # a checkpoint never captures a silently-corrupt
                        # model (rollback would otherwise resume into the
                        # garbage)
                        will_ckpt = (ckpt_mgr is not None and checkpoint_freq
                                     and checkpoint_freq > 0
                                     and ((i + 1) % checkpoint_freq == 0
                                          or i + 1 == num_boost_round))
                        _check_finite(
                            booster, evals, i,
                            check_scores=((i + 1) % sentinel_freq == 0
                                          or will_ckpt))
                    env.evaluation_result_list = evals
                    for cb in callbacks_after:
                        cb(env)
                    if run_guard is not None:
                        run_guard.tick(i + 1)
                        if event_logger is None:
                            # guarded-but-unmetered runs (supervisor
                            # heartbeat dir, no metrics_dir) still leave
                            # a minimal trail for the stall diagnosis's
                            # flight tail; metrics runs get the rich
                            # record from record_metrics instead
                            from .observability.flightrec import \
                                flight_recorder
                            flight_recorder.record_iteration(
                                iteration=i + 1)
            except EarlyStopException as e:
                booster.best_iteration = e.best_iteration + 1
                for name, metric, value, _ in e.best_score:
                    booster.best_score.setdefault(name, {})[metric] = value
            except NonFiniteError as e:
                if writer is not None:
                    # an async checkpoint may still be in flight: land it
                    # before deciding where to roll back to
                    writer.flush()
                ck = (ckpt_mgr.resumable(params) if ckpt_mgr is not None
                      else None)
                if ck is None or rollbacks >= 1:
                    raise
                # roll back: rebuild from the last good checkpoint and
                # re-run the lost iterations (transient faults don't
                # recur; a persistent one raises on the second strike)
                rollbacks += 1
                from .observability import emit_event, global_registry
                global_registry.inc("rollback_retries")
                emit_event("rollback_retry", from_iteration=ck.iteration,
                           error=str(e))
                log.warning(f"{e}\nRolling back to the checkpoint at "
                            f"iteration {ck.iteration} and retrying once")
                init_model = ck.model_path
                start_iteration = min(ck.iteration, num_boost_round)
                resume_ckpt = ck
                continue
            break

        if booster.best_iteration < 0:
            evals = booster.eval_valid(feval)
            for name, metric, value, _ in evals:
                booster.best_score.setdefault(name, {})[metric] = value
        if event_logger is not None:
            if writer is not None:
                # land any in-flight checkpoint (and its event) first so
                # train_end stays the log's terminal record
                writer.flush()
            from .observability import global_registry
            event_logger.emit(
                "train_end", total_iterations=booster.current_iteration(),
                best_iteration=booster.best_iteration,
                # post-flush counter snapshot: per-iteration counters can
                # lag async checkpoint writes; this one is settled
                counters=global_registry.snapshot()["counters"])
        return booster
    finally:
        import sys as _sys
        if _sys.exc_info()[0] is not None and (metrics_dir or guard_dir):
            # crashing: dump the flight recorder synchronously next to
            # the logs so the supervisor's crash classification can
            # surface what the rank was doing (flight-rank<r>.json)
            from .observability import process_rank as _prank
            from .observability.flightrec import dump_flight_record
            dump_flight_record(metrics_dir or guard_dir, rank=_prank(),
                               reason="crash")
        if ckpt_mgr is not None and cfg.preempt_ckpt_grace_s > 0:
            from .observability import clear_preemption_hook
            clear_preemption_hook()
        if run_guard is not None:
            run_guard.stop()
        global_timer.sync = timer_was_syncing
        if cost_was_enabled is not None:
            from .observability import enable_cost_model
            enable_cost_model(cost_was_enabled)
        if metrics_srv is not None:
            metrics_srv.shutdown()
        if profiling:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception as e:
                log.warning(f"jax profiler stop_trace failed: {e}")
        if writer is not None:
            # drain queued events/checkpoints on train end AND on error
            # (a crashed run's log stays complete up to the failure)
            writer.close()
        if event_logger is not None:
            from .observability import set_event_logger
            set_event_logger(None)
            event_logger.close()


class CVBooster:
    """Holds the per-fold boosters of cv() and redirects method calls to
    each, returning per-fold result lists (ref: python-package engine.py
    CVBooster).  Serializes as JSON of model texts + best_iteration."""

    def __init__(self, model_file=None):
        self.boosters: List[Booster] = []
        self.best_iteration = -1
        if model_file is not None:
            with open(model_file) as f:
                self._load(json.loads(f.read()))

    def _load(self, payload: Dict[str, Any]) -> None:
        self.best_iteration = payload["best_iteration"]
        self.boosters = [Booster(model_str=s) for s in payload["boosters"]]

    def model_from_string(self, model_str: str) -> "CVBooster":
        self._load(json.loads(model_str))
        return self

    def model_to_string(self, num_iteration=None, start_iteration=0,
                        importance_type="split") -> str:
        return json.dumps({
            "boosters": [b.model_to_string(num_iteration=num_iteration,
                                           start_iteration=start_iteration,
                                           importance_type=importance_type)
                         for b in self.boosters],
            "best_iteration": self.best_iteration})

    def save_model(self, filename, num_iteration=None, start_iteration=0,
                   importance_type="split") -> "CVBooster":
        atomic_write_text(filename,
                          self.model_to_string(num_iteration, start_iteration,
                                               importance_type))
        return self

    def __getattr__(self, name: str):
        if name.startswith("_") or name in ("boosters", "best_iteration"):
            raise AttributeError(name)

        def per_fold(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs)
                    for b in self.boosters]
        return per_fold


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, feval=None, init_model=None,
       callbacks: Optional[List[Callable]] = None, seed: int = 0,
       eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """K-fold cross-validation (ref: engine.py:580 cv)."""
    params = dict(params or {})
    if metrics is not None:
        params["metric"] = metrics
    cfg = Config(params)
    if "num_iterations" in cfg.raw_params:
        num_boost_round = cfg.num_iterations
    core = train_set._core_or_construct()
    n = core.num_data
    label = np.asarray(core.metadata.label)
    rng = np.random.RandomState(seed)

    qb = core.metadata.query_boundaries
    if folds is None and qb is not None:
        # query-aware folds for ranking: whole queries go to one fold
        # (ref: python-package engine.py _make_n_folds group branch —
        # splitting inside a query would leak rank context across folds)
        qb = np.asarray(qb)
        nq = len(qb) - 1
        if nq < nfold:
            log.fatal(f"cv with ranking data needs >= nfold queries "
                      f"(got {nq} queries, nfold={nfold})")
        q_perm = np.arange(nq)
        if shuffle:
            rng.shuffle(q_perm)
        fold_of_q = np.empty(nq, np.int64)
        fold_of_q[q_perm] = np.arange(nq) % nfold
        row_fold = np.repeat(fold_of_q, np.diff(qb))
        folds = [(np.nonzero(row_fold != k)[0],
                  np.nonzero(row_fold == k)[0]) for k in range(nfold)]
    elif folds is None:
        idx = np.arange(n)
        if shuffle:
            rng.shuffle(idx)
        if stratified and cfg.objective in ("binary", "multiclass", "multiclassova"):
            order = np.argsort(label[idx], kind="stable")
            idx = idx[order]
            fold_of = np.arange(n) % nfold
            folds = [(idx[fold_of != k], idx[fold_of == k]) for k in range(nfold)]
        else:
            folds = [(np.concatenate([idx[:a], idx[b:]]), idx[a:b])
                     for a, b in ((k * n // nfold, (k + 1) * n // nfold)
                                  for k in range(nfold))]

    boosters = []
    histories: List[Dict[str, List[float]]] = []
    for train_idx, test_idx in folds:
        tr = train_set.subset(np.sort(train_idx))
        va = train_set.subset(np.sort(test_idx))
        from .callback import record_evaluation
        hist: Dict[str, Dict[str, List[float]]] = {}
        cbs = list(callbacks or []) + [record_evaluation(hist)]
        bst = train(params, tr, num_boost_round, valid_sets=[va],
                    valid_names=["valid"], feval=feval, callbacks=cbs)
        boosters.append(bst)
        histories.append(hist.get("valid", {}))

    out: Dict[str, List[float]] = {}
    for metric in (histories[0].keys() if histories else []):
        rounds = min(len(h.get(metric, [])) for h in histories)
        # one [nfold, rounds] materialization + vectorized reduction:
        # per-round np.mean/np.std over Python lists converted each fold
        # value individually — with device-scalar entries that was one
        # host/device ping-pong per (metric, round, fold) (the first
        # real finding of the ISSUE 3 no-host-sync sweep outside jit)
        vals = np.asarray([h.get(metric, [])[:rounds] for h in histories],
                          dtype=np.float64)
        out[f"valid {metric}-mean"] = vals.mean(axis=0).tolist()
        out[f"valid {metric}-stdv"] = vals.std(axis=0).tolist()
    if return_cvbooster:
        cvb = CVBooster()
        cvb.boosters = boosters
        cvb.best_iteration = max((b.best_iteration for b in boosters),
                                 default=-1)
        out["cvbooster"] = cvb
    return out
