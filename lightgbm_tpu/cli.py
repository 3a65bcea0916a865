"""CLI application: `python -m lightgbm_tpu config=train.conf [k=v ...]`.

TPU-native analogue of the reference CLI (ref: src/main.cpp:14;
src/application/application.cpp:31 Application / application.h:78 Run).
Parameter precedence matches LoadParameters: command-line `key=value`
pairs win over config-file entries (first occurrence wins,
ref: application.cpp:79 KeepFirstValues).  Tasks: train, predict,
refit, save_binary, convert_model (ref: config.h TaskType).
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional

import numpy as np

from .basic import Booster, Dataset
from .config import Config, read_config_file
from .engine import train as train_api
from .utils import log


def parse_args(argv: List[str]) -> Dict[str, str]:
    """argv `key=value` tokens + optional config file, CLI first
    (ref: application.cpp:50-86 LoadParameters)."""
    params: Dict[str, str] = {}
    for tok in argv:
        if "=" not in tok:
            log.fatal(f"Unknown argument {tok!r}; expected key=value")
        k, v = tok.split("=", 1)
        params.setdefault(k.strip(), v.strip())
    conf = params.get("config", params.get("config_file", ""))
    if conf:
        for k, v in read_config_file(conf).items():
            params.setdefault(k, v)  # first (CLI) value wins
    params.pop("config", None)
    params.pop("config_file", None)
    return params


def _load_train_data(cfg: Config, params: Dict[str, str]) -> Dataset:
    if not cfg.data:
        log.fatal("No training data: set data=<file>")
    return Dataset(cfg.data, params=dict(params))


def _task_train(cfg: Config, params: Dict[str, str]) -> None:
    train_set = _load_train_data(cfg, params)
    valid_sets, valid_names = [], []
    for i, vf in enumerate(cfg.valid):
        valid_sets.append(Dataset(vf, params=dict(params),
                                  reference=train_set))
        valid_names.append(f"valid_{i}" if len(cfg.valid) > 1 else "valid")
    init_model = cfg.input_model or None
    callbacks = None
    if cfg.snapshot_freq > 0:
        # periodic checkpoints (ref: gbdt.cpp:244-248 snapshot_freq
        # writes model.snapshot_iter_N; resume via input_model)
        def _snapshot(env):
            it = env.iteration + 1
            if it % cfg.snapshot_freq == 0:
                env.model.save_model(
                    f"{cfg.output_model}.snapshot_iter_{it}")
        _snapshot.order = 100
        callbacks = [_snapshot]
    # task=train resume flags (docs/Reliability.md): checkpoint_dir=DIR
    # enables rotated atomic checkpoints every checkpoint_freq rounds;
    # re-running the same command continues from the newest one unless
    # resume=false.  (Distinct from snapshot_freq, which only writes
    # model files and never resumes by itself.)
    if cfg.checkpoint_dir:
        log.info(f"Checkpointing to {cfg.checkpoint_dir} every "
                 f"{cfg.checkpoint_freq} iteration(s) "
                 f"(resume={'on' if cfg.resume else 'off'})")
    # observability knobs (docs/Observability.md): metrics_dir= enables
    # the per-iteration JSONL event log, profile_dir= a jax profiler
    # trace; both flow to train() through the params dict
    if cfg.metrics_dir:
        log.info(f"Writing per-iteration telemetry events to "
                 f"{cfg.metrics_dir}")
    if cfg.profile_dir:
        log.info(f"Profiling run; TensorBoard trace will be written to "
                 f"{cfg.profile_dir}")
    booster = train_api(dict(params), train_set,
                        num_boost_round=cfg.num_iterations,
                        valid_sets=valid_sets or None,
                        valid_names=valid_names or None,
                        init_model=init_model, callbacks=callbacks,
                        checkpoint_dir=cfg.checkpoint_dir or None,
                        checkpoint_freq=cfg.checkpoint_freq,
                        resume=cfg.resume)
    booster.save_model(cfg.output_model)
    log.info(f"Finished training; model saved to {cfg.output_model}")


# per-chunk memory budget for streamed file prediction (bytes of float64
# features); tests shrink it to force multi-chunk runs
_PREDICT_CHUNK_BUDGET = 32 << 20


def _task_predict(cfg: Config, params: Dict[str, str]) -> None:
    """Bounded-memory file prediction: the input streams through
    parse_file_stream in row chunks (ref: predictor.hpp:30
    PipelineReader — the reference double-buffers file chunks the same
    way), so peak RSS is one chunk + the model, independent of file
    size."""
    if not cfg.input_model:
        log.fatal("task=predict needs input_model=<file>")
    booster = Booster(model_file=cfg.input_model)
    from .io.parser import parse_file_stream
    nf = booster.num_feature()
    chunk_rows = max(128, _PREDICT_CHUNK_BUDGET // max(8 * nf, 1))
    n_done = 0
    with open(cfg.output_result, "w") as f:
        for feats, _ in parse_file_stream(
                cfg.data, has_header=cfg.header,
                label_column=cfg.label_column, chunk_rows=chunk_rows,
                num_features=nf):
            pred = booster.predict(
                feats, raw_score=cfg.predict_raw_score,
                pred_leaf=cfg.predict_leaf_index,
                pred_contrib=cfg.predict_contrib,
                num_iteration=cfg.num_iteration_predict)
            for row in np.atleast_1d(pred):
                if np.ndim(row) == 0:
                    f.write(f"{row:.18g}\n")
                else:
                    f.write("\t".join(f"{v:.18g}" for v in row) + "\n")
            n_done += len(feats)
    log.info(f"Finished prediction of {n_done} rows; results saved to "
             f"{cfg.output_result}")


def _task_refit(cfg: Config, params: Dict[str, str]) -> None:
    """Refit existing tree structures to new data
    (ref: application.cpp ConvertModel... task=refit -> GBDT::RefitTree)."""
    if not cfg.input_model:
        log.fatal("task=refit needs input_model=<file>")
    booster = Booster(model_file=cfg.input_model)
    from .io.parser import parse_file
    feats, labels, _ = parse_file(cfg.data, has_header=cfg.header,
                                  label_column=cfg.label_column)
    booster.refit(feats, labels)
    booster.save_model(cfg.output_model)
    log.info(f"Finished refit; model saved to {cfg.output_model}")


def _task_save_binary(cfg: Config, params: Dict[str, str]) -> None:
    ds = _load_train_data(cfg, params)
    core = ds._core_or_construct()
    out = (cfg.data or "train") + ".bin"
    core.save_binary(out)
    log.info(f"Saved binary dataset to {out}")


def _task_serve(cfg: Config, params: Dict[str, str]) -> None:
    """Long-lived multi-model serving daemon (docs/Serving.md):
    `python -m lightgbm_tpu serve serve_models=name=model.txt [...]`.
    Loads + warms every model (bucket-ladder compiles) BEFORE serving,
    optionally exposes the line-JSON TCP front end (serve_port=0 for an
    ephemeral port), and treats SIGTERM as a drain notice — queued
    requests complete, a final `serve_drain` event lands, exit stays
    143 (the supervisor's *preempt* classification)."""
    import time as _time

    from .serving import ServingDaemon, start_frontend

    if cfg.metrics_dir:
        # serve_* events (swap/evict/drain) land in the standard JSONL
        # event log, same as training telemetry
        from .observability import set_event_logger
        from .observability.events import EventLogger
        set_event_logger(EventLogger(cfg.metrics_dir,
                                     rotate_mb=cfg.metrics_rotate_mb))
        # SIGUSR2 = dump the flight recorder + registry snapshot from
        # the LIVE daemon without killing it (reliability/faults.py)
        from .reliability.faults import register_flight_dump_signal
        register_flight_dump_signal(cfg.metrics_dir)
    entries = []
    for tok in cfg.serve_models:
        name, sep, path = tok.partition("=")
        if not sep:
            name, path = os.path.splitext(os.path.basename(tok))[0], tok
        entries.append((name.strip(), path.strip()))
    if not entries and cfg.input_model:
        entries.append(("default", cfg.input_model))
    if not entries:
        log.fatal("task=serve needs serve_models=name=model.txt[,...] "
                  "or input_model=<file>")
    daemon = ServingDaemon(cfg)
    for name, path in entries:
        daemon.registry.register(name, model_file=path, block=True)
        log.info(f"Serving model {name!r} from {path} (warmed)")
    daemon.start()
    daemon.install_signal_handlers()
    srv = None
    uds_srv = None
    if cfg.serve_port >= 0:
        srv = start_frontend(daemon, port=cfg.serve_port,
                             request_timeout_s=cfg.serve_request_timeout_s)
    if cfg.serve_uds_path:
        from .serving import start_uds_frontend
        uds_srv = start_uds_frontend(
            daemon, cfg.serve_uds_path,
            request_timeout_s=cfg.serve_request_timeout_s)
    if cfg.serve_ready_file:
        # readiness marker for the fleet supervisor: port + pid land
        # atomically only AFTER every model is loaded, warmed, and the
        # front end is listening — a torn or early file would route
        # traffic into cold compiles
        import json as _json

        from .utils import atomic_write_text
        atomic_write_text(cfg.serve_ready_file, _json.dumps({
            "pid": os.getpid(),
            "port": srv.server_address[1] if srv is not None else -1,
            "metrics_port": (daemon.metrics_server.port
                             if daemon.metrics_server else -1),
            "models": daemon.registry.versions()}))
        log.info(f"Ready file written to {cfg.serve_ready_file}")
    log.info(f"Serving {len(entries)} model(s); SIGTERM drains and exits")
    try:
        while not daemon.stopped:
            _time.sleep(0.2)
    except KeyboardInterrupt:
        log.info("Interrupted; draining the request queue")
        daemon.stop(drain=True, timeout=cfg.serve_drain_timeout_s)
    finally:
        if srv is not None:
            srv.shutdown()
        if uds_srv is not None:
            uds_srv.shutdown()


def _task_serve_fleet(cfg: Config, params: Dict[str, str]) -> None:
    """Serving fault domain (docs/Serving.md fleet section):
    `python -m lightgbm_tpu serve-fleet serve_models=m=model.txt
    serve_replicas=3 serve_port=0`.  Spawns `serve_replicas` replica
    daemons (each a supervised task=serve child with its own ready
    file; with more than one they run on the CPU, so the command needs
    JAX_PLATFORMS=cpu in its environment — serving/fleet.py says why),
    health-checks them, and fronts them with
    the retry/shed/canary router on `serve_port`.  SIGTERM drains the
    WHOLE fleet: the router stops accepting, every replica gets its own
    SIGTERM drain (each exits 143), and the runner re-delivers — exit
    stays 143."""
    import tempfile
    import time as _time

    from .serving import ReplicaFleet, Router

    if cfg.metrics_dir:
        from .observability import set_event_logger
        from .observability.events import EventLogger
        set_event_logger(EventLogger(cfg.metrics_dir,
                                     rotate_mb=cfg.metrics_rotate_mb))
    entries = []
    for tok in cfg.serve_models:
        name, sep, path = tok.partition("=")
        if not sep:
            name, path = os.path.splitext(os.path.basename(tok))[0], tok
        entries.append((name.strip(), path.strip()))
    if not entries and cfg.input_model:
        entries.append(("default", cfg.input_model))
    if not entries:
        log.fatal("task=serve-fleet needs serve_models=name=model.txt"
                  "[,...] or input_model=<file>")
    workdir = cfg.metrics_dir or tempfile.mkdtemp(prefix="lgbm-fleet-")
    # replica daemons inherit the serving knobs; their OWN ports are
    # ephemeral (the ready file reports them) and the router owns the
    # client-facing serve_port
    replica_params = {k: v for k, v in params.items()
                      if k not in ("task", "serve_port", "serve_replicas",
                                   "serve_ready_file", "metrics_dir",
                                   "metrics_port")}
    fleet = ReplicaFleet(
        num_replicas=cfg.serve_replicas, model_entries=entries,
        workdir=workdir, params=replica_params,
        max_restarts=cfg.serve_max_replica_restarts,
        health_interval_s=cfg.serve_health_interval_s,
    ).start()
    router = Router(fleet, cfg)
    for name, path in entries:
        router.register_incumbent(name, path)
    if not fleet.wait_ready(timeout=300.0, min_replicas=1):
        fleet.stop(drain=False)
        log.fatal("serve-fleet: no replica became ready within 300 s "
                  f"(see {workdir}/replica-*.log)")
    srv = router.start_frontend(port=max(cfg.serve_port, 0),
                                metrics_port=cfg.metrics_port)
    log.info(f"Fleet router listening on "
             f"{srv.server_address[0]}:{srv.server_address[1]} "
             f"({cfg.serve_replicas} replicas); SIGTERM drains the fleet")
    if cfg.serve_slo_p99_ms > 0:
        # router-observed SLO burn tracking (docs/Observability.md
        # "Fleet metrics & SLO"): slo_burn events land in the event log
        # when metrics_dir= is set, fleet_slo_burning rides /metrics
        log.info(f"SLO tracking on: p99 <= {cfg.serve_slo_p99_ms:g} ms, "
                 f"error budget {cfg.serve_slo_error_pct:g}% "
                 f"(burn windows {cfg.serve_slo_fast_window_s:g}s / "
                 f"{cfg.serve_slo_slow_window_s:g}s)")
    if router.metrics_server is not None:
        log.info(f"Fleet observability on port "
                 f"{router.metrics_server.port}: GET /metrics (merged "
                 f"fleet view) and GET /trace/<id> (sampled "
                 f"cross-process waterfalls; op=trace on the wire)")
    if cfg.serve_ready_file:
        import json as _json

        from .utils import atomic_write_text
        atomic_write_text(cfg.serve_ready_file, _json.dumps({
            "pid": os.getpid(), "port": srv.server_address[1],
            "metrics_port": (router.metrics_server.port
                             if router.metrics_server else -1),
            "replicas": fleet.describe()}))
    stopping = {"flag": False}

    def _drain():
        stopping["flag"] = True
        router.stop()
        fleet.stop(drain=True, timeout=cfg.serve_drain_timeout_s + 30.0)
        return None  # finish_preemption re-delivers; rc stays 143

    from .observability import install_sigterm_flush, set_preemption_hook
    if install_sigterm_flush():
        set_preemption_hook(_drain)
    try:
        while not stopping["flag"] and fleet.alive():
            _time.sleep(0.2)
        if not stopping["flag"]:
            log.warning("serve-fleet: every replica exhausted its "
                        "restart budget; shutting down")
    except KeyboardInterrupt:
        log.info("Interrupted; draining the fleet")
        _drain()
    finally:
        router.stop()


def _task_train_and_serve(cfg: Config, params: Dict[str, str]) -> None:
    """Online continual learning (docs/Online.md):
    `python -m lightgbm_tpu task=train-and-serve online_chunk_dir=DIR
    checkpoint_dir=CKPT [input_model=seed.txt] [serve_port=0]`.

    One process closing the train->serve loop: a DirectoryChunkSource
    watches `online_chunk_dir`, the OnlineTrainer boosts/refits per
    chunk generation, checkpoints each generation (byte-exact
    SIGTERM/crash resume), and publishes atomically — into this
    process's own serving daemon (default; serve_port/serve_uds_path
    expose it), or over the wire to a remote router/replica when
    `online_publish_addr=host:port` is set.  SIGTERM stops the loop at
    the next boundary (mid-generation: the relaunch resumes from the
    last completed generation's checkpoint) and drains the local
    daemon; exit stays 143."""
    import json as _json
    import time as _time

    from .online import (DirectoryChunkSource, LocalPublisher,
                         OnlineTrainer, WirePublisher)

    if cfg.metrics_dir:
        from .observability import set_event_logger
        from .observability.events import EventLogger
        set_event_logger(EventLogger(cfg.metrics_dir,
                                     rotate_mb=cfg.metrics_rotate_mb))
        from .reliability.faults import register_flight_dump_signal
        register_flight_dump_signal(cfg.metrics_dir)
    if not cfg.online_chunk_dir:
        log.fatal("task=train-and-serve needs online_chunk_dir=<dir>")
    if not cfg.checkpoint_dir:
        log.warning("train-and-serve without checkpoint_dir=: a restart "
                    "re-trains from scratch (no byte-exact resume)")

    daemon = None
    srv = None
    uds_srv = None
    if cfg.online_publish_addr:
        host, _, port = cfg.online_publish_addr.rpartition(":")
        if not port.isdigit():
            log.fatal(f"online_publish_addr must be host:port "
                      f"(got {cfg.online_publish_addr!r})")
        publisher = WirePublisher(host or "127.0.0.1", int(port))
        log.info(f"Publishing generations to {cfg.online_publish_addr} "
                 "(op=publish over the wire)")
    else:
        from .serving import ServingDaemon, start_frontend, \
            start_uds_frontend
        daemon = ServingDaemon(cfg).start()
        publisher = LocalPublisher(daemon)
        if cfg.serve_port >= 0:
            srv = start_frontend(
                daemon, port=cfg.serve_port,
                request_timeout_s=cfg.serve_request_timeout_s)
        if cfg.serve_uds_path:
            uds_srv = start_uds_frontend(
                daemon, cfg.serve_uds_path,
                request_timeout_s=cfg.serve_request_timeout_s)

    source = DirectoryChunkSource(cfg.online_chunk_dir)
    trainer = OnlineTrainer(source, publisher, config=cfg,
                            params=dict(params),
                            checkpoint_dir=cfg.checkpoint_dir or None,
                            seed_model=cfg.input_model or None)
    trainer.install_signal_handlers()
    if daemon is not None:
        # one preemption-hook slot: the trainer owns it; chain the
        # daemon's drain behind the loop-stop so a SIGTERM between
        # generations completes queued requests before the exit
        from .observability import set_preemption_hook

        def _stop_all():
            trainer.request_stop()
            daemon.stop(drain=True, timeout=cfg.serve_drain_timeout_s)
            return None  # finish_preemption re-delivers; rc stays 143

        set_preemption_hook(_stop_all)
    trainer.start()  # resume (or seed) + initial publish
    if cfg.serve_ready_file:
        from .utils import atomic_write_text
        atomic_write_text(cfg.serve_ready_file, _json.dumps({
            "pid": os.getpid(),
            "port": (srv.server_address[1] if srv is not None else -1),
            "uds_path": cfg.serve_uds_path or None,
            "metrics_port": (daemon.metrics_server.port
                             if daemon is not None
                             and daemon.metrics_server else -1),
            "generation": trainer.generation,
            "model": trainer.model_name}))
        log.info(f"Ready file written to {cfg.serve_ready_file}")
    log.info(f"Online loop watching {cfg.online_chunk_dir} "
             f"(mode={cfg.online_mode}, "
             f"{cfg.online_trees_per_chunk} trees/chunk"
             + (f", freshness SLO {cfg.online_max_lag_s:g}s"
                if cfg.online_max_lag_s > 0 else "") + ")")
    try:
        stats = trainer.run()
        log.info(f"Online loop finished: {stats}")
    except KeyboardInterrupt:
        log.info("Interrupted; stopping the online loop")
        trainer.request_stop()
    finally:
        if daemon is not None and not daemon.stopped:
            daemon.stop(drain=True, timeout=cfg.serve_drain_timeout_s)
        if srv is not None:
            srv.shutdown()
        if uds_srv is not None:
            uds_srv.shutdown()
        # give the last published generation a beat to settle in logs
        _time.sleep(0.0)


def _task_convert_model(cfg: Config, params: Dict[str, str]) -> None:
    """Model -> standalone C-like if-else source
    (ref: gbdt_model_text.cpp SaveModelToIfElse)."""
    if not cfg.input_model:
        log.fatal("task=convert_model needs input_model=<file>")
    booster = Booster(model_file=cfg.input_model)
    out = cfg.convert_model or "gbdt_prediction.cpp"
    with open(out, "w") as f:
        f.write(booster.model_to_if_else())
    log.info(f"Converted model saved to {out}")


def _machine_entries(cfg: Config):
    """machines="ip1:port1,ip2:port2" or machine_list_filename (one
    "ip port" per line) -> ordered list of "host:port" strings
    (ref: config.h machines/machine_list_filename; network.cpp
    Network::Init parses both the same way)."""
    if cfg.machines:
        return [e.strip() for e in str(cfg.machines).split(",")
                if e.strip()]
    if cfg.machine_list_filename:
        entries = []
        with open(cfg.machine_list_filename) as f:
            for ln in f:
                toks = ln.split()
                if len(toks) >= 2:
                    entries.append(f"{toks[0]}:{toks[1]}")
        return entries
    return []


def _maybe_init_distributed(cfg: Config) -> None:
    """Multi-machine SPMD launch (ref: application.cpp:100-115 machine
    setup; the Dask launcher plays this role in the reference's Python
    stack).  Each worker runs this same CLI with the shared `machines`
    list and its OWN local_listen_port; the rank is the machine-list
    entry matching this host and port (the reference's rank resolution),
    entry 0 doubles as the jax.distributed coordinator.  After
    initialize(), jax.devices() spans every worker and tree_learner=
    data/feature/voting shards over the global mesh — the collectives
    replace the reference's socket linkers."""
    if cfg.num_machines <= 1:
        return
    entries = _machine_entries(cfg)
    if not entries:
        log.warning("num_machines > 1 without machines / "
                    "machine_list_filename: training runs single-process "
                    "over the local devices only")
        return
    if len(entries) != cfg.num_machines:
        log.fatal(f"num_machines={cfg.num_machines} but machine list has "
                  f"{len(entries)} entries")
    rank_env = os.environ.get("LIGHTGBM_TPU_MACHINE_RANK")
    if rank_env is not None:
        rank = int(rank_env)
    else:
        import socket
        local_names = {"localhost", "127.0.0.1", socket.gethostname()}
        try:
            local_names.update(
                socket.gethostbyname_ex(socket.gethostname())[2])
        except OSError:
            pass
        rank = -1
        for i, e in enumerate(entries):
            host, sep, port = e.rpartition(":")
            if not sep or not port.isdigit():
                log.fatal(f"Malformed machines entry {e!r}; expected "
                          "host:port")
            if host in local_names and int(port) == cfg.local_listen_port:
                rank = i
                break
        if rank < 0:
            log.fatal("This machine (with local_listen_port="
                      f"{cfg.local_listen_port}) is not in the machine "
                      "list; set machines to include host:port for every "
                      "worker")
    import jax
    jax.distributed.initialize(coordinator_address=entries[0],
                               num_processes=len(entries), process_id=rank)
    log.info(f"Joined distributed cluster as rank {rank}/{len(entries)} "
             f"(coordinator {entries[0]}); global devices: "
             f"{jax.device_count()}")


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] in ("serve", "serve-fleet", "train-and-serve"):
        # `python -m lightgbm_tpu serve[-fleet] k=v ...` sugar
        argv = [f"task={argv[0]}"] + list(argv[1:])
    params = parse_args(argv)
    cfg = Config(dict(params))
    from .observability import configure_compile_cache
    configure_compile_cache(cfg.compile_cache_dir)
    _maybe_init_distributed(cfg)
    task = cfg.task
    handlers = {"train": _task_train, "predict": _task_predict,
                "prediction": _task_predict, "refit": _task_refit,
                "refit_tree": _task_refit,
                "save_binary": _task_save_binary,
                "serve": _task_serve,
                "serve-fleet": _task_serve_fleet,
                "serve_fleet": _task_serve_fleet,
                "train-and-serve": _task_train_and_serve,
                "train_and_serve": _task_train_and_serve,
                "convert_model": _task_convert_model}
    if task not in handlers:
        log.fatal(f"Unknown task {task!r}")
    handlers[task](cfg, params)
    return 0


if __name__ == "__main__":
    sys.exit(main())
