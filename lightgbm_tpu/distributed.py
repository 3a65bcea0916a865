"""Programmatic multi-machine training — the role the reference's Dask
integration plays in its Python stack (ref: python-package/lightgbm/
dask.py:414 _train: resolve workers -> build machine list -> run train on
every worker -> return the model), redesigned for the JAX runtime: the
"network" is jax.distributed + GSPMD collectives over the global device
mesh, not socket linkers.

Two entry points:

* `join_cluster(...)` — for users who already run one process per host
  (SLURM, k8s, GKE): resolves this worker's rank from a reference-style
  machine list (or explicit rank) and initializes jax.distributed; after
  it returns, plain `lgb.train(params with tree_learner=data)` shards
  over the global mesh.  This is the library form of the CLI's
  `machines=` launch (cli.py _maybe_init_distributed).

* `train_distributed(...)` — single-host convenience that SPAWNS
  num_machines local worker processes (the LocalCluster analogue),
  trains tree_learner=data across them, and returns the rank-0 model as
  a Booster.  Every worker loads the full host-side arrays (GSPMD owns
  the row sharding; workers' models are identical by construction —
  tests/test_multiprocess.py pins this).  The local workers run on the
  CPU: a chip belongs to one process at a time and nothing assigns a
  chip to a worker, so several chip-holding workers on one host are
  refused.  To train over all local chips, call `lgb.train` with
  `tree_learner=data` in ONE process — it drives every chip of the host.
"""

from __future__ import annotations

import json
import os
import pickle
import socket
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .utils import log


def resolve_rank(machines: List[str], local_listen_port: int) -> int:
    """Reference-style rank resolution: this host's (name/ip, port) found
    in the ordered machine list (ref: network.cpp Network::Init)."""
    local_names = {"localhost", "127.0.0.1", socket.gethostname()}
    try:
        local_names.update(socket.gethostbyname_ex(socket.gethostname())[2])
    except OSError:
        pass
    for i, e in enumerate(machines):
        host, sep, port = e.rpartition(":")
        if not sep or not port.isdigit():
            log.fatal(f"Malformed machines entry {e!r}; expected host:port")
        if host in local_names and int(port) == local_listen_port:
            return i
    log.fatal("This machine is not in the machine list; include host:port "
              "for every worker")


def _wait_for_coordinator(address: str, timeout: float) -> None:
    """Pre-flight TCP probe of the coordinator before handing control to
    jax.distributed.initialize: this jaxlib's coordination client
    LOG(FATAL)s (hard process abort, no Python exception) when the
    coordinator never answers, so the only place to produce a clear
    diagnostic is BEFORE calling it.  Retries until `timeout` — workers
    may legitimately start before the coordinator is up."""
    host, sep, port = address.rpartition(":")
    if not sep or not port.isdigit():
        log.fatal(f"Malformed coordinator address {address!r}; expected "
                  "host:port (the first machine-list entry)")
    deadline = time.monotonic() + timeout
    last_err: Optional[Exception] = None
    while time.monotonic() < deadline:
        try:
            with socket.create_connection((host, int(port)), timeout=2):
                return
        except OSError as e:
            last_err = e
            time.sleep(0.5)
    log.fatal(
        f"Coordinator {address} is unreachable after {timeout:.0f}s "
        f"({last_err}). Check that the rank-0 process is running, that "
        "every worker uses the SAME machine list (entry 0 is the "
        "coordinator), and that the port is not blocked by a firewall.")


def join_cluster(machines, rank: Optional[int] = None,
                 local_listen_port: int = 12400,
                 initialize_timeout: Optional[float] = None) -> int:
    """Initialize jax.distributed from a reference-style machine list.
    Returns this process's rank.  Entry 0 is the coordinator.

    `initialize_timeout` bounds how long a worker waits for the
    coordinator (seconds; jax's default is 300).  On failure the error
    names the coordinator address and the usual causes instead of a bare
    gRPC traceback (or a hard process abort from the coordination
    client)."""
    if isinstance(machines, str):
        machines = [e.strip() for e in machines.split(",") if e.strip()]
    if rank is None:
        rank = resolve_rank(machines, local_listen_port)
    if rank != 0:
        _wait_for_coordinator(machines[0],
                              timeout=(initialize_timeout
                                       if initialize_timeout is not None
                                       else 60.0))
    import jax
    kwargs = {}
    if initialize_timeout is not None:
        kwargs["initialization_timeout"] = int(initialize_timeout)
    try:
        jax.distributed.initialize(coordinator_address=machines[0],
                                   num_processes=len(machines),
                                   process_id=rank, **kwargs)
    except TypeError:
        # older jax without initialization_timeout: join with the default
        jax.distributed.initialize(coordinator_address=machines[0],
                                   num_processes=len(machines),
                                   process_id=rank)
    except Exception as e:
        log.fatal(
            f"Could not join the training cluster as rank "
            f"{rank}/{len(machines)}: coordinator {machines[0]} is "
            f"unreachable ({type(e).__name__}: {e}). Check that the rank-0 "
            "process is running, that every worker uses the SAME machine "
            "list (entry 0 is the coordinator), and that the port is not "
            "blocked by a firewall.")
    log.info(f"Joined cluster as rank {rank}/{len(machines)} "
             f"(coordinator {machines[0]})")
    return rank


_WORKER_MAIN = r"""
import json, os, pickle, sys
spec = json.load(open(sys.argv[1]))
rank = int(sys.argv[2])
for k, v in spec.get("env", {}).items():
    os.environ[k] = v
# fault-injection context: which worker this is and which launch attempt
# (retried clusters bump the attempt so one-shot faults don't re-fire)
os.environ["LGBM_TPU_FAULT_SELF_RANK"] = str(rank)
os.environ["LGBM_TPU_FAULT_ATTEMPT"] = str(spec.get("attempt", 0))
os.environ["LGBM_TPU_WORLD_SIZE"] = str(spec["num_machines"])
# permanent-loss model (reliability/faults.py): a tombstoned (rank,
# world) refuses every same-world relaunch BEFORE joining the cluster,
# so the refusal is a fast clean exit the supervisor sees immediately —
# only an elastic shrink (different world size) gets past it
if spec.get("tombstone_dir"):
    os.environ["LGBM_TPU_TOMBSTONE_DIR"] = spec["tombstone_dir"]
    sys.path.insert(0, spec["repo"])
    from lightgbm_tpu.reliability import faults as _faults
    _faults.check_tombstone()
# stall detection (reliability/guard.py): the engine's RunGuard touches
# this file once per boosting iteration; the supervising parent polls
# its mtime to catch live-but-hung ranks, and the guard's stall
# diagnosis lands next to it when the run has no metrics_dir
if spec.get("heartbeat_dir"):
    os.makedirs(spec["heartbeat_dir"], exist_ok=True)
    os.environ["LGBM_TPU_HEARTBEAT_FILE"] = os.path.join(
        spec["heartbeat_dir"], f"heartbeat-rank{rank}")
    os.environ["LGBM_TPU_STALL_DIR"] = spec["heartbeat_dir"]
import jax
jax.distributed.initialize(coordinator_address=spec["coordinator"],
                           num_processes=spec["num_machines"],
                           process_id=rank)
sys.path.insert(0, spec["repo"])
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.observability import install_sigterm_flush
from lightgbm_tpu.reliability import faults
# kill -USR1 <pid>: on-demand all-thread stack dump from a live worker
faults.register_stack_dump_signal()
# a supervisor SIGTERM flushes queued events/checkpoints before exit
install_sigterm_flush()

with open(spec["data"], "rb") as f:
    payload = pickle.load(f)
params = dict(spec["params"])
params.setdefault("tree_learner", "data")
if spec.get("reshard"):
    # elastic relaunch: every rank derives the identical deterministic
    # row plan from the same three integers (parallel/elastic.py) — no
    # coordination, no rank-0 broadcast; printed so the worker log
    # records which rows this shard now owns
    from lightgbm_tpu.parallel import reshard_plan, rows_of
    rs = spec["reshard"]
    if rs.get("num_rows"):
        plan = reshard_plan(rs["old_n"], rs["new_n"], rs["num_rows"])
        assert plan.new_n == spec["num_machines"]
        print(f"worker {rank} reshard {plan.summary()} rows="
              f"{rows_of(rs['num_rows'], rs['new_n'], rank)}", flush=True)
if isinstance(payload, str):
    ds = lgb.Dataset(payload, params=params)
else:
    ds = lgb.Dataset(payload["X"], label=payload.get("y"),
                     weight=payload.get("weight"),
                     group=payload.get("group"), params=params)
ckpt_dir = spec.get("checkpoint_dir") or None
booster = lgb.train(params, ds,
                    num_boost_round=spec["num_boost_round"],
                    checkpoint_dir=ckpt_dir,
                    checkpoint_freq=spec.get("checkpoint_freq", 0),
                    resume=bool(ckpt_dir))
if rank == 0:
    booster.save_model(spec["model_out"])
print(f"worker {rank} done", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _ckpt_num_rows(checkpoint_dir: Optional[str]) -> Optional[int]:
    """Training-row count recorded in the checkpoint manifest — the one
    number the elastic reshard plan derives from, so the parent and
    every relaunched rank agree on it without communicating."""
    if not checkpoint_dir:
        return None
    try:
        from .reliability.checkpoint import MANIFEST
        with open(os.path.join(checkpoint_dir, MANIFEST)) as f:
            n = json.load(f).get("num_rows")
        return int(n) if n else None
    except (OSError, ValueError, TypeError):
        return None


def train_distributed(params: Dict[str, Any], data, label=None, *,
                      weight=None, group=None, num_boost_round: int = 100,
                      num_machines: int = 2,
                      worker_env: Optional[Dict[str, str]] = None,
                      force_cpu: bool = False, timeout: int = 900,
                      max_retries: int = 0, checkpoint_dir: Optional[str] = None,
                      checkpoint_freq: int = 0, retry_backoff: float = 1.0,
                      poll_interval: float = 0.25,
                      stall_timeout: Optional[float] = None):
    """Spawn `num_machines` local SPMD workers, train tree_learner=data
    across their combined devices, and return the trained Booster (all
    workers produce identical models; rank 0's is returned).

    `data` may be a file path (each worker loads it — pair with
    two_round for large files) or an array; arrays are shipped to
    workers through a temp file.  `worker_env` sets per-worker env vars
    (e.g. XLA_FLAGS for virtual-device tests); `force_cpu` puts
    JAX_PLATFORMS=cpu into the workers' environment.  With
    `num_machines > 1` the workers must be on the CPU (`force_cpu`, or
    JAX_PLATFORMS=cpu inherited / in `worker_env`): each local process
    would otherwise ask for every chip of the host, and all but the
    first fail or hang.

    Fault tolerance (docs/Reliability.md): workers are SUPERVISED — the
    first non-zero exit kills the remaining cluster immediately instead
    of letting the survivors stall in collectives until `timeout`.  With
    `max_retries > 0` the whole cluster is relaunched with exponential
    backoff (`retry_backoff * 2**attempt` seconds), resuming from the
    newest checkpoint; when retries are requested without an explicit
    `checkpoint_dir`, a per-run directory with checkpoint_freq=1 is used
    so a retry repeats at most one boosting iteration.
    """
    import shutil

    from .basic import Booster
    work = tempfile.mkdtemp(prefix="lgbtpu_dist")
    try:
        return _train_distributed_in(
            work, params, data, label, weight, group,
            num_boost_round, num_machines, worker_env, force_cpu, timeout,
            Booster, max_retries=max_retries, checkpoint_dir=checkpoint_dir,
            checkpoint_freq=checkpoint_freq, retry_backoff=retry_backoff,
            poll_interval=poll_interval, stall_timeout=stall_timeout)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _train_distributed_in(work, params, data, label, weight, group,
                          num_boost_round, num_machines, worker_env,
                          force_cpu, timeout, Booster, *, max_retries=0,
                          checkpoint_dir=None, checkpoint_freq=0,
                          retry_backoff=1.0, poll_interval=0.25,
                          stall_timeout=None):
    from .config import Config
    from .reliability.elastic import GIVE_UP, SHRINK, ElasticPolicy
    from .reliability.guard import (disabled_value, next_degradation,
                                    _LADDER_KNOBS)
    from .reliability.supervisor import supervise

    run_cfg = Config(dict(params))
    auto_degrade = bool(run_cfg.auto_degrade)
    if stall_timeout is None:
        # mtime-staleness backstop: must outlast the worker guard's
        # first-compile deadline, or the parent would kill a cluster
        # that is legitimately still compiling its device program
        stall_timeout = (max(10.0 * run_cfg.stall_floor_s, 600.0)
                         if run_cfg.stall_floor_s > 0 else 0.0)
    degraded_knobs: List[str] = []

    data_path = os.path.join(work, "data.pkl")
    with open(data_path, "wb") as f:
        if isinstance(data, (str, os.PathLike)):
            pickle.dump(str(data), f)
        else:
            pickle.dump({"X": np.asarray(data),
                         "y": None if label is None else np.asarray(label),
                         "weight": (None if weight is None
                                    else np.asarray(weight)),
                         "group": (None if group is None
                                   else np.asarray(group))}, f)
    model_out = os.path.join(work, "model.txt")
    if max_retries > 0 and not checkpoint_dir:
        # retries without checkpoints would replay the whole run; give the
        # workers a per-run checkpoint dir so a retry loses <= 1 iteration
        checkpoint_dir = os.path.join(work, "ckpt")
        if checkpoint_freq <= 0:
            checkpoint_freq = 1
    script = os.path.join(work, "worker.py")
    with open(script, "w") as f:
        f.write(_WORKER_MAIN)
    # the parent's XLA_FLAGS (e.g. a test harness's virtual devices) are
    # not the workers'; worker_env sets theirs.  The platform pin is the
    # environment alone.
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    if force_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    child_platform = (worker_env or {}).get(
        "JAX_PLATFORMS", env.get("JAX_PLATFORMS", "")).strip()
    if num_machines > 1 and child_platform != "cpu":
        log.fatal(
            f"train_distributed: {num_machines} local worker processes "
            "would each ask JAX for every chip of this host, and a chip "
            "belongs to one process at a time — all but the first would "
            "fail or hang at backend init.  Run the local workers on the "
            "CPU (force_cpu=True), or train over all local chips in ONE "
            "process: lgb.train(..., tree_learner='data')")

    # supervisor-side telemetry: with metrics_dir set, the workers write
    # their rank-tagged event logs and the parent adds a "supervisor"
    # stream recording cluster relaunches (docs/Observability.md)
    evt = None
    if params.get("metrics_dir"):
        from .observability import EventLogger
        try:
            evt = EventLogger(params["metrics_dir"], rank="supervisor")
        except OSError as e:
            log.warning(f"Could not open the supervisor event log in "
                        f"{params['metrics_dir']}: {e}")

    last_failure = "no workers launched"
    # the parent owns the degradation ladder in distributed mode: the
    # workers must not ALSO consume stall files and double-degrade
    worker_params = dict(params)
    worker_params["auto_degrade"] = False
    # elastic shrink-to-fit (docs/Reliability.md §Elastic recovery): a
    # permanently lost rank shrinks the next attempt's world size
    # instead of relaunching into the same dead host forever
    policy = ElasticPolicy(num_machines,
                           min_machines=run_cfg.elastic_min_machines,
                           rank_grace_s=run_cfg.elastic_rank_grace_s)
    reshard: Optional[Dict[str, Any]] = None
    for attempt in range(max_retries + 1):
        num_machines = policy.num_machines
        # fresh coordinator port per attempt: the previous coordinator
        # process is gone and its port may linger in TIME_WAIT
        port = _free_port()
        # per-attempt heartbeat dir: rank heartbeats + (when the run has
        # no metrics_dir) the stall diagnoses land here
        hb_dir = os.path.join(work, f"hb_a{attempt}")
        os.makedirs(hb_dir, exist_ok=True)
        spec = {"coordinator": f"localhost:{port}",
                "num_machines": int(num_machines),
                "params": dict(worker_params),
                "num_boost_round": int(num_boost_round),
                "data": data_path, "model_out": model_out,
                "repo": os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__))),
                "env": dict(worker_env or {}),
                "attempt": attempt, "checkpoint_dir": checkpoint_dir,
                "checkpoint_freq": int(checkpoint_freq),
                "heartbeat_dir": hb_dir,
                # tombstones OUTLIVE attempts (unlike heartbeats): a
                # permanently lost rank must refuse every same-world
                # relaunch, so they key on the stable work dir
                "tombstone_dir": work, "reshard": reshard}
        spec_path = os.path.join(work, f"spec_{attempt}.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        # worker output goes to files, not PIPEs: a chatty later-rank worker
        # filling the ~64KB pipe buffer while an earlier rank still trains
        # would block inside a collective and stall every rank until timeout
        log_paths = [os.path.join(work, f"worker_{r}_a{attempt}.log")
                     for r in range(num_machines)]
        log_files = [open(p, "w") for p in log_paths]
        try:
            procs = [subprocess.Popen(
                [sys.executable, script, spec_path, str(r)],
                stdout=log_files[r], stderr=subprocess.STDOUT, text=True,
                env=env) for r in range(num_machines)]
            result = supervise(
                procs, log_paths, timeout, poll_interval=poll_interval,
                heartbeats=[os.path.join(hb_dir, f"heartbeat-rank{r}")
                            for r in range(num_machines)],
                stall_timeout=stall_timeout,
                stall_dir=str(params.get("metrics_dir") or "") or hb_dir)
        finally:
            for lf in log_files:
                lf.close()
        if result.ok and os.path.exists(model_out):
            if attempt > 0:
                log.info(f"Distributed training succeeded on retry "
                         f"{attempt} (resumed from {checkpoint_dir})"
                         + (f" with degraded knobs {degraded_knobs}"
                            if degraded_knobs else "")
                         + (f" on a shrunken {num_machines}-rank cluster"
                            if policy.shrinks else ""))
                if evt is not None:
                    evt.emit("cluster_retry_succeeded", attempt=attempt,
                             degraded_knobs=degraded_knobs,
                             num_machines=num_machines,
                             elastic_shrinks=policy.shrinks)
            booster = Booster(model_file=model_out)
            booster.degraded_knobs = list(degraded_knobs)
            booster.elastic_shrinks = policy.shrinks
            booster.final_num_machines = num_machines
            return booster
        last_failure = result.describe() if not result.ok else \
            "all workers exited 0 but no model file was written"
        genuine = bool(result.failures) or result.timed_out
        classification = result.classification if genuine else "crash"
        if evt is not None:
            evt.emit("cluster_attempt_failed", attempt=attempt,
                     classification=classification,
                     failure=last_failure.splitlines()[0]
                     if last_failure else "")
        if attempt < max_retries:
            decision = policy.observe(result) if genuine else None
            if decision is not None and decision.action == GIVE_UP:
                log.fatal(
                    f"distributed training cannot continue: "
                    f"{decision.reason}\n{last_failure}")
            if decision is not None and decision.action == SHRINK:
                # shrink FIRST, then walk knobs (the ladder's hang
                # evidence was gathered on a topology that no longer
                # exists); the relaunch resumes from the checkpoint on
                # the surviving world size with a deterministic row plan
                # every rank recomputes identically
                from .reliability.elastic import plan_for_shrink
                old_n, new_n = num_machines, decision.num_machines
                plan = plan_for_shrink(old_n, new_n,
                                       _ckpt_num_rows(checkpoint_dir))
                reshard = {"old_n": old_n, "new_n": new_n,
                           "num_rows": plan.num_rows if plan else None}
                log.warning(
                    f"elastic_shrink: {decision.reason}; relaunching on "
                    f"{new_n} rank(s)"
                    + (f", reshard {plan.summary()}" if plan else "")
                    + (f", resuming from {checkpoint_dir}"
                       if checkpoint_dir else ""))
                if evt is not None:
                    evt.emit("elastic_shrink", old_num_machines=old_n,
                             new_num_machines=new_n,
                             lost_ranks=decision.lost_ranks,
                             attempt=attempt + 1,
                             reshard=plan.summary() if plan else None)
            elif result.hang and auto_degrade:
                # graceful degradation (reliability/guard.py): the
                # attempt HUNG, so the relaunch disables the next risky
                # knob instead of replaying the same configuration into
                # the same stall
                effective = {k: getattr(Config(dict(worker_params)), k)
                             for k in _LADDER_KNOBS}
                knob = next_degradation(effective, degraded_knobs)
                if knob is not None:
                    worker_params[knob] = disabled_value(knob)
                    degraded_knobs.append(knob)
                    log.warning(
                        f"auto_degrade: attempt {attempt} hung; "
                        f"relaunching with {knob} disabled "
                        f"(degraded so far: {degraded_knobs})")
                    if evt is not None:
                        evt.emit("degrade", knob=knob, attempt=attempt + 1,
                                 active=list(degraded_knobs))
                else:
                    log.warning("auto_degrade: ladder exhausted; "
                                "relaunching unchanged")
            elif classification == "preempt":
                log.warning(
                    f"attempt {attempt} was preempted (SIGTERM); the "
                    "workers saved on-demand checkpoints inside the grace "
                    "window — relaunching at the same world size"
                    + (f", resuming from {checkpoint_dir}"
                       if checkpoint_dir else ""))
            delay = retry_backoff * (2 ** attempt)
            if evt is not None:
                evt.emit("cluster_retry", next_attempt=attempt + 1,
                         delay_s=delay)
            log.warning(
                f"Distributed training attempt {attempt + 1}/"
                f"{max_retries + 1} failed:\n{last_failure}\n"
                f"Relaunching the cluster in {delay:.1f}s"
                + (f", resuming from checkpoints in {checkpoint_dir}"
                   if checkpoint_dir else ""))
            time.sleep(delay)
    log.fatal(f"distributed training failed after {max_retries + 1} "
              f"attempt(s):\n{last_failure}")
