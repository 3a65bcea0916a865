"""Elastic fault domain (ISSUE 8): checkpoint integrity + generation
fallback, preemption checkpoint-on-demand, and shrink-to-fit relaunch.

The seeded chaos matrix from the issue — {preempt mid-run, corrupt
newest checkpoint, permanent rank loss, rank loss + corruption
combined} — drilled on the 8-device CPU mesh the conftest provides.
Multi-process SPMD collectives do not run on this CPU backend (the
test_multiprocess probe), so the rank-loss drills exercise the REAL
supervisor/elastic relaunch machinery (`_train_distributed_in`:
processes, tombstones, shrink, events) with a lightweight worker body,
while the training-math halves (digest fallback byte-parity, preempt
resume byte-parity, shrunken-mesh metric parity) run in-process on the
8-device mesh.  An end-to-end 8->7 SPMD drill runs where a multi-process
backend exists (slow-marked; skipped on CPU-only containers).
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.parallel import reshard_plan, rows_of
from lightgbm_tpu.reliability import (WORKER_LOST_EXIT_CODE, ElasticPolicy,
                                      CheckpointManager, faults)
from lightgbm_tpu.reliability.elastic import GIVE_UP, RETRY, SHRINK
from lightgbm_tpu.reliability.guard import STALL_EXIT_CODE, classify_returncode
from lightgbm_tpu.reliability.supervisor import SuperviseResult, WorkerFailure

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARAMS = {"objective": "regression", "num_leaves": 7, "verbosity": -1,
          "min_data_in_leaf": 5, "learning_rate": 0.2}
# the sharded-wave configuration of test_multichip_smoke: the drills
# must cover the MESH paths, not just the single-device engine
MESH_PARAMS = dict(PARAMS, tree_learner="data", tpu_growth_strategy="wave")


def _data(n=768, F=5, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, F)
    y = (2 * X[:, 0] + X[:, 1] * X[:, 2] + 0.1 * rng.randn(n))
    return X, y


@pytest.fixture(autouse=True)
def _clean_fault_env(monkeypatch):
    monkeypatch.delenv("LGBM_TPU_FAULT", raising=False)
    monkeypatch.delenv("LGBM_TPU_FAULT_CORRUPT", raising=False)
    faults.reload()
    yield
    faults.reload()


def _model_text(booster):
    return booster.model_to_string(num_iteration=-1)


def _events(path):
    if not os.path.exists(path):
        return []
    return [json.loads(ln) for ln in open(path) if ln.strip()]


# ------------------------------------------------------- reshard plan
def test_reshard_plan_covers_rows_exactly_once():
    for old_n, new_n, n in ((8, 7, 1000), (8, 4, 1024), (3, 2, 17),
                            (7, 8, 100), (5, 5, 50), (8, 1, 9)):
        plan = reshard_plan(old_n, new_n, n)
        segs = sorted((s.start, s.stop) for s in plan.segments)
        assert segs[0][0] == 0 and segs[-1][1] == n
        assert sum(b - a for a, b in segs) == n, "overlap or gap"
        for (a0, b0), (a1, b1) in zip(segs, segs[1:]):
            assert b0 == a1, "segments must tile contiguously"
        # every new rank's sources concatenate to exactly its block
        for nr in range(new_n):
            srcs = plan.sources_of(nr)
            lo, hi = rows_of(n, new_n, nr)
            assert srcs[0].start == lo and srcs[-1].stop == hi


def test_reshard_plan_identity_and_determinism():
    p = reshard_plan(8, 8, 640)
    assert p.moved_rows() == 0
    assert all(s.old_rank == s.new_rank for s in p.segments)
    # rank-independence: the plan is a pure function of three ints, so
    # any two processes (here: two calls) agree byte-for-byte
    a, b = reshard_plan(8, 7, 123457), reshard_plan(8, 7, 123457)
    assert a == b
    assert a.summary()["moved_rows"] == a.moved_rows()


# ------------------------------------------------- exit classification
def test_classify_preempt_and_lost():
    assert classify_returncode(143) == "preempt"   # SIGTERM via shell
    assert classify_returncode(-15) == "preempt"   # SIGTERM via Popen
    assert classify_returncode(WORKER_LOST_EXIT_CODE) == "lost"
    # the PR-7 table is unchanged
    assert classify_returncode(0) == "ok"
    assert classify_returncode(STALL_EXIT_CODE) == "hang"
    assert classify_returncode(None) == "hang"
    assert classify_returncode(17) == "crash"


def _result(*failures):
    return SuperviseResult(ok=False, timed_out=False,
                           failures=list(failures))


def _fail(rank, kind, rc=1):
    return WorkerFailure(rank, rc, "", kind=kind)


# ---------------------------------------------------- elastic policy
def test_policy_lost_rank_shrinks_immediately():
    p = ElasticPolicy(8, min_machines=1, rank_grace_s=3600)
    d = p.observe(_result(_fail(3, "lost", WORKER_LOST_EXIT_CODE)))
    assert d.action == SHRINK and d.num_machines == 7
    assert d.lost_ranks == [3]
    assert p.num_machines == 7


def test_policy_crash_streak_across_grace_shrinks():
    now = [0.0]
    p = ElasticPolicy(4, min_machines=1, rank_grace_s=10.0,
                      clock=lambda: now[0])
    assert p.observe(_result(_fail(2, "crash"))).action == RETRY
    now[0] = 5.0  # second failure inside the grace window: still retry
    assert p.observe(_result(_fail(2, "crash"))).action == RETRY
    now[0] = 12.0  # persisting past the window: permanently lost
    d = p.observe(_result(_fail(2, "hang")))
    assert d.action == SHRINK and d.num_machines == 3


def test_policy_alternating_ranks_and_preempt_never_shrink():
    now = [0.0]
    p = ElasticPolicy(4, min_machines=1, rank_grace_s=0.0,
                      clock=lambda: now[0])
    # alternating ranks: each failure resets the other's streak
    for t, rank in ((0, 0), (100, 1), (200, 0), (300, 1)):
        now[0] = t
        assert p.observe(_result(_fail(rank, "crash"))).action == RETRY
    # preemption is not rank damage
    for t in (400, 500, 600):
        now[0] = t
        assert p.observe(_result(_fail(2, "preempt", -15))).action == RETRY
    assert p.num_machines == 4


def test_policy_min_machines_floor_gives_up():
    p = ElasticPolicy(2, min_machines=2, rank_grace_s=0.0)
    d = p.observe(_result(_fail(1, "lost", WORKER_LOST_EXIT_CODE)))
    assert d.action == GIVE_UP
    assert "elastic_min_machines" in d.reason
    assert p.num_machines == 2


def test_supervise_result_classification_ranking():
    assert _result(_fail(0, "preempt"), _fail(1, "crash")
                   ).classification == "crash"
    assert _result(_fail(0, "lost"), _fail(1, "hang")
                   ).classification == "lost"
    assert _result(_fail(0, "preempt")).classification == "preempt"


# ------------------------------------- checkpoint integrity + fallback
def test_manifest_records_digests_for_every_generation(tmp_path):
    X, y = _data()
    ck = str(tmp_path / "ck")
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=8,
              checkpoint_dir=ck, checkpoint_freq=2)
    m = json.load(open(os.path.join(ck, "manifest.json")))
    assert m["format"] == 2
    assert m["num_rows"] == len(X)
    gens = m["generations"]
    assert [g["iteration"] for g in gens] == [4, 6, 8]
    mgr = CheckpointManager(ck, params=PARAMS)
    for g in gens:
        ok, detail = mgr._ck_from_entry(g).verify()
        assert ok, detail


def test_ckpt_corrupt_fallback_resumes_byte_identical(tmp_path, monkeypatch):
    """The acceptance drill: LGBM_TPU_FAULT=ckpt_corrupt@4 damages the
    newest checkpoint AFTER it lands; the resume quarantines it, falls
    back to generation N-1 with a ckpt_fallback event, and the finished
    run is byte-identical to an uninterrupted one.  Runs the sharded
    wave over the 8-device mesh — the production path."""
    X, y = _data()
    full = lgb.train(dict(MESH_PARAMS), lgb.Dataset(X, label=y),
                     num_boost_round=10)
    ck, mx = str(tmp_path / "ck"), str(tmp_path / "mx")
    monkeypatch.setenv("LGBM_TPU_FAULT", "ckpt_corrupt@4")
    faults.reload()
    lgb.train(dict(MESH_PARAMS), lgb.Dataset(X, label=y), num_boost_round=4,
              checkpoint_dir=ck, checkpoint_freq=1)
    monkeypatch.delenv("LGBM_TPU_FAULT")
    faults.reload()
    resumed = lgb.train(dict(MESH_PARAMS), lgb.Dataset(X, label=y),
                        num_boost_round=10, checkpoint_dir=ck,
                        checkpoint_freq=1, metrics_dir=mx)
    assert _model_text(resumed) == _model_text(full)
    # the damaged generation was quarantined, not deleted
    assert glob.glob(os.path.join(ck, "ckpt_0000004.*.corrupt-*"))
    evs = _events(os.path.join(mx, "events-rank0.jsonl"))
    fb = [e for e in evs if e["event"] == "ckpt_fallback"]
    assert len(fb) == 1 and fb[0]["from_iteration"] == 4 \
        and fb[0]["to_iteration"] == 3
    # every surviving generation still verifies
    m = json.load(open(os.path.join(ck, "manifest.json")))
    mgr = CheckpointManager(ck, params=MESH_PARAMS)
    for g in m["generations"]:
        ok, detail = mgr._ck_from_entry(g).verify()
        assert ok, detail


def test_ckpt_corrupt_bitflip_state_detected(tmp_path, monkeypatch):
    """A single flipped byte in the state npz — silent score corruption
    without digests — must also fall back, not resume into garbage."""
    X, y = _data(n=400)
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("LGBM_TPU_FAULT", "ckpt_corrupt@5")
    monkeypatch.setenv("LGBM_TPU_FAULT_CORRUPT", "bitflip")
    faults.reload()
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=5,
              checkpoint_dir=ck, checkpoint_freq=1)
    monkeypatch.delenv("LGBM_TPU_FAULT")
    faults.reload()
    mgr = CheckpointManager(ck, params=PARAMS)
    ck_obj = mgr.resumable(PARAMS)
    assert ck_obj is not None and ck_obj.iteration == 4
    assert glob.glob(os.path.join(ck, "ckpt_0000005.npz.corrupt-*"))


def test_corrupt_all_generations_starts_over(tmp_path, monkeypatch):
    X, y = _data(n=400)
    ck = str(tmp_path / "ck")
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=4,
              checkpoint_dir=ck, checkpoint_freq=2)
    for p in glob.glob(os.path.join(ck, "ckpt_*.txt")):
        with open(p, "r+b") as f:
            f.truncate(64)
    mgr = CheckpointManager(ck, params=PARAMS)
    assert mgr.resumable(PARAMS) is None
    # resume=True on a fully-corrupt dir trains from scratch, rc=0
    b = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=2,
                  checkpoint_dir=ck, checkpoint_freq=2)
    assert b.current_iteration() == 2


# --------------------------------------------- DART byte-exact resume
def test_dart_resume_byte_identical(tmp_path):
    """Carried-over PR-1 follow-up: boosting=dart resume is now
    byte-identical like GBDT (drop RNG + normalization counters + the
    full-precision shrinkage/internal_value the %g model text loses)."""
    X, y = _data(n=500)
    P = dict(PARAMS, boosting="dart", drop_rate=0.5, skip_drop=0.3)
    full = lgb.train(dict(P), lgb.Dataset(X, label=y), num_boost_round=12)
    ck = str(tmp_path / "ck")
    lgb.train(dict(P), lgb.Dataset(X, label=y), num_boost_round=7,
              checkpoint_dir=ck, checkpoint_freq=1)
    resumed = lgb.train(dict(P), lgb.Dataset(X, label=y),
                        num_boost_round=12, checkpoint_dir=ck,
                        checkpoint_freq=1)
    assert _model_text(resumed) == _model_text(full)


# ------------------------------------------------ preemption (SIGTERM)
# single-device engine on purpose: a fresh subprocess pays every compile
# cold (no cache, see conftest), and the mesh paths are already drilled
# by the corrupt-fallback and shrunken-mesh tests in this module
_PREEMPT_CHILD = r"""
import os, sys, time
sys.path.insert(0, os.environ["ELASTIC_REPO"])
import numpy as np
import lightgbm_tpu as lgb
from tests.test_elastic import PARAMS, _data
d = os.environ["ELASTIC_DIR"]
X, y = _data()
def slow(env):
    time.sleep(0.25)  # keep the run alive long enough to be preempted
b = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
              num_boost_round=40,
              checkpoint_dir=os.path.join(d, "ckpt"),
              checkpoint_freq=0,  # the ONLY checkpoint is the preempt one
              metrics_dir=os.path.join(d, "metrics"), callbacks=[slow])
print("PREEMPT_CHILD_FINISHED", b.current_iteration(), flush=True)
"""


def test_preempt_saves_on_demand_and_resume_is_byte_identical(tmp_path):
    """SIGTERM mid-run: the handler checkpoints within the grace budget
    (no periodic checkpointing configured at all), the exit classifies
    as *preempt*, and resuming reproduces the uninterrupted run
    byte-for-byte."""
    script = tmp_path / "child.py"
    script.write_text(_PREEMPT_CHILD)
    env = dict(os.environ, ELASTIC_DIR=str(tmp_path), ELASTIC_REPO=REPO)
    proc = subprocess.Popen([sys.executable, str(script)], cwd=REPO,
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    ev_path = tmp_path / "metrics" / "events-rank0.jsonl"
    deadline = time.monotonic() + 240
    preempt_at = None
    while time.monotonic() < deadline:
        its = [e["iteration"] for e in _events(str(ev_path))
               if e["event"] == "iteration"]
        if its and max(its) >= 3:
            preempt_at = max(its)
            break
        time.sleep(0.2)
    assert preempt_at is not None, "child never reached iteration 3"
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=120)
    assert "PREEMPT_CHILD_FINISHED" not in out
    assert classify_returncode(proc.returncode) == "preempt", \
        f"rc={proc.returncode}\n{out[-2000:]}"

    evs = _events(str(ev_path))
    pre = [e for e in evs if e["event"] == "preempt"]
    assert len(pre) == 1 and pre[0]["saved"] is True
    assert pre[0]["elapsed_s"] <= pre[0]["grace_s"]
    saved_it = pre[0]["iteration"]
    assert saved_it >= 3
    m = json.load(open(tmp_path / "ckpt" / "manifest.json"))
    assert m["iteration"] == saved_it and m["digests"]

    # resume in-process: byte-identical to an uninterrupted run
    X, y = _data()
    full = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                     num_boost_round=saved_it + 3)
    resumed = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                        num_boost_round=saved_it + 3,
                        checkpoint_dir=str(tmp_path / "ckpt"))
    assert _model_text(resumed) == _model_text(full)


# ------------------------------------- elastic shrink (supervisor e2e)
# Worker body for the supervisor drills: the REAL spec/env/tombstone/
# heartbeat/fault plumbing of distributed._WORKER_MAIN with the SPMD
# training replaced by a deterministic loop — multi-process collectives
# do not run on this CPU backend (see module docstring), and what these
# drills pin is the supervisor: classification, tombstones, shrink,
# renumbering, events.
_FAKE_WORKER = r"""
import json, os, sys, time
spec = json.load(open(sys.argv[1]))
rank = int(sys.argv[2])
for k, v in spec.get("env", {}).items():
    os.environ[k] = v
os.environ["LGBM_TPU_FAULT_SELF_RANK"] = str(rank)
os.environ["LGBM_TPU_FAULT_ATTEMPT"] = str(spec.get("attempt", 0))
os.environ["LGBM_TPU_WORLD_SIZE"] = str(spec["num_machines"])
if spec.get("tombstone_dir"):
    os.environ["LGBM_TPU_TOMBSTONE_DIR"] = spec["tombstone_dir"]
sys.path.insert(0, spec["repo"])
from lightgbm_tpu.reliability import faults
faults.check_tombstone()
if spec.get("reshard"):
    from lightgbm_tpu.parallel import reshard_plan
    rs = spec["reshard"]
    plan = reshard_plan(rs["old_n"], rs["new_n"], rs["num_rows"] or 0)
    assert plan.new_n == spec["num_machines"]
hb = None
if spec.get("heartbeat_dir"):
    hb = os.path.join(spec["heartbeat_dir"], f"heartbeat-rank{rank}")
for i in range(4):
    faults.maybe_crash(i)
    faults.maybe_worker_lost(i)
    if hb:
        open(hb, "a").close(); os.utime(hb, None)
    time.sleep(0.05)
if rank == 0:
    with open(os.environ["FAKE_MODEL_SRC"]) as f:
        txt = f.read()
    with open(spec["model_out"], "w") as f:
        f.write(txt)
print(f"worker {rank} done", flush=True)
"""


def _run_fake_cluster(tmp_path, monkeypatch, fault, num_machines=3,
                      extra_params=None, max_retries=3):
    from lightgbm_tpu import distributed

    X, y = _data(n=256)
    seed_model = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                           num_boost_round=2)
    src = tmp_path / "seed_model.txt"
    seed_model.save_model(str(src))
    monkeypatch.setenv("FAKE_MODEL_SRC", str(src))
    monkeypatch.setattr(distributed, "_WORKER_MAIN", _FAKE_WORKER)
    params = dict(PARAMS, metrics_dir=str(tmp_path / "mx"),
                  elastic_rank_grace_s=0.0, **(extra_params or {}))
    env = {"LGBM_TPU_FAULT": fault} if fault else {}
    booster = distributed.train_distributed(
        params, X, y, num_boost_round=2, num_machines=num_machines,
        worker_env=env, force_cpu=True, timeout=120,
        max_retries=max_retries, retry_backoff=0.01, poll_interval=0.05)
    sup = _events(str(tmp_path / "mx" / "events-ranksupervisor.jsonl"))
    return booster, sup


def test_worker_lost_shrinks_and_completes(tmp_path, monkeypatch):
    """The rank-loss drill: worker_lost@2 on rank 1 of 3 tombstones the
    rank; the supervisor classifies *lost*, shrinks 3 -> 2 (renumbered
    ranks clear the tombstone key), and the relaunch completes.  The
    elastic_shrink event carries the old/new topology."""
    monkeypatch.setenv("LGBM_TPU_FAULT_RANK", "1")
    booster, sup = _run_fake_cluster(tmp_path, monkeypatch,
                                     "worker_lost@2")
    monkeypatch.delenv("LGBM_TPU_FAULT_RANK")
    assert booster.current_iteration() == 2
    assert booster.elastic_shrinks == 1
    assert booster.final_num_machines == 2
    fails = [e for e in sup if e["event"] == "cluster_attempt_failed"]
    assert fails and fails[0]["classification"] == "lost"
    shr = [e for e in sup if e["event"] == "elastic_shrink"]
    assert len(shr) == 1
    assert shr[0]["old_num_machines"] == 3
    assert shr[0]["new_num_machines"] == 2
    assert shr[0]["lost_ranks"] == [1]
    # the tombstone outlived the attempt — that is what forces the
    # shrink instead of an endless same-size relaunch loop
    assert [e for e in sup if e["event"] == "cluster_retry_succeeded"]


def test_combined_rank_loss_with_repeated_crash(tmp_path, monkeypatch):
    """Combined drill: the same rank crashing on consecutive attempts
    (grace 0) is promoted to permanently lost even without a tombstone
    — the dead-PID-persisting shape — and the cluster still shrinks and
    completes (2 -> 1: the floor world size still trains)."""
    monkeypatch.setenv("LGBM_TPU_FAULT_RANK", "1")
    booster, sup = _run_fake_cluster(
        tmp_path, monkeypatch, "worker_crash@1@0,worker_crash@1@1",
        num_machines=2)
    monkeypatch.delenv("LGBM_TPU_FAULT_RANK")
    assert booster.current_iteration() == 2
    shr = [e for e in sup if e["event"] == "elastic_shrink"]
    assert len(shr) == 1 and shr[0]["lost_ranks"] == [1]
    assert shr[0]["old_num_machines"] == 2
    assert shr[0]["new_num_machines"] == 1


# --------------------------------- shrunken-mesh completion parity
def test_shrunken_mesh_resume_metric_parity(tmp_path):
    """The training-math half of the shrink drill, on real devices: a
    run checkpointed on an 8-device mesh and COMPLETED on a 7-device
    mesh must match the fixed-topology run's eval metrics within 1e-6
    (the resume is predict-seeded across topologies, not byte-exact —
    padding and reduction shapes legitimately change)."""
    X, y = _data()
    Xte, yte = _data(seed=12)
    p8 = dict(MESH_PARAMS, num_machines=8)
    p7 = dict(MESH_PARAMS, num_machines=7)
    ck = str(tmp_path / "ck")
    lgb.train(dict(p8), lgb.Dataset(X, label=y), num_boost_round=5,
              checkpoint_dir=ck, checkpoint_freq=1)
    shrunken = lgb.train(dict(p7), lgb.Dataset(X, label=y),
                         num_boost_round=10, checkpoint_dir=ck,
                         checkpoint_freq=1)
    assert shrunken._gbdt.mesh is not None
    assert int(shrunken._gbdt.mesh.devices.size) == 7
    fixed = lgb.train(dict(p8), lgb.Dataset(X, label=y),
                      num_boost_round=10)
    mse_s = float(np.mean((shrunken.predict(Xte) - yte) ** 2))
    mse_f = float(np.mean((fixed.predict(Xte) - yte) ** 2))
    assert abs(mse_s - mse_f) < 1e-6, (mse_s, mse_f)


# ------------------------------------------- full SPMD drill (slow)
@pytest.mark.slow
def test_spmd_worker_lost_8_to_7(tmp_path):
    """The full acceptance drill on a real multi-process backend:
    worker_lost@3 on the 8-rank cluster completes on 7 ranks with an
    elastic_shrink event and eval metrics within 1e-6 of the fixed
    7-rank run.  CPU-only jaxlib builds cannot run multi-process
    collectives (probed, like test_multiprocess) — skipped there."""
    from tests.test_fault_distributed import _multiprocess_spmd_available

    class _TF:
        def mktemp(self, name):
            d = tmp_path / name
            d.mkdir()
            return d

    if not _multiprocess_spmd_available(_TF()):
        pytest.skip("no multi-process SPMD on this backend")
    from lightgbm_tpu import distributed
    X, y = _data(n=1024)
    os.environ["LGBM_TPU_FAULT_RANK"] = "3"
    try:
        booster = distributed.train_distributed(
            dict(MESH_PARAMS, metrics_dir=str(tmp_path / "mx"),
                 elastic_rank_grace_s=0.0),
            X, y, num_boost_round=4, num_machines=8,
            worker_env={"LGBM_TPU_FAULT": "worker_lost@2"},
            force_cpu=True, timeout=600, max_retries=3,
            checkpoint_dir=str(tmp_path / "ck"), checkpoint_freq=1)
    finally:
        os.environ.pop("LGBM_TPU_FAULT_RANK", None)
    assert booster.final_num_machines == 7
    fixed = distributed.train_distributed(
        dict(MESH_PARAMS), X, y, num_boost_round=4, num_machines=7,
        force_cpu=True, timeout=600)
    d = np.abs(booster.predict(X) - fixed.predict(X))
    assert float(d.max()) < 1e-6
