"""Serving fault domain suite (docs/Serving.md fleet section): replica
fleet supervision, retry/backoff routing, load-shedding admission,
rolling publish, canary auto-rollback, drain semantics.

Two layers of fixture:

* **Stub replicas** (`tests/fleet_stub.py`) — real processes + real
  sockets speaking the serving wire protocol with a deterministic
  linear "model" (`preds = sum(row) * scale`), but no jax and no
  model load: the fleet/router machinery (spawn, poll, classify,
  backoff relaunch, health gating, retry, shed, canary math) is
  exercised end to end in milliseconds.  `fault_envs` doubles as the
  per-replica env injection hook, exactly as the bench uses it.
* **Real in-process daemons** for the daemon-side contracts the stubs
  fake: warmup-ledger readiness, ShedError fail-fast, serve_* fault
  points, drain-abandoned accounting, and the TCP client's
  deadline/reconnect behaviour.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.observability.registry import global_registry
from lightgbm_tpu.reliability import faults
from lightgbm_tpu.serving import (OverloadedError, ReplicaFleet, Router,
                                  ServingClient, ServingDaemon, ShedError,
                                  serve_counters_reset, start_frontend)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STUB = os.path.join(REPO, "tests", "fleet_stub.py")


# ------------------------------------------------------------ stub fixtures
def _mk_fleet(workdir, n=3, max_restarts=2, envs=None,
              entries=(("m", "scale1"),)):
    """Fleet of stub replicas; `envs[idx]` adds per-replica env."""
    fault_envs = {}
    for i in range(n):
        e = {"STUB_READY_FILE": os.path.join(
            str(workdir), f"replica-{i}.ready.json")}
        e.update((envs or {}).get(i, {}))
        fault_envs[i] = e
    return ReplicaFleet(
        n, list(entries), str(workdir), max_restarts=max_restarts,
        health_interval_s=0.1,
        spawn_cmd=lambda idx, rf: [sys.executable, STUB],
        fault_envs=fault_envs)


def _mk_router(fleet, **overrides):
    p = {"serve_retry_max": 3, "serve_retry_backoff_ms": 5.0,
         "serve_request_timeout_s": 15.0, "serve_canary_pct": 50.0,
         "serve_canary_min_samples": 12,
         "serve_canary_max_divergence": 2.0,
         "serve_canary_max_error_rate": 0.25}
    p.update(overrides)
    return Router(fleet, Config(p))


ROWS = np.arange(12, dtype=np.float64).reshape(3, 4)
SUMS = ROWS.sum(axis=1)


@pytest.fixture(autouse=True)
def _reset_counters():
    serve_counters_reset()
    for key in ("router_requests", "router_rows", "router_retries",
                "router_failed", "router_conn_errors", "router_timeouts",
                "serve_replica_down", "serve_replica_restarts"):
        global_registry.inc(key, -global_registry.counter(key))
    yield


# ---------------------------------------------------------------- fault core
def test_router_survives_replica_kill_zero_failed_requests(tmp_path):
    """A replica killed mid-load costs ZERO client requests: in-flight
    requests retry on a different replica, the supervisor relaunches
    the dead one with backoff, and it rejoins the rotation."""
    fleet = _mk_fleet(tmp_path, n=3).start()
    try:
        assert fleet.wait_ready(timeout=20)
        router = _mk_router(fleet)
        failures, done = [], [0]
        lock = threading.Lock()
        kill_gate = threading.Event()

        def client(tid):
            for i in range(40):
                try:
                    r = router.predict("m", ROWS, deadline_ms=10_000)
                    assert np.allclose(r.preds, SUMS)
                except Exception as e:  # noqa: BLE001
                    with lock:
                        failures.append(repr(e))
                with lock:
                    done[0] += 1
                    if done[0] >= 20:
                        kill_gate.set()

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        assert kill_gate.wait(timeout=30)
        fleet.replicas[0].proc.kill()     # hard kill, mid-load
        for t in threads:
            t.join(timeout=60)
        assert done[0] == 160 and not failures, failures[:3]
        # the supervisor classified the kill and relaunched with backoff
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            d = fleet.describe()[0]
            if d["healthy"] and d["ready"]:
                break
            time.sleep(0.05)
        d = fleet.describe()[0]
        assert d["restarts"] == 1 and d["gen"] == 2
        assert d["healthy"] and not d["down"]
        assert global_registry.counter("serve_replica_down") == 1
        assert global_registry.counter("serve_replica_restarts") == 1
    finally:
        fleet.stop(drain=False)


def test_restart_budget_exhaustion_marks_replica_down(tmp_path):
    """A replica that dies more than serve_max_replica_restarts times
    stays down; the fleet keeps serving on the survivors."""
    # replica 0 crashes on its first request, every generation
    fleet = _mk_fleet(tmp_path, n=2, max_restarts=1,
                      envs={0: {"STUB_CRASH_AFTER": "1"}}).start()
    try:
        assert fleet.wait_ready(timeout=20)
        router = _mk_router(fleet)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                router.predict("m", ROWS, deadline_ms=5_000)
            except Exception:  # noqa: BLE001 - draining the budget
                pass
            if fleet.describe()[0]["down"]:
                break
            time.sleep(0.02)
        d = fleet.describe()[0]
        assert d["down"] and d["restarts"] == 1
        # the fleet still serves on the survivor
        r = router.predict("m", ROWS, deadline_ms=5_000)
        assert np.allclose(r.preds, SUMS) and r.replica == 1
        assert fleet.alive()
    finally:
        fleet.stop(drain=False)


# ------------------------------------------------------------ shed/admission
def test_shed_retries_on_another_replica(tmp_path):
    """A structured shed is retryable: the router counts it and the
    request lands on a non-shedding replica — zero caller errors."""
    fleet = _mk_fleet(tmp_path, n=2,
                      envs={0: {"STUB_SHED": "1"}}).start()
    try:
        assert fleet.wait_ready(timeout=20)
        router = _mk_router(fleet)
        for _ in range(20):
            r = router.predict("m", ROWS, deadline_ms=10_000)
            assert np.allclose(r.preds, SUMS) and r.replica == 1
        assert global_registry.counter("serve_shed") > 0
        assert global_registry.counter("router_retries") > 0
        assert router.stats()["router_failed"] == 0
    finally:
        fleet.stop(drain=False)


def test_all_replicas_shedding_rejects_overloaded(tmp_path):
    """Admission matrix: every attempt shedding -> OverloadedError;
    every health probe advertising shed -> rejected BEFORE any attempt
    (the fleet-wide admission controller)."""
    fleet = _mk_fleet(tmp_path, n=2,
                      envs={0: {"STUB_SHED": "1"},
                            1: {"STUB_SHED": "1"}}).start()
    try:
        assert fleet.wait_ready(timeout=20)
        router = _mk_router(fleet)
        with pytest.raises(OverloadedError, match="shed"):
            router.predict("m", ROWS, deadline_ms=10_000)
        assert global_registry.counter("serve_overloaded") == 1
    finally:
        fleet.stop(drain=False)
    serve_counters_reset()
    fleet = _mk_fleet(tmp_path, n=2,
                      envs={0: {"STUB_SHED_HEALTH": "1"},
                            1: {"STUB_SHED_HEALTH": "1"}}).start()
    try:
        assert fleet.wait_ready(timeout=20)
        router = _mk_router(fleet)
        before = global_registry.counter("router_retries")
        with pytest.raises(OverloadedError, match="routable replicas"):
            router.predict("m", ROWS)
        # rejected at admission: no retries burned, no attempt made
        assert global_registry.counter("router_retries") == before
        assert global_registry.counter("serve_overloaded") == 1
    finally:
        fleet.stop(drain=False)


# -------------------------------------------------------------- publish path
def test_rolling_publish_is_version_consistent_under_load(tmp_path):
    """Rolling publish under live traffic: every response matches
    exactly the scale of the version that served it (version 1 <->
    scale1, version 2 <-> scale3) — a mixed-fleet window is fine, a
    mixed RESPONSE never is; after the roll, only v2 answers."""
    fleet = _mk_fleet(tmp_path, n=3).start()
    try:
        assert fleet.wait_ready(timeout=20)
        router = _mk_router(fleet)
        router.register_incumbent("m", "scale1")
        mismatches, errors = [], []
        stop = threading.Event()
        lock = threading.Lock()

        def client():
            while not stop.is_set():
                try:
                    r = router.predict("m", ROWS, deadline_ms=10_000)
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(repr(e))
                    continue
                exp = SUMS if r.version == 1 else SUMS * 3
                if not np.allclose(r.preds, exp):
                    with lock:
                        mismatches.append((r.version, list(r.preds)))

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.1)
        out = router.publish("m", "v2_scale3", canary_pct=0)
        assert out == {"canary": False,
                       "replicas": {0: 2, 1: 2, 2: 2}}
        time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors and not mismatches, (errors[:3],
                                               mismatches[:3])
        r = router.predict("m", ROWS)
        assert r.version == 2 and np.allclose(r.preds, SUMS * 3)
        # relaunched replicas will load the NEW incumbent
        assert dict(fleet.model_entries)["m"] == "v2_scale3"
    finally:
        fleet.stop(drain=False)


def test_canary_divergence_auto_rollback(tmp_path):
    """The auto-rollback drill: a canary whose score distribution
    diverges is rolled back — the incumbent returns to the canary
    replica, `serve_rollback` counts it, and traffic never sees an
    error."""
    fleet = _mk_fleet(tmp_path, n=2).start()
    try:
        assert fleet.wait_ready(timeout=20)
        router = _mk_router(fleet)
        router.register_incumbent("m", "scale1")
        out = router.publish("m", "bad_scale100")
        assert out["canary"] is True and out["pct"] == 50.0
        stop = threading.Event()
        errors = []

        def load():
            while not stop.is_set():
                try:
                    router.predict("m", ROWS, deadline_ms=10_000)
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))
                time.sleep(0.001)

        threads = [threading.Thread(target=load) for _ in range(3)]
        for t in threads:
            t.start()
        verdict = router.canary_wait("m", timeout=60)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert verdict == "rolled_back" and not errors
        assert global_registry.counter("serve_rollback") == 1
        stats = router.stats()
        assert "divergence" in stats["canaries"]["m"]
        assert stats["canaries"]["m"]["resolved"] == "rolled_back"
        # the canary replica serves the incumbent again (version
        # bumped by the rollback publish, scores back to scale 1)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            seen = {router.predict("m", ROWS).replica for _ in range(6)}
            if len(seen) == 2:
                break
        for _ in range(10):
            r = router.predict("m", ROWS)
            assert np.allclose(r.preds, SUMS), (r.replica, r.version)
    finally:
        fleet.stop(drain=False)


def test_canary_clean_promotes_fleet_wide(tmp_path):
    """A canary that tracks the incumbent's distribution promotes: the
    remaining replicas roll, the published path becomes the incumbent
    for future relaunches."""
    fleet = _mk_fleet(tmp_path, n=3).start()
    try:
        assert fleet.wait_ready(timeout=20)
        router = _mk_router(fleet)
        router.register_incumbent("m", "scale1")
        router.publish("m", "v2_scale1")   # same distribution
        stop = threading.Event()

        def load():
            while not stop.is_set():
                try:
                    router.predict("m", ROWS, deadline_ms=10_000)
                except Exception:  # noqa: BLE001
                    pass
                time.sleep(0.001)

        threads = [threading.Thread(target=load) for _ in range(3)]
        for t in threads:
            t.start()
        verdict = router.canary_wait("m", timeout=60)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert verdict == "promoted"
        assert global_registry.counter("serve_rollback") == 0
        # every replica now answers with the new version
        deadline = time.monotonic() + 10
        versions = set()
        while time.monotonic() < deadline:
            versions = {router.predict("m", ROWS).version
                        for _ in range(8)}
            if versions == {2}:
                break
        assert versions == {2}
        assert dict(fleet.model_entries)["m"] == "v2_scale1"
    finally:
        fleet.stop(drain=False)


# ------------------------------------------------------------------ health
def test_health_gates_routing_until_warmup(tmp_path):
    """A replica is NOT routable until its health probe reports the
    warmup ledger complete — churn never leaks cold compiles into
    live traffic."""
    fleet = _mk_fleet(tmp_path, n=1,
                      envs={0: {"STUB_WARMUP_S": "1.2"}}).start()
    try:
        deadline = time.monotonic() + 0.9
        while time.monotonic() < deadline:
            assert fleet.endpoints() == []
            time.sleep(0.1)
        assert fleet.wait_ready(timeout=20)
        assert len(fleet.endpoints()) == 1
    finally:
        fleet.stop(drain=False)


# ===================== real-daemon half (in-process) =======================
_PARAMS = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
           "metric": "none", "min_data_in_leaf": 5,
           "device_predict": "true", "device_predict_min_bucket": 32}


def _train(rounds=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(500, 6).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    bst = lgb.train(dict(_PARAMS), lgb.Dataset(X, label=y),
                    num_boost_round=rounds)
    bst._gbdt._sync_model()
    return bst, X


def _daemon(**overrides):
    p = dict(_PARAMS, serve_max_batch_rows=128,
             serve_max_coalesce_wait_ms=0.0)
    p.update(overrides)
    serve_counters_reset()
    return ServingDaemon(Config(p)).start()


@pytest.fixture
def _clean_faults():
    yield
    os.environ.pop("LGBM_TPU_FAULT", None)
    os.environ.pop("LGBM_TPU_FAULT_SLOW_S", None)
    faults.reload()


def test_daemon_health_readiness_before_and_after_warmup():
    """registry.ready() is the warmup ledger: False while a load is in
    flight, True only once every model warmed; daemon.health() carries
    it plus the shed state."""
    bst, X = _train()
    d = _daemon()
    try:
        h = d.health()
        assert h["ready"] is False and h["models"] == {}
        handle = d.registry.register("m", booster=bst, block=False)
        # a pending load parks readiness even if probed mid-warmup
        assert d.registry.ready() is False or handle.done()
        handle.wait(timeout=120)
        deadline = time.monotonic() + 10
        while not d.registry.ready() and time.monotonic() < deadline:
            time.sleep(0.02)
        h = d.health()
        assert h["ready"] is True and h["models"] == {"m": 1}
        assert h["shedding"] is False and h["pid"] == os.getpid()
    finally:
        d.stop()


def test_queue_full_sheds_fast_and_counts(_clean_faults):
    """The bounded queue FAILS FAST with ShedError (no blocking) and
    the health probe flips `shedding` inside the shed window."""
    bst, X = _train()
    os.environ["LGBM_TPU_FAULT"] = "serve_slow@1"
    os.environ["LGBM_TPU_FAULT_SLOW_S"] = "2.0"
    faults.reload()
    d = _daemon(serve_queue_depth=2)
    try:
        d.registry.register("m", booster=bst, block=True)
        futs = [d.submit("m", X[:2])]      # dispatcher pops + sleeps 2 s
        time.sleep(0.3)
        shed = None
        t0 = time.monotonic()
        for _ in range(8):                 # 2 fill the queue, then shed
            try:
                futs.append(d.submit("m", X[:2]))
            except ShedError as e:
                shed = e
                break
        elapsed = time.monotonic() - t0
        assert shed is not None and shed.depth == 2
        assert elapsed < 1.0, "shed must fail fast, not block"
        assert global_registry.counter("serve_shed") >= 1
        assert d.health()["shedding"] is True
        for f in futs:                     # queued work still completes
            assert f.result(timeout=30) is not None
    finally:
        d.stop()


def test_serve_fault_points_crash_shed_slow(_clean_faults):
    """The serve_* fault specs parse, rank-gate, and fire on the
    request counter (serve_crash drills live in the bench subprocess;
    here the shed + slow halves and the spec plumbing)."""
    bst, X = _train()
    os.environ["LGBM_TPU_FAULT"] = "serve_shed@2,serve_slow@3"
    faults.reload()
    os.environ["LGBM_TPU_FAULT_SLOW_S"] = "0.5"
    d = _daemon()
    try:
        d.registry.register("m", booster=bst, block=True)
        assert d.predict("m", X[:2]) is not None      # request 1: clean
        with pytest.raises(ShedError):                # request 2: shed
            d.submit("m", X[:2])
        t0 = time.monotonic()
        assert d.predict("m", X[:2]) is not None      # request 3: slow
        assert time.monotonic() - t0 >= 0.45
        assert global_registry.counter("faults_injected") >= 2
    finally:
        d.stop()
    # rank gating: a spec aimed at another replica never fires here
    os.environ["LGBM_TPU_FAULT"] = "serve_shed@1"
    os.environ["LGBM_TPU_FAULT_RANK"] = "5"
    faults.reload()
    try:
        d = _daemon()
        d.registry.register("m", booster=bst, block=True)
        assert d.predict("m", X[:2]) is not None
    finally:
        os.environ.pop("LGBM_TPU_FAULT_RANK", None)
        d.stop()


def test_drain_deadline_abandonment_is_announced(_clean_faults):
    """stop(drain=True) that misses its deadline counts the abandoned
    requests (`serve_drain_abandoned`) instead of dropping them
    silently; their futures fail with the stop error."""
    bst, X = _train()
    os.environ["LGBM_TPU_FAULT"] = "serve_slow@1"
    os.environ["LGBM_TPU_FAULT_SLOW_S"] = "2.0"
    faults.reload()
    d = _daemon(serve_queue_depth=64)
    d.registry.register("m", booster=bst, block=True)
    futs = [d.submit("m", X[:2])]          # holds the dispatcher 2 s
    time.sleep(0.2)
    futs += [d.submit("m", X[:2]) for _ in range(5)]
    before = global_registry.counter("serve_drain_abandoned")
    drained = d.stop(drain=True, timeout=0.2)
    assert drained is False
    assert d.coalescer.last_abandoned == 5
    assert global_registry.counter("serve_drain_abandoned") - before == 5
    failed = 0
    for f in futs[1:]:
        with pytest.raises(RuntimeError, match="stopped"):
            f.result(timeout=30)
        failed += 1
    assert failed == 5


def test_tcp_client_deadline_and_reconnect_with_backoff():
    """ServingClient.connect: deadline_ms propagates to the replica
    (a spent deadline fails fast server-side), and a dropped TCP
    connection reconnects with backoff instead of raising — the
    replica-restart shape."""
    bst, X = _train()
    d = _daemon()
    try:
        d.registry.register("m", booster=bst, block=True)
        srv = start_frontend(d, port=0, request_timeout_s=30.0)
        port = srv.server_address[1]
        c = ServingClient.connect("127.0.0.1", port)
        exp = bst.predict(X[:3])
        assert np.array_equal(c.predict("m", X[:3]), exp)
        with pytest.raises(TimeoutError):
            c.predict("m", X[:3], deadline_ms=0.001)
        # drop the server; a restart on the same port must be invisible
        srv.shutdown()
        srv.server_close()
        srv2 = start_frontend(d, port=port, request_timeout_s=30.0)
        try:
            assert np.array_equal(c.predict("m", X[:3]), exp)
            assert c.health()["ready"] is True
        finally:
            srv2.shutdown()
        c.close()
    finally:
        d.stop()


# --------------------------------------------------------------- SIGTERM
_FLEET_SIGTERM_CHILD = r"""
import os, sys, time
sys.path.insert(0, os.environ["FLEET_REPO"])
from lightgbm_tpu.config import Config
from lightgbm_tpu.observability import (install_sigterm_flush,
                                        set_preemption_hook)
from lightgbm_tpu.serving import ReplicaFleet, Router

work = os.environ["FLEET_WORK"]
stub = os.environ["FLEET_STUB"]
n = 2
fleet = ReplicaFleet(
    n, [("m", "scale1")], work, max_restarts=1, health_interval_s=0.1,
    spawn_cmd=lambda idx, rf: [sys.executable, stub],
    fault_envs={i: {"STUB_READY_FILE":
                    os.path.join(work, f"replica-{i}.ready.json")}
                for i in range(n)}).start()
assert fleet.wait_ready(timeout=30)
router = Router(fleet, Config({}))
router.start_frontend(port=0)

def _drain():
    router.stop()
    rcs = fleet.stop(drain=True, timeout=20.0)
    print("DRAINED", sorted(rcs.values()), flush=True)
    return None

assert install_sigterm_flush()
set_preemption_hook(_drain)
print("FLEET_READY", flush=True)
time.sleep(60)
"""


def test_fleet_sigterm_drains_whole_fleet_rc143(tmp_path):
    """SIGTERM to the fleet runner drains the WHOLE fleet: the router
    stops, every replica gets its own SIGTERM drain (each exits 143),
    and the runner re-delivers — its exit stays 143 so supervisors
    classify *preempt*."""
    script = tmp_path / "child.py"
    script.write_text(_FLEET_SIGTERM_CHILD)
    work = tmp_path / "fleet"
    work.mkdir()
    env = dict(os.environ, FLEET_REPO=REPO, FLEET_WORK=str(work),
               FLEET_STUB=STUB, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-u", str(script)],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    try:
        t0 = time.monotonic()
        while time.monotonic() - t0 < 120:
            line = proc.stdout.readline()
            if "FLEET_READY" in line:
                break
            if proc.poll() is not None:
                pytest.fail(f"fleet child exited early: {line}")
        else:
            pytest.fail("fleet child never became ready")
        proc.send_signal(signal.SIGTERM)
        out_rest, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode in (-signal.SIGTERM, 143), (proc.returncode,
                                                       out_rest)
    assert "DRAINED [143, 143]" in out_rest, out_rest
