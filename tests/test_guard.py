"""Stall watchdog + graceful-degradation ladder (ISSUE 7 tentpole).

A multi-device dry run once hung to the wall-clock cap with one stderr
line (a rank wedged in a collective); these
tests pin the machinery that turns that shape into a diagnosis and an
auto-recovered run: the RunGuard trips on a missing heartbeat and writes
a parseable stall diagnosis, a hung process exits with the distinct
STALL code (classified hang, not crash), the supervisor catches
live-but-silent ranks by heartbeat mtime, and an auto_degrade relaunch
resumes from checkpoint with exactly one ladder knob disabled —
producing a byte-identical model to an uninterrupted run with that knob
off."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.reliability.guard import (DEGRADE_LADDER,
                                            STALL_EXIT_CODE, RunGuard,
                                            apply_auto_degrade,
                                            classify_returncode,
                                            disabled_value, knob_enabled,
                                            next_degradation)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# wall-clock bound for each guard subprocess (compile + a few rounds +
# the ~3 s stall deadline; a REAL runaway blows far past this)
SUBPROC_BUDGET_S = 240.0


# --------------------------------------------------------------------------
# RunGuard unit behavior (in-process, no subprocesses)
# --------------------------------------------------------------------------

def test_watchdog_trips_and_writes_parseable_diagnosis(tmp_path):
    hits = []
    g = RunGuard(str(tmp_path), rank=3, stall_floor_s=0.2, stall_factor=2.0,
                 first_deadline_s=0.3, knobs={"tpu_donate_buffers": True},
                 on_stall=hits.append, poll_interval=0.05)
    g.start()
    try:
        deadline = time.monotonic() + 10.0
        while not g.tripped and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        g.stop()
    assert g.tripped and len(hits) == 1
    diag = json.load(open(tmp_path / "stall-rank3.json"))
    for key in ("kind", "rank", "silent_s", "deadline_s", "last_iteration",
                "knobs", "stacks", "jax", "exit_code"):
        assert key in diag, f"diagnosis missing {key}"
    assert diag["kind"] == "stall"
    assert diag["rank"] == 3
    assert diag["exit_code"] == STALL_EXIT_CODE
    assert diag["knobs"]["tpu_donate_buffers"] is True
    # the faulthandler dump really captured Python frames
    assert any("File" in line for line in diag["stacks"])


def test_first_compile_deadline_is_larger_then_median_takes_over(tmp_path):
    g = RunGuard(str(tmp_path), stall_floor_s=1.0, stall_factor=2.0,
                 first_deadline_s=50.0)
    # before any tick: the first-compile deadline rules
    assert g.current_deadline_s() == 50.0
    g._started_at = time.monotonic()
    g.tick(1)
    # one tick but no duration sample yet: still the conservative deadline
    assert g.current_deadline_s() == 50.0
    g.tick(2)
    # median known: deadline drops to max(floor, factor * median)
    assert g.median_iter_s() is not None
    assert g.current_deadline_s() == pytest.approx(
        max(1.0, 2.0 * g.median_iter_s()))
    assert g.current_deadline_s() < 50.0


def test_default_first_deadline_scales_with_floor(tmp_path):
    assert RunGuard(str(tmp_path),
                    stall_floor_s=120.0).first_deadline_s == 1200.0
    # tiny test floors still get a compile-sized first window
    assert RunGuard(str(tmp_path),
                    stall_floor_s=2.0).first_deadline_s == 600.0


def test_slow_iteration_under_deadline_does_not_trip(tmp_path):
    g = RunGuard(str(tmp_path), stall_floor_s=1.0, stall_factor=20.0,
                 first_deadline_s=30.0, on_stall=lambda d: None,
                 poll_interval=0.05)
    g.start()
    try:
        for i in range(1, 5):
            time.sleep(0.05)
            g.tick(i)
        time.sleep(0.5)  # slow_iter-shaped pause, well under the 1 s floor
        g.tick(5)
    finally:
        g.stop()
    assert not g.tripped


def test_tick_touches_heartbeat_file(tmp_path):
    hb = tmp_path / "heartbeat-rank0"
    g = RunGuard(str(tmp_path), stall_floor_s=60.0, heartbeat_path=str(hb))
    g._started_at = time.monotonic()
    g.tick(1)
    assert hb.exists()
    first = hb.stat().st_mtime
    time.sleep(0.05)
    g.tick(2)
    assert hb.stat().st_mtime >= first


# --------------------------------------------------------------------------
# classification + ladder units
# --------------------------------------------------------------------------

def test_classify_returncode():
    assert classify_returncode(0) == "ok"
    assert classify_returncode(STALL_EXIT_CODE) == "hang"
    assert classify_returncode(None) == "hang"   # killed past a deadline
    assert classify_returncode(124) == "hang"    # timeout(1)
    assert classify_returncode(17) == "crash"    # faults.CRASH_EXIT_CODE
    assert classify_returncode(1) == "crash"


def test_degradation_ladder_order_and_values():
    assert [k for k, _ in DEGRADE_LADDER] == [
        "tpu_donate_buffers", "compile_cache_dir", "async_host_io",
        "device_eval"]
    enabled = {"tpu_donate_buffers": True, "compile_cache_dir": "/c",
               "async_host_io": True, "device_eval": "auto"}
    order = []
    done = []
    while True:
        k = next_degradation(enabled, done)
        if k is None:
            break
        order.append(k)
        done.append(k)
    assert order == [k for k, _ in DEGRADE_LADDER]
    # knobs already off are skipped
    assert next_degradation({**enabled, "tpu_donate_buffers": False},
                            []) == "compile_cache_dir"
    assert next_degradation({"tpu_donate_buffers": False,
                             "compile_cache_dir": "off",
                             "async_host_io": False,
                             "device_eval": "false"}, []) is None
    assert disabled_value("device_eval") == "false"
    assert knob_enabled("device_eval", "auto")
    # "" is the default cache directory: on until the ladder says "off"
    assert knob_enabled("compile_cache_dir", "  ")
    assert disabled_value("compile_cache_dir") == "off"
    assert not knob_enabled("compile_cache_dir", "off")


def test_apply_auto_degrade_walks_the_ladder(tmp_path):
    mdir = str(tmp_path)

    def stall_once(cfg):
        """Simulate a watchdog trip with cfg's effective knobs."""
        with open(os.path.join(mdir, "stall-rank0.json"), "w") as f:
            json.dump({"kind": "stall", "last_iteration": 3,
                       "knobs": {k: getattr(cfg, k)
                                 for k, _ in DEGRADE_LADDER}}, f)

    params = {"compile_cache_dir": "/tmp/cache"}
    seen = []
    for expect in ("tpu_donate_buffers", "compile_cache_dir",
                   "async_host_io", "device_eval"):
        cfg = Config(dict(params))
        # re-apply prior degradations (as a restarted engine does), then
        # hang and restart once more
        apply_auto_degrade(cfg, params, mdir)
        stall_once(cfg)
        cfg = Config(dict(params))
        out = apply_auto_degrade(cfg, params, mdir)
        assert out["new"] == [expect]
        seen.append(expect)
        assert out["applied"] == seen
        assert not knob_enabled(expect, getattr(cfg, expect))
    # ladder exhausted: a fifth stall degrades nothing further
    stall_once(Config(dict(params)))
    out = apply_auto_degrade(Config(dict(params)), params, mdir)
    assert out["new"] == []
    assert out["applied"] == seen
    # every consumed stall file was archived, none left pending
    assert not os.path.exists(os.path.join(mdir, "stall-rank0.json"))
    assert len([p for p in os.listdir(mdir) if ".handled-" in p]) == 5


# --------------------------------------------------------------------------
# supervisor: live-but-silent ranks via heartbeat mtime
# --------------------------------------------------------------------------

def test_supervise_kills_cluster_on_stale_heartbeat(tmp_path):
    from lightgbm_tpu.reliability.supervisor import supervise
    logs = []
    hbs = []
    for r in range(2):
        lp = tmp_path / f"w{r}.log"
        lp.write_text(f"worker {r} alive\n")
        logs.append(str(lp))
        hb = tmp_path / f"heartbeat-rank{r}"
        hb.write_text("")
        hbs.append(str(hb))
    # rank 1 stalled 60 s ago; rank 0 is current
    old = time.time() - 60.0
    os.utime(hbs[1], (old, old))
    os.utime(hbs[0], None)
    # rank 1's guard wrote its diagnosis before wedging completely
    (tmp_path / "stall-rank1.json").write_text(
        json.dumps({"kind": "stall", "last_iteration": 4,
                    "knobs": {"tpu_donate_buffers": True}}))
    procs = [subprocess.Popen([sys.executable, "-c",
                               "import time; time.sleep(600)"])
             for _ in range(2)]
    t0 = time.monotonic()
    try:
        res = supervise(procs, logs, timeout=120.0, poll_interval=0.1,
                        heartbeats=hbs, stall_timeout=5.0,
                        stall_dir=str(tmp_path))
    finally:
        for p in procs:
            p.kill()
            p.wait()
    elapsed = time.monotonic() - t0
    assert elapsed < 30, f"stale-heartbeat kill took {elapsed:.0f}s"
    assert not res.ok
    assert res.hang, "a live-but-silent rank must classify as hang"
    stalled = [f for f in res.failures if f.kind == "hang"]
    assert [f.rank for f in stalled] == [1]
    msg = res.describe()
    assert "live-but-hung" in msg
    # the stalled rank's diagnosis tail is surfaced in the failure log
    assert "stall-rank1.json" in msg and "last_iteration" in msg


def test_supervise_classifies_stall_exit_code_as_hang(tmp_path):
    from lightgbm_tpu.reliability.supervisor import supervise
    lp = tmp_path / "w0.log"
    lp.write_text("about to stall\n")
    p = subprocess.Popen([sys.executable, "-c",
                          f"import os; os._exit({STALL_EXIT_CODE})"])
    res = supervise([p], [str(lp)], timeout=60.0, poll_interval=0.05)
    assert not res.ok and res.hang
    assert res.failures[0].kind == "hang"
    assert f"exit code {STALL_EXIT_CODE} (hang)" in res.describe()


# --------------------------------------------------------------------------
# SIGTERM flush: a supervisor kill keeps the event log complete
# --------------------------------------------------------------------------

@pytest.mark.skipif(not hasattr(signal, "SIGTERM"), reason="no SIGTERM")
def test_sigterm_flushes_async_event_log(tmp_path):
    code = f"""
import os, signal, sys, time
sys.path.insert(0, {REPO!r})
from lightgbm_tpu.observability import (AsyncWriter, EventLogger,
                                        install_sigterm_flush,
                                        set_event_logger)
w = AsyncWriter()
lg = EventLogger({str(tmp_path)!r}, rank=0, writer=w)
set_event_logger(lg)
assert install_sigterm_flush()
for i in range(200):
    lg.emit("iteration", iteration=i)
os.kill(os.getpid(), signal.SIGTERM)
time.sleep(30)  # never reached: the handler re-raises SIGTERM
"""
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    # died OF SIGTERM (not a normal exit): the handler re-delivers it
    assert res.returncode in (-signal.SIGTERM, 128 + signal.SIGTERM), \
        f"rc={res.returncode}\n{res.stderr}"
    lines = [json.loads(ln) for ln in
             (tmp_path / "events-rank0.jsonl").read_text().splitlines()]
    its = [r["iteration"] for r in lines if r["event"] == "iteration"]
    assert its == list(range(200)), "queued events were dropped on SIGTERM"
    assert lines[-1]["event"] == "sigterm"


def test_register_stack_dump_signal():
    from lightgbm_tpu.reliability import faults
    if not hasattr(signal, "SIGUSR1"):
        pytest.skip("no SIGUSR1 on this platform")
    assert faults.register_stack_dump_signal()


# --------------------------------------------------------------------------
# end-to-end: injected hang -> diagnosis -> degraded resume (acceptance)
# --------------------------------------------------------------------------

_E2E_CHILD = r"""
import os, sys
sys.path.insert(0, os.environ["GUARD_REPO"])
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.model_io import save_model_to_string

d = os.environ["GUARD_DIR"]
rng = np.random.RandomState(5)
X = rng.rand(512, 5)
y = (3 * (X[:, 0] - 0.5) + X[:, 1] * X[:, 2]).astype(np.float64)
params = {"objective": "regression", "num_leaves": 7, "verbosity": -1,
          "min_data_in_leaf": 5}
if os.environ.get("GUARD_MODE") == "clean":
    # the uninterrupted reference run, trained with the knob the ladder
    # will disable already off
    params["tpu_donate_buffers"] = False
else:
    params.update({"metrics_dir": os.path.join(d, "metrics"),
                   "checkpoint_dir": os.path.join(d, "ckpt"),
                   "checkpoint_freq": 1, "auto_degrade": True,
                   "stall_floor_s": 1.0, "stall_factor": 3.0})
b = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6)
txt = save_model_to_string(b._gbdt).split("\nparameters:")[0]
with open(os.path.join(d, os.environ["GUARD_MODEL"]), "w") as f:
    f.write(txt)
print("GUARD_DONE", b.current_iteration(), flush=True)
"""


def _run_child(tmp_path, script, mode, model_name, attempt, fault=""):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "GUARD_REPO": REPO,
                "GUARD_DIR": str(tmp_path), "GUARD_MODE": mode,
                "GUARD_MODEL": model_name,
                "LGBM_TPU_FAULT_ATTEMPT": str(attempt)})
    if fault:
        env["LGBM_TPU_FAULT"] = fault
    else:
        env.pop("LGBM_TPU_FAULT", None)
    t0 = time.monotonic()
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True,
                         timeout=SUBPROC_BUDGET_S)
    assert time.monotonic() - t0 < SUBPROC_BUDGET_S
    return res


def test_injected_hang_diagnosed_then_degraded_resume_byte_identical(
        tmp_path):
    """Acceptance: hang@3 trips the watchdog (distinct exit code +
    parseable diagnosis), and the auto_degrade relaunch completes from
    the checkpoint with exactly one ladder knob disabled, a `degrade`
    event logged, and a model byte-identical to an uninterrupted run
    with that knob off."""
    script = tmp_path / "child.py"
    script.write_text(_E2E_CHILD)
    fault = "hang@3@0"

    # attempt 0: wedges at iteration 3, watchdog diagnoses + exits
    r0 = _run_child(tmp_path, script, "guard", "model_a0.txt", 0, fault)
    assert r0.returncode == STALL_EXIT_CODE, \
        f"rc={r0.returncode}\nstdout:{r0.stdout}\nstderr:{r0.stderr}"
    assert classify_returncode(r0.returncode) == "hang"
    spath = tmp_path / "metrics" / "stall-rank0.json"
    diag = json.load(open(spath))
    assert diag["last_iteration"] == 3
    assert diag["knobs"]["tpu_donate_buffers"] is True
    assert any("File" in line for line in diag["stacks"])
    # the run's last logged event rode into the diagnosis
    assert diag["last_event"] is not None

    # attempt 1: same command; the engine consumes the stall file,
    # disables donation (ladder rung 1) and resumes from the checkpoint
    r1 = _run_child(tmp_path, script, "guard", "model_deg.txt", 1, fault)
    assert r1.returncode == 0, \
        f"rc={r1.returncode}\nstdout:{r1.stdout}\nstderr:{r1.stderr}"
    assert "GUARD_DONE 6" in r1.stdout
    state = json.load(open(tmp_path / "metrics" / "degrade-state.json"))
    assert state["degraded_knobs"] == ["tpu_donate_buffers"]
    assert not spath.exists(), "the stall file must be consumed"
    events = [json.loads(ln) for ln in
              (tmp_path / "metrics" / "events-rank0.jsonl")
              .read_text().splitlines()]
    degrades = [e for e in events if e["event"] == "degrade"]
    assert len(degrades) == 1
    assert degrades[0]["knobs"] == ["tpu_donate_buffers"]

    # byte parity vs an uninterrupted run with the degraded knob set
    r2 = _run_child(tmp_path, script, "clean", "model_clean.txt", 2)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert (tmp_path / "model_deg.txt").read_bytes() == \
        (tmp_path / "model_clean.txt").read_bytes()
