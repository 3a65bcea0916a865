"""8-device smoke test over the PR-5 host-boundary knob matrix.

An 8-device dry run wedged until the wall-clock cap (rc=124) after PR 5
landed buffer donation, async host I/O and the compile cache; the same
check had passed before PR 5.  This file localizes that interaction and guards it
from silently regressing: a short sharded-wave training (the exact
engine configuration the dry run compiles) runs across the knob
matrix on the virtual 8-device CPU mesh the conftest provides.

Invariants pinned:

* every combination TRAINS (a hang here is that signature — the
  per-run wall-clock guard turns it into a named failure instead of a
  silent tier-1 cap eat);
* the model is IDENTICAL across knob combinations — donation, async
  I/O and the compile cache are performance knobs and must never
  change results;
* no "Some donated buffers were not usable" warnings: grow-buffer
  donation is gated off under a device mesh (boosting/gbdt.py), since
  the row-sharded f32 grad/hess slices cannot alias any grow output —
  the donation x SPMD interaction implicated in that hang;
* the compile cache composes with the 8-device mesh in a fresh
  process (subprocess-isolated: a cache-write crash or hang must not
  take the test process down with it).
"""

import os
import subprocess
import sys
import time
import warnings

import numpy as np

import lightgbm_tpu as lgb

# a genuine hang blows past this by an order of magnitude;
# normal runs (incl. the one-time sharded compile) finish well inside it
RUN_BUDGET_S = 300.0


def _problem(n=1024, F=5, seed=9):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, F)
    y = (3 * (X[:, 0] - 0.5) + X[:, 1] * X[:, 2]
         + 0.1 * rng.randn(n)).astype(np.float64)
    return X, y


def _params(donate, async_io, cache_dir=""):
    return {
        "objective": "regression", "num_leaves": 7, "verbosity": -1,
        "min_data_in_leaf": 5, "learning_rate": 0.2,
        "tree_learner": "data", "tpu_growth_strategy": "wave",
        "tpu_donate_buffers": donate, "async_host_io": async_io,
        "compile_cache_dir": cache_dir,
    }


def _train(donate, async_io, cache_dir="", rounds=4):
    X, y = _problem()
    t0 = time.monotonic()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        booster = lgb.train(_params(donate, async_io, cache_dir),
                            lgb.Dataset(X, label=y),
                            num_boost_round=rounds)
    elapsed = time.monotonic() - t0
    donate_warns = [w for w in caught
                    if "donated buffers were not usable"
                    in str(w.message)]
    return booster, elapsed, donate_warns


def _model_text(booster):
    from lightgbm_tpu.boosting.model_io import save_model_to_string
    txt = save_model_to_string(booster._gbdt)
    return txt.split("\nparameters:")[0]


def test_knob_matrix_trains_identically():
    """donation x async_host_io: every combination completes inside the
    budget, produces the same model, and emits no unusable-donation
    warnings (the mesh gate in boosting/gbdt.py)."""
    X, _ = _problem()
    results = {}
    for donate in (True, False):
        for async_io in (True, False):
            booster, elapsed, donate_warns = _train(donate, async_io)
            assert elapsed < RUN_BUDGET_S, (
                f"donate={donate} async={async_io} took {elapsed:.0f}s — "
                "the wedged-in-a-collective hang signature")
            assert not donate_warns, (
                f"donate={donate} async={async_io}: grow-buffer donation "
                "leaked through the mesh gate: "
                f"{[str(w.message) for w in donate_warns]}")
            g = booster._gbdt
            assert g.mesh is not None and g.mesh.devices.size == 8, \
                "the 8-device mesh was not engaged"
            assert g.growth_strategy == "wave"
            pred = booster.predict(X)
            assert np.isfinite(pred).all()
            results[(donate, async_io)] = _model_text(booster)
    texts = set(results.values())
    assert len(texts) == 1, (
        "knob matrix changed the model: "
        f"{sorted(k for k in results if results[k] != results[(False, False)])}")


def test_donation_gated_off_under_mesh():
    """The gate itself: tpu_donate_buffers=True under the mesh must warn
    and fall back to the non-donating grow entry."""
    from lightgbm_tpu.utils import log

    class _Capture:
        def __init__(self):
            self.lines = []

        def info(self, msg):
            self.lines.append(msg)

        warning = info

    cap = _Capture()
    log.register_logger(cap)
    try:
        X, y = _problem()
        params = _params(True, False)
        params["verbosity"] = 0  # warnings on
        booster = lgb.train(params, lgb.Dataset(X, label=y),
                            num_boost_round=2)
    finally:
        log.register_logger(None)
    assert any("donation is disabled under a device mesh" in line
               for line in cap.lines), \
        f"expected the mesh donation gate to warn; got {cap.lines!r}"
    assert booster.current_iteration() == 2


_STALL_CHILD = r"""
import os, sys
sys.path.insert(0, os.environ["STALL_REPO"])
import numpy as np
import lightgbm_tpu as lgb
from tests.test_multichip_smoke import _problem, _params
d = os.environ["STALL_DIR"]
X, y = _problem()
p = _params(True, True)
p.update({"metrics_dir": os.path.join(d, "metrics"),
          "checkpoint_dir": os.path.join(d, "ckpt"), "checkpoint_freq": 1,
          "auto_degrade": True, "stall_floor_s": 2.0, "stall_factor": 3.0})
b = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=5)
assert np.isfinite(b.predict(X[:64])).all()
print("STALL_SMOKE_OK", b.current_iteration(), flush=True)
"""


def test_stall_injection_diagnosed_and_degraded_under_mesh(tmp_path):
    """ISSUE 7 acceptance on the 8-device mesh: an injected hang during
    sharded-wave training produces a stall-rank0.json (stack + knob
    fingerprint with the mesh engaged), the exit is classified as a
    HANG (not a crash) by the supervisor, and the auto_degrade relaunch
    completes from checkpoint with exactly one ladder knob disabled."""
    import json

    from lightgbm_tpu.reliability.guard import STALL_EXIT_CODE
    from lightgbm_tpu.reliability.supervisor import classify_returncode

    script = tmp_path / "stall_child.py"
    script.write_text(_STALL_CHILD)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    env.update({"STALL_DIR": str(tmp_path), "STALL_REPO": repo,
                "LGBM_TPU_FAULT": "hang@2@0",
                "LGBM_TPU_FAULT_ATTEMPT": "0"})

    # attempt 0: wedges at iteration 2 mid-mesh-training
    r0 = subprocess.run([sys.executable, str(script)], cwd=repo, env=env,
                        capture_output=True, text=True,
                        timeout=RUN_BUDGET_S)
    assert r0.returncode == STALL_EXIT_CODE, (
        f"expected the stall exit code, got rc={r0.returncode}\n"
        f"stdout: {r0.stdout[-2000:]}\nstderr: {r0.stderr[-2000:]}")
    assert classify_returncode(r0.returncode) == "hang"
    diag = json.load(open(tmp_path / "metrics" / "stall-rank0.json"))
    assert diag["last_iteration"] == 2
    assert diag["knobs"]["sharded_wave"] is True
    assert any("File" in line for line in diag["stacks"])

    # attempt 1: the engine consumes the diagnosis, disables the first
    # ladder knob and resumes from the iteration-2 checkpoint
    env["LGBM_TPU_FAULT_ATTEMPT"] = "1"
    r1 = subprocess.run([sys.executable, str(script)], cwd=repo, env=env,
                        capture_output=True, text=True,
                        timeout=RUN_BUDGET_S)
    assert r1.returncode == 0, (
        f"degraded relaunch failed rc={r1.returncode}\n"
        f"stdout: {r1.stdout[-2000:]}\nstderr: {r1.stderr[-2000:]}")
    assert "STALL_SMOKE_OK 5" in r1.stdout
    state = json.load(open(tmp_path / "metrics" / "degrade-state.json"))
    assert state["degraded_knobs"] == ["tpu_donate_buffers"]
    events = [json.loads(ln) for ln in
              (tmp_path / "metrics" / "events-rank0.jsonl")
              .read_text().splitlines()]
    assert any(e["event"] == "degrade"
               and e["knobs"] == ["tpu_donate_buffers"] for e in events)


def test_compile_cache_under_mesh_subprocess(tmp_path):
    """compile_cache_dir x 8-device mesh in a FRESH process (the dry run that
    hung was also a fresh process): must train and exit 0 inside the
    budget.  Subprocess isolation keeps a cache-layer crash or hang from
    killing the whole test session."""
    cache = tmp_path / "xla-cache"
    code = (
        "import numpy as np, lightgbm_tpu as lgb\n"
        "from tests.test_multichip_smoke import _problem, _params\n"
        "X, y = _problem()\n"
        f"p = _params(True, True, cache_dir={str(cache)!r})\n"
        "b = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=3)\n"
        "assert np.isfinite(b.predict(X)).all()\n"
        "print('SMOKE_OK', b.current_iteration())\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # lift the suite's cache-off switch (conftest.py) for this child
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                         capture_output=True, text=True,
                         timeout=RUN_BUDGET_S)
    assert res.returncode == 0, (
        f"compile-cache x mesh run failed rc={res.returncode}\n"
        f"stdout: {res.stdout[-2000:]}\nstderr: {res.stderr[-2000:]}")
    assert "SMOKE_OK 3" in res.stdout
