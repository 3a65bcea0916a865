"""Compiled-cost roofline accounting (observability/costmodel.py): the
harvest path against real jitted programs, signature keying shared with
the RecompileDetector, roofline classification math, and the
per-iteration delta plumbing record_metrics uses."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from lightgbm_tpu.observability.costmodel import (CostModel, backend_peaks,
                                                  global_cost_model,
                                                  group_of, roofline)
from lightgbm_tpu.observability.watchdog import RecompileDetector


@pytest.fixture()
def cost_model_off():
    """Every test leaves the process-wide model exactly as it found it."""
    prev = global_cost_model.enabled
    global_cost_model.enabled = False
    yield
    global_cost_model.enabled = prev


def test_group_of_folds_bucket_entries():
    assert group_of("device_predict[convert@4096]") == "device_predict"
    assert group_of("grow_tree") == "grow_tree"


def test_roofline_classification_and_mfu(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_PEAK_FLOPS", "100.0")
    monkeypatch.setenv("LGBM_TPU_PEAK_BYTES_PER_S", "10.0")
    # ridge = 10 flops/byte; below it -> hbm-bound, above -> compute
    v5e = "TPU v5 lite"
    lo = roofline(flops=50.0, bytes_accessed=10.0, seconds=1.0,
                  device_kind=v5e)
    assert lo["bound"] == "hbm" and lo["arithmetic_intensity"] == 5.0
    assert lo["mfu"] == 0.5 and lo["bw_util"] == 1.0
    hi = roofline(flops=500.0, bytes_accessed=10.0, seconds=2.0,
                  device_kind=v5e)
    assert hi["bound"] == "compute"
    assert hi["mfu"] == 2.5  # 500/2/100 — over "peak" only because the
    # peaks are synthetic; the math is what's pinned
    z = roofline(flops=0.0, bytes_accessed=0.0, seconds=0.0,
                 device_kind=v5e)
    assert z["bound"] == "unknown" and z["mfu"] is None
    # off the TPU (the default device here) the classification still
    # runs, but no share of a nominal CPU peak is reported
    cpu = roofline(flops=50.0, bytes_accessed=10.0, seconds=1.0)
    assert cpu["bound"] == "hbm" and cpu["mfu"] is None
    assert "bw_util" not in cpu


def test_backend_peaks_env_override(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_PEAK_FLOPS", "123.0")
    monkeypatch.setenv("LGBM_TPU_PEAK_BYTES_PER_S", "7.0")
    assert backend_peaks("TPU v5 lite") == (123.0, 7.0)
    # the overrides stand in for a part the table does not list
    assert backend_peaks("TPU v99") == (123.0, 7.0)
    monkeypatch.setenv("LGBM_TPU_PEAK_FLOPS", "nonsense")
    flops, _bw = backend_peaks("TPU v5 lite")
    assert flops == 197e12  # malformed override ignored, table wins


def test_unknown_tpu_kind_raises_instead_of_borrowing_a_row(monkeypatch):
    monkeypatch.delenv("LGBM_TPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("LGBM_TPU_PEAK_BYTES_PER_S", raising=False)
    assert backend_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(KeyError, match="TPU v99"):
        backend_peaks("TPU v99")
    with pytest.raises(KeyError, match="TPU v99"):
        roofline(1.0, 1.0, 1.0, device_kind="TPU v99")


def test_harvest_real_jit_and_accumulate(cost_model_off):
    cm = CostModel()
    cm.enabled = True
    fn = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((64, 32), jnp.float32)
    y = jnp.ones((32, 16), jnp.float32)
    sig = (("f32[64,32]", "f32[32,16]"), ())
    cm.observe("matmul", sig, fn, (x, y), {})
    cm.observe("matmul", sig, fn, (x, y), {})
    snap = cm.snapshot()
    assert snap["matmul"]["calls"] == 2
    assert snap["matmul"]["unharvested"] == 0
    # one matmul = 2*M*N*K flops; two calls accumulated
    assert snap["matmul"]["flops"] == pytest.approx(2 * 2 * 64 * 32 * 16)
    assert snap["matmul"]["bytes"] > 0
    assert cm.per_call("matmul") is not None
    assert cm.signatures_harvested() == 1


def test_unharvestable_entry_counts_calls(cost_model_off):
    cm = CostModel()
    cm.enabled = True
    cm.observe("plain", ("sig",), lambda x: x, (1,), {})  # no .lower
    snap = cm.snapshot()
    assert snap["plain"]["calls"] == 1
    assert snap["plain"]["unharvested"] == 1
    assert cm.per_call("plain") is None


def test_recompile_detector_reports_when_enabled(cost_model_off):
    global_cost_model.reset()
    fn = RecompileDetector(jax.jit(lambda v: v * 2.0), "doubler")
    x = jnp.ones((8,), jnp.float32)
    fn(x)  # cost model off: nothing recorded
    assert "doubler" not in global_cost_model.snapshot()
    global_cost_model.enabled = True
    fn(x)
    fn(x)
    snap = global_cost_model.snapshot()
    assert snap["doubler"]["calls"] == 2
    global_cost_model.enabled = False
    global_cost_model.reset()


def test_phase_roofline_diffs_windows(monkeypatch, cost_model_off):
    monkeypatch.setenv("LGBM_TPU_PEAK_FLOPS", "1000.0")
    monkeypatch.setenv("LGBM_TPU_PEAK_BYTES_PER_S", "100.0")
    cm = CostModel()
    prev = {"grow_tree": {"flops": 100.0, "bytes": 10.0, "calls": 1}}
    cur = {"grow_tree": {"flops": 300.0, "bytes": 30.0, "calls": 3},
           "gradients": {"flops": 50.0, "bytes": 500.0, "calls": 1},
           "idle": {"flops": 9.0, "bytes": 9.0, "calls": 3}}
    prev["idle"] = dict(cur["idle"])  # no calls this window -> omitted
    phases = {"GBDT::grow_tree": 2.0, "GBDT::grow_tree::device": 1.0,
              "GBDT::gradients": 0.5}
    out = cm.phase_roofline(prev, cur, phases, device_kind="TPU v5 lite")
    assert set(out) == {"grow_tree", "gradients"}
    g = out["grow_tree"]
    # delta flops=200 over the ::device split (1.0 s), not the host scope
    assert g["calls"] == 2 and g["device_s"] == 1.0
    assert g["mfu"] == pytest.approx(200.0 / 1.0 / 1000.0)
    assert g["bound"] == "compute"  # ai=200/20=10 >= ridge 10
    gr = out["gradients"]
    # no ::device entry -> host-scope fallback
    assert gr["device_s"] == 0.5 and gr["bound"] == "hbm"


def test_training_iteration_events_carry_roofline(tmp_path):
    """End to end: a metrics run's iteration events include per-phase
    measured MFU for the grow and gradient programs."""
    import json

    rng = np.random.RandomState(3)
    X = rng.rand(300, 4)
    y = (X[:, 0] + X[:, 1] * X[:, 2]).astype(np.float64)
    d = str(tmp_path / "metrics")
    import lightgbm_tpu as lgb
    lgb.train({"objective": "regression", "num_leaves": 7,
               "verbosity": -1, "min_data_in_leaf": 5, "metrics_dir": d},
              lgb.Dataset(X, label=y), num_boost_round=3)
    evts = [json.loads(line)
            for line in open(tmp_path / "metrics" / "events-rank0.jsonl")]
    iters = [e for e in evts if e["event"] == "iteration"]
    assert len(iters) == 3
    rl = iters[-1].get("roofline")
    assert rl and "grow_tree" in rl and "gradients" in rl
    for entry in rl.values():
        assert entry["bound"] in ("compute", "hbm", "unknown")
        assert entry["flops"] >= 0 and entry["calls"] >= 1
    # the run restores the process-wide switch on exit
    assert global_cost_model.enabled is False


def test_roofline_param_off_omits_field(tmp_path):
    import json

    rng = np.random.RandomState(4)
    X = rng.rand(200, 4)
    y = X[:, 0].astype(np.float64)
    d = str(tmp_path / "metrics")
    import lightgbm_tpu as lgb
    lgb.train({"objective": "regression", "num_leaves": 7,
               "verbosity": -1, "min_data_in_leaf": 5, "metrics_dir": d,
               "roofline": False},
              lgb.Dataset(X, label=y), num_boost_round=2)
    evts = [json.loads(line)
            for line in open(tmp_path / "metrics" / "events-rank0.jsonl")]
    iters = [e for e in evts if e["event"] == "iteration"]
    assert iters and all("roofline" not in e for e in iters)
