"""CPU-vs-TPU training consistency gate (ref: tests/python_package_test/
test_dual.py — the reference compares CPU and CUDA learners the same way,
env-gated).

Set LIGHTGBM_TEST_DUAL_CPU_TPU=1 on a host with a real TPU attached.
Each backend trains in a subprocess (the backend choice is fixed at jax
init), and predictions must agree closely: the TPU engine (wave growth +
fused Pallas histograms, bf16 one-hot accumulation) against the CPU
engine (leaf-wise + XLA scatter histograms, fp32)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("LIGHTGBM_TEST_DUAL_CPU_TPU") != "1",
    reason="dual CPU/TPU gate disabled (set LIGHTGBM_TEST_DUAL_CPU_TPU=1)")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import json, sys, os
sys.path.insert(0, os.environ["LGBT_REPO"])
import jax
platform, out_path = sys.argv[1], sys.argv[2]
import numpy as np
import lightgbm_tpu as lgb
rng = np.random.RandomState(7)
n, F = 20000, 12
X = rng.rand(n, F)
logit = 3*(X[:,0]-0.5) + 2*X[:,1]*X[:,2] - X[:,3]
y = (rng.rand(n) < 1/(1+np.exp(-3*logit))).astype(np.float32)
b = lgb.train({"objective": "binary", "num_leaves": 63, "verbose": -1,
               "min_data_in_leaf": 20, "learning_rate": 0.1},
              lgb.Dataset(X, label=y), num_boost_round=10)
p = b.predict(X[:4000])
json.dump({"platform": platform, "backend": jax.default_backend(),
           "pred": p.tolist()}, open(out_path, "w"))
"""


def _run(platform: str, tmp_path):
    out = tmp_path / f"pred_{platform}.json"
    script = tmp_path / "dual.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ)
    env["LGBT_REPO"] = _REPO
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    else:
        env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([sys.executable, str(script), platform, str(out)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.load(open(out))
    # the comparison is vacuous unless each run REALLY used its backend
    assert payload["backend"] == platform, payload["backend"]
    return np.asarray(payload["pred"])


def test_cpu_tpu_training_consistency(tmp_path):
    p_cpu = _run("cpu", tmp_path)
    p_tpu = _run("tpu", tmp_path)
    # engines differ (wave vs leaf-wise, bf16 vs fp32 accumulation), so
    # assert close agreement rather than bit equality — the reference's
    # dual gate likewise compares predictions within tolerance
    corr = float(np.corrcoef(p_cpu, p_tpu)[0, 1])
    assert corr > 0.995, corr
    assert float(np.mean(np.abs(p_cpu - p_tpu))) < 0.02
