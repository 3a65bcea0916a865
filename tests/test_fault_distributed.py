"""Worker supervision and retry in the multi-process launcher (ISSUE 1
tentpole pillar 2): a dead rank must fail the run in seconds — with the
failing rank's log tail in the error — instead of stalling every rank to
the 900 s deadline, and with retries enabled the cluster relaunches and
resumes from the last checkpoint."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from lightgbm_tpu.utils.log import LightGBMError

_WENV = {"JAX_PLATFORMS": "cpu",
         "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}


def _make_data():
    rng = np.random.RandomState(3)
    n = 1024
    X = rng.rand(n, 5)
    y = (rng.rand(n) < 1 / (1 + np.exp(-4 * (X[:, 0] - 0.5)))
         ).astype(np.float64)
    return X, y


_PARAMS = {"objective": "regression", "num_leaves": 7, "verbosity": -1,
           "min_data_in_leaf": 5, "tpu_growth_strategy": "leafwise"}


_MP_PROBE = r"""
import os, sys
pid = int(sys.argv[1]); port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=pid)
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec
mesh = Mesh(np.array(jax.devices()).reshape(-1), ("x",))
a = jax.device_put(np.arange(8.0), NamedSharding(mesh, PartitionSpec("x")))
print("probe ok", flush=True)
"""


def _multiprocess_spmd_available(tmp_path_factory) -> bool:
    """Some jaxlib builds cannot run multi-process collectives on the CPU
    backend at all (every seed test in test_multiprocess.py fails there
    too).  Probe once; retry/resume needs a working cluster."""
    import socket
    d = tmp_path_factory.mktemp("mp_probe")
    script = d / "probe.py"
    script.write_text(_MP_PROBE)
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = str(s.getsockname()[1])
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen([sys.executable, str(script), str(i), port],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True, env=env)
             for i in range(2)]
    ok = True
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()
            ok = False
            continue
        ok = ok and p.returncode == 0 and "probe ok" in out
    return ok


@pytest.fixture(scope="session")
def mp_spmd_ok(tmp_path_factory):
    return _multiprocess_spmd_available(tmp_path_factory)


@pytest.mark.skipif(bool(os.environ.get("LIGHTGBM_TPU_SKIP_MULTIPROC")),
                    reason="multiproc disabled")
def test_worker_crash_fast_fail_with_log_tail(tmp_path):
    """Satellite: an injected rank crash must surface within seconds —
    not the old serial rank-ordered wait that left every other rank
    blocked in collectives until the global deadline — and the error
    must carry the failing rank's log tail.  This holds whether the
    rank dies from the injected fault or (on jaxlib builds without
    CPU multi-process collectives) from backend init itself."""
    from lightgbm_tpu.distributed import train_distributed
    X, y = _make_data()
    wenv = dict(_WENV, LGBM_TPU_FAULT="worker_crash@1",
                LGBM_TPU_FAULT_RANK="1")
    t0 = time.monotonic()
    with pytest.raises(LightGBMError) as ei:
        train_distributed(_PARAMS, X, y, num_boost_round=4, num_machines=2,
                          force_cpu=True, worker_env=wenv, timeout=600)
    elapsed = time.monotonic() - t0
    # the supervision poll loop kills the cluster on the first failure;
    # "seconds" here budgets jax import + compile, not the 600 s deadline
    assert elapsed < 300, f"fast-fail took {elapsed:.0f}s"
    msg = str(ei.value)
    assert "rank" in msg
    assert "log tail" in msg


@pytest.mark.skipif(bool(os.environ.get("LIGHTGBM_TPU_SKIP_MULTIPROC")),
                    reason="multiproc disabled")
def test_worker_crash_retry_resumes_from_checkpoint(tmp_path, mp_spmd_ok):
    """Acceptance: rank 0 crashes at iteration 2 on the first attempt;
    with max_retries=1 the cluster relaunches (fault gated to attempt 0)
    and resumes from the auto checkpoint, matching single-process
    training."""
    if not mp_spmd_ok:
        pytest.skip("this jaxlib cannot run multi-process SPMD on CPU "
                    "(seed-known limitation; test_multiprocess.py fails "
                    "identically)")
    import lightgbm_tpu as lgb
    from lightgbm_tpu.distributed import train_distributed
    X, y = _make_data()
    wenv = dict(_WENV, LGBM_TPU_FAULT="worker_crash@2",
                LGBM_TPU_FAULT_RANK="0")
    b = train_distributed(_PARAMS, X, y, num_boost_round=4, num_machines=2,
                          force_cpu=True, worker_env=wenv, timeout=600,
                          max_retries=1, retry_backoff=0.1)
    b_single = lgb.train({**_PARAMS, "tree_learner": "serial"},
                         lgb.Dataset(X, label=y), num_boost_round=4)
    np.testing.assert_allclose(b.predict(X[:256]), b_single.predict(X[:256]),
                               rtol=2e-4, atol=2e-6)


def test_join_cluster_unreachable_coordinator_diagnostics(tmp_path):
    """join_cluster must fail within its initialize timeout with an
    error naming the coordinator, not hang for jax's 300 s default or
    dump a bare gRPC traceback.  Run in a subprocess: jax.distributed
    state is process-global."""
    script = tmp_path / "join.py"
    script.write_text(r"""
import sys
sys.path.insert(0, %r)
from lightgbm_tpu.distributed import join_cluster
from lightgbm_tpu.utils.log import LightGBMError
try:
    join_cluster(["localhost:1", "localhost:2"], rank=1,
                 initialize_timeout=3)
    print("JOINED (unexpected)")
except LightGBMError as e:
    msg = str(e)
    assert "localhost:1" in msg and "coordinator" in msg, msg
    print("DIAG OK", flush=True)
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=180)
    elapsed = time.monotonic() - t0
    assert "DIAG OK" in r.stdout, r.stdout + r.stderr
    assert elapsed < 120, f"diagnostic took {elapsed:.0f}s"
