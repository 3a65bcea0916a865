"""Multi-process SPMD training (SURVEY §2.3 #6): N real OS processes,
each with local devices, train the same sharded model via
jax.distributed — the TPU-native analogue of the reference's N CLI
workers over sockets (tests/distributed/_test_distributed.py pattern:
train in every process, assert identical models across ranks)."""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

_WORKER = r"""
import os, sys
pid = int(sys.argv[1])
out_path = sys.argv[2]
port = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=pid)
sys.path.insert(0, "/root/repo")
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.model_io import save_model_to_string

rng = np.random.RandomState(3)
n = 4096
X = rng.rand(n, 6)
logit = 4 * (X[:, 0] - 0.5) + 2 * X[:, 1] * X[:, 2] - X[:, 3]
y = (rng.rand(n) < 1 / (1 + np.exp(-3 * logit))).astype(np.float64)

booster = lgb.train(
    {"objective": "regression", "num_leaves": 15, "verbosity": -1,
     "min_data_in_leaf": 5, "learning_rate": 0.2,
     "tree_learner": "data", "tpu_growth_strategy": "leafwise"},
    lgb.Dataset(X, label=y), num_boost_round=4)
assert booster._gbdt.mesh is not None
assert len(booster._gbdt.mesh.devices.ravel()) == 4  # 2 procs x 2 devs
txt = save_model_to_string(booster._gbdt)
with open(out_path, "w") as f:
    f.write(txt)
print(f"proc {pid} done", flush=True)
"""


def _run_two_workers(tmp_path, worker_src, out_suffix, extra_args=()):
    """Shared 2-process harness: free port, env strip, spawn, reap.
    Returns (out_paths, logs); asserts both workers exited 0."""
    import socket
    script = tmp_path / "worker_h.py"
    script.write_text(worker_src)
    outs = [tmp_path / f"out_{i}.{out_suffix}" for i in range(2)]
    with socket.socket() as sock:          # pick a free port per run
        sock.bind(("localhost", 0))
        port = str(sock.getsockname()[1])
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), str(outs[i]), port,
         *map(str, extra_args)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd="/root/repo") for i in range(2)]
    logs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out = "(timeout)\n" + (out or "")
        logs.append(out)
    assert all(p.returncode == 0 for p in procs), "\n".join(logs)
    return outs, logs


@pytest.mark.skipif(bool(os.environ.get("LIGHTGBM_TPU_SKIP_MULTIPROC")),
                    reason="multiproc disabled")
def test_two_process_training_identical_models(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    outs = [tmp_path / f"model_{i}.txt" for i in range(2)]
    import socket
    with socket.socket() as sock:          # pick a free port per run
        sock.bind(("localhost", 0))
        port = str(sock.getsockname()[1])
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), str(outs[i]), port],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd="/root/repo") for i in range(2)]
    logs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        logs.append(out)
    assert all(p.returncode == 0 for p in procs), "\n".join(logs)

    texts = [o.read_text() for o in outs]
    # every rank must write the IDENTICAL model file
    # (_test_distributed.py's core assertion)
    assert texts[0] == texts[1]

    # and the multi-process model must match single-process training
    # structurally (float payloads to rounded precision)
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.model_io import save_model_to_string
    rng = np.random.RandomState(3)
    n = 4096
    X = rng.rand(n, 6)
    logit = 4 * (X[:, 0] - 0.5) + 2 * X[:, 1] * X[:, 2] - X[:, 3]
    y = (rng.rand(n) < 1 / (1 + np.exp(-3 * logit))).astype(np.float64)
    b1 = lgb.train({"objective": "regression", "num_leaves": 15,
                    "verbosity": -1, "min_data_in_leaf": 5,
                    "learning_rate": 0.2,
                    "tpu_growth_strategy": "leafwise"},
                   lgb.Dataset(X, label=y), num_boost_round=4)
    serial = save_model_to_string(b1._gbdt)

    def structure(txt):
        txt = txt.split("\nparameters:")[0]
        txt = "\n".join(l for l in txt.splitlines()
                        if not l.startswith("tree_sizes="))
        return re.sub(r"-?\d+\.\d+(e[-+]?\d+)?", "F", txt)

    assert structure(texts[0]) == structure(serial)


_CLI_WORKER = r"""
import os, sys
rank = sys.argv[1]
port = sys.argv[2]
ports = sys.argv[3]
model_out = sys.argv[4]
data = sys.argv[5]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["LIGHTGBM_TPU_MACHINE_RANK"] = rank
sys.path.insert(0, "/root/repo")
from lightgbm_tpu.cli import main
rc = main([
    "task=train", "objective=regression", "tree_learner=data",
    f"data={data}",
    "num_trees=3", "num_leaves=15", "verbosity=-1",
    "tpu_growth_strategy=leafwise", "num_machines=2",
    f"machines={ports}", f"local_listen_port={port}",
    f"output_model={model_out}",
])
assert rc == 0
print(f"cli rank {rank} done", flush=True)
"""


@pytest.mark.skipif(bool(os.environ.get("LIGHTGBM_TPU_SKIP_MULTIPROC")),
                    reason="multiproc disabled")
def test_cli_machines_two_workers_identical_models(tmp_path):
    """The CLI's machines=/local_listen_port launch (ref:
    application.cpp:100-115): two worker processes join one
    jax.distributed cluster, train tree_learner=data over the global
    mesh, and write identical model files."""
    import socket
    script = tmp_path / "cli_worker.py"
    script.write_text(_CLI_WORKER)
    # a seeded stand-in for the reference's regression.train (label in
    # column 0, tab-separated): /root/reference is not mounted here
    rng = np.random.RandomState(3)
    Xd = rng.rand(2000, 8)
    yd = 3 * (Xd[:, 0] - 0.5) + Xd[:, 1] * Xd[:, 2] + 0.1 * rng.randn(2000)
    data = tmp_path / "regression.train"
    np.savetxt(data, np.column_stack([yd, Xd]), delimiter="\t", fmt="%.6f")
    with socket.socket() as s1, socket.socket() as s2:
        s1.bind(("localhost", 0))
        s2.bind(("localhost", 0))
        p1, p2 = (str(s1.getsockname()[1]), str(s2.getsockname()[1]))
    machines = f"localhost:{p1},localhost:{p2}"
    outs = [tmp_path / f"cli_model_{i}.txt" for i in range(2)]
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), (p1, p2)[i], machines,
         str(outs[i]), str(data)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd="/root/repo") for i in range(2)]
    logs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        logs.append(out)
    assert all(p.returncode == 0 for p in procs), "\n".join(logs)
    # identical models; only the parameters dump may differ (each worker
    # records its own local_listen_port / output_model)
    texts = [o.read_text().split("parameters:")[0] for o in outs]
    assert texts[0] == texts[1]
    assert "Tree=2" in texts[0]


_EVAL_WORKER = r"""
import json, os, sys
pid = int(sys.argv[1]); out_path = sys.argv[2]; port = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=pid)
sys.path.insert(0, "/root/repo")
import numpy as np
import lightgbm_tpu as lgb

rng = np.random.RandomState(3)
n = 4096
X = rng.rand(n, 6)
y = (rng.rand(n) < 1/(1+np.exp(-4*(X[:, 0]-0.5)))).astype(np.float64)
b = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
               "tree_learner": "data", "metric": "binary_logloss,auc",
               "tpu_growth_strategy": "leafwise", "min_data_in_leaf": 5},
              lgb.Dataset(X, label=y), num_boost_round=4)
res = b._gbdt.eval_train()
with open(out_path, "w") as f:
    json.dump({k: float(v) for k, v in res}, f)
print(f"proc {pid} eval done", flush=True)
"""


@pytest.mark.skipif(bool(os.environ.get("LIGHTGBM_TPU_SKIP_MULTIPROC")),
                    reason="multiproc disabled")
def test_multiprocess_train_eval_identical_and_correct(tmp_path):
    """VERDICT r3 item 7: workers must evaluate during distributed
    training.  Train-set metrics under multi-process SPMD are computed
    as shard-local partials + GSPMD all-reduce: every rank reports the
    IDENTICAL value, and the values match a single-process run of the
    same config (AUC via the global score-bin histogram, 1/16384
    resolution)."""
    import json
    outs, _ = _run_two_workers(tmp_path, _EVAL_WORKER, "json")
    r0 = json.loads(outs[0].read_text())
    r1 = json.loads(outs[1].read_text())
    assert r0 == r1, (r0, r1)

    # single-process reference: identical data/params, host eval path
    import numpy as np
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    n = 4096
    X = rng.rand(n, 6)
    y = (rng.rand(n) < 1 / (1 + np.exp(-4 * (X[:, 0] - 0.5)))
         ).astype(np.float64)
    b = lgb.train({"objective": "binary", "num_leaves": 15,
                   "verbosity": -1, "metric": "binary_logloss,auc",
                   "tpu_growth_strategy": "leafwise",
                   "min_data_in_leaf": 5},
                  lgb.Dataset(X, label=y), num_boost_round=4)
    ref = dict(b._gbdt.eval_train())
    assert abs(ref["binary_logloss"] - r0["binary_logloss"]) < 2e-4
    assert abs(ref["auc"] - r0["auc"]) < 2e-3


@pytest.mark.skipif(bool(os.environ.get("LIGHTGBM_TPU_SKIP_MULTIPROC")),
                    reason="multiproc disabled")
def test_programmatic_cluster_launcher(tmp_path):
    """lightgbm_tpu.distributed.train_distributed — the reference
    dask.py _train equivalent: spawn workers, train tree_learner=data
    over the combined mesh, return the rank-0 Booster.  The distributed
    model must match single-process training on the same data."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.distributed import train_distributed

    rng = np.random.RandomState(3)
    n = 4096
    X = rng.rand(n, 6)
    y = (rng.rand(n) < 1 / (1 + np.exp(-4 * (X[:, 0] - 0.5)))
         ).astype(np.float64)
    params = {"objective": "regression", "num_leaves": 15,
              "verbosity": -1, "min_data_in_leaf": 5,
              "tpu_growth_strategy": "leafwise"}
    b_dist = train_distributed(
        params, X, y, num_boost_round=4, num_machines=2,
        force_cpu=True,
        worker_env={"JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    b_single = lgb.train({**params, "tree_learner": "serial"},
                         lgb.Dataset(X, label=y), num_boost_round=4)
    p_d = b_dist.predict(X[:512])
    p_s = b_single.predict(X[:512])
    np.testing.assert_allclose(p_d, p_s, rtol=2e-4, atol=2e-6)


_MC_EVAL_WORKER = r"""
import json, os, sys
pid = int(sys.argv[1]); out_path = sys.argv[2]; port = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=pid)
sys.path.insert(0, "/root/repo")
import numpy as np
import lightgbm_tpu as lgb

rng = np.random.RandomState(5)
n = 3072
X = rng.rand(n, 5)
y = (X[:, 0] * 3 + X[:, 1]).astype(np.int64) % 3
b = lgb.train({"objective": "multiclass", "num_class": 3, "num_leaves": 7,
               "verbosity": -1, "tree_learner": "data",
               "metric": "multi_logloss,multi_error,auc_mu",
               "tpu_growth_strategy": "leafwise", "min_data_in_leaf": 5},
              lgb.Dataset(X, label=y.astype(np.float64)),
              num_boost_round=3)
res = b._gbdt.eval_train()
with open(out_path, "w") as f:
    json.dump({k: float(v) for k, v in res}, f)
print(f"proc {pid} mc eval done", flush=True)
"""


@pytest.mark.skipif(bool(os.environ.get("LIGHTGBM_TPU_SKIP_MULTIPROC")),
                    reason="multiproc disabled")
def test_multiprocess_multiclass_train_eval(tmp_path):
    """Multiclass train metrics reduce on device under multi-process
    SPMD: identical on every rank, matching the single-process host
    evaluation."""
    import json
    outs, _ = _run_two_workers(tmp_path, _MC_EVAL_WORKER, "json")
    r0 = json.loads(outs[0].read_text())
    r1 = json.loads(outs[1].read_text())
    assert r0 == r1, (r0, r1)
    assert set(r0) == {"multi_logloss", "multi_error", "auc_mu"}

    import numpy as np
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(5)
    n = 3072
    X = rng.rand(n, 5)
    y = (X[:, 0] * 3 + X[:, 1]).astype(np.int64) % 3
    b = lgb.train({"objective": "multiclass", "num_class": 3,
                   "num_leaves": 7, "verbosity": -1,
                   "metric": "multi_logloss,multi_error,auc_mu",
                   "tpu_growth_strategy": "leafwise",
                   "min_data_in_leaf": 5},
                  lgb.Dataset(X, label=y.astype(np.float64)),
                  num_boost_round=3)
    ref = dict(b._gbdt.eval_train())
    assert abs(ref["multi_logloss"] - r0["multi_logloss"]) < 2e-4
    # models differ in leaf-value ulps; allow a few row flips
    assert abs(ref["multi_error"] - r0["multi_error"]) < 5 / 3072
    # auc_mu: binned pairwise AUCs (resolution 1/4096) vs exact host
    assert abs(ref["auc_mu"] - r0["auc_mu"]) < 3e-3


_RANK_EVAL_WORKER = r"""
import json, os, sys
pid = int(sys.argv[1]); out_path = sys.argv[2]; port = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=pid)
sys.path.insert(0, "/root/repo")
import numpy as np
import lightgbm_tpu as lgb

rng = np.random.RandomState(9)
sizes = rng.randint(4, 40, size=64)
n = int(sizes.sum())
X = rng.rand(n, 5)
y = rng.randint(0, 4, n).astype(np.float64)
b = lgb.train({"objective": "lambdarank", "num_leaves": 7, "verbosity": -1,
               "tree_learner": "data", "metric": "ndcg,map",
               "ndcg_eval_at": [1, 5], "min_data_in_leaf": 2,
               "tpu_growth_strategy": "leafwise"},
              lgb.Dataset(X, label=y, group=sizes), num_boost_round=3)
res = b._gbdt.eval_train()
with open(out_path, "w") as f:
    json.dump({k: float(v) for k, v in res}, f)
print(f"proc {pid} rank eval done", flush=True)
"""


@pytest.mark.skipif(bool(os.environ.get("LIGHTGBM_TPU_SKIP_MULTIPROC")),
                    reason="multiproc disabled")
def test_multiprocess_ndcg_train_eval(tmp_path):
    """NDCG train metrics under multi-process SPMD: per-query partials
    from bucketed device sort programs; identical on every rank and
    matching the single-process host evaluation (queries straddle the
    row shards — GSPMD handles the cross-shard gathers)."""
    import json
    outs, _ = _run_two_workers(tmp_path, _RANK_EVAL_WORKER, "json")
    r0 = json.loads(outs[0].read_text())
    r1 = json.loads(outs[1].read_text())
    assert r0 == r1, (r0, r1)
    assert set(r0) == {"ndcg@1", "ndcg@5", "map@1", "map@5"}

    import numpy as np
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(9)
    sizes = rng.randint(4, 40, size=64)
    n = int(sizes.sum())
    X = rng.rand(n, 5)
    y = rng.randint(0, 4, n).astype(np.float64)
    b = lgb.train({"objective": "lambdarank", "num_leaves": 7,
                   "verbosity": -1, "metric": "ndcg,map",
                   "ndcg_eval_at": [1, 5], "min_data_in_leaf": 2,
                   "tpu_growth_strategy": "leafwise"},
                  lgb.Dataset(X, label=y, group=sizes), num_boost_round=3)
    ref = dict(b._gbdt.eval_train())
    # the worker trains tree_learner=data, the reference serially: leaf
    # values differ in ulps, so budget a couple of per-query rank flips
    # (1/64 each at ndcg@1); rank-identity across workers is asserted
    # exactly above
    for k in ("ndcg@1", "ndcg@5", "map@1", "map@5"):
        assert abs(ref[k] - r0[k]) < 2.5 / 64, (k, ref[k], r0[k])


_WORKER_WAVE = r"""
import os, sys
pid = int(sys.argv[1])
out_path = sys.argv[2]
port = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=2, process_id=pid)
sys.path.insert(0, "/root/repo")
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.model_io import save_model_to_string

rng = np.random.RandomState(3)
n = 4096
X = rng.rand(n, 6)
logit = 4 * (X[:, 0] - 0.5) + 2 * X[:, 1] * X[:, 2] - X[:, 3]
y = (rng.rand(n) < 1 / (1 + np.exp(-3 * logit))).astype(np.float64)

booster = lgb.train(
    {"objective": "regression", "num_leaves": 15, "verbosity": -1,
     "min_data_in_leaf": 5, "learning_rate": 0.2,
     "tree_learner": "data", "tpu_growth_strategy": "wave"},
    lgb.Dataset(X, label=y), num_boost_round=4)
g = booster._gbdt
assert g.mesh is not None
assert len(g.mesh.devices.ravel()) == 4  # 2 procs x 2 devs
assert g.growth_strategy == "wave", g.growth_strategy
txt = save_model_to_string(g)
with open(out_path, "w") as f:
    f.write(txt)
print(f"proc {pid} done", flush=True)
"""


@pytest.mark.skipif(bool(os.environ.get("LIGHTGBM_TPU_SKIP_MULTIPROC")),
                    reason="multiproc disabled")
def test_two_process_wave_training_identical_models(tmp_path):
    """The DEFAULT (wave) engine under 2-process SPMD (2 procs x 2 CPU
    devices): the shard_map'd histogram psum spans both processes' devices
    and every rank writes the identical model — the wave-engine form of
    the reference's distributed-identity assertion
    (_test_distributed.py:168-184)."""
    outs, _ = _run_two_workers(tmp_path, _WORKER_WAVE, "txt")
    texts = [o.read_text() for o in outs]
    assert texts[0] == texts[1]
    # structural sanity vs a single-process wave run of the same problem
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    n = 4096
    X = rng.rand(n, 6)
    logit = 4 * (X[:, 0] - 0.5) + 2 * X[:, 1] * X[:, 2] - X[:, 3]
    y = (rng.rand(n) < 1 / (1 + np.exp(-3 * logit))).astype(np.float64)
    b1 = lgb.train({"objective": "regression", "num_leaves": 15,
                    "verbosity": -1, "min_data_in_leaf": 5,
                    "learning_rate": 0.2, "tpu_growth_strategy": "wave"},
                   lgb.Dataset(X, label=y), num_boost_round=4)
    b1._gbdt._drain_pending(keep_depth=0)
    got_feats = re.findall(r"split_feature=([\d ]*)", texts[0])
    got_leaves = re.findall(r"num_leaves=(\d+)", texts[0])
    want_feats = [" ".join(str(f) for f in
                           t.split_feature[:t.num_leaves - 1])
                  for t in b1._gbdt.models_]
    want_leaves = [str(t.num_leaves) for t in b1._gbdt.models_]
    assert got_feats == want_feats
    assert got_leaves == want_leaves
