"""Reliability subsystem (ISSUE 1): checkpoint/resume parity, atomic
model writes, fault injection, and the non-finite sentinel.  All
tier-1-safe: single process, JAX_PLATFORMS=cpu (conftest)."""

import json
import os
import shutil

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.reliability import CheckpointManager, NonFiniteError, faults
from lightgbm_tpu.reliability.checkpoint import hash_params
from lightgbm_tpu.utils.log import LightGBMError


def _data(seed=7, n=800, F=5):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, F)
    y = X[:, 0] * 2 + X[:, 1] ** 2 + 0.1 * rng.randn(n)
    return X, y


PARAMS = {"objective": "regression", "num_leaves": 7, "verbosity": -1,
          "min_data_in_leaf": 5}


@pytest.fixture(autouse=True)
def _clean_fault_env(monkeypatch):
    """Each test starts with no injected faults and leaves none behind."""
    monkeypatch.delenv("LGBM_TPU_FAULT", raising=False)
    faults.reload()
    yield
    faults.reload()


# --------------------------------------------------- checkpoint/resume
def test_checkpoint_resume_byte_parity(tmp_path):
    """The acceptance criterion: interrupt at iteration k, resume, and
    the final model text is byte-for-byte identical to an uninterrupted
    run (exact score-buffer restore, not predict-based reseeding)."""
    X, y = _data()
    full = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                     num_boost_round=10)
    full_txt = full.model_to_string(num_iteration=-1)

    ck = str(tmp_path / "ck")
    # "interrupted" run: stops after 6 of the 10 rounds
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=6,
              checkpoint_dir=ck, checkpoint_freq=3)
    resumed = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                        num_boost_round=10, checkpoint_dir=ck,
                        checkpoint_freq=3)
    assert resumed.model_to_string(num_iteration=-1) == full_txt


def test_checkpoint_resume_byte_parity_with_bagging(tmp_path):
    """Bagging draws must continue the interrupted run's RNG stream
    (checkpointed), not replay from the seed."""
    X, y = _data()
    p = dict(PARAMS, bagging_freq=2, bagging_fraction=0.7)
    full = lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=10)
    ck = str(tmp_path / "ck")
    lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=5,
              checkpoint_dir=ck, checkpoint_freq=5)
    resumed = lgb.train(dict(p), lgb.Dataset(X, label=y),
                        num_boost_round=10, checkpoint_dir=ck,
                        checkpoint_freq=5)
    assert resumed.model_to_string(num_iteration=-1) \
        == full.model_to_string(num_iteration=-1)


def test_checkpoint_rotation_and_manifest(tmp_path):
    X, y = _data()
    ck = str(tmp_path / "ck")
    lgb.train(dict(PARAMS, checkpoint_keep=2), lgb.Dataset(X, label=y),
              num_boost_round=9, checkpoint_dir=ck, checkpoint_freq=2)
    models = sorted(f for f in os.listdir(ck) if f.endswith(".txt"))
    # saves at 2,4,6,8 and the final iteration 9; keep_last=2 -> 8, 9
    assert models == ["ckpt_0000008.txt", "ckpt_0000009.txt"]
    with open(os.path.join(ck, "manifest.json")) as f:
        m = json.load(f)
    assert m["iteration"] == 9
    mgr = CheckpointManager(ck)
    ckpt = mgr.latest()
    assert ckpt.iteration == 9
    assert os.path.exists(ckpt.model_path)
    assert ckpt.load_state() is not None


def test_resume_ignores_mismatched_params(tmp_path):
    """A checkpoint from a different config must not be resumed into
    this run (params-hash gate)."""
    X, y = _data()
    ck = str(tmp_path / "ck")
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=3,
              checkpoint_dir=ck, checkpoint_freq=1)
    b = lgb.train(dict(PARAMS, num_leaves=15), lgb.Dataset(X, label=y),
                  num_boost_round=3, checkpoint_dir=ck, checkpoint_freq=1)
    assert b.num_trees() == 3  # trained from scratch, not 3 + 3
    # volatile knobs (verbosity, output paths) must NOT change the hash
    assert hash_params(dict(PARAMS)) == \
        hash_params(dict(PARAMS, verbosity=2, output_model="x.txt"))
    assert hash_params(dict(PARAMS)) != hash_params(dict(PARAMS, num_leaves=15))


def test_resume_false_starts_over(tmp_path):
    X, y = _data()
    ck = str(tmp_path / "ck")
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=4,
              checkpoint_dir=ck, checkpoint_freq=2)
    b = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=3,
                  checkpoint_dir=ck, checkpoint_freq=2, resume=False)
    assert b.num_trees() == 3


def test_resume_past_target_returns_checkpoint_model(tmp_path):
    """Resuming with num_boost_round <= checkpoint iteration trains no
    further trees."""
    X, y = _data()
    ck = str(tmp_path / "ck")
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=6,
              checkpoint_dir=ck, checkpoint_freq=2)
    b = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=4,
                  checkpoint_dir=ck, checkpoint_freq=2)
    assert b.num_trees() == 6


# ----------------------------------------------------- atomic writes
def test_save_model_atomic_on_replace_failure(tmp_path, monkeypatch):
    """A failed save must leave the previous model file intact and no
    temp litter (temp sibling + os.replace)."""
    X, y = _data(n=300)
    b = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=3)
    path = str(tmp_path / "model.txt")
    b.save_model(path)
    original = open(path).read()

    def _boom(src, dst):
        raise OSError("simulated crash at publish")
    monkeypatch.setattr(os, "replace", _boom)
    with pytest.raises(OSError):
        b.save_model(path, num_iteration=1)
    monkeypatch.undo()
    assert open(path).read() == original
    assert [f for f in os.listdir(tmp_path) if f.endswith(".tmp")] == []


def test_cvbooster_save_model_atomic(tmp_path, monkeypatch):
    X, y = _data(n=400)
    res = lgb.cv(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=2,
                 nfold=2, return_cvbooster=True)
    cvb = res["cvbooster"]
    path = str(tmp_path / "cv.json")
    cvb.save_model(path)
    original = open(path).read()

    def _boom(src, dst):
        raise OSError("simulated crash at publish")
    monkeypatch.setattr(os, "replace", _boom)
    with pytest.raises(OSError):
        cvb.save_model(path, num_iteration=1)
    monkeypatch.undo()
    assert open(path).read() == original


def test_ckpt_write_fail_injection_keeps_training_and_old_ckpt(
        tmp_path, monkeypatch):
    """An injected checkpoint-write failure warns and training continues;
    the previous checkpoint stays the resumable one until the next good
    write."""
    X, y = _data()
    monkeypatch.setenv("LGBM_TPU_FAULT", "ckpt_write_fail@2")
    faults.reload()
    ck = str(tmp_path / "ck")
    b = lgb.train(dict(PARAMS, verbosity=-1), lgb.Dataset(X, label=y),
                  num_boost_round=4, checkpoint_dir=ck, checkpoint_freq=1)
    assert b.num_trees() == 4  # the failed write did not kill the run
    assert CheckpointManager(ck).latest().iteration == 4
    # iteration 2's checkpoint is the one that failed
    assert not os.path.exists(os.path.join(ck, "ckpt_0000002.txt"))


# ------------------------------------------------ non-finite sentinel
def test_nan_grad_sentinel_raises_actionable_error(monkeypatch):
    X, y = _data()
    monkeypatch.setenv("LGBM_TPU_FAULT", "nan_grad@2")
    faults.reload()
    with pytest.raises(LightGBMError, match="[Nn]on-finite"):
        lgb.train(dict(PARAMS, nonfinite_check_freq=1),
                  lgb.Dataset(X, label=y), num_boost_round=5)


def test_nan_grad_rolls_back_to_checkpoint(tmp_path, monkeypatch):
    """With a checkpoint available the sentinel rolls back and retries;
    the injected fault is one-shot, so the rerun matches a clean run
    byte-for-byte."""
    X, y = _data()
    p = dict(PARAMS, nonfinite_check_freq=1)
    clean = lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=6)
    monkeypatch.setenv("LGBM_TPU_FAULT", "nan_grad@3")
    faults.reload()
    ck = str(tmp_path / "ck")
    b = lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=6,
                  checkpoint_dir=ck, checkpoint_freq=1)
    assert b.num_trees() == 6
    assert b.model_to_string(num_iteration=-1) \
        == clean.model_to_string(num_iteration=-1)


def test_custom_fobj_nan_gradients_rejected():
    X, y = _data(n=300)

    def bad_fobj(score, ds):
        g = score - y
        g[10] = np.nan
        return g, np.ones_like(g)

    with pytest.raises(NonFiniteError, match="objective"):
        lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=3,
                  fobj=bad_fobj)


# -------------------------------------------------- callback hygiene
def test_early_stopping_warns_once_without_valid_set():
    """The 'requires at least one validation set' warning fired every
    iteration; now it warns once and disables itself."""
    X, y = _data(n=300)
    msgs = []
    lgb.register_callback(msgs.append)
    try:
        lgb.train({"objective": "regression", "num_leaves": 7,
                   "verbosity": 0, "metric": "none"},
                  lgb.Dataset(X, label=y), num_boost_round=5,
                  callbacks=[lgb.early_stopping(2)])
    finally:
        lgb.register_callback(None)
    warn = [m for m in msgs if "Early stopping requires" in m]
    assert len(warn) == 1, msgs


def test_fault_spec_parsing(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_FAULT",
                       "worker_crash@3,nan_grad@5@1,bogus@1,nan_grad@x")
    faults.reload()
    assert faults.active()
    # malformed specs are dropped with a warning, valid ones kept
    assert faults._parse() == [("worker_crash", 3, 0), ("nan_grad", 5, 1)]
    # attempt gating: nan_grad@5@1 only fires on attempt 1
    monkeypatch.setenv("LGBM_TPU_FAULT_ATTEMPT", "0")
    g, h = np.ones(4), np.ones(4)
    g2, _ = faults.maybe_nan_grad(g, h, 5)
    assert np.all(np.isfinite(g2))
    monkeypatch.setenv("LGBM_TPU_FAULT_ATTEMPT", "1")
    faults.reload()
    g2, _ = faults.maybe_nan_grad(g, h, 5)
    assert np.all(np.isnan(g2))
    # one-shot: the spec does not fire twice
    g3, _ = faults.maybe_nan_grad(g, h, 5)
    assert np.all(np.isfinite(g3))


def test_cli_checkpoint_resume_flags(tmp_path):
    """task=train checkpoint_dir=/resume= flags: a re-run of the same
    command continues from the newest checkpoint and reproduces an
    uninterrupted run's trees."""
    from lightgbm_tpu.cli import main
    X, y = _data(n=400)
    data = str(tmp_path / "train.tsv")
    np.savetxt(data, np.column_stack([y, X]), delimiter="\t", fmt="%.8g")
    common = [f"data={data}", "objective=regression", "num_leaves=7",
              "min_data_in_leaf=5", "verbosity=-1"]
    clean_out = str(tmp_path / "clean.txt")
    assert main(common + ["num_trees=6", f"output_model={clean_out}"]) == 0

    ck = str(tmp_path / "ck")
    out = str(tmp_path / "model.txt")
    # "interrupted" run stops at 4 rounds, checkpointing every 2
    assert main(common + ["num_trees=4", f"checkpoint_dir={ck}",
                          "checkpoint_freq=2",
                          f"output_model={out}"]) == 0
    # re-run to the full 6 rounds: resumes from iteration 4
    assert main(common + ["num_trees=6", f"checkpoint_dir={ck}",
                          "checkpoint_freq=2",
                          f"output_model={out}"]) == 0

    def trees(path):
        return open(path).read().split("\nparameters:")[0]
    assert trees(out) == trees(clean_out)

    # resume=false starts from scratch (4 trees, not 6+)
    out2 = str(tmp_path / "model2.txt")
    assert main(common + ["num_trees=4", f"checkpoint_dir={ck}",
                          "checkpoint_freq=2", "resume=false",
                          f"output_model={out2}"]) == 0
    b = lgb.Booster(model_file=out2)
    assert b.num_trees() == 4


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_measuring_scripts_refuse_to_run_without_a_tpu(script):
    """The scripts that measure the chip do not fall back: on the CPU
    backend they exit non-zero at once — before any data is generated,
    so within seconds — and say that the TPU is missing."""
    import subprocess
    import sys
    import time
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, os.path.join(repo, script)],
                       cwd=repo, env=env, capture_output=True, text=True,
                       timeout=120)
    elapsed = time.monotonic() - t0
    assert r.returncode != 0, r.stdout
    assert "no TPU found" in r.stderr, r.stderr[-2000:]
    assert r.stdout.strip() == "", r.stdout  # no phase line, no result
    # 2^20 x 28 rows of generated data alone take longer than this
    assert elapsed < 60, f"{script} took {elapsed:.0f}s to refuse"


def test_manifest_fallback_scan(tmp_path):
    """A damaged manifest falls back to scanning ckpt_*.txt."""
    X, y = _data()
    ck = str(tmp_path / "ck")
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=4,
              checkpoint_dir=ck, checkpoint_freq=2)
    with open(os.path.join(ck, "manifest.json"), "w") as f:
        f.write("{truncated")
    ckpt = CheckpointManager(ck).latest()
    assert ckpt is not None and ckpt.iteration == 4
