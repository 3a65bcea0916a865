"""Observability subsystem (ISSUE 2 tentpole): metrics registry, JSONL
event log schema, recompile watchdog, device-memory sampling, logger
reset path."""

import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.observability import (EventLogger, MetricsRegistry,
                                        RecompileDetector,
                                        global_registry,
                                        sample_device_memory)
from lightgbm_tpu.utils import log
from lightgbm_tpu.utils.timer import global_timer


def _data(n=600, f=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = X[:, 0] + 0.5 * rng.randn(n)
    return X, y


def _read_events(metrics_dir, rank=0):
    path = os.path.join(metrics_dir, f"events-rank{rank}.jsonl")
    assert os.path.exists(path), f"missing event log {path}"
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


# --------------------------------------------------------------- registry
def test_metrics_registry_counters_and_gauges():
    reg = MetricsRegistry()
    reg.inc("a")
    reg.inc("a", 2)
    reg.set_gauge("g", 7)
    reg.set_gauge("g", 9)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 9
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}}


def test_sample_device_memory_shape():
    stats = sample_device_memory()   # {} on backends without memory_stats
    assert isinstance(stats, dict)
    for v in stats.values():
        assert isinstance(v, int) and v >= 0


# -------------------------------------------------------------- event log
def test_event_log_one_iteration_event_per_round(tmp_path):
    """Acceptance: a 10-iteration metrics run writes a parseable JSONL
    with exactly one rank-tagged `iteration` event per round whose phase
    breakdown carries the bulk of the measured wall-clock."""
    X, y = _data()
    md = str(tmp_path / "metrics")
    bst = lgb.train({"objective": "regression", "num_leaves": 7,
                     "verbosity": -1, "metric": "l2",
                     "is_provide_training_metric": True},
                    lgb.Dataset(X, label=y), num_boost_round=10,
                    valid_sets=[lgb.Dataset(X[:100], label=y[:100])],
                    metrics_dir=md)
    assert bst.current_iteration() == 10
    events = _read_events(md)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "train_start"
    assert kinds[-1] == "train_end"
    iters = [e for e in events if e["event"] == "iteration"]
    assert len(iters) == 10
    assert [e["iteration"] for e in iters] == list(range(1, 11))
    for e in iters:
        assert e["rank"] == 0
        assert e["time_s"] > 0
        assert e["phases"], "iteration event must carry a phase breakdown"
        assert e["trees"] and all(t["leaves"] >= 1 for t in e["trees"])
        assert "valid_0 l2" in e["valid"]
        assert "l2" in e["train"]
        assert e["counters"].get("trees_grown", 0) >= e["iteration"]
    # the named phases account for the bulk of the measured wall-clock
    total_time = sum(e["time_s"] for e in iters)
    total_phase = sum(sum(e["phases"].values()) for e in iters)
    assert total_phase >= 0.5 * total_time, (
        f"phases cover {total_phase:.4f}s of {total_time:.4f}s")
    # grow is always among the recorded phases
    assert any("GBDT::grow_tree" in e["phases"] for e in iters)
    # metrics run must not leave the global timer force-enabled
    assert global_timer.sync == bool(
        os.environ.get("LIGHTGBM_TPU_TIMETAG", ""))


def test_event_log_checkpoint_and_fault_events(tmp_path, monkeypatch):
    """Checkpoint writes and injected faults land on the event log
    (rank-tagged), including the failure path under LGBM_TPU_FAULT."""
    from lightgbm_tpu.reliability import faults
    monkeypatch.setenv("LGBM_TPU_FAULT", "ckpt_write_fail@5")
    faults.reload()
    X, y = _data()
    md = str(tmp_path / "metrics")
    ck = str(tmp_path / "ckpt")
    writes0 = global_registry.counter("checkpoint_writes")
    fails0 = global_registry.counter("checkpoint_failures")
    try:
        lgb.train({"objective": "regression", "num_leaves": 7,
                   "verbosity": -1, "metric": "none"},
                  lgb.Dataset(X, label=y), num_boost_round=10,
                  metrics_dir=md, checkpoint_dir=ck, checkpoint_freq=5)
    finally:
        monkeypatch.delenv("LGBM_TPU_FAULT", raising=False)
        faults.reload()
    events = _read_events(md)
    faults_seen = [e for e in events if e["event"] == "fault_injected"]
    assert [f["kind"] for f in faults_seen] == ["ckpt_write_fail"]
    assert faults_seen[0]["iteration"] == 5
    failed = [e for e in events if e["event"] == "checkpoint_write_failed"]
    assert len(failed) == 1 and failed[0]["iteration"] == 5
    ok = [e for e in events if e["event"] == "checkpoint"]
    assert [e["iteration"] for e in ok] == [10]
    # counters must reflect both outcomes.  Per-iteration events can lag
    # the ASYNC checkpoint writer (the final write lands after the last
    # iteration event snapshots the registry), so the settled numbers
    # live in train_end's post-flush snapshot (ISSUE 5).
    last = [e for e in events if e["event"] == "train_end"][-1]
    assert last["counters"].get("checkpoint_failures", 0) == fails0 + 1
    assert last["counters"].get("checkpoint_writes", 0) == writes0 + 1


def test_record_metrics_requires_sink():
    with pytest.raises(ValueError):
        lgb.record_metrics()


def test_event_log_size_rotation(tmp_path):
    """metrics_rotate_mb (ISSUE 3 satellite): when the live file would
    exceed the cap it rolls to .1, .2, ... oldest-highest, the live file
    always holds the newest events, and no event is lost."""
    md = str(tmp_path / "rot")
    # ~1 KiB cap => every few ~120-byte events rotate the file
    logger = EventLogger(md, rank=0, rotate_mb=1.0 / 1024)
    n = 50
    for i in range(n):
        logger.emit("tick", i=i, pad="x" * 80)
    logger.close()
    base = os.path.join(md, "events-rank0.jsonl")
    rolls = sorted(f for f in os.listdir(md) if f != "events-rank0.jsonl")
    assert rolls, "a 1KiB cap over ~6KiB of events must have rotated"
    assert all(f.startswith("events-rank0.jsonl.") for f in rolls)
    # every roll respects the cap; chronology: .N oldest ... .1, then live
    order = sorted((int(f.rsplit(".", 1)[1]) for f in rolls), reverse=True)
    seen = []
    for idx in order:
        p = f"{base}.{idx}"
        assert os.path.getsize(p) <= 1024
        seen += [json.loads(line)["i"] for line in open(p) if line.strip()]
    seen += [json.loads(line)["i"] for line in open(base) if line.strip()]
    assert seen == list(range(n)), "rotation lost or reordered events"


def test_event_log_rotation_via_train_param(tmp_path):
    """The metrics_rotate_mb param reaches the engine's EventLogger."""
    X, y = _data(n=200)
    md = str(tmp_path / "metrics")
    lgb.train({"objective": "regression", "num_leaves": 4,
               "verbosity": -1, "metric": "l2",
               "metrics_rotate_mb": 1.0 / 1024},
              lgb.Dataset(X, label=y), num_boost_round=8,
              metrics_dir=md)
    names = os.listdir(md)
    assert "events-rank0.jsonl" in names
    assert any(n.startswith("events-rank0.jsonl.") for n in names), (
        f"expected rotated files under a 1KiB cap, got {names}")


# ------------------------------------------------------ recompile watchdog
def test_recompile_detector_warns_once_per_new_signature():
    """Acceptance: exactly one warning per NEW shape signature after the
    first call; repeats of a seen signature stay silent."""
    import jax
    import jax.numpy as jnp

    warnings = []
    log.set_verbosity(1)   # earlier trainings may have left -1
    log.register_callback(
        lambda msg: warnings.append(msg) if "[Warning]" in msg else None)
    try:
        fn = RecompileDetector(jax.jit(lambda x: x * 2.0), "toy")
        before = global_registry.counter("recompiles")
        fn(jnp.zeros(3))                 # first signature: no warning
        assert len(warnings) == 0
        fn(jnp.zeros(4))                 # new signature: one warning
        assert len(warnings) == 1 and "re-trace" in warnings[0]
        fn(jnp.zeros(4))                 # seen signature: silent
        assert len(warnings) == 1
        fn(jnp.zeros((2, 2)))            # another new one
        assert len(warnings) == 2
        assert fn.signatures_seen == 3
        assert global_registry.counter("recompiles") == before + 2
    finally:
        log.reset()


def test_recompile_detector_fires_in_training():
    """The wrapped grow entry warns when a mid-training shape change
    re-traces the grower (forced here by shrinking the row count)."""
    X, y = _data(n=512)
    params = {"objective": "regression", "num_leaves": 7,
              "verbosity": -1, "metric": "none"}
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, label=y))
    bst.update()
    gbdt = bst._gbdt
    assert gbdt._grow_fn.signatures_seen == 1
    warnings = []
    log.set_verbosity(1)   # the booster's verbosity=-1 gated warnings off
    log.register_callback(
        lambda msg: warnings.append(msg) if "[Warning]" in msg else None)
    try:
        import jax.numpy as jnp
        # force a shape change on the jitted grow entry (what a buggy
        # caller mutating n_pad mid-run would do)
        n2 = gbdt.n_pad // 2
        gbdt._grow_fn(gbdt.binned_dev[:, :n2],
                      jnp.zeros(n2, jnp.float32),
                      jnp.ones(n2, jnp.float32),
                      jnp.ones(n2, jnp.float32),
                      gbdt._ones_col_mask, gbdt.meta, gbdt.grow_params)
    finally:
        log.reset()
    assert sum("re-trace" in w for w in warnings) == 1
    assert gbdt._grow_fn.signatures_seen == 2


# ----------------------------------------------------------- logger reset
def test_register_logger_none_unregisters(capsys):
    records = []

    class L:
        def info(self, m):
            records.append(m)

        def warning(self, m):
            records.append(m)

    lgb.register_logger(L())
    log.set_verbosity(1)   # earlier trainings may have left -1
    try:
        log.info("routed")
        assert any("routed" in r for r in records)
        lgb.register_logger(None)       # must NOT raise; unregisters
        log.info("back to stderr")
        assert not any("back to stderr" in r for r in records)
    finally:
        log.reset()


def test_log_reset_clears_state():
    log.set_verbosity(2)
    log.register_callback(lambda m: None)
    log.reset()
    assert log.get_verbosity() == 1
    assert log._LogState.callback is None
    assert getattr(log._LogState, "logger", None) is None
