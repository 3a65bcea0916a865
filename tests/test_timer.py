"""Timer/profiling subsystem (ref: utils/common.h:973 Timer/FunctionTimer,
global_timer printed at exit when TIMETAG is on).

Recording and syncing are two switches (utils/timer.py): scopes always
accumulate; only `sync` makes `block()` wait for the device."""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import timer as timer_mod
from lightgbm_tpu.utils.timer import Timer, global_timer


@pytest.fixture
def sync_calls(monkeypatch):
    """Every `jax.block_until_ready` made while the fixture is live."""
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(x)
        return real(x)
    monkeypatch.setattr(jax, "block_until_ready", counting)
    return calls


@pytest.mark.parametrize("sync", [False, True])
def test_scopes_accumulate_whatever_the_sync_switch(sync):
    t = Timer(sync=sync)
    with t.scope("a"):
        with t.scope("b"):
            pass
    with t.scope("a"):
        pass
    assert {k: c for k, _, c in t.items()} == {"a": 2, "b": 1}
    assert all(sec >= 0.0 for _, sec, _ in t.items())


def test_timer_reset_and_snapshot():
    t = Timer()
    with t.scope("x"):
        pass
    snap = t.snapshot()
    assert set(snap) == {"x"} and snap["x"][1] == 1
    with t.scope("x"):
        pass
    snap2 = t.snapshot()
    assert snap2["x"][1] == 2 and snap2["x"][0] >= snap["x"][0]
    t.reset()
    assert t.items() == () and t.snapshot() == {}


def test_scope_accumulates_when_its_body_raises():
    t = Timer()
    with pytest.raises(KeyError):
        with t.scope("boom"):
            raise KeyError("x")
    assert t.snapshot()["boom"][1] == 1
    with t.scope("after"):      # the scope stack was unwound
        t.sync = True
        t.block(jnp.arange(2))
    assert "after::device" in t.snapshot()


def test_block_is_the_identity_with_sync_off(sync_calls):
    t = Timer(sync=False)
    obj = object()
    arr = jnp.arange(4)
    with t.scope("Phase"):
        assert t.block(obj) is obj
        assert t.block(arr) is arr
        assert t.block(None) is None
    assert sync_calls == []
    assert set(t.snapshot()) == {"Phase"}       # nothing credited ::device


def test_block_syncs_and_credits_device_with_sync_on(sync_calls):
    """block() inside a scope credits the settle wait to a separate
    `<scope>::device` entry: the scope total still includes the settle,
    the ::device entry says how much of it the chip owned."""
    t = Timer(sync=True)
    with t.scope("Phase"):
        out = t.block(jnp.arange(1000) * 2)
    np.testing.assert_array_equal(np.asarray(out), np.arange(1000) * 2)
    assert len(sync_calls) == 1
    snap = t.snapshot()
    assert snap["Phase::device"][0] <= snap["Phase"][0]
    assert snap["Phase::device"][1] == 1
    # nested scopes credit the INNERMOST phase
    t.reset()
    with t.scope("Outer"):
        with t.scope("Inner"):
            t.block(jnp.arange(8))
    snap = t.snapshot()
    assert "Inner::device" in snap and "Outer::device" not in snap
    # no enclosing scope: settle happens, nothing is credited
    t.reset()
    t.block(jnp.arange(8))
    assert t.snapshot() == {}
    assert len(sync_calls) == 3


def test_trace_annotation_switch():
    """`set_trace_annotations` is the one way in (no environment
    switch); scopes record the same with it on."""
    t = Timer()
    assert not t.trace_annotations_enabled()
    t.set_trace_annotations(True)
    assert t.trace_annotations_enabled()
    with t.scope("annotated"):
        pass
    assert t.snapshot()["annotated"][1] == 1
    t.set_trace_annotations(False)
    assert not t.trace_annotations_enabled()
    assert Timer(use_jax_profiler=True).trace_annotations_enabled()


def test_scope_attributes_reach_the_annotation(monkeypatch):
    seen = []

    class FakeAnnotation:
        def __init__(self, name, **attrs):
            seen.append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    t = Timer()
    with t.scope("GBDT::iteration", iter=3):      # annotations off
        pass
    assert seen == []
    t.set_trace_annotations(True)
    with t.scope("GBDT::iteration", iter=3):
        with t.scope("GBDT::wait_tree", tree=7):
            pass
    assert seen == [("GBDT::iteration", {"iter": 3}),
                    ("GBDT::wait_tree", {"tree": 7})]
    assert t.snapshot()["GBDT::iteration"][1] == 2   # attrs: not the total


def test_device_scope_names_the_ops_and_records_nothing():
    t = Timer()

    def f(x):
        with t.device_scope("Tree::partition"):
            return x * 2 + 1
    text = jax.jit(f).lower(jnp.arange(4.0)).as_text(debug_info=True)
    assert "Tree.partition" in text
    assert t.snapshot() == {}


def test_training_records_its_spans_and_adds_no_sync(sync_calls):
    """With the sync switch off the train path records every phase and
    waits for the device nowhere (acceptance: no `block_until_ready`
    added on the train path)."""
    assert global_timer.sync is False
    global_timer.reset()
    try:
        rng = np.random.RandomState(0)
        X = rng.randn(500, 3)
        y = X[:, 0]
        bst = lgb.Booster({"objective": "regression", "num_leaves": 7,
                           "verbosity": -1}, lgb.Dataset(X, label=y))
        for _ in range(4):
            bst.update()
        assert sync_calls == []
        snap = global_timer.snapshot()
        for name in ("Dataset::find_bin", "Dataset::binning",
                     "GBDT::gradients", "GBDT::bagging", "GBDT::grow_tree",
                     "GBDT::finalize_tree"):
            assert name in snap, (name, sorted(snap))
        assert snap["GBDT::iteration"][1] == 4
        # the drain keeps two trees in flight: two waits in four iterations
        assert snap["GBDT::wait_tree"][1] == 2
        assert snap["GBDT::materialize_tree"][1] == 2
        # the parent span holds its phases
        phases = sum(snap[n][0] for n in (
            "GBDT::gradients", "GBDT::bagging", "GBDT::grow_tree",
            "GBDT::finalize_tree", "GBDT::materialize_tree"))
        assert snap["GBDT::iteration"][0] >= phases
        assert not any(name.endswith("::device") for name in snap)
    finally:
        global_timer.reset()


def test_sync_switch_charges_phases_their_device_time():
    global_timer.sync = True
    global_timer.reset()
    try:
        rng = np.random.RandomState(0)
        X = rng.randn(500, 3)
        lgb.train({"objective": "regression", "num_leaves": 7,
                   "verbosity": -1}, lgb.Dataset(X, label=X[:, 0]),
                  num_boost_round=2)
        names = {k for k, _, _ in global_timer.items()}
        assert {"GBDT::grow_tree", "GBDT::grow_tree::device",
                "GBDT::gradients::device"} <= names
    finally:
        global_timer.sync = False
        global_timer.reset()


def test_no_environment_switch_for_annotations():
    """LIGHTGBM_TPU_TRACE went with this PR; LIGHTGBM_TPU_TIMETAG is the
    one variable the module reads."""
    import inspect
    src = inspect.getsource(timer_mod)
    assert "LIGHTGBM_TPU_TRACE" not in src
    assert src.count("os.environ") == 1 and "LIGHTGBM_TPU_TIMETAG" in src


def test_scope_stack_is_thread_local():
    """The serving coalescer times dispatches concurrently with the main
    thread: each thread's block() must credit ITS OWN scope."""
    t = Timer(sync=True)
    done = threading.Event()
    ready = threading.Event()

    def worker():
        with t.scope("WorkerPhase"):
            ready.set()
            done.wait(timeout=10)
            t.block(jnp.arange(16))

    th = threading.Thread(target=worker)
    th.start()
    ready.wait(timeout=10)
    with t.scope("MainPhase"):
        t.block(jnp.arange(16))
    done.set()
    th.join(timeout=10)
    snap = t.snapshot()
    assert "MainPhase::device" in snap and "WorkerPhase::device" in snap
    assert snap["MainPhase::device"][1] == 1
    assert snap["WorkerPhase::device"][1] == 1
