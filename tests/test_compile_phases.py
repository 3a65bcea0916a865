"""Compile-phase counters (observability/compile_cache.py): JAX's own
durations of tracing, lowering, backend compile and cache load, added
into the registry as each event's OWN time, so that the four are
disjoint."""

import time

import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.observability import (compile_cache,
                                        configure_compile_cache,
                                        global_registry)

PHASES = ("jit_trace_s", "jit_lower_s", "backend_compile_s", "cache_load_s")


def _counters():
    return {k: global_registry.counter(k) for k in PHASES}


def test_first_jit_call_leaves_its_phases_in_the_registry():
    configure_compile_cache()       # installs the listeners, cache or not
    before = _counters()

    @jax.jit
    def fresh(x):                   # a function no test has compiled
        return jnp.tanh(x) * 3.0 + jnp.sum(x) - 28.0
    fresh(jnp.arange(7.0)).block_until_ready()
    after = _counters()
    for name in ("jit_trace_s", "jit_lower_s", "backend_compile_s"):
        assert after[name] > before[name], (name, before, after)
    again = _counters()
    fresh(jnp.arange(7.0)).block_until_ready()      # no second compile
    assert _counters() == again


def test_a_boosters_first_iteration_keeps_its_share_apart():
    """`first_iter_*` gain what the process totals gain inside the first
    `train_one_iter`, and nothing in later iterations."""
    import numpy as np
    import lightgbm_tpu as lgb
    first = ["first_iter_" + k for k in PHASES]
    rng = np.random.RandomState(11)
    X = rng.randn(1237, 4)                  # shapes of no other test
    bst = lgb.Booster({"objective": "regression", "num_leaves": 6,
                       "max_bin": 23, "verbosity": -1},
                      lgb.Dataset(X, label=X[:, 1]))
    before = _counters()
    before_first = {k: global_registry.counter(k) for k in first}
    bst.update()
    gained = {k: v - before[k] for k, v in _counters().items()}
    after_first = {k: global_registry.counter(k) for k in first}
    for k in PHASES:
        assert after_first["first_iter_" + k] - before_first[
            "first_iter_" + k] == pytest.approx(gained[k], abs=1e-9)
    assert gained["jit_trace_s"] > 0 and gained["backend_compile_s"] > 0
    bst.update()
    bst.update()
    assert {k: global_registry.counter(k) for k in first} == after_first


def test_listeners_are_installed_once():
    configure_compile_cache()
    configure_compile_cache()
    compile_cache._phase_tls.stack = []   # forget the events before now
    before = _counters()
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.5)
    assert _counters()["jit_lower_s"] - before["jit_lower_s"] \
        == pytest.approx(0.5)


def _report(event, seconds):
    """What JAX does when an event of `seconds` ends now."""
    compile_cache._on_duration_event(event, seconds)


def test_nested_events_count_their_own_time_only():
    """A cache load is reported inside backend_compile_duration, and a
    function traced inside another's trace inside that: each counter
    takes what is left after the events it contains."""
    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
    compile_cache._phase_tls.stack = []
    before = _counters()
    _report(TRACE, 0.010)           # inner trace ...
    time.sleep(0.02)
    _report(TRACE, 0.015)           # ... and a sibling after it,
    time.sleep(0.002)
    _report(TRACE, 10.0)            # both inside this one (it began 10 s ago)
    time.sleep(0.05)
    _report(LOAD, 0.030)            # a cache hit ...
    _report(BACKEND, 0.031)         # ... reported inside the "compile"
    _report("/jax/compilation_cache/compile_time_saved_sec", 99.0)  # no phase
    after = _counters()
    assert after["jit_trace_s"] - before["jit_trace_s"] \
        == pytest.approx(10.0, abs=1e-6)          # not 10.025
    assert after["cache_load_s"] - before["cache_load_s"] \
        == pytest.approx(0.030)
    assert after["backend_compile_s"] - before["backend_compile_s"] \
        == pytest.approx(0.001, abs=1e-6)
    assert after["jit_lower_s"] == before["jit_lower_s"]


def test_sequential_events_are_not_nested():
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    compile_cache._phase_tls.stack = []
    before = _counters()
    _report(LOWER, 0.001)
    time.sleep(0.003)
    _report(LOWER, 0.001)           # began after the first ended
    assert _counters()["jit_lower_s"] - before["jit_lower_s"] \
        == pytest.approx(0.002)
    assert len(compile_cache._phase_tls.stack) == 2
