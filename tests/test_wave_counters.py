"""The wave engine counts its own work: `TreeArrays.waves` (waves that
ran: one full histogram pass over the rows each) rides the packed tree to
the registry's `waves_total`, beside `trees_grown`."""

import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import lightgbm_tpu as lgb
from lightgbm_tpu.learner import FeatureMeta, GrowParams, wave
from lightgbm_tpu.observability import global_registry
from lightgbm_tpu.ops import histogram
from lightgbm_tpu.ops.split import MISSING_NONE, SplitParams


def _counting(monkeypatch, names, module=wave):
    """Replace `module.<name>` by a twin that reports each EXECUTION (a
    wave skipped by its lax.cond reports nothing)."""
    calls = []
    for name in names:
        real = getattr(module, name)

        def twin(*args, _real=real, _name=name, **kw):
            jax.debug.callback(lambda _n=_name: calls.append(_n))
            return _real(*args, **kw)
        monkeypatch.setattr(module, name, twin)
    return calls


def test_a_tiny_train_counts_its_waves(monkeypatch):
    calls = _counting(monkeypatch, ["_hist_wave_xla"])
    rng = np.random.RandomState(3)
    X = rng.randn(2311, 5).astype(np.float32)      # shapes of no other test
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.1 * rng.randn(2311) > 0)
    before = {k: global_registry.counter(k)
              for k in ("waves_total", "trees_grown")}
    leaves, rounds = 13, 4
    bst = lgb.train({"objective": "binary", "num_leaves": leaves,
                     "max_bin": 29, "tpu_growth_strategy": "wave",
                     "verbosity": -1},
                    lgb.Dataset(X, label=y.astype(np.float32)),
                    num_boost_round=rounds)
    assert bst._gbdt.growth_strategy == "wave"
    bst._gbdt._sync_model()          # every packed tree has been decoded
    jax.effects_barrier()
    got = {k: global_registry.counter(k) - v for k, v in before.items()}
    assert got["trees_grown"] == rounds
    assert all(t.num_leaves == leaves for t in bst._gbdt.models_)
    assert got["waves_total"] == len(calls)
    assert got["waves_total"] / got["trees_grown"] >= math.ceil(
        math.log2(leaves))


def test_waves_equal_the_kernel_calls_in_interpret_mode(monkeypatch):
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    # the kernels `ops/histogram.py wave_histograms` dispatches to
    calls = _counting(monkeypatch, ["build_histogram_wave",
                                    "build_histogram_wave_hl"], histogram)
    # shapes of no other test; at 127 bins the 1- and 2-slot waves take
    # `_hl` and the rest the full kernel, so both are dispatched
    n, F, B, L = 4096, 6, 127, 21
    rng = np.random.RandomState(5)
    Xu = rng.rand(n, F)
    binned = np.clip((Xu * B).astype(np.int64), 0, B - 1).T.astype(np.uint8)
    grad = (Xu[:, 0] - 0.5 + 0.3 * Xu[:, 1] * Xu[:, 2]
            + 0.05 * rng.randn(n)).astype(np.float32)
    meta = FeatureMeta(num_bin=jnp.full(F, B, jnp.int32),
                       missing_type=jnp.full(F, MISSING_NONE, jnp.int32),
                       default_bin=jnp.zeros(F, jnp.int32),
                       penalty=jnp.ones(F, jnp.float32))
    params = GrowParams(num_leaves=L, max_bin=B, hist_method="pallas",
                        split=SplitParams(min_data_in_leaf=5),
                        wave_prune=True)
    tree, _ = wave.grow_tree_wave(
        jnp.asarray(binned), jnp.asarray(grad), jnp.ones(n, jnp.float32),
        jnp.ones(n, jnp.float32), jnp.ones(F, bool), meta, params)
    jax.effects_barrier()
    assert int(tree.num_leaves) == L
    assert int(tree.waves) == len(calls) >= math.ceil(math.log2(L))
    assert set(calls) == {"build_histogram_wave", "build_histogram_wave_hl"}


def test_the_leafwise_engine_packs_zero_waves():
    """Its TreeArrays carry no count; the packed tree's last word is 0."""
    rng = np.random.RandomState(7)
    X = rng.randn(300, 3)
    before = global_registry.counter("waves_total")
    bst = lgb.train({"objective": "regression", "num_leaves": 5,
                     "tpu_growth_strategy": "leafwise", "verbosity": -1},
                    lgb.Dataset(X, label=X[:, 0]), num_boost_round=3)
    bst._gbdt._sync_model()
    assert bst._gbdt.growth_strategy == "leafwise"
    assert global_registry.counter("waves_total") == before
    np.testing.assert_allclose(bst.predict(X[:5]).shape, (5,))
