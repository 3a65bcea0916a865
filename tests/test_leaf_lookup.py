"""The score update's per-row leaf-value lookup (boosting/leaf_lookup.py).

The one-hot form is what a TPU runs and the CPU rule never picks, so it
is run here explicitly and held to `jnp.take(vals, clip(ids))` bit for
bit (through a bitcast to int32, never `allclose`); then whole training
jobs are run with the helper forced to each form and must not differ in
a byte, on one device and with rows sharded over four.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import leaf_lookup
from lightgbm_tpu.boosting.model_io import save_model_to_string
from lightgbm_tpu.observability import global_registry

# 9,000 is past ONE_HOT_MAX_LEAVES: the form is right at any size, the
# rule only stops choosing it there
LEAVES = [2, 31, 255, 256, 1000, 9000]
AWKWARD = np.array([-0.0, np.inf, -np.inf, 1e-42, -1e-42,
                    np.finfo(np.float32).max, -np.finfo(np.float32).max,
                    np.finfo(np.float32).tiny], np.float32)


def _bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(a, jnp.int32))


def _table(L, seed=0, nan_leaf=None):
    rng = np.random.RandomState(seed)
    vals = (rng.randn(L) * 10.0 ** rng.randint(-6, 6, L)).astype(np.float32)
    k = min(L, AWKWARD.size)
    vals[rng.permutation(L)[:k]] = AWKWARD[:k]
    if nan_leaf is not None:
        vals[nan_leaf] = np.nan
    return vals


onehot = jax.jit(leaf_lookup.lookup_onehot)
take = jax.jit(leaf_lookup.lookup_take)


@pytest.mark.parametrize("L", LEAVES)
def test_onehot_equals_take_bit_for_bit(L):
    """Negatives, -0.0, denormals, both infinities, the largest finite
    float; ids inside the table, below 0 and at or past L."""
    vals = _table(L)
    rng = np.random.RandomState(L)
    ids = np.concatenate([
        rng.randint(0, L, 1500), rng.randint(-50, 0, 100),
        rng.randint(L, L + 50, 100), [0, L - 1, -1, L, -2**31, 2**31 - 1]
    ]).astype(np.int32)
    got = _bits(onehot(jnp.asarray(vals), jnp.asarray(ids)))
    np.testing.assert_array_equal(
        got, _bits(take(jnp.asarray(vals), jnp.asarray(ids))))
    # and against the table itself, on the host
    np.testing.assert_array_equal(
        got, vals.view(np.int32)[np.clip(ids, 0, L - 1)])


@pytest.mark.parametrize("L", LEAVES)
def test_a_nan_leaf_reaches_only_its_own_rows(L):
    """A float product of the one-hot with the values would give
    `0 * NaN` to every row; the select on bit patterns gives the NaN,
    payload and all, to the rows of its leaf and leaves the others as
    they were."""
    nan_leaf = L // 2
    vals = _table(L, seed=1, nan_leaf=nan_leaf)
    vals[vals == np.inf] = 1.0      # only the NaN leaf is not finite
    vals[vals == -np.inf] = -1.0
    ids = np.random.RandomState(2).randint(0, L, 2000).astype(np.int32)
    ids[:4] = nan_leaf
    out = np.asarray(onehot(jnp.asarray(vals), jnp.asarray(ids)))
    assert np.isnan(out[ids == nan_leaf]).all()
    assert np.isfinite(out[ids != nan_leaf]).all()
    np.testing.assert_array_equal(
        _bits(out), _bits(take(jnp.asarray(vals), jnp.asarray(ids))))


@pytest.mark.parametrize("L,backend,form", [
    (2, "tpu", "onehot"), (255, "tpu", "onehot"), (4095, "tpu", "onehot"),
    (leaf_lookup.ONE_HOT_MAX_LEAVES, "tpu", "onehot"),
    (leaf_lookup.ONE_HOT_MAX_LEAVES + 1, "tpu", "take"),
    (131072, "tpu", "take"),
    (2, "cpu", "take"), (255, "cpu", "take"), (255, "gpu", "take")])
def test_the_form_is_a_rule_on_table_size_and_backend(L, backend, form):
    assert leaf_lookup.pick_form(L, backend) == form


@pytest.mark.parametrize("form", ["onehot", "take"])
def test_lookup_counts_the_form_each_program_took(monkeypatch, form):
    """By traced signature, as the kernels' wrappers count theirs: a
    program that is run again adds nothing."""
    monkeypatch.setattr(leaf_lookup, "pick_form", lambda L, backend: form)
    name = f"score_lookup_{form}_traces"
    count = lambda: global_registry.snapshot()["counters"].get(name, 0)
    vals, ids = jnp.asarray(_table(31)), jnp.zeros(64, jnp.int32)
    program = jax.jit(lambda v, i: leaf_lookup.lookup(v, i))
    before = count()
    program(vals, ids)
    program(vals, ids)
    assert count() - before == 1
    np.testing.assert_array_equal(
        _bits(program(vals, ids)), _bits(take(vals, ids)))


def _train(form, monkeypatch, extra):
    monkeypatch.setattr(leaf_lookup, "pick_form", lambda L, backend: form)
    rng = np.random.RandomState(7)
    X = rng.randn(3000, 6)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.3 * rng.randn(3000)
         > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "min_data_in_leaf": 5, "learning_rate": 0.1}
    params.update(extra)
    booster = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10)
    gbdt = booster._gbdt
    return gbdt, save_model_to_string(gbdt), np.asarray(gbdt.scores)


@pytest.mark.parametrize("extra", [
    pytest.param({}, id="one-device"),
    pytest.param({"tree_learner": "data", "num_machines": 4},
                 id="rows-over-four-devices"),
    pytest.param({"boosting": "dart", "drop_rate": 0.5}, id="dart"),
    pytest.param({"boosting": "rf", "bagging_fraction": 0.7,
                  "bagging_freq": 1}, id="rf")])
def test_training_is_byte_identical_under_each_form(monkeypatch, extra):
    """Ten iterations of a small binary job: the model text and the score
    buffer do not differ in a byte whichever form the score update took
    (gbdt's shrink program; dart's and rf's `_score_update_fn`), also
    with scores and leaf ids sharded by rows as `tree_learner=data`
    holds them."""
    g_take, text_take, scores_take = _train("take", monkeypatch, extra)
    g_hot, text_hot, scores_hot = _train("onehot", monkeypatch, extra)
    if "num_machines" in extra:
        assert g_hot.mesh is not None and g_hot.mesh.devices.size == 4
        assert len(g_hot.scores.sharding.device_set) == 4
    assert text_hot == text_take
    assert scores_hot.tobytes() == scores_take.tobytes()
    assert np.isfinite(scores_hot).all() and np.abs(scores_hot).max() > 0
