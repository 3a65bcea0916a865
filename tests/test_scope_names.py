"""The program's scopes reach the compiled programs: every `device_scope`
of the train path is in the `op_name` metadata of the ops it covers
(`jax.named_scope` -> MLIR location -> HLO `op_name` -> the profiler's
`tf_op` stat, which benchmarks/scope_trace.py reads).  Checked on the
lowered text of the three programs of a steady iteration at a tiny size:
the wave grow program, the gradient program, the score update — which,
under the TPU's rule, holds no gather."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import leaf_lookup
from lightgbm_tpu.learner import FeatureMeta, GrowParams
from lightgbm_tpu.learner.wave import grow_tree_wave
from lightgbm_tpu.ops.split import SplitParams

N, F, B, LEAVES = 512, 4, 16, 8

GROW_SCOPES = ("Tree.hist_operands", "Tree.histogram", "Tree.cache",
               "Tree.split_find", "Tree.partition", "Tree.prune")


def _lowered_text(lowered):
    return lowered.as_text(debug_info=True)


@pytest.fixture(scope="module")
def grow_text():
    meta = FeatureMeta(num_bin=jnp.full(F, B, jnp.int32),
                       missing_type=jnp.zeros(F, jnp.int32),
                       default_bin=jnp.zeros(F, jnp.int32),
                       penalty=jnp.ones(F, jnp.float32))
    params = GrowParams(num_leaves=LEAVES, max_bin=B, hist_method="onehot",
                        split=SplitParams(min_data_in_leaf=2),
                        wave_prune=True)
    rng = np.random.RandomState(0)
    args = (jnp.asarray(rng.randint(0, B, (F, N)), jnp.uint8),
            jnp.asarray(rng.randn(N), jnp.float32),
            jnp.ones(N, jnp.float32), jnp.ones(N, jnp.float32),
            jnp.ones(F, bool), meta)
    return _lowered_text(grow_tree_wave.lower(*args, params=params))


@pytest.fixture(scope="module")
def gbdt():
    rng = np.random.RandomState(0)
    X = rng.randn(400, 3)
    y = (X[:, 0] > 0).astype(np.float64)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 4,
                       "verbosity": -1}, lgb.Dataset(X, label=y))
    return bst._gbdt


@pytest.mark.parametrize("scope", GROW_SCOPES)
def test_wave_grow_program_carries_the_scope(grow_text, scope):
    assert scope in grow_text


def test_scopes_nest_as_the_reader_expects(grow_text):
    """Operand building inside the histogram wrapper is the INNER label
    (scope_trace.scope_of takes the innermost); Tree.cache does not hold
    the kernel call."""
    assert "Tree.cache/Tree.histogram" not in grow_text
    assert "Tree.hist_operands/Tree." not in grow_text


def test_gradient_program_carries_its_scope(gbdt):
    text = _lowered_text(gbdt._grad_fn_raw.lower(
        gbdt.scores, gbdt.label_dev, gbdt.weight_dev))
    assert "GBDT.gradients" in text


@pytest.mark.parametrize("fn_name", ["_score_update_shrink_fn",
                                     "_score_update_fn"])
def test_score_update_programs_carry_their_scope(gbdt, fn_name):
    L = gbdt.config.num_leaves
    leaf_vals = jnp.zeros(max(L, 2), jnp.float32)
    leaf_id = jnp.zeros(gbdt.n_pad, jnp.int32)
    args = ((gbdt.scores, 0, leaf_vals, 0.1, leaf_id, gbdt.pad_mask)
            if fn_name.endswith("shrink_fn")
            else (gbdt.scores, 0, leaf_vals, leaf_id, gbdt.pad_mask))
    text = _lowered_text(getattr(gbdt, fn_name).lower(*args))
    assert "GBDT.score_update" in text


@pytest.mark.parametrize("backend,gathers", [("tpu", False), ("cpu", True)])
@pytest.mark.parametrize("fn_name", ["_score_update_shrink_fn",
                                     "_score_update_fn"])
def test_score_update_on_a_tpu_holds_no_gather(monkeypatch, fn_name,
                                               backend, gathers):
    """At the benchmark cells' `num_leaves=255`, under the rule
    `leaf_lookup.pick_form` applies on a TPU, the program reads each
    row's leaf value by one-hot: no `stablehlo.gather` in its lowered
    text, the scope still on it (`score_update_ms` reads the same label).
    The CPU rule keeps the gather, which shows that the text would name
    one."""
    rule = leaf_lookup.pick_form
    monkeypatch.setattr(leaf_lookup, "pick_form",
                        lambda L, _: rule(L, backend))
    rng = np.random.RandomState(0)
    X = rng.randn(400, 3)
    g = lgb.Booster({"objective": "binary", "num_leaves": 255,
                     "verbosity": -1},
                    lgb.Dataset(X, label=(X[:, 0] > 0) * 1.0))._gbdt
    leaf_vals = jnp.zeros(255, jnp.float32)
    leaf_id = jnp.zeros(g.n_pad, jnp.int32)
    args = ((g.scores, 0, leaf_vals, 0.1, leaf_id, g.pad_mask)
            if fn_name.endswith("shrink_fn")
            else (g.scores, 0, leaf_vals, leaf_id, g.pad_mask))
    text = _lowered_text(getattr(g, fn_name).lower(*args))
    assert "GBDT.score_update" in text
    assert ("stablehlo.gather" in text) is gathers


def test_kernel_wrappers_scope_their_operand_building():
    """The pads and reshapes in front of the Pallas calls
    (ops/histogram.py) are Tree.hist_operands; traced, not lowered: the
    kernels themselves lower for a TPU only."""
    from lightgbm_tpu.ops.histogram import (build_histogram_rows_pallas,
                                            build_histogram_wave,
                                            build_histogram_wave_hl)
    binned = jnp.zeros((F, N), jnp.uint8)
    slot = jnp.zeros(N, jnp.int32)
    gh = jnp.zeros((3, N), jnp.float32)
    for jaxpr in (
            jax.make_jaxpr(lambda *a: build_histogram_wave(
                *a, max_bin=B, num_slots=8))(binned, slot, gh),
            jax.make_jaxpr(lambda *a: build_histogram_wave_hl(
                *a, max_bin=B, num_slots=2, out_slots=8))(
                    binned, binned.T, slot, gh),
            jax.make_jaxpr(lambda *a: build_histogram_rows_pallas(
                *a, max_bin=B))(binned.T, gh[:2].T, gh[2])):
        text = jaxpr.pretty_print(name_stack=True)
        assert "Tree.hist_operands" in text
        assert "pallas_call" in text
