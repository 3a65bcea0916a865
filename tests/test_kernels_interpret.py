"""The Pallas histogram kernels EXECUTED on the CPU, in interpret mode.

`tests/test_chip_compile.py` shows that the kernels compile for the chip
and `chip_smoke.py` runs them there; in between, interpret mode runs the
kernel bodies — operand casts included — on the CPU backend, so what a
kernel computes is tested in tier-1 and not only on the chip.  It says
nothing about tiling, VMEM or speed.
"""

import functools

import numpy as np
import pytest

import jax.numpy as jnp
from jax.experimental import pallas as pl

from lightgbm_tpu.learner import FeatureMeta, GrowParams
from lightgbm_tpu.ops.split import MISSING_NONE, SplitParams


@pytest.fixture()
def interpret_pallas(monkeypatch):
    """Every `pl.pallas_call` traced inside the test interprets its
    kernel.  Shapes in this file are used by no other test, so no jit
    cache entry traced without it can be hit (or left behind)."""
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))


def test_kernel_checks_pass_in_interpret_mode(interpret_pallas):
    """tools/kernel_checks.py — what chip_smoke.py and bench.py run on
    the chip — against its own host ground truth."""
    from tools.kernel_checks import run_checks
    assert run_checks() == "ok"


def test_wide_kernel_checks_pass_in_interpret_mode(interpret_pallas):
    """tools/kernel_checks.py --wide, which runs on the chip at the
    widest cell's 400,384 x 2,000: here at a size the interpreter
    carries, wide enough for the feature-grouped path at 128 slots."""
    from tools.kernel_checks import run_wide_checks
    assert run_wide_checks(n=1024, F=200) == "ok"


@pytest.mark.parametrize("num_slots", [1, 2, 4, 8, 64, 255])
def test_wave_kernels_equal_numpy_exactly(interpret_pallas, num_slots):
    """One operand contract — binned [F, n], slot [n], gh [C+1, n] with
    the count mask as its last row — for the fused kernel, the decomposed
    kernel (few slots) and the XLA stand-in: on grid-snapped inputs
    (eighths, so every fp32 sum is exact in any order) histograms AND
    ride-along counts equal a numpy scatter to the last bit, with a third
    of the rows carrying the out-of-range sentinel slot the recolour
    gives rows outside every computed leaf."""
    from lightgbm_tpu.learner.wave import _hist_wave_xla
    from lightgbm_tpu.ops.histogram import (build_histogram_wave,
                                            build_histogram_wave_hl,
                                            wave_slot_pad)
    n, F, B = 1536, 5, 32                          # shapes of no other test
    sentinel = wave_slot_pad(255)
    rng = np.random.RandomState(num_slots)
    binned = rng.randint(0, B, (F, n)).astype(np.uint8)
    slot = np.where(rng.rand(n) < 2 / 3, rng.randint(0, num_slots, n),
                    sentinel).astype(np.int32)
    mask = (rng.rand(n) < 0.9).astype(np.float32)
    gh = np.stack([rng.randint(-16, 17, n) / 8.0 * mask,
                   rng.randint(1, 17, n) / 8.0 * mask,
                   mask]).astype(np.float32)        # [3, n]
    want = np.zeros((num_slots, F, B, 2), np.float32)
    inb = slot < num_slots
    for f in range(F):
        for c in range(2):
            np.add.at(want[:, f, :, c], (slot[inb], binned[f][inb]),
                      gh[c][inb])
    want_cnt = np.bincount(slot[inb], weights=mask[inb],
                           minlength=num_slots).astype(np.float32)
    args = (jnp.asarray(binned), jnp.asarray(slot), jnp.asarray(gh))
    got = {"wave": build_histogram_wave(*args, max_bin=B,
                                        num_slots=num_slots),
           "xla": _hist_wave_xla(*args, max_bin=B, num_slots=num_slots)}
    if num_slots <= 8:
        got["hl"] = build_histogram_wave_hl(
            args[0], args[0].T, *args[1:], max_bin=B, num_slots=num_slots,
            out_slots=num_slots)
    for name, (hist, cnt) in got.items():
        np.testing.assert_array_equal(np.asarray(hist), want, err_msg=name)
        np.testing.assert_array_equal(np.asarray(cnt), want_cnt,
                                      err_msg=name)


def _binary_problem(n, F, B, seed=0):
    """Binned rows plus binary-logloss gradients at a score whose hessian
    (0.2447...) is NOT a bf16 value: rounding it costs 4.4e-4 a row."""
    rng = np.random.RandomState(seed)
    X = rng.rand(n, F)
    binned = np.clip((X * B).astype(np.int64), 0, B - 1).T.astype(np.uint8)
    logit = 3 * (X[:, 0] - 0.5) + 2 * X[:, 1] * X[:, 2]
    y = rng.rand(n) < 1 / (1 + np.exp(-logit))
    lv = np.where(y, 1.0, -1.0)
    resp = -lv / (1.0 + np.exp(lv * -0.29))
    grad = resp.astype(np.float32)
    hess = (np.abs(resp) * (1 - np.abs(resp))).astype(np.float32)
    meta = FeatureMeta(num_bin=jnp.full(F, B, jnp.int32),
                       missing_type=jnp.full(F, MISSING_NONE, jnp.int32),
                       default_bin=jnp.zeros(F, jnp.int32),
                       penalty=jnp.ones(F, jnp.float32))
    return binned, grad, hess, meta


def _assert_leaf_sums_match_rows(tree, leaf_id, grad, hess, L):
    """Every leaf's recorded hessian sum and output must be those of the
    rows that ended in it, to bf16 rounding (0.4%) — not off by the
    rounding bias of all the OTHER rows, which sent the leaf at the end
    of each parent-minus-sibling chain to a near-zero sum and an output
    in the thousands on the chip."""
    leaf_id = np.asarray(leaf_id)
    nl = int(tree.num_leaves)
    assert nl == L
    sum_h = np.bincount(leaf_id, weights=hess.astype(np.float64),
                        minlength=L)[:nl]
    sum_g = np.bincount(leaf_id, weights=grad.astype(np.float64),
                        minlength=L)[:nl]
    weight = np.asarray(tree.leaf_weight)[:nl]
    value = np.asarray(tree.leaf_value)[:nl]
    np.testing.assert_allclose(weight, sum_h, rtol=1e-2)
    # rounding each |g| <= 0.57 by 0.4% moves an output by at most
    # 0.004 * 0.57 / 0.2447 = 0.009 where the signs cancel
    np.testing.assert_allclose(value, -sum_g / sum_h, rtol=2e-2, atol=2e-2)


def test_wave_engine_pallas_leaf_sums_are_consistent(interpret_pallas):
    from lightgbm_tpu.learner.wave import grow_tree_wave
    n, F, B, L = 32768, 8, 64, 31
    binned, grad, hess, meta = _binary_problem(n, F, B)
    params = GrowParams(num_leaves=L, max_bin=B, hist_method="pallas",
                        split=SplitParams(min_data_in_leaf=20))
    tree, leaf_id = grow_tree_wave(
        jnp.asarray(binned), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(n, jnp.float32), jnp.ones(F, bool), meta, params)
    _assert_leaf_sums_match_rows(tree, leaf_id, grad, hess, L)


def test_leafwise_engine_bf16_onehot_leaf_sums_are_consistent():
    """The same property for the leaf-wise engine over the XLA one-hot
    lowering, whose operands are bf16 too (no Pallas involved)."""
    from lightgbm_tpu.learner import grow_tree
    n, F, B, L = 32768, 8, 64, 31
    binned, grad, hess, meta = _binary_problem(n, F, B, seed=1)
    params = GrowParams(num_leaves=L, max_bin=B, hist_method="onehot",
                        split=SplitParams(min_data_in_leaf=20))
    tree, leaf_id = grow_tree(
        jnp.asarray(binned), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(n, jnp.float32), jnp.ones(F, bool), meta, params)
    _assert_leaf_sums_match_rows(tree, leaf_id, grad, hess, L)
