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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from lightgbm_tpu.learner import FeatureMeta, GrowParams
from lightgbm_tpu.ops.split import MISSING_NONE, SplitParams


@pytest.fixture()
def interpret_pallas(monkeypatch):
    """Every `pl.pallas_call` traced inside the test interprets its
    kernel.  Shapes in this file are used by no other test, so no jit
    cache entry traced with it is left behind; the caches are cleared
    first, so none traced without it is hit either (`kernel_checks.py`'s
    recolour at 28 x 2,048 is also what `test_wide_dense.py` traces, and
    a worker may have run that file first)."""
    jax.clear_caches()
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


# ------------------------------------- the one-hot at each column's class
@pytest.mark.parametrize("num_slots", [1, 8, 64, 128, 255])
def test_classed_wave_kernel_equals_unclassed_bit_for_bit(interpret_pallas,
                                                          num_slots):
    """tools/kernel_checks.py check 9 (which the chip runs at 8 and 128
    slots): the one-hot cell's twelve code counts and the ranking cell's
    classes in a shuffled column order, every column reaching its last
    code (15 of a 16-code class among them), histograms and counts
    compared bit for bit."""
    from tools.kernel_checks import _classed_mismatches
    assert _classed_mismatches(slot_counts=(num_slots,), n=1024,
                               grid=0.125) == []


def test_classed_wave_kernel_in_class_groups_equals_unclassed(
        interpret_pallas):
    """108 columns at 255 slots: 8,160 classed one-hot rows at 3,072 B
    each are two one-block calls, the cut inside the 256-code class."""
    from lightgbm_tpu.ops.histogram import hist_classes_of, plan_wave_kernel
    from tools.kernel_checks import CLASSED_CODES, _classed_mismatches
    codes = CLASSED_CODES * 6
    plan = plan_wave_kernel(len(codes), 255, 255,
                            hist_classes=hist_classes_of(codes)[0])
    assert plan.groups == 2 and plan.onehot_rows == 8160
    assert [sum(c * k for c, k in g) for g in plan.class_groups] == [
        5344, 2816]
    assert plan.class_groups[0][-1] == (256, 1)
    assert plan.class_groups[1] == ((256, 11),)
    assert _classed_mismatches(codes, slot_counts=(255,), n=512,
                               grid=0.125) == []


def test_int8_arm_keeps_one_class(interpret_pallas):
    """`quant_scales` with `hist_classes` handed over: the plan names no
    class group (tests/test_kernel_plan.py) and the call is the unclassed
    one on the engine's own column order, so both sides agree to the
    bit."""
    from tools.kernel_checks import _classed_mismatches
    quant = dict(quant_bins=16, quant_scales=jnp.asarray([0.125, 0.125]))
    assert _classed_mismatches(slot_counts=(8,), n=1024, grid=0.125,
                               quant=quant) == []


def test_wave_engine_grows_the_same_tree_classed_and_unclassed(
        interpret_pallas):
    """The whole grow program on a table of three classes (16 / 32 / 64)
    in a mixed column order: with `hist_classes` and the order in
    `FeatureMeta` the tree and every row's leaf are those of the program
    that builds every column at `max_bin` — gradients on a grid of
    eighths, so that no order of summation can move a bit."""
    from lightgbm_tpu.learner.wave import grow_tree_wave
    from lightgbm_tpu.ops.histogram import class_ordered, hist_classes_of
    n, B, L = 4096, 64, 31
    rng = np.random.RandomState(38)
    codes = np.array([64, 16, 32, 64, 9, 64, 30, 16])
    binned = np.stack([rng.randint(0, c, n) for c in codes]).astype(np.uint8)
    score = binned[0] / 64 + (binned[1] > 7) + 0.5 * (binned[2] % 5)
    grad = (np.round((score - score.mean() + rng.randn(n)) * 8) / 8)
    hess = rng.randint(1, 9, n) / 8
    F = len(codes)
    base = dict(num_bin=jnp.asarray(codes, jnp.int32),
                missing_type=jnp.full(F, MISSING_NONE, jnp.int32),
                default_bin=jnp.zeros(F, jnp.int32),
                penalty=jnp.ones(F, jnp.float32))
    classes, order = hist_classes_of(codes)
    assert classes == ((16, 3), (32, 2), (64, 3))
    params = GrowParams(num_leaves=L, max_bin=B, hist_method="pallas",
                        split=SplitParams(min_data_in_leaf=20))
    handed = class_ordered(jnp.asarray(binned), jnp.asarray(order))
    np.testing.assert_array_equal(np.asarray(handed), binned[order])
    grown = []
    for meta, p, kw in (
            (FeatureMeta(**base), params, {}),
            (FeatureMeta(hist_order=jnp.asarray(order),
                         hist_inverse=jnp.asarray(np.argsort(order),
                                                  jnp.int32), **base),
             params._replace(hist_classes=classes),
             {"binned_classed": handed})):
        grown.append(grow_tree_wave(
            jnp.asarray(binned), jnp.asarray(grad, jnp.float32),
            jnp.asarray(hess, jnp.float32), jnp.ones(n, jnp.float32),
            jnp.ones(F, bool), meta, p, **kw))
    (tree_a, leaf_a), (tree_b, leaf_b) = grown
    assert int(tree_a.num_leaves) == L
    for name, a, b in zip(tree_a._fields, tree_a, tree_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


@pytest.mark.parametrize("extra", [{}, {"tree_learner": "data"}],
                         ids=["one_device", "row_mesh"])
def test_booster_hands_the_class_ordered_bins_to_every_tree(
        interpret_pallas, monkeypatch, extra):
    """A booster that takes the wave engine and the Pallas kernel (the
    plan asked as a TPU would be) on a table of three classes gathers
    the bins into class order ONCE, sharded by rows like the bins, and
    grows the trees of the booster that builds every column at
    `max_bin`: the model text is equal after three iterations, on one
    device and under `tree_learner=data`'s shard_map.  On one device the
    copy is left uncommitted like the bins, so the grow entry is lowered
    once: committed, it committed every later tree's gradients, and the
    second iteration lowered and loaded the program again (6 s of
    `setup_s` at the ranking cell: my chip run, PR 38)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting import gbdt
    from lightgbm_tpu.learner import plan_growth
    from lightgbm_tpu.learner.wave import grow_tree_wave_donated as entry
    monkeypatch.setattr(gbdt, "plan_growth",
                        lambda **kw: plan_growth(**{**kw, "backend": "tpu"}))
    rng = np.random.RandomState(0)
    codes, n = [63, 16, 32, 63, 9, 63, 30, 16], 4096
    X = np.stack([rng.rand(n) if c == 63 else rng.randint(0, c, n)
                  for c in codes], axis=1).astype(np.float32)
    y = (X[:, 0] + X[:, 1] / 16 + 0.3 * rng.randn(n) > 1).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "min_data_in_bin": 1, "min_data_in_leaf": 5, "verbosity": -1,
              **extra}
    models = []
    for classed in (True, False):
        booster = lgb.Booster(params=params, train_set=lgb.Dataset(
            X, label=y, params=params))
        g = booster._gbdt
        assert (g.growth_strategy, g.grow_params.hist_method) == (
            "wave", "pallas")
        assert g.grow_params.hist_classes == ((16, 3), (32, 2), (64, 3))
        handed = g._classed_kw["binned_classed"]
        assert handed.sharding == g.binned_dev.sharding
        np.testing.assert_array_equal(
            np.asarray(handed),
            np.asarray(g.binned_dev)[np.asarray(g.meta.hist_order)])
        if not classed:
            g.grow_params = g.grow_params._replace(hist_classes=())
            g._classed_kw = {}
        lowered_before = entry._cache_size()
        for _ in range(3):
            booster.update()
        if not extra:
            assert entry._cache_size() - lowered_before == 1
        models.append(booster.model_to_string())
    assert models[0] == models[1]


# ------------------- tools/hist_roof_probe.py: the chip probe's variants
def _probe_operands(codes, n, slots, seed=39):
    rng = np.random.RandomState(seed)
    binned = np.stack([rng.randint(0, c, n) for c in codes]).astype(np.uint8)
    slot = np.where(rng.rand(n) < 0.8, rng.randint(0, slots, n),
                    256).astype(np.int32)
    mask = (rng.rand(n) < 0.9).astype(np.float32)
    gh = np.stack([rng.randint(-16, 17, n) / 8.0 * mask,
                   rng.randint(1, 17, n) / 8.0 * mask, mask])
    return binned, slot, gh.astype(np.float32)


def _numpy_onehot(binned, row_codes):
    """[sum(row_codes), n]: column f's one-hot at `row_codes[f]` codes,
    stacked as `_wave_kernel` stacks them."""
    return np.concatenate([
        (binned[f][None, :] == np.arange(c)[:, None])
        for f, c in enumerate(row_codes)]).astype(np.float32)


def _packed_or(onehot, n):
    """The `build` variant's reduction of a one-hot [M, n]: any 1 over
    the 128-lane pieces of the rows, two one-hot rows a 32-bit word as a
    TPU packs bf16 (row 2k the low half: 1.0 is 0x3F80)."""
    hit = onehot.reshape(len(onehot), n // 128, 128).max(axis=1)
    hit = hit.astype(np.int32) * 0x3F80
    return hit[0::2] | (hit[1::2] << 16)


@pytest.mark.parametrize("codes,max_bin,slots", [
    ((24,) * 5, 24, 16),                # one class: [5 x 24] in one block
    ((5, 16, 17, 40, 33, 9), 40, 8),    # classes 16 x 3, 48 x 3: classed
    ((40,) * 6, 40, 255)])              # two slot groups of 128
def test_roof_probe_variants_are_the_kernels_blocks(interpret_pallas, codes,
                                                    max_bin, slots):
    """The probe times the kernel's dot without its one-hot and the
    one-hot without its dot; that they ARE the kernel's is held here: the
    `dot` variant fed the kernel's own one-hot (in place of its constant)
    returns the kernel's histograms and counts bit for bit, and the
    `build` variant's reduction is that one-hot's packed words OR-ed over
    the 128-lane pieces of every row tile."""
    from lightgbm_tpu.ops.histogram import (build_histogram_wave,
                                            hist_classes_of, wave_slot_pad)
    from tools.hist_roof_probe import variant
    n = 1536                                       # shapes of no other test
    classes, order = hist_classes_of(codes)
    classed = len(classes) > 1
    codes = np.asarray(codes)[order]
    binned, slot, gh = _probe_operands(codes, n, slots)
    kw = dict(max_bin=max_bin, num_slots=slots,
              hist_classes=classes if classed else ())
    args = (jnp.asarray(binned), jnp.asarray(slot), jnp.asarray(gh))
    hist, cnt = build_histogram_wave(*args, **kw)
    row_codes = (-(-codes // 16) * 16 if classed
                 else np.full(len(codes), -(-max_bin // 8) * 8))
    onehot = _numpy_onehot(binned, row_codes)
    (out, cnt_v), = variant("dot", *args, **kw,
                            onehot=jnp.asarray(onehot, jnp.bfloat16))
    # [M, (s, c, lg)] -> [NL, M, C], against the kernel's [NL, F, B, C]
    NLp = wave_slot_pad(slots)
    NLg = min(NLp, 128)
    out = np.asarray(out).reshape(len(onehot), NLp // NLg, 2, NLg)
    out = out.transpose(1, 3, 0, 2).reshape(NLp, len(onehot), 2)[:slots]
    r0 = 0
    for f, c in enumerate(row_codes):
        keep = min(int(c), max_bin)
        assert np.array_equal(out[:, r0:r0 + keep],
                              np.asarray(hist)[:, f, :keep]), f
        r0 += int(c)
    assert np.array_equal(np.asarray(cnt_v)[0, :slots], np.asarray(cnt))
    assert np.asarray(hist).any()
    built, = variant("build", *args, **kw)
    assert np.array_equal(np.asarray(built), _packed_or(onehot, n))
    # the constant-fed dot (what the chip times) runs in the same blocks
    (const, _), = variant("dot", *args, **kw)
    assert const.shape == (len(onehot), 2 * NLp) and np.asarray(const).any()


def test_roof_probe_variants_run_in_feature_groups(interpret_pallas):
    """The wide cell's path: 2,000... here 200 columns at 128 slots run
    in the plan's feature groups, each variant over the same grid."""
    from lightgbm_tpu.ops.histogram import plan_wave_kernel
    from tools.hist_roof_probe import variant
    F, B, n, slots = 200, 63, 1024, 128
    plan = plan_wave_kernel(F, B, slots)
    assert plan.groups > 1
    binned, slot, gh = _probe_operands((B,) * F, n, slots)
    args = (jnp.asarray(binned), jnp.asarray(slot), jnp.asarray(gh))
    kw = dict(max_bin=B, num_slots=slots)
    (out, cnt), = variant("dot", *args, **kw)
    assert out.shape == (plan.onehot_rows, 256) and cnt.shape == (8, 128)
    built, = variant("build", *args, **kw)
    onehot = _numpy_onehot(binned, np.full(F, 64))
    assert np.array_equal(np.asarray(built)[:F * 32], _packed_or(onehot, n))
