"""The two plans that decide which program a run takes, asked on the CPU
for both backends: `ops/histogram.py plan_wave_kernel` (which kernel a wave
runs, in what blocks) and `learner/select.py plan_growth` (which engine and
histogram method a booster takes).

The pins of (a) are what the benchmark's cells ran on the chip (the PR 31
ledger lines' `device_ops`), taken by asking the parent's predicates at
each shape before PR 32 deleted them; `max_bin` is what each cell's booster
derives (`grow_params.max_bin`: 255 and 63, no bundles).  If `auto` stopped
choosing `wave` + `pallas` for Higgs or Epsilon, the only other witnesses
are `chip_smoke.py` and a ~7x `iter_ms` on the chip.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.learner import plan_growth
from lightgbm_tpu.ops.histogram import (plan_wave_kernel, spike_true_slots,
                                        wave_slot_pad)

HIGGS_255, HIGGS_63, EPSILON_63 = (28, 255), (28, 63), (2000, 63)
# the one-hot cell under EFB: device columns x the largest column's codes
# (700 features at 63 bins in 12 columns on the chip, 13 in the issue's
# scratch run), which is the shape the kernel runs
EXPO_BUNDLED, EXPO_BUNDLED_13 = (12, 255), (13, 255)
LADDER = (1, 2, 4, 8, 16, 32, 64, 128)      # true slots of a 255-leaf tree


def _ladder_plan(shape, true_slots, **kw):
    """The plan of a ladder wave as `learner/wave.py` asks for it."""
    return plan_wave_kernel(*shape, wave_slot_pad(true_slots), true_slots,
                            **kw)


# ------------------------------------------------ (a) the benchmark's shapes
@pytest.mark.parametrize("shape,hl_splits", [
    # 255 bins: `_hl` [28,64,64] / [28,64,32] in the ledger are (64, 4) at
    # 8 and 4 slots; `wave` from 16 slots up
    (HIGGS_255, {1: (32, 8), 2: (32, 8), 4: (64, 4), 8: (64, 4)}),
    # 63 bins: `_hl` [28,16,16] only, for the 1 / 1 / 2-slot waves
    (HIGGS_63, {1: (16, 4), 2: (16, 4)}),
    # Epsilon: never `_hl` (its ungrouped blocks want 116 MB at one slot)
    (EPSILON_63, {}),
    # bundle columns at 255 codes: Higgs-255's splits on fewer columns
    (EXPO_BUNDLED, {1: (32, 8), 2: (32, 8), 4: (64, 4), 8: (64, 4)}),
    (EXPO_BUNDLED_13, {1: (32, 8), 2: (32, 8), 4: (64, 4), 8: (64, 4)}),
])
def test_ladder_kernels_are_the_ledgers(shape, hl_splits):
    for ts in LADDER:
        plan = _ladder_plan(shape, ts)
        assert plan.fits
        if ts in hl_splits:
            assert (plan.kernel, plan.hl_split) == ("wave_hl", hl_splits[ts])
        else:
            assert plan.kernel == "wave", (shape, ts)


@pytest.mark.parametrize("shape,at_256_slots", [
    # the chain tail's `while` traces a 256-slot call that never runs at
    # Higgs: at 255 bins it is 4 groups of 8 over F padded to 32 (22 MB as
    # one block), which is why `hist_groups_per_call` reads 1.3333 there
    # and 1.0 at 63 bins (the counter is by traced signature)
    (HIGGS_255, (32, 8, 4)), (HIGGS_63, (28, 28, 1))])
def test_higgs_full_kernel_is_one_unpadded_block(shape, at_256_slots):
    for ts in LADDER:
        plan = plan_wave_kernel(*shape, wave_slot_pad(ts))
        assert (plan.feature_pad, plan.feature_group, plan.groups) == (
            28, 28, 1)
    plan = plan_wave_kernel(*shape, wave_slot_pad(255))
    assert (plan.feature_pad, plan.feature_group,
            plan.groups) == at_256_slots


@pytest.mark.parametrize("shape", [EXPO_BUNDLED, EXPO_BUNDLED_13])
def test_bundle_columns_are_one_unpadded_block(shape):
    """12 or 13 columns are neither a multiple of 8 nor Higgs' 28: every
    call of a 255-leaf tree, the chain tail's 256-slot one too, is the
    full kernel's one block over the unpadded columns."""
    for slots in LADDER + (255,):
        plan = plan_wave_kernel(*shape, wave_slot_pad(slots))
        assert (plan.kernel, plan.feature_pad, plan.feature_group,
                plan.groups, plan.fits) == ("wave", shape[0], shape[0], 1,
                                            True)


@pytest.mark.parametrize("slots,group,groups", [
    (8, 80, 25), (16, 80, 25), (32, 40, 50), (64, 40, 50), (128, 40, 50)])
def test_epsilon_feature_groups(slots, group, groups):
    plan = plan_wave_kernel(*EPSILON_63, slots)
    assert (plan.kernel, plan.feature_pad, plan.feature_group,
            plan.groups) == ("wave", 2000, group, groups)
    assert plan.vmem_bytes <= 6 << 20


# ------------------------------------------------------ (b) the gate's edges
@pytest.mark.parametrize("features,max_bin,slots", [
    (240, 63, 8), (232, 63, 1), (128, 63, 128), (32, 255, 128),
    (20, 255, 255)])
def test_edge_shapes_of_the_comment_block_are_one_full_block(
        features, max_bin, slots):
    plan = plan_wave_kernel(features, max_bin, slots)
    assert (plan.feature_pad, plan.feature_group, plan.groups) == (
        features, features, 1)
    assert 15.0e6 <= plan.vmem_bytes <= 16 << 20


def test_one_feature_past_the_full_block_is_grouped():
    assert plan_wave_kernel(240, 63, 8).groups == 1
    plan = plan_wave_kernel(241, 63, 8)
    assert (plan.feature_pad, plan.feature_group, plan.groups) == (
        248, 8, 31)         # 248 = 8 x 31: the only 8-multiple divisors


@pytest.mark.parametrize("max_bin,slots,fits", [
    (255, 895, True), (255, 1023, False), (63, 2047, True),
    (63, 4095, False)])
def test_fits_is_the_smallest_groups(max_bin, slots, fits):
    """Against the compiler at 255 bins: tests/test_chip_compile.py."""
    assert plan_wave_kernel(28, max_bin, slots).fits is fits
    assert plan_wave_kernel(2000, max_bin, slots).fits is fits


@pytest.mark.parametrize("shape", [HIGGS_255, HIGGS_63, EPSILON_63])
def test_int8_and_unknown_true_slots_never_give_hl(shape):
    for ts in LADDER:
        assert _ladder_plan(shape, ts, int8=True).kernel == "wave"
        plan = plan_wave_kernel(*shape, wave_slot_pad(ts))
        assert (plan.kernel, plan.hl_split) == ("wave", None)


def test_hl_at_any_slots_implies_hl_at_one():
    """`learner/wave.py` builds the row-major copy of the bins where the
    plan gives `wave_hl` at ONE slot and hands it to every `wave_hl` wave:
    both gates must only close as the slots grow."""
    served = 0
    for features in (1, 8, 28, 100, 232, 500, 2000):
        for max_bin in (4, 16, 63, 64, 255, 256, 1023, 4095):
            at_one = plan_wave_kernel(features, max_bin, 8, 1).kernel
            for ts in (2, 3, 4, 8, 16, 17, 32, 64, 128, 255):
                kernel = _ladder_plan((features, max_bin), ts).kernel
                served += kernel == "wave_hl"
                assert kernel == "wave" or at_one == "wave_hl", (
                    features, max_bin, ts)
    assert served > 20


def test_spike_waves_name_at_most_16_true_slots():
    assert [spike_true_slots(k) for k in (1, 16, 17, 127)] == [
        1, 16, None, None]


# -------------------------------------------------------- (c) plan_growth
def _growth(shape=HIGGS_255, **kw):
    args = dict(backend="tpu", strategy="auto", num_leaves=255,
                num_features=shape[0], max_bin=shape[1], gpu_use_dp=False,
                pinned_leafwise=False, row_mesh=False, voting=False)
    args.update(kw)
    return plan_growth(**args)


@pytest.mark.parametrize("shape,row_mesh", [
    (HIGGS_255, False), (HIGGS_63, False), (EPSILON_63, False),
    (HIGGS_255, True),      # the four dense configurations; dp4 shards rows
    (EXPO_BUNDLED, False), (EXPO_BUNDLED_13, False)])
def test_a_tpu_takes_wave_and_pallas_for_every_configuration(shape,
                                                             row_mesh):
    assert _growth(shape, row_mesh=row_mesh) == (
        "wave", "pallas", row_mesh, ())


@pytest.mark.parametrize("kw,want", [
    (dict(backend="cpu"), ("leafwise", "segment", False)),
    (dict(backend="cpu", strategy="wave"), ("wave", "segment", False)),
    (dict(backend="cpu", row_mesh=True), ("leafwise", "segment", False)),
    (dict(gpu_use_dp=True), ("leafwise", "onehot_hp", False)),
    (dict(num_leaves=7), ("leafwise", "pallas", False)),
    (dict(strategy="leafwise"), ("leafwise", "pallas", False)),
    (dict(strategy="leafwise", row_mesh=True),
     ("leafwise", "segment", False)),
    (dict(pinned_leafwise=True, voting=True, row_mesh=True),
     ("leafwise", "segment", False)),
    (dict(strategy="wave", row_mesh=True), ("wave", "pallas", True)),
    (dict(num_leaves=1023), ("leafwise", "pallas", False)),   # not `fits`
])
def test_growth_plan_without_warnings(kw, want):
    assert _growth(**kw) == want + ((),)


def test_pinned_mode_overrides_wave_with_a_warning():
    plan = _growth(strategy="wave", pinned_leafwise=True)
    assert plan[:3] == ("leafwise", "pallas", False)
    assert plan.warnings == (
        "voting / intermediate monotone / lazy CEGB use the leaf-wise "
        "engine",)


@pytest.mark.parametrize("kw", [dict(num_leaves=1023),
                                dict(gpu_use_dp=True)])
def test_forced_wave_without_the_pallas_kernel_warns(kw):
    plan = _growth(strategy="wave", **kw)
    assert plan.strategy == "wave" and not plan.sharded_wave
    assert len(plan.warnings) == 1 and "[F, n, B]" in plan.warnings[0]


# ------------------------------------------ (d) a booster takes the plan's
@pytest.mark.parametrize("extra", [
    {}, {"tpu_growth_strategy": "wave"}, {"gpu_use_dp": True},
    {"monotone_constraints": [1, 0, 0, 0, 0, 0],
     "monotone_constraints_method": "intermediate",
     "tpu_growth_strategy": "wave"}])
def test_a_cpu_booster_reports_the_plans_choice(extra):
    rng = np.random.RandomState(0)
    X = rng.rand(600, 6).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "verbosity": -1, **extra}
    g = lgb.Booster(params=params,
                    train_set=lgb.Dataset(X, label=y, params=params))._gbdt
    plan = plan_growth(
        backend="cpu", strategy=params.get("tpu_growth_strategy", "auto"),
        num_leaves=15, num_features=6, max_bin=g.grow_params.max_bin,
        gpu_use_dp=bool(extra.get("gpu_use_dp")),
        pinned_leafwise=g.grow_params.monotone_intermediate,
        row_mesh=False, voting=False)
    assert (g.growth_strategy, g.grow_params.hist_method) == plan[:2]
    assert g.growth_strategy == (
        "wave" if extra == {"tpu_growth_strategy": "wave"} else "leafwise")


# --------------------------- (e) the shape a booster asks the plan about
def _asked_shape(monkeypatch, X, **params):
    """(num_features, max_bin) a booster over `X` hands `plan_growth`,
    and the booster."""
    from lightgbm_tpu.boosting import gbdt
    asked = {}

    def spy(**kw):
        asked.update(kw)
        return plan_growth(**kw)
    monkeypatch.setattr(gbdt, "plan_growth", spy)
    y = (np.arange(X.shape[0]) % 3 == 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "min_data_in_leaf": 5, "verbosity": -1, **params}
    g = lgb.Booster(params=params,
                    train_set=lgb.Dataset(X, label=y, params=params))._gbdt
    return (asked["num_features"], asked["max_bin"]), g


def test_without_bundles_the_plan_is_asked_about_features_x_max_bin(
        monkeypatch):
    """Every dense cell: device columns are the used features and the
    kernel's bins the booster's `max_bin`, as before the plan was asked
    about the kernel's own shape."""
    X = np.random.RandomState(0).rand(600, 6).astype(np.float32)
    shape, g = _asked_shape(monkeypatch, X)
    assert g.bundle_plan is None and not g.grow_params.has_bundles
    assert shape == (6, g.grow_params.max_bin) == (
        len(g.f_num_bin), int(g.f_num_bin.max()))
    assert g.binned_dev.shape[0] == 6


def test_under_bundles_the_plan_is_asked_about_the_kernels_shape(
        monkeypatch):
    """40 exclusive one-hot columns at 63 bins: one device column of 81
    codes, and that — not 40 x 2 — is what the kernel plan is asked."""
    from scipy import sparse
    n, F = 800, 40
    X = sparse.csr_matrix((np.ones(n, np.float32),
                           (np.arange(n), np.arange(n) % F)), shape=(n, F))
    shape, g = _asked_shape(monkeypatch, X)
    assert g.grow_params.has_bundles
    assert shape == (g.binned_dev.shape[0], g.grow_params.group_max_bin)
    assert shape == (1, 2 * F + 1) and len(g.f_num_bin) == F
    assert g.grow_params.max_bin == 2
