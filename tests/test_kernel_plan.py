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
from lightgbm_tpu.ops.histogram import (hist_classes_of, plan_wave_kernel,
                                        spike_true_slots, wave_slot_pad)
from tools.kernel_checks import EXPO_CODES, MSLR_CODES

HIGGS_255, HIGGS_63, EPSILON_63 = (28, 255), (28, 63), (2000, 63)
# the one-hot cell under EFB: device columns x the largest column's codes
# (700 features at 63 bins in 12 columns on the chip, 13 in the issue's
# scratch run), which is the shape the kernel runs
EXPO_BUNDLED, EXPO_BUNDLED_13 = (12, 255), (13, 255)
LADDER = (1, 2, 4, 8, 16, 32, 64, 128)      # true slots of a 255-leaf tree
# the ranking cell: 137 columns at 63 bins, 45 of them integer-valued
# with 4-32 codes; and both cells' columns by histogram class
# (`hist_classes_of`: codes rounded up to 16), as their boosters hold them
MSLR_63 = (137, 63)
MSLR_CLASSES = ((16, 24), (32, 7), (64, 106))
EXPO_CLASSES = ((16, 2), (32, 1), (48, 2), (64, 3), (128, 1), (208, 1),
                (256, 2))


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


def test_ranking_cell_unclassed_plan():
    """(137, 63) with every column at 63 bins — the program before the
    one-hot was classed, and any 137-column table of one class: `_hl`
    for the 1-slot waves alone (2 slots: 11.2 MB of expander products
    against `_HL_VMEM`), one 137-column block to 64 slots, three groups
    of 48 over 144 padded columns at 128."""
    for ts in LADDER:
        plan = _ladder_plan(MSLR_63, ts)
        assert plan.kernel == ("wave_hl" if ts == 1 else "wave")
        full = plan_wave_kernel(*MSLR_63, wave_slot_pad(ts))
        assert (full.feature_pad, full.feature_group, full.groups,
                full.onehot_rows) == ((144, 48, 3, 9216) if ts == 128
                                      else (137, 137, 1, 8768))
    assert _ladder_plan(MSLR_63, 1).hl_split == (16, 4)


# -------------------------- (a') several classes of column codes (PR 38)
def test_classes_are_the_sorted_multiset_of_the_columns_codes():
    assert hist_classes_of(MSLR_CODES)[0] == MSLR_CLASSES
    assert hist_classes_of(EXPO_CODES)[0] == EXPO_CLASSES
    # 113 / 45 codes (the plan of another seed) are the same classes
    assert EXPO_CODES[9:] == (115, 43, 2)
    other = EXPO_CODES[:9] + (113, 45, 2)
    assert hist_classes_of(other)[0] == EXPO_CLASSES
    # the order: a stable sort by class, whatever the columns' order
    classes, order = hist_classes_of((63, 5, 255, 17, 16, 64))
    assert classes == ((16, 2), (32, 1), (64, 2), (256, 1))
    assert order.tolist() == [1, 4, 3, 0, 5, 2] and order.dtype == np.int32
    # one class: every dense cell; past 256 codes (bin groups) always
    assert hist_classes_of((255,) * 28)[0] == ((256, 28),)
    assert hist_classes_of((63,) * 2000)[0] == ((64, 2000),)
    assert hist_classes_of((1000, 20, 63))[0] == ((1008, 3),)


@pytest.mark.parametrize("shape,classes,rows,hl_slots", [
    # 7,392 one-hot rows a tile for 8,768: `_hl` keeps the 1-slot waves
    # (24 <= 0.6 x 54.0 codes a column; at 2 slots `_HL_VMEM` refuses)
    (MSLR_63, MSLR_CLASSES, 7392, (1,)),
    # 1,200 for 3,072: the 2-, 4- and 8-slot waves leave `_hl`, whose
    # 64 / 80 / 96 lane-units are over 0.6 x 100 codes a column
    (EXPO_BUNDLED, EXPO_CLASSES, 1200, (1,))])
def test_classed_plans_of_the_two_cells(shape, classes, rows, hl_slots):
    for ts in LADDER:
        plan = _ladder_plan(shape, ts, hist_classes=classes)
        assert plan.fits
        assert plan.kernel == ("wave_hl" if ts in hl_slots else "wave")
        full = plan_wave_kernel(*shape, wave_slot_pad(ts),
                                hist_classes=classes)
        # one block over the unpadded, class-ordered columns
        assert (full.kernel, full.feature_pad, full.feature_group,
                full.groups, full.onehot_rows) == (
                    "wave", shape[0], shape[0], 1, rows)
        assert full.class_groups == (classes,)
        assert full.vmem_bytes == rows * (wave_slot_pad(ts) * 8 + 1024)
        assert full.vmem_bytes <= 16 << 20
    # the decomposed kernel itself is the unclassed plan's, split and all
    assert (_ladder_plan(shape, 1, hist_classes=classes).hl_split
            == _ladder_plan(shape, 1).hl_split)


def test_ranking_cell_chain_tail_is_two_class_groups():
    """256 slots (the chain tail's traced call): 3,072 B a one-hot row,
    so 5,461 rows a call — the 64-code class is cut after 75 columns."""
    plan = plan_wave_kernel(*MSLR_63, 256, hist_classes=MSLR_CLASSES)
    assert plan.class_groups == (((16, 24), (32, 7), (64, 75)),
                                 ((64, 31),))
    assert (plan.groups, plan.feature_group, plan.onehot_rows) == (
        2, 106, 7392)
    assert plan.vmem_bytes == 5408 * 3072


@pytest.mark.parametrize("shape", [HIGGS_255, HIGGS_63, EPSILON_63,
                                   EXPO_BUNDLED, EXPO_BUNDLED_13, MSLR_63])
def test_one_class_is_the_unclassed_plan_field_for_field(shape):
    """The switch is the NUMBER of classes: a table whose columns share
    a class (Higgs, dp4, Epsilon) gets the plan — and so the call — it
    had before classes existed, at every shape pinned above."""
    one = hist_classes_of((shape[1],) * shape[0])[0]
    assert len(one) == 1
    for ts in LADDER + (255,):
        for true_slots in (None, ts):
            for int8 in (False, True):
                args = (*shape, wave_slot_pad(ts), true_slots)
                assert (plan_wave_kernel(*args, int8=int8, hist_classes=one)
                        == plan_wave_kernel(*args, int8=int8))


def test_int8_operands_keep_one_class():
    for ts in LADDER:
        assert (_ladder_plan(EXPO_BUNDLED, ts, int8=True,
                             hist_classes=EXPO_CLASSES)
                == _ladder_plan(EXPO_BUNDLED, ts, int8=True))


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


# ------------- (f) what is static in the grow program ignores column order
def _table_with_codes(codes, n, seed=38):
    """[n, F] float32 whose column f holds `codes[f]` distinct whole
    numbers (a continuous column where that is the booster's max_bin)."""
    rng = np.random.RandomState(seed)
    top = max(codes)
    return np.stack([rng.rand(n) if c == top and c >= 63
                     else rng.randint(0, c, n) for c in codes],
                    axis=1).astype(np.float32)


def _grow_call_signature(g):
    """What `jax.jit` keys the grow entry's trace on, besides the static
    `params`: the tree structure of the arguments of one
    `train_one_iter`'s grow call and each leaf's shape and dtype."""
    import jax
    seen = []
    inner = g._grow_fn

    def spy(*args, **kw):
        dynamic = (args[:6], kw)            # args[6] is `params` (static)
        seen.append((jax.tree_util.tree_structure(dynamic),
                     [(np.shape(x), np.result_type(x).name)
                      for x in jax.tree_util.tree_leaves(dynamic)]))
        assert args[6] == g.grow_params
        return inner(*args, **kw)
    g._grow_fn = spy
    g.train_one_iter()
    g._grow_fn = inner
    assert len(seen) == 1
    return seen[0]


@pytest.mark.parametrize("codes,max_bin,classes,rows", [
    (MSLR_CODES, 63, MSLR_CLASSES, 3000),
    (EXPO_CODES, 255, EXPO_CLASSES, 8000)])
def test_column_order_reaches_the_grow_program_as_data(codes, max_bin,
                                                       classes, rows):
    """PR 37 was refused for this: the benchmark's `--seed` permutes the
    feature columns, a per-column layout static in column order made
    every seed a new program, and `setup_s` paid a cold compile (38.6 s
    at the ranking cell) on every run.  Boosters on three column
    permutations of one table hold EQUAL `GrowParams` (the jitted
    entry's static argument) and hand the grow entry arguments of equal
    structure, shapes and dtypes: one trace, one executable, one entry
    in the compile cache.  Only `hist_order` / `hist_inverse` differ."""
    X = _table_with_codes(codes, rows)
    y = (X[:, 0] > np.median(X[:, 0])).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": max_bin,
              "min_data_in_bin": 1, "min_data_in_leaf": 5,
              "tpu_growth_strategy": "wave", "verbosity": -1}
    boosters = []
    for seed in (None, 1, 2):
        order = (np.arange(len(codes)) if seed is None
                 else np.random.RandomState(seed).permutation(len(codes)))
        g = lgb.Booster(params=params, train_set=lgb.Dataset(
            X[:, order], label=y, params=params))._gbdt
        assert g.f_num_bin.tolist() == [codes[i] for i in order]
        boosters.append((g, _grow_call_signature(g)))
    first, signature = boosters[0]
    assert first.grow_params.hist_classes == classes
    assert first.growth_strategy == "wave"
    orders = set()
    for g, sig in boosters:
        assert g.grow_params == first.grow_params
        assert hash(g.grow_params) == hash(first.grow_params)
        assert sig == signature
        order = np.asarray(g.meta.hist_order)
        orders.add(tuple(order.tolist()))
        # the order sorts this booster's columns by class, and the
        # inverse undoes it
        cls = -(-g.f_num_bin // 16) * 16
        assert (np.diff(cls[order]) >= 0).all()
        assert (order[np.asarray(g.meta.hist_inverse)]
                == np.arange(len(codes))).all()
    assert len(orders) == 3


def test_a_table_of_one_class_carries_no_order(monkeypatch):
    """Every dense cell: no `hist_classes`, no order in `FeatureMeta`,
    the grow entry's arguments as they were before classes existed."""
    X = np.random.RandomState(0).rand(600, 6).astype(np.float32)
    _, g = _asked_shape(monkeypatch, X)
    assert g.grow_params.hist_classes == ()
    assert g.meta.hist_order is None and g.meta.hist_inverse is None
