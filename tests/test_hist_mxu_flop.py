"""What a histogram kernel call asks of the MXU, and the label that says
so (`ops/histogram.py mxu_flop_per_row`, `mxu_call_scope`), on the CPU.

The counts are pinned at the shapes the benchmark's cells run and held
to ROADMAP S1's table: FLOP a row x the cell's padded rows at
`peaks.json`'s 197e12 bf16 FLOP/s is the table's "reckoned" ms a call,
which the ledger's measured calls stand 2-18% over.  The padding rule is
the function's docstring: M to 8, K and N to 128."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.observability import global_registry
from lightgbm_tpu.ops.histogram import (build_histogram_rows_pallas,
                                        hist_classes_of, mxu_flop_per_row,
                                        plan_wave_kernel, wave_histograms,
                                        wave_slot_pad)
from test_kernel_plan import (EXPO_BUNDLED, EXPO_CLASSES, EXPO_CODES,
                              MSLR_63, MSLR_CLASSES, MSLR_CODES)

PEAK = 197e12
HIGGS_ROWS, EPSILON_ROWS = 2_625_536, 400_384
MSLR_ROWS, EXPO_ROWS = 2_271_232, 11_000_832
COUNT_DOT = 2 * 8 * 128         # [8, Rt] x [NLg <= 128, Rt], a row


def _ms(flop_per_row, rows):
    return 1000.0 * flop_per_row * rows / PEAK


# shape, classes, padded rows, the one-hot rows a call builds, and the
# table's ms a call to 64 slots / at 128 (ROADMAP S1; the two classed
# lines from ISSUE 39's motivation)
FULL_KERNEL = [
    ((28, 255), (), HIGGS_ROWS, (7168, 7168), (24.5, 48.9)),
    ((28, 63), (), HIGGS_ROWS, (1792, 1792), (6.11, 12.2)),
    # 2,000 columns in 25-50 feature groups: the groups' rows add up
    ((2000, 63), (), EPSILON_ROWS, (128000, 128000), (66.6, 133.2)),
    # unclassed: 137 columns in one block, 144 in three groups at 128
    (MSLR_63, (), MSLR_ROWS, (8768, 9216), (25.9, 54.4)),
    (MSLR_63, MSLR_CLASSES, MSLR_ROWS, (7392, 7392), (21.8, 43.6)),
    (EXPO_BUNDLED, EXPO_CLASSES, EXPO_ROWS, (1200, 1200), (17.2, 34.3)),
]


@pytest.mark.parametrize("shape,classes,rows,onehot,table_ms", FULL_KERNEL)
@pytest.mark.parametrize("slots", [16, 64, 128])
def test_full_kernel_count_is_the_roadmaps_table(shape, classes, rows,
                                                 onehot, table_ms, slots):
    plan = plan_wave_kernel(*shape, slots, hist_classes=classes)
    assert plan.kernel == "wave"
    M = onehot[slots == 128]
    assert plan.onehot_rows == M
    # N: 2 channels x the slot group, one 128-column tile to 64 slots
    # and two at 128; the count dot once a row tile
    hist = 2 * M * (256 if slots == 128 else 128)
    assert mxu_flop_per_row(plan, shape[0], slots) == hist + COUNT_DOT
    want = table_ms[slots == 128]
    digits = 2 if want < 10 else 1
    assert round(_ms(hist, rows), digits) == want


def test_the_headline_shape_to_the_flop():
    """`[28 x 256]`: 1,835,008 FLOP a row to 64 slots and twice that at
    128; at 255 slots two slot groups of 128 over 32 padded columns in
    four feature groups (8,192 one-hot rows), the count dot once a slot
    group."""
    for slots, hist, groups in ((1, 1_835_008, 1), (8, 1_835_008, 1),
                                (64, 1_835_008, 1), (128, 3_670_016, 1),
                                (255, 2 * 2 * 8192 * 256, 2)):
        plan = plan_wave_kernel(28, 255, slots)
        assert (mxu_flop_per_row(plan, 28, slots)
                == hist + groups * COUNT_DOT), slots
    assert round(_ms(1_835_008, HIGGS_ROWS), 1) == 24.5


def test_class_groups_each_run_their_count_dot():
    """The ranking cell's chain tail (256 slots): two `pallas_call`s over
    7,392 one-hot rows in all, two slot groups each."""
    plan = plan_wave_kernel(*MSLR_63, 256, hist_classes=MSLR_CLASSES)
    assert len(plan.class_groups) == 2
    assert mxu_flop_per_row(plan, 137, 256) == 2 * (
        2 * 7392 * 256 + 2 * COUNT_DOT)


@pytest.mark.parametrize("shape,slots,split,pack,by_hand", [
    # main: 7 dots [4 x 32, Rt] x [Rt, 4 x 8 x 2 -> 128]; Wd 28 x 8 x 2 =
    # 448 -> 512; d: K 29 -> 128; wt: K 2 -> 128
    ((28, 255), 1, (32, 8), 4,
     7 * 2 * 128 * 128 + 2 * 128 * 512 + 2 * 128 * 512 + COUNT_DOT),
    # main: 7 dots [4 x 64, Rt] x [Rt, 4 x 4 x 16 = 256]; Wd 1,792
    ((28, 255), 8, (64, 4), 4,
     7 * 2 * 256 * 256 + 2 * 128 * 1792 + 2 * 128 * 1792 + COUNT_DOT),
    # 137 columns pack one a dot: [16, Rt] x [Rt, 8 -> 128]; Wd 1,096 ->
    # 1,152; d: K 138 -> 256
    ((137, 63), 1, (16, 4), 1,
     137 * 2 * 16 * 128 + 2 * 256 * 1152 + 2 * 128 * 1152 + COUNT_DOT),
])
def test_decomposed_kernel_count_by_hand(shape, slots, split, pack,
                                         by_hand):
    from lightgbm_tpu.ops.histogram import _hl_pack
    plan = plan_wave_kernel(*shape, wave_slot_pad(slots), slots)
    assert (plan.kernel, plan.hl_split) == ("wave_hl", split)
    assert _hl_pack(shape[0], split[0]) == pack
    assert mxu_flop_per_row(plan, shape[0], slots) == by_hand


def test_decomposed_kernel_at_8_slots_asks_what_the_full_kernel_asks():
    """255 bins, 8 slots: 1,837,056 FLOP a row through either kernel —
    and the ledger's calls cost the same (`_hl` 25.75 ms, the full
    kernel 25.25-25.39: ROADMAP S1 (2))."""
    hl = plan_wave_kernel(28, 255, 8, 8)
    full = plan_wave_kernel(28, 255, 8)
    assert (hl.kernel, full.kernel) == ("wave_hl", "wave")
    assert (mxu_flop_per_row(hl, 28, 8) == mxu_flop_per_row(full, 28, 8)
            == 1_837_056)


def _labels(fn, *args):
    """The `Hist.mxu_*` parts on the name stacks of a traced call."""
    found = []
    for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns:
        found += re.findall(r"Hist\.mxu_n\d+_f\d+_e\d+",
                            str(eqn.source_info.name_stack))
    return sorted(set(found))


def _ladder_labels(codes, max_bin, n=1024):
    """The label of each ladder wave of a table whose columns hold
    `codes`, as `learner/wave.py hists_of` calls `wave_histograms`."""
    F = len(codes)
    classes, _ = hist_classes_of(codes)
    u8, i32 = jnp.uint8, jnp.int32
    out = {}
    for ts in (1, 2, 4, 8, 16, 64, 128):
        def call(b, brm, slot, gh, bc, inv, ts=ts):
            return wave_histograms(
                b, brm, slot, gh, max_bin=max_bin,
                num_slots=wave_slot_pad(ts), true_slots=ts,
                hist_classes=classes if len(classes) > 1 else (),
                binned_classed=bc, hist_inverse=inv)
        labels = _labels(call, jnp.zeros((F, n), u8), jnp.zeros((n, F), u8),
                         jnp.zeros(n, i32), jnp.zeros((3, n), jnp.float32),
                         jnp.zeros((F, n), u8), jnp.zeros(F, i32))
        assert len(labels) == 1, labels
        out[ts] = labels[0]
    return out


@pytest.mark.parametrize("codes,max_bin,onehot", [
    (EXPO_CODES, 255, 1200), (MSLR_CODES, 63, 7392)])
def test_label_is_one_for_three_column_orders(codes, max_bin, onehot):
    """Correction (m): the label is a function of shapes and the sorted
    class multiset, so every `--seed` of a cell traces the same program
    text.  The waves of 2 and 4 true slots are both padded to 8 and say
    n = 2 x 2 and 2 x 4, not 2 x 8."""
    codes = np.asarray(codes)
    tables = [codes] + [codes[np.random.RandomState(s).permutation(
        len(codes))] for s in (1, 2)]
    assert len({tuple(t.tolist()) for t in tables}) == 3
    ladders = [_ladder_labels(tuple(t.tolist()), max_bin) for t in tables]
    assert ladders[0] == ladders[1] == ladders[2]
    full = 2 * onehot * 128 + COUNT_DOT
    assert wave_slot_pad(2) == wave_slot_pad(4) == 8
    for ts in (2, 4, 8, 16, 64):
        assert ladders[0][ts] == f"Hist.mxu_n{2 * ts}_f{full}_e1"
    assert ladders[0][128] == (
        f"Hist.mxu_n256_f{2 * onehot * 256 + COUNT_DOT}_e1")
    # the 1-slot waves keep the decomposed kernel in both cells
    plan = plan_wave_kernel(len(codes), max_bin, 8, 1,
                            hist_classes=hist_classes_of(codes)[0])
    assert ladders[0][1] == "Hist.mxu_n2_f%d_e1" % mxu_flop_per_row(
        plan, len(codes), 1)


def test_a_wave_that_names_no_true_slots_says_its_padded_bound():
    """The chain tail's `while_loop` wave (`learner/wave.py`): 128
    computed slots, none of them named."""
    def call(b, slot, gh):
        return wave_histograms(b, None, slot, gh, max_bin=255,
                               num_slots=128)
    assert _labels(call, jnp.zeros((28, 1024), jnp.uint8),
                   jnp.zeros(1024, jnp.int32),
                   jnp.zeros((3, 1024), jnp.float32)) == [
        f"Hist.mxu_n256_f{2 * 7168 * 256 + COUNT_DOT}_e1"]


def test_class_groups_are_counted_in_e():
    classes = MSLR_CLASSES

    def call(b, slot, gh, bc, inv):
        return wave_histograms(b, None, slot, gh, max_bin=63,
                               num_slots=256, hist_classes=classes,
                               binned_classed=bc, hist_inverse=inv)
    b = jnp.zeros((137, 1024), jnp.uint8)
    assert _labels(call, b, jnp.zeros(1024, jnp.int32),
                   jnp.zeros((3, 1024), jnp.float32), b,
                   jnp.zeros(137, jnp.int32)) == [
        "Hist.mxu_n512_f%d_e2" % (2 * (2 * 7392 * 256 + 2 * COUNT_DOT))]


def test_leaf_wise_kernel_says_one_leafs_two_columns():
    """`build_histogram_rows`: `[Fp x Bp, Rt] x [Rt, 2 -> 128]`, the 28
    columns padded to 32 and the 255 bins to 256."""
    def call(rows, gh, mask):
        return build_histogram_rows_pallas(rows, gh, mask, max_bin=255)
    closed = jax.make_jaxpr(call)(
        jnp.zeros((1024, 28), jnp.uint8), jnp.zeros((1024, 2), jnp.float32),
        jnp.zeros(1024, jnp.float32))
    inner = closed.jaxpr.eqns[0].params["jaxpr"].jaxpr
    stacks = {str(e.source_info.name_stack) for e in inner.eqns
              if e.primitive.name == "pallas_call"}
    assert stacks == {f"Hist.mxu_n2_f{2 * 32 * 256 * 128}_e1/"
                      "build_histogram_rows"}


# ------------------------------------------- `hist_codes`, at booster init
def _booster(X, **extra):
    y = (X[:, 0] > np.median(X[:, 0])).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
              "min_data_in_bin": 1, "min_data_in_leaf": 5,
              "verbosity": -1, **extra}
    before = global_registry.counter("hist_codes")
    g = lgb.Booster(params=params,
                    train_set=lgb.Dataset(X, label=y, params=params))._gbdt
    return g, global_registry.counter("hist_codes") - before


def test_hist_codes_of_an_unbundled_booster_is_its_columns_codes():
    rng = np.random.RandomState(39)
    X = np.stack([rng.rand(800), rng.randint(0, 5, 800),
                  rng.randint(0, 17, 800), rng.rand(800)],
                 axis=1).astype(np.float32)
    g, added = _booster(X)
    assert g.bundle_plan is None
    assert g.f_num_bin.tolist() == [63, 5, 17, 63]
    assert added == 63 + 5 + 17 + 63


def test_hist_codes_of_a_bundled_booster_is_its_device_columns_codes():
    """One-hot groups of 12, 7 and 22 columns that EFB packs into a
    column each (a shared first code and two a member: 25, 15, 45) beside
    a numeric one (63): the kernel sees the bundle columns, so their code
    counts are what a histogram must multiply."""
    rng = np.random.RandomState(39)
    n = 2000
    blocks = []
    for width in (12, 7, 22):
        hot = np.zeros((n, width), np.float32)
        hot[np.arange(n), rng.randint(0, width, n)] = 1.0
        blocks.append(hot)
    X = np.concatenate(blocks + [rng.rand(n, 1).astype(np.float32)], axis=1)
    g, added = _booster(X, enable_bundle=True)
    assert g.bundle_plan is not None
    columns = np.asarray(g.bundle_plan.group_num_bin)
    assert len(columns) == g.binned_dev.shape[0] == 4 < X.shape[1]
    assert sorted(columns.tolist()) == [15, 25, 45, 63]
    assert added == 15 + 25 + 45 + 63
