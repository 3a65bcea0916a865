"""The train path at Epsilon's width (2,000 dense features, 63 bins), on
the CPU: what the cell `epsilon-400k-b63.train` runs on the chip.

At 28 features `build_histogram_wave` takes one full-F block; past
`F * unit > 16 MB` it runs in feature groups (`_pick_feature_group`),
each re-streaming `slot` and `gh`, with the per-slot row counts riding on
group 0 alone.  No test held that path before PR 30, and the decomposed
kernel (no grouping) does not fit, so every wave of a wide tree takes it.
"""

import functools
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from lightgbm_tpu.learner import FeatureMeta, GrowParams
from lightgbm_tpu.observability import global_registry
from lightgbm_tpu.ops.split import MISSING_NONE, SplitParams

B = 63


@pytest.fixture()
def interpret_pallas(monkeypatch):
    """Every `pl.pallas_call` traced inside the test interprets its
    kernel (shapes of this file are used by no other test)."""
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))


def _bf16_grid(x):
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))


@pytest.mark.parametrize("F,num_slots,groups", [
    (200, 1, 1), (200, 8, 1), (200, 128, 5),
    (2000, 1, 25), (2000, 8, 25), (2000, 128, 50)])
def test_grouped_wave_kernel_equals_numpy(interpret_pallas, F, num_slots,
                                          groups):
    """Sums of every feature group and the per-slot counts — once, not
    once a group — against a float32 numpy histogram.  The operands are
    on the bf16 grid, so the kernel's casts are exact and only the order
    of the float32 adds differs: 1e-5 relative (a bf16 accumulator would
    miss by 1e-2); counts are exact."""
    from lightgbm_tpu.ops.histogram import (build_histogram_wave,
                                            wave_slot_pad)
    n = 1024
    rng = np.random.RandomState(F + num_slots)
    binned = rng.randint(0, B, (F, n)).astype(np.uint8)
    slot = np.where(rng.rand(n) < 0.8, rng.randint(0, num_slots, n),
                    wave_slot_pad(255)).astype(np.int32)
    mask = (rng.rand(n) < 0.9).astype(np.float32)
    gh = np.stack([_bf16_grid(rng.randn(n)) * mask,
                   _bf16_grid(rng.rand(n) * 0.25) * mask, mask])
    want = np.zeros((num_slots, F, B, 2), np.float32)
    inb = np.flatnonzero(slot < num_slots)
    cols = np.broadcast_to(np.arange(F)[:, None], (F, len(inb)))
    for c in range(2):
        np.add.at(want[..., c],
                  (np.broadcast_to(slot[inb], cols.shape), cols,
                   binned[:, inb]),
                  np.broadcast_to(gh[c][inb], cols.shape))
    want_cnt = np.bincount(slot[inb], weights=mask[inb],
                           minlength=num_slots).astype(np.float32)

    before = global_registry.snapshot()["counters"]
    hist, cnt = build_histogram_wave(
        jnp.asarray(binned), jnp.asarray(slot), jnp.asarray(gh),
        max_bin=B, num_slots=num_slots)
    after = global_registry.snapshot()["counters"]
    np.testing.assert_array_equal(np.asarray(cnt), want_cnt)
    np.testing.assert_allclose(np.asarray(hist), want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())
    # the wrapper counted itself where it was traced: one call, and the
    # feature groups that call runs (benchmarks' hist_groups_per_call)
    assert (after.get("hist_kernel_calls", 0)
            - before.get("hist_kernel_calls", 0)) == 1
    assert (after.get("hist_feature_group_passes", 0)
            - before.get("hist_feature_group_passes", 0)) == groups


def test_wide_tree_wave_engine_equals_leafwise_segment(interpret_pallas):
    """A 16-leaf tree at 4,096 x 2,000 from the wave engine (Pallas
    kernel in feature groups, every wave through the full kernel) against
    the leaf-wise engine on exact `segment` histograms of the same bins.
    Depth 4 holds 16 leaves, so both engines split every leaf that can
    split: the same splits; leaf values to 1e-5 (gradients on the bf16
    grid, so the kernels' operand rounding is exact)."""
    from lightgbm_tpu.learner import grow_tree
    from lightgbm_tpu.learner.wave import grow_tree_wave
    n, F, L = 4096, 2000, 16
    rng = np.random.RandomState(30)
    X = rng.rand(n, F).astype(np.float32)
    binned = np.minimum((X * B).astype(np.int64), B - 1).T.astype(np.uint8)
    logit = (4 * (X[:, 7] - 0.5) + 3 * (X[:, 1500] - 0.5) * (X[:, 33] > 0.5)
             + 2 * (X[:, 1999] - 0.5))
    y = rng.rand(n) < 1 / (1 + np.exp(-logit))
    lv = np.where(y, 1.0, -1.0)
    resp = -lv / (1.0 + np.exp(lv * -0.29))
    grad = _bf16_grid(resp)
    hess = _bf16_grid(np.abs(resp) * (1 - np.abs(resp)))
    meta = FeatureMeta(num_bin=jnp.full(F, B, jnp.int32),
                       missing_type=jnp.full(F, MISSING_NONE, jnp.int32),
                       default_bin=jnp.zeros(F, jnp.int32),
                       penalty=jnp.ones(F, jnp.float32))
    args = (jnp.asarray(binned), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(n, jnp.float32), jnp.ones(F, bool), meta)
    base = dict(num_leaves=L, max_bin=B, max_depth=4,
                split=SplitParams(min_data_in_leaf=20))
    # overgrow-and-prune, as the booster runs it: the prune numbers the
    # nodes in the leaf-wise pop order
    t_w, leaf_w = grow_tree_wave(*args, GrowParams(hist_method="pallas",
                                                   wave_prune=True, **base))
    t_l, leaf_l = grow_tree(*args, GrowParams(hist_method="segment",
                                              **base))
    assert int(t_w.num_leaves) == int(t_l.num_leaves) == L
    for f in ("split_feature", "threshold_bin", "left_child", "right_child",
              "leaf_count", "internal_count"):
        np.testing.assert_array_equal(np.asarray(getattr(t_w, f)),
                                      np.asarray(getattr(t_l, f)), err_msg=f)
    np.testing.assert_array_equal(np.asarray(leaf_w), np.asarray(leaf_l))
    np.testing.assert_allclose(np.asarray(t_w.leaf_value),
                               np.asarray(t_l.leaf_value), rtol=1e-5,
                               atol=1e-6)
    assert {7, 1999} <= set(np.asarray(t_w.split_feature).tolist())


def test_wide_tree_builds_no_row_major_bins():
    """`binned.T` serves only the decomposed kernel, which has no feature
    grouping and does not fit at 2,000 features: the traced program holds
    no [n, 2000] array (0.8 GB a tree at 400,000 rows), where at 28
    features it holds the [n, 28] one."""
    from lightgbm_tpu.learner.wave import grow_tree_wave
    n = 2048

    def bins_shapes(F):
        meta = FeatureMeta(num_bin=jnp.full(F, B, jnp.int32),
                           missing_type=jnp.full(F, MISSING_NONE, jnp.int32),
                           default_bin=jnp.zeros(F, jnp.int32),
                           penalty=jnp.ones(F, jnp.float32))
        jaxpr = jax.make_jaxpr(
            lambda b, g, h, m, c: grow_tree_wave(
                b, g, h, m, c, meta,
                GrowParams(num_leaves=255, max_bin=B, hist_method="pallas",
                           split=SplitParams(min_data_in_leaf=20))))(
            jax.ShapeDtypeStruct((F, n), jnp.uint8),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((F,), jnp.bool_))
        return f"u8[{n},{F}]" in str(jaxpr)

    assert bins_shapes(28)
    assert not bins_shapes(2000)


def _first_fit_unbounded(nz, nbins, sample_size, max_conflict_rate):
    """The planner as it was before its search was bounded: every
    feature against every bundle that has bins left, a mask product
    each."""
    from lightgbm_tpu.io.bundle import MAX_BUNDLE_BINS
    nz_cnt = np.array([int(m.sum()) for m in nz], np.int64)
    cap = max_conflict_rate * sample_size
    groups, group_nz, group_conflicts, group_bins = [], [], [], []
    for f in np.argsort(-nz_cnt):
        f = int(f)
        for gi in range(len(groups)):
            if group_bins[gi] + nbins[f] > MAX_BUNDLE_BINS:
                continue
            conflicts = int((group_nz[gi] & nz[f]).sum())
            if group_conflicts[gi] + conflicts <= cap:
                groups[gi].append(f)
                group_nz[gi] = group_nz[gi] | nz[f]
                group_conflicts[gi] += conflicts
                group_bins[gi] += int(nbins[f])
                break
        else:
            groups.append([f])
            group_nz.append(nz[f].copy())
            group_conflicts.append(0)
            group_bins.append(1 + int(nbins[f]))
    return groups


@pytest.mark.parametrize("rate", [0.0, 0.01, 0.1])
@pytest.mark.parametrize("seed", range(4))
def test_bounded_planner_plans_as_the_unbounded_one(seed, rate):
    """One-hot blocks, sparse columns of every density, dense columns
    and overlapping ones: the count test refuses only bundles whose mask
    product would have refused them, so the plans are the same."""
    from lightgbm_tpu.io.bundle import plan_bundles_from_masks
    rng = np.random.RandomState(seed)
    S = 3000
    masks = []
    for _ in range(6):                       # exclusive one-hot blocks
        which = rng.randint(0, 5, S)
        masks += [which == j for j in range(4)]
    for density in (0.001, 0.01, 0.05, 0.2, 0.5, 0.9, 1.0):
        masks += [rng.rand(S) < density for _ in range(4)]
    nz = np.array(masks)[rng.permutation(len(masks))]
    nbins = rng.randint(2, 64, len(nz)).astype(np.int32)
    plan = plan_bundles_from_masks(nz, nbins, np.zeros(len(nz), np.int32),
                                   S, rate)
    assert plan.groups == _first_fit_unbounded(nz, nbins, S, rate)
    assert plan.effective


def test_efb_parity_inputs_plan_as_before():
    """The inputs tests/test_efb.py bundles, through the dense front end."""
    from tests.test_efb import _sparse_problem
    import lightgbm_tpu as lgb
    from lightgbm_tpu.io.bundle import plan_bundles
    X, y = _sparse_problem()
    core = lgb.Dataset(X, label=y)._core_or_construct()
    plan = plan_bundles(core.binned, core.bin_mappers, core.used_features)
    zb = plan.zero_bin
    nz = core.binned != zb[:, None]
    nbins = np.array([core.bin_mappers[f].num_bin
                      for f in core.used_features], np.int32)
    assert plan.groups == _first_fit_unbounded(nz, nbins, nz.shape[1], 0.0)
    assert sorted(len(g) for g in plan.groups) == [1, 3]


def test_two_thousand_dense_features_plan_no_bundle_quickly():
    """Dense features: every pair conflicts on nearly every row, and the
    counts say so before any mask product (there were 2 million of
    them, 50,000 rows each).  Under 2 s; no bundle."""
    from lightgbm_tpu.io.bundle import plan_bundles_from_masks
    rng = np.random.RandomState(0)
    F, S = 2000, 50_000
    nz = rng.rand(F, S) < 0.984          # 1/63 of the rows in the zero bin
    nbins = np.full(F, 63, np.int32)
    t0 = time.perf_counter()
    plan = plan_bundles_from_masks(nz, nbins, np.zeros(F, np.int32), S, 0.0)
    took = time.perf_counter() - t0
    assert not plan.effective and plan.num_groups == F
    assert took < 2.0, took


def test_planner_sample_binned_by_rows_plans_as_the_whole_sample():
    """A device-binned dataset plans its bundles from the raw rows of
    its bin-construction sample, binned on the host for the rows the
    planner takes and no others: the same plan as from all of them."""
    from tests.test_efb import _sparse_problem
    from lightgbm_tpu.io.bundle import _SAMPLE, plan_bundles
    from lightgbm_tpu.io.dataset import Dataset
    X, y = _sparse_problem(n=_SAMPLE + 10_000)
    ds = Dataset.construct_from_arrays(X.astype(np.float32), label=y)
    assert ds.efb_sample_bins() is None          # host-binned: not kept
    ds._efb_sample_raw = X.astype(np.float32)    # as the device path keeps
    lazy = ds.efb_sample_bins()
    assert lazy.shape == ds.binned.shape
    rows = np.array([5, 0, 59_999, 17])
    np.testing.assert_array_equal(lazy[:, rows], ds.binned[:, rows])
    want = plan_bundles(ds.binned, ds.bin_mappers, ds.used_features)
    got = plan_bundles(lazy, ds.bin_mappers, ds.used_features)
    assert got.groups == want.groups and want.effective
    np.testing.assert_array_equal(got.offsets, want.offsets)


@pytest.mark.parametrize("features,rows,engages", [
    (28, 1 << 20, True), (28, (1 << 20) - 1, False),
    (2000, 400_000, True), (2000, 14_000, False), (1, 1 << 20, False)])
def test_device_binning_gate_counts_cells(monkeypatch, features, rows,
                                          engages):
    """The device second pass pays for its transfer by the size of the
    matrix, rows x features: where 2^20 rows of 28 features engage it,
    400,000 rows of 2,000 do too (a row count refused them: 800M host
    `searchsorted`s)."""
    from lightgbm_tpu.io.binning import BinMapper
    from lightgbm_tpu.io.device_bin import device_binnable
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m = BinMapper()
    m.find_bin(np.linspace(-1, 1, 500), 500, 63)
    mappers = [m] * features
    assert device_binnable(mappers, range(features), np.float32,
                           rows) is engages
    assert not device_binnable(mappers, range(features), np.float64, rows)


@pytest.mark.parametrize("gate,backend,n,exits", [
    ("rows", "tpu", 400_000, True),      # the tree before PR 30
    ("cells", "tpu", 400_000, False),
    ("rows", "cpu", 400_000, False),     # no TPU: nothing is refused
    ("rows", "tpu", 100_000, False),     # the held-out rows: a short pass
    ("moved", "tpu", 400_000, False)])   # the gate cannot be asked
def test_epsilon_generator_asks_for_device_binning(monkeypatch, gate,
                                                   backend, n, exits):
    """`benchmarks/generators/epsilon_like.py` draws the cell's matrix
    only for a program that will bin it on the device: one that gates
    on rows (2^20) exits non-zero before any value is drawn, as a
    benchmark run needs of a program that cannot hold the cell."""
    from benchmarks.generators import epsilon_like
    from lightgbm_tpu.io import device_bin
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if gate == "rows":
        def by_rows(mappers, used, dtype, num_data, min_rows=1 << 20):
            return dtype == np.float32 and num_data >= min_rows
        monkeypatch.setattr(device_bin, "device_binnable", by_rows)
    elif gate == "moved":
        monkeypatch.delattr(device_bin, "device_binnable")
    if exits:
        with pytest.raises(SystemExit) as stop:
            epsilon_like.require_device_binning(n, 2000)
        assert stop.value.code not in (0, None)
    else:
        epsilon_like.require_device_binning(n, 2000)
