"""Exclusive feature bundling (ref: feature_group.h:25; greedy bundling
in dataset.cpp FindGroups; FixHistogram dataset.h:759)."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.io.bundle import build_bundled, plan_bundles


def _sparse_problem(n=4000, seed=12):
    """Three mutually exclusive LOW-CARDINALITY sparse features (the
    one-hot-encoding shape EFB exists for) + one dense feature."""
    rng = np.random.RandomState(seed)
    which = rng.randint(0, 3, n)          # exactly one sparse feature set
    X = np.zeros((n, 4))
    for j in range(3):
        m = which == j
        X[m, j] = rng.randint(1, 6, m.sum()) * 0.5   # 5 distinct values
    X[:, 3] = rng.randn(n)
    y = (X[:, 0] * 2 - X[:, 1] + 0.5 * X[:, 2] + 0.3 * X[:, 3]
         + 0.05 * rng.randn(n))
    return X, y


def test_plan_bundles_merges_exclusive_features():
    X, y = _sparse_problem()
    ds = lgb.Dataset(X, label=y)
    core = ds._core_or_construct()
    plan = plan_bundles(core.binned, core.bin_mappers, core.used_features)
    assert plan.effective
    assert plan.num_groups < core.num_features
    sizes = sorted(len(g) for g in plan.groups)
    assert sizes[-1] == 3  # the three exclusive features share a bundle
    bundled = build_bundled(core.binned, plan)
    assert bundled.shape[0] == plan.num_groups
    # decode invariant: every non-default row's code maps back to its bin
    for f in range(core.num_features):
        if not plan.in_bundle[f]:
            continue
        gi = plan.group_idx[f]
        nz = core.binned[f] != plan.zero_bin[f]
        local = bundled[gi].astype(int) - plan.offsets[f]
        m = core.bin_mappers[core.used_features[f]]
        dec = np.where((local >= 0) & (local < m.num_bin), local,
                       plan.zero_bin[f])
        # rows may lose to a conflicting member only if conflicts allowed
        np.testing.assert_array_equal(dec[nz], core.binned[f][nz])


def _onehot_problem(n=3000, seed=36):
    """The benchmark's one-hot shape at a small size, as a scipy CSR and
    densified: six exclusive groups and two numeric columns, 8 stored
    values a row, a binary label."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.generators import expo_like
    X, y = expo_like.make(n, 700, seed)
    return X, X.toarray(), y


@pytest.mark.parametrize("strategy", ["leafwise", "wave"])
@pytest.mark.parametrize("problem", ["exclusive_dense", "onehot_csr"])
def test_bundled_training_matches_unbundled(problem, strategy):
    """EFB is a device-layout optimization: with zero allowed conflicts
    the trained model must match enable_bundle=false.

    `exclusive_dense` (dense input, the booster's own planner,
    regression): up to NEAR-TIE split choices — FixHistogram
    reconstructs each member's default bin by subtraction
    (dataset.h:759, same as the reference's most_freq_bin path), so
    gains differ at the ulp level and a split whose gain gap is below
    that noise may flip; structural equality per tree with a small flip
    budget, predictions tight regardless.

    `onehot_csr` (the benchmark's shape: a scipy CSR bundled on the way
    in, against the densified matrix unbundled): THE SAME 8 trees —
    structure, thresholds and leaf counts equal, values to float32
    rounding; these labels' gain gaps are far over an ulp."""
    if problem == "exclusive_dense":
        X, y = _sparse_problem()
        bundled = dense = X
        base = {"objective": "regression", "num_leaves": 15,
                "min_data_in_leaf": 5}
    else:
        bundled, dense, y = _onehot_problem()
        base = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
                "min_data_in_leaf": 20}
    base = {**base, "verbosity": -1, "tpu_growth_strategy": strategy}
    off = {**base, "enable_bundle": False}
    b_on = lgb.train(base, lgb.Dataset(bundled, label=y, params=base),
                     num_boost_round=8)
    b_off = lgb.train(off, lgb.Dataset(dense, label=y, params=off),
                      num_boost_round=8)
    g_on, g_off = b_on._gbdt, b_off._gbdt
    assert g_on.bundle_plan is not None and g_off.bundle_plan is None
    g_on._sync_model()
    g_off._sync_model()

    def tree_struct(t):
        return (tuple(np.asarray(t.split_feature_inner)),
                tuple(np.asarray(t.threshold_in_bin)),
                tuple(np.asarray(t.left_child)),
                tuple(np.asarray(t.right_child)))

    same = sum(tree_struct(a) == tree_struct(b)
               for a, b in zip(g_on.models_, g_off.models_))
    # the first tree sees constant gradients: no near-ties from score
    # noise, must match exactly; later trees may flip near-ties
    assert tree_struct(g_on.models_[0]) == tree_struct(g_off.models_[0])
    if problem == "exclusive_dense":
        assert same >= 6, f"only {same}/8 trees structurally identical"
        np.testing.assert_allclose(b_on.predict(dense), b_off.predict(dense),
                                   rtol=1e-4, atol=1e-5)
        return
    assert same == 8
    assert g_on.grow_params.has_bundles and g_on.binned_dev.shape[0] < 16
    assert g_off.binned_dev.shape[0] == len(g_on.f_num_bin) > 50
    for a, b in zip(g_on.models_, g_off.models_):
        ni, nl = a.num_leaves - 1, a.num_leaves
        np.testing.assert_array_equal(a.split_feature[:ni],
                                      b.split_feature[:ni])
        np.testing.assert_array_equal(a.threshold[:ni], b.threshold[:ni])
        np.testing.assert_array_equal(a.decision_type[:ni],
                                      b.decision_type[:ni])
        np.testing.assert_array_equal(a.leaf_count[:nl], b.leaf_count[:nl])
        np.testing.assert_allclose(a.leaf_value[:nl], b.leaf_value[:nl],
                                   rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(b_on.predict(dense), b_off.predict(dense),
                               rtol=1e-5, atol=1e-7)


def test_dense_data_is_not_bundled():
    rng = np.random.RandomState(0)
    X = rng.randn(1000, 5)
    y = X[:, 0]
    b = lgb.train({"objective": "regression", "num_leaves": 7,
                   "verbosity": -1}, lgb.Dataset(X, label=y),
                  num_boost_round=2)
    assert b._gbdt.bundle_plan is None
