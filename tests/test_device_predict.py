"""Parity suite for the TPU-resident inference path (docs/Inference.md).

Three predictors must agree on the same model:
  * DevicePredictor (jitted tensor traversal, float32)
  * native PackedPredictor (predict.c, float64, the serving reference)
  * Tree.predict (models/tree.py, float64, the semantic source of truth)

For float32 inputs the device ROUTING (leaf indices) must be bit-identical
across the whole parity matrix — NaN missing values, zero-as-missing,
categorical bitset splits, multiclass K>1 and RF output averaging; raw
scores differ from the float64 host sums only by float32 summation
rounding.  float64 inputs must fall back to the host paths (gating test).
The recompile-watchdog test pins the bucketing contract: varying batch
sizes inside one bucket re-enter a single trace.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.inference import DevicePredictor, pack_ensemble
from lightgbm_tpu.native import PackedPredictor, predictor_lib

# f32 leaf values, <=40 trees: per-tree rounding is ~1 ulp each
RTOL, ATOL = 2e-6, 2e-6


def _mk_xy(n, seed=0, cats=True):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6).astype(np.float32)
    X[rng.rand(n) < 0.15, 0] = np.nan            # NaN missing
    X[:, 4] = np.where(rng.rand(n) < 0.3, 0.0, X[:, 4])  # zeros
    if cats:
        X[:, 5] = rng.randint(0, 12, n)          # categorical
    y = ((np.nan_to_num(X[:, 0]) + X[:, 1] > 0)
         | (X[:, 5] % 4 == 1)).astype(np.float32)
    return X, y


def _train(params, X, y, rounds=6, **dskw):
    p = dict(objective="binary", num_leaves=15, verbosity=-1, metric="none",
             min_data_in_leaf=5, device_predict="false")
    p.update(params)
    bst = lgb.train(p, lgb.Dataset(X, label=y, **dskw),
                    num_boost_round=rounds)
    bst._gbdt._sync_model()
    return bst


@pytest.fixture(scope="module")
def binary_cat():
    X, y = _mk_xy(1500)
    return _train({}, X, y, categorical_feature=[5]), X


@pytest.fixture(scope="module")
def multiclass():
    X, _ = _mk_xy(1200, seed=3, cats=False)
    y = np.random.RandomState(5).randint(0, 3, 1200).astype(np.float32)
    bst = _train({"objective": "multiclass", "num_class": 3,
                  "num_leaves": 8}, X, y, rounds=4)
    return bst, X


def _test_points(seed=9):
    """Adversarial evaluation points: NaN, exact zeros, out-of-range and
    negative categoricals, huge values."""
    X, _ = _mk_xy(400, seed=seed)
    X[:7, 5] = [-3, -0.5, 0, 31, 64, 1e7, 2.5e9]   # cat edge cases
    X[7, 2] = np.float32(1e30)
    X[8, 2] = -np.float32(1e30)
    X[9, 4] = np.float32(1e-36)                     # below zero threshold
    return X


def _device(bst, **kw):
    g = bst._gbdt
    dp = DevicePredictor(g.models_, num_class=g.num_tree_per_iteration,
                         average=g.average_output_,
                         convert=(g.objective.convert_output
                                  if g.objective is not None else None),
                         min_bucket=256, **kw)
    assert dp.ok
    return dp


def _tree_leaves(models, X64):
    return np.stack([t.get_leaf_index(X64) for t in models], axis=1)


# ------------------------------------------------------------------ routing
def test_leaf_routing_bit_exact_binary_cat(binary_cat):
    bst, X = binary_cat
    Xt = _test_points()
    dp = _device(bst)
    leaf_dev = dp.predict_leaf(Xt)
    X64 = np.asarray(Xt, np.float64)
    assert np.array_equal(leaf_dev, _tree_leaves(bst._gbdt.models_, X64))
    if predictor_lib() is not None:
        native = PackedPredictor(bst._gbdt.models_).predict_leaf(X64)
        assert np.array_equal(leaf_dev, native)


def test_leaf_routing_bit_exact_zero_as_missing():
    X, y = _mk_xy(1000, seed=11, cats=False)
    X = np.nan_to_num(X)  # zero_as_missing rejects NaN-style missing
    bst = _train({"zero_as_missing": True, "use_missing": True}, X, y)
    Xt = np.nan_to_num(_test_points(seed=12))
    Xt[:50, 4] = 0.0
    dp = _device(bst)
    assert np.array_equal(dp.predict_leaf(Xt),
                          _tree_leaves(bst._gbdt.models_,
                                       np.asarray(Xt, np.float64)))


def test_leaf_routing_bit_exact_multiclass(multiclass):
    bst, X = multiclass
    Xt = X[:300]
    dp = _device(bst)
    assert np.array_equal(dp.predict_leaf(Xt),
                          _tree_leaves(bst._gbdt.models_,
                                       np.asarray(Xt, np.float64)))


# ------------------------------------------------------------------- values
def test_raw_scores_match_host(binary_cat):
    bst, X = binary_cat
    Xt = _test_points()
    dp = _device(bst)
    raw_dev = dp.predict_raw(Xt)
    g = bst._gbdt
    raw_host = g._predict_raw_impl(np.asarray(Xt, np.float64), 0, -1,
                                   False, 10, 10.0)
    np.testing.assert_allclose(raw_dev, raw_host, rtol=RTOL, atol=ATOL)


def test_converted_predictions_fused_on_device(binary_cat):
    bst, X = binary_cat
    Xt = _test_points()
    dp = _device(bst)
    pred_dev = dp.predict(Xt)
    bst._gbdt.config.device_predict = "false"
    pred_host = bst.predict(Xt)
    np.testing.assert_allclose(pred_dev, pred_host, rtol=RTOL, atol=ATOL)
    assert (pred_dev >= 0).all() and (pred_dev <= 1).all()  # sigmoid fused


def test_multiclass_softmax_and_shapes(multiclass):
    bst, X = multiclass
    Xt = X[:200]
    dp = _device(bst)
    pred = dp.predict(Xt)
    assert pred.shape == (200, 3)
    np.testing.assert_allclose(pred.sum(axis=1), 1.0, rtol=1e-5)
    bst._gbdt.config.device_predict = "false"
    np.testing.assert_allclose(pred, bst.predict(Xt), rtol=RTOL, atol=ATOL)


def test_average_output_rf():
    X, y = _mk_xy(1200, seed=21, cats=False)
    bst = _train({"boosting": "rf", "bagging_fraction": 0.7,
                  "bagging_freq": 1}, X, y, rounds=5)
    g = bst._gbdt
    assert g.average_output_
    dp = _device(bst)
    Xt = X[:250]
    assert np.array_equal(dp.predict_leaf(Xt),
                          _tree_leaves(g.models_, np.asarray(Xt, np.float64)))
    raw_host = g._predict_raw_impl(np.asarray(Xt, np.float64), 0, -1,
                                   False, 10, 10.0)
    np.testing.assert_allclose(dp.predict_raw(Xt), raw_host,
                               rtol=RTOL, atol=ATOL)


def test_loaded_model_round_trip(binary_cat):
    """Text-loaded models (no leaf_depth) pack and route identically."""
    bst, X = binary_cat
    loaded = lgb.Booster(model_str=bst.model_to_string())
    Xt = _test_points()
    g = loaded._gbdt
    g.config.device_predict = "true"
    hit = g._device_predictor(Xt, 0, -1)
    assert hit is not None
    dp, Xt32 = hit
    assert np.array_equal(dp.predict_leaf(Xt32),
                          _tree_leaves(g.models_, np.asarray(Xt, np.float64)))


# ------------------------------------------------------------------ routing gate
def test_float64_lossless_serves_device(binary_cat):
    """f32-round-trippable float64 (integral features, f32-sourced
    pipelines) is downcast and served by the device path — the ROADMAP'd
    Serving follow-up; routing stays bit-identical because the downcast
    is exact."""
    bst, X = binary_cat
    g = bst._gbdt
    g.config.device_predict = "true"
    try:
        X64 = np.asarray(_test_points(), np.float64)  # f32-sourced
        hit = g._device_predictor(X64, 0, -1)
        assert hit is not None
        assert hit[1].dtype == np.float32
        # end to end: lossless float64 equals the pure host reference
        pred64 = bst.predict(X64)
        g.config.device_predict = "false"
        np.testing.assert_allclose(pred64, bst.predict(X64),
                                   rtol=RTOL, atol=ATOL)
    finally:
        g.config.device_predict = "false"


def test_float64_lossy_falls_back_to_host(binary_cat):
    """float64 values that do NOT survive the f32 round trip keep the
    host path (the bit-exact routing argument needs float32 inputs)."""
    bst, X = binary_cat
    g = bst._gbdt
    g.config.device_predict = "true"
    try:
        X64 = np.asarray(_test_points(), np.float64)
        X64[0, 1] = 0.1          # not representable in float32
        assert g._device_predictor(X64, 0, -1) is None
        pred64 = bst.predict(X64)
        g.config.device_predict = "false"
        np.testing.assert_allclose(pred64, bst.predict(X64), rtol=0, atol=0)
    finally:
        g.config.device_predict = "false"


def test_pred_early_stop_device_matches_host(binary_cat):
    """Device early stopping (traverse.py masked accumulation scan) must
    reproduce the host path's SEMANTICS: rows whose margin clears the
    threshold at a round check keep their partial sum.  Scores agree to
    f32 accumulation rounding; a small margin must actually change the
    answer (rows stopped), a huge margin must stop nobody."""
    bst, X = binary_cat
    g = bst._gbdt
    Xt = _test_points()
    host_es = g._predict_raw_impl(np.asarray(Xt, np.float64), 0, -1,
                                  True, 2, 0.2)
    host_plain = g._predict_raw_impl(np.asarray(Xt, np.float64), 0, -1,
                                     False, 10, 10.0)
    assert not np.allclose(host_es, host_plain)  # es engaged host-side
    g.config.device_predict = "true"
    try:
        dev_es = g.predict_raw(Xt, pred_early_stop=True,
                               pred_early_stop_freq=2,
                               pred_early_stop_margin=0.2)
        np.testing.assert_allclose(dev_es, host_es, rtol=1e-5, atol=1e-5)
        dev_off = g.predict_raw(Xt, pred_early_stop=True,
                                pred_early_stop_freq=2,
                                pred_early_stop_margin=1e9)
        np.testing.assert_allclose(dev_off, host_plain,
                                   rtol=RTOL, atol=ATOL)
    finally:
        g.config.device_predict = "false"


def test_pred_early_stop_device_multiclass(multiclass):
    """Multiclass margin = top1 - top2 (prediction_early_stop.cpp)."""
    bst, X = multiclass
    g = bst._gbdt
    Xt = np.asarray(X[:200], np.float32)
    host_es = g._predict_raw_impl(np.asarray(Xt, np.float64), 0, -1,
                                  True, 2, 0.02)
    g.config.device_predict = "true"
    try:
        dev_es = g.predict_raw(Xt, pred_early_stop=True,
                               pred_early_stop_freq=2,
                               pred_early_stop_margin=0.02)
        np.testing.assert_allclose(dev_es, host_es, rtol=1e-5, atol=1e-5)
    finally:
        g.config.device_predict = "false"


def test_pred_early_stop_margin_sweep_no_retrace(binary_cat):
    """The margin rides as a traced f32 scalar: sweeping thresholds and
    batch sizes inside a bucket re-enters ONE compiled program."""
    bst, X = binary_cat
    g = bst._gbdt
    g.config.device_predict = "true"
    try:
        g.predict_raw(X[:40], pred_early_stop=True,
                      pred_early_stop_freq=3, pred_early_stop_margin=0.5)
        dp = g._device_pred[1]
        t0 = dp.total_traces()
        assert any("+es3" in m for (m, _, _) in dp._fns)
        for margin, n in ((0.1, 17), (2.0, 40), (7.5, 256)):
            g.predict_raw(X[:n], pred_early_stop=True,
                          pred_early_stop_freq=3,
                          pred_early_stop_margin=margin)
        assert dp.total_traces() == t0
    finally:
        g.config.device_predict = "false"


def test_dart_inplace_mutation_invalidates_device_cache():
    """DART re-weights OLD trees in place (drop/normalize); the cached
    DevicePredictor must repack so a mid-training model serves its
    CURRENT drop state, matching Booster.predict (ISSUE 10 satellite)."""
    X, y = _mk_xy(600, seed=21)
    bst = _train({"boosting": "dart", "drop_rate": 0.9, "skip_drop": 0.0,
                  "learning_rate": 0.3}, X, y, rounds=5)
    g = bst._gbdt
    g.config.device_predict = "true"
    try:
        Xt = np.asarray(X[:64], np.float32)
        before = g.predict_raw(Xt)
        g.pre_gradient_hook()          # drops trees: in-place -w flip
        assert g.drop_index_, "no drop fired; raise drop_rate"
        expected = np.zeros(len(Xt))
        for t in g.models_:            # semantic truth: current trees
            expected += t.predict(np.asarray(Xt, np.float64))
        after = g.predict_raw(Xt)
        np.testing.assert_allclose(after, expected, rtol=RTOL, atol=1e-5)
        assert not np.allclose(after, before)   # stale cache would match
    finally:
        g.config.device_predict = "false"


def test_linear_tree_pack_refuses():
    X, y = _mk_xy(600, seed=31, cats=False)
    X = np.nan_to_num(X)
    bst = _train({"linear_tree": True, "objective": "regression"}, X, y,
                 rounds=2)
    assert pack_ensemble(bst._gbdt.models_) is None
    g = bst._gbdt
    g.config.device_predict = "true"
    try:
        assert g._device_predictor(X[:10], 0, -1) is None  # dp.ok False
    finally:
        g.config.device_predict = "false"


def test_booster_predict_routes_device(binary_cat):
    """Booster.predict on float32 with device_predict=true serves from the
    device path (leaf ids identical, conversion fused)."""
    bst, X = binary_cat
    g = bst._gbdt
    Xt = _test_points()
    g.config.device_predict = "false"
    host_pred = bst.predict(Xt)
    host_leaf = bst.predict(Xt, pred_leaf=True)
    g.config.device_predict = "true"
    try:
        from lightgbm_tpu.utils.timer import global_timer
        was = global_timer.sync
        global_timer.sync = True
        global_timer.reset()
        dev_pred = bst.predict(Xt)
        dev_leaf = bst.predict(Xt, pred_leaf=True)
        scopes = [name for name, _, _ in global_timer.items()]
        global_timer.sync = was
        global_timer.reset()
        assert "GBDT::predict_device" in scopes
        assert np.array_equal(dev_leaf, host_leaf)
        np.testing.assert_allclose(dev_pred, host_pred, rtol=RTOL, atol=ATOL)
    finally:
        g.config.device_predict = "false"


def test_eval_fresh_data_through_device(binary_cat):
    """The fresh-data eval path feeds float32 raw data to predict_raw, so
    a forced device config serves it (and the metric still matches)."""
    bst, X = binary_cat
    Xe, ye = _mk_xy(400, seed=41)
    g = bst._gbdt
    g.config.device_predict = "false"
    ref = lgb.Booster(model_str=bst.model_to_string())
    ref._gbdt.config.metric = ["auc"]
    host = ref.eval(lgb.Dataset(Xe, label=ye), "fresh")
    dev_bst = lgb.Booster(model_str=bst.model_to_string())
    dev_bst._gbdt.config.metric = ["auc"]
    dev_bst._gbdt.config.device_predict = "true"
    dev = dev_bst.eval(lgb.Dataset(Xe, label=ye), "fresh")
    assert host and dev
    assert host[0][1] == dev[0][1] == "auc"
    assert abs(host[0][2] - dev[0][2]) < 1e-6


# -------------------------------------------------------------- recompiles
def test_bucketing_zero_new_traces_within_bucket(binary_cat):
    bst, X = binary_cat
    dp = _device(bst)
    assert dp.bucket_rows(1) == 256 and dp.bucket_rows(256) == 256
    assert dp.bucket_rows(257) == 512 and dp.bucket_rows(1000) == 1024
    for n in (3, 50, 199, 255, 256):
        dp.predict_leaf(X[:n])
    # one bucket touched -> exactly one traced signature, one executable
    assert dp.num_traces("leaf") == 1
    (fn,) = [f for (m, _, _), f in dp._fns.items() if m == "leaf"]
    assert fn._cache_size() == 1
    # crossing the bucket boundary compiles exactly one more entry
    dp.predict_leaf(X[:300])
    dp.predict_leaf(X[:500])
    assert dp.num_traces("leaf") == 2


def test_raw_and_convert_share_routing(binary_cat):
    """convert mode must not add traces for the same buckets."""
    bst, X = binary_cat
    dp = _device(bst)
    for n in (10, 100, 10, 100):
        dp.predict(X[:n])
        dp.predict_raw(X[:n])
    assert dp.num_traces("convert") == 1
    assert dp.num_traces("raw") == 1


def test_mesh_sharded_offline_scoring(binary_cat):
    """Rows shard over the parallel/ mesh (conftest's 8 virtual CPU
    devices); results identical to the single-device program."""
    from lightgbm_tpu.parallel import make_mesh
    bst, X = binary_cat
    g = bst._gbdt
    dp = _device(bst, mesh=make_mesh(8))
    assert dp._min_bucket % 8 == 0  # buckets tile the mesh
    dp0 = _device(bst)
    Xt = X[:777]
    assert np.array_equal(dp.predict_leaf(Xt), dp0.predict_leaf(Xt))
    np.testing.assert_allclose(dp.predict(Xt), dp0.predict(Xt),
                               rtol=1e-6, atol=1e-7)


def test_model_slice_and_cache_invalidation(binary_cat):
    bst, X = binary_cat
    g = bst._gbdt
    Xt = _test_points()
    g.config.device_predict = "true"
    try:
        full = g.predict_raw(Xt)
        half = g.predict_raw(Xt, num_iteration=3)
        assert not np.allclose(full, half)
        g.config.device_predict = "false"
        host_half = g.predict_raw(np.asarray(Xt, np.float64),
                                  num_iteration=3)
        np.testing.assert_allclose(half, host_half, rtol=RTOL, atol=ATOL)
    finally:
        g.config.device_predict = "false"
