"""forcedbins_filename: forced bin upper bounds inside FindBin
(ref: src/io/bin.cpp:157-240 FindBinWithPredefinedBin,
dataset_loader.cpp:1493 GetForcedBins; examples/regression/forced_bins.json)."""

import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.io.dataset import get_forced_bins


def _data(n=3000, F=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, F) * 2 - 1
    y = X[:, 0] * 3 + np.where(X[:, 1] > -0.15, 1.0, -1.0)
    return X, y


def test_forced_bounds_change_boundaries(tmp_path):
    X, y = _data()
    fb = tmp_path / "forced.json"
    fb.write_text(json.dumps([
        {"feature": 0, "bin_upper_bound": [0.3, 0.35, 0.4]},
        {"feature": 1, "bin_upper_bound": [-0.1, -0.15, -0.2]},
    ]))
    params = {"objective": "regression", "verbosity": -1, "max_bin": 16}
    ds_plain = lgb.Dataset(X, label=y, params=params)
    ds_plain._core_or_construct()
    ds_forced = lgb.Dataset(X, label=y, params={
        **params, "forcedbins_filename": str(fb)})
    ds_forced._core_or_construct()

    ub0 = ds_forced._core.bin_mappers[0].bin_upper_bound
    ub1 = ds_forced._core.bin_mappers[1].bin_upper_bound
    for v in (0.3, 0.35, 0.4):
        assert np.any(np.isclose(ub0, v)), (v, ub0)
    for v in (-0.1, -0.15, -0.2):
        assert np.any(np.isclose(ub1, v)), (v, ub1)
    # untouched feature keeps identical boundaries
    np.testing.assert_array_equal(
        ds_plain._core.bin_mappers[2].bin_upper_bound,
        ds_forced._core.bin_mappers[2].bin_upper_bound)
    # and the boundaries really differ where forced
    assert not np.array_equal(ds_plain._core.bin_mappers[0].bin_upper_bound,
                              ub0)
    # training works end to end with the forced mappers
    b = lgb.train({**params, "forcedbins_filename": str(fb)},
                  ds_forced, num_boost_round=5)
    assert b.current_iteration() == 5


def test_forced_bins_reference_example_round_trip(tmp_path):
    """A forced_bins.json with the content of the reference's own
    (examples/regression/forced_bins.json) drives bin boundaries through
    the file-loading (CLI) path; the data file is a seeded stand-in for
    regression.train (/root/reference is not mounted here)."""
    X, y = _data(n=2000, F=6, seed=4)
    data = tmp_path / "regression.train"
    np.savetxt(data, np.column_stack([y, X]), delimiter="\t", fmt="%.6f")
    fb = tmp_path / "forced_bins.json"
    fb.write_text(json.dumps([
        {"feature": 0, "bin_upper_bound": [0.3, 0.35, 0.4]},
        {"feature": 1, "bin_upper_bound": [-0.1, -0.15, -0.2]}]))
    ds = lgb.Dataset(str(data),
                     params={"forcedbins_filename": str(fb),
                             "max_bin": 32})
    ds._core_or_construct()
    ub0 = ds._core.bin_mappers[0].bin_upper_bound
    for v in (0.3, 0.35, 0.4):
        assert np.any(np.isclose(ub0, v)), (v, ub0)


def test_forced_bins_categorical_skipped_and_missing_file_warns(tmp_path):
    X, y = _data()
    X[:, 3] = np.random.RandomState(1).randint(0, 5, len(X))
    fb = tmp_path / "forced.json"
    fb.write_text(json.dumps([
        {"feature": 3, "bin_upper_bound": [1.0, 2.0]}]))
    ds = lgb.Dataset(X, label=y, params={
        "forcedbins_filename": str(fb), "verbosity": -1},
        categorical_feature=[3])
    ds._core_or_construct()              # categorical: warn + ignore
    assert ds._core.bin_mappers[3].bin_type == 1  # BIN_CATEGORICAL
    # missing file: warn + ignore, identical to no forced bins
    got = get_forced_bins(str(tmp_path / "nope.json"), 4, ())
    assert got == [[], [], [], []]


def test_forced_bins_out_of_range_feature_fatals(tmp_path):
    from lightgbm_tpu.utils.log import LightGBMError
    fb = tmp_path / "forced.json"
    fb.write_text(json.dumps([{"feature": 9, "bin_upper_bound": [1.0]}]))
    with pytest.raises(LightGBMError):
        get_forced_bins(str(fb), 4, ())


def test_forced_bins_sparse_path(tmp_path):
    import scipy.sparse as sp
    rng = np.random.RandomState(0)
    m = sp.random(3000, 10, density=0.2, random_state=rng,
                  data_rvs=lambda k: rng.rand(k)).tocsr()
    y = np.asarray(m[:, 0].todense()).ravel()
    fb = tmp_path / "forced.json"
    fb.write_text(json.dumps([
        {"feature": 0, "bin_upper_bound": [0.25, 0.5, 0.75]}]))
    ds = lgb.Dataset(m, label=y, params={
        "forcedbins_filename": str(fb), "verbosity": -1})
    ds._core_or_construct()
    ub0 = ds._core.bin_mappers[0].bin_upper_bound
    for v in (0.25, 0.5, 0.75):
        assert np.any(np.isclose(ub0, v)), (v, ub0)
