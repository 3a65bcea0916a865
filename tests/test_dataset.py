import numpy as np
import pytest

from lightgbm_tpu.io.dataset import Dataset, load_dataset_from_file


def _toy(n=500, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + rng.normal(scale=0.1, size=n) > 0).astype(np.float32)
    return X, y


def test_construct_from_arrays():
    X, y = _toy()
    ds = Dataset.construct_from_arrays(X, label=y, max_bin=32)
    assert ds.num_data == 500
    assert ds.num_features == 5
    assert ds.binned.shape == (5, 500)
    assert ds.binned.max() < 32
    np.testing.assert_allclose(ds.metadata.label, y)


def test_trivial_feature_dropped():
    X, y = _toy()
    X = np.concatenate([X, np.ones((len(X), 1))], axis=1)  # constant column
    ds = Dataset.construct_from_arrays(X, label=y, max_bin=32)
    assert ds.num_total_features == 6
    assert ds.num_features == 5
    assert ds.used_feature_map[5] == -1


def test_valid_aligned_with_reference():
    X, y = _toy()
    Xv, yv = _toy(seed=1)
    ds = Dataset.construct_from_arrays(X, label=y, max_bin=32)
    dv = ds.create_valid(Xv, label=yv)
    assert dv.bin_mappers is ds.bin_mappers
    # same value must bin identically in both datasets
    col = ds.bin_mappers[0].values_to_bins(Xv[:, 0])
    np.testing.assert_array_equal(dv.binned[0], col)


def test_copy_subrow():
    X, y = _toy()
    w = np.arange(len(y), dtype=np.float32)
    ds = Dataset.construct_from_arrays(X, label=y, weight=w, max_bin=32)
    idx = np.array([3, 10, 100])
    sub = ds.copy_subrow(idx)
    assert sub.num_data == 3
    np.testing.assert_array_equal(sub.binned, ds.binned[:, idx])
    np.testing.assert_allclose(sub.metadata.weight, w[idx])


def test_group_metadata():
    X, y = _toy(n=10)
    ds = Dataset.construct_from_arrays(X, label=y, group=[4, 6], max_bin=16)
    np.testing.assert_array_equal(ds.metadata.query_boundaries, [0, 4, 10])
    assert ds.metadata.num_queries == 2


def test_binary_save_load(tmp_path):
    X, y = _toy()
    ds = Dataset.construct_from_arrays(X, label=y, max_bin=32)
    path = str(tmp_path / "data.bin")
    ds.save_binary(path)
    ds2 = Dataset.load_binary(path)
    np.testing.assert_array_equal(ds.binned, ds2.binned)
    np.testing.assert_allclose(ds.metadata.label, ds2.metadata.label)
    assert ds2.bin_mappers[0].num_bin == ds.bin_mappers[0].num_bin


def test_load_reference_example_file(tmp_path):
    """A file shaped like the reference's binary.train (7000 x 28, label
    in column 0, tab-separated, `.weight` sidecar), made from a seed:
    /root/reference is not mounted here."""
    rng = np.random.RandomState(5)
    X = rng.randn(7000, 28)
    y = (rng.rand(7000) < 1 / (1 + np.exp(-2 * X[:, 0]))).astype(float)
    path = tmp_path / "binary.train"
    np.savetxt(path, np.column_stack([y, X]), delimiter="\t", fmt="%.6f")
    np.savetxt(str(path) + ".weight", rng.rand(7000) + 0.5, fmt="%.4f")
    ds = load_dataset_from_file(str(path))
    assert ds.num_data == 7000
    assert ds.num_total_features == 28
    assert set(np.unique(ds.metadata.label)) == {0.0, 1.0}
    # weight sidecar file should be auto-loaded (binary.train.weight exists)
    assert ds.metadata.weight is not None
    assert len(ds.metadata.weight) == 7000


def test_dataset_from_scipy_sparse():
    """CSR/CSC input (ref: LGBM_DatasetCreateFromCSR/CSC): densified into
    the binned tensors; EFB re-compresses exclusive sparse columns."""
    import scipy.sparse as sp
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    dense = rng.rand(800, 5) * (rng.rand(800, 5) < 0.3)
    y = dense[:, 0] + dense[:, 1]
    for mat in (sp.csr_matrix(dense), sp.csc_matrix(dense)):
        b = lgb.train({"objective": "regression", "num_leaves": 7,
                       "verbosity": -1, "min_data_in_leaf": 5},
                      lgb.Dataset(mat, label=y), num_boost_round=15)
        # predict accepts sparse input too (train-CSR/predict-CSR flow)
        np.testing.assert_allclose(b.predict(mat), b.predict(dense),
                                   rtol=1e-9)
        mse = float(np.mean((b.predict(dense) - y) ** 2))
        var = float(np.var(y))
        assert mse < 0.3 * var, (mse, var)
