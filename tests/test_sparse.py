"""Sparse (scipy CSR/CSC) ingestion without densification.

ref: src/io/sparse_bin.hpp, multi_val_sparse_bin.hpp, and the density
heuristics in Dataset::GetShareStates — redesigned as CSC-direct-to-EFB
bundle codes (lightgbm_tpu/io/sparse.py).  The dense [n, F] matrix must
NEVER be materialized at ingestion; models must match the densified
path bit-for-bit on the same data.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import lightgbm_tpu as lgb


def _make_sparse(n=4000, F=60, density=0.02, seed=0):
    rng = np.random.RandomState(seed)
    m = sp.random(n, F, density=density, random_state=rng,
                  data_rvs=lambda k: rng.randn(k) + 1.0).tocsr()
    # label depends on a few columns so trees have something to learn
    d = np.asarray(m[:, :5].todense())
    logit = d.sum(axis=1) + 0.5 * (d[:, 0] > 0)
    y = (rng.rand(n) < 1 / (1 + np.exp(-logit))).astype(np.float64)
    return m, y


PARAMS = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "min_data_in_leaf": 5, "seed": 7, "deterministic": True}


def test_sparse_never_densified():
    """Ingestion must not call toarray/todense on the input."""
    m, y = _make_sparse()

    class Guarded(sp.csr_matrix):
        def toarray(self, *a, **k):
            raise AssertionError("sparse input was densified at ingestion")
        todense = toarray

    g = Guarded(m)
    ds = lgb.Dataset(g, label=y)
    ds._core_or_construct()
    core = ds._core
    assert core.pre_bundled_plan is not None
    # wide-sparse input lands in far fewer device columns than features
    assert core.binned.shape[0] < core.num_features
    b = lgb.train(PARAMS, ds, num_boost_round=5)
    assert b.current_iteration() == 5


def test_sparse_matches_dense_path_bitwise():
    """Same data through the sparse path and the densified path must give
    identical bin mappers, identical bundle plans, and identical models."""
    m, y = _make_sparse()
    b_sparse = lgb.train(PARAMS, lgb.Dataset(m, label=y), num_boost_round=8)
    b_dense = lgb.train(PARAMS, lgb.Dataset(np.asarray(m.todense()),
                                            label=y), num_boost_round=8)
    assert b_sparse.model_to_string() == b_dense.model_to_string()


def test_sparse_predict_chunked_matches_dense():
    m, y = _make_sparse()
    b = lgb.train(PARAMS, lgb.Dataset(m, label=y), num_boost_round=5)
    p_sparse = b.predict(m)
    p_dense = b.predict(np.asarray(m.todense()))
    np.testing.assert_array_equal(p_sparse, p_dense)


def test_sparse_valid_sets_and_early_stopping():
    m, y = _make_sparse()
    mv, yv = _make_sparse(seed=1)
    ds = lgb.Dataset(m, label=y)
    dv = lgb.Dataset(mv, label=yv, reference=ds)
    ev = {}
    b = lgb.train({**PARAMS, "metric": "auc"}, ds, num_boost_round=8,
                  valid_sets=[dv], valid_names=["v"],
                  callbacks=[lgb.record_evaluation(ev)])
    aucs = ev["v"]["auc"]
    assert len(aucs) == 8 and aucs[-1] > 0.5


def test_sparse_csc_and_coo_inputs():
    m, y = _make_sparse()
    p = None
    for conv in (m.tocsc(), m.tocoo()):
        b = lgb.train(PARAMS, lgb.Dataset(conv, label=y), num_boost_round=4)
        q = b.predict(np.asarray(m.todense()))
        if p is not None:
            np.testing.assert_array_equal(p, q)
        p = q


def test_sparse_save_binary_roundtrip(tmp_path):
    m, y = _make_sparse()
    ds = lgb.Dataset(m, label=y)
    ds._core_or_construct()
    path = str(tmp_path / "sparse_ds.npz")
    ds._core.save_binary(path)
    from lightgbm_tpu.io.dataset import Dataset as CoreDataset
    back = CoreDataset.load_binary(path)
    assert back.pre_bundled_plan is not None
    np.testing.assert_array_equal(back.binned, ds._core.binned)
    np.testing.assert_array_equal(back.pre_bundled_plan.offsets,
                                  ds._core.pre_bundled_plan.offsets)


def test_sparse_subset_keeps_plan():
    m, y = _make_sparse()
    ds = lgb.Dataset(m, label=y)
    ds._core_or_construct()
    sub = ds._core.copy_subrow(np.arange(100))
    assert sub.pre_bundled_plan is ds._core.pre_bundled_plan
    assert sub.binned.shape == (ds._core.binned.shape[0], 100)


def test_wide_sparse_memory_budget():
    """Structurally exclusive one-hot blocks (the news20/Criteo shape EFB
    is built for) must collapse to ~one bundle column per block; peak
    ingest memory is O(nnz + bundles*n), not O(n*F)."""
    rng = np.random.RandomState(3)
    n, F, block = 20_000, 1000, 50
    cols = rng.randint(0, block, size=(n, F // block))
    cols += np.arange(F // block)[None, :] * block
    rows = np.repeat(np.arange(n), F // block)
    # binary indicator features (the one-hot case EFB compresses):
    # each feature then has 2 bins and ~127 fit one bundle column
    vals = np.ones(n * (F // block))
    m = sp.csr_matrix((vals, (rows, cols.ravel())), shape=(n, F))
    # label depends on WHICH indicator is hot in the first block
    y = (cols[:, 0] % 2 == 0).astype(np.float64)
    ds = lgb.Dataset(m, label=y)
    ds._core_or_construct()
    ncols = ds._core.binned.shape[0]
    assert ncols <= 2 * (F // block), \
        f"{ncols} bundle columns for {F} one-hot features"
    b = lgb.train(PARAMS, ds, num_boost_round=3)
    assert b.current_iteration() == 3


def test_sparse_enable_bundle_false_keeps_per_feature_bins():
    """enable_bundle=False must disable EFB on the sparse path too: the
    dataset then stores exact per-feature bins (no conflict loss) and
    matches the dense path's model."""
    m, y = _make_sparse()
    p = {**PARAMS, "enable_bundle": False}
    ds = lgb.Dataset(m, label=y, params=p)
    ds._core_or_construct()
    assert ds._core.pre_bundled_plan is None
    assert ds._core.binned.shape[0] == ds._core.num_features
    b_sparse = lgb.train(p, ds, num_boost_round=5)
    b_dense = lgb.train(p, lgb.Dataset(np.asarray(m.todense()), label=y,
                                       params=p), num_boost_round=5)
    assert b_sparse.model_to_string() == b_dense.model_to_string()


def test_sparse_categorical_matches_dense_path():
    """Categorical features whose category 0 is a real observed bin used
    to diverge from the dense path (absent entries were filled with the
    bundle default instead of bin(0)); models must match bit-for-bit."""
    rng = np.random.RandomState(5)
    n = 3000
    X = np.zeros((n, 4))
    X[:, 0] = rng.randn(n)
    X[:, 1] = np.where(rng.rand(n) < 0.7, 0.0,
                       rng.randint(1, 6, n)).astype(float)  # sparse cat
    X[:, 2] = np.where(rng.rand(n) < 0.8, 0.0, rng.randn(n))
    X[:, 3] = rng.randint(0, 3, n).astype(float)            # dense-ish cat
    y = ((X[:, 1] == 0) & (X[:, 0] > 0)).astype(np.float64)
    import scipy.sparse as sp2
    m = sp2.csr_matrix(X)
    p = {**PARAMS, "min_data_in_leaf": 10}
    b_sp = lgb.train(p, lgb.Dataset(m, label=y, categorical_feature=[1, 3]),
                     num_boost_round=6)
    b_dn = lgb.train(p, lgb.Dataset(X, label=y, categorical_feature=[1, 3]),
                     num_boost_round=6)
    assert b_sp.model_to_string() == b_dn.model_to_string()
    np.testing.assert_array_equal(b_sp.predict(m), b_dn.predict(X))


def test_bundles_are_held_to_every_row_not_the_sample():
    """Two rare columns that never meet among the planner's 50,000
    sampled rows but do meet elsewhere: the sample alone would bundle
    them and the rows where they meet would keep the later one's code;
    held to every row (conflict rate 0) they take separate columns and
    no row loses a code."""
    import scipy.sparse as sp
    from lightgbm_tpu.io.bundle import _SAMPLE
    from lightgbm_tpu.io.sparse import _hold_to_all_rows
    from lightgbm_tpu.observability import global_registry
    n = _SAMPLE + 20_000
    sampled = np.zeros(n, bool)
    sampled[np.random.RandomState(3).choice(n, _SAMPLE, False)] = True
    unsampled, drawn = np.flatnonzero(~sampled), np.flatnonzero(sampled)
    a_rows = np.concatenate([drawn[:200], unsampled[:150]])
    b_rows = np.concatenate([drawn[200:400], unsampled[100:250]])
    c_rows = drawn[400:700]                      # exclusive everywhere
    rows = np.concatenate([a_rows, b_rows, c_rows])
    cols = np.repeat([0, 1, 2], [len(a_rows), len(b_rows), len(c_rows)])
    X = sp.csr_matrix((np.ones(len(rows), np.float32), (rows, cols)),
                      shape=(n, 3))
    y = np.zeros(n, np.float32)
    y[a_rows] = 1
    before = global_registry.counter("efb_conflict_rows")
    core = lgb.Dataset(X, label=y, params={"verbosity": -1})._core_or_construct()
    assert global_registry.counter("efb_conflict_rows") == before
    groups = sorted(sorted(g) for g in core.pre_bundled_plan.groups)
    assert groups == [[0, 2], [1]]
    # the 50 rows that hold both a and b keep both codes
    both = np.intersect1d(a_rows, b_rows)
    assert len(both) == 50
    assert np.all(np.asarray(core.binned)[:, both] != 0)
    # the unit: a cap of 50 rows lets the pair stay together
    nz = [np.sort(a_rows), np.sort(b_rows), np.sort(c_rows)]
    assert _hold_to_all_rows([[0, 1, 2]], nz, n, 0) == [[0, 2], [1]]
    assert _hold_to_all_rows([[0, 1, 2]], nz, n, 50) == [[0, 1, 2]]
    assert _hold_to_all_rows([[0, 1], [2]], nz, n, 49) == [[0], [2], [1]]
