"""Single-row fast predict (ref: c_api.h:1350-1379
LGBM_BoosterPredictForMatSingleRowFastInit/...Fast; FastConfig caching
c_api.cpp:125-160): parse/pack once, per-call work is one buffer write +
one pre-bound native call."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.native import predictor_lib

pytestmark = pytest.mark.skipif(predictor_lib() is None,
                                reason="native predictor unavailable")


def _fit(objective, y, **extra):
    rng = np.random.RandomState(0)
    X = rng.rand(2000, 6)
    p = {"objective": objective, "num_leaves": 15, "verbosity": -1}
    p.update(extra)
    return X, lgb.train(p, lgb.Dataset(X, label=y(X)), num_boost_round=12)


@pytest.mark.parametrize("objective,y,kw", [
    ("binary", lambda X: (X[:, 0] + X[:, 1] > 1).astype(float), {}),
    ("regression", lambda X: X[:, 0] * 3 + X[:, 1], {}),
    ("regression", lambda X: np.abs(X[:, 0] * 3), {"reg_sqrt": True}),
    ("multiclass", lambda X: (X[:, 0] * 3).astype(int) % 3,
     {"num_class": 3}),
])
def test_fast_matches_batch_path(objective, y, kw):
    X, b = _fit(objective, y, **kw)
    for i in (0, 17, 0, 999):     # repeats catch output-buffer reuse bugs
        want = b.predict(X[i:i + 1])
        got = b.predict(X[i:i + 1], single_row_fast=True)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
        want_raw = b.predict(X[i:i + 1], raw_score=True)
        got_raw = b.predict(X[i:i + 1], raw_score=True,
                            single_row_fast=True)
        np.testing.assert_allclose(got_raw, want_raw, rtol=1e-9)


def test_fast_handles_nan_and_1d_input():
    X, b = _fit("binary", lambda X: (X[:, 0] > 0.5).astype(float))
    row = X[3].copy()
    row[2] = np.nan
    want = b.predict(row[None, :])
    got = b.predict(row, single_row_fast=True)        # 1-D input allowed
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_fast_cache_invalidated_by_growth():
    rng = np.random.RandomState(1)
    X = rng.rand(1000, 5)
    y = (X[:, 0] > 0.5).astype(float)
    ds = lgb.Dataset(X, label=y)
    b = lgb.train({"objective": "binary", "num_leaves": 7,
                   "verbosity": -1}, ds, num_boost_round=3,
                  keep_training_booster=True)
    p1 = b.predict(X[5:6], single_row_fast=True)
    b.update()                                       # model grows
    p2 = b.predict(X[5:6], single_row_fast=True)
    np.testing.assert_allclose(p2, b.predict(X[5:6]), rtol=1e-5)
    assert not np.allclose(p1, p2)                   # new tree changed it


def test_fast_direct_api_latency_is_micro_scale(monkeypatch):
    """What makes the direct path microseconds a row, as counts (a wall
    clock on a shared CPU under six xdist workers says nothing): per call
    one native call through the handle and argument tuple bound at
    construction, no repack of the model, no new library handle and no
    new buffer — and the answers are `Booster.predict`'s."""
    import ctypes
    from lightgbm_tpu import native
    X, b = _fit("binary", lambda X: (X[:, 0] + X[:, 1] > 1).astype(float))
    sp = b._gbdt.make_single_row_fast(X.shape[1])
    assert sp is not None and sp.ok
    rows = [np.ascontiguousarray(X[i % 2000]) for i in range(3000)]
    first = sp.predict(rows[0])

    counts = {"native": 0, "pack": 0, "bind": 0}
    fn, cargs, pack = sp._fn, sp._cargs, sp._packed

    def addresses():
        return [a.ctypes.data for a in (
            sp._X, sp._out, pack.sf, pack.th, pack.dt, pack.lc, pack.rc,
            pack.lv, pack.cw, pack.cb, pack.node_off, pack.leaf_off,
            pack.cw_off, pack.cb_off)]
    buffers = addresses()

    def counted_fn(*args):
        counts["native"] += 1
        assert all(x is y for x, y in zip(args, cargs, strict=True))
        return fn(*args)

    def counted(name, real):
        def wrapper(*a, **kw):
            counts[name] += 1
            return real(*a, **kw)
        return wrapper

    sp._fn = counted_fn
    monkeypatch.setattr(native.PackedPredictor, "__init__", counted(
        "pack", native.PackedPredictor.__init__))
    monkeypatch.setattr(ctypes, "CDLL", counted("bind", ctypes.CDLL))
    got = np.stack([sp.predict(r) for r in rows])

    assert counts == {"native": len(rows), "pack": 0, "bind": 0}
    assert sp._cargs is cargs and sp._packed is pack
    assert b._gbdt._packed_pred[1] is pack
    assert addresses() == buffers
    np.testing.assert_allclose(got[0], first, rtol=0, atol=0)
    want = b.predict(X)
    np.testing.assert_allclose(got[:2000, 0], want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got[2000:, 0], want[:1000], rtol=1e-5,
                               atol=1e-7)
