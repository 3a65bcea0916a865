"""Tests for ops/histogram.py and ops/split.py against independent NumPy oracles.

The oracle re-implements the reference's sequential scan loop directly
(ref: src/treelearner/feature_histogram.hpp:831-1057) so the vectorized XLA
version is checked candidate-for-candidate, including epsilon conventions,
hessian-derived counts, missing-bin routing and tie-breaking.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.histogram import build_histogram
from lightgbm_tpu.ops.split import (K_EPSILON, MISSING_NAN, MISSING_NONE,
                                    MISSING_ZERO, SplitParams, find_best_split)

RNG = np.random.RandomState(7)


# ---------------------------------------------------------------- histogram --
def _hist_oracle(binned, gh, mask, max_bin):
    F, n = binned.shape
    out = np.zeros((F, max_bin, gh.shape[1]), dtype=np.float64)
    for f in range(F):
        for r in range(n):
            out[f, binned[f, r]] += gh[r] * mask[r]
    return out


# "onehot" is single-pass bf16 (reference GPU learner analogue: its default
# is single-precision histograms, gpu_tree_learner.h:79); tolerance reflects
# bf16 rounding of gh inputs.  "segment"/"onehot_hp" are fp32-exact paths.
@pytest.mark.parametrize("method,rtol,atol",
                         [("segment", 2e-4, 2e-4),
                          ("onehot_hp", 2e-4, 2e-4),
                          ("onehot", 5e-2, 1e-1)])
@pytest.mark.parametrize("n,F,B", [(256, 3, 8), (4096, 5, 16)])
def test_histogram_matches_oracle(method, rtol, atol, n, F, B):
    binned = RNG.randint(0, B, size=(F, n)).astype(np.int32)
    gh = RNG.randn(n, 2).astype(np.float32)
    mask = (RNG.rand(n) > 0.3).astype(np.float32)
    hist = build_histogram(jnp.array(binned), jnp.array(gh), jnp.array(mask),
                           max_bin=B, method=method)
    expect = _hist_oracle(binned, gh, mask, B)
    np.testing.assert_allclose(np.asarray(hist), expect, rtol=rtol, atol=atol)


def test_histogram_chunked_matches_unchunked():
    n, F, B = 8192, 4, 32
    binned = RNG.randint(0, B, size=(F, n)).astype(np.int32)
    gh = RNG.randn(n, 2).astype(np.float32)
    mask = np.ones(n, dtype=np.float32)
    h1 = build_histogram(jnp.array(binned), jnp.array(gh), jnp.array(mask),
                         max_bin=B, row_chunk=1024)
    h2 = build_histogram(jnp.array(binned), jnp.array(gh), jnp.array(mask),
                         max_bin=B, row_chunk=8192)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------- split oracle --
def _leaf_gain(g, h, l1, l2):
    s = np.sign(g) * max(0.0, abs(g) - l1)
    return s * s / (h + l2)


def _scan_oracle(hist_g, hist_h, nb, mt, db, sum_g, sum_h_base, num_data, p):
    """Direct loop port of FindBestThresholdSequentially (float path, offset=0)."""
    sum_h = sum_h_base + 2 * K_EPSILON
    cnt_factor = num_data / sum_h
    gain_shift = _leaf_gain(sum_g, sum_h, p.lambda_l1, p.lambda_l2)
    min_gain_shift = gain_shift + p.min_gain_to_split
    na = 1 if mt == MISSING_NAN else 0
    skip_db = mt == MISSING_ZERO

    best = dict(gain=-np.inf, thr=nb, dl=True, lg=np.nan, lh=np.nan, lc=0)

    # REVERSE
    srg, srh, src = 0.0, K_EPSILON, 0
    for t in range(nb - 1 - na, 0, -1):
        if skip_db and t == db:
            continue
        srg += hist_g[t]
        srh += hist_h[t]
        src += int(np.floor(hist_h[t] * cnt_factor + 0.5))
        if src < p.min_data_in_leaf or srh < p.min_sum_hessian_in_leaf:
            continue
        lc = num_data - src
        if lc < p.min_data_in_leaf:
            break
        slh = sum_h - srh
        if slh < p.min_sum_hessian_in_leaf:
            break
        slg = sum_g - srg
        gain = _leaf_gain(slg, slh, p.lambda_l1, p.lambda_l2) + \
            _leaf_gain(srg, srh, p.lambda_l1, p.lambda_l2)
        if gain <= min_gain_shift or gain <= best["gain"]:
            continue
        best.update(gain=gain, thr=t - 1, dl=True, lg=slg, lh=slh, lc=lc)

    # FORWARD (only when a missing direction exists)
    if mt != MISSING_NONE:
        fwd = dict(gain=-np.inf, thr=nb, lg=np.nan, lh=np.nan, lc=0)
        slg, slh, slc = 0.0, K_EPSILON, 0
        for t in range(0, nb - 1):
            if skip_db and t == db:
                continue
            if not (na and t == nb - 1):
                slg += hist_g[t]
                slh += hist_h[t]
                slc += int(np.floor(hist_h[t] * cnt_factor + 0.5))
            if slc < p.min_data_in_leaf or slh < p.min_sum_hessian_in_leaf:
                continue
            rc = num_data - slc
            if rc < p.min_data_in_leaf:
                break
            srh2 = sum_h - slh
            if srh2 < p.min_sum_hessian_in_leaf:
                break
            srg2 = sum_g - slg
            gain = _leaf_gain(slg, slh, p.lambda_l1, p.lambda_l2) + \
                _leaf_gain(srg2, srh2, p.lambda_l1, p.lambda_l2)
            if gain <= min_gain_shift or gain <= fwd["gain"]:
                continue
            fwd.update(gain=gain, thr=t, lg=slg, lh=slh, lc=slc)
        if fwd["gain"] > best["gain"]:
            best.update(gain=fwd["gain"], thr=fwd["thr"], dl=False,
                        lg=fwd["lg"], lh=fwd["lh"], lc=fwd["lc"])
    if np.isfinite(best["gain"]):
        best["gain"] -= min_gain_shift
    return best


def _run_one(nb, mt, db, p, seed, num_data=500):
    rng = np.random.RandomState(seed)
    B = 16
    hist = np.zeros((1, B, 2), dtype=np.float32)
    hist[0, :nb, 0] = rng.randn(nb).astype(np.float32)
    hist[0, :nb, 1] = rng.rand(nb).astype(np.float32) * num_data / nb
    sum_g = float(hist[0, :, 0].sum())
    sum_h = float(hist[0, :, 1].sum())
    res = find_best_split(
        jnp.array(hist), jnp.array([nb], jnp.int32), jnp.array([mt], jnp.int32),
        jnp.array([db], jnp.int32), jnp.ones(1, jnp.float32),
        jnp.ones(1, bool), jnp.float32(sum_g), jnp.float32(sum_h),
        jnp.int32(num_data), jnp.float32(0.0), p)
    oracle = _scan_oracle(hist[0, :, 0].astype(np.float64),
                          hist[0, :, 1].astype(np.float64),
                          nb, mt, db, sum_g, sum_h, num_data, p)
    return res, oracle


@pytest.mark.parametrize("mt,db", [(MISSING_NONE, 0), (MISSING_ZERO, 3),
                                   (MISSING_NAN, 0)])
@pytest.mark.parametrize("seed", range(8))
def test_split_matches_scan_oracle(mt, db, seed):
    p = SplitParams(lambda_l1=0.0, lambda_l2=0.01, min_data_in_leaf=5,
                    min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0)
    res, oracle = _run_one(12, mt, db, p, seed)
    if not np.isfinite(oracle["gain"]) or oracle["gain"] <= 0:
        assert float(res.gain) <= 0 or not np.isfinite(float(res.gain))
        return
    assert int(res.threshold) == oracle["thr"], (oracle, res)
    assert bool(res.default_left) == oracle["dl"]
    np.testing.assert_allclose(float(res.gain), oracle["gain"], rtol=1e-4)
    np.testing.assert_allclose(float(res.left_sum_gradient), oracle["lg"], rtol=1e-4)
    assert int(res.left_count) == oracle["lc"]


@pytest.mark.parametrize("seed", range(4))
def test_split_l1_and_min_gain(seed):
    p = SplitParams(lambda_l1=0.5, lambda_l2=1.0, min_data_in_leaf=3,
                    min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.1)
    res, oracle = _run_one(10, MISSING_NONE, 0, p, seed)
    if not np.isfinite(oracle["gain"]) or oracle["gain"] <= 0:
        assert float(res.gain) <= 0 or not np.isfinite(float(res.gain))
        return
    assert int(res.threshold) == oracle["thr"]
    np.testing.assert_allclose(float(res.gain), oracle["gain"], rtol=1e-4)


def test_split_multifeature_prefers_informative():
    """Feature 1 perfectly separates the gradients; must be chosen."""
    B = 8
    n = 200
    binned = np.zeros((2, n), dtype=np.int32)
    binned[0] = RNG.randint(0, B, n)          # noise feature
    binned[1] = (np.arange(n) >= n // 2).astype(np.int32) * 4  # informative
    grad = np.where(np.arange(n) >= n // 2, 1.0, -1.0).astype(np.float32)
    hess = np.ones(n, dtype=np.float32)
    gh = np.stack([grad, hess], 1)
    hist = build_histogram(jnp.array(binned), jnp.array(gh),
                           jnp.ones(n, jnp.float32), max_bin=B)
    res = find_best_split(
        hist, jnp.array([B, B], jnp.int32),
        jnp.array([MISSING_NONE, MISSING_NONE], jnp.int32),
        jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.float32), jnp.ones(2, bool),
        jnp.float32(grad.sum()), jnp.float32(hess.sum()),
        jnp.int32(n), jnp.float32(0.0),
        SplitParams(min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3))
    assert int(res.feature) == 1
    assert int(res.threshold) in (0, 1, 2, 3)
    assert float(res.gain) > 0
    # perfect separation: left mean -1, right mean +1
    np.testing.assert_allclose(float(res.left_output), 1.0, atol=0.02)
    np.testing.assert_allclose(float(res.right_output), -1.0, atol=0.02)


# ----------------------------------------- the dense (wave engine's) scan --
# `find_best_split_dense` reads N leaves' cache rows ((feature, bin,
# channel) order) and scans them bin by bin; the oracle is the reference's
# own loop above, feature by feature, in float64.  Hessians are row counts
# (1.0 a row), so RoundInt(hess * cnt_factor) is the same integer in
# float32 and float64, and every case's winner leads its runner-up by far
# more than float32 rounding — except inside a run of empty bins, where
# both sides tie bit-exactly and the visiting order decides.

def _leaf_hist(rng, F, B, nb, mt, db, n, case):
    """One leaf: bins [F, n] drawn inside each feature's num_bin, a
    gradient a row, and its [F, B, 2] histogram (float32)."""
    bins = np.stack([rng.randint(0, nb[f], n) for f in range(F)])
    grad = rng.randn(n).astype(np.float32)
    f0 = F // 2                                   # the planted feature
    if case in ("empty_run_reverse", "empty_run_forward"):
        # rows sit at or below bin k, or above an EMPTY run k+1..k+run:
        # thresholds k..k+run all cut the same rows
        nb0 = int(nb[f0])
        k, run = max(nb0 // 3 - 1, 0), max(nb0 // 4, 1)
        hi = nb0 - (2 if case == "empty_run_forward" else 1)
        assert k + run + 1 <= hi
        side = rng.rand(n) < 0.5
        lo_bins = rng.randint(0, k + 1, n)
        hi_bins = rng.randint(k + run + 1, hi + 1, n)
        bins[f0] = np.where(side, lo_bins, hi_bins)
        grad = np.where(side, -1.0, 1.0).astype(np.float32)
        grad += 0.01 * rng.randn(n).astype(np.float32)
        if case == "empty_run_forward":
            # the NaN bin's rows belong with the right side: only the
            # forward scan (missing right) separates the leaf cleanly
            na_rows = rng.rand(n) < 0.2
            bins[f0] = np.where(na_rows, nb0 - 1, bins[f0])
            grad = np.where(na_rows, 1.0, grad).astype(np.float32)
    hist = np.zeros((F, B, 2), np.float64)
    for f in range(F):
        np.add.at(hist[f, :, 0], bins[f], grad.astype(np.float64))
        np.add.at(hist[f, :, 1], bins[f], 1.0)
    return hist.astype(np.float32), grad


def _dense_case(F, B, case, seed):
    rng = np.random.RandomState(seed)
    nb = np.full(F, B, np.int32)
    mt = np.full(F, MISSING_NONE, np.int32)
    db = np.zeros(F, np.int32)
    if case in ("mixed_missing", "empty_run_forward"):
        mt = rng.choice([MISSING_NONE, MISSING_ZERO, MISSING_NAN],
                        F).astype(np.int32)
        db = rng.randint(0, max(B - 1, 1), F).astype(np.int32)
    if case == "short_feature":
        nb = rng.randint(2, B + 1, F).astype(np.int32)
        nb[F // 2] = max(B // 2, 2)
    if case == "empty_run_forward":
        mt[F // 2] = MISSING_NAN
    db = np.minimum(db, nb - 1)
    n = 600
    p = dict(plain=SplitParams(min_data_in_leaf=1, lambda_l2=0.01),
             gate_data=SplitParams(min_data_in_leaf=n // 4, lambda_l2=0.01),
             gate_hessian=SplitParams(min_data_in_leaf=1, lambda_l2=0.01,
                                      min_sum_hessian_in_leaf=n / 4 + 0.5)
             ).get(case, SplitParams(min_data_in_leaf=5, lambda_l2=0.01))
    p = p._replace(has_missing=bool((mt != MISSING_NONE).any()))
    leaves = [_leaf_hist(rng, F, B, nb, mt, db, n, case) for _ in range(3)]
    return nb, mt, db, n, p, leaves


def _oracle_leaf(hist, grad, nb, mt, db, n, p):
    """The leaf's best (feature, oracle record) over the per-feature
    reference loops; gain ties go to the smaller feature index."""
    sum_g, sum_h = float(np.float32(grad.sum(dtype=np.float64))), float(n)
    best_f, best = -1, None
    for f in range(hist.shape[0]):
        o = _scan_oracle(hist[f, :, 0].astype(np.float64),
                         hist[f, :, 1].astype(np.float64), int(nb[f]),
                         int(mt[f]), int(db[f]), sum_g, sum_h, n, p)
        if np.isfinite(o["gain"]) and (best is None
                                       or o["gain"] > best["gain"]):
            best_f, best = f, o
    return best_f, best, sum_g, sum_h


_DENSE_SHAPES = [(28, 255), (28, 63), (2000, 63), (5, 7)]
_DENSE_CASES = ["plain", "mixed_missing", "gate_data", "gate_hessian",
                "empty_run_reverse", "empty_run_forward", "short_feature"]


@pytest.mark.parametrize("case", _DENSE_CASES)
@pytest.mark.parametrize("F,B", _DENSE_SHAPES)
def test_dense_scan_matches_sequential_oracle(F, B, case):
    from lightgbm_tpu.ops.split import find_best_split_dense
    nb, mt, db, n, p, leaves = _dense_case(F, B, case, seed=F * 1000 + B)
    oracles = [_oracle_leaf(h, g, nb, mt, db, n, p) for h, g in leaves]
    hists = np.stack([h for h, _ in leaves])                # [N, F, B, 2]
    rows = hists.reshape(len(leaves), -1)
    meta = (jnp.array(nb), jnp.array(mt), jnp.array(db),
            jnp.ones(F, jnp.float32), jnp.ones(F, bool))
    sums = (jnp.array([o[2] for o in oracles], jnp.float32),
            jnp.array([o[3] for o in oracles], jnp.float32),
            jnp.full(len(leaves), n, jnp.int32),
            jnp.zeros(len(leaves), jnp.float32))
    res = find_best_split_dense(jnp.array(rows), *meta, *sums, p, max_bin=B)
    planted = case.startswith("empty_run")
    for i, (f, o, sum_g, sum_h) in enumerate(oracles):
        assert o is not None and o["gain"] > 0
        if planted:
            assert f == F // 2
            assert o["dl"] == (case == "empty_run_reverse")
        assert int(res.feature[i]) == f, (i, o)
        assert int(res.threshold[i]) == o["thr"], (i, o)
        assert bool(res.default_left[i]) == o["dl"], (i, o)
        assert int(res.left_count[i]) == o["lc"]
        assert int(res.right_count[i]) == n - o["lc"]
        np.testing.assert_allclose(float(res.left_sum_gradient[i]), o["lg"],
                                   rtol=2e-5, atol=2e-4)
        np.testing.assert_allclose(float(res.left_sum_hessian[i]), o["lh"],
                                   rtol=1e-6)
        np.testing.assert_allclose(float(res.gain[i]), o["gain"], rtol=2e-4)
        # the per-leaf entry is the same scan: equal to the last bit
        one = find_best_split(jnp.array(hists[i]), *meta,
                              *(s[i] for s in sums), p)
        for name, a, b in zip(res._fields, res, one):
            np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(b),
                                          err_msg=name)


def _scan_counters():
    from lightgbm_tpu.observability import global_registry
    return {k: global_registry.counter(f"split_scan_{k}_traces")
            for k in ("dense", "generic")}


def _traced_forms(fn, *args, **kw):
    """(dense, generic) scans TRACED by fn(*args): nothing runs."""
    import jax
    from lightgbm_tpu.ops.split import find_best_split_dense
    # a jitted entry is traced once a signature: forget the other tests'
    find_best_split.clear_cache()
    find_best_split_dense.clear_cache()
    before = _scan_counters()
    jax.eval_shape(lambda *a: fn(*a, **kw), *args)
    after = _scan_counters()
    return tuple(after[k] - before[k] for k in ("dense", "generic"))


def _grow_shapes(F, n, **meta_kw):
    import jax
    from lightgbm_tpu.learner import FeatureMeta
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, np.dtype(dt))
    meta = FeatureMeta(num_bin=sds((F,), "int32"),
                       missing_type=sds((F,), "int32"),
                       default_bin=sds((F,), "int32"),
                       penalty=sds((F,), "float32"), **meta_kw)
    return (sds((F, n), "uint8"), sds((n,), "float32"),
            sds((n,), "float32"), sds((n,), "float32"), sds((F,), "bool"),
            meta)


@pytest.mark.parametrize("F,B,hist_method", [
    (28, 255, "pallas"),        # higgs-2625k-b255.train, higgs-10500k-dp4
    (28, 63, "pallas"),         # higgs-2625k-b63.train
    (2000, 63, "pallas"),       # epsilon-400k-b63.train
    (28, 255, "segment")])      # dp4's shape again, off the chip
def test_wave_engine_traces_the_dense_scan_at_the_cells_shapes(
        F, B, hist_method):
    from lightgbm_tpu.learner import GrowParams
    from lightgbm_tpu.learner.wave import grow_tree_wave_impl
    params = GrowParams(
        num_leaves=255, max_bin=B, hist_method=hist_method, wave_prune=True,
        split=SplitParams(min_data_in_leaf=20, has_missing=False))
    dense, generic = _traced_forms(grow_tree_wave_impl,
                                   *_grow_shapes(F, 2048), params=params)
    # one trace a distinct leaf bound: the ladder's 8 ... 256 and on (the
    # jitted entry is traced once a signature, as the kernels' counters)
    assert dense >= 6 and generic == 0


@pytest.mark.parametrize("mode", ["leafwise", "bundles", "categorical",
                                  "monotone"])
def test_per_leaf_inputs_trace_the_generic_scan(mode):
    import jax
    from lightgbm_tpu.learner import GrowParams
    from lightgbm_tpu.learner.grow import grow_tree_impl
    from lightgbm_tpu.learner.wave import grow_tree_wave_impl
    F, n, B = 6, 1024, 16
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, np.dtype(dt))
    sp = SplitParams(min_data_in_leaf=5)
    kw, meta_kw, fn, Fh = {}, {}, grow_tree_wave_impl, F
    if mode == "leafwise":
        fn = grow_tree_impl
    elif mode == "categorical":
        sp = sp._replace(has_categorical=True)
        meta_kw = dict(is_cat=sds((F,), "bool"),
                       cat_index=sds((1,), "int32"))
    elif mode == "monotone":
        sp = sp._replace(has_monotone=True)
        meta_kw = dict(monotone=sds((F,), "int32"))
    else:
        Fh = 3                                  # six features in 3 groups
        kw = dict(has_bundles=True, group_max_bin=2 * B)
        meta_kw = {k: sds((F,), "int32") for k in ("group", "offset",
                                                   "zero_bin")}
        meta_kw["in_bundle"] = sds((F,), "bool")
    params = GrowParams(num_leaves=15, max_bin=B, hist_method="segment",
                        split=sp, **kw)
    args = _grow_shapes(F, n, **meta_kw)
    args = (sds((Fh, n), "uint8"),) + args[1:]
    dense, generic = _traced_forms(fn, *args, params=params)
    assert dense == 0 and generic >= 1
