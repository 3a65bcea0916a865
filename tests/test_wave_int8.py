"""Quantized int8 wave-histogram kernel (ref: dense_bin.hpp:174
ConstructHistogramIntInner; gradient_discretizer.hpp): exact int32
accumulation through the MXU int8 path.

The Pallas kernel needs real TPU hardware; under the CPU test platform
these tests skip.  On the chip the path is executed by
tools/kernel_checks.py, which chip_smoke.py runs; that the kernel
COMPILES at the headline shapes is kept among the CPU tests
(tests/test_chip_compile.py)."""

import numpy as np
import pytest


@pytest.fixture(autouse=True, scope="module")
def _needs_tpu():
    """Decided when a test of this file starts, never while the module is
    imported: every xdist worker must collect the same tests."""
    import jax
    if jax.default_backend() != "tpu":
        pytest.skip("Pallas wave kernel needs TPU")


def test_int8_wave_matches_integer_oracle():
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import build_histogram_wave
    rng = np.random.RandomState(0)
    n, F, B, NL = 1024 * 16, 12, 64, 32
    qbins, qhalf = 4, 2
    gscale, hscale = 0.0123, 0.0456
    binned = rng.randint(0, B, (F, n)).astype(np.uint8)
    slot = rng.randint(0, NL, n).astype(np.int32)
    gi = rng.randint(-qhalf, qhalf + 1, n)
    hi = rng.randint(0, qbins + 1, n)
    mask = (rng.rand(n) < 0.9).astype(np.float32)
    gh = np.stack([gi * gscale * mask, hi * hscale * mask, mask],
                  0).astype(np.float32)
    h, c = build_histogram_wave(
        jnp.asarray(binned), jnp.asarray(slot), jnp.asarray(gh),
        max_bin=B, num_slots=NL, quant_bins=qbins,
        quant_scales=jnp.asarray([gscale, hscale], jnp.float32))
    exp = np.zeros((NL, F, B, 2))
    mi = mask.astype(np.int64)
    for f in range(F):
        np.add.at(exp[:, f, :, 0], (slot, binned[f]), gi * mi)
        np.add.at(exp[:, f, :, 1], (slot, binned[f]), hi * mi)
    exp[..., 0] *= gscale
    exp[..., 1] *= hscale
    np.testing.assert_allclose(np.asarray(h), exp, rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(
        np.asarray(c), np.bincount(slot, mi, minlength=NL))


def test_quantized_wave_training_quality():
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(1)
    n, F = 100_000, 10
    X = rng.rand(n, F).astype(np.float32)
    y = (rng.rand(n) < 1 / (1 + np.exp(-4 * (X[:, 0] - 0.5)))).astype(
        np.float32)
    base = {"objective": "binary", "num_leaves": 63, "verbose": -1}
    b_fp = lgb.train(base, lgb.Dataset(X, label=y), num_boost_round=8)
    b_q = lgb.train({**base, "use_quantized_grad": True},
                    lgb.Dataset(X, label=y), num_boost_round=8)
    assert b_q._gbdt.grow_params.quant_bins > 0
    corr = np.corrcoef(b_fp.predict(X), b_q.predict(X))[0, 1]
    assert corr > 0.99, corr
