"""Decomposed (hi/lo outer-product) wave-histogram kernel
(ops/histogram.py _wave_kernel_hl): parity against a numpy scatter oracle
and against the full wave kernel.

The Pallas kernel needs real TPU hardware; under the CPU test platform
these tests skip (same gating as test_wave_int8.py).  On the chip the
path is executed by tools/kernel_checks.py, which chip_smoke.py runs;
that the kernel COMPILES at the headline shapes is kept among the CPU
tests (tests/test_chip_compile.py)."""

import numpy as np
import pytest


@pytest.fixture(autouse=True, scope="module")
def _needs_tpu():
    """Decided when a test of this file starts, never while the module is
    imported: every xdist worker must collect the same tests."""
    import jax
    if jax.default_backend() != "tpu":
        pytest.skip("Pallas wave kernel needs TPU")


@pytest.mark.parametrize("S,out_slots", [(1, 8), (2, 8), (4, 8), (8, 8)])
def test_hl_wave_matches_scatter_oracle(S, out_slots):
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import build_histogram_wave_hl
    rng = np.random.RandomState(S)
    n, F, B = 1024 * 8, 12, 256
    binned = rng.randint(0, B, (F, n)).astype(np.uint8)
    # computed slots 0..S-1; everyone else carries a sentinel
    slot = rng.randint(0, 2 * S, n).astype(np.int32)
    slot = np.where(slot < S, slot, 10 ** 6).astype(np.int32)
    g = rng.randn(n).astype(np.float32)
    h = rng.rand(n).astype(np.float32)
    mask = (rng.rand(n) < 0.9).astype(np.float32)
    gh = np.stack([g * mask, h * mask, mask], 0).astype(np.float32)
    hist, cnt = build_histogram_wave_hl(
        jnp.asarray(binned), jnp.asarray(binned.T), jnp.asarray(slot),
        jnp.asarray(gh), max_bin=B, num_slots=S, out_slots=out_slots)
    assert hist.shape == (out_slots, F, B, 2)
    # oracle at the kernel's bf16 operand precision
    gb = np.asarray(jnp.asarray(gh[0]).astype(jnp.bfloat16), np.float64)
    hb = np.asarray(jnp.asarray(gh[1]).astype(jnp.bfloat16), np.float64)
    exp = np.zeros((out_slots, F, B, 2))
    inb = slot < S
    for f in range(F):
        np.add.at(exp[:, f, :, 0], (slot[inb], binned[f][inb]), gb[inb])
        np.add.at(exp[:, f, :, 1], (slot[inb], binned[f][inb]), hb[inb])
    np.testing.assert_allclose(np.asarray(hist, np.float64), exp,
                               rtol=1e-3, atol=1e-3)
    expc = np.bincount(slot[inb], weights=mask[inb], minlength=out_slots)
    np.testing.assert_array_equal(np.asarray(cnt), expc[:out_slots])


def test_hl_wave_matches_full_kernel():
    """hl and full kernels must agree (same bf16 operands, fp32 MXU
    accumulation) so the engine can switch per wave without model drift."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (build_histogram_wave,
                                            build_histogram_wave_hl)
    rng = np.random.RandomState(0)
    n, F, B, S = 1024 * 8, 28, 256, 4
    binned = rng.randint(0, B, (F, n)).astype(np.uint8)
    slot = rng.randint(0, 2 * S, n).astype(np.int32)
    slot = np.where(slot < S, slot, 10 ** 6).astype(np.int32)
    gh = np.stack([rng.randn(n), rng.rand(n), np.ones(n)],
                  0).astype(np.float32)
    h1, c1 = build_histogram_wave_hl(
        jnp.asarray(binned), jnp.asarray(binned.T), jnp.asarray(slot),
        jnp.asarray(gh), max_bin=B, num_slots=S, out_slots=8)
    h2, c2 = build_histogram_wave(
        jnp.asarray(binned), jnp.asarray(slot), jnp.asarray(gh),
        max_bin=B, num_slots=8)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
