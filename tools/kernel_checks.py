"""On-chip Pallas kernel correctness gate, run by chip_smoke.py and
bench.py on the chip.

The kernel unit tests (tests/test_wave_hl.py, tests/test_wave_int8.py)
skip off-TPU, so without this gate a Mosaic/XLA regression in the
histogram kernels would surface only as an unexplained AUC delta.
chip_smoke.py and bench.py call run_checks() on the real chip and fail
on anything but "ok" — the TPU counterpart of the reference's dual-gate
CI (.ci scripts running both CPU and CUDA test legs).  A check that
raises is a named failure in the verdict, with its traceback on stderr.

Checks (small shapes, seconds of chip time):
  1. fused wave kernel == XLA one-hot fallback (fp32, exact histograms)
  2. decomposed hi/lo kernel == full kernel at few computed slots
  3. int8 quantized kernel: exact int32 accumulation of grid-snapped
     gradients (dequantized result equals the fp32 kernel on grid values)
  4. single-leaf Pallas histogram == segment lowering
"""
import os
import sys
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mk(n=2048, F=8, B=64, slots=8, seed=0):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    import ml_dtypes
    binned = rng.randint(0, B, size=(F, n)).astype(np.uint8)
    slot = rng.randint(0, slots, size=n).astype(np.int32)
    grad = rng.randn(n).astype(np.float32)
    hess = np.abs(rng.rand(n).astype(np.float32)) + 0.5
    mask = (rng.rand(n) < 0.9).astype(np.float32)
    gh = np.stack([grad * mask, hess * mask, mask], 0)    # [C+1, n]
    # the kernels' MXU operands are bf16 (single-precision histograms,
    # like the reference GPU learner): snap inputs to the bf16 grid so
    # host fp64 ground truth and on-chip fp32 accumulation agree exactly
    gh = gh.astype(ml_dtypes.bfloat16).astype(np.float32)
    return (jnp.asarray(binned), jnp.asarray(slot), jnp.asarray(gh),
            binned, slot, gh)


def _host_hist(binned, slot, gh, B, slots):
    """NumPy ground truth [slots, F, B, C] from gh [C+1, n]."""
    F, n = binned.shape
    C = gh.shape[0] - 1
    out = np.zeros((slots, F, B, C), np.float64)
    cnt = np.zeros(slots, np.float64)
    for r in range(n):
        s = slot[r]
        if s >= slots:
            continue
        for f in range(F):
            out[s, f, binned[f, r], :] += gh[:C, r]
        cnt[s] += gh[C, r]
    return out, cnt


def run_checks():
    """Returns "ok" or "fail:<which>"."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (build_histogram,
                                            build_histogram_rows_pallas,
                                            build_histogram_wave,
                                            build_histogram_wave_hl)
    failures = []
    B, slots = 64, 8
    binned, slot, gh, b_np, s_np, gh_np = _mk(B=B, slots=slots)
    want, want_cnt = _host_hist(b_np, s_np, gh_np, B, slots)

    # 1. fused wave kernel vs host ground truth (fp32 accumulates exactly
    #    at these magnitudes up to reduction-order ulps)
    try:
        h, cnt = build_histogram_wave(binned, slot, gh, max_bin=B,
                                      num_slots=slots)
        if not (np.allclose(np.asarray(h), want, rtol=1e-5, atol=1e-4)
                and np.allclose(np.asarray(cnt), want_cnt)):
            failures.append("wave_vs_host")
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"wave_raised({type(e).__name__})")

    # 2. decomposed hi/lo kernel vs the full kernel (few computed slots)
    try:
        few = jnp.where(slot < 2, slot, slots)   # 2 computed slots
        hf, cf = build_histogram_wave(binned, few, gh, max_bin=B,
                                      num_slots=8)
        hd, cd = build_histogram_wave_hl(binned, binned.T, few, gh,
                                         max_bin=B, num_slots=2,
                                         out_slots=8)
        if not (np.allclose(np.asarray(hf)[:2], np.asarray(hd)[:2],
                            rtol=1e-5, atol=1e-4)
                and np.allclose(np.asarray(cf)[:2], np.asarray(cd)[:2])):
            failures.append("hl_vs_full")
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"hl_raised({type(e).__name__})")

    # 3. int8 quantized kernel: grid-snapped grads accumulate EXACTLY
    try:
        qb = 16
        scales = np.array([0.11, 0.07], np.float32)
        kg = np.random.RandomState(1).randint(-qb, qb + 1, gh.shape[1])
        kh = np.random.RandomState(2).randint(0, qb + 1, gh.shape[1])
        mk = gh_np[2]
        # grid values pre-masked like the engine (grad*mask stays on grid)
        ghq = np.stack([kg * scales[0] * mk, kh * scales[1] * mk,
                        mk], 0).astype(np.float32)
        hq, cq = build_histogram_wave(
            binned, slot, jnp.asarray(ghq), max_bin=B, num_slots=slots,
            quant_bins=qb, quant_scales=jnp.asarray(scales))
        wq, wc = _host_hist(b_np, s_np, ghq, B, slots)
        # int32 accumulation then dequant: exact up to one float32 scale
        if not np.allclose(np.asarray(hq), wq, rtol=1e-6, atol=1e-5):
            failures.append("int8_exactness")
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"int8_raised({type(e).__name__})")

    # 4. single-leaf row-major Pallas histogram vs segment lowering
    try:
        rows = jnp.asarray(np.ascontiguousarray(np.asarray(binned).T))
        mask = gh[2]
        hp = build_histogram_rows_pallas(rows, gh[:2].T, mask, max_bin=B)
        hs = build_histogram(binned, gh[:2].T, mask, max_bin=B,
                             method="segment")
        if not np.allclose(np.asarray(hp), np.asarray(hs),
                           rtol=1e-5, atol=1e-4):
            failures.append("rows_pallas_vs_segment")
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"rows_raised({type(e).__name__})")

    return "ok" if not failures else "fail:" + ",".join(failures)


if __name__ == "__main__":
    print(run_checks())
