"""Probe: hi/lo outer-product decomposition of the wave histogram kernel.

The wave kernel's floor is the F*B*Rt bin one-hot built in VMEM every wave
(PERF_NOTES.md).  For waves with FEW computed slots S the one-hot factors:

  onehot_B(bin) = onehot_Bh(bin >> log2(Bl))  (x)  onehot_Bl(bin & (Bl-1))

  hist[f, bh, bl, (c,s)] = sum_n 1[hi=bh] * (1[lo=bl] * w[n, (c,s)])

LHS volume F*Bh*Rt, RHS volume F*Bl*C*S*Rt — for small S both are far
below F*B*Rt (e.g. S=1: 48 vs 256 lane-units per feature per row).

The RHS is built at FULL 128-lane efficiency with expander matmuls
(sub-128-lane elementwise ops pad to full vregs on TPU, so a naive per-f
[Rt, C*S] build would pay full-width cost):

  d  = [lo_rm | 1] @ [E ; -bl_pat]   (one matmul: lo value minus the
                                      column's bl target; 0 where matched)
  wt = w_sc @ T                      (CS -> F*Bl*CS column tiling)
  sc = where(d == 0, wt, 0)

Main dots pack P features into M (P*Bh <= 256) and P column blocks into N.

Usage: python tools/profile_hl.py   (on the TPU chip)
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

N = 1 << 20
F = 28
B = 256
C = 2
Rt = 512
REPS = 10

rng = np.random.RandomState(0)
binned_np = rng.randint(0, B, size=(F, N), dtype=np.uint8)


def timeit(name, fn, *args):
    # float() of a scalar reduced on the device is the completion
    # barrier (block_until_ready on the result would do as well).
    # Inputs are perturbed per scan step so XLA cannot hoist the call.
    @jax.jit
    def loop(b, *rest):
        def step(c, x):
            r = fn(b, *rest[:-1], rest[-1].at[0, 0].add(x))
            return c + jnp.float32(jnp.sum(r[0][..., 0])), None
        out, _ = jax.lax.scan(step, jnp.float32(0),
                              jnp.arange(REPS, dtype=jnp.float32))
        return out
    try:
        float(loop(*args))
    except Exception as e:
        print(f"{name:44s} FAILED: {str(e)[:160]}", flush=True)
        return None
    best = 1e9
    for _ in range(3):
        t0 = time.time()
        float(loop(*args))
        best = min(best, (time.time() - t0) / REPS)
    print(f"{name:44s} {best*1e3:8.2f} ms", flush=True)
    return best


# the production kernel lives in ops/histogram.py; the probe wraps it so
# re-tuning always measures what ships
from lightgbm_tpu.ops.histogram import (build_histogram_wave,            # noqa: E402
                                        build_histogram_wave_hl)


def hist_hl(binned_fm, binned_rm, slot, gh, *, Bh, Bl, S, P):
    # Bh/Bl/P are chosen inside build_histogram_wave_hl (hl_split_of);
    # the probe's parameter columns document the expected pick
    return build_histogram_wave_hl(binned_fm, binned_rm, slot, gh,
                                   max_bin=B, num_slots=S, out_slots=S,
                                   row_tile=Rt)


def main():
    binned_fm = jnp.asarray(binned_np)
    binned_rm = jnp.asarray(binned_np.T)
    gvals = rng.randn(N, C).astype(np.float32)
    mask = np.ones((N, 1), np.float32)
    gh = jnp.asarray(np.concatenate([gvals, mask], axis=1))

    print(f"n={N}, F={F}, B={B}, C={C}, Rt={Rt}", flush=True)

    for S, Bh, Bl, P in [(1, 16, 16, 4), (2, 32, 8, 4), (4, 32, 8, 2),
                         (8, 64, 4, 2), (16, 64, 4, 1)]:
        slot_np = rng.randint(0, 2 * S, size=N).astype(np.int32)
        slot_np = np.where(slot_np < S, slot_np, 999999)  # sentinels
        slot = jnp.asarray(slot_np)
        # correctness vs XLA reference on a small prefix
        ns = 1 << 14
        h, cnt = functools.partial(hist_hl, Bh=Bh, Bl=Bl, S=S, P=P)(binned_fm[:, :ns][:, :Rt * (ns // Rt)],
                           binned_rm[:ns], slot[:ns], gh[:ns])
        oh_s = (np.asarray(slot[:ns])[:, None] == np.arange(S)[None, :])
        oh_b = (binned_np[:, :ns][:, :, None] ==
                np.arange(B)[None, None, :])
        ghb = np.asarray(jnp.asarray(gh[:ns, :C]).astype(jnp.bfloat16),
                         np.float64)  # kernel operands are bf16
        ref = np.einsum("ns,fnb,nc->sfbc", oh_s.astype(np.float64),
                        oh_b.astype(np.float64), ghb)
        err = np.abs(np.asarray(h, np.float64) - ref).max()
        refc = oh_s.sum(axis=0)
        errc = np.abs(np.asarray(cnt, np.float64)[:S] - refc).max()
        assert err < 1e-2 and errc == 0, (S, err, errc)
        timeit(f"hl S={S} Bh={Bh} Bl={Bl} P={P}",
               functools.partial(hist_hl, Bh=Bh, Bl=Bl, S=S, P=P),
               binned_fm, binned_rm, slot, gh)

    # current kernel baselines
    for Kb in (8, 16):
        slot = jnp.asarray(rng.randint(0, Kb, size=N).astype(np.int32))
        timeit(f"current wave kernel Kb={Kb}",
               functools.partial(build_histogram_wave, max_bin=B,
                                 num_slots=Kb), binned_fm, slot, gh)


if __name__ == "__main__":
    main()
