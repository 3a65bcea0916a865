"""Per-wave histogram kernel cost curve on the real chip.

Times build_histogram_wave at bench shapes (1M rows, 28 features, 256 bins)
across slot counts, many reps inside one jit (scan) so per-dispatch host
noise doesn't pollute the numbers.  Purpose: decide whether the wave cost is
VPU-bound (flat in NL) or MXU-bound (linear in NL beyond ~64 slots).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.ops.histogram import build_histogram_wave

N = 1 << 20
F = 28
B = 256
REPS = 10

rng = np.random.RandomState(0)
binned = jnp.asarray(rng.randint(0, B, size=(F, N), dtype=np.uint8))
gh = jnp.asarray(rng.randn(N, 3).astype(np.float32))


def timed(num_slots):
    slot = jnp.asarray(rng.randint(0, num_slots, size=N, dtype=np.int32))

    def one(c, _):
        h, cnt = build_histogram_wave(binned, slot, gh, max_bin=B,
                                      num_slots=num_slots)
        return c + h[0, 0, 0, 0] + cnt[0], None

    @jax.jit
    def loop():
        out, _ = jax.lax.scan(one, jnp.float32(0), None, length=REPS)
        return out

    loop().block_until_ready()  # compile
    t0 = time.time()
    r = loop().block_until_ready()
    dt = (time.time() - t0) / REPS
    return dt, float(r)


for nl in (8, 16, 32, 64, 128, 256):
    dt, _ = timed(nl)
    print(f"NL={nl:4d}  {dt*1e3:8.2f} ms/call", flush=True)
