"""Measured-bandwidth microbenchmarks for the roofline report.

PERF_NOTES' roofline previously divided the wave kernel's modeled streamed
volume by the v5e's NOMINAL ~2 TB/s VMEM figure, which produced
`est_vmem_bw_frac: 1.38` — a >1.0 "fraction" that only proves the model
or the nominal roof is off.  This tool measures the roofs this chip
actually delivers:

* hbm_stream_tbps — big out-of-place elementwise op over an HBM-resident
  array (reads + writes counted), the classic stream test.
* vmem_stream_tbps — a Pallas kernel whose grid re-reads the SAME
  VMEM-resident block every step and accumulates it; after the first
  step the block never leaves VMEM, so the sustained rate is VMEM read
  bandwidth as Mosaic schedules it (including the per-step VPU add).

Every measurement ends in float(...) of one scalar reduced on the
device: that is a completion barrier and moves four bytes.

Writes docs/bandwidth.json; tools/bench_10m.py divides its volume model
by these measured roofs.
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _time(fn, *args, reps=3):
    """fn must return a SCALAR (reduce on device, pull one float, so the
    D2H copy of a whole array stays out of the timing)."""
    float(fn(*args))                # compile + first-run autotune
    best = float("inf")
    for _ in range(reps):
        t = time.time()
        _ = float(fn(*args))
        best = min(best, time.time() - t)
    return best


def hbm_stream(jax, jnp, nbytes=1 << 29, steps=256):
    n = nbytes // 4
    x = jnp.ones((n,), jnp.float32)

    @jax.jit
    def loop(a):
        def step(c, i):
            c = c * 1.0000001 + i   # carried: every step re-streams HBM
            return c, None
        out, _ = jax.lax.scan(step, a,
                              jnp.arange(steps, dtype=jnp.float32))
        return jnp.sum(out[:8])

    t = _time(loop, x)
    return 2.0 * nbytes * steps / t / 1e12   # read + write per step


def vmem_stream(jax, jnp, steps=1 << 19, rows=512, lanes=2048):
    """Accumulate the same [rows, lanes] bf16 block `steps` times; the
    block (2MB) stays VMEM-resident across grid steps (constant
    index_map), so steady-state traffic is VMEM reads."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)
        o_ref[...] += x_ref[...].astype(jnp.float32)

    x = jnp.ones((rows, lanes), jnp.bfloat16)
    call = pl.pallas_call(
        kernel, grid=(steps,),
        in_specs=[pl.BlockSpec((rows, lanes), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rows, lanes), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32))
    f = jax.jit(lambda a: jnp.sum(call(a)[:2, :8]))
    t = _time(f, x)
    return steps * rows * lanes * 2 / t / 1e12


def main():
    import jax
    import jax.numpy as jnp
    out = {
        "hbm_stream_tbps": round(hbm_stream(jax, jnp), 3),
        "vmem_stream_tbps": round(vmem_stream(jax, jnp), 3),
        "backend": jax.default_backend(),
        "measured_at": time.strftime("%Y-%m-%d"),
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "bandwidth.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
