"""Block-shape tuning for the wave kernel: unpadded F, Fg=F single group,
row-tile sweep.  Shapes: 1M rows, 28 features, 256 bins, 128 gh lanes."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

N = 1 << 20
B = 256
REPS = 10

rng = np.random.RandomState(0)


def timeit(name, fn):
    @jax.jit
    def loop():
        def step(c, _):
            r = fn()
            return c + jnp.float32(jnp.sum(r[..., 0])), None
        out, _ = jax.lax.scan(step, jnp.float32(0), None, length=REPS)
        return out
    try:
        loop().block_until_ready()
    except Exception as e:
        print(f"{name:50s} FAILED: {str(e)[:150]}", flush=True)
        return
    t0 = time.time()
    loop().block_until_ready()
    dt = (time.time() - t0) / REPS
    print(f"{name:50s} {dt*1e3:8.2f} ms", flush=True)


def kern(Fg, lanes):
    def kernel(rows_ref, gh_ref, out_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
        rows = rows_ref[...].astype(jnp.int32)
        ghv = gh_ref[...].astype(jnp.bfloat16)
        Rt = rows.shape[1]
        biota = jax.lax.broadcasted_iota(jnp.int32, (Fg, B, Rt), 1)
        oh = (rows[:, None, :] == biota).astype(jnp.bfloat16)
        acc = jax.lax.dot_general(
            oh.reshape(Fg * B, Rt), ghv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[...] += acc.reshape(Fg, B, lanes)
    return kernel


def run(name, F, Fg, row_tile, lanes=128):
    # generate on DEVICE: no host data, no H2D copy to wait for
    key = jax.random.PRNGKey(0)
    binned = jax.jit(lambda: jax.random.randint(
        key, (F, N), 0, B, jnp.int32).astype(jnp.uint8))()
    gh = jax.jit(lambda: jax.random.normal(key, (N, lanes), jnp.float32))()

    def fn():
        return pl.pallas_call(
            kern(Fg, lanes),
            grid=(F // Fg, N // row_tile),
            in_specs=[pl.BlockSpec((Fg, row_tile), lambda g, i: (g, i)),
                      pl.BlockSpec((row_tile, lanes), lambda g, i: (i, 0))],
            out_specs=pl.BlockSpec((Fg, B, lanes), lambda g, i: (g, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((F, B, lanes), jnp.float32),
        )(binned, gh)
    timeit(name, fn)


run("F=32 Fg=8  Rt=512 (current)", 32, 8, 512)
run("F=28 Fg=28 Rt=512", 28, 28, 512)
run("F=28 Fg=28 Rt=256", 28, 28, 256)
run("F=28 Fg=28 Rt=1024", 28, 28, 1024)
run("F=28 Fg=14 Rt=512", 28, 14, 512)
run("F=28 Fg=7  Rt=512", 28, 7, 512)
run("F=28 Fg=4  Rt=512", 28, 4, 512)
run("F=32 Fg=32 Rt=512", 32, 32, 512)
run("F=28 Fg=28 Rt=512 lanes=256", 28, 28, 512, 256)
run("F=28 Fg=28 Rt=384", 28, 28, 384)
