"""Multi-chip scaling evidence (VERDICT round-2 item 6): compile the
data-parallel wave training step over virtual CPU meshes of 1/2/4/8
devices, count the all-reduce collectives and their byte volumes from the
compiled HLO, time a step at each mesh size, and print the ICI-cost
projection for a v5e-8 slice.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python tools/collective_accounting.py
"""
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np

N = 1 << 14
F = 8
B = 64
L = 31


def all_reduce_stats(hlo_text):
    """(count, total bytes) of all-reduce results in compiled HLO: scan
    lines whose op is all-reduce(-start) and sum their RESULT shapes."""
    total_bytes = 0
    count = 0
    sz = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f64": 8,
          "s64": 8, "u8": 1, "s8": 1, "pred": 1}
    for line in hlo_text.splitlines():
        if ("all-reduce(" not in line and "all-reduce-start(" not in line) \
                or "=" not in line:
            continue
        # result shape sits between "= " and the op name (the op NAME
        # itself contains "all-reduce", so split after the "=")
        lhs = line.split(" = ", 1)[1].split("all-reduce")[0]
        shapes = re.findall(r"(f32|s32|bf16|f64|s64|u32|u8|s8|pred)"
                            r"\[([\d,]*)\]", lhs)
        for dt, dims in shapes:
            elems = 1
            for d in dims.split(","):
                if d:
                    elems *= int(d)
            total_bytes += elems * sz[dt]
        count += 1
    return count, total_bytes


def main():
    import jax

    import lightgbm_tpu as lgb

    rng = np.random.RandomState(0)
    X = rng.rand(N, F).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.1 * rng.randn(N) > 0.7).astype(np.float64)

    results = {}
    for ndev in (1, 2, 4, 8):
        params = {"objective": "binary", "num_leaves": L, "max_bin": B,
                  "verbosity": -1, "metric": "none",
                  "tree_learner": "data", "num_machines": ndev,
                  "tpu_growth_strategy": "wave", "hist_method": "segment"}
        b = lgb.Booster(params=params,
                        train_set=lgb.Dataset(X, label=y))
        t0 = time.time()
        b.update()                      # compile + first step
        compile_s = time.time() - t0
        t0 = time.time()
        for _ in range(3):
            b.update()
        _ = np.asarray(b._gbdt.scores[0][:4])
        step_s = (time.time() - t0) / 3
        mesh = b._gbdt.mesh
        results[ndev] = {"step_s": step_s, "compile_s": compile_s,
                         "mesh": None if mesh is None
                         else tuple(mesh.devices.shape)}
        print(f"ndev={ndev}: step {step_s*1e3:8.1f} ms "
              f"(compile {compile_s:.1f}s, mesh "
              f"{results[ndev]['mesh']})", flush=True)

    # collective accounting from the compiled HLO of the tree builder
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lightgbm_tpu.learner import FeatureMeta, GrowParams, grow_tree_wave
    from lightgbm_tpu.ops.split import SplitParams
    import jax.numpy as jnp
    devs = np.array(jax.devices()[:8]).reshape(8)
    mesh = Mesh(devs, ("row",))
    shard = NamedSharding(mesh, P(None, "row"))
    repl = NamedSharding(mesh, P())
    rowsh = NamedSharding(mesh, P("row"))
    binned = jax.device_put(
        rng.randint(0, B, size=(F, N)).astype(np.uint8), shard)
    grad = jax.device_put(rng.randn(N).astype(np.float32), rowsh)
    hess = jax.device_put(np.abs(rng.rand(N).astype(np.float32)) + 0.1,
                          rowsh)
    mask = jax.device_put(np.ones(N, np.float32), rowsh)
    cmask = jax.device_put(np.ones(F, bool), repl)
    meta = FeatureMeta(
        num_bin=jax.device_put(np.full(F, B, np.int32), repl),
        missing_type=jax.device_put(np.zeros(F, np.int32), repl),
        default_bin=jax.device_put(np.zeros(F, np.int32), repl),
        penalty=jax.device_put(np.ones(F, np.float32), repl))
    gp = GrowParams(num_leaves=L, max_bin=B, hist_method="segment",
                    split=SplitParams(min_data_in_leaf=20))
    lowered = jax.jit(grow_tree_wave, static_argnames=("params",)).lower(
        binned, grad, hess, mask, cmask, meta, gp)
    hlo = lowered.compile().as_text()
    n_ar, bytes_ar = all_reduce_stats(hlo)
    print(f"grow_tree_wave over 8-device row mesh: {n_ar} all-reduce ops, "
          f"{bytes_ar/1e6:.2f} MB reduced per tree", flush=True)

    # ICI projection at bench scale (v5e-8, 45 GB/s per link, ring
    # all-reduce 2(p-1)/p factor)
    F_b, B_b, L_b = 28, 256, 255
    kbs = [8, 8, 8, 8, 8, 16, 32, 64]      # ladder Kb with subtraction
    bytes_per_iter = sum(k * F_b * B_b * 2 * 4 for k in kbs)
    ici = bytes_per_iter * 2 * 7 / 8 / 45e9
    print(f"bench-scale projection: {bytes_per_iter/1e6:.1f} MB of "
          f"histogram psum per iter -> ~{ici*1e3:.2f} ms over v5e-8 ICI "
          f"(vs 145 ms single-chip compute)", flush=True)
    return results, n_ar, bytes_ar


if __name__ == "__main__":
    main()
