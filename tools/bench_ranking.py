"""Device vs host lambdarank gradient step on MSLR-like shapes
(VERDICT r3 item 6: >=5x gradient-step speedup at ~100k docs).

Times ONLY the gradient computation: host = the per-query numpy loop
(ranking.py RankingObjective.get_gradients_host), device = the bucketed
pairwise program (LambdarankNDCG.make_device_grad_fn) with a host
transfer as the completion barrier."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

N_DOCS = int(os.environ.get("RANKBENCH_DOCS", 100_000))
REPS = 10


def main():
    import jax.numpy as jnp

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.ranking import LambdarankNDCG

    rng = np.random.RandomState(0)
    # MSLR-WEB30K-like query-length mix (mean ~120 docs, long tail)
    lens = []
    total = 0
    while total < N_DOCS:
        ln = int(np.clip(rng.lognormal(4.2, 0.8), 1, 1200))
        lens.append(ln)
        total += ln
    lens[-1] -= total - N_DOCS
    if lens[-1] <= 0:
        lens.pop()
    n = sum(lens)
    labels = rng.randint(0, 5, n).astype(np.float64)
    md = Metadata(n)
    md.set_label(labels)
    md.set_group(np.asarray(lens, np.int64))
    obj = LambdarankNDCG(Config({"objective": "lambdarank"}))
    obj.init(md, n)
    score = rng.randn(n)

    t0 = time.time()
    for _ in range(3):
        obj.get_gradients_host(score)
    host_s = (time.time() - t0) / 3

    n_pad = (n + 1023) // 1024 * 1024
    fn = obj.make_device_grad_fn(n_pad)
    sc = jnp.zeros((1, n_pad)).at[0, :n].set(
        jnp.asarray(score, jnp.float32))
    g, h = fn(sc, None)
    _ = np.asarray(g)  # compile + settle
    t0 = time.time()
    for _ in range(REPS):
        g, h = fn(sc, None)
    _ = np.asarray(g) + np.asarray(h)  # completion barrier
    dev_s = (time.time() - t0) / REPS

    # rank_xendcg: same shapes, same harness (device program added in
    # round 5; ref cuda_rank_objective.cu:385-624)
    from lightgbm_tpu.ranking import RankXENDCG
    xobj = RankXENDCG(Config({"objective": "rank_xendcg"}))
    xobj.init(md, n)
    t0 = time.time()
    for _ in range(3):
        xobj.get_gradients_host(score)
    xe_host_s = (time.time() - t0) / 3
    xfn = xobj.make_device_grad_fn(n_pad)
    g, h = xfn(sc, None)
    _ = np.asarray(g)
    t0 = time.time()
    for _ in range(REPS):
        g, h = xfn(sc, None)
    _ = np.asarray(g) + np.asarray(h)
    xe_dev_s = (time.time() - t0) / REPS

    out = {"docs": n, "queries": len(lens),
           "host_grad_s": round(host_s, 4),
           "device_grad_s": round(dev_s, 4),
           "speedup": round(host_s / dev_s, 2),
           "xendcg_host_grad_s": round(xe_host_s, 4),
           "xendcg_device_grad_s": round(xe_dev_s, 4),
           "xendcg_speedup": round(xe_host_s / xe_dev_s, 2)}
    print(json.dumps(out))
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "bench_ranking.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
