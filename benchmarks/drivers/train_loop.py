"""The train-loop driver: one training job, closed loop by nature (the
next iteration after the last).

    lgb.Dataset -> lgb.Booster -> booster.update() ... until the clock says stop

Set-up is everything before the first timed iteration: the data (the
configuration's rows, columns in the seed's order), `Dataset.construct()` (binning), booster init, the first iteration
(trace + lower + compile or cache load) and the traffic's further warm-up
iterations, ended by `block_until_ready` on the scores.  The window then
runs `update()` with no sync added here — the program's own loop keeps
at most two trees in flight — and ends at `block_until_ready`.  A traced
run does the same set-up and brackets `traced_iters` steady iterations
with the profiler instead of the timed window.

Timing and checking helpers are copies of `chip_smoke.py` (PR 23), the
only ones proven on the chip.
"""

import importlib
import time

import numpy as np

from benchmarks import data as bench_data


def _counter(name):
    from lightgbm_tpu.observability import global_registry
    return int(global_registry.counter(name))


def _on_host(booster, **predict_args):
    """`Booster.predict` through the host predictor of the same booster
    (no device-predict program is compiled in a benchmark run)."""
    g = booster._gbdt
    prev = g.config.device_predict
    g.config.device_predict = "false"
    try:
        return booster.predict(**predict_args)
    finally:
        g.config.device_predict = prev


def _shards_ok(arr, devices, rows_per_shard):
    """`arr`'s row axis (its last) is split over all `devices`,
    `rows_per_shard` rows on each."""
    shards = arr.addressable_shards
    on = {s.device for s in shards}
    rows = sorted({int(s.data.shape[-1]) for s in shards})
    return (len(shards) == len(devices) and on == set(devices)
            and rows == [rows_per_shard])


def _mesh_facts(g, want_devices, on_chip):
    """Where the configuration expects a mesh: that many distinct
    devices (TPUs on the chip), the bin matrix one shard on each."""
    if g.mesh is None:
        return False, {"mesh": None}
    mesh_devices = list(g.mesh.devices.flat)
    distinct = len(set(mesh_devices))
    per = g.n_pad // max(distinct, 1)
    ok = (distinct == want_devices
          and (not on_chip or all(d.platform == "tpu" for d in mesh_devices))
          and _shards_ok(g.binned_dev, mesh_devices, per))
    return ok, {"mesh_devices": [str(d) for d in mesh_devices],
                "bin_shards": len(g.binned_dev.addressable_shards),
                "rows_per_shard": per}


def run(ctx):
    """Returns {"metrics", "spans", "counters", "attempted", "failed",
    "checks"}; facts go to `ctx.log` as earlier lines."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.timer import global_timer

    config, traffic = ctx.config, ctx.traffic
    params = dict(config["params"])
    quality_trees = int(traffic["quality_trees"])

    # ---------------------------------------------------------- set-up
    t0 = time.perf_counter()
    X, y = bench_data.make(config, ctx.seed)
    X_test, y_test = bench_data.make(
        config, ctx.seed, heldout_rows=int(traffic["test_rows"]))
    datagen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    train_set = lgb.Dataset(X, label=y, params=params)
    train_set.construct()
    binned = train_set._core.binned
    jax.block_until_ready(binned)
    construct_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    booster = lgb.Booster(params, train_set)
    g = booster._gbdt
    booster_init_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    booster.update()
    jax.block_until_ready(g.scores)
    first_iter_s = time.perf_counter() - t0
    cache_after_first = {"hits": _counter("compile_cache_hits"),
                         "misses": _counter("compile_cache_misses")}

    for _ in range(int(traffic["warmup_iters"]) - 1):
        booster.update()
    jax.block_until_ready(g.scores)
    setup_s = time.perf_counter() - ctx.t_start

    # ---------------------------------------------------------- window
    recompiles_before = _counter("recompiles")
    if ctx.trace:
        # host spans from the program's own scopes; no sync is added
        # (Timer.block is the identity while timing is off)
        global_timer.set_trace_annotations(True)
        jax.profiler.start_trace(ctx.trace_dir)
        t0 = time.perf_counter()
        for _ in range(int(traffic["traced_iters"])):
            with jax.profiler.TraceAnnotation("bench::update"):
                booster.update()
        with jax.profiler.TraceAnnotation("bench::sync"):
            jax.block_until_ready(g.scores)
        window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        global_timer.set_trace_annotations(False)
        attempted = int(traffic["traced_iters"])
    else:
        attempted = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds:
            booster.update()
            attempted += 1
        jax.block_until_ready(g.scores)
        window_s = time.perf_counter() - t0
    recompiles_in_window = _counter("recompiles") - recompiles_before
    first_window_tree = int(traffic["warmup_iters"])
    stats = [d.memory_stats() or {} for d in ctx.devices]

    # ---------------------------------------------------------- checks
    t0 = time.perf_counter()
    while booster.current_iteration() < quality_trees:
        booster.update()
    g._sync_model()   # materialize the trees still in flight
    leaves = [int(t.num_leaves) for t in g.models_]
    scores_finite = bool(np.isfinite(np.asarray(g.scores)).all())
    window_leaves = leaves[first_window_tree:first_window_tree + attempted]
    failed = (sum(n <= 1 for n in window_leaves)
              if scores_finite else attempted)

    reference = importlib.import_module(
        "benchmarks.references." + config["reference"])
    leaf = _on_host(booster, data=X, pred_leaf=True, num_iteration=1)
    leaf = np.asarray(leaf).reshape(len(X), -1)[:, 0]
    first_tree_ok, first_tree_facts = reference.check(
        g.models_[0], leaf, y, params["learning_rate"])

    heldout = np.asarray(_on_host(booster, data=X_test, raw_score=True,
                                  num_iteration=quality_trees))
    heldout_finite = bool(np.isfinite(heldout).all())
    heldout_quality = (bench_data.quality(config, y_test, heldout)
                       if heldout_finite else float("nan"))
    checks = {
        "no_recompile_in_window": recompiles_in_window == 0,
        "first_tree_sums_its_rows": bool(first_tree_ok),
        "train_scores_finite": scores_finite,
        "heldout_scores_finite": heldout_finite,
        "quality_at_or_over_floor":
            heldout_quality >= config["quality"]["floor"],
    }
    mesh_facts = {}
    want_devices = config.get("expect", {}).get("devices")
    if want_devices is not None:
        checks["mesh_and_shards"], mesh_facts = _mesh_facts(
            g, int(want_devices), ctx.on_chip)
    checks_s = time.perf_counter() - t0

    n_devices = 1 if g.mesh is None else int(g.mesh.devices.size)
    ctx.log(phase="train_loop", rows=len(X), features=X.shape[1],
            datagen_s=datagen_s, construct_s=construct_s,
            booster_init_s=booster_init_s, first_iter_s=first_iter_s,
            compile_cache_after_first_iter=cache_after_first,
            setup_s=setup_s, window_s=window_s, iterations=attempted,
            trees_at_end=len(leaves), leaves_min=min(leaves),
            leaves_max=max(leaves), checks_s=checks_s,
            growth_strategy=g.growth_strategy,
            hist_method=g.grow_params.hist_method,
            device_binned=isinstance(binned, jax.Array),
            binned_dev=f"{g.binned_dev.dtype}{list(g.binned_dev.shape)}",
            recompiles_in_window=recompiles_in_window,
            peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
            peak_bytes_reserved=[s.get("peak_bytes_reserved")
                                 for s in stats],
            bytes_limit=[s.get("bytes_limit") for s in stats],
            **first_tree_facts, **mesh_facts, checks=checks)
    return {
        "metrics": {"setup_s": setup_s,
                    "iter_ms": 1000.0 * window_s / max(attempted, 1),
                    "heldout_quality": heldout_quality},
        "spans": {"datagen_s": datagen_s, "construct_s": construct_s,
                  "booster_init_s": booster_init_s,
                  "first_iter_s": first_iter_s, "window_s": window_s},
        "counters": {"iterations": attempted,
                     "rows_local": g.n_pad // n_devices,
                     "features": int(g.binned_dev.shape[0]),
                     "devices": n_devices},
        "attempted": attempted, "failed": int(failed), "checks": checks,
    }
