"""The categorical train-loop driver: `train_loop`'s sequence for a job
whose integer-coded categorical columns are handed to the Dataset as
`categorical_feature`, as upstream's Python API takes them.

    lgb.Dataset(X, label=y, categorical_feature=[...]) -> lgb.Booster
        -> booster.update() ...

Set-up, window and traced run are `train_loop`'s, word for word: the same
`setup_s`, `iter_ms` and `heldout_quality` definitions, and its helpers
imported, not copied.  What differs is the Dataset (the configuration's
`categorical_columns`, in its generator's layout, mapped through the
column order `data.py` draws from `--seed`) and the checks:
`train_loop`'s `no_recompile_in_window`, finite scores and
`quality_at_or_over_floor`, and

* `engine_is_wave_pallas` (on the chip): `auto` took the wave engine with
  the Pallas histogram kernel;
* `categorical`: the booster trains with `expect.cat_features` categorical
  columns, at least one with more bins than `max_bin` (upstream's
  99%-coverage rule), through the generic gain scan
  (`split_scan_generic_traces`): a run that one-hot coded or dropped them
  is no result of this cell;
* `categorical_splits_in_model`: the first `quality_trees` trees hold
  categorical splits;
* the three checks of the plain reference
  (`references/<reference>.py check(tree, X, y, columns, params)`):
  tree 0 against the raw columns.

On the chip the driver asks the program (`require_cat_scan`), in a child
held to the CPU that runs while the data is made, whether it labels the
categorical scan `Cat::scan` and counts its columns' bins (`cat_bins`),
and exits non-zero once the data is made where it does not: the cell's
`cat_scan_ms` and `cat_scan_roofline` would find nothing to read.
"""

import importlib
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks import data as bench_data
from benchmarks.drivers.train_loop import _counter, _on_host

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCAN_PART = "Cat.scan"      # `Cat::scan` as an op_name holds it
BINS_COUNTER = "cat_bins"
PROBE_TIMEOUT_S = 120


def cat_scan_in_program():
    """What the program's categorical path labels and counts: a booster
    over a three-row matrix of one categorical column through the normal
    path, whether its registry counted `cat_bins`, and whether the gain
    scan, lowered as the booster's own parameters ask (nothing run),
    carries `Cat::scan` in its ops' names, which is where the trace's
    `cat_scan_ms` reads it."""
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability import global_registry
    from lightgbm_tpu.ops.split import find_best_split
    params = {"objective": "binary", "min_data_in_bin": 1,
              "min_data_in_leaf": 1, "verbose": -1}
    train_set = lgb.Dataset(
        np.array([[0.0], [1.0], [2.0]], np.float32),
        label=np.array([0, 1, 0], np.float32), categorical_feature=[0],
        params=params)
    g = lgb.Booster(params, train_set)._gbdt
    counted = BINS_COUNTER in global_registry.snapshot()["counters"]
    gp, meta = g.grow_params, g.meta
    scope = "no categorical"
    if gp.split.has_categorical:
        F = int(meta.num_bin.shape[0])
        text = jax.jit(lambda h: find_best_split(
            h, meta.num_bin, meta.missing_type, meta.default_bin,
            meta.penalty, jnp.ones(F, bool), jnp.zeros(()), jnp.ones(()),
            jnp.asarray(3, jnp.int32), jnp.zeros(()), gp.split,
            is_cat_feature=meta.is_cat)).lower(
                jnp.zeros((F, gp.max_bin, 2))).as_text(debug_info=True)
        scope = SCAN_PART in text
    return {"cat_bins_counted": counted, "scan_scope": scope}


def start_cat_scan_probe():
    """Ask the program (`cat_scan_in_program`) in a child process held to
    the CPU (`JAX_PLATFORMS=cpu`: it never reaches for the chip this
    process holds), so that the probe's booster leaves nothing in this
    process's counters, which `cat_scan_roofline` reads."""
    code = ("import json, sys; sys.path.insert(0, sys.argv[1]); "
            "from benchmarks.drivers import cat_train_loop as d; "
            "print(json.dumps(d.cat_scan_in_program()))")
    return subprocess.Popen(
        [sys.executable, "-c", code, ROOT], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)


def require_cat_scan(probe, log):
    """Exit non-zero, before the Dataset is made, where the program
    would leave this cell's own per-layer metrics with nothing to read.
    Where the program cannot be asked (the child fails), nothing is
    refused."""
    t0 = time.perf_counter()
    try:
        out, _ = probe.communicate(timeout=PROBE_TIMEOUT_S)
        said = json.loads(out.strip().splitlines()[-1])
        counted, scope = bool(said["cat_bins_counted"]), said["scan_scope"]
    except Exception as e:   # noqa: BLE001 - the gate has moved: not the program refused
        probe.kill()
        probe.wait()
        log(phase="cat_scan_probe", asked=False, why=repr(e)[:200],
            waited_s=time.perf_counter() - t0)
        return
    log(phase="cat_scan_probe", asked=True, cat_bins_counted=counted,
        scan_scope=scope, waited_s=time.perf_counter() - t0)
    if not counted or scope is not True:
        sys.exit("cat_train_loop: this program does not label its "
                 "categorical scan Cat::scan or count cat_bins (asked on a "
                 "three-row categorical matrix): cat_scan_ms and "
                 "cat_scan_roofline would find nothing to read; the cell "
                 "needs the spans and counters of PR 41")


def categorical_feature(config, seed):
    """The configuration's categorical columns (its generator's layout)
    where `seed`'s column order puts them."""
    where = np.argsort(bench_data.column_order(config, seed))
    return sorted(int(where[c]) for c in config["categorical_columns"])


def reference_columns(core, cats):
    """{original column: ("num", bin upper bounds) or ("cat", the
    Dataset's bin map, None where it made the column numeric)} of the
    columns the Dataset uses; which are categorical is the
    configuration's `cats`, not the Dataset's say."""
    columns = {}
    for f in core.used_features:
        m = core.bin_mappers[f]
        if int(f) in cats:
            columns[int(f)] = ("cat", None if m.bin_type == 0 else {
                "num_bin": int(m.num_bin),
                "cat_to_bin": dict(m.categorical_2_bin),
                "bin_to_cat": list(m.bin_2_categorical)})
        elif m.missing_type != 0:
            raise ValueError("cat_train_loop: the reference scans numeric "
                             f"columns without a missing type; {f} is not "
                             "one")
        else:
            columns[int(f)] = ("num", np.asarray(m.bin_upper_bound,
                                                 np.float64))
    return columns


def operand_bits(hist_method):
    """The mantissa bits each row's gradient and hessian keep before the
    program sums them: bf16's 7 under the MXU's operand methods, all of
    them (None) otherwise."""
    from lightgbm_tpu.ops.histogram import BF16_OPERAND_METHODS
    return 7 if hist_method in BF16_OPERAND_METHODS else None


def _categorical_facts(g, params, expect):
    """(does the booster train on `expect`'s count of categorical columns,
    bundled as `expect` says, one column over `max_bin` bins, through the
    generic scan; facts)."""
    is_cat = np.asarray(g.f_is_cat, bool)
    cat_bins = [int(b) for b in np.asarray(g.f_num_bin)[is_cat]]
    generic = _counter("split_scan_generic_traces")
    bundled = bool(g.grow_params.has_bundles)
    ok = (int(is_cat.sum()) == int(expect["cat_features"])
          and bundled == bool(expect.get("bundled", False))
          and bool(cat_bins) and max(cat_bins) > int(params["max_bin"])
          and generic >= 1)
    return ok, {"cat_device_columns": int(is_cat.sum()), "bundled": bundled,
                "cat_num_bin": cat_bins,
                "num_bin": [int(b) for b in np.asarray(g.f_num_bin)],
                "hist_bins": int(g.grow_params.max_bin),
                "cat_bins": _counter("cat_bins"),
                "split_scan_generic_traces": generic}


def run(ctx):
    """Returns {"metrics", "spans", "counters", "attempted", "failed",
    "checks"}; facts go to `ctx.log` as earlier lines."""
    # the probe's child runs while this process makes the data
    probe = start_cat_scan_probe() if ctx.on_chip else None

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.timer import global_timer

    config, traffic = ctx.config, ctx.traffic
    params = dict(config["params"])
    quality_trees = int(traffic["quality_trees"])
    reference = importlib.import_module("benchmarks.references."
                                        + config["reference"])
    cats = categorical_feature(config, ctx.seed)

    # ---------------------------------------------------------- set-up
    t0 = time.perf_counter()
    X, y = bench_data.make(config, ctx.seed)
    X_test, y_test = bench_data.make(
        config, ctx.seed, heldout_rows=int(traffic["test_rows"]))
    datagen_s = time.perf_counter() - t0
    if probe is not None:
        require_cat_scan(probe, ctx.log)

    t0 = time.perf_counter()
    train_set = lgb.Dataset(X, label=y, categorical_feature=cats,
                            params=params)
    train_set.construct()
    core = train_set._core
    jax.block_until_ready(core.binned)
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
    trees = g.models_
    leaves = [int(t.num_leaves) for t in trees]
    scores_finite = bool(np.isfinite(np.asarray(g.scores)).all())
    window_leaves = leaves[first_window_tree:first_window_tree + attempted]
    failed = (sum(n <= 1 for n in window_leaves)
              if scores_finite else attempted)
    cat_splits = [int(np.count_nonzero(
        np.asarray(t.decision_type[:max(int(t.num_leaves) - 1, 0)]) & 1))
        for t in trees[:quality_trees]]
    splits = sum(max(n - 1, 0) for n in leaves[:quality_trees])

    reference_checks, reference_facts = reference.check(
        trees[0], X, y, reference_columns(core, cats), params,
        operand_bits(g.grow_params.hist_method))

    heldout = np.asarray(_on_host(booster, data=X_test, raw_score=True,
                                  num_iteration=quality_trees))
    heldout_finite = bool(np.isfinite(heldout).all())
    heldout_quality = (bench_data.quality(config, y_test, heldout)
                       if heldout_finite else float("nan"))
    expect = config.get("expect", {})
    categorical, cat_facts = _categorical_facts(g, params, expect)
    checks = {
        "no_recompile_in_window": recompiles_in_window == 0,
        "train_scores_finite": scores_finite,
        "heldout_scores_finite": heldout_finite,
        "quality_at_or_over_floor":
            heldout_quality >= config["quality"]["floor"],
        "categorical": categorical,
        "categorical_splits_in_model": sum(cat_splits) > 0,
        **{k: bool(v) for k, v in reference_checks.items()},
    }
    if ctx.on_chip:
        # the CPU's `auto` is the leaf-wise engine on the XLA histogram
        checks["engine_is_wave_pallas"] = (
            g.growth_strategy == expect.get("engine", "wave")
            and g.grow_params.hist_method == expect.get("hist_method",
                                                        "pallas"))
    checks_s = time.perf_counter() - t0

    timers = global_timer.snapshot()
    ctx.log(phase="cat_train_loop", rows=X.shape[0], features=X.shape[1],
            categorical_feature=cats, datagen_s=datagen_s,
            construct_s=construct_s,
            construct_spans_s={s: timers[s][0] for s in (
                "Dataset::find_bin", "Dataset::binning") if s in timers},
            booster_init_s=booster_init_s, first_iter_s=first_iter_s,
            compile_cache_after_first_iter=cache_after_first,
            setup_s=setup_s, window_s=window_s, iterations=attempted,
            trees_at_end=len(leaves), leaves_min=min(leaves),
            leaves_max=max(leaves), checks_s=checks_s,
            cat_splits_share=sum(cat_splits) / max(splits, 1),
            cat_splits_min_a_tree=min(cat_splits),
            cat_splits_tree0=cat_splits[0],
            cat_splits_total=_counter("cat_splits_total"),
            splits_total=_counter("splits_total"),
            growth_strategy=g.growth_strategy,
            hist_method=g.grow_params.hist_method,
            binned_dev=f"{g.binned_dev.dtype}{list(g.binned_dev.shape)}",
            recompiles_in_window=recompiles_in_window,
            peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
            peak_bytes_reserved=[s.get("peak_bytes_reserved")
                                 for s in stats],
            bytes_limit=[s.get("bytes_limit") for s in stats],
            **cat_facts, **reference_facts, checks=checks)
    return {
        "metrics": {"setup_s": setup_s,
                    "iter_ms": 1000.0 * window_s / max(attempted, 1),
                    "heldout_quality": heldout_quality},
        "spans": {"datagen_s": datagen_s, "construct_s": construct_s,
                  "booster_init_s": booster_init_s,
                  "first_iter_s": first_iter_s, "window_s": window_s},
        "counters": {"iterations": attempted, "rows_local": g.n_pad,
                     "features": int(g.binned_dev.shape[0]), "devices": 1,
                     "num_leaves": int(params["num_leaves"])},
        "attempted": attempted, "failed": int(failed), "checks": checks,
    }
