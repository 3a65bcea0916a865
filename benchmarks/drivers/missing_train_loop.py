"""The missing-value train-loop driver: `train_loop`'s sequence for a job
whose numeric columns hold NaN, judged against the raw float columns.

    lgb.Dataset(X, label=y) -> lgb.Booster -> booster.update() ...

Set-up, window and traced run are `train_loop`'s: the same `setup_s`,
`iter_ms` and `heldout_quality` definitions, its helpers imported, not
copied.  What differs is the rows' column order, which `--seed` does not
move here (`make_rows`), and the judge: `train_loop`'s reference sees the
leaf each row falls in, this one sees the raw matrix and the Dataset's
bounds (`references/<reference>.py check(tree, X, y, columns, params,
operand_bits)`), so that where NaN goes is decided on the raw values.
The checks are `train_loop`'s `no_recompile_in_window`, finite scores
and `quality_at_or_over_floor`, the reference's, and

* `engine_is_wave_pallas` (on the chip): `auto` took the wave engine
  with the Pallas histogram kernel, on bins made on the device;
* `missing_typed`: the booster trains on `expect.missing_features`
  columns with a NaN bin, unbundled as `expect.bundled` says, and its
  registry counted them (`missing_features`);
* `nan_both_ways`: the first `quality_trees` trees hold a split that
  sends NaN left and one that sends it right, and the registry's
  `missing_right_splits_total` counts the NaN-right splits of every
  tree grown.

On the chip the driver asks the program (`require_missing_scan`), in a
child held to the CPU that runs while the data is made, whether it labels
the forward scan `Split::missing_right` and counts `missing_features`,
and exits non-zero once the data is made where it does not: the cell's
`missing_scan_ms` would find nothing to read and `nan_both_ways` no
counter.
"""

import importlib
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks import data as bench_data
from benchmarks.drivers.cat_train_loop import operand_bits
from benchmarks.drivers.train_loop import _counter, _on_host

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCAN_PART = "Split.missing_right"   # `Split::missing_right` as an op_name holds it
COUNTER = "missing_features"
PROBE_TIMEOUT_S = 120
MISSING_NAN = 2


def missing_scan_in_program():
    """What the program's missing-value path labels and counts: a
    booster over a four-row matrix of one column with a NaN through the
    normal path, whether its registry counted `missing_features`, and
    whether its gain scan, lowered as the booster's own parameters ask
    (nothing run), carries `Split::missing_right` in its ops' names,
    which is where the trace's `missing_scan_ms` reads it."""
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability import global_registry
    from lightgbm_tpu.ops.split import find_best_split
    params = {"objective": "binary", "min_data_in_bin": 1,
              "min_data_in_leaf": 1, "verbose": -1}
    train_set = lgb.Dataset(
        np.array([[0.0], [1.0], [np.nan], [2.0]], np.float32),
        label=np.array([0, 1, 0, 1], np.float32), params=params)
    g = lgb.Booster(params, train_set)._gbdt
    counted = COUNTER in global_registry.snapshot()["counters"]
    gp, meta = g.grow_params, g.meta
    scope = "no missing type"
    if gp.split.has_missing:
        F = int(meta.num_bin.shape[0])
        text = jax.jit(lambda h: find_best_split(
            h, meta.num_bin, meta.missing_type, meta.default_bin,
            meta.penalty, jnp.ones(F, bool), jnp.zeros(()), jnp.ones(()),
            jnp.asarray(4, jnp.int32), jnp.zeros(()), gp.split)).lower(
                jnp.zeros((F, gp.max_bin, 2))).as_text(debug_info=True)
        scope = SCAN_PART in text
    return {"missing_features_counted": counted, "scan_scope": scope}


def start_missing_scan_probe():
    """Ask the program (`missing_scan_in_program`) in a child process
    held to the CPU (`JAX_PLATFORMS=cpu`: it never reaches for the chip
    this process holds), so that the probe's booster leaves nothing in
    this process's counters."""
    code = ("import json, sys; sys.path.insert(0, sys.argv[1]); "
            "from benchmarks.drivers import missing_train_loop as d; "
            "print(json.dumps(d.missing_scan_in_program()))")
    return subprocess.Popen(
        [sys.executable, "-c", code, ROOT], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)


def require_missing_scan(probe, log):
    """Exit non-zero, before the Dataset is made, where the program
    would leave this cell's own metric and counters with nothing to
    read.  Where the program cannot be asked (the child fails), nothing
    is refused."""
    t0 = time.perf_counter()
    try:
        out, _ = probe.communicate(timeout=PROBE_TIMEOUT_S)
        said = json.loads(out.strip().splitlines()[-1])
        counted, scope = bool(said["missing_features_counted"]), said[
            "scan_scope"]
    except Exception as e:   # noqa: BLE001 - the gate has moved: not the program refused
        probe.kill()
        probe.wait()
        log(phase="missing_scan_probe", asked=False, why=repr(e)[:200],
            waited_s=time.perf_counter() - t0)
        return
    log(phase="missing_scan_probe", asked=True,
        missing_features_counted=counted, scan_scope=scope,
        waited_s=time.perf_counter() - t0)
    if not counted or scope is not True:
        sys.exit("missing_train_loop: this program does not label its "
                 "forward scan Split::missing_right or count "
                 "missing_features (asked on a four-row matrix with a "
                 "NaN): missing_scan_ms and nan_both_ways would find "
                 "nothing to read; the cell needs a program that has "
                 "them")


def make_rows(config, heldout_rows=None):
    """The configuration's rows (`data_seed`; `heldout_rows` of its
    held-out rows from `data_seed + 1`) in the generator's column order,
    whatever `--seed` says.  Elsewhere `data.py` gives each seed another
    column order, which grows the same trees under other indices short of
    an exact tie between two columns' gains.  Here ties are common: at
    0.58% positives over 968 columns a node holding a few positives has
    many candidates with equal counts on each side, the smaller column
    index wins them (upstream's rule), and each order grows other trees
    (on a v5e six orders read `iter_ms` 1,038-1,067, a spread between
    quartiles of 1.6% where the cell may show 0.5%)."""
    gen = importlib.import_module("benchmarks.generators."
                                  + config["generator"])
    if heldout_rows is None:
        return gen.make(config["rows"], config["features"],
                        config["data_seed"])
    return gen.make(heldout_rows, config["features"],
                    config["data_seed"] + 1)


def reference_columns(core):
    """{original column: (the Dataset's bin upper bounds, its missing
    type)} of the columns the Dataset uses."""
    return {int(f): (np.asarray(core.bin_mappers[f].bin_upper_bound,
                                np.float64),
                     int(core.bin_mappers[f].missing_type))
            for f in core.used_features}


def nan_sides(trees):
    """(splits sending NaN left, splits sending it right) of numerical
    nodes on NaN-typed columns in `trees`."""
    left = right = 0
    for t in trees:
        dt = np.asarray(t.decision_type[:max(int(t.num_leaves) - 1, 0)],
                        np.int64)
        nan = (((dt >> 2) & 3) == MISSING_NAN) & ((dt & 1) == 0)
        left += int(np.count_nonzero(nan & ((dt & 2) != 0)))
        right += int(np.count_nonzero(nan & ((dt & 2) == 0)))
    return left, right


def _missing_facts(g, expect, counted):
    """(does the booster train on `expect`'s count of NaN-binned
    columns, bundled as `expect` says, its init counting them
    (`counted`: what it added to `missing_features`); facts)."""
    from lightgbm_tpu.io.binning import MISSING_NAN as PROGRAM_NAN
    from lightgbm_tpu.ops.histogram import hist_classes_of
    mt = np.asarray(g.f_missing_type)
    nan_cols = int(np.count_nonzero((mt == PROGRAM_NAN)
                                    & ~np.asarray(g.f_is_cat, bool)))
    bundled = bool(g.grow_params.has_bundles)
    ok = (nan_cols == int(expect["missing_features"]) == counted
          and bundled == bool(expect.get("bundled", False))
          and bool(g.grow_params.split.has_missing))
    classes = hist_classes_of(np.asarray(g.f_num_bin))[0]
    return ok, {"missing_device_columns": nan_cols, "bundled": bundled,
                "missing_features": counted,
                "hist_classes": [list(map(int, c)) for c in classes]}


def run(ctx):
    """Returns {"metrics", "spans", "counters", "attempted", "failed",
    "checks"}; facts go to `ctx.log` as earlier lines."""
    # the probe's child runs while this process makes the data
    probe = start_missing_scan_probe() if ctx.on_chip else None

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.timer import global_timer

    config, traffic = ctx.config, ctx.traffic
    params = dict(config["params"])
    quality_trees = int(traffic["quality_trees"])
    reference = importlib.import_module("benchmarks.references."
                                        + config["reference"])

    # ---------------------------------------------------------- set-up
    t0 = time.perf_counter()
    X, y = make_rows(config)
    X_test, y_test = make_rows(config, int(traffic["test_rows"]))
    datagen_s = time.perf_counter() - t0
    if probe is not None:
        require_missing_scan(probe, ctx.log)

    t0 = time.perf_counter()
    train_set = lgb.Dataset(X, label=y, params=params)
    train_set.construct()
    core = train_set._core
    binned = core.binned
    jax.block_until_ready(binned)
    construct_s = time.perf_counter() - t0

    # the registry's counters are the process's: this booster's are what
    # it adds to them
    counted_before = {c: _counter(c) for c in (
        COUNTER, "missing_right_splits_total")}
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
    nan_left, nan_right = nan_sides(trees[:quality_trees])
    right_counted = (_counter("missing_right_splits_total")
                     - counted_before["missing_right_splits_total"])
    right_grown = nan_sides(trees)[1]

    t1 = time.perf_counter()
    reference_checks, reference_facts = reference.check(
        trees[0], X, y, reference_columns(core), params,
        operand_bits(g.grow_params.hist_method))
    reference_s = time.perf_counter() - t1

    heldout = np.asarray(_on_host(booster, data=X_test, raw_score=True,
                                  num_iteration=quality_trees))
    heldout_finite = bool(np.isfinite(heldout).all())
    heldout_quality = (bench_data.quality(config, y_test, heldout)
                       if heldout_finite else float("nan"))
    expect = config.get("expect", {})
    missing_typed, missing_facts = _missing_facts(
        g, expect, _counter(COUNTER) - counted_before[COUNTER])
    checks = {
        "no_recompile_in_window": recompiles_in_window == 0,
        "train_scores_finite": scores_finite,
        "heldout_scores_finite": heldout_finite,
        "quality_at_or_over_floor":
            heldout_quality >= config["quality"]["floor"],
        "missing_typed": missing_typed,
        "nan_both_ways": (nan_left > 0 and nan_right > 0
                          and right_counted == right_grown),
        **{k: bool(v) for k, v in reference_checks.items()},
    }
    if ctx.on_chip:
        # the CPU's `auto` is the leaf-wise engine on the XLA histogram
        checks["engine_is_wave_pallas"] = (
            g.growth_strategy == expect.get("engine", "wave")
            and g.grow_params.hist_method == expect.get("hist_method",
                                                        "pallas")
            and isinstance(binned, jax.Array))
    checks_s = time.perf_counter() - t0

    timers = global_timer.snapshot()
    ctx.log(phase="missing_train_loop", rows=X.shape[0],
            features=X.shape[1], nan_share_X=reference_facts["nan_share"],
            positive_rate=float(np.mean(y > 0)), datagen_s=datagen_s,
            construct_s=construct_s,
            construct_spans_s={s: timers[s][0] for s in (
                "Dataset::find_bin", "Dataset::binning") if s in timers},
            booster_init_s=booster_init_s, first_iter_s=first_iter_s,
            compile_cache_after_first_iter=cache_after_first,
            setup_s=setup_s, window_s=window_s, iterations=attempted,
            trees_at_end=len(leaves), leaves_min=min(leaves),
            leaves_max=max(leaves), checks_s=checks_s,
            reference_s=reference_s, nan_left_splits=nan_left,
            nan_right_splits=nan_right,
            missing_right_splits_total=right_counted,
            nan_right_splits_grown=right_grown,
            splits_total=_counter("splits_total"),
            growth_strategy=g.growth_strategy,
            hist_method=g.grow_params.hist_method,
            device_binned=isinstance(binned, jax.Array),
            binned_dev=f"{g.binned_dev.dtype}{list(g.binned_dev.shape)}",
            recompiles_in_window=recompiles_in_window,
            peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
            peak_bytes_reserved=[s.get("peak_bytes_reserved")
                                 for s in stats],
            bytes_limit=[s.get("bytes_limit") for s in stats],
            **missing_facts, **reference_facts, checks=checks)
    return {
        "metrics": {"setup_s": setup_s,
                    "iter_ms": 1000.0 * window_s / max(attempted, 1),
                    "heldout_quality": heldout_quality},
        "spans": {"datagen_s": datagen_s, "construct_s": construct_s,
                  "booster_init_s": booster_init_s,
                  "first_iter_s": first_iter_s, "window_s": window_s},
        "counters": {"iterations": attempted, "rows_local": g.n_pad,
                     "features": int(g.binned_dev.shape[0]), "devices": 1},
        "attempted": attempted, "failed": int(failed), "checks": checks,
    }
