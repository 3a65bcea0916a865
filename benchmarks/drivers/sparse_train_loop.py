"""The sparse train-loop driver: `train_loop`'s sequence for a job whose
rows are handed over as a scipy CSR matrix.

    lgb.Dataset(csr, label=y) -> lgb.Booster -> booster.update() ...

Set-up, window and traced run are `train_loop`'s, word for word: the same
`setup_s`, `iter_ms` and `heldout_quality` definitions, and its helpers
imported, not copied.  What differs is the data (the generator takes the
column order `data.py` draws from `--seed` and writes it into the CSR's
indices: `data.make` would densify), the held-out rows (a CSR too,
scored by the host predictor in row chunks) and the checks: `train_loop`'s
`no_recompile_in_window`, finite scores and `quality_at_or_over_floor`,
and

* `engine_is_wave_pallas` (on the chip): `auto` took the wave engine with
  the Pallas histogram kernel;
* `bundled`: the booster trains on EFB bundle codes — `has_bundles`,
  fewer than 64 device columns, a uint8 bin matrix, and at conflict rate
  0 no row in which one bundle member overwrote another's code (the
  program's `efb_conflict_rows`); a densified or unbundled run is no
  result of this cell;
* the three checks of the plain reference
  (`references/<reference>.py check(tree, csc, y, bounds, params)`):
  tree 0 against the raw columns.

On the chip the driver first asks the program (`require_sparse_spans`),
in a child held to the CPU and before any data is made, whether its
sparse path records the host spans `Dataset::find_bin`,
`Dataset::binning` and `GBDT::plan_bundles` and labels the bundle decode
`Efb::decode`, and exits non-zero in seconds where it does not: a tree
before PR 36 trains this shape, but records none of the three spans on
this path, so the accepted `find_bin_s`, `binning_s` and `efb_plan_s`,
which every cell that reports `setup_s` has to report, find nothing to
read there.
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
HOST_SPANS = ("Dataset::find_bin", "Dataset::binning", "GBDT::plan_bundles")
DECODE_SCOPE = "Efb.decode"     # `Efb::decode` as an op_name holds it
PROBE_TIMEOUT_S = 120
MAX_DEVICE_COLUMNS = 64


def sparse_spans_in_program():
    """What the program's sparse path records: a booster over a three-row
    CSR of three exclusive columns (one bundle) through the normal path,
    the host spans its construct left in `global_timer`, and whether the
    bundle decode, lowered as the booster calls it (nothing run), carries
    `Efb::decode` in its ops' names, which is where the trace's
    `efb_decode_ms` reads it."""
    import jax
    import jax.numpy as jnp
    from scipy import sparse
    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner.grow import bundle_hist_to_features
    from lightgbm_tpu.utils.timer import global_timer
    params = {"objective": "binary", "min_data_in_bin": 1,
              "min_data_in_leaf": 1, "verbose": -1}
    train_set = lgb.Dataset(
        sparse.csr_matrix(np.eye(3, dtype=np.float32)),
        label=np.array([0, 1, 0], np.float32), params=params)
    g = lgb.Booster(params, train_set)._gbdt
    spans = [s for s in HOST_SPANS if s in global_timer.snapshot()]
    gp = g.grow_params
    decode = "no bundle"
    if gp.has_bundles:
        text = jax.jit(lambda h, sg, sh: bundle_hist_to_features(
            h, sg, sh, g.meta, gp.max_bin, gp.group_max_bin, True)).lower(
                jnp.zeros((int(g.binned_dev.shape[0]), gp.group_max_bin, 2)),
                jnp.zeros(()), jnp.zeros(())).as_text(debug_info=True)
        decode = DECODE_SCOPE in text
    return {"spans": spans, "decode_scope": decode}


def start_sparse_spans_probe():
    """Ask the program (`sparse_spans_in_program`) in a child process held
    to the CPU (`JAX_PLATFORMS=cpu`: it never reaches for the chip this
    process holds), so that the probe's Dataset and booster leave nothing
    in this process's timers and counters, which `find_bin_s`,
    `binning_s`, `efb_plan_s` and `efb_bundle_ratio` total."""
    code = ("import json, sys; sys.path.insert(0, sys.argv[1]); "
            "from benchmarks.drivers import sparse_train_loop as d; "
            "print(json.dumps(d.sparse_spans_in_program()))")
    return subprocess.Popen(
        [sys.executable, "-c", code, ROOT], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)


def require_sparse_spans(probe, log):
    """Exit non-zero, seconds into the set-up and before any data is
    made, where the program's sparse path would leave an accepted
    per-layer metric with nothing to read.  Where the program cannot be
    asked (the child fails, or says neither), nothing is refused."""
    t0 = time.perf_counter()
    try:
        out, _ = probe.communicate(timeout=PROBE_TIMEOUT_S)
        said = json.loads(out.strip().splitlines()[-1])
        spans, decode = list(said["spans"]), said["decode_scope"]
    except Exception as e:   # noqa: BLE001 - the gate has moved: not the program refused
        probe.kill()
        probe.wait()
        log(phase="sparse_spans_probe", asked=False, why=repr(e)[:200],
            waited_s=time.perf_counter() - t0)
        return
    log(phase="sparse_spans_probe", asked=True, spans=spans,
        decode_scope=decode, waited_s=time.perf_counter() - t0)
    lacking = [s for s in HOST_SPANS if s not in spans]
    if lacking or decode is False:
        sys.exit("sparse_train_loop: this program's sparse path records no "
                 f"{', '.join(lacking) or 'Efb::decode scope'} (asked on a "
                 "three-row CSR): find_bin_s, binning_s and efb_plan_s, "
                 "which every cell that reports setup_s has to report, "
                 "would find nothing to read; the cell needs the spans of "
                 "PR 36")


def _bundle_facts(g):
    """(is the booster training on bundle codes, facts)."""
    gp, plan = g.grow_params, g.bundle_plan
    cols = int(g.binned_dev.shape[0])
    ok = (bool(gp.has_bundles) and plan is not None
          and cols < MAX_DEVICE_COLUMNS
          and str(g.binned_dev.dtype) == "uint8")
    return ok, {
        "device_columns": cols,
        "used_features": int(len(g.f_num_bin)),
        "group_num_bin": ([] if plan is None
                          else [int(b) for b in plan.group_num_bin]),
        "hist_bins": int(gp.group_max_bin), "max_bin": int(gp.max_bin)}


def _reference_bounds(core):
    """{original column: bin upper bounds} of the columns the Dataset
    uses: the candidate thresholds of the reference's root scan."""
    bounds = {}
    for f in core.used_features:
        m = core.bin_mappers[f]
        if m.missing_type != 0 or m.bin_type != 0:
            raise ValueError("sparse_train_loop: the reference scans "
                             "numerical columns without a missing type; "
                             f"column {f} is not one")
        bounds[int(f)] = np.asarray(m.bin_upper_bound, np.float64)
    return bounds


def run(ctx):
    """Returns {"metrics", "spans", "counters", "attempted", "failed",
    "checks"}; facts go to `ctx.log` as earlier lines."""
    if ctx.on_chip:
        require_sparse_spans(start_sparse_spans_probe(), ctx.log)

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.timer import global_timer

    config, traffic = ctx.config, ctx.traffic
    params = dict(config["params"])
    quality_trees = int(traffic["quality_trees"])
    generator = importlib.import_module("benchmarks.generators."
                                        + config["generator"])
    reference = importlib.import_module("benchmarks.references."
                                        + config["reference"])

    # ---------------------------------------------------------- set-up
    t0 = time.perf_counter()
    order = bench_data.column_order(config, ctx.seed)
    X, y = generator.make(config["rows"], config["features"],
                          config["data_seed"], order)
    X_test, y_test = generator.make(int(traffic["test_rows"]),
                                    config["features"],
                                    config["data_seed"] + 1, order)
    datagen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    conflicts_before = _counter("efb_conflict_rows")
    train_set = lgb.Dataset(X, label=y, params=params)
    train_set.construct()
    core = train_set._core
    construct_s = time.perf_counter() - t0
    conflict_rows = _counter("efb_conflict_rows") - conflicts_before

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

    reference_checks, reference_facts = reference.check(
        g.models_[0], X.tocsc(), y, _reference_bounds(core), params)

    heldout = np.asarray(_on_host(booster, data=X_test, raw_score=True,
                                  num_iteration=quality_trees))
    heldout_finite = bool(np.isfinite(heldout).all())
    heldout_quality = (bench_data.quality(config, y_test, heldout)
                       if heldout_finite else float("nan"))
    bundled, bundle_facts = _bundle_facts(g)
    # at conflict rate 0 no row may have lost a bundle member's code
    bundled = bundled and (conflict_rows == 0
                           or params.get("max_conflict_rate", 0.0) > 0)
    bundle_facts["conflict_rows"] = conflict_rows
    checks = {
        "no_recompile_in_window": recompiles_in_window == 0,
        "train_scores_finite": scores_finite,
        "heldout_scores_finite": heldout_finite,
        "quality_at_or_over_floor":
            heldout_quality >= config["quality"]["floor"],
        "bundled": bundled,
        **{k: bool(v) for k, v in reference_checks.items()},
    }
    if ctx.on_chip:
        # the CPU's `auto` is the leaf-wise engine on the XLA histogram
        expect = config.get("expect", {})
        checks["engine_is_wave_pallas"] = (
            g.growth_strategy == expect.get("engine", "wave")
            and g.grow_params.hist_method == expect.get("hist_method",
                                                        "pallas"))
    checks_s = time.perf_counter() - t0

    timers = global_timer.snapshot()
    ctx.log(phase="sparse_train_loop", rows=X.shape[0], features=X.shape[1],
            stored_values=int(X.nnz), datagen_s=datagen_s,
            construct_s=construct_s,
            construct_spans_s={s: timers[s][0] for s in HOST_SPANS
                               if s in timers},
            booster_init_s=booster_init_s, first_iter_s=first_iter_s,
            compile_cache_after_first_iter=cache_after_first,
            setup_s=setup_s, window_s=window_s, iterations=attempted,
            trees_at_end=len(leaves), leaves_min=min(leaves),
            leaves_max=max(leaves), checks_s=checks_s,
            growth_strategy=g.growth_strategy,
            hist_method=g.grow_params.hist_method,
            binned_dev=f"{g.binned_dev.dtype}{list(g.binned_dev.shape)}",
            split_scan_traces={
                "dense": _counter("split_scan_dense_traces"),
                "generic": _counter("split_scan_generic_traces")},
            recompiles_in_window=recompiles_in_window,
            peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
            peak_bytes_reserved=[s.get("peak_bytes_reserved")
                                 for s in stats],
            bytes_limit=[s.get("bytes_limit") for s in stats],
            **bundle_facts, **reference_facts, checks=checks)
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
