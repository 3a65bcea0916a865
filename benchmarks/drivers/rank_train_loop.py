"""The ranking train-loop driver: `train_loop`'s sequence for a job whose
rows come in query groups and whose objective is per query.

    lgb.Dataset(X, label=y, group=g) -> lgb.Booster -> booster.update() ...

Set-up, window and traced run are `train_loop`'s, word for word: the same
`setup_s`, `iter_ms` and `heldout_quality` definitions, and its helpers
imported, not copied.  What differs is the data (the generator's
`groups(rows, seed)` beside its `make`: query lengths, rows of a query
contiguous; held-out rows are whole queries), the quality
(`quality/<metric>.py score(y, scores, group)`) and the checks: the five
`train_loop` has, and

* `gradients_on_device`: the booster took its ranking gradients from the
  device program, no host loop a query (where the configuration's
  `expect.gradients` is `device`);
* `first_tree_sums_its_rows` against the plain per-query reference
  (`references/<reference>.py check(tree, leaf, y, group, lr)`);
* `gradients_match_reference_at_end`: after the window and the quality
  trees, the program's own gradient call on the live scores against the
  reference's per-query loop, for `grad_check_queries` queries drawn from
  `--seed`, the longest query and one of length 1.

On the chip the driver first asks the program, while it draws and bins
the rows, whether its ranking gradient program carries the device scope
`GBDT::gradients` (`require_gradient_scope`), and exits non-zero in
seconds where it does not: a tree before PR 34 trains this shape, but
books the whole gradient program as unscoped, so the accepted
`gradients_ms`, which every cell that reports `iter_ms` has to report,
finds nothing to read there.
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

# The device program is float32 (scores, exp, the sums over a query's
# pairs); the reference is float64 on the same float32 scores, so both
# sort alike.  An error is |program - reference| over (GRAD_ATOL +
# GRAD_RTOL * |reference|), the largest over both arrays and all checked
# rows, and passes at 1 or less.  rtol 2e-3 / atol 2e-4 are the CPU
# test's (tests/test_rank_benchmark.py): float32 rounding of a score gap
# beside the 0.01 of `delta / (0.01 + |s_i - s_j|)` moves a pair's lambda
# by 1e-5 of itself, and a sum of up to 1,251 such pairs of both signs
# loses a few digits more.  Scores rounded to bf16 (8 bits: gaps move by
# 4e-3 of a score, many times the 0.01, and near ties swap places) or a
# dropped `norm` branch (a factor of log2(1 + S) / S) read hundreds; both
# readings are in PERF.md section 6 and in every run's facts
# (`grad_err`, `grad_err_bf16_scores`).
GRAD_RTOL = 2e-3
GRAD_ATOL = 2e-4

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GRADIENT_SCOPE = "GBDT.gradients"   # `GBDT::gradients` as an op_name holds it
PROBE_TIMEOUT_S = 120


def gradient_scope_in_program():
    """Whether the program's ranking gradient program carries
    `GBDT::gradients`: a booster over three queries (12 rows) through the
    normal path, its `_compute_gradients` lowered, nothing run, and the
    scope looked for in the lowered ops' names, which is where the
    trace's `gradients_ms` reads it."""
    import jax
    import lightgbm_tpu as lgb
    rs = np.random.RandomState(0)
    train_set = lgb.Dataset(
        rs.rand(12, 3).astype(np.float32),
        label=rs.randint(0, 3, 12).astype(np.float32), group=[1, 2, 9],
        params={"min_data_in_bin": 1, "verbose": -1})
    g = lgb.Booster({"objective": "lambdarank", "min_data_in_leaf": 1,
                     "verbose": -1}, train_set)._gbdt
    text = jax.jit(g._compute_gradients).lower().as_text(debug_info=True)
    return GRADIENT_SCOPE in text


def start_gradient_scope_probe():
    """Ask the program (`gradient_scope_in_program`) in a child process
    held to the CPU (`JAX_PLATFORMS=cpu`: it never reaches for the chip
    this process holds), so that the probe's booster leaves nothing in
    this process's timers and counters, which `rank_plan_s`,
    `rank_pad_ratio`, `find_bin_s` and others total.  The child imports
    the program while this process draws and bins the data, and is
    heard before the booster is built: `setup_s` waits for it only where
    it outlasts that (it took 15-16 s beside an 11.5 s draw and a 3.8 s
    construct; my chip run, PR 34)."""
    code = ("import json, sys; sys.path.insert(0, sys.argv[1]); "
            "from benchmarks.drivers import rank_train_loop as d; "
            "print(json.dumps({'gradient_scope': "
            "d.gradient_scope_in_program()}))")
    return subprocess.Popen(
        [sys.executable, "-c", code, ROOT], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)


def require_gradient_scope(probe, log):
    """Exit non-zero, seconds into the set-up, where the program's
    ranking gradients would run outside `GBDT::gradients`.  Where the
    program cannot be asked (the child fails, or says neither), nothing
    is refused.

    Why: the tree before PR 34 runs this shape (592.7 ms an iteration,
    my chip run, PR 34, PERF.md section 6) with its gradient program,
    256-259 ms of that, under no scope: `gradients_ms` then reads
    nothing, and a traced line without it is no result of this cell."""
    t0 = time.perf_counter()
    try:
        out, _ = probe.communicate(timeout=PROBE_TIMEOUT_S)
        scoped = json.loads(out.strip().splitlines()[-1])["gradient_scope"]
    except Exception as e:   # noqa: BLE001 - the gate has moved: not the program refused
        probe.kill()
        probe.wait()
        log(phase="gradient_scope_probe", asked=False, why=repr(e)[:200],
            waited_s=time.perf_counter() - t0)
        return
    log(phase="gradient_scope_probe", asked=True, scoped=bool(scoped),
        waited_s=time.perf_counter() - t0)
    if scoped is False:
        sys.exit("rank_train_loop: this program runs its ranking "
                 "gradients outside the device scope GBDT::gradients "
                 "(lowered for three queries, no op's name holds "
                 f"{GRADIENT_SCOPE}): gradients_ms, which every cell "
                 "that reports iter_ms has to report, would find "
                 "nothing to read; the cell needs the scoped gradient "
                 "program (PR 34)")


def _bf16(x):
    """float32 values rounded to the nearest bf16 (ties to even)."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def _grad_error(got, want):
    return float(np.max(np.abs(got - want)
                        / (GRAD_ATOL + GRAD_RTOL * np.abs(want))))


def _check_gradients(g, reference, y, group, n_queries, seed):
    """The program's gradient call on its live scores against the
    reference, on the rows of the drawn queries.  Returns (ok, facts)."""
    dev_fn = getattr(g, "_ranking_dev_fn", None)
    if not dev_fn:
        return False, {"grad_err": None}
    group = np.asarray(group, np.int64)
    rng = np.random.Generator(np.random.SFC64(seed))
    drawn = rng.choice(len(group), size=min(n_queries, len(group)),
                       replace=False)
    queries = np.unique(np.concatenate(
        [drawn, [int(np.argmax(group)), int(np.argmin(group))]]))
    ends = np.cumsum(group)
    rows = np.concatenate([np.arange(ends[q] - group[q], ends[q])
                           for q in queries])
    scores = np.asarray(g.scores)[0, :len(y)].astype(np.float32)
    grad, hess = dev_fn(g.scores, g.weight_dev)
    got = np.stack([np.asarray(grad)[0, rows], np.asarray(hess)[0, rows]])
    want = np.stack(reference.gradients(y, scores, group, queries))[:, rows]
    # the nearest precision below the configuration's: the same reference
    # on scores rounded to bf16, which has to read as not correct
    low = np.stack(reference.gradients(y, _bf16(scores), group,
                                       queries))[:, rows]
    err = _grad_error(got.astype(np.float64), want)
    return err <= 1.0, {
        "grad_check_queries": int(len(queries)),
        "grad_check_rows": int(len(rows)),
        "grad_check_longest": int(group[queries].max()),
        "grad_check_score_abs_max": float(np.abs(scores[rows]).max()),
        "grad_err": err, "grad_err_bf16_scores": _grad_error(low, want)}


def run(ctx):
    """Returns {"metrics", "spans", "counters", "attempted", "failed",
    "checks"}; facts go to `ctx.log` as earlier lines."""
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
    quality = importlib.import_module("benchmarks.quality."
                                      + config["quality"]["metric"])
    probe = start_gradient_scope_probe() if ctx.on_chip else None

    # ---------------------------------------------------------- set-up
    t0 = time.perf_counter()
    X, y = bench_data.make(config, ctx.seed)
    group = generator.groups(config["rows"], config["data_seed"])
    test_rows = int(traffic["test_rows"])
    X_test, y_test = bench_data.make(config, ctx.seed,
                                     heldout_rows=test_rows)
    group_test = generator.groups(test_rows, config["data_seed"] + 1)
    datagen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    train_set = lgb.Dataset(X, label=y, group=group, params=params)
    train_set.construct()
    binned = train_set._core.binned
    jax.block_until_ready(binned)
    construct_s = time.perf_counter() - t0
    if probe:
        # by now the child has had the 15 s it takes beside the draw
        # (my chip run, PR 34), so the wait is short
        require_gradient_scope(probe, ctx.log)

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

    grads_ok, grad_facts = _check_gradients(
        g, reference, y, group, int(traffic["grad_check_queries"]),
        ctx.seed)

    leaf = _on_host(booster, data=X, pred_leaf=True, num_iteration=1)
    leaf = np.asarray(leaf).reshape(len(X), -1)[:, 0]
    first_tree_ok, first_tree_facts = reference.check(
        g.models_[0], leaf, y, group, params["learning_rate"])

    heldout = np.asarray(_on_host(booster, data=X_test, raw_score=True,
                                  num_iteration=quality_trees))
    heldout_finite = bool(np.isfinite(heldout).all())
    heldout_quality = (quality.score(y_test, heldout, group_test)
                       if heldout_finite else float("nan"))
    checks = {
        "no_recompile_in_window": recompiles_in_window == 0,
        "first_tree_sums_its_rows": bool(first_tree_ok),
        "train_scores_finite": scores_finite,
        "heldout_scores_finite": heldout_finite,
        "quality_at_or_over_floor":
            heldout_quality >= config["quality"]["floor"],
        "gradients_match_reference_at_end": bool(grads_ok),
    }
    if config.get("expect", {}).get("gradients") == "device":
        checks["gradients_on_device"] = bool(
            getattr(g, "_ranking_dev_fn", None))
    checks_s = time.perf_counter() - t0

    ctx.log(phase="rank_train_loop", rows=len(X), features=X.shape[1],
            queries=len(group), heldout_queries=len(group_test),
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
            **first_tree_facts, **grad_facts, checks=checks)
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
