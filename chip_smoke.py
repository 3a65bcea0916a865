#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one chip: train -> predict -> serve
    python chip_smoke.py --chips 4    # the data-parallel path, nothing else

One process, the entry points a user calls (`lgb.Dataset`, `lgb.train`,
`Booster.predict`, `save_model`, `ServingDaemon`), the repo's headline
shape (2^20 rows x 28 features, 255 leaves, 255 bins) on data made from
`--seed`.  Each phase prints one JSON line with its name, its seconds
and the facts it checked; the first failed check ends the run with a
non-zero exit.  The last line of a run that passed is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

There is no CPU mode: when JAX finds no TPU the script exits non-zero
before it generates any data.  The seconds, bytes and cache counts in
the phase lines are smoke facts (did it compile, did the cache hit,
does it fit), not benchmark numbers.
"""

import argparse
import importlib.metadata
import json
import os
import socket
import sys
import tempfile
import threading
import time

import numpy as np

ROWS = 1 << 20          # io/device_bin.py engages at >= 2^20 float32 rows
TEST_ROWS = 100_000
FEATURES = 28
TRAIN_ROUNDS = 8
PARALLEL_ROUNDS = 4
MIN_AUC = 0.70
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
          "learning_rate": 0.1, "min_data_in_leaf": 20}
# serving ladder: buckets 64..1024 cover the request sizes below
SERVE_PARAMS = {"device_predict_min_bucket": 64,
                "serve_max_batch_rows": 1024}
REQUEST_ROWS = (1, 7, 64, 1000)


class SmokeFailure(AssertionError):
    pass


def require(cond, what):
    """A check on results: holds on any backend."""
    if not cond:
        raise SmokeFailure(what)


def require_on_chip(cond, what):
    """A check that the device path engaged: holds only on a TPU (the CPU
    rehearsal of the phase functions replaces this one, never `require`)."""
    require(cond, what)


def emit(phase, t0, **facts):
    print(json.dumps({"phase": phase,
                      "seconds": round(time.perf_counter() - t0, 3),
                      **facts}), flush=True)


def _counter(name):
    from lightgbm_tpu.observability import global_registry
    return int(global_registry.counter(name))


def _cache_counts():
    return {"hits": _counter("compile_cache_hits"),
            "misses": _counter("compile_cache_misses")}


# ------------------------------------------------------------------ device
def phase_device(chips):
    """Find the TPU or stop: nothing is generated, built or compiled
    before this has passed."""
    t0 = time.perf_counter()
    import jax
    import jaxlib
    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        sys.exit(f"chip_smoke.py: no TPU found (JAX's default backend is "
                 f"{first.platform!r}, {len(devices)} device(s)); this "
                 "script runs on the chip only")
    if chips == 4 and len(devices) != 4:
        sys.exit(f"chip_smoke.py --chips 4 needs a host with exactly 4 "
                 f"TPU chips, JAX found {len(devices)}")
    from lightgbm_tpu import native
    from lightgbm_tpu.observability import configure_compile_cache
    emit("device", t0, platform=first.platform, kind=first.device_kind,
         count=len(devices), jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=importlib.metadata.version("libtpu"),
         compile_cache_dir=configure_compile_cache(),
         native_built={"predict": native.predictor_lib() is not None,
                       "parser": native.parser_lib() is not None,
                       "treeshap": native.treeshap_lib() is not None})
    return devices


# ----------------------------------------------------------------- kernels
def phase_kernels():
    t0 = time.perf_counter()
    from tools.kernel_checks import run_checks
    verdict = run_checks()
    require(verdict == "ok", f"on-chip kernel checks: {verdict}")
    emit("kernels", t0, kernel_checks=verdict)


# ------------------------------------------------------------------- train
def _train_timed(params, X, y, rounds):
    """`lgb.Dataset` + `lgb.train` with the clock read after every
    iteration, once the scores have settled on the device."""
    import jax
    import lightgbm_tpu as lgb

    t0 = time.perf_counter()
    train_set = lgb.Dataset(X, label=y, params=params)
    train_set.construct()
    binned = train_set._core.binned
    device_binned = isinstance(binned, jax.Array)
    jax.block_until_ready(binned)
    construct_s = time.perf_counter() - t0

    stamps, recompiles = [], []

    def after_iteration(env):
        jax.block_until_ready(env.model._gbdt.scores)
        stamps.append(time.perf_counter())
        recompiles.append(_counter("recompiles"))

    t0 = time.perf_counter()
    booster = lgb.train(params, train_set, num_boost_round=rounds,
                        callbacks=[after_iteration])
    require(len(stamps) == rounds,
            f"trained {len(stamps)} iterations, wanted {rounds}")
    steady = stamps[-6:] if rounds >= 6 else stamps
    facts = {
        "construct_s": round(construct_s, 3),
        "first_iter_s": round(stamps[0] - t0, 3),
        "steady_iter_s": round((steady[-1] - steady[0])
                               / max(len(steady) - 1, 1), 4),
        "steady_over_last": len(steady) - 1,
        "recompiles_after_first_iter": recompiles[-1] - recompiles[0],
    }
    return booster, device_binned, facts


def _require_default_tpu_engine(booster, device_binned, facts):
    """What the code selects from the backend it observes must be the
    TPU configuration — read from the booster, not from a flag."""
    import jax
    g = booster._gbdt
    require_on_chip(g.growth_strategy == "wave",
                    f"growth_strategy is {g.growth_strategy!r}, not 'wave'")
    require_on_chip(g.grow_params.hist_method == "pallas",
                    f"hist_method is {g.grow_params.hist_method!r}, "
                    "not 'pallas'")
    require_on_chip(device_binned,
                    "the bin matrix was binned on the host "
                    "(io/device_bin.py did not engage)")
    require(isinstance(g.binned_dev, jax.Array),
            "the booster's bin matrix is not a device array")
    require(facts["recompiles_after_first_iter"] == 0,
            f"{facts['recompiles_after_first_iter']} recompiles after "
            "the first iteration")
    g._sync_model()   # materialize the trees still in flight on the device
    leaves = [int(t.num_leaves) for t in g.models_]
    require(min(leaves) > 1, f"a tree did not split: leaves {leaves}")
    return leaves


def _on_host(booster, **predict_args):
    """`Booster.predict` through the host predictor of the same booster."""
    g = booster._gbdt
    prev = g.config.device_predict
    g.config.device_predict = "false"
    try:
        return booster.predict(**predict_args)
    finally:
        g.config.device_predict = prev


def _host_raw_scores(booster, X):
    return _on_host(booster, data=X, raw_score=True)


def _require_first_tree_sums_its_rows(booster, X, y, learning_rate):
    """Tree 0 against the rows it was grown on.  Every row has the same
    score when it is grown (boost_from_average), so which rows a leaf
    holds fixes its count, its hessian sum and its output; the kernels
    round each row's gradient and hessian to bf16, which moves a sum by
    0.4% at most.  (On the chip's first run the leaf at the end of each
    parent-minus-sibling chain held a sum near zero and an output in the
    thousands, and held-out AUC did not show it.)"""
    tree = booster._gbdt.models_[0]
    nl = int(tree.num_leaves)
    leaf = _on_host(booster, data=X, pred_leaf=True, num_iteration=1)
    leaf = np.asarray(leaf).reshape(len(X), -1)[:, 0]
    pavg = float(np.mean(y > 0))
    init = float(np.log(pavg / (1.0 - pavg)))
    lab = np.where(y > 0, 1.0, -1.0)
    resp = -lab / (1.0 + np.exp(lab * init))
    count = np.bincount(leaf, minlength=nl)
    sum_g = np.bincount(leaf, weights=resp, minlength=nl)
    sum_h = np.bincount(leaf, weights=np.abs(resp) * (1.0 - np.abs(resp)),
                        minlength=nl)
    require(np.array_equal(count, tree.leaf_count[:nl]),
            "tree 0: leaf counts differ from the rows the leaves hold")
    weight_err = float(np.max(np.abs(tree.leaf_weight[:nl] - sum_h) / sum_h))
    value_err = float(np.max(np.abs(
        tree.leaf_value[:nl] - (init - learning_rate * sum_g / sum_h))))
    require(weight_err <= 0.01,
            f"tree 0: a leaf's hessian sum is off by {weight_err:.3g} "
            "(relative) from the rows it holds")
    require(value_err <= 0.01,
            f"tree 0: a leaf's output is off by {value_err:.3g} from "
            "the rows it holds")
    return {"first_tree_leaves_checked": nl,
            "first_tree_weight_rel_err": round(weight_err, 5),
            "first_tree_value_abs_err": round(value_err, 5)}


def phase_train(seed, rows=ROWS, test_rows=TEST_ROWS, rounds=TRAIN_ROUNDS,
                params=PARAMS, min_auc=MIN_AUC):
    t0 = time.perf_counter()
    import jax
    from tools.higgs_like import auc, make_higgs_like
    X, y = make_higgs_like(rows, FEATURES, seed=seed)
    X_test, y_test = make_higgs_like(test_rows, FEATURES, seed=seed + 1)
    datagen_s = time.perf_counter() - t0

    booster, device_binned, facts = _train_timed(dict(params), X, y, rounds)
    leaves = _require_default_tpu_engine(booster, device_binned, facts)
    facts.update(_require_first_tree_sums_its_rows(
        booster, X, y, params["learning_rate"]))
    g = booster._gbdt
    held_out_auc = auc(y_test, _host_raw_scores(booster, X_test))
    require(held_out_auc >= min_auc,
            f"held-out AUC {held_out_auc:.5f} < {min_auc}")
    stats = jax.devices()[0].memory_stats() or {}
    require_on_chip("peak_bytes_in_use" in stats,
                    f"memory_stats() has no peak_bytes_in_use: {stats}")
    emit("train", t0, rows=rows, features=FEATURES, rounds=rounds,
         datagen_s=round(datagen_s, 3), **facts,
         growth_strategy=g.growth_strategy,
         hist_method=g.grow_params.hist_method,
         device_binned=device_binned,
         binned_dev=f"{g.binned_dev.dtype}{list(g.binned_dev.shape)}",
         leaves_min=min(leaves), leaves_max=max(leaves),
         auc=round(held_out_auc, 5),
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"),
         compile_cache=_cache_counts())
    return booster, X_test


# ----------------------------------------------------------------- predict
def phase_predict(booster, X_test, workdir):
    t0 = time.perf_counter()
    import lightgbm_tpu as lgb
    g = booster._gbdt
    require_on_chip(g.config.device_predict == "auto",
                    f"device_predict is {g.config.device_predict!r}, not "
                    "the default 'auto'")
    device_prob = booster.predict(X_test)
    device_raw = booster.predict(X_test, raw_score=True)
    dp = getattr(g, "_device_pred", None)
    require_on_chip(dp is not None and dp[1].total_traces() >= 2,
                    "Booster.predict did not take the device path under "
                    "device_predict=auto")
    require(device_prob.shape == (len(X_test),)
            and bool(np.isfinite(device_prob).all()),
            "device predictions are not finite [n]")
    host_raw = _host_raw_scores(booster, X_test)
    max_abs = float(np.max(np.abs(device_raw - host_raw)))
    require(max_abs <= 1e-6,
            f"device and host raw scores differ by {max_abs:.3e} > 1e-6")

    model_path = os.path.join(workdir, "chip_smoke_model.txt")
    booster.save_model(model_path)
    reloaded = lgb.Booster(model_file=model_path)
    require(reloaded.num_trees() == booster.num_trees(),
            "the reloaded model lost trees")
    require(np.array_equal(reloaded.predict(X_test), device_prob),
            "save_model -> Booster(model_file) changed the predictions")
    emit("predict", t0, rows=len(X_test), device_path=dp is not None,
         device_vs_host_max_abs=max_abs, reload_identical=True,
         model_bytes=os.path.getsize(model_path),
         compile_cache=_cache_counts())
    return model_path, device_prob


# ------------------------------------------------------------------- serve
def _line_json_predict(port, model, rows):
    with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
        f = s.makefile("rwb")
        f.write((json.dumps({"model": model, "rows": rows.tolist()})
                 + "\n").encode())
        f.flush()
        reply = json.loads(f.readline())
    require(reply.get("ok") is True, f"line-JSON request failed: {reply}")
    return np.asarray(reply["preds"])


def phase_serve(model_path, X_test, expected, seed,
                serve_params=SERVE_PARAMS):
    """`expected` is `Booster.predict(X_test)`; every served response
    must equal its rows."""
    t0 = time.perf_counter()
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.serving import (ServingClient, ServingDaemon,
                                      start_frontend)
    threads_before = set(threading.enumerate())
    daemon = ServingDaemon(Config(dict(serve_params))).start()
    frontend = None
    try:
        handle = daemon.registry.register("higgs", model_file=model_path,
                                          block=True)
        warmup_s = time.perf_counter() - t0
        warmup_traces = handle.entry.warmup_traces
        frontend = start_frontend(daemon, port=0)
        port = frontend.server_address[1]

        rng = np.random.RandomState(seed + 2)

        def draw(n):
            start = int(rng.randint(0, len(X_test) - n))
            return start, X_test[start:start + n]

        client = ServingClient(daemon)
        senders = {
            "in-process": lambda rows: np.asarray(
                client.predict("higgs", rows, timeout=120)),
            "line-JSON": lambda rows: _line_json_predict(port, "higgs",
                                                         rows)}
        plan = ([(n, "in-process") for n in REQUEST_ROWS * 8]
                + [(n, "line-JSON") for n in REQUEST_ROWS * 2])
        for n, via in plan:
            start, rows = draw(n)
            require(np.array_equal(senders[via](rows),
                                   expected[start:start + n]),
                    f"{via} response for {n} rows at {start} differs "
                    "from Booster.predict")
        recompiles = daemon.registry.serve_recompiles()
        require(recompiles == 0,
                f"{recompiles} serving-path recompiles after warm-up")
        stats = daemon.stats()
    finally:
        if frontend is not None:
            frontend.shutdown()
            frontend.server_close()
        drained = daemon.stop(drain=True, timeout=30)
    require(drained, "the daemon did not drain on stop")
    # a thread that outlives the stop would keep this process from exiting
    deadline = time.monotonic() + 10
    while True:
        left = [t.name for t in threading.enumerate()
                if t not in threads_before and t.is_alive()
                and not t.daemon]
        if not left or time.monotonic() > deadline:
            break
        time.sleep(0.1)
    require(not left, f"threads still alive after stop: {left}")
    emit("serve", t0, warmup_s=round(warmup_s, 3),
         warmup_traces=warmup_traces, requests=len(plan),
         in_process=sum(via == "in-process" for _, via in plan),
         line_json=sum(via == "line-JSON" for _, via in plan),
         request_rows=list(REQUEST_ROWS), all_equal_booster_predict=True,
         serve_recompiles=recompiles,
         serve_batches=int(stats["serve_batches"]),
         serve_p50_ms=stats["serve_p50_ms"],
         serve_p99_ms=stats["serve_p99_ms"], clean_stop=True,
         compile_cache=_cache_counts())


# ----------------------------------------------------------- data parallel
def _shard_facts(name, arr, devices, rows_per_shard):
    """`arr`'s row axis (its last) must be split over all `devices`,
    `rows_per_shard` rows on each."""
    shards = arr.addressable_shards
    on = {s.device for s in shards}
    rows = sorted({int(s.data.shape[-1]) for s in shards})
    require(len(shards) == len(devices) and on == set(devices),
            f"{name}: {len(shards)} shards on {len(on)} devices, wanted "
            f"one on each of {len(devices)}")
    require(rows == [rows_per_shard],
            f"{name}: shard rows {rows}, wanted {rows_per_shard} on each")
    return {"shards": len(shards), "rows_per_shard": rows_per_shard}


def _structure(booster):
    """(split feature, threshold bin) of every node of every tree."""
    out = []
    for t in booster._gbdt.models_:
        ni = int(t.num_leaves) - 1
        out.append((t.split_feature_inner[:ni].tolist(),
                    t.threshold_in_bin[:ni].tolist()))
    return out


def phase_data_parallel(seed, devices, rows=ROWS, test_rows=TEST_ROWS,
                        rounds=PARALLEL_ROUNDS, params=PARAMS):
    """tree_learner=data over every chip of the host, one process, and
    the same rounds with tree_learner=serial on the first chip."""
    t0 = time.perf_counter()
    from tools.higgs_like import auc, make_higgs_like
    X, y = make_higgs_like(rows, FEATURES, seed=seed)
    X_test, y_test = make_higgs_like(test_rows, FEATURES, seed=seed + 1)

    par, par_binned, par_facts = _train_timed(
        {**params, "tree_learner": "data"}, X, y, rounds)
    _require_default_tpu_engine(par, par_binned, par_facts)
    par_facts.update(_require_first_tree_sums_its_rows(
        par, X, y, params["learning_rate"]))
    g = par._gbdt
    require(g.mesh is not None, "tree_learner=data built no mesh")
    mesh_devices = list(g.mesh.devices.flat)
    require(len(set(mesh_devices)) == len(devices)
            and set(mesh_devices) == set(devices),
            f"the mesh holds {mesh_devices}, wanted {list(devices)}")
    require_on_chip(all(d.platform == "tpu" for d in mesh_devices),
                    f"the mesh is not on TPU devices: {mesh_devices}")
    per = g.n_pad // len(devices)
    grad, hess = g._compute_gradients()
    sharding = {
        "binned": _shard_facts("bin matrix", g.binned_dev, devices, per),
        "scores": _shard_facts("scores", g.scores, devices, per),
        "labels": _shard_facts("labels", g.label_dev, devices, per),
        "gradients": _shard_facts("gradients", grad, devices, per),
        "hessians": _shard_facts("hessians", hess, devices, per),
    }
    # the very program lgb.train ran: same builder, same arguments
    hlo = g._grow_fn.build(g.grow_params, ()).lower(
        g.binned_dev, g._slice_row_fn(grad, 0), g._slice_row_fn(hess, 0),
        g.bag_mask, g._col_mask(), g.meta).compile().as_text()
    custom_calls = hlo.count("tpu_custom_call")
    all_reduces = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
    require_on_chip(custom_calls > 0,
                    "no tpu_custom_call in the compiled grow program")
    require(all_reduces > 0, "no all-reduce in the compiled grow program")

    ser, ser_binned, ser_facts = _train_timed(
        {**params, "tree_learner": "serial"}, X, y, rounds)
    _require_default_tpu_engine(ser, ser_binned, ser_facts)
    ser_facts.update(_require_first_tree_sums_its_rows(
        ser, X, y, params["learning_rate"]))
    require(ser._gbdt.mesh is None, "tree_learner=serial built a mesh")
    on = {d for d in ser._gbdt.binned_dev.devices()}
    require(on == {devices[0]}, f"the serial run is on {on}")

    ps, ss = _structure(par), _structure(ser)
    same = [a == b for a, b in zip(ps, ss)]
    par_auc = auc(y_test, _host_raw_scores(par, X_test))
    ser_auc = auc(y_test, _host_raw_scores(ser, X_test))
    require(same[0], "the first tree differs between tree_learner=data "
                     "and tree_learner=serial")
    if all(same):
        comparison = "every tree identical"
    else:
        # the shards' histograms are summed in another order than one
        # chip sums its rows, so a late near-tie may go the other way
        comparison = "first tree identical, held-out AUC within 1e-3"
        require(abs(par_auc - ser_auc) <= 1e-3,
                f"trees differ from #{same.index(False)} on and held-out "
                f"AUC differs: data {par_auc:.5f}, serial {ser_auc:.5f}")
    emit("data_parallel", t0, rows=rows, rounds=rounds,
         mesh_devices=[str(d) for d in mesh_devices], sharding=sharding,
         tpu_custom_calls=custom_calls, all_reduces=all_reduces,
         data={**par_facts, "auc": round(par_auc, 5)},
         serial={**ser_facts, "auc": round(ser_auc, 5)},
         trees_identical=f"{sum(same)}/{len(same)}", comparison=comparison,
         compile_cache=_cache_counts())


# -------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated data (held-out rows use "
                         "seed+1)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the data-parallel path and its serial "
                         "comparison on a four-chip host, no other phase")
    args = ap.parse_args(argv)
    # the checkout must be importable before anything is printed
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import lightgbm_tpu  # noqa: F401
    import tools.higgs_like  # noqa: F401
    import tools.kernel_checks  # noqa: F401

    devices = phase_device(args.chips)
    if args.chips == 4:
        phase_data_parallel(args.seed, devices)
    else:
        phase_kernels()
        booster, X_test = phase_train(args.seed)
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
            model_path, expected = phase_predict(booster, X_test, workdir)
            phase_serve(model_path, X_test, expected, args.seed)
    print(json.dumps({"ok": True,
                      "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
