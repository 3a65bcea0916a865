#!/usr/bin/env python3
"""CPU rehearsal of the harness: no chip, no device number.

    python3 benchmarks/selftest.py

1. the manifest against the rules a later PR most easily breaks (every
   file a name points to exists, every reducer imports);
2. every reducer on the recorded trace of `testdata/` against numbers
   counted by hand;
3. `train_loop` end to end at a tiny configuration on 4 virtual CPU
   devices — serial, and `tree_learner=data` with the mesh checks —
   timed and traced, through `run.execute(..., on_chip=False)`, which the
   command line cannot reach (`run.py` has no CPU mode).

It prints counts and check names only: a time taken here says how fast
the CPU backend is, and is never written under a device metric's name.
"""

import importlib
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

TINY_TRAFFIC = {"driver": "train_loop", "warmup_iters": 2,
                "quality_trees": 6, "test_rows": 2000, "traced_iters": 3}
TINY_PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
               "learning_rate": 0.1, "min_data_in_leaf": 20,
               "verbosity": -1}


def tiny_config(**params):
    return {"generator": "higgs_like", "data_seed": 3, "rows": 4096,
            "features": 28,
            "params": {**TINY_PARAMS, **params}, "reference":
            "binary_first_tree", "quality": {"metric": "auc", "floor": 0.5}}


def require(cond, what):
    if not cond:
        sys.exit(f"selftest: FAILED: {what}")


def check_manifest(manifest):
    kinds = {"configs": "config", "workloads": "cell",
             "end_to_end": "metric", "per_layer": "metric"}
    names = [(kind, entry["name"]) for key, kind in kinds.items()
             for entry in manifest[key]]
    for _, name in names:
        require(NAME.match(name), f"bad name {name!r}")
    require(len(names) == len(set(names)), "a name appears twice")
    configs = {c["name"]: c for c in manifest["configs"]}
    for c in configs.values():
        require(os.path.isfile(os.path.join(ROOT, c["file"])),
                f"{c['file']} is missing")
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        for key in c["reduced"]:
            require(key in doc and key in doc.get("source_values", {}),
                    f"{c['name']}: reduced key {key!r} is not in its file "
                    "with the source's value beside it")
        for kind, name in (("references", doc["reference"]),
                           ("generators", doc["generator"]),
                           ("quality", doc["quality"]["metric"])):
            importlib.import_module(f"benchmarks.{kind}.{name}")
    e2e = {m["name"] for m in manifest["end_to_end"]}
    require("setup_s" in e2e, "no setup_s")
    cells = manifest["workloads"]
    for w in cells:
        require(w["config"] in configs, f"{w['name']}: unknown config")
        with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        importlib.import_module("benchmarks.drivers." + traffic["driver"])
    four = sum(w["chips"] == 4 for w in cells)
    require(four <= max(1, len(cells) // 4), "too many four-chip cells")
    for m in manifest["per_layer"]:
        require(m["moves"] in e2e, f"{m['name']} moves an unknown metric")
        with open(os.path.join(HERE, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        importlib.import_module("benchmarks.reducers." + spec["reducer"])
    return {"configs": len(configs), "cells": len(cells),
            "end_to_end": len(e2e), "per_layer": len(manifest["per_layer"])}


def check_reducers():
    """Every reader under `layer_metrics/`, in the manifest or waiting
    for its cell, on the recorded trace, against
    `testdata/trace_small.expected.json` (counted by hand, see there)."""
    from types import SimpleNamespace
    from benchmarks import run, trace
    with open(os.path.join(HERE, "testdata",
                           "trace_small.expected.json")) as f:
        expected = json.load(f)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)[expected["device_kind"]]
    tr = trace.from_json(os.path.join(HERE, "testdata", "trace_small.json"))
    rctx = SimpleNamespace(trace=tr, spans=expected["spans"],
                           counters=expected["counters"], peaks=peaks)
    entries = [{"name": f[:-len(".json")], "unit": "-"}
               for f in sorted(os.listdir(os.path.join(HERE,
                                                       "layer_metrics")))]
    got = run.layer_metrics(entries, {}, rctx)
    require(set(got) == set(expected["metrics"]),
            f"readers {sorted(got)}, hand counts for "
            f"{sorted(expected['metrics'])}")
    checked = 0
    for name, want in expected["metrics"].items():
        require(name in got, f"reducer of {name} returned nothing")
        have = got[name]["value"]
        require(abs(have - want) <= 1e-6 * max(1.0, abs(want)),
                f"{name}: reducer gives {have!r}, by hand {want!r}")
        checked += 1
    require(abs(tr.mean_busy_s() - expected["busy_s"]) < 1e-12
            and abs(tr.window_s - expected["window_s"]) < 1e-12,
            "busy union or window differs from the hand count")
    require(tr.idle_by_host_span(10) == expected["idle_gaps"],
            f"idle gaps {tr.idle_by_host_span(10)!r}")
    require(tr.top_ops(2) == expected["top_ops"], f"top ops {tr.top_ops(2)}")
    return {"reducers_checked": checked}


def log_checks_only(**facts):
    """The driver's facts hold CPU seconds under device names: print the
    verdict of each check and the engine selected, nothing else."""
    keep = ("phase", "rows", "iterations", "trees_at_end",
            "growth_strategy", "hist_method", "checks")
    print(json.dumps({k: facts[k] for k in keep if k in facts}))


def check_train_loop(manifest):
    import jax
    from benchmarks import run
    devices = jax.devices()
    require(devices[0].platform == "cpu" and len(devices) == 4,
            f"wanted 4 virtual CPU devices, have {devices}")
    e2e = {m["name"] for m in manifest["end_to_end"]}
    runs = 0
    for name, config in (
            ("serial", tiny_config()),
            ("data-parallel", {**tiny_config(tree_learner="data"),
                               "expect": {"devices": 4}})):
        cell = {"name": "selftest." + name, "chips": len(devices)}
        for trace in (False, True):
            # a seed past 2**31, as the driver's are
            res = run.execute(manifest, cell, config, TINY_TRAFFIC,
                              seed=2 ** 31 + 11, seconds=0.3, trace=trace,
                              devices=devices, on_chip=False,
                              log=log_checks_only)
            require(res["correct"] is True, f"{name} trace={trace}: "
                    "correct is false (see the line above)")
            require(res["attempted"] > 0 and res["failed"] == 0,
                    f"{name}: attempted {res['attempted']}, "
                    f"failed {res['failed']}")
            if trace:
                require({"construct_s", "first_iter_s"}
                        <= set(res["metrics"]), f"{name}: span metrics "
                        f"missing from {sorted(res['metrics'])}")
                require(res["device"]["window_s"] > 0,
                        f"{name}: no traced window")
            else:
                require(set(res["metrics"]) == {
                    m["name"] for m in manifest["end_to_end"]
                    if "workloads" not in m} & e2e,
                    f"{name}: metrics {sorted(res['metrics'])}")
            runs += 1
    return {"train_loop_runs": runs}


def main():
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    # rehearsal programs are not the chip's: keep them out of its cache
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    sys.path.insert(0, ROOT)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    counts = {}
    counts.update(check_manifest(manifest))
    counts.update(check_reducers())
    if "--no-train" not in sys.argv[1:]:
        counts.update(check_train_loop(manifest))
    print(json.dumps({"selftest": "ok", **counts}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
