#!/usr/bin/env python3
"""One cell of the benchmark, once, as a new process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data that this file finds by name: the cell
and its configuration in `BENCHMARK.json`, the configuration's sizes in
`configs/`, the traffic mix in `traffic/` (which names its driver in
`drivers/`), each per-layer metric's reader in `layer_metrics/` (which
names its reducer in `reducers/`).  This file knows no cell's name.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` and, traced, `breakdown`.
With `--trace 0` the metrics are the cell's end-to-end metrics, taken
with the profiler off; with `--trace 1` they are its per-layer metrics.
Earlier lines are facts for a reader (engine selected, cache hits,
checks), one JSON object each.

There is no CPU mode: without a TPU, or with fewer chips than the cell
asks for, this exits non-zero before any data is made and prints no
result.
"""

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
from types import SimpleNamespace   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")   # traces; fixed, in .gitignore


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def log(**facts):
    print(json.dumps(facts), flush=True)


def find_cell(manifest, workload):
    """(cell, configuration entry) by the cell's name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        sys.exit(f"run.py: no workload {workload!r} in BENCHMARK.json "
                 f"(has: {', '.join(sorted(cells))})")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    return cell, entry


def require_chips(chips):
    """The TPU devices of this machine, or no run at all."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"run.py: no TPU found (JAX's default backend is "
                 f"{devices[0].platform!r}); the benchmark runs on the "
                 "chip only")
    if len(devices) < chips:
        sys.exit(f"run.py: the cell asks for {chips} chips, JAX found "
                 f"{len(devices)}")
    return devices


def _applies(metric, cell):
    return "workloads" not in metric or cell["name"] in metric["workloads"]


def _number(value):
    return (isinstance(value, (int, float)) and not isinstance(value, bool)
            and math.isfinite(value))


def layer_metrics(entries, cell, rctx):
    """Each per-layer metric of `entries` that this cell has, through
    its own reader; a reader that finds nothing to read leaves its
    metric out."""
    out = {}
    for m in entries:
        if not _applies(m, cell):
            continue
        spec = _load(HERE, "layer_metrics", m["name"] + ".json")
        reducer = importlib.import_module(
            "benchmarks.reducers." + spec["reducer"])
        value = reducer.reduce(rctx, **spec.get("args", {}))
        if _number(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def peak_bytes(stats):
    """What the fullest chip held at its fullest.  The allocator counts
    two disjoint pools: buffers (`peak_bytes_in_use`: bins, scores,
    gradients, outputs) and what loaded programs reserve for their
    temporaries (`peak_bytes_reserved`: the padded kernel operands, 8x
    the buffers at 2^20 rows).  Free memory falls by both."""
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)) for s in stats)


def execute(manifest, cell, config, traffic, seed, seconds, trace, devices,
            on_chip=True, log=log):
    """Run the cell's driver and build the result object.  `on_chip=False`
    and `log` are for selftest.py's CPU rehearsal; the command line
    cannot reach them."""
    from benchmarks import trace as bench_trace
    trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    driver = importlib.import_module("benchmarks.drivers."
                                     + traffic["driver"])
    out = driver.run(SimpleNamespace(
        cell=cell, config=config, traffic=traffic, seed=seed,
        seconds=seconds, trace=trace, trace_dir=trace_dir, devices=devices,
        on_chip=on_chip, t_start=T_START, log=log))

    first = devices[0]
    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(devices),
              "memory_peak_bytes": peak_bytes(stats)}
    result = {"correct": all(out["checks"].values()),
              "attempted": out["attempted"], "failed": out["failed"]}
    if trace:
        tr = bench_trace.from_xplane(trace_dir)
        peaks = _load(HERE, "peaks.json")
        if on_chip and first.device_kind not in peaks:
            sys.exit(f"run.py: no peaks for device kind "
                     f"{first.device_kind!r} in peaks.json")
        rctx = SimpleNamespace(
            trace=tr, spans=out["spans"], counters=out["counters"],
            peaks=peaks.get(first.device_kind))
        result["metrics"] = layer_metrics(manifest["per_layer"], cell, rctx)
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s
        # an op's trace name is its whole HLO text: the head identifies it
        result["breakdown"] = {
            "device_ops": [[name[:96], s] for name, s in tr.top_ops(10)],
            "idle_gaps": tr.idle_by_host_span(10)}
    else:
        result["metrics"] = {
            m["name"]: {"value": float(out["metrics"][m["name"]]),
                        "unit": m["unit"]}
            for m in manifest["end_to_end"]
            if _applies(m, cell) and _number(out["metrics"].get(m["name"]))}
    result["device"] = device
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: the "
                         "manifest's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    manifest = _load(ROOT, "BENCHMARK.json")
    cell, entry = find_cell(manifest, args.workload)
    config = _load(ROOT, entry["file"])
    traffic = _load(HERE, "traffic", cell["traffic"] + ".json")
    seconds = (manifest["run_seconds"] if args.seconds is None
               else args.seconds)

    import lightgbm_tpu  # noqa: F401  (a bare directory stops here)
    devices = require_chips(cell["chips"])
    from lightgbm_tpu.observability import configure_compile_cache
    log(phase="device", workload=cell["name"], seed=args.seed,
        seconds=seconds, trace=args.trace,
        devices=[str(d) for d in devices],
        compile_cache_dir=configure_compile_cache(),
        device_init_s=time.perf_counter() - T_START)
    result = execute(manifest, cell, config, traffic, args.seed, seconds,
                     bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
