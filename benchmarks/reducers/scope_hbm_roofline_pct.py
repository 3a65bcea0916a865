"""Least time the chip could take for what a scope must do each traced
iteration, over the time the ops under the scope took, in %.  The bound
is bytes: an iteration must move `kernel_costs/<bytes_fn>.py
cost(rows_local, features)` bytes at the peak bytes/s of `peaks.json`
for this device kind.  `hbm_roofline_pct`'s twin for work that is a
scope of many ops and not a named kernel's calls.  No trace file of this
run, or no op under the scope, returns nothing."""

import importlib

from benchmarks.reducers.scope_ms_per_iter import scope_s


def reduce(ctx, scope, bytes_fn, skip=()):
    table = scope_s(ctx, skip)
    if not table or not table.get(scope):
        return None
    per_iter = importlib.import_module(
        "benchmarks.kernel_costs." + bytes_fn).cost(
            ctx.counters["rows_local"], ctx.counters["features"])
    least_s = (ctx.counters["iterations"] * per_iter
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / table[scope]
