"""Least time the chip could take for the calls matching `patterns`,
over the time they took, in %.  The bound is bytes: every call must move
`kernel_costs/<bytes_fn>.py cost(rows_local, features)` bytes at the peak
bytes/s of `peaks.json` for this device kind."""

import importlib


def reduce(ctx, patterns, bytes_fn):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace.matching_s(patterns)
    if not calls:
        return None
    per_call = importlib.import_module(
        "benchmarks.kernel_costs." + bytes_fn).cost(
            ctx.counters["rows_local"], ctx.counters["features"])
    least_s = calls * per_call / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / secs
