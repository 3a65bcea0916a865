"""Least time the chip could take for what one PART of a scope must do
each traced iteration, over the time the ops of that part took, in %.
The bound is bytes: an iteration must move
`kernel_costs/<bytes_fn>.py cost(num_leaves, program_counters)` bytes —
counted from the driver's `num_leaves` and the program's own registry
counters, since a part's work need not follow from rows and columns — at
the peak bytes/s of `peaks.json` for this device kind.
`scope_hbm_roofline_pct`'s twin for a part (`scope_part_ms_per_iter`).
No trace file of this run, no op of the scope with the part, or nothing
counted returns nothing."""

import importlib

from benchmarks.reducers import program_total, scope_part_ms_per_iter


def reduce(ctx, scope, part, bytes_fn, skip=()):
    ms = scope_part_ms_per_iter.reduce(ctx, scope, part, skip)
    if not ms:
        return None
    per_iter = importlib.import_module(
        "benchmarks.kernel_costs." + bytes_fn).cost(
            ctx.counters.get("num_leaves"), program_total.totals("counter"))
    if not per_iter:
        return None
    least_ms = 1000.0 * per_iter / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_ms / ms
