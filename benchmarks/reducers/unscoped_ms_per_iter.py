"""Device busy time per traced iteration less the ops matching `patterns`
(kernels, all-reduces: what `busy_less_ops_ms_per_iter` takes off) and
less the ops under the scopes of `scopes`, in ms (mean over the devices):
what the device did that no per-scope metric names.  With the per-scope
metrics of `scopes` it adds up to `busy_less_ops_ms_per_iter` of the same
patterns.  No trace file of this run returns nothing."""

from benchmarks.reducers.scope_ms_per_iter import scope_s


def reduce(ctx, patterns, scopes):
    if ctx.trace is None:
        return None
    table = scope_s(ctx, skip=patterns)
    if table is None:
        return None
    named, _ = ctx.trace.matching_s(patterns)
    rest = (ctx.trace.mean_busy_s() - named
            - sum(table.get(s, 0.0) for s in scopes))
    return 1000.0 * rest / ctx.counters["iterations"]
