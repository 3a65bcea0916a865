"""Device time of the ops under one of the program's scopes
(`lightgbm_tpu.utils.timer.device_scope`; `Tree.partition` is the label of
`Tree::partition`), per traced iteration, in ms (mean over the devices).
The scope of an op is the innermost label in its `op_name`
(`benchmarks/scope_trace.py`); each op counts its own time, a `cond` or
`while` less the ops of its body.  Ops whose trace name matches `skip` are
those other readers count by name (kernels, all-reduces).  Nothing to
read — no trace file of this run, no op under the scope — returns
nothing."""

from benchmarks import scope_trace


def scope_s(ctx, skip=()):
    """{scope: seconds in the window}, or None without this run's file."""
    st = scope_trace.for_trace(ctx.trace)
    if st is None:
        return None
    return st.by_scope_s(ctx.trace.window, skip=skip)


def reduce(ctx, scope, skip=()):
    table = scope_s(ctx, skip)
    if not table or scope not in table:
        return None
    return 1000.0 * table[scope] / ctx.counters["iterations"]
