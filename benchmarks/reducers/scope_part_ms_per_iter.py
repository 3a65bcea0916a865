"""Device time of one PART of a scope, per traced iteration, in ms (mean
over the devices): the own time (`ScopeTrace.own_ns`) of the ops whose
innermost scope label is `scope` and whose `op_name` holds the label
`part` as a path element (`.../GBDT.gradients/Rank.sort/...`).

A part is a `device_scope` whose label `scope_layout.json`'s pattern
does not match (`Rank::sort` -> `Rank.sort`), so the op stays under
`scope` for every per-scope reader and the parts split that scope's time
without moving it: the parts of a scope add up to its
`scope_ms_per_iter` less the ops that carry no part.  Ops whose trace
name matches `skip` are left out, as there.  Nothing to read — no trace
file of this run, no op of the scope with the part (a program from
before the part was named) — returns nothing."""

import re

from benchmarks import scope_trace


def reduce(ctx, scope, part, skip=()):
    st = scope_trace.for_trace(ctx.trace)
    if st is None:
        return None
    holds = re.compile(r"(?:^|/)" + re.escape(part) + r"(?=/|:|$)")
    rx = [re.compile(p) for p in skip]
    total = found = 0
    for device in st.devices:
        for name, op_name, own in st.own_ns(device, ctx.trace.window):
            if (scope_trace.scope_of(op_name) == scope
                    and holds.search(op_name)
                    and not any(r.search(name) for r in rx)):
                total += own
                found += 1
    if not found:
        return None
    return (1000.0 * total / len(st.devices) / 1e9
            / ctx.counters["iterations"])
