#!/usr/bin/env python3
"""What a part of a scope is made of, op by op, from a traced run.

`benchmarks/scope_trace.py` gives each scope's time; this prints the ops
whose `op_name` holds one part (`Cat.scan`, `Efb.decode`, `Rank.sort`,
...) with their own device time a traced iteration, grouped by the op's
kind (the HLO instruction's name less its number: `fusion`, `sort`,
`gather`, `while`, ...) and the tail of its `op_name` after the part,
largest first.

    python3 tools/scope_ops.py Cat.scan [file.xplane.pb | dir | trace.json]
                               [--json out]

`--json` also writes every op of the part as `[name, op_name, own ns]`
with the window and the iteration count, for reading again later.
"""

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import scope_trace  # noqa: E402
from benchmarks import trace as bench_trace  # noqa: E402


def op_kind(name):
    """`%fusion.1886 = s32[...] fusion(...)` -> `fusion`."""
    m = re.match(r"%?([A-Za-z_\-]+?)(?:[.\d]*)(?:\s|=|$)", name)
    return m.group(1) if m else name[:24]


def part_ops(st, part):
    """([(name, op_name, own ns)] of the ops under `part`, window,
    iterations) of a scope trace, over its traced window."""
    marks = [(s, s + d) for name, s, d, _ in st.host_spans
             if name.startswith(bench_trace.WINDOW_SPAN)]
    if not marks:
        marks = [(s, s + d) for ops in st.devices.values()
                 for _, s, d, _ in ops]
    window = st.window or (min(s for s, _ in marks),
                           max(e for _, e in marks))
    iters = max(sum(name == "bench::update" and window[0] <= s < window[1]
                    for name, s, *_ in st.host_spans), 1)
    key = f"/{part}/"
    ops = [(name, op_name, own)
           for device in st.devices
           for name, op_name, own in st.own_ns(device, window)
           if key in op_name + "/"]
    return ops, window, iters


def table(ops, part, iters, devices=1):
    """{(kind, op_name tail): (ms a traced iteration, events)}."""
    out = {}
    for name, op_name, own in ops:
        tail = op_name.split(f"/{part}/", 1)[-1]
        tail = "/".join(tail.split("/")[-3:])
        k = (op_kind(name), tail)
        ms, n = out.get(k, (0.0, 0))
        out[k] = (ms + own / 1e6 / iters / devices, n + 1)
    return out


def main(argv):
    if not argv:
        sys.exit(__doc__)
    part, rest = argv[0], argv[1:]
    out_json = None
    if "--json" in rest:
        i = rest.index("--json")
        out_json = rest[i + 1]
        rest = rest[:i] + rest[i + 2:]
    target = rest[0] if rest else None
    if target and target.endswith(".json"):
        st = scope_trace.from_json(target)
    else:
        path = (target if target and target.endswith(".pb")
                else scope_trace.newest_xplane(target))
        if path is None:
            sys.exit("scope_ops: no .xplane.pb found")
        st = scope_trace.from_xplane(path)
    ops, window, iters = part_ops(st, part)
    n_dev = max(len(st.devices), 1)
    rows = table(ops, part, iters, n_dev)
    total = sum(ms for ms, _ in rows.values())
    print(json.dumps({"part": part, "iterations": iters,
                      "window_s": (window[1] - window[0]) / 1e9,
                      "ms_per_iter": total, "ops": len(ops)}))
    for (kind, tail), (ms, n) in sorted(rows.items(),
                                        key=lambda kv: -kv[1][0]):
        print(f"{ms:10.3f} ms/iter {n:7d} events  {kind:12s} {tail}")
    if out_json:
        with open(out_json, "w") as f:
            json.dump({"part": part, "iterations": iters,
                       "window_ns": list(window), "devices": n_dev,
                       "ops": ops}, f)


if __name__ == "__main__":
    main(sys.argv[1:])
