"""Named timing scopes aggregated in a global timer.

TPU-native analogue of the reference's TIMETAG instrumentation
(ref: include/LightGBM/utils/common.h:973-1010 Timer/FunctionTimer,
instantiated as `global_timer` in src/boosting/gbdt.cpp:22 and printed at
process exit).

Recording and syncing are two switches (docs/Observability.md):

* recording is always on: `scope(name)` adds `(seconds, calls)` to the
  name's total — two clock reads and a dict add, like
  `MetricsRegistry.inc`.  Because jax dispatch is asynchronous, a scope
  around device work measures the HOST's part (trace, dispatch, the wait
  of whoever fetches a result) unless the sync switch is on;
* the sync switch (`sync`; the LIGHTGBM_TPU_TIMETAG env var, and
  `train(metrics_dir=...)` for the run) makes `block(x)` call
  `block_until_ready`, so the enclosing scope is charged for the device
  work it dispatched, and credits the settle wait to a separate
  `<scope>::device` entry.  That de-pipelines the loop it times: it is
  for phase breakdowns, never for a throughput number.  Off, `block()`
  is the identity and no sync is added anywhere.

`set_trace_annotations(True)` additionally emits a
`jax.profiler.TraceAnnotation` per scope (with the scope's keyword
attributes, e.g. `iter=3`), so a profiler's host timeline carries the
same names on the device's clock.

`device_scope(name)` is for code INSIDE jitted programs (histogram build,
split find, partition, collectives): it wraps the traced ops in
`jax.named_scope`, so the name (with `::` as `.`) goes into each op's
`op_name` metadata, which a profiler trace keeps per op
(benchmarks/scope_trace.py reads it).  It records nothing on the host:
the body runs once per compile, at trace time.

The scope stack is thread-local (the serving coalescer times dispatches
concurrently with the main thread).
"""

from __future__ import annotations

import atexit
import os
import threading
import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict, Tuple


class Timer:
    """Aggregates wall-clock per named scope (ref: common.h:973 Timer)."""

    def __init__(self, sync: bool = False, use_jax_profiler: bool = False):
        self.sync = sync
        self._acc: Dict[str, float] = defaultdict(float)
        self._cnt: Dict[str, int] = defaultdict(int)
        self._alock = threading.Lock()
        self._tls = threading.local()
        self._use_jax_profiler = use_jax_profiler

    def _scope_stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _add(self, name: str, dt: float) -> None:
        with self._alock:
            self._acc[name] += dt
            self._cnt[name] += 1

    # ------------------------------------------------------- profiler wiring
    def set_trace_annotations(self, on: bool) -> None:
        """Toggle jax.profiler.TraceAnnotation emission from scopes."""
        self._use_jax_profiler = bool(on)

    def trace_annotations_enabled(self) -> bool:
        return self._use_jax_profiler

    # ---------------------------------------------------------------- scopes
    @contextmanager
    def scope(self, name: str, **attrs):
        """RAII scope (ref: common.h:1000 FunctionTimer).  `attrs` go to
        the TraceAnnotation (an iteration or tree index), not the total."""
        ctx = None
        if self._use_jax_profiler:
            import jax.profiler
            ctx = jax.profiler.TraceAnnotation(name, **attrs)
            ctx.__enter__()
        stack = self._scope_stack()
        stack.append(name)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            stack.pop()
            if ctx is not None:
                ctx.__exit__(None, None, None)
            self._add(name, dt)

    @contextmanager
    def device_scope(self, name: str):
        """Scope for code traced INSIDE a jitted program: tags the traced
        ops with jax.named_scope so the name reaches each op's `op_name`
        metadata (and through it a profiler's device timeline)."""
        import jax
        with jax.named_scope(name.replace("::", ".")):
            yield

    def block(self, x):
        """block_until_ready(x) when the sync switch is on, so the
        enclosing scope is charged for the device work it dispatched
        (async dispatch otherwise bills whichever later phase syncs
        first).  Identity when it is off — dispatch stays pipelined.

        The settle wait is ALSO credited to `<enclosing scope>::device`:
        the enclosing scope's total is unchanged (dispatch + settle), and
        the ::device entry says how much of it the chip owned."""
        if not self.sync or x is None:
            return x
        t0 = time.perf_counter()
        try:
            import jax
            x = jax.block_until_ready(x)
        except Exception:
            return x
        stack = self._scope_stack()
        if stack:
            self._add(stack[-1] + "::device", time.perf_counter() - t0)
        return x

    # --------------------------------------------------------------- results
    def items(self) -> Tuple[Tuple[str, float, int], ...]:
        with self._alock:
            acc = dict(self._acc)
            cnt = dict(self._cnt)
        return tuple((k, acc[k], cnt[k])
                     for k in sorted(acc, key=acc.get, reverse=True))

    def snapshot(self) -> Dict[str, Tuple[float, int]]:
        """Point-in-time copy {name: (seconds, calls)} — per-iteration
        phase breakdowns diff two snapshots (observability/events)."""
        with self._alock:
            return {k: (self._acc[k], self._cnt[k]) for k in self._acc}

    def reset(self) -> None:
        with self._alock:
            self._acc.clear()
            self._cnt.clear()

    def print(self) -> None:
        """ref: Timer::Print at process exit."""
        if not self._acc:
            return
        from . import log
        log.info("LightGBM-TPU timers:")
        for name, sec, cnt in self.items():
            log.info(f"  {name}: {sec * 1000:.3f} ms ({cnt} calls)")


global_timer = Timer(sync=bool(os.environ.get("LIGHTGBM_TPU_TIMETAG", "")))
if global_timer.sync:
    atexit.register(global_timer.print)
