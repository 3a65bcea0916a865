"""Persistent XLA compilation cache wiring (docs/Performance.md).

Every fresh process pays the full trace+compile cost of the jitted tree
program before its first iteration (tens of seconds for the 255-leaf
wave ladder at bench scale), and a serving daemon compiles one program
per warm-up bucket.  JAX's persistent compilation cache lets a repeat
run with the same configuration deserialize the compiled executables
instead of recompiling, so the package turns it on at first use —
training init, device-predictor build, serving-daemon start, the CLI.

Where the cache lives is decided from outside, in this order:

1. `JAX_COMPILATION_CACHE_DIR` set: JAX's own handling places the cache
   there and this module sets no directory in code (a `compile_cache_dir`
   parameter that disagrees loses, with one log line);
2. else the `compile_cache_dir` parameter, when given;
3. else `<checkout>/.jax_cache` — fixed, derived from the package's
   location, never a temp name, pid or timestamp: the path is part of
   the cache key, so a directory that moves never hits.

The directory is a process-level setting: the first placement wins for
the life of the process.  `compile_cache_dir=off` (the auto_degrade
ladder's rung, reliability/guard.py) or JAX's own
`JAX_ENABLE_COMPILATION_CACHE=false` turns the cache off.

Hit/miss visibility: JAX reports cache activity through
`jax.monitoring`; a process-wide listener forwards the events into the
metrics registry as `compile_cache_hits` / `compile_cache_misses`, so
they appear in the per-iteration JSONL events and a second run of the
same config can assert hits > 0 (tests/test_async_io.py).

Only programs whose compile takes >= 1 s are persisted (the ladder
compile is the multi-second cost being amortized); the micro-jits
around it recompile cheaply each process.

Compile phases: the same bus carries how long JAX spent tracing to a
jaxpr, lowering to MLIR, in the backend's compiler and reading the cache.
A second listener adds them into the registry as `jit_trace_s`,
`jit_lower_s`, `backend_compile_s` and `cache_load_s` (process totals, in
seconds), each event's OWN time: on this jaxlib a cache hit is reported
inside `backend_compile_duration`, and a jitted function traced inside
another's trace reports inside it, so what an event holds of later-named
events is taken off it and the four counters are disjoint — they add up
to the wall-clock the thread spent compiling.  A booster's first
iteration, where the train programs are traced, lowered and compiled or
loaded, keeps its share apart as `first_iter_<counter>`
(`first_iter_compile_phases`): the process totals also hold the binning program,
later iterations' small jits and whatever predicts afterwards.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Optional

from ..utils import log
from .registry import global_registry

_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}

_DURATION_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}

CACHE_OFF = "off"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# the directory in force once configured ("" = configured off)
_configured_dir: Optional[str] = None


def _on_monitoring_event(event: str, **_kwargs) -> None:
    name = _EVENT_COUNTERS.get(event)
    if name is not None:
        global_registry.inc(name)


# per thread: the (start, seconds) of the compile-phase events seen so
# far that no later event has contained.  JAX reports an event when it
# ENDS, so the events an event contains are the stack's tail.
_phase_tls = threading.local()
_listeners_installed = False


def _on_duration_event(event: str, duration: float, **_kwargs) -> None:
    name = _DURATION_COUNTERS.get(event)
    if name is None:
        return
    start = time.perf_counter() - duration
    stack = getattr(_phase_tls, "stack", None)
    if stack is None:
        stack = _phase_tls.stack = []
    own = duration
    # events are nested or one after the other: what began after this one
    # began is inside it (the slack is the listeners' own latency)
    while stack and stack[-1][0] >= start - 1e-4:
        own -= stack.pop()[1]
    stack.append((start, duration))
    if len(stack) > 8192:    # top-level events nothing will contain
        del stack[:4096]
    global_registry.inc(name, max(own, 0.0))


@contextmanager
def first_iter_compile_phases():
    """Adds what the four compile-phase counters gain inside the block
    (a booster's first iteration) to `first_iter_<counter>` as well."""
    names = tuple(_DURATION_COUNTERS.values())
    before = [global_registry.counter(n) for n in names]
    try:
        yield
    finally:
        for name, was in zip(names, before):
            global_registry.inc("first_iter_" + name,
                                global_registry.counter(name) - was)


def _install_listeners() -> None:
    global _listeners_installed
    if _listeners_installed:
        return
    import jax
    jax.monitoring.register_event_listener(_on_monitoring_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration_event)
    _listeners_installed = True


def configure_compile_cache(cache_dir: str = "") -> Optional[str]:
    """Turn on JAX's persistent compilation cache (placement rules in
    the module docstring) and install the hit/miss and compile-phase
    listeners (the latter also when the cache is off).
    Returns the directory in force, None when the cache is off.
    Idempotent — a later call returns the placement already made,
    except that `off` always wins (the auto_degrade rung may arrive
    after the CLI has placed the cache); a directory that cannot be
    created raises."""
    global _configured_dir
    import jax
    _install_listeners()
    cache_dir = os.fspath(cache_dir or "").strip()
    turn_off = cache_dir.lower() == CACHE_OFF
    if _configured_dir is not None and not (turn_off and _configured_dir):
        if cache_dir and cache_dir != (_configured_dir or CACHE_OFF):
            log.debug(f"compile cache already placed at "
                      f"{_configured_dir or CACHE_OFF!r}; ignoring "
                      f"{cache_dir!r}")
        return _configured_dir or None
    if turn_off or not jax.config.jax_enable_compilation_cache:
        jax.config.update("jax_enable_compilation_cache", False)
        if _configured_dir:
            # already in use: JAX memoizes "is the cache used" at the
            # first compile, so drop that state too
            from jax.experimental.compilation_cache import compilation_cache
            compilation_cache.reset_cache()
        _configured_dir = ""
        log.debug("Persistent compilation cache is off")
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env_dir:
        if cache_dir and os.path.abspath(cache_dir) != os.path.abspath(
                env_dir):
            log.info(f"JAX_COMPILATION_CACHE_DIR={env_dir} places the "
                     f"compile cache; compile_cache_dir={cache_dir} is "
                     "ignored")
        target = env_dir
    else:
        target = cache_dir or DEFAULT_CACHE_DIR
        os.makedirs(target, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", target)
    # Keep a >=1 s compile-time gate: the target is the multi-second
    # ladder compile, and persisting the dozens of micro-jits around it
    # buys nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    _configured_dir = target
    log.debug(f"Persistent compilation cache at {target}")
    return target
