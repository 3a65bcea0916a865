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
"""

from __future__ import annotations

import os
from typing import Optional

from ..utils import log
from .registry import global_registry

_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
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


def configure_compile_cache(cache_dir: str = "") -> Optional[str]:
    """Turn on JAX's persistent compilation cache (placement rules in
    the module docstring) and install the hit/miss counter listener.
    Returns the directory in force, None when the cache is off.
    Idempotent — a later call returns the placement already made,
    except that `off` always wins (the auto_degrade rung may arrive
    after the CLI has placed the cache); a directory that cannot be
    created raises."""
    global _configured_dir
    import jax
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
    jax.monitoring.register_event_listener(_on_monitoring_event)
    _configured_dir = target
    log.debug(f"Persistent compilation cache at {target}")
    return target
