"""signal-handler-safety: no unbounded blocking on the process's last
breath.

A SIGTERM handler (the preemption notice) and the stall watchdog's exit
path both run when the rest of the process may already be wedged — the
AsyncWriter worker stuck on a dead disk, the main thread parked inside a
collective.  Any UNBOUNDED wait on that path turns a recoverable
preemption into the worst shape: a live process that never exits and never
explains itself.  PR 7 learned this by hand for the stall-file writer
("synchronously, never via the possibly-hung AsyncWriter"); this rule
enforces it mechanically.

Roots (callgraph v3 `concurrency_roots`):

* **signal handlers** — callables registered via `signal.signal(sig,
  fn)` (incl. nested closures) and callable arguments of
  `faulthandler.register`;
* **watchdog exit paths** — functions reachable from a thread entry
  point (`threading.Thread(target=...)` or a `.submit(...)`-deferred
  callable) that call `os._exit`: a thread that ends the process is by
  definition running while something else is broken.

The reachable set is walked with the v2 call graph plus a DUCK-TYPED
fallback: a method call on an untypeable receiver (`_current.emit(...)`,
`w.flush(...)`) resolves to every in-package method of that name.
Over-approximating reach is the correct bias for a safety rule — the
cost of a false edge is one justified suppression, the cost of a missed
edge is a hung preemption.

Flagged inside the reachable set:

* `<queue>.put(...)` without `timeout=`/`block=False` — blocks forever
  when the queue is full and its worker is wedged (the exact PR-7/8
  hazard: the terminal `sigterm`/`stall` event routed through the
  AsyncWriter's bounded queue);
* `<queue>.join()` / `<queue>.get()` without a bound;
* `<lock>.acquire()` without `timeout=`/`blocking=False`, and
  `with <lock>:` — a handler interrupting the thread that HOLDS the
  lock deadlocks on it (non-reentrancy);
* `<event>.wait()` / `<thread>.join()` without a timeout;
* jax dispatch (`jax.*` / `jnp.*` calls) — device interaction from a
  handler can block on a wedged runtime and reenters a client that is
  not async-signal-safe.

Calls that carry a bound (`timeout=`, `block=False`, `blocking=False`)
pass.  Not modeled (documented approximations): the run-scoped
preemption hook installed via `set_preemption_hook` (a module-global
function pointer the graph cannot follow — its jax dispatch is an
accepted, grace-bounded exception by design), and `if timeout is None`
guards around an unbounded branch that callers never take (suppress
with the justification saying so).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional

from ..callgraph import cached_walk
from ..core import Finding, LintContext, Rule, register
from ._concur import has_bound, local_ctor_types, receiver_kind
from .host_sync import _analyze


def _contains_os_exit(mi, fn_node: ast.AST) -> bool:
    for node in cached_walk(fn_node):
        if isinstance(node, ast.Call) \
                and (mi.dotted_of(node.func) or "") == "os._exit":
            return True
    return False


def concurrency_reaches(ctx: LintContext):
    """(handler_reach, exit_reach) — {id(fi): fi} closures, cached on
    ctx, shared with thread-shared-state."""
    cached = getattr(ctx, "_tpulint_concur_reach", None)
    if cached is None:
        index, _ = _analyze(ctx)
        handler_roots, thread_roots = index.concurrency_roots()
        handler_reach = index.reachable_from(handler_roots, duck=True)
        thread_reach = index.reachable_from(thread_roots, duck=False)
        exit_roots = [fi for fi in thread_reach.values()
                      if fi.node is not None
                      and _contains_os_exit(fi.module, fi.node)]
        exit_reach = index.reachable_from(exit_roots, duck=True)
        cached = (index, handler_reach, exit_reach, thread_reach)
        ctx._tpulint_concur_reach = cached  # type: ignore[attr-defined]
    return cached


@register
class SignalHandlerSafety(Rule):
    name = "signal-handler-safety"
    description = ("unbounded blocking (queue put/join, lock acquire, "
                   "event wait) or jax dispatch reachable from a signal "
                   "handler or a watchdog exit path")

    def check(self, ctx: LintContext) -> List[Finding]:
        _, handler_reach, exit_reach, _ = concurrency_reaches(ctx)
        out: List[Finding] = []
        seen: set = set()
        for reach, ctx_name in ((handler_reach, "a signal handler"),
                                (exit_reach, "a watchdog exit path")):
            for fi in reach.values():
                if fi.node is None or id(fi.node) in seen:
                    continue
                seen.add(id(fi.node))
                self._scan(fi, ctx_name, out)
        return out

    def _scan(self, fi, ctx_name: str, out: List[Finding]) -> None:
        mi, owner = fi.module, fi.owner_class
        pf = mi.pf
        locals_ = local_ctor_types(mi, fi.node)

        def emit(node, msg):
            out.append(Finding(
                rule=self.name, path=pf.rel, line=node.lineno,
                col=node.col_offset,
                message=f"{msg} — reachable from {ctx_name} via "
                        f"`{fi.qualname}`; the rest of the process may "
                        "already be wedged, so every wait here must be "
                        "bounded (docs/StaticAnalysis.md)"))

        for node in cached_walk(fi.node):
            if isinstance(node, ast.With):
                for item in node.items:
                    kind = receiver_kind(mi, owner, locals_,
                                         item.context_expr)
                    if kind == "lock":
                        emit(item.context_expr,
                             "`with <lock>:` acquires a lock with no "
                             "timeout; a handler interrupting the "
                             "holder deadlocks (non-reentrant)")
                continue
            if not isinstance(node, ast.Call):
                continue
            dotted = mi.dotted_of(node.func) or ""
            if dotted.startswith(("jax.", "jnp.")) \
                    or dotted.split(".", 1)[0] in ("jax", "jnp"):
                emit(node, f"`{dotted}` dispatches to the device runtime"
                           ", which may itself be wedged during a "
                           "stall/preemption")
                continue
            if not isinstance(node.func, ast.Attribute):
                continue
            meth = node.func.attr
            kind = receiver_kind(mi, owner, locals_, node.func.value)
            if meth == "put" and kind == "queue" \
                    and not has_bound(node):
                emit(node, "blocking queue put with no timeout: blocks "
                           "forever when the queue is full and its "
                           "worker is hung (write synchronously here "
                           "instead — the PR-7 stall-writer rule)")
            elif meth == "join" and kind in ("queue", "thread") \
                    and not has_bound(node) and not node.args:
                emit(node, f"unbounded {kind} join")
            elif meth == "get" and kind == "queue" \
                    and not has_bound(node):
                emit(node, "blocking queue get with no timeout")
            elif meth == "acquire" and kind == "lock" \
                    and not has_bound(node):
                emit(node, "lock acquire with no timeout (non-reentrant "
                           "deadlock if the interrupted thread holds it)")
            elif meth == "wait" and kind in ("event", "lock") \
                    and not has_bound(node) and not node.args:
                emit(node, "unbounded wait")
