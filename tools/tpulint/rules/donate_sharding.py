"""donated-sharding: donated shard_map entries need explicit shardings.

Donating a buffer into a `jax.jit(shard_map(...))` entry WITHOUT
explicit `in_shardings` leaves XLA to infer the donated layout from
the runtime arguments.  On a multi-device mesh the inferred sharding
can disagree with what the aliasing pass needs, so the donation is
silently dropped ("Some donated buffers were not usable") at best and
destabilizes the multi-device compile at worst — the donation x SPMD
interaction implicated when a multi-device dry run wedged until the
wall-clock cap.
`parallel/data_parallel.py` now passes explicit shardings on its
donate path and `boosting/gbdt.py` gates grow-buffer donation off
under a mesh; this rule keeps both invariants from regressing.

Flags: `jax.jit(<shard_map result>, donate_argnums=...)` (or
`donate_argnames`) where the donate spec is not the literal empty
tuple and no `in_shardings` keyword is present.  The shard_map result
is recognized directly (`jax.jit(shard_map(...), ...)`) or through a
local/module binding (`mapped = shard_map(...); jax.jit(mapped, ...)`).
Config-gated specs (`donate_argnums=(1, 2) if donate else ()`) count
as donating: the entry must be safe when the configuration turns
donation ON.
"""

from __future__ import annotations

import ast
from typing import List

from ..callgraph import cached_walk, module_info_for
from ..core import Finding, LintContext, Rule, register
from .spmd import _is_shard_map_call


@register
class DonatedSharding(Rule):
    name = "donated-sharding"
    description = ("jax.jit over a shard_map'd entry donates buffers "
                   "without explicit in_shardings — XLA infers the "
                   "donated layout from the arguments")

    file_local = True

    def check_file(self, ctx: LintContext, pf) -> List[Finding]:
        out: List[Finding] = []
        if pf.tree is None:
            return out
        self._check_module(module_info_for(ctx, pf), out)
        return out

    def _check_module(self, mi, out: List[Finding]) -> None:
        # names bound to a shard_map(...) result anywhere in the module
        # (module level or function-local)
        sm_names = set()
        for node in cached_walk(mi.pf.tree):
            if isinstance(node, ast.Assign) \
                    and _is_shard_map_call(mi, node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        sm_names.add(t.id)
        for node in cached_walk(mi.pf.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if mi.dotted_of(node.func) not in ("jax.jit", "jit"):
                continue
            target = node.args[0]
            is_sm = _is_shard_map_call(mi, target) or (
                isinstance(target, ast.Name) and target.id in sm_names)
            if not is_sm:
                continue
            donate_kw = [kw for kw in node.keywords
                         if kw.arg in ("donate_argnums",
                                       "donate_argnames")]
            if not donate_kw:
                continue
            maybe_donates = any(
                not (isinstance(kw.value, (ast.Tuple, ast.List))
                     and not kw.value.elts)
                for kw in donate_kw)
            has_shardings = any(kw.arg == "in_shardings"
                                for kw in node.keywords)
            if maybe_donates and not has_shardings:
                out.append(Finding(
                    rule=self.name, path=mi.pf.rel,
                    line=node.lineno, col=node.col_offset,
                    message="jax.jit over a shard_map'd entry donates "
                            "buffers without explicit in_shardings — "
                            "XLA then infers the donated layout from "
                            "the arguments (the donation x SPMD "
                            "interaction implicated in a multi-device "
                            "hang); "
                            "pass in_shardings for every donated "
                            "argument or drop the donation"))
