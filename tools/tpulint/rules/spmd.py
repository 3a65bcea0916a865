"""spmd-axis-discipline: mesh-axis and shard_map hygiene.

Under SPMD three classes of mistake produce deadlocks, wrong numbers,
or a multi-device stall (a rank wedged in a collective) — none of which a single-device
test can see:

* a collective naming an axis the mesh does not declare fails at run
  time only on a real multi-device mesh (`unbound axis name`), i.e. in
  the expensive environment;
* a collective OUTSIDE any `shard_map`-wrapped body traces fine on one
  device (axis size 1) and deadlocks or mis-reduces under GSPMD when
  ranks disagree about program order;
(The sibling `donated-sharding` rule covers the third hazard of the
family: donating into a shard_map'd entry without explicit
`in_shardings`.)

Checks (package-wide, AST + the v2 call graph):

1. **axis registry**: every `Mesh(..., (<axes>,))` construction in the
   package declares its axis names (string literals, or names bound to
   module-level string constants — `DATA_AXIS = "data"`).
2. **axis names**: literal axis arguments of `lax.psum`/`pmean`/...
   and string entries of `PartitionSpec(...)` specs must be declared
   axes.  Non-literal axes (a parameter like `params.data_axis`) are
   runtime configuration and are not checked.
3. **shard_map containment**: a collective must live in a function
   lexically inside, or reachable through the call graph from, a
   function passed to `shard_map` (the wave engine's `_psum` sits two
   modules away from its `shard_map` wrapper — the v2 graph closes
   that distance).  `distributed.py` is exempt: its collectives ride
   the multi-process `jax.experimental` runtime, not a shard_map.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from ..core import Finding, LintContext, Rule, register
from ..callgraph import cached_walk
from .host_sync import _analyze

COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "all_gather",
               "psum_scatter", "all_to_all", "ppermute", "pshuffle",
               "axis_index"}
_EXEMPT_FILES = {"distributed.py"}


def _str_const(mi, expr: ast.AST) -> Optional[str]:
    """A string literal, or a Name bound to a module-level string."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value
    if isinstance(expr, ast.Name):
        for e in mi.binding_exprs.get(expr.id, []):
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                return e.value
    return None


def _axis_strs(mi, expr: ast.AST) -> List[str]:
    out = []
    if isinstance(expr, (ast.Tuple, ast.List)):
        elts = expr.elts
    else:
        elts = [expr]
    for e in elts:
        s = _str_const(mi, e)
        if s is not None:
            out.append(s)
    return out


def _is_shard_map_call(mi, expr: ast.AST) -> bool:
    return (isinstance(expr, ast.Call)
            and (mi.dotted_of(expr.func) or "").rsplit(".", 1)[-1]
            == "shard_map")


@register
class SpmdAxisDiscipline(Rule):
    name = "spmd-axis-discipline"
    description = ("collective/PartitionSpec axis names must match the "
                   "declared mesh axes, and collectives must live inside "
                   "(or be reachable from) shard_map-wrapped bodies")

    def check(self, ctx: LintContext) -> List[Finding]:
        index, _ = _analyze(ctx)
        out: List[Finding] = []
        axes = self._declared_axes(index)
        rooted = self._shard_map_rooted(index)
        for mi in index.modules.values():
            if mi.pf.tree is None:
                continue
            self._check_module(mi, index, axes, rooted, out)
        return out

    # ---- 1. axis registry ---------------------------------------------
    def _declared_axes(self, index) -> Set[str]:
        axes: Set[str] = set()
        for mi in index.modules.values():
            if mi.pf.tree is None:
                continue
            for node in cached_walk(mi.pf.tree):
                if not isinstance(node, ast.Call):
                    continue
                dotted = (mi.dotted_of(node.func) or "").rsplit(".", 1)[-1]
                if dotted != "Mesh":
                    continue
                cand = None
                if len(node.args) >= 2:
                    cand = node.args[1]
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        cand = kw.value
                if cand is not None:
                    axes.update(_axis_strs(mi, cand))
        return axes

    # ---- 3. shard_map reachability ------------------------------------
    def _shard_map_rooted(self, index) -> Set[int]:
        """ids of def nodes lexically passed to shard_map, plus
        everything reachable from them through the call graph."""
        rooted_funcs = []  # FuncInfo seeds
        rooted_defs: Set[int] = set()

        def note_ref(mi, owner, encl_nested, expr):
            if isinstance(expr, ast.Name) and expr.id in encl_nested:
                # nested def passed to shard_map: rooted, and its own
                # callees must be expanded too (the wave engine's _psum
                # sits behind inner -> grow_tree_wave_impl)
                rooted_funcs.append(index._func_for_def(
                    mi, encl_nested[expr.id]))
                return
            for fid in index.collect_refs(mi, expr, owner, None):
                rooted_funcs.append(index.func(fid))

        for mi in index.modules.values():
            if mi.pf.tree is None:
                continue
            funcs = list(mi.top_funcs.values())
            for ci in mi.top_classes.values():
                funcs += list(ci.methods.values())
            for fi in funcs:
                if isinstance(fi.node, ast.Lambda):
                    continue
                nested = {n.name: n for n in cached_walk(fi.node)
                          if isinstance(n, (ast.FunctionDef,
                                            ast.AsyncFunctionDef))
                          and n is not fi.node}
                for node in cached_walk(fi.node):
                    if _is_shard_map_call(mi, node):
                        target = node.args[0] if node.args else None
                        for kw in node.keywords:
                            if kw.arg in ("f", "fun"):
                                target = kw.value
                        if target is not None:
                            note_ref(mi, fi.owner_class, nested, target)
            # module-level shard_map calls
            for node in cached_walk(mi.pf.tree):
                if _is_shard_map_call(mi, node) and node.args:
                    note_ref(mi, None, {}, node.args[0])

        # BFS over the call graph from the rooted functions
        seen: Set[int] = set()
        work = list(rooted_funcs)
        while work:
            fi = work.pop()
            if id(fi) in seen or fi.node is None:
                continue
            seen.add(id(fi))
            rooted_defs.add(id(fi.node))
            for node in cached_walk(fi.node):
                if isinstance(node, ast.Call):
                    for callee, _off in index.resolve_call_multi(
                            fi.module, node.func, fi.owner_class):
                        work.append(callee)
        return rooted_defs

    # ---- per-module checks --------------------------------------------
    def _check_module(self, mi, index, axes: Set[str],
                      rooted: Set[int], out: List[Finding]) -> None:
        def enclosing_defs(target: ast.AST) -> List[ast.AST]:
            # nearest enclosing def of an arbitrary node
            found: List[ast.AST] = []

            def rec(node, chain):
                if node is target:
                    found.extend(chain)
                    return True
                for child in ast.iter_child_nodes(node):
                    nxt = chain + [child] if isinstance(
                        child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)) else chain
                    if rec(child, nxt):
                        return True
                return False

            rec(mi.pf.tree, [])
            return found

        exempt = mi.pf.pkg_rel in _EXEMPT_FILES
        for node in cached_walk(mi.pf.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = mi.dotted_of(node.func) or ""
            mod, _, tail = dotted.rpartition(".")
            # 2. collective axis names + 3. shard_map containment
            if tail in COLLECTIVES and mod in ("jax.lax", "lax"):
                axis_expr = None
                if len(node.args) >= 2:
                    axis_expr = node.args[1]
                elif tail == "axis_index" and node.args:
                    axis_expr = node.args[0]
                for kw in node.keywords:
                    if kw.arg in ("axis_name", "axis"):
                        axis_expr = kw.value
                if axis_expr is not None and axes:
                    for s in _axis_strs(mi, axis_expr):
                        if s not in axes:
                            out.append(Finding(
                                rule=self.name, path=mi.pf.rel,
                                line=node.lineno, col=node.col_offset,
                                message=f"lax.{tail} names axis {s!r}, "
                                        "which no Mesh in the package "
                                        "declares (declared: "
                                        f"{sorted(axes)}) — an unbound "
                                        "axis fails only on the real "
                                        "multi-device mesh"))
                if not exempt:
                    chain = enclosing_defs(node)
                    if not any(id(d) in rooted for d in chain):
                        out.append(Finding(
                            rule=self.name, path=mi.pf.rel,
                            line=node.lineno, col=node.col_offset,
                            message=f"lax.{tail} outside any shard_map-"
                                    "wrapped body (lexically or via the "
                                    "call graph) — under GSPMD an "
                                    "unmapped collective deadlocks or "
                                    "mis-reduces when ranks disagree "
                                    "about program order"))
            # 2b. PartitionSpec axis strings
            elif tail in ("PartitionSpec", "P") and axes \
                    and mod.startswith(("jax", "")):
                for a in list(node.args) + [kw.value
                                            for kw in node.keywords]:
                    s = _str_const(mi, a)
                    if s is not None and s not in axes:
                        out.append(Finding(
                            rule=self.name, path=mi.pf.rel,
                            line=node.lineno, col=node.col_offset,
                            message=f"PartitionSpec names axis {s!r}, "
                                    "which no Mesh in the package "
                                    f"declares (declared: {sorted(axes)})"))
