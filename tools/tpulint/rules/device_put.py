"""no-device-put-in-loop: H2D transfers must not sit in Python loop bodies.

`jax.device_put` / `jnp.asarray` of host data costs a host->device
transfer and a synchronous dispatch (see boosting/gbdt.py's hot-path
notes).  Inside a Python `for` /
`while` body that cost multiplies by the trip count and the dispatch
queue never pipelines — the classic accidental serializer, and exactly
the bug an inference batcher breeds: putting each request row / bucket
element individually instead of padding once and transferring once.

The rule is lexical: any `jax.device_put` or `jnp.asarray` call inside a
`for`/`while` statement body in device-code scope is flagged.  Loops
inside jitted code are traced (unrolled) rather than executed, and a
device_put there is a no-op — but device code here keeps jnp.asarray out
of trace bodies anyway, so the rule does not special-case them; suppress
with a justification for the rare intentional per-iteration put.
Comprehensions/generators are NOT matched (the ROADMAP'd rule targets
statement loops; a comprehension converting a handful of scalars is the
common benign form).

Scope: the same device-code modules as explicit-dtype — learner/, ops/,
parallel/, inference/, io/device_bin.py.
"""

from __future__ import annotations

import ast
from typing import List

from ..core import Finding, LintContext, Rule, register
from .dtype import _in_scope

_PUT_NAMES = {"jax.device_put", "jnp.asarray", "jax.numpy.asarray"}


@register
class NoDevicePutInLoop(Rule):
    name = "no-device-put-in-loop"
    description = ("jax.device_put/jnp.asarray inside a for/while body — "
                   "one H2D transfer per iteration serializes the loop")

    file_local = True

    def check_file(self, ctx: LintContext, pf) -> List[Finding]:
        from ..callgraph import cached_walk, module_info_for
        out: List[Finding] = []
        if pf.tree is None or not _in_scope(pf.pkg_rel):
            return out
        mi = module_info_for(ctx, pf)
        seen = set()
        for loop in cached_walk(pf.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in cached_walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                dotted = mi.dotted_of(node.func) or ""
                if dotted not in _PUT_NAMES:
                    continue
                key = (node.lineno, node.col_offset)
                if key in seen:  # nested loops walk the same call twice
                    continue
                seen.add(key)
                out.append(Finding(
                    rule=self.name, path=pf.rel, line=node.lineno,
                    col=node.col_offset,
                    message=f"{dotted} inside a {'for' if isinstance(loop, ast.For) else 'while'} "
                            "body — host->device transfers in loops "
                            "serialize on the dispatch queue; batch the "
                            "data and transfer once outside the loop"))
        return out
