"""Manifest loading and abstract tracing for the IR rules.

The linted package declares its hot jitted entries in
`<package>/_lint_entries.py` (protocol documented there): each entry
names a RecompileDetector group, a zero-arg builder returning the
jitted callable plus exemplar `jax.ShapeDtypeStruct` arguments, and a
set of declared IR shapes.  This module turns an entry into a
ClosedJaxpr:

* tracing is ABSTRACT — `fn.trace(*args)` (jax AOT) with
  ShapeDtypeStruct leaves builds the jaxpr from avals alone; nothing
  touches a device and nothing compiles, so a full-package audit is
  seconds, not minutes;
* tracing runs under `jax.enable_x64`: with the default
  x64-off config jax silently clamps EVERY array to 32 bits, which
  would make `ir-no-f64` a tautology.  With x64 on, a float64 numpy
  constant or weak-type promotion in device code produces a float64
  aval in the jaxpr — exactly the latent 10–20× TPU hazard the rule
  exists to surface (it is latent: the same code run under x64, e.g.
  by an embedding application, double-widths the hot path);
* the exemplar signature is hashed with the SAME (shape, dtype,
  static) scheme RecompileDetector/CostModel fingerprint at runtime
  (observability/watchdog.py call_signature), and that hash keys the
  per-entry result cache in `.tpulint_cache.json`.

Failures are data, not crashes: a manifest that does not import, an
entry whose builder raises, or a trace error each become an
`ir-trace-error` finding anchored at the manifest.
"""

from __future__ import annotations

import hashlib
import importlib
import os
import sys
from typing import Any, Iterator, List, Optional, Tuple

MANIFEST_BASENAME = "_lint_entries.py"


def manifest_rel(ctx) -> str:
    """Repo-relative path of the package's manifest (finding anchor)."""
    return os.path.join(ctx.package_name, MANIFEST_BASENAME)


def load_manifest(package_dir: str
                  ) -> Tuple[Optional[List], Optional[str]]:
    """Import `<package>._lint_entries` and return (entries, error).

    The package is imported for real (builders use relative imports),
    with its parent directory on sys.path — the same context the
    package runs under.  A missing manifest is an error string, not an
    exception: the caller turns it into an `ir-trace-error` finding."""
    package_dir = os.path.abspath(package_dir)
    pkg_name = os.path.basename(package_dir)
    path = os.path.join(package_dir, MANIFEST_BASENAME)
    if not os.path.exists(path):
        return None, (f"no IR entrypoint manifest: {pkg_name}/"
                      f"{MANIFEST_BASENAME} does not exist")
    parent = os.path.dirname(package_dir)
    inserted = False
    if parent not in sys.path:
        sys.path.insert(0, parent)
        inserted = True
    try:
        mod = importlib.import_module(f"{pkg_name}._lint_entries")
    except Exception as e:  # noqa: BLE001 - any import failure is a finding
        return None, f"cannot import {pkg_name}._lint_entries: {e!r}"
    finally:
        if inserted:
            try:
                sys.path.remove(parent)
            except ValueError:
                pass
    entries = getattr(mod, "ENTRIES", None)
    if entries is None:
        return None, (f"{pkg_name}._lint_entries defines no ENTRIES "
                      "(see the manifest protocol in "
                      "docs/StaticAnalysis.md)")
    return list(entries), None


def _normalize_build(built) -> Tuple[Any, tuple, dict]:
    if isinstance(built, tuple):
        if len(built) == 3:
            fn, args, kwargs = built
            return fn, tuple(args), dict(kwargs)
        if len(built) == 2:
            fn, args = built
            return fn, tuple(args), {}
    return built, (), {}


def signature_of(args: tuple, kwargs: dict) -> Tuple[tuple, tuple]:
    """The RecompileDetector fingerprint of an exemplar call: ((shape,
    dtype) per array leaf, repr per static leaf) over the flattened
    (args, kwargs) pytree — byte-compatible with
    observability/watchdog.py call_signature so the cache key and the
    runtime watchdog can never disagree about what an entry's
    signature IS."""
    import jax
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    arrays, static = [], []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            arrays.append((tuple(leaf.shape), str(leaf.dtype)))
        else:
            static.append(repr(leaf))
    return tuple(arrays), tuple(static)


def signature_hash(args: tuple, kwargs: dict) -> str:
    sig = signature_of(args, kwargs)
    return hashlib.sha256(repr(sig).encode()).hexdigest()[:16]


def trace_entry(entry) -> Tuple[Optional[Any], Optional[str],
                                Optional[str]]:
    """Abstractly trace one manifest entry.

    Returns (ClosedJaxpr, signature_hash, error): on success the error
    is None; on failure the jaxpr is None and the error is a one-line
    reason (builder exception, trace exception)."""
    import jax
    try:
        fn, args, kwargs = _normalize_build(entry.build())
    except Exception as e:  # noqa: BLE001 - builder failure is a finding
        return None, None, f"builder raised: {e!r}"
    try:
        sig = signature_hash(args, kwargs)
        with jax.enable_x64(True):
            traced = fn if hasattr(fn, "trace") else jax.jit(fn)
            closed = traced.trace(*args, **kwargs).jaxpr
    except Exception as e:  # noqa: BLE001 - trace failure is a finding
        return None, None, f"abstract trace failed: {e!r}"
    return closed, sig, None


# --------------------------------------------------------------- walking
def _sub_jaxprs(params: dict) -> Iterator[Any]:
    """Jaxpr-like values nested in an eqn's params (pjit/scan/while/
    cond/custom_* all stash callee jaxprs there).  Duck-typed on
    `.eqns` / `.jaxpr` so no fragile jax-internal imports."""
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr            # ClosedJaxpr
            elif hasattr(x, "eqns"):
                yield x                  # Jaxpr


def iter_jaxprs(closed) -> Iterator[Any]:
    """Every (sub-)Jaxpr of a ClosedJaxpr, outermost first."""
    stack = [closed.jaxpr]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eq in j.eqns:
            stack.extend(_sub_jaxprs(eq.params))


def iter_eqns(closed) -> Iterator[Any]:
    """Every equation of a ClosedJaxpr, sub-jaxprs included."""
    for j in iter_jaxprs(closed):
        for eq in j.eqns:
            yield eq


def aval_of(v):
    """The abstract value of a var or literal, or None."""
    return getattr(v, "aval", None)


def dtype_name(v) -> Optional[str]:
    aval = aval_of(v)
    dt = getattr(aval, "dtype", None)
    return None if dt is None else str(dt)
