"""Each row's leaf value, for the score update (`gbdt.py _score_update`,
`_score_update_shrink`): `leaf_vals[clip(leaf_id)]`, bit for bit.

An XLA gather on a TPU walks its indices one by one (8.2 ns a row: 21.6 ms
at 2,625,536 rows and 255 leaves; PERF.md, PR 31), so there the
table is read by one-hot instead, as the recolour and the prune read
theirs (`learner/wave.py`): every row compares its id with every leaf and
sums the one value that matched.  The sum runs over the values' BIT
PATTERNS as int32 — exactly one term is nonzero, so it is exact — and not
over the floats: `0 * NaN` and `0 * inf` of a float product would poison
every row with one leaf's fault.  XLA fuses the `[L, n]` compare and
select into the reduction (nothing of that size exists in memory), with
rows on lanes.

The one-hot costs `L` compares a row where the gather's cost does not
grow with `L`, so the form is a static rule on the table's size and the
backend (`pick_form`), never an option.
"""
import jax
import jax.numpy as jnp

from ..observability import global_registry

# Largest table the one-hot form reads on a TPU.  The whole update at
# 2,625,536 rows: one-hot 0.60 / 2.25 / 8.69 / 17.27 / 34.42 ms at 255 /
# 1,023 / 4,095 / 8,191 / 16,383 leaves (2.1 ms a thousand leaves), the
# gather 21.69 / 14.11 / 18.84 / 18.84 / 18.85: they cross near 8,900
# (`tools/kernel_checks.py --score-lookup` on a v5e, PR 31; PERF.md
# section 6).
ONE_HOT_MAX_LEAVES = 8192


def pick_form(num_leaves: int, backend: str) -> str:
    """`"onehot"` or `"take"` for a table of `num_leaves` on `backend`.
    The CPU's gather is the fast form at every size."""
    if backend == "tpu" and num_leaves <= ONE_HOT_MAX_LEAVES:
        return "onehot"
    return "take"


def lookup_take(leaf_vals: jnp.ndarray, leaf_id: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(leaf_vals, jnp.clip(leaf_id, 0, leaf_vals.shape[0] - 1))


def lookup_onehot(leaf_vals: jnp.ndarray,
                  leaf_id: jnp.ndarray) -> jnp.ndarray:
    """`lookup_take` without a gather: f32[L], i32[n] -> f32[n], equal to
    it in every bit (`-0.0`, denormals, `inf`, a `NaN`'s payload), ids
    clipped into the table as there."""
    L = leaf_vals.shape[0]
    ids = jnp.clip(leaf_id, 0, L - 1)
    bits = jax.lax.bitcast_convert_type(leaf_vals, jnp.int32)
    hit = jnp.arange(L, dtype=ids.dtype)[:, None] == ids[None, :]
    row_bits = jnp.sum(jnp.where(hit, bits[:, None], 0), axis=0)
    return jax.lax.bitcast_convert_type(row_bits, jnp.float32)


_FORMS = {"onehot": lookup_onehot, "take": lookup_take}


def lookup(leaf_vals: jnp.ndarray, leaf_id: jnp.ndarray) -> jnp.ndarray:
    """The form `pick_form` names for this table and backend.  Called
    while a score-update program is traced, so the registry counts by
    traced signature which form each program took
    (`score_lookup_onehot_traces`, `score_lookup_take_traces`)."""
    form = pick_form(leaf_vals.shape[0], jax.default_backend())
    global_registry.inc(f"score_lookup_{form}_traces")
    return _FORMS[form](leaf_vals, leaf_id)
