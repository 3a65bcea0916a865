"""Jitted tensor traversal of a packed ensemble: all [rows x trees] at once.

One compiled program evaluates every tree for every row in lock-step,
`max_depth` iterations of

    node = where(x[:, feat[node]] <= thr[node], left[node], right[node])

with the reference's missing-value and categorical-bitset semantics folded
into the `where` (ref: tree.h:335 NumericalDecision, :372
CategoricalDecision; native/predict.c get_leaf_node is the host mirror of
exactly this decision).  Rows that reach a leaf early park on the negative
`~leaf` child pointer and stop moving; after max_depth steps every lane
holds a leaf.  Leaf values are gathered, summed per class and (optionally)
the objective's convert_output is applied — all in one XLA program, so a
predict call is a single device dispatch.

All arrays are EXPLICIT arguments (never closed-over constants): a jit
that embeds the model as a constant bakes it into the executable and
recompiles for every new model (see boosting/gbdt.py init's
gradient-program note).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..io.binning import MISSING_NAN, MISSING_ZERO
from .pack import CAT_MAX_F32, ZERO_THRESHOLD_F32


def ensemble_leaf_ids(x, split_feature, threshold, missing_type,
                      default_left, is_cat, left, right, cat_start,
                      cat_nwords, cat_words, depth: int):
    """x [B, F] float32, per-node arrays [T, NI] -> leaf ids [B, T] int32.

    Bit-identical to the host routing for float32 inputs: thresholds are
    pre-floored to float32 (pack.py), so every comparison agrees with the
    float64 host comparison on float32 values.
    """
    T, NI = split_feature.shape
    base = (jnp.arange(T, dtype=jnp.int32) * jnp.int32(NI))[None, :]
    sf = split_feature.reshape(-1)
    th = threshold.reshape(-1)
    mt = missing_type.reshape(-1)
    dl = default_left.reshape(-1)
    ic = is_cat.reshape(-1)
    lc = left.reshape(-1)
    rc = right.reshape(-1)
    cs = cat_start.reshape(-1)
    cn = cat_nwords.reshape(-1)
    nwords_total = cat_words.shape[0]

    def step(_, node):
        g = jnp.maximum(node, 0) + base          # [B, T] flat node index
        f = jnp.take(sf, g, mode="clip")
        v = jnp.take_along_axis(x, f, axis=1, mode="clip")
        nan = jnp.isnan(v)
        m = jnp.take(mt, g, mode="clip")
        # numerical decision (tree.h:335): NaN under non-NaN missing
        # handling is treated as 0.0 before the zero test
        fz = jnp.where(nan & (m != MISSING_NAN), jnp.float32(0), v)
        is_zero = jnp.abs(fz) <= jnp.float32(ZERO_THRESHOLD_F32)
        take_default = (((m == MISSING_ZERO) & is_zero)
                        | ((m == MISSING_NAN) & nan))
        num_left = jnp.where(take_default, jnp.take(dl, g, mode="clip"),
                             fz <= jnp.take(th, g, mode="clip"))
        # categorical decision (tree.h:372): NaN / negative / huge go
        # right; v truncates toward zero ((-1, 0) -> category 0)
        ok = (~nan) & (v > jnp.float32(-1.0)) & (v < jnp.float32(CAT_MAX_F32))
        vi = jnp.where(ok, v, jnp.float32(0)).astype(jnp.int32)
        word = vi >> jnp.int32(5)
        inset = ok & (word < jnp.take(cn, g, mode="clip"))
        widx = jnp.clip(jnp.take(cs, g, mode="clip") + word, 0,
                        nwords_total - 1)
        bit = (jnp.take(cat_words, widx, mode="clip")
               >> (vi & jnp.int32(31)).astype(jnp.uint32)) & jnp.uint32(1)
        cat_left = inset & (bit > 0)
        go_left = jnp.where(jnp.take(ic, g, mode="clip"), cat_left, num_left)
        nxt = jnp.where(go_left, jnp.take(lc, g, mode="clip"),
                        jnp.take(rc, g, mode="clip"))
        # parked lanes (already on a leaf) keep their ~leaf pointer
        return jnp.where(node >= 0, nxt, node)

    node = jnp.zeros(x.shape[:1] + (T,), jnp.int32)
    node = jax.lax.fori_loop(0, depth, step, node, unroll=False)
    return jnp.invert(node)


def _leaf_values(leaf, leaf_value):
    """Leaf ids [B, T] + values [T, NL] -> per-tree contributions [B, T]."""
    T, NL = leaf_value.shape
    flat = leaf_value.reshape(-1)
    g = leaf + (jnp.arange(T, dtype=jnp.int32) * jnp.int32(NL))[None, :]
    return jnp.take(flat, g, mode="clip")


def class_scores(leaf, leaf_value, num_class: int, average: bool):
    """Leaf ids [B, T] + values [T, NL] -> raw scores [B, K] (tree t
    belongs to class t % K; ref: predict.c lgbt_predict_batch)."""
    vals = _leaf_values(leaf, leaf_value)            # [B, T]
    B = vals.shape[0]
    T = leaf_value.shape[0]
    iters = T // num_class if num_class else 0
    scores = vals.reshape(B, iters, num_class).sum(axis=1)
    if average and iters > 0:
        scores = scores / jnp.float32(iters)         # gbdt_prediction.cpp:57
    return scores


def class_scores_early_stop(leaf, leaf_value, num_class: int, freq: int,
                            margin):
    """Raw scores with prediction early stopping as a masked accumulation
    scan (ref: prediction_early_stop.cpp; gbdt.py _predict_raw_impl is
    the host mirror).

    The traversal already settled every (row, tree) leaf in one pass —
    on a vector machine there is nothing to skip — but early stopping
    CHANGES THE ANSWER: a row whose margin clears the threshold at a
    round check keeps its partial sum and ignores all later trees.  So
    the accumulation replays the host's sequential semantics as a
    lax.scan over iterations: before adding iteration i (i > 0, i %
    freq == 0) the margin of the running sum is tested — binary margin
    = 2|score| (ref: CreateBinaryPredictionEarlyStopInstance),
    multiclass = top1 - top2 (CreateMulticlassPredictionEarlyStopInstance)
    — and rows past it stop accumulating via a per-row done mask.

    `freq` is static (it shapes the check pattern); `margin` is a traced
    f32 scalar so sweeping thresholds never re-traces the program.
    """
    vals = _leaf_values(leaf, leaf_value)            # [B, T]
    B = vals.shape[0]
    T = leaf_value.shape[0]
    K = max(num_class, 1)
    iters = T // K
    vals = jnp.moveaxis(vals.reshape(B, iters, K), 1, 0)  # [iters, B, K]

    def body(carry, xs):
        acc, done = carry
        v_i, i = xs
        if K == 1:
            m = jnp.float32(2.0) * jnp.abs(acc[:, 0])
        else:
            top2 = jax.lax.top_k(acc, 2)[0]
            m = top2[:, 0] - top2[:, 1]
        check = (i > jnp.int32(0)) & (i % jnp.int32(freq) == jnp.int32(0))
        done = done | (check & (m > margin))
        acc = acc + jnp.where(done[:, None], jnp.float32(0), v_i)
        return (acc, done), None

    acc0 = jnp.zeros((B, K), jnp.float32)
    done0 = jnp.zeros((B,), jnp.bool_)
    (acc, _), _ = jax.lax.scan(
        body, (acc0, done0),
        (vals, jnp.arange(iters, dtype=jnp.int32)))
    return acc
