"""Plain reference for the objective, histogram and split layers of a
lambdarank job: the pairwise rule a query at a time in float64 NumPy, and
tree 0 against the rows it was grown on.

Written from the published rule (LightGBM rank_objective.hpp:131-260,
`LambdarankNDCG::GetGradientsForOneQuery`), apart from
`lightgbm_tpu/ranking.py`: a loop over the queries, inside it a loop over
the higher-ranked document i, the lower-ranked documents j > i as one
vector.

    documents in stable descending score order; T = truncation level
    maxDCG_T = DCG of the ideal order's first min(T, n) documents
    for i < min(T, n - 1), j > i, label_i != label_j:
        high, low = the document with the larger label, the other
        delta = |gain_i - gain_j| * |disc_i - disc_j| / maxDCG_T
        delta /= 0.01 + |s_i - s_j|            when norm and best != worst
        p = 1 / (1 + exp(sigma * (s_high - s_low)))
        lambda_high -= sigma * delta * p;  lambda_low += sigma * delta * p
        hessian_high, hessian_low += sigma^2 * delta * p * (1 - p)
    when norm and S = sum of 2 * sigma * delta * p > 0:
        the query's lambdas and hessians *= log2(1 + S) / S

with gain = 2^label - 1 and disc(position) = 1 / log2(position + 2).

No `boost_from_average` for a ranking objective: every score is 0 when
tree 0 is grown, so score order is row order, and which rows a leaf holds
fixes its count, its hessian sum and its output.  The kernels round each
row's gradient and hessian to bf16, which moves a sum by 0.4% at most;
the tolerances below are 1%, as `binary_first_tree`'s.
"""

import numpy as np

SIGMA = 1.0
TRUNCATION = 30
NORM = True
WEIGHT_REL_TOL = 0.01
VALUE_REL_TOL = 0.01
# a leaf's output is -learning_rate * sum_g / sum_h: where the gradients
# of a leaf's rows nearly cancel, 1% of the output is under the rounding
# of the sum, so an output is held to 1% of itself or of VALUE_FLOOR
VALUE_FLOOR = 0.01


def query_gradients(labels, scores, sigma=SIGMA, truncation=TRUNCATION,
                    norm=NORM):
    """(lambdas, hessians), float64, of one query's documents in the
    order given."""
    n = len(labels)
    lam = np.zeros(n)
    hes = np.zeros(n)
    if n <= 1:
        return lam, hes
    order = np.argsort(-np.asarray(scores, np.float64), kind="stable")
    s = np.asarray(scores, np.float64)[order]
    lab = np.asarray(labels).astype(np.int64)[order]
    gain = 2.0 ** lab - 1.0
    disc = 1.0 / np.log2(np.arange(n) + 2.0)
    k = min(truncation, n)
    max_dcg = float(np.sort(gain)[::-1][:k] @ disc[:k])
    if max_dcg <= 0.0:
        return lam, hes
    spread = norm and s[0] != s[-1]
    lam_s = np.zeros(n)
    hes_s = np.zeros(n)
    total = 0.0
    for i in range(min(truncation, n - 1)):
        j = i + 1 + np.flatnonzero(lab[i + 1:] != lab[i])
        if not len(j):
            continue
        delta = (np.abs(gain[i] - gain[j]) * np.abs(disc[i] - disc[j])
                 / max_dcg)
        if spread:
            delta = delta / (0.01 + np.abs(s[i] - s[j]))
        i_high = lab[i] > lab[j]
        high_less_low = np.where(i_high, s[i] - s[j], s[j] - s[i])
        p = 1.0 / (1.0 + np.exp(sigma * high_less_low))
        push = sigma * delta * p
        curve = sigma * push * (1.0 - p)
        to_i = np.where(i_high, -push, push)
        lam_s[i] += to_i.sum()
        lam_s[j] -= to_i
        hes_s[i] += curve.sum()
        hes_s[j] += curve
        total += 2.0 * push.sum()
    if norm and total > 0.0:
        lam_s *= np.log2(1.0 + total) / total
        hes_s *= np.log2(1.0 + total) / total
    lam[order] = lam_s
    hes[order] = hes_s
    return lam, hes


def gradients(y, scores, group, queries=None):
    """(lambdas, hessians) of all rows, zeros outside `queries` (indices
    into `group`; all of them when None)."""
    ends = np.cumsum(np.asarray(group, np.int64))
    starts = ends - np.asarray(group, np.int64)
    lam = np.zeros(len(y))
    hes = np.zeros(len(y))
    with np.errstate(over="ignore"):        # exp of a wide gap: p = 0
        for q in (range(len(ends)) if queries is None else queries):
            a, b = int(starts[q]), int(ends[q])
            lam[a:b], hes[a:b] = query_gradients(y[a:b], scores[a:b])
    return lam, hes


def check(tree, leaf_of_row, y, group, learning_rate):
    """`tree`: the program's first tree (leaf_count, leaf_weight,
    leaf_value, num_leaves); `leaf_of_row`: the leaf each training row
    falls in, from the host predictor.  Returns (ok, facts)."""
    nl = int(tree.num_leaves)
    lam, hes = gradients(y, np.zeros(len(y)), group)
    count = np.bincount(leaf_of_row, minlength=nl)
    sum_g = np.bincount(leaf_of_row, weights=lam, minlength=nl)
    sum_h = np.bincount(leaf_of_row, weights=hes, minlength=nl)
    counts_equal = bool(np.array_equal(count, tree.leaf_count[:nl]))
    with np.errstate(divide="ignore", invalid="ignore"):
        weight_err = float(np.max(
            np.abs(tree.leaf_weight[:nl] - sum_h) / sum_h))
        value = -learning_rate * sum_g / sum_h
    value_err = float(np.max(np.abs(tree.leaf_value[:nl] - value)
                             / np.maximum(np.abs(value), VALUE_FLOOR)))
    ok = (counts_equal and weight_err <= WEIGHT_REL_TOL
          and value_err <= VALUE_REL_TOL)
    return ok, {"first_tree_leaves_checked": nl,
                "first_tree_counts_equal": counts_equal,
                "first_tree_weight_rel_err": weight_err,
                "first_tree_value_rel_err": value_err,
                "first_tree_rows_with_zero_hessian": int((hes == 0).sum())}
