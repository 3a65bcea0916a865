"""Plain reference for a binary objective's tree 0 grown on sparse input:
float64 NumPy on the RAW columns of the CSC matrix, independent of bins,
bundles and the engine.

Every row has the same score when tree 0 is grown (boost_from_average),
so its gradient and hessian follow from its label alone.  Three checks:

(a, b) `route` sends every training row through the tree by the model's
    real-valued thresholds (`x <= threshold` goes left; an entry that is
    not stored is 0.0; a missing value takes the node's default side), a
    node touching only the stored values of its one column.  Every
    leaf's count must equal the model's `leaf_count` exactly, and its
    weight and value must be the sums over the rows it holds
    (`binary_first_tree`'s tolerances: the kernels round each row's
    gradient and hessian to bf16, which at tree 0 moves every hessian by
    the same 0.1%, so sums read ~0.001 of 0.01).  The same sums from
    gradients rounded to the nearest precision below (an 8-bit float, 3
    bits of mantissa) are returned beside them and read 0.02: over the
    limit.
(c) the root: per-column gradient / hessian / count sums on either side
    of every candidate threshold (the Dataset's own bin upper bounds),
    built from the raw columns with no bundle, under the configuration's
    constraints.  The model's root split, scored by these sums, must
    reach the best gain they hold to `ROOT_GAIN_REL_TOL`, and its left
    and right counts must be exact.

A bundle offset shifted by one, a default bin that was not restored or a
conflict that lost a member gives other histograms, so another split or
other counts: (b) or (c) fails.
"""

import numpy as np

WEIGHT_REL_TOL = 0.01       # binary_first_tree's
VALUE_ABS_TOL = 0.01
ROOT_GAIN_REL_TOL = 1e-3    # bf16 operands move a gain by less (section 6)
LOW_MANTISSA_BITS = 3       # an 8-bit float (e4m3), the precision below bf16
MISSING_ZERO, MISSING_NAN = 1, 2
ZERO_EPS = 1e-35            # LightGBM's kZeroThreshold


def _round_to(x, mantissa_bits):
    """float64 values rounded to `mantissa_bits` bits after the leading
    one (to nearest)."""
    m, e = np.frexp(np.asarray(x, np.float64))
    scale = 2.0 ** (mantissa_bits + 1)
    return np.ldexp(np.round(m * scale) / scale, e)


def _goes_left(x, threshold, default_left, missing):
    """The numerical decision of a node (ref: tree.h NumericalDecision)."""
    nan = np.isnan(x)
    if missing != MISSING_NAN:
        x = np.where(nan, 0.0, x)
    is_missing = ((nan if missing == MISSING_NAN else False)
                  | ((np.abs(x) <= ZERO_EPS) if missing == MISSING_ZERO
                     else False))
    return np.where(is_missing, default_left, x <= threshold)


def route(tree, csc):
    """The leaf each row of `csc` falls in, by the tree's real-valued
    thresholds on the raw columns."""
    n = csc.shape[0]
    node = np.zeros(n, np.int32)        # >= 0: internal node; < 0: ~leaf
    todo = [0] if int(tree.num_leaves) > 1 else []
    while todo:
        k = todo.pop()
        dt = int(tree.decision_type[k])
        if dt & 1:
            raise ValueError("sparse_first_tree: categorical split")
        default_left, missing = bool(dt & 2), (dt >> 2) & 3
        thr = float(tree.threshold[k])
        lc, rc = int(tree.left_child[k]), int(tree.right_child[k])
        todo += [c for c in (lc, rc) if c >= 0]
        f = int(tree.split_feature[k])
        s, e = csc.indptr[f], csc.indptr[f + 1]
        rows = csc.indices[s:e]
        here = node[rows] == k
        rows = rows[here]
        left = _goes_left(csc.data[s:e][here].astype(np.float64), thr,
                          default_left, missing)
        # every row at the node goes where an absent entry (0.0) goes,
        # then the rows that store a value go where it sends them
        zero_left = bool(_goes_left(np.zeros(1), thr, default_left,
                                    missing)[0])
        node[node == k] = lc if zero_left else rc
        node[rows] = np.where(left, lc, rc)
    return ~node


def _first_tree_gradients(y):
    pavg = float(np.mean(y > 0))
    init = float(np.log(pavg / (1.0 - pavg)))
    lab = np.where(y > 0, 1.0, -1.0)
    g = -lab / (1.0 + np.exp(lab * init))
    return init, g, np.abs(g) * (1.0 - np.abs(g))


def _leaf_errors(tree, leaf, nl, init, g, h, lr, l2):
    sum_g = np.bincount(leaf, weights=g, minlength=nl)
    sum_h = np.bincount(leaf, weights=h, minlength=nl)
    weight_err = float(np.max(np.abs(tree.leaf_weight[:nl] - sum_h) / sum_h))
    value_err = float(np.max(np.abs(
        tree.leaf_value[:nl] - (init - lr * sum_g / (sum_h + l2)))))
    return weight_err, value_err


def root_scan(csc, g, h, bounds, min_data, min_hess, l2):
    """Per used column, the gain of every candidate threshold at the
    root: {column: (gains [num_bin - 1], left counts [num_bin - 1])};
    a candidate the constraints refuse reads -inf."""
    n = csc.shape[0]
    G, H = float(g.sum()), float(h.sum())
    out = {}
    for f, ub in bounds.items():
        nb = len(ub)
        s, e = csc.indptr[f], csc.indptr[f + 1]
        rows = csc.indices[s:e]
        b = np.searchsorted(ub[:-1], csc.data[s:e].astype(np.float64),
                            side="left")
        hg = np.bincount(b, weights=g[rows], minlength=nb)
        hh = np.bincount(b, weights=h[rows], minlength=nb)
        hc = np.bincount(b, minlength=nb).astype(np.float64)
        zb = int(np.searchsorted(ub[:-1], 0.0, side="left"))
        hg[zb] += G - hg.sum()          # rows that store nothing are 0.0
        hh[zb] += H - hh.sum()
        hc[zb] += n - hc.sum()
        gl, hl, cl = (np.cumsum(a)[:-1] for a in (hg, hh, hc))
        gr, hr, cr = G - gl, H - hl, n - cl
        ok = ((cl >= min_data) & (cr >= min_data)
              & (hl >= min_hess) & (hr >= min_hess))
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = (gl * gl / (hl + l2) + gr * gr / (hr + l2)
                    - G * G / (H + l2))
        out[f] = (np.where(ok, gain, -np.inf), cl)
    return out


def check(tree, csc, y, bounds, params):
    """`tree`: the program's first tree; `csc`: the raw training matrix;
    `bounds`: {original column: its bin upper bounds} for the columns
    the Dataset uses, none of them with a missing type; `params`: the
    configuration's.  Returns (checks, facts): three named checks."""
    nl = int(tree.num_leaves)
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    init, g, h = _first_tree_gradients(y)
    leaf = route(tree, csc)
    count = np.bincount(leaf, minlength=nl)
    counts_equal = bool(np.array_equal(count, tree.leaf_count[:nl]))
    weight_err, value_err = _leaf_errors(tree, leaf, nl, init, g, h, lr, l2)
    low = _leaf_errors(tree, leaf, nl, init,
                       _round_to(g, LOW_MANTISSA_BITS),
                       _round_to(h, LOW_MANTISSA_BITS), lr, l2)

    scan = root_scan(csc, g, h, bounds,
                     float(params.get("min_data_in_leaf", 20)),
                     float(params.get("min_sum_hessian_in_leaf", 1e-3)), l2)
    best_f = max(scan, key=lambda f: scan[f][0].max())
    best_gain = float(scan[best_f][0].max())
    root_f = int(tree.split_feature[0])
    lc, rc = int(tree.left_child[0]), int(tree.right_child[0])
    model_left, model_right = (
        int(tree.internal_count[c] if c >= 0 else tree.leaf_count[~c])
        for c in (lc, rc))
    gain_at_model, left_at_model = float("-inf"), -1
    if root_f in scan:
        j = int(np.argmin(np.abs(bounds[root_f][:-1]
                                 - float(tree.threshold[0]))))
        gain_at_model = float(scan[root_f][0][j])
        left_at_model = int(scan[root_f][1][j])
    ratio = gain_at_model / best_gain if best_gain > 0 else float("nan")
    root_counts_equal = (left_at_model == model_left
                         and len(y) - left_at_model == model_right)
    checks = {
        "first_tree_routes_its_rows": counts_equal,
        "first_tree_sums_its_rows": (weight_err <= WEIGHT_REL_TOL
                                     and value_err <= VALUE_ABS_TOL),
        "root_split_is_the_references": bool(
            ratio >= 1.0 - ROOT_GAIN_REL_TOL and root_counts_equal),
    }
    return checks, {
        "first_tree_leaves_checked": nl,
        "first_tree_counts_equal": counts_equal,
        "first_tree_weight_rel_err": weight_err,
        "first_tree_value_abs_err": value_err,
        "first_tree_weight_rel_err_8bit": low[0],
        "first_tree_value_abs_err_8bit": low[1],
        "root_gain_reference_best": best_gain,
        "root_gain_reference_at_model_split": gain_at_model,
        "root_gain_ratio": ratio,
        "root_gain_model": float(tree.split_gain[0]),
        "root_best_is_models_column": bool(best_f == root_f),
        "root_counts_equal": bool(root_counts_equal),
        "root_left_count": model_left, "root_right_count": model_right}
