"""Plain reference for a binary objective's tree 0 grown on numeric
columns with missing values: float64 NumPy on the RAW columns,
independent of the program's binning, predictor, engine and split code.
Written from upstream's described rules (LightGBM docs/Advanced-Topics.rst
"Missing Value Handle": `use_missing=true`, `zero_as_missing=false`, NaN
is missing and each split learns which side it goes to), not from this
repo's `io/binning.py` or `ops/split.py`.

Every row has the same score when tree 0 is grown (boost_from_average),
so its gradient and hessian follow from its label alone: a node's
histogram is its per-bin counts of positive and negative rows, and sums
at any operand precision are those counts times the two rounded values.
Three checks:

(b) `missing_types`: a column's missing type is NaN exactly where the
    rows the Dataset samples for its bins (`bin_construct_sample_cnt`,
    drawn by `data_random_seed`: the one thing taken from the program's
    convention) held a NaN, and none otherwise.  The Dataset's own types
    must be these.
(a) `route` sends every training row through the tree by the model's
    thresholds (`x <= threshold` goes left): a NaN of a NaN-typed column
    follows the node's `default_left`, a NaN of a column typed none is
    0.0, as upstream reads it.  Every leaf's count must equal the
    model's exactly, and its hessian sum (`leaf_weight`) and output
    (`leaf_value`) must be its rows' sums at the program's operand
    precision, to `WEIGHT_REL_TOL` and `VALUE_ABS_TOL`.  The same sums
    at 8 bits (3 of mantissa, the precision below bf16) and unrounded
    are returned beside them.
(c) At the root and at the widest node split on each of up to
    `MAX_NAN_NODES` columns whose NaN share is `MIN_NAN_SHARE` or more: a
    float64 search over every column, every bin boundary (the Dataset's
    upper bounds) and both directions of NaN, over the node's rows at
    the program's operand precision (`operand_bits`: bf16 under the
    Pallas kernel).  The model's split, rescored over the rows that reach
    it, must reach the best gain to `SPLIT_GAIN_REL_TOL`, its children's
    counts must be exact, and where the two directions of its threshold
    differ by more than that share of the best gain, its `default_left`
    must be the better one.  Searched at 8 bits, the reference's own
    choice at each node is judged at the operand grid and returned
    beside it.

The raw columns are binned once (`searchsorted` at the Dataset's bounds,
row blocks on a thread each) into `code * 2 + label` bytes, and a node's
histogram is a `bincount` of its rows' bytes: nothing is sorted per node.

A NaN sent to the wrong side, a NaN bin counted as a value, a column
typed none that held NaN, or a search that never sends NaN right (no
forward scan) routes rows elsewhere or finds a lesser split: (a), (b) or
(c) fails.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.references.categorical_first_tree import (
    LOW_MANTISSA_BITS, _first_tree_gradients, _round_to, bin_sample_rows)

# The program sums bf16-rounded operands in float32: against the sums at
# that grid a leaf reads what float32 accumulation leaves, against the
# same sums at 8 bits what an operand one precision coarser would give.
# On a v5e at the cell's 1,000,000 rows: hessian sums 2.45e-6 / 0.0104,
# outputs 1.6e-6 / 0.112 (unrounded sums: 0.0011 / 0.0145); the limits lie
# between, three decades from the first and a decade from the second
WEIGHT_REL_TOL = 1e-3
VALUE_ABS_TOL = 1e-3
# float32 node sums move a gain by less.  At tree 0 every row's gradient
# takes one of two values, so the 8-bit search picks the splits the bf16
# one does (ratio 1.0 at every checked node, as at bf16): the gain check
# cannot tell the two precisions apart, the leaf sums above do
SPLIT_GAIN_REL_TOL = 1e-3
MIN_NAN_SHARE = 0.5
MAX_NAN_NODES = 8
MISSING_NONE, MISSING_NAN = 0, 2
DEFAULT_LEFT = 2            # decision_type bit
BLOCK_ROWS = 65536


def missing_types(X, params):
    """{column: MISSING_NAN or MISSING_NONE} of every column of `X` by
    the rows the Dataset samples for its bins."""
    if not params.get("use_missing", True) or params.get(
            "zero_as_missing", False):
        raise ValueError("missing_first_tree: judges use_missing=true, "
                         "zero_as_missing=false")
    sample = bin_sample_rows(len(X), params)
    held = np.zeros(X.shape[1], bool)
    for s in range(0, len(sample), BLOCK_ROWS):
        held |= np.isnan(X[sample[s:s + BLOCK_ROWS]]).any(axis=0)
    return {f: MISSING_NAN if held[f] else MISSING_NONE
            for f in range(X.shape[1])}


def _real_bounds(bounds):
    """A Dataset's bin upper bounds without the NaN bin's and the last
    (+inf) one: `searchsorted` over them gives a value's bin."""
    b = np.asarray(bounds, np.float64)
    if len(b) and np.isnan(b[-1]):
        b = b[:-1]
    return b[:-1]


def bin_columns(X, y, cols, bounds, types):
    """`(keys [len(cols), n] uint8, nan_share [len(cols)])`: each row's
    bin in each column times 2 plus its label; a column's bins are its
    real bins and then a NaN slot, which its NaN fill where it is
    NaN-typed (typed none, a NaN is 0.0)."""
    n = len(X)
    real = [_real_bounds(bounds[f]) for f in cols]
    if max(len(b) for b in real) + 2 > 127:
        raise ValueError("missing_first_tree: more than 126 bins")
    is_nan_typed = [types[f] == MISSING_NAN for f in cols]
    label = (np.asarray(y) > 0).astype(np.uint8)
    keys = np.empty((len(cols), n), np.uint8)
    every = list(cols) == list(range(X.shape[1]))

    def block(r0):
        r1 = min(r0 + BLOCK_ROWS, n)
        xt = np.ascontiguousarray((X[r0:r1] if every
                                   else X[r0:r1][:, cols]).T)
        nan_count = np.empty(len(cols), np.int64)
        for j, x in enumerate(xt):
            nan = np.isnan(x)
            nan_count[j] = nan.sum()
            if is_nan_typed[j]:
                code = np.searchsorted(real[j], x, side="left")
                code[nan] = len(real[j]) + 1
            else:
                code = np.searchsorted(real[j], np.where(nan, 0.0, x),
                                       side="left")
            keys[j, r0:r1] = code * 2 + label[r0:r1]
        return nan_count

    workers = min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(workers) as pool:
        nan = sum(pool.map(block, range(0, n, BLOCK_ROWS)))
    return keys, nan / n


def _goes_left(x, threshold, default_left, missing):
    """A numerical node's decision on raw values `x` for a column of
    missing type `missing` (the reference's, not the model's)."""
    nan = np.isnan(x)
    if missing != MISSING_NAN:
        return np.where(nan, 0.0, x) <= threshold
    return np.where(nan, default_left, x <= threshold)


def route(tree, X, types, keep=()):
    """The leaf each row of `X` falls in, and {node: the rows that reach
    it} for the internal nodes in `keep`."""
    node = np.zeros(len(X), np.int64)     # >= 0: internal node; < 0: ~leaf
    todo = [0] if int(tree.num_leaves) > 1 else []
    reached = {}
    while todo:
        k = todo.pop()
        if int(tree.decision_type[k]) & 1:
            raise ValueError("missing_first_tree: categorical split")
        lc, rc = int(tree.left_child[k]), int(tree.right_child[k])
        todo += [c for c in (lc, rc) if c >= 0]
        rows = np.nonzero(node == k)[0]
        if k in keep:
            reached[k] = rows
        f = int(tree.split_feature[k])
        left = _goes_left(X[rows, f].astype(np.float64),
                          float(tree.threshold[k]),
                          bool(int(tree.decision_type[k]) & DEFAULT_LEFT),
                          types[f])
        node[rows] = np.where(left, lc, rc)
    return (~node).astype(np.int64), reached


def _two_values(g, h, y):
    """(g, h) of a negative and of a positive row: tree 0's gradients
    take one value a label."""
    pos = np.asarray(y) > 0
    gv = (float(g[~pos][0]) if (~pos).any() else 0.0,
          float(g[pos][0]) if pos.any() else 0.0)
    hv = (float(h[~pos][0]) if (~pos).any() else 0.0,
          float(h[pos][0]) if pos.any() else 0.0)
    return np.array(gv), np.array(hv)


def _leaf_errors(tree, leaf, nl, init, g, h, lr, l2):
    """(largest relative error of a leaf's hessian sum, largest absolute
    error of its output); a leaf the rows do not reach reads inf."""
    sum_g = np.bincount(leaf, weights=g, minlength=nl)
    sum_h = np.bincount(leaf, weights=h, minlength=nl)
    if not sum_h.all():
        return float("inf"), float("inf")
    weight_err = float(np.max(np.abs(tree.leaf_weight[:nl] - sum_h) / sum_h))
    value_err = float(np.max(np.abs(
        tree.leaf_value[:nl] - (init - lr * sum_g / (sum_h + l2)))))
    return weight_err, value_err


def node_counts(keys, rows, width):
    """[F, width, 2] counts of negative / positive rows a bin at one
    node (`rows`: its rows, None for all)."""
    out = np.zeros((len(keys), width, 2), np.float64)
    for j, k in enumerate(keys):
        c = np.bincount(k if rows is None else k[rows],
                        minlength=2 * width)
        out[j] = c[:2 * width].reshape(width, 2)
    return out


def _gain(gl, hl, G, H, l2):
    """Both children's gain less the unsplit node's."""
    gr, hr = G - gl, H - hl
    return gl * gl / (hl + l2) + gr * gr / (hr + l2) - G * G / (H + l2)


def candidates(counts, nreal, nan_typed, gv, hv, params):
    """Gains of every (column, boundary tau, NaN side) at one node from
    its counts and a precision's two (g, h) values: `[F, width - 1, 2]`
    (side 0: NaN right, 1: NaN left), -inf where a constraint refuses
    it or the column has no such candidate."""
    min_data = float(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    l2 = float(params.get("lambda_l2", 0.0))
    hg, hh, hc = counts @ gv, counts @ hv, counts.sum(axis=2)
    F, width = hc.shape
    real = np.arange(width)[None, :] < nreal[:, None]
    pg, ph, pc = (np.cumsum(np.where(real, a, 0.0), axis=1)[:, :-1]
                  for a in (hg, hh, hc))
    nan_slot = [np.where(nan_typed, a[np.arange(F), nreal], 0.0)[:, None]
                for a in (hg, hh, hc)]
    G, H, n = hg.sum(axis=1)[0], hh.sum(axis=1)[0], hc.sum(axis=1)[0]
    out = np.full((F, width - 1, 2), -np.inf)
    tau_ok = np.arange(width - 1)[None, :] < nreal[:, None]
    for side, (add_g, add_h, add_c) in enumerate(((0.0, 0.0, 0.0),
                                                  nan_slot)):
        gl, hl, cl = pg + add_g, ph + add_h, pc + add_c
        ok = (tau_ok & (cl >= min_data) & (n - cl >= min_data)
              & (hl >= min_hess) & (H - hl >= min_hess))
        if side == 1:
            ok &= nan_typed[:, None]
        with np.errstate(divide="ignore", invalid="ignore"):
            out[:, :, side] = np.where(ok, _gain(gl, hl, G, H, l2), -np.inf)
    return out


def split_check(tree, k, X, rows, keys, cols, real, nan_typed, types,
                grid, low, params):
    """(ok, facts) of the model's split at internal node `k` over the
    rows that reach it: its gain over the best of every candidate at the
    operand grid `grid` ((g, h) of a negative and of a positive row),
    its children's counts, the side its NaN takes; beside them the 8-bit
    search's choice (`low`) judged at the grid."""
    nreal = np.array([len(real[c]) + 1 for c in cols])
    counts = node_counts(keys, None if len(rows) == len(X) else rows,
                         int(nreal.max()) + 1)
    cand = candidates(counts, nreal, nan_typed, *grid, params)
    best = float(cand.max())
    f = int(tree.split_feature[k])
    j = cols.index(f)
    dl = bool(int(tree.decision_type[k]) & DEFAULT_LEFT)
    thr = float(tree.threshold[k])
    left = _goes_left(X[rows, f].astype(np.float64), thr, dl, types[f])
    positive = (keys[j, rows] & 1).astype(bool)
    at_left = np.array([np.count_nonzero(left & ~positive),
                        np.count_nonzero(left & positive)], np.float64)
    at_node = counts[0].sum(axis=0)
    gain_model = float(_gain(at_left @ grid[0], at_left @ grid[1],
                             at_node @ grid[0], at_node @ grid[1],
                             float(params.get("lambda_l2", 0.0))))
    lc, rc = int(tree.left_child[k]), int(tree.right_child[k])
    model_left, model_right = (
        int(tree.internal_count[c] if c >= 0 else tree.leaf_count[~c])
        for c in (lc, rc))
    counts_equal = (int(at_left.sum()) == model_left
                    and len(rows) - int(at_left.sum()) == model_right)
    # the model's boundary, NaN right (0) against NaN left (1)
    tau = min(int(np.searchsorted(real[f], thr)), cand.shape[1] - 1)
    both = cand[j, tau]
    nan_rows = int(counts[j, nreal[j]].sum()) if nan_typed[j] else 0
    gap = (float(both[0] - both[1]) / best
           if np.isfinite(both).all() else None)
    direction_ok = (not nan_rows or gap is None
                    or abs(gap) <= SPLIT_GAIN_REL_TOL or dl == (gap < 0))
    at_8bit = candidates(counts, nreal, nan_typed, *low, params)
    jj, tt, ss = np.unravel_index(int(np.argmax(at_8bit)), at_8bit.shape)
    ratio = gain_model / best
    ok = bool(ratio >= 1.0 - SPLIT_GAIN_REL_TOL and counts_equal
              and direction_ok)
    return ok, {"node": k, "column": f, "rows": len(rows),
                "nan_rows": nan_rows, "default_left": dl,
                "gain_ratio": ratio, "direction_gap": gap,
                "direction_ok": bool(direction_ok),
                "counts_equal": bool(counts_equal),
                "gain_ratio_8bit": float(cand[jj, tt, ss]) / best,
                "choice_8bit": [int(cols[jj]), int(tt), bool(ss)]}


def check(tree, X, y, columns, params, operand_bits=None):
    """`tree`: the program's first tree; `X`: the raw training matrix
    [n, F] float32 (the columns in the Dataset's order), `y` its labels;
    `columns`: {column: (the Dataset's bin upper bounds, its missing
    type)} of the columns the Dataset uses; `params`: the
    configuration's; `operand_bits`: the mantissa bits the program
    rounds each row's gradient and hessian to before it sums them (7:
    bf16; None: not rounded).  Returns (checks, facts): five named
    checks."""
    nl = int(tree.num_leaves)
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    init, g, h = _first_tree_gradients(y)
    types = missing_types(X, params)
    types_equal = all(types[f] == int(mt) for f, (_, mt) in columns.items())
    cols = sorted(columns)
    real = {f: _real_bounds(columns[f][0]) for f in cols}
    keys, nan_share = bin_columns(X, y, cols,
                                  {f: b for f, (b, _) in columns.items()},
                                  types)
    nan_typed = np.array([types[f] == MISSING_NAN for f in cols])

    share = dict(zip(cols, nan_share))
    widest = {}      # column of NaN share >= MIN_NAN_SHARE -> its widest node
    for k in range(nl - 1):
        f = int(tree.split_feature[k])
        if share.get(f, 0.0) >= MIN_NAN_SHARE and (
                f not in widest or int(tree.internal_count[k])
                > int(tree.internal_count[widest[f]])):
            widest[f] = k
    nan_nodes = sorted(widest.values(),
                       key=lambda k: -int(tree.internal_count[k]))
    nan_nodes = nan_nodes[:MAX_NAN_NODES]
    leaf, reached = route(tree, X, types, keep={0, *nan_nodes})
    count = np.bincount(leaf, minlength=nl)
    counts_equal = bool(np.array_equal(count, tree.leaf_count[:nl]))

    grid = ((g, h) if operand_bits is None else
            (_round_to(g, operand_bits), _round_to(h, operand_bits)))
    low = (_round_to(g, LOW_MANTISSA_BITS), _round_to(h, LOW_MANTISSA_BITS))
    weight_err, value_err = _leaf_errors(tree, leaf, nl, init, *grid, lr, l2)
    low_err = _leaf_errors(tree, leaf, nl, init, *low, lr, l2)
    raw_err = _leaf_errors(tree, leaf, nl, init, g, h, lr, l2)

    def at_node(k):
        return split_check(tree, k, X, reached[k], keys, cols, real,
                           nan_typed, types, _two_values(*grid, y),
                           _two_values(*low, y), params)
    root_ok, root = at_node(0) if nl > 1 else (False, {})
    nan_checked = [at_node(k) for k in nan_nodes]
    checks = {
        "missing_types_are_the_references": bool(types_equal),
        "first_tree_routes_its_rows": counts_equal,
        "first_tree_sums_its_rows": (weight_err <= WEIGHT_REL_TOL
                                     and value_err <= VALUE_ABS_TOL),
        "root_split_is_the_references": root_ok,
        "nan_splits_are_the_references": bool(nan_checked) and all(
            ok for ok, _ in nan_checked),
    }
    dt = np.asarray(tree.decision_type[:max(nl - 1, 0)], np.int64)
    nan_split = ((dt >> 2) & 3) == MISSING_NAN
    return checks, {
        "first_tree_leaves_checked": nl,
        "first_tree_counts_equal": counts_equal,
        "first_tree_weight_rel_err": weight_err,
        "first_tree_value_abs_err": value_err,
        "first_tree_weight_rel_err_8bit": low_err[0],
        "first_tree_value_abs_err_8bit": low_err[1],
        "first_tree_weight_rel_err_unrounded": raw_err[0],
        "first_tree_value_abs_err_unrounded": raw_err[1],
        "first_tree_nan_left_splits": int(np.count_nonzero(
            nan_split & ((dt & DEFAULT_LEFT) != 0))),
        "first_tree_nan_right_splits": int(np.count_nonzero(
            nan_split & ((dt & DEFAULT_LEFT) == 0))),
        "missing_types_equal": bool(types_equal),
        "nan_typed_columns": int(nan_typed.sum()),
        "nan_share": float(np.mean(nan_share)),
        **{f"root_{key}": v for key, v in root.items()},
        "nan_nodes_checked": [facts for _, facts in nan_checked]}
