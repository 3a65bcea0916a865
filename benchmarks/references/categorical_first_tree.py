"""Plain reference for a binary objective's tree 0 grown on integer-coded
categorical columns beside numeric ones: float64 NumPy on the RAW
columns, independent of the bins' device layout, the engine and the
program's split code.  Written from upstream's described algorithm
(LightGBM docs/Features.rst "Optimal Split for Categorical Features",
docs/Advanced-Topics.rst "Categorical Feature Support", the parameters'
documented meaning), not from this repo's `ops/split.py` or
`io/binning.py`.

Every row has the same score when tree 0 is grown (boost_from_average),
so its gradient and hessian follow from its label alone, and every row's
hessian is the same: counts derived from hessians are exact counts.
Four checks:

(m) `category_bins` rebuilds each categorical column's category -> bin
    map from the raw column: the rows the Dataset samples for its bins
    (`bin_construct_sample_cnt` of them, drawn by `data_random_seed`: the
    one thing taken from the program's convention), each category
    counted, the most frequent first, kept until they cover 99% of the
    sampled rows and fill `max_bin - 1` bins; the rest share the "other"
    bin 0 with NaN and negative values.  The Dataset's own map must be
    this one, and (c) scans by it.
(a) `route` sends every training row through the tree by the model as
    saved: a numeric node sends `x <= threshold` left; a categorical node
    sends a row left iff its integer category is in the node's
    `cat_threshold` bitset (raw categories, not the program's bins);
    unseen, negative and NaN categories go right.  Every leaf's count must
    equal the model's `leaf_count` exactly.
(b) every leaf's weight and value are the sums over the rows it holds, to
    `WEIGHT_REL_TOL` and `VALUE_ABS_TOL`; a leaf made by a sorted-subset
    categorical split has its output under `lambda_l2 + cat_l2`, one made
    by any other split under `lambda_l2`.  The same sums from gradients
    rounded to the nearest precision below bf16 (an 8-bit float, 3 bits
    of mantissa) are returned beside them: over the limits.
(c) the root and, for every categorical column tree 0 splits on, that
    column's node holding the most rows (at least `MIN_NODE_SHARE` of
    them), each recomputed over the rows `route` takes to it, from the
    raw columns, under the configuration's constraints: numeric columns
    at the Dataset's own bin upper bounds, categorical ones by upstream's
    rule — category -> bin by (m)'s map, the "other" bin 0 never going
    left; one-vs-rest where `num_bin <= max_cat_to_onehot`; otherwise the
    bins with `count >= cat_smooth`, sorted by `g / (h + cat_smooth)`,
    prefixes of at most `min(max_cat_threshold, (used + 1) // 2)` of them
    from either end, a candidate scored only once `min_data_per_group`
    rows have joined since the last scored one, `lambda_l2 + cat_l2` in
    its gain.  Every search sums in float64 the gradients and hessians at
    the program's operand precision (`operand_bits`: bf16 under the
    Pallas kernel, which rounds each row's before any consumer sums
    them).  That rounding is no small noise here: tree 0's gradients
    take two values, rounded apart, and at a node whose categories lean
    weakly it can move the best set by percents of the unrounded gain
    (the ratio on unrounded gradients is returned as a fact).  The
    model's split, rescored, must reach the best gain over all columns
    to `SPLIT_GAIN_REL_TOL`, its children's counts must be exact, and a
    categorical split's left category set must be its column's best,
    short of a tie (`SET_TIE_REL`).  Searched with gradients at 8 bits,
    the reference's own choice at each such node is returned beside it.

A category -> bin map off by one, a bitset word in the wrong place or the
"other" bin sent left routes rows elsewhere: (a) to (c) fail.  A scan
that picks a valid but lesser subset (no `cat_smooth`, another grouping,
a shorter prefix, coarser sort keys) fails (c) at the categorical node.
"""

import numpy as np

WEIGHT_REL_TOL = 0.01       # binary_first_tree's
VALUE_ABS_TOL = 0.003       # sound 0.000366, 8-bit 0.010041 (PERF.md section 6)
SPLIT_GAIN_REL_TOL = 1e-3   # float32 node sums move a gain by less
EXACT_TIE_REL = 1e-12       # two candidates whose float64 gains agree this far tie
SET_TIE_REL = 1e-4          # ... and two sets whose gains agree this far, float32 node sums cannot order
MIN_NODE_SHARE = 0.01       # (c) takes categorical nodes of at least this share of the rows
LOW_MANTISSA_BITS = 3       # an 8-bit float (e4m3), the precision below bf16
CATEGORICAL = 1             # decision_type bit
MISSING_NAN = 2
COVERAGE = 0.99             # the share of sampled rows the kept categories cover


def _bits(words):
    """The set of integers whose bit is set in uint32 `words`."""
    out = set()
    for w, word in enumerate(words):
        word = int(word) & 0xFFFFFFFF
        out.update(32 * w + b for b in range(32) if (word >> b) & 1)
    return out


def cat_set(tree, node):
    """The raw categories a categorical node sends left (its
    `cat_threshold` bitset as the model saves it)."""
    k = int(tree.threshold[node])
    lo, hi = tree.cat_boundaries[k], tree.cat_boundaries[k + 1]
    return _bits(tree.cat_threshold[lo:hi])


def _cat_left(x, cats):
    """Which of float values `x` are integer categories in `cats`: NaN,
    negative and unseen ones go right."""
    ok = np.isfinite(x) & (x >= 0)
    iv = np.where(ok, x, -1).astype(np.int64)
    return ok & np.isin(iv, np.fromiter(cats, np.int64, len(cats)))


def _num_left(x, threshold, default_left, missing):
    """The numerical decision (tree.h NumericalDecision), no zero rule:
    the reference's numeric columns have no missing type but NaN."""
    nan = np.isnan(x)
    if missing != MISSING_NAN:
        x = np.where(nan, 0.0, x)
        nan = np.zeros_like(nan)
    return np.where(nan, default_left, x <= threshold)


def _goes_left(tree, k, x):
    """Node `k`'s decision on the raw values `x` of its column."""
    dt = int(tree.decision_type[k])
    if dt & CATEGORICAL:
        return _cat_left(x, cat_set(tree, k))
    return _num_left(x, float(tree.threshold[k]), bool(dt & 2),
                     (dt >> 2) & 3)


def route(tree, X, keep=()):
    """The leaf each row of `X` (float64, raw columns) falls in, and
    {node: the rows that reach it} for the internal nodes in `keep`."""
    node = np.zeros(len(X), np.int64)     # >= 0: internal node; < 0: ~leaf
    todo = [0] if int(tree.num_leaves) > 1 else []
    reached = {}
    while todo:
        k = todo.pop()
        lc, rc = int(tree.left_child[k]), int(tree.right_child[k])
        todo += [c for c in (lc, rc) if c >= 0]
        rows = np.nonzero(node == k)[0]
        if k in keep:
            reached[k] = rows
        left = _goes_left(tree, k, X[rows, int(tree.split_feature[k])])
        node[rows] = np.where(left, lc, rc)
    return (~node).astype(np.int64), reached


def bin_sample_rows(n, params):
    """The rows a Dataset of `n` rows finds its bins on: all of them, or
    `bin_construct_sample_cnt` drawn without replacement by
    `data_random_seed`."""
    cnt = int(params.get("bin_construct_sample_cnt", 200000))
    if n <= cnt:
        return np.arange(n)
    rng = np.random.RandomState(int(params.get("data_random_seed", 1)))
    return np.sort(rng.choice(n, cnt, replace=False))


def category_bins(x, max_bin, min_data_in_bin=3):
    """One categorical column's bin map from its sampled raw values `x`:
    {"num_bin", "cat_to_bin", "bin_to_cat"}.  Categories by count, the
    most frequent first (a tie: the smaller category first), kept while
    they cover under `COVERAGE` of the non-missing rows or fill under
    `max_bin - 1` bins (no more bins than the column has values), until
    one seen fewer than `min_data_in_bin` times past the second; bin 0 is
    the "other" bin: NaN, negative and unkept categories."""
    x = np.asarray(x, np.float64)
    ok = np.isfinite(x) & (x >= 0)
    cats, counts = np.unique(x[ok].astype(np.int64), return_counts=True)
    order = np.lexsort((cats, -counts))
    cats, counts = cats[order], counts[order]
    cover = int(round(int(ok.sum()) * COVERAGE))
    bins = min(len(cats) + int(not ok.all()), max_bin)
    kept, used = [], 0
    for i, (c, k) in enumerate(zip(cats.tolist(), counts.tolist())):
        if used >= cover and len(kept) + 1 >= bins:
            break
        if k < min_data_in_bin and i > 1:
            break
        kept.append(c)
        used += k
    return {"num_bin": len(kept) + 1,
            "cat_to_bin": {-1: 0, **{c: b + 1 for b, c in enumerate(kept)}},
            "bin_to_cat": [-1] + kept}


def _round_to(x, mantissa_bits):
    """float64 values rounded to `mantissa_bits` bits after the leading
    one (to nearest)."""
    m, e = np.frexp(np.asarray(x, np.float64))
    scale = 2.0 ** (mantissa_bits + 1)
    return np.ldexp(np.round(m * scale) / scale, e)


def _first_tree_gradients(y):
    pavg = float(np.mean(y > 0))
    init = float(np.log(pavg / (1.0 - pavg)))
    lab = np.where(y > 0, 1.0, -1.0)
    g = -lab / (1.0 + np.exp(lab * init))
    return init, g, np.abs(g) * (1.0 - np.abs(g))


def _split_l2(tree, node, columns, params):
    """The L2 a split's children's outputs carry: `lambda_l2 + cat_l2`
    under a sorted-subset categorical split, `lambda_l2` otherwise."""
    l2 = float(params.get("lambda_l2", 0.0))
    f = int(tree.split_feature[node])
    kind, spec = columns[f]
    if (int(tree.decision_type[node]) & CATEGORICAL and kind == "cat"
            and spec["num_bin"] > int(params.get("max_cat_to_onehot", 4))):
        return l2 + float(params.get("cat_l2", 10.0))
    return l2


def _leaf_errors(tree, leaf, nl, init, g, h, lr, l2_of_leaf):
    sum_g = np.bincount(leaf, weights=g, minlength=nl)
    sum_h = np.bincount(leaf, weights=h, minlength=nl)
    weight_err = float(np.max(np.abs(tree.leaf_weight[:nl] - sum_h) / sum_h))
    value_err = float(np.max(np.abs(
        tree.leaf_value[:nl] - (init - lr * sum_g / (sum_h + l2_of_leaf)))))
    return weight_err, value_err


def _gain(gl, hl, G, H, l2_children, l2_parent):
    """Shifted gain: both children's less the unsplit leaf's."""
    gr, hr = G - gl, H - hl
    return (gl * gl / (hl + l2_children) + gr * gr / (hr + l2_children)
            - G * G / (H + l2_parent))


def _bin_sums(codes, g, h, num_bin):
    return (np.bincount(codes, weights=g, minlength=num_bin),
            np.bincount(codes, weights=h, minlength=num_bin),
            np.bincount(codes, minlength=num_bin).astype(np.float64))


def _cat_codes(x, cat_to_bin):
    """Raw categories -> the Dataset's bins; what it does not keep, and
    NaN or negative values, to the "other" bin 0."""
    cats = np.array(sorted(c for c in cat_to_bin if c >= 0), np.int64)
    bins = np.array([cat_to_bin[c] for c in cats], np.int64)
    ok = np.isfinite(x) & (x >= 0)
    iv = np.where(ok, x, -1).astype(np.int64)
    pos = np.clip(np.searchsorted(cats, iv), 0, len(cats) - 1)
    return np.where(ok & (cats[pos] == iv), bins[pos], 0)


def _candidates_categorical(x, spec, g, h, G, H, n, params):
    """Every scored candidate of one categorical column: (gain, left
    count, left raw-category set)."""
    min_data = float(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    l2 = float(params.get("lambda_l2", 0.0))
    nb = spec["num_bin"]
    hg, hh, hc = _bin_sums(_cat_codes(x, spec["cat_to_bin"]), g, h, nb)
    bin_cat = spec["bin_to_cat"]
    out = []
    if nb <= int(params.get("max_cat_to_onehot", 4)):
        for b in range(1, nb):          # one category against the rest
            if (hc[b] >= min_data and hh[b] >= min_hess
                    and n - hc[b] >= min_data and H - hh[b] >= min_hess):
                out.append((_gain(hg[b], hh[b], G, H, l2, l2), hc[b],
                            {bin_cat[b]}))
        return out
    smooth = float(params.get("cat_smooth", 10.0))
    l2c = l2 + float(params.get("cat_l2", 10.0))
    per_group = float(params.get("min_data_per_group", 100))
    used = [b for b in range(1, nb) if hc[b] >= smooth]
    used.sort(key=lambda b: hg[b] / (hh[b] + smooth))    # stable
    limit = min(int(params.get("max_cat_threshold", 32)),
                (len(used) + 1) // 2)
    for order in (used, used[::-1]):
        gl = hl = cl = group = 0.0
        for i, b in enumerate(order[:limit]):
            gl, hl, cl, group = gl + hg[b], hl + hh[b], cl + hc[b], group + hc[b]
            if cl < min_data or hl < min_hess:
                continue
            if n - cl < min_data or n - cl < per_group or H - hl < min_hess:
                break
            if group < per_group:
                continue
            group = 0.0
            out.append((_gain(gl, hl, G, H, l2c, l2), cl,
                        {bin_cat[c] for c in order[:i + 1]}))
    return out


def _candidates_numeric(x, bounds, g, h, G, H, n, params):
    """Every candidate threshold of one numeric column: (gain, left
    count, threshold)."""
    min_data = float(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    l2 = float(params.get("lambda_l2", 0.0))
    nb = len(bounds)
    hg, hh, hc = _bin_sums(np.searchsorted(bounds[:-1], x, side="left"),
                           g, h, nb)
    gl, hl, cl = (np.cumsum(a)[:-1] for a in (hg, hh, hc))
    ok = ((cl >= min_data) & (n - cl >= min_data)
          & (hl >= min_hess) & (H - hl >= min_hess))
    with np.errstate(divide="ignore", invalid="ignore"):   # refused ones
        gain = _gain(gl, hl, G, H, l2, l2)
    return [(gain[j], cl[j], float(bounds[j])) for j in np.nonzero(ok)[0]]


def candidates(X, f, columns, g, h, params):
    """Every scored candidate of column `f` over the rows of `X` (the rows
    that reach one node; `g` and `h` theirs), the best first."""
    kind, spec = columns[f]
    G, H, n = float(g.sum()), float(h.sum()), len(g)
    found = (_candidates_categorical if kind == "cat" else
             _candidates_numeric)(X[:, f], spec, g, h, G, H, n, params)
    return sorted(found, key=lambda t: -t[0])


def _gain_of(left, l2_children, g, h, params):
    """The float64 gain of sending the rows `left` of one node left."""
    return _gain(float(g[left].sum()), float(h[left].sum()), float(g.sum()),
                 float(h.sum()), l2_children,
                 float(params.get("lambda_l2", 0.0)))


def _best_over_columns(X, columns, g, h, params):
    """The best candidate's gain over every column at one node."""
    found = [candidates(X, c, columns, g, h, params) for c in columns]
    return max(c[0][0] for c in found if c)


def split_check(tree, k, X, g, h, grid, low, columns, params):
    """(ok, facts) of the model's split at internal node `k` over the
    rows that reach it (`X`, `g`, `h`: theirs; `grid`, `low`: their (g, h)
    at the program's operand precision and at 8 bits).  At `grid`, the
    model's split reaches the best gain over every column to
    `SPLIT_GAIN_REL_TOL`, and a categorical split's left set is its
    column's best short of a tie (`gap`: how far the next other set's
    gain lies under the best's); its children's counts are the rows it
    sends each way.  Beside them: the same ratio on the unrounded
    gradients, and the 8-bit search's own choice on the model's column
    judged at `grid`."""
    f = int(tree.split_feature[k])
    l2c = _split_l2(tree, k, columns, params)
    left = _goes_left(tree, k, X[:, f])
    best = _best_over_columns(X, columns, *grid, params)
    ratio = _gain_of(left, l2c, *grid, params) / best
    lc, rc = int(tree.left_child[k]), int(tree.right_child[k])
    model_left, model_right = (
        int(tree.internal_count[c] if c >= 0 else tree.leaf_count[~c])
        for c in (lc, rc))
    n_left = int(left.sum())
    counts_equal = n_left == model_left and len(g) - n_left == model_right
    facts = {"node": k, "column": f, "rows": len(g), "gain_ratio": ratio,
             "gain_ratio_unrounded": _gain_of(left, l2c, g, h, params)
             / _best_over_columns(X, columns, g, h, params),
             "counts_equal": bool(counts_equal),
             "sum_h_rel_err": abs(float(tree.internal_weight[k])
                                  - float(grid[1].sum()))
             / float(grid[1].sum())}
    set_equal = True
    if int(tree.decision_type[k]) & CATEGORICAL:
        cats = cat_set(tree, k)
        on_grid = candidates(X, f, columns, *grid, params)
        top = on_grid[0] if on_grid else (0.0, 0, None)
        set_equal = bool(on_grid) and (
            cats == top[2] or abs(_gain_of(left, l2c, *grid, params) - top[0])
            <= SET_TIE_REL * abs(top[0]))
        other = next((c[0] for c in on_grid if c[2] != top[2]), None)
        at_8bit = candidates(X, f, columns, *low, params)
        left_8bit = _cat_left(X[:, f], at_8bit[0][2]) if at_8bit else left
        facts.update(
            left_set_size=len(cats), left_set_equal=bool(set_equal),
            gap=None if other is None else (top[0] - other) / abs(top[0]),
            gain_ratio_8bit=_gain_of(left_8bit, l2c, *grid, params) / best,
            left_set_equal_8bit=bool(at_8bit) and at_8bit[0][2] == top[2])
    ok = bool(ratio >= 1.0 - SPLIT_GAIN_REL_TOL and counts_equal
              and set_equal)
    return ok, facts


def check(tree, X, y, columns, params, operand_bits=None):
    """`tree`: the program's first tree; `X`: the raw training matrix
    [n, F] (float, the columns in the Dataset's order); `columns`:
    {column: ("num", bin upper bounds) or ("cat", the Dataset's bin map
    {"num_bin", "cat_to_bin", "bin_to_cat"}, or None)} of the columns the
    Dataset uses; `params`: the configuration's; `operand_bits`: the
    mantissa bits the program rounds each row's gradient and hessian to
    before it sums them (7: bf16; None: not rounded).  Returns (checks,
    facts): five named checks.  (c) takes the root and, for every
    categorical column tree 0 splits on, the node of that column that
    holds the most rows."""
    X = np.asarray(X, np.float64)
    nl = int(tree.num_leaves)
    lr = float(params["learning_rate"])
    init, g, h = _first_tree_gradients(y)

    sample = bin_sample_rows(len(X), params)
    ref_columns, maps_equal = {}, True
    for f, (kind, spec) in columns.items():
        if kind == "cat":
            mine = category_bins(X[sample, f], int(params.get("max_bin", 255)),
                                 int(params.get("min_data_in_bin", 3)))
            maps_equal &= spec is not None and (
                list(spec["bin_to_cat"]) == mine["bin_to_cat"]
                and int(spec["num_bin"]) == mine["num_bin"])
            spec = mine
        ref_columns[f] = (kind, spec)

    widest = {}      # categorical column -> its node with the most rows
    for k in range(nl - 1):
        f = int(tree.split_feature[k])
        if int(tree.decision_type[k]) & CATEGORICAL and int(
                tree.internal_count[k]) >= MIN_NODE_SHARE * len(X) and (
                f not in widest or int(tree.internal_count[k])
                > int(tree.internal_count[widest[f]])):
            widest[f] = k
    leaf, reached = route(tree, X, keep={0, *widest.values()})
    count = np.bincount(leaf, minlength=nl)
    counts_equal = bool(np.array_equal(count, tree.leaf_count[:nl]))
    l2_of_leaf = np.array([_split_l2(tree, int(tree.leaf_parent[i]),
                                     ref_columns, params)
                           for i in range(nl)])
    weight_err, value_err = _leaf_errors(tree, leaf, nl, init, g, h, lr,
                                         l2_of_leaf)
    low = (_round_to(g, LOW_MANTISSA_BITS), _round_to(h, LOW_MANTISSA_BITS))
    low_err = _leaf_errors(tree, leaf, nl, init, *low, lr, l2_of_leaf)
    grid = ((g, h) if operand_bits is None else
            (_round_to(g, operand_bits), _round_to(h, operand_bits)))

    def at_node(k):
        rows = reached.get(k, [])
        at = (lambda a: a) if len(rows) == len(X) else (lambda a: a[rows])
        return split_check(tree, k, at(X), at(g), at(h), tuple(map(at, grid)),
                           tuple(map(at, low)), ref_columns, params)
    root_ok, root = at_node(0) if nl > 1 else (False, {})
    cat_nodes = [at_node(widest[f]) for f in sorted(widest)]
    checks = {
        "category_bins_are_the_references": bool(maps_equal),
        "first_tree_routes_its_rows": counts_equal,
        "first_tree_sums_its_rows": (weight_err <= WEIGHT_REL_TOL
                                     and value_err <= VALUE_ABS_TOL),
        "root_split_is_the_references": root_ok,
        "categorical_splits_are_the_references": bool(cat_nodes) and all(
            ok for ok, _ in cat_nodes),
    }
    return checks, {
        "first_tree_leaves_checked": nl,
        "first_tree_categorical_splits": sum(
            int(tree.decision_type[k]) & CATEGORICAL for k in range(nl - 1)),
        "first_tree_counts_equal": counts_equal,
        "first_tree_weight_rel_err": weight_err,
        "first_tree_value_abs_err": value_err,
        "first_tree_weight_rel_err_8bit": low_err[0],
        "first_tree_value_abs_err_8bit": low_err[1],
        "category_bins_equal": bool(maps_equal),
        **{f"root_{key}": v for key, v in root.items()},
        "cat_nodes_checked": [facts for _, facts in cat_nodes]}
