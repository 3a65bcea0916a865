"""Plain reference for the histogram and split layers of a binary
objective: tree 0 against the rows it was grown on, in NumPy on the host.

Every row has the same score when tree 0 is grown (boost_from_average),
so which rows a leaf holds fixes its count, its hessian sum and its
output.  The kernels round each row's gradient and hessian to bf16, which
moves a sum by 0.4% at most; the tolerances below are 1%.  (Copy of
`chip_smoke.py _require_first_tree_sums_its_rows`: on the chip's first
run a leaf at the end of each parent-minus-sibling chain held a sum near
zero and an output in the thousands, and held-out AUC did not show it.)
"""

import numpy as np

WEIGHT_REL_TOL = 0.01
VALUE_ABS_TOL = 0.01


def check(tree, leaf_of_row, y, learning_rate):
    """`tree`: the program's first tree (leaf_count, leaf_weight,
    leaf_value, num_leaves); `leaf_of_row`: the leaf each training row
    falls in, from the host predictor.  Returns (ok, facts)."""
    nl = int(tree.num_leaves)
    pavg = float(np.mean(y > 0))
    init = float(np.log(pavg / (1.0 - pavg)))
    lab = np.where(y > 0, 1.0, -1.0)
    resp = -lab / (1.0 + np.exp(lab * init))
    count = np.bincount(leaf_of_row, minlength=nl)
    sum_g = np.bincount(leaf_of_row, weights=resp, minlength=nl)
    sum_h = np.bincount(leaf_of_row,
                        weights=np.abs(resp) * (1.0 - np.abs(resp)),
                        minlength=nl)
    counts_equal = bool(np.array_equal(count, tree.leaf_count[:nl]))
    weight_err = float(np.max(np.abs(tree.leaf_weight[:nl] - sum_h) / sum_h))
    value_err = float(np.max(np.abs(
        tree.leaf_value[:nl] - (init - learning_rate * sum_g / sum_h))))
    ok = (counts_equal and weight_err <= WEIGHT_REL_TOL
          and value_err <= VALUE_ABS_TOL)
    return ok, {"first_tree_leaves_checked": nl,
                "first_tree_counts_equal": counts_equal,
                "first_tree_weight_rel_err": weight_err,
                "first_tree_value_abs_err": value_err}
