"""The categorical sorted-subset scan (`ops/split.py _cat_best_split`)
against a float32 NumPy loop written from upstream's
FindBestThresholdCategoricalInner (ref: feature_histogram.cpp:144-330),
bit for bit: gain, left sums, count, direction and prefix length.

The oracle walks the reference's loops one candidate at a time, with its
`continue` / `break` gates and its add order, in float32 as the program
computes; the program sorts once with the sums as payloads, reads the
backward view by a flip and shifts, and runs the prefix steps as
straight-line code (a loop past `_CAT_UNROLL` steps).  Counts are exact
(hessians on a quarter grid, so `cnt_factor` is 4), so the comparison
holds on any backend's rounding of `hess * cnt_factor + 0.5`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.split import (K_EPSILON, SplitParams, _CAT_UNROLL,
                                    _cat_best_split, find_best_split)

f32 = np.float32
ROWS = 400_000          # a leaf's rows; counts on a 0.25 hessian grid


def _column(rng, nb, used, B, ties=False, small=20):
    """One categorical column of one leaf: bins 1 .. nb - 1, `used` of them
    holding at least cat_smooth (10) rows, the rest 0-9 rows; bin 0 the
    remainder of ROWS.  With `ties`, the kept categories share a few
    (gradient, count) pairs, so their ratios tie exactly."""
    cnt = np.zeros(B, np.int64)
    keep = 1 + rng.choice(nb - 1, used, replace=False)
    rare = np.setdiff1d(np.arange(1, nb), keep)
    cnt[rare] = rng.randint(0, 10, rare.size)
    cnt[keep] = rng.randint(small, 2000, used)
    grad = (rng.randn(B) * np.sqrt(cnt + 1) * 0.3).astype(f32)
    if ties and used:
        pairs = rng.randint(0, 3, used)
        cnt[keep] = np.array([40, 250, 1200])[pairs]
        grad[keep] = np.array([-3.5, 1.25, 6.0], f32)[pairs]
    cnt[0] = ROWS - cnt[1:].sum()
    grad[0] = 0.5
    return grad, (cnt * 0.25).astype(f32)


def _gain1(g, h, l2):
    """GetLeafGain with l1 = 0, no max_delta_step, no path smoothing."""
    return f32(f32(g * g) / f32(h + f32(l2)))


def _oracle(grad, hess, nb, sum_g, sum_h, n, p, rand_u=None):
    """One column of one leaf, as the reference scans it, in float32.
    `sum_h` carries the +2*kEpsilon.  Returns gain, left (g, h, c),
    onehot bin, forward?, prefix index, used_bin, sorted bins."""
    eps = f32(K_EPSILON)
    cnt_factor = f32(f32(n) / sum_h)
    cnt = np.floor(hess * cnt_factor + f32(0.5)).astype(np.int64)
    mgs = _gain1(sum_g, sum_h, p.lambda_l2)
    best = dict(gain=f32(-np.inf), lg=None, lh=None, lc=None, oh=0,
                fwd=True, i=0)
    if nb <= p.max_cat_to_onehot:
        rand_t = None
        if rand_u is not None:
            span = f32(max(nb - 1, 1))
            rand_t = 1 + min(max(int(f32(rand_u[0] * span)), 0),
                             max(nb - 2, 0))
        for t in range(1, nb):
            g, h, c = grad[t], hess[t], int(cnt[t])
            lh = f32(h + eps)
            rh = f32(f32(sum_h - h) - eps)
            if (c < p.min_data_in_leaf or lh < p.min_sum_hessian_in_leaf
                    or n - c < p.min_data_in_leaf
                    or rh < p.min_sum_hessian_in_leaf):
                continue
            if rand_t is not None and t != rand_t:
                continue
            gain = f32(_gain1(g, lh, p.lambda_l2)
                       + _gain1(f32(sum_g - g), rh, p.lambda_l2))
            if gain > mgs and gain > best["gain"]:
                best.update(gain=gain, lg=g, lh=lh, lc=c, oh=t)
        return best, None, None
    valid = [t for t in range(1, nb) if cnt[t] >= p.cat_smooth]
    ratio = {t: f32(grad[t] / f32(hess[t] + f32(p.cat_smooth)))
             for t in valid}
    order = sorted(valid, key=lambda t: ratio[t])      # stable
    used = len(order)
    max_num_cat = min(p.max_cat_threshold, (used + 1) // 2)
    l2 = p.lambda_l2 + p.cat_l2
    rand_i = None
    if rand_u is not None:
        max_thr = f32(max(min(max_num_cat, used) - 1, 0))
        rand_i = min(max(int(f32(rand_u[1] * max(max_thr, f32(1.0)))), 0),
                     max(int(max_thr) - 1, 0))
    for fwd in (True, False):
        cur, lg, lh, lc = 0, f32(0.0), eps, 0
        for i in range(min(used, max_num_cat)):
            t = order[i] if fwd else order[used - 1 - i]
            lg = f32(lg + grad[t])
            lh = f32(lh + hess[t])
            lc += int(cnt[t])
            cur += int(cnt[t])
            if lc < p.min_data_in_leaf or lh < p.min_sum_hessian_in_leaf:
                continue
            rc = n - lc
            if rc < p.min_data_in_leaf or rc < p.min_data_per_group:
                break
            rh = f32(sum_h - lh)
            if rh < p.min_sum_hessian_in_leaf:
                break
            if cur < p.min_data_per_group:
                continue
            cur = 0
            if rand_i is not None and i != rand_i:
                continue
            gain = f32(_gain1(lg, lh, l2) + _gain1(f32(sum_g - lg), rh, l2))
            if gain <= mgs:
                continue
            if gain > best["gain"]:
                best.update(gain=gain, lg=lg, lh=lh, lc=lc, fwd=fwd, i=i)
    return best, used, order


def _leaves(case, leaves, B, seed):
    """[leaves, C, B] gradients and hessians, [leaves] sums and counts,
    [C] num_bin, for the columns of `case`: (num_bin, used_bin) pairs."""
    rng = np.random.RandomState(seed)
    C = len(case["cols"])
    grad = np.zeros((leaves, C, B), f32)
    hess = np.zeros((leaves, C, B), f32)
    for l in range(leaves):
        for c, (nb, used) in enumerate(case["cols"]):
            grad[l, c], hess[l, c] = _column(
                rng, nb, used, B, ties=case.get("ties", False),
                small=case.get("small", 20))
    sum_g = (rng.randn(leaves) * 40).astype(f32)
    sum_h = np.full(leaves, ROWS * 0.25, f32)
    nb = np.array([nb for nb, _ in case["cols"]], np.int32)
    rand_u = (rng.rand(leaves, C, 2).astype(f32) if case.get("extra")
              else None)
    return grad, hess, nb, sum_g, sum_h, rand_u


CASES = {
    # used_bin 0, 1, 31, 32, 33 and 188 at the cell's 189 bins
    "used_bins": dict(cols=[(189, 0), (189, 1), (189, 31), (189, 32),
                            (189, 33), (189, 188)]),
    "ties": dict(cols=[(189, 60), (64, 40), (33, 20), (189, 150)],
                 ties=True),
    "below_cat_smooth": dict(cols=[(189, 3), (40, 2), (100, 7)], small=10),
    "group_resets": dict(cols=[(189, 120), (189, 80)],
                         params=dict(min_data_per_group=1500)),
    "group_every_step": dict(cols=[(189, 120), (64, 50)],
                             params=dict(min_data_per_group=1)),
    "extra_trees": dict(cols=[(189, 100), (64, 30), (4, 3), (13, 12)],
                        extra=True, params=dict(extra_trees=True)),
    "onehot": dict(cols=[(2, 1), (3, 2), (4, 3), (4, 0), (5, 4)]),
    "looped": dict(cols=[(189, 188), (189, 100), (189, 33)],
                   params=dict(max_cat_threshold=64)),
    "looped_odd": dict(cols=[(189, 188), (120, 90)],
                       params=dict(max_cat_threshold=_CAT_UNROLL + 5)),
    "few_steps": dict(cols=[(189, 150), (20, 15)],
                      params=dict(max_cat_threshold=8)),
}


def _params(case):
    return SplitParams(has_categorical=True, min_data_in_leaf=20,
                       **case.get("params", {}))


def _scan(p, mapped):
    """`_cat_best_split` as the leaf-wise engine calls it (a leaf a
    call) or as the wave engine does (`vmap` over leaves)."""
    def one(g, h, nb, sg, sh, n, ru):
        return _cat_best_split(g, h, n.astype(jnp.float32) / sh, nb, sg, sh,
                               n, jnp.float32(0.0),
                               (sg * sg) / (sh + p.lambda_l2), p, rand_u=ru)
    if mapped:
        return jax.jit(jax.vmap(one, in_axes=(0, 0, None, 0, 0, 0, 0)))
    return jax.jit(one)


@pytest.mark.parametrize("mapped", [False, True], ids=["leaf", "vmap"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_cat_scan_matches_the_reference_loop_bit_for_bit(name, mapped):
    case, leaves, B = CASES[name], 3, 189
    p = _params(case)
    grad, hess, nb, sum_g, sum_h, rand_u = _leaves(
        case, leaves, B, seed=sorted(CASES).index(name))
    sum_h_eps = (sum_h + f32(2 * K_EPSILON)).astype(f32)
    n = np.full(leaves, ROWS, np.int32)
    scan = _scan(p, mapped)
    if mapped:
        outs = [scan(grad, hess, nb, sum_g, sum_h_eps, n, rand_u)]
    else:
        outs = [scan(grad[l], hess[l], nb, sum_g[l], sum_h_eps[l], n[l],
                     None if rand_u is None else rand_u[l])
                for l in range(leaves)]
        outs = [jax.tree_util.tree_map(lambda *v: jnp.stack(v), *outs)]
    (gain, lg, lh, lc, onehot, oh_bin, fwd, plen, used_bin,
     order) = (np.asarray(v) for v in outs[0])
    splits = 0
    for l in range(leaves):
        for c in range(len(nb)):
            want, used, sorted_bins = _oracle(
                grad[l, c], hess[l, c], int(nb[c]), sum_g[l], sum_h_eps[l],
                ROWS, p, None if rand_u is None else rand_u[l, c])
            at = f"{name} leaf {l} column {c}"
            assert gain[l, c].tobytes() == want["gain"].tobytes(), (
                at, gain[l, c], want["gain"])
            assert bool(onehot[l, c]) == (nb[c] <= p.max_cat_to_onehot), at
            if used is not None:
                assert used_bin[l, c] == used, at
                np.testing.assert_array_equal(order[l, c, :used],
                                              sorted_bins, err_msg=at)
            if not np.isfinite(want["gain"]):
                continue
            splits += 1
            assert lg[l, c].tobytes() == f32(want["lg"]).tobytes(), at
            assert lh[l, c].tobytes() == f32(want["lh"]).tobytes(), at
            assert int(lc[l, c]) == want["lc"], at
            if used is None:
                assert int(oh_bin[l, c]) == want["oh"], at
            else:
                assert bool(fwd[l, c]) == want["fwd"], at
                assert int(plen[l, c]) == want["i"], at
    assert splits >= leaves, f"{name}: too few columns split to be a test"


def test_no_category_past_cat_smooth_gives_no_candidate():
    """With no category past cat_smooth the scan reports no gain, the
    forward view and prefix 0 (the backward view's shift is clipped to
    B - 1, inside the range `_shift_left` takes)."""
    case = dict(cols=[(189, 0), (64, 0)], small=10)
    p = _params(case)
    grad, hess, nb, sum_g, sum_h, _ = _leaves(case, 1, 189, seed=5)
    sh = (sum_h + f32(2 * K_EPSILON)).astype(f32)
    out = _scan(p, False)(grad[0], hess[0], nb, sum_g[0], sh[0],
                          np.int32(ROWS), None)
    gain, used_bin, plen, fwd = (np.asarray(out[i]) for i in (0, 8, 7, 6))
    assert np.all(gain == -np.inf) and np.all(used_bin == 0)
    assert np.all(plen == 0) and np.all(fwd)


@pytest.mark.parametrize("mapped", [False, True], ids=["leaf", "vmap"])
def test_forward_keeps_an_exact_tie_between_directions(mapped):
    """Two kept categories of equal count and opposite gradients in a
    leaf whose gradients sum to 0: the forward prefix (the smaller
    ratio) and the backward one (the larger) gain exactly the same, and
    the forward one stays, as the reference's backward pass replaces a
    best only on a strictly larger gain."""
    B, nb = 8, 6
    grad = np.zeros((1, B), f32)
    hess = np.zeros((1, B), f32)
    grad[0, [2, 4]] = [-50.0, 50.0]
    hess[0, [2, 4]] = 250.0                     # 1,000 rows each
    hess[0, [1, 3, 5]] = 1.0                    # 4 rows: below cat_smooth
    p = SplitParams(has_categorical=True, min_data_in_leaf=20)
    sum_g, sum_h = f32(0.0), f32(ROWS * 0.25 + 2 * K_EPSILON)
    want, used, order = _oracle(grad[0], hess[0], nb, sum_g, sum_h, ROWS, p)
    assert want["fwd"] and used == 2 and order == [2, 4]
    nbs = np.array([nb], np.int32)
    if mapped:
        out = _scan(p, True)(grad[None], hess[None], nbs, sum_g[None],
                             sum_h[None], np.array([ROWS], np.int32),
                             None)
        out = [np.asarray(v)[0] for v in out]
    else:
        out = [np.asarray(v) for v in _scan(p, False)(
            grad, hess, nbs, sum_g, sum_h, np.int32(ROWS), None)]
    assert out[0][0].tobytes() == want["gain"].tobytes()
    assert bool(out[6][0]) and int(out[7][0]) == 0
    assert out[1][0] == f32(-50.0)


def _split_of(p, F, cat_index, mapped):
    ci = jnp.asarray(cat_index, jnp.int32)

    def one(hist, sg, sh, n):
        return find_best_split(
            hist, jnp.asarray(NB_OF_F), jnp.zeros(F, jnp.int32),
            jnp.zeros(F, jnp.int32), jnp.ones(F, jnp.float32),
            jnp.ones(F, bool), sg, sh, n, jnp.float32(0.0), p,
            is_cat_feature=jnp.zeros(F, bool).at[ci].set(True), cat_index=ci)
    return jax.jit(jax.vmap(one)) if mapped else jax.jit(one)


NB_OF_F = np.array([63, 189, 63, 40, 189, 13, 64, 189], np.int32)


@pytest.mark.parametrize("mapped", [False, True], ids=["leaf", "vmap"])
def test_find_best_split_takes_the_reference_loops_winner(mapped):
    """Through `find_best_split`, with the categorical columns picked out
    of eight by `cat_index` (data): the leaf's winner is the column whose
    reference loop gains most, its shifted gain, left sums and count bit
    for bit, and its bitset the reference's left category set."""
    cat_index = [1, 3, 4, 5, 7]
    F, B, leaves = len(NB_OF_F), 189, 4
    case = dict(cols=[(int(NB_OF_F[f]), used) for f, used in
                      zip(cat_index, (150, 30, 100, 12, 60))])
    p = _params(case)
    grad, hess, _, sum_g, sum_h, _ = _leaves(case, leaves, B, seed=44)
    hist = np.zeros((leaves, F, B, 2), f32)     # numeric columns: empty
    for c, f in enumerate(cat_index):
        hist[:, f, :, 0], hist[:, f, :, 1] = grad[:, c], hess[:, c]
    sh = (sum_h + f32(2 * K_EPSILON)).astype(f32)
    n = np.full(leaves, ROWS, np.int32)
    fn = _split_of(p, F, cat_index, mapped)
    if mapped:
        res = fn(hist, sum_g, sum_h, n)
    else:
        res = jax.tree_util.tree_map(
            lambda *v: jnp.stack(v),
            *[fn(hist[l], sum_g[l], sum_h[l], n[l]) for l in range(leaves)])
    for l in range(leaves):
        mgs = _gain1(sum_g[l], sh[l], p.lambda_l2)
        best = None
        for c, f in enumerate(cat_index):
            want, used, order = _oracle(grad[l, c], hess[l, c],
                                        int(NB_OF_F[f]), sum_g[l], sh[l],
                                        ROWS, p)
            shifted = f32(want["gain"] - mgs)
            if best is None or shifted > best[0]:
                best = (shifted, f, want, used, order)
        shifted, f, want, used, order = best
        assert np.isfinite(shifted)
        assert int(res.feature[l]) == f and bool(res.is_cat[l])
        assert np.asarray(res.gain[l]).tobytes() == shifted.tobytes()
        assert (np.asarray(res.left_sum_gradient[l]).tobytes()
                == f32(want["lg"]).tobytes())
        assert int(res.left_count[l]) == want["lc"]
        k = want["i"] + 1
        left = order[:k] if want["fwd"] else order[used - k:]
        words = np.asarray(res.cat_bitset[l]).astype(np.int64)
        got = {b for b in range(B) if (words[b // 32] >> (b % 32)) & 1}
        assert got == set(left)


def _cat_form_counters():
    from lightgbm_tpu.observability import global_registry
    return {k: global_registry.counter(f"cat_scan_{k}_traces")
            for k in ("unrolled", "looped")}


@pytest.mark.parametrize("max_cat_threshold,form", [
    (32, "unrolled"), (8, "unrolled"), (_CAT_UNROLL + 1, "looped"),
    (256, "looped")])
def test_trace_counters_say_which_form_the_scan_took(max_cat_threshold,
                                                     form):
    """`cat_scan_unrolled_traces` / `cat_scan_looped_traces` count the
    form a traced scan took: straight-line steps up to `_CAT_UNROLL`,
    a loop over blocks of them past it; nothing runs."""
    F, B = 3, 189
    p = SplitParams(has_categorical=True, max_cat_threshold=max_cat_threshold)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, np.dtype(dt))
    call = functools.partial(find_best_split, params=p)
    find_best_split.clear_cache()
    before = _cat_form_counters()
    jax.eval_shape(
        lambda h, nb, pen, cm, sg, sh, n, po, ic, ci: call(
            h, nb, nb, nb, pen, cm, sg, sh, n, po, is_cat_feature=ic,
            cat_index=ci),
        sds((F, B, 2), "float32"), sds((F,), "int32"),
        sds((F,), "float32"), sds((F,), "bool"), sds((), "float32"),
        sds((), "float32"), sds((), "int32"), sds((), "float32"),
        sds((F,), "bool"), sds((2,), "int32"))
    after = _cat_form_counters()
    moved = {k: after[k] - before[k] for k in after}
    other = "looped" if form == "unrolled" else "unrolled"
    assert moved[form] == 1 and moved[other] == 0, moved


@pytest.mark.parametrize("B", [1, 2, 7, 189, 256])
def test_shift_left_moves_each_row_by_its_own_amount(B):
    """`_shift_left`: entry i of row r reads x[r, i + shift[r]], zeros
    past the end, for every shift from 0 to B - 1."""
    from lightgbm_tpu.ops.split import _shift_left
    shift = np.arange(B, dtype=np.int32)
    x = np.random.RandomState(B).randn(B, B).astype(f32)
    want = np.zeros_like(x)
    for r, s in enumerate(shift):
        want[r, :B - s] = x[r, s:]
    got = np.asarray(jax.jit(_shift_left)(x, shift))
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("dtype", ["float32", "int32"])
def test_rows_of_picks_rows_bit_for_bit(dtype):
    """`_rows_of(a, idx)` is `a[idx]` to the last bit, without a gather:
    the categorical rows of a leaf's `[F, B, 2]` histogram, and `[F]`
    per-feature facts."""
    from lightgbm_tpu.ops.split import _rows_of
    rng = np.random.RandomState(3)
    a = (rng.randn(8, 189, 2) * 1e4).astype(dtype)
    idx = np.array([6, 0, 3, 7, 1], np.int32)
    for arr in (a, a[:, 0, 0]):
        got = np.asarray(jax.jit(_rows_of)(arr, idx))
        assert got.tobytes() == arr[idx].tobytes()
