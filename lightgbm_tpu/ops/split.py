"""Best-split finding: the reference's sequential per-bin gain scan, a slab a step.

TPU-native replacement for FeatureHistogram::FindBestThresholdSequentially
(ref: src/treelearner/feature_histogram.hpp:831-1057) and the CUDA kernels
FindBestSplitsForLeafKernel / SyncBestSplitForLeafKernel
(ref: src/treelearner/cuda/cuda_best_split_finder.cu:772,1920): the scan
runs bin by bin, as the reference's does, but every step handles ALL its
columns — (leaf, feature) pairs — as one dense slab, and carries each
column's running sums and running best along (`_scan_thresholds`).  There
is no prefix array, no argmax and no gather: on a TPU nine
`take_along_axis` over `[256, 2000, 63]` were 147 of the old scan's 164 ms
(PERF.md, PR 33).  The candidate evaluation, the gates and the tie-break
rules exist once, written against the leading bin axis and no layout; two
entries feed them:

  * `find_best_split`: one leaf's `[F, B, 2]` (the leaf-wise engine, the
    parallel learners, EFB bundles, categorical features, monotone
    constraint surfaces);
  * `find_best_split_dense`: all the leaves of a wave at once, from the
    wave engine's cache rows.

Behavioral parity notes (each mirrors a reference line):
  * counts are derived from hessians: cnt(bin) = RoundInt(hess * cnt_factor),
    cnt_factor = num_data / sum_hessian (feature_histogram.hpp:871-874).
  * accumulators are seeded with kEpsilon=1e-15 and the leaf hessian carries
    +2*kEpsilon (feature_histogram.hpp:169-171, 856, 941).
  * REVERSE scan (default_left=True) excludes the NaN bin so missing joins the
    left side; the forward scan leaves it on the right (hpp:859-867, 946-963).
  * MissingType::Zero skips the zero ("default") bin in both scans, so the zero
    bin always follows default_left (hpp:865-869 SKIP_DEFAULT_BIN).
  * `break` conditions (left side runs out of data/hessian) are monotone in the
    threshold, so masking is exactly equivalent to breaking.
  * within a scan, ties keep the first-visited threshold: largest for REVERSE,
    smallest for forward; the forward result replaces the reverse one only on
    strictly larger gain (hpp:1031).  Thresholds separated only by empty bins
    tie bit-exactly, here as there, because the sums run bin by bin.
  * across features, gain ties pick the smaller feature index
    (split_info.hpp:138-163 operator>).

The scan works in the "full bin" layout (bins 0..num_bin-1 present for every
feature, no most_freq_bin offset packing) — equivalent results, simpler tensors.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..observability import global_registry

K_EPSILON = 1e-15  # ref: include/LightGBM/meta.h:54
K_MIN_SCORE = -jnp.inf

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


class SplitParams(NamedTuple):
    """Static split hyperparameters (subset of ref Config used by the gain scan)."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    path_smooth: float = 0.0
    # categorical split finding (ref: feature_histogram.cpp:144
    # FindBestThresholdCategoricalInner); has_categorical=False skips the
    # whole categorical branch at trace time
    has_categorical: bool = False
    # whether any feature of the dataset has a missing type (a fact of the
    # Dataset, set where the booster is built): only such features have
    # forward-scan candidates (hpp:1031), so False skips that scan at
    # trace time
    has_missing: bool = True
    # static inner-feature indices of the categorical features: the scan
    # (argsort + two sequential prefix scans) runs only over these rows,
    # not all F features; () falls back to scanning every feature
    cat_features: tuple = ()
    max_cat_to_onehot: int = 4
    # monotone constraints, basic mode (ref: monotone_constraints.hpp:465
    # BasicLeafConstraints; feature_histogram.hpp:758 GetSplitGains USE_MC):
    # candidate outputs are clamped to the leaf's [min, max] and splits
    # violating the ordering are rejected.  False skips all of it at trace
    # time.  monotone_penalty is the config value fed to
    # ComputeMonotoneSplitGainPenalty (monotone_constraints.hpp:357).
    has_monotone: bool = False
    monotone_penalty: float = 0.0
    # extra-trees mode (ref: feature_histogram.hpp:192 USE_RAND): each
    # numerical feature is evaluated at ONE random threshold per leaf scan
    # instead of the full sweep; extra_seed seeds the per-scan draw
    extra_trees: bool = False
    extra_seed: int = 6
    # cost-effective gradient boosting (ref:
    # cost_effective_gradient_boosting.hpp:79 DeltaGain): per-feature gain
    # penalty = tradeoff * (penalty_split * num_data_in_leaf
    #                       + coupled_penalty[f] * not_yet_used[f])
    has_cegb: bool = False
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    # lazy per-(row, feature) acquisition penalty (ref:
    # cost_effective_gradient_boosting.hpp:139 CalculateOndemandCosts):
    # the scan receives the per-feature cost already summed over the
    # leaf's not-yet-fetched rows
    has_cegb_lazy: bool = False
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    min_data_per_group: int = 100


def cat_bitset_words(max_bin: int) -> int:
    """int32 bitset words needed for a categorical split over max_bin bins."""
    return max(1, (max_bin + 31) // 32)


class SplitResult(NamedTuple):
    """Device-side SplitInfo (ref: src/treelearner/split_info.hpp:22)."""
    gain: jnp.ndarray            # shifted gain (<=0 means no valid split)
    feature: jnp.ndarray         # inner feature index (int32)
    threshold: jnp.ndarray       # bin threshold (int32)
    default_left: jnp.ndarray    # bool
    left_sum_gradient: jnp.ndarray
    left_sum_hessian: jnp.ndarray
    left_count: jnp.ndarray      # int32
    left_output: jnp.ndarray
    right_sum_gradient: jnp.ndarray
    right_sum_hessian: jnp.ndarray
    right_count: jnp.ndarray     # int32
    right_output: jnp.ndarray
    is_cat: jnp.ndarray          # bool: categorical split (bitset routing)
    cat_bitset: jnp.ndarray      # [W] int32 words: bins going LEFT


def threshold_l1(s: jnp.ndarray, l1: float) -> jnp.ndarray:
    """ref: feature_histogram.hpp:710 ThresholdL1."""
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output(sum_g, sum_h, count, parent_output, p: SplitParams):
    """ref: feature_histogram.hpp:716 CalculateSplittedLeafOutput."""
    ret = -threshold_l1(sum_g, p.lambda_l1) / (sum_h + p.lambda_l2)
    if p.max_delta_step > 0:
        ret = jnp.clip(ret, -p.max_delta_step, p.max_delta_step)
    if p.path_smooth > K_EPSILON:
        ratio = count.astype(ret.dtype) / p.path_smooth
        ret = ret * ratio / (ratio + 1.0) + parent_output / (ratio + 1.0)
    return ret


def leaf_gain(sum_g, sum_h, count, parent_output, p: SplitParams):
    """ref: feature_histogram.hpp:800 GetLeafGain."""
    if p.max_delta_step <= 0 and p.path_smooth <= K_EPSILON:
        sg_l1 = threshold_l1(sum_g, p.lambda_l1)
        return (sg_l1 * sg_l1) / (sum_h + p.lambda_l2)
    out = leaf_output(sum_g, sum_h, count, parent_output, p)
    return leaf_gain_given_output(sum_g, sum_h, out, p)


def leaf_gain_given_output(sum_g, sum_h, out, p: SplitParams):
    """ref: feature_histogram.hpp:820 GetLeafGainGivenOutput."""
    sg_l1 = threshold_l1(sum_g, p.lambda_l1)
    return -(2.0 * sg_l1 * out + (sum_h + p.lambda_l2) * out * out)


def _round_int(x: jnp.ndarray) -> jnp.ndarray:
    """ref: utils/common.h RoundInt: static_cast<int>(x + 0.5)."""
    return jnp.floor(x + 0.5).astype(jnp.int32)


def _cat_best_split(grad, hess, cnt_factor, num_bin, sum_g, sum_h, num_data,
                    parent_output, min_gain_shift, p: SplitParams,
                    rand_u=None):
    """Per-feature best CATEGORICAL split (ref: feature_histogram.cpp:144
    FindBestThresholdCategoricalInner), vectorized over features.

    Bin 0 is the NaN/other bin and never enters a left set (the reference
    scans actual bins [1, num_bin); unseen/NaN categories route right).

    Under extra_trees (USE_RAND), rand_u is [F, 2] uniforms: one draw
    picks the single one-hot candidate bin (rand.NextInt(bin_start,
    bin_end), cpp:187), the other the single sorted-subset prefix length
    (rand.NextInt(0, max_threshold), cpp:268); the group-count reset
    still runs for skipped candidates (cpp:310-317 order).

    Returns per-feature (gain [F], left_g, left_h, left_c, use_onehot,
    onehot_bin, dir_is_fwd, prefix_len, used_bin, sorted_bins [F, B]).
    """
    F, B = grad.shape
    f32 = jnp.float32
    i32 = jnp.int32
    bins = jnp.arange(B, dtype=i32)[None, :]
    # cat_l2-augmented params for the sorted-subset branch only
    pcat = p._replace(lambda_l2=p.lambda_l2 + p.cat_l2)

    in_range = (bins >= 1) & (bins < num_bin[:, None])
    grad = jnp.where(in_range, grad, 0.0)
    hess = jnp.where(in_range, hess, 0.0)
    cnt = jnp.where(in_range, _round_int(hess * cnt_factor), 0)

    def split_gain(lg, lh, lc, rg, rh, rc, ok, pp):
        ok = (ok
              & (lc >= p.min_data_in_leaf)
              & (lh >= p.min_sum_hessian_in_leaf)
              & (rc >= p.min_data_in_leaf)
              & (rh >= p.min_sum_hessian_in_leaf))
        gain = (leaf_gain(lg, lh, lc.astype(f32), parent_output, pp)
                + leaf_gain(rg, rh, rc.astype(f32), parent_output, pp))
        return jnp.where(ok & (gain > min_gain_shift), gain, K_MIN_SCORE)

    # ---- one-hot mode: left = single category (hpp use_onehot branch) ----
    # cat_l2 does NOT apply here: the reference adds it to l2 only in the
    # sorted-subset else-branch (feature_histogram.cpp:250)
    oh_ok = in_range
    if p.extra_trees and rand_u is not None:
        # single random candidate bin in [1, num_bin)
        span = jnp.maximum(num_bin - 1, 1).astype(f32)
        oh_rand = 1 + jnp.clip((rand_u[:, 0] * span).astype(i32), 0,
                               jnp.maximum(num_bin - 2, 0))
        oh_ok = oh_ok & (bins == oh_rand[:, None])
    oh_gain = split_gain(grad, hess + K_EPSILON, cnt,
                         sum_g - grad, sum_h - hess - K_EPSILON,
                         num_data - cnt, oh_ok, p)
    oh_best = jnp.argmax(oh_gain, axis=1).astype(i32)
    take1 = lambda a, idx: jnp.take_along_axis(a, idx[:, None], 1)[:, 0]
    oh_best_gain = take1(oh_gain, oh_best)

    # ---- sorted-subset mode ----
    # categories with enough data, stably sorted by grad/(hess+cat_smooth)
    valid = in_range & (cnt >= p.cat_smooth)
    ratio = jnp.where(valid, grad / (hess + p.cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=1, stable=True).astype(i32)  # [F, B]
    sg_s = jnp.take_along_axis(grad, order, 1)
    sh_s = jnp.take_along_axis(hess, order, 1)
    sc_s = jnp.take_along_axis(cnt, order, 1)
    used_bin = jnp.sum(valid, axis=1, dtype=i32)                # [F]
    max_num_cat = jnp.minimum(p.max_cat_threshold, (used_bin + 1) // 2)
    steps = min(p.max_cat_threshold, B)
    if p.extra_trees and rand_u is not None:
        # single random prefix length in [0, max_threshold) where
        # max_threshold = max(min(max_num_cat, used_bin) - 1, 0)
        max_thr = jnp.maximum(
            jnp.minimum(max_num_cat, used_bin) - 1, 0).astype(f32)
        sub_rand = jnp.clip((rand_u[:, 1] * jnp.maximum(max_thr, 1.0))
                            .astype(i32), 0,
                            jnp.maximum(max_thr.astype(i32) - 1, 0))

    def scan_dir(fwd: bool):
        if fwd:
            g_d, h_d, c_d = sg_s, sh_s, sc_s
        else:  # from the largest-ratio end over the VALID entries
            pos = used_bin[:, None] - 1 - jnp.arange(B, dtype=i32)[None, :]
            idx = jnp.clip(pos, 0, B - 1)
            g_d = jnp.take_along_axis(sg_s, idx, 1)
            h_d = jnp.take_along_axis(sh_s, idx, 1)
            c_d = jnp.take_along_axis(sc_s, idx, 1)

        def step(carry, i):
            cum, lg, lh, lc = carry
            lg = lg + g_d[:, i]
            lh = lh + h_d[:, i]
            lc = lc + c_d[:, i]
            cum = cum + c_d[:, i]
            rc = num_data - lc
            rh = sum_h - lh
            rg = sum_g - lg
            # the reference's break conditions (right side shrinking) are
            # monotone in i, so masking == breaking; the group counter
            # resets whenever a candidate reaches evaluation, even if its
            # gain then fails min_gain_shift (cpp:296-318 order)
            left_ok = ((lc >= p.min_data_in_leaf)
                       & (lh >= p.min_sum_hessian_in_leaf))
            right_ok = ((rc >= p.min_data_in_leaf)
                        & (rc >= p.min_data_per_group)
                        & (rh >= p.min_sum_hessian_in_leaf))
            in_limit = i < jnp.minimum(used_bin, max_num_cat)
            eligible = (in_limit & left_ok & right_ok
                        & (cum >= p.min_data_per_group))
            raw = (leaf_gain(lg, lh, lc.astype(f32), parent_output, pcat)
                   + leaf_gain(rg, rh, rc.astype(f32), parent_output, pcat))
            gain_ok = eligible & (raw > min_gain_shift)
            if p.extra_trees and rand_u is not None:
                # USE_RAND: only the random prefix scores, but the group
                # counter still resets on skipped candidates (cpp:310-317)
                gain_ok = gain_ok & (i == sub_rand)
            gain = jnp.where(gain_ok, raw, K_MIN_SCORE)
            cum = jnp.where(eligible, 0, cum)
            return (cum, lg, lh, lc), (gain, lg, lh, lc)

        init = (jnp.zeros(F, i32), jnp.zeros(F, f32),
                jnp.full(F, K_EPSILON, f32), jnp.zeros(F, i32))
        _, (gains, lgs, lhs, lcs) = jax.lax.scan(
            step, init, jnp.arange(steps, dtype=i32))
        # [steps, F] -> best prefix per feature
        best_i = jnp.argmax(gains, axis=0).astype(i32)
        takeS = lambda a: jnp.take_along_axis(a, best_i[None, :], 0)[0]
        return takeS(gains), takeS(lgs), takeS(lhs), takeS(lcs), best_i

    fw_gain, fw_lg, fw_lh, fw_lc, fw_i = scan_dir(True)
    bw_gain, bw_lg, bw_lh, bw_lc, bw_i = scan_dir(False)
    use_fw = fw_gain >= bw_gain
    so_gain = jnp.where(use_fw, fw_gain, bw_gain)
    so_lg = jnp.where(use_fw, fw_lg, bw_lg)
    so_lh = jnp.where(use_fw, fw_lh, bw_lh)
    so_lc = jnp.where(use_fw, fw_lc, bw_lc)
    so_i = jnp.where(use_fw, fw_i, bw_i)

    # per-feature mode choice is static in num_bin (hpp use_onehot)
    use_onehot = num_bin <= p.max_cat_to_onehot
    gain = jnp.where(use_onehot, oh_best_gain, so_gain)
    left_g = jnp.where(use_onehot, take1(grad, oh_best), so_lg)
    left_h = jnp.where(use_onehot, take1(hess, oh_best) + K_EPSILON, so_lh)
    left_c = jnp.where(use_onehot, take1(cnt, oh_best), so_lc)
    return (gain, left_g, left_h, left_c, use_onehot, oh_best, use_fw,
            so_i, used_bin, order)


class _Columns(NamedTuple):
    """What the threshold scan knows of each of its columns — one (leaf,
    feature) pair each.  Every field broadcasts against the shape of a
    slab: per-feature fields lie along the feature axis, per-leaf fields
    along the leaf axis, and the scan never asks which is which."""
    num_bin: jnp.ndarray          # int32
    missing_type: jnp.ndarray     # int32
    default_bin: jnp.ndarray      # int32
    sum_g: jnp.ndarray            # leaf totals; sum_h carries the +2*kEpsilon
    sum_h: jnp.ndarray
    num_data: jnp.ndarray         # int32
    parent_output: jnp.ndarray


def _leaf_totals(sum_gradient, sum_hessian):
    """(sum_g, sum_h + 2*kEpsilon) as the scan uses them
    (ref: feature_histogram.hpp:169 FindBestThreshold)."""
    f32 = jnp.float32
    return sum_gradient.astype(f32), sum_hessian.astype(f32) + 2 * K_EPSILON


def _min_gain_shift(sum_g, sum_h, num_data, parent_output, params):
    """The gain a split has to beat: the unsplit leaf's, plus
    min_gain_to_split (hpp:172-175)."""
    return (leaf_gain(sum_g, sum_h, num_data.astype(jnp.float32),
                      parent_output, params) + params.min_gain_to_split)


def _scan_thresholds(slab, num_bins: int, shape, col: _Columns,
                     params: SplitParams, rand_bin=None, mono=None):
    """Best numerical threshold of every column, by the reference's own
    two sequential scans (feature_histogram.hpp:831
    FindBestThresholdSequentially): a running sum over the bins, one dense
    slab of columns a step, with the running best carried along — no
    prefix array, no argmax, no gather.

    `slab(t)` gives (gradient, hessian) sums of bin `t` (a traced int32)
    for all columns, in `shape`; the scan is written against that leading
    bin axis and knows no layout.  `rand_bin` (extra-trees) and the
    members of `mono` broadcast against `shape` like `col`'s fields;
    `mono` is (monotone, min_left, max_left, min_right, max_right): each
    bound has a leading axis that is read at the candidate threshold —
    of `num_bins` for a constraint surface, of 1 for the leaf's scalar.

    Because the sums run bin by bin, two candidates separated only by
    empty bins (an exact 0.0 added, a count of 0) have bit-identical sums
    and gains, as in the reference, and the strict `>` below keeps the
    first visited: the largest threshold in the REVERSE scan, the smallest
    in the forward one.

    Returns (gain, threshold, default_left, left_g, left_h_raw, left_c)
    per column; gain is K_MIN_SCORE where no candidate passed the gates.
    """
    f32, i32 = jnp.float32, jnp.int32
    nb, mt, db = col.num_bin, col.missing_type, col.default_bin
    sum_g, sum_h, num_data = col.sum_g, col.sum_h, col.num_data
    parent_output = col.parent_output
    cnt_factor = num_data.astype(f32) / sum_h
    is_nan = mt == MISSING_NAN
    is_zero = mt == MISSING_ZERO
    min_gain_shift = _min_gain_shift(sum_g, sum_h, num_data, parent_output,
                                     params)

    def bin_sums(t):
        """Bin t's (gradient, hessian, count) where it accumulates: not
        past the feature's bins, not its NaN bin, not a skipped zero bin
        (hpp:859-869)."""
        g, h = slab(t)
        acc = (t < nb) & ~(is_nan & (t == nb - 1)) & ~(is_zero & (t == db))
        return (jnp.where(acc, g, 0.0), jnp.where(acc, h, 0.0),
                jnp.where(acc, _round_int(h * cnt_factor), 0))

    def candidate_gain(left_g, left_h_raw, left_c, tau_ok, tau):
        """Gain where the left side is (left_g, left_h_raw + eps, left_c),
        K_MIN_SCORE where a gate refuses the candidate."""
        left_h = left_h_raw + K_EPSILON
        right_g = sum_g - left_g
        right_h = sum_h - left_h
        right_c = num_data - left_c
        ok = (tau_ok
              & (left_c >= params.min_data_in_leaf)
              & (left_h >= params.min_sum_hessian_in_leaf)
              & (right_c >= params.min_data_in_leaf)
              & (right_h >= params.min_sum_hessian_in_leaf))
        gain = (leaf_gain(left_g, left_h, left_c.astype(f32), parent_output,
                          params)
                + leaf_gain(right_g, right_h, right_c.astype(f32),
                            parent_output, params))
        if params.has_monotone:
            # constrained gain for monotone features: outputs clamped to
            # the leaf's [min, max]; ordering violations score 0
            # (feature_histogram.hpp:758-797 GetSplitGains USE_MC branch).
            # Advanced mode (monotone_constraints.hpp:858
            # AdvancedLeafConstraints) passes PER-CHILD, PER-THRESHOLD
            # constraint surfaces instead of the leaf scalar.
            mc, cmin_l, cmax_l, cmin_r, cmax_r = mono
            at = lambda a: a[jnp.minimum(tau, a.shape[0] - 1)]
            lout = jnp.clip(leaf_output(left_g, left_h, left_c.astype(f32),
                                        parent_output, params),
                            at(cmin_l), at(cmax_l))
            rout = jnp.clip(leaf_output(right_g, right_h,
                                        right_c.astype(f32),
                                        parent_output, params),
                            at(cmin_r), at(cmax_r))
            bad = (((mc > 0) & (lout > rout)) | ((mc < 0) & (lout < rout)))
            # clamping applies to EVERY feature once the leaf is
            # constrained (USE_MC templates the whole learner); the
            # ordering rejection only to monotone features
            gain_mc = (leaf_gain_given_output(left_g, left_h, lout, params)
                       + leaf_gain_given_output(right_g, right_h, rout,
                                                params))
            gain = jnp.where(bad & (mc != 0), 0.0, gain_mc)
        ok = ok & (gain > min_gain_shift)
        return jnp.where(ok, gain, K_MIN_SCORE)

    def keep_better(best, new):
        better = new[0] > best[0]           # strict: first visited wins ties
        return tuple(jnp.where(better, n, b) for n, b in zip(new, best))

    zf, zi = jnp.zeros(shape, f32), jnp.zeros(shape, i32)
    no_best = (jnp.full(shape, K_MIN_SCORE, f32), zi, zf, zf, zi)
    if params.extra_trees:
        # only the leaf's random threshold is a candidate (USE_RAND:
        # hpp:899 `t - 1 + offset != rand_threshold -> continue`)
        is_drawn = lambda tau: tau == rand_bin
    else:
        is_drawn = lambda tau: True

    # ---- REVERSE scan: the right side accumulates bins > tau from the top
    # (ref: hpp:856-930); missing (the NaN bin, a skipped zero bin) stays
    # out of it and so joins the left: default_left.  right_h = kEps +
    # suffix and left_h = sum_h - right_h; candidate_gain re-adds its own
    # eps to the raw left, so raw subtracts both.
    def rev_step(i, carry):
        right_g, right_h, right_c, best = carry
        tau = num_bins - 2 - i
        g, h, c = bin_sums(tau + 1)
        right_g, right_h, right_c = right_g + g, right_h + h, right_c + c
        tau_ok = ((tau <= nb - 2 - is_nan.astype(i32))
                  & ~(is_zero & (tau == db - 1))        # skipped iteration
                  & is_drawn(tau))
        left = (sum_g - right_g, sum_h - right_h - 2 * K_EPSILON,
                num_data - right_c)
        gain = candidate_gain(*left, tau_ok, tau)
        return right_g, right_h, right_c, keep_better(best, (gain, tau) + left)

    steps = max(num_bins - 1, 0)
    rev = jax.lax.fori_loop(0, steps, rev_step, (zf, zf, zi, no_best))[3]
    if not params.has_missing:
        # every forward candidate needs a missing type (hpp:1031 runs the
        # second scan only for such features): none in this dataset
        gain, thr, lg, lh_raw, lc = rev
        return gain, thr, jnp.ones(shape, bool), lg, lh_raw, lc

    # ---- FORWARD scan: left = inclusive prefix at tau; missing goes right
    def fwd_step(tau, carry):
        left_g, left_h, left_c, best = carry
        g, h, c = bin_sums(tau)
        left = (left_g + g, left_h + h, left_c + c)
        tau_ok = ((tau <= nb - 2) & (mt != MISSING_NONE)
                  & ~(is_zero & (tau == db))            # skipped iteration
                  & is_drawn(tau))
        gain = candidate_gain(*left, tau_ok, tau)
        return left + (keep_better(best, (gain, tau) + left),)

    fwd = jax.lax.fori_loop(0, steps, fwd_step, (zf, zf, zi, no_best))[3]
    # forward replaces reverse only on strictly larger gain (ref: hpp:1031)
    use_fwd = fwd[0] > rev[0]
    gain, thr, lg, lh_raw, lc = (jnp.where(use_fwd, f, r)
                                 for f, r in zip(fwd, rev))
    return gain, thr, ~use_fwd, lg, lh_raw, lc


def _take_one(a, idx):
    """a[idx] along the last axis as a masked reduce: an XLA gather walks
    its indices on a TPU (~1 GB/s), a one-hot select-sum does not, and with
    one selected element it is exact."""
    hit = jnp.arange(a.shape[-1], dtype=jnp.int32) == idx
    if a.dtype == jnp.bool_:
        return jnp.any(hit & a, axis=-1)
    return jnp.sum(jnp.where(hit, a, jnp.zeros((), a.dtype)), axis=-1)


def _choose_feature(per_feature, feature_penalty, col_mask, sum_g, sum_h,
                    num_data, parent_output, params: SplitParams, max_bin,
                    cat=None, cegb_coupled=None, cegb_used=None,
                    monotone=None, mono_penalty=None, cegb_lazy_cost=None,
                    clamp_winner=None, return_feature_gains=False):
    """One leaf's best feature from its per-feature bests ([F] arrays, as
    `_scan_thresholds` returns them; `sum_h` with its +2*kEpsilon): feature
    penalty, column sampling, CEGB and the monotone penalty, then the
    argmax (gain tie -> smaller index, SplitInfo::operator>) and the
    winner's `SplitResult`.  `cat` is `_cat_best_split`'s result for the
    categorical features (with their indices and mask), whose numerical
    results it replaces; `clamp_winner(feature, threshold, is_cat)` gives
    the (min_l, max_l, min_r, max_r) that clamp the winner's outputs."""
    f32 = jnp.float32
    best_gain_f, best_thr_f, default_left_f, lg, lh_raw, lc = per_feature
    num_features = best_gain_f.shape[0]
    min_gain_shift = _min_gain_shift(sum_g, sum_h, num_data, parent_output,
                                     params)
    if cat is not None:
        # categorical features replace their numerical scan results;
        # double-guard with is_cat_f (a numerical feature listed in
        # cat_features must keep its numerical result)
        (ci, is_cat_f, cgain, clg, clh, clc, c_onehot, c_ohbin, c_fwd,
         c_plen, c_ub, c_order) = cat
        catset = jnp.zeros(num_features, bool).at[ci].set(True) & is_cat_f
        best_gain_f = jnp.where(catset, best_gain_f.at[ci].set(cgain),
                                best_gain_f)
        lg = jnp.where(catset, lg.at[ci].set(clg), lg)
        lh_raw = jnp.where(catset, lh_raw.at[ci].set(clh - K_EPSILON),
                           lh_raw)
        lc = jnp.where(catset, lc.at[ci].set(clc), lc)
        default_left_f = jnp.where(catset, False, default_left_f)
        # map a winning full-F index back to its compact cat row
        pos_of_f = jnp.zeros(num_features, jnp.int32).at[ci].set(
            jnp.arange(ci.shape[0], dtype=jnp.int32))

    shifted = (best_gain_f - min_gain_shift) * feature_penalty
    if params.has_cegb:
        # ref: serial_tree_learner.cpp:983 new_split.gain -= DeltaGain(...)
        delta = params.cegb_tradeoff * (
            params.cegb_penalty_split * num_data.astype(f32))
        if cegb_coupled is not None:
            delta = delta + params.cegb_tradeoff * jnp.where(
                cegb_used, 0.0, cegb_coupled)
        if params.has_cegb_lazy and cegb_lazy_cost is not None:
            # ref: cost_effective_gradient_boosting.hpp:91 DeltaGain's
            # CalculateOndemandCosts term
            delta = delta + params.cegb_tradeoff * cegb_lazy_cost
        shifted = shifted - delta
    if params.has_monotone and params.monotone_penalty > 0:
        # depth-based penalty on monotone features' gains
        # (serial_tree_learner.cpp:987-991)
        shifted = jnp.where(monotone != 0, shifted * mono_penalty, shifted)
    shifted = jnp.where(col_mask & (best_gain_f > K_MIN_SCORE), shifted,
                        K_MIN_SCORE)
    if return_feature_gains:
        # per-feature shifted best gains, for the voting-parallel learner's
        # local vote (ref: voting_parallel_tree_learner.cpp:151 GlobalVoting
        # ranks features by their local best split gains)
        return shifted
    best_f = jnp.argmax(shifted, axis=0).astype(jnp.int32)

    g_ = jnp.max(shifted, axis=0)
    lg_, lc_ = _take_one(lg, best_f), _take_one(lc, best_f)
    lh_ = _take_one(lh_raw, best_f) + K_EPSILON
    rg_, rc_ = sum_g - lg_, num_data - lc_
    rh_ = sum_h - lh_
    thr_ = _take_one(best_thr_f, best_f)

    W = cat_bitset_words(max_bin)
    if cat is not None:
        won_cat = catset[best_f]
        cpos = pos_of_f[best_f]          # winner's compact cat row
        # leaf outputs use lambda_l2 + cat_l2 only for sorted-subset
        # categorical winners, not one-hot (feature_histogram.cpp:250)
        pcat = params._replace(lambda_l2=params.lambda_l2 + params.cat_l2)
        won_subset = won_cat & ~c_onehot[cpos]
        left_out = jnp.where(
            won_subset,
            leaf_output(lg_, lh_, lc_.astype(f32), parent_output, pcat),
            leaf_output(lg_, lh_, lc_.astype(f32), parent_output, params))
        right_out = jnp.where(
            won_subset,
            leaf_output(rg_, rh_, rc_.astype(f32), parent_output, pcat),
            leaf_output(rg_, rh_, rc_.astype(f32), parent_output, params))
        # winning left-category set as a bin bitset (ref: split_info.hpp
        # cat_threshold; bins, not raw category values, on device)
        bins_b = jnp.arange(max_bin, dtype=jnp.int32)
        sorted_w = c_order[cpos]                         # [B] sorted bins
        ub = c_ub[cpos]
        plen = c_plen[cpos] + 1
        in_set_sorted = jnp.where(
            c_fwd[cpos], bins_b < plen, (bins_b >= ub - plen) & (bins_b < ub))
        member = jnp.zeros(max_bin, bool).at[sorted_w].set(
            in_set_sorted, mode="drop")
        member = jnp.where(c_onehot[cpos],
                           bins_b == c_ohbin[cpos], member)
        member = member & won_cat
        bit = (member.astype(jnp.int32) << (bins_b % 32))
        cat_bitset = jnp.zeros(W, jnp.int32).at[bins_b // 32].add(bit)
        is_cat_out = won_cat
        thr_out = jnp.where(won_cat, 0, thr_)
    else:
        left_out = leaf_output(lg_, lh_, lc_.astype(f32), parent_output,
                               params)
        right_out = leaf_output(rg_, rh_, rc_.astype(f32), parent_output,
                                params)
        cat_bitset = jnp.zeros(W, jnp.int32)
        is_cat_out = jnp.asarray(False)
        thr_out = thr_

    if clamp_winner is not None:
        # the leaf's [min, max] clamps the winner's stored outputs too
        # (CalculateSplittedLeafOutput USE_MC, feature_histogram.hpp:740)
        lmin_w, lmax_w, rmin_w, rmax_w = clamp_winner(best_f, thr_,
                                                      is_cat_out)
        left_out = jnp.clip(left_out, lmin_w, lmax_w)
        right_out = jnp.clip(right_out, rmin_w, rmax_w)

    return SplitResult(
        gain=g_, feature=best_f, threshold=thr_out,
        default_left=_take_one(default_left_f, best_f),
        left_sum_gradient=lg_, left_sum_hessian=lh_ - K_EPSILON,
        left_count=lc_, left_output=left_out,
        right_sum_gradient=rg_, right_sum_hessian=rh_ - K_EPSILON,
        right_count=rc_, right_output=right_out,
        is_cat=is_cat_out, cat_bitset=cat_bitset)


def _count_traced_scan(form: str):
    """A gain scan of this form is being TRACED: `GET /metrics` and a
    run's snapshot then say which form each program took
    (`split_scan_dense_traces`, `split_scan_generic_traces`)."""
    global_registry.inc(f"split_scan_{form}_traces")


@functools.partial(jax.jit,
                   static_argnames=("params", "return_feature_gains"))
def find_best_split(hist: jnp.ndarray, num_bin: jnp.ndarray,
                    missing_type: jnp.ndarray, default_bin: jnp.ndarray,
                    feature_penalty: jnp.ndarray, col_mask: jnp.ndarray,
                    sum_gradient: jnp.ndarray, sum_hessian: jnp.ndarray,
                    num_data: jnp.ndarray, parent_output: jnp.ndarray,
                    params: SplitParams,
                    is_cat_feature: jnp.ndarray = None,
                    rand_bin: jnp.ndarray = None,
                    cegb_coupled: jnp.ndarray = None,
                    cegb_used: jnp.ndarray = None,
                    monotone: jnp.ndarray = None,
                    constraint_min: jnp.ndarray = None,
                    constraint_max: jnp.ndarray = None,
                    constraint_min_left: jnp.ndarray = None,
                    constraint_max_left: jnp.ndarray = None,
                    constraint_min_right: jnp.ndarray = None,
                    constraint_max_right: jnp.ndarray = None,
                    mono_penalty: jnp.ndarray = None,
                    cegb_lazy_cost: jnp.ndarray = None,
                    rand_cat_u: jnp.ndarray = None,
                    return_feature_gains: bool = False) -> SplitResult:
    """Scan all (feature, threshold, direction) candidates; return the leaf's best.

    The per-leaf entry: one leaf's histogram in `[F, B, 2]`, as the
    leaf-wise engine, the parallel learners, EFB's `bundle_hist_to_features`
    and the categorical scan's sub-array hand it over.  The wave engine's
    plain numerical mode scans all its leaves at once from its cache's
    rows: `find_best_split_dense`.

    Args:
      hist: [F, B, 2] (sum_gradient, sum_hessian) per bin.
      num_bin/missing_type/default_bin: [F] int32 per-feature bin metadata.
      feature_penalty: [F] gain multiplier (ref: meta_->penalty, feature_contri).
      col_mask: [F] bool, feature_fraction sampling mask.
      sum_gradient/sum_hessian: leaf totals (hessian WITHOUT the +2eps; added here,
        ref: feature_histogram.hpp:169 FindBestThreshold).
      num_data: actual row count in leaf (int32).
      parent_output: leaf's current output (for path smoothing).
    """
    _count_traced_scan("generic")
    num_features, max_bin, _ = hist.shape
    sum_g, sum_h = _leaf_totals(sum_gradient, sum_hessian)
    # bins leading: a step of the scan reads one [F] row of each plane
    grad, hess = hist[:, :, 0].T, hist[:, :, 1].T
    mono = None
    if params.has_monotone:
        if constraint_min_left is not None:     # [F, B] surfaces
            bounds = tuple(b.T for b in (
                constraint_min_left, constraint_max_left,
                constraint_min_right, constraint_max_right))
        else:
            bounds = (constraint_min[None], constraint_max[None]) * 2
        mono = (monotone,) + bounds
    per_feature = _scan_thresholds(
        lambda t: (grad[t], hess[t]), max_bin, (num_features,),
        _Columns(num_bin, missing_type, default_bin, sum_g, sum_h,
                 num_data, parent_output),
        params, rand_bin=rand_bin, mono=mono)

    cat = None
    if params.has_categorical:
        # the expensive scan (argsort + two sequential prefix scans) runs
        # only over the categorical rows, gathered into a static
        # F_cat-sized subarray; results scatter back into the [F] arrays
        cat_idx = (params.cat_features if params.cat_features
                   else tuple(range(num_features)))
        ci = jnp.asarray(cat_idx, jnp.int32)
        cat = (ci, is_cat_feature) + _cat_best_split(
            hist[ci, :, 0], hist[ci, :, 1],
            num_data.astype(jnp.float32) / sum_h,
            num_bin[ci], sum_g, sum_h, num_data, parent_output,
            _min_gain_shift(sum_g, sum_h, num_data, parent_output, params),
            params,
            rand_u=None if rand_cat_u is None else rand_cat_u[ci])

    clamp_winner = None
    if params.has_monotone:
        def clamp_winner(best_f, thr, is_cat):
            # Advanced mode clamps with the constraint surface AT the
            # winning (feature, threshold); categorical winners keep the
            # conservative whole-leaf scalar (their surfaces are
            # threshold-indexed).
            if constraint_min_left is None:
                return (constraint_min, constraint_max) * 2
            return tuple(jnp.where(is_cat, whole, surface[best_f, thr])
                         for whole, surface in (
                             (constraint_min, constraint_min_left),
                             (constraint_max, constraint_max_left),
                             (constraint_min, constraint_min_right),
                             (constraint_max, constraint_max_right)))
    return _choose_feature(
        per_feature, feature_penalty, col_mask, sum_g, sum_h, num_data,
        parent_output, params, max_bin, cat=cat, cegb_coupled=cegb_coupled,
        cegb_used=cegb_used, monotone=monotone, mono_penalty=mono_penalty,
        cegb_lazy_cost=cegb_lazy_cost, clamp_winner=clamp_winner,
        return_feature_gains=return_feature_gains)


@functools.partial(jax.jit, static_argnames=("params", "max_bin"))
def find_best_split_dense(rows: jnp.ndarray, num_bin: jnp.ndarray,
                          missing_type: jnp.ndarray,
                          default_bin: jnp.ndarray,
                          feature_penalty: jnp.ndarray,
                          col_mask: jnp.ndarray,
                          sum_gradient: jnp.ndarray,
                          sum_hessian: jnp.ndarray, num_data: jnp.ndarray,
                          parent_output: jnp.ndarray, params: SplitParams,
                          max_bin: int, rand_bin: jnp.ndarray = None,
                          cegb_coupled: jnp.ndarray = None,
                          cegb_used: jnp.ndarray = None) -> SplitResult:
    """`find_best_split` for N leaves at once, from the wave engine's
    cache rows: every leaf's histogram is read once, in a layout that is
    dense on the chip.

    Args:
      rows: [N, F * B * 2] float32, a leaf a row in the cache's
        (feature, bin, channel) order.
      col_mask: [F] bool, or [N, F] for per-leaf masks (by-node sampling,
        interaction constraints).
      sum_gradient / sum_hessian / num_data / parent_output: [N].
      rand_bin: [N, F] (extra-trees).
    Plain numerical features only: categorical scans, monotone constraint
    surfaces and EFB's per-feature gather arrive per leaf in `[F, B, 2]`
    and take `find_best_split`.  Returns a `SplitResult` of [N] fields.
    """
    assert not (params.has_categorical or params.has_monotone)
    _count_traced_scan("dense")
    N = rows.shape[0]
    F = num_bin.shape[0]
    num_data = num_data.astype(jnp.int32)
    sum_g, sum_h = _leaf_totals(sum_gradient, sum_hessian)
    # [2, B, N, F]: gradient and hessian as separate planes, the bins
    # leading; a step of the scan reads one [N, F] slab of each, leaves on
    # sublanes and features on lanes
    planes = rows.reshape(N, F, max_bin, 2).transpose(3, 2, 0, 1)
    col = _Columns(num_bin[None, :], missing_type[None, :],
                   default_bin[None, :], sum_g[:, None], sum_h[:, None],
                   num_data[:, None], parent_output[:, None])
    per_feature = _scan_thresholds(
        lambda t: (planes[0, t], planes[1, t]), max_bin, (N, F), col, params,
        rand_bin=rand_bin)
    choose = functools.partial(
        _choose_feature, params=params, max_bin=max_bin,
        cegb_coupled=cegb_coupled, cegb_used=cegb_used)
    return jax.vmap(
        lambda pf, cm, sg, sh, n, po: choose(pf, feature_penalty, cm, sg,
                                             sh, n, po),
        in_axes=(0, 0 if col_mask.ndim == 2 else None, 0, 0, 0, 0))(
            per_feature, col_mask, sum_g, sum_h, num_data, parent_output)
