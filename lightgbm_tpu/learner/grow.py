"""Leaf-wise (best-first) tree growth as ONE jitted XLA program.

TPU-native re-design of SerialTreeLearner::Train
(ref: src/treelearner/serial_tree_learner.cpp:179-240) and the CUDA learner's
host-driven per-leaf loop (ref: src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:155-245).
Design differences from the reference, chosen for the TPU compilation model:

* The whole num_leaves-1 split loop is a `lax.fori_loop` inside one jit — no
  per-split host round trip (the CUDA learner pays a D2H sync per split;
  SURVEY.md §3.3 flags this as the thing to avoid on TPU).
* Row partition is a row-permutation `order` with contiguous per-leaf
  segments — the TPU analogue of DataPartition's per-leaf index lists
  (ref: data_partition.hpp:21).  Each split reads only the split leaf's
  segment through a pow2-bucketed `lax.switch` (static shapes), partitions
  it in place, and builds the smaller child's histogram from just those
  rows, so a tree costs ~n*log2(L) row visits like the reference's
  partitioned scan (ref: dense_bin.hpp:99-176), not n*(L-1).
* Histogram bookkeeping keeps the reference's smaller-child trick: the smaller
  child's histogram is built fresh, the larger's is parent − smaller
  (ref: serial_tree_learner.cpp:334 BeforeFindBestSplit, feature_histogram.hpp
  Subtract).  A per-leaf histogram stack [L, F, B, 2] plays the role of the
  HistogramPool (ref: feature_histogram.hpp:1367); when it would not fit in
  HBM, `use_hist_stack=False` rebuilds both children instead.
* Bagging is a row mask multiplied into grad/hess (no subset copy);
  feature_fraction is a column mask into the gain scan.

All reductions over the row axis (histograms, sums, counts) are the only ops
touching sharded data, so the same program runs data-parallel under pjit with
rows sharded over a mesh — XLA inserts the psum that replaces
Network::ReduceScatter (ref: data_parallel_tree_learner.cpp:284).  (The
partitioned engine gathers rows by global index, so the data-parallel path
uses the masked engine: set compact_min=0 under sharding.)
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.histogram import (build_histogram, build_histogram_rows_pallas,
                             snap_to_operand_grid)
from ..ops.split import (K_MIN_SCORE, SplitParams, SplitResult,
                         cat_bitset_words, find_best_split,
                         MISSING_NAN, MISSING_ZERO)
from ..utils.timer import global_timer


class FeatureMeta(NamedTuple):
    """Per-feature bin metadata, device-resident (ref: FeatureMetainfo,
    feature_histogram.hpp:40)."""
    num_bin: jnp.ndarray        # [F] int32
    missing_type: jnp.ndarray   # [F] int32
    default_bin: jnp.ndarray    # [F] int32
    penalty: jnp.ndarray        # [F] float32 (feature_contri)
    is_cat: jnp.ndarray = None  # [F] bool (None when no categorical)
    # the categorical features' indices (int32 [F_cat]): data, so only
    # their count is static in a program, never which columns they are
    cat_index: jnp.ndarray = None
    monotone: jnp.ndarray = None  # [F] int32 -1/0/+1 (None when unused)
    cegb_coupled: jnp.ndarray = None  # [F] float32 coupled penalties
    cegb_lazy: jnp.ndarray = None     # [F] float32 lazy per-row penalties
    # EFB (ref: feature_group.h): feature -> bundle column, code offset,
    # default (zero) bin, membership flag (None/unused when not bundling)
    group: jnp.ndarray = None       # [F] int32 bundle column index
    offset: jnp.ndarray = None      # [F] int32 code offset (0 singleton)
    zero_bin: jnp.ndarray = None    # [F] int32 default bin
    in_bundle: jnp.ndarray = None   # [F] bool
    # the device columns sorted by histogram class and the inverse of
    # that order (int32 [device columns]; None with one class): WHICH
    # column holds which of `GrowParams.hist_classes` is data
    hist_order: jnp.ndarray = None
    hist_inverse: jnp.ndarray = None


class GrowParams(NamedTuple):
    """Static growth hyperparameters."""
    num_leaves: int = 31
    max_depth: int = -1
    max_bin: int = 255
    split: SplitParams = SplitParams()
    use_hist_stack: bool = True
    hist_method: str = "segment"
    # Partitioned-segment engine: the split leaf's rows are kept contiguous
    # in a row permutation and each split touches only that segment through
    # a pow2 bucket ladder starting at this size.  0 selects the masked
    # full-scan engine (every split rescans all n rows; needed under row
    # sharding, where rows may not be gathered by global index).
    compact_min: int = 4096
    # EFB: binned is [F_groups, n] bundle codes; histograms are built in
    # group space (group_max_bin bins) and converted back to per-feature
    # space for the scan (gather + FixHistogram by subtraction)
    has_bundles: bool = False
    group_max_bin: int = 0
    # the device columns' code counts as a sorted multiset of (class
    # codes, columns) — ops/histogram.py hist_classes_of: with several
    # classes the wave kernel builds each column's one-hot at its
    # class's codes.  Never per column: the column order is
    # `FeatureMeta.hist_order`, so one table in two column orders is one
    # program.  () = every column at `max_bin` / `group_max_bin`
    hist_classes: tuple = ()
    # forced splits (ref: serial_tree_learner.cpp:614 ForceSplits):
    # static BFS-ordered (leaf, inner_feature, threshold_bin) tuples
    # applied before best-gain growth; needs use_hist_stack
    forced_splits: tuple = ()
    # interaction constraints (ref: col_sampler.hpp:91 GetByNode): static
    # tuple of tuples of inner feature indices; a leaf may split only on
    # its branch features plus sets containing the whole branch
    interaction_sets: tuple = ()
    # per-node column sampling (ref: col_sampler.hpp fraction_bynode_):
    # each leaf scan draws a fresh feature subset of this fraction
    feature_fraction_bynode: float = 1.0
    bynode_seed: int = 2
    # voting-parallel (PV-Tree, ref: voting_parallel_tree_learner.cpp):
    # a parallel.voting.VotingSpec; per-leaf scans vote on top-k features
    # and reduce only the elected histograms across the mesh.  Requires
    # the masked engine (compact_min=0), no hist stack, no bundles.
    voting: object = None
    # quantized training: num_grad_quant_bins when use_quantized_grad —
    # the wave engine's Pallas kernel then accumulates exact int32
    # histograms through the MXU int8 path (needs quant_scales at call)
    quant_bins: int = 0
    # monotone_constraints_method=intermediate (ref:
    # monotone_constraints.hpp:516 IntermediateLeafConstraints): leaf
    # hyper-rectangles in bin space + a pairwise constraint recompute and
    # full pending rescan after every split replace the reference's
    # recursive GoUp/GoDownToFindLeavesToUpdate crawl.  Requires the
    # hist stack; incompatible with extra_trees / bynode sampling.
    monotone_intermediate: bool = False
    # wave engine: once the leaf budget binds, spend at most half of it
    # per wave (closer to the leaf-wise global-gain leaf allocation; a
    # few extra cheap waves).  See PERF_NOTES.md for the measured
    # wave-vs-leafwise AUC gap this addresses.
    wave_tail_halving: bool = False
    # wave engine: overgrow the tree past num_leaves with the normal
    # (cheap, level-batched) ladder, then PRUNE back to num_leaves by
    # simulating the reference's strict leaf-wise best-gain pop order
    # over the overgrown tree's exact split gains (ref:
    # serial_tree_learner.cpp:219 ArgMax leaf order).  Recovers the
    # leaf-wise tree exactly whenever its splits lie within the
    # overgrown depth; incompatible with monotone/CEGB (their
    # gains/constraints depend on realized split order).
    wave_prune: bool = False
    wave_prune_overshoot: float = 1.5
    # prune mode: leaves of the overgrow budget reserved for narrow
    # best-gain-only "spike" waves after the broad ladder (8 per wave;
    # deep probes into the top-gain frontier, see wave.py).  0 disables.
    wave_spike_reserve: int = 0
    wave_spike_k: int = 8        # splits per spike wave
    # monotone_constraints_method=advanced (ref:
    # monotone_constraints.hpp:858 AdvancedLeafConstraints): per-(leaf,
    # feature, threshold) constraint surfaces derived from the leaf
    # rects instead of the intermediate mode's whole-leaf scalar.
    # Requires monotone_intermediate.
    monotone_advanced: bool = False
    # data-parallel mesh axis name when the engine runs INSIDE
    # jax.shard_map over sharded rows (parallel/data_parallel.py
    # make_sharded_wave_fn): every row-axis reduction (histograms, root
    # sums, exact counts) is followed by a psum over this axis — the XLA
    # collective replacing the reference's Network::ReduceScatter of
    # histograms (ref: data_parallel_tree_learner.cpp:282-295).  None in
    # single-device / GSPMD-annotated runs.
    data_axis: object = None


def gather_forced_split(hist, ffeat, fthr, sum_g, sum_h_raw, nleaf,
                        meta: "FeatureMeta", B: int, sp) -> "SplitResult":
    """Scalar SplitResult for a FORCED (feature, threshold) split of one
    leaf, gathered from its feature-space histogram [F, B, 2] (ref:
    feature_histogram GatherInfoForThreshold; serial_tree_learner.cpp:614
    ForceSplits).  Missing values join the right side (default_left=False
    matches the partition rule both engines apply).  Shared by the
    leaf-wise prologue (forced_pending) and the wave engine's forced
    waves so the gather semantics cannot diverge."""
    from ..ops.split import leaf_gain, leaf_output
    f32 = jnp.float32
    sum_h = sum_h_raw + 2e-15
    cnt_factor = nleaf / sum_h
    bins = jnp.arange(B, dtype=jnp.int32)
    nb = meta.num_bin[ffeat]
    is_na = ((meta.missing_type[ffeat] == MISSING_NAN) & (bins == nb - 1))
    # MISSING_ZERO rows (the default bin) route right, matching
    # go_left_of's default_left=False partition of this split
    is_zero = ((meta.missing_type[ffeat] == MISSING_ZERO)
               & (bins == meta.default_bin[ffeat]))
    take = (bins <= fthr) & (bins < nb) & ~is_na & ~is_zero
    hf = hist[ffeat]
    lg = jnp.sum(jnp.where(take, hf[:, 0], 0.0))
    lh_raw = jnp.sum(jnp.where(take, hf[:, 1], 0.0))
    lh = lh_raw + 1e-15
    lc = jnp.round(lh_raw * cnt_factor).astype(jnp.int32)
    rg = sum_g - lg
    rh = sum_h - lh
    rc = jnp.round(nleaf).astype(jnp.int32) - lc
    po = jnp.asarray(0.0, f32)
    gain = (leaf_gain(lg, lh, lc.astype(f32), po, sp)
            + leaf_gain(rg, rh, rc.astype(f32), po, sp))
    valid = (lc > 0) & (rc > 0)
    from ..ops.split import SplitResult
    return SplitResult(
        gain=jnp.where(valid, gain, K_MIN_SCORE),
        feature=jnp.asarray(ffeat, jnp.int32),
        threshold=jnp.asarray(fthr, jnp.int32),
        default_left=jnp.asarray(False),
        left_sum_gradient=lg, left_sum_hessian=lh - 1e-15,
        left_count=lc,
        left_output=leaf_output(lg, lh, lc.astype(f32), po, sp),
        right_sum_gradient=rg, right_sum_hessian=rh - 1e-15,
        right_count=rc,
        right_output=leaf_output(rg, rh, rc.astype(f32), po, sp),
        is_cat=jnp.asarray(False),
        cat_bitset=jnp.zeros(cat_bitset_words(B), jnp.int32))



def bundle_hist_to_features(hist_g, sum_g, sum_h, meta: "FeatureMeta",
                            B: int, hist_B: int, has_bundles: bool):
    """[F_groups, B', 2] group hist -> [F, B, 2] per-feature hist under
    EFB: each member's code range is sliced out and its default bin is
    recovered by subtraction from the leaf totals
    (ref: dataset.h:759 FixHistogram).  No-op without bundles.

    `hist_g` may carry a leading leaf axis ([N, F_groups, B', 2] with
    `sum_g`, `sum_h` [N]: the wave engine's scan of a wave's leaves).
    The decode runs under the device scope `Efb::decode`, a PART of the
    caller's `Tree::split_find` (benchmarks' efb_decode_ms); the leaf
    axis is mapped INSIDE the scope, since a scope entered under `vmap`
    reaches the ops' names as `vmap(Efb.decode)`, which no reader of
    parts matches."""
    if not has_bundles:
        return hist_g
    def one(h, sg, sh):
        return _bundle_hist_to_features(h, sg, sh, meta, B, hist_B)

    with global_timer.device_scope("Efb::decode"):
        if hist_g.ndim == 4:
            return jax.vmap(one)(hist_g, sum_g, sum_h)
        return one(hist_g, sum_g, sum_h)


def _bundle_hist_to_features(hist_g, sum_g, sum_h, meta: "FeatureMeta",
                             B: int, hist_B: int):
    cols = meta.offset[:, None] + jnp.arange(B, dtype=jnp.int32)[None, :]
    valid = ((jnp.arange(B, dtype=jnp.int32)[None, :]
              < meta.num_bin[:, None])
             & (cols < hist_B))
    hist_f = hist_g[meta.group[:, None],
                    jnp.clip(cols, 0, hist_B - 1)]          # [F, B, 2]
    hist_f = hist_f * valid[:, :, None]
    zb = meta.zero_bin
    nonzb = (jnp.arange(B, dtype=jnp.int32)[None, :] != zb[:, None])
    rest = jnp.sum(hist_f * nonzb[:, :, None], axis=1)      # [F, 2]
    fix = jnp.stack([sum_g, sum_h], -1)[None, :] - rest     # [F, 2]
    fixed = jnp.take_along_axis(
        hist_f, zb[:, None, None].repeat(2, 2), 1)
    new_zb = jnp.where(meta.in_bundle[:, None], fix, fixed[:, 0, :])
    hist_f = jnp.where(
        (jnp.arange(B, dtype=jnp.int32)[None, :, None]
         == zb[:, None, None]),
        new_zb[:, None, :], hist_f)
    return hist_f


class TreeArrays(NamedTuple):
    """Device-side grown tree (mirrors Tree's parallel arrays, ref: tree.h:25)."""
    num_leaves: jnp.ndarray       # scalar int32
    split_feature: jnp.ndarray    # [L-1] int32 (inner feature index)
    threshold_bin: jnp.ndarray    # [L-1] int32
    default_left: jnp.ndarray     # [L-1] bool
    split_gain: jnp.ndarray       # [L-1] float32
    left_child: jnp.ndarray       # [L-1] int32 (~leaf encoding)
    right_child: jnp.ndarray      # [L-1] int32
    internal_value: jnp.ndarray   # [L-1] float32
    internal_weight: jnp.ndarray  # [L-1] float32
    internal_count: jnp.ndarray   # [L-1] int32
    leaf_value: jnp.ndarray       # [L] float32
    leaf_weight: jnp.ndarray      # [L] float32
    leaf_count: jnp.ndarray       # [L] int32
    leaf_parent: jnp.ndarray      # [L] int32
    leaf_depth: jnp.ndarray       # [L] int32
    split_is_cat: jnp.ndarray = None  # [L-1] bool (categorical split)
    cat_bitset: jnp.ndarray = None    # [L-1, W] int32 bins-left bitsets
    # what growing it took (wave engine only; rides the packed tree to the
    # registry's waves_total, hist_narrowed_waves_total and
    # hist_slot_tiles_total, boosting/gbdt.py)
    waves: jnp.ndarray = None         # scalar int32: waves that ran
    narrowed_waves: jnp.ndarray = None  # of them, run below their bound
    slot_tiles: jnp.ndarray = None    # 128-wide N tiles of their calls


class _PendingSplits(NamedTuple):
    """Best pending split per leaf (ref: best_split_per_leaf_,
    serial_tree_learner.h:172)."""
    gain: jnp.ndarray           # [L]
    feature: jnp.ndarray        # [L] int32
    threshold: jnp.ndarray      # [L] int32
    default_left: jnp.ndarray   # [L] bool
    left_sum_gradient: jnp.ndarray
    left_sum_hessian: jnp.ndarray
    left_count: jnp.ndarray
    left_output: jnp.ndarray
    right_sum_gradient: jnp.ndarray
    right_sum_hessian: jnp.ndarray
    right_count: jnp.ndarray
    right_output: jnp.ndarray
    is_cat: jnp.ndarray          # [L] bool
    cat_bitset: jnp.ndarray      # [L, W] int32


class _State(NamedTuple):
    tree: TreeArrays
    pending: _PendingSplits
    leaf_id: jnp.ndarray
    hist_stack: jnp.ndarray     # [L, F, B, 2] (or [1,1,1,2] dummy)
    leaf_sum_g: jnp.ndarray     # [L]
    leaf_sum_h: jnp.ndarray     # [L]
    order: jnp.ndarray          # [n + S_max] row permutation (or [1] dummy)
    leaf_start: jnp.ndarray     # [L] segment starts (partitioned engine)
    leaf_seg_cnt: jnp.ndarray   # [L] segment lengths incl. bagged-out rows
    leaf_cmin: jnp.ndarray      # [L] monotone min constraint (or [1] dummy)
    leaf_cmax: jnp.ndarray      # [L] monotone max constraint
    cegb_used: jnp.ndarray      # [F] bool coupled-penalty paid (or [1])
    leaf_branch: jnp.ndarray    # [L, F] branch features (or [1, 1])
    done: jnp.ndarray           # scalar bool
    leaf_lo: jnp.ndarray = None  # [L, F] bin-space rect lower bounds
    leaf_hi: jnp.ndarray = None  # [L, F] rect upper bounds (exclusive)
    lazy_used: jnp.ndarray = None  # [F, n] bool rows already charged


def _pending_set(p: _PendingSplits, idx, res: SplitResult) -> _PendingSplits:
    return _PendingSplits(
        gain=p.gain.at[idx].set(res.gain),
        feature=p.feature.at[idx].set(res.feature),
        threshold=p.threshold.at[idx].set(res.threshold),
        default_left=p.default_left.at[idx].set(res.default_left),
        left_sum_gradient=p.left_sum_gradient.at[idx].set(res.left_sum_gradient),
        left_sum_hessian=p.left_sum_hessian.at[idx].set(res.left_sum_hessian),
        left_count=p.left_count.at[idx].set(res.left_count),
        left_output=p.left_output.at[idx].set(res.left_output),
        right_sum_gradient=p.right_sum_gradient.at[idx].set(res.right_sum_gradient),
        right_sum_hessian=p.right_sum_hessian.at[idx].set(res.right_sum_hessian),
        right_count=p.right_count.at[idx].set(res.right_count),
        right_output=p.right_output.at[idx].set(res.right_output),
        is_cat=p.is_cat.at[idx].set(res.is_cat),
        cat_bitset=p.cat_bitset.at[idx].set(res.cat_bitset))


def grow_tree_impl(binned: jnp.ndarray, grad: jnp.ndarray,
                   hess: jnp.ndarray, row_mask: jnp.ndarray,
                   col_mask: jnp.ndarray, meta: FeatureMeta,
                   params: GrowParams, cegb_used: jnp.ndarray = None,
                   extra_tag: jnp.ndarray = None,
                   lazy_used: jnp.ndarray = None):
    """Grow one leaf-wise tree.

    Args:
      binned: [F, n] int bin codes (n may include padded rows with row_mask=0).
      grad/hess: [n] float32 gradients/hessians.
      row_mask: [n] float32 0/1 (bagging x padding mask).
      col_mask: [F] bool (feature_fraction sampling).
      meta: per-feature bin metadata.
      params: static GrowParams.

    Returns: (TreeArrays, leaf_id [n] int32)
    """
    if params.has_bundles:
        num_features = meta.num_bin.shape[0]
    else:
        num_features = binned.shape[0]
    n = binned.shape[1]
    L = params.num_leaves
    B = params.max_bin
    hist_B = params.group_max_bin if params.has_bundles else B
    sp = params.split
    f32 = jnp.float32

    row_mask = row_mask.astype(f32)
    grad = snap_to_operand_grid(grad.astype(f32) * row_mask,
                                params.hist_method)
    hess = snap_to_operand_grid(hess.astype(f32) * row_mask,
                                params.hist_method)
    gh = jnp.stack([grad, hess], axis=1)
    ones_mask = jnp.ones((n,), dtype=f32)  # grad/hess already carry row_mask

    use_pallas = params.hist_method == "pallas"

    def to_feature_hist(hist_g, sum_g, sum_h):
        return bundle_hist_to_features(hist_g, sum_g, sum_h, meta, B,
                                       hist_B, params.has_bundles)

    def hist_of(member_mask):
        """Group-space histogram [F_groups, B', 2]; converted to feature
        space only at the scan (best_of), where the leaf sums needed by
        FixHistogram are in hand.  The per-leaf stack and the smaller-
        child subtraction stay in group space (subtraction is linear, so
        group-space subtraction == feature-space subtraction)."""
        with global_timer.device_scope("Tree::histogram"):
            if use_pallas:
                return build_histogram_rows_pallas(binned.T, gh,
                                                   member_mask,
                                                   max_bin=hist_B)
            return build_histogram(binned, gh, member_mask, max_bin=hist_B,
                                   method=params.hist_method)

    def hist_of_rows(rows, gh_sub, member_mask):
        """Histogram over row-major gathered rows [S, F_groups]."""
        with global_timer.device_scope("Tree::histogram"):
            if use_pallas:
                return build_histogram_rows_pallas(rows, gh_sub,
                                                   member_mask,
                                                   max_bin=hist_B)
            return build_histogram(rows.T, gh_sub, member_mask,
                                   max_bin=hist_B,
                                   method=params.hist_method)

    def mono_penalty_of(depth):
        """ref: monotone_constraints.hpp:357 ComputeMonotoneSplitGainPenalty."""
        pen = sp.monotone_penalty
        d = depth.astype(f32)
        eps = 1e-15
        return jnp.where(pen >= d + 1.0, eps,
                         jnp.where(pen <= 1.0,
                                   1.0 - pen / jnp.exp2(d) + eps,
                                   1.0 - jnp.exp2(pen - 1.0 - d) + eps))

    if params.interaction_sets:
        _iset_masks = [
            jnp.zeros(num_features, bool).at[jnp.asarray(S, jnp.int32)]
            .set(True) for S in params.interaction_sets]

        def allowed_of(branch):
            """[F] branch mask -> [F] allowed mask
            (ref: col_sampler.hpp:91 GetByNode)."""
            allow = branch
            for Sm in _iset_masks:
                ok = ~jnp.any(branch & ~Sm)
                allow = allow | (Sm & ok)
            return allow

    if sp.extra_trees:
        _extra_key = jax.random.PRNGKey(sp.extra_seed)
        if extra_tag is not None:
            # vary draws across trees/iterations (the reference's rand_
            # is stateful over the whole run)
            _extra_key = jax.random.fold_in(_extra_key, extra_tag)

    use_bynode = params.feature_fraction_bynode < 1.0
    if use_bynode:
        _bynode_key = jax.random.PRNGKey(params.bynode_seed)
        if extra_tag is not None:
            _bynode_key = jax.random.fold_in(_bynode_key, extra_tag)
        _bynode_k = max(1, int(round(
            params.feature_fraction_bynode * num_features)))

        def _bynode_mask(tag):
            """Exactly-k column subset per leaf scan
            (ref: col_sampler.hpp GetByNode sampling k indices)."""
            u = jax.random.uniform(jax.random.fold_in(_bynode_key, tag),
                                   (num_features,))
            kth = jax.lax.top_k(u, _bynode_k)[0][-1]
            return u >= kth

    def _rand_bins(tag):
        """One random threshold per feature for this leaf scan
        (ref: feature_histogram.hpp:204 rand.NextInt(0, num_bin - 2);
        2-bin features evaluate threshold 0)."""
        u = jax.random.uniform(jax.random.fold_in(_extra_key, tag),
                               (num_features,))
        span = jnp.maximum(meta.num_bin - 2, 1).astype(f32)
        return jnp.clip((u * span).astype(jnp.int32), 0,
                        jnp.maximum(meta.num_bin - 3, 0)).astype(jnp.int32)

    def _rand_cat_us(tag):
        """[F, 2] uniforms for the categorical USE_RAND draws (one-hot
        candidate bin + sorted-subset prefix; feature_histogram.cpp:187,268),
        from a stream distinct from the numerical draws."""
        return jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(_extra_key, 0x5EED), tag),
            (num_features, 2))

    use_voting = params.voting is not None
    if use_voting:
        assert params.compact_min == 0 and not params.use_hist_stack \
            and not params.has_bundles and not params.forced_splits, \
            "voting-parallel needs the masked engine without hist stack/EFB"
        from ..parallel.voting import voting_hist_elect

    use_intermediate = params.monotone_intermediate and sp.has_monotone
    if use_intermediate:
        assert params.use_hist_stack and not sp.extra_trees \
            and not use_bynode and not use_voting, \
            "intermediate monotone mode needs the hist stack and fixed " \
            "per-leaf scans (no extra_trees / bynode sampling / voting)"

    use_lazy = sp.has_cegb_lazy
    if use_lazy:
        assert not use_voting and not use_intermediate, \
            "cegb_penalty_feature_lazy composes with neither voting nor " \
            "intermediate monotone mode"
        if lazy_used is None:
            lazy_used = jnp.zeros((num_features, n), bool)

    def best_of(hist, sum_g, sum_h, cnt, parent_out, cmin=None, cmax=None,
                depth=None, rand_tag=0, used=None, branch=None,
                member_mask=None, lazy_mask=None, lazy_used_cur=None,
                adv=None):
        cm = col_mask
        if params.interaction_sets:
            cm = cm & allowed_of(branch)
        if use_bynode:
            cm = cm & _bynode_mask(rand_tag)
        if use_voting:
            # PV-Tree: vote + reduce only the elected features' histograms
            # (hist arg is ignored; the voted one is exact where elected)
            hist, elected = voting_hist_elect(
                binned, gh, member_mask, cm, parent_out, meta,
                params.voting, sp, hist_B, params.hist_method)
            cm = cm & elected
        kw: dict = {}
        if use_lazy:
            # per-feature on-demand cost: penalty x rows in the leaf whose
            # value for f has not been fetched yet (ref:
            # cost_effective_gradient_boosting.hpp:139)
            unused = jnp.sum(
                jnp.where(lazy_used_cur, 0.0, lazy_mask[None, :]), axis=1)
            kw["cegb_lazy_cost"] = meta.cegb_lazy * unused
        if sp.has_monotone:
            kw.update(monotone=meta.monotone, constraint_min=cmin,
                      constraint_max=cmax,
                      mono_penalty=mono_penalty_of(depth))
            if adv is not None:
                # advanced mode: per-child [F, B] constraint surfaces
                kw.update(constraint_min_left=adv[0],
                          constraint_max_left=adv[1],
                          constraint_min_right=adv[2],
                          constraint_max_right=adv[3])
        if sp.extra_trees:
            kw["rand_bin"] = _rand_bins(rand_tag)
            if sp.has_categorical:
                kw["rand_cat_u"] = _rand_cat_us(rand_tag)
        if sp.has_cegb:
            kw["cegb_coupled"] = meta.cegb_coupled
            kw["cegb_used"] = used
        with global_timer.device_scope("Tree::split_find"):
            return find_best_split(to_feature_hist(hist, sum_g, sum_h),
                                   meta.num_bin, meta.missing_type,
                                   meta.default_bin, meta.penalty, cm,
                                   sum_g, sum_h, cnt, parent_out, sp,
                                   is_cat_feature=meta.is_cat,
                                   cat_index=meta.cat_index, **kw)

    # pow2 bucket ladder for the partitioned engine; the last bucket covers
    # the whole row range (used by the root split)
    bucket_sizes = []
    if 0 < params.compact_min < n and L > 2:
        s = params.compact_min
        while s < n:
            bucket_sizes.append(s)
            s *= 2
        bucket_sizes.append(n)
        # invariant for in-bounds dynamic slices: any segment larger than the
        # biggest sub-n bucket starts within the first S_MAX rows, so
        # start + n <= n + S_MAX (the padded order length) always holds
    use_partition = bool(bucket_sizes)
    S_MAX = bucket_sizes[-2] if len(bucket_sizes) > 1 else 0
    # binned in row-major [n, F] for per-segment row gathers (loop-invariant,
    # hoisted out of the split loop by XLA)
    binned_rows = binned.T if use_partition else None

    def go_left_of(fbins, feat, dleft, thr, isc, bitset):
        """Partition rule in bin space (ref: dense_bin.hpp:346-366
        SplitInner; categorical: bin in bitset -> left, ref: tree.h:372
        CategoricalDecision with the NaN/other bin 0 never in the set).
        Under EFB, fbins are BUNDLE codes: decode the feature's range,
        anything else means the feature sits at its default bin."""
        if params.has_bundles:
            # a part of the caller's Tree::partition, as in wave.py
            with global_timer.device_scope("Efb::route"):
                local = fbins - meta.offset[feat]
                fbins = jnp.where(
                    (local >= 0) & (local < meta.num_bin[feat]),
                    local, meta.zero_bin[feat])
        mt_f = meta.missing_type[feat]
        is_missing = (((mt_f == MISSING_NAN) & (fbins == meta.num_bin[feat] - 1))
                      | ((mt_f == MISSING_ZERO) & (fbins == meta.default_bin[feat])))
        num_left = jnp.where(is_missing, dleft, fbins <= thr)
        if not sp.has_categorical:
            return num_left
        word = jnp.take(bitset, fbins // 32, mode="clip")
        cat_left = ((word >> (fbins % 32)) & 1) > 0
        return jnp.where(isc, cat_left, num_left)

    # ---- root (ref: serial_tree_learner BeforeTrain + root leaf splits) ----
    sum_g0 = jnp.sum(grad)
    sum_h0 = jnp.sum(hess)
    # explicit int32 accumulator: jnp.sum promotes int32 to int64 under
    # x64 (numpy semantics), which would widen the leaf_count scatter
    cnt0 = jnp.sum(row_mask, dtype=jnp.int32)
    root_hist = None if use_voting else hist_of(ones_mask)
    inf = jnp.asarray(jnp.inf, f32)
    if cegb_used is None:
        cegb_used = jnp.zeros(num_features if sp.has_cegb else 1, bool)
    branch0 = jnp.zeros(
        (L, num_features) if params.interaction_sets else (1, 1), bool)
    root_best = best_of(root_hist, sum_g0, sum_h0, cnt0,
                        jnp.asarray(0.0, f32), -inf, inf,
                        jnp.asarray(0, jnp.int32), rand_tag=0,
                        used=cegb_used, branch=branch0[0],
                        member_mask=row_mask, lazy_mask=row_mask,
                        lazy_used_cur=lazy_used)

    ni = max(L - 1, 1)
    W = cat_bitset_words(B)
    tree = TreeArrays(
        num_leaves=jnp.asarray(1, jnp.int32),
        split_feature=jnp.zeros(ni, jnp.int32),
        threshold_bin=jnp.zeros(ni, jnp.int32),
        default_left=jnp.zeros(ni, bool),
        split_gain=jnp.zeros(ni, f32),
        left_child=jnp.zeros(ni, jnp.int32),
        right_child=jnp.zeros(ni, jnp.int32),
        internal_value=jnp.zeros(ni, f32),
        internal_weight=jnp.zeros(ni, f32),
        internal_count=jnp.zeros(ni, jnp.int32),
        leaf_value=jnp.zeros(L, f32),
        leaf_weight=jnp.zeros(L, f32).at[0].set(sum_h0),
        leaf_count=jnp.zeros(L, jnp.int32).at[0].set(cnt0),
        leaf_parent=jnp.full(L, -1, jnp.int32),
        leaf_depth=jnp.zeros(L, jnp.int32),
        split_is_cat=jnp.zeros(ni, bool),
        cat_bitset=jnp.zeros((ni, W), jnp.int32))
    pending = _PendingSplits(
        gain=jnp.full(L, K_MIN_SCORE, f32),
        feature=jnp.zeros(L, jnp.int32), threshold=jnp.zeros(L, jnp.int32),
        default_left=jnp.zeros(L, bool),
        left_sum_gradient=jnp.zeros(L, f32), left_sum_hessian=jnp.zeros(L, f32),
        left_count=jnp.zeros(L, jnp.int32), left_output=jnp.zeros(L, f32),
        right_sum_gradient=jnp.zeros(L, f32), right_sum_hessian=jnp.zeros(L, f32),
        right_count=jnp.zeros(L, jnp.int32), right_output=jnp.zeros(L, f32),
        is_cat=jnp.zeros(L, bool), cat_bitset=jnp.zeros((L, W), jnp.int32))
    pending = _pending_set(pending, 0, root_best)

    if params.use_hist_stack:
        FH = binned.shape[0]
        hist_stack = jnp.zeros((L, FH, hist_B, 2), f32).at[0].set(root_hist)
    else:
        hist_stack = jnp.zeros((1, 1, 1, 2), f32)

    if use_partition:
        order0 = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                                  jnp.zeros(max(S_MAX, 1), jnp.int32)])
        leaf_start0 = jnp.zeros(L, jnp.int32)
        leaf_seg_cnt0 = jnp.zeros(L, jnp.int32).at[0].set(n)
    else:
        order0 = jnp.zeros(1, jnp.int32)
        leaf_start0 = jnp.zeros(1, jnp.int32)
        leaf_seg_cnt0 = jnp.zeros(1, jnp.int32)

    if use_intermediate:
        # leaf hyper-rectangles in bin space (root covers every bin)
        leaf_lo0 = jnp.zeros((L, num_features), jnp.int32)
        leaf_hi0 = jnp.broadcast_to(meta.num_bin[None, :],
                                    (L, num_features)).astype(jnp.int32)
    else:
        leaf_lo0 = leaf_hi0 = jnp.zeros((1, 1), jnp.int32)
    state = _State(tree=tree, pending=pending,
                   leaf_id=jnp.zeros(n, jnp.int32), hist_stack=hist_stack,
                   leaf_sum_g=jnp.zeros(L, f32).at[0].set(sum_g0),
                   leaf_sum_h=jnp.zeros(L, f32).at[0].set(sum_h0),
                   order=order0, leaf_start=leaf_start0,
                   leaf_seg_cnt=leaf_seg_cnt0,
                   leaf_cmin=jnp.full(L if sp.has_monotone else 1, -jnp.inf,
                                      f32),
                   leaf_cmax=jnp.full(L if sp.has_monotone else 1, jnp.inf,
                                      f32),
                   cegb_used=cegb_used,
                   leaf_branch=branch0,
                   done=jnp.asarray(False),
                   leaf_lo=leaf_lo0, leaf_hi=leaf_hi0,
                   lazy_used=(lazy_used if use_lazy
                              else jnp.zeros((1, 1), bool)))

    def partition_and_hist(st: _State, best_leaf, new_leaf, feat, thr, dleft,
                           isc, bitset):
        """Partitioned engine: read the split leaf's segment through a pow2
        bucket, partition it in place (stable: left rows first), recolor the
        right rows' leaf_id, and build the smaller child's histogram from
        only the segment's rows (ref: DataPartition::Split +
        dense_bin.hpp:99 partitioned histogram scan)."""
        start = st.leaf_start[best_leaf]
        seg_cnt = st.leaf_seg_cnt[best_leaf]

        def make_branch(S):
            def branch(operand):
                order, leaf_id = operand
                idxs = jax.lax.dynamic_slice(order, (start,), (S,))
                valid = jnp.arange(S, dtype=jnp.int32) < seg_cnt
                rows = jnp.take(binned_rows, idxs, axis=0)     # [S, F']
                col = meta.group[feat] if params.has_bundles else feat
                fbins = jnp.take(rows, col, axis=1).astype(jnp.int32)
                gl = go_left_of(fbins, feat, dleft, thr, isc, bitset)
                lm = gl & valid
                rm = (~gl) & valid
                rmask = jnp.take(row_mask, idxs)
                cnt_l = jnp.sum(lm * rmask).astype(jnp.int32)
                cnt_r = jnp.sum(rm * rmask).astype(jnp.int32)
                gh_sub = jnp.take(gh, idxs, axis=0)
                smaller_is_left = cnt_l <= cnt_r
                if params.use_hist_stack:
                    small_m = jnp.where(smaller_is_left, lm, rm)
                    small_hist = hist_of_rows(rows, gh_sub,
                                              small_m.astype(f32))
                else:  # children rebuilt from scratch downstream
                    small_hist = jnp.zeros((binned.shape[0], hist_B, 2),
                                           f32)
                # stable in-place partition of the segment window; slots
                # beyond seg_cnt keep their original values
                cl_seg = jnp.sum(lm, dtype=jnp.int32)
                pos = jnp.where(
                    lm, jnp.cumsum(lm.astype(jnp.int32)) - 1,
                    jnp.where(rm,
                              cl_seg + jnp.cumsum(rm.astype(jnp.int32)) - 1,
                              S))
                buf = idxs.at[pos].set(idxs, mode="drop")
                order = jax.lax.dynamic_update_slice(order, buf, (start,))
                leaf_id = leaf_id.at[jnp.where(rm, idxs, n)].set(
                    new_leaf, mode="drop")
                return (order, leaf_id, small_hist, cnt_l, cnt_r, cl_seg,
                        smaller_is_left)
            return branch

        branches = [make_branch(S) for S in bucket_sizes]
        k = jnp.searchsorted(jnp.asarray(bucket_sizes, jnp.int32), seg_cnt)
        k = jnp.minimum(k, len(bucket_sizes) - 1)
        with global_timer.device_scope("Tree::partition"):
            (order, leaf_id, small_hist, cnt_l, cnt_r, cl_seg,
             smaller_is_left) = jax.lax.switch(k, branches,
                                               (st.order, st.leaf_id))
        leaf_start = st.leaf_start.at[new_leaf].set(start + cl_seg)
        leaf_seg_cnt = (st.leaf_seg_cnt.at[best_leaf].set(cl_seg)
                        .at[new_leaf].set(seg_cnt - cl_seg))
        return (order, leaf_id, leaf_start, leaf_seg_cnt, small_hist,
                cnt_l, cnt_r, smaller_is_left)

    def mask_and_hist(st: _State, best_leaf, new_leaf, feat, thr, dleft,
                      isc, bitset):
        """Masked engine: recolor by scanning all rows (data-parallel safe)."""
        with global_timer.device_scope("Tree::partition"):
            col = meta.group[feat] if params.has_bundles else feat
            fbins = jnp.take(binned, col, axis=0).astype(jnp.int32)
            gl = go_left_of(fbins, feat, dleft, thr, isc, bitset)
            in_leaf = st.leaf_id == best_leaf
            leaf_id = jnp.where(in_leaf & ~gl, new_leaf, st.leaf_id)
            lmaskf = (in_leaf & gl).astype(f32) * row_mask
            rmaskf = (in_leaf & ~gl).astype(f32) * row_mask
            cnt_l = jnp.sum(lmaskf).astype(jnp.int32)
            cnt_r = jnp.sum(rmaskf).astype(jnp.int32)
        smaller_is_left = cnt_l <= cnt_r
        if params.use_hist_stack:
            small_mask = jnp.where(smaller_is_left, lmaskf, rmaskf)
            small_hist = hist_of(small_mask)
        else:  # children rebuilt from scratch downstream
            small_hist = jnp.zeros((binned.shape[0], hist_B, 2), f32)
        return (st.order, leaf_id, st.leaf_start, st.leaf_seg_cnt, small_hist,
                cnt_l, cnt_r, smaller_is_left)

    KF = len(params.forced_splits)

    def body(i, st: _State, forced_leaf=None):
        # leaf selection (ref: serial_tree_learner.cpp:219 ArgMax over leaves);
        # max_depth gates children depth (ref: serial_tree_learner BeforeFindBestSplit)
        sel_gain = st.pending.gain
        if params.max_depth > 0:
            sel_gain = jnp.where(st.tree.leaf_depth < params.max_depth,
                                 sel_gain, K_MIN_SCORE)
        if forced_leaf is not None:
            # forced splits apply regardless of gain RANK but still
            # respect max_depth and the leaf budget (sel_gain carries the
            # depth mask; ForceSplits aborts past limits)
            best_leaf = jnp.asarray(forced_leaf, jnp.int32)
            proceed = jnp.logical_and(~st.done,
                                      sel_gain[best_leaf] > K_MIN_SCORE)
            proceed = jnp.logical_and(proceed, st.tree.num_leaves < L)
        else:
            best_leaf = jnp.argmax(sel_gain).astype(jnp.int32)
            proceed = jnp.logical_and(~st.done, sel_gain[best_leaf] > 0.0)
            # dynamic budget guard: with forced splits the loop trip
            # count exceeds the remaining budget (skipped forced steps
            # hand their slot back to best-gain growth)
            proceed = jnp.logical_and(proceed, st.tree.num_leaves < L)

        def do_split(st: _State) -> _State:
            # node index == step index in pure best-gain growth (static,
            # cheaper updates); skipped forced splits make them diverge,
            # so forced configs track the actual tree size dynamically
            if params.forced_splits:
                node = st.tree.num_leaves - 1
                new_leaf = st.tree.num_leaves
            else:
                node = i
                new_leaf = i + 1
            pd = st.pending
            feat = pd.feature[best_leaf]
            thr = pd.threshold[best_leaf]
            dleft = pd.default_left[best_leaf]
            isc = pd.is_cat[best_leaf]
            bitset = pd.cat_bitset[best_leaf]

            engine = partition_and_hist if use_partition else mask_and_hist
            (order, leaf_id, leaf_start, leaf_seg_cnt, small_hist,
             cnt_l, cnt_r, smaller_is_left) = engine(
                st, best_leaf, new_leaf, feat, thr, dleft, isc, bitset)

            # --- tree arrays (ref: tree.cpp Tree::Split) ---
            t = st.tree
            parent = t.leaf_parent[best_leaf]
            # fix the parent's child pointer that referenced ~best_leaf
            lc = jnp.where((parent >= 0) & (t.left_child[parent] == ~best_leaf),
                           node, t.left_child[parent])
            rc = jnp.where((parent >= 0) & (t.left_child[parent] != ~best_leaf),
                           node, t.right_child[parent])
            left_child = t.left_child.at[parent].set(
                jnp.where(parent >= 0, lc, t.left_child[parent]))
            right_child = t.right_child.at[parent].set(
                jnp.where(parent >= 0, rc, t.right_child[parent]))
            depth = t.leaf_depth[best_leaf] + 1
            tree = TreeArrays(
                num_leaves=t.num_leaves + 1,
                split_feature=t.split_feature.at[node].set(feat),
                threshold_bin=t.threshold_bin.at[node].set(thr),
                default_left=t.default_left.at[node].set(dleft),
                split_gain=t.split_gain.at[node].set(pd.gain[best_leaf]),
                left_child=left_child.at[node].set(~best_leaf),
                right_child=right_child.at[node].set(~new_leaf),
                internal_value=t.internal_value.at[node].set(t.leaf_value[best_leaf]),
                internal_weight=t.internal_weight.at[node].set(
                    pd.left_sum_hessian[best_leaf] + pd.right_sum_hessian[best_leaf]),
                internal_count=t.internal_count.at[node].set(cnt_l + cnt_r),
                leaf_value=t.leaf_value.at[best_leaf].set(pd.left_output[best_leaf])
                                       .at[new_leaf].set(pd.right_output[best_leaf]),
                leaf_weight=t.leaf_weight.at[best_leaf].set(pd.left_sum_hessian[best_leaf])
                                         .at[new_leaf].set(pd.right_sum_hessian[best_leaf]),
                leaf_count=t.leaf_count.at[best_leaf].set(cnt_l)
                                       .at[new_leaf].set(cnt_r),
                split_is_cat=t.split_is_cat.at[node].set(isc),
                cat_bitset=t.cat_bitset.at[node].set(bitset),
                leaf_parent=t.leaf_parent.at[best_leaf].set(node)
                                         .at[new_leaf].set(node),
                leaf_depth=t.leaf_depth.at[best_leaf].set(depth)
                                       .at[new_leaf].set(depth))

            # --- child histograms: smaller fresh, larger by subtraction
            # (ref: serial_tree_learner.cpp histogram subtraction) ---
            lsum_g, lsum_h = pd.left_sum_gradient[best_leaf], pd.left_sum_hessian[best_leaf]
            rsum_g, rsum_h = pd.right_sum_gradient[best_leaf], pd.right_sum_hessian[best_leaf]
            if params.use_hist_stack:
                parent_hist = st.hist_stack[best_leaf]
                large_hist = parent_hist - small_hist
                hist_l = jnp.where(smaller_is_left, small_hist, large_hist)
                hist_r = jnp.where(smaller_is_left, large_hist, small_hist)
                hist_stack = (st.hist_stack.at[best_leaf].set(hist_l)
                              .at[new_leaf].set(hist_r))
            else:
                # rebuild both children (memory-constrained / voting mode)
                lmaskf = (leaf_id == best_leaf).astype(f32) * row_mask
                rmaskf = (leaf_id == new_leaf).astype(f32) * row_mask
                if use_voting:  # best_of builds the voted hists itself
                    hist_l = hist_r = None
                else:
                    hist_l = hist_of(lmaskf)
                    hist_r = hist_of(rmaskf)
                hist_stack = st.hist_stack

            # --- monotone constraint propagation (basic mode, ref:
            # monotone_constraints.hpp:489 BasicLeafConstraints::Update:
            # the new leaf clones the parent entry, then a numerical split
            # on a monotone feature bounds both children at the midpoint)
            if sp.has_monotone and not use_intermediate:
                p_min = st.leaf_cmin[best_leaf]
                p_max = st.leaf_cmax[best_leaf]
                mc_w = meta.monotone[feat]
                mid = (pd.left_output[best_leaf]
                       + pd.right_output[best_leaf]) / 2.0
                apply = (mc_w != 0) & ~isc
                pos = apply & (mc_w > 0)
                neg = apply & (mc_w < 0)
                l_max = jnp.where(pos, jnp.minimum(p_max, mid), p_max)
                l_min = jnp.where(neg, jnp.maximum(p_min, mid), p_min)
                r_min = jnp.where(pos, jnp.maximum(p_min, mid), p_min)
                r_max = jnp.where(neg, jnp.minimum(p_max, mid), p_max)
                leaf_cmin = (st.leaf_cmin.at[best_leaf].set(l_min)
                             .at[new_leaf].set(r_min))
                leaf_cmax = (st.leaf_cmax.at[best_leaf].set(l_max)
                             .at[new_leaf].set(r_max))
            else:
                leaf_cmin, leaf_cmax = st.leaf_cmin, st.leaf_cmax
                l_min = l_max = r_min = r_max = None

            # CEGB bookkeeping (ref: UpdateLeafBestSplits): the winning
            # feature's coupled penalty is paid once; other leaves' pending
            # gains on that feature get the penalty added back
            if sp.has_cegb:
                newly_used = ~st.cegb_used[feat]
                used_vec = st.cegb_used.at[feat].set(True)
                if meta.cegb_coupled is not None:
                    refund = jnp.where(
                        newly_used & (pd.feature == feat)
                        & (pd.gain > K_MIN_SCORE),
                        sp.cegb_tradeoff
                        * meta.cegb_coupled[feat], 0.0)
                    pd = pd._replace(gain=pd.gain + refund)
            else:
                used_vec = st.cegb_used
            if params.interaction_sets:
                child_branch = st.leaf_branch[best_leaf].at[feat].set(True)
                leaf_branch = (st.leaf_branch.at[best_leaf].set(child_branch)
                               .at[new_leaf].set(child_branch))
            else:
                child_branch = st.leaf_branch[0]
                leaf_branch = st.leaf_branch
            new_sum_g = (st.leaf_sum_g.at[best_leaf].set(lsum_g)
                         .at[new_leaf].set(rsum_g))
            new_sum_h = (st.leaf_sum_h.at[best_leaf].set(lsum_h)
                         .at[new_leaf].set(rsum_h))

            if use_lazy:
                # mark the split leaf's (bagged-in) rows as fetched for the
                # winning feature BEFORE the child scans (ref:
                # cost_effective_gradient_boosting.hpp:125-135
                # UpdateLeafBestSplits lazy branch)
                lz_l = (leaf_id == best_leaf).astype(f32) * row_mask
                lz_r = (leaf_id == new_leaf).astype(f32) * row_mask
                in_parent = (lz_l + lz_r) > 0
                new_lazy = st.lazy_used.at[feat].set(
                    st.lazy_used[feat] | in_parent)
            else:
                lz_l = lz_r = None
                new_lazy = st.lazy_used

            if use_intermediate:
                # --- intermediate mode (ref: monotone_constraints.hpp:516
                # IntermediateLeafConstraints).  TPU redesign: instead of
                # the recursive GoUp/GoDownToFindLeavesToUpdate crawl that
                # finds contiguous leaves and re-finds their splits one by
                # one, track each leaf's bin-space hyper-rectangle, derive
                # every leaf's [min, max] from the pairwise contiguity
                # relation in one vectorized pass, and re-scan ALL leaves'
                # pending splits from the histogram stack (vmapped) —
                # exactly consistent constraints after every split.
                fvec = jnp.arange(num_features, dtype=jnp.int32) == feat
                lo_s = st.leaf_lo[best_leaf]
                hi_s = st.leaf_hi[best_leaf]
                cut = (thr + 1).astype(jnp.int32)
                narrow = fvec & ~isc   # categorical splits don't narrow
                # left child keeps the best_leaf slot ([lo, cut) along
                # feat); the right child inherits the parent rect with
                # lo_feat = cut
                leaf_lo = (st.leaf_lo
                           .at[new_leaf].set(jnp.where(narrow, cut, lo_s)))
                leaf_hi = (st.leaf_hi
                           .at[new_leaf].set(hi_s)
                           .at[best_leaf].set(jnp.where(narrow, cut, hi_s)))
                out = tree.leaf_value
                alive = jnp.arange(L, dtype=jnp.int32) < tree.num_leaves
                # [L, L, F]: do rects i and j overlap along f?
                ov = ((leaf_lo[:, None, :] < leaf_hi[None, :, :])
                      & (leaf_lo[None, :, :] < leaf_hi[:, None, :]))
                nov = (~ov).astype(jnp.int32)
                n_false = jnp.sum(nov, axis=2)
                # overlap in every feature except f (contiguity slice)
                exc = (n_false[:, :, None] - nov) == 0
                below = leaf_hi[:, None, :] <= leaf_lo[None, :, :]
                belowT = jnp.swapaxes(below, 0, 1)
                incf = (meta.monotone > 0)[None, None, :]
                decf = (meta.monotone < 0)[None, None, :]
                valid = (alive[None, :, None] & exc
                         & ~jnp.eye(L, dtype=bool)[:, :, None])
                # j's output upper-bounds i when j sits on i's increasing
                # side of an increasing feature (or decreasing side of a
                # decreasing one); lower bounds mirror it
                ubm = valid & ((below & incf) | (belowT & decf))
                lbm = valid & ((belowT & incf) | (below & decf))
                outj = out[None, :, None]
                leaf_cmax = jnp.min(jnp.where(ubm, outj, jnp.inf),
                                    axis=(1, 2))
                leaf_cmin = jnp.max(jnp.where(lbm, outj, -jnp.inf),
                                    axis=(1, 2))
                branch_all = (leaf_branch if params.interaction_sets
                              else jnp.zeros((L, 1), bool))

                if params.monotone_advanced:
                    # --- advanced mode (ref: monotone_constraints.hpp:858
                    # AdvancedLeafConstraints).  TPU redesign: instead of
                    # the reference's per-threshold constraint lists built
                    # by recursive tree crawls, derive PER-(leaf, feature,
                    # threshold) constraint surfaces from the leaf rects.
                    # A candidate split of leaf i on feature f at bin t
                    # makes children whose rects differ from i's only
                    # along f, so whether neighbor j bounds a child via
                    # monotone feature f' reduces to threshold-interval
                    # conditions on t — each contribution is a prefix or
                    # suffix interval of bins, aggregated with scatter-min
                    # plus a cumulative min/max along the bin axis.
                    i32_ = jnp.int32
                    inf = jnp.inf
                    inc, dec = incf, decf
                    novi = nov.astype(jnp.int32)           # [L, L, F]
                    # j bounds i above/below via feature g (parent rects)
                    sA = (below & inc) | (belowT & dec)    # [L, L, F]
                    sB = (belowT & inc) | (below & dec)
                    valid0 = (alive[None, :] & ~jnp.eye(L, dtype=bool))
                    # all features except f overlap / exactly one other
                    # non-overlapping feature
                    contig0 = (n_false[:, :, None] - novi) == 0
                    contig1 = (n_false[:, :, None] - novi) == 1
                    sA_any = jnp.sum(sA, axis=2, dtype=i32_)
                    sB_any = jnp.sum(sB, axis=2, dtype=i32_)
                    qual3A = contig1 & ((sA_any[:, :, None]
                                         - sA.astype(i32_)) >= 1)
                    qual3B = contig1 & ((sB_any[:, :, None]
                                         - sB.astype(i32_)) >= 1)
                    v0 = valid0[:, :, None]
                    lo_j = leaf_lo[None, :, :]             # [1, L, F]
                    hi_j = leaf_hi[None, :, :]
                    lo_i = leaf_lo[:, None, :]
                    hi_i = leaf_hi[:, None, :]
                    ovf = lo_i < hi_j                      # child-f overlap
                    ovf_r = lo_j < hi_i
                    B_ = B
                    ii = jnp.broadcast_to(
                        jnp.arange(L, dtype=i32_)[:, None, None], below.shape)
                    ff = jnp.broadcast_to(
                        jnp.arange(num_features,
                                   dtype=i32_)[None, None, :], below.shape)
                    ojb = jnp.broadcast_to(outj, below.shape)

                    def smin(gate, pos):
                        """[L, F, B] scatter-min of out_j at bin pos."""
                        p = jnp.where(gate & (pos >= 0), pos, B_)
                        return (jnp.full((L, num_features, B_ + 1), inf,
                                         f32)
                                .at[ii, ff, p].min(
                                    jnp.where(gate, ojb, inf))[:, :, :B_])

                    def smax(gate, pos):
                        p = jnp.where(gate & (pos >= 0), pos, B_)
                        return (jnp.full((L, num_features, B_ + 1), -inf,
                                         f32)
                                .at[ii, ff, p].max(
                                    jnp.where(gate, ojb, -inf))[:, :, :B_])

                    cummin_f = lambda a: jax.lax.cummin(a, axis=2)
                    cummin_r = lambda a: jax.lax.cummin(a, axis=2,
                                                        reverse=True)
                    cummax_f = lambda a: jax.lax.cummax(a, axis=2)
                    cummax_r = lambda a: jax.lax.cummax(a, axis=2,
                                                        reverse=True)

                    def cst(gate):
                        """[L, F] constant min over qualifying j."""
                        return jnp.min(jnp.where(gate, ojb, inf), axis=1)

                    def cst_max(gate):
                        return jnp.max(jnp.where(gate, ojb, -inf), axis=1)

                    # UPPER bounds, LEFT child ([lo_i, t+1) along f):
                    #  f'=f inc: t < lo_j  -> bins [0, lo_j): suffix min
                    #  f'=f dec: hi_j <= lo_i (belowT): all t
                    #  f'!=f: parent side + child overlaps j along f:
                    #         t >= lo_j -> prefix min
                    uL = jnp.minimum(
                        cummin_r(smin(v0 & contig0 & inc, lo_j - 1)),
                        cst(v0 & contig0 & dec & belowT)[:, :, None])
                    uL = jnp.minimum(
                        uL, cummin_f(smin(v0 & qual3A & ovf, lo_j)))
                    # UPPER bounds, RIGHT child ([t+1, hi_i)):
                    #  f'=f inc: hi_i <= lo_j (below): all t
                    #  f'=f dec: t >= hi_j - 1 -> prefix min
                    #  f'!=f: t <= hi_j - 2 -> suffix min
                    uR = jnp.minimum(
                        cst(v0 & contig0 & inc & below)[:, :, None],
                        cummin_f(smin(v0 & contig0 & dec, hi_j - 1)))
                    uR = jnp.minimum(
                        uR, cummin_r(smin(v0 & qual3A & ovf_r, hi_j - 2)))
                    # LOWER bounds mirror with sB / swapped sides
                    lL = jnp.maximum(
                        cummax_r(smax(v0 & contig0 & dec, lo_j - 1)),
                        cst_max(v0 & contig0 & inc & belowT)[:, :, None])
                    lL = jnp.maximum(
                        lL, cummax_f(smax(v0 & qual3B & ovf, lo_j)))
                    lR = jnp.maximum(
                        cst_max(v0 & contig0 & dec & below)[:, :, None],
                        cummax_f(smax(v0 & contig0 & inc, hi_j - 1)))
                    lR = jnp.maximum(
                        lR, cummax_r(smax(v0 & qual3B & ovf_r, hi_j - 2)))
                    adv_all = (lL, uL, lR, uR)

                    def _rescan(h, sg, sh, c, po, mn, mx, d, br, a0, a1,
                                a2, a3):
                        return best_of(h, sg, sh, c, po, mn, mx, d,
                                       rand_tag=0, used=used_vec, branch=br,
                                       adv=(a0, a1, a2, a3))

                    res = jax.vmap(_rescan)(
                        hist_stack, new_sum_g, new_sum_h, tree.leaf_count,
                        tree.leaf_value, leaf_cmin, leaf_cmax,
                        tree.leaf_depth, branch_all, *adv_all)
                else:
                    def _rescan(h, sg, sh, c, po, mn, mx, d, br):
                        return best_of(h, sg, sh, c, po, mn, mx, d,
                                       rand_tag=0, used=used_vec, branch=br)

                    res = jax.vmap(_rescan)(
                        hist_stack, new_sum_g, new_sum_h, tree.leaf_count,
                        tree.leaf_value, leaf_cmin, leaf_cmax,
                        tree.leaf_depth, branch_all)
                pending = _PendingSplits(
                    gain=jnp.where(alive, res.gain, K_MIN_SCORE),
                    feature=res.feature, threshold=res.threshold,
                    default_left=res.default_left,
                    left_sum_gradient=res.left_sum_gradient,
                    left_sum_hessian=res.left_sum_hessian,
                    left_count=res.left_count,
                    left_output=res.left_output,
                    right_sum_gradient=res.right_sum_gradient,
                    right_sum_hessian=res.right_sum_hessian,
                    right_count=res.right_count,
                    right_output=res.right_output,
                    is_cat=res.is_cat, cat_bitset=res.cat_bitset)
            else:
                leaf_lo, leaf_hi = st.leaf_lo, st.leaf_hi
                # tag spaces: forced prologue steps use [1..2KF], the main
                # loop [2KF+1..] — no collision between the two phases
                tag_base = i if forced_leaf is not None else i + KF
                best_l = best_of(hist_l, lsum_g, lsum_h, cnt_l,
                                 pd.left_output[best_leaf], l_min, l_max,
                                 depth, rand_tag=2 * tag_base + 1,
                                 used=used_vec, branch=child_branch,
                                 member_mask=lmaskf if use_voting else None,
                                 lazy_mask=lz_l, lazy_used_cur=new_lazy)
                best_r = best_of(hist_r, rsum_g, rsum_h, cnt_r,
                                 pd.right_output[best_leaf], r_min, r_max,
                                 depth, rand_tag=2 * tag_base + 2,
                                 used=used_vec, branch=child_branch,
                                 member_mask=rmaskf if use_voting else None,
                                 lazy_mask=lz_r, lazy_used_cur=new_lazy)
                pending = _pending_set(_pending_set(pd, best_leaf, best_l),
                                       new_leaf, best_r)
            return _State(tree=tree, pending=pending, leaf_id=leaf_id,
                          hist_stack=hist_stack,
                          leaf_sum_g=new_sum_g,
                          leaf_sum_h=new_sum_h,
                          order=order, leaf_start=leaf_start,
                          leaf_seg_cnt=leaf_seg_cnt,
                          leaf_cmin=leaf_cmin, leaf_cmax=leaf_cmax,
                          cegb_used=used_vec,
                          leaf_branch=leaf_branch,
                          done=st.done,
                          leaf_lo=leaf_lo, leaf_hi=leaf_hi,
                          lazy_used=new_lazy)

        if forced_leaf is not None:
            # an invalid forced split (empty child) is skipped; growth
            # continues (ForceSplits abandons forcing, not the tree)
            return jax.lax.cond(proceed, do_split, lambda s: s, st)
        return jax.lax.cond(proceed, do_split,
                            lambda s: s._replace(done=jnp.asarray(True)), st)

    def forced_pending(st: _State, leaf, feat, thr):
        """Pending entry for a forced (feature, threshold) split of
        `leaf` (shared gather: gather_forced_split)."""
        hist = bundle_hist_to_features(
            st.hist_stack[leaf], st.leaf_sum_g[leaf], st.leaf_sum_h[leaf],
            meta, B, hist_B, params.has_bundles)
        res = gather_forced_split(
            hist, feat, thr, st.leaf_sum_g[leaf], st.leaf_sum_h[leaf],
            st.tree.leaf_count[leaf].astype(f32), meta, B, sp)
        return st._replace(pending=_pending_set(st.pending, leaf, res))

    forcing_ok = jnp.asarray(True)
    for k, (fleaf, ffeat, fthr) in enumerate(params.forced_splits):
        if k >= L - 1:
            break
        old_pending = state.pending
        old_nl = state.tree.num_leaves
        state = forced_pending(state, fleaf, ffeat, fthr)
        # the parse-time BFS leaf numbers are only valid while every
        # forced split applies; after the first skip, abort the rest
        # (ForceSplits' abort semantics) by poisoning the forced gain
        state = state._replace(pending=state.pending._replace(
            gain=jnp.where(forcing_ok, state.pending.gain, K_MIN_SCORE)))
        state = body(k, state, forced_leaf=fleaf)
        applied = state.tree.num_leaves > old_nl
        forcing_ok = forcing_ok & applied
        # a skipped forced split must not clobber the leaf's real
        # pending entry (growth continues on real gains)
        state = state._replace(pending=jax.tree.map(
            lambda new, old: jnp.where(applied, new, old),
            state.pending, old_pending))
    if L > 1:
        # the full trip count runs even after forced steps: skipped
        # forced splits return their slot to best-gain growth, and the
        # dynamic num_leaves < L guard in body enforces the budget
        state = jax.lax.fori_loop(0, L - 1, body, state)
    if use_lazy:
        # persistent per-(feature, row) fetched bitset rides along so the
        # driver can thread it into the next tree
        return state.tree, state.leaf_id, state.lazy_used
    return state.tree, state.leaf_id


# two jit entries over the same tracer program: the boosting loop's
# default donates the per-class grad/hess slices (their buffers die
# here — XLA reuses the HBM for the tree program's scratch instead of
# holding both), while linear-tree training, which re-reads the slices
# for leaf fitting after growth, keeps the non-donating entry
# (boosting/gbdt.py selects; docs/Performance.md)
# tpulint: disable-next=donate-argnums -- linear-tree training reuses grad/hess after growth; the default loop takes grow_tree_donated
grow_tree = jax.jit(grow_tree_impl, static_argnames=("params",))
grow_tree_donated = jax.jit(grow_tree_impl, static_argnames=("params",),
                            donate_argnums=(1, 2))


def make_grow_tree(params: GrowParams):
    """Partial application helper so callers hold one traced function."""
    def fn(binned, grad, hess, row_mask, col_mask, meta):
        return grow_tree(binned, grad, hess, row_mask, col_mask, meta, params)
    return fn
