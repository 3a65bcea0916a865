"""Wave (level-batched best-first) tree growth — the TPU-fast engine.

Strict leaf-wise growth (learner/grow.py) splits one leaf per step: 254
sequential fori_loop iterations of gathers and bucket bookkeeping for a
255-leaf tree, which on TPU is dominated by per-op overheads rather than
FLOPs.  The wave engine instead splits EVERY positive-gain leaf per round
(capped by the num_leaves budget, best-gain-first like the reference's leaf
ordering), so a tree takes ~log2(num_leaves) rounds of fully vectorized
work:

  1. one fused multi-leaf Pallas histogram pass over all rows
     (ops/histogram.py build_histogram_wave — all leaves' histograms in one
     MXU sweep whose output columns are leaf slots; ref:
     cuda_histogram_constructor.cu builds per-leaf histograms in shared
     memory the same way),
  2. one vmapped gain scan over [NLp, F, B] (ref:
     feature_histogram.hpp:192 FindBestThreshold, batched over leaves),
  3. one recolour pass (ops/recolour.py: rows look up their leaf's split
     in one small per-wave table, a row-tiled Pallas call with rows on
     lanes; ref: dense_bin.hpp:346 SplitInner applied to all splitting
     leaves at once).

The wave loop is UNROLLED over ceil(log2(num_leaves)) rounds with a
per-round slot bound (8, 16, ..., padded num_leaves), so early rounds pay
kernels sized to the leaves that actually exist; each round is wrapped in
lax.cond and skipped once no leaf splits.

Tree shape: identical to leaf-wise when split gains decrease monotonically
with depth (the common case on real losses); on non-monotone gain
landscapes leaf-wise may deepen one branch where wave spreads a level, a
quality-neutral tradeoff (XGBoost's depthwise analogue).  When the
num_leaves budget binds mid-round only the highest-gain leaves split,
matching leaf-wise's preference.  All row-axis ops are reductions/maps, so
the engine shards over a data mesh without changes.

Counts: the per-wave gain scan and the stored tree use EXACT partition
counts from a third histogram channel accumulating the row mask (the
reference's DataPartition counts, tree.cpp Tree::Split); the per-bin counts
inside the scan remain the reference's RoundInt(hess * cnt_factor)
approximation for parity (feature_histogram.hpp:871-874).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..ops.histogram import (plan_wave_kernel, snap_to_operand_grid,
                             spike_true_slots, wave_histograms,
                             wave_slot_pad)
from ..ops.recolour import (pack_table, recolour_wave, recolour_xla,
                            table_layout)
from ..ops.split import (K_MIN_SCORE, SplitResult, cat_bitset_words,
                         find_best_split, find_best_split_dense)
from .grow import (FeatureMeta, GrowParams, TreeArrays,
                   bundle_hist_to_features, gather_forced_split)
from ..utils.timer import global_timer


def _hist_wave_xla(binned_fm, slot, gh, *, max_bin, num_slots):
    """XLA fallback (CPU tests): per-slot masked histograms via one-hot
    einsum.  Small shapes only.  gh is [C+1, n] like the Pallas kernels'
    (its LAST row is the count mask); returns (hist [NL, F, B, C],
    counts [NL]) like them."""
    oh_slot = (slot[:, None]
               == jnp.arange(num_slots, dtype=jnp.int32)[None, :])  # [n, NL]
    oh_bin = (binned_fm[:, :, None] ==
              jnp.arange(max_bin, dtype=jnp.int32)[None, None, :])  # [F,n,B]
    # [NL, F, B, C]; histograms are exact accumulators, so force fp32
    # contraction (the TPU default would round operands to bf16)
    hist = jnp.einsum("nl,fnb,cn->lfbc", oh_slot.astype(jnp.float32),
                      oh_bin.astype(jnp.float32), gh[:-1],
                      precision=jax.lax.Precision.HIGHEST)
    counts = jnp.einsum("nl,n->l", oh_slot.astype(jnp.float32), gh[-1],
                        precision=jax.lax.Precision.HIGHEST)
    return hist, counts


def grow_tree_wave_impl(binned: jnp.ndarray, grad: jnp.ndarray,
                        hess: jnp.ndarray, row_mask: jnp.ndarray,
                        col_mask: jnp.ndarray,
                        meta: FeatureMeta, params: GrowParams,
                        cegb_used: jnp.ndarray = None,
                        extra_tag: jnp.ndarray = None,
                        quant_scales: jnp.ndarray = None,
                        binned_classed: jnp.ndarray = None):
    """Grow one tree by waves.  Same contract as grow.grow_tree, plus
    `binned_classed`: `binned[meta.hist_order]` (ops/histogram.py
    class_ordered), which a booster whose columns hold several
    `params.hist_classes` makes once and hands to every tree."""

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
    i32 = jnp.int32

    use_pallas = params.hist_method == "pallas"
    use_int8 = (use_pallas and params.quant_bins > 0
                and quant_scales is not None)
    # Which form the gain scan takes, by what the configuration shows:
    # plain numerical features are scanned for all leaves of a wave at
    # once, straight from the cache's rows — `find_best_split_dense`.
    # EFB's per-feature gather, the categorical scan's sub-array and the
    # monotone constraint surfaces take a leaf's [F, B, 2], so with any
    # of them each leaf goes through `find_best_split` under a vmap.
    dense_scan = not (params.has_bundles or sp.has_categorical
                      or sp.has_monotone)

    with global_timer.device_scope("Tree::hist_operands"):
        row_mask = row_mask.astype(f32)
        grad = grad.astype(f32) * row_mask
        hess = hess.astype(f32) * row_mask
        if not use_int8:
            # (the int8 path recovers exact grid integers from k * scale)
            grad = snap_to_operand_grid(grad, params.hist_method)
            hess = snap_to_operand_grid(hess, params.hist_method)
        # 2 histogram channels; the trailing row is the count mask
        # consumed by the kernel's fused per-slot count output (output
        # lanes are the MXU cost driver — see _wave_kernel).  [C+1, n],
        # rows on lanes: the layout the three vectors are born in and the
        # kernels' blocks read, so it is built once a tree and no wave
        # re-lays it out (stacked on the minor axis it came out
        # column-major, 512 B a row once padded to the chip's tiling)
        gh = jnp.stack([grad, hess, row_mask], axis=0)

    # Under shard_map (parallel/data_parallel.py) rows are the local shard:
    # every row-axis reduction is completed by a psum over the data axis —
    # the same computed-slot histogram reduction the reference's
    # distributed learner performs with Network::ReduceScatter
    # (ref: data_parallel_tree_learner.cpp:282-295).  All other state
    # (tree arrays, caches, gain scan) is replicated, so the bookkeeping
    # needs no synchronization — the reference's SyncUpGlobalBestSplit
    # (:441) becomes a no-op by construction.
    def _psum(x):
        if params.data_axis is None:
            return x
        # the collective replacing the reference's Network::ReduceScatter
        # of histograms (data_parallel_tree_learner.cpp:282-295); tagged
        # so profiler timelines show time-in-collectives per wave
        with global_timer.device_scope("Network::psum"):
            # tpulint: disable-next=collective-discipline -- the wave engine's single histogram/count reduction point; parallel/data_parallel.py wraps this engine in shard_map and owns the data_axis contract
            return jax.lax.psum(x, params.data_axis)

    # several classes of column codes, and their class-ordered copy
    # handed over: the full kernel reads that and `wave_histograms` hands
    # the histograms back in this engine's order; one class (and the
    # int8 arm) is the call on `binned` itself
    hist_classes = (params.hist_classes
                    if use_pallas and not use_int8
                    and binned_classed is not None
                    and len(params.hist_classes) > 1 else ())
    classed = (dict(hist_classes=hist_classes,
                    binned_classed=binned_classed,
                    hist_inverse=meta.hist_inverse)
               if hist_classes else {})
    binned_rm = None
    # both of the plan's `wave_hl` gates only close as the slots grow:
    # where one slot is refused (2,000 features: its ungrouped blocks
    # count 116 MB of VMEM) no wave of this tree can use the row-major
    # copy, and it is not built (0.8 GB there)
    hl_at_one_slot = plan_wave_kernel(
        binned.shape[0], hist_B, 1, 1, int8=use_int8,
        hist_classes=hist_classes).kernel == "wave_hl"
    if use_pallas and hl_at_one_slot:
        # row-major copy for the decomposed small-S kernel's lo side
        # (transposed once per tree; bins are static so XLA keeps it
        # resident for all waves of the tree)
        with global_timer.device_scope("Tree::hist_operands"):
            binned_rm = binned.T
    # quantized grid grads -> exact int32 accumulation through the MXU
    # int8 path (ref: dense_bin.hpp:174 ConstructHistogramIntInner)
    quant = (dict(quant_bins=params.quant_bins, quant_scales=quant_scales)
             if use_int8 else {})

    def hists_of(kslot, ghm, num_slots, true_slots=None):
        """Group-space histograms for the COMPUTED (compact) slots only;
        rows outside computed leaves carry zeroed gh channels.  The full
        per-leaf set is completed by sibling subtraction at the cache.
        `true_slots` (<= num_slots) is the unpadded computed-slot bound:
        when it is small the decomposed hi/lo kernel streams far less
        VMEM volume (ops/histogram.py plan_wave_kernel picks)."""
        with global_timer.device_scope("Tree::histogram"):
            if use_pallas:
                H, cnt = wave_histograms(
                    binned, binned_rm, kslot, ghm, max_bin=hist_B,
                    num_slots=num_slots, true_slots=true_slots, **quant,
                    **classed)
            else:
                H, cnt = _hist_wave_xla(binned, kslot, ghm, max_bin=hist_B,
                                        num_slots=num_slots)
            # shard-local -> global (psum is a no-op single-device)
            return _psum(H), _psum(cnt)

    if sp.extra_trees:
        _extra_key = jax.random.PRNGKey(sp.extra_seed)
        if extra_tag is not None:
            _extra_key = jax.random.fold_in(_extra_key, extra_tag)

        def _rand_bins(tag):
            """[NLp_max, F] random thresholds for this wave's leaf scans
            (ref: feature_histogram.hpp:204 USE_RAND; 2-bin features
            evaluate threshold 0)."""
            u = jax.random.uniform(jax.random.fold_in(_extra_key, tag),
                                   (Lp, num_features))
            span = jnp.maximum(meta.num_bin - 2, 1).astype(f32)[None, :]
            return jnp.clip((u * span).astype(jnp.int32), 0,
                            jnp.maximum(meta.num_bin - 3, 0)[None, :]
                            ).astype(jnp.int32)

        def _rand_cat_us(tag):
            """[NLp_max, F, 2] uniforms for the categorical USE_RAND
            draws (feature_histogram.cpp:187,268)."""
            return jax.random.uniform(
                jax.random.fold_in(jax.random.fold_in(_extra_key, 0x5EED),
                                   tag), (Lp, num_features, 2))

    if sp.has_monotone:
        def _pen_of(depth):
            """ref: monotone_constraints.hpp:357."""
            pen, d = sp.monotone_penalty, depth.astype(f32)
            return jnp.where(pen >= d + 1.0, 1e-15,
                             jnp.where(pen <= 1.0,
                                       1.0 - pen / jnp.exp2(d) + 1e-15,
                                       1.0 - jnp.exp2(pen - 1.0 - d)
                                       + 1e-15))

    use_interaction = bool(params.interaction_sets)
    if use_interaction:
        _iset_masks = jnp.stack([
            jnp.zeros(num_features, bool).at[jnp.asarray(S, jnp.int32)]
            .set(True) for S in params.interaction_sets])    # [S, F]

        def _allowed_of(branch):
            """[NLp, F] branch masks -> [NLp, F] allowed masks (ref:
            col_sampler.hpp:91 GetByNode, vectorized over leaves): a
            feature is allowed iff it lies in some constraint set that
            contains the leaf's whole branch, or is itself on the
            branch."""
            ok = ~jnp.any(branch[:, None, :] & ~_iset_masks[None, :, :],
                          axis=2)                            # [NLp, S]
            return branch | jnp.any(
                ok[:, :, None] & _iset_masks[None, :, :], axis=1)

    use_bynode = params.feature_fraction_bynode < 1.0
    if use_bynode:
        _bynode_key = jax.random.PRNGKey(params.bynode_seed)
        if extra_tag is not None:
            _bynode_key = jax.random.fold_in(_bynode_key, extra_tag)
        _bynode_k = max(1, int(round(
            params.feature_fraction_bynode * num_features)))

        def _bynode_masks(tag):
            """[NLp_max, F] exactly-k column subsets per leaf scan
            (ref: col_sampler.hpp GetByNode)."""
            u = jax.random.uniform(jax.random.fold_in(_bynode_key, tag),
                                   (Lp, num_features))
            kth = jax.lax.top_k(u, _bynode_k)[0][:, -1:]
            return u >= kth

    def _best_one(h, sg, sh, c, po, cmin, cmax, dep, rb, rcu, used, bym):
        kw = {}
        if sp.has_monotone:
            kw.update(monotone=meta.monotone, constraint_min=cmin,
                      constraint_max=cmax, mono_penalty=_pen_of(dep))
        if sp.extra_trees:
            kw["rand_bin"] = rb
            if sp.has_categorical:
                kw["rand_cat_u"] = rcu
        if sp.has_cegb:
            kw["cegb_coupled"] = meta.cegb_coupled
            kw["cegb_used"] = used
        cm = col_mask if bym is None else (col_mask & bym)
        return find_best_split(
            h, meta.num_bin, meta.missing_type, meta.default_bin,
            meta.penalty, cm, sg, sh, c, po, sp,
            is_cat_feature=meta.is_cat, **kw)

    best_vm = jax.vmap(_best_one,
                       in_axes=(0, 0, 0, 0, 0,
                                0 if sp.has_monotone else None,
                                0 if sp.has_monotone else None,
                                0 if sp.has_monotone else None,
                                0 if sp.extra_trees else None,
                                0 if (sp.extra_trees
                                      and sp.has_categorical) else None,
                                None,
                                0 if (use_bynode or use_interaction)
                                else None))

    def best_of(rows, sg, sh, c, po, cmin, cmax, dep, rb, rcu, used, bym):
        """Best split of each leaf whose cache row is in `rows` [N, Dh]."""
        if not dense_scan:
            # bundle columns -> per-feature bins for all of `rows` first
            # (its own part, Efb::decode, of the caller's scope), then
            # the scan a leaf: the ops `vmap` made of both as one
            hists = bundle_hist_to_features(
                rows.reshape(-1, Fh, hist_B, 2), sg, sh, meta, B, hist_B,
                params.has_bundles)
            return best_vm(hists, sg, sh, c, po, cmin, cmax, dep, rb, rcu,
                           used, bym)
        kw = {}
        if sp.extra_trees:
            kw["rand_bin"] = rb
        if sp.has_cegb:
            kw.update(cegb_coupled=meta.cegb_coupled, cegb_used=used)
        return find_best_split_dense(
            rows, meta.num_bin, meta.missing_type, meta.default_bin,
            meta.penalty, col_mask if bym is None else (col_mask & bym),
            sg, sh, c, po, sp, max_bin=B, **kw)

    # incremental gain scan: a leaf's best split depends only on its own
    # histogram/sums, which change ONLY when the leaf is created — so in
    # the plain mode the per-wave scan touches just the <= 2*Kb leaves
    # the previous wave created instead of all NLp (the reference
    # likewise scans only the two fresh leaves per split,
    # serial_tree_learner.cpp:340 FindBestSplits).  Modes whose scan
    # inputs change globally per wave (fresh extra-trees/bynode draws,
    # branch-dependent interaction masks, monotone constraint updates,
    # CEGB's used-feature set) keep the full rescan.
    incremental_scan = not (sp.extra_trees or use_bynode
                            or use_interaction or sp.has_monotone
                            or sp.has_cegb)

    sum_g0 = _psum(jnp.sum(grad))
    sum_h0 = _psum(jnp.sum(hess))
    cnt0 = _psum(jnp.sum(row_mask)).astype(i32)

    # overgrow-and-prune quality mode (see GrowParams.wave_prune): the
    # ladder grows to Lg > L leaves, then the leaf-wise pop order is
    # simulated over the overgrown gains and the tree pruned back to L
    # prune composes with tail_halving: halving only changes WHICH nodes
    # the overgrown ladder explores (gain-adaptive tail allocation), the
    # replay then picks the leaf-wise order over whatever was grown.
    # Forced splits disable prune: the replay ranks by gain and could
    # discard a forced node (the reference keeps forced splits
    # unconditionally, serial_tree_learner.cpp:614).
    prune = (params.wave_prune and L > 2 and not sp.has_monotone
             and not sp.has_cegb and not params.forced_splits)
    Lg = (min(max(L, int(math.ceil(L * params.wave_prune_overshoot))),
              4 * L) if prune else L)
    # spike waves (prune mode): reserve part of the overgrow budget for
    # a few best-gain-ONLY waves after the broad ladder — narrow deep
    # probes into the top-gain frontier, which is where the leaf-wise
    # order spends the splits the level-uniform ladder misses (the
    # "exploration adaptivity" residual of PERF_NOTES).  Each spike wave
    # computes <= 8 slots, so it rides the cheap decomposed hi/lo kernel.
    spike_k = int(getattr(params, "wave_spike_k", 8) or 8)
    spike_waves = (int(params.wave_spike_reserve) // spike_k
                   if prune and L >= 8 * spike_k else 0)
    reserve = min(spike_waves * spike_k, max(Lg - L, 0))
    spike_waves = reserve // spike_k
    Lg_main = Lg - spike_waves * spike_k

    ni = max(Lg - 1, 1)
    W = cat_bitset_words(B)
    # leaf-indexed arrays are sized to the padded slot bound (>= Lg) so
    # static [:NLp] slices stay in range; sliced back to [L] on return
    Lp = wave_slot_pad(Lg)
    tree = TreeArrays(
        num_leaves=jnp.asarray(1, i32),
        split_feature=jnp.zeros(ni, i32),
        threshold_bin=jnp.zeros(ni, i32),
        default_left=jnp.zeros(ni, bool),
        split_gain=jnp.zeros(ni, f32),
        left_child=jnp.zeros(ni, i32),
        right_child=jnp.zeros(ni, i32),
        internal_value=jnp.zeros(ni, f32),
        internal_weight=jnp.zeros(ni, f32),
        internal_count=jnp.zeros(ni, i32),
        leaf_value=jnp.zeros(Lp, f32),
        leaf_weight=jnp.zeros(Lp, f32).at[0].set(sum_h0),
        leaf_count=jnp.zeros(Lp, i32).at[0].set(cnt0),
        leaf_parent=jnp.full(Lp, -1, i32),
        leaf_depth=jnp.zeros(Lp, i32),
        split_is_cat=jnp.zeros(ni, bool),
        cat_bitset=jnp.zeros((ni, W), i32),
        waves=jnp.asarray(0, i32))

    # per-leaf running sums / outputs for the gain scan
    leaf_sum_g0 = jnp.zeros(Lp, f32).at[0].set(sum_g0)
    leaf_sum_h0 = jnp.zeros(Lp, f32).at[0].set(sum_h0)
    leaf_out0 = jnp.zeros(Lp, f32)
    cm_n = Lp if sp.has_monotone else 1
    leaf_cmin0 = jnp.full(cm_n, -jnp.inf, f32)
    leaf_cmax0 = jnp.full(cm_n, jnp.inf, f32)

    # per-leaf histogram cache (flat [Lp, F'*B'*2] for MXU-friendly
    # selection matmuls; a row is a leaf's histogram in (feature, bin,
    # channel) order) + exact count cache, carried across waves (the
    # HistogramPool analogue, feature_histogram.hpp:1367); completed by
    # sibling subtraction
    Fh = binned.shape[0]
    Dh = Fh * hist_B * 2
    cache_h0 = jnp.zeros((Lp, Dh), f32)
    cache_c0 = jnp.zeros(Lp, f32)
    # pending-split tables from the previous wave (Lp-indexed by the slot
    # that split): new right slot, pair rank (= compact kernel slot of the
    # smaller child), smaller-side flag
    pend_sel0 = jnp.zeros(Lp, bool)
    pend_new0 = jnp.zeros(Lp, i32)
    pend_rank0 = jnp.zeros(Lp, i32)
    pend_sl0 = jnp.zeros(Lp, bool)

    def wave_hists(kslot, cache_h, cache_c,
                   pend_sel, pend_new, pend_rank, pend_sl, Kb, first,
                   Ks=None):
        """Update the per-leaf histogram cache for the leaves created by
        the previous wave: ONE fused kernel pass computes the SMALLER
        child of each pending split (compact slot = pair rank), the larger
        sibling is parent − smaller (ref: serial_tree_learner.cpp:334
        smaller/larger leaf split, feature_histogram.hpp Subtract) — so
        late waves stream half the rows' worth of MXU lanes instead of
        every leaf's.  kslot [n] is the compact computed slot per row,
        assigned during the PREVIOUS wave's recolor (rows outside a
        computed leaf carry the out-of-range sentinel Lp, which matches no
        slot one-hot bucket — no per-row gather or gh masking needed
        here)."""
        H, cnt = hists_of(kslot, gh, Kb, Ks)           # [Kb, F', B', 2]
        with global_timer.device_scope("Tree::cache"):
            return _complete_cache(H, cnt, cache_h, cache_c, pend_sel,
                                   pend_new, pend_rank, pend_sl, Kb,
                                   first)

    def _complete_cache(H, cnt, cache_h, cache_c, pend_sel, pend_new,
                        pend_rank, pend_sl, Kb, first):
        """Sibling subtraction and the scatter of both children into
        the per-leaf cache (the part of `wave_hists` after the kernel)."""
        cnt = cnt.astype(f32)
        if first:
            # root wave: kslot is all zeros; one computed slot
            cache_h = cache_h.at[0].set(H.reshape(Kb, Dh)[0])
            cache_c = cache_c.at[0].set(cnt[0])
            return cache_h, cache_c
        # rank -> (parent slot, right slot, smaller-left) tables
        rdrop = jnp.where(pend_sel, pend_rank, Kb)
        slots = jnp.arange(Lp, dtype=i32)
        p_of = jnp.zeros(Kb, i32).at[rdrop].set(slots, mode="drop")
        q_of = jnp.zeros(Kb, i32).at[rdrop].set(pend_new, mode="drop")
        sl_of = jnp.zeros(Kb, bool).at[rdrop].set(pend_sl, mode="drop")
        valid = jnp.zeros(Kb, bool).at[rdrop].set(True, mode="drop")
        # gather (parent) and scatter (children) as ONE-HOT MXU MATMULS:
        # XLA's slice gather/scatter runs ~1GB/s on TPU, while a [Kb, Lp]
        # selection matmul against the flat [Lp, D] cache is microseconds
        # on the MXU and EXACT — one-hot rows have at most one nonzero, so
        # there is no accumulation and HIGHEST precision reproduces the
        # fp32 operand bit-for-bit
        HI = jax.lax.Precision.HIGHEST
        Hf = H.reshape(Kb, Dh)
        lr = jnp.arange(Lp, dtype=i32)
        pv = jnp.where(valid, p_of, Lp)
        qv = jnp.where(valid, q_of, Lp)
        P_par = (pv[:, None] == lr[None, :]).astype(f32)    # [Kb, Lp]
        parent_h = jax.lax.dot_general(P_par, cache_h,
                                       (((1,), (0,)), ((), ())),
                                       precision=HI)        # [Kb, Dh]
        other_h = parent_h - Hf
        slb = sl_of[:, None]
        W = jnp.concatenate([(lr[:, None] == pv[None, :]),
                             (lr[:, None] == qv[None, :])],
                            axis=1).astype(f32)             # [Lp, 2Kb]
        child_h = jnp.concatenate([jnp.where(slb, Hf, other_h),
                                   jnp.where(slb, other_h, Hf)], axis=0)
        upd = jax.lax.dot_general(W, child_h, (((1,), (0,)), ((), ())),
                                  precision=HI)             # [Lp, Dh]
        keep = 1.0 - jnp.clip(jnp.sum(W, axis=1), 0.0, 1.0)
        cache_h = cache_h * keep[:, None] + upd
        parent_c = jnp.sum(P_par * cache_c[None, :], axis=1)
        other_c = parent_c - cnt
        child_c = jnp.concatenate([jnp.where(sl_of, cnt, other_c),
                                   jnp.where(sl_of, other_c, cnt)])
        cache_c = cache_c * keep + jnp.sum(W * child_c[None, :], axis=1)
        return cache_h, cache_c

    def _forced_entry(fleaf, ffeat, fthr, cache_h, cache_c, leaf_sum_g,
                      leaf_sum_h):
        """SplitResult for a forced split of `fleaf` from its cached
        histogram (shared gather: grow.gather_forced_split)."""
        hist = bundle_hist_to_features(
            cache_h[fleaf].reshape(Fh, hist_B, 2), leaf_sum_g[fleaf],
            leaf_sum_h[fleaf], meta, B, hist_B, params.has_bundles)
        res = gather_forced_split(hist, ffeat, fthr, leaf_sum_g[fleaf],
                                  leaf_sum_h[fleaf], cache_c[fleaf],
                                  meta, B, sp)
        return res, res.gain > K_MIN_SCORE

    def wave_body(state, NLp, Kb, first=False, Ks=None, lg_cap=None,
                  budget_cap=None, forced=None):
        """One wave with a static slot bound NLp >= current num_leaves and
        a static computed-slot bound Kb >= splits of the previous wave.
        Ks is the TRUE (unpadded) computed-slot bound for the decomposed
        small-S histogram kernel.  `lg_cap` bounds the leaf budget (the
        overgrow target for this PHASE of growth; defaults to Lg) and
        `budget_cap` additionally caps the splits of this single wave
        (the spike waves' narrow best-gain-only deepening)."""
        (tree, leaf_id, kslot, leaf_sum_g, leaf_sum_h, leaf_out,
         leaf_cmin, leaf_cmax, used_vec, leaf_branch, cache_h, cache_c,
         pend_sel, pend_new, pend_rank, pend_sl, best_state, _) = state
        NL = tree.num_leaves

        # 1. refresh the per-leaf cache for last wave's children (smaller
        #    child computed, larger by subtraction), then scan the leaves
        #    whose histograms changed (all of them on the first wave /
        #    non-incremental modes; DataPartition cnt_leaf_data exactness
        #    rides the count cache)
        cache_h, cache_c = wave_hists(kslot, cache_h, cache_c, pend_sel,
                                      pend_new, pend_rank, pend_sl, Kb,
                                      first, Ks)
        counts = jnp.round(cache_c[:NLp]).astype(i32)
        active = jnp.arange(NLp, dtype=i32) < NL
        rb = (_rand_bins(tree.num_leaves)[:NLp] if sp.extra_trees else None)
        rcu = (_rand_cat_us(tree.num_leaves)[:NLp]
               if sp.extra_trees and sp.has_categorical else None)
        mono_args = ((leaf_cmin[:NLp], leaf_cmax[:NLp],
                      tree.leaf_depth[:NLp]) if sp.has_monotone
                     else (None, None, None))
        bym = (_bynode_masks(tree.num_leaves)[:NLp] if use_bynode
               else None)
        if use_interaction:
            allow = _allowed_of(leaf_branch[:NLp])
            bym = allow if bym is None else (bym & allow)
        # the incremental rescan gathers [2*Kb, Dh] from the cache (XLA
        # gathers run ~1GB/s) and its cost scales with the STATIC bound
        # Kb, not realized splits — it only beats the resident full scan
        # when that bound is a small fraction of NLp.  In practice that
        # is the spike waves after the first (Kb=8 vs NLp=pad(Lg));
        # ladder waves, the chain-tail while loop (Kb=pad(Lg/2)), and
        # short forced prologues all keep the full scan: a doubling
        # ladder scans 528 leaves a tree where 511 are new, so the
        # rescans are 3% of it
        use_inc = incremental_scan and not first and 4 * Kb <= NLp
        if not use_inc:
            rows = cache_h[:NLp]
            with global_timer.device_scope("Tree::split_find"):
                best = best_of(rows, leaf_sum_g[:NLp], leaf_sum_h[:NLp],
                               counts, leaf_out[:NLp], *mono_args, rb,
                               rcu, used_vec, bym)
            if incremental_scan:
                best_state = jax.tree.map(
                    lambda a, u: a.at[:NLp].set(u), best_state, best)
        else:
            # rescan ONLY the <= 2*Kb leaves the previous wave created:
            # the split parents (now their left children, same slot) and
            # the new right slots
            psl = jnp.argsort(-pend_sel.astype(i32))[:Kb]
            valid_p = jnp.take(pend_sel, psl)
            parents = jnp.where(valid_p, psl, Lp)
            news = jnp.where(valid_p, jnp.take(pend_new, psl), Lp)
            changed = jnp.concatenate([parents, news])       # [2*Kb]
            ch = jnp.clip(changed, 0, Lp - 1)
            h_ch = jnp.take(cache_h, ch, axis=0)
            with global_timer.device_scope("Tree::split_find"):
                best_ch = best_of(h_ch, jnp.take(leaf_sum_g, ch),
                                  jnp.take(leaf_sum_h, ch),
                                  jnp.round(jnp.take(cache_c, ch))
                                  .astype(i32),
                                  jnp.take(leaf_out, ch), *mono_args,
                                  rb, rcu, used_vec, bym)
            best_state = jax.tree.map(
                lambda a, u: a.at[changed].set(u, mode="drop"),
                best_state, best_ch)
            best = jax.tree.map(lambda a: a[:NLp], best_state)

        # 2. select splitting leaves: positive gain, active, depth ok,
        #    best-gain-first within the remaining leaf budget
        if forced is not None:
            # forced wave (ref: serial_tree_learner.cpp:614 ForceSplits):
            # exactly one predetermined (leaf, feature, threshold) split,
            # applied regardless of gain RANK/SIGN but only with
            # non-empty children and within depth/leaf budget
            fleaf, ffeat, fthr = forced
            fentry, fvalid = _forced_entry(fleaf, ffeat, fthr, cache_h,
                                           cache_c, leaf_sum_g,
                                           leaf_sum_h)
            best = jax.tree.map(
                lambda a, u: a.at[fleaf].set(u), best, fentry)
            ok = fvalid & (fleaf < NL) & (NL < L)
            if params.max_depth > 0:
                ok = ok & (tree.leaf_depth[fleaf] < params.max_depth)
            split_sel = (jnp.arange(NLp, dtype=i32) == fleaf) & ok
            rank_of = jnp.zeros(NLp, i32)
            n_split = jnp.sum(split_sel, dtype=i32)
        else:
            gain = jnp.where(active, best.gain, K_MIN_SCORE)
            if params.max_depth > 0:
                gain = jnp.where(tree.leaf_depth[:NLp] < params.max_depth,
                                 gain, K_MIN_SCORE)
            want = gain > 0.0
            budget = (Lg if lg_cap is None else lg_cap) - NL
            if budget_cap is not None:
                budget = jnp.minimum(budget, budget_cap)
            if params.wave_tail_halving:
                # once the leaf budget binds, spend at most half of it
                # per wave (always best-gain-first): the tail of the
                # tree then allocates leaves closer to the leaf-wise
                # global-gain order at the cost of ~log2(L) extra
                # (cheap, few-slot) waves — see PERF_NOTES.md
                budget = jnp.where(budget < NL,
                                   jnp.maximum((budget + 1) // 2, 1),
                                   budget)
            order = jnp.argsort(-gain)                # best first
            rank_of = jnp.zeros(NLp, i32).at[order].set(
                jnp.arange(NLp, dtype=i32))
            split_sel = want & (rank_of < budget)
            n_split = jnp.sum(split_sel, dtype=i32)

        # node/new-leaf numbering by gain rank (leaf-wise split order)
        node_of = jnp.where(split_sel, NL - 1 + rank_of, 0)
        newleaf_of = jnp.where(split_sel, NL + rank_of, 0)

        # 3. tree arrays, vectorized over leaves (ref: tree.cpp Tree::Split)
        t = tree
        # parent child-pointer fix: nodes whose child pointer references a
        # splitting leaf now point at that leaf's new internal node
        def fix_child(child):
            ll = jnp.where(child < 0, ~child, 0)
            is_leaf_ref = (child < 0) & (jnp.arange(ni, dtype=i32)
                                         < NL - 1)
            repl = jnp.take(node_of, jnp.clip(ll, 0, NLp - 1))
            hit = is_leaf_ref & jnp.take(split_sel, jnp.clip(ll, 0, NLp - 1))
            return jnp.where(hit, repl, child)
        left_child = fix_child(t.left_child)
        right_child = fix_child(t.right_child)

        # scatter per-splitting-leaf node records
        sl_nodes = node_of                             # [NLp] targets
        drop = jnp.where(split_sel, sl_nodes, ni)      # OOB -> dropped
        def nset(arr, vals):
            return arr.at[drop].set(vals, mode="drop")
        left_child = nset(left_child,
                          ~jnp.arange(NLp, dtype=i32))  # left = old leaf
        right_child = nset(right_child, ~newleaf_of)
        split_feature = nset(t.split_feature, best.feature)
        threshold_bin = nset(t.threshold_bin, best.threshold)
        default_left = nset(t.default_left, best.default_left)
        split_gain = nset(t.split_gain, best.gain)
        internal_value = nset(t.internal_value, t.leaf_value[:NLp])
        internal_weight = nset(t.internal_weight,
                               best.left_sum_hessian + best.right_sum_hessian)
        internal_count = nset(t.internal_count, counts)  # exact
        split_is_cat = nset(t.split_is_cat, best.is_cat)
        cat_bitset = t.cat_bitset.at[drop].set(best.cat_bitset, mode="drop")

        # leaf records: old slot becomes the left child, new slot the right
        ldrop = jnp.where(split_sel, jnp.arange(NLp, dtype=i32), Lp)
        rdrop = jnp.where(split_sel, newleaf_of, Lp)
        depth1 = t.leaf_depth[:NLp] + 1
        def lset(arr, lvals, rvals):
            return (arr.at[ldrop].set(lvals, mode="drop")
                    .at[rdrop].set(rvals, mode="drop"))
        leaf_value = lset(t.leaf_value, best.left_output, best.right_output)
        leaf_weight = lset(t.leaf_weight, best.left_sum_hessian,
                           best.right_sum_hessian)
        # leaf_count here is the scan's approximation; the exact counts are
        # restored from the count channel each wave and at finalization
        leaf_count = lset(t.leaf_count, best.left_count, best.right_count)
        leaf_parent = lset(t.leaf_parent, sl_nodes, sl_nodes)
        leaf_depth = lset(t.leaf_depth, depth1, depth1)
        leaf_sum_g = lset(leaf_sum_g, best.left_sum_gradient,
                          best.right_sum_gradient)
        leaf_sum_h = lset(leaf_sum_h, best.left_sum_hessian,
                          best.right_sum_hessian)
        leaf_out = lset(leaf_out, best.left_output, best.right_output)
        if use_interaction:
            # children extend the branch with the winning feature (ref:
            # col_sampler.hpp used_feature_indices_ per-branch tracking)
            fb = (jnp.arange(num_features, dtype=i32)[None, :]
                  == best.feature[:, None]) & split_sel[:, None]
            newb = leaf_branch[:NLp] | fb
            leaf_branch = lset(leaf_branch, newb, newb)
        if sp.has_monotone:
            # basic-mode constraint propagation (BasicLeafConstraints::
            # Update): children bounded at the output midpoint
            p_min = leaf_cmin[:NLp]
            p_max = leaf_cmax[:NLp]
            mc_w = jnp.take(meta.monotone, best.feature)
            mid = (best.left_output + best.right_output) / 2.0
            apply = split_sel & (mc_w != 0) & ~best.is_cat
            pos = apply & (mc_w > 0)
            neg = apply & (mc_w < 0)
            l_max = jnp.where(pos, jnp.minimum(p_max, mid), p_max)
            l_min = jnp.where(neg, jnp.maximum(p_min, mid), p_min)
            r_min = jnp.where(pos, jnp.maximum(p_min, mid), p_min)
            r_max = jnp.where(neg, jnp.minimum(p_max, mid), p_max)
            leaf_cmin = lset(leaf_cmin, l_min, r_min)
            leaf_cmax = lset(leaf_cmax, l_max, r_max)

        tree = TreeArrays(
            num_leaves=NL + n_split,
            split_feature=split_feature, threshold_bin=threshold_bin,
            default_left=default_left, split_gain=split_gain,
            left_child=left_child, right_child=right_child,
            internal_value=internal_value, internal_weight=internal_weight,
            internal_count=internal_count,
            leaf_value=leaf_value, leaf_weight=leaf_weight,
            leaf_count=leaf_count, leaf_parent=leaf_parent,
            leaf_depth=leaf_depth,
            split_is_cat=split_is_cat, cat_bitset=cat_bitset,
            # one histogram pass over the rows per wave
            waves=t.waves + 1)

        # 4. recolour rows (ops/recolour.py): the wave's per-leaf records
        # as one small byte table, each row's record read from it by the
        # one-hot of its leaf (no per-row XLA gather: 8.2 ns a row on a
        # TPU; the prune's row remap and the score update read their
        # tables the same way), its bin of the split column picked, its
        # side taken.  With the Pallas kernels a record lives in VMEM and
        # only the new leaf_id and kslot reach HBM; the XLA form is the
        # same rule for a backend without them.
        # smaller side per split pair, chosen by the SCAN's (approximate,
        # RoundInt-parity) counts — either choice yields the same exact
        # pair of histograms by subtraction
        with global_timer.device_scope("Tree::partition"):
            small_left = best.left_count <= best.right_count
            layout = table_layout(
                num_columns=binned.shape[0], max_bin=B, column_bins=hist_B,
                num_slots=NLp, sentinel=Lp, has_bundles=params.has_bundles,
                cat_words=W if sp.has_categorical else 0)
            fields = dict(
                split_sel=split_sel, column=best.feature,
                threshold=best.threshold, default_left=best.default_left,
                new_leaf=newleaf_of, rank=rank_of, small_left=small_left,
                missing_type=jnp.take(meta.missing_type, best.feature),
                default_bin=jnp.take(meta.default_bin, best.feature),
                num_bin=jnp.take(meta.num_bin, best.feature))
            if params.has_bundles:
                fields.update(
                    column=jnp.take(meta.group, best.feature),
                    offset=jnp.take(meta.offset, best.feature),
                    zero_bin=jnp.take(meta.zero_bin, best.feature))
            if sp.has_categorical:
                fields.update(is_cat=best.is_cat,
                              cat_bitset=best.cat_bitset)
            # the NEXT wave's computed-slot assignment rides this pass: a
            # row is in the computed set iff it landed in its pair's
            # smaller child; everyone else gets the sentinel Lp
            recolour = recolour_wave if use_pallas else recolour_xla
            leaf_id, kslot = recolour(pack_table(layout, **fields), leaf_id,
                                      binned, layout=layout)

        if sp.has_cegb:
            # all of this wave's winning features become used (coupled
            # penalties within one wave are charged per splitting leaf —
            # a wave-batching deviation from the reference's per-split
            # accounting, which refunds later leaves in the same level)
            used_vec = used_vec.at[jnp.where(split_sel, best.feature,
                                             num_features)].set(
                True, mode="drop")
        # pending tables for the next wave's cache completion
        lpz = jnp.zeros(Lp, i32)
        pend_sel = jnp.zeros(Lp, bool).at[:NLp].set(split_sel)
        pend_new = lpz.at[:NLp].set(newleaf_of)
        pend_rank = lpz.at[:NLp].set(rank_of)
        pend_sl = jnp.zeros(Lp, bool).at[:NLp].set(small_left)
        cont = (n_split > 0) & (tree.num_leaves
                                < (Lg if lg_cap is None else lg_cap))
        return (tree, leaf_id, kslot, leaf_sum_g, leaf_sum_h, leaf_out,
                leaf_cmin, leaf_cmax, used_vec, leaf_branch, cache_h,
                cache_c, pend_sel, pend_new, pend_rank, pend_sl,
                best_state, cont)

    if cegb_used is None:
        cegb_used = jnp.zeros(num_features if sp.has_cegb else 1, bool)
    leaf_branch0 = jnp.zeros(
        (Lp, num_features) if use_interaction else (1, 1), bool)
    # per-leaf cached best splits for the incremental scan (dummy scalar
    # pytree when the full rescan runs — lax.cond branches must match)
    if incremental_scan:
        best0 = SplitResult(
            gain=jnp.full(Lp, K_MIN_SCORE, f32),
            feature=jnp.zeros(Lp, i32), threshold=jnp.zeros(Lp, i32),
            default_left=jnp.zeros(Lp, bool),
            left_sum_gradient=jnp.zeros(Lp, f32),
            left_sum_hessian=jnp.zeros(Lp, f32),
            left_count=jnp.zeros(Lp, i32), left_output=jnp.zeros(Lp, f32),
            right_sum_gradient=jnp.zeros(Lp, f32),
            right_sum_hessian=jnp.zeros(Lp, f32),
            right_count=jnp.zeros(Lp, i32),
            right_output=jnp.zeros(Lp, f32),
            is_cat=jnp.zeros(Lp, bool),
            cat_bitset=jnp.zeros((Lp, W), i32))
    else:
        best0 = jnp.zeros((), f32)
    state = (tree, jnp.zeros(n, i32), jnp.zeros(n, i32), leaf_sum_g0,
             leaf_sum_h0, leaf_out0, leaf_cmin0, leaf_cmax0, cegb_used,
             leaf_branch0, cache_h0, cache_c0, pend_sel0, pend_new0,
             pend_rank0, pend_sl0, best0, jnp.asarray(L > 1))
    # forced prologue (ref: serial_tree_learner.cpp:614 ForceSplits): one
    # forced split per wave, in the parse-time BFS numbering (one split
    # per step keeps the leaf ids aligned).  The first skipped forced
    # split aborts the rest (the reference's abort semantics); its slot
    # returns to best-gain growth.
    KF = min(len(params.forced_splits), max(L - 1, 0))
    if KF:
        forcing_ok = jnp.asarray(True)
        for k in range(KF):
            fleaf, ffeat, fthr = params.forced_splits[k]
            nl_before = state[0].num_leaves
            state = jax.lax.cond(
                forcing_ok,
                functools.partial(wave_body, NLp=wave_slot_pad(k + 2),
                                  Kb=wave_slot_pad(1), first=(k == 0),
                                  Ks=1, forced=(fleaf, ffeat, fthr)),
                lambda s: s, state)
            forcing_ok = forcing_ok & (state[0].num_leaves > nl_before)
        # re-arm growth for the best-gain phase
        state = state[:-1] + ((jnp.asarray(L > 1)
                               & (state[0].num_leaves < Lg_main)),)

    num_waves = max(1, math.ceil(math.log2(Lg_main))) if Lg_main > 1 else 0
    for k in range(num_waves):
        # entering ladder wave k the tree has grown from <= KF+1 leaves
        # (forced prologue) through k doubling waves: NL <= (KF+1)*2^k.
        # The bounds must be MULTIPLICATIVE in KF+1 — an additive bound
        # would undersize Ks and the hl kernel would silently zero-pad
        # real children (its out_slots contract)
        NLp = wave_slot_pad(min((KF + 1) << k, Lg_main))
        # computed slots this wave = splits of the previous wave (root
        # wave computes 1 slot; after a forced prologue the first ladder
        # wave's pending split is the last forced wave's single one)
        Ks = (1 if k == 0 and KF else
              min((KF + 1) << max(k - 1, 0), Lg_main))
        Kb = wave_slot_pad(Ks)
        state = jax.lax.cond(state[-1],
                             functools.partial(wave_body, NLp=NLp, Kb=Kb,
                                               first=(k == 0 and not KF),
                                               Ks=Ks, lg_cap=Lg_main),
                             lambda s: s, state)
    if num_waves > 0:
        # growth slower than doubling (chain-shaped gain landscapes) needs
        # more rounds than the unrolled ladder: keep waving at the full
        # slot bound until no leaf splits or the budget is exhausted.
        # Splits per wave <= min(NL, Lg - NL) <= Lg // 2.
        state = jax.lax.while_loop(
            lambda s: s[-1],
            functools.partial(wave_body, NLp=wave_slot_pad(Lg_main),
                              Kb=wave_slot_pad(max(Lg_main // 2, 1)),
                              lg_cap=Lg_main), state)
    for s_i in range(spike_waves):
        # narrow deepening: the previous wave may have split up to
        # spike_k leaves (or Lg_main//2 for the first spike), so the
        # computed-slot bound is that previous wave's split cap
        KsS = min(spike_k if s_i > 0 else max(Lg_main // 2, 1), Lg)
        # tpulint: disable-next=no-device-put-in-loop -- re-arm cont: trace-time constant in the unrolled spike ladder, not a runtime H2D
        state = state[:-1] + (jnp.asarray(True),)
        state = jax.lax.cond(
            state[0].num_leaves < Lg,
            functools.partial(wave_body, NLp=wave_slot_pad(Lg),
                              Kb=wave_slot_pad(KsS),
                              Ks=spike_true_slots(KsS),
                              budget_cap=spike_k),
            lambda s: s, state)

    def _prune_to_leafwise(tree, leaf_id):
        """Prune the overgrown (<= Lg leaves) tree back to L leaves in the
        reference's strict leaf-wise order (serial_tree_learner.cpp:219
        ArgMax over leaf gains): simulate the best-gain pop sequence over
        the overgrown tree's exact split gains, keep the popped splits,
        renumber nodes/leaves by pop order (the reference's creation
        order), and remap rows to their nearest kept ancestor's side.
        Exactly the leaf-wise tree whenever its splits lie within the
        overgrown region; a node's gain depends only on its row set, so
        kept gains are identical to what leaf-wise would have computed."""
        nodes = jnp.arange(ni, dtype=i32)
        NN = tree.num_leaves - 1                   # realized node count
        created = nodes < NN
        lc, rc = tree.left_child, tree.right_child
        # parent-of-node via child-pointer scatter
        lci = jnp.where(created & (lc >= 0), lc, ni)
        rci = jnp.where(created & (rc >= 0), rc, ni)
        par = (jnp.full(ni, -1, i32).at[lci].set(nodes, mode="drop")
               .at[rci].set(nodes, mode="drop"))
        gains = jnp.where(created, tree.split_gain, K_MIN_SCORE)

        nf = max(L - 1, 1)
        kept0 = jnp.zeros(ni, bool)
        avail0 = created & (par == -1)             # the root node
        new_id0 = jnp.zeros(ni, i32)
        pop0 = jnp.zeros(nf, i32)
        lid_of0 = jnp.zeros(ni, i32)               # leaf id a node splits
        dep_of0 = jnp.zeros(ni, i32)               # depth of that leaf
        nl_l0 = jnp.zeros(ni, i32)                 # left/right child leaf
        nl_r0 = jnp.zeros(ni, i32)                 # ids assigned at pop

        def pop_step(t, st):
            kept, avail, new_id, pop, lid_of, dep_of, nl_l, nl_r, cnt = st
            score = jnp.where(avail & ~kept & (gains > 0.0), gains,
                              K_MIN_SCORE)
            j = jnp.argmax(score).astype(i32)
            ok = score[j] > K_MIN_SCORE
            jd = jnp.where(ok, j, ni)
            kept = kept.at[jd].set(True, mode="drop")
            new_id = new_id.at[jd].set(cnt, mode="drop")
            pop = pop.at[jnp.where(ok, cnt, nf)].set(j, mode="drop")
            ll = lid_of[j]
            nl_l = nl_l.at[jd].set(ll, mode="drop")
            nl_r = nl_r.at[jd].set(cnt + 1, mode="drop")
            lcj, rcj = lc[j], rc[j]
            dl = dep_of[j] + 1
            lt = jnp.where(ok & (lcj >= 0), lcj, ni)
            rt = jnp.where(ok & (rcj >= 0), rcj, ni)
            lid_of = (lid_of.at[lt].set(ll, mode="drop")
                      .at[rt].set(cnt + 1, mode="drop"))
            dep_of = (dep_of.at[lt].set(dl, mode="drop")
                      .at[rt].set(dl, mode="drop"))
            avail = (avail.at[lt].set(True, mode="drop")
                     .at[rt].set(True, mode="drop"))
            return (kept, avail, new_id, pop, lid_of, dep_of, nl_l, nl_r,
                    cnt + jnp.where(ok, 1, 0))

        (kept, _, new_id, pop, lid_of, dep_of, nl_l, nl_r,
         n_kept) = jax.lax.fori_loop(
            0, nf, pop_step,
            (kept0, avail0, new_id0, pop0, lid_of0, dep_of0, nl_l0, nl_r0,
             jnp.asarray(0, i32)))

        # rebuild node arrays [nf] in pop order
        tf = jnp.arange(nf, dtype=i32)
        valid_t = tf < n_kept
        old = jnp.where(valid_t, pop, 0)

        def gat(a, fill=0):
            v = jnp.take(a, old, axis=0)
            if a.ndim > 1:
                return jnp.where(valid_t[:, None], v, fill)
            return jnp.where(valid_t, v, fill)

        olc, orc = jnp.take(lc, old), jnp.take(rc, old)
        olci, orci = jnp.clip(olc, 0, ni - 1), jnp.clip(orc, 0, ni - 1)
        lk = (olc >= 0) & jnp.take(kept, olci)
        rk = (orc >= 0) & jnp.take(kept, orci)
        onl_l, onl_r = jnp.take(nl_l, old), jnp.take(nl_r, old)
        left_f = jnp.where(valid_t,
                           jnp.where(lk, jnp.take(new_id, olci), ~onl_l), 0)
        right_f = jnp.where(valid_t,
                            jnp.where(rk, jnp.take(new_id, orci), ~onl_r), 0)

        # leaf arrays [Lp]: a kept node's side becomes a final leaf when
        # its overgrown child there is not kept — source values are the
        # overgrown leaf's (child < 0) or the pruned node's internal ones
        def side_leaf(oc, is_leaf_here, nl):
            oci = jnp.clip(oc, 0, ni - 1)
            osl = jnp.clip(~oc, 0, Lp - 1)
            lid = jnp.where(valid_t & is_leaf_here, nl, Lp)
            val = jnp.where(oc >= 0, jnp.take(tree.internal_value, oci),
                            jnp.take(tree.leaf_value, osl))
            wgt = jnp.where(oc >= 0, jnp.take(tree.internal_weight, oci),
                            jnp.take(tree.leaf_weight, osl))
            cntv = jnp.where(oc >= 0, jnp.take(tree.internal_count, oci),
                             jnp.take(tree.leaf_count, osl))
            return lid, val, wgt, cntv

        lid_l, val_l, wgt_l, cnt_l = side_leaf(olc, ~lk, onl_l)
        lid_r, val_r, wgt_r, cnt_r = side_leaf(orc, ~rk, onl_r)
        dep1 = jnp.take(dep_of, old) + 1

        def scat(init, vl, vr):
            return (init.at[lid_l].set(vl, mode="drop")
                    .at[lid_r].set(vr, mode="drop"))

        single = n_kept == 0                      # no kept split: 1 leaf
        leaf_value_f = jnp.where(
            single, jnp.zeros(Lp, f32).at[0].set(tree.leaf_value[0]),
            scat(jnp.zeros(Lp, f32), val_l, val_r))
        leaf_weight_f = jnp.where(
            single, jnp.zeros(Lp, f32).at[0].set(tree.leaf_weight[0]),
            scat(jnp.zeros(Lp, f32), wgt_l, wgt_r))
        leaf_count_f = jnp.where(
            single, jnp.zeros(Lp, i32).at[0].set(tree.leaf_count[0]),
            scat(jnp.zeros(Lp, i32), cnt_l, cnt_r))
        leaf_parent_f = jnp.where(
            single, jnp.full(Lp, -1, i32),
            scat(jnp.full(Lp, -1, i32), tf, tf))
        leaf_depth_f = jnp.where(
            single, jnp.zeros(Lp, i32),
            scat(jnp.zeros(Lp, i32), dep1, dep1))

        tree_f = TreeArrays(
            num_leaves=n_kept + 1,
            split_feature=gat(tree.split_feature),
            threshold_bin=gat(tree.threshold_bin),
            default_left=gat(tree.default_left, False),
            split_gain=gat(tree.split_gain, 0.0),
            left_child=left_f, right_child=right_f,
            internal_value=gat(tree.internal_value, 0.0),
            internal_weight=gat(tree.internal_weight, 0.0),
            internal_count=gat(tree.internal_count),
            leaf_value=leaf_value_f, leaf_weight=leaf_weight_f,
            leaf_count=leaf_count_f, leaf_parent=leaf_parent_f,
            leaf_depth=leaf_depth_f,
            split_is_cat=gat(tree.split_is_cat, False),
            cat_bitset=gat(tree.cat_bitset),
            waves=tree.waves)

        # rows: overgrown leaf slot -> nearest kept ancestor's side leaf.
        # Walk up until the current node is kept (or the root is passed);
        # overgrown depth is bounded by the wave count but chain shapes
        # can be deep, so iterate to convergence.
        s_ids = jnp.arange(Lp, dtype=i32)
        node0 = tree.leaf_parent
        side0 = jnp.where(
            jnp.take(rc, jnp.clip(node0, 0, ni - 1)) == ~s_ids, 1, 0)

        def w_cond(st):
            node, _ = st
            done = (node < 0) | jnp.take(kept, jnp.clip(node, 0, ni - 1))
            return jnp.any(~done)

        def w_step(st):
            node, side = st
            nodei = jnp.clip(node, 0, ni - 1)
            done = (node < 0) | jnp.take(kept, nodei)
            pnode = jnp.take(par, nodei)
            pside = jnp.where(
                jnp.take(rc, jnp.clip(pnode, 0, ni - 1)) == node, 1, 0)
            return (jnp.where(done, node, pnode),
                    jnp.where(done, side, pside))

        node_w, side_w = jax.lax.while_loop(w_cond, w_step, (node0, side0))
        nwi = jnp.clip(node_w, 0, ni - 1)
        lid_map = jnp.where(
            node_w >= 0,
            jnp.where(side_w == 1, jnp.take(nl_r, nwi),
                      jnp.take(nl_l, nwi)), 0)
        # remap rows through the [Lp] table as a one-hot MXU matmul
        # (byte-decomposed, bit-exact; same rationale as the recolor pass)
        tab = jnp.stack([(lid_map & 255).astype(jnp.bfloat16),
                         ((lid_map >> 8) & 255).astype(jnp.bfloat16)], 1)
        ohr = (leaf_id[:, None] ==
               s_ids[None, :]).astype(jnp.bfloat16)
        got = jax.lax.dot_general(ohr, tab, (((1,), (0,)), ((), ())),
                                  preferred_element_type=f32)
        leaf_id_f = got[:, 0].astype(i32) + (got[:, 1].astype(i32) << 8)
        # exact final counts ride the SAME one-hot (ref: DataPartition
        # cnt_leaf_data): per-old-slot masked counts from one extra MXU
        # column, scattered through the [Lp] slot->leaf table — no second
        # [n, Lp] one-hot pass
        cnt_slot = _psum(jax.lax.dot_general(
            row_mask.astype(jnp.bfloat16)[None, :], ohr,
            (((1,), (0,)), ((), ())), preferred_element_type=f32)[0])
        exact = jnp.zeros(Lp, f32).at[lid_map].add(cnt_slot).astype(i32)
        tree_f = tree_f._replace(leaf_count=exact)
        return tree_f, leaf_id_f

    tree, leaf_id = state[0], state[1]
    if prune and num_waves > 0:
        with global_timer.device_scope("Tree::prune"):
            tree, leaf_id = _prune_to_leafwise(tree, leaf_id)
    elif num_waves > 0:
        # exact final counts from the final partition (ref: DataPartition
        # cnt_leaf_data).  A one-hot MXU matmul instead of a 1M-element
        # scatter-add: the one-hot and 0/1 mask are exact in bf16 and the
        # fp32 accumulator holds integer sums < 2^24 exactly.
        oh = (leaf_id[:, None] ==
              jnp.arange(Lp, dtype=i32)[None, :]).astype(jnp.bfloat16)
        exact = _psum(jax.lax.dot_general(
            row_mask.astype(jnp.bfloat16)[None, :], oh,
            (((1,), (0,)), ((), ())),
            preferred_element_type=f32)[0]).astype(i32)
        tree = tree._replace(leaf_count=exact)
    if Lp != L:  # back to the caller-visible [L] leaf layout
        tree = tree._replace(
            leaf_value=tree.leaf_value[:L], leaf_weight=tree.leaf_weight[:L],
            leaf_count=tree.leaf_count[:L], leaf_parent=tree.leaf_parent[:L],
            leaf_depth=tree.leaf_depth[:L])
    return tree, leaf_id

# tpulint: disable-next=donate-argnums -- the shard_map wrapper (parallel/data_parallel.py) and linear-tree paths reuse grad/hess; the default loop takes grow_tree_wave_donated
grow_tree_wave = jax.jit(grow_tree_wave_impl, static_argnames=("params",))
# default single-device entry: the per-class grad/hess slices die at the
# grow call, so their HBM is donated into the tree program
# (boosting/gbdt.py selects; docs/Performance.md)
grow_tree_wave_donated = jax.jit(grow_tree_wave_impl,
                                 static_argnames=("params",),
                                 donate_argnums=(1, 2))
