"""Voting-parallel (PV-Tree) tree learner over a device mesh.

TPU-native redesign of VotingParallelTreeLearner
(ref: src/treelearner/voting_parallel_tree_learner.cpp:151-181 GlobalVoting,
:184 CopyLocalHistogram, :296 FindBestSplitsFromHistograms):

  reference (socket collectives)            TPU (explicit collectives in a
                                            shard_map region inside the jit)
  ----------------------------------------- -------------------------------
  rows pre-partitioned per machine          binned [F, n] sharded on axis n
  local histograms per worker               per-device hist in the region
  local best split per feature with         find_best_split(..., return_
    min_data/min_hessian scaled by 1/M        feature_gains=True) on local
    (voting_parallel_tree_learner.cpp:62)     sums with the scaled params
  each worker proposes its top-k features   lax.top_k on the count-weighted
    by gain*count/mean_count (:165)           local gain vector
  Allgather proposals; global election =    lax.pmax of the masked proposal
    top-k features by max weighted gain       vector, then lax.top_k
    (GlobalVoting :151)
  ReduceScatter ONLY the elected            lax.psum of the gathered
    features' histograms (:184)               [k, B, 2] sub-histogram
  best split among elected features,        the usual global gain scan with
    SyncUpGlobalBestSplit (:296)              col_mask &= elected

The point of PV-Tree is traffic: per leaf scan the wire carries
k*B*2 + F floats instead of the full F*B*2 histogram.  On an ICI mesh this
matters once F is large or the mesh spans DCN (multi-pod).

Approximation note (same spirit as the reference): the *election* ranks
features by unconstrained local gains — monotone/CEGB/extra-trees
adjustments apply in the exact global scan over the elected features.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from ..ops.histogram import build_histogram
from ..ops.split import K_MIN_SCORE, SplitParams, find_best_split


class VotingSpec(NamedTuple):
    """Static voting-parallel configuration (hashable: jit static arg)."""
    mesh: Mesh
    top_k: int          # ref: config.h top_k (default 20)
    num_machines: int   # mesh size M


def local_split_params(sp: SplitParams, num_machines: int) -> SplitParams:
    """The reference scales the per-leaf minima by 1/M for the LOCAL scans
    (ref: voting_parallel_tree_learner.cpp:62-63).  The election ranks
    features by plain unconstrained gains: monotone/CEGB/extra-trees need
    per-leaf state the vote region does not carry, and they apply exactly
    in the global scan over the elected features."""
    return sp._replace(
        min_data_in_leaf=max(1, sp.min_data_in_leaf // num_machines),
        min_sum_hessian_in_leaf=sp.min_sum_hessian_in_leaf / num_machines,
        extra_trees=False, has_monotone=False, has_cegb=False)


def voting_hist_elect(binned, gh, member_mask, col_mask, parent_output,
                      meta, spec: VotingSpec, sp: SplitParams,
                      max_bin: int, hist_method: str):
    """Per-leaf voted histogram: returns ([F, B, 2] histogram that is exact
    for the elected features and zero elsewhere, [F] elected mask).

    Runs as a shard_map region over the mesh's data axis so the collectives
    are explicit: pmax carries the vote, psum reduces only the winners.
    """
    axis = spec.mesh.axis_names[0]
    M = spec.num_machines
    k = spec.top_k
    sp_local = local_split_params(sp, M)
    f32 = jnp.float32
    is_cat = (meta.is_cat if meta.is_cat is not None
              else jnp.zeros_like(meta.num_bin, bool))
    cat_index = (meta.cat_index if meta.cat_index is not None
                 else jnp.arange(meta.num_bin.shape[0], dtype=jnp.int32))

    def local_fn(b_l, gh_l, mask_l, num_bin, missing_type, default_bin,
                 penalty, is_cat_f, cat_index, cm, parent_out):
        # local leaf sums + histogram over this device's row shard
        hist_l = build_histogram(b_l, gh_l, mask_l, max_bin=max_bin,
                                 method=hist_method)
        sum_g_l = jnp.sum(gh_l[:, 0] * mask_l)
        sum_h_l = jnp.sum(gh_l[:, 1] * mask_l)
        cnt_l = jnp.sum(mask_l).astype(jnp.int32)
        gains = find_best_split(
            hist_l, num_bin, missing_type, default_bin,
            penalty, cm, sum_g_l, sum_h_l, cnt_l, parent_out,
            sp_local, is_cat_feature=is_cat_f, cat_index=cat_index,
            return_feature_gains=True)                      # [F]
        # count-weighted gain (ref: GlobalVoting :165: gain * count/mean)
        cnt_g = jax.lax.psum(cnt_l, axis)
        w = cnt_l.astype(f32) / jnp.maximum(cnt_g.astype(f32) / M, 1.0)
        weighted = jnp.where(gains > K_MIN_SCORE, gains * w, K_MIN_SCORE)
        # local proposal: this worker's top-k features
        kth = jax.lax.top_k(weighted, k)[0][-1]
        prop = jnp.where(weighted >= kth, weighted, K_MIN_SCORE)
        # global election by per-feature MAX weighted gain, exactly the
        # reference's GlobalVoting (voting_parallel_tree_learner.cpp:
        # 151-180): it concatenates every worker's proposals and keeps the
        # top-k features by the largest weighted gain any worker reported
        # (ArrayArgs::MaxK) — it never tallies votes.  pmax of the masked
        # proposal vectors gives each feature its max proposed gain;
        # non-proposed features stay at K_MIN_SCORE.
        glob = jax.lax.pmax(prop, axis)
        top_v, top_i = jax.lax.top_k(glob, k)
        valid = top_v > K_MIN_SCORE
        # reduce ONLY the elected features' histograms
        sub = jax.lax.psum(hist_l[top_i], axis)             # [k, B, 2]
        F = hist_l.shape[0]
        dst = jnp.where(valid, top_i, F)                    # drop invalid
        hist = jnp.zeros_like(hist_l).at[dst].set(sub, mode="drop")
        elected = jnp.zeros((F,), bool).at[dst].set(True, mode="drop")
        return hist, elected

    repl = P()
    # outputs are replicated by construction (psum/pmax of replicated
    # election indices) but the static replication checker cannot infer
    # it through top_k/scatter — hence check_vma=False
    return jax.shard_map(
        local_fn, mesh=spec.mesh,
        in_specs=(P(None, axis), P(axis, None), P(axis),
                  repl, repl, repl, repl, repl, repl, repl, repl),
        out_specs=(P(), P()), check_vma=False)(
            binned, gh, member_mask, meta.num_bin, meta.missing_type,
            meta.default_bin, meta.penalty, is_cat, cat_index, col_mask,
            jnp.asarray(parent_output, f32))
