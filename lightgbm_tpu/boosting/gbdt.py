"""GBDT boosting driver (ref: src/boosting/gbdt.cpp, gbdt.h:37).

Orchestrates the TPU training loop: binned data and scores live on device; per
iteration the objective's gradient map, bagging mask, the jitted whole-tree
grower and the score update all run as XLA computations.  Trees are pulled to
host as `Tree` objects (one small D2H per tree, like the CUDA learner's
CUDATree::ToHost, ref: src/io/cuda/cuda_tree.cpp) for model serialization and
raw-feature prediction.
"""

from __future__ import annotations

import copy as _copy
import functools
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..config import Config
from ..io.binning import BIN_CATEGORICAL, MISSING_NAN
from ..io.dataset import Dataset
from ..learner import (FeatureMeta, GrowParams, grow_tree,
                       grow_tree_donated, grow_tree_wave,
                       grow_tree_wave_donated, plan_growth)
from ..models.tree import Tree
from ..objective import ObjectiveFunction
from ..ops.histogram import class_ordered, hist_classes_of
from ..ops.split import SplitParams
from ..metric import Metric
from ..observability import (first_iter_compile_phases,
                             global_registry as _metrics)
from ..reliability import faults
from ..utils import log
from ..utils.timer import global_timer
from . import leaf_lookup

K_EPSILON = 1e-15
_PAD = 1024  # row padding multiple (histogram chunking requirement)

# score/gradient buffers are donated through the jitted update entries
# (docs/Performance.md); CPU XLA cannot alias every donated buffer and
# warns per executable — same silencing as inference/predictor.py
import warnings as _warnings  # noqa: E402

_warnings.filterwarnings("ignore",
                         message="Some donated buffers were not usable")

# sentinel stored in models_ for device trees not yet pulled to host
_PENDING_TREE = object()


@functools.partial(jax.jit, static_argnames=("top_k", "other_k"),
                   donate_argnums=(0, 1))
def _goss_sample(grad, hess, pad_mask, key, top_k, other_k):
    """Gradient one-side sampling on device (ref: goss.hpp:118-165):
    keep the top_k rows by sum_k |g*h|, Bernoulli-sample ~other_k of the rest
    and amplify them by (n_kept_pool)/other_k.  The incoming grad/hess
    are replaced by the rescaled outputs, so their buffers are donated."""
    imp = jnp.sum(jnp.abs(grad * hess), axis=0) * pad_mask
    thr = jax.lax.top_k(imp, top_k)[0][-1]
    is_top = (imp >= thr) & (pad_mask > 0)
    n_real = jnp.sum(pad_mask)
    rest = n_real - jnp.sum(is_top.astype(jnp.float32))
    prob = other_k / jnp.maximum(rest, 1.0)
    sampled = ((jax.random.uniform(key, imp.shape) < prob)
               & ~is_top & (pad_mask > 0))
    multiply = rest / other_k
    scale = jnp.where(sampled, multiply, 1.0)
    keep = (is_top | sampled).astype(grad.dtype)
    return keep, grad * scale[None, :], hess * scale[None, :]


def _fetch_host(a) -> np.ndarray:
    """Device -> host fetch that also works for multi-process arrays:
    np.asarray refuses ANY array spanning non-addressable devices, but the
    packed tree buffer is pinned fully-replicated under multi-process
    SPMD (see _pack_tree_fn), so the local shard IS the whole value."""
    if isinstance(a, jax.Array) and not a.is_fully_addressable:
        return np.asarray(a.addressable_shards[0].data)
    return np.asarray(a)


def _mesh_size(config, ndev: int) -> int:
    """Device-mesh size policy shared by the EFB gate and
    _make_training_mesh (ref: config.h num_machines; application.cpp:100
    machine setup).  Under multi-process SPMD the machine list already
    defines the cluster, so the mesh spans every global device; in a
    single process num_machines caps the local device count (mesh
    emulation of an N-machine run)."""
    if jax.process_count() > 1:
        return ndev
    want = config.num_machines if config.num_machines > 1 else ndev
    return min(want, ndev)


def _pad_rows(arr: np.ndarray, n_pad: int, axis: int = -1, fill=0):
    n = arr.shape[axis]
    if n == n_pad:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, n_pad - n)
    return np.pad(arr, widths, constant_values=fill)


def leaf_index_bin_space(split_feature_inner, threshold_bin, default_left,
                         left_child, right_child, num_leaves,
                         missing_type, num_bin, default_bin,
                         binned: np.ndarray, is_cat_node=None,
                         cat_boundaries_inner=None,
                         cat_threshold_inner=None,
                         bundle_group=None, bundle_offset=None,
                         bundle_zero_bin=None) -> np.ndarray:
    """Vectorized bin-space tree traversal on host (mirror of the device
    partition rule; ref: dense_bin.hpp:346-366 SplitInner + tree.h:372
    CategoricalDecision over bin bitsets).  When bundle_* arrays are
    given, `binned` holds EFB bundle codes (sparse-ingested datasets)
    and each node's feature bin is decoded from its bundle column."""
    from ..io.binning import MISSING_NAN, MISSING_ZERO
    n = binned.shape[1]
    if num_leaves <= 1:
        return np.zeros(n, dtype=np.int32)
    has_cat = is_cat_node is not None and np.any(is_cat_node)
    if has_cat:
        cb = np.asarray(cat_boundaries_inner, np.int64)
        ct = np.asarray(cat_threshold_inner, np.uint32)
    node = np.zeros(n, dtype=np.int32)
    for _ in range(num_leaves):
        active = node >= 0
        if not active.any():
            break
        nd = node[active]
        f = split_feature_inner[nd]
        if bundle_group is not None:
            code = binned[bundle_group[f], np.nonzero(active)[0]]
            code = code.astype(np.int64)
            off = bundle_offset[f]
            local = code - off
            valid = (local >= 0) & (local < num_bin[f])
            b = np.where(off == 0, code,
                         np.where(valid, local, bundle_zero_bin[f]))
        else:
            b = binned[f, np.nonzero(active)[0]]
        mt = missing_type[f]
        is_missing = (((mt == MISSING_NAN) & (b == num_bin[f] - 1))
                      | ((mt == MISSING_ZERO) & (b == default_bin[f])))
        go_left = np.where(is_missing, default_left[nd], b <= threshold_bin[nd])
        if has_cat:
            cat_nd = is_cat_node[nd]
            cat_idx = np.where(cat_nd, threshold_bin[nd], 0)
            start = cb[cat_idx]
            nwords = cb[cat_idx + 1] - start
            word = b.astype(np.int64) // 32
            ok = word < nwords
            wv = ct[np.clip(start + word, 0, len(ct) - 1)] if len(ct) else 0
            cat_left = ok & (((wv >> (b % 32).astype(np.uint32)) & 1) > 0)
            go_left = np.where(cat_nd, cat_left, go_left)
        node[active] = np.where(go_left, left_child[nd], right_child[nd])
    return (~node).astype(np.int32)


class GBDT:
    """ref: src/boosting/gbdt.cpp GBDT."""

    average_output_ = False  # RF overrides (ref: gbdt.h average_output_)

    def __init__(self):
        self.models_: List[Tree] = []
        self.iter_ = 0
        self.num_init_iteration_ = 0
        self.config: Optional[Config] = None
        self.train_data: Optional[Dataset] = None
        self.objective: Optional[ObjectiveFunction] = None
        self.best_iteration = -1
        self._pending = []       # device trees awaiting host materialization
        self._stump_idxs = set()  # model indices of no-split trees
        self._device_eval = None  # lazy ops.metrics.DeviceEval
        self._finite_cache = None  # (grads_finite, scores_finite) this iter

    # ------------------------------------------------------------ distributed
    def _make_training_mesh(self, config: Config):
        """Distributed learner selection (ref: tree_learner.cpp:15
        CreateTreeLearner; SURVEY §2.3).  tree_learner=data shards the row
        axis over a 1-D device mesh: the histogram reduction becomes a GSPMD
        psum, replacing Network::ReduceScatter
        (data_parallel_tree_learner.cpp:284), and the best-split argmax runs
        on the replicated histogram, replacing SyncUpGlobalBestSplit.
        tree_learner=feature shards the FEATURE axis of the binned matrix
        (feature_parallel_tree_learner.cpp:23): each device scans its feature
        block and the argmax all-gathers the winner.  voting is data-parallel
        with the PV-Tree top-k vote: per-leaf scans elect ~top_k features
        and reduce only those histograms over the mesh
        (voting_parallel_tree_learner.cpp:151 GlobalVoting)."""
        tl = config.tree_learner
        if tl not in ("serial", "data", "feature", "voting"):
            log.fatal(f"Unknown tree_learner {tl!r}")
        self._voting = tl == "voting"
        if tl == "serial":
            return None
        n_mesh = _mesh_size(config, len(jax.devices()))
        if tl == "feature":
            # GSPMD needs the sharded axis size divisible by the mesh: use
            # the largest divisor of the device column count (the reference
            # instead hand-balances unequal feature subsets,
            # feature_parallel_tree_learner.cpp:30)
            F = self._n_device_cols
            requested = n_mesh
            while n_mesh > 1 and F % n_mesh != 0:
                n_mesh -= 1
            if n_mesh != requested:
                log.warning(
                    f"tree_learner=feature: {F} feature columns have no "
                    f"equal split over {requested} devices; using "
                    f"{n_mesh} device(s) instead"
                    + (" (feature parallelism DISABLED — consider "
                       "tree_learner=data)" if n_mesh <= 1 else ""))
        if n_mesh <= 1:
            self._voting = False
            return None
        from ..parallel import make_mesh
        self._mesh_axis = 1 if tl in ("data", "voting") else 0
        return make_mesh(n_mesh)

    def _put_by_row(self, arr, axis=None, is_binned=False):
        """Place a host array on the mesh, sharded along its row axis (the
        LAST axis unless given); no-op single-device put without a mesh.
        Under feature-parallel only the binned [F, n] matrix is sharded
        (axis 0); all row tensors stay replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        if self.mesh is None:
            return jnp.asarray(arr)
        a = np.asarray(arr)
        if self._mesh_axis == 0:
            if not is_binned:
                return jnp.asarray(a)
            spec = P("data", None)
        else:
            ax = a.ndim - 1 if axis is None else axis
            spec = P(*(["data" if i == ax else None
                        for i in range(a.ndim)]))
        return jax.device_put(a, NamedSharding(self.mesh, spec))

    # ------------------------------------------------------------------ init
    def init(self, config: Config, train_data: Dataset,
             objective: Optional[ObjectiveFunction],
             metrics: Sequence[Metric]) -> None:
        # persistent XLA compilation cache: repeat runs of the same
        # config skip the ladder compile; must be wired before the first
        # jit below traces (docs/Performance.md)
        from ..observability import configure_compile_cache
        configure_compile_cache(config.compile_cache_dir)
        self.config = config
        self.train_data = train_data
        self.objective = objective
        self.train_metrics = list(metrics)
        self.shrinkage_rate = config.learning_rate
        self.num_class = config.num_class
        self.num_tree_per_iteration = (objective.num_model_per_iteration()
                                       if objective is not None else config.num_class)
        self.num_data = train_data.num_data
        self.valid_sets: List[Dataset] = []
        self.valid_metrics: List[List[Metric]] = []
        self.valid_names: List[str] = []
        self.valid_scores: List[np.ndarray] = []
        self.class_need_train = [True] * self.num_tree_per_iteration

        n = train_data.num_data
        self.n_pad = (n + _PAD - 1) // _PAD * _PAD
        binned = train_data.binned
        # EFB: bundle exclusive sparse features into shared device columns
        # (ref: feature_group.h; io/bundle.py).  The bundle plan is purely
        # a device-layout optimization — host paths (prediction, leaf ids,
        # model IO) keep per-feature bins.
        self.bundle_plan = None
        # the PV-Tree vote is per-feature, so EFB is skipped only when
        # voting will actually engage: a >1-device mesh exists AND the
        # num_machines cap doesn't reduce the mesh to a single device
        # (otherwise _make_training_mesh returns None and serial training
        # would silently lose bundling)
        voting_engages = (config.tree_learner == "voting"
                          and _mesh_size(config, len(jax.devices())) > 1)
        if train_data.pre_bundled_plan is not None:
            # sparse CSC-direct ingestion already produced bundle codes
            # (io/sparse.py); never re-plan or densify
            self.bundle_plan = train_data.pre_bundled_plan
        elif (config.enable_bundle and train_data.num_features > 1
                and not voting_engages):
            from ..io.bundle import build_bundled, plan_bundles
            # host span: the planner and the binning of its row sample
            # (benchmarks' efb_plan_s)
            with global_timer.scope("GBDT::plan_bundles"):
                plan_src = binned
                if isinstance(binned, jax.Array):
                    # device-binned: plan from host bins of the
                    # construction sample, which the dataset kept — no
                    # device gather, no D2H of matrix columns (the cost
                    # of gathering them from a local chip instead: not
                    # re-measured since bring-up)
                    plan_src = train_data.efb_sample_bins()
                    if plan_src is None:
                        plan_src = train_data.binned_host()
                plan = plan_bundles(
                    plan_src, train_data.bin_mappers,
                    train_data.used_features,
                    max_conflict_rate=config.max_conflict_rate)
            if plan.effective:
                self.bundle_plan = plan
                if isinstance(binned, jax.Array):
                    binned = train_data.binned_host()
                binned = build_bundled(binned, plan)
                log.info(f"EFB bundled {len(plan.group_idx)} features into "
                         f"{plan.num_groups} columns")
        if self.bundle_plan is not None:
            # what bundling takes off the kernel's feature axis and what
            # the decode must move, counted where the plan is fixed
            # (benchmarks' efb_bundle_ratio, efb_decode_roofline)
            from ..observability import global_registry
            bp = self.bundle_plan
            for name, value in (
                    ("efb_features", len(bp.group_idx)),
                    ("efb_bundles", bp.num_groups),
                    ("efb_member_bins", sum(
                        train_data.bin_mappers[f].num_bin
                        for f in train_data.used_features)),
                    ("efb_bundle_bins", int(bp.group_num_bin.sum()))):
                global_registry.inc(name, value)
        dtype = np.uint8 if (binned.max() if self.bundle_plan else
                             train_data.max_num_bin - 1) <= 255 else np.int32
        self._n_device_cols = binned.shape[0]
        self.mesh = self._make_training_mesh(config)
        if self.mesh is not None and self._mesh_axis == 1:
            # sharded rows: each device's local shard must itself be a
            # _PAD multiple (the sharded-wave Pallas kernel tiles local
            # rows; shard_map sees only the shard) — pad the global row
            # count to _PAD * mesh_size
            m = _PAD * int(self.mesh.devices.size)
            self.n_pad = (n + m - 1) // m * m
        if self._voting and train_data.pre_bundled_plan is not None:
            # the PV-Tree vote is per-feature; bundle codes from sparse
            # ingestion cannot vote — run the plain data-parallel
            # histogram reduction over the same mesh instead
            log.warning("tree_learner=voting needs per-feature bins; "
                        "sparse pre-bundled datasets fall back to "
                        "data-parallel histogram reduction")
            self._voting = False
        if isinstance(binned, jax.Array) and self.mesh is None:
            # device-binned dataset (io/device_bin.py): pad on device —
            # the 280MB-class bin matrix never makes a host round-trip.
            # The unpadded buffer is DONATED so only one device copy
            # stays resident; the dataset keeps a view descriptor for
            # lazy host recovery (binned_host)
            pad = self.n_pad - binned.shape[1]
            n_true = binned.shape[1]
            if pad == 0:
                bd = binned
            else:
                bd = jnp.pad(binned, ((0, 0), (0, pad)))
                # drop the unpadded device copy — the dataset recovers a
                # host view lazily through _binned_view when needed
                train_data.binned = None
            self.binned_dev = (bd if bd.dtype == dtype
                               else bd.astype(dtype))
            train_data._binned_view = (self.binned_dev, n_true)
        else:
            if isinstance(binned, jax.Array):
                binned = train_data.binned_host()   # mesh placement is
                # host-driven (_put_by_row shards the host copy)
            self.binned_dev = self._put_by_row(
                _pad_rows(binned.astype(dtype), self.n_pad), axis=1,
                is_binned=True)
        self.pad_mask = self._put_by_row(
            _pad_rows(np.ones(n, np.float32), self.n_pad))

        # per-feature metadata, device side
        mt, nb, db, cat = [], [], [], []
        for f in train_data.used_features:
            m = train_data.bin_mappers[f]
            mt.append(m.missing_type)
            nb.append(m.num_bin)
            db.append(m.default_bin)
            cat.append(m.bin_type == BIN_CATEGORICAL)
        self.f_missing_type = np.array(mt, np.int32)
        self.f_num_bin = np.array(nb, np.int32)
        self.f_default_bin = np.array(db, np.int32)
        self.f_is_cat = np.array(cat, bool)
        penalty = np.ones(len(nb), np.float32)
        if config.feature_contri:
            for i, f in enumerate(train_data.used_features):
                if f < len(config.feature_contri):
                    penalty[i] = config.feature_contri[f]
        # monotone constraints indexed by real feature -> used features
        # (ref: config.h monotone_constraints; monotone_constraints.hpp)
        mono = np.zeros(len(nb), np.int32)
        if config.monotone_constraints:
            mc_list = list(config.monotone_constraints)
            for i, f in enumerate(train_data.used_features):
                if f < len(mc_list):
                    mono[i] = int(mc_list[f])
        self.f_monotone = mono
        has_mono = bool(np.any(mono != 0))
        if has_mono and config.monotone_constraints_method not in (
                "basic", "intermediate", "advanced"):
            log.fatal("Unknown monotone_constraints_method "
                      f"{config.monotone_constraints_method!r}")
        self._mono_intermediate = False
        self._mono_advanced = False
        if has_mono and config.monotone_constraints_method != "basic":
            if config.extra_trees or config.feature_fraction_bynode < 1.0:
                log.warning("monotone_constraints_method="
                            f"{config.monotone_constraints_method} "
                            "falls back to basic with extra_trees / "
                            "feature_fraction_bynode (the full-tree "
                            "pending rescan has no per-leaf random state)")
            else:
                self._mono_intermediate = True
                self._mono_advanced = (
                    config.monotone_constraints_method == "advanced")
        # CEGB (ref: cost_effective_gradient_boosting.hpp IsEnable)
        has_lazy = bool(config.cegb_penalty_feature_lazy)
        has_cegb = (config.cegb_tradeoff < 1.0
                    or config.cegb_penalty_split > 0.0
                    or bool(config.cegb_penalty_feature_coupled)
                    or has_lazy)
        lazy = np.zeros(len(nb), np.float32)
        if has_lazy:
            lz = list(config.cegb_penalty_feature_lazy)
            if len(lz) != train_data.num_total_features:
                log.fatal("cegb_penalty_feature_lazy should be the same "
                          "size as feature number.")
            for i, f in enumerate(train_data.used_features):
                lazy[i] = lz[f]
        coupled = np.zeros(len(nb), np.float32)
        if config.cegb_penalty_feature_coupled:
            cp = list(config.cegb_penalty_feature_coupled)
            if len(cp) != train_data.num_total_features:
                log.fatal("cegb_penalty_feature_coupled should be the same "
                          "size as feature number.")
            for i, f in enumerate(train_data.used_features):
                coupled[i] = cp[f]
        self._cegb_used = (jnp.zeros(len(nb), bool) if has_cegb else None)
        if has_lazy and self._mono_intermediate:
            log.warning("monotone intermediate mode falls back to basic "
                        "with cegb_penalty_feature_lazy")
            self._mono_intermediate = False
        if has_lazy and self._voting:
            log.fatal("cegb_penalty_feature_lazy is not supported with "
                      "tree_learner=voting")
        # per-(feature, row) fetched bitset, persistent across trees
        # (ref: cost_effective_gradient_boosting.hpp:63 feature_used_in_data_)
        self._lazy_used = (self._put_by_row(
            np.zeros((len(nb), self.n_pad), bool), axis=1)
            if has_lazy else None)
        bp = self.bundle_plan
        # the device columns' histogram classes: the multiset is static
        # in the grow program, which column holds which is data
        # (ops/histogram.py hist_classes_of)
        column_codes = self.f_num_bin if bp is None else bp.group_num_bin
        hist_classes, hist_order = hist_classes_of(column_codes)
        one_class = len(hist_classes) < 2
        _metrics.set_gauge("hist_classes", len(hist_classes))
        # what a histogram of this table must multiply a row and output
        # column, whatever the kernels pad it to (the benchmark's
        # `hist_mxu_roofline` counts useful MXU work from it)
        _metrics.inc("hist_codes", int(np.sum(column_codes)))
        # categorical columns and their bins: what a scanned leaf's
        # categorical scan must read (the benchmark's cat_scan_roofline)
        cat_index = np.nonzero(self.f_is_cat)[0].astype(np.int32)
        if len(cat_index):
            _metrics.inc("cat_features", len(cat_index))
            _metrics.inc("cat_bins", int(self.f_num_bin[cat_index].sum()))
        # numerical columns whose last bin holds NaN: where the gain scan
        # runs its forward (missing goes right) pass
        self._nan_numerical = ((self.f_missing_type == MISSING_NAN)
                               & ~self.f_is_cat)
        if self._nan_numerical.any():
            _metrics.inc("missing_features", int(self._nan_numerical.sum()))
        self.meta = FeatureMeta(
            num_bin=jnp.asarray(self.f_num_bin),
            missing_type=jnp.asarray(self.f_missing_type),
            default_bin=jnp.asarray(self.f_default_bin),
            penalty=jnp.asarray(penalty),
            is_cat=jnp.asarray(self.f_is_cat),
            cat_index=jnp.asarray(cat_index) if len(cat_index) else None,
            monotone=jnp.asarray(mono),
            cegb_coupled=jnp.asarray(coupled),
            cegb_lazy=jnp.asarray(lazy),
            group=None if bp is None else jnp.asarray(bp.group_idx),
            offset=None if bp is None else jnp.asarray(bp.offsets),
            zero_bin=None if bp is None else jnp.asarray(bp.zero_bin),
            in_bundle=None if bp is None else jnp.asarray(bp.in_bundle),
            hist_order=None if one_class else jnp.asarray(hist_order),
            hist_inverse=(None if one_class else
                          jnp.asarray(np.argsort(hist_order), jnp.int32)))

        max_b = int(self.f_num_bin.max()) if len(nb) else 1
        # the shape the histogram kernel runs and the per-leaf stack
        # holds: device columns x their bins, which under bundles is not
        # features x max_bin (700 x 63 features in 13 columns of 255)
        hist_b = max_b if bp is None else int(bp.group_num_bin.max())
        # histogram stack memory guard (HistogramPool analogue)
        stack_bytes = (config.num_leaves * self._n_device_cols * hist_b
                       * 2 * 4)
        budget = (config.histogram_pool_size * 1024 * 1024
                  if config.histogram_pool_size > 0 else 512 * 1024 * 1024)
        self.grow_params = GrowParams(
            num_leaves=config.num_leaves,
            max_depth=config.max_depth,
            max_bin=max_b,
            split=SplitParams(
                lambda_l1=config.lambda_l1, lambda_l2=config.lambda_l2,
                min_data_in_leaf=config.min_data_in_leaf,
                min_sum_hessian_in_leaf=config.min_sum_hessian_in_leaf,
                min_gain_to_split=config.min_gain_to_split,
                max_delta_step=config.max_delta_step,
                path_smooth=config.path_smooth,
                has_categorical=bool(self.f_is_cat.any()),
                has_missing=bool((self.f_missing_type != 0).any()),
                max_cat_to_onehot=config.max_cat_to_onehot,
                max_cat_threshold=config.max_cat_threshold,
                cat_l2=config.cat_l2, cat_smooth=config.cat_smooth,
                min_data_per_group=config.min_data_per_group,
                has_monotone=has_mono,
                monotone_penalty=config.monotone_penalty,
                extra_trees=config.extra_trees,
                extra_seed=config.extra_seed,
                has_cegb=has_cegb,
                cegb_tradeoff=config.cegb_tradeoff,
                cegb_penalty_split=config.cegb_penalty_split,
                has_cegb_lazy=has_lazy),
            has_bundles=bp is not None,
            group_max_bin=0 if bp is None else hist_b,
            hist_classes=() if one_class else hist_classes,
            feature_fraction_bynode=config.feature_fraction_bynode,
            bynode_seed=config.feature_fraction_seed + 1,
            monotone_intermediate=self._mono_intermediate,
            monotone_advanced=self._mono_advanced,
            wave_tail_halving=config.wave_tail_halving,
            wave_prune=config.wave_prune,
            wave_prune_overshoot=config.wave_prune_overshoot,
            wave_spike_reserve=config.wave_spike_reserve,
            wave_spike_k=config.wave_spike_k,
            # int8 MXU histogram path for quantized training (grid must
            # fit int8; hessian ints reach num_grad_quant_bins).  The
            # int32 accumulator must hold n * max_int for a root-level
            # cell (the reference bounds this with per-leaf 8/16/32-bit
            # histogram widths, SetNumBitsInHistogramBin); larger inputs
            # fall back to the fp32 kernel
            quant_bins=(config.num_grad_quant_bins
                        if (config.use_quantized_grad
                            and config.num_grad_quant_bins <= 126
                            and self.n_pad * config.num_grad_quant_bins
                            < 2**31) else 0),
            use_hist_stack=stack_bytes <= budget)
        if (self.grow_params.monotone_intermediate
                and not self.grow_params.use_hist_stack):
            log.warning("monotone intermediate mode needs the per-leaf "
                        "histogram stack (histogram_pool_size); falling "
                        "back to basic")
            self.grow_params = self.grow_params._replace(
                monotone_intermediate=False)
        if self.mesh is not None and self._mesh_axis == 1:
            # row sharding: masked engine (global-index row gathers would
            # all-gather the binned matrix).  The wave engine keeps its
            # Pallas histogram and runs under explicit shard_map; only the
            # leaf-wise engine, which rides GSPMD annotations, downgrades
            # to the XLA segment histogram (learner/select.py).
            from ..parallel import grow_params_for_mesh
            self.grow_params = grow_params_for_mesh(self.grow_params)
            if self._voting:
                # PV-Tree vote (ref: voting_parallel_tree_learner.cpp):
                # children rebuilt per scan (elected feature sets differ
                # between parent and children, so subtraction is invalid)
                from ..parallel.voting import VotingSpec
                if config.forcedsplits_filename:
                    log.fatal("tree_learner=voting does not support "
                              "forced splits")
                if config.top_k <= 0:
                    log.fatal("top_k should be greater than 0 "
                              "(ref: config.cpp CHECK_GT(top_k, 0))")
                if self.grow_params.monotone_intermediate:
                    log.warning("monotone intermediate mode falls back to "
                                "basic under tree_learner=voting (no "
                                "histogram stack to rescan)")
                self.grow_params = self.grow_params._replace(
                    use_hist_stack=False, monotone_intermediate=False,
                    voting=VotingSpec(self.mesh, min(config.top_k, len(nb)),
                                      int(self.mesh.devices.size)))
        # forced splits (ref: serial_tree_learner.cpp:614 ForceSplits):
        # parse the BFS JSON into static (leaf, inner_feature, bin) tuples
        # using our split numbering (left child keeps the leaf index,
        # right child becomes leaf step+1)
        if config.forcedsplits_filename:
            import json as _json
            from collections import deque
            with open(config.forcedsplits_filename) as f:
                forced_json = _json.load(f)
            inner_of = {f: i for i, f in enumerate(train_data.used_features)}
            forced = []
            queue = deque([(forced_json, 0)])
            while queue and len(forced) < config.num_leaves - 1:
                node, leaf = queue.popleft()
                if not node or "feature" not in node:
                    continue
                real_f = int(node["feature"])
                if real_f not in inner_of:
                    log.warning(f"forced split feature {real_f} unused; "
                                "skipping subtree")
                    continue
                fi = inner_of[real_f]
                mapper = train_data.bin_mappers[real_f]
                thr_bin = mapper.value_to_bin(float(node["threshold"]))
                new_leaf = len(forced) + 1
                forced.append((leaf, fi, int(thr_bin)))
                if "left" in node and node["left"]:
                    queue.append((node["left"], leaf))
                if "right" in node and node["right"]:
                    queue.append((node["right"], new_leaf))
            self.grow_params = self.grow_params._replace(
                forced_splits=tuple(forced))
            if not self.grow_params.use_hist_stack:
                log.fatal("forced splits need the per-leaf histogram stack; "
                          "raise histogram_pool_size")
        if config.tpu_growth_strategy not in ("auto", "wave", "leafwise"):
            log.fatal("Unknown tpu_growth_strategy "
                      f"{config.tpu_growth_strategy!r}; "
                      "expected auto, wave, or leafwise")
        # interaction constraints (ref: config.h:585; col_sampler.hpp:91):
        # "[0,1,2],[2,3]" -> static inner-index sets
        if config.interaction_constraints:
            import re as _re
            inner_of = {f: i for i, f in enumerate(train_data.used_features)}
            sets = []
            # accept both the string form "[0,1],[2,3]" and the python
            # list-of-lists form (str() of which nests brackets)
            for grp in _re.findall(r"\[([^\[\]]*)\]",
                                   str(config.interaction_constraints)):
                idxs = tuple(sorted(inner_of[int(tok)]
                                    for tok in grp.split(",")
                                    if tok.strip() != ""
                                    and int(tok) in inner_of))
                if idxs:
                    sets.append(idxs)
            self.grow_params = self.grow_params._replace(
                interaction_sets=tuple(sets))
        # growth engine and histogram method: one function of the
        # configuration, the backend and the shape (learner/select.py)
        plan = plan_growth(
            backend=jax.default_backend(),
            strategy=config.tpu_growth_strategy,
            num_leaves=config.num_leaves,
            num_features=self._n_device_cols, max_bin=hist_b,
            gpu_use_dp=config.gpu_use_dp,
            pinned_leafwise=(self.grow_params.voting is not None
                             or self.grow_params.monotone_intermediate
                             or self.grow_params.split.has_cegb_lazy),
            row_mesh=self.mesh is not None and self._mesh_axis == 1,
            voting=self.grow_params.voting is not None)
        for msg in plan.warnings:
            log.warning(msg)
        self.grow_params = self.grow_params._replace(
            hist_method=plan.hist_method)
        # grad/hess buffer donation into the grow program
        # (docs/Performance.md): the per-class slices die at the grow
        # call in every configuration except linear trees, whose leaf
        # fitting re-reads them afterwards
        donate_grow = (config.tpu_donate_buffers and not config.linear_tree)
        if donate_grow and self.mesh is not None:
            # Donating the sharded grad/hess slices under the mesh is the
            # donation x SPMD interaction implicated when a multi-device
            # dry run wedged until the wall-clock cap: XLA cannot alias the row-sharded f32 inputs into
            # any output of the grow program (different dtype/sharding),
            # so donation buys nothing and destabilizes the multi-device
            # compile.  tests/test_multichip_smoke.py guards this matrix.
            log.warning("tpu_donate_buffers: grow-buffer donation is "
                        "disabled under a device mesh (sharded inputs "
                        "cannot alias the grow outputs)")
            donate_grow = False
        if plan.sharded_wave:
            from ..parallel import make_sharded_wave_fn
            self._grow_fn = make_sharded_wave_fn(self.mesh,
                                                 donate=donate_grow)
        elif plan.strategy == "wave":
            self._grow_fn = (grow_tree_wave_donated if donate_grow
                             else grow_tree_wave)
        else:
            self._grow_fn = (grow_tree_donated if donate_grow
                             else grow_tree)
        self.growth_strategy = plan.strategy
        # recompile watchdog (docs/Observability.md): a mid-training
        # shape change on a jitted hot-path entry re-traces the whole
        # program — a multi-second stall with no other symptom.  The
        # wrapper warns once per new signature and counts `recompiles`
        # into the metrics registry.
        from ..observability import RecompileDetector
        self._grow_fn = RecompileDetector(self._grow_fn, "grow_tree")
        # several histogram classes: the wave kernel reads the bins in
        # class order — one more copy of them, made here once a booster
        # (the int8 arm keeps one class and the engine's own order)
        self._classed_kw = {}
        if (plan.strategy == "wave" and plan.hist_method == "pallas"
                and self.grow_params.hist_classes
                and not self.grow_params.quant_bins):
            # (under a mesh, sharded by rows like the bins; on one device
            # left uncommitted like them: one committed argument would
            # commit every later tree's gradients and lower the grow
            # program a second time at the second iteration)
            sharded = ({} if self.mesh is None else
                       {"out_shardings": self.binned_dev.sharding})
            self._classed_kw["binned_classed"] = jax.jit(
                class_ordered, **sharded)(self.binned_dev,
                                          self.meta.hist_order)

        # scores [K, n_pad] on device
        K = self.num_tree_per_iteration
        self.scores = self._put_by_row(
            np.zeros((K, self.n_pad), np.float32), axis=1)
        md = train_data.metadata
        self.has_init_score = md.init_score is not None
        if self.has_init_score:
            init = np.asarray(md.init_score, np.float64)
            if len(init) == n:
                init = np.tile(init, (K, 1)) if K > 1 else init[None, :]
            else:
                init = init.reshape(K, n)
            self.scores = self._put_by_row(
                _pad_rows(init.astype(np.float32), self.n_pad), axis=1)

        if objective is not None:
            objective.init(md, n)
            # objective.label may be transformed (e.g. reg_sqrt) — use it
            self.label_dev = self._put_by_row(
                _pad_rows(np.asarray(objective.label, np.float32), self.n_pad))
            self.weight_dev = (None if md.weight is None
                               else self._put_by_row(_pad_rows(
                                   np.asarray(md.weight, np.float32),
                                   self.n_pad)))
            if getattr(objective, "need_train", True) is False:
                self.class_need_train = [False] * K
            if not getattr(objective, "run_on_host", False):
                # one jitted gradient program per training run, taking the
                # FULL [K, n] scores and returning [K, n] grads.  All large
                # arrays are EXPLICIT arguments: a jit that closes over a
                # big device array embeds it as a constant — baked into
                # the executable, copied at compile time, and a new
                # compile for every new array; slicing/expansion also
                # stay inside jit (one dispatch instead of several eager
                # ones; the per-dispatch cost on a local chip: not
                # re-measured since bring-up).
                if self.num_tree_per_iteration > 1:
                    def _gradk(sc, lab, w):
                        with global_timer.device_scope("GBDT::gradients"):
                            return objective.get_gradients(sc, lab, w)
                    self._grad_fn_raw = jax.jit(_gradk)
                else:  # single-model path: slice + expand inside jit
                    def _grad1(sc, lab, w):
                        with global_timer.device_scope("GBDT::gradients"):
                            g, h = objective.get_gradients(sc[0], lab, w)
                            return g[None, :], h[None, :]
                    self._grad_fn_raw = jax.jit(_grad1)
                from ..observability import RecompileDetector
                self._grad_fn_raw = RecompileDetector(self._grad_fn_raw,
                                                      "gradients")
                self._grad_fn = lambda sc: self._grad_fn_raw(
                    sc, self.label_dev, self.weight_dev)
        for m in self.train_metrics:
            m.init(md, n)
        self.init_scores_applied = [0.0] * K

        # ---- host-boundary machinery (docs/Performance.md) ----
        # device eval metrics: built lazily on the first eval tick
        # (ops/metrics.py); _finite_cache carries the sentinel flags
        # fetched with (or instead of) that tick's packed vector
        self._device_eval = None
        self._finite_cache = None
        self._true_flag = jnp.asarray(True)

        # tpulint: disable-next=donate-argnums -- read-only sentinel reduction; the boosting loop keeps updating the score buffer
        @jax.jit
        def _finite_flags(scores, grad_ok):
            return jnp.stack([grad_ok.astype(jnp.float32),
                              jnp.all(jnp.isfinite(scores))
                              .astype(jnp.float32)])
        self._finite_flags_fn = _finite_flags
        # private device-side copy for async checkpointing: the live
        # buffer may be DONATED to the next update while the writer
        # thread is still fetching, so snapshots fetch their own copy
        # tpulint: disable-next=donate-argnums -- the point is a second live copy; donating would delete the source buffer
        self._snapshot_scores_fn = jax.jit(lambda scores: scores + 0.0)
        # Donation: the per-iteration score updates consume the old
        # buffer and produce its replacement — donating lets XLA reuse
        # the HBM allocation instead of copying [K, n_pad] every tree
        # (enforced package-wide by the tpulint donate-argnums rule).
        _donate0 = (0,) if config.tpu_donate_buffers else ()

        # each row's leaf value comes through leaf_lookup: by one-hot on a
        # TPU (bit for bit the gather's value, a tenth of its time and
        # less), so no per-row XLA gather is left in the training loop
        def _score_update(scores, class_id, leaf_vals, leaf_id, pad_mask):
            with global_timer.device_scope("GBDT::score_update"):
                delta = leaf_lookup.lookup(leaf_vals, leaf_id)
                return scores.at[class_id].add(delta * pad_mask)
        self._score_update_fn = jax.jit(_score_update,
                                        donate_argnums=_donate0)

        @jax.jit
        def _pack_tree(t):
            # single flat f32 buffer so the host pulls the whole tree in ONE
            # D2H transfer instead of sixteen (the per-transfer cost on a
            # local chip: not re-measured since bring-up); int arrays ride
            # along bit-exactly via
            # bitcast (mirrors CUDATree::ToHost's batched copy,
            # ref: src/io/cuda/cuda_tree.cpp)
            as_f32 = lambda a: jax.lax.bitcast_convert_type(
                a.astype(jnp.int32), jnp.float32)
            return jnp.concatenate([
                as_f32(t.num_leaves[None]),
                as_f32(t.split_feature), as_f32(t.threshold_bin),
                as_f32(t.default_left), t.split_gain,
                as_f32(t.left_child), as_f32(t.right_child),
                t.internal_value, t.internal_weight,
                as_f32(t.internal_count),
                t.leaf_value, t.leaf_weight, as_f32(t.leaf_count),
                as_f32(t.leaf_parent), as_f32(t.leaf_depth),
                as_f32(t.split_is_cat),
                as_f32(t.cat_bitset.reshape(-1)),
                # the wave engine's own counts of the waves it ran, of
                # those whose histogram call ran narrower than the wave's
                # bound, and of those calls' N tiles ride along (no
                # transfer of their own)
                as_f32(jnp.stack([jnp.asarray(0 if c is None else c)
                                  for c in (t.waves, t.narrowed_waves,
                                            t.slot_tiles)]))])
        if jax.process_count() > 1 and self.mesh is not None:
            # multi-process SPMD: GSPMD may assign the packed buffer a
            # sharding spanning other processes' devices, which the host
            # cannot fetch; pin it fully-replicated so every rank reads
            # its local copy (the reference's workers likewise each hold
            # the whole model after SyncUpGlobalBestSplit)
            from jax.sharding import NamedSharding, PartitionSpec
            self._pack_tree_fn = jax.jit(
                _pack_tree,
                out_shardings=NamedSharding(self.mesh, PartitionSpec()))
        else:
            self._pack_tree_fn = _pack_tree
        from ..ops.split import cat_bitset_words
        self._cat_words = cat_bitset_words(max_b)
        # hot-path helpers kept inside jit (one cached dispatch each,
        # where an eager op re-traces; cost per eager op on a local chip:
        # not re-measured since bring-up)
        self._slice_row_fn = jax.jit(
            lambda a, k: jax.lax.dynamic_index_in_dim(a, k, 0,
                                                      keepdims=False))
        self._score_add_fn = jax.jit(lambda sc, k, v: sc.at[k].add(v),
                                     donate_argnums=_donate0)

        def _score_update_shrink(scores, class_id, leaf_vals, rate,
                                 leaf_id, pad_mask):
            with global_timer.device_scope("GBDT::score_update"):
                # the shrinkage multiplies the [L] table, before the
                # lookup: a row's value is that one f32 product
                delta = leaf_lookup.lookup(leaf_vals * rate, leaf_id)
                return scores.at[class_id].add(delta * pad_mask)
        self._score_update_shrink_fn = jax.jit(_score_update_shrink,
                                               donate_argnums=_donate0)
        # ---- quantized training (ref: gradient_discretizer.{hpp,cpp};
        # config use_quantized_grad/num_grad_quant_bins/stochastic_rounding).
        # Gradients/hessians are snapped to the reference's integer grid on
        # device and DEQUANTIZED in place: the information content matches
        # the reference's int8 path exactly (k * scale for k in
        # [-qbins/2, qbins/2]), while accumulation stays in the fp32
        # histogram kernels (small integers times one scale are exact in
        # bf16 multiply / fp32 add).  The reference's 8/16/32-bit histogram
        # bin-width selection (SetNumBitsInHistogramBin) is a CPU memory
        # optimization with no TPU analogue.
        if config.linear_tree and objective is not None and getattr(
                objective, "need_renew_tree_output", False):
            # ref: config.cpp "Cannot use regression_l1 objective for
            # linear tree" (renewal overwrites the fitted leaf models)
            log.fatal(f"Cannot use objective {config.objective!r} "
                      "with linear_tree")
        self.use_quant = config.use_quantized_grad
        if self.use_quant:
            qhalf = max(config.num_grad_quant_bins // 2, 1)
            qbins = config.num_grad_quant_bins
            stoch = config.stochastic_rounding
            const_hess = bool(objective is not None
                              and getattr(objective, "is_constant_hessian",
                                          False)
                              and train_data.metadata.weight is None)
            base_key = jax.random.PRNGKey(config.seed + 5)

            def _disc(grad, hess, it):
                # ref: gradient_discretizer.cpp:120-160 DiscretizeGradients
                gscale = jnp.maximum(jnp.max(jnp.abs(grad)), 1e-35) / qhalf
                if const_hess:
                    hscale = jnp.maximum(jnp.max(jnp.abs(hess)), 1e-35)
                else:
                    hscale = (jnp.maximum(jnp.max(jnp.abs(hess)), 1e-35)
                              / qbins)
                if stoch:
                    kg, kh = jax.random.split(
                        jax.random.fold_in(base_key, it))
                    rg = jax.random.uniform(kg, grad.shape)
                    rh = jax.random.uniform(kh, hess.shape)
                else:
                    rg = rh = 0.5
                # static_cast<int8_t> truncates toward zero; the +/- noise
                # by gradient sign makes it stochastic round away from zero
                gi = jnp.trunc(grad / gscale + jnp.sign(grad) * rg)
                hi = (jnp.ones_like(hess) if const_hess
                      else jnp.trunc(hess / hscale + rh))
                return (gi * gscale, hi * hscale,
                        jnp.stack([gscale, hscale]))
            # tpulint: disable-next=donate-argnums -- the float grad/hess slices are reused for leaf renewal (float_grads) after discretization
            self._discretize_fn = jax.jit(_disc)
            if config.quant_train_renew_leaf:
                renew_p = SplitParams(
                    lambda_l1=config.lambda_l1, lambda_l2=config.lambda_l2,
                    max_delta_step=config.max_delta_step)

                def _renew(leaf_value, leaf_id, grad, hess, mask):
                    # ref: gradient_discretizer.cpp RenewIntGradTreeOutput —
                    # leaf outputs recomputed from the ORIGINAL float grads
                    from ..ops.split import leaf_output
                    L = leaf_value.shape[0]
                    ids = jnp.clip(leaf_id, 0, L - 1)
                    sg = jnp.zeros(L, jnp.float32).at[ids].add(grad * mask)
                    sh = jnp.zeros(L, jnp.float32).at[ids].add(hess * mask)
                    out = leaf_output(sg, sh, jnp.zeros(L, jnp.float32),
                                      0.0, renew_p)
                    return jnp.where(sh > 0, out, leaf_value)
                # the float grad/hess slices die here: renewal is their
                # last consumer, so their buffers are donated
                self._renew_quant_fn = jax.jit(
                    _renew, donate_argnums=((2, 3)
                                            if config.tpu_donate_buffers
                                            else ()))

        if has_cegb:
            F_used = len(nb)

            @jax.jit
            def _cegb_mark(used, split_feature, num_leaves):
                m = (jnp.arange(split_feature.shape[0], dtype=jnp.int32)
                     < num_leaves - 1)
                return used.at[jnp.where(m, split_feature, F_used)].set(
                    True, mode="drop")
            self._cegb_mark_fn = _cegb_mark
        self._rng_bag = np.random.RandomState(config.bagging_seed)
        self._rng_feat = np.random.RandomState(config.feature_fraction_seed)
        self._ones_col_mask = jnp.ones(len(nb), bool)
        self._bag_mask_host = np.ones(self.n_pad, np.float32)
        self._bag_mask_host[n:] = 0.0
        self.bag_mask = self._put_by_row(self._bag_mask_host)

    def _raw_or_reconstruct(self, ds: Dataset) -> np.ndarray:
        """Raw feature matrix for prediction: the kept raw data when present,
        else representative bin values (exact for trees trained with the same
        bin mappers, since numerical thresholds are bin upper bounds)."""
        if ds.raw_data is not None:
            return ds.raw_data
        from ..io.binning import MISSING_NAN, MISSING_ZERO
        X = np.zeros((ds.num_data, ds.num_total_features))
        for i, f in enumerate(ds.used_features):
            m = ds.bin_mappers[f]
            lut = np.array([m.bin_to_value(b) for b in range(m.num_bin)])
            # missing-value bins must reconstruct to the value the predictor's
            # default_left routing expects, not the bin's upper bound
            if m.missing_type == MISSING_NAN:
                lut[m.num_bin - 1] = np.nan
            elif m.missing_type == MISSING_ZERO:
                lut[m.default_bin] = 0.0
            X[:, f] = lut[np.clip(ds.feature_bins(i), 0, m.num_bin - 1)]
        return X

    def continue_from(self, prev: "GBDT", train_raw=None,
                      valid_raws=None) -> None:
        """Continued training: adopt prev's trees and seed train/valid scores
        with its predictions (ref: application.cpp:94-97 init score from
        input_model; gbdt.h:70 MergeFrom)."""
        if hasattr(prev, "_sync_model"):
            prev._sync_model()
        K = self.num_tree_per_iteration
        if prev.num_tree_per_iteration != K:
            log.fatal("Cannot continue training: the initial model has "
                      f"{prev.num_tree_per_iteration} trees per iteration, "
                      f"this one needs {K}")
        if getattr(prev, "average_output_", False) != self.average_output_:
            log.fatal("Cannot continue training across averaging modes "
                      "(rf vs gbdt/dart): tree outputs would be combined "
                      "with the wrong weights")
        self.models_ = [_copy.deepcopy(t) for t in prev.models_]
        for t in self.models_:
            self._reconstruct_bin_space(t)
        self.num_init_iteration_ = len(self.models_) // max(K, 1)
        self.iter_ = 0
        X = (train_raw if train_raw is not None
             else self._raw_or_reconstruct(self.train_data))
        raw = prev.predict_raw(np.asarray(X, np.float64))
        raw = raw[:, None] if raw.ndim == 1 else raw  # [n, K]
        self.scores = self.scores + jnp.asarray(
            _pad_rows(raw.T.astype(np.float32), self.n_pad))
        for vi, vds in enumerate(self.valid_sets):
            vX = (valid_raws[vi] if valid_raws is not None
                  and valid_raws[vi] is not None
                  else self._raw_or_reconstruct(vds))
            vraw = prev.predict_raw(np.asarray(vX, np.float64))
            vraw = vraw[:, None] if vraw.ndim == 1 else vraw
            self.valid_scores[vi] += vraw.T

    def _ensure_finite_flags(self):
        """(gradients_finite, scores_finite) for the current iteration.
        The device eval tick folds both flags into its packed fetch
        (ops/metrics.py); when no device eval ran this iteration, one
        dedicated tiny [2]-vector fetch computes them — either way the
        sentinel never pulls score samples to host (it used to fetch
        scores[:, :256])."""
        if self._finite_cache is None:
            flag = getattr(self, "_grad_ok", None)
            if flag is None:
                flag = self._true_flag
            flags = _fetch_host(self._finite_flags_fn(self.scores, flag))
            self._finite_cache = (bool(flags[0] > 0), bool(flags[1] > 0))
        return self._finite_cache

    def gradients_finite(self) -> bool:
        """Accumulated device-side gradient-finiteness flag (engine
        sentinel; one shared host fetch per check tick)."""
        return self._ensure_finite_flags()[0]

    def scores_finite(self) -> bool:
        """Device-side all-finite reduction over the full score buffer
        (engine sentinel; rides the same fetch as gradients_finite)."""
        return self._ensure_finite_flags()[1]

    # ------------------------------------------------------- checkpoint state
    def capture_train_state(self, async_copy: bool = False):
        """Exact trainer state for CheckpointManager: the float32 score
        buffer plus the stateful sampling RNGs.  Model text alone is not
        enough for byte-identical resume — re-seeding scores from
        predictions differs from the accumulated buffer in ulps, which
        changes later trees.  Returns None when the scores span
        non-addressable devices (multi-process SPMD): resume then falls
        back to predict-based seeding, which is rank-deterministic.

        With `async_copy` (the async checkpoint writer,
        docs/Performance.md) the scores stay a DEVICE array in the
        returned dict: a private snapshot copy whose D2H transfer is
        started here and completed by whoever serializes the state —
        the training thread never blocks on the fetch, and the live
        buffer is free to be donated to the next update meanwhile."""
        sc = self.scores
        if isinstance(sc, jax.Array) and not sc.is_fully_addressable:
            return None
        if async_copy and isinstance(sc, jax.Array):
            sc = self._snapshot_scores_fn(sc)
            copy_async = getattr(sc, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
        else:
            sc = np.asarray(sc)
        state = {"scores": sc,
                 "num_data": np.int64(self.num_data),
                 "rng_bag": np.array(self._rng_bag.get_state(legacy=False),
                                     dtype=object),
                 "rng_feat": np.array(self._rng_feat.get_state(legacy=False),
                                      dtype=object),
                 "bag_mask": np.asarray(self._bag_mask_host)}
        return state

    def restore_train_state(self, state) -> bool:
        """Restore a capture_train_state() payload (after continue_from
        adopted the checkpoint's trees).  Returns True when the exact
        score buffer was restored."""
        if state is None:
            return False
        ok = False
        sc = state.get("scores")
        if sc is not None:
            sc = np.asarray(sc, np.float32)
            n = int(state.get("num_data", sc.shape[-1]))
            if n != self.num_data:
                log.warning(f"Checkpoint state has {n} rows but the train "
                            f"set has {self.num_data}; keeping "
                            "predict-seeded scores")
            else:
                # re-pad for this run's mesh (n_pad can differ)
                self.scores = self._put_by_row(
                    _pad_rows(sc[:, :n], self.n_pad), axis=1)
                ok = True
        for key, rng in (("rng_bag", self._rng_bag),
                         ("rng_feat", self._rng_feat)):
            st = state.get(key)
            if st is not None:
                try:
                    rng.set_state(st.item() if hasattr(st, "item") else st)
                except (ValueError, TypeError) as e:
                    log.warning(f"Could not restore {key} RNG state: {e}")
        bm = state.get("bag_mask")
        if bm is not None and len(bm) >= self.num_data:
            mask = np.zeros(self.n_pad, np.float32)
            mask[:self.num_data] = np.asarray(bm, np.float32)[:self.num_data]
            self._bag_mask_host = mask
            self.bag_mask = self._put_by_row(mask)
        return ok

    def add_valid_data(self, valid_data: Dataset, name: str,
                       metrics: Sequence[Metric]) -> None:
        self.valid_sets.append(valid_data)
        self.valid_names.append(name)
        ms = list(metrics)
        for m in ms:
            m.init(valid_data.metadata, valid_data.num_data)
        self.valid_metrics.append(ms)
        K = self.num_tree_per_iteration
        sc = np.zeros((K, valid_data.num_data), np.float64)
        md = valid_data.metadata
        if md.init_score is not None:
            init = np.asarray(md.init_score, np.float64)
            sc += (np.tile(init, (K, 1)) if init.ndim == 1 and K > 1
                   else init.reshape(K, -1))
        self.valid_scores.append(sc)

    # ------------------------------------------------------------------ train
    def _boost_from_average(self, class_id: int) -> float:
        """ref: gbdt.cpp:313 BoostFromAverage."""
        cfg, obj = self.config, self.objective
        if self.models_ or self.has_init_score or obj is None:
            return 0.0
        if cfg.boost_from_average or self.train_data.num_features == 0:
            init = obj.boost_from_score(class_id)
            if abs(init) > K_EPSILON:
                self.scores = self._score_add_fn(self.scores, class_id, init)
                for sc in self.valid_scores:
                    sc[class_id] += init
                log.info(f"Start training from score {init:.6f}")
                return init
        elif obj.name in ("regression_l1", "quantile", "mape"):
            log.warning(f"Disabling boost_from_average in {obj.name} "
                        "may cause the slow convergence")
        return 0.0

    def _compute_gradients(self):
        """Per-class gradients [K, n_pad] (ref: gbdt.cpp:220 Boosting)."""
        obj = self.objective
        if getattr(obj, "run_on_host", False):
            # ranking objectives with a device program (bucketed pairwise
            # lambdas / masked-softmax passes + on-device position-bias
            # Newton state, ranking.py make_device_grad_fn) skip the host
            # round-trip entirely; the per-query host loop remains only
            # for position-bias rank_xendcg and custom objectives
            dev_fn = getattr(self, "_ranking_dev_fn", None)
            if dev_fn is None and hasattr(obj, "make_device_grad_fn"):
                dev_fn = obj.make_device_grad_fn(self.n_pad)
                self._ranking_dev_fn = dev_fn if dev_fn else False
            if dev_fn:
                return dev_fn(self.scores, self.weight_dev)
            score_h = np.asarray(self._slice_row_fn(
                self.scores, 0))[:self.num_data].astype(np.float64)
            g, h = obj.get_gradients_host(score_h)
            grad = jnp.asarray(_pad_rows(g, self.n_pad))[None, :]
            hess = jnp.asarray(_pad_rows(h, self.n_pad))[None, :]
            return grad, hess
        return self._grad_fn(self.scores)

    def _update_bagging(self, grad=None, hess=None):
        """Row sampling per iteration.  Bagging is a row mask (ref:
        src/boosting/bagging.hpp); GOSS also rescales small-gradient rows
        (ref: src/boosting/goss.hpp:118-165 Helper).  Returns
        (bag_mask, grad, hess)."""
        cfg = self.config
        n = self.num_data
        # sampling streams are keyed by the ABSOLUTE iteration so a
        # checkpoint resume (or init_model continuation) advances the
        # stream instead of replaying the first run's draws
        abs_iter = self.num_init_iteration_ + self.iter_
        if cfg.data_sample_strategy == "goss" and grad is not None:
            # not subsampled for the first 1/learning_rate iterations
            if abs_iter < int(1.0 / max(cfg.learning_rate, 1e-10)):
                return self.bag_mask, grad, hess
            top_k = max(1, int(n * cfg.top_rate))
            other_k = max(1, int(n * cfg.other_rate))
            key = jax.random.PRNGKey(cfg.bagging_seed + abs_iter)
            mask, grad, hess = _goss_sample(
                grad, hess, self.pad_mask, key, top_k, other_k)
            return mask, grad, hess
        if cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0:
            if abs_iter % cfg.bagging_freq == 0:
                pos_frac, neg_frac = cfg.pos_bagging_fraction, cfg.neg_bagging_fraction
                if (pos_frac < 1.0 or neg_frac < 1.0) and self.objective is not None \
                        and self.objective.name == "binary":
                    # balanced bagging (ref: bagging.hpp balanced_bagging_)
                    lab = np.asarray(self.train_data.metadata.label) > 0
                    mask = np.zeros(self.n_pad, np.float32)
                    for cls_mask, frac in ((lab, pos_frac), (~lab, neg_frac)):
                        cls_idx = np.nonzero(cls_mask)[0]
                        take = int(len(cls_idx) * frac)
                        mask[self._rng_bag.choice(cls_idx, take, replace=False)] = 1.0
                else:
                    cnt = int(n * cfg.bagging_fraction)
                    mask = np.zeros(self.n_pad, np.float32)
                    idx = self._rng_bag.choice(n, cnt, replace=False)
                    mask[idx] = 1.0
                self._bag_mask_host = mask
                self.bag_mask = jnp.asarray(mask)
        return self.bag_mask, grad, hess

    def _col_mask(self):
        cfg = self.config
        F = self.train_data.num_features
        if cfg.feature_fraction >= 1.0:
            return self._ones_col_mask
        cnt = max(1, int(round(F * cfg.feature_fraction)))
        mask = np.zeros(F, bool)
        mask[self._rng_feat.choice(F, cnt, replace=False)] = True
        return jnp.asarray(mask)

    def pre_gradient_hook(self) -> None:
        """Called before training scores are read for gradient computation
        (custom fobj path).  DART drops trees here so the user's objective
        sees the dropped ensemble (ref: dart.hpp:77 GetTrainingScore)."""

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        """One boosting iteration; returns True when training should stop
        (ref: gbdt.cpp:338 TrainOneIter)."""
        # the parent of the phase spans below: what it holds beyond them
        # is the host loop's own work (slices, dispatch, bookkeeping)
        with global_timer.scope("GBDT::iteration",
                                iter=self.num_init_iteration_ + self.iter_):
            if self.iter_ > 0:
                return self._train_one_iter(gradients, hessians)
            # the first iteration traces, lowers and compiles or loads
            # the train programs: first_iter_jit_trace_s, ...
            with first_iter_compile_phases():
                return self._train_one_iter(gradients, hessians)

    def _train_one_iter(self, gradients, hessians) -> bool:
        K = self.num_tree_per_iteration
        if faults.active():
            faults.maybe_crash(self.num_init_iteration_ + self.iter_)
            faults.maybe_worker_lost(self.num_init_iteration_ + self.iter_)
            faults.maybe_hang(self.num_init_iteration_ + self.iter_)
        # sentinel flags fetched for the previous iteration are stale now
        self._finite_cache = None
        init_scores = [0.0] * K
        if gradients is None:
            for k in range(K):
                init_scores[k] = self._boost_from_average(k)
            with global_timer.scope("GBDT::gradients"):
                grad, hess = self._compute_gradients()
                grad, hess = global_timer.block((grad, hess))
            if faults.active():
                grad, hess = faults.maybe_nan_grad(
                    grad, hess, self.num_init_iteration_ + self.iter_)
            if self.config.nonfinite_check_freq > 0:
                # device-side finiteness flag, accumulated lazily (no host
                # sync here); the split program masks NaN gains/values to
                # zero, so corrupt gradients otherwise degrade the model
                # SILENTLY.  engine.train fetches the flag every
                # nonfinite_check_freq iterations (gradients_finite()).
                ok = (jnp.all(jnp.isfinite(grad))
                      & jnp.all(jnp.isfinite(hess)))
                prev = getattr(self, "_grad_ok", None)
                self._grad_ok = ok if prev is None else (prev & ok)
        else:
            grad = jnp.asarray(_pad_rows(np.asarray(gradients, np.float32)
                                         .reshape(K, -1), self.n_pad))
            hess = jnp.asarray(_pad_rows(np.asarray(hessians, np.float32)
                                         .reshape(K, -1), self.n_pad))

        with global_timer.scope("GBDT::bagging"):
            bag_mask, grad, hess = self._update_bagging(grad, hess)
        should_continue = False
        for k in range(K):
            tree = None
            if self.class_need_train[k] and self.train_data.num_features > 0:
                g_k = self._slice_row_fn(grad, k)
                h_k = self._slice_row_fn(hess, k)
                if self.use_quant:
                    # per-tree discretization (ref: serial_tree_learner
                    # BeforeTrain -> DiscretizeGradients on the class slice);
                    # keyed by absolute iteration so resume/continuation
                    # advances the rounding stream
                    gq, hq, qscales = self._discretize_fn(
                        g_k, h_k,
                        np.int32((self.num_init_iteration_ + self.iter_)
                                 * K + k))
                else:
                    gq, hq, qscales = g_k, h_k, None
                # the float g_k/h_k slices are consumed after growth only
                # by linear-leaf fitting (donation off) and quantized leaf
                # renewal (gq/hq are then distinct buffers); snapshot the
                # tuple BEFORE the grow call — when quantization is off,
                # gq/hq ALIAS g_k/h_k and the donated grow entries delete
                # their argument buffers (tpulint donated-buffer-reuse)
                float_grads = ((g_k, h_k)
                               if (self.config.linear_tree
                                   or (self.use_quant
                                       and self.config
                                       .quant_train_renew_leaf))
                               else None)
                with global_timer.scope("GBDT::grow_tree"):
                    grow_kw = ({"cegb_used": self._cegb_used}
                               if self._cegb_used is not None else {})
                    if (self.config.extra_trees
                            or self.config.feature_fraction_bynode < 1.0):
                        # continued training advances the stream instead
                        # of replaying the first run's draws
                        grow_kw["extra_tag"] = np.int32(
                            (self.num_init_iteration_ + self.iter_) * K
                            + k)
                    if self._lazy_used is not None:
                        grow_kw["lazy_used"] = self._lazy_used
                    grow_kw.update(self._classed_kw)
                    if (qscales is not None
                            and self.growth_strategy == "wave"
                            and self.grow_params.quant_bins > 0):
                        grow_kw["quant_scales"] = qscales
                    if faults.active():
                        # one rank wedging HERE leaves its peers blocked
                        # inside the histogram psum — the live-but-hung
                        # shape the stall watchdog exists for
                        faults.maybe_collective_stall(
                            self.num_init_iteration_ + self.iter_)
                    out = self._grow_fn(
                        self.binned_dev, gq, hq, bag_mask,
                        self._col_mask(), self.meta, self.grow_params,
                        **grow_kw)
                    out = global_timer.block(out)
                    if self._lazy_used is not None:
                        arrays, leaf_id, self._lazy_used = out
                    else:
                        arrays, leaf_id = out
                if self._cegb_used is not None:
                    self._cegb_used = self._cegb_mark_fn(
                        self._cegb_used, arrays.split_feature,
                        arrays.num_leaves)
                with global_timer.scope("GBDT::finalize_tree"):
                    tree = self._finalize_tree(arrays, leaf_id, k,
                                               init_scores[k],
                                               float_grads=float_grads)
                _metrics.inc("trees_grown")
            if tree is None:
                if len(self.models_) < K:
                    tree = self._make_const_stump(k)
                else:
                    tree = Tree(2)
                    tree.num_leaves = 1
            else:
                should_continue = True
            self.models_.append(tree)

        if not should_continue:
            return self._stop_training(len(self.models_) // K - 1)
        # keep a short materialization pipeline: drain down to 2 in-flight
        # trees each iteration.  The oldest buffers have settled by then, so
        # the pull is a cheap transfer and the loop needs no readiness
        # probes (is_ready); a bounded depth also bounds the device
        # buffers the queue keeps alive.  The depth of 2 was chosen on an
        # earlier runtime and is not re-measured since bring-up.
        self._drain_pending(keep_depth=2)
        stop_iter = self._all_stump_iteration()
        if stop_iter is not None:
            return self._stop_training(stop_iter)
        self.iter_ += 1
        return False

    def _all_stump_iteration(self) -> Optional[int]:
        """First iteration whose K drained trees ALL grew no split (the
        reference's stop condition; a single class stalling only yields a
        stump for that class, ref: gbdt.cpp:395-418)."""
        K = self.num_tree_per_iteration
        for it in sorted({idx // K for idx in self._stump_idxs}):
            if all(it * K + k in self._stump_idxs for k in range(K)):
                return it
        return None

    def _make_const_stump(self, k: int) -> Tree:
        """Constant one-leaf tree for a class with no first-iteration split
        (boost_from_score when averages were not applied; ref:
        gbdt.cpp:372-391)."""
        tree = Tree(2)
        tree.num_leaves = 1
        init = 0.0
        if (self.objective is not None
                and not self.config.boost_from_average
                and not self.has_init_score):
            init = self.objective.boost_from_score(k)
            self.scores = self._score_add_fn(self.scores, k, init)
            for sc in self.valid_scores:
                sc[k] += init
        tree.leaf_value[0] = init
        tree.shrinkage = 1.0
        return tree

    def _stop_training(self, stop_iter: int) -> bool:
        """Reference stop semantics: drop the iteration that failed to split
        and everything after it (ref: gbdt.cpp:338-418 TrainOneIter's
        no-split handling), then report stop."""
        K = self.num_tree_per_iteration
        self._drain_pending(keep_depth=0)
        self._stump_idxs.clear()
        log.warning("Stopped training because there are no more leaves "
                    "that meet the split requirements")
        # trees past the stop point already contributed to the device
        # scores (the pipelined update runs a couple of iterations ahead);
        # revert them so scores stay consistent with the kept model
        for idx in range(stop_iter * K, len(self.models_)):
            tree = self.models_[idx]
            if isinstance(tree, Tree) and tree.num_leaves > 1:
                neg = _copy.deepcopy(tree)
                neg.leaf_value[:neg.num_leaves] *= -1.0
                self._add_tree_score(neg, idx % K, train=True, valid=False)
        if stop_iter > 0:
            del self.models_[stop_iter * K:]
            self.iter_ = stop_iter
        else:
            # first iteration: keep constant stumps (boost_from_score)
            del self.models_[K:]
            self.iter_ = 0
            for k in range(K):
                tree = self.models_[k]
                if not isinstance(tree, Tree) or tree.num_leaves > 1:
                    self.models_[k] = self._make_const_stump(k)
        return True

    def _arrays_to_tree(self, arrays) -> Optional[Tree]:
        """Device TreeArrays -> host Tree (pure conversion; one batched D2H
        transfer of the whole tree as a flat buffer, like CUDATree::ToHost,
        ref: src/io/cuda/cuda_tree.cpp)."""
        return self._packed_to_tree(_fetch_host(self._pack_tree_fn(arrays)))

    def _packed_to_tree(self, flat: np.ndarray) -> Optional[Tree]:
        """Decode the packed flat tree buffer into a host Tree."""
        ints = flat.view(np.int32)
        _metrics.inc("waves_total", int(ints[-3]))
        _metrics.inc("hist_narrowed_waves_total", int(ints[-2]))
        _metrics.inc("hist_slot_tiles_total", int(ints[-1]))
        L = self.config.num_leaves
        ni = max(L - 1, 1)
        W = self._cat_words
        parts = []
        off = 1
        for size, arr_ints in ((ni, True), (ni, True), (ni, True),
                               (ni, False), (ni, True), (ni, True),
                               (ni, False), (ni, False), (ni, True),
                               (L, False), (L, False), (L, True),
                               (L, True), (L, True),
                               (ni, True), (ni * W, True)):
            parts.append(ints[off:off + size] if arr_ints
                         else flat[off:off + size])
            off += size
        (split_feature, threshold_bin, default_left, split_gain,
         left_child, right_child, internal_value, internal_weight,
         internal_count, leaf_value, leaf_weight, leaf_count,
         leaf_parent, leaf_depth, split_is_cat, cat_bits_flat) = parts
        cat_bits = cat_bits_flat.reshape(ni, W)

        class _Host:  # attribute-compatible host view of TreeArrays
            pass
        arrays = _Host()
        arrays.num_leaves = ints[0]
        arrays.split_feature = split_feature
        arrays.threshold_bin = threshold_bin
        arrays.default_left = default_left != 0
        arrays.split_gain = split_gain
        arrays.left_child = left_child
        arrays.right_child = right_child
        arrays.internal_value = internal_value
        arrays.internal_weight = internal_weight
        arrays.internal_count = internal_count
        arrays.leaf_value = leaf_value
        arrays.leaf_weight = leaf_weight
        arrays.leaf_count = leaf_count
        arrays.leaf_parent = leaf_parent
        arrays.leaf_depth = leaf_depth
        num_leaves = int(arrays.num_leaves)
        if num_leaves <= 1:
            return None
        _metrics.inc("splits_total", num_leaves - 1)
        _metrics.inc("cat_splits_total",
                     int(np.count_nonzero(split_is_cat[:num_leaves - 1])))
        # numerical splits that send NaN right: the forward scan's winners
        _metrics.inc("missing_right_splits_total", int(np.count_nonzero(
            self._nan_numerical[split_feature[:num_leaves - 1]]
            & (default_left[:num_leaves - 1] == 0)
            & (split_is_cat[:num_leaves - 1] == 0))))
        ds = self.train_data
        L = self.config.num_leaves
        tree = Tree(max(L, 2))
        tree.num_leaves = num_leaves
        ni = num_leaves - 1
        sf_inner = np.asarray(arrays.split_feature)[:ni]
        thr_bin = np.asarray(arrays.threshold_bin)[:ni]
        dleft = np.asarray(arrays.default_left)[:ni]
        tree.split_feature_inner[:ni] = sf_inner
        tree.split_feature[:ni] = np.array(
            [ds.used_features[f] for f in sf_inner], np.int32)
        tree.threshold_in_bin[:ni] = thr_bin
        is_cat_node = split_is_cat[:ni] != 0
        for i in range(ni):
            mapper = ds.bin_mappers[tree.split_feature[i]]
            if is_cat_node[i]:
                # decode the device bins-left bitset, then register via the
                # shared Tree bookkeeping (tree.py register_cat_split)
                words = cat_bits[i]
                bins_left = [b for b in range(mapper.num_bin)
                             if (words[b // 32] >> (b % 32)) & 1]
                cats_left = [mapper.bin_2_categorical[b] for b in bins_left
                             if mapper.bin_2_categorical[b] >= 0]
                tree.register_cat_split(i, bins_left, cats_left,
                                        mapper.missing_type)
                continue
            tree.threshold[i] = mapper.bin_to_value(int(thr_bin[i]))
            dt = 0
            if dleft[i]:
                dt |= 2
            dt |= (mapper.missing_type & 3) << 2
            tree.decision_type[i] = dt
        tree.split_gain[:ni] = np.asarray(arrays.split_gain)[:ni]
        tree.left_child[:ni] = np.asarray(arrays.left_child)[:ni]
        tree.right_child[:ni] = np.asarray(arrays.right_child)[:ni]
        tree.internal_value[:ni] = np.asarray(arrays.internal_value)[:ni]
        tree.internal_weight[:ni] = np.asarray(arrays.internal_weight)[:ni]
        tree.internal_count[:ni] = np.asarray(arrays.internal_count)[:ni]
        nl = num_leaves
        tree.leaf_value[:nl] = np.asarray(arrays.leaf_value)[:nl]
        tree.leaf_weight[:nl] = np.asarray(arrays.leaf_weight)[:nl]
        tree.leaf_count[:nl] = np.asarray(arrays.leaf_count)[:nl]
        tree.leaf_parent[:nl] = np.asarray(arrays.leaf_parent)[:nl]
        tree.leaf_depth[:nl] = np.asarray(arrays.leaf_depth)[:nl]
        return tree

    def _finalize_tree(self, arrays, leaf_id, class_id: int,
                       init_score: float, float_grads=None):
        """Renew/shrink/score-update after growing (ref: gbdt.cpp:395-407).

        Fast path: a host sync on a fresh device result stalls the loop
        until the whole tree program has run (JAX dispatch is
        asynchronous), so when no host-side tree work is needed
        this iteration (no renewal objective, no valid sets), the score
        update runs device-side with shrinkage fused and the host Tree is
        materialized LATER from a pending queue (_drain_pending) once its
        packed buffer has settled — the boosting loop never blocks on D2H.
        """
        obj = self.objective
        if self.config.linear_tree:
            # linear leaves (ref: linear_tree_learner.cpp:184
            # CalculateLinear runs after the structure is grown, before
            # shrinkage; scores then need the full linear prediction)
            tree = self._arrays_to_tree(arrays)
            if tree is None:
                return None
            g, h = float_grads
            bag = self._bag_mask_host[:self.num_data]
            self._calculate_linear(
                tree, np.asarray(leaf_id)[:self.num_data],
                np.asarray(g)[:self.num_data] * bag,
                np.asarray(h)[:self.num_data] * bag)
            tree.apply_shrinkage(self.shrinkage_rate)
            X = self._raw_or_reconstruct(self.train_data)
            delta = tree.predict(np.asarray(X, np.float64))
            self.scores = self._score_add_fn(
                self.scores, class_id,
                jnp.asarray(_pad_rows(delta.astype(np.float32),
                                      self.n_pad)))
            self._add_tree_score(tree, class_id, train=False)
            if abs(init_score) > K_EPSILON:
                tree.add_bias(init_score)
            return tree
        if (self.use_quant and self.config.quant_train_renew_leaf
                and float_grads is not None):
            # quantized leaf renewal runs first, then any objective renewal
            # (ref: serial tree learner renews int-grad outputs inside
            # Train; GBDT::TrainOneIter renews for the objective after)
            arrays = arrays._replace(leaf_value=self._renew_quant_fn(
                arrays.leaf_value, leaf_id, float_grads[0], float_grads[1],
                self.bag_mask))
        need_sync = ((obj is not None and obj.need_renew_tree_output)
                     or bool(self.valid_sets))
        if not need_sync:
            packed = self._pack_tree_fn(arrays)
            copy_async = getattr(packed, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
            self._pending.append(dict(
                packed=packed, idx=len(self.models_),
                init=init_score, rate=self.shrinkage_rate))
            self.scores = self._score_update_shrink_fn(
                self.scores, class_id, arrays.leaf_value,
                self.shrinkage_rate, leaf_id, self.pad_mask)
            return _PENDING_TREE

        tree = self._arrays_to_tree(arrays)
        if tree is None:
            return None
        num_leaves = tree.num_leaves
        nl = num_leaves
        L = self.config.num_leaves

        # per-leaf output renewal (ref: RenewTreeOutput; L1/quantile/MAPE)
        obj = self.objective
        leaf_id_host = None
        if obj is not None and obj.need_renew_tree_output:
            leaf_id_host = np.asarray(leaf_id)[:self.num_data]
            score_host = np.asarray(self.scores[class_id])[:self.num_data]
            bag = self._bag_mask_host[:self.num_data] > 0
            renewed = obj.renew_tree_output(
                np.where(bag, leaf_id_host, -1), score_host, num_leaves)
            if renewed is not None:
                tree.leaf_value[:nl] = renewed

        tree.apply_shrinkage(self.shrinkage_rate)

        # score update on device (ref: ScoreUpdater::AddScore(tree_learner))
        leaf_vals = jnp.asarray(tree.leaf_value[:max(L, 2)].astype(np.float32))
        self.scores = self._score_update_fn(self.scores, class_id, leaf_vals,
                                            leaf_id, self.pad_mask)
        # valid scores on host
        self._add_tree_score(tree, class_id, train=False)

        if abs(init_score) > K_EPSILON:
            tree.add_bias(init_score)
        return tree

    def _drain_pending(self, keep_depth: int = 0) -> None:
        """Materialize pending device trees oldest-first until at most
        keep_depth remain in flight."""
        if len(self._pending) > keep_depth:
            with global_timer.scope("GBDT::materialize_tree"):
                self._drain_pending_now(keep_depth)

    def _drain_pending_now(self, keep_depth: int) -> None:
        while len(self._pending) > keep_depth:
            p = self._pending.pop(0)
            # the one place the steady loop blocks on the device
            with global_timer.scope("GBDT::wait_tree", tree=p["idx"]):
                flat = _fetch_host(p["packed"])
            tree = self._packed_to_tree(flat)
            if tree is None:
                # grew no split: keep a 0-value stump for this class (ref:
                # gbdt.cpp:372-391) and record it for the stop condition
                self._stump_idxs.add(p["idx"])
                tree = Tree(2)
                tree.num_leaves = 1
                tree.shrinkage = 1.0
                self.models_[p["idx"]] = tree
            else:
                tree.apply_shrinkage(p["rate"])
                if abs(p["init"]) > K_EPSILON:
                    tree.add_bias(p["init"])
                self.models_[p["idx"]] = tree

    def _sync_model(self) -> None:
        """Block until models_ holds real host trees (public consumers —
        predict/save/eval/rollback — call this first)."""
        self._drain_pending(keep_depth=0)
        stop_iter = self._all_stump_iteration()
        if stop_iter is not None:
            self._stop_training(stop_iter)

    # -------------------------------------------------------- score plumbing
    def _reconstruct_bin_space(self, tree: Tree) -> None:
        """Rebuild a text-adopted tree's BIN-space routing fields against
        this run's bin mappers (threshold_in_bin, split_feature_inner,
        inner categorical bitsets).  Model text stores real-valued
        thresholds only; training-time score adds (_add_tree_score —
        DART drops/normalize, RF averaging) route rows in bin space, so
        without this a resumed DART run subtracts GARBAGE contributions
        for every adopted tree it drops.  Exact inverse of
        _arrays_to_tree's bin->value mapping: the real threshold IS
        bin_upper_bound[bin], so searchsorted recovers the bin."""
        ni = tree.num_leaves - 1
        if getattr(tree, "_bin_space_valid", True):
            return
        tree._bin_space_valid = True
        if ni <= 0:
            return
        ds = self.train_data
        if ds is None or not getattr(ds, "bin_mappers", None):
            return
        from ..models.tree import K_CATEGORICAL_MASK, _to_bitset
        inner_of = {f: i for i, f in enumerate(ds.used_features)}
        cat_mask = (tree.decision_type[:ni] & K_CATEGORICAL_MASK) > 0
        per_ci_bins: Dict[int, List[int]] = {}
        for nd in range(ni):
            f = int(tree.split_feature[nd])
            if f in inner_of:
                tree.split_feature_inner[nd] = inner_of[f]
            mapper = ds.bin_mappers[f]
            if cat_mask[nd]:
                # outer bitset holds category VALUES; the inner one
                # holds this dataset's bin indices for those values
                ci = int(tree.threshold[nd])
                tree.threshold_in_bin[nd] = ci
                lo = tree.cat_boundaries[ci]
                hi = tree.cat_boundaries[ci + 1]
                cats = [32 * w + j
                        for w, word in enumerate(tree.cat_threshold[lo:hi])
                        for j in range(32) if (word >> j) & 1]
                c2b = getattr(mapper, "categorical_2_bin", {})
                per_ci_bins[ci] = _to_bitset(
                    [c2b[c] for c in cats if c in c2b])
                continue
            ub = np.asarray(mapper.bin_upper_bound, np.float64)
            b = int(np.searchsorted(ub, float(tree.threshold[nd]),
                                    side="left"))
            tree.threshold_in_bin[nd] = min(b, max(mapper.num_bin - 1, 0))
        if tree.num_cat > 0:
            ct_inner: List[int] = []
            cb_inner = [0]
            for ci in range(tree.num_cat):
                ct_inner.extend(per_ci_bins.get(ci, []))
                cb_inner.append(len(ct_inner))
            tree.cat_threshold_inner = ct_inner
            tree.cat_boundaries_inner = cb_inner

    def _tree_leaf_ids(self, tree: Tree, ds) -> np.ndarray:
        """Bin-space leaf index of every row for a tree trained on this
        dataset's bin mappers.  `ds` may store per-feature bins or (for
        sparse-ingested data) bundle codes with its own plan."""
        from ..models.tree import K_CATEGORICAL_MASK
        ni = tree.num_leaves - 1
        binned = ds.binned_host()
        plan = ds.pre_bundled_plan
        bundle_kw = {}
        if plan is not None:
            bundle_kw = dict(bundle_group=plan.group_idx,
                             bundle_offset=plan.offsets,
                             bundle_zero_bin=plan.zero_bin)
        return leaf_index_bin_space(
            tree.split_feature_inner[:ni], tree.threshold_in_bin[:ni],
            (tree.decision_type[:ni] & 2) > 0,
            tree.left_child[:ni], tree.right_child[:ni], tree.num_leaves,
            self.f_missing_type, self.f_num_bin, self.f_default_bin, binned,
            is_cat_node=(tree.decision_type[:ni] & K_CATEGORICAL_MASK) > 0,
            cat_boundaries_inner=tree.cat_boundaries_inner,
            cat_threshold_inner=tree.cat_threshold_inner, **bundle_kw)

    def _add_tree_score(self, tree: Tree, class_id: int,
                        train: bool = True, valid: bool = True) -> None:
        """score += tree's *current* leaf outputs (ref: score_updater.hpp:21
        AddScore; used by DART drop/normalize and RF averaging)."""
        if train:
            ids = self._tree_leaf_ids(tree, self.train_data)
            # fixed-size leaf_vals so _score_update_fn compiles once
            L = max(self.config.num_leaves, 2)
            vals = np.zeros(L, np.float32)
            vals[:tree.num_leaves] = tree.leaf_value[:tree.num_leaves]
            self.scores = self._score_update_fn(
                self.scores, class_id, jnp.asarray(vals),
                jnp.asarray(_pad_rows(ids, self.n_pad)), self.pad_mask)
        if valid:
            for vi, vds in enumerate(self.valid_sets):
                if tree.is_linear:
                    vX = self._raw_or_reconstruct(vds)
                    self.valid_scores[vi][class_id] += tree.predict(
                        np.asarray(vX, np.float64))
                else:
                    vids = self._tree_leaf_ids(tree, vds)
                    self.valid_scores[vi][class_id] += tree.leaf_value[vids]

    # ------------------------------------------------------------------- eval
    def eval_train(self):
        if (isinstance(self.scores, jax.Array)
                and not self.scores.is_fully_addressable):
            return self._eval_train_sharded()
        de = self._device_eval
        if de is None:
            from ..ops.metrics import DeviceEval
            de = self._device_eval = DeviceEval(self)
        if de.ok:
            if not de._plans:
                return []
            out, grads_ok, scores_ok = de.run(self.scores,
                                              getattr(self, "_grad_ok",
                                                      None))
            # the sentinel flags rode the packed fetch: cache them so
            # this tick's _check_finite costs no second sync
            self._finite_cache = (grads_ok, scores_ok)
            return out
        score = np.asarray(self.scores)[:, :self.num_data].astype(np.float64)
        return self._eval(score, self.train_metrics, self.train_data)

    def _eval_train_sharded(self):
        """Train-set metrics under multi-process SPMD: the scores span
        non-addressable devices, so each metric is computed as
        shard-local partial sums that GSPMD all-reduces over the mesh —
        every rank reads identical replicated scalars (the TPU analogue
        of the reference workers' synchronized Eval in gbdt.cpp
        EvalAndCheckEarlyStopping).  AUC uses a global score-bin
        histogram (metric.py device_binned_auc)."""
        from ..metric import device_binned_auc, device_pointwise_loss
        if getattr(self, "_sharded_eval_fn", None) is None:
            obj = self.objective
            plans = []      # (metric_name, kind, loss_fn)
            for m in self.train_metrics:
                base = m.name
                if self.num_tree_per_iteration > 1:
                    # multiclass: per-row class probabilities from the
                    # [K, n] scores, reduced the same sharded way
                    if base in ("multi_logloss", "multi_error"):
                        plans.append((base, base, None))
                    elif base == "auc_mu":
                        # pairwise-projection binned AUCs (metric.py
                        # device_auc_mu); the weight matrix is static
                        plans.append((base, "auc_mu",
                                      np.asarray(m.class_weights)))
                    else:
                        log.warning(f"train metric {base} has no sharded "
                                    "device form; skipped under "
                                    "multi-process SPMD")
                    continue
                if base == "auc":
                    plans.append((base, "auc", None))
                    continue
                if base == "average_precision":
                    plans.append((base, "average_precision", None))
                    continue
                if base == "ndcg":
                    from ..metric import ndcg_device_plan
                    bks, efn = ndcg_device_plan(
                        m, self.n_pad,
                        shared_buckets=getattr(obj, "_dev_buckets", None))
                    self._ndcg_buckets = bks
                    plans.append((base, "ndcg", (efn, list(m.eval_at))))
                    continue
                if base == "map":
                    from ..metric import map_device_plan
                    bks, efn = map_device_plan(
                        m, self.n_pad,
                        shared_buckets=getattr(obj, "_dev_buckets", None))
                    self._map_buckets = bks
                    plans.append((base, "map", (efn, list(m.eval_at))))
                    continue
                fn = device_pointwise_loss(base, self.config)
                if fn is None:
                    log.warning(f"train metric {base} has no sharded "
                                "device form; skipped under "
                                "multi-process SPMD")
                    continue
                sqrt_after = base == "rmse"
                plans.append((base, "sqrt" if sqrt_after else "avg", fn))
            self._sharded_eval_plans = plans
            # metrics compare in ORIGINAL label space (the host path uses
            # metadata.label): label_dev may be objective-transformed
            # (reg_sqrt) or absent entirely (custom fobj), so build a
            # dedicated sharded copy from the metadata
            md = self.train_data.metadata
            self._eval_label_dev = self._put_by_row(
                _pad_rows(np.asarray(md.label, np.float32), self.n_pad))
            self._eval_weight_dev = (
                None if md.weight is None else self._put_by_row(
                    _pad_rows(np.asarray(md.weight, np.float32),
                              self.n_pad)))

            def _fn(scores, label, weight, pad_mask, ndcg_buckets,
                    map_buckets):
                from ..metric import (device_auc_mu,
                                      device_binned_average_precision)
                w = pad_mask if weight is None else weight * pad_mask
                den = jnp.sum(w)
                outs = []
                if self.num_tree_per_iteration > 1:
                    # [K, n] -> per-class probabilities (softmax for
                    # multiclass; ova objectives convert per class)
                    prob = (obj.convert_output(scores) if obj is not None
                            and not getattr(obj, "run_on_host", False)
                            else scores)
                    K = prob.shape[0]
                    lab_oh = (label[None, :]
                              == jnp.arange(K, dtype=prob.dtype)[:, None])
                    p_lab = jnp.sum(jnp.where(lab_oh, prob, 0.0), axis=0)
                    for _, kind, extra in plans:
                        if kind == "multi_logloss":
                            pt = -jnp.log(jnp.clip(p_lab, 1e-15, 1.0))
                        elif kind == "auc_mu":
                            # pairwise projections are of RAW scores
                            # (multiclass_metric.hpp:255 uses score)
                            outs.append(device_auc_mu(
                                scores, label, w, extra))
                            continue
                        else:   # multi_error: true-class prob not in
                            # top_k; ties count AGAINST the row (ref:
                            # multiclass_metric.hpp:142 LossOnPoint
                            # counts >= incl. self, error when > top_k)
                            num_ge = jnp.sum(prob >= p_lab[None, :],
                                             axis=0)
                            pt = (num_ge > self.config.multi_error_top_k
                                  ).astype(jnp.float32)
                        outs.append(jnp.sum(pt * w) / den)
                    return tuple(outs)
                sc = scores[0]
                conv = (obj.convert_output(sc) if obj is not None
                        and not getattr(obj, "run_on_host", False) else sc)
                for _, kind, fn in plans:
                    if kind == "auc":
                        outs.append(device_binned_auc(conv, label, w))
                    elif kind == "average_precision":
                        outs.append(device_binned_average_precision(
                            conv, label, w))
                    elif kind == "ndcg":
                        # per-query partials from the raw scores (ndcg is
                        # rank-based; conversion is monotone) — one value
                        # per eval_at k
                        outs.append(fn[0](sc, ndcg_buckets))
                    elif kind == "map":
                        outs.append(fn[0](sc, map_buckets))
                    else:
                        v = jnp.sum(fn(conv, label) * w) / den
                        outs.append(jnp.sqrt(v) if kind == "sqrt" else v)
                return tuple(outs)

            # tpulint: disable-next=donate-argnums -- eval reads the live sharded score buffer; training keeps updating it
            self._sharded_eval_fn = jax.jit(_fn)
        vals = self._sharded_eval_fn(self.scores, self._eval_label_dev,
                                     self._eval_weight_dev, self.pad_mask,
                                     getattr(self, "_ndcg_buckets", []),
                                     getattr(self, "_map_buckets", []))
        out = []
        for (name, kind, extra), v in zip(self._sharded_eval_plans, vals):
            if kind in ("ndcg", "map"):
                out.extend((f"{name}@{k}", float(v[ki]))
                           for ki, k in enumerate(extra[1]))
            else:
                out.append((name, float(v)))
        return out

    def eval_valid(self, idx: int):
        return self._eval(self.valid_scores[idx], self.valid_metrics[idx],
                          self.valid_sets[idx])

    def _eval(self, score, metrics, dataset):
        out = []
        sc = score[0] if score.shape[0] == 1 else score
        for m in metrics:
            out.extend(m.eval(sc, self.objective))
        return out

    # ---------------------------------------------------------------- predict
    def _bump_model_mutations(self) -> None:
        """Invalidate the packed/device predictor caches after an IN-PLACE
        tree mutation that `len(models_)` cannot see — DART drop/
        normalize re-weighting, refit, set_leaf_output.  Serving a model
        mid-mutation must repack, never reuse stale leaf values."""
        self._model_mutations = getattr(self, "_model_mutations", 0) + 1

    def _packed_for(self, start_iteration: int, end: int, K: int):
        """Cached native PackedPredictor for a model slice, invalidated by
        growth (len) and in-place mutation (_model_mutations)."""
        from ..native import PackedPredictor, predictor_lib
        if predictor_lib() is None:
            return None
        key = (start_iteration, end, len(self.models_),
               getattr(self, "_model_mutations", 0))
        cached = getattr(self, "_packed_pred", None)
        if cached is None or cached[0] != key:
            cached = (key, PackedPredictor(
                self.models_[start_iteration * K:end * K]))
            self._packed_pred = cached
        packed = cached[1]
        return packed if packed.ok else None

    def make_single_row_fast(self, num_features: int,
                             start_iteration: int = 0,
                             num_iteration: int = -1,
                             raw_score: bool = False):
        """Cached single-row fast predictor (ref: c_api.h:1350
        LGBM_BoosterPredictForMatSingleRowFastInit): parse/pack once,
        reuse buffers per call.  None when the native predictor is
        unavailable (linear trees / no compiler)."""
        from ..native import SingleRowFastPredictor
        self._sync_model()
        K = self.num_tree_per_iteration
        total_iters = len(self.models_) // K
        if num_iteration is None or num_iteration < 0:
            num_iteration = total_iters - start_iteration
        end = min(start_iteration + num_iteration, total_iters)
        packed = self._packed_for(start_iteration, end, K)
        if packed is None:
            return None
        conv = None
        if not raw_score and self.objective is not None:
            conv = getattr(self.objective, "convert_output_host", None)
        sp = SingleRowFastPredictor(packed, num_features, K,
                                    self.average_output_, convert=conv)
        return sp if sp.ok else None

    def _host_fallback(self, reason: str):
        """One host-fallback decision of the device-predict router,
        named by its docs/Inference.md fallback-matrix KEY —
        tools/check_fallback_docs.py syncs the matrix against these
        call sites in both directions, so a new quiet host fallback
        cannot ship undocumented.  Returns None for the caller."""
        log.debug(f"device_predict: host fallback ({reason})")
        return None

    def _device_predictor(self, X, start_iteration: int, num_iteration: int,
                          pred_early_stop: bool = False):
        """Route decision for the TPU-resident inference path
        (docs/Inference.md fallback matrix).  Returns (DevicePredictor,
        float32 matrix) ready to serve, or None when the host paths
        must: float64 data that is NOT losslessly f32-representable
        (the bit-exact routing argument needs float32 inputs; lossless
        float64 — integral features, f32-round-tripped pipelines — is
        downcast and served, the ROADMAP'd Serving follow-up),
        linear-tree models, empty slices, or device_predict=false /
        auto without a TPU backend.  Prediction early stopping serves on
        device too (traverse.py class_scores_early_stop masked scan);
        the `pred_early_stop` argument is kept for callers that gate es
        activation themselves."""
        cfg = self.config
        mode = getattr(cfg, "device_predict", "false") if cfg else "false"
        if mode == "false":
            return None
        arr = X if isinstance(X, np.ndarray) else np.asarray(X)
        if arr.dtype == np.float32:
            X32 = arr
        elif arr.dtype == np.float64:
            # cheap host check: one downcast + one compare pass.  Equal
            # after the round trip (NaN kept as missing) means the f32
            # traversal routes bit-identically to the float64 host path.
            X32 = arr.astype(np.float32)
            if not bool(np.all((X32 == arr) | np.isnan(arr))):
                return self._host_fallback("float64-lossy")
        else:
            return self._host_fallback("non-float-input")
        if mode == "auto" and jax.default_backend() != "tpu":
            return None
        if jax.process_count() > 1:
            # predict is a host API; a packed model placed on this
            # process's devices cannot address remote shards, and the
            # peers are not running the same dispatch
            return self._host_fallback("multi-process")
        self._sync_model()
        K = self.num_tree_per_iteration
        total_iters = len(self.models_) // max(K, 1)
        if num_iteration is None or num_iteration < 0:
            num_iteration = total_iters - start_iteration
        end = min(start_iteration + num_iteration, total_iters)
        if end <= start_iteration:
            return self._host_fallback("empty-slice")
        dp = self._device_pred_for(start_iteration, end, K)
        # dp.ok is False exactly when the slice cannot pack — linear
        # trees (inference/pack.py) being the one reachable case here
        return (dp, X32) if dp.ok else self._host_fallback("linear-tree")

    def _device_pred_for(self, start_iteration: int, end: int, K: int):
        """Cached DevicePredictor per model slice, invalidated by growth
        (len) and in-place mutation, mirroring _packed_for."""
        from ..inference import DevicePredictor
        key = (start_iteration, end, len(self.models_),
               getattr(self, "_model_mutations", 0))
        cached = getattr(self, "_device_pred", None)
        if cached is None or cached[0] != key:
            # a model loaded from file predicts without ever training:
            # the bucket ladder's compiles want the cache too
            from ..observability import configure_compile_cache
            configure_compile_cache(
                getattr(self.config, "compile_cache_dir", ""))
            obj = self.objective
            conv = obj.convert_output if obj is not None else None
            mesh = None
            if (getattr(self, "mesh", None) is not None
                    and getattr(self, "_mesh_axis", 1) == 1
                    and jax.process_count() == 1):
                # offline scoring shards rows over the training mesh; the
                # model replicates (each chip holds the whole ensemble)
                mesh = self.mesh
            cached = (key, DevicePredictor(
                self.models_[start_iteration * K:end * K], num_class=K,
                average=self.average_output_, convert=conv,
                min_bucket=getattr(self.config, "device_predict_min_bucket",
                                   4096),
                mesh=mesh))
            self._device_pred = cached
        return cached[1]

    def _device_predict_run(self, dp, X, mode: str,
                            early_stop=None) -> np.ndarray:
        """One device predict dispatch + telemetry (timer scope and a
        structured `predict` event when an EventLogger is active).
        `early_stop=(freq, margin)` routes through the device masked
        accumulation scan (parity with the host early-stop path)."""
        from ..observability import emit_event
        with global_timer.scope("GBDT::predict_device"):
            if mode == "leaf":
                out = dp.predict_leaf(X)
            elif mode == "raw":
                out = dp.predict_raw(X, early_stop=early_stop)
            else:
                out = dp.predict(X, early_stop=early_stop)
        n = out.shape[0]
        emit_event("predict", path="device", mode=mode, rows=int(n),
                   trees=dp.pack.num_trees, bucket=dp.bucket_rows(n),
                   early_stop=early_stop is not None)
        return out

    def _es_tuple(self, pred_early_stop, freq, margin):
        """(freq, margin) when prediction early stopping engages — same
        gate as the host path's use_es (off under output averaging,
        ref: gbdt_prediction.cpp)."""
        if pred_early_stop and not self.average_output_ and freq > 0:
            return (int(freq), float(margin))
        return None

    def predict_raw(self, X: np.ndarray, start_iteration: int = 0,
                    num_iteration: int = -1, pred_early_stop: bool = False,
                    pred_early_stop_freq: int = 10,
                    pred_early_stop_margin: float = 10.0) -> np.ndarray:
        """Raw scores [n] or [n, K] (ref: gbdt_prediction.cpp PredictRaw;
        early stopping per prediction_early_stop.cpp: rows whose margin
        exceeds the threshold every round_period iterations keep their
        partial sum — binary margin = 2|score|, multiclass = top1-top2)."""
        hit = self._device_predictor(X, start_iteration, num_iteration,
                                     pred_early_stop)
        if hit is not None:
            es = self._es_tuple(pred_early_stop, pred_early_stop_freq,
                                pred_early_stop_margin)
            return self._device_predict_run(hit[0], hit[1], "raw", es)
        with global_timer.scope("GBDT::predict"):
            return self._predict_raw_impl(
                X, start_iteration, num_iteration, pred_early_stop,
                pred_early_stop_freq, pred_early_stop_margin)

    def _predict_raw_impl(self, X, start_iteration, num_iteration,
                          pred_early_stop, pred_early_stop_freq,
                          pred_early_stop_margin) -> np.ndarray:
        self._sync_model()
        X = np.asarray(X, dtype=np.float64)
        n = X.shape[0]
        K = self.num_tree_per_iteration
        total_iters = len(self.models_) // K
        if num_iteration < 0:
            num_iteration = total_iters - start_iteration
        end = min(start_iteration + num_iteration, total_iters)
        use_es = pred_early_stop and not self.average_output_
        if not use_es and end > start_iteration:
            # native batch predictor (OpenMP over rows; ref:
            # src/application/predictor.hpp) — Python path on fallback.
            # The flattened pack is cached per model slice and invalidated
            # by growth/mutation (set_leaf_output etc. bump the counter).
            packed = self._packed_for(start_iteration, end, K)
            if packed is not None:
                res = packed.predict(X, K, self.average_output_)
                if res is not None:
                    return res[:, 0] if K == 1 else res
        out = np.zeros((K, n))
        active_idx = np.arange(n) if use_es else None
        Xa = X
        for i, it in enumerate(range(start_iteration, end)):
            if use_es and i > 0 and i % pred_early_stop_freq == 0:
                sub = out[:, active_idx]
                if K == 1:
                    margin = 2.0 * np.abs(sub[0])
                else:
                    top2 = np.partition(sub, K - 2, axis=0)[K - 2:]
                    margin = top2[1] - top2[0]
                keep = margin <= pred_early_stop_margin
                active_idx = active_idx[keep]
                if len(active_idx) == 0:
                    break
                # the point of early stopping is SKIPPING work: later
                # trees only traverse the still-active rows
                Xa = X[active_idx]
            for k in range(K):
                pred = self.models_[it * K + k].predict(Xa)
                if use_es:
                    out[k][active_idx] += pred
                else:
                    out[k] += pred
        if self.average_output_ and end > start_iteration:
            out /= end - start_iteration  # ref: gbdt_prediction.cpp:57
        return out[0] if K == 1 else out.T

    def predict(self, X: np.ndarray, raw_score: bool = False,
                start_iteration: int = 0, num_iteration: int = -1,
                pred_leaf: bool = False, **pred_kwargs) -> np.ndarray:
        if pred_leaf:
            return self.predict_leaf_index(X, start_iteration, num_iteration)
        if not raw_score and self.objective is not None:
            hit = self._device_predictor(
                X, start_iteration, num_iteration,
                pred_kwargs.get("pred_early_stop", False))
            if hit is not None:
                es = self._es_tuple(
                    pred_kwargs.get("pred_early_stop", False),
                    pred_kwargs.get("pred_early_stop_freq", 10),
                    pred_kwargs.get("pred_early_stop_margin", 10.0))
                # convert_output fused into the device program
                return self._device_predict_run(hit[0], hit[1], "convert",
                                                es)
        raw = self.predict_raw(X, start_iteration, num_iteration,
                               **pred_kwargs)
        if raw_score or self.objective is None:
            return raw
        # host path: the scores are already NumPy — use the objective's
        # host converter instead of a host->device->host round trip
        conv = self.objective.convert_output_host
        if raw.ndim == 2:
            return np.asarray(conv(raw.T)).T
        return np.asarray(conv(raw))

    def _calculate_linear(self, tree: Tree, leaf_id: np.ndarray,
                          grad: np.ndarray, hess: np.ndarray) -> None:
        """Fit linear leaf models by weighted ridge normal equations
        (ref: linear_tree_learner.cpp:184 CalculateLinear, Eq 3 of
        arXiv:1802.05640: coeffs = -(X'HX + lambda)^-1 X'g over the leaf's
        numerical branch features plus a constant column; rows with NaN in
        any branch feature are excluded; degenerate leaves keep
        leaf_value as the constant)."""
        from ..io.binning import BIN_NUMERICAL
        cfg = self.config
        ds = self.train_data
        raw = self._raw_or_reconstruct(ds)
        tree.is_linear = True
        nl = tree.num_leaves
        # branch features per leaf: climb the parent chain
        for leaf in range(nl):
            feats = []
            node = tree.leaf_parent[leaf]
            while node >= 0:
                feats.append(int(tree.split_feature[node]))
                # find this node's parent: scan child pointers
                parents = np.nonzero(
                    (tree.left_child[:nl - 1] == node)
                    | (tree.right_child[:nl - 1] == node))[0]
                node = int(parents[0]) if len(parents) else -1
            feats = sorted(set(
                f for f in feats
                if ds.bin_mappers[f].bin_type == BIN_NUMERICAL))
            rows = np.nonzero((leaf_id == leaf) & (hess > 0))[0]
            k = len(feats)
            if len(rows) == 0:
                tree.leaf_const[leaf] = tree.leaf_value[leaf]
                tree.leaf_features[leaf] = []
                tree.leaf_features_inner[leaf] = []
                tree.leaf_coeff[leaf] = []
                continue
            Xl = raw[np.ix_(rows, feats)] if k else np.zeros((len(rows), 0))
            ok = ~np.isnan(Xl).any(axis=1)
            if ok.sum() < k + 1:
                tree.leaf_const[leaf] = tree.leaf_value[leaf]
                tree.leaf_features[leaf] = []
                tree.leaf_features_inner[leaf] = []
                tree.leaf_coeff[leaf] = []
                continue
            Xd = np.column_stack([Xl[ok], np.ones(int(ok.sum()))])
            g = grad[rows][ok]
            h = hess[rows][ok]
            XTHX = Xd.T @ (Xd * h[:, None])
            XTHX[np.arange(k), np.arange(k)] += cfg.linear_lambda
            XTg = Xd.T @ g
            try:
                coeffs = -np.linalg.solve(XTHX, XTg)
            except np.linalg.LinAlgError:
                coeffs = -np.linalg.pinv(XTHX) @ XTg
            keep = [i for i in range(k)
                    if abs(coeffs[i]) > 1e-35]     # kZeroThreshold filter
            tree.leaf_features[leaf] = [feats[i] for i in keep]
            tree.leaf_features_inner[leaf] = [
                ds.inner_feature_index(feats[i]) for i in keep]
            tree.leaf_coeff[leaf] = [float(coeffs[i]) for i in keep]
            tree.leaf_const[leaf] = float(coeffs[k])

    def refit(self, X: np.ndarray, label: np.ndarray,
              weight: Optional[np.ndarray] = None) -> None:
        """Refit the existing tree structures' leaf values to new data
        (ref: gbdt.cpp:252 RefitTree; serial_tree_learner.cpp:241
        FitByExistingTree: new_leaf = decay*old + (1-decay)*output*shrink)."""
        self._sync_model()
        import jax.numpy as jnp_
        from ..io.dataset import Metadata
        from ..objective import create_objective
        X = np.asarray(X, np.float64)
        n = X.shape[0]
        K = self.num_tree_per_iteration
        leaf_preds = self.predict_leaf_index(X)        # [n, num_trees]
        md = Metadata(n)
        md.set_label(np.asarray(label, np.float64))
        if weight is not None:
            md.set_weight(weight)
        obj = self.objective or create_objective(self.config)
        obj.init(md, n)
        lab = jnp_.asarray(np.asarray(obj.label, np.float32))
        w = (None if md.weight is None
             else jnp_.asarray(np.asarray(md.weight, np.float32)))
        score = np.zeros((K, n), np.float64)
        try:
            self._refit_trees(obj, lab, w, score, leaf_preds)
        finally:
            # the in-place leaf mutations invalidate the packed-predictor
            # cache; bump AFTER them (not before predict_leaf_index above,
            # which would repopulate the cache under the new key) and even
            # when a later iteration raises mid-mutation
            self._model_mutations = getattr(self, "_model_mutations", 0) + 1

    def _refit_trees(self, obj, lab, w, score, leaf_preds):
        import jax.numpy as jnp_
        cfg = self.config
        K = self.num_tree_per_iteration
        num_iters = len(self.models_) // K
        decay = cfg.refit_decay_rate
        l1, l2 = cfg.lambda_l1, cfg.lambda_l2
        n = score.shape[1]
        for it in range(num_iters):
            sc = jnp_.asarray(score.astype(np.float32))
            g, h = obj.get_gradients(sc if K > 1 else sc[0], lab, w)
            g = np.asarray(g).reshape(K, n)
            h = np.asarray(h).reshape(K, n)
            for k in range(K):
                m = it * K + k
                tree = self.models_[m]
                nl = tree.num_leaves
                lp = np.clip(leaf_preds[:, m], 0, nl - 1)
                sg = np.bincount(lp, weights=g[k], minlength=nl)[:nl]
                sh = np.bincount(lp, weights=h[k], minlength=nl)[:nl] + K_EPSILON
                sg_l1 = np.sign(sg) * np.maximum(np.abs(sg) - l1, 0.0)
                out = -sg_l1 / (sh + l2)
                if cfg.max_delta_step > 0:
                    out = np.clip(out, -cfg.max_delta_step,
                                  cfg.max_delta_step)
                new = (decay * tree.leaf_value[:nl]
                       + (1.0 - decay) * out * tree.shrinkage)
                tree.leaf_value[:nl] = new
                tree.leaf_count[:nl] = np.bincount(lp, minlength=nl)[:nl]
                score[k] += new[lp]

    def predict_contrib(self, X: np.ndarray, start_iteration: int = 0,
                        num_iteration: int = -1) -> np.ndarray:
        """SHAP feature contributions [n, K*(F+1)]: per class, F per-feature
        columns plus the expected value, summing to the raw score
        (ref: gbdt.h:314 PredictContrib; tree.h:139; TreeSHAP in
        src/io/tree.cpp)."""
        from ..native import tree_shap
        # the recursive path-weight algorithm has no device form yet
        # (ROADMAP "kill the host-fallback matrix")
        self._host_fallback("pred-contrib")
        self._sync_model()
        X = np.asarray(X, dtype=np.float64)
        if X.ndim == 1:
            X = X[None, :]
        n = X.shape[0]
        K = self.num_tree_per_iteration
        F = self.train_data.num_total_features
        total_iters = len(self.models_) // K
        if num_iteration < 0:
            num_iteration = total_iters - start_iteration
        end = min(start_iteration + num_iteration, total_iters)
        phi = np.zeros((K, n, F + 1))
        for it in range(start_iteration, end):
            for k in range(K):
                tree_shap(self.models_[it * K + k], X, phi[k])
        if self.average_output_ and end > start_iteration:
            phi /= end - start_iteration
        if K == 1:
            return phi[0]
        return phi.transpose(1, 0, 2).reshape(n, K * (F + 1))

    def predict_leaf_index(self, X: np.ndarray, start_iteration: int = 0,
                           num_iteration: int = -1) -> np.ndarray:
        hit = self._device_predictor(X, start_iteration, num_iteration)
        if hit is not None:
            return self._device_predict_run(hit[0], hit[1], "leaf")
        self._sync_model()
        X = np.asarray(X, dtype=np.float64)
        K = self.num_tree_per_iteration
        total_iters = len(self.models_) // K
        if num_iteration < 0:
            num_iteration = total_iters - start_iteration
        end = min(start_iteration + num_iteration, total_iters)
        if end > start_iteration:
            # same native traversal as predict, returning leaf ids;
            # shares predict_raw's packed-model cache
            packed = self._packed_for(start_iteration, end, K)
            if packed is not None:
                res = packed.predict_leaf(X)
                if res is not None:
                    return res
        cols = []
        for it in range(start_iteration, end):
            for k in range(K):
                cols.append(self.models_[it * K + k].get_leaf_index(X))
        return np.stack(cols, axis=1) if cols else np.zeros((X.shape[0], 0), np.int32)

    @property
    def num_trees(self) -> int:
        return len(self.models_)

    def current_iteration(self) -> int:
        return len(self.models_) // max(self.num_tree_per_iteration, 1)

    def rollback_one_iter(self) -> None:
        """ref: gbdt.cpp:443 RollbackOneIter (model-side only; scores are
        rebuilt lazily on next use)."""
        self._sync_model()
        K = self.num_tree_per_iteration
        if len(self.models_) >= K:
            del self.models_[-K:]
            self.iter_ -= 1

    # --------------------------------------------------------------- model IO
    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        self._sync_model()
        F = self.train_data.num_total_features if self.train_data else (
            max(int(t.split_feature[:t.num_leaves - 1].max(initial=0))
                for t in self.models_) + 1 if self.models_ else 0)
        out = np.zeros(F)
        for t in self.models_:
            if importance_type == "split":
                out += t.feature_importance_split(F)
            else:
                out += t.feature_importance_gain(F)
        return out
