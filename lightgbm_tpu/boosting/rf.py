"""Random-forest mode: bagged trees, no shrinkage, averaged outputs
(ref: src/boosting/rf.hpp:25 RF).

Gradients are computed ONCE from the constant init score (no boosting);
the training score is maintained as the running average of tree predictions
via the multiply/add/multiply pattern of rf.hpp TrainOneIter.
"""

from __future__ import annotations

from typing import List

import numpy as np
import jax.numpy as jnp

from ..models.tree import Tree
from ..utils import log
from .gbdt import GBDT, K_EPSILON


class RF(GBDT):
    """ref: rf.hpp:25."""

    average_output_ = True

    def init(self, config, train_data, objective, metrics) -> None:
        if config.data_sample_strategy == "goss":
            # GOSS reweights gradients per iteration; RF reuses ONE
            # gradient map for every tree (rf.hpp:95 Boosting computes
            # once) — the combination is meaningless, and the goss
            # sampler donates its inputs, which would consume the
            # persistent RF gradient buffers
            log.fatal("RF mode does not support data_sample_strategy=goss")
        if config.data_sample_strategy == "bagging":
            ok = ((config.bagging_freq > 0
                   and 0.0 < config.bagging_fraction < 1.0)
                  or 0.0 < config.feature_fraction < 1.0)
            if not ok:
                log.fatal("RF mode requires bagging "
                          "(bagging_freq > 0 and bagging_fraction in (0, 1)) "
                          "or feature_fraction in (0, 1)")
        if objective is None:
            log.fatal("RF mode does not support custom objective functions")
        super().init(config, train_data, objective, metrics)
        if self.has_init_score:
            log.fatal("RF mode does not support init_score")
        self.shrinkage_rate = 1.0
        self._rf_boosting()

    def _rf_boosting(self) -> None:
        """Gradients from the constant init score, computed once
        (ref: rf.hpp:95 Boosting)."""
        cfg, obj = self.config, self.objective
        K = self.num_tree_per_iteration
        self._rf_init_scores: List[float] = [0.0] * K
        if cfg.boost_from_average and self.train_data.num_features > 0:
            for k in range(K):
                self._rf_init_scores[k] = obj.boost_from_score(k)
        saved = self.scores
        self.scores = jnp.broadcast_to(
            jnp.asarray(self._rf_init_scores, jnp.float32)[:, None],
            (K, self.n_pad)).astype(jnp.float32) * 1.0
        self._rf_grad, self._rf_hess = self._compute_gradients()
        self.scores = saved

    # NOTE on rf.hpp:44-47's MultiplyScore(1/num_init): our continue_from
    # seeds with prev.predict_raw(), which already averages when the init
    # model is an RF (average_output_), so the seeded scores are correct
    # as-is and no extra division happens here.

    def _rf_multiply_score(self, class_id: int, val: float) -> None:
        """ref: rf.hpp:210 MultiplyScore (train + valid updaters)."""
        self.scores = self.scores.at[class_id].multiply(val)
        for sc in self.valid_scores:
            sc[class_id] *= val

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        """ref: rf.hpp:117 TrainOneIter — never stops, never shrinks."""
        if gradients is not None or hessians is not None:
            log.fatal("RF mode does not support custom objective functions")
        # sentinel flags fetched for the previous iteration are stale now
        self._finite_cache = None
        K = self.num_tree_per_iteration
        bag_mask, grad, hess = self._update_bagging(self._rf_grad,
                                                    self._rf_hess)
        cur = float(self.iter_ + self.num_init_iteration_)
        for k in range(K):
            tree = None
            leaf_id = None
            if self.class_need_train[k] and self.train_data.num_features > 0:
                grow_kw = {}
                if self._cegb_used is not None:
                    grow_kw["cegb_used"] = self._cegb_used
                if self._lazy_used is not None:
                    grow_kw["lazy_used"] = self._lazy_used
                grow_kw.update(self._classed_kw)
                out = self._grow_fn(
                    self.binned_dev, self._slice_row_fn(grad, k),
                    self._slice_row_fn(hess, k), bag_mask,
                    self._col_mask(), self.meta, self.grow_params,
                    **grow_kw)
                if self._lazy_used is not None:
                    arrays, leaf_id, self._lazy_used = out
                else:
                    arrays, leaf_id = out
                if self._cegb_used is not None:
                    self._cegb_used = self._cegb_mark_fn(
                        self._cegb_used, arrays.split_feature,
                        arrays.num_leaves)
                tree = self._arrays_to_tree(arrays)
            if tree is not None:
                nl = tree.num_leaves
                init = self._rf_init_scores[k]
                obj = self.objective
                if obj is not None and obj.need_renew_tree_output:
                    # residual against the constant init score, matching
                    # rf.hpp's residual_getter = label - init
                    leaf_id_host = np.asarray(leaf_id)[:self.num_data]
                    bag = self._bag_mask_host[:self.num_data] > 0
                    renewed = obj.renew_tree_output(
                        np.where(bag, leaf_id_host, -1),
                        np.full(self.num_data, init, np.float64), nl)
                    if renewed is not None:
                        tree.leaf_value[:nl] = renewed
                if abs(init) > K_EPSILON:
                    tree.add_bias(init)
                # running average: score = (score*cur + tree_pred)/(cur+1)
                self._rf_multiply_score(k, cur)
                L = self.config.num_leaves
                leaf_vals = jnp.asarray(
                    tree.leaf_value[:max(L, 2)].astype(np.float32))
                self.scores = self._score_update_fn(
                    self.scores, k, leaf_vals, leaf_id, self.pad_mask)
                self._add_tree_score(tree, k, train=False)
                self._rf_multiply_score(k, 1.0 / (cur + 1.0))
            else:
                tree = Tree(2)
                tree.num_leaves = 1
                if len(self.models_) < K:
                    output = 0.0
                    if not self.class_need_train[k]:
                        output = self.objective.boost_from_score(k)
                    tree.leaf_value[0] = output
                    tree.shrinkage = 1.0
                    self._rf_multiply_score(k, cur)
                    self.scores = self.scores.at[k].add(
                        float(output) * self.pad_mask)
                    for sc in self.valid_scores:
                        sc[k] += output
                    self._rf_multiply_score(k, 1.0 / (cur + 1.0))
            self.models_.append(tree)
        self.iter_ += 1
        return False
