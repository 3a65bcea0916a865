"""Tree learners: jitted whole-tree growth on TPU.

Replaces the reference's src/treelearner/ (SerialTreeLearner + CUDA single-GPU
learner): the per-leaf loop runs inside one XLA program (lax.fori_loop) instead
of a host-driven kernel-launch loop, per SURVEY.md §3.3's TPU lesson.
"""

from .grow import (FeatureMeta, GrowParams, TreeArrays, grow_tree,
                   grow_tree_donated, make_grow_tree)
from .select import GrowthPlan, plan_growth
from .wave import grow_tree_wave, grow_tree_wave_donated

__all__ = ["FeatureMeta", "GrowParams", "GrowthPlan", "TreeArrays",
           "grow_tree", "grow_tree_donated", "grow_tree_wave",
           "grow_tree_wave_donated", "make_grow_tree", "plan_growth"]
