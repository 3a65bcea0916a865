"""Which growth engine and histogram method a booster takes.

One pure function of the configuration, the backend and the shape, beside
the two engines it chooses between (wave.py, grow.py): a CPU test can ask
what a TPU would take (tests/test_kernel_plan.py).  The kernel's own
limits are ops/histogram.py plan_wave_kernel's; nothing here counts bytes.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

from ..ops.histogram import plan_wave_kernel


class GrowthPlan(NamedTuple):
    strategy: str               # "wave" | "leafwise"
    hist_method: str            # "pallas" | "onehot_hp" | "segment"
    sharded_wave: bool          # the wave engine under shard_map
    warnings: Tuple[str, ...]   # for the caller to log


def plan_growth(*, backend: str, strategy: str, num_leaves: int,
                num_features: int, max_bin: int, gpu_use_dp: bool,
                pinned_leafwise: bool, row_mesh: bool,
                voting: bool) -> GrowthPlan:
    """Growth engine: wave (level-batched; one MXU histogram sweep per
    round with leaf slots as the matmul's output columns) vs strict
    leaf-wise (partitioned segments; the reference-parity order).

    `strategy` is `tpu_growth_strategy` (auto / wave / leafwise, checked
    by the caller); `pinned_leafwise` names the modes that recompute
    global state after every split (below); `row_mesh` is a mesh that
    shards rows, `voting` the PV-Tree learner on it.  `num_features` and
    `max_bin` are the shape the histogram kernel runs: device columns
    and their bins, which under EFB are bundle columns and the largest
    column's codes, not features and `max_bin`."""
    # Fused Pallas one-hot kernel on TPU (one-hot tiles live only in
    # VMEM, like the CUDA shared-memory histogram kernels); XLA's
    # scatter path wins on CPU.  Both accumulate fp32; gpu_use_dp
    # selects the 3-pass high-precision matmul fallback instead
    # (ref: gpu_tree_learner.h:79 single-precision default).
    hist_method = (("onehot_hp" if gpu_use_dp else "pallas")
                   if backend == "tpu" else "segment")
    warnings = []
    if pinned_leafwise:
        # interaction constraints and forced splits run on the wave
        # engine (branch masks compose with waves; forced splits
        # apply as a one-split-per-wave prologue, wave.py).  Voting
        # elects per-leaf feature sets (children not derivable by
        # subtraction), and intermediate monotone / lazy CEGB
        # recompute global state after EVERY split — inherently
        # sequential, so they keep the leaf-wise engine (measured
        # 0.958 s/iter at bench scale vs the same-host oracle's
        # 9.8 — see PERF_NOTES).
        if strategy == "wave":
            warnings.append("voting / intermediate monotone / lazy CEGB "
                            "use the leaf-wise engine")
        strategy = "leafwise"
    pallas_wave = (hist_method == "pallas"
                   and plan_wave_kernel(num_features, max_bin,
                                        num_leaves).fits)
    if strategy == "auto":
        strategy = ("wave" if backend == "tpu" and num_leaves >= 8
                    and pallas_wave else "leafwise")
    elif strategy == "wave" and backend == "tpu" and not pallas_wave:
        warnings.append("tpu_growth_strategy=wave without the fused Pallas "
                        "histogram falls back to the XLA one-hot wave "
                        "histogram, which materializes [F, n, B] — only "
                        "viable for small datasets")
    if strategy == "leafwise" and row_mesh:
        # leaf-wise under a row mesh rides GSPMD annotations,
        # which cannot partition a pallas_call
        hist_method = "segment"
    # data-parallel wave: the DEFAULT engine sharded over the row
    # mesh via shard_map + histogram psum (the reference's
    # ReduceScatter path, data_parallel_tree_learner.cpp:282)
    sharded_wave = strategy == "wave" and row_mesh and not voting
    return GrowthPlan(strategy, hist_method, sharded_wave, tuple(warnings))
