"""Histogram construction: the hottest op of GBDT training, as XLA computations.

TPU-native replacement for the reference's per-bin accumulation loops
(ref: src/io/dense_bin.hpp:99-176 ConstructHistogramInner and the CUDA
shared-memory kernels in src/treelearner/cuda/cuda_histogram_constructor.cu).
Instead of scalar scatter loops, histograms are built as one XLA computation over
the whole binned matrix:

  hist[f, b, c] = sum over rows r of (binned[f, r] == b) * gh[r, c]

Two interchangeable lowerings:

* ``segment`` — flat `segment_sum` keyed by ``f * B + bin`` (a single fused
  scatter-add; exact fp32 accumulation, the default).
* ``onehot`` — one-hot matmul ``gh.T @ onehot(bin)`` that maps onto the MXU
  systolic array (per the pallas guide's "histogram as matmul" recipe).

Both are row-chunked with `lax.scan` so peak memory is bounded regardless of
num_data; the row axis is the data-parallel sharding axis, so under pjit/shard_map
the chunk reduction lowers to a `psum` across the mesh — the ICI/DCN equivalent of
the reference's `Network::ReduceScatter` of histograms
(ref: src/treelearner/data_parallel_tree_learner.cpp:284).

The histogram stores 2 channels (sum_gradient, sum_hessian) per bin, matching the
reference's float histogram entry (ref: include/LightGBM/bin.h:46 kHistEntrySize);
data counts are derived downstream from hessian sums exactly as the reference does
(Common::RoundInt(hess * cnt_factor), ref: feature_histogram.hpp:873).
"""

from __future__ import annotations

import functools
import itertools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..observability.registry import global_registry
from ..utils.timer import global_timer

# Each kernel's `name` is its custom-call's name in a profiler trace
# (`%build_histogram_wave.23 = ...`): the benchmark's readers find the
# kernels by the head `build_histogram` (benchmarks/layer_metrics/), so a
# rename here drops `hist_kernel_ms` out of the result line.


def _count_traced_call(feature_groups: int) -> None:
    """Registry counters of the wave kernels' wrappers, added where a
    wrapper is TRACED (its body runs once per signature and enclosing
    trace, not once per call on the device): `hist_kernel_calls` and the
    feature groups those calls run, each of which re-streams `slot` and
    `gh` over all rows.  Their ratio is groups per call however often
    the program is compiled (benchmarks' `hist_groups_per_call`)."""
    global_registry.inc("hist_kernel_calls")
    global_registry.inc("hist_feature_group_passes", feature_groups)


def mxu_call_scope(plan: "WaveKernelPlan", num_features: int,
                   num_slots: int, true_slots: Optional[int] = None,
                   C: int = 2):
    """The `device_scope` PART a histogram kernel call runs under, inside
    `Tree::histogram`: `Hist::mxu_n<n>_f<f>_e<e>`, three integers that
    ride each kernel event's `op_name` into the device trace at no run
    time (docs/Observability.md has the grammar; the benchmark's
    `hist_mxu_roofline` / `hist_mxu_padding` read them).  `n`: the useful
    output columns, channels x the wave's TRUE computed slots where it
    names them (the ladder's 2- and 4-slot waves are both padded to 8);
    `f`: the MXU FLOP a row the call's dots ask (`mxu_flop_per_row`);
    `e`: the `pallas_call`s the call issues, so that a reader shares `f`
    among the call's events.  Shapes and the sorted class multiset in,
    never the column order: every `--seed` of a cell traces the same
    labels.  `scope_layout.json`'s label pattern does not match `Hist.`,
    so every op stays under `Tree.histogram`."""
    n = C * (num_slots if true_slots is None else true_slots)
    f = mxu_flop_per_row(
        plan, num_features,
        true_slots if plan.kernel == "wave_hl" else num_slots, C)
    e = max(len(plan.class_groups), 1)
    return global_timer.device_scope(f"Hist::mxu_n{n}_f{f}_e{e}")


# lowerings whose MXU operands are bf16: each row's accumuland is rounded
# to bf16 on its way into the dot (the one-hot side is exact, the
# accumulator is fp32)
BF16_OPERAND_METHODS = ("pallas", "onehot")


def snap_to_operand_grid(x: jnp.ndarray, method: str) -> jnp.ndarray:
    """Round per-row accumulands (gradients, hessians) to the grid the
    `method`'s MXU operands live on, ONCE, before anything sums them.

    A leaf's sums reach the engines two ways: as histogram bins (sums of
    the ROUNDED rows, for a bf16-operand lowering) and as root totals
    minus sibling sums.  With exact fp32 root totals the two disagree by
    (rounding bias) x (rows outside the leaf) — 4.4e-4 a row at hessian
    0.2447, i.e. hundreds at 2^20 rows — and the leaf at the end of every
    parent-minus-sibling chain got a sum near zero and an output in the
    thousands (seen on the chip, PR 23).  Rounding first makes every
    consumer sum the same numbers; the kernels' own casts become exact.
    `reduce_precision`, not a convert pair: XLA may drop
    f32->bf16->f32 as excess precision."""
    if method in BF16_OPERAND_METHODS:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _pick_chunk(n: int, num_features: int, max_bin: int, method: str) -> int:
    """Row-chunk size.  For `onehot` the [F, R, B] one-hot materialization is
    the memory driver (keep it ~64MB); for `segment` the flat id/value copies
    are (keep F*R around 4M)."""
    if method == "onehot":
        r = (64 << 20) // max(num_features * max_bin * 2, 1)
    elif method == "onehot_hp":
        r = (64 << 20) // max(num_features * max_bin * 4, 1)
    else:
        r = (1 << 22) // max(num_features, 1)
    r = max(1024, r)
    r = 1 << (int(r) - 1).bit_length()  # next pow2
    return min(r, _round_up(n, 1024))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _hist_chunk_segment(binned_c: jnp.ndarray, gh_c: jnp.ndarray,
                        num_bins_total: int, max_bin: int) -> jnp.ndarray:
    """One chunk: binned_c [F, R] int, gh_c [R, 2] -> [F*B, 2] via segment_sum."""
    num_features = binned_c.shape[0]
    offsets = (jnp.arange(num_features, dtype=jnp.int32) * max_bin)[:, None]
    ids = (binned_c.astype(jnp.int32) + offsets).reshape(-1)  # [F*R]
    vals = jnp.broadcast_to(gh_c[None, :, :],
                            (num_features,) + gh_c.shape).reshape(-1, gh_c.shape[-1])
    return jax.ops.segment_sum(vals, ids, num_segments=num_bins_total,
                               indices_are_sorted=False, unique_indices=False)


def _hist_chunk_onehot(binned_c: jnp.ndarray, gh_c: jnp.ndarray,
                       num_bins_total: int, max_bin: int,
                       compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """One chunk via MXU one-hot matmul: [C, R] @ [R, F*B] with C=gh channels.

    Default is single-pass bf16 multiply with fp32 accumulation — the
    one-hot side is exact in bf16 and the reference's own GPU learner uses
    single-precision histograms by default (ref: gpu_tree_learner.h:79
    gpu_use_dp=false; its quantized path even uses int8 grads).  Pass
    compute_dtype=float32 for the 3-pass high-precision variant.
    """
    num_features, rows = binned_c.shape
    onehot = (binned_c[:, :, None] ==
              jnp.arange(max_bin, dtype=binned_c.dtype)[None, None, :])
    onehot = onehot.astype(compute_dtype)                   # [F, R, B]
    onehot = jnp.transpose(onehot, (1, 0, 2)).reshape(rows, num_features * max_bin)
    precision = (jax.lax.Precision.HIGH if compute_dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(
        gh_c.astype(compute_dtype), onehot, (((0,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32).T               # [F*B, C]


def _hist_pallas_kernel(Fg: int, Bp: int, C: int):
    """Fused one-hot histogram kernel: per (feature-group, row-tile) build
    the [Fg, Bp, Rt] one-hot in VMEM only (never HBM) and contract all
    features' bins against gh in ONE MXU dot — the Pallas analogue of the
    CUDA shared-memory histogram kernel (ref:
    cuda_histogram_constructor.cu:18-230, which accumulates per-block
    histograms in shared memory for the same reason)."""
    def kernel(rows_ref, gh_ref, out_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
        rows = rows_ref[...].astype(jnp.int32)        # [Fg, Rt]
        ghv = gh_ref[...].astype(jnp.bfloat16)        # [Rt, C]
        Rt = rows.shape[1]
        biota = jax.lax.broadcasted_iota(jnp.int32, (Fg, Bp, Rt), 1)
        oh = (rows[:, None, :] == biota).astype(jnp.bfloat16)  # [Fg, Bp, Rt]
        acc = jax.lax.dot_general(
            oh.reshape(Fg * Bp, Rt), ghv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [Fg*Bp, C]
        out_ref[...] += acc.reshape(Fg, Bp, C)
    return kernel


@functools.partial(jax.jit, static_argnames=("max_bin", "row_tile"))
def build_histogram_rows_pallas(rows: jnp.ndarray, gh: jnp.ndarray,
                                mask: jnp.ndarray, *, max_bin: int,
                                row_tile: int = 512) -> jnp.ndarray:
    """Histogram over row-major binned data [S, F] via the fused Pallas
    kernel.  S must be a multiple of row_tile.  Returns [F, B, C] float32."""
    S, F = rows.shape
    C = gh.shape[-1]
    Bp = (max_bin + 127) // 128 * 128
    if S % row_tile != 0:
        raise ValueError(f"rows {S} not a multiple of row_tile {row_tile}")
    # feature-major layout; pad F to the TPU's 8-sublane block granule
    Fp = (F + 7) // 8 * 8
    with global_timer.device_scope("Tree::hist_operands"):
        gh = (gh * mask.astype(gh.dtype)[:, None]).astype(jnp.float32)
        rows_fm = rows.T
        if Fp != F:
            rows_fm = jnp.pad(rows_fm, ((0, Fp - F), (0, 0)))
    # feature group bounded by the [Fg, Bp, Rt] bf16 one-hot in VMEM (~2MB)
    Fg = _pick_feature_group(Fp, Bp * row_tile * 2, 2 << 20)
    plan = WaveKernelPlan(kernel="rows", feature_pad=Fp, feature_group=Fg,
                          groups=Fp // Fg, hl_split=None,
                          vmem_bytes=Fg * Bp * row_tile * 2, fits=True,
                          onehot_rows=Fp * Bp)
    with mxu_call_scope(plan, F, 1, C=C):    # one leaf's histogram
        out = pl.pallas_call(
            _hist_pallas_kernel(Fg, Bp, C),
            grid=(Fp // Fg, S // row_tile),
            in_specs=[pl.BlockSpec((Fg, row_tile), lambda g, i: (g, i)),
                      pl.BlockSpec((row_tile, C), lambda g, i: (i, 0))],
            out_specs=pl.BlockSpec((Fg, Bp, C), lambda g, i: (g, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((Fp, Bp, C), jnp.float32),
            metadata=_kernel_metadata(plan, F, 1, C),
            name="build_histogram_rows",
        )(rows_fm, gh)
    return out[:F, :max_bin, :]                       # [F, B, C]


def _wave_kernel(C: int, Fg: int, Bg: int, NLg: int, classes: tuple = ()):
    """Multi-leaf fused histogram kernel for wave (level-batched) growth.

    Per (slot-group, bin-group, feature-group, row-tile) grid cell, build
    the [Fg, Bg, Rt] bin one-hot and the slot-separated channel matrix
    [C*NLg, Rt] in VMEM — rows on lanes in both, like the operand blocks
    slot [1, Rt] and gh [C+1, Rt] they are built from — then ONE MXU dot
    (both operands contracted over their lane axis, the q·kᵀ form)
    accumulates all NLg leaves' and all C channels' histograms at once.  The leaf-slot axis is what fills
    the MXU's 128-wide output dimension — a plain per-leaf histogram dot
    has C=2 output columns and idles 126/128 of the systolic array, which
    is the dominant cost of histogram construction on TPU.  Fusing the
    channels into the output dimension (instead of one dot per channel)
    matters for the same reason: the MXU pads output lanes to 128, so
    early waves with few slots pay for 128 lanes regardless — C dots at
    NLg<=64 slots cost C times one fused dot.  (TPU replacement for the
    CUDA per-leaf shared-memory kernels,
    ref: cuda_histogram_constructor.cu:18.)

    With `classes` — runs `(codes, columns)` of the block's Fg columns,
    as `plan_wave_kernel` cut them — each run's one-hot is built at its
    own `codes` and the runs are stacked on the sublane axis (every
    `codes` a whole number of bf16 sublane tiles): the dot's M is the
    sum of the columns' class codes and not Fg * Bg, and the out block
    is the flat [M, lanes] that sum gives."""
    def kernel(rows_ref, slot_ref, gh_ref, out_ref, cnt_ref):
        bg = pl.program_id(0)
        g = pl.program_id(1)
        @pl.when(pl.program_id(2) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
        @pl.when((bg == 0) & (g == 0) & (pl.program_id(2) == 0))
        def _init_cnt():
            cnt_ref[...] = jnp.zeros_like(cnt_ref)
        # quantized mode: int8 operands (half the one-hot bytes, 2x MXU
        # int8 rate) with exact int32 accumulation — Mosaic legalizes int8
        # select and int8 dot, but NOT int8 multiply, so the channel
        # matrix is built with where() instead of mask*value
        int8_mode = out_ref.dtype == jnp.int32
        mxu_t = jnp.int8 if int8_mode else jnp.bfloat16
        acc_t = jnp.int32 if int8_mode else jnp.float32
        # offset the SMALL [Fg, Rt] rows instead of the big [Fg, Bg, Rt]
        # iota: one elementwise pass less over the big shape.  (On this
        # chip the build is not the call's floor, the dot is: fed a
        # constant one-hot the kernel costs what it costs with the build,
        # `tools/hist_roof_probe.py`, PERF.md section 6, PR 39)
        rows = rows_ref[...].astype(jnp.int32) - bg * Bg  # [Fg, Rt]
        slot = slot_ref[...]                             # [1, Rt]
        Rt = rows.shape[1]
        if classes:
            runs, f0 = [], 0
            for codes, cols in classes:
                biota = jax.lax.broadcasted_iota(jnp.int32,
                                                 (cols, codes, Rt), 1)
                run = jax.lax.slice_in_dim(rows, f0, f0 + cols)
                oh = (run[:, None, :] == biota).astype(mxu_t)
                runs.append(oh.reshape(cols * codes, Rt))
                f0 += cols
            oh2 = jnp.concatenate(runs, axis=0)
        else:
            biota = jax.lax.broadcasted_iota(jnp.int32, (Fg, Bg, Rt), 1)
            oh = (rows[:, None, :] == biota).astype(mxu_t)
            oh2 = oh.reshape(Fg * Bg, Rt)
        lanes = (((1,), (1,)), ((), ()))     # contract both over rows
        S = out_ref.shape[-1] // (C * NLg)
        for s in range(S):  # slot groups REUSE the bin one-hot; each
            # costs its MXU dot and nothing else (a call: 25.4 ms to 64
            # slots, 50.2 at 128; its dots alone 25.4 and 50.1: PR 39)
            # rows stay on lanes: the slot one-hot [NLg, Rt] and the
            # slot-separated channel matrix [C*NLg, Rt] (c-major) are
            # built from sublane broadcasts of the [1, Rt] operand rows —
            # dense vregs at any slot count, where a [Rt, NLg] one-hot
            # padded NLg to 128 lanes
            soh = (slot - s * NLg ==
                   jax.lax.broadcasted_iota(jnp.int32, (NLg, Rt), 0))
            # select in 32 bits (Mosaic relayouts i1->i8 selects badly and
            # cannot multiply int8), then narrow for the MXU
            sc = jnp.concatenate(
                [jnp.where(soh, gh_ref[c:c + 1, :], 0)
                 for c in range(C)], axis=0).astype(mxu_t)
            acc = jax.lax.dot_general(
                oh2, sc, lanes,
                preferred_element_type=acc_t)            # [Fg*Bg, C*NLg]
            # lane dim stays flat (Mosaic cannot split the lane dim); the
            # caller unscrambles the (slot-group, channel, slot) layout
            w = C * NLg
            if classes:
                out_ref[:, s * w:(s + 1) * w] += acc
            else:
                out_ref[:, :, s * w:(s + 1) * w] += acc.reshape(Fg, Bg, w)
            # exact per-slot row counts ride along as a [8, NLg] dot of the
            # mask row (gh[C]) against the slot one-hot — one cell only,
            # replacing a separate 20ms scatter-add pass
            @pl.when((bg == 0) & (g == 0))
            def _count():
                mask8 = jnp.broadcast_to(gh_ref[C:C + 1, :],
                                         (8, Rt)).astype(mxu_t)
                cacc = jax.lax.dot_general(
                    mask8, jnp.where(soh, 1, 0).astype(mxu_t), lanes,
                    preferred_element_type=acc_t)        # [8, NLg]
                cnt_ref[:, s * NLg:(s + 1) * NLg] += cacc
    return kernel


def _wave_kernel_hl(C: int, Fg: int, Bh: int, Bl: int, S: int, P: int):
    """Decomposed (hi/lo outer-product) wave kernel for FEW computed slots.

    The flat cost of `_wave_kernel` to 64 slots is its dot over the
    F*B*Rt bin one-hot, whose N the MXU pads to a 128-column tile however
    few slots ride it (MXU-bound: PR 39's probe, PERF.md section 6; the
    one-hot's build hides under the dot).  For waves whose computed-slot
    count S is small, the one-hot factors over a hi/lo split of the bin
    code

        onehot_B(bin) = onehot_Bh(bin >> log2(Bl)) (x) onehot_Bl(bin & Bl-1)

        hist[f, bh, bl, (c,s)] = sum_n 1[hi=bh] * (1[lo=bl] * w[n,(c,s)])

    so the materialized volume drops from F*B*Rt to
    F*(Bh + Bl*C*S)*Rt — e.g. 48 vs 256 lane-units per feature per row at
    S=1.  Measured on the v5e chip this is ~1.5x the full kernel at S<=2
    and ~1.25x at S=4; the advantage vanishes by
    S=16, where `_wave_kernel`'s slot-riding RHS is already optimal.

    The RHS is built at FULL 128-lane width with expander matmuls —
    sub-128-lane elementwise ops pad to whole vregs on TPU, so a naive
    per-feature [Rt, C*S] build would pay full-width cost anyway:

        d  = [lo_rm | 1] @ [E ; -bl_pat]   (lo minus the column's target
                                            bl; zero exactly on match)
        wt = w_sc_tᵀ @ T                   (tile CS channels across cols;
                                            w_sc_t [C*S, Rt] is built rows
                                            on lanes from the slot [1, Rt]
                                            and gh [C+1, Rt] blocks)
        sc = where(d == 0, wt, 0)

    Main dots pack P features into M and P column blocks into N; only the
    diagonal (f, f) blocks of each [P*Bh, P*Bl*C*S] product are kept.
    (Counterpart of the same smaller-child histogramming the reference
    does serially, dense_bin.hpp:99-176; decomposition is TPU-only.)"""
    CS = C * S
    Wd = Fg * Bl * CS
    shift = Bl.bit_length() - 1

    def kernel(rows_ref, rows_rm_ref, slot_ref, gh_ref, out_ref, cnt_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
            cnt_ref[...] = jnp.zeros_like(cnt_ref)
        i32, bf16 = jnp.int32, jnp.bfloat16
        rows = rows_ref[...].astype(i32)          # [Fg, Rt] (lanes=Rt)
        Rt = rows.shape[1]
        rows_rm = rows_rm_ref[...].astype(i32)    # [Rt, Fg] (sublanes=Rt)
        slot = slot_ref[...]                      # [1, Rt]

        hi = rows >> shift
        biota = jax.lax.broadcasted_iota(i32, (Fg, Bh, Rt), 1)
        hi_oh = (hi[:, None, :] == biota).astype(bf16)

        # w_sc_t [C*S, Rt] (c-major), rows on lanes: row c*S + s holds
        # channel c where the row's slot is s (selected by a row iota, not
        # concatenated per channel: S < 8 rows are no whole sublane tile)
        r_cs = jax.lax.broadcasted_iota(i32, (CS, Rt), 0)
        val = gh_ref[C - 1:C, :]
        for c in range(C - 2, -1, -1):
            val = jnp.where(r_cs < (c + 1) * S, gh_ref[c:c + 1, :], val)
        w_sc_t = jnp.where(slot == r_cs % S, val, 0.0).astype(bf16)

        lo = (rows_rm & (Bl - 1)).astype(bf16)    # [Rt, Fg]
        ones = jnp.ones((Rt, 1), bf16)
        lhs2 = jnp.concatenate([lo, ones], axis=1)            # [Rt, Fg+1]
        colf = jax.lax.broadcasted_iota(i32, (Fg + 1, Wd), 1) // (Bl * CS)
        rowi = jax.lax.broadcasted_iota(i32, (Fg + 1, Wd), 0)
        blp = (jax.lax.broadcasted_iota(i32, (Fg + 1, Wd), 1) // CS) % Bl
        E2 = jnp.where(rowi == Fg, (-blp).astype(bf16),
                       (colf == rowi).astype(bf16))           # [Fg+1, Wd]
        d = jax.lax.dot_general(lhs2, E2, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        csp = jax.lax.broadcasted_iota(i32, (CS, Wd), 1)
        Tm = (csp % CS ==
              jax.lax.broadcasted_iota(i32, (CS, Wd), 0)).astype(bf16)
        wt = jax.lax.dot_general(w_sc_t, Tm, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc = jnp.where(d == 0.0, wt, 0.0).astype(bf16)        # [Rt, Wd]

        BCS = Bl * CS
        for f0 in range(0, Fg, P):
            lhs = hi_oh[f0:f0 + P].reshape(P * Bh, Rt)
            rhs = sc[:, f0 * BCS:(f0 + P) * BCS]
            acc = jax.lax.dot_general(lhs, rhs, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            for p in range(P):
                out_ref[f0 + p] += acc[p * Bh:(p + 1) * Bh,
                                       p * BCS:(p + 1) * BCS]
        # ride-along exact counts (mask row against the slot one-hot)
        # (a whole sublane tile of slots: Mosaic cannot widen a one-row
        # i1 one-hot)
        mask8 = jnp.broadcast_to(gh_ref[C:C + 1, :], (8, Rt)).astype(bf16)
        soh_t = (slot == jax.lax.broadcasted_iota(i32, (max(S, 8), Rt), 0))
        cacc = jax.lax.dot_general(
            mask8, soh_t.astype(bf16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)[:, :S]        # [8, S]
        cnt_ref[...] += cacc
    return kernel


def hl_split_of(max_bin: int, num_slots: int, C: int):
    """(Bh, Bl) split for the decomposed kernel, tuned on the chip:
    balance Bh against Bl*C*S."""
    CS = C * num_slots
    best = None
    for Bl in (2, 4, 8, 16, 32):
        Bh = -(-max_bin // Bl)
        Bh8 = max(8, -(-Bh // 8) * 8)
        cost = Bh8 + Bl * CS
        if best is None or cost < best[0]:
            best = (cost, Bh8, Bl)
    return best[1], best[2]


# VMEM gates of `build_histogram_wave`.  A call's footprint is counted in
# `_wave_unit_bytes` a feature of its block: the f32 accumulator
# [Bg, S*C*NLg] plus the bf16 bin one-hot [Bg, Rt].  The gates were set on
# an older runtime at 28 features; PR 30 put them to the compiler that is
# installed now (compile-only for a described v5e, whose scoped-VMEM
# limit reads 16.00 MiB; tests/test_chip_compile.py keeps the wide cases)
# and ran the grouped path on the chip at 2,000 features
# (tools/kernel_checks.py --wide).  What was read:
# * a grouped call's scoped allocation IS `Fg * unit` (25.0 MB counted,
#   25.21 MiB allocated and refused; 10.0 and 13.3 MB compile), so groups
#   up to ~15 MB would compile and 6 MB leaves room (raising it doubles
#   Fg at 128 slots: a `perf_opt`, judged by the wide cell);
# * a one-group call allocates the one-hot only (F * unit = 24 MB
#   compiles at 128 slots, 32 MB misses by 248 KB), so 16 MB admits no
#   shape the compiler refuses at any slot count: 240 features at 8
#   slots, 232 at 1, 128 at 128 (63 bins), 32 at 128 and 20 at 255 slots
#   (255 bins), each 15.0-16.0 MB, all compile;
# * the smallest legal group (8 features) fits exactly while
#   `8 * unit <= 16 MiB`: at 255 bins 895 slots (16.0 MB) compile and
#   1,023 (18.0) do not; at 63 bins 2,047 (8.5) do and 4,095 (16.5) do
#   not.  (`wave_pallas_vmem_ok` counted one slot group and was true for
#   every shape.)
# A CLASSED call (several `hist_classes`, PR 38) is counted by the
# one-hot row and not by the feature: `_onehot_row_bytes` (the same
# accumulator row + one-hot row, `unit / Bg`) times the rows its block
# builds, `sum(class codes x columns)`.  Each of its class groups is a
# `pallas_call` of its own with one block over its columns — a one-group
# call as above, so its gate is `_FULL_F_VMEM`: the ranking cell's 7,392
# rows count 15.1 MB at 128 slots and compile as one block (unclassed:
# 144 padded columns in three groups), the one-hot cell's 1,200 count
# 2.5 MB (tests/test_chip_compile.py compiles both to 255 slots).
_SCOPED_VMEM = 16 << 20     # the compiler's limit for one kernel
_FULL_F_VMEM = 16 << 20     # one full-F block when F * unit fits this
_GROUP_VMEM = 6 << 20       # else feature groups of at most this


def _bin_pad(max_bin: int) -> Tuple[int, int]:
    """(Bp, Bg) of `build_histogram_wave`: the bins padded to the
    8-sublane granule, in one bin group where 256 hold them (rows are
    then streamed once per wave) and else in groups of 256."""
    Bp = max(8, _round_up(max_bin, 8))
    Bg = min(Bp, 256)
    return _round_up(Bp, Bg), Bg


def _onehot_row_bytes(num_slots: int, C: int = 2, row_tile: int = 512) -> int:
    """VMEM bytes a one-hot row of a `build_histogram_wave` block costs:
    its f32 accumulator row [S*C*NLg] and its bf16 one-hot row [Rt]."""
    return wave_slot_pad(num_slots) * C * 4 + row_tile * 2


def _wave_unit_bytes(max_bin: int, num_slots: int, C: int = 2,
                     row_tile: int = 512) -> int:
    """VMEM bytes a feature of a `build_histogram_wave` block costs."""
    return _bin_pad(max_bin)[1] * _onehot_row_bytes(num_slots, C, row_tile)


def _pick_feature_group(Fp: int, unit_bytes: int, budget: int) -> int:
    """Largest 8-multiple divisor of Fp whose VMEM cost Fg*unit_bytes fits
    the budget (TPU blocks need 8-aligned sublane dims; 8 is the floor)."""
    Fg = 8
    for cand in range(8, Fp + 1, 8):
        if Fp % cand == 0 and cand * unit_bytes <= budget:
            Fg = cand
    return Fg


def wave_slot_pad(num_slots: int) -> int:
    """Slot-axis padding for the wave kernel: the out block's last dim must
    be a multiple of 128 or the whole (padded) slot axis."""
    if num_slots <= 128:
        return max(8, (num_slots + 7) // 8 * 8)
    return (num_slots + 127) // 128 * 128


# The decomposed kernel has no feature grouping (its blocks are (F, Rt)):
# the hi one-hot [F, Bh, Rt] bf16, the [Rt, Wd] expander products (d and
# wt in f32, sc in bf16) and the f32 accumulator must fit together
_HL_VMEM = 12 << 20
# measured crossover of the decomposed kernel's materialized volume
# against the full kernel's F*B *(old chip)* — against what the full
# kernel really builds a column, which with several classes is their
# mean and not `max_bin`; re-tuning it is ROADMAP S1 (2)
_HL_CROSSOVER = 0.6
# The spike waves (learner/wave.py) name their true slot count to the plan
# only up to this many and take the full kernel past it, whatever the
# crossover says: `_wave_kernel_hl`'s advantage was measured to vanish by
# 16 slots.  The ladder's waves have no such cap (they differ from this
# where 0.6 * max_bin admits more than 16 slots, max_bin > ~430):
# ROADMAP S1 (2) settles both together.
_HL_SPIKE_MAX_SLOTS = 16


def spike_true_slots(true_slots: int) -> Optional[int]:
    """The `true_slots` a spike wave hands `plan_wave_kernel`."""
    return true_slots if true_slots <= _HL_SPIKE_MAX_SLOTS else None


# A column's class: its codes rounded up to whole bf16 sublane tiles (16
# rows), so that the classes' one-hots stack in VMEM without a relayout.
# Past 256 codes the kernel runs bin groups, which a classed call has
# none of: such a table is one class.
_CLASS_STEP = 16
_CLASS_MAX_CODES = 256


def hist_classes_of(num_bins) -> Tuple[tuple, np.ndarray]:
    """(`hist_classes`, `hist_order`) of a table's device columns, from
    their code counts `num_bins` [F] — on the host, once a booster.

    `hist_classes` is what the program may know statically: the sorted
    tuple of (class codes, columns of that class), a function of the
    MULTISET of the columns' classes.  Which column holds which class
    is `hist_order` (int32 [F]: a stable sort of the columns by class),
    and that reaches the program as data — a table whose columns arrive
    in another order runs the same program (PERF.md section 6, PR 38)."""
    num_bins = np.asarray(num_bins, np.int64)
    cls = np.maximum(_round_up(num_bins, _CLASS_STEP), _CLASS_STEP)
    if cls.size and cls.max() > _CLASS_MAX_CODES:
        cls[:] = cls.max()
    codes, counts = np.unique(cls, return_counts=True)
    return (tuple((int(c), int(k)) for c, k in zip(codes, counts)),
            np.argsort(cls, kind="stable").astype(np.int32))


def class_ordered(binned_fm: jnp.ndarray,
                  hist_order: jnp.ndarray) -> jnp.ndarray:
    """`binned_fm[hist_order]`: the bins with their columns in class
    order, moved a column at a time.  (As one gather of whole rows XLA
    cuts the copy into 32,512-element pieces, 339 of them at 11,000,832
    rows: 15.6 s to compile for a v5e and a second to load, against 0.4 s
    for this loop.)"""
    def move(j, out):
        column = jax.lax.dynamic_slice_in_dim(binned_fm, hist_order[j], 1)
        return jax.lax.dynamic_update_slice_in_dim(out, column, j, axis=0)
    return jax.lax.fori_loop(0, binned_fm.shape[0], move,
                             jnp.zeros_like(binned_fm))


class WaveKernelPlan(NamedTuple):
    """Which wave kernel a call takes and in what blocks: shapes in,
    nothing of the data."""
    kernel: str                 # "wave" | "wave_hl" ("rows": leaf-wise)
    feature_pad: int            # full kernel: F as its grid sees it (Fp)
    feature_group: int          # full kernel: features a block (Fg)
    groups: int                 # full kernel: passes over the rows
    hl_split: Optional[Tuple[int, int]]   # decomposed kernel: (Bh, Bl)
    vmem_bytes: int             # of `kernel`, as its gate counted them
    fits: bool                  # the full kernel's smallest group compiles
    onehot_rows: int            # full kernel: one-hot rows a row tile
    # full kernel, several classes: a group's runs (codes, columns) of
    # the class-ordered columns, a `pallas_call` a group; else ()
    class_groups: tuple = ()


def _class_groups(hist_classes: tuple, row_bytes: int, budget: int) -> tuple:
    """The class-ordered columns cut into runs whose one-hot rows, at
    `row_bytes` each, fit `budget`: each group a tuple of (codes,
    columns).  A column past the budget alone stands alone (`fits` is
    what says it cannot run)."""
    room = budget // row_bytes
    groups, rows = [[]], 0
    for codes in (c for c, cols in hist_classes for _ in range(cols)):
        if groups[-1] and rows + codes > room:
            groups.append([])
            rows = 0
        groups[-1].append(codes)
        rows += codes
    return tuple(tuple((c, len(list(run))) for c, run in itertools.groupby(g))
                 for g in groups)


def plan_wave_kernel(num_features: int, max_bin: int, num_slots: int,
                     true_slots: Optional[int] = None, *,
                     int8: bool = False, C: int = 2,
                     row_tile: int = 512,
                     hist_classes: tuple = ()) -> WaveKernelPlan:
    """The one owner of "which histogram kernel does a wave run".

    `num_slots` is the padded computed-slot bound (the output's), and
    `true_slots` the unpadded one where the caller knows it: only then,
    and never for int8 operands, can the decomposed kernel take the wave
    — when its materialized volume is meaningfully below what the full
    kernel builds a column and its ungrouped blocks fit VMEM.  Otherwise
    the full kernel runs, as one full-F block where that fits (no padding
    of F to the 8-sublane granule: 12.5% of one-hot volume and MXU rows
    at 28 features, and fewer grid cells) and else in feature groups.
    `fits` is false where even the smallest legal group (8 features, all
    `num_slots` slot groups) is past the compiler's scoped VMEM: such a
    booster takes the leaf-wise engine (learner/select.py).

    `hist_classes` (`hist_classes_of`) is the table's sorted (codes,
    columns) multiset.  One class, or none named, is the call at
    `max_bin` for every column.  With several (never for int8 operands:
    that arm keeps one class) the full kernel builds each column's
    one-hot at its class's codes: VMEM is counted on the classed one-hot
    — `sum(codes x columns) x _onehot_row_bytes` a block — a group is a
    run of class-ordered columns within `_FULL_F_VMEM` (each its own
    one-block call, so a column is never padded), and the decomposed
    kernel is held against the classed volume a column, not `max_bin`."""
    if int8 or len(hist_classes) < 2:
        hist_classes = ()
    unit = _wave_unit_bytes(max_bin, num_slots, C, row_tile)
    class_groups = ()
    if hist_classes:
        assert sum(k for _, k in hist_classes) == num_features
        row_bytes = _onehot_row_bytes(num_slots, C, row_tile)
        class_groups = _class_groups(hist_classes, row_bytes, _FULL_F_VMEM)
        group_rows = [sum(c * k for c, k in g) for g in class_groups]
        Fp, Fg = num_features, max(sum(k for _, k in g)
                                   for g in class_groups)
        groups, onehot_rows = len(class_groups), sum(group_rows)
        full_vmem = max(group_rows) * row_bytes
    else:
        if num_features * unit <= _FULL_F_VMEM:
            Fp = Fg = num_features
        else:
            # TPU block constraint: the binned block's second-to-last dim
            # (Fg) must be a multiple of 8 OR the whole (unpadded) F
            Fp = _round_up(num_features, 8)
            # feature group bounded by the VMEM accumulator
            # [Fg, Bg, S*C*NLg] plus the [Fg, Bg, Rt] bf16 one-hot
            Fg = _pick_feature_group(Fp, unit, _GROUP_VMEM)
        groups, onehot_rows = Fp // Fg, Fp * _bin_pad(max_bin)[0]
        full_vmem = Fg * unit
    kernel, split, vmem = "wave", None, full_vmem
    if true_slots is not None:
        Bh, Bl = split = hl_split_of(max_bin, true_slots, C)
        CS = C * true_slots
        Wd = num_features * Bl * CS
        hl_vmem = (num_features * Bh * row_tile * 2 + row_tile * Wd * 10
                   + num_features * Bh * Bl * CS * 4)
        # what the full kernel builds a column: `max_bin` with one
        # class, the classed mean with several (100 codes at the one-hot
        # cell's 12 columns, where `max_bin` is 255)
        full_codes = (onehot_rows / num_features if hist_classes
                      else max_bin)
        # Bh > 256 would overflow the feature-packed M dimension (and
        # such giant max_bin configs gain nothing from decomposition
        # anyway)
        if (not int8 and Bh <= 256
                and Bh + Bl * CS <= _HL_CROSSOVER * full_codes
                and hl_vmem <= _HL_VMEM):
            kernel, vmem = "wave_hl", hl_vmem
    return WaveKernelPlan(kernel, Fp, Fg, groups, split, vmem,
                          8 * unit <= _SCOPED_VMEM, onehot_rows,
                          class_groups)


def _hl_pack(num_features: int, Bh: int) -> int:
    """Features `_wave_kernel_hl` packs into the M of one main dot."""
    return next((p for p in (4, 2, 1)
                 if num_features % p == 0 and p * Bh <= 256), 1)


def mxu_flop_per_row(plan: WaveKernelPlan, num_features: int,
                     num_slots: int, C: int = 2) -> int:
    """The MXU FLOP A ROW of the table that a call of `plan.kernel` asks:
    its dots as they are written, tile-padded as the v5e's 128 x 128 MXU
    runs them.  Shapes and `plan` (which holds the sorted class multiset
    and nothing of the column order) in; `num_slots` is what the kernel
    computes — the padded bound for `wave`, the true slots for `wave_hl`.

    THE PADDING RULE, written here and nowhere else: a dot
    `[M, K] x [K, N]` costs `2 x M8 x K128 x N128` — the output columns N
    and the contracted K go up to whole 128-wide tiles of the array, the
    streamed rows M to the 8-sublane granule.  A dot that contracts over
    the row tile (K = Rt, whole tiles) so costs `2 x M8 x N128` a row; one
    whose M is the row tile costs `2 x K128 x N128` a row.

    * `wave` (`_wave_kernel`): a slot group's `[M, Rt] x [C*NLg, Rt]` dot
      S times, M the one-hot rows the call really builds
      (`plan.onehot_rows`: classed or not, feature- and bin-padded, over
      all its blocks), and the `[8, Rt] x [NLg, Rt]` count dot S times in
      each of its `pallas_call`s.  At `[28 x 256]` and up to 64 slots:
      2 x 7,168 x 128 = 1,835,008 for the histogram (24.5 ms at
      2,625,536 rows and 197e12 FLOP/s) + 2,048 for the counts; at 128
      slots N is two tiles and the histogram's part doubles.
    * `wave_hl` (`_wave_kernel_hl`): the F / P main dots
      `[P*Bh, Rt] x [Rt, P*Bl*CS]`, the expander
      `d = [Rt, F+1] x [F+1, Wd]`, `wt = [CS, Rt]^T x [CS, Wd]` and the
      `[8, Rt] x [max(S, 8), Rt]` count dot.
    * `rows` (`_hist_pallas_kernel`): `[Fp*Bp, Rt] x [Rt, C]` over its
      feature groups."""
    tiles = _round_up
    rows = tiles(plan.onehot_rows, 8)
    if plan.kernel == "rows":
        return 2 * rows * tiles(C, 128)
    if plan.kernel == "wave":
        NLp = wave_slot_pad(num_slots)
        NLg = min(NLp, 128)
        S = NLp // NLg
        calls = max(len(plan.class_groups), 1)
        return S * (2 * rows * tiles(C * NLg, 128)
                    + calls * 2 * 8 * tiles(NLg, 128))
    Bh, Bl = plan.hl_split
    CS = C * num_slots
    P = _hl_pack(num_features, Bh)
    Wd = tiles(num_features * Bl * CS, 128)
    return ((num_features // P) * 2 * tiles(P * Bh, 8)
            * tiles(P * Bl * CS, 128)
            + 2 * tiles(num_features + 1, 128) * Wd
            + 2 * tiles(CS, 128) * Wd
            + 2 * 8 * tiles(max(num_slots, 8), 128))


def _kernel_metadata(plan: WaveKernelPlan, num_features: int,
                     num_slots: int, C: int) -> dict:
    """What a `pallas_call` says of itself in its custom-call's
    `kernel_metadata`: the MXU FLOP a row it asks.  A frontend attribute
    is part of the HLO and so of the compile cache's key, which ignores
    the `op_name` labels: a program that differed from another in
    `mxu_call_scope`'s labels alone would be handed that one's
    executable, and its trace would carry no label (ROADMAP correction
    (k)).  Not a `cost_estimate`: with one the compiler scheduled the
    Higgs cells' program 0.1% slower (PERF.md section 6, PR 39)."""
    return {"mxu_flop_per_row":
            str(mxu_flop_per_row(plan, num_features, num_slots, C))}


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "num_slots", "out_slots",
                                    "row_tile"))
def build_histogram_wave_hl(binned_fm: jnp.ndarray, binned_rm: jnp.ndarray,
                            slot: jnp.ndarray, gh: jnp.ndarray, *,
                            max_bin: int, num_slots: int, out_slots: int,
                            row_tile: int = 512):
    """Decomposed-kernel variant of `build_histogram_wave` for waves with
    few computed slots (see `_wave_kernel_hl`).  Same operands —
    binned_fm [F, n], slot [n] int32, gh [C+1, n] with the count mask as
    its last row — plus binned_rm [n, F], the row-major copy of the bins
    for the kernel's lo side.  `num_slots` is the TRUE computed-slot
    bound; the output is zero-padded to `out_slots` rows so callers keep
    the padded-Kb contract.  Returns
    (hist [out_slots, F, B, C] float32, counts [out_slots] float32)."""
    F, n = binned_fm.shape
    C = gh.shape[0] - 1
    S = num_slots
    plan = plan_wave_kernel(F, max_bin, out_slots, S, C=C,
                            row_tile=row_tile)._replace(kernel="wave_hl")
    Bh, Bl = plan.hl_split
    P = _hl_pack(F, Bh)
    if n % row_tile != 0:
        raise ValueError(f"n {n} not a multiple of row_tile {row_tile}")
    with global_timer.device_scope("Tree::hist_operands"):
        slot_row = slot.reshape(1, n)
    _count_traced_call(1)       # no feature grouping: blocks are (F, Rt)
    out, cnt = pl.pallas_call(
        _wave_kernel_hl(C, F, Bh, Bl, S, P),
        grid=(n // row_tile,),
        in_specs=[
            pl.BlockSpec((F, row_tile), lambda i: (0, i)),
            pl.BlockSpec((row_tile, F), lambda i: (i, 0)),
            pl.BlockSpec((1, row_tile), lambda i: (0, i)),
            pl.BlockSpec((C + 1, row_tile), lambda i: (0, i))],
        out_specs=[
            pl.BlockSpec((F, Bh, Bl * C * S), lambda i: (0, 0, 0)),
            pl.BlockSpec((8, S), lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((F, Bh, Bl * C * S), jnp.float32),
            jax.ShapeDtypeStruct((8, S), jnp.float32)],
        metadata=_kernel_metadata(plan, F, S, C),
        name="build_histogram_wave_hl",
    )(binned_fm, binned_rm, slot_row, gh)
    # [F, Bh, (bl, c, s)] -> [S, F, B, C], zero-padded to out_slots
    h = out.reshape(F, Bh, Bl, C, S).transpose(4, 0, 1, 2, 3)
    h = h.reshape(S, F, Bh * Bl, C)[:, :, :max_bin, :]
    pad = out_slots - S
    if pad > 0:
        h = jnp.concatenate(
            [h, jnp.zeros((pad,) + h.shape[1:], h.dtype)], axis=0)
        cntv = jnp.concatenate([cnt[0], jnp.zeros(pad, cnt.dtype)])
    else:
        cntv = cnt[0]
    return h, cntv


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "num_slots", "row_tile",
                                    "quant_bins", "hist_classes"))
def build_histogram_wave(binned_fm: jnp.ndarray, slot: jnp.ndarray,
                         gh: jnp.ndarray, *, max_bin: int, num_slots: int,
                         row_tile: int = 512, quant_bins: int = 0,
                         quant_scales: jnp.ndarray = None,
                         hist_classes: tuple = ()):
    """Histograms for all leaf slots in one fused pass over the rows.

    Grid = (bin groups, feature groups, row tiles); each cell builds the
    bin one-hot ONCE and loops the slot groups inside, one MXU dot per
    slot group whose output columns are (channel, slot) pairs.  The leaf-
    slot axis fills the MXU's 128-wide output dimension — a plain per-leaf
    histogram dot has C=2 output columns and idles most of the systolic
    array.  The one-hot (F*B*n per wave) is built exactly once
    regardless of slot count and hides under the dots, which are what a
    call costs (MXU-bound at every cell's shape: PR 39's probe,
    `tools/hist_roof_probe.py`, PERF.md section 6).
    Exact per-slot row counts ride along as a second output — the
    mask column against the slot one-hot.  (TPU replacement for the CUDA
    per-leaf shared-memory kernels, cuda_histogram_constructor.cu:18.)

    Args:
      binned_fm: [F, n] feature-major bin codes.
      slot: [n] int32 leaf slot per row.
      gh: [C+1, n] per-row accumulands (gradient, hessian, ..., row-mask),
        rows on the minor axis — the layout the [n] vectors are born in
        and the kernel's (C+1, row_tile) blocks read, so no operand is
        padded to 128 lanes in HBM or VMEM; the LAST row is the count
        mask (zeros for excluded rows).
      max_bin: B (static).  num_slots: NL leaf slots (static).
      quant_bins: when > 0, gh's channels carry grid-snapped quantized
        values (ref: gradient_discretizer.cpp DiscretizeGradients): the
        kernel recovers the int8 grid indices and accumulates EXACT int32
        histograms through the MXU's 2x int8 path, dequantizing on the
        way out — the TPU analogue of the reference's int16/int32
        quantized histograms (dense_bin.hpp:174 ConstructHistogramIntInner).
      hist_classes: `hist_classes_of`'s (codes, columns) multiset where
        `binned_fm`'s columns are in CLASS ORDER (`hist_order`), each
        column's codes below its class's: with several classes each
        column's one-hot is built at its class's codes and the result's
        columns are in class order too (`wave_histograms` brings them
        back).  Not for int8 operands.

    Returns: (hist [NL, F, B, C] float32, counts [NL] float32).
    """
    F, n = binned_fm.shape
    C = gh.shape[0] - 1
    use_int8 = quant_scales is not None
    if use_int8:
        assert quant_bins <= 126, "int8 grid bound"
        # gh's channels carry k * scale for int grid indices k; divide by
        # the TRUE scales (threaded from DiscretizeGradients) so the
        # round() recovers the exact ints
        with global_timer.device_scope("Tree::hist_operands"):
            gh = jnp.concatenate(
                [jnp.round(gh[:C] / quant_scales[:, None])
                 .astype(jnp.int32),
                 (gh[C:] > 0).astype(jnp.int32)], axis=0)
    NLp = wave_slot_pad(num_slots)
    NLg = min(NLp, 128)
    # one bin group when it fits: rows are then streamed once per wave
    Bp, Bg = _bin_pad(max_bin)
    if n % row_tile != 0:
        raise ValueError(f"n {n} not a multiple of row_tile {row_tile}")
    S = NLp // NLg
    plan = plan_wave_kernel(F, max_bin, num_slots, C=C, row_tile=row_tile,
                            int8=use_int8, hist_classes=hist_classes)
    Fp, Fg = plan.feature_pad, plan.feature_group
    if Fp != F:
        with global_timer.device_scope("Tree::hist_operands"):
            binned_fm = jnp.pad(binned_fm, ((0, Fp - F), (0, 0)))
    acc_t = jnp.int32 if use_int8 else jnp.float32
    with global_timer.device_scope("Tree::hist_operands"):
        slot_row = slot.reshape(1, n)
    _count_traced_call(plan.groups)
    row_specs = [pl.BlockSpec((1, row_tile), lambda bg, g, i: (0, i)),
                 pl.BlockSpec((C + 1, row_tile), lambda bg, g, i: (0, i))]
    cnt_spec = pl.BlockSpec((8, NLp), lambda bg, g, i: (0, 0))
    cnt_shape = jax.ShapeDtypeStruct((8, NLp), acc_t)
    if plan.class_groups:
        # a `pallas_call` a group, each ONE block over its run of the
        # class-ordered columns (the one group of every cell's 1-128
        # slot calls is the operand itself); every class's rows are
        # cut or zero-padded to B on the way out
        f0, parts = 0, []
        for group in plan.class_groups:
            Fk = sum(k for _, k in group)
            Mk = sum(c * k for c, k in group)
            cols = binned_fm
            if Fk != F:
                with global_timer.device_scope("Tree::hist_operands"):
                    cols = binned_fm[f0:f0 + Fk]
            out, cnt_k = pl.pallas_call(
                _wave_kernel(C, Fk, Bg, NLg, group),
                grid=(1, 1, n // row_tile),
                in_specs=[pl.BlockSpec((Fk, row_tile),
                                       lambda bg, g, i: (0, i))] + row_specs,
                out_specs=[pl.BlockSpec((Mk, S * C * NLg),
                                        lambda bg, g, i: (0, 0)), cnt_spec],
                out_shape=[jax.ShapeDtypeStruct((Mk, S * C * NLg), acc_t),
                           cnt_shape],
                metadata=_kernel_metadata(
                    plan._replace(onehot_rows=Mk, class_groups=(group,)),
                    Fk, num_slots, C),
                name="build_histogram_wave",
            )(cols, slot_row, gh)
            if f0 == 0:
                cnt = cnt_k
            r0 = 0
            for codes, k in group:
                part = out[r0:r0 + codes * k].reshape(k, codes, -1)
                parts.append(
                    part[:, :max_bin] if codes >= max_bin else
                    jnp.pad(part, ((0, 0), (0, max_bin - codes), (0, 0))))
                r0 += codes * k
            f0 += Fk
        out = jnp.concatenate(parts, axis=0)        # [F, B, (s, c, lg)]
    else:
        out, cnt = pl.pallas_call(
            _wave_kernel(C, Fg, Bg, NLg),
            grid=(Bp // Bg, Fp // Fg, n // row_tile),
            in_specs=[pl.BlockSpec((Fg, row_tile),
                                   lambda bg, g, i: (g, i))] + row_specs,
            out_specs=[
                pl.BlockSpec((Fg, Bg, S * C * NLg),
                             lambda bg, g, i: (g, bg, 0)), cnt_spec],
            out_shape=[
                jax.ShapeDtypeStruct((Fp, Bp, S * C * NLg), acc_t),
                cnt_shape],
            metadata=_kernel_metadata(plan, F, num_slots, C),
            name="build_histogram_wave",
        )(binned_fm, slot_row, gh)
    # [Fp, Bp, (s, c, lg)] -> [NL, F, B, C]
    Fo, Bo = out.shape[:2]
    out = out.reshape(Fo, Bo, S, C, NLg).transpose(2, 4, 0, 1, 3)
    hist = out.reshape(S * NLg, Fo, Bo, C)[:num_slots, :F, :max_bin, :]
    if use_int8:
        # dequantize the exact int sums back to the float grid
        hist = hist.astype(jnp.float32) * quant_scales[None, None, None, :]
        return hist, cnt[0, :num_slots].astype(jnp.float32)
    return hist, cnt[0, :num_slots]


def wave_histograms(binned_fm: jnp.ndarray, binned_rm: jnp.ndarray,
                    slot: jnp.ndarray, gh: jnp.ndarray, *, max_bin: int,
                    num_slots: int, true_slots: Optional[int] = None,
                    quant_bins: int = 0, quant_scales: jnp.ndarray = None,
                    hist_classes: tuple = (),
                    binned_classed: jnp.ndarray = None,
                    hist_inverse: jnp.ndarray = None):
    """Run the kernel `plan_wave_kernel` names for this wave (plain Python:
    the kernels are the jitted entries and carry the scopes).  Operands as
    `build_histogram_wave`'s, plus `binned_rm` [n, F] — built by the
    caller where the plan gives `wave_hl` at one slot, which every
    `wave_hl` wave of the same shape implies (both gates only close as
    the slots grow) — and the wave's `true_slots` where it knows them.
    `quant_scales` selects the full kernel's int8 operands.  Where the
    plan cuts `hist_classes` into class groups the full kernel reads
    `binned_classed`, the caller's `binned_fm[hist_order]`, and its
    histograms come back through `hist_inverse` (the order's inverse):
    every caller sees its own column order, whatever kernel ran.
    Returns (hist [num_slots, F, B, C] float32, counts [num_slots])."""
    F, C = binned_fm.shape[0], gh.shape[0] - 1
    plan = plan_wave_kernel(F, max_bin, num_slots, true_slots,
                            int8=quant_scales is not None, C=C,
                            hist_classes=hist_classes)
    # what the call asks of the MXU rides its kernel events' `op_name`
    with mxu_call_scope(plan, F, num_slots, true_slots, C):
        if plan.kernel == "wave_hl":
            return build_histogram_wave_hl(
                binned_fm, binned_rm, slot, gh, max_bin=max_bin,
                num_slots=true_slots, out_slots=num_slots)
        # Rt stays 512: 1024 is ~3% faster on small slot counts but
        # exceeds the 16 MB scoped-VMEM limit at 128 slots
        if plan.class_groups:
            hist, cnt = build_histogram_wave(
                binned_classed, slot, gh, max_bin=max_bin,
                num_slots=num_slots, hist_classes=hist_classes)
            return jnp.take(hist, hist_inverse, axis=1, mode="clip"), cnt
        return build_histogram_wave(
            binned_fm, slot, gh, max_bin=max_bin, num_slots=num_slots,
            quant_bins=quant_bins, quant_scales=quant_scales)


@functools.partial(jax.jit, static_argnames=("max_bin", "method", "row_chunk"))
def build_histogram(binned: jnp.ndarray, gh: jnp.ndarray, mask: jnp.ndarray,
                    *, max_bin: int, method: str = "segment",
                    row_chunk: int = 0) -> jnp.ndarray:
    """Masked histogram over all rows.

    Args:
      binned: [F, n] integer bin codes (n padded to a multiple of the chunk).
      gh:     [n, C] per-row values to accumulate (gradient, hessian, ...).
      mask:   [n] 0/1 leaf-membership x bagging mask (float or bool).
      max_bin: B, the padded per-feature bin count (static).
      method: "segment" (scatter-add) or "onehot" (MXU matmul).
      row_chunk: rows per scan step; 0 = auto.

    Returns: hist [F, B, C] float32.
    """
    num_features, n = binned.shape
    channels = gh.shape[-1]
    gh = gh * mask.astype(gh.dtype)[:, None]
    total = num_features * max_bin
    chunk = row_chunk or _pick_chunk(n, num_features, max_bin, method)
    if method == "segment":
        kernel = _hist_chunk_segment
    elif method == "onehot":
        kernel = _hist_chunk_onehot
    elif method == "onehot_hp":
        kernel = functools.partial(_hist_chunk_onehot,
                                   compute_dtype=jnp.float32)
    else:
        raise ValueError(f"unknown histogram method {method!r}")
    if n <= chunk:
        out = kernel(binned, gh, total, max_bin)
        return out.reshape(num_features, max_bin, channels)

    while n % chunk != 0 and chunk > 1024:
        chunk //= 2  # n is padded to a 1024 multiple; shrink to a divisor
    if n % chunk != 0:
        raise ValueError(f"num_data {n} must be padded to a multiple of {chunk}")
    num_chunks = n // chunk
    binned_chunks = binned.reshape(num_features, num_chunks, chunk).transpose(1, 0, 2)
    gh_chunks = gh.reshape(num_chunks, chunk, channels)

    def step(acc, xs):
        bc, gc = xs
        return acc + kernel(bc, gc, total, max_bin), None

    init = jnp.zeros((total, channels), dtype=jnp.float32)
    out, _ = jax.lax.scan(step, init, (binned_chunks, gh_chunks))
    return out.reshape(num_features, max_bin, channels)
