"""Which unit bounds the fused histogram kernel: a chip probe (ROADMAP
S1 (1)), `python tools/kernel_checks.py --roof`.

At each benchmark cell's kernel shape and at 1 / 16 / 64 / 128 slots it
times, a call:

  (a) `full`   the kernel as it is (`ops/histogram.py
               build_histogram_wave`, classed where the cell's is);
  (b) `dot`    the same blocks and grid with the dot fed a CONSTANT bf16
               operand of the one-hot's shape, read from a VMEM scratch
               that the first grid step fills: no compare, no select, no
               cast of the `F x B x Rt` volume — the MXU's part with the
               slot-separated channel matrix and the count dot as they
               are;
  (c) `build`  the one-hot built exactly as the kernel builds it and
               reduced on the VPU (its packed words OR-ed over the row
               tile's 128-lane pieces: one more op a vreg built, so the
               reading is an upper bound of the build) — no dot.

(b) close to (a) and (c) far below names the MXU; (c) close to (a) names
the VPU's build.  The variant kernels live here and not as a path or a
flag in `ops/histogram.py`: they copy `_wave_kernel`'s body and
`build_histogram_wave`'s blocks, which `tests/test_kernels_interpret.py
test_roof_probe_variants_*` holds to the kernel on the CPU (the `dot`
variant fed the kernel's own one-hot gives the kernel's histograms).
"""

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.ops.histogram import (_bin_pad, build_histogram_wave,
                                        hist_classes_of, mxu_flop_per_row,
                                        plan_wave_kernel, wave_slot_pad)
from tools.kernel_checks import EXPO_CODES, MSLR_CODES

ROW_TILE = 512
# The kernel's own blocks fill the compiler's 16 MiB of scoped VMEM at
# 128 slots (7,392 one-hot rows: 15.1 MB), and a variant holds one more
# buffer of the one-hot's size (the constant; the reduction's pieces):
# the variants ask for a wider limit, which moves no op of theirs.
_VMEM_LIMIT = pltpu.CompilerParams(vmem_limit_bytes=40 << 20)
# (name, the device columns' code counts, `hist_B`, padded rows): the
# kernel shapes of the six cells (`dp4` runs `b255`'s on each chip)
SHAPES = (
    ("b255 [28 x 256]", (255,) * 28, 255, 2_625_536),
    ("b63 [28 x 64]", (63,) * 28, 63, 2_625_536),
    ("eps63 [2000 x 64] in groups", (63,) * 2000, 63, 400_384),
    ("mslr63 classed (7392)", MSLR_CODES, 63, 2_271_232),
    ("expo63 classed (1200)", EXPO_CODES, 255, 11_000_832),
)
SLOTS = (1, 16, 64, 128)


def _onehot(rows, classes, Fg, Bg, Rt):
    """`_wave_kernel`'s bin one-hot [M, Rt] bf16 of a block's rows
    `[Fg, Rt]` (already offset by the bin group)."""
    bf16 = jnp.bfloat16
    if classes:
        runs, f0 = [], 0
        for codes, cols in classes:
            biota = jax.lax.broadcasted_iota(jnp.int32, (cols, codes, Rt), 1)
            run = jax.lax.slice_in_dim(rows, f0, f0 + cols)
            runs.append((run[:, None, :] == biota).astype(bf16)
                        .reshape(cols * codes, Rt))
            f0 += cols
        return jnp.concatenate(runs, axis=0)
    biota = jax.lax.broadcasted_iota(jnp.int32, (Fg, Bg, Rt), 1)
    return (rows[:, None, :] == biota).astype(bf16).reshape(Fg * Bg, Rt)


def _dot_kernel(C, NLg, M, const):
    """`_wave_kernel` with its one-hot read, not built: from the VMEM
    scratch `oh_ref` (`const`: filled once, whatever the rows hold) or
    from a fourth operand block `[M, Rt]` (the CPU test's: the kernel's
    own one-hot, so the histograms are the kernel's)."""
    def kernel(*refs):
        if const:
            slot_ref, gh_ref, out_ref, cnt_ref, oh_ref = refs
        else:
            oh_ref, slot_ref, gh_ref, out_ref, cnt_ref = refs
        first = ((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
                 & (pl.program_id(2) == 0))

        @pl.when(pl.program_id(2) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(first)
        def _init_cnt():
            cnt_ref[...] = jnp.zeros_like(cnt_ref)
            if const:       # a seventh of it ones, in 128-lane pieces
                i = (jax.lax.broadcasted_iota(jnp.int32, (M, 128), 0)
                     + jax.lax.broadcasted_iota(jnp.int32, (M, 128), 1))
                for k in range(oh_ref.shape[1] // 128):
                    oh_ref[:, k * 128:(k + 1) * 128] = (
                        (i + k) % 7 == 0).astype(jnp.bfloat16)
        slot = slot_ref[...]
        Rt = slot.shape[1]
        lanes = (((1,), (1,)), ((), ()))
        S = out_ref.shape[-1] // (C * NLg)
        for s in range(S):
            soh = (slot - s * NLg ==
                   jax.lax.broadcasted_iota(jnp.int32, (NLg, Rt), 0))
            sc = jnp.concatenate(
                [jnp.where(soh, gh_ref[c:c + 1, :], 0)
                 for c in range(C)], axis=0).astype(jnp.bfloat16)
            acc = jax.lax.dot_general(oh_ref[...], sc, lanes,
                                      preferred_element_type=jnp.float32)
            w = C * NLg
            out_ref[:, s * w:(s + 1) * w] += acc

            @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
            def _count():
                mask8 = jnp.broadcast_to(gh_ref[C:C + 1, :],
                                         (8, Rt)).astype(jnp.bfloat16)
                cacc = jax.lax.dot_general(
                    mask8, jnp.where(soh, 1, 0).astype(jnp.bfloat16), lanes,
                    preferred_element_type=jnp.float32)
                cnt_ref[:, s * NLg:(s + 1) * NLg] += cacc
    return kernel


def _build_kernel(Fg, Bg, classes):
    """The one-hot built as `_wave_kernel` builds it and reduced on the
    VPU into `[M / 2, 128]` words: no dot.  The reduction is the
    cheapest that still reads every vreg built — the bf16 one-hot seen
    as packed 32-bit words (two rows a word), OR-ed over the row tile's
    128-lane pieces, one VPU op a built vreg.  (A bf16 `maximum` over
    the same pieces cost more than the whole kernel, 31.5 against 25.4
    ms at `[28 x 256]`: the v5e's VPU has no bf16 lanes and widens every
    piece; PERF.md section 6, PR 39.)"""
    def kernel(rows_ref, out_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
        rows = rows_ref[...].astype(jnp.int32) - pl.program_id(0) * Bg
        Rt = rows.shape[1]
        words = pltpu.bitcast(_onehot(rows, classes, Fg, Bg, Rt), jnp.int32)
        acc = words[:, :128]
        for k in range(1, Rt // 128):
            acc = acc | words[:, k * 128:(k + 1) * 128]
        out_ref[...] = out_ref[...] | acc
    return kernel


def _blocks(F, max_bin, num_slots, hist_classes):
    """The grid and the one-hot blocks `build_histogram_wave` runs: per
    `pallas_call` (a class group, or the one call) its (columns' start,
    columns, feature group Fg, M of a block, grid head (bin groups,
    feature groups), classes)."""
    plan = plan_wave_kernel(F, max_bin, num_slots, hist_classes=hist_classes)
    Bp, Bg = _bin_pad(max_bin)
    if plan.class_groups:
        calls, f0 = [], 0
        for group in plan.class_groups:
            Fk = sum(k for _, k in group)
            calls.append((f0, Fk, Fk, sum(c * k for c, k in group),
                          (1, 1), group))
            f0 += Fk
        return plan, calls
    Fp, Fg = plan.feature_pad, plan.feature_group
    return plan, [(0, Fp, Fg, Fg * Bg, (Bp // Bg, Fp // Fg), ())]


def variant(mode, binned_fm, slot, gh, *, max_bin, num_slots,
            hist_classes=(), onehot=None):
    """One call of variant `mode` ("dot" | "build") on
    `build_histogram_wave`'s operands, in its blocks and grid; returns
    each `pallas_call`'s raw outputs.  `onehot` [M, n] bf16 (one-group
    calls only) takes the constant's place in "dot"."""
    F, n = binned_fm.shape
    C = gh.shape[0] - 1
    Rt = ROW_TILE
    NLp = wave_slot_pad(num_slots)
    NLg = min(NLp, 128)
    S = NLp // NLg
    plan, calls = _blocks(F, max_bin, num_slots, hist_classes)
    if plan.feature_pad != F:
        binned_fm = jnp.pad(binned_fm, ((0, plan.feature_pad - F), (0, 0)))
    slot_row = slot.reshape(1, n)
    Bg = _bin_pad(max_bin)[1]
    outs = []
    for f0, Fk, Fg, M, (nb, ng), classes in calls:
        grid = (nb, ng, n // Rt)
        if mode == "build":
            outs.append(pl.pallas_call(
                _build_kernel(Fg, Bg, classes), grid=grid,
                in_specs=[pl.BlockSpec((Fg, Rt),
                                       lambda bg, g, i: (g, i))],
                out_specs=pl.BlockSpec(
                    (M // 2, 128), lambda bg, g, i, ng=ng: (bg * ng + g, 0)),
                out_shape=jax.ShapeDtypeStruct((nb * ng * M // 2, 128),
                                               jnp.int32),
                compiler_params=_VMEM_LIMIT,
                name="roof_probe_build",
            )(binned_fm[f0:f0 + Fk]))
            continue
        row_specs = [pl.BlockSpec((1, Rt), lambda bg, g, i: (0, i)),
                     pl.BlockSpec((C + 1, Rt), lambda bg, g, i: (0, i))]
        const = onehot is None
        outs.append(pl.pallas_call(
            _dot_kernel(C, NLg, M, const), grid=grid,
            in_specs=([] if const else [
                pl.BlockSpec((M, Rt), lambda bg, g, i: (0, i))]) + row_specs,
            out_specs=[
                pl.BlockSpec((M, S * C * NLg),
                             lambda bg, g, i, ng=ng: (bg * ng + g, 0)),
                pl.BlockSpec((8, NLp), lambda bg, g, i: (0, 0))],
            out_shape=[
                jax.ShapeDtypeStruct((nb * ng * M, S * C * NLg),
                                     jnp.float32),
                jax.ShapeDtypeStruct((8, NLp), jnp.float32)],
            scratch_shapes=([pltpu.VMEM((M, Rt), jnp.bfloat16)]
                            if const else []),
            compiler_params=_VMEM_LIMIT,
            name="roof_probe_dot",
        )(*(() if const else (onehot,)), slot_row, gh))
    return outs


def _operands(codes, n, slots):
    """Bins that draw every code of each column, slots over `slots`, unit
    gradients: made on the device (the timings do not read the values)."""
    i32 = jnp.int32
    codes = jnp.asarray(codes, i32)[:, None]
    row = jnp.arange(n, dtype=i32)[None, :]
    col = jnp.arange(codes.shape[0], dtype=i32)[:, None]
    binned = ((row * 7 + col * 13) % codes).astype(jnp.uint8)
    slot = (jnp.arange(n, dtype=i32) * 5) % slots
    gh = jnp.stack([jnp.full(n, 0.5, jnp.float32),
                    jnp.full(n, 0.25, jnp.float32),
                    jnp.ones(n, jnp.float32)])
    return binned, slot, gh


def _ms_a_call(fn, *args, calls=6):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def time_roof(shapes=SHAPES, slot_counts=SLOTS, modes=("full", "dot", "build"),
              peak=197e12, out=sys.stderr):
    """Prints the times of `modes` a shape and slot count, and the
    padded-shape MXU time `mxu_flop_per_row` x rows at `peak` beside
    them."""
    for name, codes, max_bin, n in shapes:
        classes, order = hist_classes_of(codes)
        classes = classes if len(classes) > 1 else ()
        F = len(codes)
        for slots in slot_counts:
            operands = _operands(
                np.asarray(codes)[order] if classes else codes, n, slots)
            kw = dict(max_bin=max_bin, num_slots=slots, hist_classes=classes)
            plan = plan_wave_kernel(F, max_bin, slots, hist_classes=classes)
            mxu_ms = 1e3 * mxu_flop_per_row(plan, F, slots) * n / peak
            ms = {}
            for mode in modes:
                fn = ((lambda b, s, g: build_histogram_wave(b, s, g, **kw))
                      if mode == "full" else
                      (lambda b, s, g, mode=mode: variant(mode, b, s, g,
                                                          **kw)))
                ms[mode] = _ms_a_call(jax.jit(fn), *operands)
            print(f"roof {name} n={n} slots={slots}: "
                  + ", ".join(f"{m} {ms[m]:.2f} ms" for m in modes)
                  + f"; padded-shape MXU time {mxu_ms:.2f} (one-hot rows "
                  f"{plan.onehot_rows}, groups {plan.groups})",
                  file=out, flush=True)
            del operands
