"""On-chip Pallas kernel correctness gate, run by chip_smoke.py and
bench.py on the chip.

The kernel unit tests (tests/test_wave_hl.py, tests/test_wave_int8.py)
skip off-TPU, so without this gate a Mosaic/XLA regression in the
histogram kernels would surface only as an unexplained AUC delta.
chip_smoke.py and bench.py call run_checks() on the real chip and fail
on anything but "ok" — the TPU counterpart of the reference's dual-gate
CI (.ci scripts running both CPU and CUDA test legs).  A check that
raises is a named failure in the verdict, with its traceback on stderr.

Checks (small shapes, seconds of chip time):
  1. fused wave kernel == XLA one-hot fallback (fp32, exact histograms)
  2. decomposed hi/lo kernel == full kernel at few computed slots
  3. int8 quantized kernel: exact int32 accumulation of grid-snapped
     gradients (dequantized result equals the fp32 kernel on grid values)
  4. single-leaf Pallas histogram == segment lowering
  5. the score update's one-hot leaf-value lookup == `jnp.take`, bit for
     bit, at 2 to 1,000 leaves with -0.0, a denormal, the largest float,
     both infinities and a NaN among the values and ids outside the table

  6. the wave engine's gain scan (`ops/split.py find_best_split_dense`)
     == a bin-by-bin float32 scan on the host, at the benchmark's own
     `[256, 2000, 63]` and `[256, 28, 255]`, all three missing types and
     runs of empty bins among the features: feature, threshold,
     `default_left` and counts exactly, sums to float32; and at
     `[256, 2000, 63]` the program the cells run, no missing type in the
     data and `has_missing=False` (the REVERSE scan alone)

  7. the ranking gradient program (`ranking.py`: a query's rows read and
     added back as whole 128-wide rows, payloads riding the sorts) == the
     index-map formulation it replaced (`rank_index_map_reference.py`:
     `jnp.take`, `argsort` + `take_along_axis`, `.at[].add`), bit for
     bit, on 512 queries of 1 to 1,251 documents with tied scores

  8. the EFB decode of a bundle-column histogram
     (`learner/grow.py bundle_hist_to_features`, under `vmap` as the wave
     engine's scan calls it) == NumPy's slice of each member's code range
     and subtraction from the leaf totals, bit for bit, on the one-hot
     cell's plan (12 columns of up to 255 codes, 504 features at 63
     bins); histogram entries are whole numbers, so no order of
     summation can move a bit

  9. the fused wave kernel with each column's one-hot at its class's
     codes (`hist_classes`, the bins in class order, the result brought
     back by the order's inverse) == the same kernel with every column
     at the largest's codes, bit for bit and counts too, on the one-hot
     cell's twelve code counts and the ranking cell's three classes in a
     shuffled column order (codes that reach their class's bound among
     them), at 8 and 128 slots

  10. the recolour (`ops/recolour.py`): the row-tiled kernel == its XLA
     form == the rule written out in NumPy, `leaf_id` and `kslot` bit for
     bit, at 8 and 256 leaves: plain columns with all three missing
     types, bundle columns (codes inside a member's range, outside it,
     the zero bin), categorical bitsets, leaves that do not split, and
     more columns than one block holds

`run_wide_checks()` (`python tools/kernel_checks.py --wide`; a minute of
chip time, so not part of `run_checks`) holds the fused wave kernel to a
plain float32 reference at the widest benchmark cell's own shape,
400,384 x 2,000 at 63 bins and 1 / 8 / 128 slots: the feature-grouped
path, which 28 features never take.

`time_score_lookup()` (`python tools/kernel_checks.py --score-lookup`;
two minutes) times the score update with each form of the lookup at
2,625,536 rows and 255 to 16,383 leaves, and checks the bits there too:
the reading behind `boosting/leaf_lookup.py ONE_HOT_MAX_LEAVES`.

`time_rank_gradients()` (`python tools/kernel_checks.py --rank-gradients`;
two minutes) runs check 7's two programs on the ranking cell's own plan
(18,919 queries, 2,270,296 rows), compares every bit and times both.

`time_classed_kernel()` (`python tools/kernel_checks.py --classed`; two
minutes) runs check 9's two calls at the one-hot cell's and the ranking
cell's own shapes (`[12, 11,000,832]` at 255 codes, `[137, 2,271,232]`
at 63) and 8 / 64 / 128 slots: bits compared, ms a call of each.

`python tools/kernel_checks.py --roof` (`tools/hist_roof_probe.py`; four
minutes) names the unit that bounds the fused wave kernel: at each
cell's kernel shape and 1 / 16 / 64 / 128 slots, ms a call of the kernel
as it is, of its dot fed a constant one-hot, and of its one-hot built
without a dot (ROADMAP S1 (1); PERF.md section 6, PR 39).

`time_recolour()` (`python tools/kernel_checks.py --recolour`; two
minutes) runs check 10's two forms at each cell's own shape (28 / 137 /
2,000 plain columns, 12 bundle columns; 8 and 256 leaves): rows whose
`leaf_id` or `kslot` differ between the kernel, the XLA form and NumPy,
and ms a call of each form.

`time_hl_crossover()` (`python tools/kernel_checks.py --hl`; a few
minutes) times the two kernels a ladder wave can take — the full one,
classed where the cell is, and the decomposed one — at each cell's
kernel shape and 1 / 2 / 4 / 8 true slots, compares their histograms
bit for bit on operands whose sums are exact, and prints the winner
beside the FLOP share the plan holds against `_HL_CROSSOVER`.
"""
import os
import sys
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mk(n=2048, F=8, B=64, slots=8, seed=0):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    import ml_dtypes
    binned = rng.randint(0, B, size=(F, n)).astype(np.uint8)
    slot = rng.randint(0, slots, size=n).astype(np.int32)
    grad = rng.randn(n).astype(np.float32)
    hess = np.abs(rng.rand(n).astype(np.float32)) + 0.5
    mask = (rng.rand(n) < 0.9).astype(np.float32)
    gh = np.stack([grad * mask, hess * mask, mask], 0)    # [C+1, n]
    # the kernels' MXU operands are bf16 (single-precision histograms,
    # like the reference GPU learner): snap inputs to the bf16 grid so
    # host fp64 ground truth and on-chip fp32 accumulation agree exactly
    gh = gh.astype(ml_dtypes.bfloat16).astype(np.float32)
    return (jnp.asarray(binned), jnp.asarray(slot), jnp.asarray(gh),
            binned, slot, gh)


def _host_hist(binned, slot, gh, B, slots):
    """NumPy ground truth [slots, F, B, C] from gh [C+1, n]."""
    F, n = binned.shape
    C = gh.shape[0] - 1
    out = np.zeros((slots, F, B, C), np.float64)
    cnt = np.zeros(slots, np.float64)
    for r in range(n):
        s = slot[r]
        if s >= slots:
            continue
        for f in range(F):
            out[s, f, binned[f, r], :] += gh[:C, r]
        cnt[s] += gh[C, r]
    return out, cnt


def run_checks():
    """Returns "ok" or "fail:<which>"."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (build_histogram,
                                            build_histogram_rows_pallas,
                                            build_histogram_wave,
                                            build_histogram_wave_hl)
    failures = []
    B, slots = 64, 8
    binned, slot, gh, b_np, s_np, gh_np = _mk(B=B, slots=slots)
    want, want_cnt = _host_hist(b_np, s_np, gh_np, B, slots)

    # 1. fused wave kernel vs host ground truth (fp32 accumulates exactly
    #    at these magnitudes up to reduction-order ulps)
    try:
        h, cnt = build_histogram_wave(binned, slot, gh, max_bin=B,
                                      num_slots=slots)
        if not (np.allclose(np.asarray(h), want, rtol=1e-5, atol=1e-4)
                and np.allclose(np.asarray(cnt), want_cnt)):
            failures.append("wave_vs_host")
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"wave_raised({type(e).__name__})")

    # 2. decomposed hi/lo kernel vs the full kernel (few computed slots)
    try:
        few = jnp.where(slot < 2, slot, slots)   # 2 computed slots
        hf, cf = build_histogram_wave(binned, few, gh, max_bin=B,
                                      num_slots=8)
        hd, cd = build_histogram_wave_hl(binned, binned.T, few, gh,
                                         max_bin=B, num_slots=2,
                                         out_slots=8)
        if not (np.allclose(np.asarray(hf)[:2], np.asarray(hd)[:2],
                            rtol=1e-5, atol=1e-4)
                and np.allclose(np.asarray(cf)[:2], np.asarray(cd)[:2])):
            failures.append("hl_vs_full")
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"hl_raised({type(e).__name__})")

    # 3. int8 quantized kernel: grid-snapped grads accumulate EXACTLY
    try:
        qb = 16
        scales = np.array([0.11, 0.07], np.float32)
        kg = np.random.RandomState(1).randint(-qb, qb + 1, gh.shape[1])
        kh = np.random.RandomState(2).randint(0, qb + 1, gh.shape[1])
        mk = gh_np[2]
        # grid values pre-masked like the engine (grad*mask stays on grid)
        ghq = np.stack([kg * scales[0] * mk, kh * scales[1] * mk,
                        mk], 0).astype(np.float32)
        hq, cq = build_histogram_wave(
            binned, slot, jnp.asarray(ghq), max_bin=B, num_slots=slots,
            quant_bins=qb, quant_scales=jnp.asarray(scales))
        wq, wc = _host_hist(b_np, s_np, ghq, B, slots)
        # int32 accumulation then dequant: exact up to one float32 scale
        if not np.allclose(np.asarray(hq), wq, rtol=1e-6, atol=1e-5):
            failures.append("int8_exactness")
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"int8_raised({type(e).__name__})")

    # 4. single-leaf row-major Pallas histogram vs segment lowering
    try:
        rows = jnp.asarray(np.ascontiguousarray(np.asarray(binned).T))
        mask = gh[2]
        hp = build_histogram_rows_pallas(rows, gh[:2].T, mask, max_bin=B)
        hs = build_histogram(binned, gh[:2].T, mask, max_bin=B,
                             method="segment")
        if not np.allclose(np.asarray(hp), np.asarray(hs),
                           rtol=1e-5, atol=1e-4):
            failures.append("rows_pallas_vs_segment")
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"rows_raised({type(e).__name__})")

    # 5. the score update's one-hot lookup vs the gather, bit for bit
    try:
        for L in (2, 31, 255, 1000):
            if not _lookup_bits_equal(65536, L):
                failures.append(f"score_lookup_bits_{L}")
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"score_lookup_raised({type(e).__name__})")

    # 6. the dense gain scan vs a sequential host scan, at the cells' shapes
    try:
        for N, F, B, has_missing in ((256, 2000, 63, True),
                                     (256, 28, 255, True),
                                     (256, 2000, 63, False)):
            failures.extend(_scan_mismatches(N, F, B, has_missing))
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"split_scan_raised({type(e).__name__})")

    # 7. the ranking gradients vs the index-map formulation, bit for bit
    try:
        differ = _rank_gradient_bits(*_rank_gradient_case(
            _rank_lengths(512, seed=35)))["differ"]
        failures.extend(f"rank_gradients_{name}_differ_in_{k}"
                        for name, k in differ.items() if k)
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"rank_gradients_raised({type(e).__name__})")

    # 8. the EFB decode vs NumPy's slice-and-subtract, bit for bit
    try:
        failures.extend(_efb_decode_mismatches())
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"efb_decode_raised({type(e).__name__})")

    # 9. the classed wave kernel vs the unclassed one, bit for bit
    try:
        failures.extend(_classed_mismatches())
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"classed_raised({type(e).__name__})")

    # 10. the recolour: kernel, XLA form and the host's rule, bit for bit
    try:
        failures.extend(_recolour_mismatches())
    except Exception as e:    # noqa: BLE001 - named in the verdict
        traceback.print_exc()
        failures.append(f"recolour_raised({type(e).__name__})")

    return "ok" if not failures else "fail:" + ",".join(failures)


# the one-hot cell's bundle columns: codes a column, and the bins of each
# of its members (one 63-bin numeric column alone; 2-bin one-hot members)
EFB_COLUMNS = ((63, (63,)), (63, (63,)), (45, (2,) * 22), (15, (2,) * 7),
               (25, (2,) * 12), (255, (2,) * 127), (255, (2,) * 127),
               (63, (2,) * 31), (195, (2,) * 97), (115, (2,) * 57),
               (43, (2,) * 21), (2, (2,)))


def _efb_plan(columns=EFB_COLUMNS):
    """(group, offset, zero_bin, in_bundle, num_bin) [F] as `io/bundle.py`
    lays a plan out: a member's codes start at its offset (1 for the
    first: code 0 is "every member at its default"), a column's only
    member keeps its own bins (offset 0)."""
    group, offset, in_bundle, num_bin = [], [], [], []
    for gi, (_, members) in enumerate(columns):
        off = 1
        for nb in members:
            group.append(gi)
            offset.append(off if len(members) > 1 else 0)
            in_bundle.append(len(members) > 1)
            num_bin.append(nb)
            off += nb
    F = len(group)
    return (np.array(group, np.int32), np.array(offset, np.int32),
            np.zeros(F, np.int32), np.array(in_bundle, bool),
            np.array(num_bin, np.int32))


def _efb_decode_host(hist_g, sum_g, sum_h, plan, B):
    """One leaf's [G, hist_B, 2] -> [F, B, 2], a feature at a time."""
    group, offset, zero_bin, in_bundle, num_bin = plan
    out = np.zeros((len(group), B, 2), np.float32)
    total = np.array([sum_g, sum_h], np.float32)
    for f in range(len(group)):
        nb = int(num_bin[f])
        out[f, :nb] = hist_g[group[f], offset[f]:offset[f] + nb]
        if in_bundle[f]:
            rest = np.delete(out[f], zero_bin[f], axis=0).sum(
                0, dtype=np.float32)
            out[f, zero_bin[f]] = total - rest
    return out


def _efb_decode_mismatches(leaves=8, B=63, seed=36):
    import jax.numpy as jnp
    from lightgbm_tpu.learner import FeatureMeta
    from lightgbm_tpu.learner.grow import bundle_hist_to_features
    plan = _efb_plan()
    group, offset, zero_bin, in_bundle, num_bin = plan
    hist_B = max(c for c, _ in EFB_COLUMNS)
    rs = np.random.RandomState(seed)
    hist = rs.randint(-999, 1000, (leaves, len(EFB_COLUMNS), hist_B, 2)
                      ).astype(np.float32)
    sums = rs.randint(-99999, 100000, (2, leaves)).astype(np.float32)
    zeros = jnp.zeros(len(group), jnp.int32)
    meta = FeatureMeta(
        num_bin=jnp.asarray(num_bin), missing_type=zeros, default_bin=zeros,
        penalty=jnp.ones(len(group), jnp.float32),
        group=jnp.asarray(group), offset=jnp.asarray(offset),
        zero_bin=jnp.asarray(zero_bin), in_bundle=jnp.asarray(in_bundle))
    got = np.asarray(bundle_hist_to_features(
        jnp.asarray(hist), jnp.asarray(sums[0]), jnp.asarray(sums[1]), meta,
        B, hist_B, True))
    want = np.stack([_efb_decode_host(hist[i], sums[0, i], sums[1, i], plan,
                                      B) for i in range(leaves)])
    # a bin outside a member's range is the gathered entry times 0.0,
    # which keeps the entry's sign: -0.0 + 0.0 is +0.0
    differ = int(np.sum((got + np.float32(0)).view(np.uint32)
                        != want.view(np.uint32)))
    return [f"efb_decode_differs_in_{differ}"] if differ else []


# the device columns of the one-hot and the ranking cell by code count
# (the bundle plan's twelve; 45 of 137 columns integer-valued), and check
# 9's: the twelve, then the ranking cell's classes at their bounds (16 /
# 32 codes) and under them
EXPO_CODES = tuple(c for c, _ in EFB_COLUMNS)
MSLR_CODES = (63,) * 92 + (16,) * 24 + (32,) * 7 + (63,) * 14
CLASSED_CODES = EXPO_CODES + (16, 32, 4, 8, 63, 16)


def _classed_operands(codes, n, seed=38, grid=0.0):
    """(binned [F, n] with column f drawing all of `codes[f]` codes, its
    columns shuffled; gh [3, n]; the shuffled codes).  `grid` > 0 puts
    gradients and hessians on multiples of it (every float32 sum exact in
    any order: what the CPU's interpreter needs, whose dot may block its
    adds by the operand's height); 0 leaves them on the bf16 grid, random
    — what the chip is held to."""
    import ml_dtypes
    rng = np.random.RandomState(seed)
    codes = np.asarray(codes)[rng.permutation(len(codes))]
    binned = np.stack([rng.randint(0, c, n) for c in codes]).astype(np.uint8)
    binned[:, 0] = codes - 1            # every column reaches its last code
    mask = (rng.rand(n) < 0.9).astype(np.float32)
    if grid:
        g = rng.randint(-16, 17, n) * grid
        h = rng.randint(1, 17, n) * grid
    else:
        g, h = rng.randn(n), rng.rand(n) * 0.25
    gh = np.stack([g * mask, h * mask, mask]).astype(np.float32)
    return (binned, gh.astype(ml_dtypes.bfloat16).astype(np.float32), codes)


def _classed_calls(binned, codes, max_bin):
    """(classed, unclassed): each `(slot, gh, num_slots, **quant) ->
    (hist, counts)` through `wave_histograms`, the classed one as the
    wave engine hands it over (`learner/wave.py`)."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (class_ordered, hist_classes_of,
                                            wave_histograms)
    classes, order = hist_classes_of(codes)
    assert len(classes) > 1
    binned = jnp.asarray(binned)
    handed = dict(hist_classes=classes,
                  binned_classed=class_ordered(binned, jnp.asarray(order)),
                  hist_inverse=jnp.asarray(np.argsort(order), jnp.int32))

    def call(extra):
        return lambda slot, gh, num_slots, **quant: wave_histograms(
            binned, None, slot, gh, max_bin=max_bin, num_slots=num_slots,
            **quant, **extra)
    return call(handed), call({})


def _bits_differ(a, b):
    """Entries of two float32 arrays whose bits differ."""
    return int(np.count_nonzero(np.asarray(a).view(np.uint32)
                                != np.asarray(b).view(np.uint32)))


def _classed_mismatches(codes=CLASSED_CODES, slot_counts=(8, 128), n=2048,
                        grid=0.0, quant=None):
    """Check 9.  `quant` (`quant_bins`, `quant_scales`) runs the int8
    arm, which keeps one class: the same call on both sides."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import wave_slot_pad
    binned, gh, codes = _classed_operands(codes, n, grid=grid)
    classed, unclassed = _classed_calls(binned, codes, int(codes.max()))
    rng = np.random.RandomState(39)
    failures = []
    for slots in slot_counts:
        slot = jnp.asarray(np.where(rng.rand(n) < 0.8,
                                    rng.randint(0, slots, n),
                                    wave_slot_pad(255)).astype(np.int32))
        got, want = (f(slot, jnp.asarray(gh), slots, **(quant or {}))
                     for f in (classed, unclassed))
        for name, a, b in zip(("sums", "counts"), got, want):
            differ = _bits_differ(a, b)
            if differ or not np.any(b):
                failures.append(f"classed_{name}_differ_in_{differ}"
                                f"_at_{slots}_slots")
    return failures


def time_classed_kernel(calls=10):
    """Check 9's two calls at the cells' own shapes: bits, ms a call."""
    import time
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import wave_slot_pad
    for name, codes, n in (("expo", EXPO_CODES, 11_000_832),
                           ("mslr", MSLR_CODES, 2_271_232)):
        binned, gh, codes = _classed_operands(codes, n)
        fns = _classed_calls(binned, codes, int(codes.max()))
        gh = jnp.asarray(gh)
        rng = np.random.RandomState(39)
        for slots in (8, 64, 128):
            slot = jnp.asarray(np.where(
                rng.rand(n) < 0.8, rng.randint(0, slots, n),
                wave_slot_pad(255)).astype(np.int32))
            outs, ms = [], []
            for f in fns:
                outs.append(jax.block_until_ready(f(slot, gh, slots)))
                t0 = time.perf_counter()
                for _ in range(calls):
                    out = f(slot, gh, slots)
                jax.block_until_ready(out)
                ms.append((time.perf_counter() - t0) / calls * 1e3)
            differ = [_bits_differ(a, b) for a, b in zip(*outs)]
            print(f"classed {name} [{len(codes)}, {n}] slots={slots}: "
                  f"classed {ms[0]:.2f} ms, unclassed {ms[1]:.2f} ms a call "
                  f"(the inverse take included), entries whose bits differ "
                  f"(sums, counts) {differ}", file=sys.stderr)


def _rank_lengths(queries, seed):
    """Query lengths as the ranking cell's are drawn (log-normal, mean
    120, 1 to 1,251), with 1, 2 and 1,251 written in."""
    rng = np.random.RandomState(seed)
    lens = np.clip(np.rint(np.exp(rng.normal(np.log(120) - 0.245, 0.7,
                                             queries))), 1, 1251)
    lens[rng.choice(queries, 3, replace=False)] = (1, 2, 1251)
    return lens.astype(np.int64)


def _rank_gradient_case(lengths, seed=35):
    """(a lambdarank objective at the cell's settings over `lengths`,
    n_pad, scores [1, n_pad] rounded to 1/16 so that many tie)."""
    import jax.numpy as jnp
    from types import SimpleNamespace
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ranking import LambdarankNDCG
    rng = np.random.RandomState(seed)
    n = int(lengths.sum())
    n_pad = -(-n // 1024) * 1024
    y = rng.choice(5, size=n, p=[.52, .32, .13, .02, .01]).astype(np.float32)
    obj = LambdarankNDCG(Config({"objective": "lambdarank"}))
    obj.init(SimpleNamespace(
        label=y, weight=None, position=None, init_score=None,
        query_boundaries=np.concatenate([[0], np.cumsum(lengths)])), n)
    scores = (np.round(rng.randn(n_pad) * 24) / 16).astype(np.float32)
    return obj, n_pad, jnp.asarray(scores)[None, :]


def _rank_gradient_bits(obj, n_pad, scores):
    """The program's and the reference's gradients of `scores`:
    {"differ": {"grad": entries whose bits differ, "hess": ...},
     "new": the program's callable, "old": the reference's}.  On a TPU
    both are compiled as the training loop compiles them; XLA's CPU
    backend vectorises the same arithmetic differently behind different
    data movement (an ulp: tests/test_rank_gradients_layout.py), so
    there both are compiled with the backend's optimiser off."""
    import jax
    import jax.numpy as jnp
    from tools import rank_index_map_reference as reference
    new = obj.make_device_grad_fn(n_pad)
    ref = reference.lambdarank(obj, n_pad)
    old = lambda s, w: ref(s, w, jnp.zeros(1, jnp.float32))[:2]
    if jax.default_backend() == "cpu":
        plain = {"xla_backend_optimization_level": 0}
        run = lambda fn: jax.jit(fn).lower(scores, None).compile(
            compiler_options=plain)(scores, None)
    else:
        run = lambda fn: fn(scores, None)
    as_bits = lambda a: np.asarray(
        jax.lax.bitcast_convert_type(a, jnp.int32))
    differ = {name: int((as_bits(a) != as_bits(b)).sum())
              for name, a, b in zip(("grad", "hess"), run(new), run(old))}
    return {"differ": differ, "new": new, "old": old}


def time_rank_gradients(rows=2_270_296, seed=35, calls=20):
    """One JSON line: the ranking cell's plan (`mslr_like.groups`), how
    many entries of the program's gradients and hessians differ in their
    bits from the index-map reference's, and milliseconds a call of
    each, the host's clock around `calls` calls."""
    import json
    import time
    import jax
    from benchmarks.generators import mslr_like
    from lightgbm_tpu.observability import global_registry
    names = ("rank_queries", "rank_docs", "rank_padded_docs",
             "rank_window_rows", "rank_buckets")
    before = {n: global_registry.counter(n) for n in names}
    obj, n_pad, scores = _rank_gradient_case(mslr_like.groups(rows, seed),
                                             seed)
    got = _rank_gradient_bits(obj, n_pad, scores)
    line = {n: global_registry.counter(n) - before[n] for n in names}
    line.update(device=jax.devices()[0].device_kind, n_pad=n_pad,
                bits_differ=got["differ"])
    for name in ("old", "new"):
        fn = got[name]
        for _ in range(3):
            out = fn(scores, None)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(scores, None)
        jax.block_until_ready(out)
        line[f"{name}_ms"] = (time.perf_counter() - t0) / calls * 1e3
    print(json.dumps(line), flush=True)


def _scan_operands(N, F, B, has_missing, seed=41, rows_a_leaf=4000):
    """N leaves' histograms [N, F, B, 2] (hessian = rows a bin, so counts
    are exact), every feature with its own num_bin, missing type (none
    anywhere without `has_missing`), default bin and a run of empty
    bins."""
    rng = np.random.RandomState(seed)
    nb = rng.randint(max(B // 2, 2), B + 1, F).astype(np.int32)
    mt = rng.randint(0, 3, F).astype(np.int32) * int(has_missing)
    db = (rng.randint(0, B, F) % nb).astype(np.int32)
    bins = np.arange(B)[None, :]
    lo = rng.randint(0, B, F)[:, None]
    open_bin = (bins < nb[:, None]) & ~((bins >= lo) & (bins < lo + B // 8))
    open_bin[:, 0] = True
    p = open_bin / open_bin.sum(1, keepdims=True)
    # rows of a (leaf, feature) over its open bins, the remainder of the
    # flooring into bin 0
    w = rng.gamma(4.0, 0.25, (N, F, B)) * p[None]
    cnt = np.floor(w / w.sum(2, keepdims=True) * rows_a_leaf).astype(np.int64)
    cnt[:, :, 0] += rows_a_leaf - cnt.sum(2)
    grad = (rng.randn(N, F, B) * np.sqrt(cnt)
            + 0.05 * cnt * np.sign(bins - B / 3)[None]).astype(np.float32)
    hist = np.stack([grad, cnt.astype(np.float32)], -1)
    sum_g = grad[:, 0, :].sum(1, dtype=np.float64).astype(np.float32)
    return hist, nb, mt, db, sum_g, rows_a_leaf


def _host_scan(hist, nb, mt, db, sum_g, n, min_data, min_hess, l2):
    """The reference's two sequential scans (feature_histogram.hpp:831),
    bin by bin in float32, for every (leaf, feature) at once; then each
    leaf's best feature, ties to the smaller index."""
    f32 = np.float32
    N, F, B, _ = hist.shape
    eps = f32(1e-15)
    sum_g = sum_g[:, None]
    sum_h = f32(n) + f32(2) * eps
    cnt_factor = f32(n) / sum_h
    gain_of = lambda g, h: (g * g) / (h + f32(l2))
    shift = gain_of(sum_g, sum_h)
    is_nan, is_zero = (mt == 2)[None], (mt == 1)[None]
    nbr, dbr = nb[None], db[None]

    def bin_sums(t):
        acc = (t < nbr) & ~(is_nan & (t == nbr - 1)) & ~(is_zero & (t == dbr))
        g, h = hist[:, :, t, 0], hist[:, :, t, 1]
        c = np.floor(h * cnt_factor + f32(0.5)).astype(np.int32)
        return np.where(acc, g, f32(0)), np.where(acc, h, f32(0)), \
            np.where(acc, c, 0)

    def gains(lg, lh_raw, lc, ok):
        lh = lh_raw + eps
        rg, rh, rc = sum_g - lg, sum_h - lh, n - lc
        ok = (ok & (lc >= min_data) & (lh >= f32(min_hess))
              & (rc >= min_data) & (rh >= f32(min_hess)))
        with np.errstate(all="ignore"):
            gain = gain_of(lg, lh) + gain_of(rg, rh)
        return np.where(ok & (gain > shift), gain, -np.inf).astype(f32)

    def scan(forward):
        run = [np.zeros((N, F), f32), np.zeros((N, F), f32),
               np.zeros((N, F), np.int32)]
        best = [np.full((N, F), -np.inf, f32), np.zeros((N, F), np.int32),
                np.zeros((N, F), f32), np.zeros((N, F), f32),
                np.zeros((N, F), np.int32)]
        for i in range(B - 1):
            tau = i if forward else B - 2 - i
            run = [a + b for a, b in zip(run, bin_sums(tau if forward
                                                       else tau + 1))]
            if forward:
                left = run
                ok = ((tau <= nbr - 2) & (mt != 0)[None]
                      & ~(is_zero & (tau == dbr)))
            else:
                left = [sum_g - run[0], sum_h - run[1] - f32(2) * eps,
                        n - run[2]]
                ok = ((tau <= nbr - 2 - is_nan)
                      & ~(is_zero & (tau == dbr - 1)))
            new = [gains(*left, ok), np.full((N, F), tau, np.int32)] + left
            better = new[0] > best[0]
            best = [np.where(better, a, b) for a, b in zip(new, best)]
        return best

    rev, fwd = scan(False), scan(True)
    use_fwd = fwd[0] > rev[0]
    gain, thr, lg, lh_raw, lc = (np.where(use_fwd, a, b)
                                 for a, b in zip(fwd, rev))
    f = np.argmax(gain, axis=1)
    at = lambda a: a[np.arange(N), f]
    return dict(feature=f, threshold=at(thr), default_left=~at(use_fwd),
                left_count=at(lc), left_sum_gradient=at(lg),
                left_sum_hessian=at(lh_raw), gain=at(gain) - shift[:, 0])


def _scan_mismatches(N, F, B, has_missing):
    """Names of the fields in which the chip's dense scan and the host's
    differ at [N, F, B]; [] when they agree."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import SplitParams, find_best_split_dense
    hist, nb, mt, db, sum_g, n = _scan_operands(N, F, B, has_missing)
    min_data, min_hess, l2 = 40, 100.5, 0.01
    want = _host_scan(hist, nb, mt, db, sum_g, n, min_data, min_hess, l2)
    rows = hist.reshape(N, -1)
    got = find_best_split_dense(
        jnp.asarray(rows), jnp.asarray(nb), jnp.asarray(mt),
        jnp.asarray(db), jnp.ones(F, jnp.float32), jnp.ones(F, bool),
        jnp.asarray(sum_g), jnp.full(N, n, jnp.float32),
        jnp.full(N, n, jnp.int32), jnp.zeros(N, jnp.float32),
        SplitParams(lambda_l2=l2, min_data_in_leaf=min_data,
                    min_sum_hessian_in_leaf=min_hess,
                    has_missing=has_missing),
        max_bin=B)
    bad = []
    if not (np.isfinite(want["gain"]).all() and (want["gain"] > 0).all()):
        bad.append("reference_has_no_split")
    for name in ("feature", "threshold", "default_left", "left_count"):
        if not np.array_equal(np.asarray(getattr(got, name)), want[name]):
            bad.append(name)
    for name, rtol in (("left_sum_gradient", 1e-5),
                       ("left_sum_hessian", 1e-6), ("gain", 1e-4)):
        if not np.allclose(np.asarray(getattr(got, name)), want[name],
                           rtol=rtol, atol=1e-3):
            bad.append(name)
    # with missing types both scans have to win somewhere
    dl = want["default_left"]
    if has_missing and (dl.all() or not dl.any()):
        bad.append("one_direction_only")
    tag = "" if has_missing else "_nomissing"
    return [f"split_scan_{F}x{B}{tag}_{b}" for b in bad]


def _lookup_operands(n, L, seed=31):
    """A leaf-value table with every awkward float in it (as far as `L`
    has room) and ids that reach 3 past both ends of it."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    vals = rng.randn(L).astype(np.float32)
    awkward = np.array([-0.0, np.nan, np.inf, -np.inf, 1e-42,
                        np.finfo(np.float32).max], np.float32)
    vals[:min(L - 1, awkward.size)] = awkward[:L - 1]
    ids = rng.randint(-3, L + 3, n).astype(np.int32)
    return jnp.asarray(vals), jnp.asarray(ids)


def _lookup_bits_equal(n, L):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.boosting.leaf_lookup import lookup_onehot, lookup_take
    vals, ids = _lookup_operands(n, L)
    as_bits = lambda a: np.asarray(
        jax.lax.bitcast_convert_type(a, jnp.int32))
    return bool(np.array_equal(as_bits(jax.jit(lookup_onehot)(vals, ids)),
                               as_bits(jax.jit(lookup_take)(vals, ids))))


def time_score_lookup(n=2_625_536,
                      leaves=(255, 1023, 2047, 4095, 8191, 16383),
                      calls=20):
    """One JSON line per table size: milliseconds a call of the score
    update (`scores.at[k].add(lookup(vals * rate, ids) * pad_mask)`,
    scores donated, as `boosting/gbdt.py _score_update_shrink`) with each
    form of the lookup, the host's clock around `calls` calls, and
    whether the two forms' rows agree in every bit."""
    import json
    import time
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.boosting.leaf_lookup import lookup_onehot, lookup_take

    def update_with(form):
        def update(scores, class_id, leaf_vals, rate, leaf_id, pad_mask):
            delta = form(leaf_vals * rate, leaf_id)
            return scores.at[class_id].add(delta * pad_mask)
        return jax.jit(update, donate_argnums=(0,))

    pad_mask = jnp.ones(n, jnp.float32)
    for L in leaves:
        vals, ids = _lookup_operands(n, L)
        line = {"rows": n, "leaves": L,
                "device": jax.devices()[0].device_kind,
                "bits_equal": _lookup_bits_equal(n, L)}
        for name, form in (("take", lookup_take), ("onehot", lookup_onehot)):
            update = update_with(form)
            scores = jnp.zeros((1, n), jnp.float32)
            for _ in range(3):
                scores = update(scores, 0, vals, 0.1, ids, pad_mask)
            scores.block_until_ready()
            t0 = time.perf_counter()
            for _ in range(calls):
                scores = update(scores, 0, vals, 0.1, ids, pad_mask)
            scores.block_until_ready()
            line[f"{name}_ms"] = (time.perf_counter() - t0) / calls * 1e3
        print(json.dumps(line), flush=True)


def _recolour_case(F, n, leaves, bundles=False, cat_words=0, max_bin=255,
                   column_bins=255, sentinel=256, seed=40):
    """A wave's per-leaf records as `learner/wave.py` step 4 hands them
    to `ops/recolour.py pack_table` (NumPy, `[leaves]` each), and the
    rows: `leaf_id [n]`, `binned [F, n]` uint8.  A third of the leaves do
    not split; missing types are none / zero / NaN in turn; under
    bundles a member's range is `[offset, offset + num_bin)` of codes up
    to `column_bins`, so codes fall inside it, outside it and on its
    ends."""
    rng = np.random.RandomState(seed)
    i32 = np.int32
    num_bin = rng.randint(2, max_bin + 1, leaves).astype(i32)
    fields = dict(
        split_sel=rng.rand(leaves) < 2 / 3,
        column=rng.randint(0, F, leaves).astype(i32),
        threshold=(rng.rand(leaves) * num_bin).astype(i32),
        default_left=rng.rand(leaves) < 0.5,
        new_leaf=rng.randint(0, sentinel, leaves).astype(i32),
        rank=rng.randint(0, leaves, leaves).astype(i32),
        small_left=rng.rand(leaves) < 0.5,
        missing_type=(np.arange(leaves) % 3).astype(i32),
        default_bin=(rng.rand(leaves) * num_bin).astype(i32),
        num_bin=num_bin)
    if bundles:
        fields.update(
            offset=rng.randint(0, column_bins - max_bin + 1,
                               leaves).astype(i32),
            zero_bin=(rng.rand(leaves) * num_bin).astype(i32))
    if cat_words:
        fields.update(
            is_cat=rng.rand(leaves) < 0.5,
            cat_bitset=rng.randint(-2 ** 31, 2 ** 31, (leaves, cat_words),
                                   dtype=np.int64).astype(i32))
    leaf_id = rng.randint(0, leaves, n).astype(i32)
    binned = rng.randint(0, column_bins + 1 if bundles else max_bin,
                         (F, n)).astype(np.uint8)
    return fields, leaf_id, binned


def _recolour_host(fields, leaf_id, binned, sentinel=256):
    """The recolour's rule written out a row at a time in NumPy, from
    the records themselves and not from the packed table: (leaf_id,
    kslot) as `learner/wave.py` computed them before the rule moved to
    `ops/recolour.py`."""
    of = {k: v[leaf_id] for k, v in fields.items()}
    fbin = binned[of["column"], np.arange(leaf_id.size)].astype(np.int32)
    if "offset" in of:
        local = fbin - of["offset"]
        fbin = np.where((local >= 0) & (local < of["num_bin"]), local,
                        of["zero_bin"])
    is_missing = (((of["missing_type"] == 2) & (fbin == of["num_bin"] - 1))
                  | ((of["missing_type"] == 1)
                     & (fbin == of["default_bin"])))
    go_left = np.where(is_missing, of["default_left"],
                       fbin <= of["threshold"])
    if "cat_bitset" in of:
        W = of["cat_bitset"].shape[1]
        word = np.take_along_axis(
            of["cat_bitset"], np.clip(fbin // 32, 0, W - 1)[:, None], 1)[:, 0]
        go_left = np.where(of["is_cat"], ((word >> (fbin % 32)) & 1) > 0,
                           go_left)
    sel = of["split_sel"]
    return (np.where(sel & ~go_left, of["new_leaf"], leaf_id),
            np.where(sel & (go_left == of["small_left"]), of["rank"],
                     sentinel).astype(np.int32))


def _recolour_forms(fields, leaf_id, binned, max_bin=255, column_bins=255,
                    sentinel=256):
    """[(name, fn)] of the recolour's two device forms on this case, each
    `fn()` -> (leaf_id, kslot) on the device."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.recolour import (pack_table, recolour_wave,
                                           recolour_xla, table_layout)
    cat = fields.get("cat_bitset")
    layout = table_layout(
        num_columns=binned.shape[0], max_bin=max_bin,
        column_bins=column_bins, num_slots=fields["rank"].size,
        sentinel=sentinel, has_bundles="offset" in fields,
        cat_words=0 if cat is None else cat.shape[1])
    tab = pack_table(layout, **{k: jnp.asarray(v) for k, v in fields.items()})
    rows = (jnp.asarray(leaf_id), jnp.asarray(binned))
    xla = jax.jit(recolour_xla, static_argnames=("layout",))
    return [("kernel", lambda: recolour_wave(tab, *rows, layout=layout)),
            ("xla", lambda: xla(tab, *rows, layout=layout))]


def _recolour_mismatches():
    """Names of the cases in which a form of the recolour differs from
    the host's rule in any row; [] when all agree."""
    cases = {
        "plain_5x8": dict(F=5, n=1536, leaves=8),
        "plain_28x256": dict(F=28, n=2048, leaves=256),
        "bundled_12x64": dict(F=12, n=2048, leaves=64, bundles=True,
                              max_bin=63),
        "categorical_7x16": dict(F=7, n=1024, leaves=16, cat_words=8),
        "column_blocks_300x16": dict(F=300, n=1024, leaves=16)}
    bad = []
    for name, case in cases.items():
        fields, leaf_id, binned = _recolour_case(**case)
        want = _recolour_host(fields, leaf_id, binned)
        for form, fn in _recolour_forms(fields, leaf_id, binned,
                                        case.get("max_bin", 255)):
            got = fn()
            if not all(np.array_equal(np.asarray(g), w)
                       for g, w in zip(got, want)):
                bad.append(f"recolour_{name}_{form}")
    return bad


def time_recolour(calls=10):
    """Check 10's forms at each cell's own shape: rows that differ from
    the host's rule, ms a call."""
    import time
    import jax
    shapes = (("higgs", dict(F=28, n=2_625_536)),
              ("mslr", dict(F=137, n=2_271_232, max_bin=63)),
              ("epsilon", dict(F=2000, n=400_384, max_bin=63)),
              ("expo", dict(F=12, n=11_000_832, bundles=True, max_bin=63)))
    for name, shape in shapes:
        for leaves in (8, 256):
            fields, leaf_id, binned = _recolour_case(leaves=leaves, **shape)
            want = _recolour_host(fields, leaf_id, binned)
            line = f"recolour {name} [{shape['F']}, {shape['n']}] leaves={leaves}:"
            for form, fn in _recolour_forms(fields, leaf_id, binned,
                                            shape.get("max_bin", 255)):
                got = jax.block_until_ready(fn())
                differ = [int((np.asarray(g) != w).sum())
                          for g, w in zip(got, want)]
                t0 = time.perf_counter()
                for _ in range(calls):
                    out = fn()
                jax.block_until_ready(out)
                ms = (time.perf_counter() - t0) / calls * 1e3
                line += (f" {form} {ms:.3f} ms a call, rows that differ "
                         f"from the host's rule (leaf_id, kslot) {differ};")
            print(line, file=sys.stderr)


# the categorical cell's columns (`expo-cat8-b63.train_cat`): bins a
# categorical column after upstream's 99%-coverage rule, the kernel's
# `max_bin` (the largest) and the configuration's categorical parameters
CAT_WIDTHS = (13, 32, 8, 23, 189, 170)
# and its eight device columns' codes: the two numerics at 63 bins
CAT_CODES = CAT_WIDTHS + (63, 63)
CAT_PARAMS = {"min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
              "lambda_l2": 0.0, "max_cat_to_onehot": 4,
              "max_cat_threshold": 32, "cat_smooth": 10, "cat_l2": 10,
              "min_data_per_group": 100}


def _cat_scan_case(num_bin, rows, rare, seed):
    """One categorical column of one leaf as tree 0's root sees it: raw
    categories 0 .. num_bin - 2 kept in bins 1 .. num_bin - 1, skewed
    counts, a binary label whose rate moves with the category, the
    gradients of that label at a constant score and one hessian for every
    row.  With `rare`, a third of the kept categories hold fewer rows
    than `cat_smooth` and 1% of the rows are of categories the map does
    not keep ("other", bin 0).  Returns (x, g, h, spec) with `spec` the
    column's bin map as `categorical_first_tree` takes it."""
    from benchmarks.references.categorical_first_tree import (
        _first_tree_gradients)
    rng = np.random.RandomState(seed)
    k = num_bin - 1
    cnt = rng.multinomial(rows, rng.dirichlet(np.full(k, 0.7)))
    if rare:
        few = rng.choice(k, max(k // 3, 1), replace=False)
        cnt[few] = rng.randint(1, CAT_PARAMS["cat_smooth"], few.size)
    x = np.repeat(np.arange(k), cnt)
    if rare:
        x = np.concatenate([x, k + rng.randint(0, 10, rows // 100)])
    x = x[rng.permutation(x.size)].astype(np.float64)
    rate = 1.0 / (1.0 + np.exp(-(rng.randn(k + 10) - 1.4)))
    y = (rng.rand(x.size) < rate[x.astype(np.int64)]).astype(np.float32)
    _, g, h = _first_tree_gradients(y)
    spec = {"num_bin": num_bin, "cat_to_bin": {c: c + 1 for c in range(k)},
            "bin_to_cat": [-1] + list(range(k))}
    return x, g, h, spec


def _cat_scan_program(hists, num_bin, sums, n, B, mapped):
    """`ops/split.py find_best_split` on leaves of one categorical column
    each ([N, 1, B, 2]): a leaf a call, as the leaf-wise engine calls it,
    or with the leaf axis mapped, as the wave engine's `vmap` does."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import SplitParams, find_best_split
    keys = ("min_data_in_leaf", "min_sum_hessian_in_leaf", "lambda_l2",
            "max_cat_to_onehot", "max_cat_threshold", "cat_smooth",
            "cat_l2", "min_data_per_group")
    sp = SplitParams(has_categorical=True,
                     **{k: CAT_PARAMS[k] for k in keys})
    one = jnp.ones(1, jnp.int32)

    def scan(h, sg, sh, cnt):
        return find_best_split(
            h, num_bin * one, 2 * one, one, jnp.ones(1, jnp.float32),
            jnp.ones(1, bool), sg, sh, cnt, jnp.zeros((), jnp.float32), sp,
            is_cat_feature=jnp.ones(1, bool), cat_index=0 * one)
    args = (jnp.asarray(hists), jnp.asarray(sums[:, 0], jnp.float32),
            jnp.asarray(sums[:, 1], jnp.float32), jnp.asarray(n, jnp.int32))
    if mapped:
        return jax.jit(jax.vmap(scan))(*args)
    out = [jax.jit(scan)(*(a[i] for a in args)) for i in range(len(n))]
    return jax.tree_util.tree_map(lambda *v: jnp.stack(v), *out)


def _cat_scan_mismatches(widths=CAT_WIDTHS, rows=20_000, leaves=4, B=None,
                         seed=41):
    """Names of the cases in which the categorical scan's choice differs
    from the float64 rule of `benchmarks/references/
    categorical_first_tree.py` (written from upstream's description, not
    from `ops/split.py`): the left category set (short of an exact tie),
    its count exactly, the gain to 1e-4; [] when all agree.  `leaves`
    cases a width, half of them with rare and "other" categories, on both
    engines' ways of calling the scan."""
    from benchmarks.references.categorical_first_tree import (
        EXACT_TIE_REL, _candidates_categorical)
    B = B or max(widths)
    bad = []
    for nb in widths:
        hists = np.zeros((leaves, 1, B, 2), np.float32)
        sums, n, want = np.zeros((leaves, 2)), [], []
        for i in range(leaves):
            x, g, h, spec = _cat_scan_case(nb, rows, i % 2 == 1,
                                           seed + 97 * nb + i)
            codes = np.where(x < nb - 1, x + 1, 0).astype(np.int64)
            hists[i, 0, :, 0] = np.bincount(codes, g, B)
            hists[i, 0, :, 1] = np.bincount(codes, h, B)
            sums[i] = g.sum(), h.sum()
            n.append(x.size)
            cands = _candidates_categorical(x, spec, g, h, g.sum(), h.sum(),
                                            x.size, CAT_PARAMS)
            want.append(cands)
        for mapped in (False, True):
            got = _cat_scan_program(hists, nb, sums, np.array(n), B, mapped)
            engine = "wave" if mapped else "leafwise"
            for i, cands in enumerate(want):
                name = f"cat_scan_{nb}bins_leaf{i}_{engine}"
                if not cands:
                    if float(got.gain[i]) > 0:
                        bad.append(name + "_split_where_none")
                    continue
                best = max(c[0] for c in cands)
                words = np.asarray(got.cat_bitset[i]).astype(np.int64)
                cats = {b - 1 for b in range(1, nb)
                        if (words[b // 32] >> (b % 32)) & 1}
                # the program's set among the rule's candidates: the best,
                # or one that ties it exactly (a set and its complement
                # are one partition, reached from either end)
                mine = [c for c in cands if c[2] == cats]
                if not bool(got.is_cat[i]):
                    bad.append(name + "_not_categorical")
                if not mine or best - mine[0][0] > EXACT_TIE_REL * best:
                    bad.append(name + "_set")
                elif int(got.left_count[i]) != int(mine[0][1]):
                    bad.append(name + "_left_count")
                if abs(float(got.gain[i]) - best) > 1e-4 * abs(best):
                    bad.append(name + "_gain")
    return bad


def time_categorical(calls=10):
    """The categorical cell's two chip checks: the recolour's bitset route
    at its shape (8 columns of up to 189 bins, 11,000,832 rows, 6 bitset
    words; 8 / 64 / 256 leaves), rows of `leaf_id` / `kslot` that differ
    from the NumPy rule and ms a call of each form; and the categorical
    scan against the float64 rule at the cell's widths.  Prints a
    verdict, "ok" or "fail:<which>"."""
    import time
    import jax
    failures = []
    shape = dict(F=8, n=11_000_832, max_bin=189, column_bins=189,
                 cat_words=6)
    for leaves in (8, 64, 256):
        fields, leaf_id, binned = _recolour_case(leaves=leaves, **shape)
        want = _recolour_host(fields, leaf_id, binned)
        line = f"recolour categorical [8, {shape['n']}] leaves={leaves}:"
        for form, fn in _recolour_forms(fields, leaf_id, binned, 189, 189):
            got = jax.block_until_ready(fn())
            differ = [int((np.asarray(g) != w).sum())
                      for g, w in zip(got, want)]
            if any(differ):
                failures.append(f"recolour_categorical_{leaves}_{form}")
            t0 = time.perf_counter()
            for _ in range(calls):
                out = fn()
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / calls * 1e3
            line += (f" {form} {ms:.3f} ms a call, rows that differ from "
                     f"the host's rule (leaf_id, kslot) {differ};")
        print(line, file=sys.stderr)
    scan_bad = _cat_scan_mismatches(rows=200_000)
    print(f"categorical scan at widths {CAT_WIDTHS}, 200,000 rows a leaf: "
          f"mismatches {scan_bad}", file=sys.stderr)
    failures += scan_bad
    print("ok" if not failures else "fail:" + ",".join(failures))


def _wide_reference(binned_blk, slot, gh, B, slots):
    """[slots, fb, B, 2] float32 histograms of a block of features, as
    plain one-hot products in float32 (`highest`: the MXU's six-pass
    float32 matmul, no bf16 anywhere)."""
    import jax
    import jax.numpy as jnp
    fb, n = binned_blk.shape
    with jax.default_matmul_precision("highest"):
        oh_bin = (binned_blk[:, None, :]
                  == jnp.arange(B, dtype=binned_blk.dtype)[None, :, None])
        oh_slot = slot[None, :] == jnp.arange(slots, dtype=slot.dtype)[:, None]
        w = (oh_slot[:, None, :].astype(jnp.float32)
             * gh[None, :2, :]).reshape(slots * 2, n)
        out = jnp.einsum("fbn,kn->fbk", oh_bin.astype(jnp.float32), w)
    return out.reshape(fb, B, slots, 2).transpose(2, 0, 1, 3)


def run_wide_checks(n=400_384, F=2000, B=63, slot_counts=(1, 8, 128),
                    block=8, rel_tol=1e-5):
    """The fused wave kernel through its feature-grouped path against
    `_wide_reference`, computed `block` features at a time.  Returns
    "ok" or "fail:<which>"; the readings go to stderr.

    `rel_tol` 1e-5 of the largest sum, per channel: gradients and
    hessians are on the bf16 grid, so the kernel's operand casts are
    exact and the two sides differ only in the order of their float32
    adds (read: 1e-7 and under).  A bf16 accumulator would miss by 1e-2.
    Counts are exact."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (build_histogram_wave,
                                            plan_wave_kernel,
                                            snap_to_operand_grid,
                                            wave_slot_pad)
    kb, ks, kw, kg, kh, km = jax.random.split(jax.random.PRNGKey(30), 6)
    binned = jax.random.randint(kb, (F, n), 0, B, jnp.int32).astype(jnp.uint8)
    mask = (jax.random.uniform(km, (n,)) < 0.9).astype(jnp.float32)
    gh = jnp.stack([
        snap_to_operand_grid(jax.random.normal(kg, (n,)), "pallas") * mask,
        snap_to_operand_grid(jax.random.uniform(kh, (n,)) * 0.25,
                             "pallas") * mask,
        mask])
    reference = jax.jit(_wide_reference, static_argnums=(3, 4))
    failures = []
    for slots in slot_counts:
        # a fifth of the rows outside every computed leaf, as the
        # recolour leaves them
        slot = jnp.where(jax.random.uniform(kw, (n,)) < 0.8,
                         jax.random.randint(ks, (n,), 0, slots),
                         wave_slot_pad(255)).astype(jnp.int32)
        try:
            plan = plan_wave_kernel(F, B, slots)
            if not plan.fits:
                failures.append(f"wide_plan_does_not_fit_{slots}")
            hist, cnt = build_histogram_wave(binned, slot, gh, max_bin=B,
                                             num_slots=slots)
            err = jnp.zeros(2)
            top = jnp.zeros(2)
            for f0 in range(0, F, block):
                ref = reference(binned[f0:f0 + block], slot, gh, B, slots)
                diff = jnp.abs(hist[:, f0:f0 + block] - ref)
                err = jnp.maximum(err, diff.max(axis=(0, 1, 2)))
                top = jnp.maximum(top, jnp.abs(ref).max(axis=(0, 1, 2)))
            rel = np.asarray(err / top)
            s_np, m_np = np.asarray(slot), np.asarray(mask)
            inb = s_np < slots
            want_cnt = np.bincount(s_np[inb], weights=m_np[inb],
                                   minlength=slots)
            counts_exact = bool(np.array_equal(np.asarray(cnt), want_cnt))
            print(f"wide {n}x{F} B={B} slots={slots}: "
                  f"groups={plan.groups}x{plan.feature_group} "
                  f"rel_err={rel.tolist()} counts_exact={counts_exact}",
                  file=sys.stderr)
            if not (rel <= rel_tol).all():
                failures.append(f"wide_sums_{slots}")
            if not counts_exact:
                failures.append(f"wide_counts_{slots}")
        except Exception as e:    # noqa: BLE001 - named in the verdict
            traceback.print_exc()
            failures.append(f"wide_raised_{slots}({type(e).__name__})")
    return "ok" if not failures else "fail:" + ",".join(failures)


# (name, the device columns' code counts, `hist_B`, padded rows, classed):
# the kernel shapes of the seven cells (`dp4` runs `b255`'s on each chip),
# the classed ones as their boosters hold them, and the ranking cell's
# columns as one class (137 columns, one a decomposed dot), whose FLOP
# share sits nearest the crossover
HL_SHAPES = (
    ("b255", (255,) * 28, 255, 2_625_536, True),
    ("b63", (63,) * 28, 63, 2_625_536, True),
    ("eps63", (63,) * 2000, 63, 400_384, True),
    ("mslr63", MSLR_CODES, 63, 2_271_232, True),
    ("mslr63-one-class", MSLR_CODES, 63, 2_271_232, False),
    ("expo63", EXPO_CODES, 255, 11_000_832, True),
    ("expocat63", CAT_CODES, 189, 11_000_832, True),
)


def _hl_operands(codes, n, slots, seed=42):
    """Bins drawing each column's codes, a fifth of the rows outside every
    computed slot (gradient, hessian and mask 0, as the recolour leaves
    them), and gradients and hessians on multiples of 1/8 with zero mean:
    every float32 sum of them is exact in any order, so two kernels that
    sum alike agree bit for bit.  Made on the device."""
    import jax
    import jax.numpy as jnp
    kb, ks, kw, kg, kh = jax.random.split(jax.random.PRNGKey(seed), 5)
    i32, f32 = jnp.int32, jnp.float32
    binned = jax.random.randint(kb, (len(codes), n), 0,
                                jnp.asarray(codes, i32)[:, None]
                                ).astype(jnp.uint8)
    inside = (jax.random.uniform(kw, (n,)) < 0.8).astype(f32)
    slot = jax.random.randint(ks, (n,), 0, slots, i32)
    g, h = (jax.random.randint(k, (n,), -8, 9).astype(f32) / 8
            for k in (kg, kh))
    return binned, slot, jnp.stack([g * inside, h * inside, inside])


def time_hl_crossover(shapes=HL_SHAPES, slot_counts=(1, 2, 4, 8), calls=10):
    """The two wave kernels a ladder wave can take, at each cell's kernel
    shape and 1 / 2 / 4 / 8 true slots: ms a call of the full kernel
    (`build_histogram_wave` at the padded slots, classed where the cell
    is) and of the decomposed one (`build_histogram_wave_hl`, where its
    VMEM gate admits the call), entries of their histograms and counts
    that differ, and beside the measured winner the share of the full
    kernel's padded MXU FLOP the decomposed one asks and the kernel
    `plan_wave_kernel` gives.  The reading behind `_HL_CROSSOVER`.
    Returns "ok" or "fail:<which>"; the readings go to stderr."""
    import time
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (
        build_histogram_wave, build_histogram_wave_hl, class_ordered,
        hist_classes_of, hl_split_of, hl_vmem, mxu_flop_per_row,
        plan_wave_kernel, wave_histograms, wave_slot_pad)

    def ms_a_call(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / calls * 1e3

    failures = []
    for name, codes, max_bin, n, classed in shapes:
        F = len(codes)
        classes, order = hist_classes_of(codes)
        classes = classes if classed and len(classes) > 1 else ()
        for slots in slot_counts:
            NLp = wave_slot_pad(slots)
            full_plan = plan_wave_kernel(F, max_bin, NLp,
                                         hist_classes=classes)
            hl_plan = full_plan._replace(
                kernel="wave_hl", hl_split=hl_split_of(max_bin, slots, 2))
            share = (mxu_flop_per_row(hl_plan, F, slots)
                     / mxu_flop_per_row(full_plan, F, NLp))
            picked = plan_wave_kernel(F, max_bin, NLp, slots,
                                      hist_classes=classes).kernel
            line = (f"hl {name} [{F}, {n}] B={max_bin} slots={slots}: "
                    f"FLOP share {share:.3f}, plan {picked}")
            try:
                binned, slot, gh = _hl_operands(codes, n, slots)
                handed = {}
                if classes:
                    handed = dict(
                        hist_classes=classes,
                        binned_classed=class_ordered(binned,
                                                     jnp.asarray(order)),
                        hist_inverse=jnp.asarray(np.argsort(order),
                                                 jnp.int32))
                full_ms = ms_a_call(
                    lambda b, s, g: build_histogram_wave(
                        b, s, g, max_bin=max_bin, num_slots=NLp,
                        hist_classes=classes),
                    handed.get("binned_classed", binned), slot, gh)
                line += f"; full {full_ms:.3f} ms"
                if hl_vmem(F, max_bin, slots) is None:
                    print(line + "; hl refused by its VMEM gate",
                          file=sys.stderr, flush=True)
                    continue
                binned_rm = binned.T
                hl_ms = ms_a_call(
                    lambda b, r, s, g: build_histogram_wave_hl(
                        b, r, s, g, max_bin=max_bin, num_slots=slots,
                        out_slots=NLp),
                    binned, binned_rm, slot, gh)
                want = wave_histograms(binned, None, slot, gh,
                                       max_bin=max_bin, num_slots=NLp,
                                       **handed)
                got = build_histogram_wave_hl(binned, binned_rm, slot, gh,
                                              max_bin=max_bin,
                                              num_slots=slots, out_slots=NLp)
                differ = [int(jnp.sum(a != b)) for a, b in zip(got, want)]
                winner = "wave_hl" if hl_ms < full_ms else "wave"
                print(line + f"; hl {hl_ms:.3f} ms; winner {winner}; "
                      f"entries that differ (sums, counts) {differ}",
                      file=sys.stderr, flush=True)
                if any(differ) or not bool(jnp.any(want[0])):
                    failures.append(f"hl_differs_{name}_{slots}")
                if picked != winner:
                    failures.append(f"plan_misses_{name}_{slots}")
            except Exception as e:    # noqa: BLE001 - named in the verdict
                traceback.print_exc()
                failures.append(f"hl_raised_{name}_{slots}"
                                f"({type(e).__name__})")
    return "ok" if not failures else "fail:" + ",".join(failures)


if __name__ == "__main__":
    if "--score-lookup" in sys.argv[1:]:
        time_score_lookup()
    elif "--rank-gradients" in sys.argv[1:]:
        time_rank_gradients()
    elif "--classed" in sys.argv[1:]:
        time_classed_kernel()
    elif "--recolour" in sys.argv[1:]:
        time_recolour()
    elif "--categorical" in sys.argv[1:]:
        time_categorical()
    elif "--roof" in sys.argv[1:]:
        from tools.hist_roof_probe import time_roof
        time_roof()
    elif "--hl" in sys.argv[1:]:
        print(time_hl_crossover())
    else:
        print(run_wide_checks() if "--wide" in sys.argv[1:]
              else run_checks())
