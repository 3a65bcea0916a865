"""The wave engine's recolour: every row looks its leaf's split up and moves.

After a wave has chosen its splits, each row reads its leaf's record —
does the leaf split, on which column, at which threshold, where do missing
values go, which new leaf and which computed slot — picks its own bin of
that column and takes a side (ref: dense_bin.hpp:346 SplitInner, applied
to all splitting leaves at once).  The per-leaf records are a small table
`[rows, NLp]` of byte-valued bf16 (`pack_table`); a row's record is the
table times the one-hot of its leaf, exact on the MXU: one nonzero
product an output.

Two forms of one rule.  `recolour_wave` is a row-tiled Pallas call, rows
on lanes: a grid step reads `leaf_id` as a (1, Rt) block, the bins as an
(F, Rt) block of the `[F, n]` array the histogram kernels read, the table
whole; a row's record is born, used and dropped in VMEM, and the new
`leaf_id` and the next wave's `kslot` are the only things written: 4 + F
+ 8 bytes a row.  `recolour_xla` is the same functions on `[1, n]`
arrays: what a backend without Pallas runs, and the kernel's reference
in tests (as XLA compiles it, the record of every row goes to HBM as f32
and again as int32, and each use reads the whole of it back: 1.8 GB a
wave at 2.6M rows x 28 columns where the rows need 0.1 GB).  The rule
itself — `lookup_fields`, `select_bin`, `bundle_bin`, `route_rows` — is
written once and called from both.

This file holds no histogram kernel on purpose: a `tpu_custom_call`
carries the file and line of every op, so an edit here leaves the
histogram kernels' compile-cache keys alone (PERF.md, PR 32).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.registry import global_registry
from ..utils.timer import global_timer
from .histogram import _round_up
from .split import MISSING_NAN, MISSING_ZERO

# bits of the `flags` field
_SEL, _DEFAULT_LEFT, _SMALL_LEFT, _HAS_MISSING, _IS_CAT = 1, 2, 4, 8, 16


def _nbytes(bound: int) -> int:
    """Bytes that hold every value in [0, bound)."""
    return 1 if bound <= 1 << 8 else 2 if bound <= 1 << 16 else 3


class TableLayout(NamedTuple):
    """Where each field of a leaf's record lives in the table: static,
    from shapes and flags alone (never from the column order).  A field
    is `nbytes` consecutive byte rows from `row`, low byte first; the
    categorical bitset is `4 * cat_words` byte rows from `cat_row` (byte
    b holds bins 8b..8b+7).  `rows` pads the whole to bf16's 16
    sublanes."""
    fields: Tuple[Tuple[str, int, int], ...]     # (name, row, nbytes)
    cat_row: int
    cat_words: int
    rows: int
    sentinel: int        # Lp: the slot of a row outside every computed leaf
    has_bundles: bool

    def field(self, got: jnp.ndarray, name: str) -> jnp.ndarray:
        """Field `name` of looked-up records `got` [rows, R] -> [1, R]."""
        row, nbytes = next((r, b) for f, r, b in self.fields if f == name)
        out = got[row:row + 1]
        for j in range(1, nbytes):
            out = out + (got[row + j:row + j + 1] << (8 * j))
        return out


def table_layout(*, num_columns: int, max_bin: int, column_bins: int,
                 num_slots: int, sentinel: int, has_bundles: bool = False,
                 cat_words: int = 0) -> TableLayout:
    """The layout for a wave of `num_slots` leaves over `num_columns`
    device columns of up to `column_bins` codes, features of up to
    `max_bin` bins.  At 28 columns, 255 bins and 256 leaves every field
    is one byte: six rows, one f32 vreg a 128 rows."""
    bounds = [("flags", 32), ("col", num_columns), ("thr", max_bin),
              ("new", sentinel), ("rank", num_slots), ("miss", max_bin)]
    if has_bundles:
        bounds += [("off", column_bins), ("nbin", max_bin + 1),
                   ("zero", max_bin)]
    fields, row = [], 0
    for name, bound in bounds:
        fields.append((name, row, _nbytes(bound)))
        row += _nbytes(bound)
    cat_row = _round_up(row, 8)
    if cat_words:
        row = cat_row + 4 * cat_words
    return TableLayout(tuple(fields), cat_row, cat_words, _round_up(row, 16),
                       sentinel, has_bundles)


def pack_table(layout: TableLayout, *, split_sel, column, threshold,
               default_left, new_leaf, rank, small_left, missing_type,
               default_bin, num_bin, offset=None, zero_bin=None,
               is_cat=None, cat_bitset=None) -> jnp.ndarray:
    """The wave's table `[layout.rows, NLp]` bf16 from its per-leaf
    vectors `[NLp]`: does the leaf split, on which device column, at
    which threshold bin, the way of missing values, the right child's
    leaf, the pair's rank, is the left child the smaller; the split
    feature's missing type, default bin and bin count; under bundles its
    `offset` in the column and its `zero_bin`; with categorical features
    `is_cat` and `cat_bitset [NLp, W]`."""
    i32 = jnp.int32
    # the one bin that reads "missing" in the leaf's feature, if any
    missing_bin = jnp.where(
        missing_type == MISSING_NAN, num_bin - 1,
        jnp.where(missing_type == MISSING_ZERO, default_bin, -1))
    has_missing = missing_bin >= 0
    flags = (split_sel.astype(i32) * _SEL
             + default_left.astype(i32) * _DEFAULT_LEFT
             + small_left.astype(i32) * _SMALL_LEFT
             + has_missing.astype(i32) * _HAS_MISSING)
    if is_cat is not None:
        flags = flags + is_cat.astype(i32) * _IS_CAT
    values = dict(flags=flags, col=column, thr=threshold, new=new_leaf,
                  rank=rank, miss=jnp.where(has_missing, missing_bin, 0),
                  off=offset, nbin=num_bin, zero=zero_bin)
    byte_rows = [(values[name].astype(i32) >> (8 * j)) & 255
                 for name, _, nbytes in layout.fields for j in range(nbytes)]
    if layout.cat_words:
        byte_rows += [jnp.zeros_like(flags)] * (layout.cat_row
                                                - len(byte_rows))
        byte_rows += [(cat_bitset[:, w] >> (8 * j)) & 255
                      for w in range(layout.cat_words) for j in range(4)]
    byte_rows += [jnp.zeros_like(flags)] * (layout.rows - len(byte_rows))
    return jnp.stack(byte_rows, axis=0).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# The rule, on [1, R] rows: R is a lane tile inside the kernel, n outside
# ---------------------------------------------------------------------------

def lookup_fields(tab: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """Each row's record: `tab [rows, NLp]` times the one-hot of `leaf
    [1, R]` against a sublane iota -> int32 `[rows, R]`, bytes exact."""
    slots = jax.lax.broadcasted_iota(jnp.int32,
                                     (tab.shape[1], leaf.shape[1]), 0)
    onehot = (leaf == slots).astype(jnp.bfloat16)           # [NLp, R]
    got = jax.lax.dot_general(tab, onehot, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return got.astype(jnp.int32)


def select_bin(col: jnp.ndarray, bins: jnp.ndarray,
               first: int = 0) -> jnp.ndarray:
    """The code of column `col [1, R]` in `bins [Fg, R]`, the columns
    `first ..`: a compare-select over the block's columns (0 where `col`
    is not among them, so column blocks add up)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, bins.shape, 0)
    return jnp.sum(jnp.where(col - first == cols, bins.astype(jnp.int32), 0),
                   axis=0, keepdims=True)


def bundle_bin(layout: TableLayout, got: jnp.ndarray,
               code: jnp.ndarray) -> jnp.ndarray:
    """A bundle column's code to the split feature's bin: inside the
    member's range its local bin, outside it the feature's zero bin."""
    local = code - layout.field(got, "off")
    inside = (local >= 0) & (local < layout.field(got, "nbin"))
    return jnp.where(inside, local, layout.field(got, "zero"))


def route_rows(layout: TableLayout, got: jnp.ndarray, fbin: jnp.ndarray,
               leaf: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Records `got [rows, R]`, each row's bin of its split feature
    `fbin [1, R]` and its leaf -> (new leaf, next wave's computed slot).
    Missing goes the split's default way, everything else by the
    threshold (or the category's bit); a row of a leaf that does not
    split stays.  The slot is the pair's rank where the row landed in
    its pair's smaller child and the sentinel elsewhere, which matches
    no slot one-hot of the histogram kernels."""
    flags = layout.field(got, "flags")
    sel = (flags & _SEL) > 0
    default_left = (flags & _DEFAULT_LEFT) > 0
    small_left = (flags & _SMALL_LEFT) > 0
    is_missing = (((flags & _HAS_MISSING) > 0)
                  & (fbin == layout.field(got, "miss")))
    # booleans meet in and / or / xor alone: Mosaic has no select of i1
    go_left = ((is_missing & default_left)
               | (~is_missing & (fbin <= layout.field(got, "thr"))))
    if layout.cat_words:
        W = layout.cat_words
        at = jnp.clip(fbin >> 5, 0, W - 1) * 4 + ((fbin >> 3) & 3)
        sets = got[layout.cat_row:layout.cat_row + 4 * W]
        byte = select_bin(at, sets)
        cat_left = ((byte >> (fbin & 7)) & 1) > 0
        is_cat = (flags & _IS_CAT) > 0
        go_left = (is_cat & cat_left) | (~is_cat & go_left)
    new_leaf = jnp.where(sel & ~go_left, layout.field(got, "new"), leaf)
    kslot = jnp.where(sel & ~(go_left ^ small_left),
                      layout.field(got, "rank"), layout.sentinel)
    return new_leaf, kslot


# ---------------------------------------------------------------------------
# The two forms
# ---------------------------------------------------------------------------

def recolour_xla(tab: jnp.ndarray, leaf_id: jnp.ndarray, binned: jnp.ndarray,
                 *, layout: TableLayout):
    """The rule as plain XLA ops over all rows at once.  `tab
    [layout.rows, NLp]`, `leaf_id [n]`, `binned [F, n]` -> (leaf_id,
    kslot), both `[n]` int32."""
    global_registry.inc("recolour_xla_traces")
    leaf = leaf_id[None, :]
    got = lookup_fields(tab, leaf)
    if layout.has_bundles:
        # the bundle column's select and decode: a part of the caller's
        # scope (benchmarks' efb_route_ms reads it where this form runs)
        with global_timer.device_scope("Efb::route"):
            fbin = bundle_bin(layout, got,
                              select_bin(layout.field(got, "col"), binned))
    else:
        fbin = select_bin(layout.field(got, "col"), binned)
    new_leaf, kslot = route_rows(layout, got, fbin, leaf)
    return new_leaf[0], kslot[0]


_LANE_TILE = 4096           # rows of one pass of the rule inside a step
_BLOCK_BYTES = 2 << 20      # of bins a grid step reads
_MAX_ROW_TILE = 32768
_MAX_COLUMN_BLOCK = 256     # more columns than this go by a grid axis


def plan_recolour(num_columns: int, n: int, itemsize: int = 1):
    """(columns a block, rows a block) of the kernel's bins operand, from
    its shape: all columns in one block up to 256, else 256 a step of a
    second grid axis; the rows that keep a block near 2 MB, so that the
    ~0.35 us a grid step costs is spread over 8-32 thousand rows.  (On
    the chip, ms a call at 2,625,536 x 28 and 256 leaves: 1.21 with 512
    rows a pass, 0.84 with 1,024, 0.69 with 2,048, 0.66 with 4,096; 0.66
    with 32,768 rows a block for 0.67 with 16,384.  At 2,000 columns
    neither the pass, 512 to 4,096, nor the select in chunks of 32
    columns moved the call from 1.37-1.40 ms: PERF.md, PR 40.)"""
    Fg = min(num_columns, _MAX_COLUMN_BLOCK)
    sub = 32 // itemsize                       # sublanes of a packed tile
    rt = _BLOCK_BYTES // (_round_up(Fg, sub) * itemsize)
    rt = max(_LANE_TILE, min(_MAX_ROW_TILE, rt // _LANE_TILE * _LANE_TILE))
    return Fg, min(rt, _round_up(n, _LANE_TILE))


def _recolour_kernel(layout: TableLayout, Fg: int, Rt: int):
    def kernel(tab_ref, leaf_ref, bins_ref, leaf_out, slot_out, got_ref,
               fbin_ref):
        g = pl.program_id(1)
        last = pl.num_programs(1) - 1

        def tile(j, carry):
            at = pl.ds(pl.multiple_of(j * _LANE_TILE, _LANE_TILE),
                       _LANE_TILE)

            @pl.when(g == 0)
            def _lookup():
                got_ref[:, at] = lookup_fields(tab_ref[...], leaf_ref[:, at])
                fbin_ref[:, at] = jnp.zeros((1, _LANE_TILE), jnp.int32)

            got = got_ref[:, at]
            fbin_ref[:, at] += select_bin(layout.field(got, "col"),
                                          bins_ref[:, at], g * Fg)

            @pl.when(g == last)
            def _route():
                fbin = fbin_ref[:, at]
                if layout.has_bundles:
                    fbin = bundle_bin(layout, got, fbin)
                leaf_out[:, at], slot_out[:, at] = route_rows(
                    layout, got, fbin, leaf_ref[:, at])
            return carry

        jax.lax.fori_loop(0, Rt // _LANE_TILE, tile, 0)
    return kernel


@functools.partial(jax.jit, static_argnames=("layout",))
def recolour_wave(tab: jnp.ndarray, leaf_id: jnp.ndarray,
                  binned: jnp.ndarray, *, layout: TableLayout):
    """The rule as one Pallas call over row tiles, rows on lanes.
    Operands and results as `recolour_xla`'s.  Grid = (row blocks, column
    blocks); a step looks its rows' records up (first column block),
    adds the block's share of each row's bin, and routes (last column
    block).  The last row block may hang over `n`: what it reads there
    is never written back."""
    global_registry.inc("recolour_kernel_traces")
    F, n = binned.shape
    Fg, Rt = plan_recolour(F, n, binned.dtype.itemsize)
    row_spec = pl.BlockSpec((1, Rt), lambda i, g: (0, i))
    rows = jax.ShapeDtypeStruct((1, n), jnp.int32)
    new_leaf, kslot = pl.pallas_call(
        _recolour_kernel(layout, Fg, Rt),
        grid=(pl.cdiv(n, Rt), pl.cdiv(F, Fg)),
        in_specs=[pl.BlockSpec(tab.shape, lambda i, g: (0, 0)), row_spec,
                  pl.BlockSpec((Fg, Rt), lambda i, g: (g, i))],
        out_specs=[row_spec, row_spec],
        out_shape=[rows, rows],
        scratch_shapes=[pltpu.VMEM((layout.rows, Rt), jnp.int32),
                        pltpu.VMEM((1, Rt), jnp.int32)],
        # not `^%build_histogram`: the benchmark's readers count this call
        # under its scope, `Tree.partition`, and not among the kernels
        name="recolour_wave",
    )(tab, leaf_id.reshape(1, n), binned)
    return new_leaf[0], kslot[0]
