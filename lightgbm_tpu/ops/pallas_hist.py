"""Pallas TPU histogram kernel: the fused-gather, nibble-factorized form.

The TPU answer to the reference's OpenCL histogram kernels
(``src/treelearner/ocl/histogram256.cl`` — per-workgroup local-memory
histograms with hand-rolled atomic float adds).  TPUs have no fast random
scatter, so the native formulation is a one-hot x weights contraction on
the MXU — and this module holds the one kernel that survived two
generations of that idea: ``hist6_fused``, which DMAs the leaf's indexed
panel rows into VMEM itself (no separate gather pass, no staging buffer)
and contracts through the hi/lo nibble factorization.

The gen-1 kernels (a combined-index one-hot dot and a standalone nibble
form, both over PRE-GATHERED ``[M, F]`` rows) lived here until round 9.
They stopped Mosaic-lowering on the current jax/libtpu (the quarantine
that used to sit in tests/test_mosaic_aot.py), the fused kernel subsumed
both their roles, and they were deleted — the dispatch ladder is now
fused vs the XLA reference paths (ops/histogram.py).  Their hard-won
Mosaic lessons survive as the fused kernel's design notes below.

``hist6_fused_local`` is the shard-local entry for the GSPMD hybrid
(parallel/gspmd.py): inside a ``shard_map`` island it derives the leaf's
LOCAL order window from the row->leaf partition and runs the same kernel
over the device's row shard — one kernel from laptop CPU (interpret mode)
to pod slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NUM_CH = 6   # weight channels: (g_hi, g_lo, h_hi, h_lo, c, unused)
LANES = 128  # TPU vector register lane width — bin axis is padded to this
NIB = 16     # nibble radix: bin = hi*16 + lo, each one-hot 16 wide


# ---------------------------------------------------------------------------
# The fused-gather, nibble-factorized histogram kernel.
#
# The retired gen-1 path paid two separately-measured costs per split
# (docs/PERF.md cost model): a random row gather through XLA (~12.6 ns/elem,
# staged into a pow2-padded [M, F] HBM buffer) and a one-hot MXU contraction
# whose 6-channel M dim padded to 128 (~21x slot waste).  This kernel is the
# same move the reference made when it fused gather+accumulate into one
# OpenCL pass (src/treelearner/ocl/histogram256.cl): the row gather happens
# INSIDE the kernel — per-tile, the window of the leaf's ``order`` indices is
# DMAd into SMEM and each indexed panel row is DMAd from HBM straight into
# VMEM, so the gathered [M, F] matrix never exists in HBM and the separate
# gather dispatch disappears — and the contraction is the nibble-factorized
# form (bin = hi*16 + lo, M = ch x hi = 96 rows, 16-wide lo one-hot) that
# cuts the MXU slot cost ~2x at B_pad = 256.  PERF.md projects the stack at
# ~8.5 ns/row vs the measured 22 + 12.6.
#
# Three structural points:
#
# * the input is the FUSED PANEL (data/packing.py:pack_fused_panel): packed
#   bin words + the three bitcast f32 weight columns in one u32 row, so the
#   per-row DMA is a single contiguous burst and the hi/lo bf16 weight
#   split happens on-chip, per tile;
# * the grid is 1-D over row tiles and may be DYNAMIC (a traced tile
#   count): the grower passes ceil(cnt / row_tile), so a small leaf costs
#   a small grid — this is what retires the gather-bucket ``lax.switch``
#   (no static pow2 staging buffer means no static bucket sizes);
# * rows at positions >= cnt are redirected to the panel's sentinel row
#   (all-zero words AND zero weights), so tile padding needs no masking
#   anywhere downstream.
#
# Mosaic surfaces kept deliberately boring (round-2/round-5 lessons): the
# output block is written in static 128-lane groups (8 features x 16 lo
# bins) via full-width concatenated stores — never a sub-lane-width partial
# store — and every reshape happens outside the kernel in XLA.
# ---------------------------------------------------------------------------

FUSED_GROUP = 8        # features per 128-lane output group (8 * NIB = 128)
FUSED_MAX_COLS = 512   # feature-loop unroll + VMEM output-block ceiling
IDX_ALIGN = 1024       # i32 1-D tile: dynamic slices of ``order`` must sit
#                        on this boundary AND have a multiple-of-it length
#                        (Mosaic "tile index divisible by tiling" / "slice
#                        shape aligned to tile boundaries", both proven by
#                        the v5e AOT probe), so the kernel over-fetches the
#                        enclosing aligned region


def fused_idx_fetch(row_tile: int) -> int:
    """Elements of ``order`` the kernel fetches per tile: the smallest
    IDX_ALIGN multiple covering a row_tile window at any residual offset
    (< IDX_ALIGN) inside an aligned region."""
    return -(-(row_tile + IDX_ALIGN - 1) // IDX_ALIGN) * IDX_ALIGN


def _hist_kernel_fused(sc_ref, order_ref, panel_ref, out_ref,
                       idx_smem, rows_vmem, idx_sem, row_sem, *,
                       sentinel: int, n_words: int, words_per: int,
                       n_cols_pad: int, row_tile: int):
    ri = pl.program_id(0)

    @pl.when(ri == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    start = sc_ref[0]
    cnt = sc_ref[1]
    # the tile's slice of the leaf's ``order`` window, HBM -> SMEM: these
    # are the row ids the per-row DMAs below need as scalars.  The window
    # position is arbitrary but the source slice must be IDX_ALIGN-aligned,
    # so fetch the enclosing aligned region and read at the residual
    # offset — 3x the index bytes, which is noise next to the panel rows.
    pos = start + ri * row_tile
    aligned = pl.multiple_of((pos // IDX_ALIGN) * IDX_ALIGN, IDX_ALIGN)
    off = pos - aligned
    idx_copy = pltpu.make_async_copy(
        order_ref.at[pl.ds(aligned, fused_idx_fetch(row_tile))], idx_smem,
        idx_sem)
    idx_copy.start()
    idx_copy.wait()

    base = ri * row_tile

    def _row_copy(i):
        # positions past the leaf's count read the sentinel row (zero
        # words, zero weights) — same contract as the gen-1 sentinel pad.
        # pl.ds(r, 1) keeps the slice 2-D: integer .at[r] indexing squeezes
        # the row dim and that squeeze is what the LLO lowering choked on
        # ("dynamic_dim_it != dynamic_sizes.end()", v5e AOT probe) — the
        # compact kernel's proven dynamic-offset DMAs are all pl.ds-shaped
        r = jnp.where(base + i < cnt, idx_smem[off + i], sentinel)
        return pltpu.make_async_copy(panel_ref.at[pl.ds(r, 1), :],
                                     rows_vmem.at[pl.ds(i, 1), :],
                                     row_sem)

    # start every row DMA, then drain: the copies are independent and tiny
    # (W+3 u32 words each), so queueing them all before the first wait is
    # what lets the DMA engines overlap them
    def _start(i, _):
        _row_copy(i).start()
        return 0
    lax.fori_loop(0, row_tile, _start, 0)

    def _wait(i, _):
        _row_copy(i).wait()
        return 0
    lax.fori_loop(0, row_tile, _wait, 0)

    # word rows on the sublane axis (same orientation trick as the gen-1
    # kernels' [F, N] layout): static sublane indexing below, no dynamic
    # lane slicing for Mosaic to reject.  The untransposed form stays live
    # too: the lo one-hot needs COLUMN-shaped bins, and Mosaic rejects the
    # [TR] -> [TR, 1] shape cast from a sublane-layout vector (v5e AOT
    # probe) — a static [TR, 1] lane slice of the row-major value is
    # column-shaped from birth.
    rows2d = rows_vmem[...]                          # [TR, n_words + 3] u32
    words_t = rows2d.T                               # [n_words + 3, TR] u32
    shift = 32 // words_per
    wmask = jnp.uint32((1 << shift) - 1)

    # on-chip hi/lo weight split (the _split_hi_lo contract): channels
    # (g_hi, g_lo, h_hi, h_lo, c, 0), the retired gen-1 kernels' layout.
    # NO bf16 values exist below full-tile width: Mosaic rejected both the
    # gen-1 nibble form's [6, 1, TR] broadcast-multiply (vector.shape_cast)
    # and a [1, TR] bf16 sublane broadcast (vector.broadcast) — bf16's
    # packed (16, 128) tiling makes narrow bf16 vectors a hostile surface
    # (both caught by the v5e AOT probe).  So the hi half is computed IN
    # f32 via integer round-to-nearest-even on the raw bits (bit-identical
    # to an f32->bf16->f32 round-trip), everything stays f32 through the
    # broadcasts, and the one cast to bf16 happens on the full [96, TR]
    # tile right before the MXU.
    def _bf16_round_f32(wf):
        """f32 value of bf16(wf), without materializing a bf16 vector."""
        u = lax.bitcast_convert_type(wf, jnp.uint32)
        r = (u + jnp.uint32(0x7fff) + ((u >> 16) & jnp.uint32(1))) \
            & jnp.uint32(0xffff0000)
        return lax.bitcast_convert_type(r, jnp.float32)

    chans32 = []
    for k in range(2):
        wf = lax.bitcast_convert_type(words_t[n_words + k], jnp.float32)
        w_hi = _bf16_round_f32(wf)
        chans32 += [w_hi, wf - w_hi]
    chans32.append(lax.bitcast_convert_type(words_t[n_words + 2],
                                            jnp.float32))
    chans32.append(jnp.zeros_like(chans32[-1]))

    tr = row_tile
    # U's weight factor, feature-independent, built once per row tile —
    # strictly 2-D f32: each channel row broadcast to its 16-row band
    w_rep = jnp.concatenate(
        [jnp.broadcast_to(ch[None, :], (NIB, tr)) for ch in chans32],
        axis=0)                                      # [96, TR] f32
    for g0 in range(0, n_cols_pad, FUSED_GROUP):
        blocks = []
        for k in range(FUSED_GROUP):
            c = g0 + k
            w_i = c // words_per
            sh = (c % words_per) * shift
            binc = ((words_t[w_i] >> sh) & wmask).astype(jnp.int32)
            hi = binc >> 4                           # [TR], < 16
            oh_hi = (hi[None, :] ==
                     lax.broadcasted_iota(jnp.int32, (NIB, tr), 0)
                     ).astype(jnp.float32)           # [16, TR]
            # masked weights in f32, ONE full-tile bf16 cast before the
            # dot (oh is 0/1, so bf16(w * oh) == bf16(w) * oh exactly)
            u = (w_rep * jnp.concatenate([oh_hi] * NUM_CH, axis=0)
                 ).astype(jnp.bfloat16)              # [96, TR]
            lo_col = ((rows2d[:, w_i:w_i + 1] >> sh)
                      & wmask).astype(jnp.int32) & 15  # [TR, 1]
            oh_lo = (lo_col ==
                     lax.broadcasted_iota(jnp.int32, (tr, NIB), 1)
                     ).astype(jnp.bfloat16)          # [TR, 16]
            blocks.append(jnp.dot(u, oh_lo,
                                  preferred_element_type=jnp.float32))
        # one concatenated 128-lane-aligned store per feature group — the
        # masked sub-lane partial stores Mosaic has mislowered never happen
        out_ref[:, g0 * NIB:(g0 + FUSED_GROUP) * NIB] += jnp.concatenate(
            blocks, axis=1)                          # [96, 128]


def hist6_fused(order: jnp.ndarray, panel: jnp.ndarray, start, cnt,
                n_cols: int, words_per: int, num_bins: int,
                row_tile: int = 512, num_row_tiles=None,
                interpret: bool = False) -> jnp.ndarray:
    """Fused-gather nibble histogram: order [NO] i32 row ids (the leaf's
    window lives at [start, start + cnt)), panel [N + 1, n_words + 3] u32
    (pack_fused_panel layout, last row = sentinel) -> [6, n_cols, num_bins]
    f32.

    ``num_row_tiles`` is the grid length: a python int for a static grid,
    or a traced i32 scalar >= 1 (must equal ceil(max(cnt, 1) / row_tile))
    for the grower's dynamic-grid form.  ``start``/``cnt`` may be traced
    scalars either way.  The caller guarantees NO >= max(start + cnt)
    rounded down to IDX_ALIGN, plus fused_idx_fetch(row_tile): the aligned
    over-fetch may read that far past the window (the grower pads
    ``order`` with sentinel tail accordingly).
    """
    assert 1 < num_bins <= NIB * NIB, num_bins
    assert n_cols <= FUSED_MAX_COLS, (n_cols, FUSED_MAX_COLS)
    assert order.shape[0] >= fused_idx_fetch(row_tile), order.shape
    n_cols_pad = -(-n_cols // FUSED_GROUP) * FUSED_GROUP
    # the panel's word region covers exactly the group-padded columns
    # (pack_fused_panel layout); everything beyond words + 3 weight
    # columns is DMA-alignment padding, never read
    n_words = n_cols_pad // words_per
    assert panel.shape[1] >= n_words + 3, (panel.shape, n_words)
    sentinel = panel.shape[0] - 1
    if num_row_tiles is None:
        num_row_tiles = 1
    sc = jnp.stack([jnp.asarray(start, jnp.int32),
                    jnp.asarray(cnt, jnp.int32)])
    out2d = pl.pallas_call(
        functools.partial(_hist_kernel_fused, sentinel=sentinel,
                          n_words=n_words, words_per=words_per,
                          n_cols_pad=n_cols_pad, row_tile=row_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_row_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((NUM_CH * NIB, n_cols_pad * NIB),
                                   lambda ri, sc: (0, 0)),
            scratch_shapes=[pltpu.SMEM((fused_idx_fetch(row_tile),),
                                       jnp.int32),
                            pltpu.VMEM((row_tile, panel.shape[1]),
                                       jnp.uint32),
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA],
        ),
        out_shape=jax.ShapeDtypeStruct((NUM_CH * NIB, n_cols_pad * NIB),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(sc, order, panel)
    # [(ch, hi), (f, lo)] -> [ch, f, hi*16+lo], all in XLA (the same
    # epilogue the retired gen-1 nibble form used)
    out4 = out2d.reshape(NUM_CH, NIB, n_cols_pad, NIB)
    return out4.transpose(0, 2, 1, 3).reshape(
        NUM_CH, n_cols_pad, NIB * NIB)[:, :n_cols, :num_bins]


def hist6_fused_local(row_leaf: jnp.ndarray, leaf_id, panel: jnp.ndarray,
                      n_cols: int, words_per: int, num_bins: int,
                      row_tile: int = 512,
                      interpret: bool = False) -> jnp.ndarray:
    """Shard-local fused histogram for the GSPMD hybrid: derive the leaf's
    LOCAL order window from the row -> leaf partition, then run the same
    ``hist6_fused`` kernel over this device's row shard.

    row_leaf [n_loc] i32 (this shard's row -> leaf ids), leaf_id traced i32
    scalar, panel the shard's pack_fused_panel output (sentinel row
    appended by the caller before packing) -> [6, n_cols, num_bins] f32
    partial histogram (sums over the local rows only; the caller reduces
    across shards).

    The serial grower keeps ``order`` incrementally via its partition
    switch; under GSPMD the row -> leaf map IS the state, so the window is
    rebuilt per call with a cumsum compaction — O(n_loc) work, and the
    kernel's dynamic grid still makes the gather cost leaf-sized
    (ceil(cnt / row_tile) tiles, not n_loc / row_tile).
    """
    n_loc = row_leaf.shape[0]
    match = row_leaf == jnp.asarray(leaf_id, row_leaf.dtype)
    pos = jnp.cumsum(match.astype(jnp.int32)) - 1      # rank among matches
    cnt = jnp.sum(match.astype(jnp.int32))
    tail = fused_idx_fetch(row_tile)
    # compaction scatter: matching rows land at their rank, the rest are
    # routed out of bounds and dropped.  The tail (and any slot past cnt)
    # is never USED — the kernel redirects positions >= cnt to the panel's
    # sentinel row — it only has to exist for the aligned over-fetch.
    order = jnp.full((n_loc + tail,), n_loc, jnp.int32)
    order = order.at[jnp.where(match, pos, n_loc + tail)].set(
        jnp.arange(n_loc, dtype=jnp.int32), mode="drop")
    num_row_tiles = jnp.maximum(1, -(-cnt // row_tile)).astype(jnp.int32)
    return hist6_fused(order, panel, 0, cnt, n_cols, words_per, num_bins,
                       row_tile=row_tile, num_row_tiles=num_row_tiles,
                       interpret=interpret)
