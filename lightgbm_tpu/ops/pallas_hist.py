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

from ..data.packing import FUSED_COL_STEP, fused_col_tiles

NUM_CH = 6   # weight channels: (g_hi, g_lo, h_hi, h_lo, c, unused)
LANES = 128  # TPU vector register lane width — bin axis is padded to this
NIB = 16     # nibble radix: bin = hi*16 + lo, the lo one-hot 16 wide
FUSED_MAX_BINS = 512   # widest histogram the kernel serves (uint16 bins)
HI_ALIGN = 8           # the hi one-hot past 256 bins: whole f32 sublane tiles


def fused_hi(num_bins: int) -> int:
    """Rows of the hi one-hot for a histogram ``num_bins`` wide: 16 up to
    256 bins (the two-nibble form every layout of 256 or fewer bins has
    always traced), past that ceil(num_bins / 16) rounded up to whole f32
    sublane tiles (279 bins: 18 -> 24; 512: 32)."""
    if num_bins <= NIB * NIB:
        return NIB
    return -(-(-(-num_bins // NIB)) // HI_ALIGN) * HI_ALIGN


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
# form (bin = hi*16 + lo, M = NUM_CH x HI rows, 16-wide lo one-hot) that
# cuts the MXU slot cost ~2x against a one-hot as wide as the histogram.
#
# HI, the hi one-hot's height, is static per layout (``fused_hi``): 16 up
# to 256 bins, so M = 96 and the program is the one every uint8 layout has
# always traced; past 256 bins (uint16 bins, two 16-bit bins a word) it is
# ceil(width / 16) rounded up to whole f32 sublane tiles of 8, up to 512
# bins (HI = 32, M = 192).  Only M grows with the width.  The 16-lane lo
# one-hot, the FUSED_GROUP of 8 features to a 128-lane output group and the
# step of 4 groups stay: they fix the output's lane layout (a group is 8 x
# 16 lo bins = 128 lanes, whatever HI is), and widening the lo side instead
# would break that 128-lane group for every width.  A taller M costs the MXU
# M / 96 of today's pushes a feature and row tile (1.5x at 279 bins, where
# HI = 24 holds 18 live rows in 24: the padding to 8 keeps every channel
# band of the concatenated [M, TR] weight operand on whole tiles).
#
# What the v5e read (scripts/probe_hist_fetch.py, PR 28; PERF.md section
# 5): a row of 28 columns is 4.9 ns of arithmetic, and before PR 28 it was
# 24.2 ns of issuing its descriptor and 6.3 of waiting for it, one row
# after another, 35.7 in all.  The scalar core's issue is what binds: a
# row's 512 B are in VMEM long before the next descriptor is built.  So
# the fetch of a row tile is
#
#   * ONE block copy where the caller built the window as the identity
#     (``contiguous``: the root, whose ``order`` is an ``arange``): no
#     index, no SMEM, no per-row loop — 4.9 ns a row, the arithmetic's;
#   * otherwise one descriptor a row, issued 32 to a trip of the loop
#     (a trip cost as much as a descriptor: 8.6 ns a row at one a trip)
#     and waited for ONCE (a DMA semaphore counts bytes): 19.3 ns a row;
#   * either way into one of TWO slots, tile i + 1 started before tile i
#     is waited for.  That hides the block copy behind the arithmetic
#     whole; on indexed rows it reads 0.3 ns at 28 columns and 0.7-1.4
#     at 2000, and is kept because the block form needs the slots anyway
#     and one schedule is less code than two.
#
# Four structural points:
#
# * the input is the FUSED PANEL (data/packing.py:pack_fused_panel): column
#   tiles of 128 u32 words, each a row's packed bin words + the three
#   bitcast f32 weight columns, so the per-row DMA is ONE descriptor of a
#   512 B burst per column tile and the hi/lo bf16 weight split happens
#   on-chip, per tile;
# * the width is a LOOP, not an unroll: a column tile is walked in steps of
#   32 columns (``fori_loop``; one step inline for a narrow data set), each
#   step reading its 8 or 16 word rows of the transposed tile at a dynamic
#   sublane offset and adding into its own [NUM_CH x HI, 512] slab of the
#   output block, so neither the program nor its VMEM stack grows with the
#   column count (unrolled, 256 columns already asked for 22.5 MB of the 16 MB a
#   kernel gets, and 2000 would compile for minutes: v5e AOT probe, PR 27).
#   Both one-hots are built [16, TR] and the dot contracts the row axis of
#   both operands: a step that indexes columns dynamically has no static
#   [TR, 1] lane slice to make a column-shaped lo one-hot from (the hi
#   one-hot is [HI, TR]);
# * the grid is 1-D over row tiles and may be DYNAMIC (a traced tile
#   count): the grower passes ceil(cnt / row_tile), so a small leaf costs
#   a small grid — this is what retires the gather-bucket ``lax.switch``
#   (no static pow2 staging buffer means no static bucket sizes);
# * rows at positions >= cnt are redirected to the panel's sentinel row
#   (all-zero words AND zero weights), so tile padding needs no masking
#   anywhere downstream.
#
# Mosaic surfaces kept deliberately boring (round-2/round-5 lessons): the
# output block is written in whole [NUM_CH x HI, 512] slabs of four
# 128-lane groups (8 features x 16 lo bins each), indexed on their leading
# axis — never a sub-lane-width partial store, never a dynamic lane offset —
# and every reshape happens outside the kernel in XLA.
# ---------------------------------------------------------------------------

FUSED_GROUP = 8        # features per 128-lane output group (8 * NIB = 128)
STEP_LANES = FUSED_COL_STEP * NIB   # output lanes of one step: 4 groups
IDX_ALIGN = 1024       # i32 1-D tile: dynamic slices of ``order`` must sit
#                        on this boundary AND have a multiple-of-it length
#                        (Mosaic "tile index divisible by tiling" / "slice
#                        shape aligned to tile boundaries", both proven by
#                        the v5e AOT probe), so the kernel over-fetches the
#                        enclosing aligned region
VMEM_DEFAULT = 16 << 20   # what a Mosaic kernel may use on the v5e unasked
ISSUE_UNROLL = 32      # row copies issued per trip of the issue loop (probe,
#                        PR 28: 20.6 ns a row at 8, 19.8 at 16, 19.5 at 32)


def fused_idx_fetch(row_tile: int) -> int:
    """Elements of ``order`` the kernel fetches per tile: the smallest
    IDX_ALIGN multiple covering a row_tile window at any residual offset
    (< IDX_ALIGN) inside an aligned region."""
    return -(-(row_tile + IDX_ALIGN - 1) // IDX_ALIGN) * IDX_ALIGN


def _bf16_round_f32(wf):
    """f32 value of bf16(wf), without materializing a bf16 vector: integer
    round-to-nearest-even on the raw bits."""
    u = lax.bitcast_convert_type(wf, jnp.uint32)
    r = (u + jnp.uint32(0x7fff) + ((u >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xffff0000)
    return lax.bitcast_convert_type(r, jnp.float32)


def _loop(n: int, body):
    """``body(i)`` for i in [0, n): inline where there is one trip (the
    narrow data set's whole kernel), a ``fori_loop`` otherwise."""
    if n == 1:
        body(0)
        return

    def trip(i, carry):
        body(i)
        return carry
    lax.fori_loop(0, n, trip, 0)


def _accumulate(rows_ref, words_vmem, out_ref, *, tile_words: int,
                words_per: int, tile_steps: int, col_tiles: int,
                row_tile: int, hi: int):
    """Add one fetched row tile, ``rows_ref`` [col_tiles, row_tile, 128]
    u32 in VMEM, into the output block: the kernel's arithmetic, whatever
    brought the rows."""
    tr = row_tile
    shift = 32 // words_per
    wmask = jnp.uint32((1 << shift) - 1)
    step_words = FUSED_COL_STEP // words_per
    nib_iota = lax.broadcasted_iota(jnp.int32, (NIB, tr), 0)
    hi_iota = (nib_iota if hi == NIB
               else lax.broadcasted_iota(jnp.int32, (hi, tr), 0))

    def _tile(t):
        # word rows on the sublane axis (same orientation trick as the
        # gen-1 kernels' [F, N] layout), parked in VMEM so that a step
        # reads ITS words as whole sublanes at a dynamic, 8-aligned
        # offset: no dynamic lane slicing for Mosaic to reject
        words_vmem[...] = rows_ref[t].T              # [128, TR] u32

        # on-chip hi/lo weight split (the _split_hi_lo contract): channels
        # (g_hi, g_lo, h_hi, h_lo, c, 0), the retired gen-1 kernels'
        # layout.  NO bf16 values exist below full-tile width: Mosaic
        # rejected both the gen-1 nibble form's [6, 1, TR]
        # broadcast-multiply (vector.shape_cast) and a [1, TR] bf16
        # sublane broadcast (vector.broadcast) — bf16's packed (16, 128)
        # tiling makes narrow bf16 vectors a hostile surface (both caught
        # by the v5e AOT probe).  So the hi half is computed IN f32 via
        # integer round-to-nearest-even on the raw bits (bit-identical to
        # an f32->bf16->f32 round-trip), everything stays f32 through the
        # broadcasts, and the one cast to bf16 happens on the full
        # [NUM_CH x HI, TR] tile right before the MXU.
        chans32 = []
        for k in range(2):
            wf = lax.bitcast_convert_type(words_vmem[tile_words + k],
                                          jnp.float32)
            w_hi = _bf16_round_f32(wf)
            chans32 += [w_hi, wf - w_hi]
        chans32.append(lax.bitcast_convert_type(words_vmem[tile_words + 2],
                                                jnp.float32))
        chans32.append(jnp.zeros_like(chans32[-1]))
        # U's weight factor, feature-independent, built once per row and
        # column tile — strictly 2-D f32: each channel row broadcast to
        # its HI-row band
        w_rep = jnp.concatenate(
            [jnp.broadcast_to(ch[None, :], (hi, tr)) for ch in chans32],
            axis=0)                                  # [NUM_CH x HI, TR] f32

        def _step(s):
            w0 = s * step_words
            if not isinstance(w0, int):
                w0 = pl.multiple_of(w0, step_words)
            words = words_vmem[pl.ds(w0, step_words), :]
            groups = []
            for g0 in range(0, FUSED_COL_STEP, FUSED_GROUP):
                blocks = []
                for c in range(g0, g0 + FUSED_GROUP):
                    sh = (c % words_per) * shift
                    binc = ((words[c // words_per] >> sh)
                            & wmask).astype(jnp.int32)   # [TR]
                    oh_hi = ((binc >> 4)[None, :] == hi_iota
                             ).astype(jnp.float32)       # [HI, TR]
                    # masked weights in f32, ONE full-tile bf16 cast before
                    # the dot (oh is 0/1, so bf16(w * oh) == bf16(w) * oh
                    # exactly)
                    u = (w_rep * jnp.concatenate([oh_hi] * NUM_CH, axis=0)
                         ).astype(jnp.bfloat16)          # [NUM_CH x HI, TR]
                    # the lo one-hot in the same [16, TR] orientation: the
                    # dot contracts the row axis of both operands
                    oh_lo = ((binc & 15)[None, :] == nib_iota
                             ).astype(jnp.bfloat16)      # [16, TR]
                    blocks.append(lax.dot_general(
                        u, oh_lo, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32))
                # one concatenated 128-lane group — the masked sub-lane
                # partial stores Mosaic has mislowered never happen
                groups.append(jnp.concatenate(blocks, axis=1))  # [M, 128]
            out_ref[t * tile_steps + s] += jnp.concatenate(groups, axis=1)

        _loop(tile_steps, _step)

    _loop(col_tiles, _tile)


def _hist_kernel_fused(sc_ref, order_ref, panel_ref, out_ref,
                       idx_smem, rows_vmem, words_vmem, idx_sem, row_sem, *,
                       sentinel: int, contiguous: bool, tile_words: int,
                       words_per: int, tile_steps: int, col_tiles: int,
                       row_tile: int, hi: int):
    ri = pl.program_id(0)
    slot = ri % 2
    start = sc_ref[0]
    cnt = sc_ref[1]

    def _fetch(tile, slot):
        """Start the copies that bring row tile ``tile`` of the window
        into ``rows_vmem[slot]``, all signalling ``row_sem[slot]``."""
        if contiguous:
            # the window IS the panel's first rows, in order: one
            # descriptor carries the whole tile (no index, no SMEM)
            r0 = pl.multiple_of(tile * row_tile, row_tile)
            pltpu.make_async_copy(panel_ref.at[:, pl.ds(r0, row_tile), :],
                                  rows_vmem.at[slot],
                                  row_sem.at[slot]).start()
            return
        # the tile's slice of the leaf's ``order`` window, HBM -> SMEM:
        # these are the row ids the per-row DMAs below need as scalars.
        # The window position is arbitrary but the source slice must be
        # IDX_ALIGN-aligned, so fetch the enclosing aligned region and
        # read at the residual offset — 3x the index bytes, which is noise
        # next to the panel rows.  The ids are read while the row copies
        # are ISSUED and never again (nothing below rebuilds a
        # descriptor), so one SMEM buffer serves both slots.
        pos = start + tile * row_tile
        aligned = pl.multiple_of((pos // IDX_ALIGN) * IDX_ALIGN, IDX_ALIGN)
        off = pos - aligned
        idx_copy = pltpu.make_async_copy(
            order_ref.at[pl.ds(aligned, fused_idx_fetch(row_tile))],
            idx_smem, idx_sem)
        idx_copy.start()
        idx_copy.wait()
        base = tile * row_tile

        def _issue(j, carry):
            # positions past the leaf's count read the sentinel row (zero
            # words, zero weights) — same contract as the gen-1 sentinel
            # pad.  pl.ds(r, 1) keeps the slice's row dim: integer .at[r]
            # indexing squeezes it and that squeeze is what the LLO
            # lowering choked on ("dynamic_dim_it != dynamic_sizes.end()",
            # v5e AOT probe); a dynamic offset lowers where the DMA's slice
            # is pl.ds-shaped.  One descriptor a row: its 512 B
            # from every column tile.  Unrolled by hand: Mosaic's
            # ``fori_loop`` takes ``unroll`` 1 or the whole trip count.
            for k in range(ISSUE_UNROLL):
                i = j * ISSUE_UNROLL + k
                r = jnp.where(base + i < cnt, idx_smem[off + i], sentinel)
                pltpu.make_async_copy(panel_ref.at[:, pl.ds(r, 1), :],
                                      rows_vmem.at[slot, :, pl.ds(i, 1), :],
                                      row_sem.at[slot]).start()
            return carry
        lax.fori_loop(0, row_tile // ISSUE_UNROLL, _issue, 0)

    @pl.when(ri == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        _fetch(0, 0)

    # two slots: the rows of tile ri + 1 fly while tile ri is computed
    @pl.when(ri + 1 < pl.num_programs(0))
    def _ahead():
        _fetch(ri + 1, 1 - slot)

    # ONE wait a tile: a DMA semaphore counts bytes, so a descriptor that
    # spans the whole slot stands for every copy that filled it (one block
    # copy, or row_tile row copies); only its destination's size is read
    rows_ref = rows_vmem.at[slot]
    pltpu.make_async_copy(rows_ref, rows_ref, row_sem.at[slot]).wait()

    _accumulate(rows_ref, words_vmem, out_ref, tile_words=tile_words,
                words_per=words_per, tile_steps=tile_steps,
                col_tiles=col_tiles, row_tile=row_tile, hi=hi)


def hist6_fused(order: jnp.ndarray, panel: jnp.ndarray, start, cnt,
                n_cols: int, words_per: int, num_bins: int,
                row_tile: int = 512, num_row_tiles=None,
                contiguous: bool = False,
                interpret: bool = False) -> jnp.ndarray:
    """Fused-gather nibble histogram: order [NO] i32 row ids (the leaf's
    window lives at [start, start + cnt)), panel [tiles, R, 128] u32
    (pack_fused_panel layout, last row = sentinel) -> [6, n_cols, num_bins]
    f32.

    ``num_row_tiles`` is the grid length: a python int for a static grid,
    or a traced i32 scalar >= 1 (must equal ceil(max(cnt, 1) / row_tile))
    for the grower's dynamic-grid form.  ``start``/``cnt`` may be traced
    scalars either way.  The caller guarantees NO >= max(start + cnt)
    rounded down to IDX_ALIGN, plus fused_idx_fetch(row_tile): the aligned
    over-fetch may read that far past the window (the grower pads
    ``order`` with sentinel tail accordingly).

    ``contiguous`` is what a caller says that BUILT the window as the
    identity: ``start == 0`` and ``order[i] == i`` for i < cnt (the root:
    ``order0`` is an ``arange``).  The kernel then fetches a tile as one
    block of ``row_tile`` consecutive panel rows and reads neither
    ``order`` nor ``cnt``, so the grid is static and every panel row from
    ``cnt`` to ``num_row_tiles * row_tile`` is a sentinel row
    (``pack_fused_panel(..., row_multiple=row_tile)`` pads so).
    """
    assert 1 < num_bins <= FUSED_MAX_BINS, num_bins
    hi = fused_hi(num_bins)
    # a bin is read through a word's lane of 32 // words_per bits
    assert num_bins <= 1 << (32 // words_per), (num_bins, words_per)
    assert row_tile % ISSUE_UNROLL == 0, row_tile
    assert order.shape[0] >= fused_idx_fetch(row_tile), order.shape
    col_tiles, tile_cols = fused_col_tiles(n_cols, words_per)
    assert panel.shape[0] == col_tiles, (panel.shape, col_tiles)
    tile_steps = tile_cols // FUSED_COL_STEP
    sentinel = panel.shape[1] - 1
    if num_row_tiles is None:
        num_row_tiles = 1
    if contiguous:
        assert isinstance(num_row_tiles, int) \
            and num_row_tiles * row_tile <= panel.shape[1], (
                num_row_tiles, row_tile, panel.shape)
    sc = jnp.stack([jnp.asarray(start, jnp.int32),
                    jnp.asarray(cnt, jnp.int32)])
    out_shape = (col_tiles * tile_steps, NUM_CH * hi, STEP_LANES)
    # the output block stays in VMEM over the row grid (twice: Pallas
    # double-buffers it) beside the two slots of panel rows and a tile's
    # transpose; past the compiler's default the kernel asks for what it
    # holds and as much again as the default for the values of a step
    held = (2 * 4 * out_shape[0] * out_shape[1] * out_shape[2]
            + (2 * col_tiles + 1) * row_tile * LANES * 4)
    vmem_limit = (held + VMEM_DEFAULT
                  if held > VMEM_DEFAULT // 2 else None)
    out3d = pl.pallas_call(
        functools.partial(_hist_kernel_fused, sentinel=sentinel,
                          contiguous=contiguous,
                          tile_words=tile_cols // words_per,
                          words_per=words_per, tile_steps=tile_steps,
                          col_tiles=col_tiles, row_tile=row_tile, hi=hi),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_row_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(out_shape, lambda ri, sc: (0, 0, 0)),
            scratch_shapes=[pltpu.SMEM((fused_idx_fetch(row_tile),),
                                       jnp.int32),
                            pltpu.VMEM((2, col_tiles, row_tile, LANES),
                                       jnp.uint32),
                            pltpu.VMEM((LANES, row_tile), jnp.uint32),
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit),
    )(sc, order, panel)
    # [step, (ch, hi), (f, lo)] -> [ch, step * f, hi*16+lo], all in XLA (the
    # epilogue the retired gen-1 nibble form used, a step at a time); the
    # phantom columns of the even spread and the bins past the width drop
    # out here
    out5 = out3d.reshape(col_tiles * tile_steps, NUM_CH, hi,
                         FUSED_COL_STEP, NIB)
    return out5.transpose(1, 0, 3, 2, 4).reshape(
        NUM_CH, col_tiles * tile_cols, hi * NIB)[:, :n_cols, :num_bins]


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
