"""Small-bin column packing — the TPU answer to dense 4-bit bins.

The reference stores features with <= 16 bins nibble-packed
(``src/io/dense_nbits_bin.hpp:12-405``) and its GPU learner packs 8
features per int32 (``gpu_tree_learner.cpp:234-556``) because histogram
building is bandwidth-bound.  Here the same observation holds — the
per-leaf row gather of the binned matrix is the HBM roofline
(docs/PERF.md) — but the packing is designed around the MXU histogram
kernel instead of translated:

two physical columns a (lo) and b (hi), both with <= 16 bins, share one
byte ``v = a | (b << 4)``.  The byte value IS the joint (a, b) bin index
over a 16 x 16 grid, so the EXISTING 256-wide one-hot histogram kernels
(pallas / einsum / segment) run on packed columns UNCHANGED; the two
16-bin feature histograms fall out of the joint [256]-bin histogram by
summing over each nibble axis (``unfold_packed_hist``).  Per packed
pair this HALVES the gather bytes AND the histogram compute relative to
two unpacked uint8 columns at a 256-wide one-hot.

The packed matrix is a SECOND device copy used only by the histogram
path; routing/partition and leaf traversal keep the unpacked matrix
(they read single columns — decode would buy nothing).  Packed-pair
datasets are narrow by construction (<= 16-bin columns), so the extra
copy is small exactly when it exists.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

PACK_MAX_BIN = 16          # nibble capacity
PACK_JOINT_BINS = 256      # joint (lo, hi) index space
FUSED_COL_STEP = 32        # columns the fused kernel contracts per step of
#                            its loop over a tile: four 128-lane output
#                            groups of 8 features x 16 lo bins, whose bin
#                            words are 8 (uint8) or 16 (uint16) whole
#                            sublanes of the transposed tile


def pack_gather_words(mat):
    """[N, C] uint8/uint16 -> ([N, W] uint32, lanes_per_word).

    On TPU a random row gather costs per ELEMENT, not per byte (measured
    ~12.6 ns/elem on v5e through XLA's gather); packing 4 uint8 (or 2
    uint16) bin columns into each uint32 word cuts the gathered element
    count 4x (2x), and the unpack after the gather is a handful of
    shift/mask vector ops that XLA fuses into the consumer.  The same
    word layout is what the fused histogram kernel's in-kernel row
    DMA reads (ops/pallas_hist.hist6_fused)."""
    import jax.numpy as jnp
    n, c = mat.shape
    assert mat.dtype.itemsize <= 2, mat.dtype   # u32 words hold 4 u8 or 2 u16
    per = 4 if mat.dtype.itemsize == 1 else 2
    w = -(-c // per)
    m = jnp.pad(mat, ((0, 0), (0, w * per - c))).astype(jnp.uint32)
    m = m.reshape(n, w, per)
    packed = m[:, :, 0]
    for k in range(1, per):
        packed = packed | (m[:, :, k] << (k * (32 // per)))
    return packed, per


def unpack_gather_words(words, c: int, per: int):
    """[M, W] uint32 -> [M, C] int32 (inverse of :func:`pack_gather_words`)."""
    import jax.numpy as jnp
    shift = 32 // per
    mask = jnp.uint32((1 << shift) - 1)
    parts = [(words >> (k * shift)) & mask for k in range(per)]
    stacked = jnp.stack(parts, axis=-1).reshape(words.shape[0], -1)
    return stacked[:, :c].astype(jnp.int32)


FUSED_PANEL_LANES = 128    # one column tile of the panel: Mosaic DMA row
#                            slices must span whole 128-lane tiles, so a
#                            tile is one aligned [1, 128]-u32 burst (512 B
#                            — the HBM transaction class a random row read
#                            touches regardless of how few bytes it keeps)


def fused_col_tiles(n_cols: int, per: int):
    """(column tiles, columns per tile) of the fused panel for ``n_cols``
    histogram columns packed ``per`` to a word.

    A tile is FUSED_PANEL_LANES words: its columns' bin words, then the
    three weight words.  The kernel walks a tile in steps of
    FUSED_COL_STEP columns, so a tile holds a whole number of steps: at
    most 480 uint8 columns (120 words).  The columns are spread evenly
    over the fewest tiles (28 columns: one tile of 32; 2000: five of
    416)."""
    cap = (FUSED_PANEL_LANES - 3) * per // FUSED_COL_STEP * FUSED_COL_STEP
    tiles = max(1, -(-n_cols // cap))
    even = -(-n_cols // tiles)
    return tiles, -(-even // FUSED_COL_STEP) * FUSED_COL_STEP


def pack_fused_panel(bins_pad, gw_pad, hw_pad, cw_pad, row_multiple: int = 1):
    """The u32 row layout the fused histogram kernel DMAs per row:
    [N(+1), C] uint8/uint16 bins + three f32 weight columns ->
    ([tiles, R, 128] uint32, lanes_per_word), R = N(+1) rounded up to
    ``row_multiple`` with more sentinel (all-zero) rows: the kernel's
    block fetch reads whole row tiles, the last one past N.

    The columns are zero-padded to ``tiles * tile_cols``
    (:func:`fused_col_tiles`) BEFORE word packing, so the kernel's phantom
    features always read real, provably-zero words, and cut into tiles of
    FUSED_PANEL_LANES words each: a tile's bin words, then the f32 weights
    as bitcast u32 words (pure bitcasts — values are bit-identical through
    the panel; every tile carries its own copy, so a tile is a whole
    narrow panel), then zeros (the Mosaic DMA alignment above — 512 B a
    row and tile, the price of an aligned burst).  Callers pass
    SENTINEL-padded inputs: the last row must carry zero bins and zero
    weights, because the kernel redirects every past-the-count position
    to it."""
    import jax.numpy as jnp
    from jax import lax
    c = bins_pad.shape[1]
    tiles, tile_cols = fused_col_tiles(c, 4 // bins_pad.dtype.itemsize)
    if tiles * tile_cols > c:
        bins_pad = jnp.pad(bins_pad, ((0, 0), (0, tiles * tile_cols - c)))
    words, per = pack_gather_words(bins_pad)
    tile_words = tile_cols // per
    weights = [lax.bitcast_convert_type(w.astype(jnp.float32),
                                        jnp.uint32)[:, None]
               for w in (gw_pad, hw_pad, cw_pad)]
    # a tile at a time, each the two-dimensional concatenate-and-pad the
    # one-tile panel has always been: the v5e's compiler fuses that form
    # into one pass over the rows (built as one [N, tiles, 128]
    # concatenate it kept four 16-times padded byte planes of the bins
    # alive: 20 GB at 10.5M rows)
    pad_rows = -words.shape[0] % row_multiple
    panel = [jnp.pad(jnp.concatenate(
        [words[:, t * tile_words:(t + 1) * tile_words]] + weights, axis=1),
        ((0, pad_rows), (0, FUSED_PANEL_LANES - tile_words - 3)))
        for t in range(tiles)]
    return jnp.stack(panel), per


class PackPlan(NamedTuple):
    """Static (host) description of the packed layout.

    Maps each PHYSICAL column f of the logical binned matrix to its
    storage: ``byte_col[f]`` is its column in the packed matrix,
    ``shift[f]`` is 0 (lo nibble / unpacked) or 4 (hi nibble), and
    ``is_packed[f]`` says whether f shares its byte with a partner.
    """
    byte_col: np.ndarray       # [Fp] i32
    shift: np.ndarray          # [Fp] i32, 0 or 4
    is_packed: np.ndarray      # [Fp] bool
    num_storage_cols: int
    num_phys_cols: int

    @property
    def num_packed(self) -> int:
        return int(self.is_packed.sum())


def build_pack_plan(col_num_bins) -> Optional[PackPlan]:
    """Pairing plan over physical columns: columns with <= 16 bins are
    packed two-per-byte (an odd leftover keeps a byte to itself in the
    lo nibble); wider columns pass through.

    Returns None when packing would not pay: fewer than 2 packable
    columns, or the joint-form histogram is WIDER than the unpacked one
    — ``storage_cols * 256 > phys_cols * B`` (B = the histogram width
    the unpacked layout needs, i.e. the max column bins).  The single
    inequality covers both degenerate regimes: a couple of narrow
    columns among thousands of wide ones (the full-matrix second copy
    would buy ~nothing), and an all-narrow dataset whose unpacked
    histograms are tiny (B <= 16: a 256-bin joint psum/einsum would
    move up to 8x MORE than the 2 x 16 bins it replaces)."""
    nb = np.asarray(col_num_bins, dtype=np.int64)
    fp = len(nb)
    narrow = np.flatnonzero(nb <= PACK_MAX_BIN)
    if len(narrow) < 2:
        return None
    n_storage = (fp - len(narrow)) + (len(narrow) + 1) // 2
    if n_storage * PACK_JOINT_BINS > fp * int(nb.max()):
        return None
    wide = np.flatnonzero(nb > PACK_MAX_BIN)
    byte_col = np.zeros(fp, dtype=np.int32)
    shift = np.zeros(fp, dtype=np.int32)
    is_packed = np.zeros(fp, dtype=bool)
    c = 0
    for f in wide:
        byte_col[f] = c
        c += 1
    for i in range(0, len(narrow) - 1, 2):
        a, b = narrow[i], narrow[i + 1]
        byte_col[a] = byte_col[b] = c
        shift[b] = 4
        is_packed[a] = is_packed[b] = True
        c += 1
    if len(narrow) % 2:
        f = narrow[-1]
        byte_col[f] = c
        c += 1
    return PackPlan(byte_col, shift, is_packed, c, fp)


def pack_columns(binned: np.ndarray, plan: PackPlan) -> np.ndarray:
    """[N, Fp] binned matrix -> [N, C] packed storage matrix (same
    dtype; nibble pairs merged, other columns copied)."""
    n = binned.shape[0]
    out = np.zeros((n, plan.num_storage_cols), dtype=binned.dtype)
    for f in range(plan.num_phys_cols):
        shifted = (binned[:, f].astype(np.int32)
                   << int(plan.shift[f])).astype(binned.dtype)
        np.bitwise_or(out[:, plan.byte_col[f]], shifted,
                      out=out[:, plan.byte_col[f]])
    return out


def unfold_packed_hist(hist_c, plan: PackPlan, out_bins: int):
    """Joint storage-column histograms -> physical-column histograms.

    hist_c [C, B_joint >= 256, S] -> [Fp, out_bins, S]: a packed
    column's joint histogram reshaped to [16, 16] grids sums over the
    partner's axis to give each nibble feature's 16-bin histogram (the
    FixHistogram-style reconstruction, but exact — no parent needed);
    unpacked columns pass through."""
    import jax.numpy as jnp
    c, bj, s = hist_c.shape
    h4 = hist_c[:, :PACK_JOINT_BINS].reshape(c, PACK_MAX_BIN, PACK_MAX_BIN, s)
    lo_h = h4.sum(axis=1)                      # [C, 16, S] lo-nibble feature
    hi_h = h4.sum(axis=2)                      # [C, 16, S] hi-nibble feature
    byte_col = jnp.asarray(plan.byte_col)
    nib = jnp.where((jnp.asarray(plan.shift) == 0)[:, None, None],
                    lo_h[byte_col], hi_h[byte_col])        # [Fp, 16, S]
    if out_bins > PACK_MAX_BIN:
        nib = jnp.pad(nib, ((0, 0), (0, out_bins - PACK_MAX_BIN), (0, 0)))
    else:
        nib = nib[:, :out_bins]
    wide = hist_c[byte_col, :out_bins]
    if out_bins > bj:
        wide = jnp.pad(wide, ((0, 0), (0, out_bins - bj), (0, 0)))
    return jnp.where(jnp.asarray(plan.is_packed)[:, None, None], nib, wide)
