"""Leaf-wise tree growing as a single jitted XLA program.

TPU-native re-design of ``SerialTreeLearner::Train``
(``src/treelearner/serial_tree_learner.cpp:152-205``):

* the reference's ``DataPartition`` index reordering is kept as-is on device:
  an index array ``order`` groups rows contiguously by leaf
  (``data_partition.hpp:94-146``); per split only the SPLITTING leaf's
  window of ``order`` is sliced out (a size of ``_partition_sizes``),
  routed, stably sorted left before right and written back — O(leaf) per
  split, exactly the reference's per-leaf partition cost, summing to
  O(N·log L) per tree instead of O(N·L); the few leaves that hold a good share of all
  rows are split by one sort of all N rows instead (``partition_dense``:
  a sort is cheaper an element than the window's read by row id);
* per split only the **smaller child** is histogrammed — its rows are
  gathered through ``order`` into a padded buffer of a static size chosen by
  ``lax.switch`` (``_bucket_sizes``: ~log2(N) compiled buckets) and reduced by
  a one-hot MXU matmul (Pallas kernel on TPU); the larger child is obtained
  by parent − smaller subtraction exactly like the reference
  (``serial_tree_learner.cpp:482-488``).  Per-leaf parent histograms live in
  an HBM pool ``hist_store [L, K, 128]`` — the reference's HistogramPool
  (``feature_histogram.hpp:429-597``) without the LRU, since HBM fits all
  leaves;
* the split loop is a ``lax.while_loop`` with all per-leaf state in fixed
  ``[num_leaves]`` arrays, so one compilation serves every tree and there
  are no host round-trips inside a tree;
* distribution hooks in via a strategy object (``SerialStrategy`` here,
  parallel variants in ``parallel.learner``) whose ``reduce_hist``/``find``
  methods insert XLA collectives — the data-parallel learner's ReduceScatter
  (``data_parallel_tree_learner.cpp:148-163``) collapses to a ``psum`` of
  the smaller-child histogram.

Output is a struct-of-arrays tree (same SoA layout as the reference ``Tree``,
``include/LightGBM/tree.h:20-370``) plus the final row→leaf map used for the
O(N) training-score update.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .data.packing import (PACK_JOINT_BINS, pack_fused_panel,
                           pack_gather_words, unfold_packed_hist,
                           unpack_gather_words)
from .obs.counters import counters as obs_counters
from .ops.histogram import (on_tpu, subset_histogram, subset_histogram_flat,
                            subset_histogram_fused)
from .ops.pallas_hist import FUSED_MAX_BINS, fused_idx_fetch
from .ops.split import (MISSING_NAN, MISSING_ZERO, SplitConfig, SplitResult,
                        best_split, leaf_output, make_fused_ctx)


class GrowerConfig(NamedTuple):
    """Static (compile-time) training params for one tree."""
    num_leaves: int = 31
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    max_bin: int = 256               # B: histogram width (max over features)
    hist_method: str = "auto"        # fused | einsum | segment | auto
                                     # (fused = the in-kernel-gather Pallas
                                     # rung; falls back to an XLA reference
                                     # rung when the layout cannot fuse)
    row_tile: int = 512              # Pallas grid: rows per block
    bucket_min_log2: int = 6         # smallest window size (_bucket_sizes)
    #                                  (64 rows: tail splits of deep trees
    #                                  stop paying kilobucket padding —
    #                                  round-7 leaves-sweep measurement)
    has_categorical: bool = False    # static: enables the categorical path
    has_missing: bool = True         # static: False skips the dir=+1 scan
    max_cat_threshold: int = 256
    max_cat_group: int = 64
    cat_smooth_ratio: float = 0.01
    min_cat_smooth: float = 5.0
    max_cat_smooth: float = 100.0
    hist_interpret: bool = False     # run the fused histogram kernel in
                                     # interpret mode — the off-TPU parity
                                     # path; never inferred, never on-chip
    split_find: str = "fused"        # best-split scan formulation: fused
                                     # (per-direction reductions right off
                                     # the hot histogram, loop-invariant
                                     # masks hoisted out of the grow loop)
                                     # | chain (the historical packed
                                     # [F, 2B, 4] candidate form — the
                                     # forced A/B baseline).  Bit-identical
                                     # trees either way (pinned).

    def split_config(self) -> SplitConfig:
        return SplitConfig(self.lambda_l1, self.lambda_l2, self.min_gain_to_split,
                           self.min_data_in_leaf, self.min_sum_hessian_in_leaf,
                           self.has_categorical, self.has_missing,
                           self.max_cat_threshold,
                           self.max_cat_group, self.cat_smooth_ratio,
                           self.min_cat_smooth, self.max_cat_smooth,
                           self.split_find)


class TreeArrays(NamedTuple):
    """Device-side SoA tree; mirrors the reference Tree fields (tree.h:316-370)."""
    num_leaves: jnp.ndarray       # scalar i32 (actual leaves grown)
    split_feature: jnp.ndarray    # [L-1] i32 (inner/used feature index)
    threshold_bin: jnp.ndarray    # [L-1] i32
    default_left: jnp.ndarray     # [L-1] bool
    left_child: jnp.ndarray       # [L-1] i32 (node index, or ~leaf if < 0)
    right_child: jnp.ndarray      # [L-1] i32
    split_gain: jnp.ndarray       # [L-1] f32
    internal_value: jnp.ndarray   # [L-1] f32
    internal_count: jnp.ndarray   # [L-1] f32
    leaf_value: jnp.ndarray       # [L] f32 (unshrunk)
    leaf_count: jnp.ndarray       # [L] f32
    leaf_parent: jnp.ndarray      # [L] i32
    leaf_depth: jnp.ndarray       # [L] i32
    is_cat: jnp.ndarray           # [L-1] bool: categorical decision node
    cat_bins: jnp.ndarray         # [L-1, B] bool: bins routed left


class FeatureMeta(NamedTuple):
    """Per-LOGICAL-feature static metadata as device arrays.

    With EFB (``data/bundling.py``) several logical features share one
    physical binned column; ``col``/``offset`` carry the decode maps
    (both None when the dataset is unbundled and columns are 1:1)."""
    num_bin: jnp.ndarray       # [E] i32
    missing_type: jnp.ndarray  # [E] i32 (0 none / 1 zero / 2 nan)
    default_bin: jnp.ndarray   # [E] i32
    is_categorical: jnp.ndarray  # [E] bool
    col: jnp.ndarray = None    # [E] i32 physical column (None: identity)
    offset: jnp.ndarray = None  # [E] i32 first bundle slot (-1: unbundled)


def decode_bundle_bin(raw, feat, meta: FeatureMeta):
    """Physical column bin -> logical sub-feature bin for feature ``feat``.

    Bundle slot layout (bundling.py): slot 0 = all-default; feature f owns
    slots [offset, offset + num_bin - 2] (its bins minus the default bin, in
    order).  Out-of-range slots mean "another feature is active" -> f sits in
    its default bin — the sparse-bin semantics of the reference FeatureGroup."""
    off = meta.offset[feat]
    nb = meta.num_bin[feat]
    db = meta.default_bin[feat]
    local = raw - off
    in_range = (local >= 0) & (local < nb - 1)
    sub = jnp.where(in_range, local + (local >= db).astype(raw.dtype), db)
    return jnp.where(off < 0, raw, sub)


WIDE_WIDTH_STEP = 32   # past 256 bins the width is a multiple of this


def layout_width(max_num_bin: int) -> int:
    """The histogram width (``GrowerConfig.max_bin``) a grower is built at
    for data whose widest column has ``max_num_bin`` bins: that many up to
    256; past 256 rounded up to a multiple of ``WIDE_WIDTH_STEP``.  A
    categorical column keeps the categories that cover 99 % of the rows its
    bin mapper sampled, so the same data binned from another sample keeps a
    bin or two more or fewer (278 to 279 of 297 categories on ``expo-cat``):
    without the step each such width is a grow program of its own, compiled
    anew.  The step's bins belong to no column, so no scan reads them."""
    if max_num_bin <= 256:
        return max_num_bin
    return -(-max_num_bin // WIDE_WIDTH_STEP) * WIDE_WIDTH_STEP


def fused_gate_reason(bins_dtype, weights_dtype, hist_width: int):
    """None when the fused-gather kernel can run on this layout, else the
    human-readable reason it cannot."""
    if jnp.dtype(bins_dtype).itemsize > 2:
        return f"bin dtype {jnp.dtype(bins_dtype)} is wider than 2 bytes"
    if jnp.dtype(weights_dtype) != jnp.float32:
        return f"weights dtype {jnp.dtype(weights_dtype)} is not float32"
    if hist_width > FUSED_MAX_BINS:
        return (f"histogram width {hist_width} exceeds the fused "
                f"kernel's limit {FUSED_MAX_BINS}")
    return None


def resolve_hist_method(use_pallas: bool, cpu_hist_method: str, bins_dtype,
                        weights_dtype, hist_width: int):
    """The histogram method a layout trains with, and why it is not the
    one that was asked for: ``(method, reason)``, ``reason`` None unless
    :func:`fused_gate_reason` refused ``fused``.

    The ONE place the kernel is chosen.  On the chip ``use_pallas`` asks
    for the fused Pallas kernel and ``use_pallas=false`` for the
    MXU-shaped ``einsum`` reference; off the chip ``cpu_hist_method`` is
    the method (tests put the interpreted fused kernel there).  A fused
    request the layout cannot serve resolves to the XLA reference of the
    backend, so that ``GrowerConfig.hist_method`` always names the kernel
    that runs; ``make_grower`` raises on a fused config it cannot serve."""
    reference = "einsum" if on_tpu() else "segment"
    wanted = (cpu_hist_method if not on_tpu()
              else "fused" if use_pallas else reference)
    if wanted != "fused":
        return wanted, None
    reason = fused_gate_reason(bins_dtype, weights_dtype, hist_width)
    return ("fused", None) if reason is None else (reference, reason)


def _row_leaf_from_intervals(order, leaf_start, leaf_cnt, n):
    """row -> leaf map recovered from the final leaf intervals of ``order``.

    ``leaf_start``/``leaf_cnt`` always partition positions [0, n) into
    disjoint per-leaf intervals, so the map is an interval lookup pushed
    through the ``order`` permutation, ONCE per tree, and with no lookup
    by N indices: on the v5e a gather or a scatter pays 6 to 9 ns an
    element where this two-operand sort moves one for 2.1 (10.5M rows:
    22.5 ms a tree where a gather and a scatter took 179; PERF.md section
    6, PR 33).

    *Leaf of a position*: the L intervals ranked by ``start``, at each
    start the step from the previous interval's leaf id to this one's
    (inactive leaves rank last and spill to slot ``n``), and a cumulative
    sum over ``[0, n)``: integer, exact.  *Row order*: ``order[:n]`` holds
    every row once, so it is a unique key and the plain two-operand sort
    by it IS the inverse permutation (asked for a stable sort XLA carries
    an ``iota`` as a third operand: :func:`partition_window`)."""
    with jax.named_scope("row_leaf"):
        obs_counters.inc("row_leaf_dispatch", impl="sort")
        L = leaf_start.shape[0]
        starts = jnp.where(leaf_cnt > 0, leaf_start, n)   # inactive -> n
        starts, leaf_ids = lax.sort(
            (starts, jnp.arange(L, dtype=jnp.int32)), num_keys=1)
        prev = jnp.concatenate([jnp.zeros((1,), jnp.int32), leaf_ids[:-1]])
        steps = jnp.zeros((n + 1,), jnp.int32).at[starts].add(
            leaf_ids - prev, indices_are_sorted=True,
            mode="promise_in_bounds")
        leaf_of_pos = jnp.cumsum(steps[:n])       # slot n is not read
        _, row_leaf = lax.sort((order[:n], leaf_of_pos), num_keys=1,
                               is_stable=False)
        return row_leaf


# past 2**24 a float32 sum of row counts rounds: a split's counts are a
# cumulative sum over the bins and the parent's count less it
_F32_EXACT_ROWS = 2 ** 24


def _leaf_rows(order, lsc, cw_pad, n):
    """Each leaf's in-bag rows on this shard, as integers: its window's
    length when every row is in the bag, else the bag flags of its window
    of ``order`` summed (a gather of N rows, paid under bagging alone)."""
    start, cnt = lsc[:, 0], lsc[:, 1]

    def bagged():
        flags = (cw_pad[order[:n]] > 0).astype(jnp.int32)
        csum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(flags)])
        return csum[start + cnt] - csum[start]
    return lax.cond(jnp.all(cw_pad[:n] > 0), lambda: cnt, bagged)


class _LoopState(NamedTuple):
    """Grow-loop carry.  The per-leaf split pool and the tree-in-progress
    travel as PACKED row matrices — one row write per updated leaf/node
    instead of one scatter per field (round-8 frontier packing: at 255
    leaves the ~30 per-split field scatters were a measurable slice of the
    fixed cost, and every extra carried-array scatter is copy-insertion
    surface).  ``TreeArrays`` is unpacked ONCE after the loop."""
    step: jnp.ndarray
    order: jnp.ndarray           # [N + tail] i32: row ids grouped by leaf
    rl: jnp.ndarray              # [N] i32: the leaf each row is in, dense
    #                              (what the partition's dense branch keys on)
    lsc: jnp.ndarray             # [L, 2] i32: (first position, local count)
    hist_store: jnp.ndarray      # [L, K, 128]: per-leaf histograms, a
    #                              leaf's K / 8 whole (8, 128) tiles
    #                              (pool_flat, pool_tiles)
    feat_ok: jnp.ndarray         # [L, E] bool: per-leaf is_splittable flags
    sgain: jnp.ndarray           # [L] f32: per-leaf best gain (the heap key)
    sf32: jnp.ndarray            # [L, 8] f32 split pool: left_sum_g,
    #                              left_sum_h, left_count, right_sum_g,
    #                              right_sum_h, right_count, left_output,
    #                              right_output
    si32: jnp.ndarray            # [L, 3] i32 split pool: feature,
    #                              threshold, default_left
    scat: jnp.ndarray            # [L] bool: categorical split ([0] when the
    #                              dataset has no categoricals)
    scatb: jnp.ndarray           # [L, B] bool: bins routed left ([0, 0])
    tnf: jnp.ndarray             # [L-1, 3] f32 nodes: split_gain,
    #                              internal_value, internal_count
    tni: jnp.ndarray             # [L-1, 5] i32 nodes: feature, threshold,
    #                              default_left, left_child, right_child
    tlf: jnp.ndarray             # [L, 2] f32 leaves: value, count
    tli: jnp.ndarray             # [L, 2] i32 leaves: parent, depth
    tcat: jnp.ndarray            # [L-1] bool: node is categorical ([0])
    tcatb: jnp.ndarray           # [L-1, B] bool: node cat_bins ([0, 0])


class SerialStrategy:
    """Single-device learner (SerialTreeLearner analogue).

    A strategy supplies the traced hooks that differ between the reference's
    tree learners (serial / data / feature / voting,
    ``src/treelearner/*tree_learner.cpp``); parallel variants live in
    ``lightgbm_tpu.parallel.learner``:

    * ``setup(bins, meta, feat_valid, num_cols) -> ctx`` — per-shard
      feature views (``num_cols``: the physical columns the histograms
      hold, the expansion map's width);
    * ``hist_bins(ctx, bins) -> [N, F_hist]`` — the matrix to histogram
      (feature-parallel shards slice their own columns);
    * ``reduce_hist(hist) -> hist`` — cross-shard reduction of a freshly
      measured histogram (data-parallel: ``psum``; voting: identity, its
      reduction happens selectively inside ``find``);
    * ``expand(ctx, hist, pg, ph, pc) -> hist`` — measured histograms
      over PHYSICAL (bundle) columns as ones over the logical columns the
      scan reads, a split's two children as one batch (EFB; called only on
      a bundled data set, under the device scope ``bundle_expand``);
    * ``find(ctx, hist, pg, ph, pc, feat_ok) -> (SplitResult, feat_ok')``
      — globally agreed best split (feature indices in the full/global
      numbering) plus the leaf's per-feature is_splittable flags, from
      the histogram ``expand`` returned.
      ``feat_ok`` [E] carries the PARENT leaf's flags: features it
      prunes are excluded from this scan, and from the whole subtree —
      the reference's feature-pruning heuristic
      (serial_tree_learner.cpp:406-417);
    * ``reduce_scalar(x)`` — global sums of row statistics;
    * ``row_shards()`` — the shards the rows are split over.
    """

    def __init__(self, cfg: "GrowerConfig"):
        self.cfg = cfg

    def setup(self, bins, meta: FeatureMeta, feat_valid, num_cols: int):
        maps = (make_expand_maps(meta, self.cfg.max_bin, num_cols)
                if meta.col is not None else None)
        scfg = self.cfg.split_config()
        # the fused scan's keep/candidate masks depend only on the feature
        # metadata — building them HERE hoists them out of the grow loop's
        # body (the chain path re-derives them every split)
        fctx = (make_fused_ctx(meta.num_bin, meta.missing_type,
                               meta.default_bin, self.cfg.max_bin, scfg)
                if scfg.split_find == "fused" else None)
        return (meta, feat_valid, maps, fctx)

    def hist_bins(self, ctx, bins):
        return bins

    def reduce_hist(self, hist):
        return hist

    def expand(self, ctx, hist, pg, ph, pc):
        return expand_bundle_hist(hist, pg, ph, pc, ctx[2])

    def find(self, ctx, hist, pg, ph, pc, feat_ok):
        meta, feat_valid, _, fctx = ctx
        return best_split(hist, pg, ph, pc, meta.num_bin,
                          meta.missing_type, meta.default_bin,
                          feat_valid & feat_ok, self.cfg.split_config(),
                          is_cat=meta.is_categorical, with_feat_ok=True,
                          fused_ctx=fctx)

    def reduce_scalar(self, x):
        return x

    def row_shards(self):
        return 1


def make_expand_maps(meta: FeatureMeta, num_bins: int, num_cols: int,
                     col_start=None):
    """The fixed map of a bundle histogram's physical slots onto the logical
    columns (FixHistogram in tensor form, dataset.cpp:749-768), from traced
    jnp ops over the meta at a static shape: ``num_cols`` physical columns
    of ``num_bins`` slots.

    ``col_start`` restricts the map to the window of ``num_cols`` physical
    columns from there (feature-parallel shards own a column slice,
    feature_parallel_tree_learner.cpp:31-50): slots are the local flat
    layout's and logical features outside the window are masked.
    Returns ``(dest, recon_dest, lo, hi, feat_in_window)``:

    * ``dest`` [num_cols * num_bins]: the logical ``e * B + b`` each flat
      slot feeds, or an index past the ``E * B`` table where it feeds nobody
      (a bundle's slot 0, slots past a column's bins, features outside the
      window), distinct for every slot so the move's indices stay unique;
    * ``recon_dest`` [E]: where a bundled feature's default bin goes (it has
      no slot: it is the node's totals less the feature's slots), else past
      the table as well;
    * ``lo``, ``hi`` [E]: the flat range of the feature's slots;
    * ``feat_in_window`` [E] bool, None for global maps."""
    b = jnp.arange(num_bins, dtype=jnp.int32)[None, :]          # [1, B]
    off = meta.offset[:, None]
    nb = meta.num_bin[:, None]
    db = meta.default_bin[:, None]
    c = meta.col[:, None]
    e_count = meta.col.shape[0]
    n_slots = num_cols * num_bins
    if col_start is not None:
        in_win = (c >= col_start) & (c < col_start + num_cols)
        c = c - col_start
    else:
        in_win = jnp.ones_like(c, bool)
    slot = off + b - (b > db).astype(jnp.int32)
    src = jnp.where(off < 0, c * num_bins + b,
                    c * num_bins + jnp.clip(slot, 0, num_bins - 1))
    valid = (b < nb) & in_win
    recon = (off >= 0) & (b == db) & valid
    e = jnp.arange(e_count, dtype=jnp.int32)[:, None]
    logical = e * num_bins + b                                  # [E, B]
    past = e_count * num_bins
    # one move of E * B entries, once a grow call: every kept (e, b) names
    # its source slot, and no two name the same one
    dest = (past + jnp.arange(n_slots, dtype=jnp.int32)).at[
        jnp.where(valid & ~recon, src, n_slots + logical)].set(
        logical, mode="drop", unique_indices=True)
    recon_dest = jnp.where(recon.any(axis=1, keepdims=True),
                           e * num_bins + db, past + n_slots + e)[:, 0]
    lo = jnp.maximum((c * num_bins + off)[:, 0], 1)             # [E]
    hi = jnp.maximum((c * num_bins + off + nb - 2)[:, 0], 1)
    if col_start is not None:
        lo = jnp.clip(lo, 1, n_slots - 1)
        hi = jnp.clip(hi, 1, n_slots - 1)
        return dest, recon_dest, lo, hi, in_win[:, 0]
    return dest, recon_dest, lo, hi, None


def expand_bundle_hist(hist, pg, ph, pc, maps):
    """[..., F_physical, B, 3] bundle histograms -> [..., E_logical, B, 3]
    (leading axes: histograms expanded together, a split's two children).

    The F_physical * B measured slots are moved into a zero-filled logical
    table by the fixed map of :func:`make_expand_maps`, and each bundled
    feature's default bin is set to parent - sum(own slots) in the same
    move: F_physical * B + E indices a histogram and plane, where a gather
    over the logical table took E * B (179,200 on ``expo`` for 2,560 slots,
    1,400 of them real bins).  The move is of single values into ONE flat
    table of ``[3, K, E, B]`` planes, bins minor, the form the scan reads:
    given windows of ``[3]`` or ``[K]`` values to move (what a plane axis
    or ``jax.vmap`` makes of it) the v5e's compiler lays the window's axis
    minor and pads it to 128 lanes, a table of 183.5 MB for 4.3 MB."""
    dest, recon_dest, lo, hi = maps[:4]
    *lead, fp, b, w = hist.shape
    e_count = recon_dest.shape[0]
    flat = hist.reshape(-1, fp * b, w)                          # [K, Fp*B, 3]
    k = flat.shape[0]
    cs = jnp.cumsum(flat, axis=1)
    range_sum = cs[:, hi] - cs[:, lo - 1]                       # [K, E, 3]
    parent = jnp.stack([jnp.asarray(pg, flat.dtype),
                        jnp.asarray(ph, flat.dtype),
                        jnp.asarray(pc, flat.dtype)], axis=-1)
    recon_val = parent.reshape(k, 1, w) - range_sum
    upd = jnp.moveaxis(jnp.concatenate([flat, recon_val], axis=1), 2, 0)
    idx = jnp.concatenate([dest, recon_dest])                   # [M]
    # plane p of histogram i is block p * K + i of the flat table; a move
    # that feeds nobody goes past the table, to a place of its own
    table = e_count * b
    block = jnp.arange(w * k, dtype=jnp.int32).reshape(w, k, 1) * table
    spare = w * k * table + jnp.arange(upd.size, dtype=jnp.int32).reshape(
        upd.shape)
    out = jnp.zeros((w * k * table,), flat.dtype).at[
        jnp.where(idx < table, block + idx, spare).reshape(-1)].set(
        upd.reshape(-1), mode="drop", unique_indices=True)
    return jnp.moveaxis(out.reshape(w, *lead, e_count, b), 0, -1)


def _set(arr, idx, value):
    return arr.at[idx].set(value)


# The ``jax.named_scope`` names of the grow programs (this file's and
# ``parallel/gspmd.py``'s) are what a device trace reads them by:
# ``partition`` (``part_route``, ``part_read``, ``part_sort``, ``part_dense``
# and ``bundle_decode`` inside it), ``histogram`` (``hist_root``),
# ``hist_pool``, ``split_find`` (``cat_scan`` inside it), ``bundle_expand``,
# ``row_leaf``, ``fused_panel``, ``node_tables``.  Bump when a scope is
# added, renamed or moved: the cache key ignores names.  jax strips an
# operation's metadata before it hashes a program for the persistent
# compile cache, so two
# programs that differ in their scopes alone share one cache entry and the
# later one runs with the earlier one's names; the program's own name IS in
# the key, so the jitted grow functions carry the revision in theirs.
SCOPE_REVISION = 3


def scoped_program_name(fn):
    """``fn`` renamed ``<its name>_s<SCOPE_REVISION>``: what ``jax.jit``
    names the program after (``jit_grow_tree_s3``).  ``grow_tree`` stays in
    the name: the benchmark finds the program by that substring."""
    fn.__name__ = fn.__qualname__ = f"{fn.__name__}_s{SCOPE_REVISION}"
    return fn


def route_goes_left(binf, meta: FeatureMeta, feat, thr, dleft,
                    has_categorical: bool = False, is_cat_l=None,
                    cat_row=None, max_bin: int = 0):
    """Left/right routing decision for rows with raw bin values ``binf``
    on a split (feature ``feat``, threshold ``thr``) — tree.h:257-313.

    ONE implementation shared by the windowed partition branches below and
    the GSPMD grower's whole-column routing (``parallel/gspmd.py``): the
    two paths must take bit-identical decisions, so the primitive sequence
    lives here once.  ``binf`` is the PHYSICAL bin column (bundle decode
    happens inside when the meta carries EFB maps)."""
    if meta.col is not None:  # EFB: physical slot -> logical bin
        with jax.named_scope("bundle_decode"):
            binf = decode_bundle_bin(binf, feat, meta)
    mt_f = meta.missing_type[feat]
    nb_f = meta.num_bin[feat]
    db_f = meta.default_bin[feat]
    is_missing = (((mt_f == MISSING_NAN) & (binf == nb_f - 1))
                  | ((mt_f == MISSING_ZERO) & (binf == db_f)))
    goes_left = jnp.where(is_missing, dleft, binf <= thr)
    if has_categorical:
        cat_go_left = bin_flags(cat_row, jnp.clip(binf, 0, max_bin - 1))
        goes_left = jnp.where(is_cat_l, cat_go_left, goes_left)
    return goes_left


def bin_flags(flags, binf):
    """``flags[binf]`` for a per-bin ``bool[B]`` table and in-range bins of
    any shape, without a gather per element: the table packed into
    ``ceil(B / 32)`` words, the word picked by a chain of selects, the bit by
    a shift.  The partition routes all N rows of the split column, and on
    the v5e the gather costs 7.8 ns a row even from 255 entries (82.1 ms a
    split at 10.5M rows, against 0.24 for the chain of 8 words that 255
    bins take: PERF.md section 5); 279 bins take a chain of 9."""
    nb = flags.shape[0]
    nw = -(-nb // 32)
    bits = jnp.pad(flags, (0, 32 * nw - nb)).reshape(nw, 32)
    words = jnp.sum(bits.astype(jnp.uint32)
                    << jnp.arange(32, dtype=jnp.uint32), axis=1)
    hi = binf >> 5
    word = jnp.broadcast_to(words[0], binf.shape)
    for k in range(1, nw):
        word = jnp.where(hi == k, words[k], word)
    return ((word >> (binf & 31).astype(jnp.uint32)) & 1).astype(bool)


def pack_row_bits(flags):
    """``bool[N]`` (or 0 / 1 words) -> ``uint32[M]``: row ``i``'s flag is
    bit ``i >> log2 M`` of word ``i & (M - 1)``, ``M`` the power of two
    that makes 32 planes cover ``N``.  A plane is a contiguous run of rows, so
    packing is one aligned slice per plane that holds rows, shifted and
    or-ed: elementwise, no relayout.  The flags are widened to words FIRST:
    sliced as bytes, the v5e's compiler computes them twice (its program
    for this, compiled here: 2.2M estimated cycles against 1.1M)."""
    n = flags.shape[0]
    m = 1 << (-(-n // 32) - 1).bit_length()
    planes = -(-n // m)
    flags = jnp.pad(flags.astype(jnp.uint32), (0, planes * m - n))
    words = flags[:m]
    for k in range(1, planes):
        words = words | (flags[k * m:(k + 1) * m] << k)
    return words


def take_row_bits(words, rows):
    """The flags of ``rows`` (in-bounds row ids) out of
    :func:`pack_row_bits`' table: one 32-bit gather and a shift."""
    m = words.shape[0]
    w = words.at[rows & (m - 1)].get(mode="promise_in_bounds")
    plane = rows >> (m.bit_length() - 1)
    return ((w >> plane.astype(jnp.uint32)) & 1).astype(bool)


def partition_window(order, start, cnt, size: int, left_bits):
    """Stable two-way partition of one leaf's window of ``order``
    (``DataPartition::Split``, data_partition.hpp:94-146).

    ``order`` is ``i32[N + tail]``: row ids grouped by leaf, then sentinel
    slots holding ``N``, enough of them that ``start + size`` stays inside
    (:func:`_order_tail`).  The window is the ``size`` (static) slots from
    ``start``, of which the first ``cnt`` are the leaf's rows;
    ``left_bits`` is :func:`pack_row_bits` of the ``bool[N]`` decision "this
    row goes left".  Returns ``(order, n_left)``: the leaf's rows that go
    left, in the sequence they had, then those that go right, in theirs;
    every slot outside ``[start, start + cnt)`` as it was.  Closes over
    nothing: this is the seam a different transport of the window replaces.

    The transport is ONE sort of the window and one slice written back.
    The key is unique, ``group * size + slot`` with the group left / right
    / past the leaf, so a plain two-operand sort IS the stable partition:
    asked for a stable sort of the three-valued key alone, XLA carries an
    ``iota`` through every pass as a third operand to break the ties
    (v5e, 10.5M rows: the sort 0.112 s a tree against 0.187; a cumsum, a
    rank and a scatter into ``order`` took 0.97; PERF.md section 6, PR 30)."""
    if 3 * size > 2 ** 31:
        raise ValueError(f"window of {size} slots: 3 * size overflows the "
                         f"int32 sort key")
    with jax.named_scope("part_read"):    # the read by row id, per slot
        win = lax.dynamic_slice(order, (start,), (size,))
        slot = jnp.arange(size, dtype=jnp.int32)
        valid = slot < cnt
        # slots past the leaf may hold the sentinel N: read row 0's bit there
        goes_left = take_row_bits(left_bits, jnp.where(valid, win, 0)) & valid
        nl = jnp.sum(goes_left.astype(jnp.int32))
        # slots past the leaf (the last group) are already contiguous at the
        # window's tail, so the sort returns them where they were
        key = slot + jnp.where(goes_left, 0,
                               jnp.where(valid, size, 2 * size))
    with jax.named_scope("part_sort"):    # the window's transport
        _, new_win = lax.sort((key, win), is_stable=False, num_keys=1)
        order = lax.dynamic_update_slice(order, new_win, (start,))
    return order, nl


def partition_dense(order, start, cnt, rl, left_leaf, right_leaf):
    """:func:`partition_window`'s result for a leaf of any size, with no
    read by row id: ONE one-operand sort over all ``n`` rows.

    ``rl`` is the dense row -> leaf vector AFTER the split, ``i32[n]``: the
    leaf's rows that go left carry ``left_leaf``, those that go right
    ``right_leaf``, every other row its own leaf.  The key is unique,
    ``group * n + row`` with the group left / right / any other leaf, so
    the sorted keys less their group's offset are the left child's rows
    ascending, then the right child's ascending, then the rest: the first
    ``cnt`` of them, placed at ``start``, are the leaf's new window, and
    every slot outside ``[start, start + cnt)`` comes back as it was, by a
    dense select over ``order``.  Nothing here depends on a window size:
    one branch serves every leaf above the window table
    (:func:`_partition_sizes`).

    **The invariant this rests on: every leaf's window of ``order`` ascends
    by row id.**  The root's is ``arange(n)``, a stable partition keeps
    both children's ascending, and nothing else writes ``order``.  So
    "the sequence they had" (:func:`partition_window`) IS ascending row id,
    and the two functions return the same ``order`` bit for bit
    (``tests/test_partition_window.py``).

    On the v5e a read by row id costs 7.13 ns a padded window slot
    whatever its operand's size and this sort 1.27 ns a row, so for a leaf
    that holds more than an eighth of all rows sorting all of them is the
    cheaper transport: under 14 ms a call at 10.5M rows where the
    root's window took 115 (PERF.md section 6, PR 35)."""
    n = rl.shape[0]
    if 3 * n > 2 ** 31:
        raise ValueError(f"{n} rows: 3 * n overflows the int32 sort key")
    total = order.shape[0]
    with jax.named_scope("part_dense"):
        group = jnp.where(rl == left_leaf, 0,
                          jnp.where(rl == right_leaf, 1, 2))
        nl = jnp.sum((group == 0).astype(jnp.int32))
        key = lax.sort(group * n + jnp.arange(n, dtype=jnp.int32),
                       is_stable=False)
        rows = key - jnp.where(key < n, 0, jnp.where(key < 2 * n, n, 2 * n))
        # rows[j] belongs at order[start + j]: a slice of the sorted rows,
        # padded on both sides, that starts ``start`` slots before them
        placed = lax.dynamic_slice(jnp.pad(rows, (n, total - n)),
                                   (n - start,), (total,))
        pos = jnp.arange(total, dtype=jnp.int32)
        order = jnp.where((pos >= start) & (pos < start + cnt), placed,
                          order)
    return order, nl


POOL_TILE = (8, 128)     # the v5e's f32 tile: sublanes, lanes


def pool_tiles(n_cols: int, num_bins: int) -> int:
    """K, the rows of 128 lanes that hold one leaf's histogram in the pool:
    its ``3 * n_cols * num_bins`` floats over 128, rounded up to a whole
    number of (8, 128) tiles."""
    sub, lanes = POOL_TILE
    return -(-(3 * n_cols * num_bins) // (sub * lanes)) * sub


def pool_flat(hist):
    """[..., F, B, 3] histograms -> [..., K, 128] slices of the per-leaf
    pool (K = :func:`pool_tiles`): one statistic's [F, B] plane after
    another, the tail past ``3 * F * B`` zeros.  The pool is carried as
    ``[L, K, 128]`` so that its layout is not the compiler's to choose and a
    leaf is whole tiles: as ``[L, F, B, 3]`` the v5e's compiler relays the
    WHOLE pool on every split (a 1.56 GB copy a split at 255 x 2000 x 255),
    and as ``[L, 3 * F * B]`` rows it tiles the leaf axis as the sublanes,
    so that one leaf is one sublane of every tile and each read or write of
    a leaf moves eight leaves' bytes."""
    lead = hist.shape[:-3]
    n_cols, num_bins = hist.shape[-3:-1]
    k = pool_tiles(n_cols, num_bins)
    flat = jnp.moveaxis(hist, -1, -3).reshape(lead + (-1,))
    flat = jnp.pad(flat, [(0, 0)] * len(lead)
                   + [(0, k * POOL_TILE[1] - flat.shape[-1])])
    return flat.reshape(lead + (k, POOL_TILE[1]))


def pool_hist(tiles, n_cols: int, num_bins: int):
    """[..., K, 128] pool slices (:func:`pool_flat`) -> the [..., F, B, 3]
    histograms, the zero tail dropped."""
    lead = tiles.shape[:-2]
    flat = tiles.reshape(lead + (-1,))[..., :3 * n_cols * num_bins]
    return jnp.moveaxis(flat.reshape(lead + (3, n_cols, num_bins)), -3, -1)


def pool_split(store, leaf, pair, hist_small):
    """One split's work on the per-leaf pool ``store [L, K, 128]``: leaf
    ``leaf``'s histogram read as one slice of whole tiles, the larger child
    as the parent less the smaller ``hist_small [F, B, 3]`` (the reference's
    subtraction, ``serial_tree_learner.cpp:482-488``), and both children
    written to the rows ``pair`` (smaller, larger) by ONE pair scatter.
    Returns the new store and the children's ``[2, F, B, 3]`` histograms in
    (smaller, larger) order.

    One scatter, not two ``dynamic_update_slice``s: a read-then-double-
    update chain on the carried pool made XLA:CPU clone all of it twice a
    split (docs/PERF.md round 7; pinned by tests/test_grow_jaxpr.py).  The
    children are ONE buffer behind an optimization barrier, read by the
    scan and by the write: without it XLA:CPU fuses the read and the
    subtraction into both, the scan's copy may then run after the write,
    and the pool is cloned every split.  Of the forms tried on the v5e at
    2000 columns that XLA:CPU does not clone the pool for, this one is the
    quickest end to end (PERF.md section 5)."""
    n_cols, num_bins = hist_small.shape[:2]
    parent = pool_hist(lax.dynamic_index_in_dim(store, leaf, axis=0,
                                                keepdims=False),
                       n_cols, num_bins)
    hist2 = lax.optimization_barrier(
        jnp.stack([hist_small, parent - hist_small]))
    tiles2 = jnp.stack([pool_flat(hist_small), pool_flat(hist2[1])])
    store = store.at[pair].set(tiles2, unique_indices=True,
                               mode="promise_in_bounds")
    return store, hist2


def pool_rows(res: SplitResult, axis: int):
    """SplitResult fields -> packed split-pool rows (f32, i32) — the
    round-8 frontier packing layout (``_LoopState.sf32``/``si32``)."""
    f32 = jnp.stack([res.left_sum_g, res.left_sum_h, res.left_count,
                     res.right_sum_g, res.right_sum_h,
                     res.right_count, res.left_output,
                     res.right_output], axis=axis)
    i32 = jnp.stack([res.feature, res.threshold,
                     res.default_left.astype(jnp.int32)], axis=axis)
    return f32, i32


def unpack_tree(num_leaves, tni, tnf, tlf, tli, tcat, tcatb,
                cfg: "GrowerConfig") -> TreeArrays:
    """Packed tree carriers -> the public :class:`TreeArrays` (one set of
    column slices, outside any loop); shared by every grower flavor."""
    L = cfg.num_leaves
    return TreeArrays(
        num_leaves=num_leaves,
        split_feature=tni[:, 0],
        threshold_bin=tni[:, 1],
        default_left=tni[:, 2].astype(bool),
        left_child=tni[:, 3],
        right_child=tni[:, 4],
        split_gain=tnf[:, 0],
        internal_value=tnf[:, 1],
        internal_count=tnf[:, 2],
        leaf_value=tlf[:, 0],
        leaf_count=tlf[:, 1],
        leaf_parent=tli[:, 0],
        leaf_depth=tli[:, 1],
        is_cat=(tcat if cfg.has_categorical
                else jnp.zeros((L - 1,), bool)),
        cat_bins=(tcatb if cfg.has_categorical
                  else jnp.zeros((L - 1, cfg.max_bin), bool)),
    )


def _depth_gate(res: SplitResult, leaf_depth, max_depth) -> SplitResult:
    """A leaf at depth d (root = 0) may be split iff d < max_depth
    (serial_tree_learner.cpp:326+ BeforeFindBestSplit guard)."""
    if max_depth <= 0:
        return res
    ok = leaf_depth < max_depth
    return res._replace(found=res.found & ok,
                        gain=jnp.where(ok, res.gain, -jnp.inf))


# Window sizes above 2^HALF_STEP_ABOVE_LOG2 come in half-steps.  On the v5e
# a partition branch costs 8.6 ns (the routing read's gather) + 1.1 to 2.9
# ns (the sort) a padded window element, on top of a dense pass over all N
# rows that the smallest window pays too (0.13 ms a split at 10.5M rows):
# a window of 2^13 is 0.09 ms, less than that pass, so finer sizes there
# could save a few ms of a tree while each size is one more branch to
# compile.  Above it the padding is what a split costs (PERF.md section 6,
# PR 30).
HALF_STEP_ABOVE_LOG2 = 13

# A padded window slot costs what this many rows of the dense branch cost,
# so the partition's window table ends at the last size of at most
# n / WINDOW_SLOT_COSTS_DENSE_ROWS slots and a larger leaf is partitioned
# by ONE sort over all n rows (``partition_dense``).  Measured on the v5e
# in the grow program (traced runs of the cells; PERF.md section 6, PR
# 35).  A window costs 7.13 ns a padded slot for its read by row id at
# every row count, and 0.86 to 2.0 for its sort: 8.5 ms at 1,048,576
# slots, 13.1 at 1,572,864, 115 at the root's 12,582,912.  The dense
# branch's one-operand sort costs 13.3 ms a call at 10.5M rows (1.27 ns a
# row; 12.75 at 10M), 1.77 at 2.27M (0.78), 0.225 at 400,000 (0.56), its
# passes under 0.7 ms more at 10.5M rows: a sort's passes grow with log n
# where the read's price stays, so a slot costs 6.3 dense rows at 10.5M,
# 9.4 at 2.27M and 12 at 400,000, and the two transports tie at 1,572,864
# slots, between 196,608 and 262,144, and at 32,768.  8 puts the tie's
# size on the dense side at 10.5M and 10M rows (one branch fewer to
# compile, and ``order``'s tail halved) and keeps one size too many at
# the two smaller row counts, which costs under 1 ms a tree there.
WINDOW_SLOT_COSTS_DENSE_ROWS = 8


def _bucket_sizes(cfg: "GrowerConfig", n: int):
    """The static, ascending table of window sizes covering [1, n]: one
    branch of the partition's ``lax.switch`` (``pbranches``, every cell)
    and of the XLA reference rungs' histogram gather (``branches``) each.

    Powers of two from ``2^bucket_min_log2``, and ``3 * 2^(k-1)`` between
    them above ``2^HALF_STEP_ABOVE_LOG2`` (mean padding 1.44 of the leaf
    below it, about 1.21 above).  The table ends at the first size that
    holds ``n``: a larger one could never be selected and would still be
    compiled, as the largest branch."""
    sizes, k = [], cfg.bucket_min_log2
    while True:
        sizes.append(1 << k)
        if k >= HALF_STEP_ABOVE_LOG2 and sizes[-1] < n:
            sizes.append(3 << (k - 1))
        if sizes[-1] >= n:
            return sizes
        k += 1


def _partition_sizes(cfg: "GrowerConfig", n: int):
    """The partition's window table: :func:`_bucket_sizes`' sizes up to the
    last one whose window branch is cheaper than the dense branch over all
    ``n`` rows (:data:`WINDOW_SLOT_COSTS_DENSE_ROWS`), and the smallest
    size in any case.  A leaf with more rows than the table's last size
    takes :func:`partition_dense`, the branch after the table's
    (``_bucket_index(cnt, sizes) == len(sizes)``)."""
    sizes = _bucket_sizes(cfg, n)
    return sizes[:1] + [s for s in sizes[1:]
                        if s * WINDOW_SLOT_COSTS_DENSE_ROWS <= n]


def _order_tail(sizes):
    """Sentinel slots ``order`` needs past its ``n`` rows so that no window
    is sliced out of bounds (``dynamic_slice`` would clamp its start and
    move the window).  A leaf of ``cnt`` rows ends at or before ``n`` and
    gets the smallest size that holds ``cnt``, so its window overhangs
    ``n`` by less than that size less the size below it: the tail is the
    widest step of the table, not its largest size (262,143 slots for
    the partition's 1,048,576 at 10.5M rows; the dense branch slices no
    window and needs none)."""
    return max([sizes[0]] + [b - a - 1 for a, b in zip(sizes, sizes[1:])])


def _bucket_index(scnt, sizes):
    """Index of the smallest bucket holding ``scnt`` rows, ``len(sizes)``
    where none does: exact integer comparisons against the static size
    table (a float log2 would mis-round near large powers of two and
    silently drop rows)."""
    return jnp.sum((scnt > jnp.asarray(sizes, jnp.int32)).astype(jnp.int32))


def make_grower(cfg: GrowerConfig, strategy=None, pack_plan=None,
                step_limit: bool = False) -> Callable:
    """Build the jittable ``grow_tree`` function.

    ``strategy`` selects the (distributed) learner; default is the
    single-device :class:`SerialStrategy`.  This mirrors the reference's
    ``CreateTreeLearner`` factory (tree_learner.cpp:9-33) with strategies in
    place of subclass overrides.

    ``step_limit=True`` prepends a traced ``max_steps`` i32 scalar to the
    returned function's signature and caps the split loop at that many
    steps — the per-step cost profiler (scripts/profile_grow_steps.py)
    times t(k) - t(k-1) over one compilation to get the step-index→ms
    curve.  Training never sets it.

    ``pack_plan`` (data/packing.py) switches the histogram path to a
    nibble-packed storage matrix, the dense_nbits_bin.hpp analogue: the
    returned function then takes an EXTRA second argument ``hist_bins``
    — the packed [N, C] matrix — while routing keeps reading the
    unpacked ``bins``.  Joint 256-bin histograms over the storage
    columns are unfolded to physical columns right after measurement,
    so everything downstream (hist store, parent subtraction, bundle
    expansion, split scan) is layout-agnostic.
    """
    L = cfg.num_leaves
    if strategy is None:
        strategy = SerialStrategy(cfg)
    hist_width = (max(PACK_JOINT_BINS, cfg.max_bin) if pack_plan is not None
                  else cfg.max_bin)

    def grow_impl(bins: jnp.ndarray,        # [N, F] uint8/uint16/int32
                  hist_src: jnp.ndarray,    # [N, C] histogram storage matrix
                  gw: jnp.ndarray,          # [N] f32   grad * bag_weight
                  hw: jnp.ndarray,          # [N] f32   hess * bag_weight
                  cw: jnp.ndarray,          # [N] f32   bag weight (0/1 or frac)
                  meta: FeatureMeta,
                  feat_valid: jnp.ndarray,  # [F] bool
                  max_steps=None            # profiler-only split-loop cap
                  ):
        n, f = bins.shape
        dtype = gw.dtype
        ctx = strategy.setup(hist_src, meta, feat_valid,
                             pack_plan.num_phys_cols if pack_plan is not None
                             else hist_src.shape[1])
        hbins = strategy.hist_bins(ctx, hist_src)    # [N, F_hist]
        fh = (pack_plan.num_phys_cols if pack_plan is not None
              else hbins.shape[1])

        # static window sizes: one gather branch each on the XLA reference
        # rungs (the whole table) and one partition branch each up to
        # where the dense branch is the cheaper one
        bsizes = _bucket_sizes(cfg, n)
        psizes = _partition_sizes(cfg, n)

        # ``fused_panel`` (the name the GSPMD grower gives its panel's
        # packing): what every tree builds anew out of arrays that change
        # with no tree (the bins) or only in their weights: the padded
        # copies, the column-major copy, the packed panel of either rung
        with jax.named_scope("fused_panel"):
            # sentinel row n: weight 0, bin 0 — receives all buffer padding
            hbins_pad = jnp.concatenate(
                [hbins, jnp.zeros((1, hbins.shape[1]), hbins.dtype)], axis=0)
            gw_pad = jnp.concatenate([gw, jnp.zeros((1,), dtype)])
            hw_pad = jnp.concatenate([hw, jnp.zeros((1,), dtype)])
            cw_pad = jnp.concatenate([cw, jnp.zeros((1,), dtype)])

            # column-major copy of the routing matrix, made once per tree
            # outside the split loop: each partition branch slices its split
            # column out of it
            bins_cm = bins.T
        n_hist_cols = hbins.shape[1]
        use_fused = cfg.hist_method == "fused"
        if use_fused:
            # the kernel DMAs the indexed panel rows itself: no gather
            # bucket ``branches`` are traced, nothing is gathered outside
            # the kernel.  The method was chosen by resolve_hist_method;
            # a config that names fused on a layout the kernel cannot
            # serve is the caller's error, not a second policy here
            reason = fused_gate_reason(hbins.dtype, dtype, hist_width)
            if reason is not None:
                raise ValueError(
                    f"hist_method=fused cannot run on this layout: {reason}")
            # rows padded to whole row tiles: the root fetches blocks
            with jax.named_scope("fused_panel"):
                fused_panel, fused_per = pack_fused_panel(
                    hbins_pad, gw_pad, hw_pad, cw_pad,
                    row_multiple=cfg.row_tile)
        # the XLA reference rungs read a split's rows by ONE row gather
        # where the layout allows it: a gather costs per index, not per
        # byte (12.6 ns a row for 28 bytes, the same for one f32 column),
        # so the bin columns packed into u32 words with the three f32
        # weight columns bitcast beside them ([N, W + 3] u32, pure
        # bitcasts) are one gather where rows and weights apart are four
        use_panel = (not use_fused and hbins.dtype.itemsize <= 2
                     and dtype == jnp.float32)
        if use_panel:
            with jax.named_scope("fused_panel"):
                hwords_pad, words_per = pack_gather_words(hbins_pad)
                n_words = hwords_pad.shape[1]
                panel = jnp.concatenate(
                    [hwords_pad]
                    + [lax.bitcast_convert_type(w, jnp.uint32)[:, None]
                       for w in (gw_pad, hw_pad, cw_pad)], axis=1)

        # the jax.named_scope names below are baked into the HLO: a device
        # trace attributes the per-split kernels to them (a host span here
        # would fire once, while jit traces).  SCOPE_REVISION lists them

        def find(hist, pg, ph, pc, feat_ok):
            # trace-time identity evidence (the hist_dispatch discipline):
            # bench rungs / decide_flips verify the split_find label
            # against this counter
            obs_counters.inc("split_find_dispatch", impl=cfg.split_find)
            with jax.named_scope("split_find"):
                return strategy.find(ctx, hist, pg, ph, pc, feat_ok)

        bundled = meta.col is not None

        def expand(hist, pg, ph, pc):
            """EFB: the measured [..., F_physical, B, 3] histograms as the
            [..., E_logical, B, 3] ones the scan reads (the children as ONE
            batch, not under ``jax.vmap``: ``expand_bundle_hist``).  A scope
            of its own, opened BESIDE ``split_find`` and never inside it: a
            trace charges an operation to the leftmost scope of its name."""
            obs_counters.inc("bundle_expand_dispatch", impl="slots",
                             logical=meta.num_bin.shape[0], physical=fh,
                             slots=fh * cfg.max_bin)
            with jax.named_scope("bundle_expand"):
                return strategy.expand(ctx, hist, pg, ph, pc)

        def hist_subset(rows, g_, h_, c_, site="split"):
            return subset_histogram(rows, g_, h_, c_, hist_width,
                                    method=cfg.hist_method, site=site)

        def hist_fused_window(order, sstart, scnt):
            """Fused rung: histogram the window [sstart, sstart + scnt) of
            ``order`` with a DYNAMIC grid — ceil(scnt / row_tile) tiles, so
            a small leaf costs a small kernel launch instead of a pow2
            bucket (the lax.switch this path retires)."""
            nt = jnp.maximum(1, (scnt + cfg.row_tile - 1) // cfg.row_tile)
            return subset_histogram_fused(
                order, fused_panel, sstart, scnt, n_hist_cols, fused_per,
                hist_width, row_tile=cfg.row_tile,
                num_row_tiles=nt.astype(jnp.int32),
                interpret=cfg.hist_interpret, site="split")

        def measure(idx):
            """RAW histogram of rows ``idx`` (sentinel-padded): packed
            storage columns stay in joint form so a cross-shard psum
            moves one 256-bin histogram per packed PAIR; ``globalize``
            unfolds after the reduction (unfolding is linear, so the
            order is correctness-neutral and bandwidth-positive)."""
            if use_panel:
                pan = panel.at[idx].get(mode="promise_in_bounds")
                rows = unpack_gather_words(pan[:, :n_words],
                                           hbins_pad.shape[1], words_per)
                g_, h_, c_ = (lax.bitcast_convert_type(pan[:, n_words + k],
                                                       jnp.float32)
                              for k in range(3))
                return hist_subset(rows, g_, h_, c_)
            rows = hbins_pad.at[idx].get(mode="promise_in_bounds")
            return hist_subset(rows, gw_pad[idx], hw_pad[idx], cw_pad[idx])

        def globalize(hist):
            """reduce across shards, then unfold packed columns."""
            hist = strategy.reduce_hist(hist)
            if pack_plan is not None:
                hist = unfold_packed_hist(hist, pack_plan, cfg.max_bin)
            return hist

        def bucket_branch(size):
            def branch(args):
                order, sstart, scnt = args
                idx = lax.dynamic_slice(order, (sstart,), (size,))
                valid = jnp.arange(size, dtype=jnp.int32) < scnt
                return measure(jnp.where(valid, idx, n))
            return branch

        # fused rung: no gather buckets are traced at all — the
        # staging switch exists only for the fallback rungs
        branches = None if use_fused else [bucket_branch(s) for s in bsizes]

        # ---- localized partition (DataPartition::Split,
        # data_partition.hpp:94-146).  The reference re-partitions only the
        # SPLITTING leaf's index range; the same here: the split column
        # is routed once, then a window branch slices the leaf's window
        # out of ``order`` and writes it back stably partitioned — O(leaf)
        # per split, not O(N) — and a leaf larger than the table's last
        # window takes the dense branch, one sort of all N rows, which is
        # the cheaper transport there (_partition_sizes).  Routing
        # decisions follow tree.h:257-313.

        def route(feat, thr, dleft, is_cat_l, cat_row):
            """``bool[N]``: the rows that go left.  The column is a
            dense slice of the column-major copy and its N decisions are
            elementwise (0.24 ms a split at 10.5M rows, paid by the
            smallest window too)."""
            col_idx = feat if meta.col is None else meta.col[feat]
            colv = lax.dynamic_index_in_dim(
                bins_cm, col_idx, axis=0, keepdims=False)
            return route_goes_left(
                colv.astype(jnp.int32), meta, feat, thr, dleft,
                has_categorical=cfg.has_categorical,
                is_cat_l=is_cat_l, cat_row=cat_row, max_bin=cfg.max_bin)

        def window_branch(size):

            def branch(args):
                # one bit per window row out of the routed column packed
                # 32 to a word: a table of N/8 bytes, small enough to
                # stay on chip while a rank-1 gather reads it by row id:
                # 7.1 to 8.6 ns an element on the v5e, where the (row,
                # col) byte gather this replaced read 20 from HBM, and
                # the column itself as s32[N], which the grow program
                # also keeps in HBM, 23.5 (scripts/probe_route_read.py;
                # PERF.md section 5)
                order, start, cnt, left_bits, _, _, _ = args
                obs_counters.inc("partition_route_dispatch", read="column",
                                 size=size)
                return partition_window(order, start, cnt, size, left_bits)
            return branch

        def dense_branch(args):
            order, start, cnt, _, rl, l, new_leaf = args
            obs_counters.inc("partition_route_dispatch", read="dense", size=n)
            return partition_dense(order, start, cnt, rl, l, new_leaf)

        pbranches = [window_branch(s) for s in psizes] + [dense_branch]

        # ---- root ----------------------------------------------------------
        root_g = strategy.reduce_scalar(jnp.sum(gw))
        root_h = strategy.reduce_scalar(jnp.sum(hw))
        root_c = strategy.reduce_scalar(jnp.sum(cw))

        # fused rung: the kernel's aligned index over-fetch may read up to
        # fused_idx_fetch(row_tile) past the window, so the sentinel tail
        # must cover that too (sentinel reads are harmless — they only
        # ever resolve to the zero-weight panel row)
        tail = (max(_order_tail(psizes), fused_idx_fetch(cfg.row_tile))
                if use_fused else _order_tail(bsizes))
        order0 = jnp.concatenate(
            [jnp.arange(n, dtype=jnp.int32),
             jnp.full((tail,), n, jnp.int32)])
        num_logical = meta.num_bin.shape[0]
        feat_ok_all = jnp.ones((num_logical,), bool)
        # ``hist_root`` inside ``histogram``: the one histogram over all n
        # rows, apart from the L - 1 per-split ones
        with jax.named_scope("histogram"), jax.named_scope("hist_root"):
            if use_fused:
                # the fused rung is SELF-CONTAINED: the root histogram goes
                # through the fused kernel too — it is the one
                # lowering-proven Pallas path (see test_mosaic_aot) — on a
                # static grid, and ``contiguous``: its window is the
                # ``arange`` that order0 was built from just above, so the
                # kernel fetches whole blocks of panel rows
                hist_root = globalize(subset_histogram_fused(
                    order0, fused_panel, 0, n, n_hist_cols, fused_per,
                    hist_width, row_tile=cfg.row_tile,
                    num_row_tiles=-(-n // cfg.row_tile), contiguous=True,
                    interpret=cfg.hist_interpret, site="root"))
            else:
                hist_root = globalize(hist_subset(hbins, gw, hw, cw,
                                                  site="root"))
        res_root, root_feat_ok = find(
            expand(hist_root, root_g, root_h, root_c) if bundled
            else hist_root, root_g, root_h, root_c, feat_ok_all)
        res_root = _depth_gate(res_root, jnp.asarray(0), cfg.max_depth)

        k_pool = pool_tiles(fh, cfg.max_bin)
        obs_counters.inc("hist_pool_layout", leaf_tiles=k_pool // POOL_TILE[0],
                         pad=k_pool * POOL_TILE[1] - 3 * fh * cfg.max_bin)
        hist_store0 = jnp.zeros((L, k_pool, POOL_TILE[1]), dtype)
        hist_store0 = hist_store0.at[0].set(pool_flat(hist_root))
        feat_ok_store0 = jnp.zeros((L, num_logical), bool).at[0].set(
            root_feat_ok)

        root_f32, root_i32 = pool_rows(res_root, 0)
        sgain0 = jnp.full((L,), -jnp.inf, res_root.gain.dtype).at[0].set(
            res_root.gain)
        sf32_0 = jnp.zeros((L, 8), dtype).at[0].set(root_f32)
        si32_0 = jnp.zeros((L, 3), jnp.int32).at[0].set(root_i32)
        if cfg.has_categorical:
            scat0 = jnp.zeros((L,), bool).at[0].set(res_root.is_cat)
            scatb0 = jnp.zeros((L, cfg.max_bin), bool).at[0].set(
                res_root.cat_bins)
            tcat0 = jnp.zeros((L - 1,), bool)
            tcatb0 = jnp.zeros((L - 1, cfg.max_bin), bool)
        else:   # statically absent: no categorical state is carried at all
            scat0 = jnp.zeros((0,), bool)
            scatb0 = jnp.zeros((0, 0), bool)
            tcat0 = jnp.zeros((0,), bool)
            tcatb0 = jnp.zeros((0, 0), bool)

        lsc0 = jnp.zeros((L, 2), jnp.int32).at[0, 1].set(n)
        tnf0 = jnp.zeros((L - 1, 3), dtype)
        tni0 = jnp.zeros((L - 1, 5), jnp.int32)
        tlf0 = jnp.zeros((L, 2), dtype).at[0, 1].set(root_c)
        tli0 = jnp.concatenate([jnp.full((L, 1), -1, jnp.int32),
                                jnp.zeros((L, 1), jnp.int32)], axis=1)

        def cond(state: _LoopState):
            ok = ((state.step < L - 1)
                  & (jnp.max(state.sgain) > 0.0))
            if max_steps is not None:
                ok = ok & (state.step < max_steps)
            return ok

        def body(state: _LoopState) -> _LoopState:
            i = state.step
            # ``node_tables``, here and twice below: the reads and writes
            # of the [L]-row tables a split costs beside its partition,
            # kernel, pool and split find
            with jax.named_scope("node_tables"):
                l = jnp.argmax(state.sgain).astype(jnp.int32)
                new_leaf = i + 1
                node = i
                pair_lr = jnp.stack([l, new_leaf])

                # one row read per pool instead of one gather per field
                irow = lax.dynamic_index_in_dim(state.si32, l, axis=0,
                                                keepdims=False)
                frow = lax.dynamic_index_in_dim(state.sf32, l, axis=0,
                                                keepdims=False)
                feat, thr = irow[0], irow[1]
                dleft = irow[2].astype(bool)

                # --- localized routing + stable partition of leaf l's
                #     window (only that leaf's slice of ``order`` is
                #     touched) ----------------------------------------------
                lrow = lax.dynamic_index_in_dim(state.lsc, l, axis=0,
                                                keepdims=False)
                start, cnt = lrow[0], lrow[1]
                cat_args = ((state.scat[l], state.scatb[l])
                            if cfg.has_categorical else (None, None))
            with jax.named_scope("partition"):
                # the split column routed ONCE, before the switch, into
                # one word a row: the window branches read the words as
                # bits, the dense branch as ``rl``, whose update is one
                # more pass over them.  The barrier keeps them ONE buffer:
                # without it the v5e's compiler routes the column a second
                # time for ``rl``, into a byte a row, at 0.58 ms a split
                # where the words take 0.24 (PERF.md section 6, PR 35).
                # ``part_route``: the pass over all N rows that the
                # smallest leaf's split pays too (entered twice, around
                # the branch index, so that the operations keep the order
                # they were traced in before they had this name)
                with jax.named_scope("part_route"):
                    went_left = lax.optimization_barrier(
                        route(feat, thr, dleft,
                              *cat_args).astype(jnp.uint32))
                    rl = jnp.where((state.rl == l) & (went_left == 0),
                                   new_leaf, state.rl)
                which = _bucket_index(cnt, psizes)
                with jax.named_scope("part_route"):
                    left_bits = pack_row_bits(went_left)
                order, nl = lax.switch(
                    which, pbranches,
                    (state.order, start, cnt, left_bits, rl, l, new_leaf))
            with jax.named_scope("node_tables"):
                nr = cnt - nl
                lsc = state.lsc.at[pair_lr].set(
                    jnp.stack([jnp.stack([start, nl]),
                               jnp.stack([start + nl, nr])]),
                    unique_indices=True, mode="promise_in_bounds")

                # --- record the node (Tree::Split, tree.h:319-345): one row
                #     write per packed table + one element write that relinks
                #     the parent's child pointer (the root split has no parent;
                #     its relink is redirected into row ``node``, which the
                #     full row write below overwrites) ----------------------
                prow = lax.dynamic_index_in_dim(state.tli, l, axis=0,
                                                keepdims=False)
                parent_node = prow[0]
                child_depth = prow[1] + 1
                pn_safe = jnp.where(parent_node >= 0, parent_node, node)
                side = jnp.where(state.tni[pn_safe, 3] == ~l, 3, 4)
                tni = state.tni.at[pn_safe, side].set(
                    node, mode="promise_in_bounds")
                tni = tni.at[node].set(
                    jnp.stack([feat, thr, irow[2], ~l, ~new_leaf]),
                    mode="promise_in_bounds")

                parent_g = frow[0] + frow[3]
                parent_h = frow[1] + frow[4]
                tnf = state.tnf.at[node].set(
                    jnp.stack([state.sgain[l],
                               leaf_output(parent_g, parent_h,
                                           cfg.lambda_l1, cfg.lambda_l2),
                               state.tlf[l, 1]]),
                    mode="promise_in_bounds")
                tlf = state.tlf.at[pair_lr].set(
                    jnp.stack([jnp.stack([frow[6], frow[2]]),
                               jnp.stack([frow[7], frow[5]])]),
                    unique_indices=True, mode="promise_in_bounds")
                tli = state.tli.at[pair_lr].set(
                    jnp.broadcast_to(jnp.stack([node, child_depth]), (2, 2)),
                    unique_indices=True, mode="promise_in_bounds")
                if cfg.has_categorical:
                    tcat = state.tcat.at[node].set(cat_args[0],
                                                   mode="promise_in_bounds")
                    tcatb = state.tcatb.at[node].set(cat_args[1],
                                                     mode="promise_in_bounds")
                else:
                    tcat, tcatb = state.tcat, state.tcatb

            # --- smaller-child histogram + parent subtraction ----------------
            # (the reference's smaller/larger trick,
            #  serial_tree_learner.cpp:326-404,482-488)
            small_left = frow[2] <= frow[5]
            sstart = jnp.where(small_left, start, start + nl)
            scnt = jnp.where(small_left, nl, nr)   # LOCAL count of that child
            with jax.named_scope("histogram"):
                if use_fused:
                    # the kernel gathers the window rows itself from the
                    # fused panel — no bucket switch, no staging buffer
                    hist_small = hist_fused_window(order, sstart, scnt)
                else:
                    ki = _bucket_index(scnt, bsizes[:-1])
                    hist_small = lax.switch(ki, branches,
                                            (order, sstart, scnt))
                hist_small = globalize(hist_small)
            # the pool's own work under one name: the parent's read, the
            # subtraction and the pair write.  Everything downstream runs in
            # (smaller, larger) order and is written back through the
            # PERMUTED pair index
            with jax.named_scope("hist_pool"):
                pair_sl = jnp.where(small_left, pair_lr, pair_lr[::-1])
                hist_store, hist2 = pool_split(state.hist_store, l, pair_sl,
                                               hist_small)

            # children scan only the features the PARENT found splittable
            # (serial_tree_learner.cpp:406-417 pruning heuristic).  Both
            # children go through ONE vmapped find: the candidate scan is
            # dozens of small ops on [E, B] arrays whose cost on TPU is
            # per-op launch, not math — batching the pair halves it
            with jax.named_scope("node_tables"):
                fok_parent = lax.dynamic_index_in_dim(
                    state.feat_ok, l, axis=0, keepdims=False)
                lr3 = jnp.stack([lax.slice(frow, (0,), (3,)),
                                 lax.slice(frow, (3,), (6,))])   # [2, 3]
                sl3 = jnp.where(small_left, lr3, lr3[::-1])
            # the pair is expanded as ONE batch, outside the vmap; the scan's
            # scope is entered OUTSIDE the vmap too: inside it alone the
            # children's scan is named ``vmap(split_find)``, which a trace's
            # scope pattern does not read as ``split_find``
            scan2 = (expand(hist2, sl3[:, 0], sl3[:, 1], sl3[:, 2])
                     if bundled else hist2)
            with jax.named_scope("split_find"):
                res2, fok2 = jax.vmap(find, in_axes=(0, 0, 0, 0, None))(
                    scan2, sl3[:, 0], sl3[:, 1], sl3[:, 2], fok_parent)
            res2 = _depth_gate(res2, child_depth, cfg.max_depth)
            with jax.named_scope("node_tables"):
                feat_ok = state.feat_ok.at[pair_sl].set(
                    fok2 & fok_parent[None, :], unique_indices=True)
                rows_f32, rows_i32 = pool_rows(res2, 1)
                sgain = state.sgain.at[pair_sl].set(
                    res2.gain, unique_indices=True, mode="promise_in_bounds")
                sf32 = state.sf32.at[pair_sl].set(
                    rows_f32, unique_indices=True, mode="promise_in_bounds")
                si32 = state.si32.at[pair_sl].set(
                    rows_i32, unique_indices=True, mode="promise_in_bounds")
                if cfg.has_categorical:
                    scat = state.scat.at[pair_sl].set(
                        res2.is_cat, unique_indices=True,
                        mode="promise_in_bounds")
                    scatb = state.scatb.at[pair_sl].set(
                        res2.cat_bins, unique_indices=True,
                        mode="promise_in_bounds")
                else:
                    scat, scatb = state.scat, state.scatb
            return _LoopState(i + 1, order, rl, lsc, hist_store,
                              feat_ok, sgain, sf32, si32, scat, scatb,
                              tnf, tni, tlf, tli, tcat, tcatb)

        state = _LoopState(jnp.asarray(0, jnp.int32), order0,
                           jnp.zeros((n,), jnp.int32), lsc0, hist_store0,
                           feat_ok_store0, sgain0, sf32_0, si32_0, scat0,
                           scatb0, tnf0, tni0, tlf0, tli0, tcat0, tcatb0)
        state = lax.while_loop(cond, body, state)
        tlf = state.tlf
        if n * strategy.row_shards() > _F32_EXACT_ROWS:
            # the split scan's counts have rounded: each leaf's rows again,
            # summed as integers over its window and over the shards
            with jax.named_scope("leaf_rows"):
                rows = strategy.reduce_scalar(
                    _leaf_rows(state.order, state.lsc, cw_pad, n))
                tlf = tlf.at[:, 1].set(rows.astype(dtype))
        # unpack the packed carriers into the public TreeArrays ONCE per
        # tree (a handful of column slices outside the loop)
        tree = unpack_tree(state.step + 1, state.tni, state.tnf, tlf,
                           state.tli, state.tcat, state.tcatb, cfg)
        row_leaf = _row_leaf_from_intervals(state.order, state.lsc[:, 0],
                                            state.lsc[:, 1], n)
        return tree, row_leaf

    if step_limit:
        # profiler entry: traced step cap first, unpacked layout only
        def grow_tree_limited(max_steps, bins, gw, hw, cw, meta, feat_valid):
            return grow_impl(bins, bins, gw, hw, cw, meta, feat_valid,
                             max_steps=max_steps)
        return scoped_program_name(grow_tree_limited)

    if pack_plan is None:
        # keep the historical 6-arg signature: histogram from the same
        # matrix routing reads
        def grow_tree(bins, gw, hw, cw, meta, feat_valid):
            return grow_impl(bins, bins, gw, hw, cw, meta, feat_valid)
        return scoped_program_name(grow_tree)

    def grow_tree_packed(bins, hist_bins, gw, hw, cw, meta, feat_valid):
        return grow_impl(bins, hist_bins, gw, hw, cw, meta, feat_valid)
    return scoped_program_name(grow_tree_packed)


class StreamedGrower:
    """Host-driven streamed grow loop (``data_stream=chunked``).

    The resident growers keep the whole split loop inside one jitted
    ``lax.while_loop`` because the binned matrix is device-resident.
    Out-of-core that is impossible — each split's smaller-child histogram
    needs a pass over ALL row blocks, and blocks arrive through the
    double-buffered :class:`~.data.stream.BlockStreamer` pipeline — so
    the loop moves to the HOST, built from four jitted pieces whose
    compilation count is static (the ``grower_jit_entries`` gauge pins
    the chunk loop at zero recompiles):

    * ``_block_step`` — routing + per-block partial histogram for ONE
      static-shape block: applies the pending split to the block's
      ``row_leaf`` slice (the exact :func:`route_goes_left` sequence the
      resident growers use), then masks the smaller child and
      scatter-adds its partial ``[F, B, 3]`` histogram into the carried
      accumulator.  Block partials accumulate in fixed block order, so
      trees are byte-identical to the resident path under
      order-insensitive (integer) weights — the same summation-order
      discipline the GSPMD path pins (``parallel/gspmd.py``);
    * ``_prep`` — reads the split pool and emits the pending split's
      parameters as device scalars (no host round-trip);
    * ``_root`` / ``_apply_split`` — the GSPMD body's bookkeeping minus
      the row ops: parent-subtraction, packed tree writes, the vmapped
      two-child ``best_split``, pool updates, and the continue flag —
      the ONE scalar the host reads per split;
    * ``_finalize`` — packed carriers -> :class:`TreeArrays` plus the
      per-block ``row_leaf`` vectors concatenated into the grow
      contract's ``[N]`` map.

    Call contract matches the serial grower's product with the
    device-resident matrix replaced by the streamer:
    ``grower(streamer, gw, hw, cw, meta, feat_valid) -> (TreeArrays,
    row_leaf)``.  Restrictions (gated loudly in ``boosting``): serial
    single-device, raw-bin layout only (no pack plan / fused panel —
    the per-tree weights those embed cannot be host-pre-packed ahead of
    the tree)."""

    def __init__(self, cfg: GrowerConfig):
        self.cfg = cfg
        L = cfg.num_leaves
        hist_width = cfg.max_bin

        def _expand(meta, hist, pg, ph, pc):
            # the children as ONE batch, outside the find's vmap
            # (``expand_bundle_hist``)
            if meta.col is None:
                return hist
            maps = make_expand_maps(meta, cfg.max_bin, hist.shape[-3])
            with jax.named_scope("bundle_expand"):
                return expand_bundle_hist(hist, pg, ph, pc, maps)

        def _find(meta, feat_valid, hist, pg, ph, pc, feat_ok):
            scfg = cfg.split_config()
            fctx = (make_fused_ctx(meta.num_bin, meta.missing_type,
                                   meta.default_bin, cfg.max_bin, scfg)
                    if scfg.split_find == "fused" else None)
            obs_counters.inc("split_find_dispatch", impl=cfg.split_find)
            with jax.named_scope("split_find"):
                return best_split(hist, pg, ph, pc, meta.num_bin,
                                  meta.missing_type, meta.default_bin,
                                  feat_valid & feat_ok, scfg,
                                  is_cat=meta.is_categorical,
                                  with_feat_ok=True, fused_ctx=fctx)

        def block_step(bins_blk, rl_blk, gp, hp, cp, start, meta,
                       l, new_leaf, feat, thr, dleft, cat_is, cat_row,
                       small_id, valid, acc):
            """Route the pending split over one block, then accumulate
            the smaller child's partial histogram.  ``l = -1`` (the root
            pass) matches no row, so routing is the identity and
            ``small_id = 0`` histograms every valid row at the root."""
            c_rows = bins_blk.shape[0]
            dtype = gp.dtype
            col_idx = feat if meta.col is None else meta.col[feat]
            binf = lax.dynamic_index_in_dim(
                bins_blk, col_idx, axis=1, keepdims=False).astype(jnp.int32)
            with jax.named_scope("partition"):
                goes_left = route_goes_left(
                    binf, meta, feat, thr, dleft,
                    has_categorical=cfg.has_categorical,
                    is_cat_l=cat_is if cfg.has_categorical else None,
                    cat_row=cat_row if cfg.has_categorical else None,
                    max_bin=cfg.max_bin)
                in_l = rl_blk == l
                rl_blk = jnp.where(in_l,
                                   jnp.where(goes_left, l, new_leaf),
                                   rl_blk)
            g_blk = lax.dynamic_slice(gp, (start,), (c_rows,))
            h_blk = lax.dynamic_slice(hp, (start,), (c_rows,))
            c_blk = lax.dynamic_slice(cp, (start,), (c_rows,))
            mask = ((rl_blk == small_id)
                    & (jnp.arange(c_rows, dtype=jnp.int32) < valid)
                    ).astype(dtype)
            with jax.named_scope("histogram"):
                part = subset_histogram_flat(bins_blk, g_blk * mask,
                                             h_blk * mask, c_blk * mask,
                                             hist_width, site="stream")
            return rl_blk, acc + part

        def root(hist_root, gp, hp, cp, meta, feat_valid):
            dtype = gp.dtype
            num_logical = meta.num_bin.shape[0]
            fh = hist_root.shape[0]
            root_g = jnp.sum(gp)
            root_h = jnp.sum(hp)
            root_c = jnp.sum(cp)
            res_root, root_feat_ok = _find(
                meta, feat_valid,
                _expand(meta, hist_root, root_g, root_h, root_c),
                root_g, root_h, root_c, jnp.ones((num_logical,), bool))
            res_root = _depth_gate(res_root, jnp.asarray(0), cfg.max_depth)
            hist_store0 = jnp.zeros((L, fh, cfg.max_bin, 3), dtype) \
                .at[0].set(hist_root)
            feat_ok0 = jnp.zeros((L, num_logical), bool).at[0].set(
                root_feat_ok)
            root_f32, root_i32 = pool_rows(res_root, 0)
            sgain0 = jnp.full((L,), -jnp.inf,
                              res_root.gain.dtype).at[0].set(res_root.gain)
            sf32_0 = jnp.zeros((L, 8), dtype).at[0].set(root_f32)
            si32_0 = jnp.zeros((L, 3), jnp.int32).at[0].set(root_i32)
            if cfg.has_categorical:
                scat0 = jnp.zeros((L,), bool).at[0].set(res_root.is_cat)
                scatb0 = jnp.zeros((L, cfg.max_bin), bool).at[0].set(
                    res_root.cat_bins)
                tcat0 = jnp.zeros((L - 1,), bool)
                tcatb0 = jnp.zeros((L - 1, cfg.max_bin), bool)
            else:
                scat0 = jnp.zeros((0,), bool)
                scatb0 = jnp.zeros((0, 0), bool)
                tcat0 = jnp.zeros((0,), bool)
                tcatb0 = jnp.zeros((0, 0), bool)
            tnf0 = jnp.zeros((L - 1, 3), dtype)
            tni0 = jnp.zeros((L - 1, 5), jnp.int32)
            tlf0 = jnp.zeros((L, 2), dtype).at[0, 1].set(root_c)
            tli0 = jnp.concatenate([jnp.full((L, 1), -1, jnp.int32),
                                    jnp.zeros((L, 1), jnp.int32)], axis=1)
            state = (sgain0, sf32_0, si32_0, scat0, scatb0, hist_store0,
                     feat_ok0, tnf0, tni0, tlf0, tli0, tcat0, tcatb0)
            cont = (L > 1) & (jnp.max(sgain0) > 0.0)
            return state, cont

        def prep(sgain, sf32, si32, scat, scatb, step):
            """The pending split's parameters as device scalars — fed
            straight into the block passes, no host read."""
            l = jnp.argmax(sgain).astype(jnp.int32)
            new_leaf = jnp.asarray(step + 1, jnp.int32)
            irow = lax.dynamic_index_in_dim(si32, l, axis=0, keepdims=False)
            frow = lax.dynamic_index_in_dim(sf32, l, axis=0, keepdims=False)
            small_left = frow[2] <= frow[5]
            small_id = jnp.where(small_left, l, new_leaf)
            if cfg.has_categorical:
                cat_is, cat_row = scat[l], scatb[l]
            else:
                cat_is = jnp.asarray(False)
                cat_row = jnp.zeros((cfg.max_bin,), bool)
            return (l, new_leaf, irow[0], irow[1], irow[2].astype(bool),
                    cat_is, cat_row, small_id)

        def apply_split(state, hist_small, i, meta, feat_valid):
            """Everything the GSPMD body does AFTER its histogram —
            parent subtraction, packed tree writes, the vmapped
            two-child find, pool updates — plus the continue flag the
            host reads once per split."""
            (sgain, sf32, si32, scat, scatb, hist_store, feat_ok,
             tnf, tni, tlf, tli, tcat, tcatb) = state
            l = jnp.argmax(sgain).astype(jnp.int32)
            new_leaf = jnp.asarray(i + 1, jnp.int32)
            node = jnp.asarray(i, jnp.int32)
            pair_lr = jnp.stack([l, new_leaf])
            irow = lax.dynamic_index_in_dim(si32, l, axis=0, keepdims=False)
            frow = lax.dynamic_index_in_dim(sf32, l, axis=0, keepdims=False)
            feat, thr = irow[0], irow[1]
            cat_args = ((scat[l], scatb[l]) if cfg.has_categorical else ())

            prow = lax.dynamic_index_in_dim(tli, l, axis=0, keepdims=False)
            parent_node = prow[0]
            child_depth = prow[1] + 1
            pn_safe = jnp.where(parent_node >= 0, parent_node, node)
            side = jnp.where(tni[pn_safe, 3] == ~l, 3, 4)
            tni = tni.at[pn_safe, side].set(node, mode="promise_in_bounds")
            tni = tni.at[node].set(
                jnp.stack([feat, thr, irow[2], ~l, ~new_leaf]),
                mode="promise_in_bounds")
            parent_g = frow[0] + frow[3]
            parent_h = frow[1] + frow[4]
            tnf = tnf.at[node].set(
                jnp.stack([sgain[l],
                           leaf_output(parent_g, parent_h,
                                       cfg.lambda_l1, cfg.lambda_l2),
                           tlf[l, 1]]),
                mode="promise_in_bounds")
            tlf = tlf.at[pair_lr].set(
                jnp.stack([jnp.stack([frow[6], frow[2]]),
                           jnp.stack([frow[7], frow[5]])]),
                unique_indices=True, mode="promise_in_bounds")
            tli = tli.at[pair_lr].set(
                jnp.broadcast_to(jnp.stack([node, child_depth]), (2, 2)),
                unique_indices=True, mode="promise_in_bounds")
            if cfg.has_categorical:
                tcat = tcat.at[node].set(cat_args[0],
                                         mode="promise_in_bounds")
                tcatb = tcatb.at[node].set(cat_args[1],
                                           mode="promise_in_bounds")

            small_left = frow[2] <= frow[5]
            hist_parent = lax.dynamic_index_in_dim(hist_store, l, axis=0,
                                                   keepdims=False)
            hist_large = hist_parent - hist_small
            hist2 = jnp.stack([hist_small, hist_large])
            pair_sl = jnp.where(small_left, pair_lr, pair_lr[::-1])
            hist_store = hist_store.at[pair_sl].set(
                hist2, unique_indices=True, mode="promise_in_bounds")

            fok_parent = lax.dynamic_index_in_dim(feat_ok, l, axis=0,
                                                  keepdims=False)
            lr3 = jnp.stack([lax.slice(frow, (0,), (3,)),
                             lax.slice(frow, (3,), (6,))])
            sl3 = jnp.where(small_left, lr3, lr3[::-1])
            res2, fok2 = jax.vmap(
                lambda h, pg, ph, pc, fo: _find(meta, feat_valid, h, pg,
                                                ph, pc, fo),
                in_axes=(0, 0, 0, 0, None))(
                _expand(meta, hist2, sl3[:, 0], sl3[:, 1], sl3[:, 2]),
                sl3[:, 0], sl3[:, 1], sl3[:, 2], fok_parent)
            res2 = _depth_gate(res2, child_depth, cfg.max_depth)
            feat_ok = feat_ok.at[pair_sl].set(fok2 & fok_parent[None, :],
                                              unique_indices=True)
            rows_f32, rows_i32 = pool_rows(res2, 1)
            sgain = sgain.at[pair_sl].set(
                res2.gain, unique_indices=True, mode="promise_in_bounds")
            sf32 = sf32.at[pair_sl].set(
                rows_f32, unique_indices=True, mode="promise_in_bounds")
            si32 = si32.at[pair_sl].set(
                rows_i32, unique_indices=True, mode="promise_in_bounds")
            if cfg.has_categorical:
                scat = scat.at[pair_sl].set(
                    res2.is_cat, unique_indices=True,
                    mode="promise_in_bounds")
                scatb = scatb.at[pair_sl].set(
                    res2.cat_bins, unique_indices=True,
                    mode="promise_in_bounds")
            cont = (new_leaf < L - 1) & (jnp.max(sgain) > 0.0)
            state = (sgain, sf32, si32, scat, scatb, hist_store, feat_ok,
                     tnf, tni, tlf, tli, tcat, tcatb)
            return state, cont

        def finalize(state, rl_blocks, num_leaves, n):
            (_, _, _, _, _, _, _,
             tnf, tni, tlf, tli, tcat, tcatb) = state
            tree = unpack_tree(jnp.asarray(num_leaves, jnp.int32), tni,
                               tnf, tlf, tli, tcat, tcatb, cfg)
            row_leaf = jnp.concatenate(list(rl_blocks))[:n]
            return tree, row_leaf

        self._block_step = jax.jit(block_step)
        self._root = jax.jit(root)
        self._prep = jax.jit(prep)
        self._apply_split = jax.jit(apply_split)
        # n selects the [:n] trim statically — a static argnum, not a
        # per-tree retrace (one dataset = one n)
        self._finalize = jax.jit(finalize, static_argnums=(3,))
        # reusable per-call constants (filled on first call)
        self._rl_zero = None
        self._acc_zero = None
        self._root_args = None

    def _cache_size(self) -> int:
        """Total compilation count over the streamed jit pieces — what
        the ``grower_jit_entries`` gauge reads (engine.py).  A chunk
        loop that recompiles shows up here immediately."""
        total = 0
        for fn in (self._block_step, self._root, self._prep,
                   self._apply_split, self._finalize):
            cs = getattr(fn, "_cache_size", None)
            if cs is not None:
                total += int(cs())
        return total

    def hlo_census(self, streamer, meta: FeatureMeta, feat_valid,
                   label: str = "grow"):
        """Compiled-HLO collective census summed over the streamed jit
        pieces at the training shapes — the single-device streamed
        program must add ZERO collectives (tests pin the census empty).
        After a training run the lowerings re-hit the jit cache, so this
        is a read, not a second compile."""
        from .obs.collectives import hlo_census as census
        cfg = self.cfg
        store = streamer.store
        chunk, ncols = store.chunk_rows, store.num_cols
        # committed like the training inputs, so these lowerings HIT the
        # training's cache entries instead of adding placement variants
        dev = streamer.device
        zr = jax.device_put(jnp.zeros((store.padded_rows,), jnp.float32),
                            dev)
        blk = jax.device_put(jnp.zeros((chunk, ncols), store.dtype), dev)
        rl = jax.device_put(jnp.zeros((chunk,), jnp.int32), dev)
        acc = jax.device_put(
            jnp.zeros((ncols, cfg.max_bin, 3), jnp.float32), dev)
        state, _ = self._root(acc, zr, zr, zr, meta, feat_valid)
        params = self._prep(state[0], state[1], state[2], state[3],
                            state[4], 0)
        lowered = (
            self._block_step.lower(blk, rl, zr, zr, zr, 0, meta, *params,
                                   chunk, acc),
            self._root.lower(acc, zr, zr, zr, meta, feat_valid),
            self._prep.lower(state[0], state[1], state[2], state[3],
                             state[4], 0),
            self._apply_split.lower(state, acc, 0, meta, feat_valid),
            self._finalize.lower(state, (rl,) * store.num_blocks, 1,
                                 store.num_rows),
        )
        out = {}
        for lw in lowered:
            for op, rec in census(lw.compile(), label=label).items():
                cur = out.setdefault(op, {"count": 0, "bytes": 0,
                                          "max_bytes": 0})
                cur["count"] += rec["count"]
                cur["bytes"] += rec["bytes"]
                cur["max_bytes"] = max(cur["max_bytes"], rec["max_bytes"])
        return out

    def __call__(self, streamer, gw, hw, cw, meta: FeatureMeta,
                 feat_valid):
        cfg = self.cfg
        L = cfg.num_leaves
        store = streamer.store
        n = store.num_rows
        chunk = store.chunk_rows
        np_rows = store.padded_rows
        pad = np_rows - n
        # every _block_step input is COMMITTED to the pipeline's device:
        # the jit cache keys on argument placement, so mixing committed
        # blocks with uncommitted zero constants / weight vectors forks
        # the compilation per combination — the zero-recompile pin
        # (grower_jit_entries) demands one stable signature
        dev = streamer.device
        if pad:
            gp = jnp.pad(gw, (0, pad))
            hp = jnp.pad(hw, (0, pad))
            cp = jnp.pad(cw, (0, pad))
        else:
            gp, hp, cp = gw, hw, cw
        gp, hp, cp = (jax.device_put(v, dev) for v in (gp, hp, cp))
        if self._rl_zero is None or self._rl_zero.shape[0] != chunk:
            self._rl_zero = jax.device_put(jnp.zeros((chunk,), jnp.int32),
                                           dev)
        if self._acc_zero is None \
                or self._acc_zero.shape[0] != store.num_cols:
            self._acc_zero = jax.device_put(
                jnp.zeros((store.num_cols, cfg.max_bin, 3), gw.dtype), dev)
        if self._root_args is None:
            # root-pass split params as committed device scalars so the
            # root and split passes share ONE block_step compilation
            # (Python ints would trace weakly-typed and fork the cache)
            self._root_args = jax.device_put(
                (jnp.asarray(-1, jnp.int32),      # l: matches no row
                 jnp.asarray(0, jnp.int32),       # new_leaf
                 jnp.asarray(0, jnp.int32),       # feat
                 jnp.asarray(0, jnp.int32),       # thr
                 jnp.asarray(False),              # dleft
                 jnp.asarray(False),              # cat_is
                 jnp.zeros((cfg.max_bin,), bool),  # cat_row
                 jnp.asarray(0, jnp.int32)), dev)  # small_id

        def pass_blocks(rl, params):
            """One full pass over the pipeline: route + accumulate the
            pending split's smaller-child histogram across all blocks
            in fixed block order (summation-order discipline)."""
            l, new_leaf, feat, thr, dleft, cat_is, cat_row, sid = params
            acc = self._acc_zero
            for k, dev_blk, valid in streamer.blocks():
                rl[k], acc = self._block_step(
                    dev_blk, rl[k], gp, hp, cp, k * chunk, meta,
                    l, new_leaf, feat, thr, dleft, cat_is, cat_row,
                    sid, valid, acc)
            return acc

        rl = [self._rl_zero] * store.num_blocks
        hist_root = pass_blocks(rl, self._root_args)
        state, cont = self._root(hist_root, gp, hp, cp, meta, feat_valid)
        step = 0
        # ONE host scalar read per split — the streamed analogue of the
        # resident while_loop's traced cond
        while step < L - 1 and bool(jax.device_get(cont)):
            params = self._prep(state[0], state[1], state[2], state[3],
                                state[4], step)
            hist_small = pass_blocks(rl, params)
            state, cont = self._apply_split(state, hist_small, step,
                                            meta, feat_valid)
            step += 1
        return self._finalize(state, tuple(rl), step + 1, n)
