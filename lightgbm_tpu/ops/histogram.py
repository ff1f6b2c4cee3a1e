"""Histogram construction — the hottest op in GBDT training.

The reference builds per-leaf feature histograms with cache-tuned scatter-adds
(``src/io/dense_bin.hpp:66-132``) or an OpenCL local-memory atomic kernel
(``src/treelearner/ocl/histogram256.cl``).  TPUs have no fast random scatter,
so the native formulation is a one-hot × weights contraction on the MXU over a
*gathered row subset* — the grower gathers only the smaller child of each
split through its leaf-contiguous ``order`` array (the reference's
smaller-child trick, ``serial_tree_learner.cpp:326-404``), so the work per
split is proportional to the smaller child, not to the dataset:

* ``subset_histogram_fused`` (-> ``pallas_hist.hist6_fused``) — THE Pallas
  rung: the row gather happens INSIDE the kernel (per-tile DMA of indexed
  panel rows into VMEM) and the contraction is nibble-factorized, so
  neither the gathered [M, F] matrix nor the one-hot ever exists in HBM.
  It serves every width up to ``pallas_hist.FUSED_MAX_BINS`` (512, uint16
  bins past 256).
  Takes the leaf's ``order`` window + offset, not gathered rows.
  ``subset_histogram_fused_local`` is the same rung entered from inside
  the GSPMD shard_map island (per-shard row -> leaf partition instead of
  an order window).
* ``subset_histogram_segment`` — one ``segment_sum`` scatter-add over the
  combined (feature, bin) index; the default CPU path (fallback rungs,
  test mesh), where scatter lowers well.  ``subset_histogram_flat`` is
  its unchunked GSPMD sibling.
* ``subset_histogram_einsum`` — chunked f32 one-hot einsum; the
  MXU-shaped debug/parity oracle (``use_pallas=false`` on TPU).

The ladder is fused vs the XLA reference paths — the gen-1 pre-gathered
Pallas kernels (onehot/nibble over a staged [M, F] buffer) were retired in
round 9 when they stopped Mosaic-lowering and the fused kernel subsumed
their role (see pallas_hist.py).

Each histogram entry is ``(sum_gradients, sum_hessians, count)`` exactly like
the reference ``HistogramBinEntry`` (``include/LightGBM/bin.h:27-56``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.counters import counters as obs_counters
from ..utils import faults as faults_mod

NUM_STATS = 3     # (sum_grad, sum_hess, count)


def _maybe_inject_hist_fault(method: str, site: str) -> None:
    """Armed ``hist_fail`` injection point: dispatch (host/trace time)
    raises deterministically so the error-surface of the hottest op is
    testable on CPU (utils/faults.py)."""
    fi = faults_mod.get_faults()
    if fi.enabled and fi.fire("hist_fail"):
        raise faults_mod.InjectedFault(
            f"hist_fail: injected histogram dispatch failure "
            f"(method={method}, site={site})")


def on_tpu() -> bool:
    """Whether the default jax backend is a TPU (the one platform probe:
    every decision that depends on the chip asks here)."""
    return any(d.platform == "tpu" for d in jax.devices())


def _split_hi_lo(x: jnp.ndarray):
    """Split f32 into a (bf16 hi, bf16 lo) pair so a single-pass bf16 MXU
    matmul accumulates with ~f32 accuracy (hi + lo recombined after the dot).
    The one-hot operand is exact in bf16, so only the weights need splitting."""
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(x.dtype)).astype(jnp.bfloat16)
    return hi, lo


def subset_histogram_einsum(rows: jnp.ndarray, g: jnp.ndarray, h: jnp.ndarray,
                            c: jnp.ndarray, num_bins: int,
                            rows_per_chunk: int = 8192) -> jnp.ndarray:
    """Histogram of a gathered row subset: rows [M, F] int, g/h/c [M] f32
    (weights must be 0 for padding rows) -> [F, B, 3].

    f32 one-hot x weights einsum, chunked over rows so the one-hot tensor
    stays small.  This is the CPU / debugging path; the TPU path is the
    fused Pallas kernel (``pallas_hist.hist6_fused``)."""
    rows = rows.astype(jnp.int32)
    m, f = rows.shape
    b = num_bins
    w = jnp.stack([g, h, c], axis=-1)                   # [M, 3]
    chunk = min(rows_per_chunk, m)
    pad = (-m) % chunk
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
    n_chunks = (m + pad) // chunk
    rows_c = rows.reshape(n_chunks, chunk, f)
    w_c = w.reshape(n_chunks, chunk, NUM_STATS)

    def body(acc, args):
        rc, wc = args
        onehot = (rc[:, :, None] == lax.broadcasted_iota(jnp.int32, (1, 1, b), 2))
        part = jnp.einsum("mfb,mk->fbk", onehot.astype(wc.dtype), wc,
                          precision=lax.Precision.HIGHEST)
        return acc + part, None

    acc0 = jnp.zeros((f, b, NUM_STATS), dtype=w.dtype)
    acc, _ = lax.scan(body, acc0, (rows_c, w_c))
    return acc


def subset_histogram_segment(rows: jnp.ndarray, g: jnp.ndarray,
                             h: jnp.ndarray, c: jnp.ndarray,
                             num_bins: int,
                             rows_per_chunk: int = 2048) -> jnp.ndarray:
    """Histogram via scatter-add (``segment_sum``) over the combined
    (feature, bin) index — O(M·F) adds instead of the einsum's O(M·F·B)
    MACs.  This IS the reference's dense_bin.hpp:66-132 accumulation in
    XLA form; scatter lowers well on CPU (where the fallback rungs run)
    but poorly on TPU, which is exactly why the TPU path is the MXU
    one-hot contraction instead.  Chunked over rows (like the einsum
    path) so the transient [chunk·F, 3] update buffer stays cache-sized:
    measured on the 1-core bench host at 256k x 28 x 255, 2048 rows/chunk
    runs 1.6x faster than 16384 (95 vs 152 ns/row — the [chunk*F, 3]
    scatter source fits L2 next to the 85 KB accumulator; 4096 already
    regresses)."""
    rows = rows.astype(jnp.int32)
    m, f = rows.shape
    w = jnp.stack([g, h, c], axis=-1)                    # [M, 3]
    chunk = min(rows_per_chunk, m)
    pad = (-m) % chunk
    if pad:
        # padding rows: weight 0 into bin 0 — contributes nothing
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
    n_chunks = (m + pad) // chunk
    offsets = jnp.arange(f, dtype=jnp.int32)[None, :] * num_bins
    rows_c = rows.reshape(n_chunks, chunk, f)
    w_c = w.reshape(n_chunks, chunk, NUM_STATS)

    def body(acc, args):
        rc, wc = args
        idx = (rc + offsets).reshape(-1)
        vals = jnp.broadcast_to(wc[:, None, :], (chunk, f, NUM_STATS))
        part = jax.ops.segment_sum(vals.reshape(-1, NUM_STATS), idx,
                                   num_segments=f * num_bins)
        return acc + part, None

    acc0 = jnp.zeros((f * num_bins, NUM_STATS), dtype=w.dtype)
    if n_chunks == 1:
        # single-chunk windows (every sub-2048-row bucket of the deep-tree
        # tail): the scan machinery is pure overhead — unroll it.  The
        # ``acc0 +`` is kept so the float results stay bit-identical to
        # the scanned form (dropping it would turn a -0.0 bin sum into
        # the raw part's -0.0 vs the scan's 0.0 + -0.0 == 0.0).
        hist, _ = body(acc0, (rows_c[0], w_c[0]))
    else:
        hist, _ = lax.scan(body, acc0, (rows_c, w_c))
    return hist.reshape(f, num_bins, NUM_STATS)


def subset_histogram_flat(rows: jnp.ndarray, g: jnp.ndarray, h: jnp.ndarray,
                          c: jnp.ndarray, num_bins: int,
                          site: str = "split") -> jnp.ndarray:
    """UNCHUNKED scatter-add histogram — the GSPMD formulation
    (``parallel/gspmd.py``; docs/DISTRIBUTED.md).

    Same math as :func:`subset_histogram_segment` minus the row-chunking
    scan: under ``NamedSharding`` the scan's carried accumulator makes
    the XLA SPMD partitioner ALL-GATHER the row shards (measured: a
    ``s32[4,2048,8]`` all-gather at 8k x 8), while the flat single
    ``segment_sum`` partitions cleanly — each device scatters its own
    row shard into the (feature-sharded) output slice and the compiler
    inserts one shard-sized reduction.  The [M·F, 3] transient this
    re-widens is per DEVICE (M = rows/shard), which is exactly the
    regime the GSPMD path runs in."""
    obs_counters.inc("hist_dispatch", method="segment", site=site,
                     interpret=False)
    _maybe_inject_hist_fault("segment", site)
    rows = rows.astype(jnp.int32)
    m, f = rows.shape
    w = jnp.stack([g, h, c], axis=-1)                    # [M, 3]
    idx = (rows + jnp.arange(f, dtype=jnp.int32)[None, :] * num_bins)
    vals = jnp.broadcast_to(w[:, None, :], (m, f, NUM_STATS))
    hist = jax.ops.segment_sum(vals.reshape(-1, NUM_STATS),
                               idx.reshape(-1),
                               num_segments=f * num_bins)
    return hist.reshape(f, num_bins, NUM_STATS)


def subset_histogram_fused(order: jnp.ndarray, panel: jnp.ndarray,
                           start, cnt, n_cols: int, words_per: int,
                           num_bins: int, row_tile: int = 512,
                           num_row_tiles=None,
                           contiguous: bool = False,
                           interpret: bool = False,
                           site: str = "split") -> jnp.ndarray:
    """Fused rung: histogram a leaf's ``order`` window WITHOUT a separate
    gather pass — the kernel DMAs the indexed panel rows itself, or whole
    blocks of them where the caller built the window as the identity
    (``contiguous``; see hist6_fused for its contract).

    order [NO] i32 (window at [start, start + cnt); see hist6_fused for
    the tail-padding contract), panel [tiles, R, 128] u32
    (data/packing.py:pack_fused_panel) -> [n_cols, num_bins, 3] f32 with
    the reference (sum_grad, sum_hess, count) layout; gradients/hessians
    carry the bf16 hi/lo accuracy contract (counts exact)."""
    from .pallas_hist import fused_hi, hist6_fused
    # dispatch-identity evidence (trace-time, per call site): bench rungs
    # and decide_flips verify the label against this counter; ``width`` is
    # the histogram width the kernel was built at, ``hi`` its hi one-hot's
    # height (16 up to 256 bins)
    obs_counters.inc("hist_dispatch", method="fused", site=site,
                     interpret=bool(interpret),
                     col_tiles=panel.shape[0],
                     fetch="block" if contiguous else "rows",
                     width=num_bins, hi=fused_hi(num_bins))
    _maybe_inject_hist_fault("fused", site)
    h6 = hist6_fused(order, panel, start, cnt, n_cols, words_per, num_bins,
                     row_tile=row_tile, num_row_tiles=num_row_tiles,
                     contiguous=contiguous, interpret=interpret)
    return jnp.stack([h6[0] + h6[1], h6[2] + h6[3], h6[4]], axis=-1)


def subset_histogram_fused_local(row_leaf: jnp.ndarray, leaf_id,
                                 panel: jnp.ndarray, n_cols: int,
                                 words_per: int, num_bins: int,
                                 row_tile: int = 512,
                                 interpret: bool = False,
                                 site: str = "split") -> jnp.ndarray:
    """Fused rung, shard-local form for the GSPMD hybrid: the same kernel
    as :func:`subset_histogram_fused`, but entered from INSIDE a shard_map
    island where the leaf's membership lives as the row -> leaf partition
    (``row_leaf``) instead of a maintained order window.

    Returns the [n_cols, num_bins, 3] PARTIAL histogram over this shard's
    rows matching ``leaf_id``; the caller (parallel/gspmd.py) hands the
    cross-shard reduction to the SPMD partitioner."""
    from .pallas_hist import fused_hi, hist6_fused_local
    # dispatch-identity evidence: under shard_map this traces once for the
    # whole mesh, same as any other trace-time counter — observed_kernel()
    # and the census must still attribute the hybrid to the fused kernel
    obs_counters.inc("hist_dispatch", method="fused", site=site,
                     interpret=bool(interpret),
                     col_tiles=panel.shape[0], fetch="rows",
                     width=num_bins, hi=fused_hi(num_bins))
    _maybe_inject_hist_fault("fused", site)
    h6 = hist6_fused_local(row_leaf, leaf_id, panel, n_cols, words_per,
                           num_bins, row_tile=row_tile, interpret=interpret)
    return jnp.stack([h6[0] + h6[1], h6[2] + h6[3], h6[4]], axis=-1)


def subset_histogram(rows: jnp.ndarray, g: jnp.ndarray, h: jnp.ndarray,
                     c: jnp.ndarray, num_bins: int,
                     method: str = "auto",
                     site: str = "split") -> jnp.ndarray:
    """Dispatch a PRE-GATHERED subset histogram: rows [M, F] int, g/h/c [M]
    -> [F, B, 3].

    Only the XLA reference formulations live here (segment | einsum |
    auto): the fused Pallas rung takes an order window or a row -> leaf
    partition, not gathered rows, so it enters through
    :func:`subset_histogram_fused` / :func:`subset_histogram_fused_local`
    — by the time rows are gathered there is nothing left to fuse."""
    if method == "auto":
        method = "segment"
    # the RESOLVED method, per call site — trace-time counts that the
    # rung-honesty checks (bench.py / decide_flips.py) read back
    obs_counters.inc("hist_dispatch", method=method, site=site,
                     interpret=False)
    _maybe_inject_hist_fault(method, site)
    if method == "einsum":
        return subset_histogram_einsum(rows, g, h, c, num_bins)
    if method == "segment":
        return subset_histogram_segment(rows, g, h, c, num_bins)
    raise ValueError(f"unknown histogram method {method!r}")
