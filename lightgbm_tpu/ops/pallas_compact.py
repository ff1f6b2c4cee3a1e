"""Pallas two-pass compaction partition kernel.

The TPU answer to the cost class the reference never pays: its
``DataPartition::Split`` (src/treelearner/data_partition.hpp:94-146) is a
cache-resident two-pointer sweep, ~1 ns/row on a Xeon core; the XLA
translations measured on the v5e all sit in the per-element-random class
(rank scatter ~20 ns/elem; payload sort is many full-window passes).  This
kernel is the designed escape (docs/ROUND4_NOTES.md "parked design"): a
stable two-way compaction expressed as block-local one-hot permutation
matmuls on the MXU plus manually-sequenced dynamic-offset DMA writes —
all sequential HBM traffic, projected ~5 ns/row.

Shape contract: the window is a [size, CP] f32 matrix (size % 512 == 0)
whose columns are [left_mask, right_mask, rank_left, rank_right, order,
*payload_halves]; every value must be exactly representable in f32
(masks 0/1, block-local ranks < 512, order < 2**24, u32 payload split
into u16 halves by :func:`compact_window`, which the grower's
``partition_branch`` drives with the same packed-word/bitcast payload
marshalling the sort path uses).  The stable ranks are precomputed in
XLA so the kernel body is pure compare + matmul + DMA.

Algorithm (grid = (2 phases, size/512 blocks), sequential on TPU):

* XLA pre-pass computes per-(phase, block) output BASES: exclusive cumsum
  of per-block left counts; right bases offset by the total left count.
  Bases ride in as scalar prefetch.
* Each grid step loads its [512, CP] block, reads the phase's
  precomputed stable rank column, applies it as a [512, 512] one-hot
  permutation matmul (stability = cumsum order; exactness = one nonzero
  per output row in f32), and DMAs the full 512-row result to
  ``out[base : base+512]``.
* Garbage tails: each step writes all 512 rows, but bases ascend within a
  phase and the right phase starts at the total left count, so every
  step's tail is overwritten by its successor; the final <=512-row spill
  lands in the +512 scratch margin of the output buffer, and rows past
  ``cnt`` are restored by the caller's ``where(j < cnt, ...)`` merge.

The kernel never scatters and never reads HBM at a random address: all
input blocks are sequential reads, all output DMAs are sequential bursts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLK = 512       # rows per block; every gather-bucket size divides it
LANES = 128     # output DMA width must be a multiple of this (Mosaic)


def _compact_kernel(bases_ref, blk_ref, out_ref, scratch, sem):
    p = pl.program_id(0)            # 0 = lefts, 1 = rights
    k = pl.program_id(1)
    nb = pl.num_programs(1)
    blk = blk_ref[...]                                   # [BLK, CP]
    mask = jnp.where(p == 0, blk[:, 0], blk[:, 1])       # [BLK] 0/1 f32
    # block-local stable ranks are PRECOMPUTED in XLA and ride as columns
    # 2/3 — the kernel body is pure compare + dot + DMA, with no in-kernel
    # scan to lower (one less Mosaic surface; round-2 lesson)
    rank = jnp.where(p == 0, blk[:, 2], blk[:, 3]).astype(jnp.int32)
    # one-hot permutation: P[o, i] = (rank[i] == o) & mask[i]
    onehot = ((rank[None, :] ==
               lax.broadcasted_iota(jnp.int32, (BLK, BLK), 0))
              & (mask[None, :] > 0)).astype(jnp.float32)
    # only the DATA columns (4:) are permuted and written out — the mask
    # and rank columns are kernel inputs nobody reads back.  The output
    # width is zero-padded to a 128-lane multiple IN the kernel: Mosaic
    # rejects HBM slices whose minor dim is not tile-aligned ("Slice
    # shape along dimension 1 must be aligned to tiling (128)", proven
    # via v5e AOT compile), so the narrower no-pad form cannot lower.
    # HIGHEST pins the MXU to true-f32 contraction: the default precision
    # may run bf16 passes, which would truncate order ids > 2^16 and
    # payload halves — exactness, not speed, is the contract here
    data = blk[:, 4:]
    out_w = scratch.shape[1]
    if data.shape[1] < out_w:
        data = jnp.concatenate(
            [data, jnp.zeros((BLK, out_w - data.shape[1]), data.dtype)],
            axis=1)
    scratch[...] = jnp.dot(onehot, data,
                           preferred_element_type=jnp.float32,
                           precision=lax.Precision.HIGHEST)
    base = bases_ref[p * nb + k]
    copy = pltpu.make_async_copy(
        scratch, out_ref.at[pl.ds(base, BLK), :], sem)
    copy.start()
    # wait inside the same sequential grid step: successor steps must
    # observe this write before issuing theirs (the overwrite cascade)
    copy.wait()


def compact_pallas(mat: jnp.ndarray, bases: jnp.ndarray,
                   interpret: bool = False) -> jnp.ndarray:
    """mat: [size, CP] f32 with columns [left_mask, right_mask, rank_left,
    rank_right, *data] (data = order + payload halves); bases:
    [2 * size/512] i32 output row offsets per (phase, block).
    Returns [size + 512, ceil((CP-4)/128)*128] f32 — the permuted DATA
    columns, zero-padded to a lane-aligned width (a Mosaic DMA
    requirement); caller slices [:size] rows, reads the first CP-4
    columns, and merges tails.
    """
    size, cp = mat.shape
    assert size % BLK == 0 and cp > 4, (size, cp)
    out_w = -(-(cp - 4) // LANES) * LANES
    nb = size // BLK
    return pl.pallas_call(
        _compact_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(2, nb),
            in_specs=[pl.BlockSpec((BLK, cp), lambda p, k, bases: (k, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((BLK, out_w), jnp.float32),
                            pltpu.SemaphoreType.DMA],
        ),
        out_shape=jax.ShapeDtypeStruct((size + BLK, out_w), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(bases, mat)


def compact_window(win: jnp.ndarray, goes_left: jnp.ndarray,
                   valid: jnp.ndarray, payload_u32=(),
                   interpret: bool = False):
    """Stable two-way partition of a window by ``goes_left``.

    win: [size] i32 (values < 2**24); goes_left/valid: [size] bool with
    ``valid`` a prefix mask (j < cnt) and goes_left False outside it;
    payload_u32: extra u32 [size] columns permuted identically.

    Returns (new_win, new_payload_tuple, nl) where rows past the valid
    prefix keep their original values and ``nl`` is the left count (the
    kernel's base computation already pays for it — callers must not
    re-reduce).  Stability and output order match the rank-scatter
    partition bit-for-bit.
    """
    size = win.shape[0]
    gl = goes_left & valid
    gr = valid & ~goes_left
    glf = gl.astype(jnp.float32)
    grf = gr.astype(jnp.float32)
    # per-(phase, block) output bases: lefts pack from 0, rights from nl
    nb = size // BLK
    lcnt = glf.reshape(nb, BLK).sum(axis=1).astype(jnp.int32)
    rcnt = grf.reshape(nb, BLK).sum(axis=1).astype(jnp.int32)
    nl = lcnt.sum()
    lbase = jnp.cumsum(lcnt) - lcnt
    rbase = nl + jnp.cumsum(rcnt) - rcnt
    bases = jnp.concatenate([lbase, rbase])
    # block-local stable ranks, precomputed here so the kernel has no
    # in-kernel scan: global inclusive cumsum minus the block's exclusive
    # prefix, minus 1 (values < 512, f32-exact; garbage on non-side rows
    # is masked by the kernel's mask columns)
    # int32 cumsum: exact at any window size (an f32 running sum would
    # round past 2^24 rows and silently collide two output rows)
    csl = jnp.cumsum(gl.astype(jnp.int32))
    csr = jnp.cumsum(gr.astype(jnp.int32))
    rank_l = csl - jnp.repeat(lbase, BLK) - 1
    rank_r = csr - jnp.repeat(rbase - nl, BLK) - 1
    cols = [glf, grf, rank_l.astype(jnp.float32),
            rank_r.astype(jnp.float32), win.astype(jnp.float32)]
    for c in payload_u32:
        cu = c.astype(jnp.uint32)
        cols.append((cu & 0xffff).astype(jnp.float32))
        cols.append((cu >> 16).astype(jnp.float32))
    # the INPUT matrix is unpadded (BlockSpec reads are block-granular and
    # Mosaic pads vregs internally); the OUTPUT is lane-padded to 128
    # inside the kernel because Mosaic requires DMA slice widths aligned
    # to the tiling — a real write-amplification cost (128 f32/row vs
    # cp-4) that the on-chip A/B prices; it is the cost of lowering, not
    # a choice
    mat = jnp.stack(cols, axis=1)
    out = compact_pallas(mat, bases, interpret=interpret)[:size]
    new_win = jnp.where(valid, out[:, 0].astype(jnp.int32), win)
    new_payload = []
    for i in range(len(payload_u32)):
        lo = out[:, 1 + 2 * i].astype(jnp.uint32)
        hi = out[:, 2 + 2 * i].astype(jnp.uint32)
        merged = lo | (hi << 16)
        new_payload.append(jnp.where(valid, merged,
                                     payload_u32[i].astype(jnp.uint32)))
    return new_win, tuple(new_payload), nl
