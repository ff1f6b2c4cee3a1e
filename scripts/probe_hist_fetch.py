"""Chip probe for the fused histogram kernel's row fetch (ISSUE 28): where
the 35 ns a row of ``hist6_fused`` go.

Times ONE kernel call over a whole window at the benchmark's two data
shapes (10,500,000 x 28 and 400,000 x 2000, ``uint8`` bins, row tile 512)
and prints ns a row.  The kernel here is a copy of the library's fetch
stage with its parts made switchable; the arithmetic is the library's own
(``pallas_hist._accumulate``).  Forms, by name:

  parent      the kernel as it was before PR 28: one slot; a tile's index
              slice to SMEM, one descriptor a row started, then every
              descriptor rebuilt and waited for, then the arithmetic
  fetch       ``parent`` without the arithmetic: issue + 512 waits a tile
  issue       ``fetch`` with ONE wait a tile (a descriptor spanning the
              slot): what the 512 rebuilt waits cost is fetch - issue
  arith       the arithmetic alone: rows fetched for the first tile only
  one_wait    ``parent`` with the one wait               (ISSUE 28's (b))
  unrolled    ``one_wait``, issue loop unrolled by 8     ((b) + (c))
  ahead       ``unrolled`` on two slots, tile i + 1 issued before tile i
              is waited for                              ((a) + (b) + (c))
  ahead_waits ``ahead`` with 512 waits a tile            ((a) + (c))
  ahead32     ``ahead`` unrolled by 32: the library's form
  block       two slots, one block copy a tile (identity windows only)
  lib_rows / lib_block
              ``pallas_hist.hist6_fused`` itself, indexed and
              ``contiguous``: must read what ``ahead32`` / ``block`` read

(Two more cuts were timed with an earlier version of this file and did not
pay: the sentinel select taken out of the tiles that lie wholly under the
count, and a 2-D descriptor for a one-tile panel; PERF.md section 5 keeps
their readings.)

Windows: ``identity`` (``order = arange``: the root) and ``leaf`` (a sorted
random quarter of the rows: what a per-split call sees).  Every form that
fetches and computes is compared bit for bit with ``parent`` on the chip
before it is timed.

Writes one JSON dict to stdout and ``chiprun_out/probe_hist_fetch.json``.
Off the TPU (``JAX_PLATFORMS=cpu``) it runs the forms in interpret mode at
a small size, checks them equal and writes no timing.
"""
import faulthandler
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.data.packing import (FUSED_COL_STEP, fused_col_tiles,
                                       pack_fused_panel)
from lightgbm_tpu.ops import pallas_hist as ph

ROW_TILE = 512

#        name:        (fetch,  slots, waits,  unroll, arith)
FORMS = {
    "parent":      ("rows", 1, "rows", 1, True),
    "fetch":       ("rows", 1, "rows", 1, False),
    "issue":       ("rows", 1, "one", 1, False),
    "arith":       ("once", 1, "one", 1, True),
    "one_wait":    ("rows", 1, "one", 1, True),
    "unrolled":    ("rows", 1, "one", 8, True),
    "ahead":       ("rows", 2, "one", 8, True),
    "ahead_waits": ("rows", 2, "rows", 8, True),
    "ahead32":     ("rows", 2, "one", 32, True),
    "block":       ("block", 2, "one", 1, True),
}
COMPUTES_ALL = [k for k, v in FORMS.items() if v[4] and v[0] != "once"]


def _kernel(sc_ref, order_ref, panel_ref, out_ref, idx_smem, rows_vmem,
            words_vmem, idx_sem, row_sem, *, fetch, slots, waits, unroll,
            arith, sentinel, row_tile, acc):
    ri = pl.program_id(0)
    slot = ri % slots
    start, cnt = sc_ref[0], sc_ref[1]
    idx_len = ph.fused_idx_fetch(row_tile)

    def row_copy(tile, slot, i):
        # the parent's descriptor: the id read from SMEM, sentinel past cnt
        pos = start + tile * row_tile
        off = pos - (pos // ph.IDX_ALIGN) * ph.IDX_ALIGN
        r = jnp.where(tile * row_tile + i < cnt,
                      idx_smem[slot * idx_len + off + i], sentinel)
        return pltpu.make_async_copy(panel_ref.at[:, pl.ds(r, 1), :],
                                     rows_vmem.at[slot, :, pl.ds(i, 1), :],
                                     row_sem.at[slot])

    def start_tile(tile, slot):
        if fetch == "block":
            r0 = pl.multiple_of(tile * row_tile, row_tile)
            pltpu.make_async_copy(panel_ref.at[:, pl.ds(r0, row_tile), :],
                                  rows_vmem.at[slot], row_sem.at[slot]).start()
            return
        pos = start + tile * row_tile
        aligned = pl.multiple_of((pos // ph.IDX_ALIGN) * ph.IDX_ALIGN,
                                 ph.IDX_ALIGN)
        idx = pltpu.make_async_copy(
            order_ref.at[pl.ds(aligned, idx_len)],
            idx_smem.at[pl.ds(pl.multiple_of(slot * idx_len, idx_len),
                              idx_len)], idx_sem)
        idx.start()
        idx.wait()

        def trip(j, carry):
            for k in range(unroll):
                row_copy(tile, slot, j * unroll + k).start()
            return carry
        lax.fori_loop(0, row_tile // unroll, trip, 0)

    def wait_tile(tile, slot):
        if waits == "one":
            ref = rows_vmem.at[slot]
            pltpu.make_async_copy(ref, ref, row_sem.at[slot]).wait()
            return

        def trip(i, carry):
            row_copy(tile, slot, i).wait()
            return carry
        lax.fori_loop(0, row_tile, trip, 0)

    @pl.when(ri == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        if slots == 2 or fetch == "once":
            start_tile(0, 0)
        if fetch == "once":
            wait_tile(0, 0)

    if slots == 2:
        @pl.when(ri + 1 < pl.num_programs(0))
        def _ahead():
            start_tile(ri + 1, 1 - slot)
        wait_tile(ri, slot)
    elif fetch != "once":
        start_tile(ri, slot)
        wait_tile(ri, slot)

    if arith:
        acc(rows_vmem.at[slot], words_vmem, out_ref)


def probe_hist(form, order, panel, start, cnt, n_cols, num_row_tiles,
               row_tile=ROW_TILE, interpret=False):
    """``hist6_fused``'s raw ``[steps, 96, 512]`` block under ``form``."""
    fetch, slots, waits, unroll, arith = FORMS[form]
    col_tiles, tile_cols = fused_col_tiles(n_cols, 4)
    tile_steps = tile_cols // FUSED_COL_STEP
    acc = functools.partial(ph._accumulate, tile_words=tile_cols // 4,
                            words_per=4, tile_steps=tile_steps,
                            col_tiles=col_tiles, row_tile=row_tile,
                            hi=ph.NIB)     # uint8 bins: the 16-row hi one-hot
    out_shape = (col_tiles * tile_steps, ph.NUM_CH * ph.NIB, ph.STEP_LANES)
    held = (2 * 4 * out_shape[0] * out_shape[1] * out_shape[2]
            + (slots * col_tiles + 1) * row_tile * ph.LANES * 4)
    sc = jnp.stack([jnp.asarray(start, jnp.int32),
                    jnp.asarray(cnt, jnp.int32)])
    return pl.pallas_call(
        functools.partial(_kernel, fetch=fetch, slots=slots, waits=waits,
                          unroll=unroll, arith=arith,
                          sentinel=panel.shape[1] - 1, row_tile=row_tile,
                          acc=acc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(num_row_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(out_shape, lambda ri, sc: (0, 0, 0)),
            scratch_shapes=[
                pltpu.SMEM((slots * ph.fused_idx_fetch(row_tile),),
                           jnp.int32),
                pltpu.VMEM((slots, col_tiles, row_tile, ph.LANES),
                           jnp.uint32),
                pltpu.VMEM((ph.LANES, row_tile), jnp.uint32),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA((slots,))]),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(held + ph.VMEM_DEFAULT
                              if held > ph.VMEM_DEFAULT // 2 else None)),
    )(sc, order, panel)


def probe_hist6(form, order, panel, start, cnt, n_cols, num_bins,
                num_row_tiles, row_tile=ROW_TILE, interpret=False):
    """``probe_hist`` through the library's epilogue: what ``hist6_fused``
    returns, [6, n_cols, num_bins], for a form that fetches every tile and
    computes.  ``tests/test_fused_hist.py`` holds the library's pipeline to
    the ``parent`` form of this."""
    col_tiles, tile_cols = fused_col_tiles(n_cols, 4)
    out3d = probe_hist(form, order, panel, start, cnt, n_cols,
                       num_row_tiles, row_tile, interpret)
    out5 = out3d.reshape(-1, ph.NUM_CH, ph.NIB, FUSED_COL_STEP, ph.NIB)
    return out5.transpose(1, 0, 3, 2, 4).reshape(
        ph.NUM_CH, col_tiles * tile_cols, ph.NIB * ph.NIB
    )[:, :n_cols, :num_bins]


def make_inputs(n, f, seed):
    """A panel as the grower packs it (sentinel row, rows padded to whole
    tiles) and the two windows, all made on the device."""
    @jax.jit
    def build(key):
        kb, kg, kh = jax.random.split(key, 3)
        bins = jax.random.randint(kb, (n, f), 0, 255, jnp.int32).astype(
            jnp.uint8)
        g = jax.random.normal(kg, (n,), jnp.float32)
        h = jnp.abs(jax.random.normal(kh, (n,), jnp.float32))
        zrow = jnp.zeros((1, f), jnp.uint8)
        zw = jnp.zeros((1,), jnp.float32)
        return pack_fused_panel(
            jnp.concatenate([bins, zrow]), jnp.concatenate([g, zw]),
            jnp.concatenate([h, zw]),
            jnp.concatenate([jnp.ones((n,), jnp.float32), zw]),
            row_multiple=ROW_TILE)[0]
    panel = jax.block_until_ready(build(jax.random.PRNGKey(seed)))
    tail = np.full(ph.fused_idx_fetch(ROW_TILE), n, np.int32)
    rng = np.random.default_rng(seed)
    leaf = np.sort(rng.choice(n, size=n // 4, replace=False)).astype(np.int32)
    windows = {
        "identity": (jnp.asarray(np.concatenate(
            [np.arange(n, dtype=np.int32), tail])), n),
        "leaf": (jnp.asarray(np.concatenate(
            [leaf, np.full(n - leaf.size, n, np.int32), tail])), leaf.size),
    }
    return panel, windows


def timed(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def dump(res):
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_hist_fetch.json", "w") as fh:
        json.dump(res, fh, indent=1)


def run_shape(n, f, res, interpret, seed=28):
    panel, windows = make_inputs(n, f, seed)
    shape = f"{n}x{f}"
    for wname, (order, cnt) in windows.items():
        nt = -(-cnt // ROW_TILE)
        fns = {form: jax.jit(
            lambda o, p, form=form, c=cnt, nt=nt: probe_hist6(
                form, o, p, 0, c, f, 255, nt, interpret=interpret))
            for form in FORMS}
        fns["lib_rows"] = jax.jit(lambda o, p, c=cnt, nt=nt: ph.hist6_fused(
            o, p, 0, c, f, 4, 255, row_tile=ROW_TILE, num_row_tiles=nt,
            interpret=interpret))
        if wname == "identity":
            fns["lib_block"] = jax.jit(
                lambda o, p, c=cnt, nt=nt: ph.hist6_fused(
                    o, p, 0, c, f, 4, 255, row_tile=ROW_TILE,
                    num_row_tiles=nt, contiguous=True, interpret=interpret))
        else:
            del fns["block"]
        want = np.asarray(fns["parent"](order, panel))
        for form, fn in fns.items():
            # a wait that never comes back must end the call, not hold the
            # chip until the tool's time limit
            faulthandler.dump_traceback_later(300, exit=True)
            print(f"{shape} {wname} {form}", file=sys.stderr, flush=True)
            if form in COMPUTES_ALL or form.startswith("lib_"):
                got = np.asarray(fn(order, panel))
                assert np.array_equal(got, want), (shape, wname, form)
                res["identical"].append(f"{shape}.{wname}.{form}")
            if interpret:
                continue
            s = timed(fn, order, panel)
            res["ns_per_row"][f"{shape}.{wname}.{form}"] = s / cnt * 1e9
            res["ms_per_call"][f"{shape}.{wname}.{form}"] = s * 1e3
            print(f"{shape:14s} {wname:9s} {form:12s} {s / cnt * 1e9:9.3f} "
                  f"ns/row {s * 1e3:10.3f} ms/call", file=sys.stderr,
                  flush=True)
            dump(res)
        faulthandler.cancel_dump_traceback_later()


def main():
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    shapes = ([(10_500_000, 28), (400_000, 2000)] if on_chip
              else [(3 * ROW_TILE + 7, 28), (2 * ROW_TILE + 7, 600)])
    if len(sys.argv) > 1:
        shapes = [tuple(int(x) for x in a.split("x")) for a in sys.argv[1:]]
    res = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "row_tile": ROW_TILE, "identical": [], "ns_per_row": {},
           "ms_per_call": {}}
    for n, f in shapes:
        run_shape(n, f, res, interpret=not on_chip)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
