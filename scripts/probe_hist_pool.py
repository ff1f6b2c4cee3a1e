"""Chip probe for the per-leaf histogram pool: what one split's pool work
costs (the parent's read, the subtraction, the pair write) in the two
layouts the grower has carried the pool in.

  flat    ``[L, 3 * F * B]``: a leaf one row, one statistic's [F, B] plane
          after another.  The v5e's compiler tiles such an array (8, 128)
          with the LEAF axis as the sublanes, so a leaf is one sublane of
          every tile
  tiled   ``[L, K, 128]``: the same floats in K rows of 128 lanes, K a
          multiple of 8, so a leaf is K / 8 whole tiles
          (``grower.pool_split``, the grower's own step)

Each form is a ``while_loop`` of L - 1 splits over a pool of L leaves
that starts full: split i reads leaf i // 2, takes a smaller child that
changes every split from it, writes both children to (i // 2, i + 1) in
an order that alternates, and hands the children's [2, F, B, 3]
histograms to a stand-in for the split scan (a prefix sum over the bins
and its largest value, carried).  ``none`` is the same loop with no pool
(the children made from the smaller child alone): the smaller child's
making and the stand-in's cost, to take off the other two.  Each program
is compiled ahead (compile seconds, temporaries, and whether its text
holds the pool as a ``[1, 3 * F * B]`` or ``[2, 3 * F * B]`` shape), then
run K times, one host clock around the K runs and one
``block_until_ready``, over K * (L - 1): milliseconds a split.

    python scripts/probe_hist_pool.py [columns] [bins] [leaves]

Default 2000 columns, 255 bins, 255 leaves (``epsilon``).  Writes one JSON
dict to stdout and to ``chiprun_out/probe_hist_pool_<F>x<B>.json``.  Off
the TPU it only checks that the two layouts give the same pool and the
same children bit for bit (pass a small width) and writes no timing.
"""
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from lightgbm_tpu.grower import pool_flat, pool_hist, pool_split

REPEATS = 5


def flat_split(store, leaf, pair, hist_small):
    """The step as the grower took it on the ``[L, 3 * F * B]`` pool."""
    n_cols, num_bins = hist_small.shape[:2]
    row = lax.dynamic_index_in_dim(store, leaf, axis=0, keepdims=False)
    parent = jnp.moveaxis(row.reshape(3, n_cols, num_bins), 0, -1)
    hist2 = jnp.stack([hist_small, parent - hist_small])
    rows = jnp.moveaxis(hist2, -1, -3).reshape(2, -1)
    store = store.at[pair].set(rows, unique_indices=True,
                               mode="promise_in_bounds")
    return store, hist2


def none_split(store, leaf, pair, hist_small):
    return store, jnp.stack([hist_small, hist_small + hist_small])


FORMS = {"flat": (flat_split, lambda h: jnp.moveaxis(h, -1, -3).reshape(
             h.shape[0], -1)),
         "tiled": (pool_split, pool_flat),
         "none": (none_split, lambda h: h[:1, :1, :1])}


def program(form, num_leaves):
    step, _ = FORMS[form]

    def run(store, base):
        def body(c):
            i, st, acc = c
            leaf = i // 2
            pair = jnp.where(i % 2 == 0, jnp.stack([leaf, i + 1]),
                             jnp.stack([i + 1, leaf]))
            small = base * (0.25 + 0.5 / (i + 2).astype(base.dtype))
            st, hist2 = step(st, leaf, pair, small)
            scan = jnp.max(lax.cumsum(hist2, axis=2))
            return i + 1, st, jnp.maximum(acc, scan)
        return lax.while_loop(lambda c: c[0] < num_leaves - 1, body,
                              (jnp.int32(0), store, jnp.float32(-1e30)))[1:]
    return jax.jit(run)


def pool_in_rows(text, width):
    """The compiled text's ``[1, 3FB]`` / ``[2, 3FB]`` shapes: the flat
    pool's rows, each padded from one or two sublanes to a tile."""
    return sorted(set(re.findall(rf"f32\[[12],{width}\]", text)))


def main():
    n_cols = int(sys.argv[1]) if len(sys.argv) > 1 else 2000
    num_bins = int(sys.argv[2]) if len(sys.argv) > 2 else 255
    num_leaves = int(sys.argv[3]) if len(sys.argv) > 3 else 255
    dev = jax.devices()[0]
    width = 3 * n_cols * num_bins
    res = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "columns": n_cols, "bins": num_bins, "leaves": num_leaves,
           "leaf_bytes": width * 4, "forms": {}}
    rng = np.random.default_rng(41)
    full = jnp.asarray(rng.random((num_leaves, n_cols, num_bins, 3),
                                  dtype=np.float32) + 1.0)
    base = jnp.asarray(rng.random((n_cols, num_bins, 3), dtype=np.float32))
    out = {}
    for form in FORMS:
        store = FORMS[form][1](full)
        t0 = time.perf_counter()
        exe = program(form, num_leaves).lower(store, base).compile()
        row = {"compile_s": time.perf_counter() - t0,
               "temp_bytes": int(exe.memory_analysis().temp_size_in_bytes),
               "pool_in_rows": pool_in_rows(exe.as_text(), width),
               "pool_bytes": int(store.size) * 4}
        got = exe(store, base)
        jax.block_until_ready(got)
        out[form] = got
        if dev.platform == "tpu":
            t0 = time.perf_counter()
            for _ in range(REPEATS):
                got = exe(store, base)
            jax.block_until_ready(got)
            ms = (time.perf_counter() - t0) / REPEATS * 1e3
            row.update(ms_per_loop=ms, ms_per_split=ms / (num_leaves - 1))
        res["forms"][form] = row
        print(f"{form:6s} " + " ".join(
            f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in row.items()), file=sys.stderr, flush=True)
    # the same pool and the same children's scan in both layouts
    flat = np.asarray(out["flat"][0]).reshape(num_leaves, 3, n_cols,
                                              num_bins)
    tiled = np.asarray(pool_hist(out["tiled"][0], n_cols, num_bins))
    assert np.array_equal(np.moveaxis(flat, 1, -1).view(np.uint32),
                          tiled.view(np.uint32))
    assert np.asarray(out["flat"][1]) == np.asarray(out["tiled"][1])
    res["same_bits"] = True
    if dev.platform == "tpu":
        none = res["forms"]["none"]["ms_per_split"]
        for form in ("flat", "tiled"):
            f = res["forms"][form]
            f["pool_ms_per_split"] = f["ms_per_split"] - none
            f["pool_ms_per_tree"] = f["pool_ms_per_split"] * (num_leaves - 1)
            # a split reads the parent and writes two children: three of
            # the leaf's rows at the least
            f["pool_gb_per_s"] = 3 * width * 4 / f["pool_ms_per_split"] / 1e6
        os.makedirs("chiprun_out", exist_ok=True)
        name = f"chiprun_out/probe_hist_pool_{n_cols}x{num_bins}.json"
        with open(name, "w") as fh:
            json.dump(res, fh, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
