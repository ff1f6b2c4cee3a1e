"""Chip probe for the partition's routing read (ISSUE 26): what one split
pays to fetch the split column's bin for every row of its window.

Times the read ALONE, per element, at the benchmark's data shape
(10,500,000 x 28 uint8) for sorted ``idx`` windows of several buckets and
densities, in the three forms that are the finding:

  2d        ``bins[idx, col]`` on the row-major ``u8[N, F]`` matrix (the
            two-dimensional gather the grower had before PR 26)
  b         ``bins_cm[col]`` sliced from a column-major copy ``u8[F, N]``,
            converted to ``s32[N]``, then a rank-1 gather: the best form
            of ISSUE 26's three here, and slower than ``2d`` once wired
            into the grower, which leaves its 42 MB column in HBM
  bits      the WHOLE column decided first (``<= thr``, elementwise), the
            N decisions packed 32 to a word (``grower.pack_row_bits``) and
            one word gathered per window row (``take_row_bits``): the form
            the grower kept.  Its table is N/8 bytes, on chip here and in
            the grow program.  Every split pays the dense pass over N
            rows, so the small buckets say where it stops paying.
  bits_cat  ``bits`` with a categorical split's decision for all N rows,
            through ``grower.bin_flags`` as ``route_goes_left`` takes it

(ISSUE 26's forms (a), (c) and a packed (c'), and ``bits_cat`` with
``cat_row[bin]`` as a gather, were timed with earlier cuts of this file;
PERF.md section 5 keeps their readings.)  Each timing is
one jitted ``fori_loop`` of K reads whose column depends on the loop
counter (nothing hoists), one host clock around it, divided by K.

Writes one JSON dict to stdout and to ``chiprun_out/probe_route_read.json``.
Off the TPU it only checks the forms equal (pass a small row count) and
writes no timing.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from lightgbm_tpu.grower import bin_flags, pack_row_bits, take_row_bits


def cat_table(k):
    """A 255-bin left set that follows the loop counter."""
    return (jnp.arange(255) * 7 + k) % 3 == 0


def goes_left(form, src, idx, col, n, thr):
    """The routing decision of one split for the window's rows."""
    i = jnp.minimum(idx, n - 1)
    if form == "2d":
        return src.at[i, col].get(
            mode="promise_in_bounds").astype(jnp.int32) <= thr
    colv = lax.dynamic_index_in_dim(src, col, axis=0, keepdims=False)
    if form == "b":
        return colv.astype(jnp.int32).at[i].get(
            mode="promise_in_bounds") <= thr
    if form == "bits":
        return take_row_bits(pack_row_bits(colv.astype(jnp.int32) <= thr), i)
    if form == "bits_cat":
        return take_row_bits(pack_row_bits(
            bin_flags(cat_table(thr), colv.astype(jnp.int32))), i)
    raise ValueError(form)


def reference(form, bins, idx, col, n, thr):
    binf = np.asarray(bins)[np.minimum(np.asarray(idx), n - 1), col]
    if form == "bits_cat":
        return np.asarray(cat_table(thr))[binf]
    return binf <= thr


def make_loop(form, n, f, reps):
    def run(src, idx, thr):
        def step(k, acc):
            left = goes_left(form, src, idx, (k * 5 + 3) % f, n, thr + k)
            return acc + jnp.sum(left.astype(jnp.int32))
        return lax.fori_loop(0, reps, step, jnp.int32(0))
    return jax.jit(run)


def windows(n, size, rng):
    """Sorted row ids, sentinel ``n`` past the count, as a leaf's window."""
    out = {}
    for name, stride in (("every1", 1), ("every2", 2), ("every64", 64)):
        cnt = min(size, n // stride)
        idx = np.full(size, n, np.int32)
        idx[:cnt] = np.arange(cnt, dtype=np.int64) * stride
        out[name] = idx
    cnt = min(n, size * 3 // 4)        # a leaf that fills 3/4 of its bucket
    idx = np.full(size, n, np.int32)
    idx[:cnt] = np.sort(rng.choice(n, size=cnt, replace=False)).astype(np.int32)
    out["leaf"] = idx
    return out


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 10_500_000
    logs = ([int(x) for x in sys.argv[2].split(",")] if len(sys.argv) > 2
            else [24, 20, 16, 10])
    f = 28
    dev = jax.devices()[0]
    res = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rows": n, "cols": f, "ns_per_element": {}, "ms_per_split": {}}
    rng = np.random.default_rng(26)
    # from the host, as the booster uploads its binned matrix
    bins = jnp.asarray(rng.integers(0, 255, size=(n, f), dtype=np.uint8))
    cm = jax.jit(lambda b: b.T)(bins)
    srcs = {"2d": bins, "b": cm, "bits": cm, "bits_cat": cm}

    check = jnp.asarray(windows(n, 1 << 10, rng)["leaf"])
    for form, src in srcs.items():
        got = np.asarray(jax.jit(goes_left, static_argnums=(0, 4))(
            form, src, check, 7, n, 100))
        assert np.array_equal(got, reference(form, bins, check, 7, n, 100)), \
            form
    if dev.platform != "tpu":
        print(json.dumps(res))
        return

    for lg in logs:
        size = 1 << lg
        reps = max(2, min(64, (1 << 26) // size))
        wins = {k: jnp.asarray(v) for k, v in windows(n, size, rng).items()}
        for form, src in srcs.items():
            fn = make_loop(form, n, f, reps)
            for wname, idx in wins.items():
                if form == "bits_cat" and wname != "leaf":
                    continue        # the dense pass does not see the window
                s = timed(fn, src, idx, jnp.int32(100)) / reps
                res["ns_per_element"][f"2^{lg}.{wname}.{form}"] = s / size * 1e9
                res["ms_per_split"][f"2^{lg}.{wname}.{form}"] = s * 1e3
                print(f"2^{lg} {wname:8s} {form:12s} {s / size * 1e9:9.3f} "
                      f"ns/element {s * 1e3:9.4f} ms/split",
                      file=sys.stderr, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_route_read.json", "w") as fh:
        json.dump(res, fh, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
