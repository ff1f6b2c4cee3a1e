"""Fine-grained TPU timing probe: separates link latency from device time.

The headline bench conflates three costs that can differ widely:
per-dispatch+sync round-trip latency, device->host transfer time, and
actual on-device execution.  This probe times each in isolation so the
next optimization targets the real bottleneck (the reference's analogue is
the GPU learner's per-phase timing, gpu_tree_learner.cpp + TIMETAG):

  1. round-trip latency of a trivial jitted op (dispatch + block);
  2. pipelined dispatch rate (N dispatches, one block) - the cost floor of
     an async training loop;
  3. subset_histogram (XLA reference rung) at several row counts, amortized;
  4. the gather / cumsum / scatter trio the partition is built from, at the
     root-split window size;
  5. grow_tree end-to-end, amortized over 5 calls with ONE final block;
  6. train_one_iter through the booster (pipelined), 10 iters.

Writes one JSON dict to stdout (plus progress on stderr).  Runs on
whatever backend jax picks - on CPU it is a rehearsal, numbers are only
meaningful on the chip.

On SIGTERM (e.g. ``timeout -k 30``) the probe flushes
the PARTIAL result dict before dying: a stage timeout banks every number
measured so far — with ``"probe_failed"`` naming the interrupted step —
instead of leaving an empty artifact.
"""
import functools
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightgbm_tpu.utils.cache import enable_persistent_cache  # noqa: E402
enable_persistent_cache()

import numpy as np


def _t(fn, n=1, warmup=True):
    """Wall time of fn() x n with one final block, after an optional
    warmup call (compile excluded)."""
    import jax
    if warmup:
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = None
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    import jax
    import jax.numpy as jnp
    res = {"platform": jax.devices()[0].platform, "rows": rows}
    stage = {"name": "startup"}

    def _flush_partial(signum, frame):
        # SIGTERM from the playbook's `timeout -k`: bank the partial dict
        # (stdout is the artifact) and exit before SIGKILL lands
        res["probe_failed"] = {
            "kind": "probe_failed", "stage": stage["name"],
            "signal": signal.Signals(signum).name,
            "rc": 128 + signum}
        print(json.dumps(res), flush=True)
        os._exit(128 + signum)

    signal.signal(signal.SIGTERM, _flush_partial)
    print(f"platform: {res['platform']}", file=sys.stderr, flush=True)

    stage["name"] = "rtt"
    # 1. round-trip latency ---------------------------------------------------
    one = jnp.ones((8,), jnp.float32)
    add = jax.jit(lambda x: x + 1)
    res["rtt_ms"] = _t(lambda: add(one), n=10) * 1e3
    # transfer sync: device_get of a tiny array
    res["device_get_tiny_ms"] = _t(lambda: jax.device_get(add(one)), n=10) * 1e3
    print(f"rtt {res['rtt_ms']:.1f} ms, tiny device_get "
          f"{res['device_get_tiny_ms']:.1f} ms", file=sys.stderr, flush=True)

    stage["name"] = "dispatch"
    # 2. pipelined dispatch rate ---------------------------------------------
    def burst():
        x = one
        for _ in range(50):
            x = add(x)
        return x
    res["dispatch_pipelined_ms"] = _t(burst, n=1) * 1e3 / 50
    print(f"pipelined dispatch {res['dispatch_pipelined_ms']:.2f} ms/op",
          file=sys.stderr, flush=True)

    stage["name"] = "hist"
    # 3. histogram op at several sizes ---------------------------------------
    from lightgbm_tpu.ops.histogram import subset_histogram
    rng = np.random.RandomState(0)
    f = 28
    method = "einsum" if res["platform"] == "tpu" else "segment"
    res["hist_method"] = method
    bins_full = jnp.asarray(rng.randint(0, 255, size=(rows, f), dtype=np.uint8))
    res["hist_ms"] = {}
    # multiples of 2048 (the segment method's chunk; also a pallas row_tile
    # multiple), capped at the probe size
    sizes = sorted({min(m, rows) // 2048 * 2048
                    for m in (1 << 17, 1 << 19, rows)})
    for m in sizes:
        sub = bins_full[:m]
        g = jnp.ones((m,), jnp.float32)
        fn = jax.jit(lambda b, gg: subset_histogram(b, gg, gg, gg, 255,
                                                    method=method))
        res["hist_ms"][str(m)] = _t(lambda: fn(sub, g), n=5) * 1e3
        print(f"hist {m} rows: {res['hist_ms'][str(m)]:.1f} ms",
              file=sys.stderr, flush=True)

    stage["name"] = "partition"
    # 4. partition primitives at the root window size ------------------------
    n = rows
    order = jnp.asarray(np.arange(n, dtype=np.int32))
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    goes_left = jnp.asarray(rng.rand(n) < 0.5)

    take_fn = jax.jit(lambda o: jnp.take(bins_full, o, axis=0))
    res["gather_rows_ms"] = _t(lambda: take_fn(perm), n=5) * 1e3

    # 4b. gather/scatter A/B family: each candidate implementation of the
    # grower's two hot data movements, timed head-to-head so the next
    # optimization pass picks from measurements, not guesses
    from lightgbm_tpu.grower import pack_gather_words, unpack_gather_words
    words, per = pack_gather_words(bins_full)          # [N, 7] u32
    jax.block_until_ready(words)
    take_pib = jax.jit(lambda o: bins_full.at[o].get(mode="promise_in_bounds"))
    res["gather_rows_pib_ms"] = _t(lambda: take_pib(perm), n=5) * 1e3
    take_words = jax.jit(lambda o: unpack_gather_words(
        words.at[o].get(mode="promise_in_bounds"), f, per))
    res["gather_rows_words_ms"] = _t(lambda: take_words(perm), n=5) * 1e3
    print(f"gather A/B: take {res['gather_rows_ms']:.1f} / pib "
          f"{res['gather_rows_pib_ms']:.1f} / words "
          f"{res['gather_rows_words_ms']:.1f} ms", file=sys.stderr, flush=True)

    # 4b2. gather panel (round 5): ONE [N, W+3] u32 row gather vs the word
    # gather PLUS three separate f32 column gathers — prices exactly what
    # the panel removes from every split of the XLA reference rungs
    from jax import lax as _lax
    # three DISTINCT arrays, like the grower's gw/hw/cw — identical
    # operands would be CSE'd into one gather and underprice this side
    wg, wh, wc = (jnp.asarray(rng.randn(n).astype(np.float32))
                  for _ in range(3))
    panel = jnp.concatenate(
        [words] + [_lax.bitcast_convert_type(w, jnp.uint32)[:, None]
                   for w in (wg, wh, wc)], axis=1)
    jax.block_until_ready(panel)
    g3 = jax.jit(lambda o: (words.at[o].get(mode="promise_in_bounds"),
                            wg.at[o].get(mode="promise_in_bounds"),
                            wh.at[o].get(mode="promise_in_bounds"),
                            wc.at[o].get(mode="promise_in_bounds")))
    res["gather_words_plus3_ms"] = _t(lambda: g3(perm), n=5) * 1e3
    gp = jax.jit(lambda o: panel.at[o].get(mode="promise_in_bounds"))
    res["gather_panel_ms"] = _t(lambda: gp(perm), n=5) * 1e3
    print(f"gather panel A/B: words+3cols "
          f"{res['gather_words_plus3_ms']:.1f} / panel "
          f"{res['gather_panel_ms']:.1f} ms", file=sys.stderr, flush=True)

    # 4b3. fused-gather kernel head-to-head with the external-gather +
    # XLA-histogram pipeline it replaces: compare hist_fused_ms[m] against
    # gather_rows_words_ms (scaled by m/rows) + hist_ms[m] — the fused
    # kernel folds both into one dispatch with no staging buffer.  TPU
    # only: interpret-mode timings mean nothing, and a Mosaic rejection
    # here is itself evidence (recorded).
    if res["platform"] == "tpu":
        stage["name"] = "hist_fused"
        try:
            from lightgbm_tpu.data.packing import pack_fused_panel
            from lightgbm_tpu.ops.histogram import subset_histogram_fused
            from lightgbm_tpu.ops.pallas_hist import fused_idx_fetch
            bins_pad = jnp.concatenate(
                [bins_full, jnp.zeros((1, f), bins_full.dtype)])
            wpad = jnp.concatenate([wg, jnp.zeros((1,), jnp.float32)])
            fpanel, fper = pack_fused_panel(bins_pad, wpad, wpad, wpad)
            order_f = jnp.concatenate(
                [perm, jnp.full((fused_idx_fetch(512),), n, jnp.int32)])
            jax.block_until_ready(fpanel)
            res["hist_fused_ms"] = {}
            for m in sizes:
                nt = max(1, m // 512)
                ffn = jax.jit(functools.partial(
                    lambda o, cnt, nt: subset_histogram_fused(
                        o, fpanel, 0, cnt, f, fper, 255,
                        num_row_tiles=nt), cnt=m, nt=nt))
                res["hist_fused_ms"][str(m)] = _t(
                    lambda: ffn(order_f), n=5) * 1e3
                print(f"hist fused {m} rows: "
                      f"{res['hist_fused_ms'][str(m)]:.1f} ms",
                      file=sys.stderr, flush=True)
        except Exception as e:
            res["hist_fused_error"] = str(e)[:300]
            print(f"fused kernel probe failed: {e}",
                  file=sys.stderr, flush=True)

    # 4c. does a row scatter cost per INDEX or per ELEMENT?  If per index,
    # the leaf-ordered-bins design (permuting [window, F] data rows with
    # the same scatter that permutes `order`) is nearly free and deletes
    # BOTH hot gathers; if per element it costs 28x and loses.
    upd = jnp.asarray(rng.randint(0, 255, size=(n, f), dtype=np.uint8))
    scat1 = jax.jit(lambda p, o: jnp.zeros((n,), jnp.int32)
                    .at[p].set(o, unique_indices=True))
    res["scatter_1col_ms"] = _t(lambda: scat1(perm, order), n=5) * 1e3
    scatw = jax.jit(lambda p, u: jnp.zeros((n, f), jnp.uint8)
                    .at[p].set(u, unique_indices=True))
    res["scatter_wide_ms"] = _t(lambda: scatw(perm, upd), n=5) * 1e3
    # 4d. column gather from [F, N] (transposed) vs [N, F] row-major:
    # the partition branch reads ONE feature column at window row ids
    bins_t = jnp.asarray(np.ascontiguousarray(np.asarray(bins_full).T))
    colg_rm = jax.jit(lambda p: bins_full.at[p, 3].get(
        mode="promise_in_bounds"))
    res["gather_col_rowmajor_ms"] = _t(lambda: colg_rm(perm), n=5) * 1e3
    colg_t = jax.jit(lambda p: bins_t.at[3, p].get(mode="promise_in_bounds"))
    res["gather_col_transposed_ms"] = _t(lambda: colg_t(perm), n=5) * 1e3
    print(f"scatter 1col {res['scatter_1col_ms']:.1f} / wide(28) "
          f"{res['scatter_wide_ms']:.1f} ms; col gather rm "
          f"{res['gather_col_rowmajor_ms']:.1f} / transposed "
          f"{res['gather_col_transposed_ms']:.1f} ms",
          file=sys.stderr, flush=True)

    def part(ord_, gl):
        c1 = jnp.cumsum(gl.astype(jnp.int32))
        c0 = jnp.cumsum((~gl).astype(jnp.int32))
        nl = c1[-1]
        rank = jnp.where(gl, c1 - 1, nl + c0 - 1)
        return jnp.zeros((n,), jnp.int32).at[rank].set(ord_)
    part_fn = jax.jit(part)
    res["partition_window_ms"] = _t(lambda: part_fn(order, goes_left), n=5) * 1e3

    # 4e. sort-as-partition: a stable sort on the 1-bit goes_left key with
    # the window as payload IS the stable partition, and XLA:TPU's sort
    # network does only vectorized sequential memory passes — no random
    # HBM access at all.  If this beats the rank scatter, the partition
    # leaves the per-element-random cost class entirely.
    from jax import lax

    def part_sort(ord_, gl):
        keys = (~gl).astype(jnp.int32)
        _, out = lax.sort((keys, ord_), is_stable=True, num_keys=1)
        return out
    part_sort_fn = jax.jit(part_sort)
    res["partition_sort_ms"] = _t(
        lambda: part_sort_fn(order, goes_left), n=5) * 1e3
    print(f"partition via stable sort {res['partition_sort_ms']:.1f} ms",
          file=sys.stderr, flush=True)

    def part_opt(ord_, gl):
        # the production form after the round-4 retune: one cumsum
        # (closed-form valid count) + unique-indices permutation scatter
        c1 = jnp.cumsum(gl.astype(jnp.int32))
        nl = c1[-1]
        j = jnp.arange(n, dtype=jnp.int32)
        c0 = (j + 1) - c1
        rank = jnp.where(gl, c1 - 1, nl + c0 - 1)
        return jnp.zeros((n,), jnp.int32).at[rank].set(
            ord_, unique_indices=True, mode="promise_in_bounds")
    part_opt_fn = jax.jit(part_opt)
    res["partition_window_opt_ms"] = _t(
        lambda: part_opt_fn(order, goes_left), n=5) * 1e3
    print(f"gather {res['gather_rows_ms']:.1f} ms, partition window "
          f"{res['partition_window_ms']:.1f} ms (opt "
          f"{res['partition_window_opt_ms']:.1f})", file=sys.stderr, flush=True)

    stage["name"] = "grower"
    # 5 + 6. the real grower and booster -------------------------------------
    from bench import make_data
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.data.dataset import construct
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.utils import log as _log
    _log.set_verbosity(-1)
    X, y = make_data(rows, f)
    cfg = config_from_params({
        "objective": "binary", "num_leaves": 255, "max_bin": 255,
        "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100,
        "learning_rate": 0.1, "verbose": -1,
        "use_pallas": res["platform"] == "tpu"})
    ds = construct(X, cfg, label=y)
    bst = create_boosting(cfg, ds, create_objective(cfg))

    gmat = bst.bins
    g0, h0 = bst._grad_fn(bst.scores)
    cnt = jnp.ones((rows,), jnp.float32)
    fv = jnp.ones(bst._num_bin_host.shape[0], bool)
    t0 = time.perf_counter()
    jax.block_until_ready(
        bst.grow(gmat, g0[0], h0[0], cnt, bst.meta, fv)[0].num_leaves)
    res["grow_compile_s"] = time.perf_counter() - t0
    res["grow_ms"] = _t(
        lambda: bst.grow(gmat, g0[0], h0[0], cnt, bst.meta, fv)[0].num_leaves,
        n=5, warmup=False) * 1e3
    print(f"grow compile {res['grow_compile_s']:.0f} s, grow "
          f"{res['grow_ms']:.0f} ms/tree", file=sys.stderr, flush=True)

    n_it = 10
    bst.train_one_iter()            # warm the full-iteration path
    t0 = time.perf_counter()
    for _ in range(n_it):
        bst.train_one_iter()
    bst._drain_pending()
    jax.block_until_ready(bst.scores)
    res["train_iter_ms"] = (time.perf_counter() - t0) / n_it * 1e3
    res["pipelined"] = bool(bst._pipeline)
    print(f"train_one_iter {res['train_iter_ms']:.0f} ms "
          f"(pipelined={res['pipelined']})", file=sys.stderr, flush=True)
    print(json.dumps(res))           # flush everything banked so far: the
    # rows sweep below recompiles the grower per size
    sys.stdout.flush()

    stage["name"] = "rows_sweep"
    # 5b. rows-sweep decomposition: grow wall ~ a + b*rows at fixed 255
    # leaves, so the intercept a / 254 splits is the per-split FIXED cost
    # (kernel-launch / small-op overhead in the while-loop body) and b the
    # per-row work — the two candidate explanations for the measured
    # ~850 ms/tree separated without trace tooling
    res["grow_ms_by_rows"] = {str(int(rows)): res["grow_ms"]}
    for m in sorted({rows // 16, rows // 4}):
        mm = max(4096, m // 2048 * 2048)
        if mm >= rows:        # degenerate at tiny rehearsal sizes
            continue
        # slice OUTSIDE the timed region — in-region slices would scale
        # with mm and contaminate the per-row slope being measured
        sub = (gmat[:mm], g0[0][:mm], h0[0][:mm], cnt[:mm])
        jax.block_until_ready(sub)
        fn = (lambda sub: lambda: bst.grow(
            *sub, bst.meta, fv)[0].num_leaves)(sub)
        res["grow_ms_by_rows"][str(mm)] = _t(fn, n=3) * 1e3
        print(f"grow at {mm} rows: {res['grow_ms_by_rows'][str(mm)]:.0f} ms",
              file=sys.stderr, flush=True)
    xs = np.array(sorted(float(k) for k in res["grow_ms_by_rows"]))
    ys = np.array([res["grow_ms_by_rows"][str(int(x))] for x in xs])
    if len(xs) >= 2:
        b_slope, a_icept = np.polyfit(xs, ys, 1)
        res["grow_per_split_fixed_ms"] = max(a_icept, 0.0) / 254
        res["grow_per_mrow_ms"] = b_slope * 1e6
        print(f"decomposition: per-split fixed "
              f"{res['grow_per_split_fixed_ms']:.3f} ms, per-Mrow "
              f"{res['grow_per_mrow_ms']:.0f} ms", file=sys.stderr, flush=True)

    print(json.dumps(res))


if __name__ == "__main__":
    main()
