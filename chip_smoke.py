"""On-chip smoke: the quickest proof that the program still starts on a TPU.

    python3 chip_smoke.py

Drives the main path once through the entry points a user calls, in the
one process that owns the chip: ``lgb.train`` on the Higgs-shaped model
the repo is built around (binary, 1,000,000 x 28 from ``bench.make_data``,
255 leaves, 255 bins, one validation set, 5 iterations — depth cut, width
not), then the trained model through ``ModelServer`` at 1/64/4096-row
requests.  Every phase checks what came out by the repo's own means and
the first failed check ends the run non-zero; no JSON line is printed
unless every phase passed.  On a host with >= 4 chips a mesh leg trains
``tree_learner=data`` over four devices and compares against one chip.

Exits non-zero, with no result line, when jax's platform is not ``tpu``.
Times printed along the way are information, not claims.
"""
import gc
import json
import sys
import time

import numpy as np

ROWS, VALID_ROWS, FEATURES = 1_000_000, 100_000, 28
TREES, MESH_TREES = 5, 3
PARAMS = {
    "objective": "binary", "num_leaves": 255, "max_bin": 255,
    "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100,
    "learning_rate": 0.1, "metric": ["binary_logloss", "auc"],
    "verbose": -1,
}
# valid AUC after 5 trees was 0.8628 on the first good v5e run (my chip
# run, PR 21); the run is seeded, so the floor only has to absorb a
# compiler's different summation order
VALID_AUC_FLOOR = 0.85
REQUEST_ROWS = (1, 64, 4096, 1, 64, 4096)
# the bf16 hi/lo contract of tests/test_fused_hist.py
HIST_RTOL = HIST_ATOL = 3e-4
KERNEL_LEAF_ROWS = 1 << 16     # oracle check: the leaf nearest this size


def check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED — {what}")
    print(f"chip_smoke: ok — {what}", flush=True)


def device_phase():
    """The platform gate and the environment line.  Runs first: nothing
    heavy may happen on a host with no accelerator."""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: jax platform is {dev.platform!r}, "
                         "not 'tpu' — no accelerator, no result")
    from importlib.metadata import version
    from lightgbm_tpu.utils.cache import enable_persistent_cache
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"chip_smoke: device {json.dumps(device)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={version('libtpu')} "
          f"compile_cache={enable_persistent_cache()}", flush=True)
    return device


def make_problem(rows=ROWS, valid_rows=VALID_ROWS, features=FEATURES):
    """One ``make_data`` draw split into train and validation rows (a
    second draw would label by a different weight vector)."""
    from bench import make_data
    X, y = make_data(rows + valid_rows, features)
    return X[:rows], y[:rows], X[rows:], y[rows:]


def train_phase(X, y, Xv, yv, trees=TREES, params=PARAMS):
    """``lgb.train`` with the defaults a user gets; returns the booster."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import memory as obs_memory
    from lightgbm_tpu.obs.counters import counters

    counters.reset()
    dtrain = lgb.Dataset(X, label=y)
    dvalid = lgb.Dataset(Xv, label=yv, reference=dtrain)
    marks = []                       # (wall clock, grower jit entries)

    def mark(env):
        jax.block_until_ready(env.model.inner.scores)
        marks.append((time.perf_counter(),
                      int(env.model.inner.grow._cache_size())))

    evals = {}
    t0 = time.perf_counter()
    bst = lgb.train(dict(params), dtrain, num_boost_round=trees,
                    valid_sets=[dtrain, dvalid],
                    valid_names=["train", "valid"], evals_result=evals,
                    verbose_eval=False, callbacks=[mark])
    stamps = [t0] + [m[0] for m in marks]
    per_iter = np.diff(stamps)
    print(f"chip_smoke: info — first iteration (binning + compile) "
          f"{per_iter[0]:.1f} s; steady {np.median(per_iter[1:]):.3f} "
          f"s/tree (median of {len(per_iter) - 1}, eval included)",
          flush=True)

    leaves = [t.num_leaves for t in bst.inner.models]
    check(len(leaves) == trees
          and all(n == params["num_leaves"] for n in leaves),
          f"{trees} trees of {params['num_leaves']} leaves (got {leaves})")
    for name in ("train", "valid"):
        ll = evals[name]["binary_logloss"]
        check(np.all(np.isfinite(ll)) and np.all(np.diff(ll) < 0),
              f"{name} logloss finite and falling "
              f"({', '.join(f'{v:.5f}' for v in ll)})")
    auc = evals["valid"]["auc"][-1]
    check(auc >= VALID_AUC_FLOOR,
          f"valid AUC {auc:.4f} >= floor {VALID_AUC_FLOOR}")

    dispatch = counters.get("hist_dispatch")
    width = bst.inner.grower_cfg.max_bin
    want = {f"col_tiles=1,fetch={fetch},hi=16,interpret=False,method=fused,"
            f"site={s},width={width}"
            for s, fetch in (("root", "block"), ("split", "rows"))}
    check(set(dispatch) == want,
          f"hist_dispatch is the compiled fused kernel at root and split "
          f"({dispatch})")
    downgrades = counters.events("layout_downgrade")
    check(not downgrades, f"no layout_downgrade event ({downgrades})")
    entries = [m[1] for m in marks]
    check(entries[0] >= 1 and len(set(entries)) == 1,
          f"grower_jit_entries does not move after the first tree "
          f"({entries})")

    capacity = obs_memory.device_capacity()
    check(isinstance(capacity, int) and capacity > 0,
          f"device capacity is a number ({capacity} bytes)")
    placed = counters.events("placement_decision")
    check(len(placed) == 1 and placed[0]["capacity_bytes"] == capacity,
          f"placement_decision names it ({placed})")
    return bst


def kernel_phase(bst, X, y):
    """``hist6_fused`` against the segment-sum oracle on one real leaf
    window of the first tree — compiled, on the chip, outside any timing.
    The window is the leaf's run in a leaf-grouped ``order`` array, as
    the grower keeps it; the weights are the model's own gradients."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.data.packing import pack_fused_panel
    from lightgbm_tpu.ops.histogram import (subset_histogram_fused,
                                            subset_histogram_segment)
    from lightgbm_tpu.ops.pallas_hist import fused_idx_fetch

    gbdt = bst.inner
    cfg = gbdt.grower_cfg
    bins = np.asarray(gbdt.bins)
    n, f = bins.shape
    leaf_of_row = gbdt.models[0].predict_leaf_index(X)
    sizes = np.bincount(leaf_of_row, minlength=cfg.num_leaves)
    leaf = int(np.argmin(np.abs(sizes - KERNEL_LEAF_ROWS)))
    order_np = np.argsort(leaf_of_row, kind="stable").astype(np.int32)
    start = int(sizes[:leaf].sum())
    cnt = int(sizes[leaf])
    sel = order_np[start:start + cnt]

    p = 1.0 / (1.0 + np.exp(-np.asarray(gbdt.scores[0], np.float64)))
    g = (p - y).astype(np.float32)
    h = (p * (1.0 - p)).astype(np.float32)
    c = np.ones(n, np.float32)

    pad1 = lambda a: jnp.concatenate([jnp.asarray(a),
                                      jnp.zeros((1,), jnp.float32)])
    panel, per = pack_fused_panel(
        jnp.concatenate([jnp.asarray(bins), jnp.zeros((1, f), bins.dtype)]),
        pad1(g), pad1(h), pad1(c))
    tr = cfg.row_tile
    order = jnp.concatenate([jnp.asarray(order_np),
                             jnp.full((fused_idx_fetch(tr),), n, jnp.int32)])
    fused = jax.jit(lambda o, pn, s, ct: subset_histogram_fused(
        o, pn, s, ct, f, per, cfg.max_bin, row_tile=tr,
        num_row_tiles=jnp.maximum(1, (ct + tr - 1) // tr).astype(jnp.int32),
        interpret=False, site="smoke"))
    out = np.asarray(fused(order, panel, jnp.int32(start), jnp.int32(cnt)))
    oracle = jax.jit(lambda r, gg, hh, cc: subset_histogram_segment(
        r, gg, hh, cc, cfg.max_bin))
    ref = np.asarray(oracle(jnp.asarray(bins[sel]), jnp.asarray(g[sel]),
                            jnp.asarray(h[sel]), jnp.asarray(c[sel])))
    check(out.shape == ref.shape == (f, cfg.max_bin, 3)
          and np.all(np.isfinite(out)),
          f"fused histogram is finite, shape {out.shape}")
    check(np.array_equal(out[:, :, 2], ref[:, :, 2])
          and out[:, :, 2].sum() == cnt * f,
          f"fused counts equal the oracle exactly (leaf {leaf}: window "
          f"[{start}, {start + cnt}) of order)")
    diff = np.abs(out - ref)
    check(np.all(diff <= HIST_ATOL + HIST_RTOL * np.abs(ref)),
          f"fused g/h inside the bf16 hi/lo contract (worst |fused - "
          f"oracle| {diff.max():.2e}; atol {HIST_ATOL}, rtol {HIST_RTOL})")


def serving_phase(bst, X):
    """The trained model through ``ModelServer`` (the ``python -m
    lightgbm_tpu.serving`` object); every answer against the host
    per-tree loop under the bit-identity contract of docs/SERVING.md."""
    from lightgbm_tpu.inference import jit_entries
    from lightgbm_tpu.predictor import Predictor
    from lightgbm_tpu.serving import ModelServer

    gbdt = bst.inner
    t0 = time.perf_counter()
    server = ModelServer(booster=bst, params={"verbose": -1})
    try:
        print(f"chip_smoke: info — server load + ladder warm-up "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        backend = gbdt.predict_engine(build=False).backend
        check(backend == "xla", f"PredictEngine backend is xla ({backend})")
        warmed = jit_entries()
        host = Predictor(gbdt.models, gbdt.num_class, gbdt.objective)
        lo = 0
        for rows in REQUEST_ROWS:
            x = X[lo:lo + rows]
            lo += rows
            t0 = time.perf_counter()
            got = server.predict(x)
            dt = time.perf_counter() - t0
            want = host.predict(x)
            check(got.shape == (rows,) and np.all(np.isfinite(got))
                  and np.array_equal(got, want),
                  f"{rows}-row request equals host Predictor.predict bit "
                  f"for bit ({dt * 1e3:.1f} ms, information only)")
        check(jit_entries() == warmed and warmed >= 1,
              f"predict_jit_entries does not move after warm-up "
              f"({warmed} -> {jit_entries()})")
    finally:
        server.stop()


def _int_objective(preds, dataset):
    """Binary logloss gradients rounded to small integers: every f32
    histogram sum is then exact whatever the summation order, so one chip
    and four chips must grow the same trees to the byte
    (tests/test_gspmd.py pins the same identity on the CPU mesh)."""
    p = 1.0 / (1.0 + np.exp(-preds))
    y = dataset.get_label()
    return (np.round(8.0 * (p - y)).astype(np.float32),
            np.maximum(1.0, np.round(8.0 * p * (1.0 - p)))
            .astype(np.float32))


def _tree_arrays(bst):
    return [(t.num_leaves, t.split_feature, t.threshold_bin, t.left_child,
             t.right_child, t.leaf_value, t.leaf_count)
            for t in bst.inner.models]


def _bytes_in_use(devices):
    import jax
    return [int(d.memory_stats()["bytes_in_use"])
            for d in jax.devices()[:devices]]


def mesh_phase(X, y, trees=MESH_TREES, params=PARAMS, devices=4):
    """``tree_learner=data`` over ``devices`` chips in this one process,
    ``gspmd_hist`` flat and fused, against the one-chip trees."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.counters import counters

    def train(extra):
        counters.reset()
        t0 = time.perf_counter()
        bst = lgb.train({**params, "metric": "None", **extra},
                        lgb.Dataset(X, label=y), num_boost_round=trees,
                        fobj=_int_objective, verbose_eval=False)
        print(f"chip_smoke: info — {extra or 'one chip'}: {trees} trees in "
              f"{time.perf_counter() - t0:.1f} s (compile included)",
              flush=True)
        return bst

    n = X.shape[0]
    bst = train({})
    one_chip = _tree_arrays(bst)
    one_chip_bytes = _bytes_in_use(devices)[0]
    check(all(t[0] == params["num_leaves"] for t in one_chip),
          f"one chip: {trees} trees of {params['num_leaves']} leaves")
    del bst
    gc.collect()

    for hist in ("fused", "flat"):
        bst = train({"tree_learner": "data", "mesh_devices": devices,
                     "gspmd_hist": hist})
        gbdt = bst.inner
        shards = gbdt.bins.addressable_shards
        placed = sorted((s.device.id, s.data.shape[0]) for s in shards)
        check(len({d for d, _ in placed}) == devices
              and all(r == n // devices for _, r in placed),
              f"{hist}: binned rows sit on {devices} distinct devices, "
              f"{n // devices} each ({placed})")
        used = _bytes_in_use(devices)
        check(max(used) < one_chip_bytes
              and max(used[1:]) <= 1.1 * min(used[1:]),
              f"{hist}: no device holds the one-chip footprint — "
              f"bytes_in_use {used} vs {one_chip_bytes} on one chip "
              f"(shares {[round(u / one_chip_bytes, 2) for u in used]})")
        method = "fused" if hist == "fused" else "segment"
        dispatch = counters.get("hist_dispatch")
        check(all(f"method={method}" in k and "interpret=False" in k
                  for k in dispatch) and dispatch
              and not counters.events("layout_downgrade"),
              f"{hist}: hist_dispatch is compiled {method}, no downgrade "
              f"({dispatch})")
        census = gbdt.grow_hlo_census(label=f"smoke_{hist}")
        reduces = {op: rec for op, rec in census.items()
                   if op in ("all-reduce", "reduce-scatter")}
        hist_bytes = X.shape[1] * gbdt.grower_cfg.max_bin * 3 * 4
        # the compiler may fold the three root scalars into the same op
        check(any(hist_bytes <= r["max_bytes"] < 2 * hist_bytes
                  for r in reduces.values()),
              f"{hist}: compiled HLO carries the per-split [F, B, 3] "
              f"reduction of {hist_bytes} bytes ({census})")
        got = _tree_arrays(bst)
        same = len(got) == len(one_chip) and all(
            a[0] == b[0] and all(np.array_equal(u, v)
                                 for u, v in zip(a[1:], b[1:]))
            for a, b in zip(got, one_chip))
        check(same, f"{hist}: trees identical to one chip under integer "
                    "weights")
        del bst, gbdt, shards
        gc.collect()


def main():
    device = device_phase()
    X, y, Xv, yv = make_problem()
    bst = train_phase(X, y, Xv, yv)
    kernel_phase(bst, X, y)
    serving_phase(bst, Xv)
    if device["count"] >= 4:
        del bst
        gc.collect()
        mesh_phase(X, y)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
