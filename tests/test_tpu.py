"""Real-TPU test tier — run with ``LGBM_TPU_TESTS_ON_TPU=1`` on a host with
a live TPU.  This is the GPU_DEBUG_COMPARE discipline
(``gpu_tree_learner.cpp:1018-1043``) as an actual test tier: Mosaic
lowering + on-device numerics are exactly the class of failure interpret
mode cannot see (round 2 shipped a kernel that had only ever run
interpreted, and it failed Mosaic compilation on the chip)."""
import os

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("LGBM_TPU_TESTS_ON_TPU") != "1",
    reason="set LGBM_TPU_TESTS_ON_TPU=1 on a TPU host")


@pytest.fixture(scope="module")
def tpu():
    """The chip, or a FAILURE: under LGBM_TPU_TESTS_ON_TPU=1 a run that
    lands on another platform must not go green with nothing run."""
    import jax
    dev = jax.devices()[0]
    assert dev.platform == "tpu", (
        f"LGBM_TPU_TESTS_ON_TPU=1 but jax runs on {dev.platform!r}")
    return dev


@pytest.mark.parametrize("num_bins,leaves", [(63, 31), (255, 255)])
def test_grow_tree_compiles_on_tpu(tpu, num_bins, leaves):
    """The FULL jitted grower (gather buckets, lax.switch, while_loop,
    pallas hist) must lower + compile for TPU at bench shapes."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower

    n, f = 1 << 15, 28
    cfg = GrowerConfig(num_leaves=leaves, min_data_in_leaf=1,
                       min_sum_hessian_in_leaf=100.0, max_bin=num_bins,
                       hist_method="fused", bucket_min_log2=10)
    meta = FeatureMeta(
        num_bin=jnp.full((f,), num_bins, jnp.int32),
        missing_type=jnp.zeros((f,), jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.zeros((f,), bool))
    grow = jax.jit(make_grower(cfg))
    args = (jnp.zeros((n, f), jnp.uint8), jnp.zeros((n,), jnp.float32),
            jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32),
            meta, jnp.ones((f,), bool))
    grow.lower(*args).compile()


def test_end_to_end_train_auc_on_tpu(tpu):
    """Train a real model on-device and hit a sane AUC — the bench loop in
    miniature, pallas path on."""
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(3)
    n, f = 200_000, 28
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f)
    y = ((X @ w + rng.randn(n)) > 0).astype(np.float32)
    params = dict(objective="binary", num_leaves=63, max_bin=255,
                  min_data_in_leaf=1, min_sum_hessian_in_leaf=100,
                  learning_rate=0.1, verbose=-1, use_pallas=True)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=20)
    p = bst.predict(X[:20000])
    yy = y[:20000]
    order = np.argsort(p)
    ranks = np.empty_like(order, dtype=np.float64)
    ranks[order] = np.arange(len(p))
    pos = yy > 0
    n1, n0 = pos.sum(), (~pos).sum()
    auc = (ranks[pos].sum() - n1 * (n1 - 1) / 2) / (n1 * n0)
    assert auc > 0.85, auc


def test_packed_training_matches_unpacked_on_tpu(tpu):
    """Nibble packing through the REAL pallas path: structure-identical
    models packed vs unpacked on-device (the CPU-tier equivalence of
    tests/test_packing.py re-pinned where Mosaic lowering and bf16
    numerics are live)."""
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(9)
    n = 50_000
    wide = rng.randn(n, 4).astype(np.float32)
    small = rng.randint(0, 9, size=(n, 12)).astype(np.float32)
    X = np.column_stack([wide, small])
    y = ((wide[:, 0] + 0.4 * small[:, 0] - 0.3 * small[:, 1]
          + 0.5 * rng.randn(n)) > 0).astype(np.float32)
    out = {}
    for packing in (True, False):
        params = dict(objective="binary", num_leaves=31, max_bin=255,
                      min_data_in_leaf=20, learning_rate=0.1, verbose=-1,
                      use_pallas=True, enable_bin_packing=packing)
        out[packing] = lgb.train(params, lgb.Dataset(X, label=y),
                                 num_boost_round=5)
    assert out[True].inner._pack_plan is not None, "packing did not engage"
    for t1, t2 in zip(out[True].inner.models, out[False].inner.models):
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)


def test_gspmd_fused_hybrid_matches_flat_on_tpu(tpu):
    """gspmd_hist=fused (shard_map islands + Mosaic kernel) vs flat
    (pure-XLA scatter-add) over the real device mesh: structure-identical
    models — the on-chip half of the CPU byte-identity pins in
    tests/test_gspmd.py, with live Mosaic lowering and bf16 numerics."""
    import jax
    import lightgbm_tpu as lgb
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device TPU slice")
    rng = np.random.RandomState(11)
    n, f = 50_000, 16
    X = rng.randn(n, f).astype(np.float32)
    y = ((X @ rng.randn(f)) > 0).astype(np.float32)
    out = {}
    for gh in ("flat", "fused"):
        params = dict(objective="binary", num_leaves=31, max_bin=255,
                      min_data_in_leaf=20, learning_rate=0.1, verbose=-1,
                      tree_learner="data", gspmd_hist=gh)
        out[gh] = lgb.train(params, lgb.Dataset(X, label=y),
                            num_boost_round=5)
    for t1, t2 in zip(out["flat"].inner.models, out["fused"].inner.models):
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)


def test_fused_hist_matches_einsum_on_device(tpu):
    """On-device proof of the fused-gather kernel: compiles under Mosaic,
    matches the f32 einsum oracle over the same gathered window (counts
    exact, g/h within the bf16 hi/lo-split envelope), and prints the
    throughput (host clock, information only)."""
    import sys
    import time
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.data.packing import pack_fused_panel
    from lightgbm_tpu.ops.histogram import (subset_histogram_einsum,
                                            subset_histogram_fused)
    from lightgbm_tpu.ops.pallas_hist import fused_idx_fetch

    rng = np.random.RandomState(8)
    n, f, b, tr = 1 << 17, 28, 255, 512
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = np.abs(rng.randn(n)).astype(np.float32)
    c = np.ones(n, np.float32)
    bins_pad = jnp.concatenate(
        [jnp.asarray(bins), jnp.zeros((1, f), jnp.uint8)])
    pad1 = lambda x: jnp.concatenate(
        [jnp.asarray(x), jnp.zeros((1,), jnp.float32)])
    panel, per = pack_fused_panel(bins_pad, pad1(g), pad1(h), pad1(c))
    perm = rng.permutation(n).astype(np.int32)
    order = jnp.concatenate(
        [jnp.asarray(perm), jnp.full((fused_idx_fetch(tr),), n, jnp.int32)])
    start, cnt = 1029, (1 << 16) + 123        # unaligned, partial last tile
    nt = -(-cnt // tr)
    fused = jax.jit(lambda o, p, s, ct: subset_histogram_fused(
        o, p, s, ct, f, per, b, row_tile=tr, num_row_tiles=nt))
    out = np.asarray(fused(order, panel, start, cnt))
    sel = perm[start:start + cnt]
    oracle = jax.jit(lambda r, gg, hh, cc: subset_histogram_einsum(
        r, gg, hh, cc, b))
    ref = np.asarray(oracle(jnp.asarray(bins[sel]), jnp.asarray(g[sel]),
                            jnp.asarray(h[sel]), jnp.asarray(c[sel])))
    np.testing.assert_array_equal(out[:, :, 2], ref[:, :, 2])
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)
    # throughput: fused gathers in-kernel, so judge it against any
    # hist-only rung + the ~12.6 ns/row external gather it absorbs
    args = (order, panel, jnp.asarray(start, jnp.int32),
            jnp.asarray(cnt, jnp.int32))
    fused_dyn = jax.jit(lambda o, p, s, ct: subset_histogram_fused(
        o, p, s, ct, f, per, b, row_tile=tr,
        num_row_tiles=jnp.maximum(1, (ct + tr - 1) // tr).astype(jnp.int32)))
    # warm both up with the arguments the loop times: the call above
    # passed python ints, a different jit signature than these arrays
    jax.block_until_ready(fused(*args))
    jax.block_until_ready(fused_dyn(*args))
    for name, fn, a in (("fused", fused, args), ("fused_dyn", fused_dyn,
                                                 args)):
        t0 = time.perf_counter()
        out2 = None
        for _ in range(5):
            out2 = fn(*a)
        jax.block_until_ready(out2)
        dt = (time.perf_counter() - t0) / 5
        print(f"hist {name}: {dt*1e3:.2f} ms at {cnt} rows "
              f"({dt/cnt*1e9:.1f} ns/row)", file=sys.stderr)
