"""Nibble-packed small-bin storage (data/packing.py).

The packed histogram path must be EXACTLY equivalent to the unpacked
one — packing is a storage transform, not an approximation — so every
test here asserts bit-identical tree structure / predictions between
``enable_bin_packing`` on and off (the reference validates its 4-bit
bins the same way: dense_nbits_bin.hpp shares the dense-bin test
suite).
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.data.packing import (PACK_MAX_BIN, build_pack_plan,
                                       pack_columns, unfold_packed_hist)


def test_plan_pairs_narrow_columns():
    plan = build_pack_plan([255, 9, 16, 255, 5, 17, 12])
    #        narrow: 1, 2, 4, 6 -> two bytes; wide: 0, 3, 5
    assert plan.num_phys_cols == 7
    assert plan.num_storage_cols == 5
    assert plan.num_packed == 4
    assert not plan.is_packed[[0, 3, 5]].any()
    # partners share a byte with complementary shifts
    pairs = {}
    for f in np.flatnonzero(plan.is_packed):
        pairs.setdefault(plan.byte_col[f], []).append(plan.shift[f])
    assert all(sorted(v) == [0, 4] for v in pairs.values())


def test_plan_odd_leftover_and_too_few():
    plan = build_pack_plan([255, 10, 11, 12])
    assert plan.num_storage_cols == 3
    assert plan.num_packed == 2           # the odd column keeps its byte
    assert build_pack_plan([255, 12]) is None
    assert build_pack_plan([17, 18, 300]) is None


def test_plan_refuses_unprofitable_packing():
    # all-narrow: the unpacked histogram is [F, 16] — a 256-bin joint
    # form would move 8x more per psum/einsum, so the plan must refuse
    assert build_pack_plan([10, 11, 12]) is None
    # two narrow among many wide: a near-full second matrix copy to
    # save 1 byte/row of gather — refuse
    assert build_pack_plan([255] * 2000 + [9, 9]) is None
    # half narrow at 255-bin width: clear win — engage
    assert build_pack_plan([255] * 8 + [9] * 8) is not None


def test_pack_roundtrip_values():
    rng = np.random.RandomState(0)
    nb = [255, 9, 16, 5, 255, 13]
    binned = np.stack([rng.randint(0, b, size=200) for b in nb],
                      axis=1).astype(np.uint8)
    plan = build_pack_plan(nb)
    packed = pack_columns(binned, plan)
    assert packed.shape == (200, plan.num_storage_cols)
    for f in range(len(nb)):
        got = (packed[:, plan.byte_col[f]] >> plan.shift[f])
        if plan.is_packed[f]:
            got = got & (PACK_MAX_BIN - 1)
        np.testing.assert_array_equal(got, binned[:, f])


def test_unfold_matches_direct_histogram():
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    nb = [255, 9, 16, 5, 13]
    n = 500
    binned = np.stack([rng.randint(0, b, size=n) for b in nb],
                      axis=1).astype(np.uint8)
    w = rng.rand(n, 3).astype(np.float32)
    plan = build_pack_plan(nb)
    packed = pack_columns(binned, plan)
    # joint histograms over storage columns
    hist_c = np.zeros((plan.num_storage_cols, 256, 3), np.float32)
    for c in range(plan.num_storage_cols):
        np.add.at(hist_c, (c, packed[:, c]), w)
    out = np.asarray(unfold_packed_hist(jnp.asarray(hist_c), plan, 255))
    for f in range(len(nb)):
        direct = np.zeros((255, 3), np.float32)
        np.add.at(direct, binned[:, f], w)
        np.testing.assert_allclose(out[f], direct, rtol=1e-6, atol=1e-5)


def test_unfold_composes_with_fused_kernel_interpret():
    """The TPU path histograms PACKED storage columns with the fused
    Pallas kernel at the 256-wide joint index; interpret mode pins that
    combination (kernel x packing) without a chip: joint histograms
    from the kernel, unfolded, must equal per-feature histograms
    computed directly."""
    import jax.numpy as jnp
    from lightgbm_tpu.data.packing import pack_fused_panel
    from lightgbm_tpu.ops.histogram import subset_histogram_fused
    from lightgbm_tpu.ops.pallas_hist import fused_idx_fetch
    rng = np.random.RandomState(2)
    nb = [255, 9, 16, 5, 13]
    n = 600
    binned = np.stack([rng.randint(0, b, size=n) for b in nb],
                      axis=1).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = np.abs(rng.randn(n)).astype(np.float32)
    c = np.ones(n, np.float32)
    plan = build_pack_plan(nb)
    packed = pack_columns(binned, plan)
    zrow = np.zeros((1, packed.shape[1]), packed.dtype)
    zw = np.zeros((1,), np.float32)
    panel, per = pack_fused_panel(
        jnp.asarray(np.concatenate([packed, zrow])),
        jnp.asarray(np.concatenate([g, zw])),
        jnp.asarray(np.concatenate([h, zw])),
        jnp.asarray(np.concatenate([c, zw])))
    order = np.concatenate([np.arange(n, dtype=np.int32),
                            np.full((fused_idx_fetch(512),), n, np.int32)])
    hist_c = subset_histogram_fused(
        jnp.asarray(order), panel, 0, n, packed.shape[1], per, 256,
        row_tile=512, num_row_tiles=-(-n // 512), interpret=True)
    out = np.asarray(unfold_packed_hist(hist_c, plan, 255))
    w = np.stack([g, h, c], axis=1)
    for f in range(len(nb)):
        direct = np.zeros((255, 3), np.float32)
        np.add.at(direct, binned[:, f], w)
        np.testing.assert_allclose(out[f], direct, rtol=2e-5, atol=2e-4)


def _narrow_problem(n=4000, seed=3):
    """Mixed matrix: 2 wide continuous columns + 10 small-cardinality
    columns (<=16 bins) + 2 small categoricals."""
    rng = np.random.RandomState(seed)
    wide = rng.randn(n, 2)
    small = rng.randint(0, 9, size=(n, 10)).astype(np.float64)
    cats = rng.randint(0, 7, size=(n, 2)).astype(np.float64)
    X = np.column_stack([wide, small, cats])
    logits = (wide[:, 0] + 0.3 * small[:, 0] - 0.2 * small[:, 1]
              + np.asarray([0.5, -0.4, 0.1, 0.3, -0.2, 0.0, 0.2])[
                  cats[:, 0].astype(int)])
    y = (logits + 0.5 * rng.randn(n) > 0).astype(np.float64)
    return X, y, [12, 13]


def _train(X, y, cats, packing, extra=None):
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 20, "enable_bin_packing": packing,
              "enable_bundle": False}
    params.update(extra or {})
    ds = lgb.Dataset(X, label=y, categorical_feature=cats)
    return lgb.train(params, ds, num_boost_round=5, verbose_eval=False)


def _assert_same_model(b1, b2, X):
    """Tree STRUCTURE must be bit-identical; leaf values may differ by
    f32 summation-order noise (the packed path reduces each feature's
    bins over the partner-nibble axis — same noise class as the
    data-parallel psum, which test_parallel tolerates identically)."""
    for t1, t2 in zip(b1.inner.models, b2.inner.models):
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=5e-5, atol=5e-6)
    np.testing.assert_allclose(b1.predict(X), b2.predict(X),
                               rtol=5e-5, atol=5e-6)


def test_packed_training_matches_unpacked():
    X, y, cats = _narrow_problem()
    b_on = _train(X, y, cats, True)
    b_off = _train(X, y, cats, False)
    assert b_on.inner._pack_plan is not None, "packing did not engage"
    assert b_off.inner._pack_plan is None
    _assert_same_model(b_on, b_off, X)


def test_packed_training_with_bagging_subset():
    X, y, cats = _narrow_problem()
    extra = {"bagging_fraction": 0.4, "bagging_freq": 1}
    b_on = _train(X, y, cats, True, extra)
    b_off = _train(X, y, cats, False, extra)
    assert b_on.inner._pack_plan is not None
    assert b_on.inner._subset_state is not None, "subset path not exercised"
    _assert_same_model(b_on, b_off, X)


def test_packed_training_with_efb_bundles():
    """EFB one-hot bundles produce <=16-bin physical columns — the case
    packing exists for; bundle expansion must compose with unfolding."""
    rng = np.random.RandomState(7)
    n = 4000
    dense = rng.randn(n, 3)
    blocks = []
    logits = dense[:, 0].copy()
    for g in range(4):
        which = rng.randint(0, 7, size=n)
        block = np.zeros((n, 6))
        sel = which < 6
        block[np.flatnonzero(sel), which[sel]] = 1.0
        logits += rng.randn(7)[which] * 0.5
        blocks.append(block)
    X = np.column_stack([dense] + blocks)
    y = (logits + 0.4 * rng.randn(n) > 0).astype(np.float64)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 20}
    b_on = lgb.train({**params, "enable_bin_packing": True},
                     lgb.Dataset(X, label=y), num_boost_round=5,
                     verbose_eval=False)
    b_off = lgb.train({**params, "enable_bin_packing": False},
                      lgb.Dataset(X, label=y), num_boost_round=5,
                      verbose_eval=False)
    assert b_on.inner.train_set.layout is not None, "expected EFB bundles"
    assert b_on.inner._pack_plan is not None, "packing did not engage"
    _assert_same_model(b_on, b_off, X)


@pytest.mark.parametrize("learner", ["data", "voting"])
def test_packed_distributed_matches_unpacked(learner):
    X, y, cats = _narrow_problem()
    extra = {"tree_learner": learner}
    if learner == "voting":
        extra["top_k"] = 8
    b_on = _train(X, y, cats, True, extra)
    b_off = _train(X, y, cats, False, extra)
    assert b_on.inner._pack_plan is not None
    _assert_same_model(b_on, b_off, X)


def test_feature_parallel_gates_packing_off():
    X, y, cats = _narrow_problem()
    b = _train(X, y, cats, True, {"tree_learner": "feature"})
    assert b.inner._pack_plan is None
