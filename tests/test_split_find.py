"""Round 8: fused split-find parity.

The fused scan (``ops/split.py:_fused_numerical``) restructures ONLY the
candidate selection — per-direction row reductions instead of the packed
``[F, 2B, 4]`` argmax — while every float entering the decision is
computed by the same primitive sequence as the chain formulation.  These
tests pin that contract byte-for-byte:

* ``best_split`` chain-vs-fused over randomized histograms (missing-type
  mixes, L1/L2, feat_valid holes, categorical features), with and without
  the hoisted loop-invariant ctx;
* the full grower at 255 leaves: ``split_find=fused`` and ``chain`` grow
  BYTE-identical trees (bf16-exact integer weights, the
  test_fused_hist.py discipline).
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower
from lightgbm_tpu.ops.split import (SplitConfig, best_split, make_fused_ctx)


def _random_hist(rng, e, b, has_missing, n_rows=200):
    hist = np.zeros((e, b, 3), np.float32)
    nb = rng.randint(3, b + 1, size=e).astype(np.int32)
    mt = (rng.randint(0, 3, size=e) if has_missing
          else np.zeros(e)).astype(np.int32)
    db = np.minimum(rng.randint(0, 4, size=e), nb - 1).astype(np.int32)
    for i in range(e):
        m = rng.randint(20, n_rows)
        bi = rng.randint(0, nb[i], size=m)
        g = rng.randn(m).astype(np.float32)
        h = (np.abs(rng.randn(m)) + 0.01).astype(np.float32)
        np.add.at(hist[i, :, 0], bi, g)
        np.add.at(hist[i, :, 1], bi, h)
        np.add.at(hist[i, :, 2], bi, 1.0)
    return hist, nb, mt, db


def _assert_results_equal(a, b, label):
    for name, va, vb in zip(a._fields, a, b):
        va, vb = np.asarray(va), np.asarray(vb)
        assert va.dtype == vb.dtype and va.shape == vb.shape, (label, name)
        assert va.tobytes() == vb.tobytes(), (label, name, va, vb)


@pytest.mark.parametrize("has_missing", [False, True])
def test_best_split_fused_byte_identical(has_missing):
    rng = np.random.RandomState(0 if has_missing else 1)
    e, b = 12, 64
    for trial in range(12):
        cfg = SplitConfig(lambda_l1=0.1 * (trial % 3),
                          lambda_l2=0.5 * (trial % 2),
                          min_data_in_leaf=1 + trial % 5,
                          min_sum_hessian_in_leaf=1e-3,
                          has_missing=has_missing)
        hist, nb, mt, db = _random_hist(rng, e, b, has_missing)
        pg = float(hist[0, :, 0].sum())
        ph = float(hist[0, :, 1].sum())
        pc = float(hist[0, :, 2].sum())
        fv = rng.rand(e) > 0.15
        args = (jnp.asarray(hist), jnp.float32(pg), jnp.float32(ph),
                jnp.float32(pc), jnp.asarray(nb), jnp.asarray(mt),
                jnp.asarray(db), jnp.asarray(fv))
        r_chain, ok_chain = best_split(
            *args, cfg._replace(split_find="chain"), with_feat_ok=True)
        r_fused, ok_fused = best_split(
            *args, cfg._replace(split_find="fused"), with_feat_ok=True)
        ctx = make_fused_ctx(jnp.asarray(nb), jnp.asarray(mt),
                             jnp.asarray(db), b, cfg)
        r_ctx, ok_ctx = best_split(
            *args, cfg._replace(split_find="fused"), with_feat_ok=True,
            fused_ctx=ctx)
        _assert_results_equal(r_chain, r_fused, f"trial {trial}")
        _assert_results_equal(r_chain, r_ctx, f"trial {trial} ctx")
        np.testing.assert_array_equal(np.asarray(ok_chain),
                                      np.asarray(ok_fused))
        np.testing.assert_array_equal(np.asarray(ok_chain),
                                      np.asarray(ok_ctx))


def test_best_split_fused_categorical_byte_identical():
    """With categorical features the fused numerical scan shares the
    chain's categorical machinery — the combined result must stay
    byte-identical too."""
    rng = np.random.RandomState(5)
    e, b = 10, 32
    cfg = SplitConfig(min_data_in_leaf=2, min_sum_hessian_in_leaf=1e-3,
                      has_categorical=True, has_missing=True,
                      max_cat_threshold=16)
    for trial in range(6):
        hist, nb, mt, db = _random_hist(rng, e, b, True)
        is_cat = rng.rand(e) < 0.4
        pg = float(hist[0, :, 0].sum())
        ph = float(hist[0, :, 1].sum())
        pc = float(hist[0, :, 2].sum())
        args = (jnp.asarray(hist), jnp.float32(pg), jnp.float32(ph),
                jnp.float32(pc), jnp.asarray(nb), jnp.asarray(mt),
                jnp.asarray(db), jnp.ones((e,), bool))
        kw = dict(is_cat=jnp.asarray(is_cat), with_feat_ok=True)
        r_chain, ok_c = best_split(*args, cfg._replace(split_find="chain"),
                                   **kw)
        r_fused, ok_f = best_split(*args, cfg._replace(split_find="fused"),
                                   **kw)
        _assert_results_equal(r_chain, r_fused, f"cat trial {trial}")
        np.testing.assert_array_equal(np.asarray(ok_c), np.asarray(ok_f))


def _grow(split_find, n=4000, f=10, b=63, leaves=255, seed=31,
          has_missing=False):
    cfg = GrowerConfig(num_leaves=leaves, min_data_in_leaf=1, max_bin=b,
                       hist_method="segment", has_missing=has_missing,
                       split_find=split_find)
    meta = FeatureMeta(
        num_bin=jnp.full((f,), b, jnp.int32),
        missing_type=jnp.full((f,), 2 if has_missing else 0, jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.zeros((f,), bool))
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    # bf16-exact integer weights: histogram sums are exact in any
    # accumulation order, so the pin below is BYTE-identical
    g = rng.randint(-8, 9, size=n).astype(np.float32)
    h = (rng.randint(0, 5, size=n) + 1).astype(np.float32)
    c = np.ones(n, np.float32)
    grow = jax.jit(make_grower(cfg))
    tree, rl = grow(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                    jnp.asarray(c), meta, jnp.ones((f,), bool))
    return jax.tree_util.tree_map(np.asarray, tree), np.asarray(rl)


@pytest.mark.parametrize("has_missing", [False, True])
def test_grower_255_leaf_fused_chain_byte_identical(has_missing):
    t_f, rl_f = _grow("fused", has_missing=has_missing)
    t_c, rl_c = _grow("chain", has_missing=has_missing)
    assert int(t_f.num_leaves) > 200      # the deep-tree regime actually ran
    for name in t_f._fields:
        a, b = getattr(t_f, name), getattr(t_c, name)
        assert a.tobytes() == b.tobytes(), (has_missing, name)
    assert rl_f.tobytes() == rl_c.tobytes()
