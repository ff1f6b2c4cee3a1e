"""The per-leaf histogram pool laid out in whole (8, 128) tiles.

``grower.make_grower`` carries every leaf's [F, B, 3] histogram in one pool
``[L, K, 128]``: a leaf's ``3 * F * B`` floats, one statistic's [F, B] plane
after another, in K rows of 128 lanes, K rounded up to a multiple of 8 and
the tail zeros (``grower.pool_flat`` / ``pool_hist`` / ``pool_tiles``).
These tests pin the map both ways bit for bit, the split's pool step
against plain arithmetic, the trees a wide data set grows against digests
recorded from the flat ``[L, 3 * F * B]`` pool that came before, and the
trace-time counter ``hist_pool_layout`` that says which pool a program was
built with.
"""
import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.grower import (FeatureMeta, GrowerConfig, make_grower,
                                 pool_flat, pool_hist, pool_split,
                                 pool_tiles)
from lightgbm_tpu.obs.counters import counters

# (columns, bins, K): Epsilon's, Higgs's, MS LTR's widths and the
# categorical Expo grower's 8 columns at 288 bins; none of them has
# 3 * F * B a multiple of 1024, so each pads
WIDTHS = [(2000, 255, 11960), (28, 255, 168), (137, 255, 824),
          (8, 288, 56)]


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _hist(rng, shape):
    """float32 histograms drawn as raw bits: NaNs with payloads,
    infinities, signed zeros and denormals all appear."""
    raw = rng.integers(0, 2 ** 32, size=shape, dtype=np.uint64)
    return raw.astype(np.uint32).view(np.float32)


@pytest.mark.parametrize("f,b,k", WIDTHS)
def test_pool_round_trip_is_bit_for_bit(f, b, k):
    rng = np.random.default_rng(f * 1000 + b)
    hist = _hist(rng, (2, f, b, 3))
    hist[0, 0, 0] = [-0.0, np.inf, -np.inf]
    tiles = pool_flat(jnp.asarray(hist))
    assert pool_tiles(f, b) == k and k % 8 == 0
    assert tiles.shape == (2, k, 128) and tiles.dtype == jnp.float32
    n = 3 * f * b
    assert 0 < k * 128 - n < 8 * 128
    flat = _bits(tiles).reshape(2, -1)
    # one statistic's [F, B] plane after another, then zeros
    np.testing.assert_array_equal(
        flat[:, :n], _bits(np.moveaxis(hist, -1, 1)).reshape(2, -1))
    assert not flat[:, n:].any()
    np.testing.assert_array_equal(_bits(pool_hist(tiles, f, b)),
                                  _bits(hist))
    # one leaf alone maps the same as a row of the batch
    np.testing.assert_array_equal(_bits(pool_flat(jnp.asarray(hist[1]))),
                                  _bits(tiles[1]))


def test_pool_split_is_the_subtraction_and_one_pair_write():
    f, b, L = 28, 63, 7
    rng = np.random.default_rng(5)
    parents = rng.standard_normal((L, f, b, 3)).astype(np.float32)
    small = rng.standard_normal((f, b, 3)).astype(np.float32)
    store = pool_flat(jnp.asarray(parents))
    leaf, pair = 2, jnp.asarray([5, 2], jnp.int32)
    new, hist2 = jax.jit(pool_split)(store, jnp.int32(leaf), pair,
                                     jnp.asarray(small))
    large = parents[leaf] - small
    np.testing.assert_array_equal(_bits(hist2[0]), _bits(small))
    np.testing.assert_array_equal(_bits(hist2[1]), _bits(large))
    got = np.asarray(pool_hist(new, f, b))
    np.testing.assert_array_equal(_bits(got[5]), _bits(small))
    np.testing.assert_array_equal(_bits(got[2]), _bits(large))
    for other in (0, 1, 3, 4, 6):
        np.testing.assert_array_equal(_bits(got[other]),
                                      _bits(parents[other]))
    assert not _bits(new).reshape(L, -1)[:, 3 * f * b:].any()


# ---- the trees are the flat pool's --------------------------------------
#
# Recorded from the grower that carried the pool as [L, 3 * F * B] rows, on
# this data: 4,000 x 300 columns, 63 bins, 63 leaves, whose leaf row (3 *
# 300 * 63 = 56,700 floats) pads to 448 rows of 128 lanes.  The pool is a
# layout: the same f32 subtraction gives the same trees bit for bit.

N, F, B, L = 4000, 300, 63, 63
MODEL_DIGEST = "6c6c88f10e799758"       # lgb.train, 3 trees, model text
TREE_DIGEST = "4b88b46567214856"        # make_grower: every tree array
ROW_LEAF_DIGEST = "90fe36173b78b896"    # ... and its row -> leaf map


def _sha(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def _wide_data():
    rng = np.random.default_rng(41)
    X = rng.standard_normal((N, F)).astype(np.float32)
    y = (X[:, :20] @ rng.standard_normal(20)
         + 0.5 * rng.standard_normal(N) > 0).astype(np.float32)
    return rng, X, y


def test_wide_model_text_is_the_flat_pools():
    _, X, y = _wide_data()
    bst = lgb.train(dict(objective="binary", num_leaves=L,
                         min_data_in_leaf=5, max_bin=B, verbose=-1),
                    lgb.Dataset(X, label=y), num_boost_round=3)
    text = bst.model_to_string()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == MODEL_DIGEST


def test_wide_tree_and_row_leaf_are_the_flat_pools():
    rng, _, _ = _wide_data()
    cfg = GrowerConfig(num_leaves=L, min_data_in_leaf=5, max_bin=B,
                       hist_method="segment")
    meta = FeatureMeta(num_bin=jnp.full((F,), B, jnp.int32),
                       missing_type=jnp.zeros((F,), jnp.int32),
                       default_bin=jnp.zeros((F,), jnp.int32),
                       is_categorical=jnp.zeros((F,), bool))
    bins = rng.integers(0, B, size=(N, F)).astype(np.uint8)
    g = rng.standard_normal(N).astype(np.float32)
    h = (0.1 + rng.random(N)).astype(np.float32)
    tree, row_leaf = jax.jit(make_grower(cfg))(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
        jnp.ones((N,), jnp.float32), meta, jnp.ones((F,), bool))
    assert int(tree.num_leaves) == L
    assert _sha(*jax.tree_util.tree_leaves(tree)) == TREE_DIGEST
    assert _sha(row_leaf) == ROW_LEAF_DIGEST


# ---- which pool a program was built with --------------------------------

@pytest.mark.parametrize("f,b,k", WIDTHS[1:])
def test_layout_counter_fires_once_a_trace(f, b, k):
    n = 512
    cfg = GrowerConfig(num_leaves=4, min_data_in_leaf=1, max_bin=b,
                       hist_method="segment")
    meta = FeatureMeta(num_bin=jnp.full((f,), b, jnp.int32),
                       missing_type=jnp.zeros((f,), jnp.int32),
                       default_bin=jnp.zeros((f,), jnp.int32),
                       is_categorical=jnp.zeros((f,), bool))
    args = (jnp.zeros((n, f), jnp.uint8 if b <= 256 else jnp.uint16),
            jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32),
            jnp.ones((n,), jnp.float32), meta, jnp.ones((f,), bool))
    key = f"leaf_tiles={k // 8},pad={k * 128 - 3 * f * b}"
    before = counters.get("hist_pool_layout").get(key, 0)
    jaxpr = jax.make_jaxpr(make_grower(cfg))(*args)
    assert counters.get("hist_pool_layout")[key] == before + 1
    # the loop carries the pool in that shape
    carried = [v.aval for e in jaxpr.eqns if e.primitive.name == "while"
               for v in e.outvars]
    assert any(a.shape == (4, k, 128) and a.dtype == jnp.float32
               for a in carried)
