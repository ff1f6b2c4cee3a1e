"""Word-packed histogram gather (`gather_words`) — the TPU gather-cost
optimization must be bit-neutral: packing 4 uint8 (2 uint16) bin columns
per gathered uint32 word changes data movement only, never the histogram,
the tree, or the row→leaf map.  Off-TPU the 'auto' knob resolves to 'off',
so this is the only coverage the words path gets without a chip."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.grower import (FeatureMeta, GrowerConfig, make_grower,
                                 pack_gather_words, unpack_gather_words)


@pytest.mark.parametrize("dtype,cols", [(np.uint8, 1), (np.uint8, 7),
                                        (np.uint16, 5), (np.uint16, 2)])
def test_pack_roundtrip(dtype, cols):
    rng = np.random.RandomState(3)
    hi = np.iinfo(dtype).max
    mat = rng.randint(0, hi + 1, size=(129, cols)).astype(dtype)
    words, per = pack_gather_words(jnp.asarray(mat))
    assert per == (4 if dtype == np.uint8 else 2)
    back = np.asarray(unpack_gather_words(words, cols, per))
    assert np.array_equal(back, mat.astype(np.int32))


def test_pack_rejects_wide_dtypes():
    with pytest.raises(AssertionError):
        pack_gather_words(jnp.zeros((4, 4), jnp.int32))


def test_grow_words_on_off_identical():
    rng = np.random.RandomState(11)
    n, f, b = 6000, 9, 47
    bins = jnp.asarray(rng.randint(0, b, size=(n, f), dtype=np.uint8))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    h = jnp.asarray(np.ones(n, np.float32))
    c = jnp.asarray(np.ones(n, np.float32))
    meta = FeatureMeta(num_bin=jnp.full((f,), b, jnp.int32),
                       missing_type=jnp.zeros((f,), jnp.int32),
                       default_bin=jnp.zeros((f,), jnp.int32),
                       is_categorical=jnp.zeros((f,), bool))
    fv = jnp.ones((f,), bool)
    outs = {}
    for words in ("off", "on"):
        cfg = GrowerConfig(num_leaves=31, min_data_in_leaf=1, max_bin=b,
                           hist_method="segment", bucket_min_log2=6,
                           gather_words=words)
        tree, row_leaf = jax.jit(make_grower(cfg))(bins, g, h, c, meta, fv)
        outs[words] = jax.tree.map(np.asarray, (tree, row_leaf))
    ref_tree, ref_rl = outs["off"]
    got_tree, got_rl = outs["on"]
    for a, bb in zip(ref_tree, got_tree):
        assert np.array_equal(a, bb)
    assert np.array_equal(ref_rl, got_rl)
    # row_leaf really is a leaf id per row consistent with leaf counts
    num_leaves = int(ref_tree.num_leaves)
    counts = np.bincount(ref_rl, minlength=num_leaves)
    assert counts.sum() == n
    assert np.array_equal(
        np.sort(counts[:num_leaves]),
        np.sort(ref_tree.leaf_count[:num_leaves].astype(np.int64)))


def test_grow_ordered_bins_identical():
    """ordered_bins=on maintains a leaf-ordered data copy whose windows
    present rows in exactly the gather path's sequence — trees and
    row_leaf must be bit-identical to the gather path."""
    rng = np.random.RandomState(7)
    n, f, b = 6000, 9, 47
    bins = jnp.asarray(rng.randint(0, b, size=(n, f), dtype=np.uint8))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    h = jnp.asarray(np.ones(n, np.float32))
    c = jnp.asarray(np.ones(n, np.float32))
    meta = FeatureMeta(num_bin=jnp.full((f,), b, jnp.int32),
                       missing_type=jnp.zeros((f,), jnp.int32),
                       default_bin=jnp.zeros((f,), jnp.int32),
                       is_categorical=jnp.zeros((f,), bool))
    fv = jnp.ones((f,), bool)
    outs = {}
    for mode in ("off", "on"):
        cfg = GrowerConfig(num_leaves=31, min_data_in_leaf=1, max_bin=b,
                           hist_method="segment", bucket_min_log2=6,
                           ordered_bins=mode)
        tree, row_leaf = jax.jit(make_grower(cfg))(bins, g, h, c, meta, fv)
        outs[mode] = jax.tree.map(np.asarray, (tree, row_leaf))
    for a, bb in zip(outs["off"][0], outs["on"][0]):
        assert np.array_equal(a, bb)
    assert np.array_equal(outs["off"][1], outs["on"][1])


def test_grow_ordered_bins_identical_efb_end_to_end():
    """ordered_bins through the full training stack with EFB bundles and
    bagging: model text must match the gather path exactly."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(8)
    n = 3000
    dense = rng.randn(n, 4)
    onehot = (rng.rand(n, 12) < 0.06).astype(np.float64) \
        * rng.randint(1, 4, size=(n, 12))
    X = np.concatenate([dense, onehot], axis=1)
    y = (dense[:, 0] + (onehot[:, 3] > 0) + 0.2 * rng.randn(n) > 0.4)
    y = y.astype(np.float64)
    texts = {}
    for mode in ("off", "on"):
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "min_data_in_leaf": 5, "bagging_fraction": 0.8,
                  "bagging_freq": 1, "seed": 7, "ordered_bins": mode,
                  "enable_bin_packing": False}
        bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
        texts[mode] = bst.model_to_string()
    assert texts["off"] == texts["on"]


def test_grow_partition_sort_identical():
    """partition_impl=sort (stable 3-way-key payload sort) must reproduce
    the rank-scatter partition bit for bit, including past-the-leaf window
    slots returning to their original positions."""
    rng = np.random.RandomState(9)
    n, f, b = 6000, 9, 47
    bins = jnp.asarray(rng.randint(0, b, size=(n, f), dtype=np.uint8))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    h = jnp.asarray(np.ones(n, np.float32))
    c = jnp.asarray(np.ones(n, np.float32))
    meta = FeatureMeta(num_bin=jnp.full((f,), b, jnp.int32),
                       missing_type=jnp.zeros((f,), jnp.int32),
                       default_bin=jnp.zeros((f,), jnp.int32),
                       is_categorical=jnp.zeros((f,), bool))
    fv = jnp.ones((f,), bool)
    outs = {}
    for impl in ("scatter", "sort"):
        cfg = GrowerConfig(num_leaves=31, min_data_in_leaf=1, max_bin=b,
                           hist_method="segment", bucket_min_log2=6,
                           partition_impl=impl)
        tree, row_leaf = jax.jit(make_grower(cfg))(bins, g, h, c, meta, fv)
        outs[impl] = jax.tree.map(np.asarray, (tree, row_leaf))
    for a, bb in zip(outs["scatter"][0], outs["sort"][0]):
        assert np.array_equal(a, bb)
    assert np.array_equal(outs["scatter"][1], outs["sort"][1])


def test_grow_partition_sort_with_ordered_bins_identical():
    """sort partition carrying the leaf-ordered payloads (packed bin words
    + bitcast weights) must match the scatter+gather baseline bit for bit."""
    rng = np.random.RandomState(10)
    n, f, b = 6000, 9, 47
    bins = jnp.asarray(rng.randint(0, b, size=(n, f), dtype=np.uint8))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    h = jnp.asarray(np.abs(rng.randn(n)).astype(np.float32))
    c = jnp.asarray(np.ones(n, np.float32))
    meta = FeatureMeta(num_bin=jnp.full((f,), b, jnp.int32),
                       missing_type=jnp.zeros((f,), jnp.int32),
                       default_bin=jnp.zeros((f,), jnp.int32),
                       is_categorical=jnp.zeros((f,), bool))
    fv = jnp.ones((f,), bool)
    outs = {}
    for ordered, impl in (("off", "scatter"), ("on", "sort")):
        cfg = GrowerConfig(num_leaves=31, min_data_in_leaf=1, max_bin=b,
                           hist_method="segment", bucket_min_log2=6,
                           ordered_bins=ordered, partition_impl=impl)
        tree, row_leaf = jax.jit(make_grower(cfg))(bins, g, h, c, meta, fv)
        outs[(ordered, impl)] = jax.tree.map(np.asarray, (tree, row_leaf))
    ref = outs[("off", "scatter")]
    got = outs[("on", "sort")]
    for a, bb in zip(ref[0], got[0]):
        assert np.array_equal(a, bb)
    assert np.array_equal(ref[1], got[1])


@pytest.mark.parametrize("ordered,impl", [("off", "sort"), ("on", "sort")])
def test_grow_missing_routing_ordered_sort(ordered, impl):
    """NaN- and zero-missing routing decisions must survive the ordered /
    sort paths bit for bit (default_left handling happens on the routing
    column read, which differs per path)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(12)
    n = 4000
    X = rng.randn(n, 6)
    X[rng.rand(n, 6) < 0.15] = np.nan          # NaN missing
    X[:, 2] = np.where(rng.rand(n) < 0.5, 0.0, X[:, 2])  # zero-heavy col
    y = ((np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 1])) > 0).astype(float)
    base = {"objective": "binary", "num_leaves": 15, "verbose": -1,
            "min_data_in_leaf": 5, "use_missing": True,
            "enable_bin_packing": False}
    ref = lgb.train(dict(base), lgb.Dataset(X, label=y), num_boost_round=5)
    got = lgb.train(dict(base, ordered_bins=ordered, partition_impl=impl),
                    lgb.Dataset(X, label=y), num_boost_round=5)
    assert ref.model_to_string() == got.model_to_string()


def test_grow_bucket_scheme_pow15_identical():
    """pow15 buckets change only padded (masked) work — trees identical."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(13)
    n = 5000
    X = rng.randn(n, 8)
    y = (X[:, 0] + 0.5 * rng.randn(n) > 0).astype(float)
    base = {"objective": "binary", "num_leaves": 31, "verbose": -1,
            "min_data_in_leaf": 3, "enable_bin_packing": False}
    ref = lgb.train(dict(base), lgb.Dataset(X, label=y), num_boost_round=5)
    got = lgb.train(dict(base, bucket_scheme="pow15"),
                    lgb.Dataset(X, label=y), num_boost_round=5)
    assert ref.model_to_string() == got.model_to_string()


def test_grow_gather_panel_identical():
    """Folding the bitcast weight columns into the word gather (one row
    gather per split) moves identical bits — trees bit-identical with the
    panel on or off, with and without bagging weights."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(31)
    n = 4000
    X = rng.randn(n, 6)
    y = (X[:, 0] + 0.4 * rng.randn(n) > 0).astype(float)
    for extra in ({}, {"bagging_fraction": 0.7, "bagging_freq": 1}):
        base = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                "min_data_in_leaf": 5, "gather_words": "on",
                "enable_bin_packing": False}
        base.update(extra)
        ref = lgb.train(dict(base, gather_panel="off"),
                        lgb.Dataset(X, label=y), num_boost_round=4)
        got = lgb.train(dict(base, gather_panel="on"),
                        lgb.Dataset(X, label=y), num_boost_round=4)
        assert ref.model_to_string() == got.model_to_string(), extra


# ---- the routing read against a plain numpy router -------------------------
#
# The partition slices the split column out of a column-major copy, routes
# ALL its rows, packs the decisions to bits and gathers one word per window
# row.  The router below knows nothing of that: it replays
# the grown tree's splits on ``bins[rows, col]`` with its own copy of the
# decision rule (tree.h:257-313) and must arrive at every split's left
# count and at the grower's row -> leaf map.

def _route_case(kind):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(26)
    n = 4000
    params = {"max_bin": 31, "verbose": -1}
    cat = "auto"
    if kind == "numeric_missing":
        X = rng.randn(n, 5)
        X[rng.rand(n, 5) < 0.1] = np.nan
    elif kind == "categorical":
        X = np.concatenate(
            [rng.randn(n, 2),
             rng.randint(0, 12, size=(n, 2)).astype(np.float64)], axis=1)
        cat = [2, 3]
    elif kind == "efb":
        sparse = np.zeros((n, 10))
        act = np.where(rng.rand(n) < 0.5)[0]    # mutually exclusive columns
        sparse[act, rng.randint(0, 10, size=act.size)] = rng.randint(
            1, 4, size=act.size)
        X = np.concatenate([rng.randn(n, 3), sparse], axis=1)
    elif kind == "uint16":
        X = rng.randn(n, 3)
        params.update(max_bin=400, min_data_in_bin=1)
    y = (np.nan_to_num(X[:, 0]) + 0.7 * np.nan_to_num(X[:, -1])
         + 0.3 * rng.randn(n) > 0.3).astype(np.float64)
    td = lgb.Dataset(X, label=y, params=params,
                     categorical_feature=cat).construct().constructed
    return td.binned, td.feature_meta(), td.max_num_bin(), y


def _numpy_route(bins, fm, tree, num_leaves):
    """Replay the splits in node order: node i splits the leaf that keeps
    its id on the left and opens leaf i + 1 on the right."""
    n = bins.shape[0]
    row_leaf = np.zeros(n, np.int32)
    left_counts = []
    for node in range(num_leaves - 1):
        child = node
        while child >= 0:               # the split leaf's id: leftmost leaf
            child = int(tree.left_child[child])
        leaf = ~child
        rows = np.where(row_leaf == leaf)[0]
        feat = int(tree.split_feature[node])
        col = int(fm["col"][feat]) if "col" in fm else feat
        b = bins[rows, col].astype(np.int64)
        nb, db = int(fm["num_bin"][feat]), int(fm["default_bin"][feat])
        off = int(fm["offset"][feat]) if "offset" in fm else -1
        if off >= 0:                    # bundle slot -> the feature's own bin
            local = b - off
            inside = (local >= 0) & (local < nb - 1)
            b = np.where(inside, local + (local >= db), db)
        if tree.is_cat[node]:
            left = tree.cat_bins[node][np.clip(b, 0, tree.cat_bins.shape[1] - 1)]
        else:
            mt = int(fm["missing_type"][feat])
            missing = (b == nb - 1) if mt == 2 else (b == db) if mt == 1 \
                else np.zeros(len(b), bool)
            left = np.where(missing, bool(tree.default_left[node]),
                            b <= int(tree.threshold_bin[node]))
        left_counts.append(int(left.sum()))
        row_leaf[rows[~left]] = node + 1
    return left_counts, row_leaf


@pytest.mark.parametrize("kind", ["numeric_missing", "categorical", "efb",
                                  "uint16"])
def test_grow_routes_like_plain_numpy_router(kind):
    from lightgbm_tpu.obs.counters import counters
    bins, fm, max_bin, y = _route_case(kind)
    assert bins.dtype == (np.uint16 if kind == "uint16" else np.uint8)
    assert ("col" in fm) == (kind == "efb")
    n = bins.shape[0]
    meta = FeatureMeta(**{k: jnp.asarray(v) for k, v in fm.items()})
    cfg = GrowerConfig(num_leaves=31, min_data_in_leaf=5, max_bin=max_bin,
                       hist_method="segment", bucket_min_log2=6,
                       has_categorical=bool(fm["is_categorical"].any()),
                       has_missing=bool((fm["missing_type"] != 0).any()))
    g = jnp.asarray((0.5 - y).astype(np.float32))
    one = jnp.ones((n,), jnp.float32)
    before = counters.get("partition_route_dispatch").get("read=column", 0)
    tree, row_leaf = jax.jit(make_grower(cfg))(
        jnp.asarray(bins), g, one * 0.25, one, meta,
        jnp.ones((len(fm["num_bin"]),), bool))
    # one count per traced partition branch: the grower says which read
    # it was built with
    assert counters.snapshot()["counters"]["partition_route_dispatch"][
        "read=column"] > before
    tree = jax.tree.map(np.asarray, tree)
    num_leaves = int(tree.num_leaves)
    assert num_leaves > 8
    if kind == "categorical":
        assert tree.is_cat[:num_leaves - 1].any()
    if kind == "efb":
        assert (fm["offset"][tree.split_feature[:num_leaves - 1]] >= 0).any()
    left_counts, want_leaf = _numpy_route(bins, fm, tree, num_leaves)
    for node, cnt in enumerate(left_counts):
        lc = int(tree.left_child[node])
        got = tree.internal_count[lc] if lc >= 0 else tree.leaf_count[~lc]
        assert int(got) == cnt, (kind, node, int(got), cnt)
    assert np.array_equal(np.asarray(row_leaf), want_leaf)


# ---- the bit tables the routing read is made of ----------------------------

@pytest.mark.parametrize("n", [1, 31, 32, 33, 1000, 4097, 70001])
def test_pack_row_bits_round_trip(n):
    """Every row's flag comes back from the packed table, whatever N is to
    the 32 planes (the last plane is short, or empty, unless 32 * M == N)."""
    from lightgbm_tpu.grower import pack_row_bits, take_row_bits
    flags = np.random.RandomState(n).rand(n) < 0.4
    words = jax.jit(pack_row_bits)(jnp.asarray(flags))
    m = words.shape[0]
    assert words.dtype == jnp.uint32 and m & (m - 1) == 0
    assert 32 * m >= n and (m == 1 or 16 * m < n)
    rows = jnp.asarray(np.random.RandomState(1).permutation(n).astype(np.int32))
    got = take_row_bits(words, rows)
    assert np.array_equal(np.asarray(got), flags[np.asarray(rows)])


@pytest.mark.parametrize("nb", [5, 32, 33, 255, 256, 1024, 1025])
def test_bin_flags_is_the_table_lookup(nb):
    """The select chain over packed words reads what ``flags[binf]``
    reads, whatever B is to the 32 bits of a word."""
    from lightgbm_tpu.grower import bin_flags
    rng = np.random.RandomState(nb)
    flags = rng.rand(nb) < 0.5
    binf = rng.randint(0, nb, size=(3, 500)).astype(np.int32)
    binf[0, :2] = (0, nb - 1)
    got = jax.jit(bin_flags)(jnp.asarray(flags), jnp.asarray(binf))
    assert got.dtype == jnp.bool_ and got.shape == binf.shape
    assert np.array_equal(np.asarray(got), flags[binf])
