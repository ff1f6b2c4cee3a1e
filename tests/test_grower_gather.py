"""The grower's row movement: the word packing the XLA reference rungs
gather through, and the partition's routing read against a plain numpy
router that knows nothing of how the grower moves rows."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.grower import (HALF_STEP_ABOVE_LOG2, FeatureMeta,
                                 GrowerConfig, _partition_sizes, make_grower,
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


# ---- the routing read against a plain numpy router -------------------------
#
# The partition slices the split column out of a column-major copy, routes
# ALL its rows, packs the decisions to bits and gathers one word per window
# row, or, for a leaf larger than its window table holds, sorts all the rows
# keyed on the dense row -> leaf vector (PR 35).  The router below knows
# nothing of that: it replays
# the grown tree's splits on ``bins[rows, col]`` with its own copy of the
# decision rule (tree.h:257-313) and must arrive at every split's left
# count and at the grower's row -> leaf map.

def _route_case(kind):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(26)
    # half_step: rows enough for the partition's table to keep windows of
    # 3 * 2^12 and 3 * 2^13 slots (it ends where the dense branch is the
    # cheaper one: ``grower._partition_sizes``)
    n = 200000 if kind == "half_step" else 4000
    params = {"max_bin": 31, "verbose": -1}
    cat = "auto"
    if kind in ("numeric_missing", "half_step"):
        X = rng.randn(n, 5)
        X[rng.rand(n, 5) < 0.1] = np.nan
    elif kind in ("nan_missing", "zero_missing"):
        # the label rises with every column; the rows that miss column 0
        # are mostly positives (they belong right of any threshold) and
        # those that miss column 1 mostly negatives (they belong left)
        X = rng.randn(n, 4)
        lab = X.sum(axis=1) + 0.5 * rng.randn(n) > 0
        hole = np.nan if kind == "nan_missing" else 0.0
        X[(rng.rand(n) < np.where(lab, 0.45, 0.03)), 0] = hole
        X[(rng.rand(n) < np.where(lab, 0.03, 0.45)), 1] = hole
        params["zero_as_missing"] = kind == "zero_missing"
        y = lab.astype(np.float64)
    elif kind == "categorical":
        X = np.concatenate(
            [rng.randn(n, 2),
             rng.randint(0, 12, size=(n, 2)).astype(np.float64)], axis=1)
        cat = [2, 3]
    elif kind == "efb":
        X = _bundled_columns(rng, n)
    elif kind == "uint16":
        X = rng.randn(n, 3)
        params.update(max_bin=400, min_data_in_bin=1)
    if kind not in ("nan_missing", "zero_missing"):
        y = (np.nan_to_num(X[:, 0]) + 0.7 * np.nan_to_num(X[:, -1])
             + 0.3 * rng.randn(n) > 0.3).astype(np.float64)
    td = lgb.Dataset(X, label=y, params=params,
                     categorical_feature=cat).construct().constructed
    return td.binned, td.feature_meta(), td.max_num_bin(), y


def _bundled_columns(rng, n):
    sparse = np.zeros((n, 10))
    act = np.where(rng.rand(n) < 0.5)[0]        # mutually exclusive columns
    sparse[act, rng.randint(0, 10, size=act.size)] = rng.randint(
        1, 4, size=act.size)
    return np.concatenate([rng.randn(n, 3), sparse], axis=1)


def _numpy_route(bins, fm, tree, num_leaves, weight=None):
    """Replay the splits in node order: node i splits the leaf that keeps
    its id on the left and opens leaf i + 1 on the right.  Returns every
    split's left count (the sum of ``weight``, a row each by default) and
    the row -> leaf map."""
    n = bins.shape[0]
    weight = np.ones(n) if weight is None else np.asarray(weight, np.float64)
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
        left_counts.append(int(round(weight[rows[left]].sum())))
        row_leaf[rows[~left]] = node + 1
    return left_counts, row_leaf


def _assert_routed_like_numpy(bins, fm, tree, row_leaf, weight=None, tag=""):
    tree = jax.tree.map(np.asarray, tree)
    num_leaves = int(tree.num_leaves)
    left_counts, want_leaf = _numpy_route(bins, fm, tree, num_leaves, weight)
    for node, cnt in enumerate(left_counts):
        lc = int(tree.left_child[node])
        got = tree.internal_count[lc] if lc >= 0 else tree.leaf_count[~lc]
        assert int(got) == cnt, (tag, node, int(got), cnt)
    assert np.array_equal(np.asarray(row_leaf), want_leaf), tag
    return tree, num_leaves


@pytest.mark.parametrize("kind", ["numeric_missing", "nan_missing",
                                  "zero_missing", "categorical", "efb",
                                  "uint16", "half_step"])
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
    counters.reset()
    tree, row_leaf = jax.jit(make_grower(cfg))(
        jnp.asarray(bins), g, one * 0.25, one, meta,
        jnp.ones((len(fm["num_bin"]),), bool))
    # one count per traced partition branch: the grower says which read
    # and which window sizes it was built with, the dense branch after them
    sizes = _partition_sizes(cfg, n)
    assert counters.get("partition_route_dispatch") == {
        **{f"read=column,size={s}": 1 for s in sizes},
        f"read=dense,size={n}": 1}
    tree, num_leaves = _assert_routed_like_numpy(bins, fm, tree, row_leaf,
                                                 tag=kind)
    assert num_leaves > 8
    nodes = slice(0, num_leaves - 1)
    halves = [s for s in sizes if s & (s - 1)]
    split_rows = tree.internal_count[nodes].astype(np.int64)
    # both transports ran: the root and its like by the dense branch,
    # smaller leaves in windows
    assert (split_rows > sizes[-1]).any() and (split_rows <= sizes[-1]).any()
    if kind == "half_step":
        # splits ran in half-step windows: a split leaf's row count picked
        # a size that is no power of two
        assert halves == [12288, 24576]
        picked = {min(s for s in sizes if s >= c)
                  for c in split_rows if c <= sizes[-1]}
        assert picked & set(halves), sorted(picked)
    else:
        assert not halves and sizes[-1] <= 1 << HALF_STEP_ABOVE_LOG2
    if kind == "categorical":
        assert tree.is_cat[nodes].any()
    if kind == "efb":
        assert (fm["offset"][tree.split_feature[nodes]] >= 0).any()
    if kind in ("nan_missing", "zero_missing"):
        # both answers to "where do the missing rows go" were taken, on
        # columns that have missing rows
        mt = 2 if kind == "nan_missing" else 1
        on_missing = fm["missing_type"][tree.split_feature[nodes]] == mt
        assert (tree.default_left[nodes] & on_missing).any()
        assert (~tree.default_left[nodes] & on_missing).any()


def test_grow_dense_columns_route_like_plain_numpy_router():
    """Columns with no missing type and unit hessians, straight into
    ``make_grower``: every window slot past the leaf comes back where it
    was, or the next leaf's rows would be misplaced in the row -> leaf map
    the router arrives at."""
    rng = np.random.RandomState(9)
    n, f, b = 6000, 9, 47
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    fm = {"num_bin": np.full(f, b, np.int32),
          "missing_type": np.zeros(f, np.int32),
          "default_bin": np.zeros(f, np.int32),
          "is_categorical": np.zeros(f, bool)}
    cfg = GrowerConfig(num_leaves=31, min_data_in_leaf=1, max_bin=b,
                       hist_method="segment", bucket_min_log2=6)
    tree, row_leaf = jax.jit(make_grower(cfg))(
        jnp.asarray(bins), jnp.asarray(rng.randn(n).astype(np.float32)),
        jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32),
        FeatureMeta(**{k: jnp.asarray(v) for k, v in fm.items()}),
        jnp.ones((f,), bool))
    _, num_leaves = _assert_routed_like_numpy(bins, fm, tree, row_leaf)
    assert num_leaves == 31


def _record_grow_calls(bst, rounds):
    """Every call ``GBDT.train_one_iter`` makes into the grower over
    ``rounds`` updates: (bins, bag weights, (tree, row_leaf))."""
    gbdt = bst.inner
    grow, calls = gbdt.grow, []

    def recording(bins, gw, hw, cw, meta, feat_valid):
        out = grow(bins, gw, hw, cw, meta, feat_valid)
        calls.append((np.asarray(bins), np.asarray(cw), out))
        return out

    gbdt.grow = recording
    for _ in range(rounds):
        bst.update()
    gbdt.grow = grow
    return calls


def test_training_with_missing_values_routes_like_plain_numpy_router():
    """NaN-missing columns and a zero-heavy one through ``lgb.Booster``,
    at a row count whose largest leaves go through the dense branch (the
    partition's window table ends at 1,024 slots of 12,000 rows) and the
    rest through windows: five trees, each replayed by the numpy router."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(12)
    n = 12000
    X = rng.randn(n, 6)
    X[rng.rand(n, 6) < 0.15] = np.nan          # NaN missing
    X[:, 2] = np.where(rng.rand(n) < 0.5, 0.0, X[:, 2])  # zero-heavy col
    y = ((np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 1])) > 0).astype(float)
    bst = lgb.Booster(
        {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "min_data_in_leaf": 5, "use_missing": True,
         "enable_bin_packing": False}, lgb.Dataset(X, label=y))
    calls = _record_grow_calls(bst, 5)
    fm = bst.inner.train_set.feature_meta()
    assert (fm["missing_type"] == 2).any() and len(calls) == 5
    for i, (bins, cw, (tree, row_leaf)) in enumerate(calls):
        assert bins.shape[0] == n
        tree, num_leaves = _assert_routed_like_numpy(bins, fm, tree,
                                                     row_leaf, tag=i)
        assert num_leaves == 15
        nodes = slice(0, num_leaves - 1)
        assert (fm["missing_type"][tree.split_feature[nodes]] == 2).any()


def test_bagged_bundled_training_routes_like_plain_numpy_router():
    """EFB bundles and ``bagging_fraction`` end to end: every call that
    ``GBDT.train_one_iter`` makes into the grower, with the matrix and the
    bag weights it really passes, is replayed by the numpy router."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(8)
    n = 3000
    X = _bundled_columns(rng, n)
    y = (X[:, 0] + (X[:, 6] > 0) + 0.2 * rng.randn(n) > 0.4).astype(np.float64)
    bst = lgb.Booster(
        {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "min_data_in_leaf": 5, "bagging_fraction": 0.8, "bagging_freq": 1,
         "seed": 7, "enable_bin_packing": False},
        lgb.Dataset(X, label=y))
    calls = _record_grow_calls(bst, 4)
    fm = bst.inner.train_set.feature_meta()
    assert "col" in fm and len(calls) == 4
    for i, (bins, cw, (tree, row_leaf)) in enumerate(calls):
        assert 0 < cw.sum() < n or bins.shape[0] < n    # a bag was drawn
        _, num_leaves = _assert_routed_like_numpy(bins, fm, tree, row_leaf,
                                                  weight=cw, tag=i)
        assert num_leaves > 4


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
