"""``grower.partition_window``: the stable two-way partition of one leaf's
window of ``order`` against a numpy oracle.

The contract is the reference's ``DataPartition::Split``
(src/treelearner/data_partition.hpp:94-146): the leaf's rows that go left
keep their sequence, then those that go right keep theirs, and no slot
outside the leaf's own is written — the rows of the next leaf that the
power-of-two window also covers, and the sentinel tail, come back as they
went in.  The one transport (one sort of the window) is held to it, and
whatever replaces it has to pass this file unchanged.  The window's size
comes from ``grower._bucket_sizes``, whose table and whose lookup
(``_bucket_index``) are pinned here too.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.grower import (HALF_STEP_ABOVE_LOG2, GrowerConfig,  # noqa: E402
                                 _bucket_index, _bucket_sizes, _order_tail,
                                 pack_row_bits, partition_window)


def _oracle(order, start, cnt, left):
    seg = order[start:start + cnt]
    goes = left[seg]
    want = order.copy()
    want[start:start + cnt] = np.concatenate([seg[goes], seg[~goes]])
    return want, int(goes.sum())


def _check(rng, n, start, size, cnt, frac, msg="", tail=None):
    """``order``: a permutation of the N rows, then ``tail`` sentinels
    (``size`` of them unless said)."""
    order = np.concatenate([rng.permutation(n).astype(np.int32),
                            np.full(size if tail is None else tail, n,
                                    np.int32)])
    left = rng.rand(n) < frac
    got, nl = jax.jit(partition_window, static_argnums=3)(
        jnp.asarray(order), jnp.int32(start), jnp.int32(cnt), size,
        pack_row_bits(jnp.asarray(left)))
    want, want_nl = _oracle(order, start, cnt, left)
    assert int(nl) == want_nl, msg
    np.testing.assert_array_equal(np.asarray(got), want, err_msg=msg)


@pytest.mark.parametrize("size,cnt", [
    (1024, 1024),       # the leaf fills its window
    (1024, 700),        # the window runs on into the next leaf's rows
    (2048, 1),          # one row
    (512, 0),           # an empty leaf: nothing moves
    (1536, 1300),       # a window that is no power of two
])
def test_partition_window_matches_stable_partition_oracle(size, cnt):
    _check(np.random.RandomState(size + cnt), n=5000, start=1700,
           size=size, cnt=cnt, frac=0.4)


@pytest.mark.parametrize("where", ["fills", "next_leaf", "sentinel_tail"])
@pytest.mark.parametrize("size", [3072, 6144, 12288, 24576])
def test_partition_window_half_step_sizes(size, where):
    """Windows of ``3 * 2^(k-1)`` slots: the leaf fills the window, the
    window runs on into the next leaf's rows, and the last leaf's window
    runs into a sentinel tail that is no longer than its overhang."""
    n = size + 4000
    cnt = {"fills": size, "next_leaf": (7 * size) // 10,
           "sentinel_tail": size - 300}[where]
    start = n - cnt if where == "sentinel_tail" else 1700
    _check(np.random.RandomState(size + cnt), n=n, start=start, size=size,
           cnt=cnt, frac=0.55,
           tail=300 if where == "sentinel_tail" else None)


def test_partition_window_randomized_sweep():
    """Window size, leaf size, left share (all left, all right and empty
    among them) and the window's place, the last leaf's window running
    into the sentinel tail among them."""
    rng = np.random.RandomState(99)
    n = 3000
    for trial in range(25):
        size = 64 * rng.randint(1, 33)
        cnt = int(rng.choice([0, 1, size, size - 1, rng.randint(1, size + 1)]))
        frac = float(rng.choice([0.0, 1.0, rng.rand()]))
        start = int(rng.choice([0, n - cnt, rng.randint(0, n - cnt + 1)]))
        _check(rng, n, start, size, cnt, frac,
               msg=f"trial={trial} size={size} cnt={cnt} frac={frac} "
                   f"start={start}")


# ---- the window table ------------------------------------------------------

TABLE_ROWS = [1, 64, 5000, 400000, 10500000]


@pytest.mark.parametrize("n", TABLE_ROWS)
def test_bucket_sizes_table(n):
    """Ascending; the last size is the first that holds ``n`` (a larger one
    could never be selected and would still be compiled and set ``order``'s
    tail); consecutive sizes at most double, and grow by at most a half
    above the threshold, below which there is no half-step."""
    cfg = GrowerConfig()
    sizes = _bucket_sizes(cfg, n)
    edge = 1 << HALF_STEP_ABOVE_LOG2
    assert sizes[0] == 1 << cfg.bucket_min_log2
    assert sizes == sorted(set(sizes))
    assert sizes[-1] >= n and all(s < n for s in sizes[:-1])
    for a, b in zip(sizes, sizes[1:]):
        assert b <= 2 * a
        if a >= edge:
            assert 2 * b <= 3 * a, (a, b)
    assert all(s & (s - 1) == 0 for s in sizes if s <= edge)
    halves = [s for s in sizes if s & (s - 1)]
    assert all(s % 3 == 0 and (s // 3) & (s // 3 - 1) == 0 for s in halves)
    # what the cells and the CPU tests compile: 10 sizes more than the 19
    # powers of two at Higgs's rows, 6 more than 14 at epsilon's, none here
    assert (len(sizes), len(halves)) == {
        1: (1, 0), 64: (1, 0), 5000: (8, 0), 400000: (20, 6),
        10500000: (29, 11)}[n]


@pytest.mark.parametrize("n", TABLE_ROWS)
def test_bucket_index_at_every_boundary(n):
    """``cnt`` = size - 1, size, size + 1 for every size of the table picks
    the smallest window that holds it, and that window, for the LAST leaf
    of ``order`` (the one that ends at ``n``), stays inside the sentinel
    tail: ``_order_tail`` is the widest step of the table, and one slot
    less would not do."""
    sizes = _bucket_sizes(GrowerConfig(), n)
    tail = _order_tail(sizes)
    cnts = sorted({c for s in sizes for c in (0, s - 1, s, s + 1)
                   if 0 <= c <= min(n, sizes[-1])} | {n})
    got = np.asarray(jax.jit(jax.vmap(lambda c: _bucket_index(c, sizes)))(
        jnp.asarray(cnts, jnp.int32)))
    overhang = []
    for c, k in zip(cnts, got):
        assert sizes[k] >= c and (k == 0 or sizes[k - 1] < c), (c, k)
        overhang.append((n - c) + sizes[k] - n)
    assert max(overhang) <= tail
    assert max(overhang) == tail or n < sizes[0]
    assert tail == {1: 64, 64: 64, 5000: 4095, 400000: 131071,
                    10500000: 4194303}[n]


def test_partition_window_sizes_metric_reads_the_size_tag():
    """The benchmark's ``partition_window_sizes`` counts the distinct
    ``size`` tags of ``partition_route_dispatch``: as many as the table has
    from a traced grower, None from a program that does not tag."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmarks.harness import metrics
    from lightgbm_tpu.grower import FeatureMeta, make_grower
    from lightgbm_tpu.obs.counters import counters
    n, f, b = 20000, 3, 8
    cfg = GrowerConfig(num_leaves=4, min_data_in_leaf=1, max_bin=b,
                       hist_method="segment")
    meta = FeatureMeta(num_bin=jnp.full((f,), b, jnp.int32),
                       missing_type=jnp.zeros((f,), jnp.int32),
                       default_bin=jnp.zeros((f,), jnp.int32),
                       is_categorical=jnp.zeros((f,), bool))
    one = jnp.ones((n,), jnp.float32)
    counters.reset()
    for _ in range(2):      # a second trace adds counts, not sizes
        jax.jit(make_grower(cfg)).lower(
            jnp.zeros((n, f), jnp.uint8), one, one, one, meta,
            jnp.ones((f,), bool))
    sizes = _bucket_sizes(cfg, n)
    assert counters.get("partition_route_dispatch") == {
        f"read=column,size={s}": 2 for s in sizes}
    assert metrics.read_metric("partition_window_sizes", {}) == len(sizes) \
        == 11
    counters.reset()
    counters.inc("partition_route_dispatch", 10, read="column")
    assert metrics.read_metric("partition_window_sizes", {}) is None
    counters.reset()
    assert metrics.read_metric("partition_window_sizes", {}) is None
