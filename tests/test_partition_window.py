"""``grower.partition_window``: the stable two-way partition of one leaf's
window of ``order`` against a numpy oracle.

The contract is the reference's ``DataPartition::Split``
(src/treelearner/data_partition.hpp:94-146): the leaf's rows that go left
keep their sequence, then those that go right keep theirs, and no slot
outside the leaf's own is written — the rows of the next leaf that the
power-of-two window also covers, and the sentinel tail, come back as they
went in.  Both transports are held to it: one sort of the window
(``partition_window``), and for a leaf larger than the window table's last
size one sort of all N rows keyed on the dense row -> leaf vector
(``partition_dense``, PR 35), which must return the same ``order`` bit for
bit.  The window's size comes from ``grower._bucket_sizes``, the
partition's shorter table from ``_partition_sizes``; both tables and their
lookup (``_bucket_index``) are pinned here too.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.grower import (HALF_STEP_ABOVE_LOG2,  # noqa: E402
                                 WINDOW_SLOT_COSTS_DENSE_ROWS, GrowerConfig,
                                 _bucket_index, _bucket_sizes, _order_tail,
                                 _partition_sizes, _row_leaf_from_intervals,
                                 pack_row_bits, partition_dense,
                                 partition_window)


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



# ---- the dense branch ------------------------------------------------------
#
# ``partition_dense`` reads no window: it sorts all N rows keyed (left /
# right / any other leaf) on the row id and places the first ``cnt`` at
# ``start``.  It rests on every leaf's window of ``order`` ascending by row
# id, so the layouts below are built that way: three leaves (before, the
# one that splits, after), each an ascending run of a random row set.

LEAF, RIGHT = 1, 9      # the splitting leaf keeps its id on the left


def _ascending_layout(rng, n, start, cnt, tail):
    """(order, rl): leaf 0 in ``[0, start)``, LEAF in ``[start, start +
    cnt)``, leaf 2 after it, every window ascending; ``tail`` sentinels."""
    rows = rng.permutation(n).astype(np.int32)
    cuts = [0, start, start + cnt, n]
    order = np.concatenate(
        [np.sort(rows[a:b]) for a, b in zip(cuts, cuts[1:])]
        + [np.full(tail, n, np.int32)])
    rl = np.empty(n, np.int32)
    for leaf, (a, b) in zip((0, LEAF, 2), zip(cuts, cuts[1:])):
        rl[order[a:b]] = leaf
    return order, rl


def _check_dense(rng, n, start, cnt, frac, tail, msg="", window=True):
    order, rl = _ascending_layout(rng, n, start, cnt, tail)
    left = rng.rand(n) < frac
    want, want_nl = _oracle(order, start, cnt, left)
    after = np.where((rl == LEAF) & ~left, RIGHT, rl).astype(np.int32)
    got, nl = jax.jit(partition_dense)(
        jnp.asarray(order), jnp.int32(start), jnp.int32(cnt),
        jnp.asarray(after), jnp.int32(LEAF), jnp.int32(RIGHT))
    assert int(nl) == want_nl, msg
    np.testing.assert_array_equal(np.asarray(got), want, err_msg=msg)
    if not window:
        return
    # and ``partition_window`` itself, in the smallest window of the WHOLE
    # table that holds the leaf, on an ``order`` with tail enough for it
    size = next(s for s in _bucket_sizes(GrowerConfig(), n) if s >= cnt)
    wide = np.concatenate([order[:n], np.full(size, n, np.int32)])
    win, win_nl = jax.jit(partition_window, static_argnums=3)(
        jnp.asarray(wide), jnp.int32(start), jnp.int32(cnt), size,
        pack_row_bits(jnp.asarray(left)))
    assert int(win_nl) == int(nl), msg
    np.testing.assert_array_equal(np.asarray(win)[:n], np.asarray(got)[:n],
                                  err_msg=msg)


DENSE_ROWS = 30000


@pytest.mark.parametrize("frac", [0.45, 1.0, 0.0],
                         ids=["mixed", "all_left", "all_right"])
@pytest.mark.parametrize("fill,where", [
    ("table_end_plus_1", "middle"), ("table_end_plus_1", "end"),
    ("half", "middle"), ("half", "end"), ("all", "end")])
def test_partition_dense_matches_oracle_and_window(fill, where, frac):
    """Leaves of one row more than the partition's table holds (the
    smallest that takes the branch), half of all rows and all of them; in
    the middle of ``order`` and as its last leaf, next to a sentinel tail
    as short as the grower's (``_order_tail`` of the SHORTER table: the
    dense branch slices no window)."""
    n = DENSE_ROWS
    psizes = _partition_sizes(GrowerConfig(), n)
    cnt = {"table_end_plus_1": psizes[-1] + 1, "half": n // 2, "all": n}[fill]
    start = n - cnt if where == "end" else (n - cnt) // 3
    assert _bucket_index(cnt, psizes) == len(psizes)
    _check_dense(np.random.RandomState(cnt + start), n, start, cnt, frac,
                 tail=_order_tail(psizes), msg=f"{fill} {where} {frac}")


def test_partition_dense_randomized_sweep():
    """Leaf size (empty and one row among them: the branch is right for
    any leaf, the table only says where it is cheaper), left share and
    place, with a tail of any length, none among them."""
    rng = np.random.RandomState(35)
    n = 3000
    for trial in range(25):
        cnt = int(rng.choice([0, 1, n, n - 1, rng.randint(1, n + 1)]))
        frac = float(rng.choice([0.0, 1.0, rng.rand()]))
        start = int(rng.choice([0, n - cnt, rng.randint(0, n - cnt + 1)]))
        tail = int(rng.choice([0, 1, 64, rng.randint(1, 2000)]))
        _check_dense(rng, n, start, cnt, frac, tail, window=trial % 5 == 0,
                     msg=f"trial={trial} cnt={cnt} frac={frac} start={start} "
                         f"tail={tail}")


def test_partition_dense_refuses_a_key_that_overflows():
    n = 2 ** 31 // 3 + 1
    with pytest.raises(ValueError, match="overflows"):
        jax.eval_shape(partition_dense,
                       jax.ShapeDtypeStruct((n + 64,), jnp.int32),
                       jnp.int32(0), jnp.int32(5),
                       jax.ShapeDtypeStruct((n,), jnp.int32),
                       jnp.int32(0), jnp.int32(1))


@pytest.mark.parametrize("n,leaves", [(3000, 12), (20000, 8)])
def test_every_window_ascends_and_rl_is_the_row_leaf_map(n, leaves,
                                                         monkeypatch):
    """The invariant the dense branch rests on, on a grown tree: after
    EVERY split each leaf's window of ``order`` ascends by row id, the
    windows tile ``[0, n)``, and the dense vector ``rl`` in the loop state
    is the row -> leaf map that ``_row_leaf_from_intervals`` recovers from
    ``order`` (the grower returns the latter; ROADMAP S3).  The loop's
    state after k splits is the carry of a grower capped at k steps, read
    where ``lax.while_loop`` hands it back."""
    from lightgbm_tpu import grower
    from lightgbm_tpu.grower import FeatureMeta, make_grower
    rng = np.random.RandomState(n)
    f, b = 5, 32
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    grad = (bins[:, 0] > 11) * 1.0 - (bins[:, 1] > 20) + rng.randn(n)
    cfg = GrowerConfig(num_leaves=leaves, min_data_in_leaf=1, max_bin=b,
                       hist_method="segment", has_missing=False)
    meta = FeatureMeta(num_bin=jnp.full((f,), b, jnp.int32),
                       missing_type=jnp.zeros((f,), jnp.int32),
                       default_bin=jnp.zeros((f,), jnp.int32),
                       is_categorical=jnp.zeros((f,), bool))
    states = []

    class Lax:
        def __getattr__(self, name):
            return getattr(jax.lax, name)

        def while_loop(self, cond, body, init):
            states.append(jax.lax.while_loop(cond, body, init))
            return states[-1]
    monkeypatch.setattr(grower, "lax", Lax())
    grow = make_grower(cfg, step_limit=True)
    one = jnp.ones((n,), jnp.float32)
    psizes = _partition_sizes(cfg, n)
    dense_splits = 0
    for k in range(1, leaves):
        tree, row_leaf = grow(jnp.int32(k), jnp.asarray(bins),
                              jnp.asarray(grad.astype(np.float32)), one,
                              one, meta, jnp.ones((f,), bool))
        st = states[-1]
        assert int(st.step) == k
        order, rl = np.asarray(st.order), np.asarray(st.rl)
        lsc = np.asarray(st.lsc)[:k + 1]
        assert sorted(order[:n]) == list(range(n))
        assert (order[n:] == n).all()
        covered = 0
        for leaf, (start, cnt) in enumerate(lsc):
            win = order[start:start + cnt]
            assert (np.diff(win) > 0).all(), (k, leaf)
            assert (rl[win] == leaf).all(), (k, leaf)
            covered += cnt
        assert covered == n
        np.testing.assert_array_equal(rl, np.asarray(row_leaf))
        np.testing.assert_array_equal(rl, np.asarray(
            _row_leaf_from_intervals(st.order, st.lsc[:, 0], st.lsc[:, 1], n)))
        dense_splits += int(np.asarray(tree.internal_count)[k - 1]
                            > psizes[-1])
    # both transports ran: the root's split and the next ones dense, the
    # later ones in windows
    assert 1 <= dense_splits < leaves - 1

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
def test_partition_sizes_table(n):
    """The partition's table is the whole table's prefix up to the last
    size of at most ``n / WINDOW_SLOT_COSTS_DENSE_ROWS`` slots (a window of
    more costs more than the dense branch over all ``n`` rows), and the
    smallest size whatever ``n``: a function of ``n`` alone."""
    cfg = GrowerConfig()
    whole, sizes = _bucket_sizes(cfg, n), _partition_sizes(cfg, n)
    assert sizes == whole[:len(sizes)] and sizes[0] == whole[0]
    assert all(s * WINDOW_SLOT_COSTS_DENSE_ROWS <= n for s in sizes[1:])
    if len(sizes) < len(whole):
        assert whole[len(sizes)] * WINDOW_SLOT_COSTS_DENSE_ROWS > n
    halves = [s for s in sizes if s & (s - 1)]
    # what the cells and the CPU tests compile, the dense branch apart:
    # 22 windows where there were 29 at Higgs's and expo's rows (the seven
    # that went are the largest, 1,572,864 to 12,582,912 slots), 13 for
    # 20 at epsilon's; (sizes, half-steps among them, the last size)
    assert (len(sizes), len(halves), sizes[-1]) == {
        1: (1, 0, 64), 64: (1, 0, 64), 5000: (4, 0, 512),
        400000: (13, 3, 49152), 10500000: (22, 7, 1048576)}[n]
    if n == 10500000:       # expo's 10M rows get Higgs's table
        assert _partition_sizes(cfg, 10000000) == sizes


@pytest.mark.parametrize("n", TABLE_ROWS)
def test_bucket_index_at_every_boundary(n):
    """``cnt`` = size - 1, size, size + 1 for every size of a table picks
    the smallest window that holds it, and that window, for the LAST leaf
    of ``order`` (the one that ends at ``n``), stays inside the sentinel
    tail: ``_order_tail`` is the widest step of the table, and one slot
    less would not do.  Held for the whole table (the XLA reference rungs'
    histogram ladder, which looks up among all sizes but the last) and for
    the partition's, where a count past the last size picks
    ``len(sizes)``, the dense branch, which slices no window."""
    for sizes, bounds in ((_bucket_sizes(GrowerConfig(), n), -1),
                          (_partition_sizes(GrowerConfig(), n), None)):
        tail = _order_tail(sizes)
        cnts = sorted({c for s in sizes for c in (0, s - 1, s, s + 1)
                       if 0 <= c <= n} | {n})
        got = np.asarray(jax.jit(jax.vmap(
            lambda c: _bucket_index(c, sizes[:bounds])))(
                jnp.asarray(cnts, jnp.int32)))
        overhang = []
        for c, k in zip(cnts, got):
            if k == len(sizes):             # the dense branch
                assert bounds is None and c > sizes[-1]
                continue
            assert sizes[k] >= c and (k == 0 or sizes[k - 1] < c), (c, k)
            overhang.append((n - c) + sizes[k] - n)
        assert max(overhang) <= tail
        assert max(overhang) == tail or n < sizes[0]
        if bounds is None:
            assert (got == len(sizes)).any() == (n > sizes[-1])
            assert tail == {1: 64, 64: 64, 5000: 255, 400000: 16383,
                            10500000: 262143}[n]
        else:
            assert tail == {1: 64, 64: 64, 5000: 4095, 400000: 131071,
                            10500000: 4194303}[n]


def test_partition_window_sizes_metric_reads_the_size_tag():
    """The benchmark's ``partition_window_sizes`` counts the distinct
    ``size`` tags of ``partition_route_dispatch``: as many as the
    partition's table has, and one for the dense branch (tagged with
    ``n``), from a traced grower, None from a program that does not tag;
    ``partition_dense_branch`` counts the keys tagged ``read=dense``."""
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
    sizes = _partition_sizes(cfg, n)
    assert counters.get("partition_route_dispatch") == {
        **{f"read=column,size={s}": 2 for s in sizes},
        f"read=dense,size={n}": 2}
    assert metrics.read_metric("partition_window_sizes", {}) \
        == len(sizes) + 1 == 7
    assert metrics.read_metric("partition_dense_branch", {}) == 1
    counters.reset()
    counters.inc("partition_route_dispatch", 10, read="column")
    assert metrics.read_metric("partition_window_sizes", {}) is None
    assert metrics.read_metric("partition_dense_branch", {}) is None
    counters.reset()
    assert metrics.read_metric("partition_window_sizes", {}) is None
    assert metrics.read_metric("partition_dense_branch", {}) is None
