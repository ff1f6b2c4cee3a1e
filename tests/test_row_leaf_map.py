"""``grower._row_leaf_from_intervals``: the row -> leaf map at a tree's end
against a plain numpy oracle.

The contract: the active leaves' ``[start, start + cnt)`` partition the
positions ``[0, n)`` of ``order``, and ``row_leaf[order[p]]`` is the leaf
whose interval holds ``p``.  A leaf with ``cnt == 0`` is not in the tree
yet and its ``start`` is whatever was left there.  The form is pinned on
the jaxpr too: no lookup by N indices (on the v5e a gather or a scatter
pays 6 to 9 ns an element, the two-operand sort on the unique key 1 to 2:
PERF.md section 6, PR 33), and whatever replaces it has to pass this file.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.grower import (GrowerConfig, _bucket_sizes,  # noqa: E402
                                 _order_tail, _row_leaf_from_intervals)
from lightgbm_tpu.utils.jaxpr_audit import _sub_jaxprs  # noqa: E402


def _oracle(order, start, cnt, n):
    want = np.full(n, -1, np.int32)
    for leaf in range(len(start)):
        want[order[start[leaf]:start[leaf] + cnt[leaf]]] = leaf
    assert (want >= 0).all()
    return want


def _intervals(rng, n, L, active, last_one_row=False):
    """``active`` of the ``L`` leaves (ids drawn at random, leaf 0 among
    them as in a tree) share ``[0, n)``; the others have ``cnt == 0`` and
    a stale ``start``: an active leaf's, 0, ``n``, past ``n``."""
    cuts = rng.choice(np.arange(1, n), size=active - 1, replace=False) \
        if active > 1 else np.array([], np.int64)
    if last_one_row and active > 1:
        cuts[0] = n - 1             # a one-row leaf ending exactly at n
        cuts = np.unique(cuts)
        active = len(cuts) + 1
    first = np.concatenate([[0], np.sort(cuts)])
    ids = np.concatenate([[0], 1 + rng.permutation(L - 1)[:active - 1]])
    ids = rng.permutation(ids)
    stale = np.concatenate([first, [0, n, n + 3]])
    start = rng.choice(stale, size=L).astype(np.int32)
    cnt = np.zeros(L, np.int32)
    start[ids] = first
    cnt[ids] = np.diff(np.concatenate([first, [n]]))
    return start, cnt


def _order(rng, n, tail):
    return np.concatenate([rng.permutation(n).astype(np.int32),
                           np.full(tail, n, np.int32)])


def _table_tail(n):
    return _order_tail(_bucket_sizes(GrowerConfig(), n))


CASES = {
    # name: (n, L, active leaves, sentinel tail, last leaf one row at n)
    "one_leaf_holds_all": (5000, 31, 1, 64, False),
    "255_leaves": (5000, 255, 255, 64, False),
    "inactive_stale_starts": (5000, 255, 40, 64, False),
    "leaf_ends_at_n": (5000, 63, 17, 64, True),
    "n1": (1, 7, 1, 63, False),
    "n64_every_row_a_leaf": (64, 64, 64, 63, False),
    "n64_no_tail": (64, 15, 9, 0, False),
    "table_tail_5000": (5000, 255, 200, _table_tail(5000), False),
    "table_tail_40959": (40959, 255, 255, _table_tail(40959), True),
    "table_tail_40959_sparse": (40959, 255, 3, _table_tail(40959), False),
}


@pytest.mark.parametrize("case", CASES)
def test_row_leaf_map_matches_interval_oracle(case):
    n, L, active, tail, last = CASES[case]
    rng = np.random.default_rng(len(case) + n + L)
    start, cnt = _intervals(rng, n, L, active, last)
    order = _order(rng, n, tail)
    got = jax.jit(_row_leaf_from_intervals, static_argnums=3)(
        jnp.asarray(order), jnp.asarray(start), jnp.asarray(cnt), n)
    assert got.dtype == jnp.int32 and got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(got),
                                  _oracle(order, start, cnt, n))


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def test_row_leaf_map_has_no_lookup_by_n_indices():
    """At n = 32,768: every gather and scatter takes L indices or fewer,
    and the ONE sort over n elements has two operands (``order[:n]`` as a
    unique key, the leaf of each position) and is not asked to be stable
    (XLA would carry an ``iota`` as a third: ``partition_window``)."""
    n, L = 32768, 255
    jaxpr = jax.make_jaxpr(_row_leaf_from_intervals, static_argnums=3)(
        jnp.zeros((n + _table_tail(n),), jnp.int32),
        jnp.zeros((L,), jnp.int32), jnp.zeros((L,), jnp.int32), n)
    eqns = list(_walk_eqns(jaxpr.jaxpr))
    indexed = [e for e in eqns if e.primitive.name == "gather"
               or e.primitive.name.startswith("scatter")]
    assert all(e.invars[1].aval.shape[0] <= L for e in indexed), \
        [(e.primitive.name, e.invars[1].aval.shape) for e in indexed]
    big = [e for e in eqns if e.primitive.name == "sort"
           and e.invars[0].aval.shape[0] >= n]
    assert len(big) == 1
    assert len(big[0].invars) == 2 and big[0].params["num_keys"] == 1
    assert not big[0].params["is_stable"]
    small = [e for e in eqns if e.primitive.name == "sort"
             and e not in big]
    assert all(e.invars[0].aval.shape == (L,) for e in small)
