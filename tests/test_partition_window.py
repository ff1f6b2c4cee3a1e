"""``grower.partition_window``: the stable two-way partition of one leaf's
window of ``order`` against a numpy oracle.

The contract is the reference's ``DataPartition::Split``
(src/treelearner/data_partition.hpp:94-146): the leaf's rows that go left
keep their sequence, then those that go right keep theirs, and no slot
outside the leaf's own is written — the rows of the next leaf that the
power-of-two window also covers, and the sentinel tail, come back as they
went in.  Both transports of today (``scatter``, ``sort``) are held to it,
and whatever replaces them has to pass this file unchanged.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.grower import pack_row_bits, partition_window  # noqa: E402


def _oracle(order, start, cnt, left):
    seg = order[start:start + cnt]
    goes = left[seg]
    want = order.copy()
    want[start:start + cnt] = np.concatenate([seg[goes], seg[~goes]])
    return want, int(goes.sum())


IMPLS = ["scatter", "sort"]


def _check(rng, impl, n, start, size, cnt, frac, msg=""):
    """``order``: a permutation of the N rows, then ``size`` sentinels."""
    order = np.concatenate([rng.permutation(n).astype(np.int32),
                            np.full(size, n, np.int32)])
    left = rng.rand(n) < frac
    got, nl = jax.jit(partition_window, static_argnums=(3, 5))(
        jnp.asarray(order), jnp.int32(start), jnp.int32(cnt), size,
        pack_row_bits(jnp.asarray(left)), impl)
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
@pytest.mark.parametrize("impl", IMPLS)
def test_partition_window_matches_stable_partition_oracle(impl, size, cnt):
    _check(np.random.RandomState(size + cnt), impl, n=5000, start=1700,
           size=size, cnt=cnt, frac=0.4)


@pytest.mark.parametrize("impl", IMPLS)
def test_partition_window_randomized_sweep(impl):
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
        _check(rng, impl, n, start, size, cnt, frac,
               msg=f"trial={trial} size={size} cnt={cnt} frac={frac} "
                   f"start={start}")
