"""Work counts against a tree worked out by hand, and the bound that keeps
every share they feed at or under 100 %.

    python3 -m pytest benchmarks/tests -q      (on the CPU; no chip needed)
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks.harness import peaks, work  # noqa: E402

SHAPE = {"rows": 10, "columns": 3, "bins": 4, "bin_bytes": 1}
# ten rows; the root sends 6 left (node 1) and 4 right (leaf 2); node 1
# sends 4 left (leaf 0) and 2 right (leaf 1)
LEFT, RIGHT = [1, ~0], [~2, ~1]
INTERNAL, LEAF = [10, 6], [4, 2, 4]


def test_three_leaf_tree_by_hand():
    w = work.tree_work(SHAPE, LEFT, RIGHT, INTERNAL, LEAF)
    # histograms: the root's 10 rows, then the smaller child of each split
    assert w["histogram"]["rows"] == 10 + 4 + 2
    table = 3 * 4 * 12
    assert w["histogram"]["bytes"] == 16 * (3 + 8) + 3 * table + 2 * 2 * table
    assert w["histogram"]["ops"] == 16 * 3 * 2 + 2 * 3 * 4 * 3
    # partition: each split moves its parent's rows
    assert w["partition"]["rows"] == 10 + 6
    assert w["partition"]["bytes"] == 16 * 9
    assert w["partition"]["ops"] == 16
    assert w["step"]["bytes"] == 1184 + 144 + 10 * 28
    assert w["step"]["ops"] == 168 + 16 + 10 * 8


def random_tree(rng, n_rows, n_leaves):
    """Leaf-wise growth with random cuts, in LightGBM's layout."""
    left, right, internal, leaf = [], [], [], [n_rows]
    where = {0: None}                       # leaf -> (parent, side)
    for _ in range(n_leaves - 1):
        cands = [l for l in range(len(leaf)) if leaf[l] >= 2]
        if not cands:
            break
        l = int(rng.choice(cands))
        cut = int(rng.randint(1, leaf[l]))
        node, new_leaf = len(left), len(leaf)
        if where[l] is not None:
            parent, side = where[l]
            (left if side == 0 else right)[parent] = node
        internal.append(leaf[l])
        left.append(~l)
        right.append(~new_leaf)
        leaf.append(leaf[l] - cut)
        leaf[l] = cut
        where[l], where[new_leaf] = (node, 0), (node, 1)
    return left, right, internal, leaf


def test_no_share_can_pass_100_percent():
    """The counted work is a floor: no more rows than any grower that
    histograms a child of every split and moves every parent's rows, and
    the least time is what that work costs at the peak — so a measured time
    under it would mean the trace left work out, not that the chip beat
    its own peak."""
    rng = np.random.RandomState(7)
    pk = peaks.peaks_for("TPU v5 lite")
    for _ in range(50):
        n_rows = int(rng.randint(50, 5000))
        tree = random_tree(rng, n_rows, int(rng.randint(2, 64)))
        shape = dict(SHAPE, rows=n_rows)
        w = work.tree_work(shape, *tree)
        hist_rows, part_rows, splits = work.tree_rows(*tree)
        assert part_rows == sum(tree[2])
        assert hist_rows <= n_rows + part_rows / 2
        assert w["step"]["bytes"] >= w["histogram"]["bytes"] \
            + w["partition"]["bytes"]
        for layer in w.values():
            secs, bound = peaks.least_seconds(layer, pk)
            assert bound == "memory" and secs > 0


def test_unknown_device_is_an_error():
    try:
        peaks.peaks_for("TPU v9 imaginary")
    except KeyError as e:
        assert "no peaks on file" in str(e)
    else:
        raise AssertionError("an unknown device_kind got peaks")
