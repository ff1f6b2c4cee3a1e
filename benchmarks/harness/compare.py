"""The comparison that decides ``correct``: each number beside its limit.

``answers`` are what a grower hands over for one tree — the program's tree,
or the control's (the reference at lower precision in the program's place):
``leaf_value``, ``leaf_count`` and the chosen split of every node as
(``feature``, ``bin``).  ``ref`` is the reference's step over the same tree.
"""
import numpy as np


def worst_leaf_gap(got, want):
    """Widest |got - want| over leaves, against the reference's own value or
    its median leaf's, whichever is larger (some leaves are all but 0)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    return float(np.max(np.abs(got - want) / scale))


def split_gap(ref_gains, feature, bins):
    """Widest gap by which a chosen split's gain lies below the reference's
    best at that node, against that best or the median node's, whichever is
    larger (a late node's gain is the small difference of large sums); 1
    where the choice is not allowed."""
    worst = 0.0
    bests = ref_gains.reshape(ref_gains.shape[0], -1).max(-1)
    finite = bests[np.isfinite(bests)]
    floor = float(np.median(finite)) if len(finite) else 0.0
    for i in range(ref_gains.shape[0]):
        best = float(bests[i])
        b = int(bins[i])
        chosen = float(ref_gains[i, int(feature[i]), b]) if b >= 0 \
            else -np.inf
        if not (np.isfinite(best) and np.isfinite(chosen)):
            gap = 1.0       # the reference would not split here, or not so
        else:
            gap = (best - chosen) / max(best, floor)
        worst = max(worst, gap)
    return worst


def own_choice(gains):
    """The split a grower with these gains puts first at every node."""
    flat = gains.reshape(gains.shape[0], -1).argmax(-1)
    return flat // gains.shape[2], flat % gains.shape[2]


def median_leaf_gap(got, want):
    """The median leaf's gap, by the same measure as the worst leaf's: the
    steady companion that summation noise in one leaf cannot move."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    return float(np.median(np.abs(got - want) / scale))


NOT_COMPARED = ("leaf_gap", "split_gap")     # read and printed; PERF.md 2


def judge_tree(ref, answers):
    return {
        "leaf_gap_median": median_leaf_gap(answers["leaf_value"],
                                           ref["leaf_value"]),
        "count_mismatch": int(np.sum(np.asarray(answers["leaf_count"])
                                     != ref["leaf_count"])),
        "leaf_gap": worst_leaf_gap(answers["leaf_value"], ref["leaf_value"]),
        "split_gap": split_gap(ref["gains"], answers["feature"],
                               answers["bin"]),
    }


def metric_gap(got, want):
    """Widest relative gap over data sets and metrics after one tree."""
    worst = 0.0
    for name, by_metric in want.items():
        for metric, value in by_metric.items():
            worst = max(worst, abs(got[name][metric] - value) / abs(value))
    return worst


def worst_of(ref, answers):
    """Where the two widest gaps of a tree sit: the look that PERF.md asks
    for when a reading is far off (rows in the leaf, rows and gain at the
    node).  Printed, never compared."""
    want = np.asarray(ref["leaf_value"], np.float64)
    got = np.asarray(answers["leaf_value"], np.float64)
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    leaf = int(np.argmax(np.abs(got - want) / scale))
    gains = ref["gains"]
    best = gains.reshape(gains.shape[0], -1).max(-1)
    chosen = np.array([gains[i, int(f), int(b)] if b >= 0 else -np.inf
                       for i, (f, b) in enumerate(zip(answers["feature"],
                                                      answers["bin"]))])
    with np.errstate(invalid="ignore"):
        node = int(np.argmax(np.where(np.isfinite(chosen),
                                      (best - chosen) / best, 1.0)))
    return (f"worst leaf {leaf}: {int(ref['leaf_count'][leaf])} rows, "
            f"reference {want[leaf]:.6g}, given {got[leaf]:.6g}; "
            f"worst node {node}: best gain {best[node]:.6g}, "
            f"given split's {chosen[node]:.6g}, root gain {best[0]:.6g}")


def merge_worst(total, one):
    for k, v in one.items():
        total[k] = total[k] + v if k == "count_mismatch" and k in total \
            else max(total.get(k, 0), v)
    return total


def verdict(numbers, limits):
    """[(name, value, limit, ok)] for every number, and whether all hold.
    A number with no limit on file fails: nothing is passed by default."""
    rows = []
    for name, value in numbers.items():
        if name in NOT_COMPARED:
            continue
        limit = limits.get(name)
        ok = (limit is not None and np.isfinite(value) and value <= limit)
        rows.append((name, float(value), limit, bool(ok)))
    return rows, all(r[3] for r in rows)
