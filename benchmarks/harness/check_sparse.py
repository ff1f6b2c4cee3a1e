"""``check.check_training`` for a matrix handed over as ``scipy.sparse``: run
the sparse reference (``reference_sparse.py``) over what a training window
produced and read every number that ``correct`` compares.  Used by the
sparse training driver, by ``control_sparse.py`` and by the tests."""
import time

import numpy as np

from . import check, compare, reference, reference_sparse


def check_training(X, y, trees, bounds, bundles, offsets, binned, params,
                   follow, seed, final_score, score_rows=100000,
                   control_precision=None, say=print):
    """``trees`` are all the trees the run grew, in order; the first
    ``follow`` are followed by the reference.  ``bundles`` are the program's
    bundle lists (columns of ``X``, in the order they were pushed),
    ``offsets`` each column's first slot in its bundle's column (a list a
    bundle), both taken as given; ``binned`` is the ``[rows, bundles]``
    matrix the program trained on.  Returns (numbers, control numbers or
    None, seconds spent)."""
    t0 = time.perf_counter()
    max_bin = int(params.get("max_bin", 255))
    numbers = {"bound_faults": reference.bound_faults(bounds, max_bin)
               + reference_sparse.bundle_faults(bundles, bounds, max_bin,
                                                offsets)}
    cols = reference_sparse.Columns(X, bounds, bundles)
    # where two columns of a bundle met, the slot the program trained on
    # against the slot of the column that has to stay
    met = cols.conflicts
    held = np.asarray(binned)[met["row"], met["bundle"]]
    numbers["bundle_conflict_gap"] = int(
        (held != reference_sparse.conflict_slots(cols, bundles,
                                                 offsets)).sum())
    say(f"reference: {len(cols.rows)} stored entries by column at "
        f"{time.perf_counter() - t0:.1f} s; bundle_conflict_rows, the "
        f"entries overwritten inside a bundle: {len(held)}; the program's "
        f"column holds another slot than the staying column's at "
        f"{numbers['bundle_conflict_gap']} of them")
    ref = reference_sparse.Follower(cols, y, params, "float64")
    low = control = None
    if control_precision:
        low = reference_sparse.Follower(cols, y, params, control_precision)
        control = {}
    for t, tree in enumerate(trees[:follow]):
        out = ref.step(tree)
        answers = check.program_answers(tree, bounds)
        compare.merge_worst(numbers, compare.judge_tree(out, answers))
        say(f"reference: tree {t + 1} " + compare.worst_of(out, answers))
        if low is not None:
            compare.merge_worst(control, compare.judge_tree(
                out, check.control_answers(low.step(tree), out)))
        say(f"reference: tree {t + 1} followed at "
            f"{time.perf_counter() - t0:.1f} s")
    # the state the window left, against the trees it says it grew
    rows = check.sample_rows(cols.n, score_rows, seed)
    want = reference_sparse.score_by_trees(cols.take(rows), trees)
    numbers["score_gap"] = compare.worst_leaf_gap(
        np.asarray(final_score)[rows], want)
    return numbers, control, time.perf_counter() - t0
