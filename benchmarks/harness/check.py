"""Run the reference over what a training window produced and read every
number that ``correct`` compares.  Used by the training driver, by the
control (``control.py``) and by the tests under ``tests/``."""
import time

import numpy as np

from . import compare, reference


def sample_rows(n, count, seed):
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    return np.sort(rng.choice(n, size=min(count, n), replace=False))


def program_answers(tree, bounds):
    return {"leaf_value": tree["leaf_value"], "leaf_count": tree["leaf_count"],
            "feature": tree["split_feature"],
            "bin": reference.threshold_bins(tree, bounds)}


def control_answers(low, ref):
    """What the lower-precision grower would hand over at the same nodes:
    its own leaf values and the split its own gains put first."""
    feature, bins = compare.own_choice(low["gains"])
    return {"leaf_value": low["leaf_value"], "leaf_count": ref["leaf_count"],
            "feature": feature, "bin": bins}


def check_training(cols, y, trees, bounds, params, follow, seed,
                   final_score, valid=None, final_valid_score=None,
                   evals=None, metrics=(), score_rows=100000,
                   control_precision=None, say=print):
    """``trees`` are all the trees the run grew, in order; the first
    ``follow`` are followed by the reference.  Returns (numbers, control numbers or
    None, seconds spent)."""
    t0 = time.perf_counter()
    grown = list(trees)
    numbers = {"bound_faults": reference.bound_faults(
        bounds, int(params.get("max_bin", 255)))}
    bins = reference.bin_columns(cols, bounds)
    ref = reference.Follower(cols, y, bounds, params, "float64", valid, bins)
    low = control = None
    if control_precision:
        low = reference.Follower(cols, y, bounds, params, control_precision,
                                 None, bins)
        control = {}
    for t, tree in enumerate(grown[:follow]):
        out = ref.step(tree, metrics if evals else ())
        answers = program_answers(tree, bounds)
        compare.merge_worst(numbers, compare.judge_tree(out, answers))
        say(f"reference: tree {t + 1} " + compare.worst_of(out, answers))
        if evals:
            got = {name: {m: evals[name][m][t] for m in metrics}
                   for name in out["metrics"]}
            compare.merge_worst(numbers, {"metric_gap": compare.metric_gap(
                got, out["metrics"])})
        if low is not None:
            compare.merge_worst(control, compare.judge_tree(
                out, control_answers(low.step(tree), out)))
            # the planted fault "an answer altered where it is produced":
            # the program's own tree with its largest leaf off by a tenth
            altered = dict(answers, leaf_value=np.array(answers["leaf_value"]))
            big = int(np.argmax(np.abs(altered["leaf_value"])))
            altered["leaf_value"][big] *= 1.1
            compare.merge_worst(control, {"altered_leaf_gap": compare.judge_tree(
                out, altered)["leaf_gap"]})
        say(f"reference: tree {t + 1} followed at "
            f"{time.perf_counter() - t0:.1f} s")
    # the state the window left, against the trees it says it grew
    rows = sample_rows(cols.shape[1], score_rows, seed)
    want = reference.score_by_trees(cols[:, rows], grown)
    gap = compare.worst_leaf_gap(np.asarray(final_score)[rows], want)
    if valid is not None and final_valid_score is not None:
        rows_v = sample_rows(valid[0].shape[1], score_rows // 2, seed)
        want_v = reference.score_by_trees(valid[0][:, rows_v], grown)
        gap = max(gap, compare.worst_leaf_gap(
            np.asarray(final_valid_score)[rows_v], want_v))
    numbers["score_gap"] = gap
    return numbers, control, time.perf_counter() - t0
