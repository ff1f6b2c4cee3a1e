"""``check.check_training`` for categorical columns: run the categorical
reference (``reference_cat.py``) over what a training window produced and
read every number that ``correct`` compares.  Used by the categorical
training driver, by ``control_cat.py`` and by the tests."""
import time

import numpy as np

from . import check, compare, reference_cat


def judge_tree(ref, answers):
    """``compare.judge_tree`` for a tree of category sets: the leaves as
    there; the split by its gain, which the reference computed from the
    given set, against the reference's own best at the node, either way:
    the widest gap as ``split_gap`` (read: the harness compares no
    ``split_gap``) and the nodes off by more than a tolerance as
    ``cat_split_faults`` (compared)."""
    return {
        "leaf_gap_median": compare.median_leaf_gap(answers["leaf_value"],
                                                   ref["leaf_value"]),
        "count_mismatch": int(np.sum(np.asarray(answers["leaf_count"])
                                     != ref["leaf_count"])),
        "leaf_gap": compare.worst_leaf_gap(answers["leaf_value"],
                                           ref["leaf_value"]),
        "split_gap": reference_cat.split_gap(ref["best_gain"],
                                             answers["gain"]),
        "cat_split_faults": reference_cat.cat_split_faults(
            ref["best_gain"], answers["gain"]),
    }


def merge(total, one):
    """``compare.merge_worst``, with the nodes off the scan summed over the
    trees as the count mismatches are."""
    faults = total.get("cat_split_faults", 0) + one.pop("cat_split_faults")
    compare.merge_worst(total, one)
    total["cat_split_faults"] = faults
    return total


def control_answers(low, ref, params):
    """What the lower-precision grower would hand over at the same nodes:
    its own leaf values and the gain, in the reference's histograms, of the
    split its own gains put first."""
    ng, nh, nc = ref["node_hist"]
    gains = [reference_cat.set_gain(ng[i, f], nh[i, f], nc[i, f], bins,
                                    params)
             for i, (f, bins) in enumerate(low["own_split"])]
    return {"leaf_value": low["leaf_value"], "leaf_count": ref["leaf_count"],
            "gain": np.asarray(gains)}


def worst_of(ref, answers):
    """Where the widest leaf gap of a tree sits (printed, never compared)."""
    want = np.asarray(ref["leaf_value"], np.float64)
    got = np.asarray(answers["leaf_value"], np.float64)
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    leaf = int(np.argmax(np.abs(got - want) / scale))
    return (f"worst leaf {leaf}: {int(ref['leaf_count'][leaf])} rows, "
            f"reference {want[leaf]:.6g}, given {got[leaf]:.6g}; root gain "
            f"reference best {ref['best_gain'][0]:.6g}, given "
            f"{answers['gain'][0]:.6g}")


def widest_gaps(ref, answers, tree, top=6):
    """The nodes whose given set lies farthest from the published scan's
    best, either way (printed, never compared): node, its rows, the gap."""
    gaps = reference_cat.node_gaps(ref["best_gain"], answers["gain"])
    nodes = np.argsort(-np.abs(gaps), kind="stable")[:top]
    return " ".join(f"{int(i)}:{int(tree['internal_count'][i])}:"
                    f"{gaps[i]:.3g}" for i in nodes if gaps[i] != 0)


def check_training(codes, y, trees, maps, params, follow, seed, final_score,
                   sampled, score_rows=100000, control_precision=None,
                   say=print):
    """``codes`` [columns, rows] integers; ``trees`` all the trees the run
    grew, in order, each with its nodes' category sets (``cat_codes``); the
    first ``follow`` are followed.  ``maps`` the program's category -> bin
    maps, taken as given; ``sampled`` the rows it binned on.  Returns
    (numbers, control numbers or None, seconds spent)."""
    t0 = time.perf_counter()
    numbers = {"cat_map_faults": reference_cat.map_faults(
        codes, maps, int(params.get("max_bin", 255)), sampled)}
    ref = reference_cat.Follower(codes, y, maps, params, "float64")
    say(f"reference: kept bins {[int(nb) for _, nb in maps]}, "
        f"full-categorical {ref.full.astype(int).tolist()}, "
        f"cat_map_faults {numbers['cat_map_faults']} at "
        f"{time.perf_counter() - t0:.1f} s")
    low = control = None
    if control_precision:
        low = reference_cat.Follower(codes, y, maps, params,
                                     control_precision, ref.bins)
        control = {}
    for t, tree in enumerate(trees[:follow]):
        out = ref.step(tree)
        answers = {"leaf_value": tree["leaf_value"],
                   "leaf_count": tree["leaf_count"],
                   "gain": out["given_gain"]}
        merge(numbers, judge_tree(out, answers))
        say(f"reference: tree {t + 1} " + worst_of(out, answers))
        say(f"reference: tree {t + 1} widest set gaps " + widest_gaps(
            out, answers, tree))
        if low is not None:
            merge(control, judge_tree(
                out, control_answers(low.step(tree), out, params)))
        say(f"reference: tree {t + 1} followed at "
            f"{time.perf_counter() - t0:.1f} s")
    # the state the window left, against the trees it says it grew
    rows = check.sample_rows(codes.shape[1], score_rows, seed)
    want = reference_cat.score_by_trees(codes[:, rows], trees)
    numbers["score_gap"] = compare.worst_leaf_gap(
        np.asarray(final_score)[rows], want)
    return numbers, control, time.perf_counter() - t0
