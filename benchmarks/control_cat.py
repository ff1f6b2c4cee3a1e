"""``control.py`` for a categorical cell: the program's numbers, the
control's and the planted fault's, on several seeds in one process.  Not run
by the benchmark's own runs.

    python3 benchmarks/control_cat.py --workload expo-cat.train-cat \
        --seeds 11 12 13 --seconds 5 [--control bfloat16] [--fault NAME] \
        [--rows N] [--out FILE]

For each seed: the cell's own set-up and a short window through the timed
path, then the categorical reference over the first trees — and, with
``--control``, the reference again at the lower precision put in the
program's place.  ``--fault`` breaks the program underneath first
(:data:`FAULTS`; ``tests/test_expo_cat_cell.py`` plants the same fault and
sees it fail a limit).  Prints one JSON line per seed.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def category_moved(put):
    """One category moved to the other side of one split where the host
    tree is produced: the first category that the tree's first categorical
    node sends left goes right in the tree handed over, whose counts and
    outputs are those of the split that was made."""
    import numpy as np
    from lightgbm_tpu import tree as tree_mod
    real = tree_mod.Tree.from_arrays

    def from_arrays(*a, **kw):
        t = real(*a, **kw)
        for i in range(t.num_leaves - 1):
            if t.is_categorical(i):
                lo, hi = t.cat_boundaries[int(t.threshold[i])], \
                    t.cat_boundaries[int(t.threshold[i]) + 1]
                words = t.cat_threshold[lo:hi]
                k = int(np.flatnonzero(words)[0])
                word = int(words[k])
                t.cat_threshold[lo + k] = np.uint32(word & (word - 1))
                break
        return t
    put(tree_mod.Tree, "from_arrays", staticmethod(from_arrays))


def scan_fault(**change):
    """A fault of the program's categorical scan: the grower's sort by ratio,
    prefixes and ``max_cat_group`` accounting run with ``change`` in the
    published settings' place, while the reference keeps them."""
    def plant(put):
        from lightgbm_tpu.ops import split
        real = split._categorical_candidates

        def faulty(*a):
            *a, cfg = a
            return real(*a, cfg._replace(**change))
        put(split, "_categorical_candidates", faulty)
    return plant


FAULTS = {"category_moved": category_moved,
          # the ratios sorted unsmoothed, (g / h)
          "no_smoothing": scan_fault(min_cat_smooth=0.0, max_cat_smooth=0.0),
          # every prefix a candidate: the max_cat_group accounting dropped
          "no_group_limit": scan_fault(max_cat_group=2 ** 30)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--rows", type=int, default=None,
                    help="smaller than the cell's own: for the CPU only")
    ap.add_argument("--fault", default=None, choices=sorted(FAULTS))
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from benchmarks import run as bench_run
    from benchmarks.harness import cells, check_cat
    cell = cells.cell(args.workload)
    if args.rows:
        cell["config"] = dict(cell["config"], rows=args.rows)
        device = {"platform": "rehearsal", "kind": "none", "count": 0}
    else:
        device = bench_run.gate(cell["chips"])
    real = check_cat.check_training
    if args.fault:
        FAULTS[args.fault](setattr)

    for seed in args.seeds:
        got = {}

        def with_control(*a, **kw):
            numbers, control, secs = real(
                *a, **dict(kw, control_precision=args.control))
            got.update(control=control, reference_s=secs)
            return numbers, control, secs

        check_cat.check_training = with_control
        t = time.perf_counter()
        try:
            res = bench_run.run_cell(cell, seed, args.seconds, False, device)
        finally:
            check_cat.check_training = real
        line = {"workload": args.workload, "seed": seed, "fault": args.fault,
                "correct": res["correct"],
                "program": dict({k: v["value"]
                                 for k, v in res["compared"].items()},
                                **res["read_not_compared"]),
                "control": got.get("control"),
                "reference_s": got.get("reference_s"),
                "trees_per_s": res["metrics"]["trees_per_s"]["value"],
                "setup_s": res["metrics"]["setup_s"]["value"],
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "seconds": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
