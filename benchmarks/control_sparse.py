"""``control.py`` for a sparse cell: the program's numbers, the control's and
the planted faults', on several seeds in one process.  Not run by the
benchmark's own runs.

    python3 benchmarks/control_sparse.py --workload expo.train-sparse \
        --seeds 11 12 13 --seconds 5 [--control bfloat16] [--fault NAME] \
        [--rows N] [--out FILE]

For each seed: the cell's own set-up and a short window through the timed
path, then the sparse reference over the first trees — and, with
``--control``, the reference again at the lower precision put in the
program's place.  ``--fault`` breaks the program underneath first
(:data:`FAULTS`; ``benchmarks/tests/test_correct_sparse.py`` plants the same
two and sees each fail a limit).  Prints one JSON line per seed; limits go
into the traffic file by hand, between the two sets of readings (PERF.md
section 2).
"""
import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def earlier_wins(put):
    """Where two columns of a bundle meet in a row the EARLIER one stays: the
    columns of every bundle are pushed in the reverse order, into the slots
    they own."""
    from lightgbm_tpu.data import dataset
    real = dataset._bin_stored

    def bin_stored(ds, csr, dtype):
        lay = ds.layout
        if lay is None or not lay.has_bundles:
            return real(ds, csr, dtype)
        back, offsets, k = copy.copy(lay), [], 0
        for bundle in lay.bundles:
            offsets += lay.sub_offset[k:k + len(bundle)][::-1]
            k += len(bundle)
        back.bundles = [b[::-1] for b in lay.bundles]
        back.sub_offset = offsets
        holder = copy.copy(ds)
        holder.layout = back
        return real(holder, csr, dtype)
    put(dataset, "_bin_stored", bin_stored)


def decode_off_by_one(put):
    """A split's column is decoded one slot off where the host tree is
    produced: the tree handed over names, for every split, the column that
    owns the NEXT slot of the bundle, with the counts and outputs of the
    split that was made.  (The same slip inside the grower, in the
    partition's decode or in the histogram's expansion, leaves a child's
    recorded sums and its rows disagreeing; the scores run away within three
    trees and training stops before the window opens: a run with no result,
    not one with a wrong one.)"""
    from lightgbm_tpu import tree as tree_mod
    real = tree_mod.Tree.from_arrays

    def from_arrays(arrays, used_features, *a, **kw):
        used = list(used_features)
        return real(arrays, used[1:] + used[-1:], *a, **kw)
    put(tree_mod.Tree, "from_arrays", staticmethod(from_arrays))


FAULTS = {"earlier_wins": earlier_wins,
          "decode_off_by_one": decode_off_by_one}


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
    from benchmarks.harness import cells, check_sparse
    cell = cells.cell(args.workload)
    if args.rows:
        cell["config"] = dict(cell["config"], rows=args.rows)
        device = {"platform": "rehearsal", "kind": "none", "count": 0}
    else:
        device = bench_run.gate(cell["chips"])
    real = check_sparse.check_training
    if args.fault:
        FAULTS[args.fault](setattr)

    for seed in args.seeds:
        got = {}

        def with_control(*a, **kw):
            numbers, control, secs = real(
                *a, **dict(kw, control_precision=args.control))
            got.update(control=control, reference_s=secs)
            return numbers, control, secs

        check_sparse.check_training = with_control
        t = time.perf_counter()
        try:
            res = bench_run.run_cell(cell, seed, args.seconds, False, device)
        finally:
            check_sparse.check_training = real
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
