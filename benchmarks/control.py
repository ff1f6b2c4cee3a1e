"""Readings for the limits: the program's numbers and the control's, on
several seeds in one process (set-up is most of a run, so one process reads
them all).  Not run by the benchmark's own runs.

    python3 benchmarks/control.py --workload higgs.train --seeds 11 12 13 \
        --seconds 5 [--control bfloat16] [--rows N]

For each seed: the cell's own set-up and a short window through the timed
path, then the reference over the first trees — and, with ``--control``, the
reference again at the lower precision put in the program's place.  Prints
one JSON line per seed; limits go into the traffic file by hand, between
the two sets of readings (PERF.md section 2).
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def plant(fault):
    """Break the timed path underneath, as tests/test_correct.py does."""
    if fault == "half_batch":
        import jax.numpy as jnp
        from lightgbm_tpu import boosting
        real = boosting.GBDT._sample

        def sample(self, it, g, h):
            g, h, cnt = real(self, it, g, h)
            keep = (jnp.arange(cnt.shape[0]) % 2 == 0).astype(cnt.dtype)
            return g * keep, h * keep, cnt * keep
        boosting.GBDT._sample = sample
    elif fault == "altered_answer":
        from lightgbm_tpu import tree as tree_mod
        real_from = tree_mod.Tree.from_arrays

        def from_arrays(*a, **kw):
            t = real_from(*a, **kw)
            if t.num_leaves > 1:
                t.leaf_value[0] *= 1.1
            return t
        tree_mod.Tree.from_arrays = staticmethod(from_arrays)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--rows", type=int, default=None,
                    help="smaller than the cell's own: for the CPU only")
    ap.add_argument("--fault", default=None,
                    choices=("half_batch", "altered_answer"),
                    help="plant this fault in the program and read the "
                         "numbers it gives")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from benchmarks import run as bench_run
    from benchmarks.harness import cells, check
    cell = cells.cell(args.workload)
    if args.rows:
        cell["config"] = dict(cell["config"], rows=args.rows,
                              valid_rows=max(args.rows // 20, 1000))
        device = {"platform": "rehearsal", "kind": "none", "count": 0}
    else:
        device = bench_run.gate(cell["chips"])
    real = check.check_training
    if args.fault:
        plant(args.fault)

    for seed in args.seeds:
        got = {}

        def with_control(*a, **kw):
            numbers, control, secs = real(
                *a, **dict(kw, control_precision=args.control))
            got.update(control=control, reference_s=secs)
            return numbers, control, secs

        check.check_training = with_control
        t = time.perf_counter()
        try:
            res = bench_run.run_cell(cell, seed, args.seconds, False, device)
        finally:
            check.check_training = real
        line = {"workload": args.workload, "seed": seed, "fault": args.fault,
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
