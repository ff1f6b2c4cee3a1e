"""Training driver for a sparse job: what ``train.py`` is, with the matrix
handed over as a ``scipy.sparse.csr_matrix``, as a user of one-hot or hashed
categorical logs hands it over.

One ``lgb.train`` call on ``lgb.Dataset(csr_matrix, label=y)`` with no
validation set and no metric, timed from inside by ``train.Window``; set-up
drives the one booster through its first ``reference_trees`` iterations and
the same booster goes on into the window.  Afterwards the sparse plain
reference (``harness/reference_sparse.py``) follows those trees on the stored
entries; the program's bundle lists are handed to it as its thresholds are,
it works out for itself which entries a later column of a bundle overwrote,
and the matrix the program trained on has to hold the staying column's slot
in each of those rows (``harness/check_sparse.py``).

A configuration for this driver (``configs/expo.json``) has, beside the keys
``benchmarks/README.md`` lists: ``stored_per_row`` (the stored entries of a
row: the work a histogram row is counted at) and ``draw`` (the generator's
parameters, ``harness/data_sparse.py``).  A program that takes no
``scipy.sparse`` matrix fails at ``Dataset.construct``, before anything is
binned or compiled.
"""
import gc
import time

import numpy as np

from benchmarks.drivers import train
from benchmarks.harness import check_sparse, data_sparse, work

SCOPES_PROGRAM = train.SCOPES_PROGRAM


def run(cell, seed, seconds, trace, t_process, say, trace_dir):
    cfg, traffic = cell["config"], cell["traffic"]
    rows, cols_n = int(cfg["rows"]), int(cfg["columns"])
    follow = int(traffic["reference_trees"])
    parts = {}

    t = time.perf_counter()
    X, y = data_sparse.make_problem(rows, cols_n, seed,
                                    int(cfg["draw_seed"]), cfg["draw"])
    parts["generate_s"] = time.perf_counter() - t

    t = time.perf_counter()
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.utils.cache import enable_persistent_cache
    parts["import_s"] = time.perf_counter() - t
    parts["compile_cache"] = enable_persistent_cache()
    params = dict(cfg["params"], metric="None")

    t = time.perf_counter()
    dtrain = lgb.Dataset(X, label=y)
    dtrain.construct(config_from_params(dict(params)))
    parts["bin_s"] = time.perf_counter() - t

    window = train.Window(follow, seconds, trace,
                          int(traffic.get("trace_iterations", 3)), trace_dir)
    # the host span around the call into the program: on in every run, as
    # in train.py (the call stack is part of the compile cache's key)
    update = lgb.Booster.update
    lgb.Booster.update = train._spanned(update, "bench:update")
    t_train = time.perf_counter()
    try:
        bst = lgb.train(params, dtrain, num_boost_round=10 ** 6,
                        verbose_eval=False, callbacks=[window])
    finally:
        lgb.Booster.update = update
    if window.t1 is None:
        raise SystemExit("the window never closed: training stopped early")
    steps = np.diff([t_train] + window.setup_stamps)
    parts["first_iteration_s"] = float(steps[0])   # upload, compile, tree 1
    parts["later_warmup_s"] = float(steps[1:].sum())
    setup_s = window.t0 - t_process
    parts["other_s"] = setup_s - sum(v for k, v in parts.items()
                                     if k.endswith("_s"))
    say("setup parts: " + " ".join(
        f"{k}={v:.2f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in parts.items()))

    iters = len(window.stamps)
    elapsed = window.t1 - window.t0
    iter_times = np.diff([window.t0] + window.stamps)
    stats = jax.devices()[0].memory_stats() or {}     # None off the chip
    gbdt = bst.inner
    trees = [train.plain_tree(t) for t in gbdt.models]
    built = dtrain.constructed
    bounds = [np.asarray(m.bin_upper_bound, np.float64)
              for m in built.bin_mappers]
    # what the program bundled, in the order it pushed the columns, each
    # column's first slot, and the matrix it trained on (one byte a row and
    # bundle: kept for the reference, which reads it where columns met)
    lay = built.layout
    bundles = ([list(b) for b in lay.bundles] if lay
               else [[j] for j in range(cols_n)])
    ends = np.cumsum([len(b) for b in bundles])
    offsets = [list(lay.sub_offset[e - len(b):e]) if lay else [-1]
               for b, e in zip(bundles, ends)]
    binned = built.binned
    final_score = np.asarray(gbdt.scores[0], np.float64)
    # an untraced run keeps its iteration times too: a run that reads low
    # shows here whether every tree was slower or some were other trees
    say("iteration seconds: " + " ".join(f"{v:.3f}" for v in iter_times))
    say(f"window: {iters} iterations in {elapsed:.3f} s; {len(trees)} trees "
        f"held; {cols_n} columns in {len(bundles)} physical ones, widest "
        f"{built.max_num_bin()} slots; "
        f"memory_stats {stats}")

    # which kernel the grower was built with, and whether a layout was
    # refused one on the way (trace-time counts of the program's own)
    from lightgbm_tpu.obs.counters import counters
    say(f"program dispatch: hist_dispatch {counters.get('hist_dispatch')}; "
        f"bundle_expand_dispatch {counters.get('bundle_expand_dispatch')}; "
        f"layout_downgrade events {counters.events('layout_downgrade')}")

    # free the program's state before the reference runs
    bst.free_dataset()
    del bst, gbdt, dtrain, built
    gc.collect()

    numbers, _, ref_s = check_sparse.check_training(
        X, y, trees, bounds, bundles, offsets, binned, cfg["params"], follow,
        seed, final_score,
        score_rows=int(traffic.get("score_sample_rows", 100000)), say=say)
    say(f"reference: {ref_s:.1f} s for {follow} trees")

    # The work counts are the DATA's, not the layout's: a histogram row is
    # its stored entries (``stored_per_row`` one-byte bins beside g and h),
    # never 700 cells of which 692 are 0 by construction, and a histogram
    # table is the columns' real bins (2 each for one-hot columns: 1,400
    # entries, not 700 x 256).  ``work.tree_work`` takes the shape it is
    # given, so it is given ``stored_per_row`` columns whose bins add up to
    # that table.  ``hist_roofline``, ``partition_roofline`` and ``tree_mfu``
    # then read the same work whether the program bundles, expands or walks
    # the entries, and none is credited with 7 GB a pass that no
    # implementation has to read.
    stored = int(cfg["stored_per_row"])
    shape = {"rows": rows, "columns": stored,
             "bins": -(-sum(len(b) for b in bounds) // stored),
             "bin_bytes": int(cfg.get("bin_bytes", 1))}
    grown = [t for t in trees if len(t["left_child"])]
    window_work = {}
    for t in grown[follow:follow + iters]:
        work.add_work(window_work, work.tree_work(
            shape, t["left_child"], t["right_child"], t["internal_count"],
            t["leaf_count"]))
    return {
        "attempted": iters, "failed": 0,
        "end_to_end": {"trees_per_s": iters / elapsed, "setup_s": setup_s},
        "numbers": numbers, "limits": traffic["limits"],
        # allocated plus reserved, as in train.py: both are closed to any
        # other use
        "memory": {"peak_bytes": int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)),
                   "allocated_peak_bytes":
                       int(stats.get("peak_bytes_in_use", 0)),
                   "reserved_peak_bytes":
                       int(stats.get("peak_bytes_reserved", 0)),
                   "limit_bytes": int(stats.get("bytes_limit", 0))},
        "context": {
            "iterations": iters, "elapsed_s": elapsed,
            "iter_times_s": [float(v) for v in iter_times],
            "jit_entries": window.entries, "work": window_work,
            "setup_parts": parts, "reference_s": ref_s,
            "program": SCOPES_PROGRAM,
        },
    }
