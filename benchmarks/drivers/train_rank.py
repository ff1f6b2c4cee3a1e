"""Training driver for a ranking job: what ``train.py`` is, with query groups.

One ``lgb.train`` call on ``lgb.Dataset(X, label=y, group=sizes)`` with no
metric, timed from inside by ``train.Window``; set-up drives the one booster
through its first ``reference_trees`` iterations and the same booster goes on
into the window.  Afterwards the plain reference follows those trees with its
own LambdaRank gradients (``harness/reference_rank.py``), and everything else
— histograms, leaf outputs, counts, gains, the score the window left — is
``harness/check.py``'s, as for the binary cells.
"""
import contextlib
import gc
import time

import numpy as np

from benchmarks.drivers import train
from benchmarks.harness import (check, data_rank, reference, reference_rank,
                                work)

SCOPES_PROGRAM = train.SCOPES_PROGRAM


@contextlib.contextmanager
def gradients_of(fn):
    """``reference.Follower.step`` calls ``reference.gradients`` by name:
    the Follower follows another objective where that name is another
    function.  (An argument of ``check.check_training`` would say it better:
    PERF.md section 7.)"""
    binary, reference.gradients = reference.gradients, fn
    try:
        yield
    finally:
        reference.gradients = binary


def run(cell, seed, seconds, trace, t_process, say, trace_dir):
    cfg, traffic = cell["config"], cell["traffic"]
    rows, cols_n = int(cfg["rows"]), int(cfg["columns"])
    follow = int(traffic["reference_trees"])
    parts = {}

    t = time.perf_counter()
    X, y, sizes = data_rank.make_problem(rows, cols_n, seed,
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
    dtrain = lgb.Dataset(X, label=y, group=sizes)
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
    bounds = [np.asarray(m.bin_upper_bound, np.float64)
              for m in dtrain.constructed.bin_mappers]
    final_score = np.asarray(gbdt.scores[0], np.float64)
    leaves = [t["num_leaves"] for t in trees]
    say(f"window: {iters} iterations in {elapsed:.3f} s; {len(trees)} trees "
        f"held, {sum(n < int(params['num_leaves']) for n in leaves)} of them "
        f"short of {params['num_leaves']} leaves (least {min(leaves)}); "
        f"memory_stats {stats}")

    # free the program's state before the reference runs
    bst.free_dataset()
    del bst, gbdt, dtrain
    gc.collect()

    cols = np.ascontiguousarray(X.T)
    del X
    # the scores each followed tree's gradients rank on: the given trees'
    # own outputs so far (reference_rank.py says why)
    given = [np.zeros(rows)]
    for tree in trees[:follow - 1]:
        given.append(given[-1] + tree["leaf_value"][reference.route(cols, tree)])
    gradients = reference_rank.Gradients(sizes, cfg["params"], given)
    with gradients_of(gradients):
        numbers, _, ref_s = check.check_training(
            cols, y, trees, bounds, cfg["params"], follow, seed, final_score,
            score_rows=int(traffic.get("score_sample_rows", 100000)), say=say)
    say(f"reference: {ref_s:.1f} s for {follow} trees; documents whose rank "
        f"by the given trees' outputs is not their rank by the reference's "
        f"own, by gradient pass: {gradients.rank_moves}")

    shape = {"rows": rows, "columns": cols_n,
             "bins": int(cfg["params"]["max_bin"]),
             "bin_bytes": int(cfg.get("bin_bytes", 1))}
    # the objective's work is the data's, the same for every tree: all
    # pairs inside a query.  Its bytes (score and label read, g and h
    # written) are in work.py's pass over scores and gradients already, so
    # the step gains its operations alone.
    objective = reference_rank.objective_work(sizes)
    grown = [t for t in trees if len(t["left_child"])]
    window_work = {}
    for t in grown[follow:follow + iters]:
        one = work.tree_work(shape, t["left_child"], t["right_child"],
                             t["internal_count"], t["leaf_count"])
        one["objective"] = objective
        one["step"]["ops"] += objective["ops"]
        work.add_work(window_work, one)
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
            "program": SCOPES_PROGRAM, "query_pairs": objective["pairs"],
        },
    }
