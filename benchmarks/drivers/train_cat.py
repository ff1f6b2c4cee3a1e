"""Training driver for categorical columns: what ``train.py`` is, with the
matrix handed over as integer category codes and ``categorical_feature``
naming its columns, as a user of the reference's direct categorical support
hands it over.

One ``lgb.train`` call on ``lgb.Dataset(X, label=y,
categorical_feature=...)`` with no validation set and no metric, timed from
inside by ``train.Window``; set-up drives the one booster through its first
``reference_trees`` iterations and the same booster goes on into the window.
Afterwards the categorical plain reference (``harness/reference_cat.py``)
follows those trees on the raw codes; the program's category -> bin maps are
handed to it as its thresholds are, and checked (``harness/check_cat.py``).

A configuration for this driver (``configs/expo-cat.json``) has, beside the
keys ``benchmarks/README.md`` lists: ``categorical_columns`` and ``draw``
(``harness/data_sparse.py``'s parameters: the rows are that draw's, each
field's column less the field's first column being its category's code).
"""
import gc
import time

import numpy as np

from benchmarks.drivers import train
from benchmarks.harness import check_cat, data_sparse, work

SCOPES_PROGRAM = train.SCOPES_PROGRAM


def make_problem(rows, seed, draw_seed, draw):
    """(X float32 [rows, fields] of category codes, labels): the one draw
    of ``data_sparse``, its rows shuffled by ``seed`` as there."""
    sizes = np.asarray(draw["field_sizes"], np.int64)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)
    cats, y = data_sparse.make_fields(rows, draw_seed, draw)
    order = np.random.Generator(np.random.PCG64(int(seed))).permutation(rows)
    cats = cats[order]
    cats -= starts
    return cats.astype(np.float32), y[order]


def plain_cat_tree(t):
    """``train.plain_tree`` and, for every node, its ``decision_type`` and
    the category codes it sends left (None at a numerical node)."""
    out = train.plain_tree(t)
    n = len(out["left_child"])
    out["decision_type"] = np.asarray(t.decision_type[:n], np.int64)
    out["cat_codes"] = [
        np.flatnonzero(np.unpackbits(
            np.asarray(t.cat_bitset(i), np.uint32).view(np.uint8),
            bitorder="little")) if t.is_categorical(i) else None
        for i in range(n)]
    return out


def run(cell, seed, seconds, trace, t_process, say, trace_dir):
    cfg, traffic = cell["config"], cell["traffic"]
    rows = int(cfg["rows"])
    follow = int(traffic["reference_trees"])
    parts = {}

    t = time.perf_counter()
    X, y = make_problem(rows, seed, int(cfg["draw_seed"]), cfg["draw"])
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
    config = config_from_params(dict(params))
    dtrain = lgb.Dataset(X, label=y,
                         categorical_feature=list(cfg["categorical_columns"]))
    dtrain.construct(config)
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
    trees = [plain_cat_tree(t) for t in gbdt.models]
    built = dtrain.constructed
    maps = [(list(m.bin_2_categorical or []), int(m.num_bin))
            for m in built.bin_mappers]
    sampled = min(int(config.bin_construct_sample_cnt), rows)
    final_score = np.asarray(gbdt.scores[0], np.float64)
    say("iteration seconds: " + " ".join(f"{v:.3f}" for v in iter_times))
    say(f"window: {iters} iterations in {elapsed:.3f} s; {len(trees)} trees "
        f"held; kept bins {[nb for _, nb in maps]} in "
        f"{built.binned.dtype}; memory_stats {stats}")
    # which kernel the grower was built with, at what width, and whether a
    # layout was refused one on the way (trace-time counts of the program's)
    from lightgbm_tpu.obs.counters import counters
    say(f"program dispatch: hist_dispatch {counters.get('hist_dispatch')}; "
        f"layout_downgrade events {counters.events('layout_downgrade')}")

    # free the program's state before the reference runs
    bst.free_dataset()
    del bst, gbdt, dtrain, built
    gc.collect()

    codes = np.ascontiguousarray(X.T).astype(np.int32)
    del X
    numbers, _, ref_s = check_cat.check_training(
        codes, y, trees, maps, cfg["params"], follow, seed, final_score,
        sampled, score_rows=int(traffic.get("score_sample_rows", 100000)),
        say=say)
    say(f"reference: {ref_s:.1f} s for {follow} trees")

    # The work counts are the DATA's: a histogram row is the 8 columns'
    # two-byte bins beside g and h, and a histogram table the columns' real
    # kept bins (their sum over the columns, spread evenly over them), never
    # the widest column's bins, nor the 16-row steps of the kernel's hi
    # one-hot, times every column.  ``hist_roofline``, ``partition_roofline``
    # and ``tree_mfu`` then read the same work however wide the kernel pads.
    cols_n = len(maps)
    shape = {"rows": rows, "columns": cols_n,
             "bins": -(-sum(nb for _, nb in maps) // cols_n),
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
