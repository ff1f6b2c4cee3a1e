"""Training driver: one ``lgb.train`` call, timed from inside by a callback.

Set-up builds the one booster and drives it through its first
``reference_trees`` iterations — the compile or cache load, the warm-up and
the trees the reference follows are the same iterations — and the same
booster goes on into the window, which ends with the first iteration that
completes at or after ``--seconds``.  Every iteration goes
``engine.train`` -> ``Booster.update`` -> ``GBDT.train_one_iter`` (and
``eval_train`` / ``eval_valid`` where the traffic evaluates); the benchmark
adds only the callback, which waits for the device and reads the clock.
"""
import gc
import shutil
import time

import numpy as np

from benchmarks.harness import check, data, work

SCOPES_PROGRAM = "grow_tree"


def plain_tree(t):
    """The program's host tree as plain arrays (its answers)."""
    n = max(int(t.num_leaves) - 1, 0)
    return {
        "num_leaves": int(t.num_leaves),
        "split_feature": np.asarray(t.split_feature[:n], np.int64),
        "threshold": np.asarray(t.threshold[:n], np.float64),
        "left_child": np.asarray(t.left_child[:n], np.int64),
        "right_child": np.asarray(t.right_child[:n], np.int64),
        "leaf_value": np.asarray(t.leaf_value[:t.num_leaves], np.float64),
        "leaf_count": np.asarray(t.leaf_count[:t.num_leaves], np.int64),
        "internal_count": np.asarray(t.internal_count[:n], np.int64),
    }


class Window:
    """The ``lgb.train`` callback that is the benchmark's clock."""
    order = 10 ** 6                       # after every other callback
    before_iteration = False

    def __init__(self, follow, seconds, trace, trace_iterations, trace_dir):
        self.follow, self.seconds = follow, seconds
        self.trace, self.trace_iterations = trace, trace_iterations
        self.trace_dir = trace_dir
        self.setup_stamps, self.stamps = [], []
        self.t0 = self.t1 = None
        self.entries = [None, None]
        self._span = None

    def __call__(self, env):
        import jax
        from lightgbm_tpu.callback import EarlyStopException
        gbdt = env.model.inner
        with jax.profiler.TraceAnnotation("bench:sync"):
            jax.block_until_ready(
                [gbdt.scores] + [vs.scores for vs in gbdt.valid_sets])
        now = time.perf_counter()
        done = env.iteration + 1
        if done < self.follow:
            self.setup_stamps.append(now)
        elif done == self.follow:
            self.setup_stamps.append(now)
            self.entries[0] = int(gbdt.grow._cache_size())
            if self.trace:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self._span = jax.profiler.TraceAnnotation("bench:window")
                self._span.__enter__()
            self.t0 = time.perf_counter()
        else:
            self.stamps.append(now)
            if now - self.t0 >= self.seconds or (
                    self.trace and len(self.stamps) >= self.trace_iterations):
                self.t1 = now
                if self.trace:
                    self._span.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                self.entries[1] = int(gbdt.grow._cache_size())
                raise EarlyStopException(env.iteration, None)


def _spanned(fn, name):
    import jax

    def wrapper(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapper


def run(cell, seed, seconds, trace, t_process, say, trace_dir):
    cfg, traffic = cell["config"], cell["traffic"]
    rows, cols_n = int(cfg["rows"]), int(cfg["columns"])
    evaluates = bool(traffic.get("eval"))
    valid_rows = int(cfg["valid_rows"]) if evaluates else 0
    follow = int(traffic["reference_trees"])
    parts = {}

    t = time.perf_counter()
    X, y, Xv, yv = data.make_problem(rows, valid_rows, cols_n, seed,
                                       int(cfg["draw_seed"]))
    parts["generate_s"] = time.perf_counter() - t

    t = time.perf_counter()
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.utils.cache import enable_persistent_cache
    parts["import_s"] = time.perf_counter() - t
    parts["compile_cache"] = enable_persistent_cache()
    params = dict(cfg["params"])
    params["metric"] = list(traffic["metrics"]) if evaluates else "None"

    t = time.perf_counter()
    config = config_from_params(dict(params))
    dtrain = lgb.Dataset(X, label=y)
    dtrain.construct(config)
    valid_sets = valid_names = None
    if evaluates:
        dvalid = lgb.Dataset(Xv, label=yv, reference=dtrain)
        dvalid.construct(config)
        valid_sets, valid_names = [dtrain, dvalid], ["training", "valid"]
    parts["bin_s"] = time.perf_counter() - t

    window = Window(follow, seconds, trace,
                    int(traffic.get("trace_iterations", 3)), trace_dir)
    # host spans around the calls into the program, from this file only.
    # They are on in every run, traced or not: an annotation costs well
    # under a microsecond with no trace open, and the call stack is part of
    # what the compile cache keys the grower on, so a traced run that went
    # another way in would compile it a second time.
    undo = []
    for name, span in (("update", "bench:update"),
                       ("eval_train", "bench:eval"),
                       ("eval_valid", "bench:eval")):
        undo.append((name, getattr(lgb.Booster, name)))
        setattr(lgb.Booster, name,
                _spanned(getattr(lgb.Booster, name), span))
    evals = {}
    t_train = time.perf_counter()
    try:
        bst = lgb.train(params, dtrain, num_boost_round=10 ** 6,
                        valid_sets=valid_sets, valid_names=valid_names,
                        evals_result=evals if evaluates else None,
                        verbose_eval=False, callbacks=[window])
    finally:
        for name, fn in undo:
            setattr(lgb.Booster, name, fn)
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
    trees = [plain_tree(t) for t in gbdt.models]
    bounds = [np.asarray(m.bin_upper_bound, np.float64)
              for m in dtrain.constructed.bin_mappers]
    final_score = np.asarray(gbdt.scores[0], np.float64)
    final_valid = (np.asarray(gbdt.valid_sets[0].scores[0], np.float64)
                   if evaluates else None)
    say(f"window: {iters} iterations in {elapsed:.3f} s; "
        f"{len(trees)} trees held; memory_stats {stats}")

    # free the program's state before the reference runs
    bst.free_dataset()
    del bst, gbdt, dtrain
    if evaluates:
        del dvalid, valid_sets
    gc.collect()

    cols = np.ascontiguousarray(X.T)
    valid = (np.ascontiguousarray(Xv.T), yv.astype(np.float64)) \
        if evaluates else None
    del X, Xv
    numbers, _, ref_s = check.check_training(
        cols, y, trees, bounds, cfg["params"], follow, seed, final_score,
        valid=valid, final_valid_score=final_valid,
        evals=evals if evaluates else None,
        metrics=tuple(traffic.get("metrics", ())),
        score_rows=int(traffic.get("score_sample_rows", 100000)), say=say)
    say(f"reference: {ref_s:.1f} s for {follow} trees")

    shape = {"rows": rows, "columns": cols_n,
             "bins": int(cfg["params"]["max_bin"]),
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
        # the chip is as full as what is allocated plus what the runtime
        # has reserved for the compiled programs' temporaries (the grower's
        # are most of it): both are closed to any other use
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
