"""Benchmark: Higgs-shaped GBDT training throughput on TPU.

Workload mirrors the reference's headline benchmark config
(docs/GPU-Performance.md:101-117): binary objective, 255 leaves, 255 bins,
min_data_in_leaf=1, min_sum_hessian_in_leaf=100, lr=0.1, 28 dense features.
Rows default to 1M (BENCH_ROWS overrides; the published Higgs is 10.5M —
set BENCH_ROWS=10500000 to reproduce it).

Shape knobs (the reference's other headline datasets):
  BENCH_FEATURES=2000   Epsilon-shaped wide dense matrix
  BENCH_SPARSITY=0.9    fraction of zero entries in one-hot-style blocks —
                        mutually-exclusive columns that EFB should bundle
                        (Bosch-style sparse regime, GPU-Performance.md:112)

Baseline: the reference v2.0.5 CLI measured on THIS host (1 CPU core,
identical synthetic data/config at 1M rows, marginal cost of trees 2-11 so
load/bin time cancels — scripts/measure_ref_baseline.py, result committed
in docs/ref_baseline_measured.json): 0.3955 s/tree = 2.5285 trees/s.  The
host exposes exactly one CPU, so the published 28-thread rig
(docs/GPU-Performance.md:101-117) cannot be measured here (num_threads=28
on one core was measured too: 1.60 trees/s — oversubscription hurts); we
scale the measured single-core throughput linearly by 28 (optimistic for
the CPU — LightGBM scales sublinearly) to get a conservative stand-in:
70.8 trees/s at 1M rows x 28 features.  Histogram cost is linear in
rows x features, so the baseline scales by
(1M / BENCH_ROWS) * (28 / BENCH_FEATURES) for other shapes;
BENCH_BASELINE_TPS overrides with a directly measured number (e.g. from the
interop-built reference CLI).  ``vs_baseline`` = our trees/s / that.

One process for each chip: this process is a thin, jax-free SUPERVISOR and
the measured workload runs in ONE child subprocess (BENCH_CHILD=1), which
owns the device.  The default run is tpu + fused (the in-kernel-gather
histogram kernel): no chip, or a failed child, is a non-zero exit with the
child's error — nothing walks down to another kernel or to the CPU, and no
result is labelled with a platform it did not run on.  BENCH_PLATFORM=cpu
is the explicit CPU run (segment-sum histograms) the tests use;
BENCH_FUSED=0 is the explicit einsum run on the chip.
BENCH_MESH_FUSED=1 (with BENCH_MESH=1) swaps the mesh rung's configs for
the gspmd_hist fused-vs-flat A/B pairs.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline",
"telemetry"[, "leaves_sweep", "degraded", "kernel_mismatch"]}.
"leaves_sweep" (cpu rung by default; BENCH_LEAVES_SWEEP=1 to force on
tpu, =0 to disable) is the deep-tree fixed-cost micro-rung: marginal ms
per additional leaf between 31- and 255-leaf trees at <= 200k rows —
the per-split fixed overhead the round-7 work collapsed, tracked per
round.  "serving" (cpu rung by default; BENCH_SERVING=1 to force on tpu,
=0 to disable) is the high-QPS inference micro-rung (docs/SERVING.md):
p50/p99 latency + QPS of the SoA microbatch engine at 1/64/4096-row
batches on the freshly trained model, the speedup over the per-tree
Predictor.predict host loop, and a mixed-size async replay pinned to
zero recompiles via the predict_jit_entries gauge.  The "telemetry" block
carries the OBSERVED histogram-kernel identity (lightgbm_tpu.obs dispatch
counters) — if it disagrees with the rung label the result is marked
degraded + kernel_mismatch so decide_flips.py refuses to compare it.
"metrics_snapshot" embeds the live Prometheus sample map
(obs/metrics.snapshot) next to "telemetry"/"memory" so
scripts/obs_diff.py can regression-diff two rungs at the metrics level.
"model_quality" embeds the obs/model_quality tracker summary of the
measured training (top features by cumulative gain, gain-decay curve) so
bench_history.py can warn on an importance flip between same-config runs.
BENCH_TRACE=<path> additionally writes a Chrome-trace span file for the
measured child (render: `python -m lightgbm_tpu.obs <path>`).

BENCH_MESH=1 switches the whole run to the ``mesh`` rung (docs/
DISTRIBUTED.md): GSPMD-vs-shard_map data-parallel training on a FORCED
8-logical-device host mesh — data/feature/auto (planner) shardings over
200k x 28 and a feature-wide 2k-column shape, with trees/s and the
compiled-HLO collective census (op counts + bytes) embedded per
configuration.  A host-mesh rung by construction (it A/Bs the
formulations, not chip throughput).

BENCH_STREAMED=1 switches to the ``streamed`` rung: resident-vs-chunked
out-of-core training A/B over an artificial ``hbm_budget`` that forces
the placement pre-flight to leave the binned matrix host-side and
double-buffer it through the device (data/stream.py) — trees/s, rows/s,
the measured pipeline stall fraction and the ``grower_jit_entries``
zero-recompile pin per configuration.
"""
import json
import os
import subprocess
import sys
import time

BASELINE_TREES_PER_SEC_1M = 2.5285 * 28  # see module docstring

# only binning-relevant params key the dataset cache: grower knobs
# (bin packing, use_pallas, split_find, ...)
# never change the constructed dataset, and hashing them would make every
# A/B stage re-bin.  INVARIANT (pinned by tests/test_bench_keys.py): this
# set must stay a superset of every
# construction-relevant Config attribute read under lightgbm_tpu/data/ —
# a new construction knob missing here would silently reuse stale cached
# datasets in A/B runs.
BINNING_KEYS = frozenset({
    "enable_bundle", "max_bin", "min_data_in_bin", "use_missing",
    "zero_as_missing", "bin_construct_sample_cnt", "max_conflict_rate",
    "min_data_in_leaf", "data_random_seed"})


def make_data(n, f=28, sparsity=0.0, seed=42):
    import numpy as np
    rng = np.random.RandomState(seed)
    if sparsity > 0.0:
        # Bosch-style regime: dense head + blocks of mutually-exclusive
        # one-hot-ish columns (zero = missing/default) that EFB can bundle.
        f_dense = max(4, f // 10)
        f_sparse = f - f_dense
        X = np.zeros((n, f), dtype=np.float32)
        X[:, :f_dense] = rng.randn(n, f_dense).astype(np.float32)
        group = max(2, int(round(1.0 / max(1e-6, 1.0 - sparsity))))
        n_groups = (f_sparse + group - 1) // group
        hot = rng.randint(0, group + 1, size=(n, n_groups))  # group = "all zero"
        for gi in range(n_groups):      # one-hot indicator columns (2 bins)
            base = f_dense + gi * group
            width = min(group, f - base)
            sel = hot[:, gi]
            idx = np.flatnonzero(sel < width)
            X[idx, base + sel[idx]] = 1.0
        w = rng.randn(f).astype(np.float32) * 0.5
    else:
        X = rng.randn(n, f).astype(np.float32)
        X[:, ::4] = np.abs(X[:, ::4]) + 0.1
        mask = rng.rand(n, max(1, f // 7)) < 0.3
        X[:, :max(1, f // 7)][mask] = 0.0
        w = rng.randn(f) * 0.5
    y = ((X @ w + rng.randn(n)) > 0).astype(np.float32)
    return X, y


def _construct_cached(make_xy, cfg, n_rows, n_feat, sparsity, params):
    """Construct the binned dataset, memoized on disk.

    Dataset construction is deterministic in (shape, sparsity, binning
    params), so repeat bench runs load the committed-format binary cache
    (Dataset.save_binary) instead of re-binning.  ``make_xy`` is a thunk:
    on a cache hit the synthetic data is never even generated (~20-30 s
    at the 10.5M shape).
    BENCH_DS_CACHE= (empty) disables; binning-relevant BENCH_EXTRA_PARAMS
    are part of the key.
    """
    from lightgbm_tpu.basic import Dataset
    from lightgbm_tpu.data.dataset import construct
    cache_dir = os.environ.get(
        "BENCH_DS_CACHE",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_cache"))
    if not cache_dir:
        X, y = make_xy()
        return construct(X, cfg, label=y)
    import hashlib
    from lightgbm_tpu.config import canonicalize_params
    # keys are canonicalized first so aliases/case/whitespace neither miss
    # the BINNING_KEYS filter nor alias a stale entry; the set itself
    # (module constant) mirrors what lightgbm_tpu/data/ actually reads at
    # construction (incl. min_data_in_leaf's trivial-feature pre-filter
    # and the bin-sample seed) and is invariant-checked in CI.
    raw = dict(kv.partition("=")[::2] for kv in filter(
        None, os.environ.get("BENCH_EXTRA_PARAMS", "").split(",")))
    canon = canonicalize_params(raw)
    extras = ",".join(f"{k}={v}" for k, v in sorted(canon.items())
                      if k in BINNING_KEYS)
    xh = hashlib.md5(extras.encode()).hexdigest()[:8] if extras else "0"
    # version salt: a binning-code change must invalidate cached datasets,
    # or the bench would attribute stale-bin numbers to the code under test
    pkg = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "lightgbm_tpu")
    vh = hashlib.md5()
    for rel in ("data/binning.py", "data/bundling.py", "data/dataset.py",
                "native/gbt_native.cpp"):
        with open(os.path.join(pkg, rel), "rb") as f:
            vh.update(f.read())
    bundle_on = str(params.get("enable_bundle", False)).lower() in ("true",
                                                                    "1")
    key = (f"r{n_rows}_f{n_feat}_s{sparsity}_b{params['max_bin']}"
           f"_e{int(bundle_on)}_x{xh}_v{vh.hexdigest()[:8]}")
    path = os.path.join(cache_dir, key + ".bin")
    if os.path.exists(path):
        try:
            ds = Dataset._load_binary_training_data(path)
            sys.stderr.write(f"bench: dataset cache hit {path}\n")
            return ds
        except Exception as e:          # corrupt/stale cache: rebuild
            sys.stderr.write(f"bench: dataset cache unreadable ({e}); "
                             "rebuilding\n")
    X, y = make_xy()
    ds = construct(X, cfg, label=y)
    try:
        os.makedirs(cache_dir, exist_ok=True)
        wrapper = Dataset(None)
        wrapper._constructed = ds
        wrapper.save_binary(path, compress=False)
    except Exception as e:
        sys.stderr.write(f"bench: dataset cache save failed ({e})\n")
    return ds


def _leaves_sweep(params, n_rows, n_feat, sparsity):
    """Deep-tree fixed-cost micro-rung: per-tree time at 31 vs 255 leaves
    on <= 200k rows (CPU-safe), reported as marginal ms per additional
    leaf at fixed N.  This is the quantity the round-7 perf work
    collapsed (carried-state copies + kilobucket padding made it ~70% of
    deep-tree time); embedding it in every BENCH JSON lets the trajectory
    track deep-tree overhead per round.  Runs by default on the cpu rung,
    BENCH_LEAVES_SWEEP=1 forces it on tpu rungs (two extra grower
    compiles), =0 disables."""
    import time

    import jax
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.obs.counters import counters as obs_counters

    rows = min(n_rows, 200_000)
    lo, hi = 31, 255
    n_timed = int(os.environ.get("BENCH_LEAVES_SWEEP_TREES", 2))
    ds = None

    def measure(split_find):
        nonlocal ds
        sec = {}
        for leaves in (lo, hi):
            p = dict(params, num_leaves=leaves)
            if split_find is not None:
                p["split_find"] = split_find
            cfg = config_from_params(p)
            if ds is None:      # num_leaves never keys dataset construction
                ds = _construct_cached(
                    lambda: make_data(rows, n_feat, sparsity), cfg, rows,
                    n_feat, sparsity, p)
            booster = create_boosting(cfg, ds, create_objective(cfg))
            booster.train_one_iter()              # warmup (compile)
            jax.block_until_ready(booster.scores)
            t0 = time.perf_counter()
            for _ in range(n_timed):
                booster.train_one_iter()
            jax.block_until_ready(booster.scores)
            sec[leaves] = (time.perf_counter() - t0) / n_timed
        return sec, (sec[hi] - sec[lo]) / (hi - lo) * 1e3

    sec, marginal = measure(None)         # the configured default
    obs_counters.gauge("leaves_sweep_marginal_ms_per_leaf", marginal)
    out = {"rows": rows, "leaves": [lo, hi],
           "split_find": params.get("split_find", "fused"),
           "sec_per_tree": {str(k): round(v, 4) for k, v in sec.items()},
           "marginal_ms_per_leaf": round(marginal, 3)}
    # in-rung split-find A/B (round 8): the chain forced-baseline partner
    # rides the same dataset/process so the pair shares host conditions;
    # BENCH_LEAVES_AB=0 skips the extra two boosters
    if os.environ.get("BENCH_LEAVES_AB", "") != "0" \
            and params.get("split_find", "fused") != "chain":
        sec_c, marginal_c = measure("chain")
        out["chain_sec_per_tree"] = {str(k): round(v, 4)
                                     for k, v in sec_c.items()}
        out["chain_marginal_ms_per_leaf"] = round(marginal_c, 3)
    return out


def _serving_rung(booster, n_feat, sparsity):
    """High-QPS serving micro-rung (docs/SERVING.md): p50/p99 latency and
    QPS of the SoA microbatch engine at 1/64/4096-row batches on the model
    this child just trained, the speedup over the per-tree
    ``Predictor.predict`` host loop, and a mixed-size request replay
    through the async ModelServer pinned to ZERO recompiles via the
    ``predict_jit_entries`` gauge.  Default-on for the cpu rung,
    BENCH_SERVING=1 forces it on tpu, =0 disables."""
    import time

    import numpy as np
    from lightgbm_tpu.inference import jit_entries
    from lightgbm_tpu.obs.counters import counters as obs_counters
    from lightgbm_tpu.serving import ModelServer

    X, _ = make_data(8192, n_feat, sparsity, seed=7)
    X = np.asarray(X, np.float64)
    # the engine exactly as serving would build it ('auto' backend:
    # SoA microbatch executables on an accelerator, the OpenMP C++
    # traversal on a bare-CPU backend) plus a forced-xla twin so the
    # jitted path is measured on every tier, and — when the model packs —
    # the packed-node-word traversal twin (serving_traversal=packed) so
    # the xla-vs-packed headroom is a tracked number per round
    auto_eng = booster.predict_engine(prewarm=True)
    from lightgbm_tpu.inference import PredictEngine
    xla_eng = auto_eng if (auto_eng.backend, auto_eng.traversal) == \
        ("xla", "xla") else \
        PredictEngine(booster.models, booster.num_class,
                      prewarm=True, backend="xla", traversal="xla")
    packed_eng = PredictEngine(booster.models, booster.num_class,
                               prewarm=False, backend="xla",
                               traversal="packed")
    packed_eng = packed_eng.prewarm() if packed_eng.traversal == "packed" \
        else None                      # unpackable model: no packed row
    entries_warm = jit_entries()
    p = booster.predictor()            # engine attached (just built)

    # the displaced baseline: the per-tree host-traversal loop the
    # acceptance bar prices the engine against
    x4 = X[:4096]
    t0 = time.perf_counter()
    p.predict_raw_trees(x4)
    old_s = time.perf_counter() - t0

    out = {"predict_jit_entries": entries_warm,
           "backend": auto_eng.backend,
           "traversal": auto_eng.traversal, "backends": {}}
    engines = {auto_eng.backend: auto_eng}
    if xla_eng is not auto_eng:
        engines["xla"] = xla_eng
    if packed_eng is not None and \
            (auto_eng.backend, auto_eng.traversal) != ("xla", "packed"):
        engines["xla+packed"] = packed_eng
    for name, eng in engines.items():
        buckets = {}
        for b, reps in ((1, 50), (64, 30), (4096, 5)):
            xb = X[:b]
            eng.raw_scores(xb)         # touch (compiled at prewarm)
            lats = []
            t0 = time.perf_counter()
            for _ in range(reps):
                t1 = time.perf_counter()
                eng.raw_scores(xb)
                lats.append((time.perf_counter() - t1) * 1e3)
            total = time.perf_counter() - t0
            lats = np.asarray(lats)
            buckets[str(b)] = {
                "p50_ms": round(float(np.percentile(lats, 50)), 3),
                "p99_ms": round(float(np.percentile(lats, 99)), 3),
                "qps": round(reps * b / total, 1),
            }
        out["backends"][name] = {
            "buckets": buckets,
            "speedup_vs_predict_loop": round(
                buckets["4096"]["qps"] / (4096 / old_s), 2)}
    out["buckets"] = out["backends"][auto_eng.backend]["buckets"]
    out["predict_loop_rows_per_s"] = round(4096 / old_s, 1)
    out["speedup_vs_predict_loop"] = \
        out["backends"][auto_eng.backend]["speedup_vs_predict_loop"]

    # mixed-size replay, twice: through the async server (coalescing, as
    # deployed) and against the forced-xla ladder — the recompile pin
    # must hold on the JITTED path, not just on a backend that never
    # compiles
    rng = np.random.RandomState(3)
    sizes = rng.choice([1, 2, 8, 33, 64, 200, 512, 1111, 4096], size=60)
    for s in sizes:
        xla_eng.raw_scores(X[:int(s)])
    srv = ModelServer(booster=booster,
                      params={"verbose": -1, "latency_budget_ms": 1.0})
    futs = [srv.submit(X[:int(s)]) for s in sizes]
    for f in futs:
        f.result(timeout=300)
    rep = srv.stop()
    out["replay"] = {"requests": rep["requests"], "rows": rep["rows"],
                     "batches": rep["batches"], "qps": rep["qps"],
                     "rows_per_s": rep["rows_per_s"]}
    out["recompiles"] = jit_entries() - entries_warm
    out["zero_recompile"] = out["recompiles"] == 0
    obs_counters.gauge("predict_jit_entries", jit_entries())
    return out


def _mesh_rung_child():
    """The ``mesh`` rung (BENCH_MESH=1): GSPMD-vs-shard_map training on a
    FORCED 8-logical-device host mesh (docs/DISTRIBUTED.md).

    Two shapes — the 200k x 28 deep-tree shape and a feature-wide
    2k-column shape (the histogram-pool-bound regime the sharding
    planner exists for) — each trained under the data / feature / auto
    (planner) GSPMD shardings plus the forced shard_map A/B partner,
    with trees/s AND the compiled-HLO collective census (op counts +
    bytes, ``GBDT.grow_hlo_census``) embedded per configuration.  Always
    a host-mesh CPU rung by construction: the 8 logical devices stand in
    for chips, so the numbers A/B the FORMULATIONS (who inserts the
    collectives, what payloads move), not chip throughput — deciding the
    on-chip default needs the four-chip host (ROADMAP S7)
    (``scripts/decide_flips.py`` renders the pair as coverage)."""
    import time

    import jax
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.obs.counters import counters as obs_counters
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.utils import log as _log

    _log.set_verbosity(-1)
    n_devices = len(jax.devices())
    n_timed = int(os.environ.get("BENCH_MESH_TREES", 1))
    fused_ab = os.environ.get("BENCH_MESH_FUSED") == "1"
    # per-shape sharding sets: feature sharding only makes sense on the
    # wide shape (its histogram pool is the planner's reason to exist),
    # and on the VIRTUAL mesh all 8 devices share one host's cores — the
    # feature sharding of a 28-column shape would just 8x the row scans
    configs_narrow = [
        ("gspmd_data", {"parallel_impl": "gspmd", "mesh_shape": "data"}),
        ("gspmd_auto", {"parallel_impl": "gspmd", "mesh_shape": "auto"}),
        ("shardmap_data", {"parallel_impl": "shardmap"}),
    ]
    configs_wide = [
        ("gspmd_feature", {"parallel_impl": "gspmd",
                           "mesh_shape": "feature"}),
        ("gspmd_auto", {"parallel_impl": "gspmd", "mesh_shape": "auto"}),
        ("shardmap_data", {"parallel_impl": "shardmap"}),
    ]
    if fused_ab:
        # BENCH_MESH_FUSED=1: the gspmd_hist fused-vs-flat A/B
        # (shard_map islands + interpret-mode fused kernel vs pure-XLA
        # scatter-add) on the data mesh AND the 2x4 hybrid mesh, where
        # the island's partials cross the shard-sized reduction; the
        # wide shape rides the feature mesh (2000 cols / 8 shards = 250
        # per device — inside the kernel's 512-col ceiling)
        def _pair(ms):
            return [
                (f"gspmd_flat_{ms}",
                 {"parallel_impl": "gspmd", "mesh_shape": ms,
                  "gspmd_hist": "flat"}),
                (f"gspmd_fused_{ms}",
                 {"parallel_impl": "gspmd", "mesh_shape": ms,
                  "gspmd_hist": "fused"}),
            ]
        configs_narrow = _pair("data") + _pair("2x4")
        configs_wide = _pair("feature")
    shapes = [
        (int(os.environ.get("BENCH_MESH_ROWS", 200_000)),
         int(os.environ.get("BENCH_MESH_FEATURES", 28)),
         int(os.environ.get("BENCH_MESH_LEAVES", 63)), configs_narrow),
        (int(os.environ.get("BENCH_MESH_WIDE_ROWS", 10_000)),
         int(os.environ.get("BENCH_MESH_WIDE_FEATURES", 2000)),
         int(os.environ.get("BENCH_MESH_WIDE_LEAVES", 15)), configs_wide),
    ]
    out_shapes = {}
    headline = None
    for rows, feats, leaves, configs in shapes:
        key = f"{rows // 1000}kx{feats}"
        params = {
            "objective": "binary", "num_leaves": leaves,
            "max_bin": int(os.environ.get("BENCH_MESH_MAX_BIN", 63)),
            "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100,
            "learning_rate": 0.1, "verbose": -1, "use_pallas": False,
            "tree_learner": "data",
        }
        ds = None
        rows_out = {}
        for name, extra in configs:
            p = dict(params, **extra)
            cfg = config_from_params(p)
            if ds is None:   # impl/mesh knobs never key construction
                ds = _construct_cached(
                    lambda: make_data(rows, feats, 0.0), cfg, rows, feats,
                    0.0, p)
            try:
                # fresh counters per config: the observed-kernel identity
                # and any layout_downgrade events below belong to THIS
                # configuration, not whatever trained before it
                obs_counters.reset()
                booster = create_boosting(cfg, ds, create_objective(cfg))
                booster.train_one_iter()          # warmup (compile)
                jax.block_until_ready(booster.scores)
                t0 = time.perf_counter()
                for _ in range(n_timed):
                    booster.train_one_iter()
                jax.block_until_ready(booster.scores)
                dt = (time.perf_counter() - t0) / n_timed
                rec = {"trees_per_sec": round(1.0 / dt, 4),
                       "impl": booster._parallel_impl,
                       "observed_kernel": obs_counters.observed_kernel(),
                       "collectives": booster.grow_hlo_census(
                           label=f"{key}:{name}")}
                downs = obs_counters.events("layout_downgrade")
                if downs:
                    rec["downgrades"] = downs
                if booster._gspmd_plan is not None:
                    plan = booster._gspmd_plan
                    rec["mesh"] = f"{plan.data}x{plan.feature}"
                    rec["block_shard_bins"] = plan.block_shard_bins
                rows_out[name] = rec
            except Exception as e:   # one config never kills the rung
                rows_out[name] = {"error": str(e)[:200]}
        g = rows_out.get("gspmd_data") or rows_out.get("gspmd_feature") \
            or {}
        s = rows_out.get("shardmap_data", {})
        if "trees_per_sec" in g and "trees_per_sec" in s:
            rows_out["gspmd_vs_shardmap"] = round(
                g["trees_per_sec"] / s["trees_per_sec"], 3)
        for ms in ("data", "2x4", "feature"):
            fu = rows_out.get(f"gspmd_fused_{ms}", {})
            fl = rows_out.get(f"gspmd_flat_{ms}", {})
            if "trees_per_sec" in fu and "trees_per_sec" in fl:
                rows_out[f"fused_vs_flat_{ms}"] = round(
                    fu["trees_per_sec"] / fl["trees_per_sec"], 3)
                if headline is None and ms == "data":
                    headline = fu["trees_per_sec"]
        out_shapes[key] = rows_out
        if headline is None:
            headline = g.get("trees_per_sec", 0.0)
    result = {
        "metric": (f"mesh gspmd_hist fused-vs-flat A/B "
                   f"(cpu, forced {n_devices}-device host mesh)"
                   if fused_ab else
                   f"mesh GSPMD-vs-shardmap data-parallel training "
                   f"(cpu, forced {n_devices}-device host mesh)"),
        "value": headline or 0.0,
        "unit": "trees/sec",
        "vs_baseline": None,
        "mesh": {"devices": n_devices, "timed_trees": n_timed,
                 "fused_ab": fused_ab, "shapes": out_shapes},
    }
    print(json.dumps(result))


def _streamed_rung_child():
    """The ``streamed`` rung (BENCH_STREAMED=1): resident-vs-chunked
    out-of-core A/B under an ARTIFICIAL hbm_budget (docs/OBSERVABILITY.md
    ``stream_*`` counters, data/stream.py pipeline).

    One shape, two boosters over the SAME binned dataset: the classic
    fully-device-resident baseline, then ``data_stream=auto`` with
    ``hbm_budget`` scaled below the resident predicted peak so the
    pre-flight placement walk MUST leave the binned matrix host-side and
    stream it through the double-buffered block pipeline.  Per config:
    trees/s, rows/s, the measured stall fraction (blocking wait on
    incoming blocks / wall time — the pipeline's overlap evidence), the
    ``grower_jit_entries`` zero-recompile pin across the chunk loop, and
    the planner's ``PlacementPlan``.  A host rung by construction (CPU's
    synchronous dispatch makes the stall fraction a conservative upper
    bound — the TPU's async DMA only hides MORE of the copy)."""
    import time

    import jax
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.obs import memory as obs_memory
    from lightgbm_tpu.obs.counters import counters as obs_counters
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.utils import log as _log

    _log.set_verbosity(-1)
    rows = int(os.environ.get("BENCH_STREAMED_ROWS", 400_000))
    feats = int(os.environ.get("BENCH_STREAMED_FEATURES", 28))
    n_timed = int(os.environ.get("BENCH_STREAMED_TREES", 3))
    chunk_pin = int(os.environ.get("BENCH_STREAMED_CHUNK", 0))
    params = {
        "objective": "binary",
        "num_leaves": int(os.environ.get("BENCH_STREAMED_LEAVES", 63)),
        "max_bin": int(os.environ.get("BENCH_STREAMED_MAX_BIN", 63)),
        "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100,
        "learning_rate": 0.1, "verbose": -1, "use_pallas": False,
    }
    cfg0 = config_from_params(params)
    ds = _construct_cached(lambda: make_data(rows, feats, 0.0), cfg0,
                           rows, feats, 0.0, params)
    # the artificial budget: resident's predicted peak scaled down so
    # resident refuses but a (possibly halved) chunk pipeline still fits
    pred = obs_memory.predict_hbm(
        rows=rows, features=int(ds.binned.shape[1]),
        bins=params["max_bin"], leaves=params["num_leaves"],
        bin_bytes=int(ds.binned.dtype.itemsize))
    frac = float(os.environ.get("BENCH_STREAMED_BUDGET_FRACTION", 0.7))
    budget = int(pred["peak_bytes"] * frac)
    configs = [
        ("resident", {"data_stream": "resident"}),
        ("chunked", dict({"data_stream": "auto", "hbm_budget": budget},
                         **({"stream_chunk_rows": chunk_pin}
                            if chunk_pin else {}))),
    ]
    out = {}
    for name, extra in configs:
        cfg = config_from_params(dict(params, **extra))
        try:
            obs_counters.reset()
            booster = create_boosting(cfg, ds, create_objective(cfg))
            placements = obs_counters.events("placement_decision")
            booster.train_one_iter()          # warmup (compile)
            jax.block_until_ready(booster.scores)
            streamer = booster._streamer
            if streamer is not None:
                streamer.take_wait_ms()       # drop warmup-pass waits
            gauge_fn = getattr(booster.grow, "_cache_size", None)
            entries_warm = gauge_fn() if gauge_fn else None
            stalls_warm = obs_counters.total("stream_stalls")
            t0 = time.perf_counter()
            for _ in range(n_timed):
                booster.train_one_iter()
            jax.block_until_ready(booster.scores)
            dt = (time.perf_counter() - t0) / n_timed
            rec = {"trees_per_sec": round(1.0 / dt, 4),
                   "rows_per_sec": round(rows / dt, 1)}
            if streamer is not None:
                wait_ms = streamer.take_wait_ms()
                rec["stream_wait_ms_per_tree"] = round(wait_ms / n_timed, 3)
                rec["stall_fraction"] = round(
                    min(1.0, wait_ms / (dt * n_timed * 1e3)), 4)
                rec["stalls"] = int(
                    obs_counters.total("stream_stalls") - stalls_warm)
                rec["blocks"] = streamer.store.num_blocks
                rec["chunk_rows"] = streamer.store.chunk_rows
            if gauge_fn is not None:
                rec["grower_jit_entries"] = gauge_fn()
                rec["zero_recompile"] = \
                    rec["grower_jit_entries"] == entries_warm
            plan = getattr(booster, "_placement", None)
            if plan is not None:
                rec["placement"] = {
                    "mode": plan.mode, "chunk_rows": plan.chunk_rows,
                    "peak_bytes": plan.peak_bytes,
                    "capacity": plan.capacity}
            elif placements:
                rec["placement"] = placements[-1]
            downs = obs_counters.events("layout_downgrade")
            if downs:
                rec["downgrades"] = downs
            out[name] = rec
        except Exception as e:       # one config never kills the rung
            out[name] = {"error": str(e)[:200]}
    r, c = out.get("resident", {}), out.get("chunked", {})
    if "trees_per_sec" in r and "trees_per_sec" in c:
        out["chunked_vs_resident"] = round(
            c["trees_per_sec"] / r["trees_per_sec"], 3)
    result = {
        "metric": (f"streamed out-of-core training A/B "
                   f"({rows // 1000}k x {feats}, artificial hbm_budget, "
                   f"cpu host pipeline)"),
        "value": c.get("trees_per_sec", 0.0),
        "unit": "trees/sec",
        "vs_baseline": None,
        "streamed": {"rows": rows, "features": feats,
                     "timed_trees": n_timed, "hbm_budget": budget,
                     "budget_fraction": frac,
                     "predicted_resident_peak": pred["peak_bytes"],
                     "configs": out},
    }
    print(json.dumps(result))


def child_main():
    """The measured workload.  Runs under BENCH_CHILD with the platform and
    histogram method fixed by the supervisor; prints the result JSON line."""
    platform_want = os.environ["BENCH_CHILD_PLATFORM"]      # 'tpu' | 'cpu'
    mode = os.environ.get("BENCH_CHILD_MODE", "segment")
    if mode == "mesh":
        # the mesh rung runs on a FORCED 8-logical-device host mesh —
        # flags must land before the CPU client is created
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        _mesh_rung_child()
        return
    if mode == "streamed":
        # the streamed rung is a host-pipeline A/B: one device, the
        # binned matrix host-side, blocks flowing through device_put
        os.environ["JAX_PLATFORMS"] = "cpu"
        _streamed_rung_child()
        return
    #                      fused | einsum | segment (cpu)
    use_pallas = mode == "fused"
    if platform_want == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    n_rows = int(os.environ.get("BENCH_ROWS", 1_000_000))
    n_feat = int(os.environ.get("BENCH_FEATURES", 28))
    sparsity = float(os.environ.get("BENCH_SPARSITY", 0))
    n_timed = int(os.environ.get("BENCH_TREES", 10))
    if platform_want == "cpu":
        # cap the last-resort rung so it finishes inside the stage timeout
        # (vs_baseline stays honest — the baseline scales by rows).  With
        # the segment-sum histogram + localized partition the CPU rung
        # runs ~0.4 trees/s at 1M x 28; histogram work scales with
        # rows x features, so the cap shrinks proportionally for wide
        # shapes (never below 50k rows).
        cap = max(50_000, int(1_000_000 * 28 / max(n_feat, 1)))
        n_rows = int(os.environ.get("BENCH_ROWS_CPU", min(n_rows, cap)))
        n_timed = int(os.environ.get("BENCH_TREES_CPU", min(n_timed, 5)))

    import jax
    if jax.devices()[0].platform != platform_want:
        # a result is only ever labelled with the platform it ran on
        sys.stderr.write(f"bench child: wanted {platform_want}, got "
                         f"{jax.devices()[0].platform}\n")
        sys.exit(3)
    from lightgbm_tpu.utils.cache import enable_persistent_cache
    enable_persistent_cache()
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.data.dataset import construct
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.obs import devprof as obs_devprof
    from lightgbm_tpu.obs import memory as obs_memory
    from lightgbm_tpu.obs import trace as obs_trace
    from lightgbm_tpu.obs.counters import counters as obs_counters
    from lightgbm_tpu.utils import log as _log

    _log.set_verbosity(-1)
    # telemetry: fresh counters per rung so the observed-kernel evidence is
    # THIS child's; BENCH_TRACE collects a span trace alongside the JSON.
    # Memory accounting is always on for the measured child — every bench
    # JSON carries a "memory" block (predicted + measured peak bytes)
    obs_counters.reset()
    bench_trace = os.environ.get("BENCH_TRACE", "")
    # device-time attribution (obs/devprof.py): armed rungs capture
    # profiler windows over dedicated un-timed steady iterations (below)
    # and embed the device_profile block; needs the tracer's
    # TraceAnnotation phase windows, so tracing arms alongside
    devprof_armed = os.environ.get("BENCH_DEVICE_PROFILE", "") == "1"
    profile_iters = int(os.environ.get("BENCH_PROFILE_ITERS", "2") or 2)
    if bench_trace or devprof_armed:
        obs_trace.start(bench_trace or None)
    obs_memory.start()
    # model-quality plane: every bench JSON embeds the tracker summary
    # (top features by cumulative gain, gain-decay curve) so
    # bench_history.py can flag an importance flip between runs at the
    # same config.  Host-side folds over the drain's fetched arrays only.
    from lightgbm_tpu.obs import model_quality as obs_model_quality
    obs_model_quality.start()
    if devprof_armed:
        obs_devprof.start(profile_iters=profile_iters)
    platform = jax.devices()[0].platform
    params = {
        "objective": "binary",
        "num_leaves": int(os.environ.get("BENCH_LEAVES", 255)),
        "max_bin": int(os.environ.get("BENCH_MAX_BIN", 255)),
        "min_data_in_leaf": 1,
        "min_sum_hessian_in_leaf": 100,
        "learning_rate": 0.1,
        "verbose": -1,
        "use_pallas": use_pallas and platform == "tpu",
        "enable_bundle": sparsity > 0.0,
    }
    # ad-hoc A/B knobs (e.g. BENCH_EXTRA_PARAMS=enable_bin_packing=false)
    for kv in filter(None, os.environ.get("BENCH_EXTRA_PARAMS",
                                          "").split(",")):
        k, _, v = kv.partition("=")
        params[k] = v
    cfg = config_from_params(params)
    t0 = time.perf_counter()
    ds = _construct_cached(lambda: make_data(n_rows, n_feat, sparsity),
                           cfg, n_rows, n_feat, sparsity, params)
    sys.stderr.write(f"bench: construct {time.perf_counter() - t0:.1f}s, "
                     f"{ds.binned.shape[1]} physical cols for {n_feat} "
                     f"features\n")
    booster = create_boosting(cfg, ds, create_objective(cfg))

    t0 = time.perf_counter()
    booster.train_one_iter()          # warmup (compile)
    jax.block_until_ready(booster.scores)
    sys.stderr.write(f"bench: warmup (compile) {time.perf_counter() - t0:.1f}s\n")
    if devprof_armed:
        # devprof windows run over DEDICATED steady iterations so the
        # capture/parse overhead never perturbs the timed loop below
        t0 = time.perf_counter()
        for _ in range(profile_iters):
            booster.train_one_iter()
        jax.block_until_ready(booster.scores)
        sys.stderr.write(f"bench: devprof capture ({profile_iters} iters) "
                         f"{time.perf_counter() - t0:.1f}s\n")
    t0 = time.perf_counter()
    for _ in range(n_timed):
        booster.train_one_iter()
    jax.block_until_ready(booster.scores)
    dt = time.perf_counter() - t0
    trees_per_sec = n_timed / dt
    sys.stderr.write("bench " + booster.timers.report() + "\n")

    link = _link_profile(jax)
    sys.stderr.write(f"bench: link {json.dumps(link)}\n")

    # label from the grower's RESOLVED method, not the requested mode: a
    # fused request that fell back (layout gate) must never be recorded
    # as a fused number
    resolved = booster.grower_cfg.hist_method
    kernel_tag = (f", {resolved}" if platform == "tpu"
                  and resolved == "fused" else "")

    # rung honesty: the telemetry dispatch counters record which kernel the
    # grower ACTUALLY traced.  A disagreement with the resolved label (e.g.
    # a fused request silently downgraded inside jit, or a pallas rung
    # degraded to einsum) marks the rung degraded so decide_flips never
    # compares mislabeled numbers.  The kernel identity is snapshotted
    # BEFORE the leaves-sweep micro-rung trains its extra boosters.
    observed = obs_counters.observed_kernel()
    # split-find identity of the MEASURED training, snapshotted before the
    # leaves-sweep micro-rung trains its extra (possibly chain-forced A/B)
    # boosters into the same counter registry
    split_find_counts = obs_counters.get("split_find_dispatch")

    # model-quality summary of the MEASURED training, snapshotted (and
    # the tracker disarmed) BEFORE the micro-rungs train extra boosters
    _ = booster.models               # drain the async tail into the tracker
    model_quality = obs_model_quality.get_tracker().summary()
    obs_model_quality.stop()

    # device-time attribution block, finalized BEFORE the micro-rungs so
    # it describes the measured training only (obs/devprof.py)
    device_profile = obs_devprof.stop() if devprof_armed else None
    if devprof_armed and not bench_trace:
        # the tracer was armed only to mirror phase windows into the
        # devprof captures — stop it here so its span overhead never rides
        # the leaves-sweep / serving micro-rung numbers below (no path set,
        # so stop() writes nothing and returns None)
        obs_trace.stop()
    if device_profile is not None:
        sys.stderr.write(
            f"bench: devprof captured={device_profile['captured_iterations']}"
            f" attributed={device_profile['attributed_fraction']}"
            f" phases={json.dumps(device_profile['phase_device_ms'])}\n")

    # device-memory evidence, also snapshotted BEFORE the leaves sweep so
    # its extra boosters never inflate the measured number: the predicted
    # peak (obs/memory.predict_hbm fit model, pre-flight recorded it at
    # booster setup) against the measured peak (TPU memory_stats, or the
    # live-array census on the CPU rung — the predicted-vs-measured
    # agreement tests/test_memory.py pins within the documented tolerance)
    mem_monitor = obs_memory.get_memory()
    mem_monitor.sample(site="bench_end")
    pred = getattr(booster, "memory_prediction", None) or \
        obs_memory.predict_hbm(rows=booster.num_data,
                               features=int(ds.binned.shape[1]),
                               bins=params["max_bin"],
                               leaves=params["num_leaves"])
    measured_peak = mem_monitor.measured_peak()
    mem_expected = (pred["peak_bytes"]
                    if mem_monitor.source == "memory_stats"
                    else pred["resident_bytes"])
    memory_block = {
        "predicted_peak_bytes": pred["peak_bytes"],
        "predicted_resident_bytes": pred["resident_bytes"],
        "predicted_components": dict(
            sorted({**pred["residents"], **pred["transients"]}.items(),
                   key=lambda kv: -kv[1])[:6]),
        "measured_peak_bytes": measured_peak,
        "measured_source": mem_monitor.source,
        "measured_vs_predicted": round(measured_peak / mem_expected, 3)
        if mem_expected else None,
        "top_residents": mem_monitor.top_residents(),
        "device_capacity_bytes": obs_memory.device_capacity(),
    }
    sys.stderr.write(f"bench: memory {json.dumps(memory_block)}\n")

    # deep-tree fixed-cost micro-rung (31 vs 255 leaves, <= 200k rows):
    # default on for the cpu rung, opt-in (BENCH_LEAVES_SWEEP=1) on tpu
    sweep_flag = os.environ.get("BENCH_LEAVES_SWEEP", "")
    leaves_sweep = None
    if sweep_flag != "0" and (platform == "cpu" or sweep_flag == "1"):
        try:
            leaves_sweep = _leaves_sweep(params, n_rows, n_feat, sparsity)
            sys.stderr.write(f"bench: leaves_sweep {json.dumps(leaves_sweep)}\n")
        except Exception as e:       # the micro-rung never kills the bench
            leaves_sweep = {"error": str(e)[:200]}

    # serving micro-rung (docs/SERVING.md): engine latency/QPS ladder +
    # zero-recompile replay on the freshly trained model.  Default on for
    # the cpu rung like the leaves sweep; BENCH_SERVING=1 forces on tpu
    serving_flag = os.environ.get("BENCH_SERVING", "")
    serving = None
    if serving_flag != "0" and (platform == "cpu" or serving_flag == "1"):
        try:
            serving = _serving_rung(booster, n_feat, sparsity)
            sys.stderr.write(f"bench: serving {json.dumps(serving)}\n")
        except Exception as e:       # the micro-rung never kills the bench
            serving = {"error": str(e)[:200]}

    # live-metrics view of the measured child (obs/metrics.py): the same
    # flat sample map a GET /metrics scrape would serve, embedded so
    # scripts/obs_diff.py can regression-diff two rungs at the metrics
    # level (decide_flips prints its coverage row)
    from lightgbm_tpu.obs import metrics as obs_metrics
    metrics_snapshot = obs_metrics.snapshot()

    trace_file = obs_trace.stop() if bench_trace else None
    telemetry = {
        "observed_kernel": observed,
        "hist_dispatch": obs_counters.get("hist_dispatch"),
        # split-find identity (round 8): which best-split scan the grower
        # actually traced — decide_flips refuses a split_find A/B whose
        # label disagrees with this
        "split_find_dispatch": split_find_counts,
        "layout_downgrades": obs_counters.events("layout_downgrade"),
    }
    if trace_file:
        telemetry["trace"] = trace_file
    kernel_mismatch = observed is not None and observed != resolved
    if kernel_mismatch:
        sys.stderr.write(f"bench: KERNEL IDENTITY MISMATCH — rung label "
                         f"{resolved}, telemetry observed {observed}\n")

    if "BENCH_BASELINE_TPS" in os.environ:
        # an externally measured baseline is tied to the shape it was
        # measured at (BENCH_BASELINE_ROWS, default: the requested
        # BENCH_ROWS) — rescale if this rung ran a capped shape
        base_rows = int(os.environ.get(
            "BENCH_BASELINE_ROWS", os.environ.get("BENCH_ROWS", 1_000_000)))
        baseline = float(os.environ["BENCH_BASELINE_TPS"]) \
            * (base_rows / n_rows)
    else:
        baseline = (BASELINE_TREES_PER_SEC_1M
                    * (1_000_000 / n_rows) * (28 / n_feat))
    result = {
        "metric": f"higgs-like {n_rows // 1000}k x{n_feat} binary GBDT "
                  f"training throughput, {params['num_leaves']} leaves, "
                  f"{params['max_bin']} bins ({platform}{kernel_tag}"
                  f"{f', sparsity={sparsity}' if sparsity else ''})",
        "value": round(trees_per_sec, 4),
        "unit": "trees/sec",
        "vs_baseline": round(trees_per_sec / baseline, 4),
        "link": link,
        "telemetry": telemetry,
        "memory": memory_block,
        "metrics_snapshot": metrics_snapshot,
        "model_quality": model_quality,
    }
    if device_profile is not None:
        result["device_profile"] = device_profile
        devprof_out = os.environ.get("BENCH_DEVPROF", "")
        if devprof_out:
            with open(devprof_out, "w") as f:
                json.dump(device_profile, f)
    if leaves_sweep is not None:
        result["leaves_sweep"] = leaves_sweep
    if serving is not None:
        result["serving"] = serving
    if kernel_mismatch:
        result["kernel_mismatch"] = True
        result["degraded"] = (f"kernel identity mismatch: rung label "
                              f"{resolved} but telemetry observed {observed}")
    print(json.dumps(result))


def _link_profile(jax):
    """Measure the host<->device link constants (RTT, pipelined dispatch,
    small device_get) so every bench number carries the line condition it
    was measured under."""
    import numpy as np
    try:
        f = jax.jit(lambda x: x + 1)
        x = f(np.float32(0))            # compile
        jax.block_until_ready(x)
        t0 = time.perf_counter()
        for _ in range(10):
            jax.block_until_ready(f(x))
        rtt_ms = (time.perf_counter() - t0) / 10 * 1e3
        t0 = time.perf_counter()
        y = x
        for _ in range(100):
            y = f(y)
        jax.block_until_ready(y)
        dispatch_ms = (time.perf_counter() - t0) / 100 * 1e3
        big = jax.device_put(np.zeros((1 << 18,), np.float32))  # 1 MB
        jax.block_until_ready(big)
        t0 = time.perf_counter()
        np.asarray(big)
        get_ms = (time.perf_counter() - t0) * 1e3
        return {"rtt_ms": round(rtt_ms, 3),
                "dispatch_ms": round(dispatch_ms, 3),
                "get_1mb_ms": round(get_ms, 3)}
    except Exception as e:              # never let diagnostics kill the bench
        return {"error": str(e)[:120]}


_NOISE_MARKERS = (
    # the LLVM cpu-feature dump (one multi-thousand-char line; BENCH_r05
    # banked it as the entire scheduled-run tail)
    "vs host machine features",
    "This could lead to execution errors",
)
_MAX_STDERR_LINE = 400


def _clean_stderr(err: str, limit: int = 4000) -> str:
    """Bound child stderr before passthrough: the scheduled driver banks
    only the LAST 2000 chars of output, so one unbounded diagnostic line
    can evict every real signal.  Known-noise lines are dropped (with a
    stub naming what was dropped), any line is capped, the total bounded."""
    lines = []
    for ln in (err or "").splitlines():
        if any(m in ln for m in _NOISE_MARKERS):
            lines.append(f"[{len(ln)}-char diagnostic dropped: "
                         f"{ln[:80]}...]")
            continue
        if len(ln) > _MAX_STDERR_LINE:
            ln = (ln[:_MAX_STDERR_LINE]
                  + f" ...[{len(ln) - _MAX_STDERR_LINE} chars truncated]")
        lines.append(ln)
    out = "\n".join(lines)
    return out[-limit:]


def _run_child(platform: str, mode: str, timeout_s: int):
    """Run the one measured child; returns its parsed result dict, or a
    bounded error string when it printed none."""
    env = dict(os.environ)
    env["BENCH_CHILD"] = "1"
    env["BENCH_CHILD_PLATFORM"] = platform
    env["BENCH_CHILD_MODE"] = mode
    label = f"{platform}+{mode}"
    try:
        r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           capture_output=True, text=True, timeout=timeout_s,
                           env=env)
    except subprocess.TimeoutExpired as e:
        tail = ""
        if e.stderr:
            err = e.stderr if isinstance(e.stderr, str) else e.stderr.decode(
                "utf-8", "replace")
            sys.stderr.write(_clean_stderr(err))
            tail = " last stderr: " + _clean_stderr(err.strip(), 200) \
                .replace("\n", " | ")
        return f"{label}: timeout {timeout_s}s{tail}"
    sys.stderr.write(_clean_stderr(r.stderr))
    if r.returncode == 0:
        for line in reversed(r.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except json.JSONDecodeError:
                    break
    tail = _clean_stderr((r.stderr or r.stdout).strip(), 300) \
        .replace("\n", " | ")
    return f"{label}: rc={r.returncode} {tail}"


def main():
    if os.environ.get("BENCH_CHILD") == "1":
        child_main()
        return
    timeout_s = int(os.environ.get("BENCH_STAGE_TIMEOUT", 3600))
    if os.environ.get("BENCH_MESH") == "1":
        # forced 8-logical-device HOST mesh: GSPMD-vs-shardmap A/B +
        # compiled-HLO collective census, labelled cpu in its own metric
        platform, mode = "cpu", "mesh"
    elif os.environ.get("BENCH_STREAMED") == "1":
        # resident-vs-chunked out-of-core A/B over an artificial
        # hbm_budget, a host-pipeline rung labelled cpu in its own metric
        platform, mode = "cpu", "streamed"
    elif os.environ.get("BENCH_PLATFORM") == "cpu":
        platform, mode = "cpu", "segment"
    else:
        platform = "tpu"
        mode = "einsum" if os.environ.get("BENCH_FUSED") == "0" else "fused"
    res = _run_child(platform, mode, timeout_s)
    if not isinstance(res, dict):
        sys.stderr.write(f"\nbench: {res}\n")
        sys.exit(1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
