"""Single-chip perf sweep + phase breakdown (run on the TPU host).

Produces the evidence behind docs/PERF.md: per-phase timing of the bench
workload, a tile-size sweep for the Pallas histogram kernel (the analogue of
the reference's GPU workgroup tuning, gpu_tree_learner.cpp:103-121), and an
optional device-time attribution capture (obs/devprof.py — one capture
path for the whole repo; the raw profiler artifacts land in trace_dir and
the attributed per-phase summary in trace_dir/devprof.json).

    python scripts/tpu_profile.py [rows] [trace_dir]
"""
import os
import sys
import time

from lightgbm_tpu.utils.cache import enable_persistent_cache  # noqa: E402
enable_persistent_cache()


def make_data(n, f=28, seed=42):
    sys.path.insert(0, ".")
    from bench import make_data as bench_make
    return bench_make(n, f)



def train_tps(X, y, n_timed=10, **extra_params):
    import jax
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.data.dataset import construct
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.utils import log as _log
    _log.set_verbosity(-1)

    params = dict(objective="binary", num_leaves=255, max_bin=255,
                  min_data_in_leaf=1, min_sum_hessian_in_leaf=100,
                  learning_rate=0.1, verbose=-1, use_pallas=True)
    params.update(extra_params)
    cfg = config_from_params(params)
    # the sweep varies only kernel/grower knobs — the binned dataset is
    # identical across configs, so reuse bench.py's DISK-cached
    # construction (a relaunched profile run skips binning entirely).  A sweep over binning-relevant knobs
    # must bypass the cache — its key does not cover them.
    binning_knobs = {"min_data_in_bin", "bin_construct_sample_cnt",
                     "data_random_seed", "enable_bundle",
                     "max_conflict_rate", "use_missing", "zero_as_missing"}
    if binning_knobs & set(extra_params):
        ds = construct(X, cfg, label=y)
    else:
        from bench import _construct_cached
        ds = _construct_cached(lambda: (X, y), cfg, X.shape[0], X.shape[1],
                               0.0, params)
    bst = create_boosting(cfg, ds, create_objective(cfg))
    t0 = time.perf_counter()
    bst.train_one_iter()
    jax.block_until_ready(bst.scores)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n_timed):
        bst.train_one_iter()
    jax.block_until_ready(bst.scores)
    dt = time.perf_counter() - t0
    return n_timed / dt, compile_s, bst


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    trace_dir = sys.argv[2] if len(sys.argv) > 2 else None
    import jax
    print("platform:", jax.devices()[0].platform, flush=True)
    X, y = make_data(rows)

    # --- baseline config + phase breakdown -----------------------------------
    tps, comp, bst = train_tps(X, y)
    print(f"\nbaseline (rt=512, bmin=10): {tps:.3f} trees/s "
          f"(compile {comp:.0f}s)")
    print("phases:", bst.timers.report(), flush=True)

    # --- tile sweep ----------------------------------------------------------
    # the fused kernel's only tiling knob is the row tile (feature tiling
    # died with the retired gen-1 kernels)
    print("\ntile sweep (trees/s):")
    for rt in (256, 512, 1024, 2048):
        try:
            tps_i, comp_i, _ = train_tps(X, y, n_timed=5,
                                         pallas_row_tile=rt)
            print(f"  row_tile={rt:5d}: {tps_i:7.3f} "
                  f"(compile {comp_i:.0f}s)", flush=True)
        except Exception as e:
            print(f"  row_tile={rt:5d}: FAILED "
                  f"{str(e)[:120]}", flush=True)

    # --- gather bucket sweep -------------------------------------------------
    print("\nbucket_min_log2 sweep (trees/s):")
    for bmin in (8, 10, 12, 14):
        tps_i, comp_i, _ = train_tps(X, y, n_timed=5,
                                     pallas_bucket_min_log2=bmin)
        print(f"  bmin={bmin:2d}: {tps_i:7.3f} (compile {comp_i:.0f}s)",
              flush=True)

    if trace_dir:
        # the devprof plane owns profiler start/stop now (one capture path
        # with bench.py / engine.train): armed before a short training, it
        # skips the compile firing, captures per-iteration windows into
        # trace_dir, and attributes device op time to the named_scope
        # phase twins.  Telemetry spans must be live for the host phase
        # windows to reach the capture.
        import json as _json
        from lightgbm_tpu.obs import devprof as obs_devprof
        from lightgbm_tpu.obs import trace as obs_trace
        obs_trace.start(None)
        obs_devprof.start(log_dir=trace_dir, profile_iters=2,
                          keep_artifacts=True)
        try:
            tps_i, _, _ = train_tps(X, y, n_timed=2)
        finally:
            summary = obs_devprof.stop()
            obs_trace.stop()
        if summary is not None:
            out = os.path.join(trace_dir, "devprof.json")
            with open(out, "w") as f:
                _json.dump(summary, f, indent=1)
            print("device-time attribution:",
                  _json.dumps({k: summary[k] for k in
                               ("captured_iterations", "attributed_fraction",
                                "phase_device_ms")}))
            print("trace written to", trace_dir, "— summary", out)


if __name__ == "__main__":
    main()
