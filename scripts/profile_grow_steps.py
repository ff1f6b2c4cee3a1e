"""Per-step cost profiler for the jitted grow loop (CPU tier).

The round-6 verdict's top lever: at a fixed row count, per-tree time keeps
growing with the leaf count, i.e. a large per-split cost is FIXED — paid by
loop-body constants (carried-state copies, op launches, min-bucket padding)
rather than by the rows the split touches.  This script produces the three
pieces of evidence that localize it:

  1. **step-index → ms curve**: one grower compiled with a traced
     ``max_steps`` cap (``make_grower(..., step_limit=True)``) is timed at
     increasing caps; the difference quotient is the marginal cost of the
     k-th split.  Early splits touch big windows (row-proportional cost),
     the tail of the curve IS the per-split fixed cost.
  2. **leaves sweep**: whole trees at 31/63/127/255 leaves, the marginal
     ms/leaf between consecutive sizes — the same quantity bench.py's
     ``leaves_sweep`` rung tracks per round.
  3. **loop-body jaxpr audit** (utils/jaxpr_audit.py): every op whose
     operand is O(N) or O(L·F·B) per step, the structural cause of 1-2.
  4. **compiled-executable memory analysis** (obs/memory.py): the jitted
     grower's and the binned-predict executable's argument/output/temp
     bytes from ``compiled.memory_analysis()``, next to the analytic
     ``predict_hbm`` transient model — the numbers the
     tests/test_grow_jaxpr.py byte-budget ratchet pins at its own shape.

Results land in the obs counter registry as gauges (so a surrounding
telemetry trace embeds them) and as ONE json line on stdout.

Usage:
  python scripts/profile_grow_steps.py [rows] [--leaves 255]
      [--sweep 31,63,127,255] [--features 28] [--max-bin 255]
      [--stride 16] [--hist-method segment]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightgbm_tpu.utils.cache import enable_persistent_cache  # noqa: E402
enable_persistent_cache()


def make_problem(n, f, b, seed=42):
    import numpy as np
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, b, size=(n, f)).astype(
        np.uint8 if b <= 256 else np.int32)
    g = rng.randn(n).astype(np.float32)
    h = (np.abs(rng.randn(n)) + 0.1).astype(np.float32)
    c = np.ones(n, np.float32)
    return bins, g, h, c


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("rows", nargs="?", type=int, default=200_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--max-bin", type=int, default=255)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--sweep", default="31,63,127,255")
    ap.add_argument("--stride", type=int, default=16,
                    help="step-curve sampling stride")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--hist-method", default="segment",
                    help="segment (CPU default) | einsum | fused | pallas")
    ap.add_argument("--bucket-min-log2", type=int, default=None,
                    help="override cfg.bucket_min_log2 (floor A/B)")
    ap.add_argument("--split-find", default="fused",
                    help="best-split scan: fused (default) | chain "
                         "(forced round-7 baseline)")
    ap.add_argument("--has-missing", action="store_true",
                    help="trace the two-direction scan (missing values)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower
    from lightgbm_tpu.obs.counters import counters as obs_counters
    from lightgbm_tpu.utils.jaxpr_audit import audit_loop_body

    n, f, b = args.rows, args.features, args.max_bin
    bins, g, h, c = make_problem(n, f, b)
    meta = FeatureMeta(
        num_bin=jnp.full((f,), b, jnp.int32),
        missing_type=jnp.zeros((f,), jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.zeros((f,), bool))
    dev = (jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
           meta, jnp.ones((f,), bool))

    def cfg_for(leaves):
        kw = {}
        if args.bucket_min_log2 is not None:
            kw["bucket_min_log2"] = args.bucket_min_log2
        return GrowerConfig(num_leaves=leaves, min_data_in_leaf=1,
                            min_sum_hessian_in_leaf=100.0, max_bin=b,
                            hist_method=args.hist_method,
                            split_find=args.split_find,
                            has_missing=args.has_missing,
                            hist_interpret=args.hist_method == "fused"
                            and jax.devices()[0].platform != "tpu", **kw)

    def timed(fn, *a, reps=args.reps):
        out = fn(*a)
        jax.block_until_ready(out)          # warm (compile)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn(*a)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best, out

    result = {"rows": n, "features": f, "max_bin": b,
              "hist_method": args.hist_method,
              "platform": jax.devices()[0].platform}

    # ---- 1. step-index -> ms curve ------------------------------------
    L = args.leaves
    grow_lim = jax.jit(make_grower(cfg_for(L), step_limit=True))
    caps = sorted({0, 1, 2, 4, 8,
                   *range(args.stride, L - 1, args.stride), L - 1})
    sys.stderr.write(f"step curve: L={L}, {len(caps)} caps\n")
    times = {}
    for k in caps:
        dt, _ = timed(grow_lim, jnp.asarray(k, jnp.int32), *dev)
        times[k] = dt
    curve = []
    for k0, k1 in zip(caps, caps[1:]):
        curve.append({"steps": [k0, k1],
                      "ms_per_step": round((times[k1] - times[k0])
                                           / (k1 - k0) * 1e3, 3)})
    result["step_curve"] = curve
    tail = [p["ms_per_step"] for p in curve[len(curve) // 2:]]
    tail_ms = sorted(tail)[len(tail) // 2] if tail else 0.0
    result["tail_ms_per_step"] = round(tail_ms, 3)
    obs_counters.gauge("grow_step_tail_ms", tail_ms)
    for p in curve:
        sys.stderr.write(f"  steps {p['steps'][0]:4d}-{p['steps'][1]:4d}: "
                         f"{p['ms_per_step']:8.3f} ms/step\n")

    # ---- 2. leaves sweep ----------------------------------------------
    sweep = sorted(int(x) for x in args.sweep.split(","))
    per_tree = {}
    for leaves in sweep:
        grow = jax.jit(make_grower(cfg_for(leaves)))
        dt, out = timed(grow, *dev)
        per_tree[leaves] = dt
        sys.stderr.write(f"leaves={leaves:4d}: {dt * 1e3:9.1f} ms/tree "
                         f"(grown {int(out[0].num_leaves)})\n")
    marginal = []
    for l0, l1 in zip(sweep, sweep[1:]):
        marginal.append({"leaves": [l0, l1],
                         "ms_per_leaf": round(
                             (per_tree[l1] - per_tree[l0]) / (l1 - l0) * 1e3,
                             3)})
    result["leaves_sweep"] = {
        "per_tree_ms": {str(k): round(v * 1e3, 1)
                        for k, v in per_tree.items()},
        "marginal": marginal}
    if len(sweep) >= 2:
        lo, hi = sweep[0], sweep[-1]
        mlh = (per_tree[hi] - per_tree[lo]) / (hi - lo) * 1e3
        result["marginal_ms_per_leaf"] = round(mlh, 3)
        obs_counters.gauge("leaves_sweep_marginal_ms_per_leaf", mlh)
        sys.stderr.write(f"marginal {lo}->{hi}: {mlh:.3f} ms/leaf\n")

    # ---- 3. loop-body jaxpr audit -------------------------------------
    from lightgbm_tpu.utils.jaxpr_audit import find_while_body
    jaxpr = jax.make_jaxpr(make_grower(cfg_for(L)))(*dev)
    big = audit_loop_body(jaxpr, min_elems=min(n, b * f * L))
    inventory = [{"prim": r["prim"],
                  "shapes": [list(s) for s in r["shapes"]],
                  "elems": r["elems"]} for r in big]
    result["loop_body_big_ops"] = inventory
    sys.stderr.write("loop-body ops with O(N) / O(L*F*B) operands:\n")
    for r in inventory:
        sys.stderr.write(f"  {r['prim']:24s} {r['shapes']}\n")
    body = find_while_body(jaxpr)
    result["loop_body_eqns"] = len(body.eqns)
    obs_counters.gauge("grow_body_eqns", len(body.eqns))
    sys.stderr.write(f"loop-body top-level eqns: {len(body.eqns)} "
                     f"(split_find={args.split_find})\n")

    # ---- 3b. split-find chain inventory (round-8 evidence artifact) ----
    # op count + bytes materialized by the best-split scan alone, at the
    # in-loop shape (the vmapped pair of children), chain vs fused — the
    # before/after decomposition docs/PERF.md round 8 cites
    from lightgbm_tpu.ops.split import SplitConfig, best_split

    def find_inventory(impl):
        scfg = SplitConfig(min_data_in_leaf=1,
                           min_sum_hessian_in_leaf=100.0,
                           has_missing=args.has_missing, split_find=impl)
        num_bin = jnp.full((f,), b, jnp.int32)
        zeros = jnp.zeros((f,), jnp.int32)
        fv = jnp.ones((f,), bool)

        def pair_find(h2, pg, ph, pc):
            return jax.vmap(lambda hh, a, b_, c_: best_split(
                hh, a, b_, c_, num_bin, zeros, zeros, fv, scfg,
                with_feat_ok=True))(h2, pg, ph, pc)

        h2 = jax.ShapeDtypeStruct((2, f, b, 3), jnp.float32)
        s2 = jax.ShapeDtypeStruct((2,), jnp.float32)
        jx = jax.make_jaxpr(pair_find)(h2, s2, s2, s2)

        def walk(jaxpr):
            eqns, bytes_ = 0, 0
            for e in jaxpr.eqns:
                eqns += 1
                for v in e.outvars:
                    aval = getattr(v, "aval", None)
                    if aval is not None and getattr(aval, "shape", None) \
                            is not None:
                        sz = 1
                        for d in aval.shape:
                            sz *= int(d)
                        bytes_ += sz * aval.dtype.itemsize
                for val in e.params.values():
                    vals = val if isinstance(val, (list, tuple)) else [val]
                    for s in vals:
                        sub = getattr(s, "jaxpr", None)
                        if sub is not None and hasattr(sub, "eqns"):
                            se, sb = walk(sub)
                            eqns += se
                            bytes_ += sb
            return eqns, bytes_

        eqns, bytes_ = walk(jx.jaxpr)
        return {"eqns": eqns, "bytes_materialized": bytes_}

    result["split_find"] = {impl: find_inventory(impl)
                            for impl in ("chain", "fused")}
    for impl, inv in result["split_find"].items():
        obs_counters.gauge(f"split_find_{impl}_eqns", inv["eqns"])
        sys.stderr.write(
            f"split-find[{impl}]: {inv['eqns']} eqns, "
            f"{inv['bytes_materialized'] / 1e6:.2f} MB materialized per "
            f"pair-find\n")

    # ---- 4. compiled-executable memory analysis -----------------------
    from lightgbm_tpu.obs import memory as obs_memory
    grow_mem = obs_memory.analyze_jitted(make_grower(cfg_for(L)), *dev,
                                         label="grow")
    result["grow_memory"] = grow_mem
    if grow_mem:
        sys.stderr.write(
            f"grow executable: args {grow_mem['argument_bytes'] / 1e6:.2f} "
            f"MB, temp {grow_mem['temp_bytes'] / 1e6:.2f} MB, peak "
            f"{grow_mem['peak_bytes'] / 1e6:.2f} MB\n")
    from lightgbm_tpu.predictor import predict_binned_leaf
    P = L - 1
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    pred_mem = obs_memory.analyze_jitted(
        predict_binned_leaf,           # nested jit collapses in lowering
        jax.ShapeDtypeStruct((n, f), dev[0].dtype),
        i32(P), i32(P), jax.ShapeDtypeStruct((P,), jnp.bool_),
        i32(P), i32(P), i32(f, 5),
        jax.ShapeDtypeStruct((P,), jnp.bool_),
        jax.ShapeDtypeStruct((P, b), jnp.bool_),
        label="predict")
    result["predict_memory"] = pred_mem
    if pred_mem:
        sys.stderr.write(
            f"predict executable: temp {pred_mem['temp_bytes'] / 1e6:.2f} "
            f"MB, peak {pred_mem['peak_bytes'] / 1e6:.2f} MB\n")
    model = obs_memory.predict_hbm(rows=n, features=f, bins=b, leaves=L)
    result["predict_hbm"] = {"transient_bytes": model["transient_bytes"],
                             "peak_bytes": model["peak_bytes"]}
    sys.stderr.write(
        f"analytic model: transients {model['transient_bytes'] / 1e6:.2f} "
        f"MB, peak {model['peak_bytes'] / 1e6:.2f} MB\n")

    print(json.dumps(result))


if __name__ == "__main__":
    main()
