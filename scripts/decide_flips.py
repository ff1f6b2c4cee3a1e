"""Read a directory of bench artifacts and print the default-flip decision
table.

Mechanizes the PERF.md playbook: each A/B artifact is compared against its
matched baseline (the 1M headline, except the sparse packing A/B which is
judged against bench_sparse.json), flagged WIN/LOSE/NOISE with the >=5%
criterion.  Decisions require clean TPU numbers on BOTH sides — degraded
or CPU-fallback artifacts never decide a TPU default, and an artifact
whose telemetry-observed kernel identity (bench.py's "telemetry" block,
the lightgbm_tpu.obs dispatch counters) disagrees with its rung label is
rejected the same way: a tpu+fused rung that actually ran einsum must
never decide anything.  A stage that died (timeout, lost machine) leaves a
structured ``probe_failed`` artifact instead of an empty file — rendered
here as a FAILED row, never mistaken for "not captured".  Decisions still
land as code edits (boosting.py auto-resolution block) — this script only
reads.

Usage: python scripts/decide_flips.py <dir of bench artifacts>/
"""
import importlib.util
import json
import os
import sys


_OBS_DIFF = None


def _load_obs_diff():
    """scripts/ is not a package; load the sibling regression differ by
    path (the tests' _load_script idiom), once."""
    global _OBS_DIFF
    if _OBS_DIFF is None:
        p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "obs_diff.py")
        spec = importlib.util.spec_from_file_location("obs_diff", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _OBS_DIFF = mod
    return _OBS_DIFF

# (artifact, knob, action, baseline_artifact or None=headline)
FLIPS = [
    # INVERTED pair: the headline bench_1m.json is the tpu+fused number
    # (the default ladder tries fused first), so this artifact is the
    # forced-XLA side — LOSE here means the fused kernel won and stays
    # what use_pallas runs on the chip
    ("bench_1m_xla.json", "BENCH_FUSED=0 (XLA einsum rung forced)",
     "if this LOSES >=5% to the headline, the fused kernel stays the "
     "TPU default (use_pallas=true)", None),
    ("bench_sparse_nopack.json", "enable_bin_packing=false",
     "flip packing default off on TPU if OFF wins",
     "bench_sparse.json"),
    # INVERTED pair like the gen-1 one: bench_leaves_fused.json carries the
    # default (split_find=fused), the chain artifact is the forced
    # baseline — LOSE here means the fused split-find won on-chip and the
    # default stands; a WIN >= 5% means the chain must come back on TPU
    ("bench_leaves_chain.json", "split_find=chain (forced baseline)",
     "if this WINS >=5% over bench_leaves_fused.json, flip split_find "
     "fused->chain on TPU (config.py) — otherwise the fused scan stands",
     "bench_leaves_fused.json"),
]
COVERAGE = ["bench_1m_63bin.json", "bench_higgs_full.json",
            "bench_wide.json", "bench_sparse.json", "bench_leaves.json",
            "bench_leaves_fused.json", "bench_serving.json",
            "bench_mesh.json", "bench_mesh_fused.json",
            "bench_streamed.json"]
# scripts/obs_diff.py thresholds for the in-pair drift annotations (the
# same defaults the CLI uses)
_DIFF_THRESHOLDS = {"throughput_pct": 10.0, "latency_pct": 25.0,
                    "p99_pct": 25.0, "memory_pct": 20.0}


def load(path):
    try:
        with open(path) as f:
            for line in reversed(f.read().strip().splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    return json.loads(line)
    except (OSError, json.JSONDecodeError):
        return None
    return None


def platform(d):
    m = d.get("metric", "")
    return "tpu" if "(tpu" in m else "cpu" if "(cpu" in m else "?"


def label_kernel(d):
    """Kernel named by the rung LABEL (the metric string)."""
    m = d.get("metric", "")
    for k in ("fused", "pallas"):
        if f", {k}" in m:
            return k
    return None


def observed_kernel(d):
    """Kernel identity the bench child's telemetry actually observed
    (lightgbm_tpu.obs dispatch counters), when the artifact carries it."""
    return (d.get("telemetry") or {}).get("observed_kernel")


def clean_tpu(d):
    """Only an undegraded on-chip number whose telemetry-observed kernel
    identity agrees with its label may decide a TPU default."""
    if (d is None or platform(d) != "tpu" or "degraded" in d
            or d.get("kernel_mismatch") or d.get("value", 0) <= 0):
        return False
    obs, lab = observed_kernel(d), label_kernel(d)
    # telemetry-era artifacts must agree with their label; pre-telemetry
    # artifacts (no "telemetry" block) keep deciding as before
    return obs is None or lab is None or obs == lab


def memory_row(d):
    """One-line device-memory coverage summary of an artifact's "memory"
    block (bench.py embeds predicted + measured peak bytes in every rung
    JSON; obs/memory.py is the producer).  None when the artifact
    predates the memory block."""
    m = d.get("memory")
    if not isinstance(m, dict):
        return None
    pred = m.get("predicted_peak_bytes", 0)
    meas = m.get("measured_peak_bytes", 0)
    ratio = m.get("measured_vs_predicted")
    cap_b = m.get("device_capacity_bytes")
    return (f"memory: predicted peak {pred / 1e9:.3f} GB, measured "
            f"{meas / 1e9:.3f} GB ({m.get('measured_source')}"
            f"{f', x{ratio} of model' if ratio is not None else ''}"
            f"{f', capacity {cap_b / 1e9:.1f} GB' if cap_b else ''})")


def metrics_row(d):
    """One-line coverage summary of an artifact's "metrics_snapshot"
    block (the live /metrics sample map bench.py embeds next to
    telemetry/memory; obs/metrics.py is the producer).  None when the
    artifact predates the live telemetry plane."""
    m = d.get("metrics_snapshot")
    if not isinstance(m, dict):
        return None
    return (f"metrics: {len(m.get('samples', {}))} live samples "
            f"(schema v{m.get('schema_version')})")


def model_quality_row(d):
    """One-line model-quality coverage summary of an artifact's
    "model_quality" block (the obs/model_quality.py tracker summary
    bench.py embeds next to metrics_snapshot: per-feature cumulative
    gain, gain-decay curve).  None when the artifact predates the
    model-quality plane."""
    mq = d.get("model_quality")
    if not isinstance(mq, dict):
        return None
    top = mq.get("top_features") or []
    head = ", ".join(f"{t.get('feature')}={t.get('gain'):.4g}"
                     for t in top[:3])
    curve = mq.get("gain_curve") or []
    decay = ""
    if len(curve) >= 2 and curve[0][1]:
        decay = f", gain decay x{curve[-1][1] / curve[0][1]:.3f}"
    return (f"model_quality: {mq.get('trees_seen')} tree(s) audited"
            f"{f', top gain: {head}' if head else ''}{decay}")


def devprof_row(d):
    """One-line device-time coverage summary of an artifact's
    "device_profile" block (obs/devprof.py: programmatic profiler windows
    attributed to the named_scope phase twins) — the row that explains
    WHY a rung wins, not just that it does.  None when the artifact
    predates the attribution plane."""
    dp = d.get("device_profile")
    if not isinstance(dp, dict):
        return None
    phases = dp.get("phase_device_ms") or {}
    top = ", ".join(f"{p}={ms:g}ms" for p, ms in list(phases.items())[:3])
    frac = dp.get("attributed_fraction")
    gaps = [it.get("idle_gap_fraction") for it in dp.get("iterations", [])
            if isinstance(it.get("idle_gap_fraction"), (int, float))]
    gap_tag = f", idle gap ~{sum(gaps) / len(gaps):.0%}" if gaps else ""
    return (f"devprof: {dp.get('captured_iterations')} window(s), "
            f"{dp.get('total_op_ms')} ms device op time"
            f"{f' ({frac:.0%} attributed)' if frac is not None else ''}"
            f"{f': {top}' if top else ''}{gap_tag}")


def observed_split_find(d):
    """Dominant split_find identity the child's telemetry traced
    (bench.py embeds the grower's split_find_dispatch counter)."""
    counts = (d.get("telemetry") or {}).get("split_find_dispatch") or {}
    best, best_n = None, 0
    for key, n in counts.items():
        tags = dict(kv.split("=", 1) for kv in key.split(",") if "=" in kv)
        impl = tags.get("impl")
        if impl and n > best_n:
            best, best_n = impl, n
    return best


def serving_row(d):
    """One-line serving-rung summary of an artifact's "serving" block
    (bench.py `_serving_rung`, docs/SERVING.md): chosen backend, the
    batch-4096 latency/QPS, the speedup over the displaced
    Predictor.predict host loop, and whether the mixed-size replay held
    the predict_jit_entries gauge (zero recompiles)."""
    s = d.get("serving")
    if not isinstance(s, dict) or "error" in s:
        return None
    b4 = (s.get("buckets") or {}).get("4096", {})
    trav = f"/{s['traversal']}" if s.get("traversal") else ""
    return (f"serving[{s.get('backend')}{trav}]: 4096-row p50 "
            f"{b4.get('p50_ms')} ms / {b4.get('qps')} rows/s "
            f"({s.get('speedup_vs_predict_loop')}x the predict loop), "
            f"{s.get('predict_jit_entries')} jit entries, "
            f"replay recompiles={s.get('recompiles')}")


def mesh_rows(d):
    """Per-shape lines for the mesh rung A/Bs (bench.py BENCH_MESH=1,
    docs/DISTRIBUTED.md): trees/s per sharding with the telemetry
    kernel identity, the planner's chosen mesh, the in-pair ratios, any
    loud layout downgrades, and the compiled-HLO collective census of
    the GSPMD executable.  Covers both the shard_map-vs-GSPMD rung
    (bench_mesh.json) and the gspmd_hist fused-vs-flat rung
    (BENCH_MESH_FUSED=1, bench_mesh_fused.json).  A host-mesh rung: it
    compares the collective FORMULATIONS, so the ratios are
    informational — on-TPU defaults await an on-chip pair.

    Capability note (ISSUE 18): these rungs run SINGLE-process (one host
    mesh over local devices).  The gspmd side now also serves real
    multi-process elastic groups — ``parallel_impl=auto`` resolves to
    gspmd across processes, and the supervisor re-plans its mesh on a
    shrink — but a multi-host on-chip A/B of that path is still an open
    rung; until it lands, these single-process numbers are the only
    mesh evidence and decide nothing about the multi-process default."""
    m = d.get("mesh")
    if not isinstance(m, dict):
        return []
    out = []
    for shape, cfgs in (m.get("shapes") or {}).items():
        parts, ratios, downs = [], [], []
        for name, rec in cfgs.items():
            if isinstance(rec, (int, float)):
                ratios.append(f"{name}={rec}")
                continue
            if not isinstance(rec, dict):
                continue
            if "error" in rec:
                parts.append(f"{name}=ERR")
                continue
            mesh_tag = f"@{rec['mesh']}" if rec.get("mesh") else ""
            kern = rec.get("observed_kernel")
            kern_tag = f"[{kern}]" if kern else ""
            parts.append(f"{name}{mesh_tag}{kern_tag}="
                         f"{rec.get('trees_per_sec')}")
            for ev in rec.get("downgrades") or []:
                downs.append(f"  {name} DOWNGRADE "
                             f"{ev.get('requested')}->{ev.get('resolved')}"
                             f": {ev.get('reason')}")
        out.append(f"mesh[{shape}]: " + ", ".join(parts + ratios))
        out.extend(downs)
        gd = (cfgs.get("gspmd_data") or cfgs.get("gspmd_fused_data")
              or cfgs.get("gspmd_fused_2x4") or {})
        cen = gd.get("collectives")
        if isinstance(cen, dict) and cen:
            ops = ", ".join(f"{op} {rec['count']}x/{rec['bytes']}B"
                            for op, rec in sorted(cen.items()))
            out.append(f"  gspmd collectives (compiled HLO): {ops}")
    if m.get("fused_ab"):
        out.append("  gspmd_hist flip: fused_vs_flat_* >= 1.05 with "
                   "observed_kernel agreeing per side -> gspmd_hist "
                   "auto->fused (boosting._setup_gspmd); host-mesh "
                   "numbers are informational, the on-chip pair decides")
    return out


def streamed_rows(d):
    """Lines for the streamed rung A/B (bench.py BENCH_STREAMED=1): the
    resident-vs-chunked throughput pair under the artificial hbm_budget,
    the measured pipeline stall fraction, the chunk pipeline shape, and
    the zero-recompile pin.  A host rung: the chunked/resident ratio and
    stall fraction are the pipeline's overlap evidence (CPU's synchronous
    dispatch makes both conservative — on-chip DMA hides more of the
    copy); ``data_stream`` auto stays the default either way, the rung
    exists so the streamed regime's cost is a tracked number."""
    s = d.get("streamed")
    if not isinstance(s, dict):
        return []
    out = []
    parts = []
    for name in ("resident", "chunked"):
        rec = (s.get("configs") or {}).get(name)
        if not isinstance(rec, dict):
            continue
        if "error" in rec:
            parts.append(f"{name}=ERR")
            continue
        mode = (rec.get("placement") or {}).get("mode")
        parts.append(f"{name}{f'[{mode}]' if mode else ''}="
                     f"{rec.get('trees_per_sec')}")
    ratio = (s.get("configs") or {}).get("chunked_vs_resident")
    if ratio is not None:
        parts.append(f"chunked_vs_resident={ratio}")
    out.append(f"streamed[{s.get('rows')}x{s.get('features')}, budget "
               f"{s.get('hbm_budget')}B]: " + ", ".join(parts))
    ch = (s.get("configs") or {}).get("chunked") or {}
    if "stall_fraction" in ch:
        out.append(f"  chunk pipeline: {ch.get('blocks')} x "
                   f"{ch.get('chunk_rows')} rows, stall fraction "
                   f"{ch['stall_fraction']} "
                   f"({ch.get('stream_wait_ms_per_tree')} ms wait/tree, "
                   f"{ch.get('stalls')} stalls), jit entries "
                   f"{ch.get('grower_jit_entries')}"
                   f"{' ZERO-RECOMPILE' if ch.get('zero_recompile') else ' RECOMPILED'}")
    return out


def probe_failed_row(d):
    """Render a structured probe_failed artifact (a stage that timed out
    or died; the microprobe's SIGTERM flush) — distinct from "not
    captured"."""
    if not isinstance(d, dict) or d.get("kind") != "probe_failed":
        return None
    sig = f" [{d['signal']}]" if d.get("signal") else ""
    return (f"PROBE FAILED rc={d.get('rc')}{sig} at stage "
            f"'{d.get('stage')}' — see stderr_tail in the artifact")


def main():
    cap = sys.argv[1]
    head = load(os.path.join(cap, "bench_1m.json"))
    if not head:
        print("no headline bench in", cap)
        return
    hpf = probe_failed_row(head)
    if hpf:
        print(f"headline: {hpf}")
        print("headline stage died -> NO flip decisions from this capture")
        return
    deciding = clean_tpu(head)
    obs = observed_kernel(head)
    print(f"headline: {head['value']} trees/s ({platform(head)}"
          f"{' DEGRADED' if 'degraded' in head else ''}"
          f"{f', observed kernel {obs}' if obs else ''}) "
          f"vs_baseline={head.get('vs_baseline')} link={head.get('link')}")
    hm = memory_row(head)
    if hm:
        print(f"{'':10}{hm}")
    hs = serving_row(head)
    if hs:
        print(f"{'':10}{hs}")
    hx = metrics_row(head)
    if hx:
        print(f"{'':10}{hx}")
    hq = model_quality_row(head)
    if hq:
        print(f"{'':10}{hq}")
    hd = devprof_row(head)
    if hd:
        print(f"{'':10}{hd}")
    if not deciding:
        print("headline is not a clean TPU number -> NO flip decisions "
              "from this capture; table below is informational only")
    print()
    print(f"{'artifact':34} {'trees/s':>9} {'vs base':>8}  verdict / action")
    for fname in COVERAGE:
        d = load(os.path.join(cap, fname))
        if d is None:
            print(f"{fname:34} {'—':>9} {'—':>8}  (not captured)")
        elif probe_failed_row(d):
            print(f"{fname:34} {'—':>9} {'—':>8}  {probe_failed_row(d)}")
        else:
            print(f"{fname:34} {d['value']:>9} {'—':>8}  coverage shape, "
                  f"platform {platform(d)}, "
                  f"vs_baseline={d.get('vs_baseline')}"
                  f"{' DEGRADED' if 'degraded' in d else ''}")
            ls = d.get("leaves_sweep")
            if isinstance(ls, dict) and "marginal_ms_per_leaf" in ls:
                ab = (f", chain A/B {ls['chain_marginal_ms_per_leaf']}"
                      if "chain_marginal_ms_per_leaf" in ls else "")
                print(f"{'':53}deep-tree fixed cost: "
                      f"{ls['marginal_ms_per_leaf']} ms/leaf "
                      f"[{ls.get('split_find', 'fused')}]{ab} "
                      f"({ls['leaves'][0]} vs {ls['leaves'][1]} leaves at "
                      f"{ls['rows']} rows; round-7 CPU pre/post was "
                      f"11.5 -> ~3.4)")
            mr = memory_row(d)
            if mr:
                print(f"{'':53}{mr}")
            sr = serving_row(d)
            if sr:
                print(f"{'':53}{sr}")
            xr = metrics_row(d)
            if xr:
                print(f"{'':53}{xr}")
            qr = model_quality_row(d)
            if qr:
                print(f"{'':53}{qr}")
            dr = devprof_row(d)
            if dr:
                print(f"{'':53}{dr}")
            for line in mesh_rows(d):
                print(f"{'':53}{line}")
            for line in streamed_rows(d):
                print(f"{'':53}{line}")
    for fname, knob, action, base_name in FLIPS:
        d = load(os.path.join(cap, fname))
        if d is None:
            print(f"{fname:34} {'—':>9} {'—':>8}  (not captured)")
            continue
        if probe_failed_row(d):
            print(f"{fname:34} {'—':>9} {'—':>8}  {probe_failed_row(d)}: "
                  f"no decision ({knob})")
            continue
        base = head if base_name is None else load(
            os.path.join(cap, base_name))
        flags = " DEGRADED" if "degraded" in d else ""
        ok, lk = observed_kernel(d), label_kernel(d)
        if d.get("kernel_mismatch") or (ok and lk and ok != lk):
            flags += f" KERNEL-MISMATCH(label {lk}, observed {ok})"
        # the split-find A/B pair must each carry their advertised scan
        # identity (telemetry split_find_dispatch) or the pair decides
        # nothing — same honesty rule as the histogram-kernel label
        if fname.startswith("bench_leaves_"):
            want = "chain" if "chain" in fname else "fused"
            seen = observed_split_find(d)
            if seen is not None and seen != want:
                flags += f" SPLIT-FIND-MISMATCH(label {want}, observed " \
                         f"{seen})"
                print(f"{fname:34} {d['value']:>9} {'—':>8} {flags}: "
                      f"no decision ({knob})")
                continue
        if not deciding or not clean_tpu(d) or not clean_tpu(base):
            print(f"{fname:34} {d['value']:>9} {'—':>8}  "
                  f"platform {platform(d)}{flags}: not a clean TPU pair, "
                  f"no decision ({knob})")
            continue
        ratio = d["value"] / base["value"]
        verdict = ("WIN" if ratio >= 1.05
                   else "LOSE" if ratio <= 0.95 else "NOISE")
        print(f"{fname:34} {d['value']:>9} {ratio:>8.3f}  {verdict}: {knob}")
        if verdict == "WIN":
            print(f"{'':53}-> {action}")
        # non-throughput drift between the pair (memory peaks, serving
        # percentiles, identity flags) via the shared regression differ —
        # a WIN that doubled its p99 or HBM peak should not flip quietly
        diff = _load_obs_diff()
        for x in diff.compare_bench(base, d, _DIFF_THRESHOLDS):
            if x["check"] == "throughput" or x["severity"] == "info":
                continue
            print(f"{'':53}obs_diff {x['severity'].upper()} {x['check']}: "
                  f"{x['detail']}")
    mp = load(os.path.join(cap, "microprobe.json"))
    if mp:
        print()
        mpf = probe_failed_row(mp) or probe_failed_row(
            mp.get("probe_failed"))
        if mpf:
            # the SIGTERM flush banks partial numbers under the failure
            # marker; render the failure AND whatever was measured
            print(f"microprobe: {mpf}")
        print("microprobe decomposition:",
              {k: round(mp[k], 3) for k in
               ("grow_per_split_fixed_ms", "grow_per_mrow_ms", "grow_ms",
                "partition_sort_ms",
                "partition_window_opt_ms", "gather_panel_ms",
                "gather_words_plus3_ms") if k in mp})


if __name__ == "__main__":
    main()
