"""Automated regression diffing between two telemetry artifacts.

Before/after verdicts used to be eyeballed JSON; this script mechanizes
them for CI and ``decide_flips.py``:

    python scripts/obs_diff.py BASELINE CANDIDATE [options]

Both artifacts must be the same kind; the kind is sniffed from content:

* **bench JSON** (``bench.py`` output: ``{"metric", "value", ...}``) —
  throughput drop, kernel-identity / split-find-identity mismatches
  (telemetry blocks), memory-peak drift, serving p50/p99 drift per
  bucket, leaves-sweep marginal-ms/leaf drift;
* **trace** (``obs/trace.py`` JSON/JSONL) — per-phase STEADY-STATE mean
  deltas (the first, compile-inclusive firing of every host span is
  excluded, per the obs/report.py compile⚠ rule), observed-kernel
  mismatch from the embedded counter summaries;
* **metrics snapshot** — a ``.prom``/``.txt`` Prometheus scrape or the
  ``{"schema_version", "samples"}`` block ``obs/metrics.snapshot()``
  emits (bench JSONs embed one as ``metrics_snapshot``) — drift on
  latency/memory samples, dispatch-identity label-set mismatch;
* **probe_failed record** (``{"kind": "probe_failed", ...}``, e.g. the
  microprobe's SIGTERM flush when a stage dies) — sniffed on EITHER side: a failed candidate
  is a FAIL finding naming the dead stage and exit code, a failed
  baseline is a warn (nothing to compare against), never a load error.

Exit codes: 0 = within thresholds, 1 = regression (any FAIL finding),
2 = usage/load error.  ``--json`` prints the findings structurally.
Identity mismatches are always FAIL — a pair whose kernels differ
compares nothing (the decide_flips honesty rule).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

SCHEMA_VERSION = 1

# finding severities: fail flips the exit code, warn/info never do
FAIL, WARN, INFO = "fail", "warn", "info"


def _finding(check, severity, detail, a=None, b=None):
    out = {"check": check, "severity": severity, "detail": detail}
    if a is not None:
        out["baseline"] = a
    if b is not None:
        out["candidate"] = b
    return out


def _pct(a, b):
    """Relative change b vs a in percent (None when a is 0/absent)."""
    try:
        a, b = float(a), float(b)
    except (TypeError, ValueError):
        return None
    if a == 0:
        return None
    return (b - a) / abs(a) * 100.0


# ----------------------------------------------------------------- loading


def load_artifact(path):
    """(kind, data): kind in bench | trace | metrics."""
    if path.endswith((".prom", ".txt")):
        from lightgbm_tpu.obs.metrics import parse_prometheus
        with open(path) as f:
            return "metrics", parse_prometheus(f.read())
    if path.endswith(".jsonl"):
        from lightgbm_tpu.obs.report import load_events
        return "trace", load_events(path)
    with open(path) as f:
        text = f.read().strip()
    # bench stdout may carry log lines before the JSON (decide_flips rule:
    # the last '{'-line is the document)
    doc = None
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                doc = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    if doc is None:
        doc = json.loads(text)
    if isinstance(doc, list):
        return "trace", doc
    if doc.get("kind") == "probe_failed" or (
            isinstance(doc.get("probe_failed"), dict)):
        # a stage that died left a structured failure record (or the
        # microprobe's partial dict carrying one) in the artifact's place
        return "probe_failed", doc
    if "traceEvents" in doc:
        return "trace", list(doc["traceEvents"])
    if "samples" in doc:
        return "metrics", dict(doc["samples"])
    if "value" in doc and "metric" in doc:
        return "bench", doc
    raise ValueError(f"unrecognized artifact shape in {path}")


# ------------------------------------------------------------------- bench


def _observed_split_find(d):
    counts = (d.get("telemetry") or {}).get("split_find_dispatch") or {}
    best, best_n = None, 0
    for key, n in counts.items():
        tags = dict(kv.split("=", 1) for kv in key.split(",") if "=" in kv)
        impl = tags.get("impl")
        if impl and n > best_n:
            best, best_n = impl, n
    return best


def compare_bench(a, b, thresholds):
    f = []
    thr = thresholds["throughput_pct"]
    drop = _pct(a.get("value"), b.get("value"))
    if drop is not None and drop < -thr:
        f.append(_finding("throughput", FAIL,
                          f"trees/s dropped {-drop:.1f}% (> {thr}%)",
                          a.get("value"), b.get("value")))
    elif drop is not None:
        f.append(_finding("throughput", INFO,
                          f"trees/s changed {drop:+.1f}%",
                          a.get("value"), b.get("value")))
    ka = (a.get("telemetry") or {}).get("observed_kernel")
    kb = (b.get("telemetry") or {}).get("observed_kernel")
    if ka and kb and ka != kb:
        f.append(_finding("kernel_identity", FAIL,
                          "observed histogram kernel changed", ka, kb))
    sa, sb = _observed_split_find(a), _observed_split_find(b)
    if sa and sb and sa != sb:
        f.append(_finding("split_find_identity", FAIL,
                          "observed split-find impl changed", sa, sb))
    for flag in ("kernel_mismatch", "degraded"):
        if b.get(flag) and not a.get(flag):
            f.append(_finding(flag, FAIL,
                              f"candidate is {flag} and baseline is not",
                              None, str(b.get(flag))[:120]))
    ma = (a.get("memory") or {}).get("measured_peak_bytes")
    mb = (b.get("memory") or {}).get("measured_peak_bytes")
    g = _pct(ma, mb)
    if g is not None and g > thresholds["memory_pct"]:
        f.append(_finding("memory_peak", FAIL,
                          f"measured peak grew {g:.1f}% "
                          f"(> {thresholds['memory_pct']}%)", ma, mb))
    buckets_a = ((a.get("serving") or {}).get("buckets") or {})
    buckets_b = ((b.get("serving") or {}).get("buckets") or {})
    for bucket in sorted(set(buckets_a) & set(buckets_b), key=int):
        for q, thr_key in (("p50_ms", "latency_pct"),
                           ("p99_ms", "p99_pct")):
            g = _pct(buckets_a[bucket].get(q), buckets_b[bucket].get(q))
            if g is not None and g > thresholds[thr_key]:
                f.append(_finding(
                    f"serving_{q}", FAIL,
                    f"bucket {bucket} {q} grew {g:.1f}% "
                    f"(> {thresholds[thr_key]}%)",
                    buckets_a[bucket].get(q), buckets_b[bucket].get(q)))
    la = (a.get("leaves_sweep") or {}).get("marginal_ms_per_leaf")
    lb = (b.get("leaves_sweep") or {}).get("marginal_ms_per_leaf")
    g = _pct(la, lb)
    if g is not None and g > thresholds["throughput_pct"]:
        f.append(_finding("marginal_ms_per_leaf", FAIL,
                          f"deep-tree marginal cost grew {g:.1f}%", la, lb))
    # model-quality block (obs/model_quality.py tracker summary embedded
    # by bench.py): a changed top-gain feature at the SAME config is a
    # learned-model shift, not an infra regression — warn, never fail
    mqa = ((a.get("model_quality") or {}).get("top_features") or [])
    mqb = ((b.get("model_quality") or {}).get("top_features") or [])
    if mqa and mqb:
        fa, fb = mqa[0].get("feature"), mqb[0].get("feature")
        if fa != fb:
            f.append(_finding("importance_flip", WARN,
                              "top-gain feature changed", fa, fb))
        else:
            g = _pct(mqa[0].get("gain"), mqb[0].get("gain"))
            if g is not None:
                f.append(_finding("importance_top_gain", INFO,
                                  f"top feature `{fa}` gain {g:+.1f}%",
                                  mqa[0].get("gain"), mqb[0].get("gain")))
    return f


# ------------------------------------------------------------------- trace


def _phase_steady(events):
    from lightgbm_tpu.obs.report import phase_table
    return {r["span"]: r["steady_mean_ms"]
            for r in phase_table(events, steady=True)}


def _trace_kernel(events):
    from lightgbm_tpu.obs.report import observed_kernel, summary_payload
    snap = summary_payload(events, "counters") or {}
    return observed_kernel(snap.get("counters", {}))


def compare_trace(a, b, thresholds):
    f = []
    ka, kb = _trace_kernel(a), _trace_kernel(b)
    if ka and kb and ka != kb:
        f.append(_finding("kernel_identity", FAIL,
                          "observed histogram kernel changed", ka, kb))
    pa, pb = _phase_steady(a), _phase_steady(b)
    thr = thresholds["throughput_pct"]
    for span in sorted(set(pa) & set(pb)):
        g = _pct(pa[span], pb[span])
        if g is None:
            continue
        # sub-millisecond spans drown in scheduler noise — report, don't
        # fail (compile time is already excluded via the steady mean)
        sev = FAIL if g > thr and pa[span] >= 1.0 else \
            WARN if g > thr else INFO
        if g > thr or sev == INFO and abs(g) > thr:
            f.append(_finding(
                f"phase:{span}", sev,
                f"steady-state mean {g:+.1f}% "
                f"({pa[span]:.3f} -> {pb[span]:.3f} ms)",
                round(pa[span], 3), round(pb[span], 3)))
    return f


# ----------------------------------------------------------------- metrics


def compare_metrics(a, b, thresholds):
    f = []
    da = {k for k in a if k.startswith("lgbm_tpu_hist_dispatch_total")}
    db = {k for k in b if k.startswith("lgbm_tpu_hist_dispatch_total")}
    if da and db and da != db:
        f.append(_finding("dispatch_identity", FAIL,
                          "hist_dispatch label sets differ",
                          sorted(da - db), sorted(db - da)))
    watch = (("_p99_ms", thresholds["p99_pct"]),
             ("_p50_ms", thresholds["latency_pct"]),
             ("memory_peak_bytes", thresholds["memory_pct"]),
             ("hbm_predicted_peak_bytes", thresholds["memory_pct"]),
             ("phase_steady_ms", thresholds["throughput_pct"]))
    for key in sorted(set(a) & set(b)):
        for needle, thr in watch:
            if needle not in key:
                continue
            g = _pct(a[key], b[key])
            if g is not None and g > thr:
                f.append(_finding(key, FAIL,
                                  f"grew {g:.1f}% (> {thr}%)",
                                  a[key], b[key]))
            break
    # serving drift gauges (obs/model_quality.DriftMonitor): a candidate
    # PSI past the canonical 0.2 alert line where the baseline was quiet
    # is a data shift, not a code regression — warn
    for key in sorted(k for k in b if "feature_drift" in k):
        va, vb = a.get(key, 0.0), b[key]
        if vb > 0.2 >= va:
            f.append(_finding(key, WARN,
                              "serving PSI crossed 0.2", va, vb))
    # importance gauges: top cumulative-gain feature flip across runs
    def _top_gain(snap):
        gains = {k: v for k, v in snap.items()
                 if k.startswith("lgbm_tpu_feature_gain_total")}
        return max(gains, key=gains.get) if gains else None
    ga, gb = _top_gain(a), _top_gain(b)
    if ga and gb and ga != gb:
        f.append(_finding("importance_flip", WARN,
                          "top-gain feature label changed", ga, gb))
    return f


# --------------------------------------------------------------------- CLI


def _probe_failure(d):
    """The probe_failed record inside an artifact (top-level or the
    microprobe's partial-flush subkey)."""
    if d.get("kind") == "probe_failed":
        return d
    return d.get("probe_failed")


def compare(path_a, path_b, thresholds):
    """(kind, findings) for two artifact paths; raises ValueError on a
    kind mismatch."""
    kind_a, a = load_artifact(path_a)
    kind_b, b = load_artifact(path_b)
    if "probe_failed" in (kind_a, kind_b):
        # never a load error: render the dead stage as a finding so the
        # capture verdict names it (FAIL only when the CANDIDATE died —
        # a failed baseline leaves nothing to regress against)
        f = []
        if kind_b == "probe_failed":
            pf = _probe_failure(b) or {}
            sig = f" [{pf['signal']}]" if pf.get("signal") else ""
            f.append(_finding(
                "probe_failed", FAIL,
                f"candidate stage '{pf.get('stage')}' died "
                f"rc={pf.get('rc')}{sig}"))
        if kind_a == "probe_failed":
            pf = _probe_failure(a) or {}
            f.append(_finding(
                "probe_failed", WARN,
                f"baseline is a probe_failed record (stage "
                f"'{pf.get('stage')}', rc={pf.get('rc')}) — nothing to "
                f"compare against"))
        return "probe_failed", f
    if kind_a != kind_b:
        raise ValueError(f"artifact kinds differ: {path_a} is {kind_a}, "
                         f"{path_b} is {kind_b}")
    fn = {"bench": compare_bench, "trace": compare_trace,
          "metrics": compare_metrics}[kind_a]
    return kind_a, fn(a, b, thresholds)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python scripts/obs_diff.py",
        description="Regression-diff two telemetry artifacts (bench JSON, "
                    "trace JSON[L], or metrics snapshot); exit 1 on "
                    "regression beyond thresholds.")
    ap.add_argument("baseline")
    ap.add_argument("candidate")
    ap.add_argument("--threshold", type=float, default=10.0,
                    help="throughput / phase steady-state regression "
                         "threshold, %% (default 10)")
    ap.add_argument("--latency-threshold", type=float, default=25.0,
                    help="serving p50 growth threshold, %% (default 25)")
    ap.add_argument("--p99-threshold", type=float, default=25.0,
                    help="serving p99 growth threshold, %% (default 25)")
    ap.add_argument("--memory-threshold", type=float, default=20.0,
                    help="memory peak growth threshold, %% (default 20)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings")
    args = ap.parse_args(argv)
    thresholds = {"throughput_pct": args.threshold,
                  "latency_pct": args.latency_threshold,
                  "p99_pct": args.p99_threshold,
                  "memory_pct": args.memory_threshold}
    try:
        kind, findings = compare(args.baseline, args.candidate, thresholds)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"obs_diff: cannot compare: {e}", file=sys.stderr)
        return 2
    failed = [x for x in findings if x["severity"] == FAIL]
    verdict = "REGRESSION" if failed else "OK"
    if args.json:
        print(json.dumps({"schema_version": SCHEMA_VERSION, "kind": kind,
                          "verdict": verdict, "findings": findings},
                         indent=1))
    else:
        print(f"obs_diff [{kind}] {args.baseline} -> {args.candidate}: "
              f"{verdict} ({len(failed)} regression(s), "
              f"{len(findings)} finding(s))")
        for x in findings:
            mark = {"fail": "FAIL", "warn": "warn", "info": "info"}[
                x["severity"]]
            extra = ""
            if "baseline" in x:
                extra = f"  [{x['baseline']} -> {x.get('candidate')}]"
            print(f"  {mark:4} {x['check']}: {x['detail']}{extra}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
