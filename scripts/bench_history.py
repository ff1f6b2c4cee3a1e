"""Longitudinal trend verdicts over a bench-artifact series.

``scripts/obs_diff.py`` is pairwise-only — it mechanized the before/after
eyeball, but nothing in the plane reads the whole scheduled series: the
TPU probe timed out on BENCH_r03 through r05 and no artifact flagged the
streak.  This script folds a time-ordered series of bench artifacts into
trend verdicts:

    python scripts/bench_history.py BENCH_r*.json [options]

Accepted entry forms (sniffed per file, mixed freely):

* **scheduled-driver record** — ``{"n", "cmd", "rc", "tail", "parsed"}``
  (the external runner banks the last 2000 chars of output as ``tail``
  and the last JSON line as ``parsed``);
* **bare bench JSON** — ``bench.py`` stdout (the last ``{``-line rule);
* **probe_failed artifact** — ``{"kind": "probe_failed", ...}``, the
  record a stage that died leaves behind;
* **capture directory** — a directory whose ``bench_1m.json`` headline
  artifact is the entry.

Verdicts (entries are taken in the given CLI order = time order):

* ``probe_failure_streak`` — ≥ ``--streak`` consecutive entries whose TPU
  probe failed (the first-class ``probe_failed``/``runner.probe_failed``
  field from bench.py, the ``degraded`` fallback strings, or the probe
  messages the driver tail banked) → FAIL;
* ``run_failure_streak`` — consecutive entries that produced no parsed
  result at all (nonzero rc) → warn (the probe streak is the actionable
  one; a dead run compares nothing);
* ``throughput_drift`` — within one metric identity, the newest value
  falls below the median of its predecessors beyond the noise band
  (``--drift-pct`` or 2× the observed coefficient of variation,
  whichever is larger) → FAIL; a rise beyond the band is ``info``;
* ``kernel_identity_flip`` — consecutive entries of one metric identity
  traced different histogram kernels → FAIL (mislabeled series);
* ``memory_peak_creep`` — the newest measured peak grew beyond
  ``--memory-pct`` over the median of its predecessors → FAIL;
* ``stall_fraction_creep`` — within a streamed-rung identity
  (``bench_streamed.json``), the chunked side's measured pipeline stall
  fraction grew more than 0.15 absolute over the median of its
  predecessors → FAIL (the double-buffered pipeline is hiding less of
  the host→device copy);
* ``importance_flip`` — within one metric identity, consecutive entries'
  ``model_quality`` blocks name different top-gain features → warn (the
  learned model changed at the same config: data or determinism drift,
  not an infra regression — the throughput verdicts stay the gate);
* ``device_profile_coverage`` — how many entries carry the devprof
  attribution block → info (the capture-backlog freshness view).

Exit codes follow obs_diff: 0 = all green, 1 = any FAIL verdict,
2 = usage/load error.  ``--json`` prints findings structurally.
"""
import argparse
import glob
import json
import os
import statistics
import sys

SCHEMA_VERSION = 1

FAIL, WARN, INFO = "fail", "warn", "info"


def _finding(check, severity, detail, rounds=None):
    out = {"check": check, "severity": severity, "detail": detail}
    if rounds:
        out["rounds"] = list(rounds)
    return out


# ----------------------------------------------------------------- loading


def load_entry(path):
    """One raw artifact document from a file or capture directory."""
    if os.path.isdir(path):
        inner = sorted(glob.glob(os.path.join(path, "bench_1m*.json")))
        if not inner:
            raise ValueError(f"{path}: capture directory has no "
                             "bench_1m*.json headline artifact")
        path = inner[0]
    with open(path) as f:
        text = f.read().strip()
    # bench stdout may carry log lines before the JSON (the obs_diff /
    # decide_flips rule: the last '{'-line is the document)
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                break
    return json.loads(text)     # raises ValueError with the real position


_PROBE_TAIL_MARKERS = ("tpu probe failed", "tpu probe attempt",
                       "skipping tpu rungs")


def _probe_failed(parsed, tail):
    """Did this round's TPU probe fail?  First-class fields first
    (bench.py ``probe_failed`` / ``runner.probe_failed`` / the
    ``lgbm_tpu_probe_failed_total`` counter), then the degraded strings
    and driver-banked probe messages older artifacts carry."""
    if isinstance(parsed, dict):
        if parsed.get("probe_failed"):
            return True
        runner = parsed.get("runner")
        if isinstance(runner, dict) and runner.get("probe_failed"):
            return True
        if "tpu probe failed" in str(parsed.get("degraded", "")):
            return True
        samples = (parsed.get("metrics_snapshot") or {}).get("samples", {})
        for k, v in samples.items():
            if k.startswith("lgbm_tpu_probe_failed_total") and v:
                return True
    t = str(tail or "")
    return any(m in t for m in _PROBE_TAIL_MARKERS)


def normalize(raw, label):
    """One raw document -> the flat series entry the verdicts read."""
    entry = {"label": label, "probe_failed": False, "run_failed": False,
             "rc": 0, "value": None, "metric": None, "kernel": None,
             "memory_peak": None, "device_profile": None,
             "stall_fraction": None, "top_gain_feature": None}
    if not isinstance(raw, dict):
        entry["run_failed"] = True
        return entry
    if raw.get("kind") == "probe_failed":
        # a capture-stage death artifact: the run died, and the probe
        # evidence (if any) is in its banked stderr tail
        entry["run_failed"] = True
        entry["rc"] = raw.get("rc")
        entry["probe_failed"] = _probe_failed(None, raw.get("stderr_tail"))
        return entry
    if "cmd" in raw and ("tail" in raw or "parsed" in raw):
        # scheduled-driver record wrapping the bench output
        parsed = raw.get("parsed")
        parsed = parsed if isinstance(parsed, dict) else None
        rc = raw.get("rc", 0)
        tail = raw.get("tail", "")
    else:
        parsed, rc, tail = raw, 0, ""
    entry["rc"] = rc
    entry["run_failed"] = bool(rc) or parsed is None
    entry["probe_failed"] = _probe_failed(parsed, tail)
    if parsed is not None:
        v = parsed.get("value")
        entry["value"] = float(v) if isinstance(v, (int, float)) else None
        entry["metric"] = parsed.get("metric")
        entry["kernel"] = (parsed.get("telemetry") or {}) \
            .get("observed_kernel")
        mp = (parsed.get("memory") or {}).get("measured_peak_bytes")
        entry["memory_peak"] = int(mp) if isinstance(mp, (int, float)) \
            and mp else None
        entry["device_profile"] = parsed.get("device_profile")
        # streamed-rung artifacts (bench_streamed.json): the chunked
        # side's measured pipeline stall fraction, tracked for creep
        sf = (((parsed.get("streamed") or {}).get("configs") or {})
              .get("chunked") or {}).get("stall_fraction")
        entry["stall_fraction"] = (float(sf)
                                   if isinstance(sf, (int, float))
                                   else None)
        # model-quality block (obs/model_quality.py summary): the
        # top-cumulative-gain feature, tracked for same-config flips
        top = ((parsed.get("model_quality") or {}).get("top_features")
               or [{}])[0]
        tg = top.get("feature")
        entry["top_gain_feature"] = str(tg) if tg else None
    return entry


# ---------------------------------------------------------------- verdicts


def _streaks(entries, key):
    """Maximal runs of consecutive entries where ``entry[key]`` is truthy,
    as label lists."""
    runs, cur = [], []
    for e in entries:
        if e.get(key):
            cur.append(e["label"])
        else:
            if cur:
                runs.append(cur)
            cur = []
    if cur:
        runs.append(cur)
    return runs


def _groups(entries):
    """Measured entries grouped by metric identity, series order kept.

    A parsed value stays in the series even when the driver recorded a
    nonzero rc (``run_failed``) — the measurement happened; dropping it
    would silently thin the drift/flip/creep evidence.  The odd exit is
    still counted by the run_failure_streak verdict."""
    groups = {}
    for e in entries:
        if e["value"] is None or e["value"] <= 0:
            continue
        groups.setdefault(e["metric"] or "?", []).append(e)
    return groups


def verdicts(entries, drift_pct=15.0, memory_pct=25.0, streak_min=2):
    findings = []
    for run in _streaks(entries, "probe_failed"):
        if len(run) >= streak_min:
            findings.append(_finding(
                "probe_failure_streak", FAIL,
                f"TPU probe failed {len(run)} round(s) running "
                f"({run[0]}..{run[-1]}) — the accelerator evidence is "
                "going stale while the series looks green", rounds=run))
    for run in _streaks(entries, "run_failed"):
        if len(run) >= streak_min:
            findings.append(_finding(
                "run_failure_streak", WARN,
                f"{len(run)} consecutive round(s) exited nonzero or "
                f"produced no parsed result ({run[0]}..{run[-1]})",
                rounds=run))
    for metric, group in _groups(entries).items():
        if len(group) >= 3:
            *prev, last = group
            vals = [e["value"] for e in prev]
            med = statistics.median(vals)
            cv_pct = (statistics.pstdev(vals) / med * 100.0) if med else 0.0
            band = max(drift_pct, 2.0 * cv_pct)
            change = (last["value"] - med) / med * 100.0 if med else 0.0
            detail = (f"{metric}: {last['label']} at {last['value']:.4g} vs "
                      f"median {med:.4g} of {len(prev)} prior round(s) "
                      f"({change:+.1f}%, noise band ±{band:.1f}%)")
            if change < -band:
                findings.append(_finding(
                    "throughput_drift", FAIL, detail,
                    rounds=[e["label"] for e in group]))
            elif change > band:
                findings.append(_finding(
                    "throughput_gain", INFO, detail,
                    rounds=[e["label"] for e in group]))
        for a, b in zip(group, group[1:]):
            if a["kernel"] and b["kernel"] and a["kernel"] != b["kernel"]:
                findings.append(_finding(
                    "kernel_identity_flip", FAIL,
                    f"{metric}: traced kernel flipped {a['kernel']} -> "
                    f"{b['kernel']} between {a['label']} and {b['label']} "
                    "— the series mixes kernel identities",
                    rounds=[a["label"], b["label"]]))
        peaks = [e for e in group if e["memory_peak"]]
        if len(peaks) >= 3:
            *prev, last = peaks
            med = statistics.median(e["memory_peak"] for e in prev)
            growth = (last["memory_peak"] - med) / med * 100.0 if med else 0.0
            if growth > memory_pct:
                findings.append(_finding(
                    "memory_peak_creep", FAIL,
                    f"{metric}: measured peak {last['memory_peak'] / 1e6:.1f}"
                    f" MB at {last['label']} is {growth:+.1f}% over the "
                    f"median of {len(prev)} prior round(s) "
                    f"(threshold {memory_pct:g}%)",
                    rounds=[e["label"] for e in peaks]))
        tops = [e for e in group if e.get("top_gain_feature")]
        for a, b in zip(tops, tops[1:]):
            if a["top_gain_feature"] != b["top_gain_feature"]:
                # the learned model, not the machinery: warn, never fail
                findings.append(_finding(
                    "importance_flip", WARN,
                    f"{metric}: top-gain feature flipped "
                    f"{a['top_gain_feature']} -> {b['top_gain_feature']} "
                    f"between {a['label']} and {b['label']} at the same "
                    "config — the learned model shifted",
                    rounds=[a["label"], b["label"]]))
        stalls = [e for e in group if e["stall_fraction"] is not None]
        if len(stalls) >= 3:
            # absolute creep on the [0,1] fraction: the pipeline's overlap
            # regressing (transfers no longer hidden) is a FAIL even when
            # trees/s noise masks it
            *prev, last = stalls
            med = statistics.median(e["stall_fraction"] for e in prev)
            delta = last["stall_fraction"] - med
            if delta > 0.15:
                findings.append(_finding(
                    "stall_fraction_creep", FAIL,
                    f"{metric}: chunked stall fraction "
                    f"{last['stall_fraction']:.3f} at {last['label']} is "
                    f"{delta:+.3f} over the median "
                    f"({med:.3f}) of {len(prev)} prior round(s) — the "
                    "stream pipeline is hiding less of the copy",
                    rounds=[e["label"] for e in stalls]))
    with_dp = [e["label"] for e in entries if e["device_profile"]]
    findings.append(_finding(
        "device_profile_coverage", INFO,
        f"{len(with_dp)}/{len(entries)} entr{'y' if len(entries) == 1 else 'ies'} "
        "carry the devprof attribution block", rounds=with_dp))
    return findings


# --------------------------------------------------------------------- CLI


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python scripts/bench_history.py",
        description="Fold a time-ordered bench-artifact series "
                    "(BENCH_r*.json, bench JSONs, capture dirs) into trend "
                    "verdicts; exit 1 on any FAIL verdict.")
    ap.add_argument("entries", nargs="+",
                    help="artifacts in time order (shell-glob BENCH_r*.json"
                         " sorts correctly)")
    ap.add_argument("--drift-pct", type=float, default=15.0,
                    help="throughput drift floor of the noise band, %% "
                         "(default 15; widened by 2x the observed CV)")
    ap.add_argument("--memory-pct", type=float, default=25.0,
                    help="memory-peak growth threshold, %% (default 25)")
    ap.add_argument("--streak", type=int, default=2,
                    help="consecutive failures that make a streak "
                         "(default 2)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings")
    args = ap.parse_args(argv)
    series = []
    try:
        for path in args.entries:
            label = os.path.splitext(os.path.basename(path.rstrip("/")))[0]
            series.append(normalize(load_entry(path), label))
    except (OSError, ValueError) as e:
        print(f"bench_history: cannot load series: {e}", file=sys.stderr)
        return 2
    findings = verdicts(series, drift_pct=args.drift_pct,
                        memory_pct=args.memory_pct, streak_min=args.streak)
    failed = [x for x in findings if x["severity"] == FAIL]
    verdict = "REGRESSION" if failed else "OK"
    if args.json:
        print(json.dumps({"schema_version": SCHEMA_VERSION,
                          "entries": [e["label"] for e in series],
                          "verdict": verdict, "findings": findings},
                         indent=1))
    else:
        print(f"bench_history over {len(series)} entr"
              f"{'y' if len(series) == 1 else 'ies'} "
              f"({series[0]['label']}..{series[-1]['label']}): {verdict} "
              f"({len(failed)} failure(s), {len(findings)} finding(s))")
        for x in findings:
            mark = {"fail": "FAIL", "warn": "warn", "info": "info"}[
                x["severity"]]
            print(f"  {mark:4} {x['check']}: {x['detail']}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
