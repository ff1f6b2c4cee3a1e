"""Device-time attribution plane (obs/devprof.py) and the longitudinal
bench-history verdicts (scripts/bench_history.py).

The attribution layer is pure — these tests feed it synthetic Chrome-trace
fixtures in both accelerator shapes (TPU-style device-pid streams with
named_scope tokens in op metadata; XLA:CPU-style ``hlo_op``-tagged host
events attributed through the TraceAnnotation phase windows) — plus one
armed end-to-end CPU training that pins the acceptance bar: >= 90% of
captured device op time lands on named phases.  Disarmed, the plane must
stay the shared no-op singleton (the hot-loop contract).
"""
import gzip
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import devprof as obs_devprof
from lightgbm_tpu.obs import report as obs_report

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- fixtures


def _tpu_fixture():
    """TPU-shaped capture: a device-labelled pid whose op events carry the
    named_scope path in ``tf_op`` metadata (scope attribution), plus one
    op with no recoverable scope (stays unattributed — no host windows
    here)."""
    return [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0 XLA Ops"}},
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "python"}},
        {"ph": "X", "pid": 7, "tid": 0, "name": "fusion.42",
         "ts": 100.0, "dur": 600.0,
         "args": {"tf_op": "boosting/histogram/fused_hist"}},
        {"ph": "X", "pid": 7, "tid": 0, "name": "split_find.best_gain",
         "ts": 700.0, "dur": 300.0, "args": {}},
        {"ph": "X", "pid": 7, "tid": 0, "name": "dynamic-update-slice.3",
         "ts": 1000.0, "dur": 100.0,
         "args": {"long_name": "tree/partition/apply_split"}},
        # no scope token anywhere and no host window -> unattributed
        {"ph": "X", "pid": 7, "tid": 0, "name": "copy.9",
         "ts": 1100.0, "dur": 100.0, "args": {}},
        # python-tracer frame on a host pid: never an op event
        {"ph": "X", "pid": 1, "tid": 0, "name": "$train_one_iter",
         "ts": 0.0, "dur": 2000.0, "args": {}},
    ]


def _cpu_fixture():
    """XLA:CPU-shaped capture: ``hlo_op``-tagged host events with no scope
    tokens, attributed through the TraceAnnotation phase windows (midpoint
    containment, innermost wins; a trailing op falls back to the last
    window dispatched before it)."""
    return [
        # nested host windows (obs/trace.phase annotations): tree wraps
        # tree.wait
        {"ph": "X", "pid": 1, "tid": 2, "name": "lgb:tree",
         "ts": 0.0, "dur": 1000.0, "args": {}},
        {"ph": "X", "pid": 1, "tid": 2, "name": "lgb:tree.wait",
         "ts": 100.0, "dur": 400.0, "args": {}},
        # an annotation with arguments, as the profiler's Chrome-trace
        # export writes it: short name, the whole in long_name
        {"ph": "X", "pid": 1, "tid": 2, "name": "score",
         "ts": 600.0, "dur": 300.0,
         "args": {"iteration": "0", "long_name": "lgb:score"}},
        # a bare name is no window of the program's
        {"ph": "X", "pid": 1, "tid": 2, "name": "tree",
         "ts": 0.0, "dur": 2000.0, "args": {}},
        # midpoint 250 inside both -> innermost (tree.wait)
        {"ph": "X", "pid": 1, "tid": 3, "name": "convolution.1",
         "ts": 150.0, "dur": 200.0, "args": {"hlo_op": "convolution.1"}},
        # midpoint 700 -> score (and inside tree: innermost wins)
        {"ph": "X", "pid": 1, "tid": 3, "name": "reduce.2",
         "ts": 650.0, "dur": 100.0, "args": {"hlo_op": "reduce.2"}},
        # starts after every window closed -> last-before fallback
        # (async dispatch ordering) -> the most recently STARTED window,
        # score
        {"ph": "X", "pid": 1, "tid": 3, "name": "add.3",
         "ts": 1100.0, "dur": 100.0, "args": {"hlo_op": "add.3"}},
        # an untagged host event is not an op
        {"ph": "X", "pid": 1, "tid": 2, "name": "some_host_thing",
         "ts": 0.0, "dur": 50.0, "args": {}},
    ]


# ----------------------------------------------------- attribution core


def test_tpu_scope_attribution_roundtrip():
    out = obs_devprof.attribute(_tpu_fixture())
    assert out["op_count"] == 4
    assert out["total_op_ms"] == pytest.approx(1.1)
    assert out["phase_device_ms"]["histogram"] == pytest.approx(0.6)
    assert out["phase_device_ms"]["split_find"] == pytest.approx(0.3)
    assert out["phase_device_ms"]["partition"] == pytest.approx(0.1)
    assert out["attributed_fraction"] == pytest.approx(1.0 / 1.1, abs=1e-3)
    # the unattributed op is still visible in the top-ops table
    unattr = [o for o in out["top_ops"] if o["op"] == "copy.9"]
    assert unattr and unattr[0]["phase"] == "(unattributed)"
    # phase table is sorted by descending device time
    assert list(out["phase_device_ms"]) == ["histogram", "split_find",
                                            "partition"]


def test_cpu_window_attribution_roundtrip():
    out = obs_devprof.attribute(_cpu_fixture())
    assert out["op_count"] == 3
    # innermost containment beats the outer tree window; phases are named
    # without the annotation prefix
    assert out["phase_device_ms"]["tree.wait"] == pytest.approx(0.2)
    # score's contained op + the trailing op that falls back to the most
    # recently started window
    assert out["phase_device_ms"]["score"] == pytest.approx(0.2)
    assert set(out["phase_device_ms"]) == {"tree.wait", "score"}
    assert out["attributed_fraction"] == pytest.approx(1.0)


def test_device_busy_merges_overlapping_ops():
    """device_busy_ms is the interval UNION — concurrent streams must not
    double-count."""
    evs = [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "pid": 7, "name": "histogram.a", "ts": 0.0,
         "dur": 500.0, "args": {}},
        {"ph": "X", "pid": 7, "name": "histogram.b", "ts": 300.0,
         "dur": 500.0, "args": {}},       # overlaps [300, 500]
        {"ph": "X", "pid": 7, "name": "histogram.c", "ts": 900.0,
         "dur": 100.0, "args": {}},       # disjoint
    ]
    out = obs_devprof.attribute(evs)
    assert out["total_op_ms"] == pytest.approx(1.1)     # summed
    assert out["device_busy_ms"] == pytest.approx(0.9)  # union


def test_trace_loaders_json_gz_jsonl(tmp_path):
    evs = _tpu_fixture()
    p_json = tmp_path / "t.trace.json"
    p_json.write_text(json.dumps({"traceEvents": evs}))
    p_gz = tmp_path / "t.trace.json.gz"
    with gzip.open(p_gz, "wt") as f:
        json.dump({"traceEvents": evs}, f)
    p_jsonl = tmp_path / "t.jsonl"
    lines = [json.dumps(e) for e in evs]
    lines.append('{"ph": "X", "name": "torn')        # killed-writer tail
    p_jsonl.write_text("\n".join(lines))
    assert obs_devprof.load_trace_events(str(p_json)) == evs
    assert obs_devprof.load_trace_events(str(p_gz)) == evs
    assert obs_devprof.load_trace_events(str(p_jsonl)) == evs


def test_find_capture_files_profiler_layout(tmp_path):
    """The jax.profiler on-disk shape:
    <dir>/plugins/profile/<run>/<host>.trace.json.gz"""
    run = tmp_path / "plugins" / "profile" / "2026_08_06"
    run.mkdir(parents=True)
    art = run / "host0.trace.json.gz"
    with gzip.open(art, "wt") as f:
        json.dump({"traceEvents": []}, f)
    found = obs_devprof.find_capture_files(str(tmp_path))
    assert found == [str(art)]


# ------------------------------------------------- singleton discipline


def test_disarmed_plane_is_shared_noop():
    """The hot-loop contract: disarmed, get_devprof() is the one
    NULL_DEVPROF and iteration() hands back the one NULL_WINDOW — no
    per-iteration allocation."""
    dp = obs_devprof.get_devprof()
    assert dp is obs_devprof.NULL_DEVPROF
    assert dp.enabled is False
    assert dp.iteration(0) is obs_devprof.NULL_WINDOW
    assert dp.iteration(7) is dp.iteration(8)
    with dp.iteration(0):
        pass
    assert dp.pop_idle_gap() is None
    assert dp.summary() is None


def _train(extra=None, rounds=2):
    rng = np.random.RandomState(0)
    X = rng.randn(500, 8).astype(np.float32)
    y = (X @ rng.randn(8) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
              "verbose": -1}
    params.update(extra or {})
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    return lgb.train(params, ds, num_boost_round=rounds, verbose_eval=False)


def test_train_without_param_stays_disarmed():
    _train()
    assert obs_devprof.get_devprof() is obs_devprof.NULL_DEVPROF


def test_device_profile_rejects_profile_dir_combo(tmp_path):
    """Both knobs arm the one process-wide profiler session — combining
    them must die loudly at config time, not half-capture."""
    with pytest.raises(RuntimeError, match="device_profile"):
        _train(extra={"device_profile": True,
                      "profile_dir": str(tmp_path / "prof")})


def test_summary_keeps_device_pid_ops_across_windows(tmp_path, monkeypatch):
    """Regression: summary() re-attributes over the profiler's RETAINED
    state, which no longer carries the process_name metadata that
    identifies device pids — the classified ops must be stored as ops, not
    re-filtered, or TPU-style captures (device-pid events without hlo_op
    args) come back empty on the second pass."""
    import jax
    dp = obs_devprof.DeviceProfiler(log_dir=str(tmp_path), profile_iters=1,
                                    keep_artifacts=True)

    def fake_start(d):
        os.makedirs(d, exist_ok=True)

    def fake_stop():
        with open(os.path.join(dp._cur_dir, "host.trace.json"), "w") as f:
            json.dump({"traceEvents": _tpu_fixture()}, f)

    monkeypatch.setattr(jax.profiler, "start_trace", fake_start)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake_stop)
    with dp.iteration(0):       # compile firing — never captured
        pass
    with dp.iteration(1):       # captured steady-state window
        pass
    s = dp.summary()
    assert s["captured_iterations"] == 1
    assert s["op_count"] == 4                      # device-pid ops survive
    assert s["total_op_ms"] == pytest.approx(1.1)
    assert s["phase_device_ms"]["histogram"] == pytest.approx(0.6)
    assert s["phase_device_ms"]["split_find"] == pytest.approx(0.3)
    assert s["device_busy_ms"] == pytest.approx(1.1)
    # the per-iteration accounting agrees with the summary's device view
    assert s["iterations"][0]["device_busy_ms"] == pytest.approx(1.1)


def test_armed_cpu_capture_attributes_device_time():
    """Acceptance pin: an armed CPU training captures steady-state windows
    (the compile firing excluded) and attributes >= 90% of captured op
    time to named phases; the singleton is restored to NULL afterwards."""
    from lightgbm_tpu.obs import metrics as obs_metrics
    try:
        _train(extra={"device_profile": True, "profile_iters": 2,
                      "pipeline_trees": False}, rounds=4)
        assert obs_metrics.last_capture_age() >= 0  # freshness gauge armed
    finally:
        # don't leak the capture timestamp into the rest of the suite
        obs_metrics._last_capture_ts = None
    assert obs_devprof.get_devprof() is obs_devprof.NULL_DEVPROF
    s = obs_devprof.last_summary()
    assert s is not None and not s.get("capture_failed")
    assert s["schema_version"] == obs_devprof.SCHEMA_VERSION
    assert s["source"] == "jax.profiler"
    assert 1 <= s["captured_iterations"] <= 2
    assert s["op_count"] > 0
    assert s["attributed_fraction"] >= 0.9
    assert s["phase_device_ms"]
    for it in s["iterations"]:
        assert it["iteration"] >= 1          # iteration 0 is the compile
        assert 0.0 <= it["idle_gap_fraction"] <= 1.0
        assert it["overlap_fraction"] == pytest.approx(
            1.0 - it["idle_gap_fraction"], abs=1e-3)


# -------------------------------------------------------- bench contract


def test_bench_child_embeds_device_profile_block():
    """A CPU-tier bench child with BENCH_DEVICE_PROFILE=1 must emit the
    schema-versioned device_profile block next to telemetry/memory/
    metrics_snapshot, meeting the >= 90% attribution bar (acceptance
    criterion), and honor BENCH_DEVPROF as the per-rung artifact path."""
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        devprof_out = os.path.join(td, "devprof.json")
        env = dict(os.environ, BENCH_CHILD="1", BENCH_CHILD_PLATFORM="cpu",
                   BENCH_CHILD_MODE="segment", BENCH_ROWS="5000",
                   BENCH_ROWS_CPU="5000", BENCH_TREES_CPU="1",
                   BENCH_LEAVES="15", BENCH_LEAVES_SWEEP="0",
                   BENCH_DS_CACHE="", BENCH_TRACE="",
                   BENCH_DEVICE_PROFILE="1", BENCH_PROFILE_ITERS="2",
                   BENCH_DEVPROF=devprof_out, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                           capture_output=True, text=True, timeout=300,
                           env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        line = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        doc = json.loads(line)
        dp = doc["device_profile"]
        assert dp["schema_version"] == obs_devprof.SCHEMA_VERSION
        assert dp["captured_iterations"] >= 1
        assert dp["attributed_fraction"] >= 0.9
        assert dp["phase_device_ms"]
        assert "memory" in doc and "metrics_snapshot" in doc
        # the devprof plane's freshness gauge rides the snapshot
        samples = doc["metrics_snapshot"]["samples"]
        age = [v for k, v in samples.items()
               if k.startswith("lgbm_tpu_last_capture_age_seconds")]
        assert age and age[0] >= 0
        # per-rung artifact for the capture scripts
        with open(devprof_out) as f:
            assert json.load(f)["captured_iterations"] >= 1


# ------------------------------------------------------ report rendering


def test_report_renders_device_time_section(tmp_path):
    """A trace carrying the embedded device_profile summary must render
    the Device time section with the phase and per-iteration tables."""
    payload = {"schema_version": 1, "source": "jax.profiler",
               "profile_iters": 2, "captured_iterations": 2,
               "iterations": [
                   {"iteration": 1, "host_ms": 10.0, "device_busy_ms": 9.0,
                    "overlap_fraction": 0.9, "idle_gap_fraction": 0.1},
                   {"iteration": 2, "host_ms": 10.0, "device_busy_ms": 8.0,
                    "overlap_fraction": 0.8, "idle_gap_fraction": 0.2}],
               "phase_device_ms": {"histogram": 6.0, "split_find": 2.0},
               "top_ops": [{"op": "fusion.42", "phase": "histogram",
                            "ms": 6.0, "count": 12}],
               "op_count": 13, "total_op_ms": 8.5, "attributed_ms": 8.0,
               "attributed_fraction": 0.94, "device_busy_ms": 8.5}
    events = [
        {"ph": "X", "name": "boosting", "ts": 0, "dur": 1000,
         "pid": 0, "tid": 0, "args": {}},
        {"ph": "i", "name": "telemetry.summary", "ts": 1001, "pid": 0,
         "tid": 0, "args": {"kind": "device_profile", "payload": payload}},
    ]
    p = tmp_path / "trace.jsonl"
    p.write_text("\n".join(json.dumps(e) for e in events))
    text = obs_report.render(str(p))
    assert "## Device time (devprof attribution)" in text
    assert "histogram" in text and "fusion.42" in text
    assert "94.0% attributed" in text
    assert "idle gap" in text


# ------------------------------------------------------ bench_history CLI


def _series_doc(value, kernel="fused", peak=2_000_000_000, extra=None):
    doc = {"metric": "higgs-like 1000k x28 binary GBDT (tpu, fused)",
           "value": value, "unit": "trees/sec",
           "telemetry": {"observed_kernel": kernel},
           "memory": {"measured_peak_bytes": peak}}
    doc.update(extra or {})
    return doc


def _write_series(tmp_path, docs):
    paths = []
    for i, doc in enumerate(docs):
        p = tmp_path / f"r{i:02d}.json"
        p.write_text(json.dumps(doc))
        paths.append(str(p))
    return paths


def test_bench_history_all_green_exits_zero(tmp_path, capsys):
    bh = _load_script("bench_history")
    paths = _write_series(tmp_path, [_series_doc(v)
                                     for v in (1.20, 1.22, 1.19, 1.21)])
    assert bh.main(paths) == 0
    assert "OK" in capsys.readouterr().out


def test_bench_history_throughput_drift_fails(tmp_path, capsys):
    bh = _load_script("bench_history")
    paths = _write_series(tmp_path, [_series_doc(v)
                                     for v in (1.20, 1.21, 1.19, 0.80)])
    rc = bh.main(paths + ["--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(x["check"] == "throughput_drift" and x["severity"] == "fail"
               for x in out["findings"])


def test_bench_history_probe_streak_first_class_field(tmp_path, capsys):
    """The new first-class probe_failed field (bench.py) is enough — no
    degraded string or driver tail needed."""
    bh = _load_script("bench_history")
    docs = [_series_doc(1.2),
            _series_doc(0.4, extra={"probe_failed": True}),
            _series_doc(0.4, extra={"runner": {"probe_failed": True}})]
    rc = bh.main(_write_series(tmp_path, docs) + ["--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    streaks = [x for x in out["findings"]
               if x["check"] == "probe_failure_streak"]
    assert streaks and streaks[0]["rounds"] == ["r01", "r02"]


def test_bench_history_nonzero_rc_keeps_parsed_values(tmp_path, capsys):
    """A driver record whose bench emitted a valid result line but exited
    nonzero still feeds the drift series — the measurement happened; only
    the run_failure_streak counts the odd exit."""
    bh = _load_script("bench_history")
    docs = [_series_doc(v) for v in (1.20, 1.21, 1.19)]
    # last round: parsed result present, driver rc nonzero -> the 0.80
    # value must still trigger the drift FAIL instead of vanishing
    docs.append({"cmd": "bench.py", "rc": 1, "tail": "late crash",
                 "parsed": _series_doc(0.80)})
    rc = bh.main(_write_series(tmp_path, docs) + ["--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(x["check"] == "throughput_drift" and x["severity"] == "fail"
               for x in out["findings"])


def test_bench_history_kernel_identity_flip_fails(tmp_path, capsys):
    bh = _load_script("bench_history")
    docs = [_series_doc(1.2), _series_doc(1.2, kernel="segment"),
            _series_doc(1.2)]
    rc = bh.main(_write_series(tmp_path, docs) + ["--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(x["check"] == "kernel_identity_flip"
               for x in out["findings"])


def test_bench_history_memory_creep_fails(tmp_path, capsys):
    bh = _load_script("bench_history")
    docs = [_series_doc(1.2, peak=int(2e9)), _series_doc(1.2, peak=int(2e9)),
            _series_doc(1.2, peak=int(2e9)), _series_doc(1.2, peak=int(3e9))]
    rc = bh.main(_write_series(tmp_path, docs) + ["--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(x["check"] == "memory_peak_creep" for x in out["findings"])


def test_bench_history_coverage_counts_devprof_blocks(tmp_path, capsys):
    bh = _load_script("bench_history")
    docs = [_series_doc(1.2),
            _series_doc(1.2, extra={"device_profile":
                                    {"captured_iterations": 2}})]
    assert bh.main(_write_series(tmp_path, docs) + ["--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    cov = [x for x in out["findings"]
           if x["check"] == "device_profile_coverage"]
    assert cov and "1/2" in cov[0]["detail"]


def test_bench_history_load_error_exits_two(tmp_path, capsys):
    bh = _load_script("bench_history")
    assert bh.main([str(tmp_path / "missing.json")]) == 2
    capsys.readouterr()
