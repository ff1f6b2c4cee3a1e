"""Live telemetry plane (docs/OBSERVABILITY.md "Live telemetry"):
streaming metrics export (obs/metrics.py), the per-rank flight recorder
(obs/flight.py), supervisor straggler verdicts, and the obs_diff
regression differ — plus the event-registry lint and the
zero-added-collectives pin with the whole plane armed."""
import glob
import importlib.util
import json
import os
import re
import socket
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import flight as obs_flight
from lightgbm_tpu.obs import metrics as obs_metrics
from lightgbm_tpu.obs.counters import counters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Prometheus text exposition: metric line = name{labels} value
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.]+([eE][-+]?[0-9]+)?$")


def _make_xy(n=400, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X @ rng.randn(f) > 0).astype(np.float32)
    return X, y


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _assert_prometheus_parseable(text):
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"unparseable sample line: {line!r}"


# ------------------------------------------------------------ render core


def test_prometheus_render_contract():
    counters.reset()
    counters.inc("hist_dispatch", method="fused", site="root")
    counters.inc("hist_dispatch", method="fused", site="split")
    counters.gauge("memory_peak_bytes", 12345678)
    counters.gauge("weird-name with spaces", 1)      # sanitized, not dropped
    text = obs_metrics.render_prometheus()
    _assert_prometheus_parseable(text)
    assert "# TYPE lgbm_tpu_hist_dispatch_total counter" in text
    assert ('lgbm_tpu_hist_dispatch_total{method="fused",site="root"} 1'
            in text)
    assert "# TYPE lgbm_tpu_memory_peak_bytes gauge" in text
    assert "lgbm_tpu_memory_peak_bytes 12345678" in text
    assert "lgbm_tpu_weird_name_with_spaces 1" in text
    # the registry's own bookkeeping rides along
    assert "lgbm_tpu_events_dropped_total 0" in text
    assert "lgbm_tpu_process_index 0" in text
    counters.reset()


def test_snapshot_and_parse_roundtrip():
    counters.reset()
    counters.inc("hist_dispatch", method="segment", site="t")
    counters.gauge("hbm_predicted_peak_bytes", 1e6)
    snap = obs_metrics.snapshot()
    assert snap["schema_version"] == obs_metrics.SCHEMA_VERSION
    parsed = obs_metrics.parse_prometheus(obs_metrics.render_prometheus())
    # the snapshot sample map and a parsed scrape agree key-for-key
    assert parsed == snap["samples"]
    assert 'lgbm_tpu_hist_dispatch_total{method="segment",site="t"}' \
        in parsed
    counters.reset()


def test_sources_counter_sum_gauge_last_wins():
    class Src:
        def samples(self):
            return [("zz_src_calls", {"k": "a"}, 2.0, "counter"),
                    ("zz_src_level", {}, 5.0, "gauge")]

    class Src2(Src):
        def samples(self):
            return [("zz_src_calls", {"k": "a"}, 3.0, "counter"),
                    ("zz_src_level", {}, 7.0, "gauge")]

    counters.reset()
    a, b = Src(), Src2()
    obs_metrics.register_source(a.samples)
    obs_metrics.register_source(b.samples)
    parsed = obs_metrics.parse_prometheus(obs_metrics.render_prometheus())
    assert parsed['lgbm_tpu_zz_src_calls_total{k="a"}'] == 5.0   # summed
    assert parsed["lgbm_tpu_zz_src_level"] == 7.0                # last wins
    del a, b   # weakrefs: dead sources drop out of the next render
    parsed = obs_metrics.parse_prometheus(obs_metrics.render_prometheus())
    assert not any("zz_src" in k for k in parsed)


# -------------------------------------------------------------- exporter


def test_exporter_http_contract():
    counters.reset()
    counters.inc("hist_dispatch", method="segment", site="x")
    exp = obs_metrics.start_exporter(0)           # ephemeral test port
    try:
        url = f"http://127.0.0.1:{exp.port}"
        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == obs_metrics.CONTENT_TYPE
            body = r.read().decode()
        _assert_prometheus_parseable(body)
        assert "lgbm_tpu_hist_dispatch_total" in body
        assert counters.total("metrics_scrapes") == 1
        with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
            assert json.loads(r.read())["ok"] is True
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(url + "/nope", timeout=30)
    finally:
        obs_metrics.stop_exporter()
    assert obs_metrics.get_exporter() is obs_metrics.NULL_EXPORTER
    counters.reset()


def test_disarmed_fast_paths_are_shared_noops(tmp_path):
    """The PR 2/PR 5 singleton discipline pin for both new legs: disarmed,
    the active exporter/recorder ARE the shared null objects and a plain
    training never arms them."""
    assert obs_metrics.get_exporter() is obs_metrics.NULL_EXPORTER
    assert obs_flight.get_flight() is obs_flight.NULL_FLIGHT
    # the null recorder's hot-path methods are constant no-ops
    fl = obs_flight.get_flight()
    assert fl.record("x", a=1) is None and fl.progress(3) is None
    assert not fl.enabled and not obs_metrics.get_exporter().enabled
    X, y = _make_xy(200)
    lgb.train({"objective": "binary", "num_leaves": 4, "verbose": -1},
              lgb.Dataset(X, label=y), num_boost_round=1,
              verbose_eval=False)
    assert obs_metrics.get_exporter() is obs_metrics.NULL_EXPORTER
    assert obs_flight.get_flight() is obs_flight.NULL_FLIGHT


def test_exporter_bind_failure_disarms_loudly():
    blocker = socket.socket()
    blocker.bind(("", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    try:
        exp = obs_metrics.start_exporter(port)     # already taken
        assert exp is obs_metrics.NULL_EXPORTER    # disarmed, no raise
    finally:
        blocker.close()
        obs_metrics.stop_exporter()


# ------------------------------------------------- training with the plane


@pytest.fixture(scope="module")
def live_training(tmp_path_factory):
    """One training with the WHOLE live plane armed (metrics_port +
    obs_stream_path + telemetry + heartbeats + snapshots): scrapes
    /metrics mid-run from a callback, returns (scrape body, content type,
    stream path, counter snapshot)."""
    d = tmp_path_factory.mktemp("live")
    port = _free_port()
    stream = str(d / "flight.jsonl")
    out = str(d / "m.txt")
    got = {}

    def scrape_cb(env):
        if env.iteration >= 1 and "body" not in got:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=60) as r:
                got["body"] = r.read().decode()
                got["ctype"] = r.headers["Content-Type"]

    X, y = _make_xy()
    # pipeline_trees=false: the synchronous path knows per-iteration leaf
    # counts, so progress records carry ms_per_leaf (pipelined ones omit
    # it — the tree drains iterations later)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
               "metrics_port": port, "obs_stream_path": stream,
               "telemetry": True, "heartbeat_interval": 0.001,
               "pipeline_trees": False,
               "snapshot_freq": 2, "output_model": out},
              lgb.Dataset(X, label=y), num_boost_round=4,
              verbose_eval=False, callbacks=[scrape_cb])
    return got, stream, counters.snapshot()


def test_metrics_port_scrape_during_training(live_training):
    got, _, _ = live_training
    assert "body" in got, "mid-training scrape never happened"
    assert got["ctype"] == obs_metrics.CONTENT_TYPE
    _assert_prometheus_parseable(got["body"])
    # dispatch counters + phase families + iteration gauge are live
    assert 'lgbm_tpu_hist_dispatch_total{' in got["body"]
    assert 'method="segment"' in got["body"]
    assert 'lgbm_tpu_phase_seconds_total{phase="tree"}' in got["body"]
    assert 'lgbm_tpu_phase_steady_ms{phase="tree"}' in got["body"]
    assert "lgbm_tpu_train_iterations" in got["body"]
    # armed plane is scoped to the training: disarmed afterwards
    assert obs_metrics.get_exporter() is obs_metrics.NULL_EXPORTER
    assert obs_flight.get_flight() is obs_flight.NULL_FLIGHT


def test_flight_stream_progress_records(live_training):
    _, stream, _ = live_training
    path = obs_flight.stream_path(stream, 0)
    recs = obs_flight.read_stream(path)
    prog = [r for r in recs if r["event"] == "progress"]
    assert len(prog) == 4
    assert [r["iteration"] for r in prog] == [1, 2, 3, 4]
    for r in prog:
        assert r["rank"] == 0 and r["seconds"] > 0
        assert r["kernel"] == "segment"
        assert r["trees_per_sec"] > 0
        # memory monitor armed (telemetry=true): the peak rides along
        assert r["hbm_peak_bytes"] > 0
        # synchronous path: ms/leaf is known
        assert r["ms_per_leaf"] > 0
    # the armed memory monitor streams its peak inflections
    assert any(r["event"] == "hbm_peak" for r in recs)


def test_live_plane_adds_zero_collectives(live_training):
    """Acceptance pin: exporter + flight recorder + heartbeats +
    snapshots armed on the happy path issue ZERO host-object collectives
    (the PR 6 rule extended over the live plane; everything is host-side
    registry reads and unsynced file appends)."""
    _, _, snap = live_training
    assert snap["counters"].get("collective_calls", {}) == {}
    assert snap["counters"].get("collective_bytes", {}) == {}


# ------------------------------------------------------- flight recorder


def test_flight_rotation_and_torn_tail(tmp_path):
    p = str(tmp_path / "s.jsonl")
    rec = obs_flight.FlightRecorder(p, rank=3, max_bytes=4096)
    for i in range(120):
        rec.progress(i, seconds=0.01)
    rec.close()
    assert os.path.exists(p + ".1"), "stream never rotated"
    assert os.path.getsize(p) <= 4096 and os.path.getsize(p + ".1") <= 4096
    recs = obs_flight.read_stream(p)
    # rotation keeps one generation: the newest records survive in order
    iters = [r["iteration"] for r in recs if r["event"] == "progress"]
    assert iters == sorted(iters) and iters[-1] == 119
    assert all(r["rank"] == 3 for r in recs)
    # torn tail (killed writer): the partial line is skipped, not raised
    with open(p, "a") as f:
        f.write('{"event": "torn')
    assert len(obs_flight.read_stream(p)) == len(recs)
    tail = obs_flight.tail_records(p, max_bytes=512)
    assert tail and tail[-1]["iteration"] == 119


def test_flight_absorbs_counter_ring_events(tmp_path):
    p = str(tmp_path / "s.jsonl")
    obs_flight.start(p, rank=0)
    try:
        counters.event("layout_downgrade", stage="test", reason="probe")
    finally:
        obs_flight.stop()
    recs = obs_flight.read_stream(p)
    ev = [r for r in recs if r["event"] == "layout_downgrade"]
    # the event streamed the moment it was recorded — not at stop()
    assert ev and ev[0]["reason"] == "probe"
    # disarmed again: later events do not reach the closed stream
    counters.event("layout_downgrade", stage="test", reason="after")
    assert len([r for r in obs_flight.read_stream(p)
                if r["event"] == "layout_downgrade"]) == 1


def test_straggler_detection_on_synthetic_two_rank_streams(tmp_path):
    """Unit pin for the supervisor's verdict: two synthetic rank streams,
    rank 1 progressing 10x slower — detect_stragglers names it; equal
    rates (or a single rank) never trigger."""
    base = str(tmp_path / "g.jsonl")
    t0 = 1000.0
    for rank, step in ((0, 0.1), (1, 1.0)):
        rec = obs_flight.FlightRecorder(obs_flight.stream_path(base, rank),
                                        rank=rank)
        for i in range(6):
            rec.record("progress", iteration=i + 1)
        rec.close()
        # rewrite timestamps deterministically (wall-clock writes are
        # near-instant here)
        p = obs_flight.stream_path(base, rank)
        recs = obs_flight.read_stream(p)
        with open(p, "w") as f:
            for i, r in enumerate(recs):
                r["t"] = t0 + i * step
                f.write(json.dumps(r) + "\n")
    rates = {r: obs_flight.progress_rate(
        obs_flight.tail_records(obs_flight.stream_path(base, r)))
        for r in (0, 1)}
    assert rates[0] == pytest.approx(10.0) \
        and rates[1] == pytest.approx(1.0)
    verdicts = obs_flight.detect_stragglers(rates, factor=4.0)
    assert len(verdicts) == 1 and verdicts[0]["rank"] == 1
    assert verdicts[0]["behind"] == pytest.approx(5.5)
    assert obs_flight.detect_stragglers({0: 5.0, 1: 5.0}, 4.0) == []
    assert obs_flight.detect_stragglers({0: 5.0, 1: None}, 4.0) == []


# --------------------------------------------------- supervisor integration

STRAGGLER_WORKER = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
from lightgbm_tpu.utils.cache import enable_persistent_cache
enable_persistent_cache()
import numpy as np
import lightgbm_tpu as lgb

rank = int(os.environ["LGBM_TPU_RANK"])
rng = np.random.RandomState(5)
X = rng.randn(300, 6).astype(np.float32)
y = (X @ rng.randn(6) > 0).astype(np.float32)

def throttle(env):
    if rank == 1:
        time.sleep(0.5)     # the straggler: alive, beating, but slow

lgb.train({"objective": "binary", "num_leaves": 5, "verbose": -1,
           "heartbeat_interval": 0.05,
           "obs_stream_path": os.environ["TEST_STREAM"],
           "output_model": os.environ["TEST_SNAP"]},
          lgb.Dataset(X, label=y), num_boost_round=8,
          verbose_eval=False, callbacks=[throttle])
print("WORKER_DONE", rank)
"""


def test_supervised_two_process_straggler_event(tmp_path):
    """Acceptance pin: a 2-process supervised run where one rank is
    throttled produces a structured ``rank_straggler`` event naming the
    slow rank — and the group still completes (a straggler verdict is
    health evidence, never a restart trigger)."""
    from lightgbm_tpu import supervisor as sup_mod
    counters.reset()
    script = tmp_path / "worker.py"
    script.write_text(STRAGGLER_WORKER)
    stream = str(tmp_path / "flight.jsonl")
    env = {"TEST_STREAM": stream, "TEST_SNAP": str(tmp_path / "m.txt"),
           "PYTHONPATH": ROOT + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    sup = sup_mod.Supervisor(
        [sys.executable, str(script)], str(tmp_path / "m.txt"), 2,
        heartbeat_interval=0.05, hang_timeout=120.0, restart_limit=0,
        poll_interval=0.05, env=env, obs_stream=stream,
        straggler_factor=4.0, straggler_interval=0.2)
    rc = sup.run()
    assert rc == 0, "throttled group must still complete (no restart)"
    evs = counters.events("rank_straggler")
    assert evs, "no rank_straggler event for a 10x-throttled rank"
    assert evs[0]["rank"] == 1
    assert evs[0]["rate"] < evs[0]["median_rate"]
    assert evs[0]["behind"] >= 4.0
    # one verdict per incarnation, not one per poll
    assert len(evs) == 1
    assert counters.events("group_restart") == []
    # both ranks' flight streams exist and carry rank-tagged progress
    for r in (0, 1):
        recs = obs_flight.read_stream(obs_flight.stream_path(stream, r))
        assert any(e["event"] == "progress" and e["rank"] == r
                   for e in recs)


def test_supervisor_metrics_source_restart_gauges(tmp_path):
    """Satellite: supervisor restart state is scrapeable — budget
    remaining, last restart, per-rank heartbeat age — through the same
    metrics view."""
    from lightgbm_tpu import checkpoint as ckpt
    from lightgbm_tpu import supervisor as sup_mod
    counters.reset()
    out = str(tmp_path / "m.txt")
    sup = sup_mod.Supervisor(["true"], out, 2, restart_limit=3,
                             obs_stream="", metrics_port=0)
    hb = ckpt.Heartbeat(ckpt.heartbeat_path(out, 0), 0.0)
    hb.stamp(7, force=True)
    parsed = obs_metrics.parse_prometheus(obs_metrics.render_prometheus())
    assert parsed["lgbm_tpu_restart_budget_remaining"] == 3
    assert parsed["lgbm_tpu_last_restart_unix"] == 0
    assert parsed["lgbm_tpu_supervisor_world"] == 2
    assert parsed['lgbm_tpu_rank_iteration{rank="0"}'] == 7
    assert parsed['lgbm_tpu_rank_heartbeat_age_seconds{rank="0"}'] >= 0
    # rank 1 never stamped: -1, not absent — "one scrape answers it"
    assert parsed['lgbm_tpu_rank_heartbeat_age_seconds{rank="1"}'] == -1
    del sup


# ----------------------------------------------------------- serving front


@pytest.fixture(scope="module")
def tiny_server():
    X, y = _make_xy(300)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbose": -1},
                    lgb.Dataset(X, label=y, free_raw_data=False),
                    num_boost_round=2, verbose_eval=False)
    from lightgbm_tpu.serving import ModelServer
    counters.reset()
    srv = ModelServer(booster=bst, params={"verbose": -1,
                                           "latency_budget_ms": 0},
                      prewarm=False)
    srv.predict(X[:1])
    srv.predict(X[:40])
    yield srv
    srv.stop()


def test_serving_metrics_endpoint_contract(tiny_server):
    """Acceptance pin: GET /metrics on a live ModelServer returns
    Prometheus-parseable output reflecting the dispatch counters and the
    per-bucket latency histograms."""
    from http.server import ThreadingHTTPServer
    from lightgbm_tpu.serving import _run_http
    srv = tiny_server
    httpd_box = {}
    orig_init = ThreadingHTTPServer.__init__

    def patched(self, addr, handler):
        orig_init(self, ("127.0.0.1", 0), handler)
        httpd_box["srv"] = self

    ThreadingHTTPServer.__init__ = patched
    try:
        t = threading.Thread(target=lambda: _run_http(srv, 0), daemon=True)
        t.start()
        deadline = time.time() + 30
        while "srv" not in httpd_box and time.time() < deadline:
            time.sleep(0.01)
        port = httpd_box["srv"].server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=60) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == obs_metrics.CONTENT_TYPE
            body = r.read().decode()
    finally:
        ThreadingHTTPServer.__init__ = orig_init
        if "srv" in httpd_box:
            httpd_box["srv"].shutdown()
    _assert_prometheus_parseable(body)
    # per-bucket latency: p50/p99 gauges + the windowed histogram
    assert 'lgbm_tpu_serving_p50_ms{bucket="1"}' in body
    assert 'lgbm_tpu_serving_p99_ms{bucket="64"}' in body
    assert re.search(
        r'lgbm_tpu_serving_latency_ms_bucket\{bucket="1",le="0\.5"\}', body)
    assert 'le="+Inf"' in body
    # predict-dispatch identity counters ride the same scrape
    assert "lgbm_tpu_predict_dispatch_total{" in body
    assert "lgbm_tpu_serving_requests_total 2" in body
    assert "lgbm_tpu_serving_jit_entries" in body
    parsed = obs_metrics.parse_prometheus(body)
    assert parsed['lgbm_tpu_serving_latency_ms_bucket{bucket="1",le="+Inf"}'] \
        == parsed['lgbm_tpu_serving_latency_ms_count{bucket="1"}'] == 1


# ----------------------------------------------------------------- obs_diff


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_fixture():
    return {
        "metric": "higgs-like 1000k x28 binary GBDT (tpu, fused)",
        "value": 1.2, "unit": "trees/sec",
        "telemetry": {"observed_kernel": "fused",
                      "split_find_dispatch": {"impl=fused": 5}},
        "memory": {"measured_peak_bytes": 2_000_000_000},
        "serving": {"buckets": {
            "64": {"p50_ms": 1.0, "p99_ms": 2.0},
            "4096": {"p50_ms": 5.0, "p99_ms": 9.0}}},
        "leaves_sweep": {"marginal_ms_per_leaf": 3.0},
        "metrics_snapshot": {"schema_version": 1, "samples": {
            "lgbm_tpu_memory_peak_bytes": 2e9}},
    }


def test_obs_diff_bench_verdict_roundtrip(tmp_path):
    """Acceptance pin: identical recorded bench JSONs exit 0; an injected
    p99 regression exits nonzero naming the bucket."""
    od = _load_script("obs_diff")
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    doc = _bench_fixture()
    a.write_text(json.dumps(doc))
    b.write_text(json.dumps(doc))
    assert od.main([str(a), str(b)]) == 0
    doc["serving"]["buckets"]["4096"]["p99_ms"] = 30.0   # injected p99
    b.write_text(json.dumps(doc))
    assert od.main([str(a), str(b)]) == 1
    _, findings = od.compare(str(a), str(b),
                             {"throughput_pct": 10, "latency_pct": 25,
                              "p99_pct": 25, "memory_pct": 20})
    fails = [x for x in findings if x["severity"] == "fail"]
    assert len(fails) == 1 and fails[0]["check"] == "serving_p99_ms"
    assert "4096" in fails[0]["detail"]


def test_obs_diff_identity_and_memory_checks(tmp_path):
    od = _load_script("obs_diff")
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    base = _bench_fixture()
    a.write_text(json.dumps(base))
    # kernel identity mismatch is always FAIL (the decide_flips rule)
    doc = json.loads(json.dumps(base))
    doc["telemetry"]["observed_kernel"] = "einsum"
    b.write_text(json.dumps(doc))
    assert od.main([str(a), str(b)]) == 1
    # throughput drop beyond threshold
    doc = json.loads(json.dumps(base))
    doc["value"] = 1.0
    b.write_text(json.dumps(doc))
    assert od.main(["--threshold", "10", str(a), str(b)]) == 1
    assert od.main(["--threshold", "30", str(a), str(b)]) == 0
    # memory-peak growth
    doc = json.loads(json.dumps(base))
    doc["memory"]["measured_peak_bytes"] = 3_000_000_000
    b.write_text(json.dumps(doc))
    assert od.main([str(a), str(b)]) == 1
    # kind mismatch is a usage error, not a verdict
    t = tmp_path / "t.jsonl"
    t.write_text('{"name": "score", "ph": "X", "ts": 0, "dur": 1000}\n')
    assert od.main([str(a), str(t)]) == 2


def test_obs_diff_trace_steady_state_excludes_compile(tmp_path):
    """Trace kind: per-phase deltas judge the STEADY-STATE mean — an
    identical giant first (compile) firing never trips the verdict, a
    doubled steady state does."""
    od = _load_script("obs_diff")

    def write_trace(path, steady_ms):
        evs = []
        ts = 0.0
        for dur_ms in [500.0] + [steady_ms] * 4:    # first = compile
            evs.append({"name": "score", "ph": "X", "ts": ts,
                        "dur": dur_ms * 1e3})
            ts += dur_ms * 1e3 + 10
        path.write_text("\n".join(json.dumps(e) for e in evs) + "\n")

    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    write_trace(a, 10.0)
    write_trace(b, 10.5)          # +5%: within threshold, compile ignored
    assert od.main([str(a), str(b)]) == 0
    write_trace(b, 20.0)          # steady state doubled
    assert od.main([str(a), str(b)]) == 1


def test_obs_diff_metrics_snapshot_kind(tmp_path):
    od = _load_script("obs_diff")
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    base = {"schema_version": 1, "samples": {
        'lgbm_tpu_serving_p99_ms{bucket="64"}': 2.0,
        "lgbm_tpu_memory_peak_bytes": 1e9}}
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(base))
    assert od.main([str(a), str(b)]) == 0
    doc = json.loads(json.dumps(base))
    doc["samples"]['lgbm_tpu_serving_p99_ms{bucket="64"}'] = 4.0
    b.write_text(json.dumps(doc))
    assert od.main([str(a), str(b)]) == 1


def test_decide_flips_metrics_coverage_row():
    df = _load_script("decide_flips")
    assert df.metrics_row({}) is None
    row = df.metrics_row(_bench_fixture())
    assert "1 live samples" in row and "schema v1" in row


# ------------------------------------------------------------- event lint


_EVENT_CALL = re.compile(r"\.event\(")
_NAME_IN_HEAD = re.compile(r'"([a-z_]{3,})"')


def _emitted_event_names():
    names = set()
    # bench.py rides along: an undocumented event there is just as
    # unactionable as one in the library
    paths = glob.glob(os.path.join(ROOT, "lightgbm_tpu", "**", "*.py"),
                      recursive=True) + [os.path.join(ROOT, "bench.py")]
    for path in paths:
        src = open(path).read()
        for m in _EVENT_CALL.finditer(src):
            # the first-argument segment: everything before the first
            # kwarg '=' (covers literals, multi-line calls, and the
            # conditional "model_swap" if ... else "model_load" form)
            head = src[m.end():m.end() + 200].split("=", 1)[0]
            names.update(_NAME_IN_HEAD.findall(head))
    return names


def test_event_registry_lint():
    """Fast-tier grep lint (the PR 5/PR 7 family): every obs event type
    emitted anywhere in lightgbm_tpu/ must be documented in
    docs/OBSERVABILITY.md's structured-event table — an event no one can
    look up is telemetry no one can act on."""
    emitted = _emitted_event_names()
    assert len(emitted) >= 15, \
        f"lint pattern matched too few event sites — it broke: {emitted}"
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    table = doc.split("## Structured event registry", 1)
    assert len(table) == 2, "OBSERVABILITY.md lost its event registry"
    documented = set(re.findall(r"^\| `([a-z_]+)`", table[1], re.M))
    missing = sorted(emitted - documented)
    assert not missing, (
        "obs events emitted but not documented in docs/OBSERVABILITY.md's "
        f"event table: {missing}")


# ----------------------------------------------------------- timer steady


def test_phase_timers_steady_means():
    from lightgbm_tpu.utils.timer import PhaseTimers
    t = PhaseTimers()
    t.add("score", 10.0)            # compile-inclusive first firing
    t.add("score", 0.5)
    t.add("score", 0.7)
    t.add("once", 2.0)
    means = t.steady_means()
    assert means["score"] == pytest.approx(0.6)    # first excluded
    assert means["once"] == pytest.approx(2.0)     # single firing: itself
    t.reset()
    assert t.steady_means() == {} and t.first == {}
