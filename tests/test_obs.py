"""Telemetry subsystem (lightgbm_tpu.obs): spans, counters, collectives,
report CLI, and the honesty checks built on them."""
import importlib
import importlib.util
import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import collectives as obs_coll
from lightgbm_tpu.obs import report as obs_report
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.obs.counters import counters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_xy(n=500, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X @ rng.randn(f) > 0).astype(np.float32)
    return X, y


def _train(trace_path=None, extra=None, rounds=2):
    X, y = _make_xy()
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
              "verbose": -1}
    if trace_path is not None:
        params["trace_path"] = trace_path
    params.update(extra or {})
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    return lgb.train(params, ds, num_boost_round=rounds, verbose_eval=False)


@pytest.fixture(scope="module")
def traced_training(tmp_path_factory):
    """One 2-iteration CPU training with a Chrome-trace (.json) output;
    returns (path, counter snapshot taken right after training)."""
    path = str(tmp_path_factory.mktemp("obs") / "train_trace.json")
    _train(trace_path=path)
    return path, counters.snapshot()


# ---------------------------------------------------------------- tracer core


def test_span_nesting_and_chrome_json(tmp_path):
    tr = obs_trace.Tracer(str(tmp_path / "t.json"))
    with tr.span("outer", kind="test"):
        with tr.span("inner"):
            pass
    out = tr.write()
    with open(out) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    by_name = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert set(by_name) == {"outer", "inner"}
    outer, inner = by_name["outer"], by_name["inner"]
    # X events carry microsecond ts/dur and pid/tid; nesting is expressed
    # through ts containment (how Chrome rebuilds the flame graph)
    for e in (outer, inner):
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"kind": "test"}
    # the file is self-contained: the counter snapshot rides as the final
    # telemetry.summary event
    assert events[-1]["name"] == "telemetry.summary"
    assert events[-1]["args"]["kind"] == "counters"


def test_jsonl_output_and_partial_tolerance(tmp_path):
    p = str(tmp_path / "t.jsonl")
    tr = obs_trace.Tracer(p)
    with tr.span("a"):
        pass
    tr.instant("mark", reason="x")
    tr.write()
    events = obs_report.load_events(p)
    assert {"a", "mark"} <= {e["name"] for e in events}
    # a torn tail line (killed child) must not break parsing
    with open(p, "a") as f:
        f.write('{"name": "torn')
    events2 = obs_report.load_events(p)
    assert len(events2) == len(events)


def test_disabled_tracer_is_allocation_free():
    obs_trace.stop()          # ensure the module default state
    t = obs_trace.get_tracer()
    assert t is obs_trace.NULL_TRACER and not t.enabled
    # the disabled fast path hands back ONE shared context manager —
    # no per-span allocation in the hot loop
    assert t.span("a", x=1) is t.span("b") is obs_trace.NULL_SPAN
    t.instant("nope")
    t.summary("nope", {})
    assert t.events() == []


def test_phase_timers_feed_the_tracer_sink():
    from lightgbm_tpu.utils.timer import PhaseTimers
    with obs_trace.tracing() as tr:
        t = PhaseTimers()
        with t.phase("zz_phase"):
            pass
        t.report("zz timers")
        events = tr.events()
    assert any(e["name"] == "zz_phase" and e["ph"] == "X" for e in events)
    summaries = [e for e in events if e["name"] == "telemetry.summary"]
    assert any(e["args"]["kind"] == "zz timers"
               and "zz_phase" in e["args"]["payload"]["seconds"]
               for e in summaries)
    assert obs_trace.get_tracer() is obs_trace.NULL_TRACER


# ------------------------------------------------------------ training spans


def test_cpu_training_emits_iteration_phase_tree_and_compile_counter(
        traced_training):
    path, snap = traced_training
    events = obs_report.load_events(path)
    spans = [e for e in events if e.get("ph") == "X"]
    x_names = [e["name"] for e in spans]
    # set-up boundaries, per-iteration spans, the phases inside them and
    # the children that make the tree phase's self time mean something
    for name in ("dataset.construct", "setup.device", "setup.grower",
                 "train", "iteration", "boosting", "bagging", "tree",
                 "tree.wait", "tree.host", "score"):
        assert name in x_names, f"missing span {name!r} in {sorted(set(x_names))}"
    assert x_names.count("iteration") == 2
    # jitted code opens no host span: it would fire once, at trace time
    assert not {"histogram", "split_find", "partition"} & set(x_names)
    assert not any("traced" in e.get("args", {}) for e in spans)

    def inside(child, parent):
        return (parent["ts"] <= child["ts"] + 1e-3 and child["ts"]
                + child["dur"] <= parent["ts"] + parent["dur"] + 1e-3)

    train_ev = next(e for e in spans if e["name"] == "train")
    iters = [e for e in spans if e["name"] == "iteration"]
    assert [e["args"]["index"] for e in iters] == [0, 1]
    assert all(inside(it, train_ev) for it in iters)
    # every phase lies in the iteration whose index it carries
    for name in ("boosting", "bagging", "tree", "score"):
        for ev in (e for e in spans if e["name"] == name):
            assert inside(ev, iters[ev["args"]["iteration"]]), name
    grower = next(e for e in spans if e["name"] == "setup.grower")
    assert inside(grower, next(e for e in spans
                               if e["name"] == "setup.device"))
    # what the trace-time spans used to hint at, measured: the grower's
    # trace / lower / compile seconds, and one compile call
    from lightgbm_tpu.grower import SCOPE_REVISION
    fun = f"fun=grow_tree_s{SCOPE_REVISION}"
    secs = snap["counters"]["compile_seconds"]
    for stage in ("trace", "lower", "backend"):
        assert secs[f"{fun},stage={stage}"] > 0, sorted(secs)
    assert snap["counters"]["compile_calls"][fun] >= 1


def test_report_renders_phase_and_kernel_tables(traced_training):
    path, _ = traced_training
    text = obs_report.render(path)
    assert "Per-phase spans" in text
    assert "Per-kernel dispatch identity" in text
    assert "iteration" in text
    # CPU default histogram path is segment — the observed identity line
    assert "Observed histogram kernel identity:** `segment`" in text


def test_cli_round_trips_a_training_trace(traced_training):
    path, _ = traced_training
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-m", "lightgbm_tpu.obs", path],
                       capture_output=True, text=True, cwd=ROOT, env=env,
                       timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Per-phase spans" in r.stdout
    assert "iteration" in r.stdout
    r2 = subprocess.run([sys.executable, "-m", "lightgbm_tpu.obs", "--json",
                         path], capture_output=True, text=True, cwd=ROOT,
                        env=env, timeout=240)
    assert r2.returncode == 0
    doc = json.loads(r2.stdout)
    assert any(p["span"] == "iteration" for p in doc["phases"])


# ------------------------------------- always-on spans, scopes and counters


def _capture_spans(tmp_path, params, rounds=2, valid=True):
    """A plain ``lgb.train`` under ``jax.profiler.start_trace``; returns the
    host-plane events of the capture as (name, start ns, end ns, stats)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    X, y = _make_xy()
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    log_dir = str(tmp_path / "capture")
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        lgb.train(dict({"objective": "binary", "num_leaves": 7,
                        "min_data_in_leaf": 5, "verbose": -1,
                        "pipeline_trees": False,
                        "metric": ["auc", "binary_logloss"]}, **params),
                  ds, num_boost_round=rounds, verbose_eval=False,
                  valid_sets=[ds] if valid else None)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns, ev.start_ns
                            + ev.duration_ns, dict(ev.stats)))
    return out


@pytest.mark.parametrize("tracer", ["off", "trace_path"])
def test_profiler_capture_holds_program_spans_without_telemetry(
        tmp_path, tracer):
    """A profiler capture needs no telemetry switch: ``lgb:iteration`` and
    its phases are in it, ONE annotation per span in either tracer state,
    and no host span of the program goes without the prefix."""
    params = {} if tracer == "off" else {
        "trace_path": str(tmp_path / "t.json")}
    events = _capture_spans(tmp_path, params, rounds=2)
    spans = [e for e in events if e[0].startswith("lgb:")]
    count = {}
    for name, *_ in spans:
        count[name] = count.get(name, 0) + 1
    # one per span: 2 iterations, one tree each, evaluated once each
    for name, n in (("lgb:train", 1), ("lgb:iteration", 2),
                    ("lgb:boosting", 2), ("lgb:bagging", 2),
                    ("lgb:tree", 2), ("lgb:tree.wait", 2),
                    ("lgb:tree.host", 2), ("lgb:score", 2),
                    ("lgb:metric", 2), ("lgb:metric.fetch", 2),
                    ("lgb:metric.auc", 2), ("lgb:metric.binary_logloss", 2),
                    ("lgb:dataset.construct", 1), ("lgb:setup.device", 1),
                    ("lgb:setup.grower", 1)):
        assert count.get(name) == n, (name, count)
    bare = {"train", "iteration", "boosting", "bagging", "tree", "score",
            "metric", "histogram", "split_find", "partition"}
    assert not bare & {e[0] for e in events}

    def inside(child, parent):
        return parent[1] <= child[1] and child[2] <= parent[2]

    iters = sorted((e for e in spans if e[0] == "lgb:iteration"),
                   key=lambda e: e[1])
    assert [int(e[3]["index"]) for e in iters] == [0, 1]
    for name in ("lgb:boosting", "lgb:bagging", "lgb:tree", "lgb:score"):
        for ev in (e for e in spans if e[0] == name):
            assert inside(ev, iters[int(ev[3]["iteration"])]), name
    trees = {int(e[3]["iteration"]): e for e in spans if e[0] == "lgb:tree"}
    for name in ("lgb:tree.wait", "lgb:tree.host"):
        for ev in (e for e in spans if e[0] == name):
            assert inside(ev, trees[int(ev[3]["iteration"])]), name
    # the metric span covers the fetch of the scores and each metric
    metrics = sorted((e for e in spans if e[0] == "lgb:metric"),
                     key=lambda e: e[1])
    for name in ("lgb:metric.fetch", "lgb:metric.auc",
                 "lgb:metric.binary_logloss"):
        kids = sorted((e for e in spans if e[0] == name), key=lambda e: e[1])
        assert all(inside(k, m) for k, m in zip(kids, metrics)), name
    assert {e[3]["data"] for e in metrics} == {"training"}


def test_metric_phase_covers_the_score_fetch(tmp_path, monkeypatch):
    """``eval_*`` hand ``_eval`` the DEVICE scores: the host copy is made
    inside the metric phase, under ``metric.fetch``."""
    from lightgbm_tpu.boosting import GBDT
    seen = []
    real = GBDT._eval_scores

    def spy(self, scores):
        seen.append(type(scores).__module__.split(".")[0])
        return real(self, scores)

    monkeypatch.setattr(GBDT, "_eval_scores", spy)
    path = str(tmp_path / "t.json")
    X, y = _make_xy()
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
               "min_data_in_leaf": 5, "metric": "auc", "trace_path": path},
              ds, num_boost_round=1, verbose_eval=False, valid_sets=[ds])
    assert seen and "numpy" not in seen, seen
    events = obs_report.load_events(path)
    metric = next(e for e in events if e["name"] == "metric")
    for name in ("metric.fetch", "metric.auc"):
        kid = next(e for e in events if e["name"] == name)
        assert metric["ts"] <= kid["ts"]
        assert kid["ts"] + kid["dur"] <= metric["ts"] + metric["dur"] + 1e-3
    assert metric["args"]["iteration"] == 0      # the tree it evaluates
    assert metric["args"]["data"] == "training"


def test_phase_primitive_counts_with_the_tracer_off():
    """Disabled tracer: still the shared NULL_SPAN (nothing recorded), and
    the phase still lands in the process-wide registry and the timers."""
    from lightgbm_tpu.utils.timer import PhaseTimers
    obs_trace.stop()
    assert obs_trace.get_tracer().span("zz") is obs_trace.NULL_SPAN
    before = counters.get("phase_calls").get("phase=zz_off", 0)
    ph = obs_trace.phase("zz_off", iteration=3)
    with ph:
        assert ph.span is obs_trace.NULL_SPAN
    t = PhaseTimers()
    with t.phase("zz_off"):
        pass
    assert counters.get("phase_calls")["phase=zz_off"] == before + 2
    assert counters.get("phase_seconds")["phase=zz_off"] >= ph.seconds > 0
    # the timers keep their own totals from the same measurement
    assert t.counts["zz_off"] == 1 and t.seconds["zz_off"] > 0
    assert obs_trace.get_tracer().events() == []


def test_phase_and_compile_counters_after_plain_training():
    """No telemetry parameter: the registry still says where set-up and
    the loop spent their seconds, and which function compiled."""
    calls0 = counters.get("phase_calls")
    _train(rounds=2)
    secs, calls = counters.get("phase_seconds"), counters.get("phase_calls")
    for phase, n in (("dataset.construct", 1), ("setup.device", 1),
                     ("setup.grower", 1), ("train", 1), ("iteration", 2),
                     ("boosting", 2), ("tree", 2), ("score", 2)):
        key = f"phase={phase}"
        assert secs[key] > 0, key
        assert calls[key] - calls0.get(key, 0) >= n, key
    comp = counters.get("compile_seconds")
    for stage in ("trace", "lower", "backend"):
        assert comp[f"fun=get_gradients,stage={stage}"] > 0, sorted(comp)
    # the labels are closed over as a constant, so a second data set of
    # the SAME shape compiles the gradient program again: pinned here for
    # the perf_opt PR that passes them as an argument to undo
    n1 = counters.get("compile_calls")["fun=get_gradients"]
    X, y = _make_xy(seed=1)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
               "min_data_in_leaf": 5}, lgb.Dataset(X, label=y),
              num_boost_round=1, verbose_eval=False)
    assert counters.get("compile_calls")["fun=get_gradients"] == n1 + 1


def test_training_reset_keeps_what_was_counted_before_it():
    """``Dataset.construct`` may run before ``train()`` (the benchmark's
    driver does): the per-training reset keeps the phase and compile
    counters and still clears the kernel-identity evidence."""
    X, y = _make_xy()
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    counters.reset()
    ds.construct()
    binned = counters.get("phase_seconds")["phase=dataset.construct"]
    assert binned > 0
    counters.inc("hist_dispatch", 7, method="einsum", site="stale")
    lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
               "min_data_in_leaf": 5, "telemetry": True}, ds,
              num_boost_round=1, verbose_eval=False)
    assert counters.get("phase_seconds")["phase=dataset.construct"] == binned
    assert counters.get("phase_calls")["phase=dataset.construct"] == 1
    assert "method=einsum,site=stale" not in counters.get("hist_dispatch")


def test_compile_listener_charges_nested_traces_once():
    """jit traces nest and each reports its whole duration: the listener
    charges every second to the innermost function only."""
    # (the package attribute ``obs.counters`` is the registry instance)
    counters_mod = importlib.import_module("lightgbm_tpu.obs.counters")
    ev = "/jax/core/compile/jaxpr_trace_duration"

    def seconds(fun):
        return counters.get("compile_seconds").get(
            f"fun={fun},stage=trace", 0.0)

    inner0, outer0 = seconds("zz_inner"), seconds("zz_outer")
    # made-up durations would claim the real traces of the last second
    counters_mod._open_traces.done = []
    counters_mod._on_compile_duration(ev, 0.25, fun_name="zz_inner")
    counters_mod._on_compile_duration(ev, 1.0, fun_name="zz_outer")
    assert seconds("zz_inner") - inner0 == pytest.approx(0.25)
    assert seconds("zz_outer") - outer0 == pytest.approx(0.75)
    # lower and backend are one function's own; jit(f) and f are one name
    calls0 = counters.get("compile_calls").get("fun=zz_outer", 0)
    counters_mod._on_compile_duration(
        "/jax/core/compile/backend_compile_duration", 0.5,
        fun_name="jit(zz_outer)")
    assert counters.get("compile_seconds")[
        "fun=zz_outer,stage=backend"] == pytest.approx(0.5)
    assert counters.get("compile_calls")["fun=zz_outer"] == calls0 + 1
    hits0 = counters.total("compile_cache_hits")
    counters_mod._on_compile_event("/jax/compilation_cache/cache_hits")
    counters_mod._on_compile_event("/jax/compilation_cache/cache_misses")
    assert counters.total("compile_cache_hits") == hits0 + 1


def test_loop_programs_carry_their_scopes():
    """``objective`` and ``score_update`` are entered inside the traced
    functions, so they are in the lowered program whatever the cache
    holds: a device trace attributes the loop's own programs by them."""
    bst = _train(rounds=1)
    gbdt = bst.inner
    grad = gbdt._grad_fn.lower(gbdt.scores).as_text(debug_info=True)
    assert "jit(get_gradients)/objective/" in grad
    k, lr = gbdt.scores[0], np.float32(0.1)
    leaf = np.zeros(gbdt.num_data, np.int32)
    upd = gbdt._update_score.lower(
        k, np.zeros(7, np.float32), leaf, lr).as_text(debug_info=True)
    assert "jit(_update_score)/score_update/" in upd
    nodes = np.full(6, -1, np.int32)
    from lightgbm_tpu.boosting import _route_update_score
    routed = _route_update_score.lower(
        k, gbdt.bins, nodes, nodes, np.zeros(6, bool), nodes, nodes,
        gbdt.feat_info, np.zeros(6, bool), np.zeros((6, 1), bool),
        np.zeros(7, np.float32), lr).as_text(debug_info=True)
    assert "/score_update/" in routed and "predict_binned_leaf" in routed
    # neither token is part of another scope's or a source file's name
    assert "objective" not in upd and "score_update" not in grad


def test_hbm_gauges_record_the_allocators_peaks():
    """After the first tree the allocator's two peaks are gauged, to be
    read beside ``hbm_predicted_peak_bytes``; off the chip there are no
    allocator statistics and no gauges."""
    from lightgbm_tpu.obs import memory as obs_memory

    class Chip:
        def memory_stats(self):
            return {"peak_bytes_in_use": 1290, "peak_bytes_reserved": 10760,
                    "bytes_limit": 16000}

    _train(rounds=1)
    gauges = counters.snapshot()["gauges"]
    assert gauges["hbm_predicted_peak_bytes"] > 0
    assert "hbm_reserved_peak_bytes" not in gauges      # CPU: no stats
    obs_memory.gauge_hbm_peaks(Chip())
    gauges = counters.snapshot()["gauges"]
    assert gauges["hbm_in_use_peak_bytes"] == 1290
    assert gauges["hbm_reserved_peak_bytes"] == 10760
    counters.reset()


# ------------------------------------------------------------------- counters


def test_counter_registry_resets_between_trainings(tmp_path):
    _train(extra={"telemetry": True})
    first = counters.get("hist_dispatch")
    assert first and sum(first.values()) > 0
    _train(extra={"telemetry": True})
    second = counters.get("hist_dispatch")
    # identical training => identical trace-time dispatch counts; without
    # the per-training reset the second run would accumulate to ~2x
    assert second == first


def test_dispatch_identity_einsum_vs_interpret_fused():
    from lightgbm_tpu.data.packing import pack_fused_panel
    from lightgbm_tpu.ops.histogram import (subset_histogram,
                                            subset_histogram_fused_local)
    rng = np.random.RandomState(3)
    rows = rng.randint(0, 16, size=(256, 8)).astype(np.uint8)
    g = rng.randn(256).astype(np.float32)
    h = np.abs(rng.randn(256)).astype(np.float32)
    c = np.ones(256, np.float32)

    counters.reset()
    h_e = subset_histogram(rows, g, h, c, 16, method="einsum", site="t")
    assert counters.get("hist_dispatch") == {
        "interpret=False,method=einsum,site=t": 1}

    counters.reset()
    zrow = np.zeros((1, 8), np.uint8)
    zw = np.zeros((1,), np.float32)
    panel, per = pack_fused_panel(np.concatenate([rows, zrow]),
                                  np.concatenate([g, zw]),
                                  np.concatenate([h, zw]),
                                  np.concatenate([c, zw]))
    row_leaf = np.zeros(256, np.int32)
    h_f = subset_histogram_fused_local(row_leaf, 0, panel, 8, per, 16,
                                       interpret=True, site="t")
    assert counters.observed_kernel() == "fused"
    assert counters.get("hist_dispatch") == {
        "col_tiles=1,fetch=rows,hi=16,interpret=True,method=fused,site=t,"
        "width=16": 1}
    # fused accumulates in bf16 hi/lo pairs (~f32 accuracy, not exact)
    np.testing.assert_allclose(np.asarray(h_e), np.asarray(h_f),
                               rtol=1e-3, atol=1e-4)


def test_observed_kernel_matches_hist_method():
    _train(extra={"telemetry": True})                      # CPU default
    assert counters.observed_kernel() == "segment"
    _train(extra={"telemetry": True, "cpu_hist_method": "einsum"})
    assert counters.observed_kernel() == "einsum"


def test_event_ring_buffer_is_bounded_with_overflow_counter():
    """Satellite of the memory-observability PR: long trainings with
    telemetry on must not grow host memory without bound — the event store
    is a ring that counts what it drops instead of leaking."""
    counters.reset()
    cap = counters.MAX_EVENTS
    for i in range(cap + 7):
        counters.event("spam", i=i)
    evs = counters.events("spam")
    assert len(evs) == cap
    assert evs[0]["i"] == 7 and evs[-1]["i"] == cap + 6   # oldest evicted
    assert counters.events_dropped() == 7
    snap = counters.snapshot()
    assert snap["events_dropped"] == 7
    counters.reset()
    assert counters.events_dropped() == 0


def test_events_and_spans_carry_process_index(tmp_path):
    counters.reset()
    counters.event("probe")
    assert counters.events("probe")[0]["proc"] == 0    # single-process CPU
    assert counters.snapshot()["process_index"] == 0
    tr = obs_trace.Tracer(str(tmp_path / "t.json"))
    with tr.span("a"):
        pass
    tr.instant("b")
    assert all(e["proc"] == 0 for e in tr.events())


def test_cli_merges_multiple_traces_rank_tagged(tmp_path):
    """Satellite: the report CLI accepts several trace files (one per
    process of a multi-host run) and merges them into ONE rank-tagged
    report — the first concrete step on the ROADMAP multi-process
    coordination item."""
    paths = []
    for rank in (0, 1):
        p = str(tmp_path / f"r{rank}.jsonl")
        tr = obs_trace.Tracer(p)
        tr.proc = rank                   # what a rank-r process would stamp
        with tr.span("iteration", index=0):
            pass
        # per-rank serving stats + HLO census (what a GSPMD rank running
        # a server would embed): the merged report must keep BOTH ranks'
        # sections, not just the last file's
        counters.reset()
        counters.inc("hlo_collective_calls", value=2 + rank,
                     op="all-reduce", label="grow")
        counters.inc("hlo_collective_bytes", value=1024 * (rank + 1),
                     op="all-reduce", label="grow")
        tr.summary("serving stats",
                   {"requests": 10 + rank, "rows": 100, "batches": 3,
                    "qps": 5.0, "rows_per_s": 50.0, "swaps": 0,
                    "buckets": {"64": {"count": 10, "p50_ms": 1.0 + rank,
                                       "p99_ms": 2.0, "max_ms": 3.0,
                                       "hist": {"<=1ms": 10}}}})
        tr.write()
        paths.append(p)
    counters.reset()
    text = obs_report.render(paths)
    assert "[r0] iteration" in text and "[r1] iteration" in text
    assert "rank 0" in text and "rank 1" in text
    # per-rank serving sections (PR 5 left this single-trace only)
    assert "## Serving / predict — rank 0" in text
    assert "## Serving / predict — rank 1" in text
    assert "10 requests" in text and "11 requests" in text
    # the census table keeps every rank's row attributable
    census = text.split("Compiled-HLO collective census", 1)[1]
    assert "| 0 | all-reduce | grow | 2 | 1024 |" in census
    assert "| 1 | all-reduce | grow | 3 | 2048 |" in census
    # the --json twin carries one entry per file with its rank, the
    # per-rank serving/census entries, and a schema stamp
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.obs", "--json", *paths],
        capture_output=True, text=True, cwd=ROOT, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=ROOT + os.pathsep
                 + os.environ.get("PYTHONPATH", "")))
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(r.stdout)
    assert doc["schema_version"] == obs_report.REPORT_SCHEMA_VERSION
    assert [f["rank"] for f in doc["files"]] == [0, 1]
    assert [f["serving_stats"]["requests"] for f in doc["files"]] == [10, 11]
    assert all("op=all-reduce" in ",".join(f["hlo_collectives"])
               for f in doc["files"])


# ---------------------------------------------------------------- collectives


def test_collectives_intercept_records_traced_psum():
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))

    def f(x):
        return lax.psum(x, "d")

    sm = shard_map(f, mesh=mesh, in_specs=(P("d"),), out_specs=P(),
                   check_vma=False)
    counters.reset()
    with obs_coll.intercept(count=True) as records:
        jax.jit(sm).lower(jax.ShapeDtypeStruct((8,), jnp.float32))
    assert len(records) == 1
    rec = records[0]
    assert rec["op"] == "psum" and rec["axis"] == "d"
    assert rec["bytes"] == 4 * 4          # local shard: 4 rows x f32
    assert rec["per_split"] is False
    assert counters.total("collective_calls") == 1
    # interception is transactional: lax is restored afterwards
    assert lax.psum is not records and "wrap" not in repr(lax.psum)


def test_distributed_strategies_count_collectives():
    """Tracing the data-parallel grower populates the collective counters
    (the runtime accounting parallel/learner.py feeds via note_collective)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig
    from lightgbm_tpu.parallel.learner import make_distributed_grower
    counters.reset()
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    cfg = GrowerConfig(num_leaves=4, max_bin=15, min_data_in_leaf=1,
                       hist_method="segment")
    fn = make_distributed_grower(cfg, mesh, "data")
    bins = jax.ShapeDtypeStruct((1024, 8), jnp.uint8)
    w = jax.ShapeDtypeStruct((1024,), jnp.float32)
    meta = FeatureMeta(
        num_bin=jax.ShapeDtypeStruct((8,), jnp.int32),
        missing_type=jax.ShapeDtypeStruct((8,), jnp.int32),
        default_bin=jax.ShapeDtypeStruct((8,), jnp.int32),
        is_categorical=jax.ShapeDtypeStruct((8,), jnp.bool_))
    fv = jax.ShapeDtypeStruct((8,), jnp.bool_)
    fn.lower(bins, w, w, w, meta, fv)
    calls = counters.get("collective_calls")
    assert any("site=reduce_hist" in k for k in calls)
    assert any("site=reduce_scalar" in k for k in calls)
    assert counters.total("collective_bytes") > 0


# ------------------------------------------------------- honesty + utilities


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_decide_flips_rejects_kernel_identity_mismatch():
    df = _load_script("decide_flips")
    base = {"metric": "higgs-like 1000k x28 ... (tpu, fused)", "value": 1.2}
    assert df.clean_tpu(dict(base, telemetry={"observed_kernel": "fused"}))
    # pre-telemetry artifacts keep deciding (no evidence either way)
    assert df.clean_tpu(dict(base))
    # the child's mismatch flag vetoes the artifact
    assert not df.clean_tpu(dict(base, kernel_mismatch=True,
                                 degraded="kernel identity mismatch"))
    # telemetry disagreeing with the rung label vetoes even without flags
    assert not df.clean_tpu(dict(base,
                                 telemetry={"observed_kernel": "pallas"}))
    pallas = {"metric": "... (tpu, pallas)", "value": 1.0,
              "telemetry": {"observed_kernel": "einsum"}}
    assert not df.clean_tpu(pallas)
    assert df.label_kernel(base) == "fused"
    assert df.observed_kernel(pallas) == "einsum"


def test_log_reimport_never_double_attaches_handlers():
    from lightgbm_tpu.utils import log as log_mod
    logger = logging.getLogger("lightgbm_tpu")

    def owned():
        return [h for h in logger.handlers
                if getattr(h, "_lightgbm_tpu_owned", False)]

    assert len(owned()) == 1
    importlib.reload(log_mod)
    assert len(owned()) == 1
    # even with a foreign handler attached first (pytest's logging plugin
    # pattern), a reload must neither skip nor duplicate ours
    foreign = logging.NullHandler()
    logger.addHandler(foreign)
    try:
        importlib.reload(log_mod)
        assert len(owned()) == 1
    finally:
        logger.removeHandler(foreign)
