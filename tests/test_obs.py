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


def test_cpu_training_emits_iteration_and_split_span_tree(traced_training):
    path, _ = traced_training
    events = obs_report.load_events(path)
    x_names = [e["name"] for e in events if e.get("ph") == "X"]
    # per-iteration spans from boosting, per-phase from the timers sink,
    # per-split (trace-time) spans from the grower
    for name in ("train", "iteration", "boosting", "tree", "score",
                 "histogram", "split_find", "partition"):
        assert name in x_names, f"missing span {name!r} in {sorted(set(x_names))}"
    assert x_names.count("iteration") == 2
    # iteration spans nest inside the train span
    train_ev = next(e for e in events if e["name"] == "train")
    for it in (e for e in events if e["name"] == "iteration"):
        assert train_ev["ts"] <= it["ts"] + 1e-3
        assert it["ts"] + it["dur"] <= train_ev["ts"] + train_ev["dur"] + 1e-3
    # the grower's split spans carry the call-site tag
    hist_sites = {e.get("args", {}).get("site")
                  for e in events if e["name"] == "histogram"}
    assert {"root", "split"} <= hist_sites


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
        "interpret=True,method=fused,site=t": 1}
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
