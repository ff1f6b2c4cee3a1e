"""Device-memory observability (lightgbm_tpu.obs.memory): the disarmed
no-op fast path, the tagged live-array census, compiled-executable
memory analysis, predicted-vs-measured agreement of the fit model, the
pre-compile hbm_budget pre-flight, and the source lint pairing every
warn-once layout downgrade with an obs event."""
import glob
import json
import os
import re
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import memory as obs_memory
from lightgbm_tpu.obs.counters import counters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train(n=2000, f=16, extra=None, rounds=2, leaves=15):
    rng = np.random.RandomState(7)
    X = rng.randn(n, f).astype(np.float32)
    y = (X @ rng.randn(f) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": leaves,
              "min_data_in_leaf": 5, "verbose": -1}
    params.update(extra or {})
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    return lgb.train(params, ds, num_boost_round=rounds, verbose_eval=False)


# ------------------------------------------------------- singleton fast path


def test_disarmed_monitor_is_shared_noop():
    obs_memory.stop()      # ensure the module default state
    m = obs_memory.get_memory()
    assert m is obs_memory.NULL_MEMORY and not m.enabled
    # every disarmed operation is a constant no-op: nothing sampled,
    # nothing allocated, the same singleton handed back every time
    assert m.sample("iteration") is None
    assert m.measured_peak() == 0 and m.top_residents() == []
    assert m.summary() == {}
    assert obs_memory.get_memory() is m
    # annotate on the shared NULL_SPAN must not grow it an args dict
    from lightgbm_tpu.obs.trace import NULL_SPAN
    m.annotate(NULL_SPAN)
    assert not hasattr(NULL_SPAN, "_args")


def test_disarmed_training_records_no_memory_gauges():
    counters.reset()
    _train(n=400, f=8)     # no telemetry param -> monitor stays disarmed
    gauges = counters.snapshot()["gauges"]
    assert not any(k.startswith("memory_") for k in gauges)


# ------------------------------------------------------------- live census


def test_census_tags_training_residents():
    _train(n=3000, f=12, extra={"telemetry": True})
    events = counters.events("memory_summary")
    assert len(events) == 1
    summ = events[0]
    assert summ["source"] == "live_census"   # CPU tier has no memory_stats
    tags = dict(r.split("=") for r in summ["top_residents"])
    # the census attributes the big residents to their owners
    assert "binned" in tags and "scores" in tags
    assert int(tags["binned"]) == 3000 * 12       # uint8 binned matrix
    g = counters.snapshot()["gauges"]
    assert g["memory_measured_peak_bytes"] >= g["memory_baseline_bytes"]


def test_phase_spans_carry_peak_bytes(tmp_path):
    path = str(tmp_path / "t.json")
    _train(n=1500, f=8, extra={"trace_path": path})
    from lightgbm_tpu.obs import report as obs_report
    events = obs_report.load_events(path)
    annotated = [e for e in events if e.get("ph") == "X"
                 and "peak_bytes" in e.get("args", {})]
    names = {e["name"] for e in annotated}
    # the PhaseTimers phases get the memory annotation for free
    assert {"boosting", "tree"} <= names
    assert all(e["args"]["peak_bytes"] > 0 for e in annotated)
    # and the rendered report grows the peak MB column
    text = obs_report.render(path)
    assert "peak MB" in text and "## Memory" in text


# --------------------------------------------------- predicted vs measured

# Documented predicted-vs-measured acceptance band for the RESIDENT model
# on the CPU census (obs/memory.RESIDENT_TOLERANCE): measured/predicted in
# [0.65, 1.35].  The census counts every live jax array including small
# untracked ones (feature meta, tree SoA, jit constants), the model counts
# the O(N) payloads — at bench-like shapes the difference is percent-level,
# the band leaves room for allocator/layout variation across jax versions.


@pytest.mark.parametrize("n,f", [(60_000, 20), (8_000, 120)])
def test_predicted_vs_measured_agree_on_cpu(n, f):
    baseline = obs_memory.live_census()["total_bytes"]
    bst = _train(n=n, f=f, extra={"telemetry": True}, rounds=3)
    pred = bst.inner.memory_prediction
    g = counters.snapshot()["gauges"]
    measured = g["memory_measured_peak_bytes"] - baseline
    ratio = measured / pred["resident_bytes"]
    tol = obs_memory.RESIDENT_TOLERANCE
    assert 1 - tol <= ratio <= 1 + tol, (
        f"measured {measured} vs predicted resident "
        f"{pred['resident_bytes']} (ratio {ratio:.3f}) outside the "
        f"documented +-{tol:.0%} band at {n}x{f}")


def test_predict_hbm_reproduces_the_memory_doc_constants():
    # the Epsilon-like shape's hist_store — the headline number the
    # hand-computed docs/MEMORY.md table carried (now generated)
    pred = obs_memory.predict_hbm(rows=400_000, features=2000, bins=255,
                                  leaves=255)
    assert pred["transients"]["hist_store"] == 255 * 2000 * 255 * 3 * 4
    assert pred["residents"]["binned"] == 400_000 * 2000
    # monotonic in every axis the model claims to price
    lo = obs_memory.predict_hbm(rows=10_000, features=28)
    hi = obs_memory.predict_hbm(rows=20_000, features=28)
    assert hi["peak_bytes"] > lo["peak_bytes"]
    wide = obs_memory.predict_hbm(rows=10_000, features=56)
    assert wide["peak_bytes"] > lo["peak_bytes"]


# ------------------------------------------------------------- static leg


def test_executable_memory_records_gauges_and_event():
    counters.reset()

    def f(x):
        return jnp.sort(x) + 1.0

    x = jnp.zeros((4096,), jnp.float32)
    m = obs_memory.analyze_jitted(f, x, label="probe")
    assert m is not None
    assert m["argument_bytes"] == 4096 * 4
    assert m["output_bytes"] == 4096 * 4
    assert m["peak_bytes"] == (m["argument_bytes"] + m["output_bytes"]
                               + m["temp_bytes"])
    g = counters.snapshot()["gauges"]
    assert g["exec_probe_peak_bytes"] == m["peak_bytes"]
    evs = counters.events("exec_memory")
    assert evs and evs[-1]["label"] == "probe"


# --------------------------------------------------------------- pre-flight


def test_preflight_raises_under_tiny_hbm_budget():
    with pytest.raises(RuntimeError, match="hbm_budget"):
        _train(n=2000, f=16, extra={"hbm_budget": 10_000})
    # the structured event names the verdict even though training died
    evs = counters.events("hbm_preflight")
    assert evs and evs[-1]["verdict"] == "over_budget"


def test_preflight_warns_over_detected_capacity(monkeypatch, caplog):
    pred = obs_memory.predict_hbm(rows=1_000_000, features=28)
    monkeypatch.setattr(obs_memory, "device_capacity", lambda device=None:
                        pred["peak_bytes"] // 2)
    with caplog.at_level("WARNING", logger="lightgbm_tpu"):
        out = obs_memory.preflight(pred, hbm_budget=0.0, context="test")
    assert out["verdict"] == "over_capacity"
    assert any("exceeds device capacity" in r.message for r in caplog.records)


def test_preflight_ok_within_budget():
    pred = obs_memory.predict_hbm(rows=1000, features=8)
    out = obs_memory.preflight(pred, hbm_budget=16e9)
    assert out["verdict"] == "ok"
    assert counters.snapshot()["gauges"]["hbm_predicted_peak_bytes"] == \
        pred["peak_bytes"]


def test_negative_hbm_budget_rejected_at_parse_time():
    from lightgbm_tpu.config import config_from_params
    with pytest.raises(RuntimeError, match="hbm_budget"):
        config_from_params({"objective": "binary", "hbm_budget": -1})


# ------------------------------------------------- generated docs/MEMORY.md


def test_memory_doc_table_matches_predict_hbm():
    """The docs/MEMORY.md shape table is generated from predict_hbm
    (scripts/gen_memory_doc.py) — a model change must regenerate the doc
    or this fails, keeping the committed numbers honest."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        import gen_memory_doc
    finally:
        sys.path.pop(0)
    expected = gen_memory_doc.render_table()
    with open(os.path.join(ROOT, "docs", "MEMORY.md")) as f:
        doc = f.read()
    assert expected.strip() in doc, (
        "docs/MEMORY.md shape table is stale — regenerate with "
        "`python scripts/gen_memory_doc.py`")


# ----------------------------------------- downgrade-event source invariant

# log.warning sites that look like silent-degradation messages but are NOT
# device-layout downgrades; every exemption carries its reason.
_DOWNGRADE_LINT_EXEMPT = {
    # engine: snapshot_resume skipped multi-process — a checkpointing
    # capability gap (ROADMAP), not a kernel/layout substitution
    "snapshot_resume is single-process for now",
    # basic.py: a host FILE-LOADING strategy fallback (two-round loading
    # vs in-memory) — changes how bytes reach the host, never which
    # device kernel/layout runs
    "use_two_round_loading falls back to in-memory",
}


def _warning_calls(src):
    """(start_line, message_literal) for each log.warning call, with the
    adjacent string literals joined."""
    out = []
    for m in re.finditer(r"log\.warning\(", src):
        start = src.count("\n", 0, m.start()) + 1
        tail = src[m.end():m.end() + 600]
        msg = "".join(re.findall(r'"([^"]*)"', tail.split(")\n", 1)[0]))
        out.append((start, msg))
    return out


def test_every_downgrade_warning_also_emits_a_layout_event():
    """Grep-based source lint (the test_bench_keys.py spirit): any
    warn-once fallback path whose message says a requested layout/kernel
    was ignored / fell back / is unavailable must ALSO record a
    `layout_downgrade` obs event within the same block, so the memory/obs
    event stream — not just stderr — carries every degradation."""
    pat = re.compile(r"(ignored|falls back|falling back|unavailable)")
    files = (glob.glob(os.path.join(ROOT, "lightgbm_tpu", "*.py"))
             + glob.glob(os.path.join(ROOT, "lightgbm_tpu", "ops", "*.py"))
             + glob.glob(os.path.join(ROOT, "lightgbm_tpu", "data", "*.py"))
             + glob.glob(os.path.join(ROOT, "lightgbm_tpu", "parallel",
                                      "*.py"))
             + glob.glob(os.path.join(ROOT, "lightgbm_tpu", "native",
                                      "*.py"))
             + glob.glob(os.path.join(ROOT, "lightgbm_tpu", "obs",
                                      "*.py")))
    missing = []
    checked = 0
    for path in files:
        with open(path) as f:
            src = f.read()
        lines = src.splitlines()
        for line_no, msg in _warning_calls(src):
            if not pat.search(msg):
                continue
            if any(ex in msg for ex in _DOWNGRADE_LINT_EXEMPT):
                continue
            checked += 1
            window = "\n".join(lines[line_no - 1:line_no + 14])
            if "layout_downgrade" not in window:
                missing.append(f"{os.path.relpath(path, ROOT)}:{line_no} "
                               f"({msg[:60]!r})")
    assert checked >= 8, "lint pattern matched too few sites — it broke"
    assert not missing, (
        "warn-once downgrade paths without a layout_downgrade obs event "
        f"(add counters.event('layout_downgrade', ...) or exempt with a "
        f"reason): {missing}")


@pytest.mark.parametrize("bins,bytes_a_bin", [(255, 1), (256, 1), (257, 2),
                                              (279, 2)])
def test_predict_hbm_counts_the_binned_matrix_at_its_dtype(bins, bytes_a_bin):
    """Unasked, the binned matrix is counted at the dtype the data set
    stores it in: uint8 up to 256 bins, uint16 past them."""
    pred = obs_memory.predict_hbm(rows=1000, features=8, bins=bins)
    assert pred["residents"]["binned"] == 1000 * 8 * bytes_a_bin
