"""bench.py contract invariants: dataset-cache keys and the memory block
every rung JSON must embed.

The bench memoizes constructed datasets on disk keyed by shape + the
BINNING_KEYS subset of params.  A construction-relevant Config attribute
read by the data layer but missing from that allowlist would silently
reuse STALE cached datasets across A/B runs.  This test greps the data
layer for every Config attribute it actually reads and asserts the
allowlist stays a superset, so drift is caught in CI rather than in a
measurement.
"""
import glob
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Config attributes the data layer reads that CANNOT change the
# constructed dataset bytes.  Every exemption must carry its reason;
# anything new and unexplained fails the test until it is classified
# (either here or in BINNING_KEYS).
NON_CONSTRUCTION_READS = {
    "has_header",      # file parsing only — bench constructs from arrays,
                       # and the parsed values, not the header flag, are
                       # what binning consumes
}


def _data_layer_cfg_reads():
    attrs = set()
    pat = re.compile(r"\b(?:cfg|config)\.([a-z][a-z0-9_]*)\b")
    for path in glob.glob(os.path.join(REPO, "lightgbm_tpu", "data", "*.py")):
        with open(path) as f:
            attrs |= set(pat.findall(f.read()))
    return attrs


def test_binning_keys_superset_of_data_layer_reads():
    import bench
    from lightgbm_tpu.config import Config
    reads = _data_layer_cfg_reads()
    # only attribute names that are actual Config fields matter (the regex
    # also catches unrelated locals named cfg/config in principle)
    fields = set(Config.__dataclass_fields__)
    reads &= fields
    assert reads, "grep found no Config reads — the pattern broke"
    unexplained = reads - bench.BINNING_KEYS - NON_CONSTRUCTION_READS
    assert not unexplained, (
        f"lightgbm_tpu/data/ reads Config attributes {sorted(unexplained)} "
        "that are neither in bench.BINNING_KEYS (construction-relevant -> "
        "must key the dataset cache) nor exempted in "
        "NON_CONSTRUCTION_READS (with a reason). Classify them.")


def test_bench_child_embeds_memory_block():
    """Every bench JSON must carry the "memory" block (predicted +
    measured peak bytes, obs/memory.py) — acceptance criterion of the
    memory-observability PR; on the CPU rung the measured source is the
    live-array census and the ratio against the resident model must stay
    inside the documented tolerance."""
    import json
    import subprocess
    import sys
    from lightgbm_tpu.obs.memory import RESIDENT_TOLERANCE
    env = dict(os.environ, BENCH_CHILD="1", BENCH_CHILD_PLATFORM="cpu",
               BENCH_CHILD_MODE="segment", BENCH_ROWS="5000",
               BENCH_ROWS_CPU="5000", BENCH_TREES_CPU="1",
               BENCH_LEAVES="15", BENCH_LEAVES_SWEEP="0", BENCH_DS_CACHE="",
               BENCH_TRACE="", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.strip().splitlines()
            if ln.startswith("{")][-1]
    doc = json.loads(line)
    mem = doc["memory"]
    for key in ("predicted_peak_bytes", "predicted_resident_bytes",
                "measured_peak_bytes", "measured_source", "top_residents"):
        assert key in mem, f"memory block missing {key}"
    # the live-telemetry PR's twin contract: every bench JSON also embeds
    # the metrics_snapshot block (obs/metrics.snapshot — the flat
    # /metrics sample map scripts/obs_diff.py compares)
    ms = doc["metrics_snapshot"]
    assert ms["schema_version"] >= 1
    assert any(k.startswith("lgbm_tpu_hist_dispatch_total")
               for k in ms["samples"]), sorted(ms["samples"])[:10]
    assert "lgbm_tpu_memory_peak_bytes" in ms["samples"]
    assert mem["measured_source"] == "live_census"
    assert mem["measured_peak_bytes"] > 0
    # tiny shapes carry proportionally more fixed overhead than the bench
    # rungs, so allow twice the documented band here; the tight band is
    # pinned at bench-like shapes in tests/test_memory.py
    ratio = mem["measured_vs_predicted"]
    assert ratio is not None and \
        1 - 2 * RESIDENT_TOLERANCE <= ratio <= 1 + 2 * RESIDENT_TOLERANCE


def test_binning_keys_are_real_config_fields():
    """The allowlist must not rot: every key must remain a Config field
    (a renamed knob would otherwise silently stop keying the cache)."""
    import bench
    from lightgbm_tpu.config import Config
    fields = set(Config.__dataclass_fields__)
    missing = set(bench.BINNING_KEYS) - fields
    assert not missing, f"BINNING_KEYS entries are not Config fields: " \
                        f"{sorted(missing)}"
