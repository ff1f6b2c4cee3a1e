"""lightgbm_tpu.obs — structured telemetry: spans, counters, collectives,
device memory.

Four pillars (see docs/OBSERVABILITY.md):

* :mod:`.trace` — ``phase()``, the always-on span (an ``lgb:<name>``
  ``jax.profiler.TraceAnnotation`` in every profiler capture plus the
  ``phase_seconds`` counter), and the nested-span tracer that records the
  same spans as Chrome-trace JSON/JSONL when enabled;
* :mod:`.counters` — process-wide counters/events (histogram-kernel
  dispatch identity, layout downgrades, collective bytes, phase seconds,
  compile seconds per function and stage);
* :mod:`.memory` — device-memory observability: live HBM accounting
  (``memory_stats`` / tagged live-array census), compiled-executable
  ``memory_analysis`` capture, the ``predict_hbm`` fit-predictor and the
  pre-compile ``hbm_budget`` pre-flight;
* :mod:`.report` — ``python -m lightgbm_tpu.obs <trace>...`` renders the
  per-phase / per-kernel / memory markdown tables (multiple trace files
  merge rank-tagged);
* :mod:`.metrics` — the LIVE plane: a Prometheus text view of the whole
  registry (counters/gauges, phase steady-state means, memory peaks,
  serving latency histograms), served from ``GET /metrics`` on the
  serving HTTP front and a standalone ``metrics_port`` exporter thread;
* :mod:`.flight` — per-rank flight recorder: a bounded rotated JSONL
  stream of iteration progress + structured events as they happen
  (``obs_stream_path``), tailed by the supervisor for straggler verdicts.

Enable from training via ``engine.train(params={"trace_path": ...})`` or
``telemetry=true``; from the bench via ``BENCH_TRACE=<path>``; the live
plane via ``metrics_port`` / ``obs_stream_path``.
"""
from . import flight, memory, metrics, trace
from .counters import counters
from .trace import get_tracer

__all__ = ["flight", "memory", "metrics", "trace", "counters",
           "get_tracer"]
