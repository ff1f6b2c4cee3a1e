"""Always-on lightweight phase timers.

The reference accumulates per-phase ``std::chrono`` counters under
``#ifdef TIMETAG`` (``serial_tree_learner.cpp:10-37``, ``gbdt.cpp:22-64``)
and dumps them at destruction.  Here the counters are always on and
reported through the logger.  Each phase is measured once, by
``obs.trace.phase`` (the ``lgb:<name>`` profiler annotation, the
process-wide ``phase_seconds`` counter, the Chrome-trace span when the
tracer records); this class keeps the per-owner totals of that measurement.
Deep kernel-level profiles come from ``jax.profiler`` instead (see
``engine.train``'s ``profile_dir`` parameter).
"""
from __future__ import annotations

import collections
import contextlib
from typing import Dict

from ..obs import memory as obs_memory
from ..obs import trace as obs_trace
from . import log


class PhaseTimers:
    """Accumulating wall-clock counters keyed by phase name."""

    def __init__(self):
        self.seconds: Dict[str, float] = collections.defaultdict(float)
        self.counts: Dict[str, int] = collections.defaultdict(int)
        # first recorded duration per phase: a first firing that includes
        # a jit compile poisons the mean (the obs/report.py compile⚠
        # separation) — kept here so the LIVE metrics view can serve
        # steady-state means, not just totals
        self.first: Dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str, **args):
        ph = obs_trace.phase(name, **args)
        ph.__enter__()
        try:
            yield
        finally:
            # attach the phase's peak device bytes to the span it already
            # emits (both singletons: a no-op unless the tracer AND the
            # memory monitor are armed; the sample is a host-side read)
            obs_memory.get_memory().annotate(ph.span)
            ph.__exit__(None, None, None)
            self.add(name, ph.seconds)

    def add(self, name: str, seconds: float) -> None:
        self.seconds[name] += seconds
        self.counts[name] += 1
        self.first.setdefault(name, seconds)

    def steady_means(self) -> Dict[str, float]:
        """Mean seconds per phase with the first (possibly
        compile-inclusive) firing excluded; a single-firing phase reports
        that firing."""
        out: Dict[str, float] = {}
        for name, total in list(self.seconds.items()):
            n = self.counts.get(name, 0)
            first = self.first.get(name, 0.0)
            out[name] = ((total - first) / (n - 1)) if n > 1 \
                else (first if n else 0.0)
        return out

    def report(self, header: str = "phase timers") -> str:
        parts = [f"{k}: {v:.3f}s/{self.counts[k]}x"
                 for k, v in sorted(self.seconds.items(), key=lambda kv: -kv[1])]
        text = f"{header}: " + ", ".join(parts) if parts else f"{header}: (empty)"
        log.debug("%s", text)
        # telemetry sink as well as the logger: the totals land in the
        # trace file's summary stream (no-op when telemetry is off)
        obs_trace.get_tracer().summary(header, {
            "seconds": dict(self.seconds), "counts": dict(self.counts)})
        return text

    def reset(self) -> None:
        self.seconds.clear()
        self.counts.clear()
        self.first.clear()
