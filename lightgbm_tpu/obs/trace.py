"""Nested-span tracer: Chrome-trace JSON/JSONL out, XProf-correlated.

The reference only ever had ``#ifdef TIMETAG`` chrono counters
(``serial_tree_learner.cpp:10-37``); here the evidence is produced by the
library itself.  One process-wide active tracer (module functions
:func:`start` / :func:`stop` / :func:`get_tracer`):

* **disabled** (the default) it is a :class:`NullTracer` whose ``span()``
  returns ONE shared no-op context manager
  (pinned by ``tests/test_obs.py::test_disabled_tracer_is_allocation_free``);
* **enabled** it records wall-clock spans as Chrome trace events
  (``ph: "X"``, microsecond ``ts``/``dur``).

:func:`phase` is the primitive every boundary of the boosting loop and of
set-up goes through, tracer on or off: ONE ``jax.profiler.TraceAnnotation``
named ``lgb:<name>`` (so any profiler capture — ``profile_dir``, the
benchmark's ``--trace 1`` — holds the program's spans on the device
trace's clock with no telemetry switch), plus ``phase_seconds{phase=}`` /
``phase_calls{phase=}`` in the process-wide counter registry, plus the
Chrome event when the tracer records.  ``utils/timer.PhaseTimers`` keeps
its per-booster totals from the same measurement.

Output format follows the Chrome Trace Event spec: a ``*.jsonl`` path gets
one event object per line (append-friendly, crash-tolerant — a killed
child still leaves a readable prefix); any other path gets the standard
``{"traceEvents": [...], "otherData": {...}}`` object.  Counter/summary
payloads (the :mod:`lightgbm_tpu.obs.counters` snapshot, phase-timer
totals) are embedded as instant events named ``telemetry.summary`` so one
file carries the whole story; ``obs/report.py`` renders it.

Jitted code carries no host span (one would fire once, while jit traces):
its device time is read from the ``jax.named_scope`` names baked into the
lowered HLO, and its trace/compile cost from the ``compile_seconds``
counters (``obs/counters.install_compile_listener``).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from .counters import counters    # (counters imports this module lazily)

# resolved lazily; False once probing failed (jax absent / too old)
_TraceAnnotation: Any = None

# process index resolved once (multi-host traces from different ranks must
# stay distinguishable after they are merged into one report)
_PROC: Any = None


def process_index() -> int:
    global _PROC
    if _PROC is None:
        try:
            import jax
            _PROC = int(jax.process_index())
        except Exception:
            _PROC = 0
    return _PROC


# every host span of the program rides profiler captures under this prefix
# (benchmarks/harness/program_spans.py keeps exactly these)
ANNOTATION_PREFIX = "lgb:"


def _jax_annotation(name: str, args: dict):
    """``TraceAnnotation("lgb:<name>", **args)``: well under a microsecond
    with no capture open; inside one it is the span on the trace's clock."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        try:
            from jax.profiler import TraceAnnotation as ta
            _TraceAnnotation = ta
        except Exception:  # pragma: no cover - jax is a hard dep here
            _TraceAnnotation = False
    if not _TraceAnnotation:
        return None
    return _TraceAnnotation(ANNOTATION_PREFIX + name, **args)


class _NullSpan:
    """Shared no-op context manager (the disabled fast path)."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: every operation is a no-op; ``span()`` hands back
    the one shared :data:`NULL_SPAN` so the instrumented hot loops never
    allocate when telemetry is off."""
    enabled = False
    path: Optional[str] = None

    def span(self, name: str, **args):
        return NULL_SPAN

    def instant(self, name: str, **args) -> None:
        pass

    def summary(self, name: str, payload: Dict[str, Any]) -> None:
        pass

    def events(self) -> List[dict]:
        return []


NULL_TRACER = NullTracer()


class _Span:
    __slots__ = ("_tr", "_name", "_args", "_ts", "_jax")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tr = tracer
        self._name = name
        self._args = args
        self._ts = 0.0
        self._jax = None

    def __enter__(self):
        ann = _jax_annotation(self._name, self._args)
        if ann is not None:
            ann.__enter__()
            self._jax = ann
        self._ts = self._tr._now_us()
        return self

    def __exit__(self, *exc):
        dur = self._tr._now_us() - self._ts
        if self._jax is not None:
            self._jax.__exit__(*exc)
        ev = {"name": self._name, "ph": "X", "ts": round(self._ts, 3),
              "dur": round(dur, 3), "pid": self._tr.pid,
              "proc": self._tr.proc, "tid": threading.get_ident()}
        if self._args:
            ev["args"] = self._args
        self._tr._append(ev)
        return False


class Tracer:
    """Recording tracer.  Thread-safe; timestamps are microseconds since
    construction (``perf_counter`` based, like the phase timers)."""
    enabled = True

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.pid = os.getpid()
        self.proc = process_index()
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._events: List[dict] = []

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _append(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    def span(self, name: str, **args) -> _Span:
        """Context manager recording one complete ("X") event; nesting is
        expressed through ts/dur containment, exactly how Chrome/Perfetto
        rebuild the flame graph."""
        return _Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        ev = {"name": name, "ph": "i", "s": "p", "ts": round(self._now_us(), 3),
              "pid": self.pid, "proc": self.proc,
              "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._append(ev)

    def summary(self, name: str, payload: Dict[str, Any]) -> None:
        """Attach a structured summary payload (phase-timer totals, counter
        snapshots) as a ``telemetry.summary`` instant event."""
        self.instant("telemetry.summary", kind=name, **{"payload": payload})

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def write(self, path: Optional[str] = None) -> Optional[str]:
        """Serialize to ``path`` (default: the constructor path).  Embeds a
        final summary event carrying the current counter-registry snapshot
        so the trace file is self-contained."""
        path = path or self.path
        from . import metrics as obs_metrics  # lazy: avoid import cycles
        # the live-scrape view rides along so obs_diff can compare two
        # traces at the metrics level without a /metrics endpoint
        self.summary("metrics", obs_metrics.snapshot())
        self.summary("counters", counters.snapshot())
        if not path:
            return None
        events = self.events()
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            if path.endswith(".jsonl"):
                for ev in events:
                    f.write(json.dumps(ev) + "\n")
            else:
                json.dump({"traceEvents": events,
                           "otherData": {"producer": "lightgbm_tpu.obs"}}, f)
        return path


_active: Any = NULL_TRACER


class _Phase:
    """One measured boundary (see :func:`phase`).  ``seconds`` holds the
    duration once the block has exited; ``span`` is the recording tracer
    span while it runs (``NULL_SPAN`` with the tracer off)."""
    __slots__ = ("name", "_args", "_ann", "span", "_t0", "seconds")

    def __init__(self, name: str, args: dict):
        self.name = name
        self._args = args
        self._ann = None
        self.span = NULL_SPAN
        self._t0 = 0.0
        self.seconds = 0.0

    def __enter__(self):
        tr = _active
        if tr.enabled:
            # the recording span opens the one annotation itself
            self.span = self._ann = _Span(tr, self.name, self._args)
        else:
            self._ann = _jax_annotation(self.name, self._args)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        counters.inc("phase_seconds", self.seconds, phase=self.name)
        counters.inc("phase_calls", 1, phase=self.name)
        return False


def phase(name: str, **args) -> _Phase:
    """``with phase("tree", iteration=i):`` — the always-on span: one
    ``lgb:<name>`` profiler annotation, the ``phase_seconds`` /
    ``phase_calls`` counters, and the Chrome event when the tracer is on."""
    return _Phase(name, args)


def get_tracer():
    """The process-wide active tracer (NullTracer when telemetry is off)."""
    return _active


def start(path: Optional[str] = None) -> Tracer:
    """Install a recording tracer as the process-wide active one."""
    global _active
    _active = Tracer(path)
    return _active


def stop() -> Optional[str]:
    """Write the active trace (if it has a path) and disable tracing.
    Returns the written path, or None."""
    global _active
    tr, _active = _active, NULL_TRACER
    if isinstance(tr, Tracer):
        return tr.write()
    return None


@contextlib.contextmanager
def tracing(path: Optional[str] = None):
    """``with tracing("t.json"):`` — enable for a block, write on exit."""
    tr = start(path)
    try:
        yield tr
    finally:
        stop()
