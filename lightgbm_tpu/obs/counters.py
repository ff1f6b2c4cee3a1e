"""Process-wide counter/event registry.

The structured side of the telemetry subsystem: cheap named counters with
optional tags, bounded structured events, and gauges.  The load-bearing
users:

* **histogram-kernel dispatch identity** — every dispatch site records
  ``hist_dispatch`` tagged ``method=fused|einsum|segment``, so a
  ``BENCH_*.json`` can prove which kernel a rung *actually* traced
  instead of trusting its label (:func:`observed_kernel`, consumed by
  ``bench.py`` / ``scripts/decide_flips.py``);
* **layout-downgrade events** — the warn-once fallback paths (fused
  gate, ``gspmd_hist=fused`` mesh gating)
  also record a ``layout_downgrade`` event with the machine-readable
  reason;
* **collective accounting** — ``obs/collectives.py`` feeds
  ``collective_calls`` / ``collective_bytes`` tagged by op + site;
* **checkpoint lifecycle events** — the resume paths
  (:mod:`lightgbm_tpu.checkpoint`) record ``checkpoint_skipped``
  (iteration + reason for every torn/demoted snapshot the scan rejected),
  ``checkpoint_resume`` (iteration + ``kind=single|group``), and
  ``preempt_checkpoint`` (clean preemption exits) — so a resumed run's
  telemetry explains exactly which snapshot it continued from and why;
* **supervisor lifecycle events** — the self-healing supervisor
  (:mod:`lightgbm_tpu.supervisor`) records every liveness decision:
  ``rank_dead`` (exit code + last heartbeat), ``rank_hang`` (heartbeat
  age vs the effective hang timeout), ``group_restart`` (attempt, resume
  iteration, backoff), ``restart_budget_exhausted``, ``crash_report``
  (a rank left one behind), and ``stale_sweep`` (startup hygiene
  removals) — an unattended recovery is never an unexplained one;
* **phase and compile accounting** — ``obs/trace.phase`` adds
  ``phase_seconds`` / ``phase_calls`` tagged ``phase=`` for every boundary
  of the boosting loop and of set-up, and
  :func:`install_compile_listener` adds ``compile_seconds`` tagged
  ``fun=``, ``stage=trace|lower|backend``, ``compile_calls`` tagged
  ``fun=`` and ``compile_cache_hits`` from jax's own monitoring events:
  "which step recompiled, and what it cost".  These are the
  :data:`PROCESS_COUNTERS`: they describe the process (a data set binned
  before ``train()``, a program compiled by an earlier booster), so the
  per-training reset keeps them.  ``efb_layout`` tagged ``logical=``,
  ``physical=``, ``max_slots=`` is of their kind: one count a data set
  that exclusive feature bundling packed, at its construction.

Counts recorded from inside jit tracing are TRACE-time counts (once per
compiled call site), which is exactly the "per call site" identity the
honesty checks need — a recompile shows up as a fresh increment.
"""
from __future__ import annotations

import collections
import re
import threading
import time
from typing import Any, Dict, Iterable, List, Optional

# families that outlive a training (see the module docstring)
PROCESS_COUNTERS = ("phase_seconds", "phase_calls", "compile_seconds",
                    "compile_calls", "compile_cache_hits", "efb_layout")


def _tag_key(tags: Dict[str, Any]) -> str:
    if not tags:
        return ""
    return ",".join(f"{k}={tags[k]}" for k in sorted(tags))


class CounterRegistry:
    """Thread-safe registry: counters[name][tag_key] -> number."""

    MAX_EVENTS = 512     # ring buffer: telemetry must never grow host
    #                      memory without bound — a long training with
    #                      telemetry on keeps the newest MAX_EVENTS events
    #                      and counts the overflow (``events_dropped``)
    #                      instead of leaking

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Dict[str, float]] = {}
        self._gauges: Dict[str, float] = {}
        self._events: collections.deque = collections.deque(
            maxlen=self.MAX_EVENTS)
        self._events_dropped = 0
        self._sinks: List[Any] = []

    # ------------------------------------------------------------- writers

    def inc(self, name: str, value: float = 1, **tags) -> None:
        key = _tag_key(tags)
        with self._lock:
            bucket = self._counters.setdefault(name, {})
            bucket[key] = bucket.get(key, 0) + value

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def event(self, name: str, **fields) -> None:
        """Record a structured event (layout downgrade, recompile, ...).
        Storage is a bounded ring: at capacity the OLDEST event is evicted
        and ``events_dropped`` counts the loss (surfaced in snapshots and
        the report) so truncation is visible, never silent."""
        from .trace import process_index   # lazy: avoid import cycles
        ev = {"event": name, "proc": process_index(), **fields}
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._events_dropped += 1
            self._events.append(ev)
            sinks = tuple(self._sinks)
        for sink in sinks:       # outside the lock: a sink may take its own
            try:
                sink(ev)
            except Exception:
                pass             # a telemetry sink must never break emitters

    def add_sink(self, fn) -> None:
        """Subscribe a callable to every future structured event (the
        flight recorder streams the ring to disk as it fills)."""
        with self._lock:
            if fn not in self._sinks:
                self._sinks.append(fn)

    def remove_sink(self, fn) -> None:
        with self._lock:
            if fn in self._sinks:
                self._sinks.remove(fn)

    def reset(self, keep: Iterable[str] = ()) -> None:
        """Drop everything but the counter families named in ``keep``."""
        with self._lock:
            kept = {n: self._counters[n] for n in keep
                    if n in self._counters}
            self._counters.clear()
            self._counters.update(kept)
            self._gauges.clear()
            self._events.clear()
            self._events_dropped = 0

    # ------------------------------------------------------------- readers

    def get(self, name: str) -> Dict[str, float]:
        with self._lock:
            return dict(self._counters.get(name, {}))

    def total(self, name: str) -> float:
        with self._lock:
            return sum(self._counters.get(name, {}).values())

    def events(self, name: Optional[str] = None) -> List[dict]:
        with self._lock:
            evs = list(self._events)
        return evs if name is None else [e for e in evs
                                         if e.get("event") == name]

    def events_tail(self, n: int) -> List[dict]:
        """The newest ``n`` events across all names — what a crash report
        flushes (checkpoint.write_crash_report): the last things this
        process observed before dying."""
        with self._lock:
            evs = list(self._events)
        return evs[-max(0, int(n)):]

    def events_dropped(self) -> int:
        with self._lock:
            return self._events_dropped

    def snapshot(self) -> Dict[str, Any]:
        from .trace import process_index
        with self._lock:
            return {"counters": {n: dict(b)
                                 for n, b in self._counters.items()},
                    "gauges": dict(self._gauges),
                    "events": list(self._events),
                    "events_dropped": self._events_dropped,
                    "process_index": process_index()}

    # --------------------------------------------- derived: kernel identity

    def observed_kernel(self) -> Optional[str]:
        """The histogram-kernel identity this process actually traced: the
        dominant ``method=`` tag of ``hist_dispatch`` (trace-time call-site
        counts).  None when no histogram was dispatched yet."""
        per_method: Dict[str, float] = {}
        for key, v in self.get("hist_dispatch").items():
            tags = dict(kv.split("=", 1) for kv in key.split(",") if "=" in kv)
            m = tags.get("method")
            if m:
                per_method[m] = per_method.get(m, 0) + v
        if not per_method:
            return None
        return max(per_method, key=per_method.get)


counters = CounterRegistry()


# ------------------------------------------------------ compile accounting

_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # on a persistent-cache hit this is the load time: what set-up pays
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_WRAPPED_NAME = re.compile(r"^\w+\((.*)\)$")
_compile_listener_installed = False
# per thread, the traces that ended and may lie inside one still running:
# jit traces nest (a jnp function traced inside ``grow_tree`` reports its
# own duration first), and each second is charged once, to the innermost
_open_traces = threading.local()


def _fun_tag(fun_name: Any) -> str:
    """``jit(get_gradients)`` and ``get_gradients`` are one function."""
    name = str(fun_name or "?")
    m = _WRAPPED_NAME.match(name)
    return re.sub(r"[,=]", "_", m.group(1) if m else name)


def _on_compile_duration(event: str, duration_secs: float, **kw) -> None:
    stage = _COMPILE_STAGES.get(event)
    if stage is None:
        return
    fun = _fun_tag(kw.get("fun_name"))
    seconds = float(duration_secs)
    if stage == "trace":
        end = time.perf_counter()
        start = end - seconds
        done = getattr(_open_traces, "done", None)
        if done is None:
            done = _open_traces.done = []
        while done and done[-1][0] >= start:
            seconds -= done.pop()[1]
        del done[:-64]          # top-level traces are never claimed
        done.append((start, float(duration_secs)))
        seconds = max(seconds, 0.0)
    elif stage == "backend":
        counters.inc("compile_calls", 1, fun=fun)
    counters.inc("compile_seconds", seconds, fun=fun, stage=stage)


def _on_compile_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        counters.inc("compile_cache_hits", 1)


def install_compile_listener() -> None:
    """Listen, once per process, to jax's compile events.  Nothing here
    runs unless jax traces, lowers, compiles or loads a program."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_compile_duration)
    monitoring.register_event_listener(_on_compile_event)
    _compile_listener_installed = True
