"""Device-time attribution: programmatic profiler capture + phase accounting.

Every host-side span the obs stack records (``obs/trace.py``,
``utils/timer.py``) measures wall-clock around an *async dispatch* — on an
accelerator it cannot say where device time actually goes.  This module
closes that gap without XProf-in-a-browser: it arms ``jax.profiler``
capture windows over steady-state boosting iterations, parses the emitted
trace-event artifacts on the host, and attributes device op time to the
``jax.named_scope`` phase twins the kernels already carry (``histogram``
root/split, ``split_find``, ``partition``, ``fused_panel``, the serving
``traverse``, ``objective``, ``score_update``) — falling back to the host
``TraceAnnotation`` phase windows (``lgb:boosting`` / ``lgb:tree`` /
``lgb:score`` / ...) that ``obs/trace.phase`` puts into every capture.
It is NOT taught the grower's nested scopes (``part_read``, ``hist_root``,
... : ``grower.SCOPE_REVISION``): it doubles nested operations on the chip
(ROADMAP D6) and goes or is mended in a ``simplicity`` PR of its own.

Capture discipline follows the PhaseTimers convention: the FIRST firing
seen is the compile and is never captured; the next ``profile_iters``
steady-state iterations each get their own start/stop window, parsed
immediately so the per-iteration idle-gap fraction is known before the
flight-recorder progress record for that iteration is written.

Disarmed (the default) the plane is :data:`NULL_DEVPROF` — one shared
no-op whose ``iteration()`` returns the shared :data:`NULL_WINDOW`; the
hot-loop cost is an attribute read and two no-op calls, no allocation
(pinned by ``tests/test_devprof.py``).  Armed, the capture overhead is
explicit and bounded: ``profile_iters`` windows, then the profiler is
never touched again.

The parsing layer (:func:`load_trace_events`, :func:`op_events`,
:func:`phase_windows`, :func:`attribute`) is pure — tier-1 tests feed it
synthetic trace-event fixtures, no TPU required.  ``scripts/
bench_history.py`` reuses the same loader for longitudinal artifacts.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

from ..utils import log
from .counters import counters
from .trace import ANNOTATION_PREFIX

SCHEMA_VERSION = 1

# device-side named_scope twins baked into the lowered HLO; XProf-style
# artifacts carry them in op names / tf_op metadata ("scope attribution")
SCOPE_PHASES = ("histogram", "split_find", "partition", "fused_panel",
                "traverse", "objective", "score_update")
# host-side TraceAnnotation windows obs/trace.phase puts into captures
# ("window attribution" — the CPU/sync fallback when scope names are
# fused away or the backend does not label ops).  Leaf phases only: a
# window that holds others (lgb:iteration, lgb:tree over tree.wait) would
# still resolve to its innermost child, so listing it adds nothing.
HOST_PHASES = tuple(ANNOTATION_PREFIX + p for p in (
    "boosting", "bagging", "tree", "tree.wait", "tree.host", "score",
    "metric", "metric.fetch", "predict_bin", "predict_traverse",
    "predict_margin", "serving_batch"))

_SCOPE_RE = re.compile(
    r"(?:^|[/ .])(" + "|".join(SCOPE_PHASES) + r")(?:[/ .\d]|$)")

TOP_K = 10


# --------------------------------------------------------------- parsing


def load_trace_events(path: str) -> List[dict]:
    """Trace events from a Chrome-trace artifact: ``.json`` / ``.json.gz``
    holding ``{"traceEvents": [...]}`` or a bare list, or ``.jsonl`` with
    one event per line (torn tails tolerated, like obs/report.py)."""
    opener = gzip.open if path.endswith(".gz") else open
    if path.endswith(".jsonl"):
        events = []
        with opener(path, "rt") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    break  # torn tail from a killed writer
        return events
    with opener(path, "rt") as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        return list(doc.get("traceEvents", []))
    return list(doc) if isinstance(doc, list) else []


def find_capture_files(log_dir: str) -> List[str]:
    """The Chrome-trace artifacts of a ``jax.profiler`` capture directory
    (``plugins/profile/<run>/<host>.trace.json.gz``), newest run last."""
    pats = (os.path.join(log_dir, "plugins", "profile", "*", "*.trace.json*"),
            os.path.join(log_dir, "*.trace.json*"))
    out: List[str] = []
    for pat in pats:
        out.extend(sorted(glob.glob(pat), key=os.path.getmtime))
    return out


def _is_device_pid(ev: dict, device_pids: set) -> bool:
    return ev.get("pid") in device_pids


def _device_pids(events: List[dict]) -> set:
    """Process ids the profiler labels as device streams (TPU/GPU planes:
    ``process_name`` metadata like "/device:TPU:0 ...")."""
    pids = set()
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            name = str((ev.get("args") or {}).get("name", ""))
            if "/device:" in name.lower() or "xla ops" in name.lower():
                pids.add(ev.get("pid"))
    return pids


def op_events(events: List[dict]) -> List[dict]:
    """Complete ("X") events that represent device/XLA op executions:
    events on a device-labelled pid, or host-backend events tagged with an
    ``hlo_op`` arg (the XLA:CPU form).  Python-tracer frames (``$``-prefixed
    names) and untagged host activity are excluded."""
    device_pids = _device_pids(events)
    out = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = str(ev.get("name", ""))
        if name.startswith("$"):
            continue
        args = ev.get("args") or {}
        if _is_device_pid(ev, device_pids) or "hlo_op" in args:
            out.append(ev)
    return out


def host_phase(ev: dict) -> Optional[str]:
    """The phase (prefix taken off) of a ``lgb:`` TraceAnnotation window,
    else None.  The profiler's Chrome-trace export shortens the name of an
    annotation that carries arguments to what follows the colon and keeps
    the whole in ``long_name``."""
    if ev.get("ph") != "X":
        return None
    name = str((ev.get("args") or {}).get("long_name") or ev.get("name", ""))
    return name[len(ANNOTATION_PREFIX):] if name in HOST_PHASES else None


def phase_windows(events: List[dict]) -> List[Tuple[float, float, str]]:
    """Host phase windows ``(ts, end, phase)`` from the ``lgb:``
    TraceAnnotations of ``obs/trace.phase``, sorted by start time."""
    wins = []
    for ev in events:
        phase = host_phase(ev)
        if phase is not None:
            ts = float(ev.get("ts", 0.0))
            dur = float(ev.get("dur", 0.0))
            wins.append((ts, ts + dur, phase))
    wins.sort()
    return wins


def _scope_phase(ev: dict) -> Optional[str]:
    """Phase from the named_scope token in the op name or its metadata
    (TPU/GPU traces carry the scope path in ``tf_op``/``long_name``)."""
    hay = [str(ev.get("name", ""))]
    for v in (ev.get("args") or {}).values():
        if isinstance(v, str):
            hay.append(v)
    for h in hay:
        m = _SCOPE_RE.search(h)
        if m:
            return m.group(1)
    return None


def _window_phase(ev: dict,
                  wins: List[Tuple[float, float, str]]) -> Optional[str]:
    """Fallback attribution: the innermost host window containing the op's
    midpoint; else the window with maximal time overlap; else the last
    window dispatched before the op began (async dispatch ordering)."""
    ts = float(ev.get("ts", 0.0))
    end = ts + float(ev.get("dur", 0.0))
    mid = (ts + end) / 2.0
    containing = [w for w in wins if w[0] <= mid <= w[1]]
    if containing:
        return min(containing, key=lambda w: w[1] - w[0])[2]
    best, best_ov = None, 0.0
    for w in wins:
        ov = min(end, w[1]) - max(ts, w[0])
        if ov > best_ov:
            best, best_ov = w[2], ov
    if best:
        return best
    before = [w for w in wins if w[0] <= ts]
    return before[-1][2] if before else None


def _busy_us(ops: List[dict], t0: Optional[float] = None,
             t1: Optional[float] = None) -> float:
    """Union length (µs) of the op intervals, optionally clipped to
    [t0, t1] — device busy time without double-counting overlap."""
    spans = []
    for ev in ops:
        a = float(ev.get("ts", 0.0))
        b = a + float(ev.get("dur", 0.0))
        if t0 is not None:
            a = max(a, t0)
        if t1 is not None:
            b = min(b, t1)
        if b > a:
            spans.append((a, b))
    spans.sort()
    busy, cur_a, cur_b = 0.0, None, None
    for a, b in spans:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                busy += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        busy += cur_b - cur_a
    return busy


def attribute(events: List[dict], top_k: int = TOP_K,
              ops: Optional[List[dict]] = None) -> Dict[str, Any]:
    """Attribute device op time to named phases.

    Scope-token attribution first (named_scope twins in op names/metadata),
    host-window fallback second.  Returns the per-phase device-ms table,
    the top-K op list, totals, and the attributed fraction.

    ``ops`` bypasses :func:`op_events` with already-classified op events —
    required when ``events`` no longer carries the ``process_name``
    metadata that identifies device pids (the armed profiler's retained
    state)."""
    if ops is None:
        ops = op_events(events)
    wins = phase_windows(events)
    phase_us: Dict[str, float] = {}
    per_op: Dict[Tuple[str, str], Dict[str, float]] = {}
    attributed = 0.0
    total = 0.0
    for ev in ops:
        dur = float(ev.get("dur", 0.0))
        total += dur
        phase = _scope_phase(ev) or _window_phase(ev, wins)
        if phase:
            phase_us[phase] = phase_us.get(phase, 0.0) + dur
            attributed += dur
        key = (str(ev.get("name", "")), phase or "(unattributed)")
        agg = per_op.setdefault(key, {"us": 0.0, "count": 0})
        agg["us"] += dur
        agg["count"] += 1
    top = sorted(per_op.items(), key=lambda kv: -kv[1]["us"])[:top_k]
    return {
        "phase_device_ms": {p: round(us / 1e3, 4)
                            for p, us in sorted(phase_us.items(),
                                                key=lambda kv: -kv[1])},
        "top_ops": [{"op": name, "phase": phase,
                     "ms": round(agg["us"] / 1e3, 4),
                     "count": int(agg["count"])}
                    for (name, phase), agg in top],
        "op_count": len(ops),
        "total_op_ms": round(total / 1e3, 4),
        "attributed_ms": round(attributed / 1e3, 4),
        "attributed_fraction": round(attributed / total, 4) if total else None,
        "device_busy_ms": round(_busy_us(ops) / 1e3, 4),
    }


# ------------------------------------------------------------- profiler


class _NullWindow:
    """Shared no-op iteration context (the disarmed fast path)."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_WINDOW = _NullWindow()


class NullDeviceProfiler:
    """Disarmed plane: every operation a no-op, ``iteration()`` hands back
    the one shared :data:`NULL_WINDOW` — zero allocation in the loop."""
    enabled = False

    def iteration(self, index: int = 0):
        return NULL_WINDOW

    def pop_idle_gap(self) -> Optional[float]:
        return None

    def summary(self) -> Optional[Dict[str, Any]]:
        return None


NULL_DEVPROF = NullDeviceProfiler()


class _IterWindow:
    __slots__ = ("_dp", "_index")

    def __init__(self, dp: "DeviceProfiler", index: int):
        self._dp = dp
        self._index = index

    def __enter__(self):
        self._dp._enter(self._index)
        return self

    def __exit__(self, *exc):
        self._dp._exit(self._index)
        return False


class DeviceProfiler:
    """Armed plane: one ``jax.profiler`` start/stop window per captured
    steady-state iteration, parsed immediately on stop."""
    enabled = True

    def __init__(self, log_dir: Optional[str] = None, profile_iters: int = 2,
                 keep_artifacts: bool = False, top_k: int = TOP_K):
        self._own_dir = log_dir is None
        self.log_dir = log_dir or tempfile.mkdtemp(prefix="lgbm_devprof_")
        self.profile_iters = max(1, int(profile_iters))
        self.keep_artifacts = keep_artifacts
        self.top_k = top_k
        self._seen = 0            # firings observed (first = compile, skipped)
        self._capturing = False
        self._failed = False
        self._t_start = 0.0
        self._cur_dir = ""
        self._last_gap: Optional[float] = None
        self.iterations: List[Dict[str, Any]] = []
        # classified per-window, kept separately: device-pid ops are only
        # identifiable while the process_name metadata is at hand, so
        # summary() must never re-run op_events() over retained state
        self._ops: List[dict] = []          # op events, all windows
        self._host_events: List[dict] = []  # host phase-window events

    # ----------------------------------------------------- window control

    def iteration(self, index: int = 0) -> _IterWindow:
        return _IterWindow(self, index)

    def _enter(self, index: int) -> None:
        self._seen += 1
        if (self._seen <= 1 or self._failed
                or len(self.iterations) >= self.profile_iters):
            return  # compile firing / already done / profiler unusable
        self._cur_dir = os.path.join(self.log_dir, "iter_%05d" % index)
        try:
            import jax
            jax.profiler.start_trace(self._cur_dir)
        except Exception as exc:  # profiler busy (profile_dir) or absent
            self._failed = True
            log.warning("devprof: start_trace failed, device-time "
                        "attribution disabled for this run: %s", exc)
            return
        self._capturing = True
        self._t_start = time.perf_counter()

    def _exit(self, index: int) -> None:
        if not self._capturing:
            return
        self._capturing = False
        host_s = time.perf_counter() - self._t_start
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as exc:
            self._failed = True
            log.warning("devprof: stop_trace failed: %s", exc)
            return
        events: List[dict] = []
        for path in find_capture_files(self._cur_dir):
            try:
                events.extend(load_trace_events(path))
            except Exception as exc:
                log.warning("devprof: unreadable artifact %s: %s", path, exc)
        ops = op_events(events)
        busy_us = _busy_us(ops)
        # host_ms spans start_trace-return to stop_trace-call, so any
        # profiler-induced host overhead inside the window counts as idle
        # gap — on short iterations idle_gap_fraction is biased high
        host_us = host_s * 1e6
        overlap = min(1.0, busy_us / host_us) if host_us > 0 else 0.0
        gap = round(max(0.0, 1.0 - overlap), 4)
        self._last_gap = gap
        self._ops.extend(ops)
        self._host_events.extend(
            ev for ev in events if host_phase(ev) is not None)
        self.iterations.append({
            "iteration": int(index),
            "host_ms": round(host_s * 1e3, 4),
            "device_busy_ms": round(busy_us / 1e3, 4),
            "overlap_fraction": round(overlap, 4),
            "idle_gap_fraction": gap,
        })
        counters.event("devprof_capture", iteration=int(index),
                       ops=len(ops), device_busy_ms=round(busy_us / 1e3, 3),
                       idle_gap_fraction=gap)
        from . import metrics as obs_metrics
        obs_metrics.note_capture()
        if not self.keep_artifacts:
            shutil.rmtree(self._cur_dir, ignore_errors=True)

    # ---------------------------------------------------------- reporting

    def pop_idle_gap(self) -> Optional[float]:
        """The just-captured iteration's idle-gap fraction, once (the
        flight-recorder progress record consumes it)."""
        gap, self._last_gap = self._last_gap, None
        return gap

    def summary(self) -> Optional[Dict[str, Any]]:
        """The schema-versioned ``device_profile`` block: attribution over
        every captured window, plus the per-iteration accounting."""
        block: Dict[str, Any] = {
            "schema_version": SCHEMA_VERSION,
            "source": "jax.profiler",
            "profile_iters": self.profile_iters,
            "captured_iterations": len(self.iterations),
            "iterations": list(self.iterations),
        }
        if self._failed:
            block["capture_failed"] = True
        block.update(attribute(self._host_events, top_k=self.top_k,
                               ops=self._ops))
        return block

    def finalize(self) -> Optional[Dict[str, Any]]:
        if self._capturing:  # training aborted mid-window
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._capturing = False
        out = self.summary()
        if self._own_dir and not self.keep_artifacts:
            shutil.rmtree(self.log_dir, ignore_errors=True)
        return out


# ------------------------------------------------- process-wide singleton

_active: Any = NULL_DEVPROF
_last_summary: Optional[Dict[str, Any]] = None


def get_devprof():
    """The process-wide device profiler (NULL_DEVPROF when disarmed)."""
    return _active


def start(log_dir: Optional[str] = None, profile_iters: int = 2,
          keep_artifacts: bool = False) -> DeviceProfiler:
    """Arm the device-time attribution plane process-wide."""
    global _active
    if isinstance(_active, DeviceProfiler):
        stop()
    _active = DeviceProfiler(log_dir=log_dir, profile_iters=profile_iters,
                             keep_artifacts=keep_artifacts)
    return _active


def stop() -> Optional[Dict[str, Any]]:
    """Disarm; returns (and stashes) the final ``device_profile`` block."""
    global _active, _last_summary
    dp, _active = _active, NULL_DEVPROF
    if isinstance(dp, DeviceProfiler):
        _last_summary = dp.finalize()
        return _last_summary
    return None


def last_summary() -> Optional[Dict[str, Any]]:
    """The most recent finalized ``device_profile`` block (bench embeds
    it after ``stop()``)."""
    return _last_summary
