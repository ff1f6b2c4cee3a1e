"""The program's own host spans in a profiler capture: ``lgb:`` annotations.

``lightgbm_tpu/obs/trace.phase`` puts one ``lgb:<name>`` annotation around
every boundary of the boosting loop and of set-up, tracer on or off, so a
``--trace 1`` run's ``.xplane.pb`` holds them on the clock of the device
operations.  This file reads them (``harness/trace.py`` keeps only the
benchmark's ``bench:`` spans) and gives

- the spans, and each span's *self* time (its duration less its children's),
- the window's device-idle time, cut at the spans' edges and put down to the
  narrowest ``lgb:`` span that covers each piece,
- the device time of each program (``XLA Modules`` line) in the window,

and says so on standard error (``program idle gaps: tree.wait=... score=...``)
so that a run's log carries the breakdown.  A program without the spans (the
parent of the PR that brought them) gives tables that are empty; a process
with no capture gives None.

``ctx`` holds neither the trace directory nor the cell's name, so the capture
is the newest under ``.bench_cache/trace/*/`` written since this process
started; it is read once per process.  The window is the ``bench:window``
span, as in ``harness/trace.py``.

An event is what ``xplane.read_events`` makes: {"plane", "line", "name",
"meta", "ts", "dur"}, nanoseconds.
"""
import glob
import os
import sys

from . import cells

PREFIX = "lgb:"
WINDOW_SPAN = "bench:window"
ITERATION = "iteration"            # a span's name, the prefix taken off
NO_SPAN = "(no lgb span)"
MIN_GAP_NS = 1000.0
TRACE_ROOT = os.path.join(cells.CHECKOUT, ".bench_cache", "trace")

_loaded = {}


def process_start():
    """Seconds since the epoch at which this process started; 0 where
    /proc does not say (then any capture counts as this process's)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(float(line.split()[1]) for line in f
                        if line.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return 0.0


def newest_capture(root=None, since=None):
    """Path of the newest ``.xplane.pb`` of any cell under ``root`` that was
    written at or after ``since``; None without one."""
    root = TRACE_ROOT if root is None else root
    since = process_start() if since is None else since
    files = [f for f in glob.glob(os.path.join(
        root, "*", "plugins", "profile", "*", "*.xplane.pb"))
        if os.path.getmtime(f) >= since - 1.0]
    return max(files, key=os.path.getmtime) if files else None


def read_capture(path):
    """Device operations and programs, ``lgb:`` spans and the window."""
    from . import xplane

    def keep(plane, line, name):
        if plane.startswith("/device:"):
            return line in ("XLA Ops", "XLA Modules")
        return name.startswith(PREFIX) or name == WINDOW_SPAN
    return xplane.read_events(path, keep)


def is_device(ev, line):
    return ev["plane"].startswith("/device:") and ev["line"] == line


def spans_of(events):
    """The ``lgb:`` spans as dicts {"name", "ts", "end", "self_ns",
    "parent"}, outermost first on each thread; ``name`` has no prefix."""
    by_line = {}
    for e in events:
        if e["name"].startswith(PREFIX):
            by_line.setdefault((e["plane"], e["line"]), []).append(e)
    out = []
    for evs in by_line.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in evs:
            span = {"name": e["name"][len(PREFIX):], "ts": e["ts"],
                    "end": e["ts"] + e["dur"], "self_ns": e["dur"],
                    "parent": None}
            while stack and span["ts"] >= stack[-1]["end"]:
                stack.pop()
            if stack:
                span["parent"] = stack[-1]["name"]
                stack[-1]["self_ns"] -= min(span["end"], stack[-1]["end"]) \
                    - span["ts"]
            out.append(span)
            stack.append(span)
    for span in out:
        span["self_ns"] = max(span["self_ns"], 0.0)
    return out


def busy_intervals(ops, t0, t1):
    """Merged [a, b] in which some device operation ran, clipped."""
    merged = []
    for a, b in sorted((max(e["ts"], t0), min(e["ts"] + e["dur"], t1))
                       for e in ops):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def idle_by_span(busy, spans, t0, t1):
    """({span name: idle ns}, idle ns under an iteration span): every gap of
    the window cut at the spans' edges, each piece put down to the narrowest
    span covering it."""
    by_name, in_iteration = {}, 0.0
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a < MIN_GAP_NS:
            continue
        near = [s for s in spans if s["ts"] < b and s["end"] > a]
        cuts = sorted({a, b} | {x for s in near for x in (s["ts"], s["end"])
                                if a < x < b})
        for c, d in zip(cuts, cuts[1:]):
            mid = (c + d) / 2
            covering = [s for s in near if s["ts"] <= mid <= s["end"]]
            name = min(covering, key=lambda s: s["end"] - s["ts"])["name"] \
                if covering else NO_SPAN
            by_name[name] = by_name.get(name, 0.0) + (d - c)
            if any(s["name"] == ITERATION for s in covering):
                in_iteration += d - c
    return by_name, in_iteration


def reduce_spans(events):
    """The capture's numbers; None where it holds no window or no device
    operation in it."""
    window = [e for e in events if e["name"] == WINDOW_SPAN]
    if not window:
        return None
    t0, t1 = window[0]["ts"], window[0]["ts"] + window[0]["dur"]
    ops = [e for e in events if is_device(e, "XLA Ops")
           and e["ts"] + e["dur"] > t0 and e["ts"] < t1]
    if not ops:
        return None
    spans = [s for s in spans_of(events) if s["end"] > t0 and s["ts"] < t1]
    busy = busy_intervals(ops, t0, t1)
    idle, in_iteration = idle_by_span(busy, spans, t0, t1)
    self_ns = {}
    for s in spans:
        self_ns[s["name"]] = self_ns.get(s["name"], 0.0) + s["self_ns"]
    programs = {}
    for e in events:
        if is_device(e, "XLA Modules"):
            ns = min(e["ts"] + e["dur"], t1) - max(e["ts"], t0)
            if ns > 0:
                name = e["name"].split("(")[0]
                programs[name] = programs.get(name, 0.0) + ns
    return {
        "window_ns": t1 - t0,
        "spans": spans,
        "iterations": sum(1 for s in spans
                          if s["name"] == ITERATION
                          and s["ts"] >= t0 and s["end"] <= t1),
        "span_self_ns": self_ns,
        "idle_ns": idle, "idle_in_iteration_ns": in_iteration,
        "program_ns": programs,
    }


def _table(d, scale, top=12):
    return " ".join(f"{k}={v / scale:.3f}" for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]) \
        or "(none)"


def say_tables(r):
    out = sys.stderr
    n = max(r["iterations"], 1)
    print(f"bench: program idle gaps: {_table(r['idle_ns'], 1e6)} "
          f"(ms in a window of {r['window_ns'] / 1e9:.3f} s, "
          f"{r['iterations']} lgb:iteration spans)", file=out)
    print(f"bench: program span self time: "
          f"{_table(r['span_self_ns'], 1e6 * n)} (ms per iteration)",
          file=out)
    print(f"bench: device programs: {_table(r['program_ns'], 1e6 * n)} "
          f"(ms per iteration)", file=out, flush=True)


def load():
    """The newest capture of this process, reduced (once); None without."""
    if "result" not in _loaded:
        path = newest_capture()
        result = reduce_spans(read_capture(path)) if path else None
        if result:
            say_tables(result)
        _loaded["result"] = result
    return _loaded["result"]
