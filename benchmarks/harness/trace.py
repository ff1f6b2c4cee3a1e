"""From a profiler trace to numbers: device busy union, device self time per
``named_scope``, idle gaps by host span, longest device operations.

The arithmetic (busy union without double counting, scope token in the
operation's name or metadata) is copied from ``lightgbm_tpu/obs/devprof.py``
so that a later change to the program's file cannot move the yardstick; the
original is listed in PERF.md's Open questions.  Two things are new: it
reads the profiler's ``.xplane.pb`` (``xplane.py``) instead of a Chrome
trace, and it charges every operation its *self* time,
because the TPU's operation line nests (a ``while`` or ``conditional`` holds
the operations of its body), so plain sums would count time twice.

An event is a dict {"plane", "line", "name", "meta", "ts", "dur"}, times in
nanoseconds on the trace's clock.  ``load_events`` makes them from a trace;
``tests/recorded_trace.json`` keeps a few hundred from a real chip run.
"""
import glob
import os
import re

HOST_SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
UNSPANNED = "bench:between_iterations"
TOP_K = 10
MIN_GAP_NS = 1000.0


def load_events(trace_dir):
    """Events of the newest capture under ``trace_dir``: every device
    plane's lines, and of the host planes only ``bench:`` spans and
    XLA:CPU operations (those whose stats name an ``hlo_module``)."""
    from . import xplane
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        return []

    def keep(plane, line, name):
        return (plane.startswith("/device:")
                or name.startswith(HOST_SPAN_PREFIX)
                or line.startswith("tf_XLA"))
    events = xplane.read_events(files[-1], keep)
    return [e for e in events
            if e["plane"].startswith("/device:")
            or e["name"].startswith(HOST_SPAN_PREFIX)
            or "jit_" in e["meta"]]


def is_op(ev):
    """A device operation: on a device plane's operation line, or an
    XLA:CPU operation (host plane, kept by ``load_events`` for ``hlo_op``)."""
    if ev["name"].startswith(HOST_SPAN_PREFIX):
        return False
    if ev["plane"].startswith("/device:"):
        return ev["line"] == "XLA Ops"
    return True


def is_module(ev):
    return ev["plane"].startswith("/device:") and ev["line"] == "XLA Modules"


def host_spans(events):
    return sorted(((e["ts"], e["ts"] + e["dur"], e["name"]) for e in events
                   if e["name"].startswith(HOST_SPAN_PREFIX)),
                  key=lambda s: (s[0], -s[1]))


def window_of(events):
    """[start, end] of the ``bench:window`` span; the ops' extent without."""
    for a, b, name in host_spans(events):
        if name == WINDOW_SPAN:
            return a, b
    ops = [e for e in events if is_op(e)]
    if not ops:
        return None
    return (min(e["ts"] for e in ops), max(e["ts"] + e["dur"] for e in ops))


def busy_intervals(ops, t0, t1):
    """Merged [a, b] intervals in which some operation ran, clipped."""
    spans = sorted((max(e["ts"], t0), min(e["ts"] + e["dur"], t1))
                   for e in ops)
    merged = []
    for a, b in spans:
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def self_times(ops):
    """(event, self ns, chain of enclosing events) for every operation:
    its duration less what its direct children cover, line by line."""
    out = []
    by_line = {}
    for e in ops:
        by_line.setdefault((e["plane"], e["line"]), []).append(e)
    for evs in by_line.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []                       # records [event, self ns, chain]
        for e in evs:
            while stack and e["ts"] >= _end(stack[-1][0]):
                stack.pop()
            rec = [e, e["dur"], [s[0] for s in stack]]
            if stack:
                parent = stack[-1]
                parent[1] -= min(_end(e), _end(parent[0])) - e["ts"]
            out.append(rec)
            stack.append(rec)
    return [(ev, max(self_ns, 0.0), chain) for ev, self_ns, chain in out]


def _end(ev):
    return ev["ts"] + ev["dur"]


def scope_of(ev, chain, scope_re):
    """The scope token of the operation itself, else of the nearest
    enclosing operation that has one."""
    for e in [ev] + chain[::-1]:
        m = scope_re.search(e["name"]) or scope_re.search(e["meta"])
        if m:
            return m.group(1)
    return None


def reduce_trace(events, scopes, program="grow_tree"):
    """The trace's numbers, or None where it holds no device operation.

    ``scopes`` are the ``named_scope`` tokens to charge; ``program`` marks
    the jitted program (by module name) whose unscoped self time is
    reported as ``other``.
    """
    ops = [e for e in events if is_op(e)]
    win = window_of(events)
    if not ops or win is None:
        return None
    t0, t1 = win
    ops = [e for e in ops if e["ts"] + e["dur"] > t0 and e["ts"] < t1]
    if not ops:
        return None
    scope_re = re.compile(r"(?:^|[/ .])(" + "|".join(map(re.escape, scopes))
                          + r")(?:[/ .\d]|$)")
    modules = [(e["ts"], e["ts"] + e["dur"]) for e in events
               if is_module(e) and program in e["name"]]

    def in_program(ev, chain):
        if any(program in e["meta"] or program in e["name"]
               for e in [ev] + chain):
            return True
        mid = ev["ts"] + ev["dur"] / 2
        return any(a <= mid <= b for a, b in modules)

    scope_ns = {s: 0.0 for s in scopes}
    other_ns = 0.0
    program_ns = 0.0
    per_op = {}
    for ev, self_ns, chain in self_times(ops):
        scope = scope_of(ev, chain, scope_re)
        inside = in_program(ev, chain)
        if scope is not None:
            scope_ns[scope] += self_ns
        elif inside:
            other_ns += self_ns
        if inside:
            program_ns += self_ns
        key = f"{scope}/{ev['name']}" if scope else ev["name"]
        per_op[key] = per_op.get(key, 0.0) + self_ns
    busy = busy_intervals(ops, t0, t1)
    busy_ns = sum(b - a for a, b in busy)

    spans = [s for s in host_spans(events) if s[2] != WINDOW_SPAN]
    gaps = {}
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a < MIN_GAP_NS:
            continue
        mid = (a + b) / 2
        covering = [s for s in spans if s[0] <= mid <= s[1]]
        name = min(covering, key=lambda s: s[1] - s[0])[2] if covering \
            else UNSPANNED
        gaps[name] = gaps.get(name, 0.0) + (b - a)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP_K]]

    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "scope_ms": {s: v / 1e6 for s, v in scope_ns.items()},
        "program_other_ms": other_ns / 1e6,
        "program_ms": program_ns / 1e6,
        "device_ops": top(per_op),
        "idle_gaps": top(gaps),
        "op_count": len(ops),
    }
