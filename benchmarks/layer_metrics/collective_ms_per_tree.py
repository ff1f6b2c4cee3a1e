"""Device time of the collective operations inside ``bench:window``, per
tree and per device: the operations whose HLO opcode is ``all-reduce``,
``reduce-scatter``, ``all-gather``, ``collective-permute`` or
``all-to-all`` (their ``-start`` and ``-done`` halves too), read off the
operation's HLO text in its metadata (the v5e compile names the grow
loop's all-reduce after the jax op, ``psum.50``), or whose short name
starts with one; their durations summed over every device plane and
divided by the iterations and by the device count (``devices`` of the
run's context).  A collective holds no operation inside it, so its
duration is its self time.

The capture is the run's one (found as ``harness/sub_scopes.py`` finds
it) read again: its device operations and the window's span.  None
without a capture, a window, or a collective in it (a run on one chip)."""
import re

from benchmarks.harness import program_spans, trace, xplane

KINDS = ("all-reduce", "reduce-scatter", "all-gather", "collective-permute",
         "all-to-all")
OPCODE = re.compile(r"\s(?:" + "|".join(KINDS) + r")(?:-start|-done)?\(")
CTX_KEY = "collective_ms_per_tree"


def is_collective(text):
    """``text``: an operation's HLO text or its short name."""
    return text.startswith(KINDS) or bool(OPCODE.search(text))


def collective_ns(path):
    """(window ns of collectives, operations) of one capture; None without
    the window span."""
    def keep(plane, line, name):
        return ((plane.startswith("/device:") and line == "XLA Ops")
                or name == trace.WINDOW_SPAN)
    events = xplane.read_events(path, keep)
    win = [(e["ts"], e["ts"] + e["dur"]) for e in events
           if e["name"] == trace.WINDOW_SPAN]
    if not win:
        return None
    t0, t1 = win[0]
    ops = [e for e in events if e["plane"].startswith("/device:")
           and (is_collective(e["name"]) or is_collective(e["meta"]))]
    return (sum(max(0.0, min(e["ts"] + e["dur"], t1) - max(e["ts"], t0))
                for e in ops), len(ops))


def read(ctx):
    if not ctx.get("trace") or not ctx.get("iterations"):
        return None
    if CTX_KEY not in ctx:
        path = program_spans.newest_capture()
        ctx[CTX_KEY] = collective_ns(path) if path else None
    got = ctx[CTX_KEY]
    if not got or not got[1]:
        return None
    return got[0] / 1e6 / ctx["iterations"] / max(ctx.get("devices", 1), 1)
