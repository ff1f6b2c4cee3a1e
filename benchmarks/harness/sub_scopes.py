"""A second pass over the run's one capture: the ``named_scope`` tokens that
stand INSIDE another (``partition/part_read``, ``histogram/hist_root``) or
beside the read ones inside the grower's unscoped rest (``fused_panel``,
``node_tables``), and the unscoped copies of the carried arrays.

``harness/trace.py`` charges an operation to the LEFTMOST wanted token of
its name (``scope_of``), so a token nested under ``partition`` reads 0
there, and one handed to it that stands at the top would leave
``grower_other_ms_per_tree``.  This pass leaves the first one's numbers as
they are and reads the same capture again: an operation's *self* time
(``trace.self_times``, inside ``bench:window``) goes to EVERY token of this
pass that stands in its own name or, lacking any, in its nearest enclosing
operation's that has one.  Inclusive: ``part_route`` holds
``bundle_decode`` and both read.

The tokens are the ``sub_scope`` key of every ``layer_metrics/*.json`` that
has one (not ``scope``: ``metrics.scopes_wanted`` hands that to the first
pass) and ``PRINTED_ONLY``, which a run's log carries and no metric reads.
Per token: self ms, and the operations whose HLO name starts with ``sort``
(the partition's dense branch runs once per such operation under
``part_dense``).  ``copies``: self ms of the grower program's operations
under NO token of either pass whose HLO name is one of the ``counts`` list
of the metric that reads them (whole copies of ``order``, ``rl`` evicted
and fetched); read only where this pass found a token of its own at the top
level, because without ``fused_panel`` the panel's own copies would be
among them.  A capture of a program without the tokens gives None for
each and raises nothing.

The capture is found as ``program_spans`` finds it (``ctx`` holds neither
the trace directory nor the cell's name) and read with ``trace.load_events``,
once a run; one line on standard error says every token, the
residues of ``partition`` and of the unscoped rest, and what the pass cost.
"""
import os
import re
import sys
import time

from . import cells, program_spans, trace

PRINTED_ONLY = ("rank_sort", "rank_pairs", "rank_write")
REST_TOP = 12
CTX_KEY = "sub_scopes"          # where a run's context keeps the result


def _specs():
    folder = os.path.join(cells.ROOT, "layer_metrics")
    return [cells.load_json("layer_metrics", f)
            for f in sorted(os.listdir(folder)) if f.endswith(".json")]


def tokens_wanted():
    """(this pass's tokens, the first pass's, the HLO names counted as
    copies): ``sub_scope``, ``scope`` and ``counts`` of the metrics' files,
    found by listing the directory."""
    specs = _specs()
    sub = [s["sub_scope"] for s in specs if s.get("sub_scope")]
    first = [s["scope"] for s in specs if s.get("scope")]
    # (the rooflines' ``counts`` is a sentence: what their work counts)
    counts = [n for s in specs if isinstance(s.get("counts"), list)
              for n in s["counts"]]
    return (list(dict.fromkeys(sub + list(PRINTED_ONLY))),
            list(dict.fromkeys(first)), list(dict.fromkeys(counts)))


def _token_re(tokens):
    """A token between the separators of a name stack, as the first pass
    spells it (``trace.reduce_trace``)."""
    if not tokens:
        return re.compile(r"(?!)")              # matches nothing
    return re.compile(r"(?:^|[/ .])(" + "|".join(map(re.escape, tokens))
                      + r")(?=[/ .\d]|$)")


def nearest(ev, chain, own):
    """``own(e)`` of the operation itself, else of the nearest enclosing
    operation for which it is not empty.  ``own`` is asked once for each
    distinct (name, meta): a capture repeats a few thousand of them in
    every split, and the patterns' searches through the operations' HLO
    text were most of this pass's time."""
    for e in [ev] + chain[::-1]:
        key = (e["name"], e["meta"])
        if key not in own.seen:
            own.seen[key] = own(e)
        if own.seen[key]:
            return own.seen[key]
    return None


def asked_once(fn):
    fn.seen = {}
    return fn


def hlo_kind(ev):
    """``copy-done.8`` -> ``copy-done``."""
    return ev["name"].split(".")[0]


def reduce_sub_scopes(events, sub, first, counts, program="grow_tree"):
    """The second pass's numbers (nanoseconds), or None where the capture
    holds no device operation in the window."""
    ops = [e for e in events if trace.is_op(e)]
    win = trace.window_of(events)
    if not ops or win is None:
        return None
    t0, t1 = win
    ops = [e for e in ops if e["ts"] + e["dur"] > t0 and e["ts"] < t1]
    if not ops:
        return None
    sub_re, first_re = _token_re(sub), _token_re(first)
    modules = [(e["ts"], e["ts"] + e["dur"]) for e in events
               if trace.is_module(e) and program in e["name"]]

    def in_program(ev, chain):          # as trace.reduce_trace's
        if any(program in e["meta"] or program in e["name"]
               for e in [ev] + chain):
            return True
        mid = ev["ts"] + ev["dur"] / 2
        return any(a <= mid <= b for a, b in modules)

    @asked_once
    def own_tokens(e):
        return frozenset(sub_re.findall(e["name"])) \
            | frozenset(sub_re.findall(e["meta"]))

    @asked_once
    def own_scope(e):
        return trace.scope_of(e, [], first_re)

    ns = {t: 0.0 for t in sub}
    sorts = {t: 0 for t in sub}
    seen, top_level = set(), False
    copies = 0.0
    rest = {}                   # first-pass scope or "other" -> {op: ns}
    for ev, self_ns, chain in trace.self_times(ops):
        mine = nearest(ev, chain, own_tokens) or frozenset()
        outer = nearest(ev, chain, own_scope)
        for t in mine:
            ns[t] += self_ns
            sorts[t] += hlo_kind(ev) == "sort"
        seen |= mine
        if mine:
            top_level = top_level or outer is None
            continue
        if outer is None and not in_program(ev, chain):
            continue
        if outer is None and hlo_kind(ev) in counts:
            copies += self_ns
            continue
        table = rest.setdefault(outer or "other", {})
        table[ev["name"]] = table.get(ev["name"], 0.0) + self_ns
    return {"ns": {t: ns[t] for t in sub if t in seen},
            "sorts": {t: sorts[t] for t in sub if t in seen},
            "copies_ns": copies if top_level else None,
            "rest_ns": rest, "op_count": len(ops)}


def say_line(r, iterations, seconds):
    n = max(iterations, 1)

    def ms(v):
        return f"{v / 1e6 / n:.3f}"
    parts = [f"{t}={ms(v)}"
             + (f"(sorts {r['sorts'][t]})" if r["sorts"][t] else "")
             for t, v in r["ns"].items()]
    if r["copies_ns"] is not None:
        parts.append(f"copies={ms(r['copies_ns'])}")
    print(f"bench: sub-scopes: {' '.join(parts) or '(none)'} (ms a tree; "
          f"{r['op_count']} events in {seconds:.1f} s)", file=sys.stderr)
    if not r["ns"]:
        return
    for scope in ("partition", "other"):
        table = r["rest_ns"].get(scope, {})
        top = sorted(table.items(), key=lambda kv: -kv[1])[:REST_TOP]
        print(f"bench: sub-scopes: {scope} under no token of this pass "
              f"{ms(sum(table.values()))}: "
              + " ".join(f"{k}={ms(v)}" for k, v in top),
              file=sys.stderr, flush=True)


def load(ctx):
    """This process's newest capture through the second pass, once a run
    (the result is kept in the run's context); None without a capture or a
    device operation in its window."""
    if CTX_KEY not in ctx:
        t = time.perf_counter()
        path = program_spans.newest_capture()
        result = None
        if path:
            # <trace_dir>/plugins/profile/<time>/<host>.xplane.pb
            trace_dir = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.dirname(path))))
            result = reduce_sub_scopes(trace.load_events(trace_dir),
                                       *tokens_wanted(),
                                       ctx.get("program", "grow_tree"))
        if result:
            say_line(result, ctx.get("iterations") or 0,
                     time.perf_counter() - t)
        ctx[CTX_KEY] = result
    return ctx[CTX_KEY]


def read(ctx, metric):
    """The value of the metric named ``metric`` (its file says what it
    reads: ``sub_scope``'s ms a tree, that token's ``sort`` operations a
    tree where ``"reads": "sorts"``, or the copies' ms a tree where it has
    ``counts``); None where the capture has nothing of it."""
    if not ctx.get("trace") or not ctx.get("iterations"):
        return None
    r = load(ctx)
    if not r:
        return None
    spec = cells.load_json("layer_metrics", metric + ".json")
    if "sub_scope" not in spec:
        value = r["copies_ns"]
        return None if value is None else value / 1e6 / ctx["iterations"]
    token = spec["sub_scope"]
    if token not in r["ns"]:
        return None
    if spec.get("reads") == "sorts":
        return r["sorts"][token] / ctx["iterations"]
    return r["ns"][token] / 1e6 / ctx["iterations"]
