"""Device self time under ``split_find/cat_scan``, a tree: the categorical
split find's sort of every column's bins by their smoothed ratio, the prefix
sums of both directions and the ``max_cat_group`` accounting scan.  None
from a program without the scope, or where no column is categorical.

The token stands inside ``split_find``, so the first pass charges its
operations there (``harness/trace.py:scope_of``); this reader reads the
run's one capture again with ``harness/sub_scopes.reduce_sub_scopes`` for
this token alone, once a run, and only in the cells that list it."""
import os

from benchmarks.harness import program_spans, sub_scopes, trace

TOKEN = "cat_scan"
CTX_KEY = "cat_scan_pass"        # where a run's context keeps the pass


def read(ctx):
    if not ctx.get("trace") or not ctx.get("iterations"):
        return None
    if CTX_KEY not in ctx:
        result, path = None, program_spans.newest_capture()
        if path:
            # <trace_dir>/plugins/profile/<time>/<host>.xplane.pb
            trace_dir = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.dirname(path))))
            _, first, counts = sub_scopes.tokens_wanted()
            result = sub_scopes.reduce_sub_scopes(
                trace.load_events(trace_dir), [TOKEN], first, counts,
                ctx.get("program", "grow_tree"))
        ctx[CTX_KEY] = result
    r = ctx[CTX_KEY]
    if not r or TOKEN not in r["ns"]:
        return None
    return r["ns"][TOKEN] / 1e6 / ctx["iterations"]
