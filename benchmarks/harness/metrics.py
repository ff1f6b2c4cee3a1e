"""Per-layer metrics: one file each under ``layer_metrics/``.

``<name>.json`` says how the metric is read: ``"scope_ms_per_tree"`` (device
self time under a ``named_scope``, per tree) and ``"scope_roofline"`` (least
time for a layer's counted work over the device time under its scope) need no
code; ``"python"`` hands the run's context to ``read(ctx)`` in ``<name>.py``
beside it.  A reader that finds nothing to read returns None and the metric
is left out of the line.
"""
from . import cells
from .peaks import least_seconds


def scope_ms_per_tree(ctx, spec):
    trace = ctx.get("trace")
    if not trace or not ctx["iterations"]:
        return None
    ms = trace["scope_ms"].get(spec["scope"])
    return ms / ctx["iterations"] if ms else None


def roofline_share(ctx, layer, seconds):
    """100 x least time for the window's counted work in ``layer`` over
    ``seconds``; None without peaks, work or time."""
    if not ctx.get("peaks") or not seconds or layer not in ctx["work"]:
        return None
    return 100.0 * least_seconds(ctx["work"][layer], ctx["peaks"])[0] / seconds


def scope_roofline(ctx, spec):
    trace = ctx.get("trace")
    if not trace:
        return None
    return roofline_share(ctx, spec["work"],
                          trace["scope_ms"].get(spec["scope"], 0) / 1e3)


READERS = {"scope_ms_per_tree": scope_ms_per_tree,
           "scope_roofline": scope_roofline}


def read_metric(name, ctx):
    spec = cells.load_json("layer_metrics", name + ".json")
    if spec["reader"] == "python":
        return cells.load_module("layer_metrics", name + ".py").read(ctx)
    return READERS[spec["reader"]](ctx, spec)


def scopes_wanted(names):
    """The named_scope tokens that the listed metrics read."""
    scopes = [cells.load_json("layer_metrics", n + ".json").get("scope")
              for n in names]
    return list(dict.fromkeys(s for s in scopes if s))
