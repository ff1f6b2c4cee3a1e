"""Device self time of the grower's program under none of the read scopes:
the loop body's bookkeeping, the histogram pool, the tree arrays."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx["iterations"] or not trace["program_other_ms"]:
        return None
    return trace["program_other_ms"] / ctx["iterations"]
