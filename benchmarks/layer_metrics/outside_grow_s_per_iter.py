"""Window time per iteration less the device time of the grower's program
per iteration: score update, evaluation, host work and gaps."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx["iterations"] or not trace["program_ms"]:
        return None
    return (trace["window_s"] - trace["program_ms"] / 1e3) / ctx["iterations"]
