"""peak_bytes_in_use over bytes_limit, read when the window has closed."""


def read(ctx):
    mem = ctx["memory"]
    if not mem["limit_bytes"]:
        return None
    return 100.0 * mem["peak_bytes"] / mem["limit_bytes"]
