"""Slowest iteration of the window by the host's clock."""


def read(ctx):
    return max(ctx["iter_times_s"]) if ctx["iter_times_s"] else None
