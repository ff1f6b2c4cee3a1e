"""Grower jit entries after the window minus before: 0 in a sound run."""


def read(ctx):
    before, after = ctx["jit_entries"]
    if before is None or after is None:
        return None
    return after - before
