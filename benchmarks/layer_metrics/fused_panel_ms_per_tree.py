"""Device self time under ``fused_panel``, a tree: what every tree builds
anew out of arrays that do not change (the padded copies, the
column-major copy of the bins, the packed panel).  Part of
``grower_other_ms_per_tree``."""
from benchmarks.harness import sub_scopes


def read(ctx):
    return sub_scopes.read(ctx, "fused_panel_ms_per_tree")
