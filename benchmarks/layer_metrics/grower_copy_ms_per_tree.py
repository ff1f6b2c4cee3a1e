"""Device self time of the grower program's copies and asynchronous slices
under no ``named_scope`` of either pass, a tree (the HLO names in this
metric's ``counts``): whole copies of ``order``, ``rl`` evicted to HBM and
fetched back.  Part of ``grower_other_ms_per_tree``."""
from benchmarks.harness import sub_scopes


def read(ctx):
    return sub_scopes.read(ctx, "grower_copy_ms_per_tree")
