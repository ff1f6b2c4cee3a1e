"""Device self time under ``partition/part_route``, a tree: the pass over
all N rows that every split pays (the split column routed into a word a
row, ``rl``'s select, the bit packing), ``bundle_decode`` inside it."""
from benchmarks.harness import sub_scopes


def read(ctx):
    return sub_scopes.read(ctx, "partition_route_ms_per_tree")
