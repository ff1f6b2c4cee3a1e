"""Device self time under ``bundle_decode``, a tree: the routed column's
physical slots decoded to the split column's logical bins
(``decode_bundle_bin``, inside ``partition/part_route``); only a
bundled data set has it."""
from benchmarks.harness import sub_scopes


def read(ctx):
    return sub_scopes.read(ctx, "bundle_decode_ms_per_tree")
