"""Device self time under ``node_tables``, a tree: the split loop's reads
and writes of its [L]-row tables (the best leaf, the split's rows, the
node's record, the children's splits).  Part of
``grower_other_ms_per_tree``."""
from benchmarks.harness import sub_scopes


def read(ctx):
    return sub_scopes.read(ctx, "node_tables_ms_per_tree")
