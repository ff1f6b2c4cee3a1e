"""Device self time under ``partition/.../part_dense``, a tree: the dense
branch's one-operand sort of all N rows, its key, the select into
``order``."""
from benchmarks.harness import sub_scopes


def read(ctx):
    return sub_scopes.read(ctx, "partition_dense_ms_per_tree")
