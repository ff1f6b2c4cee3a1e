"""Device self time under ``partition/.../part_sort``, a tree: a window
branch's two-operand sort and the slice written back into ``order``."""
from benchmarks.harness import sub_scopes


def read(ctx):
    return sub_scopes.read(ctx, "partition_window_sort_ms_per_tree")
