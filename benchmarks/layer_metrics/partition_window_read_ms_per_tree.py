"""Device self time under ``partition/.../part_read``, a tree: a window
branch's slice of ``order``, its read of the bits by row id (a padded
slot each), the count and the sort key."""
from benchmarks.harness import sub_scopes


def read(ctx):
    return sub_scopes.read(ctx, "partition_window_read_ms_per_tree")
