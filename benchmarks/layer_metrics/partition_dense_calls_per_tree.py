"""How often the partition's dense branch RUNS, a tree: the ``sort``
operations under ``part_dense`` in the window over its iterations (the
branch is one sort a call).  A reading, not a goal: ``better`` is
``lower`` for want of a third value."""
from benchmarks.harness import sub_scopes


def read(ctx):
    return sub_scopes.read(ctx, "partition_dense_calls_per_tree")
