"""Partition branches of the grower built as the dense one (ONE sort of the
routed column over all N rows, no read by row id, for a leaf larger than the
window table's last size): the ``partition_route_dispatch`` keys tagged
``read=dense``, 1 from a program that has the branch.  How often it RUNS is
in the traced run's operation table (the one-operand sort under
``partition/``).  None from a program that tags no branch so (the window
table alone), or has no such counter."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    tags = [_program_counters._tags(key) for key in
            _program_counters.counter("partition_route_dispatch") or {}]
    return sum(t.get("read") == "dense" for t in tags) or None
