"""Window sizes the grower's partition was built with, one compiled branch
each: the distinct ``size`` tags of the program's ``partition_route_dispatch``
counter (29 at 10.5M rows, 20 at 400,000).  Read beside ``compile_s``, it
says what the table cost.  None where no partition branch was traced, or
from a program that does not tag the size."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    tags = [_program_counters._tags(key) for key in
            _program_counters.counter("partition_route_dispatch") or {}]
    return len({t["size"] for t in tags if "size" in t}) or None
