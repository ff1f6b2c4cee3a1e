"""Seconds jax spent tracing, lowering and compiling (or loading from the
compile cache) in this process: the program's ``compile_seconds`` summed
over functions and stages.  Nearly all of it falls in set-up; a compile in
the window shows in ``compiles_in_window``."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    buckets = _program_counters.counter("compile_seconds")
    return sum(buckets.values()) if buckets else None
