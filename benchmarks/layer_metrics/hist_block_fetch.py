"""Call sites of the fused histogram kernel built with the block fetch (one
copy a row tile, for a window its caller built as the identity: the root):
the ``hist_dispatch`` keys tagged ``fetch=block``.  0 where the kernel ran
with every site indexed; None where it did not run, or from a program that
does not tag its fetch."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    tags = [_program_counters._tags(key)
            for key in _program_counters.counter("hist_dispatch") or {}]
    fetches = [t["fetch"] for t in tags if "fetch" in t]
    return fetches.count("block") if fetches else None
