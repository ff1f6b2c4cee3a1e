"""Column tiles the fused histogram kernel walks for a row: the
``col_tiles`` tag of the program's ``hist_dispatch`` counter (1 where a row's
bin words and weights fit one 128-word tile).  None where the fused kernel
did not run, or from a program that does not tag it."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    tiles = [int(_program_counters._tags(key)["col_tiles"])
             for key in _program_counters.counter("hist_dispatch") or {}
             if "col_tiles" in _program_counters._tags(key)]
    return max(tiles) if tiles else None
