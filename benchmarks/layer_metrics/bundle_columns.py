"""Physical columns that exclusive feature bundling left of the logical ones:
the ``physical`` tag of the program's ``efb_layout`` counter (one count a data
set that was bundled, at its construction).  It is the width the histogram
kernel and the pool work at.  None where nothing was bundled, or from a
program that does not count it."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    cols = [int(_program_counters._tags(key)["physical"])
            for key in _program_counters.counter("efb_layout") or {}
            if "physical" in _program_counters._tags(key)]
    return max(cols) if cols else None
