"""The histogram width the fused kernel was built at: the ``width`` tag of
the program's ``hist_dispatch`` counter, the widest over its call sites.
None where the fused kernel did not run (a layout it refused went to an XLA
reference histogram), or from a program that does not tag its width."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    widths = [int(_program_counters._tags(key)["width"])
              for key in _program_counters.counter("hist_dispatch") or {}
              if "width" in _program_counters._tags(key)]
    return max(widths) if widths else None
