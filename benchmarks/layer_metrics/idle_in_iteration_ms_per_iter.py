"""Device-idle time of the window that lies under an ``lgb:iteration`` span,
per iteration: the chip waiting on the program's own host code, as against
callbacks and the caller (``harness/program_spans.py``)."""
from benchmarks.harness import program_spans


def read(ctx):
    spans = program_spans.load()
    if not spans or not spans["iterations"]:
        return None
    return spans["idle_in_iteration_ns"] / 1e6 / spans["iterations"]
