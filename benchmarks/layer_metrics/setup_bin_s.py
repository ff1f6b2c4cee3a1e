"""Seconds inside ``Dataset.construct`` (load, bin, pack), every data set of
the run: the program's ``phase_seconds{phase=dataset.construct}``."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    return _program_counters.phase_seconds("dataset.construct")
