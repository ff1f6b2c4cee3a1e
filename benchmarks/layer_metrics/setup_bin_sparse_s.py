"""Seconds spent binning a sparse matrix from its stored entries (grouping
them by column, binning each column's values, writing the bundle slots),
inside ``Dataset.construct``: the program's
``phase_seconds{phase=dataset.bin_sparse}``."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    return _program_counters.phase_seconds("dataset.bin_sparse")
