"""Seconds spent deciding the bundles (the sampled rows' non-zero pattern and
the greedy packing), inside ``Dataset.construct``: the program's
``phase_seconds{phase=dataset.find_bundles}``."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    return _program_counters.phase_seconds("dataset.find_bundles")
