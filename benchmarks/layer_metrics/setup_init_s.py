"""Seconds inside ``GBDT._setup_device``: upload, placement and the grower's
build, its child ``setup.grower`` included: the program's
``phase_seconds{phase=setup.device}``."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    return _program_counters.phase_seconds("setup.device")
