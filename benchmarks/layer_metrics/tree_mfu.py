"""The whole step's share of the chip's peak: least time the chip could take
for the window's trees (histograms + row movement + one pass over scores and
gradients: the same counts as the two rooflines) over the window's wall
time.  Memory-bound, so the peak is the HBM's."""
from benchmarks.harness.metrics import roofline_share


def read(ctx):
    return roofline_share(ctx, "step", ctx["elapsed_s"])
