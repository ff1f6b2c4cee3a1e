"""Device self time under ``histogram/hist_root``, a tree: the one
histogram over all N rows (the kernel's block fetch), apart from the
per-split ones: ``hist_kernel_ms_per_tree`` less this is theirs."""
from benchmarks.harness import sub_scopes


def read(ctx):
    return sub_scopes.read(ctx, "hist_root_ms_per_tree")
