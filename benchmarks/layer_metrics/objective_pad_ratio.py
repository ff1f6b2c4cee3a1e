"""Pair slots the ranking objective was built with over the pairs the data
has: the ``pair_slots`` tag of the program's ``objective_dispatch`` counter
(every padded query's squared length, summed) over the driver's
``query_pairs`` (the sum of squared query lengths).  1 is no padding.  None
where the driver counts no pairs, or from a program that does not tag."""
from benchmarks.layer_metrics import _program_counters


def read(ctx):
    slots = [int(_program_counters._tags(key)["pair_slots"])
             for key in _program_counters.counter("objective_dispatch") or {}
             if "pair_slots" in _program_counters._tags(key)]
    if not slots or not ctx.get("query_pairs"):
        return None
    return max(slots) / ctx["query_pairs"]
