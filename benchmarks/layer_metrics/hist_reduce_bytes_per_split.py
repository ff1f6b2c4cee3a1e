"""Bytes the cross-chip reductions inside the grow loop's body move at
every split: the program's counter ``grow_loop_collective_bytes{op}``
(``boosting._record_mesh_layout``: the compiled grow program's collectives
read once after its first call, ``utils/jaxpr_audit.hlo_loop_census``),
its ``all-reduce`` and ``reduce-scatter`` payloads summed.  One leaf's
histogram table where only the histogram crosses chips; more where the
compiler moves rows.  None from a program without the counter."""
from benchmarks.layer_metrics import _program_counters

REDUCTIONS = ("all-reduce", "reduce-scatter")


def read(ctx):
    by_op = _program_counters.counter("grow_loop_collective_bytes")
    if not by_op:
        return None
    return float(sum(v for key, v in by_op.items()
                     if _program_counters._tags(key).get("op") in REDUCTIONS))
