"""Structural audits of the jitted grow loop: body jaxpr + compiled HLO.

The grow loop's per-split cost must scale with the rows the split touches,
not with loop-body constants: an op whose operand is O(N) (the full
``order``/``bins`` carriers) or O(L·F·B) (the ``hist_store`` pool)
executed once per split re-widens the per-split fixed cost that round 7
collapsed (measured ~5 ms/split of hidden 22 MB ``hist_store`` copies at
the 255-leaf bench shape — docs/PERF.md).  This module inventories every
such op so the regression guard (tests/test_grow_jaxpr.py) fails loudly
when one creeps back in, and the per-step profiler
(scripts/profile_grow_steps.py) prints the same inventory as evidence.

The jaxpr audit is formulation-level: XLA-inserted copies are invisible
here, but the copy-insertion pathologies observed so far were all driven
by the jaxpr formulation (read-then-double-update chains on a carried
buffer), so pinning the formulation pins the fix.

:func:`hlo_collective_census` is the compiled-HLO complement for the
GSPMD era (docs/DISTRIBUTED.md): with ``NamedSharding`` the compiler —
not a call site — decides which collectives run, so the only honest
accounting reads them back out of the compiled executable.  The census
parses the post-optimization HLO text for collective ops with byte
estimates from their result shapes; ``obs/collectives.hlo_census`` feeds
it into the counter registry and bench telemetry.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

# every collective the XLA SPMD partitioner inserts; "-start" async
# variants (TPU) are matched by prefix.  NOTE: on this jax/XLA a
# feature-sharded reduction typically compiles to an all-reduce of the
# SHARD-sized partial (each device computes only its output slice first)
# — communication-equivalent to a reduce-scatter, so judge payload BYTES,
# not op spelling, when pinning "no full-pool traffic".
HLO_COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "all-gather",
                      "collective-permute", "all-to-all")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of an HLO result type string — a single shape
    (``f32[2,64,3]{2,1,0}``) or a tuple (``(f32[8], s32[8])``)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        elems = 1
        for d in dims.split(","):
            if d:
                elems *= int(d)
        total += elems * _DTYPE_BYTES[dtype]
    return total


def hlo_collective_census(compiled_or_text) -> Dict[str, Dict[str, int]]:
    """Count compiler-inserted collectives in a compiled executable.

    Accepts a compiled object (anything with ``as_text()``) or the HLO
    text itself; returns ``{op: {"count", "bytes", "max_bytes"}}`` over
    :data:`HLO_COLLECTIVE_OPS` (ops absent from the program are absent
    from the dict).  ``bytes`` sums the result-shape payloads of every
    STATIC occurrence — a collective inside a while body is counted once,
    like the trace-time accounting of ``obs/collectives.note_collective``
    it replaces on the GSPMD path."""
    text = compiled_or_text if isinstance(compiled_or_text, str) \
        else compiled_or_text.as_text()
    out: Dict[str, Dict[str, int]] = {}
    for op in HLO_COLLECTIVE_OPS:
        # `%name = <type> all-reduce(...)` / `all-reduce-start(...)`
        for m in re.finditer(
                rf"=\s+(\(?[a-z0-9]+\[[^=]*?)\s+{op}(?:-start)?\(", text):
            nb = _shape_bytes(m.group(1))
            rec = out.setdefault(op, {"count": 0, "bytes": 0, "max_bytes": 0})
            rec["count"] += 1
            rec["bytes"] += nb
            rec["max_bytes"] = max(rec["max_bytes"], nb)
    return out


def hlo_loop_census(text: str) -> Dict[str, Dict[str, int]]:
    """:func:`hlo_collective_census` of the grow loop's body alone: of the
    ``while`` loops of the ENTRY computation the one whose body reaches
    the most HLO lines (its branches, calls and nested loops: a kernel's
    own grid loop is reached from the grow loop's, not the other way).
    What it holds runs once a split; the root's and the tree's own
    collectives stand outside it."""
    comps: Dict[str, List[str]] = {}
    entry, cur = None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if cur is None and head:
            cur = head.group(2)
            comps[cur] = []
            if head.group(1):
                entry = cur
        elif cur is not None and line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)

    def reach(root):
        seen, todo = set(), [root]
        while todo:
            name = todo.pop()
            if name in seen or name not in comps:
                continue
            seen.add(name)
            for line in comps[name]:
                todo.extend(re.findall(
                    r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)", line))
                for group in re.findall(
                        r"branch_computations=\{([^}]*)\}", line):
                    todo.extend(n.strip().lstrip("%")
                                for n in group.split(","))
        return seen

    bodies = [reach(m.group(1)) for line in comps.get(entry, [])
              for m in [re.search(r"\bwhile\(.*\bbody=%?([\w.\-]+)", line)]
              if m]
    if not bodies:
        return {}
    loop = max(bodies, key=lambda names: sum(len(comps[n]) for n in names))
    return hlo_collective_census(
        "\n".join(line for name in loop for line in comps[name]))


def _aval_elems(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _eqn_max_elems(eqn) -> int:
    ops = [v for v in list(eqn.invars) + list(eqn.outvars)
           if hasattr(v, "aval")]
    return max((_aval_elems(v) for v in ops), default=0)


def find_while_body(closed_jaxpr) -> Optional[Any]:
    """The body jaxpr of the FIRST ``while`` eqn found by recursive
    descent (the grow loop; pjit/custom-call wrappers are transparent)."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "while":
                return eqn.params["body_jaxpr"].jaxpr
            for sub in _sub_jaxprs(eqn):
                found = walk(sub)
                if found is not None:
                    return found
        return None
    return walk(closed_jaxpr.jaxpr)


def _sub_jaxprs(eqn) -> List[Any]:
    out = []
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else [val]
        for v in vals:
            jx = getattr(v, "jaxpr", None)
            if jx is not None and hasattr(jx, "eqns"):
                out.append(jx)
            elif hasattr(v, "eqns"):
                out.append(v)
    return out


def audit_loop_body(closed_jaxpr, min_elems: int,
                    recurse_branches: bool = False) -> List[Dict[str, Any]]:
    """Inventory the grow-loop BODY's eqns whose largest operand/output
    holds >= ``min_elems`` elements.

    Returns records ``{prim, elems, shapes}`` in body order.  ``cond``
    eqns (the partition / gather-bucket ``lax.switch``es) are reported as
    single records and NOT descended into by default: their branches are
    the sanctioned O(window) machinery that legitimately slices the O(N)
    carriers.  ``recurse_branches=True`` descends for exploratory use.
    """
    body = find_while_body(closed_jaxpr)
    if body is None:
        raise ValueError("no while loop found in jaxpr")
    records: List[Dict[str, Any]] = []

    def visit(jaxpr, path):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            elems = _eqn_max_elems(eqn)
            if elems >= min_elems:
                shapes = sorted(
                    {tuple(getattr(v.aval, "shape", ()))
                     for v in list(eqn.invars) + list(eqn.outvars)
                     if hasattr(v, "aval")
                     and _aval_elems(v) >= min_elems})
                records.append({"prim": name, "elems": elems,
                                "shapes": shapes, "path": path})
            if name == "cond" and not recurse_branches:
                continue
            for sub in _sub_jaxprs(eqn):
                visit(sub, path + (name,))

    visit(body, ())
    return records
