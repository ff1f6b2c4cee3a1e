"""Structural guard: the grow-loop body must stay free of per-split
fixed-cost ops.

Round 7 measured ~70% of deep-tree time going to per-split work that was
independent of the rows the split touched — the dominant term was XLA
copy-insertion cloning the whole ``hist_store [L, F, B, 3]`` pool twice
per split, driven by a read-then-double-update jaxpr formulation.  These
tests pin the fixed formulation so the cost class fails loudly instead of
silently re-widening:

* the loop BODY may touch O(N)-sized carriers only through the two
  ``lax.switch``es (partition + gather-bucket — the sanctioned O(window)
  machinery) and through the ONE routing of the split column under the
  ``partition`` scope, which is elementwise over N (PR 35: it feeds the
  window branches' bits and the dense vector ``rl`` alike);
* the ``hist_store`` pool may be touched only by ONE read (dynamic_slice)
  and ONE fused pair-write (scatter) — the two-dynamic_update_slice chain
  that triggered the copies must not come back;
* this also verifies the split-find stays restricted to the two fresh
  children: a rescan of stale leaves would materialize [L, F, 2B]-sized
  candidate arrays in the body, which the O(N) audit flags (the shapes
  below exceed the threshold);
* the compiled CPU executable must contain ZERO full-pool copies — the
  sharpest pin, directly on the regression XLA exhibited.
"""
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from lightgbm_tpu.grower import (FeatureMeta, GrowerConfig, _bucket_sizes,
                                 _order_tail, _partition_sizes, make_grower,
                                 pool_tiles)
from lightgbm_tpu.utils.jaxpr_audit import audit_loop_body, find_while_body

N, F, B, L = 32768, 8, 64, 15
K = pool_tiles(F, B)     # the pool's rows of 128 lanes a leaf: 16


def _grow_and_args(split_find="fused", has_missing=True):
    cfg = GrowerConfig(num_leaves=L, min_data_in_leaf=1, max_bin=B,
                       hist_method="segment", split_find=split_find,
                       has_missing=has_missing)
    meta = FeatureMeta(
        num_bin=jnp.full((F,), B, jnp.int32),
        missing_type=jnp.zeros((F,), jnp.int32),
        default_bin=jnp.zeros((F,), jnp.int32),
        is_categorical=jnp.zeros((F,), bool))
    rng = np.random.RandomState(0)
    args = (jnp.asarray(rng.randint(0, B, size=(N, F)).astype(np.uint8)),
            jnp.asarray(rng.randn(N).astype(np.float32)),
            jnp.asarray(np.abs(rng.randn(N)).astype(np.float32)),
            jnp.ones((N,), jnp.float32), meta, jnp.ones((F,), bool))
    return make_grower(cfg), args


@pytest.mark.parametrize("split_find", ["fused", "chain"])
def test_loop_body_has_no_unsanctioned_big_ops(split_find):
    grow, args = _grow_and_args(split_find)
    jaxpr = jax.make_jaxpr(grow)(*args)
    # re-recorded on purpose when the pool became [L, K, 128], a leaf
    # whole (8, 128) tiles (3 * F * B = 1,536 floats in K = 16 rows)
    store_elems = L * K * 128

    # O(N) audit: find-pair candidate arrays ([2, F, 2B, 4] = 8192 elems)
    # sit well under N, a stale-leaf rescan ([L, F, 2B, 4] = 61440) well
    # over it — the threshold separates the two by construction.  The
    # fused scan's widest arrays ([2, F, B, 3]) sit under the chain's, so
    # the same threshold pins both formulations.
    assert 4 * L * F * 2 * B > N > 4 * 2 * F * 2 * B
    big = audit_loop_body(jaxpr, min_elems=N)
    assert len([r for r in big if r["prim"] == "cond"]) == 2
    # outside the two switches the body is N-wide only in the routing of
    # the split column (PR 35: once a split, before the switch): every
    # such equation sits under the ``partition`` scope and is a slice or
    # elementwise — nothing there reads by index, sorts, accumulates or
    # rewrites a carrier
    wide = [e for e in find_while_body(jaxpr).eqns
            if e.primitive.name != "cond" and max(
                (int(np.prod(v.aval.shape)) for v in e.invars + e.outvars
                 if hasattr(v, "aval")), default=0) >= N]
    outside = [e.primitive.name for e in wide
               if "partition" not in str(e.source_info.name_stack)]
    assert not outside, (
        f"grow-loop body touches O(N)-sized operands outside the "
        f"sanctioned partition/bucket switches and the routing: {outside}")
    prims = {e.primitive.name for e in wide}
    assert not prims & {"gather", "scatter", "scatter-add", "sort", "cumsum",
                        "dynamic_update_slice", "concatenate", "while",
                        "transpose", "reduce_sum", "copy", "copy_p"}, prims
    assert prims & {"jit", "pjit", "select_n"} and "dynamic_slice" in prims

    # hist_store audit: exactly one read + one fused pair-write
    store = [r for r in audit_loop_body(jaxpr, min_elems=store_elems)
             if any(int(np.prod(s or (1,))) == store_elems
                    for s in r["shapes"])]
    store_prims = sorted(r["prim"] for r in store)
    assert store_prims == ["dynamic_slice", "scatter"], (
        f"hist_store must be touched by exactly one dynamic_slice read "
        f"and one scatter pair-write; got {store}")


# every traced transfer/callback primitive jax can put in a jaxpr — a
# per-split host round-trip inside the grow loop would appear as one of
# these (the round-8 device-resident-frontier contract)
_HOST_PRIMS = ("callback", "infeed", "outfeed", "host_callback",
               "device_put", "debug_print")


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for v in vals:
                sub = getattr(v, "jaxpr", None)
                if sub is not None and hasattr(sub, "eqns"):
                    yield from _walk_eqns(sub)
                elif hasattr(v, "eqns"):
                    yield from _walk_eqns(v)


@pytest.mark.parametrize("split_find", ["fused", "chain"])
def test_loop_body_has_no_host_transfers(split_find):
    """The whole frontier stays device-resident: no callback / infeed /
    outfeed / transfer primitive may appear anywhere in the loop body
    (including the switch branches) — the only per-tree device_get is the
    final tree pull boosting already does, OUTSIDE the loop."""
    from lightgbm_tpu.utils.jaxpr_audit import find_while_body
    grow, args = _grow_and_args(split_find)
    body = find_while_body(jax.make_jaxpr(grow)(*args))
    bad = [e.primitive.name for e in _walk_eqns(body)
           if any(t in e.primitive.name for t in _HOST_PRIMS)]
    assert not bad, (
        f"grow-loop body contains host-transfer primitives {bad} — a "
        f"per-split host round-trip has been reintroduced")


def _partition_switch(body):
    """The one ``lax.switch`` under the ``partition`` scope."""
    switches = [e for e in body.eqns if e.primitive.name == "cond"
                and "partition" in str(e.source_info.name_stack)]
    assert len(switches) == 1
    return switches[0]


def test_partition_gathers_a_column_not_the_matrix():
    """The routing read (PR 26, and ONCE a split since PR 35): under the
    ``partition`` scope the split column is sliced out of the column-major
    copy by one ``dynamic_slice`` at the body's top level, routed whole and
    packed to bits there; a window branch reads the bits by ONE rank-1
    gather — no gather takes a rank-2 operand, least of all the ``[N, F]``
    matrix (on the v5e the two-index byte gather on ``u8[10500000,28]`` read
    20 ns an element: ledger, PR 25), and the table it does read is N/8
    bytes — and slices no column of its own."""
    grow, args = _grow_and_args()
    body = find_while_body(jax.make_jaxpr(grow)(*args))
    column_reads = [e for e in body.eqns
                    if e.primitive.name == "dynamic_slice"
                    and e.invars[0].aval.shape == (F, N)]
    assert len(column_reads) == 1
    assert "partition" in str(column_reads[0].source_info.name_stack)
    branches = _partition_switch(body).params["branches"]
    assert len(branches) == len(_partition_sizes(GrowerConfig(), N)) + 1
    for br in branches[:-1]:
        inner = list(_walk_eqns(br.jaxpr))
        gathers = [e.invars[0].aval for e in inner
                   if e.primitive.name == "gather"]
        assert [(a.shape, str(a.dtype)) for a in gathers] == \
            [((N // 32,), "uint32")], gathers
        assert not [e for e in inner if e.primitive.name == "dynamic_slice"
                    and e.invars[0].aval.shape == (F, N)]


def test_dense_branch_is_one_sort_of_one_operand_and_no_gather():
    """The partition's last branch (PR 35) reads nothing by row id: no
    gather, no scatter, ONE sort, of one ``i32[N]`` operand (a second
    operand, or the ``iota`` a stable sort carries, would be a third more
    time a pass: PERF.md section 6, PR 30), and ``order`` comes back by a
    select, not by an update in place of a slice."""
    grow, args = _grow_and_args()
    body = find_while_body(jax.make_jaxpr(grow)(*args))
    inner = list(_walk_eqns(
        _partition_switch(body).params["branches"][-1].jaxpr))
    prims = [e.primitive.name for e in inner]
    assert not {"gather", "scatter", "scatter-add",
                "dynamic_update_slice"} & set(prims)
    sorts = [e for e in inner if e.primitive.name == "sort"]
    assert len(sorts) == 1
    assert [(v.aval.shape, str(v.aval.dtype)) for v in sorts[0].invars] == \
        [((N,), "int32")]
    assert sorts[0].params["is_stable"] is False


def test_routing_is_one_buffer_of_words_behind_a_barrier():
    """The split column's N decisions leave the routing as ONE ``uint32[N]``
    buffer that an ``optimization_barrier`` pins: ``rl``'s update and the
    bit table both read it.  Without the barrier the v5e's compiler
    routed the column twice, the second time into a byte a row at 0.58 ms
    a split (PERF.md section 6, PR 35).  The body's one other barrier is
    the pool's: the children's [2, F, B, 3] histograms, one buffer that
    the scan and the pool's write both read (``grower.pool_split``)."""
    grow, args = _grow_and_args()
    body = find_while_body(jax.make_jaxpr(grow)(*args))
    barriers = [e for e in body.eqns
                if e.primitive.name == "optimization_barrier"]
    assert len(barriers) == 2
    routing = [e for e in barriers
               if "partition" in str(e.source_info.name_stack)]
    assert len(routing) == 1
    assert [(v.aval.shape, str(v.aval.dtype))
            for v in routing[0].outvars] == [((N,), "uint32")]
    pool = [e for e in barriers
            if "hist_pool" in str(e.source_info.name_stack)]
    assert [(v.aval.shape, str(v.aval.dtype)) for e in pool
            for v in e.outvars] == [((2, F, B, 3), "float32")]


def test_rl_is_carried_and_updated_by_one_select():
    """The dense row -> leaf vector is a loop carrier of N ``int32`` that
    the body writes once, by the select of the routing pass, and hands to
    the switch read-only: no branch returns it, so the switch cannot copy
    it, and nothing else in the body produces an N-wide ``int32``."""
    grow, args = _grow_and_args()
    body = find_while_body(jax.make_jaxpr(grow)(*args))
    switch = _partition_switch(body)
    assert [v.aval.shape for v in switch.outvars
            if v.aval.shape and v.aval.shape[0] >= N] == [
        (N + _order_tail(_bucket_sizes(GrowerConfig(), N)),)]
    made = [e for e in body.eqns for v in e.outvars
            if v.aval.shape == (N,) and str(v.aval.dtype) == "int32"
            and e.primitive.name not in ("convert_element_type",)]
    assert len(made) == 1 and made[0].primitive.name in ("jit", "pjit",
                                                         "select_n")
    rl_out = made[0].outvars[0]
    assert rl_out in body.outvars and rl_out in switch.invars


# ---- loop-body size ratchet ------------------------------------------------
#
# On XLA:CPU the deep-tree tail is op-DISPATCH bound: the per-split fixed
# cost tracks the body's post-fusion thunk count, for which the traced
# equation count is the stable jaxpr-level proxy (docs/PERF.md round 8).
# Measured on jax 0.4.37 at this shape: 414 top-level eqns for the fused
# no-missing body (the bench regime; the chain body is 459), 527 with the
# missing direction on (more but individually narrower eqns than the
# chain's 523 — the packed [F, 2B, 4] arrays are gone either way).  The
# ratchets leave ~15% headroom for toolchain drift but fail on a
# structural regression: per-field pool/tree scatters, a de-hoisted mask
# chain, or per-split host work are each worth 30+ eqns.  If a jax
# upgrade legitimately moves the count, re-measure and ratchet
# deliberately.  Re-recorded on jax 0.9.0 in PR 26: 392 and 505, the same
# before and after the partition's read went from the (row, col) gather
# to a column slice and a rank-1 gather (both live inside the switch
# branch, which the body's top level does not count); the budgets keep
# the ~15%.  Re-recorded on purpose in PR 35: 528 and 641 (the parent
# read 396 and 509 on the same day), because the routing of the split
# column moved OUT of the partition's branches to the body's top level,
# where it runs once a split whichever branch is taken: its slice, its
# decision and the 32 planes of ``pack_row_bits`` are 132 equations that
# every one of the 12 branches used to hold a copy of, so the traced
# program shrank while this count grew.  On XLA:CPU they fuse into a few
# thunks; the budgets keep the ~15%.

BODY_EQNS_BUDGET = {False: 600, True: 730}


@pytest.mark.parametrize("has_missing", [False, True])
def test_fused_body_eqn_count_within_budget(has_missing):
    from lightgbm_tpu.utils.jaxpr_audit import find_while_body
    grow, args = _grow_and_args("fused", has_missing=has_missing)
    body = find_while_body(jax.make_jaxpr(grow)(*args))
    n_eqns = len(body.eqns)
    assert n_eqns <= BODY_EQNS_BUDGET[has_missing], (
        f"fused grow-loop body has {n_eqns} top-level eqns "
        f"(budget {BODY_EQNS_BUDGET[has_missing]}, has_missing="
        f"{has_missing}) — per-split fixed dispatch cost has re-widened")


def test_compiled_body_has_no_full_pool_copies():
    """Recorded copy-free on jax 0.4.37, lost on jax 0.9.0 (XLA:CPU cloned
    the [L, F, B, 3] pool twice a split; the v5e's compiler relaid it whole
    once a split), and back since the pool is carried as [L, 3 * F * B]
    rows; re-recorded on purpose for the [L, K, 128] pool of whole tiles
    (``grower.pool_flat``): no copy of it either."""
    grow, args = _grow_and_args()
    txt = jax.jit(grow).lower(*args).compile().as_text()
    shape = f"f32\\[{L},{K},128\\]"
    assert re.search(rf"{shape}", txt), "the pool's shape is not in the text"
    copies = re.findall(rf"= {shape}[^ ]* copy", txt)
    assert not copies, (
        f"{len(copies)} full hist_store copies in the compiled "
        f"executable — the per-split fixed cost regression is back")


# ---- order-carrier copy ratchet --------------------------------------------
#
# XLA copy-insertion clones the ``order`` carrier around the partition
# switch's in-place scatter: a conditional branch that both slices and
# scatters its operand gets a defensive copy (a minimal
# slice-argsort-scatter-in-cond repro exhibits the same copies, so the
# formulation cannot dodge it — the compiler won't cooperate).  One copy
# executes per split (~1.85 MB at 200k rows, PR 9 residue).  The HLO text
# carries one STATIC copy per gather-bucket branch; at this shape (N=32k,
# bucket_min_log2=6 -> buckets 64..32768) that was 11 copies of
# s32[N + maxbuf] on jax 0.4.37.  Re-recorded on jax 0.9.0: 12 — ten
# inside partition-switch branches, one in the while body itself and one
# at the loop's initial carry.  Pinned as a ratchet so sharding-annotation
# work (or a toolchain move) can never silently multiply it — and the
# GSPMD grower, which has no ``order`` carrier at all, is pinned copy-free
# below as the contrast.
#
# Re-recorded on purpose in PR 30, which made one sort of the window
# (slice, sort, dynamic_update_slice) the only transport and put half-step
# sizes into the window table above 2^13.  The sort form ALONE read what the
# scatter form read: 12 copies on the power-of-two table (one a branch, as
# before: the slice-then-update of a conditional's operand draws the same
# defensive copy as the slice-then-scatter did, and no second one; temp
# bytes 3,905,912 against 3,905,272).  The table now has 12 sizes at this
# N (12288 and 24576 are new), so the text has 14: twelve branches, the
# body, the initial carry.  Still ONE executes a split.  The carrier's
# length comes from the table: N + its widest step (``_order_tail``: 8,191
# slots here), where it was N + 2^ceil(log2 N), 65,536 entries, whatever
# the table: each copy is 40,959 entries long.

#
# Re-recorded on purpose in PR 35: 9.  The partition's table now ends where
# the dense branch is the cheaper transport (``_partition_sizes``: 7 sizes
# at this N, 64 to 4096, where the whole table has 12), so the text holds
# seven window branches' copies, the body's and the initial carry's; the
# dense branch, the eighth, builds ``order`` by a select over all of it and
# draws no copy (the parent read 14 on the same day).  Still ONE executes a
# split.  The carrier on this rung (``segment``: the XLA reference's
# histogram ladder slices windows of the WHOLE table) stays N + 8,191; on
# the fused rung it is N + the widest step of the partition's own table
# (``_order_tail(_partition_sizes(...))``: 262,143 slots at 10.5M rows
# where it was 4,194,303).

ORDER_COPY_BUDGET = 9      # one a window size of the partition + 2


def test_compiled_order_copy_count_ratchet():
    grow, args = _grow_and_args()
    txt = jax.jit(grow).lower(*args).compile().as_text()
    assert len(_partition_sizes(GrowerConfig(), N)) + 2 == ORDER_COPY_BUDGET
    carrier = N + _order_tail(_bucket_sizes(GrowerConfig(), N))
    copies = re.findall(rf"= s32\[{carrier}\][^ ]* copy\(", txt)
    assert 1 <= len(copies) <= ORDER_COPY_BUDGET, (
        f"{len(copies)} order-carrier copies in the compiled executable "
        f"(budget {ORDER_COPY_BUDGET}, recorded on jax 0.9.0) "
        f"— copy-insertion around the conditional in-place update has "
        f"multiplied; re-measure deliberately before widening")
    # ``rl``, the other N-wide carrier, is copied nowhere: the body
    # updates it in place and no branch of the switch returns it
    assert not re.findall(rf"= s32\[{N}\][^ ]* copy\(", txt)


def test_gspmd_grower_has_no_order_carrier_copies():
    """The GSPMD grower's partition is the row_leaf map — no ``order``
    permutation, no switch, no O(N) conditional carrier for XLA to
    clone.  Pinned so the two growers' copy classes stay distinguishable
    in perf work."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from lightgbm_tpu.parallel.gspmd import make_gspmd_grower
    from lightgbm_tpu.parallel.mesh import BATCH_AXIS, make_named_mesh
    cfg = GrowerConfig(num_leaves=L, min_data_in_leaf=1, max_bin=B,
                       hist_method="segment")
    _, args = _grow_and_args()
    bins, g, h, c, meta, fv = args
    mesh = make_named_mesh(8, 1)
    grow = make_gspmd_grower(cfg, mesh)
    rs = NamedSharding(mesh, P(BATCH_AXIS))
    txt = grow.lower(
        jax.device_put(bins, NamedSharding(mesh, P(BATCH_AXIS, None))),
        jax.device_put(g, rs), jax.device_put(h, rs),
        jax.device_put(c, rs), meta, fv).compile().as_text()
    copies = re.findall(rf"= s32\[\d{{5,}}\][^ ]* copy\(", txt)
    assert not copies, (
        f"O(N) i32 copies appeared in the GSPMD grower: {copies[:4]}")


# ---- byte-budget ratchet (obs/memory.executable_memory) -------------------
#
# The zero-copy HLO pin above catches the exact regression XLA exhibited;
# this pins the BUDGET CLASS: the compiled grower's temp bytes at this
# shape.  Measured 2,673,800 on the jax-0.4.37 CPU backend and 3,735,288
# on jax 0.9.0, the installed one this budget is recorded against (the
# pool-clone pair the xfail above names is part of that figure).
# Re-recorded on purpose in PR 26: 3,997,432, which is the same figure
# plus N * F = 262,144 bytes, the column-major copy of the routing matrix
# that the partition slices its split column from (the body's equation
# counts, 392 / 505, and the 12 order copies did not move).  That fits
# the budget as it stood, so the budget stays (2.5% of headroom left).
# The budget allowed ~10% drift at 3,735,288 but NOT a further
# copy-insertion regression — one more pair of full hist_store
# [15,8,64,3] clones alone is +737,280 temp bytes, which overshoots the
# headroom.  If a jax upgrade legitimately moves the number, re-measure
# and ratchet the constant (and say so in the commit); never widen it past
# one pool-clone pair.  Read again in PR 30: 3,905,272 on the parent (PR
# 29's deletions took 92,160 off), 3,905,912 with the sort transport on
# the same table, 3,906,168 with the two half-step branches, 3,709,560
# with ``order`` 24,577 entries shorter (two live copies of it; the same
# for the stable three-operand sort and the two-operand one): the budget
# stands.  Read again in PR 33, whose end-of-tree map (``row_leaf``: a
# cumulative sum and one sort where a gather and a scatter of N were) is
# outside the loop and moves no pin on the body: 3,709,560 on the parent,
# 3,578,552 with it (one N-entry i32 temporary fewer); body 392 / 505
# equations and 14 order copies as before.  Nothing re-recorded.  Read
# again in PR 35 (the dense row -> leaf vector ``rl`` joins the carry,
# N * 4 = 131,072 bytes, and five window branches with their temporaries
# leave the text): 3,578,552 on the parent, 3,578,240 with it.  The budget
# stands.

TEMP_BYTES_BUDGET = 4_100_000
TEMP_BYTES_FLOOR = 1_000_000    # sanity: hist_store alone is 368,640 —
#                                 a near-zero reading means the analysis
#                                 broke, not that memory got free


def test_compiled_grower_temp_bytes_within_budget():
    from lightgbm_tpu.obs.counters import counters
    from lightgbm_tpu.obs.memory import executable_memory
    grow, args = _grow_and_args()
    compiled = jax.jit(grow).lower(*args).compile()
    m = executable_memory(compiled, label="grow_pin")
    assert m is not None, "memory_analysis unavailable on this backend"
    # argument bytes track the real input payloads (small slack: XLA's
    # bool/padding accounting differs from numpy nbytes by a few bytes)
    nbytes = sum(int(np.asarray(a).nbytes)
                 for a in jax.tree_util.tree_leaves(args))
    assert abs(m["argument_bytes"] - nbytes) <= 64
    assert TEMP_BYTES_FLOOR <= m["temp_bytes"] <= TEMP_BYTES_BUDGET, (
        f"compiled grower temp bytes {m['temp_bytes']} left the recorded "
        f"budget [{TEMP_BYTES_FLOOR}, {TEMP_BYTES_BUDGET}] — either a "
        f"copy-insertion regression (see docstring) or a toolchain move "
        f"that must be re-measured deliberately")
    # the helper records the evidence as gauges for reports/benches
    assert counters.snapshot()["gauges"]["exec_grow_pin_temp_bytes"] == \
        m["temp_bytes"]


def test_row_leaf_map_is_scoped_and_counted_once_a_trace():
    """The end-of-tree map carries what names it in a capture and in the
    counters: the ``row_leaf`` scope on its sort (the benchmark's
    ``row_leaf_ms_per_tree`` reads device time by that token) and one
    ``row_leaf_dispatch{impl=sort}`` a trace."""
    from lightgbm_tpu.obs.counters import counters
    grow, args = _grow_and_args()
    before = counters.get("row_leaf_dispatch").get("impl=sort", 0)
    txt = jax.jit(grow).lower(*args).as_text(debug_info=True)
    assert counters.get("row_leaf_dispatch") == {"impl=sort": before + 1}
    assert re.search(r'jit\(grow_tree_s\d+\)/row_leaf/sort', txt)
    assert re.search(r'jit\(grow_tree_s\d+\)/row_leaf/jit\(cumsum\)', txt)
