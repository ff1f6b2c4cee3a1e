"""GSPMD NamedSharding learners on the virtual 8-device CPU mesh.

The tentpole contracts of the compiler-owned distributed path
(parallel/gspmd.py, docs/DISTRIBUTED.md), all CPU-verifiable:

* trees grown under EVERY mesh shape (8x1, 1x8, 2x4; bins replicated or
  block-sharded over feature) are BYTE-identical to the single-device
  grower at fixed num_leaves — integer-valued weights make every f32
  histogram sum order-insensitive, so the pin is exact (the PR 9 byte-pin
  style), not approximate;
* the compiled grow loop's collective census shows the SCATTERED
  histogram reduction (payload = the feature shard's slice, the
  reduce-scatter the reference hand-rolled) and no all-gather of the
  histogram pool;
* the memory-driven planner (parallel/mesh.plan_mesh) picks pure
  data-parallel when everything fits, walks to feature-sharded shapes
  when the histogram pool outgrows the per-device budget, and raises a
  structured MeshPlanError when nothing fits.
"""
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower
from lightgbm_tpu.parallel.gspmd import make_gspmd_grower
from lightgbm_tpu.parallel.mesh import (BATCH_AXIS, FEATURE_AXIS,
                                        MeshPlanError, make_named_mesh,
                                        parse_mesh_shape, plan_mesh)
from lightgbm_tpu.utils.jaxpr_audit import hlo_collective_census

N, F, B, L = 4096, 8, 32, 15


def _cfg(**kw):
    base = dict(num_leaves=L, min_data_in_leaf=1, max_bin=B,
                hist_method="segment", has_missing=False)
    base.update(kw)
    return GrowerConfig(**base)


def _meta(missing=False):
    return FeatureMeta(
        num_bin=jnp.full((F,), B, jnp.int32),
        missing_type=jnp.full((F,), 2 if missing else 0, jnp.int32),
        default_bin=jnp.zeros((F,), jnp.int32),
        is_categorical=jnp.zeros((F,), bool))


def _int_args(seed=0):
    """Integer-valued f32 weights: every histogram sum is exact in f32
    regardless of summation order, so the masked whole-partition sums of
    the GSPMD grower equal the serial grower's windowed sums BIT-exactly."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(N, F)).astype(np.uint8)
    g = rng.randint(-8, 9, size=N).astype(np.float32)
    h = rng.randint(1, 9, size=N).astype(np.float32)
    c = np.ones(N, np.float32)
    return bins, g, h, c


@pytest.fixture(scope="module")
def serial_result():
    cfg = _cfg()
    bins, g, h, c = _int_args()
    grow = jax.jit(make_grower(cfg))
    tree, row_leaf = grow(jnp.asarray(bins), jnp.asarray(g),
                          jnp.asarray(h), jnp.asarray(c), _meta(),
                          jnp.ones((F,), bool))
    return (jax.tree.map(np.asarray, tree), np.asarray(row_leaf))


def _gspmd_grow(mesh, block_shard=False, cfg=None):
    cfg = cfg or _cfg()
    bins, g, h, c = _int_args()
    grow = make_gspmd_grower(cfg, mesh)
    bspec = P(BATCH_AXIS, FEATURE_AXIS if block_shard else None)
    binsd = jax.device_put(bins, NamedSharding(mesh, bspec))
    rs = NamedSharding(mesh, P(BATCH_AXIS))
    tree, row_leaf = grow(binsd, jax.device_put(g, rs),
                          jax.device_put(h, rs), jax.device_put(c, rs),
                          _meta(), jnp.ones((F,), bool))
    return jax.tree.map(np.asarray, tree), np.asarray(row_leaf)


@pytest.mark.parametrize("shape", [(8, 1), (1, 8), (2, 4)],
                         ids=["8x1", "1x8", "2x4"])
def test_gspmd_trees_byte_identical_across_mesh_shapes(shape, serial_result):
    """Acceptance pin: data-/feature-/block-sharded GSPMD growing is the
    SAME tree as the single-device grower — every TreeArrays field equal
    to the byte, and the row->leaf partition equal row-for-row."""
    tree_s, rl_s = serial_result
    tree_g, rl_g = _gspmd_grow(make_named_mesh(*shape))
    for name, a, b in zip(tree_s._fields, tree_s, tree_g):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"TreeArrays.{name} diverged on the {shape} mesh")
    np.testing.assert_array_equal(rl_s, rl_g)


def test_gspmd_block_sharded_bins_identical(serial_result):
    """shard_axes=batch,feature: the binned matrix itself block-shards
    over BOTH axes (the Block-distributed GBT layout) — routing's column
    read crosses shards, XLA inserts the gather, trees stay identical."""
    tree_s, rl_s = serial_result
    tree_g, rl_g = _gspmd_grow(make_named_mesh(2, 4), block_shard=True)
    for name, a, b in zip(tree_s._fields, tree_s, tree_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"TreeArrays.{name}")
    np.testing.assert_array_equal(rl_s, rl_g)


def test_gspmd_missing_direction_identical():
    """The has_missing routing path (default-direction decisions) under
    sharding: same helper, same decisions, identical trees."""
    cfg = _cfg(has_missing=True)
    bins, g, h, c = _int_args(seed=3)
    meta = _meta(missing=True)
    tree_s, rl_s = jax.jit(make_grower(cfg))(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
        meta, jnp.ones((F,), bool))
    mesh = make_named_mesh(2, 4)
    grow = make_gspmd_grower(cfg, mesh)
    rs = NamedSharding(mesh, P(BATCH_AXIS))
    tree_g, rl_g = grow(
        jax.device_put(bins, NamedSharding(mesh, P(BATCH_AXIS, None))),
        jax.device_put(g, rs), jax.device_put(h, rs),
        jax.device_put(c, rs), meta, jnp.ones((F,), bool))
    for name, a, b in zip(tree_s._fields, jax.tree.map(np.asarray, tree_s),
                          jax.tree.map(np.asarray, tree_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"TreeArrays.{name}")
    np.testing.assert_array_equal(np.asarray(rl_s), np.asarray(rl_g))


# ---- the gspmd_hist=fused hybrid (shard_map islands) -----------------------


def _fused_cfg(**kw):
    """The hybrid grower's config: the fused Pallas kernel inside the
    GSPMD program's shard_map islands, interpret mode on this CPU host
    (same program shape as the chip, kernel emulated)."""
    return _cfg(hist_method="fused", hist_interpret=True, **kw)


@pytest.mark.parametrize("shape", [(8, 1), (1, 8), (2, 4)],
                         ids=["8x1", "1x8", "2x4"])
def test_gspmd_fused_hybrid_byte_identical_across_mesh_shapes(
        shape, serial_result):
    """Tentpole acceptance: the hybrid — each device running the fused
    gather-histogram kernel over its row shard inside a shard_map island,
    the partitioner owning the cross-shard reduction — grows the SAME
    tree as the single-device grower on every mesh shape, to the byte
    (integer-valued weights make every f32 histogram sum
    order-insensitive, so bf16 hi/lo splitting of exact small integers
    is also exact)."""
    tree_s, rl_s = serial_result
    tree_g, rl_g = _gspmd_grow(make_named_mesh(*shape), cfg=_fused_cfg())
    for name, a, b in zip(tree_s._fields, tree_s, tree_g):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"TreeArrays.{name} diverged on the {shape} hybrid")
    np.testing.assert_array_equal(rl_s, rl_g)


def test_gspmd_fused_hybrid_missing_direction_identical():
    """has_missing routing (default-direction decisions) composed with
    the hybrid islands: identical trees."""
    cfg_s = _cfg(has_missing=True)
    bins, g, h, c = _int_args(seed=3)
    meta = _meta(missing=True)
    tree_s, rl_s = jax.jit(make_grower(cfg_s))(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
        meta, jnp.ones((F,), bool))
    mesh = make_named_mesh(2, 4)
    grow = make_gspmd_grower(_fused_cfg(has_missing=True), mesh)
    rs = NamedSharding(mesh, P(BATCH_AXIS))
    tree_g, rl_g = grow(
        jax.device_put(bins, NamedSharding(mesh, P(BATCH_AXIS, None))),
        jax.device_put(g, rs), jax.device_put(h, rs),
        jax.device_put(c, rs), meta, jnp.ones((F,), bool))
    for name, a, b in zip(tree_s._fields, jax.tree.map(np.asarray, tree_s),
                          jax.tree.map(np.asarray, tree_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"TreeArrays.{name}")
    np.testing.assert_array_equal(np.asarray(rl_s), np.asarray(rl_g))


def test_gspmd_fused_hybrid_categorical_identical():
    """A categorical dataset through the hybrid: the one-vs-rest /
    many-vs-many categorical split machinery reads the same pooled
    histograms, so trees must stay byte-identical to serial."""
    rng = np.random.RandomState(11)
    bins = rng.randint(0, B, size=(N, F)).astype(np.uint8)
    g = rng.randint(-8, 9, size=N).astype(np.float32)
    h = rng.randint(1, 9, size=N).astype(np.float32)
    c = np.ones(N, np.float32)
    meta = FeatureMeta(
        num_bin=jnp.full((F,), B, jnp.int32),
        missing_type=jnp.zeros((F,), jnp.int32),
        default_bin=jnp.zeros((F,), jnp.int32),
        is_categorical=jnp.asarray([True] * 3 + [False] * (F - 3)))
    cfg_s = _cfg(has_categorical=True, max_cat_threshold=16)
    tree_s, rl_s = jax.jit(make_grower(cfg_s))(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
        meta, jnp.ones((F,), bool))
    mesh = make_named_mesh(2, 4)
    grow = make_gspmd_grower(
        _fused_cfg(has_categorical=True, max_cat_threshold=16), mesh)
    rs = NamedSharding(mesh, P(BATCH_AXIS))
    tree_g, rl_g = grow(
        jax.device_put(bins, NamedSharding(mesh, P(BATCH_AXIS, None))),
        jax.device_put(g, rs), jax.device_put(h, rs),
        jax.device_put(c, rs), meta, jnp.ones((F,), bool))
    for name, a, b in zip(tree_s._fields, jax.tree.map(np.asarray, tree_s),
                          jax.tree.map(np.asarray, tree_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"TreeArrays.{name}")
    np.testing.assert_array_equal(np.asarray(rl_s), np.asarray(rl_g))


def test_gspmd_fused_zero_recompile_across_calls():
    """Trace-time dispatch counters pin compile behavior: growing twice
    per mesh (fresh data, same shapes) traces the hybrid ONCE per mesh —
    the dynamic-grid kernel and the islands introduce no shape-dependent
    retrace."""
    from lightgbm_tpu.obs import counters
    counters.reset()
    for shape in [(8, 1), (2, 4)]:
        mesh = make_named_mesh(*shape)
        grow = make_gspmd_grower(_fused_cfg(), mesh)
        rs = NamedSharding(mesh, P(BATCH_AXIS))
        for seed in (0, 1):
            bins, g, h, c = _int_args(seed=seed)
            binsd = jax.device_put(bins,
                                   NamedSharding(mesh, P(BATCH_AXIS, None)))
            jax.block_until_ready(grow(
                binsd, jax.device_put(g, rs), jax.device_put(h, rs),
                jax.device_put(c, rs), _meta(), jnp.ones((F,), bool))[0])
    disp = counters.get("hist_dispatch")
    # one trace per mesh per site: 2 meshes x {root, split}, never 4 (the
    # 8x1 mesh runs the serial grower in its island: its root fetches the
    # identity window by block; the 2x4 island's root selects by row_leaf)
    key = "col_tiles=1,fetch={},hi=16,interpret=True,method=fused,site={}," \
        f"width={B}"
    assert disp == {
        key.format("block", "root"): 1,
        key.format("rows", "root"): 1,
        key.format("rows", "split"): 2,
    }, disp


@pytest.mark.mesh8
def test_gspmd_hist_fused_end_to_end_and_auto_stays_flat():
    """Boosting-level resolution: gspmd_hist=fused engages the hybrid
    (observed kernel identity = fused, no downgrade events), produces the
    same predictions as the forced-flat A/B partner, and auto resolves
    flat until the capture A/B flips it."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import counters
    rng = np.random.RandomState(7)
    X = rng.randn(2000, 16)
    y = (X @ rng.randn(16) > 0).astype(np.float64)

    def train(**extra):
        params = {"objective": "binary", "verbose": -1, "num_leaves": 31,
                  "min_data_in_leaf": 5, "tree_learner": "data", **extra}
        return lgb.train(params, lgb.Dataset(X, label=y),
                         num_boost_round=3, verbose_eval=False)

    flat = train(gspmd_hist="flat")
    counters.reset()
    fused = train(gspmd_hist="fused")
    assert fused.inner.grower_cfg.hist_method == "fused"
    assert counters.observed_kernel() == "fused"
    assert not counters.events("layout_downgrade")
    np.testing.assert_allclose(fused.predict(X), flat.predict(X),
                               rtol=2e-5, atol=2e-6)
    auto = train()                                 # gspmd_hist defaults auto
    assert auto.inner.grower_cfg.hist_method == "segment"


@pytest.mark.mesh8
def test_gspmd_hist_fused_downgrades_loudly_on_unfusable_layout():
    """30 histogram columns do not split evenly over 8 feature shards:
    the request must degrade to flat BEFORE labels are read — loud
    warning + structured layout_downgrade event — and the training still
    runs."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import counters
    rng = np.random.RandomState(3)
    X = rng.randn(1500, 30)
    y = (X @ rng.randn(30) > 0).astype(np.float64)
    counters.reset()
    bst = lgb.train({"objective": "binary", "verbose": -1,
                     "num_leaves": 15, "min_data_in_leaf": 5,
                     "tree_learner": "data", "mesh_shape": "1x8",
                     "gspmd_hist": "fused"},
                    lgb.Dataset(X, label=y), num_boost_round=2,
                    verbose_eval=False)
    assert bst.inner.grower_cfg.hist_method == "segment"
    evs = [e for e in counters.events("layout_downgrade")
           if e.get("requested") == "gspmd_hist=fused"]
    assert evs and evs[0]["resolved"] == "flat", evs
    assert "feature shards" in evs[0]["reason"], evs
    assert np.isfinite(bst.predict(X[:10])).all()


# ---- compiled-HLO collective audit -----------------------------------------

# Recorded on jax 0.9.0 (XLA:CPU, 8 virtual devices).  Its all-reduce
# combiner folds the three root scalar sums (g, h, count) into the ROOT
# histogram's reduction as one tuple all-reduce, so the largest reduction
# payload is the histogram plus 12 bytes; the per-split reduction inside
# the while body is the bare histogram.  (jax 0.4.37 kept them apart.)
ROOT_SCALAR_BYTES = 3 * 4


def _compile_gspmd(mesh):
    cfg = _cfg()
    bins, g, h, c = _int_args()
    grow = make_gspmd_grower(cfg, mesh)
    binsd = jax.device_put(bins, NamedSharding(mesh, P(BATCH_AXIS, None)))
    rs = NamedSharding(mesh, P(BATCH_AXIS))
    return grow.lower(binsd, jax.device_put(g, rs), jax.device_put(h, rs),
                      jax.device_put(c, rs), _meta(),
                      jnp.ones((F,), bool)).compile()


def test_hlo_census_scattered_reduce_no_pool_allgather():
    """The acceptance audit: on the 2x4 mesh the grow executable's
    histogram reduction is SCATTERED — the cross-batch reduce moves one
    feature shard's slice ([F/4, B, 3]), the communication shape of a
    reduce-scatter (on this XLA the partitioner emits it as partial
    compute + shard-sized all-reduce; judge bytes, not spelling) — and
    NOTHING all-gathers the histogram pool (or even one leaf's full
    histogram)."""
    census = hlo_collective_census(_compile_gspmd(make_named_mesh(2, 4)))
    full_hist = F * B * 3 * 4            # one leaf's [F, B, 3] f32
    slice_hist = full_hist // 4          # the feature shard's slice
    pool = L * full_hist                 # the whole hist_store
    reduces = {op: rec for op, rec in census.items()
               if op in ("all-reduce", "reduce-scatter")}
    assert reduces, f"no histogram reduction collective found: {census}"
    assert (max(r["max_bytes"] for r in reduces.values())
            <= slice_hist + ROOT_SCALAR_BYTES), (
        f"histogram reduction moves more than the feature shard's slice "
        f"({slice_hist} B) — the scattered-reduce contract broke: {census}")
    ag = census.get("all-gather", {"max_bytes": 0})
    assert ag["max_bytes"] < full_hist, (
        f"an all-gather moves a full histogram (>= {full_hist} B) — the "
        f"pool must never be re-replicated: {census}")
    assert ag["max_bytes"] < pool


def test_hlo_census_data_parallel_is_plain_allreduce():
    """Pure data-parallel (8x1): no feature axis to scatter over — the
    histogram reduction is one full [F, B, 3] sum, exactly the psum the
    shard_map learner issued by hand, now compiler-inserted."""
    census = hlo_collective_census(_compile_gspmd(make_named_mesh(8, 1)))
    full_hist = F * B * 3 * 4
    reduces = {op: rec for op, rec in census.items()
               if op in ("all-reduce", "reduce-scatter")}
    assert reduces
    assert (max(r["max_bytes"] for r in reduces.values())
            == full_hist + ROOT_SCALAR_BYTES)
    assert "all-gather" not in census


def _compile_gspmd_fused(mesh):
    bins, g, h, c = _int_args()
    grow = make_gspmd_grower(_fused_cfg(), mesh)
    binsd = jax.device_put(bins, NamedSharding(mesh, P(BATCH_AXIS, None)))
    rs = NamedSharding(mesh, P(BATCH_AXIS))
    return grow.lower(binsd, jax.device_put(g, rs), jax.device_put(h, rs),
                      jax.device_put(c, rs), _meta(),
                      jnp.ones((F,), bool)).compile()


def test_hlo_census_fused_hybrid_no_rowshard_or_pool_allgather():
    """Hybrid acceptance audit (2x4): the island boundary must not make
    the partitioner materialize anyone else's rows or histograms — no
    all-gather reaches a full row shard (the panel stays device-local) or
    a full leaf histogram, and the cross-shard reduction payload is at
    most the feature shard's slice, exactly the flat path's scattered
    contract."""
    census = hlo_collective_census(_compile_gspmd_fused(make_named_mesh(2, 4)))
    full_hist = F * B * 3 * 4            # one leaf's [F, B, 3] f32
    slice_hist = full_hist // 4          # the feature shard's slice
    row_shard = (N // 2) * F             # one device's u8 bin rows
    reduces = {op: rec for op, rec in census.items()
               if op in ("all-reduce", "reduce-scatter")}
    assert reduces, f"no histogram reduction collective found: {census}"
    assert (max(r["max_bytes"] for r in reduces.values())
            <= slice_hist + ROOT_SCALAR_BYTES), (
        f"hybrid reduction moves more than the feature shard's slice "
        f"({slice_hist} B): {census}")
    ag = census.get("all-gather", {"max_bytes": 0})
    assert ag["max_bytes"] < min(full_hist, row_shard), (
        f"an all-gather re-materializes a row shard or a full histogram: "
        f"{census}")


def test_hlo_census_fused_hybrid_data_parallel():
    """Hybrid on pure data-parallel (8x1): one full [F, B, 3] cross-batch
    sum of the island partials — the compiler-inserted psum — and no
    all-gather anywhere."""
    census = hlo_collective_census(_compile_gspmd_fused(make_named_mesh(8, 1)))
    full_hist = F * B * 3 * 4
    reduces = {op: rec for op, rec in census.items()
               if op in ("all-reduce", "reduce-scatter")}
    assert reduces
    assert (max(r["max_bytes"] for r in reduces.values())
            == full_hist + ROOT_SCALAR_BYTES)
    assert "all-gather" not in census


def test_hlo_census_parser_units():
    """The census parser itself: counts, byte totals, tuple shapes,
    async -start spellings, and layout suffixes."""
    txt = """
  %r0 = f32[2,64,3]{2,1,0} all-reduce(f32[2,64,3]{2,1,0} %x), replica_groups={}
  %r1 = f32[8]{0} all-reduce-start(f32[8]{0} %y)
  %g0 = (s32[16]{0}, f32[4,2]{1,0}) all-gather(s32[16]{0} %a, f32[4,2]{1,0} %b)
  %p0 = u8[128]{0} collective-permute(u8[128]{0} %z)
"""
    census = hlo_collective_census(txt)
    assert census["all-reduce"]["count"] == 2
    assert census["all-reduce"]["bytes"] == 2 * 64 * 3 * 4 + 8 * 4
    assert census["all-reduce"]["max_bytes"] == 2 * 64 * 3 * 4
    assert census["all-gather"] == {"count": 1, "bytes": 16 * 4 + 8 * 4,
                                    "max_bytes": 16 * 4 + 8 * 4}
    assert census["collective-permute"]["bytes"] == 128
    assert "reduce-scatter" not in census


def test_hlo_census_records_counters_and_event():
    """obs/collectives.hlo_census feeds the counter registry (calls +
    bytes per op, tagged with the executable label) and one structured
    event — what the obs report's census section and bench telemetry
    read."""
    from lightgbm_tpu.obs.collectives import hlo_census
    from lightgbm_tpu.obs.counters import counters
    counters.reset()
    txt = ("%r0 = f32[8]{0} all-reduce(f32[8]{0} %x)\n"
           "%g0 = s32[16]{0} all-gather(s32[16]{0} %y)\n")
    cen = hlo_census(txt, label="unit")
    assert cen["all-reduce"] == {"count": 1, "bytes": 32, "max_bytes": 32}
    snap = counters.snapshot()
    assert snap["counters"]["hlo_collective_calls"][
        "label=unit,op=all-reduce"] == 1
    assert snap["counters"]["hlo_collective_bytes"][
        "label=unit,op=all-gather"] == 64
    events = [e for e in counters.events("hlo_collectives")
              if e.get("label") == "unit"]
    assert events and "all_reduce" in events[0]


def test_serial_grower_compiles_without_collectives():
    """Control: the single-device grower's census is empty — the census
    never hallucinates collectives out of plain HLO."""
    cfg = _cfg()
    bins, g, h, c = _int_args()
    compiled = jax.jit(make_grower(cfg)).lower(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(c),
        _meta(), jnp.ones((F,), bool)).compile()
    assert hlo_collective_census(compiled) == {}


# ---- the memory-driven sharding planner ------------------------------------

# Epsilon-wide planner shape: the histogram pool [255, 2000, 255, 3] f32
# is ~1.56 GB — the component that outgrows a chip first (docs/MEMORY.md)
PLANNER_SHAPE = dict(rows=400_000, features=2000, bins=255, leaves=255)


def test_plan_mesh_prefers_pure_data_when_everything_fits():
    plan = plan_mesh(8, capacity=64 << 30, **PLANNER_SHAPE)
    assert (plan.data, plan.feature) == (8, 1)
    assert not plan.block_shard_bins
    assert plan.per_device_bytes <= 64 << 30


def test_plan_mesh_feature_shards_when_pool_exceeds_budget():
    """The acceptance case: a shape whose predicted histogram pool
    exceeds one device's budget gets a feature-sharded mesh from
    mesh_shape=auto — the dataset trains anyway."""
    shape = dict(PLANNER_SHAPE, rows=20_000)
    pool = 255 * 2000 * 255 * 3 * 4
    capacity = 1 << 30                      # 1 GB/device < the 1.56 GB pool
    assert pool > capacity
    plan = plan_mesh(8, capacity=capacity, **shape)
    assert plan.feature > 1, plan
    assert plan.per_device_bytes <= capacity
    assert plan.components["hist_store"] <= pool // plan.feature + 4096


def test_plan_mesh_block_shards_bins_under_row_pressure():
    """When feature shards alone cannot fit (the replicated-along-feature
    binned matrix / scatter workspace stays too big), the planner
    block-shards the data itself — the replication half of the
    decision.  Capacity is probed from the model so the test tracks
    predict_hbm instead of hard-coding bytes."""
    from lightgbm_tpu.obs.memory import predict_hbm
    shape = dict(rows=400_000, features=2000, bins=255, leaves=255)
    peaks = {(d, f, blk): predict_hbm(data_shards=d, feature_shards=f,
                                      block_shard_bins=blk,
                                      **shape)["peak_bytes"]
             for d in (1, 2, 4, 8) for f in (8 // d,)
             for blk in ((False, True) if f > 1 else (False,))}
    best_block = min(v for (d, f, blk), v in peaks.items() if blk)
    best_plain = min(v for (d, f, blk), v in peaks.items() if not blk)
    assert best_block < best_plain, peaks
    capacity = (best_block + best_plain) // 2
    plan = plan_mesh(8, capacity=capacity, **shape)
    assert plan.block_shard_bins, (plan, peaks)
    assert plan.feature > 1
    assert plan.per_device_bytes <= capacity


def test_plan_mesh_over_capacity_is_structured_error():
    with pytest.raises(MeshPlanError) as ei:
        plan_mesh(8, capacity=64 << 20, **PLANNER_SHAPE)
    msg = str(ei.value)
    assert "hbm_budget" in msg
    assert "hist_store" in msg or "binned" in msg    # component breakdown
    assert re.search(r"\d+x\d+", msg)                # best candidate named


def test_plan_mesh_no_capacity_signal_prefers_learner_shape():
    assert plan_mesh(8, capacity=None, prefer="data",
                     **PLANNER_SHAPE).feature == 1
    assert plan_mesh(8, capacity=None, prefer="feature",
                     **PLANNER_SHAPE).data == 1
    sq = plan_mesh(8, capacity=None, prefer="square", **PLANNER_SHAPE)
    assert {sq.data, sq.feature} == {2, 4}


def test_parse_mesh_shape():
    assert parse_mesh_shape("auto", 8) is None
    assert parse_mesh_shape("data", 8) == (8, 1)
    assert parse_mesh_shape("feature", 8) == (1, 8)
    assert parse_mesh_shape("2x4", 8) == (2, 4)
    assert parse_mesh_shape("2X4", 8) == (2, 4)
    with pytest.raises(ValueError):
        parse_mesh_shape("4x4", 8)          # needs 16 devices
    with pytest.raises(ValueError):
        parse_mesh_shape("banana", 8)
    with pytest.raises(ValueError):
        parse_mesh_shape("0x8", 8)


def test_mesh_shape_auto_feature_shards_under_hbm_budget():
    """End-to-end acceptance: with mesh_shape=auto and a per-device
    budget the histogram pool exceeds, engine pre-flight plans a
    feature-sharded mesh and the training RUNS (the dataset that "does
    not fit" trains anyway); an impossible budget is a structured
    pre-flight error before anything compiles."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.memory import predict_hbm
    rng = np.random.RandomState(7)
    Xx = rng.randn(3000, 40)
    yy = (Xx @ rng.randn(40) > 0).astype(np.float64)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 63,
              "min_data_in_leaf": 5, "tree_learner": "data"}
    probe = lgb.train(dict(params), lgb.Dataset(Xx, label=yy),
                      num_boost_round=1, verbose_eval=False)
    gcfg = probe.inner.grower_cfg
    shape = dict(rows=3000, features=int(probe.inner.bins.shape[1]),
                 bins=gcfg.max_bin, leaves=gcfg.num_leaves)
    peaks = {f: predict_hbm(data_shards=8 // f, feature_shards=f,
                            **shape)["peak_bytes"] for f in (1, 2, 4, 8)}
    pool = shape["leaves"] * shape["features"] * shape["bins"] * 3 * 4
    fit = min(v for f, v in peaks.items() if f > 1)
    assert fit < peaks[1] and fit < pool
    budget = (fit + min(peaks[1], pool)) // 2
    bst = lgb.train(dict(params, hbm_budget=budget),
                    lgb.Dataset(Xx, label=yy), num_boost_round=2,
                    verbose_eval=False)
    plan = bst.inner._gspmd_plan
    assert plan is not None and plan.feature > 1, plan
    assert pool > budget            # the pool really exceeded the budget
    assert plan.per_device_bytes <= budget
    assert len(bst.inner.models) >= 2   # it trained
    # nothing fits: structured pre-flight error, before any compile
    with pytest.raises(MeshPlanError):
        lgb.train(dict(params, hbm_budget=1 << 16),
                  lgb.Dataset(Xx, label=yy), num_boost_round=1,
                  verbose_eval=False)


def test_mesh_shape_config_rejected_at_parse_time():
    from lightgbm_tpu.config import config_from_params
    with pytest.raises(RuntimeError):
        config_from_params({"mesh_shape": "banana"})
    with pytest.raises(RuntimeError):
        config_from_params({"parallel_impl": "mpi"})
    with pytest.raises(RuntimeError):
        config_from_params({"shard_axes": "rows"})
