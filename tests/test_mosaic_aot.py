"""Offline Mosaic lowering proofs via TPU AOT compilation.

Round 2 shipped a kernel that had only ever run in interpret mode and it
failed Mosaic compilation on the chip; rounds 3-5 gated every risky
kernel behind an ON-CHIP compile test, leaving the riskiest surfaces
unproven whenever no chip was at hand.  This tier removes that blind
spot: ``libtpu`` is present in the
image, so ``jax.experimental.topologies`` can AOT-compile for a v5e
target with NO device attached — real Mosaic lowering, the exact
failure class interpret mode cannot see.  (Numerics still need the
chip: the on-chip tier in test_tpu.py remains the execution proof.)

Proven value: offline runs of these caught lowering failures that every
interpret-mode test passed (``test_fused_hist_kernel_lowers`` lists five).
"""
import os

import numpy as np
import pytest

pytestmark = pytest.mark.slow

jax = pytest.importorskip("jax")


@pytest.fixture(scope="module")
def v5e():
    # the persistent compile cache is a pure liability for this module:
    # AOT topology executables written to it fail re-read with
    # 'UNIMPLEMENTED: DeserializeLoadedExecutable' warnings on every rerun
    # (cache churn, zero hit benefit — these compiles are uncacheable by
    # design), so disable it for the fixture's lifetime and restore after
    prev_cache_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu in this environment
            pytest.skip(f"TPU AOT topology unavailable: {e}")
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(topo.devices[:1]), ("d",))
        sh = NamedSharding(mesh, P())

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
        yield arg
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_cache_dir)


@pytest.mark.parametrize("grid,num_bins,f", [
    ("static", 255, 28), ("dynamic", 255, 28), ("dynamic", 63, 28),
    ("dynamic", 256, 12), ("dynamic", 255, 2000), ("dynamic", 255, 513),
    ("contiguous", 255, 28), ("contiguous", 255, 2000),
    # past 256 bins: uint16 bins two to a word, a 24-row hi one-hot
    ("dynamic", 279, 8), ("contiguous", 279, 8), ("dynamic", 512, 8),
])
def test_fused_hist_kernel_lowers(v5e, grid, num_bins, f):
    """The fused-gather kernel Mosaic-compiles for v5e: in-kernel
    index fetch (aligned over-read), per-row panel DMA, nibble
    contraction — with both static and DYNAMIC (traced tile count) grids,
    and the root's ``contiguous`` form (one block copy a row tile, static
    grid, the panel's rows padded to whole tiles).
    Offline runs of this proof caught FIVE real lowering failures that
    every interpret-mode test passed: unaligned dynamic 1-D slice
    offsets, non-tile-multiple slice lengths, sub-128-lane panel row
    slices, an LLO compiler crash on integer-indexed (dim-squeezing)
    DMAs, and narrow-bf16 shape-cast/broadcast rejections."""
    import jax.numpy as jnp
    from lightgbm_tpu.data.packing import fused_col_tiles
    from lightgbm_tpu.ops.histogram import subset_histogram_fused
    from lightgbm_tpu.ops.pallas_hist import fused_idx_fetch
    n, tr = 1 << 16, 512
    # pack_fused_panel's layout: column tiles of 128 words (513 columns are
    # two, 2000 five: the kernel walks them in steps of 32 columns, so its
    # program does not grow with the width)
    contiguous = grid == "contiguous"
    per = 4 if num_bins <= 256 else 2          # uint8 or uint16 bins
    panel = (fused_col_tiles(f, per)[0], n + (tr if contiguous else 1), 128)
    no = n + fused_idx_fetch(tr)
    if grid == "dynamic":
        fn = jax.jit(lambda o, p, s, c, nt: subset_histogram_fused(
            o, p, s, c, f, per, num_bins, row_tile=tr, num_row_tiles=nt))
        fn.lower(v5e((no,), jnp.int32), v5e(panel, jnp.uint32),
                 v5e((), jnp.int32), v5e((), jnp.int32),
                 v5e((), jnp.int32)).compile()
    else:
        fn = jax.jit(lambda o, p, s, c: subset_histogram_fused(
            o, p, s, c, f, per, num_bins, row_tile=tr,
            num_row_tiles=n // tr if contiguous else 16,
            contiguous=contiguous))
        fn.lower(v5e((no,), jnp.int32), v5e(panel, jnp.uint32),
                 v5e((), jnp.int32), v5e((), jnp.int32)).compile()


def test_fused_grower_lowers(v5e):
    """The FULL grower on the fused rung (dynamic-grid kernel inside the
    while-loop body, gather-bucket switch retired) Mosaic-compiles at the
    bench config — always on, not gated behind LGBM_TPU_AOT_FULL: this is
    the exact program the tpu+fused bench rung runs."""
    import jax.numpy as jnp
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower
    n, f = 1 << 17, 28
    cfg = GrowerConfig(num_leaves=255, min_data_in_leaf=1,
                       min_sum_hessian_in_leaf=100.0, max_bin=255,
                       hist_method="fused")
    meta = FeatureMeta(
        num_bin=v5e((f,), jnp.int32), missing_type=v5e((f,), jnp.int32),
        default_bin=v5e((f,), jnp.int32),
        is_categorical=v5e((f,), jnp.bool_))
    grow = jax.jit(make_grower(cfg))
    grow.lower(v5e((n, f), jnp.uint8), v5e((n,), jnp.float32),
               v5e((n,), jnp.float32), v5e((n,), jnp.float32),
               meta, v5e((f,), jnp.bool_)).compile()


def test_categorical_fused_grower_lowers(v5e):
    """The grower of the categorical Expo cell on the fused rung: 8
    categorical uint16 columns whose widest keeps 279 bins, built at
    ``layout_width``'s 288 (the kernel's 24-row hi one-hot, the
    sort-by-ratio scan, the 9-word bitset routing) Mosaic-compiles for v5e
    (about 22 s here at 2^17 rows)."""
    import jax.numpy as jnp
    from lightgbm_tpu.grower import (FeatureMeta, GrowerConfig, layout_width,
                                     make_grower)
    n, f = 1 << 17, 8
    cfg = GrowerConfig(num_leaves=255, min_data_in_leaf=1,
                       min_sum_hessian_in_leaf=100.0,
                       max_bin=layout_width(279),
                       hist_method="fused", has_categorical=True)
    meta = FeatureMeta(
        num_bin=v5e((f,), jnp.int32), missing_type=v5e((f,), jnp.int32),
        default_bin=v5e((f,), jnp.int32),
        is_categorical=v5e((f,), jnp.bool_))
    grow = jax.jit(make_grower(cfg))
    grow.lower(v5e((n, f), jnp.uint16), v5e((n,), jnp.float32),
               v5e((n,), jnp.float32), v5e((n,), jnp.float32),
               meta, v5e((f,), jnp.bool_)).compile()


def test_wide_grower_pool_is_whole_tiles(v5e):
    """Epsilon's grower (2000 columns, 255 bins and leaves, the fused rung)
    compiled for the v5e carries the per-leaf pool as ``[L, K, 128]`` under
    the (8, 128) tiling, so a leaf is K / 8 whole tiles, and nothing under
    ``hist_pool`` holds a leaf as a ``[1, 3FB]`` or ``[2, 3FB]`` row, the
    shape whose leaf axis the compiler tiled as the sublanes.  No copy of
    the whole pool either (about 20 s here at 16,384 rows)."""
    import re
    import jax.numpy as jnp
    from lightgbm_tpu.grower import (FeatureMeta, GrowerConfig, make_grower,
                                     pool_tiles)
    n, f, b, L = 1 << 14, 2000, 255, 255
    cfg = GrowerConfig(num_leaves=L, min_data_in_leaf=1,
                       min_sum_hessian_in_leaf=100.0, max_bin=b,
                       hist_method="fused")
    meta = FeatureMeta(
        num_bin=v5e((f,), jnp.int32), missing_type=v5e((f,), jnp.int32),
        default_bin=v5e((f,), jnp.int32),
        is_categorical=v5e((f,), jnp.bool_))
    txt = jax.jit(make_grower(cfg)).lower(
        v5e((n, f), jnp.uint8), v5e((n,), jnp.float32),
        v5e((n,), jnp.float32), v5e((n,), jnp.float32),
        meta, v5e((f,), jnp.bool_)).compile().as_text()
    k = pool_tiles(f, b)
    pool = f"f32[{L},{k},128]"
    layouts = set(re.findall(re.escape(pool) + r"\{([^}]*)\}", txt))
    assert layouts == {"2,1,0:T(8,128)"}, layouts
    rows = [line for line in txt.splitlines() if "hist_pool" in line
            and re.search(rf"f32\[[12],{3 * f * b}\]", line)]
    assert not rows, rows[:3]
    assert not re.findall(rf"= {re.escape(pool)}[^ ]* copy", txt)


FULL_GROWER_PROOFS = pytest.mark.skipif(
    os.environ.get("LGBM_TPU_AOT_FULL") != "1",
    reason="~25 min of uncacheable XLA:TPU AOT compiles; run with "
           "LGBM_TPU_AOT_FULL=1 (the pre-window checklist) — the kernel-"
           "level proofs below always run and catch lowering regressions")


@FULL_GROWER_PROOFS
@pytest.mark.parametrize("n", [1 << 17, 98304, (1 << 17) + 1], ids=[
    "pow2_rows",        # the window table ends at 2^17
    "half_step_rows",   # ... at 3 * 2^15, the largest window and the tail
    "one_row_more",     # ... at 3 * 2^16, the first size that holds n
])
def test_full_grower_lowers(v5e, n):
    """The FULL grower (partition switch over the window table with its
    half-step sizes, the sort transport, the dense branch after the
    table's last window, while_loop, Pallas kernel) must Mosaic-compile
    for v5e at the bench config, wherever the whole table ends (the
    partition's own ends at 16,384, 12,288 and 16,384 slots)."""
    import jax.numpy as jnp
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower
    f = 28
    cfg = GrowerConfig(num_leaves=255, min_data_in_leaf=1,
                       min_sum_hessian_in_leaf=100.0, max_bin=255,
                       hist_method="fused")
    meta = FeatureMeta(
        num_bin=v5e((f,), jnp.int32), missing_type=v5e((f,), jnp.int32),
        default_bin=v5e((f,), jnp.int32),
        is_categorical=v5e((f,), jnp.bool_))
    grow = jax.jit(make_grower(cfg))
    grow.lower(v5e((n, f), jnp.uint8), v5e((n,), jnp.float32),
               v5e((n,), jnp.float32), v5e((n,), jnp.float32),
               meta, v5e((f,), jnp.bool_)).compile()


@FULL_GROWER_PROOFS
def test_full_grower_lowers_wide(v5e):
    """Epsilon-wide (F=2000) grower Mosaic-compiles on the FUSED rung: five
    column tiles through the same kernel as the 28-column data set, the
    per-leaf pool carried as [255, 3 * 2000 * 255] rows (about 140 s here
    on one core at 400,000 rows, PR 27)."""
    import jax.numpy as jnp
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower
    n, f = 1 << 17, 2000
    cfg = GrowerConfig(num_leaves=255, min_data_in_leaf=1,
                       min_sum_hessian_in_leaf=100.0, max_bin=255,
                       hist_method="fused")
    meta = FeatureMeta(
        num_bin=v5e((f,), jnp.int32), missing_type=v5e((f,), jnp.int32),
        default_bin=v5e((f,), jnp.int32),
        is_categorical=v5e((f,), jnp.bool_))
    grow = jax.jit(make_grower(cfg))
    grow.lower(v5e((n, f), jnp.uint8), v5e((n,), jnp.float32),
               v5e((n,), jnp.float32), v5e((n,), jnp.float32),
               meta, v5e((f,), jnp.bool_)).compile()


@pytest.mark.parametrize("learner", ["data", "voting", "feature",
                                     "data_feature"])
def test_distributed_grower_lowers_4chip(learner):
    """All four distributed tree learners Mosaic-compile for a REAL
    4-chip v5e topology — shard_map + ICI collectives (psum, argmax
    sync, all_gather votes) through the actual TPU lowering, not the
    CPU-mesh stand-in.  The strongest multi-chip evidence available
    without multi-chip hardware; execution still needs a real slice."""
    import numpy as np
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig
    from lightgbm_tpu.parallel.learner import make_distributed_grower
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    devs = np.array(topo.devices)
    cfg = GrowerConfig(num_leaves=63, min_data_in_leaf=1,
                       min_sum_hessian_in_leaf=100.0, max_bin=255,
                       hist_method="fused")
    n, f = 1 << 16, 32
    if learner == "data_feature":
        mesh = Mesh(devs.reshape(2, 2), ("data", "feature"))
        row_spec, bins_spec = P("data"), P("data", None)
    else:
        axis = "feature" if learner == "feature" else "data"
        mesh = Mesh(devs.reshape(4), (axis,))
        row_spec = P(axis) if learner != "feature" else P()
        bins_spec = P(axis, None) if learner != "feature" else P()
    fn = make_distributed_grower(cfg, mesh, learner)

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))
    meta = FeatureMeta(
        num_bin=arg((f,), jnp.int32, P()),
        missing_type=arg((f,), jnp.int32, P()),
        default_bin=arg((f,), jnp.int32, P()),
        is_categorical=arg((f,), jnp.bool_, P()))
    fn.lower(arg((n, f), jnp.uint8, bins_spec),
             arg((n,), jnp.float32, row_spec),
             arg((n,), jnp.float32, row_spec),
             arg((n,), jnp.float32, row_spec),
             meta, arg((f,), jnp.bool_, P())).compile()


def test_gspmd_fused_hybrid_lowers_4chip():
    """The gspmd_hist=fused hybrid — shard_map pack + kernel islands
    inside the compiler-partitioned grow program — Mosaic-compiles for a
    REAL 4-chip v5e topology (2x2 batch x feature mesh): the strongest
    offline evidence that the island boundary, the per-shard fused
    kernel, and the partitioner-owned cross-shard reduction compose
    through actual TPU lowering."""
    import numpy as np
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig
    from lightgbm_tpu.parallel.gspmd import make_gspmd_grower
    from lightgbm_tpu.parallel.mesh import BATCH_AXIS, FEATURE_AXIS
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    devs = np.array(topo.devices).reshape(2, 2)
    mesh = Mesh(devs, (BATCH_AXIS, FEATURE_AXIS))
    cfg = GrowerConfig(num_leaves=63, min_data_in_leaf=1,
                       min_sum_hessian_in_leaf=100.0, max_bin=255,
                       hist_method="fused")
    n, f = 1 << 16, 32
    grow = make_gspmd_grower(cfg, mesh)

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))
    meta = FeatureMeta(
        num_bin=arg((f,), jnp.int32, P()),
        missing_type=arg((f,), jnp.int32, P()),
        default_bin=arg((f,), jnp.int32, P()),
        is_categorical=arg((f,), jnp.bool_, P()))
    grow.lower(arg((n, f), jnp.uint8, P(BATCH_AXIS, None)),
               arg((n,), jnp.float32, P(BATCH_AXIS)),
               arg((n,), jnp.float32, P(BATCH_AXIS)),
               arg((n,), jnp.float32, P(BATCH_AXIS)),
               meta, arg((f,), jnp.bool_, P())).compile()


def test_packed_grower_lowers(v5e):
    """The bin-packing composition (packed storage matrix + joint 256-bin
    Pallas histograms + unfold) Mosaic-compiles — the sparse capture
    stage's exact on-chip path."""
    import numpy as np
    import jax.numpy as jnp
    from lightgbm_tpu.data.packing import build_pack_plan
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower
    f = 24
    col_bins = [255, 255] + [9] * (f - 2)        # 2 wide + 22 narrow cols
    plan = build_pack_plan(col_bins)
    assert plan is not None and plan.num_packed >= 20
    n = 1 << 16
    cfg = GrowerConfig(num_leaves=63, min_data_in_leaf=1,
                       min_sum_hessian_in_leaf=100.0, max_bin=255,
                       hist_method="fused")
    meta = FeatureMeta(
        num_bin=v5e((f,), jnp.int32), missing_type=v5e((f,), jnp.int32),
        default_bin=v5e((f,), jnp.int32),
        is_categorical=v5e((f,), jnp.bool_))
    grow = jax.jit(make_grower(cfg, pack_plan=plan))
    grow.lower(v5e((n, f), jnp.uint8),
               v5e((n, plan.num_storage_cols), jnp.uint8),
               v5e((n,), jnp.float32), v5e((n,), jnp.float32),
               v5e((n,), jnp.float32), meta,
               v5e((f,), jnp.bool_)).compile()


def test_lambdarank_buckets_lower(v5e):
    """The ranking objective over length buckets compiles for v5e at
    MS LTR's query lengths (1 to 1,251: every table size from 1 to 1,536
    that the draw fills), and no ``[C, D, D]`` pair block is written to
    memory: the temporaries stay under what ONE chunk's block would take."""
    import jax.numpy as jnp
    from lightgbm_tpu import objectives
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.metadata import Metadata
    rng = np.random.default_rng(31)
    sizes = np.concatenate([[1, 2, 1251, 1024, 700, 500],
                            rng.integers(1, 400, 1500)])
    n = int(sizes.sum())
    md = Metadata()
    md.label = rng.integers(0, 5, n).astype(np.float32)
    md.weight = None
    md.query_boundaries = np.concatenate([[0], np.cumsum(sizes)]) \
        .astype(np.int32)
    obj = objectives.LambdarankNDCG(Config())
    obj.init(md, n)
    assert len(obj._buckets) >= 14
    compiled = jax.jit(obj.get_gradients).lower(
        v5e((1, n), jnp.float32)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 4 * objectives._PAIR_BLOCK, temp


def test_score_update_lowers_without_gather_or_collective():
    """The score update at Higgs's rows and 255 leaves compiles for the v5e
    with no gather (the pick of a row's leaf value is selects over the
    leaves), on one chip and with the rows sharded over a 4 x 1 mesh of
    the 2x2 host, where it holds no collective either."""
    import re
    import numpy as np
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.boosting import _update_score
    from lightgbm_tpu.parallel.mesh import BATCH_AXIS
    from lightgbm_tpu.utils.jaxpr_audit import hlo_collective_census
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    n, leaves = 10_500_000, 255
    for devs in (topo.devices[:1], topo.devices):
        mesh = Mesh(np.array(devs), (BATCH_AXIS,))

        def arg(shape, dtype, spec):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, spec))
        text = _update_score.lower(
            arg((n * len(devs),), jnp.float32, P(BATCH_AXIS)),
            arg((leaves,), jnp.float32, P()),
            arg((n * len(devs),), jnp.int32, P(BATCH_AXIS)),
            arg((), jnp.float32, P())).compile().as_text()
        assert not re.search(r"\bgather\(", text)
        assert hlo_collective_census(text) == {}
