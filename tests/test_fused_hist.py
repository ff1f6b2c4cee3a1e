"""Gen-2 fused-gather histogram kernel parity (fast tier).

The kernel performs the row gather ITSELF (per-tile DMA of indexed panel
rows) — so parity is pinned against the segment-sum oracle over the same
window of a shared ``order`` array, across bin widths (incl. non-pow2),
sentinel padding, dynamic grids, and the packed/EFB storage composition,
all in interpret mode so regressions are caught without a TPU.  The
Mosaic lowering proof lives in tests/test_mosaic_aot.py (slow tier); the
on-chip throughput A/B is the capture playbook's bench_1m_gen1.json.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.data.packing import pack_fused_panel
from lightgbm_tpu.ops.histogram import (subset_histogram_fused,
                                        subset_histogram_segment)
from lightgbm_tpu.ops.pallas_hist import fused_idx_fetch

ROW_TILE = 512


def _problem(n, f, b, seed=0, integer_weights=False, dtype=np.uint8):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, b, size=(n, f)).astype(dtype)
    if integer_weights:
        # bf16-exact weights: small integers survive the kernel's hi/lo
        # split exactly and their f32 sums are order-independent, so the
        # fused kernel must be BIT-identical to the segment oracle
        g = rng.randint(-8, 9, size=n).astype(np.float32)
        h = rng.randint(0, 5, size=n).astype(np.float32)
    else:
        g = rng.randn(n).astype(np.float32)
        h = np.abs(rng.randn(n)).astype(np.float32)
    c = (rng.rand(n) > 0.2).astype(np.float32)
    return bins, g, h, c


def _fused_inputs(bins, g, h, c):
    """Sentinel-pad and panel-pack exactly the way the grower does."""
    n, f = bins.shape
    bins_pad = jnp.concatenate(
        [jnp.asarray(bins), jnp.zeros((1, f), jnp.asarray(bins).dtype)])
    pad1 = lambda x: jnp.concatenate([jnp.asarray(x), jnp.zeros((1,),
                                                                jnp.float32)])
    panel, per = pack_fused_panel(bins_pad, pad1(g), pad1(h), pad1(c))
    return panel, per


def _order_with_tail(perm, n):
    return jnp.concatenate(
        [jnp.asarray(perm, jnp.int32),
         jnp.full((fused_idx_fetch(ROW_TILE),), n, jnp.int32)])


@pytest.mark.parametrize("b", [255, 63, 256])   # non-pow2, small, full-joint
def test_fused_matches_segment_oracle(b):
    """Window histograms across bin widths, with a window that is NOT a
    row-tile multiple (the final tile runs past cnt into sentinel rows)."""
    n, f = 4096, 12
    bins, g, h, c = _problem(n, f, b, seed=b)
    panel, per = _fused_inputs(bins, g, h, c)
    rng = np.random.RandomState(1)
    perm = rng.permutation(n).astype(np.int32)
    order = _order_with_tail(perm, n)
    start, cnt = 700, 1900
    sel = perm[start:start + cnt]
    ref = np.asarray(subset_histogram_segment(
        jnp.asarray(bins[sel]), jnp.asarray(g[sel]), jnp.asarray(h[sel]),
        jnp.asarray(c[sel]), b))
    nt = -(-cnt // ROW_TILE)
    out = np.asarray(subset_histogram_fused(
        order, panel, start, cnt, f, per, b, row_tile=ROW_TILE,
        num_row_tiles=nt, interpret=True))
    # bf16 hi/lo split: ~2^-17 relative error on g/h sums, counts exact
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)
    np.testing.assert_array_equal(out[:, :, 2], ref[:, :, 2])


def test_fused_bit_identical_integer_weights():
    """With bf16-exact weights the fused kernel is BIT-identical to the
    segment oracle — the round-5 pallas_compact discipline applied to a
    kernel whose float path is otherwise tolerance-pinned."""
    n, f, b = 3072, 28, 255
    bins, g, h, c = _problem(n, f, b, seed=7, integer_weights=True)
    panel, per = _fused_inputs(bins, g, h, c)
    perm = np.random.RandomState(3).permutation(n).astype(np.int32)
    order = _order_with_tail(perm, n)
    start, cnt = 1029, 1536    # deliberately unaligned window start
    sel = perm[start:start + cnt]
    ref = np.asarray(subset_histogram_segment(
        jnp.asarray(bins[sel]), jnp.asarray(g[sel]), jnp.asarray(h[sel]),
        jnp.asarray(c[sel]), b))
    out = np.asarray(subset_histogram_fused(
        order, panel, start, cnt, f, per, b, row_tile=ROW_TILE,
        num_row_tiles=-(-cnt // ROW_TILE), interpret=True))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("f,tiles", [(600, 2), (1100, 3)])
def test_fused_wide_bit_identical_integer_weights(f, tiles):
    """Past one column tile: 600 columns cross one tile boundary (two
    tiles of 320, 40 phantom columns), 1100 are not a multiple of their
    tile (three of 384).  Same pin as at 28 columns: bit-identical to
    the segment oracle on bf16-exact weights, over an unaligned window
    whose last row tile runs into the sentinel."""
    from lightgbm_tpu.data.packing import fused_col_tiles
    assert fused_col_tiles(f, 4)[0] == tiles
    n, b = 1536, 255
    bins, g, h, c = _problem(n, f, b, seed=f, integer_weights=True)
    panel, per = _fused_inputs(bins, g, h, c)
    assert panel.shape == (tiles, n + 1, 128)
    perm = np.random.RandomState(3).permutation(n).astype(np.int32)
    order = _order_with_tail(perm, n)
    start, cnt = 301, 700
    sel = perm[start:start + cnt]
    ref = np.asarray(subset_histogram_segment(
        jnp.asarray(bins[sel]), jnp.asarray(g[sel]), jnp.asarray(h[sel]),
        jnp.asarray(c[sel]), b))
    out = np.asarray(subset_histogram_fused(
        order, panel, start, cnt, f, per, b, row_tile=ROW_TILE,
        num_row_tiles=-(-cnt // ROW_TILE), interpret=True))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("b,f,hi", [(257, 8, 24), (279, 8, 24),
                                    (512, 12, 32), (279, 40, 24)])
def test_fused_past_256_bins_matches_segment_oracle(b, f, hi):
    """uint16 bins, two to a word: the hi one-hot grows to ``hi`` rows
    (ceil(b / 16) on whole sublane tiles) and the kernel stays bit-identical
    to the segment oracle on bf16-exact weights, every bin of the width
    populated, over an unaligned window whose last tile runs into the
    sentinel; 40 columns take two steps of the column loop."""
    from lightgbm_tpu.ops.pallas_hist import fused_hi
    assert fused_hi(b) == hi
    n = 2048
    bins, g, h, c = _problem(n, f, b, seed=b + f, integer_weights=True,
                             dtype=np.uint16)
    bins[:b, 0] = np.arange(b)          # the last bin is read, not only 0
    panel, per = _fused_inputs(bins, g, h, c)
    assert per == 2
    perm = np.random.RandomState(5).permutation(n).astype(np.int32)
    order = _order_with_tail(perm, n)
    start, cnt = 97, 1700
    sel = perm[start:start + cnt]
    ref = np.asarray(subset_histogram_segment(
        jnp.asarray(bins[sel]), jnp.asarray(g[sel]), jnp.asarray(h[sel]),
        jnp.asarray(c[sel]), b))
    out = np.asarray(subset_histogram_fused(
        order, panel, start, cnt, f, per, b, row_tile=ROW_TILE,
        num_row_tiles=-(-cnt // ROW_TILE), interpret=True))
    assert out.shape == (f, b, 3)
    np.testing.assert_array_equal(out, ref)


def _kernel_jaxpr(b, f=28, per=4):
    """The kernel body's jaxpr and its output block, as the grower traces
    it at histogram width ``b``."""
    import jax
    from lightgbm_tpu.data.packing import fused_col_tiles
    from lightgbm_tpu.ops.pallas_hist import hist6_fused
    n = 4096
    panel = jax.ShapeDtypeStruct((fused_col_tiles(f, per)[0], n + 1, 128),
                                 jnp.uint32)
    order = jax.ShapeDtypeStruct((n + fused_idx_fetch(ROW_TILE),), jnp.int32)
    s = jax.ShapeDtypeStruct((), jnp.int32)
    jx = jax.make_jaxpr(lambda o, p, a, c, nt: hist6_fused(
        o, p, a, c, f, per, b, row_tile=ROW_TILE, num_row_tiles=nt))(
            order, panel, s, s, s)
    call = next(e for e in jx.jaxpr.eqns if e.primitive.name == "pallas_call")
    return str(call.params["jaxpr"]), call.outvars[0].aval.shape


def test_fused_kernel_at_256_bins_or_fewer_is_the_two_nibble_program():
    """Every width of 256 bins or fewer traces ONE kernel program: a 16-row
    hi one-hot, [96, 512] output slabs (6 channels x 16), whatever the
    width; past 256 only the hi one-hot's height moves."""
    from lightgbm_tpu.ops.pallas_hist import NUM_CH, fused_hi
    assert {fused_hi(b) for b in (2, 16, 63, 255, 256)} == {16}
    base, shape = _kernel_jaxpr(255)
    assert shape == (1, NUM_CH * 16, 512)
    for b in (63, 256):
        assert _kernel_jaxpr(b) == (base, shape)
    wide, wide_shape = _kernel_jaxpr(279, f=8, per=2)
    assert wide_shape == (1, NUM_CH * 24, 512) and wide != base


def test_fused_dynamic_grid_matches_static():
    """The grower's dynamic-grid form (traced tile count) must equal the
    static grid bin for bin."""
    import jax
    n, f, b = 2048, 8, 63
    bins, g, h, c = _problem(n, f, b, seed=11)
    panel, per = _fused_inputs(bins, g, h, c)
    perm = np.random.RandomState(5).permutation(n).astype(np.int32)
    order = _order_with_tail(perm, n)
    start, cnt = 333, 1000
    static = np.asarray(subset_histogram_fused(
        order, panel, start, cnt, f, per, b, row_tile=ROW_TILE,
        num_row_tiles=2, interpret=True))

    @jax.jit
    def dyn(order, panel, start, cnt):
        nt = jnp.maximum(1, (cnt + ROW_TILE - 1) // ROW_TILE)
        return subset_histogram_fused(
            order, panel, start, cnt, f, per, b, row_tile=ROW_TILE,
            num_row_tiles=nt.astype(jnp.int32), interpret=True)
    dynamic = np.asarray(dyn(order, panel, jnp.asarray(start, jnp.int32),
                             jnp.asarray(cnt, jnp.int32)))
    np.testing.assert_array_equal(static, dynamic)


def test_fused_empty_and_tiny_windows():
    """cnt = 0 (empty smaller child) must produce an all-zero histogram;
    cnt = 1 a single-row one — both through the mandatory >= 1-tile grid."""
    n, f, b = 1024, 4, 16
    bins, g, h, c = _problem(n, f, b, seed=13)
    panel, per = _fused_inputs(bins, g, h, c)
    order = _order_with_tail(np.arange(n, dtype=np.int32), n)
    empty = np.asarray(subset_histogram_fused(
        order, panel, 5, 0, f, per, b, row_tile=ROW_TILE,
        num_row_tiles=1, interpret=True))
    assert (empty == 0).all()
    one = np.asarray(subset_histogram_fused(
        order, panel, 5, 1, f, per, b, row_tile=ROW_TILE,
        num_row_tiles=1, interpret=True))
    ref = np.asarray(subset_histogram_segment(
        jnp.asarray(bins[5:6]), jnp.asarray(g[5:6]), jnp.asarray(h[5:6]),
        jnp.asarray(c[5:6]), b))
    np.testing.assert_array_equal(one[:, :, 2], ref[:, :, 2])
    np.testing.assert_allclose(one, ref, rtol=3e-4, atol=3e-4)


def _identity_problem(n, f, dtype, row_tile):
    """A panel of ``n`` real rows padded to whole row tiles, as the grower
    packs it, and the identity ``order`` the root passes."""
    b = 255 if dtype == np.uint8 else 256
    bins, g, h, c = _problem(n, f, b, seed=n + f, dtype=dtype)
    pad1 = lambda x: jnp.concatenate([jnp.asarray(x),
                                      jnp.zeros((1,), jnp.float32)])
    panel, per = pack_fused_panel(
        jnp.concatenate([jnp.asarray(bins), jnp.zeros((1, f), dtype)]),
        pad1(g), pad1(h), pad1(c), row_multiple=row_tile)
    assert panel.shape[1] % row_tile == 0 and panel.shape[1] > n
    order = jnp.concatenate(
        [jnp.arange(n, dtype=jnp.int32),
         jnp.full((fused_idx_fetch(row_tile),), n, jnp.int32)])
    return panel, per, order, b, float(c.sum())


BLOCK_TILE = 128     # the smallest row tile the configuration admits


@pytest.mark.parametrize("n", [1, BLOCK_TILE - 1, BLOCK_TILE, BLOCK_TILE + 1,
                               3 * BLOCK_TILE + 7])
@pytest.mark.parametrize("f,dtype", [(28, np.uint8), (600, np.uint8),
                                     (1100, np.uint8), (28, np.uint16)],
                         ids=["28", "600", "1100", "28-uint16"])
def test_fused_block_fetch_identical_to_indexed(n, f, dtype):
    """The root's fetch form (``contiguous``: one block copy a row tile,
    no index) against the indexed form over the same identity window:
    the same tiles in the same order into the same block, so bit for bit,
    float weights and all — through the ragged last tile, whose rows past
    ``n`` are the panel's sentinel padding."""
    panel, per, order, b, counted = _identity_problem(n, f, dtype, BLOCK_TILE)
    kw = dict(row_tile=BLOCK_TILE, num_row_tiles=-(-n // BLOCK_TILE),
              interpret=True)
    rows = np.asarray(subset_histogram_fused(order, panel, 0, n, f, per, b,
                                             **kw))
    block = np.asarray(subset_histogram_fused(order, panel, 0, n, f, per, b,
                                              contiguous=True, **kw))
    assert rows[:, :, 2].sum() == f * counted     # every row, once a column
    np.testing.assert_array_equal(block, rows)


@pytest.mark.parametrize("dyn_grid", [False, True], ids=["static", "dynamic"])
@pytest.mark.parametrize("cnt", [0, 1, ROW_TILE, 2 * ROW_TILE + 3])
def test_fused_pipeline_identical_to_one_slot(cnt, dyn_grid):
    """The two-slot fetch (tile i + 1 issued before tile i is waited for,
    one wait a tile) against a ONE-slot kernel that issues, waits row by
    row and then computes, as the kernel did before the pipeline
    (``scripts/probe_hist_fetch.py``'s ``parent`` form, around the same
    arithmetic): bit for bit over windows at an unaligned start."""
    import importlib
    import jax
    probe = importlib.import_module("scripts.probe_hist_fetch")
    n, f, b = 2048, 28, 255
    bins, g, h, c = _problem(n, f, b, seed=cnt)
    panel, per = _fused_inputs(bins, g, h, c)
    perm = np.random.RandomState(9).permutation(n).astype(np.int32)
    order = _order_with_tail(perm, n)
    start = 333
    nt = max(1, -(-cnt // ROW_TILE))
    ref = np.asarray(probe.probe_hist6("parent", order, panel, start, cnt, f,
                                       b, nt, interpret=True))
    from lightgbm_tpu.ops.pallas_hist import hist6_fused
    if dyn_grid:
        got = jax.jit(lambda o, p, s, k: hist6_fused(
            o, p, s, k, f, per, b, row_tile=ROW_TILE,
            num_row_tiles=jnp.maximum(1, (k + ROW_TILE - 1) // ROW_TILE),
            interpret=True))(order, panel, jnp.int32(start), jnp.int32(cnt))
    else:
        got = hist6_fused(order, panel, start, cnt, f, per, b,
                          row_tile=ROW_TILE, num_row_tiles=nt, interpret=True)
    assert (np.asarray(got)[4].sum() > 0) == (cnt > 0)
    np.testing.assert_array_equal(np.asarray(got), ref)


def _grow_tree_strings(hist_method, bins, g, h, c, num_bins, pack_plan=None,
                       hist_bins=None, num_bin_arr=None, num_leaves=15,
                       min_data_in_leaf=5):
    import jax
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower
    f = bins.shape[1]
    cfg = GrowerConfig(num_leaves=num_leaves,
                       min_data_in_leaf=min_data_in_leaf, max_bin=num_bins,
                       hist_method=hist_method,
                       hist_interpret=hist_method == "fused")
    meta = FeatureMeta(
        num_bin=(jnp.asarray(num_bin_arr, jnp.int32)
                 if num_bin_arr is not None
                 else jnp.full((f,), num_bins, jnp.int32)),
        missing_type=jnp.zeros((f,), jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.zeros((f,), bool))
    grow = jax.jit(make_grower(cfg, pack_plan=pack_plan))
    args = (jnp.asarray(bins),) + (
        (jnp.asarray(hist_bins),) if pack_plan is not None else ())
    tree, row_leaf = grow(*args, jnp.asarray(g), jnp.asarray(h),
                          jnp.asarray(c), meta,
                          jnp.ones((f,), bool))
    return jax.tree_util.tree_map(np.asarray, tree), np.asarray(row_leaf)


def test_grower_fused_tree_identical_to_segment():
    """End-to-end: the full grower on the fused rung (interpret mode,
    dynamic grids, no gather-bucket switch) grows the IDENTICAL tree to
    the segment rung — structure, thresholds, and row routing."""
    n, f, b = 3000, 10, 63
    bins, g, h, c = _problem(n, f, b, seed=17)
    c[:] = 1.0
    t_seg, rl_seg = _grow_tree_strings("segment", bins, g, h, c, b)
    t_fus, rl_fus = _grow_tree_strings("fused", bins, g, h, c, b)
    assert int(t_seg.num_leaves) > 4          # the tree actually grew
    np.testing.assert_array_equal(t_seg.split_feature, t_fus.split_feature)
    np.testing.assert_array_equal(t_seg.threshold_bin, t_fus.threshold_bin)
    np.testing.assert_array_equal(rl_seg, rl_fus)
    np.testing.assert_allclose(t_seg.leaf_value, t_fus.leaf_value,
                               rtol=2e-4, atol=2e-4)


def test_grower_wide_tree_identical_across_rungs():
    """A 31-leaf tree at 600 columns (two column tiles) is the same tree
    on the fused and the segment rung: bf16-exact integer weights, so
    byte for byte."""
    n, f, b = 1500, 600, 63
    bins, g, h, c = _problem(n, f, b, seed=41, integer_weights=True)
    kw = dict(num_leaves=31, min_data_in_leaf=1)
    t_seg, rl_seg = _grow_tree_strings("segment", bins, g, h, c, b, **kw)
    t_fus, rl_fus = _grow_tree_strings("fused", bins, g, h, c, b, **kw)
    assert int(t_seg.num_leaves) == 31
    np.testing.assert_array_equal(t_seg.split_feature, t_fus.split_feature)
    np.testing.assert_array_equal(t_seg.threshold_bin, t_fus.threshold_bin)
    np.testing.assert_array_equal(rl_seg, rl_fus)
    np.testing.assert_array_equal(t_seg.leaf_value, t_fus.leaf_value)


def test_grower_fused_packed_storage():
    """The packed-pair (Dense4bits/EFB-style) composition: joint 256-bin
    histograms over the packed storage matrix through the FUSED kernel,
    unfolded to per-feature histograms — tree identical to segment."""
    from lightgbm_tpu.data.packing import build_pack_plan, pack_columns
    n, f = 2500, 12
    col_bins = [255, 255] + [9] * (f - 2)      # 2 wide + 10 nibble-packable
    rng = np.random.RandomState(23)
    bins = np.stack([rng.randint(0, nb, size=n) for nb in col_bins],
                    axis=1).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = np.abs(rng.randn(n)).astype(np.float32)
    c = np.ones(n, np.float32)
    plan = build_pack_plan(col_bins)
    assert plan is not None and plan.num_packed == f - 2
    packed = pack_columns(bins, plan)
    kw = dict(pack_plan=plan, hist_bins=packed, num_bin_arr=col_bins)
    t_seg, rl_seg = _grow_tree_strings("segment", bins, g, h, c, 255, **kw)
    t_fus, rl_fus = _grow_tree_strings("fused", bins, g, h, c, 255, **kw)
    assert int(t_seg.num_leaves) > 4
    np.testing.assert_array_equal(t_seg.split_feature, t_fus.split_feature)
    np.testing.assert_array_equal(t_seg.threshold_bin, t_fus.threshold_bin)
    np.testing.assert_array_equal(rl_seg, rl_fus)


def test_grower_255_leaf_tree_identical_across_rungs():
    """Deep-tree (255-leaf) identity pin across histogram rungs — the
    leaves-sweep regime the round-7 fast-path work (fused pair-write to
    the hist store, 64-row bucket floor, narrow sub-512 Pallas row
    tiles) optimizes.  Every rung must grow the identical tree:
    structure, thresholds, and row routing, deep into the sub-128-row
    tail buckets the small-leaf fast path introduces.  bf16-exact
    integer weights make every rung's histogram sums EXACT in any
    accumulation order, so the pin is byte-identical — float weights
    would let last-ulp summation differences flip near-tied deep splits
    and pin nothing."""
    n, f, b = 4000, 10, 63
    bins, g, h, c = _problem(n, f, b, seed=31, integer_weights=True)
    kw = dict(num_leaves=255, min_data_in_leaf=1)
    t_seg, rl_seg = _grow_tree_strings("segment", bins, g, h, c, b, **kw)
    t_ein, rl_ein = _grow_tree_strings("einsum", bins, g, h, c, b, **kw)
    t_fus, rl_fus = _grow_tree_strings("fused", bins, g, h, c, b, **kw)
    assert int(t_seg.num_leaves) > 200    # the tail buckets actually ran
    for t, rl in ((t_ein, rl_ein), (t_fus, rl_fus)):
        assert int(t.num_leaves) == int(t_seg.num_leaves)
        np.testing.assert_array_equal(t_seg.split_feature, t.split_feature)
        np.testing.assert_array_equal(t_seg.threshold_bin, t.threshold_bin)
        np.testing.assert_array_equal(rl_seg, rl)
        np.testing.assert_array_equal(t_seg.leaf_value, t.leaf_value)


def test_fused_config_on_wide_bins_raises():
    """A > 2-byte bin matrix cannot word-pack.  Which method such a layout
    trains with is ``resolve_hist_method``'s to say (the XLA reference, with
    one ``layout_downgrade`` from the booster's set-up); a ``GrowerConfig``
    that names the fused kernel on it all the same is an error that carries
    the gate's reason, not a second, silent choice at trace time."""
    n, f, b = 1500, 6, 63
    bins, g, h, c = _problem(n, f, b, seed=29, dtype=np.int32)
    with pytest.raises(ValueError, match="hist_method=fused cannot run on "
                       "this layout: bin dtype int32 is wider than 2 bytes"):
        _grow_tree_strings("fused", bins, g, h, c, b)


def test_hist_block_fetch_metric_reads_the_fetch_tag():
    """The benchmark's ``hist_block_fetch`` counts the ``hist_dispatch``
    call sites built with the block fetch: 1 from a grower on the fused
    rung (the root), None from a program that does not tag its fetch."""
    import json
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmarks.harness import metrics
    from lightgbm_tpu.obs.counters import counters
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        entry = next(m for m in json.load(fh)["per_layer"]
                     if m["name"] == "hist_block_fetch")
    assert entry["layer"] == "histogram kernel"
    assert entry["moves"] == "trees_per_s" and entry["better"] == "higher"

    counters.reset()
    bins, g, h, c = _problem(600, 4, 16, seed=3)
    _grow_tree_strings("fused", bins, g, h, c, 16, num_leaves=4)
    assert set(counters.get("hist_dispatch")) == {
        f"col_tiles=1,fetch={fetch},hi=16,interpret=True,method=fused,"
        f"site={s},width=16" for s, fetch in (("root", "block"),
                                              ("split", "rows"))}
    assert metrics.read_metric("hist_block_fetch", {}) == 1
    counters.reset()
    counters.inc("hist_dispatch", method="fused", site="root", col_tiles=1)
    assert metrics.read_metric("hist_block_fetch", {}) is None
    counters.reset()
    assert metrics.read_metric("hist_block_fetch", {}) is None
