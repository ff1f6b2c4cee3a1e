"""GSPMD tree growing: NamedSharding over a named (batch, feature) mesh.

The shard_map learners (``parallel/learner.py``) re-created the
reference's hand-rolled network layer in XLA clothing: every psum /
all_gather is still a CALL SITE someone chose.  This module inverts the
contract — the grow program is written once over GLOBAL arrays, inputs
and loop carries are annotated with :class:`jax.sharding.NamedSharding`
over a named 2-D ``(batch, feature)`` mesh, and the XLA SPMD partitioner
inserts (and overlaps) the collectives itself:

* binned data, gradients and the row->leaf partition carry row-sharded
  on ``batch`` (optionally block-sharded over ``feature`` too — the
  "Block-distributed Gradient Boosted Trees" row x column layout);
* the per-leaf histogram pool ``[L, F, B, 3]`` shards on ``feature`` —
  the component that outgrows one chip's HBM first (docs/MEMORY.md), and
  the reason ``mesh_shape=auto`` exists (``parallel/mesh.plan_mesh``);
* the per-split histogram is a plain masked sum over rows; with the
  output constrained to the feature sharding, the partitioner has each
  device reduce only its own output slice and inserts the shard-sized
  cross-``batch`` reduction — the reduce-scatter the reference
  implemented by hand (``data_parallel_tree_learner.cpp:148-163``),
  now owned by the compiler (pinned via the compiled-HLO census,
  ``utils/jaxpr_audit.hlo_collective_census``).

What changes against the windowed serial grower: the ``order``
permutation (and its gather-bucket ``lax.switch``) cannot live under
GSPMD — a data-dependent window slice of a sharded carrier would force
the partitioner to materialize the global array.  The partition is
instead the direct row->leaf map: routing a split is one elementwise
update of ``row_leaf`` (collective-free — every row's bin is local), and
the smaller child's histogram selects on ``row_leaf == child`` over all
local rows.  Per-device split cost is O(rows/shard) instead of the
serial path's O(window) — the trade the reference's data-parallel
learner also makes (each worker scans its whole partition), bought back
by sharding.  Routing decisions, split selection and leaf outputs reuse
the serial grower's exact helpers (``route_goes_left`` / ``best_split``
/ ``pool_rows`` / ``unpack_tree``), so trees are the SAME trees —
byte-identical under order-insensitive (integer) weights, pinned across
mesh shapes in tests/test_gspmd.py.

The HISTOGRAM itself has two formulations under the same program shape
(``gspmd_hist``, resolved in ``boosting._setup_gspmd``):

* ``flat`` — the masked whole-partition scatter-add
  (``subset_histogram_flat``): pure XLA, partitions on any layout;
* ``fused`` — the hybrid: a ``shard_map`` manual-sharding ISLAND inside
  the same jit'd program, in which each device runs the fused Pallas
  gather-histogram (``ops/pallas_hist.hist6_fused``) over its own row
  shard of the packed ``pack_fused_panel`` layout.  Mosaic owns the
  inside of the island (per-shard index compaction + in-kernel row
  DMAs); the SPMD partitioner still owns everything OUTSIDE it — the
  island returns per-device feature-sliced partials and the cross-shard
  reduction into the feature-sharded pool is the partitioner's, with
  the same shard-sized payload the flat path gets (pinned via the HLO
  census: no all-gather of row shards, ever).  One kernel from laptop
  CPU (``hist_interpret=True``) to pod slice.

On a mesh of row shards alone (``feature`` extent 1, the
``tree_learner=data`` layout) the ``fused`` form widens the island to the
WHOLE grow loop (``parallel/learner.make_distributed_grower``'s data
learner under the program name ``grow_tree``): each device grows on
its own rows with the serial grower's machinery (its ``order`` window
partition, the dense branch, the fused kernel over its own panel), and
only the histogram reduction (``hist_reduce``, one psum of a
[F, B, 3] table a split) and the root's three sums cross chips.  A
split then costs each device what its leaf's rows cost, where the
row -> leaf selection above costs all its rows (a cumulative sum and a
scatter of N / d a split; PERF.md, PR 38).

``parallel/sync.py``'s hardened host-object ladder stays the
control-plane (bin finding, checkpoint barriers, preemption agreement):
GSPMD owns the data plane only.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..data.packing import (PACK_JOINT_BINS, pack_fused_panel,
                            unfold_packed_hist)
from ..grower import (FeatureMeta, GrowerConfig, _depth_gate,
                      expand_bundle_hist, make_expand_maps, pool_rows,
                      route_goes_left, scoped_program_name, unpack_tree)
from ..obs.counters import counters as obs_counters
from ..ops.histogram import subset_histogram_flat, subset_histogram_fused_local
from ..ops.split import best_split, leaf_output, make_fused_ctx
from .learner import make_distributed_grower
from .mesh import BATCH_AXIS, FEATURE_AXIS


def make_gspmd_grower(cfg: GrowerConfig, mesh: Mesh,
                      bundled: bool = False, pack_plan=None,
                      block_shard: bool = False) -> Callable:
    """Build the jitted GSPMD ``grow_tree`` over global arrays.

    Same call signature as ``make_grower``'s product — ``fn(bins,
    [hist_bins,] gw, hw, cw, meta, feat_valid) -> (TreeArrays,
    row_leaf)`` — operating on arrays placed with
    ``NamedSharding(mesh, ...)`` (uncommitted inputs are resharded by the
    first call).  ``row_leaf`` comes back row-sharded on ``batch``.

    The histogram formulation follows ``cfg.hist_method``: ``"fused"``
    builds the shard_map hybrid (module docstring) — the fused Pallas
    kernel is a manual-layout custom call the SPMD partitioner cannot
    split, so it runs INSIDE a manual-sharding island over per-shard
    locals, and only its per-device partial sums re-enter partitioner
    territory.  Any other value runs the flat scatter-add
    (``subset_histogram_flat``; the scan-chunked forms make the
    partitioner all-gather the row shards, and unfusable layouts are
    downgraded loudly by ``boosting._setup_gspmd`` before this builder
    runs — by then the request is always fused or flat).
    """
    L = cfg.num_leaves
    hist_width = (max(PACK_JOINT_BINS, cfg.max_bin) if pack_plan is not None
                  else cfg.max_bin)
    shard_hist = int(mesh.shape[FEATURE_AXIS]) > 1
    f_shards = int(mesh.shape[FEATURE_AXIS])
    use_fused = cfg.hist_method == "fused"
    if use_fused and f_shards == 1 and not block_shard:
        # the island around the whole grow loop: the data-parallel learner
        return make_distributed_grower(cfg, mesh, "data", bundled=bundled,
                                       pack_plan=pack_plan, name="grow_tree")

    def cstr(x, spec):
        return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def smap(fn, in_specs, out_specs):
        return shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def grow_impl(bins, hist_src, gw, hw, cw, meta: FeatureMeta,
                  feat_valid):
        n, f = bins.shape
        dtype = gw.dtype
        scfg = cfg.split_config()
        fctx = (make_fused_ctx(meta.num_bin, meta.missing_type,
                               meta.default_bin, cfg.max_bin, scfg)
                if scfg.split_find == "fused" else None)
        num_logical = meta.num_bin.shape[0]
        fh = (pack_plan.num_phys_cols if pack_plan is not None
              else hist_src.shape[1])
        maps = (make_expand_maps(meta, cfg.max_bin, fh)
                if meta.col is not None else None)

        def expand(hist, pg, ph, pc):
            # the children as ONE batch, outside the find's vmap
            # (``expand_bundle_hist``)
            if maps is None:
                return hist
            with jax.named_scope("bundle_expand"):
                return expand_bundle_hist(hist, pg, ph, pc, maps)

        def find(hist, pg, ph, pc, feat_ok):
            obs_counters.inc("split_find_dispatch", impl=cfg.split_find)
            with jax.named_scope("split_find"):
                return best_split(hist, pg, ph, pc, meta.num_bin,
                                  meta.missing_type, meta.default_bin,
                                  feat_valid & feat_ok, scfg,
                                  is_cat=meta.is_categorical,
                                  with_feat_ok=True, fused_ctx=fctx)

        # ---- fused island: per-shard panel, packed once per grow --------
        # loop-invariant (weights are per-tree constants: the fused kernel
        # selects leaf membership through the row -> leaf partition, not
        # through masked weights), so XLA hoists it out of the while loop.
        # in_specs reshard hist_src's feature axis even when the global
        # carrier is feature-replicated: each device packs only ITS column
        # slice (f-way compute parallelism, and the island's partials stay
        # slice-sized — a local slice, never a collective).
        panel = None
        if use_fused:
            sc_cols = hist_src.shape[1]
            # layout gates live in boosting._setup_gspmd (loudly, before
            # labels are read); by trace time they must all hold
            assert sc_cols % f_shards == 0, (sc_cols, f_shards)
            fcols_loc = sc_cols // f_shards
            words_per = 4 if hist_src.dtype.itemsize == 1 else 2
            panel_fspec = FEATURE_AXIS if f_shards > 1 else None
            # Pin the GLOBAL carriers to their caller placements before the
            # island sees them.  Without the pin the island's
            # feature-sharded in_spec wins the sharding-propagation
            # argument and bins goes feature-sharded program-wide — then
            # routing's dynamic column read inside the while body
            # re-gathers a full row shard EVERY split, exactly the
            # collective the hybrid exists to avoid (the HLO census test
            # pins its absence).  With the pin the reshard is a one-time
            # local slice at the island boundary.
            bins = cstr(bins, P(BATCH_AXIS,
                                FEATURE_AXIS if block_shard else None))
            if pack_plan is None:
                hist_src = bins
            else:
                # the packed histogram matrix is always placed
                # feature-replicated by boosting (P(batch, None))
                hist_src = cstr(hist_src, P(BATCH_AXIS, None))

            def pack_island(bins_loc, g_loc, h_loc, c_loc):
                zrow = jnp.zeros((1, bins_loc.shape[1]), bins_loc.dtype)
                zw = jnp.zeros((1,), g_loc.dtype)
                p, _ = pack_fused_panel(
                    jnp.concatenate([bins_loc, zrow], axis=0),
                    jnp.concatenate([g_loc, zw]),
                    jnp.concatenate([h_loc, zw]),
                    jnp.concatenate([c_loc, zw]))
                return p

            with jax.named_scope("fused_panel"):
                panel = smap(
                    pack_island,
                    in_specs=(P(BATCH_AXIS, panel_fspec), P(BATCH_AXIS),
                              P(BATCH_AXIS), P(BATCH_AXIS)),
                    out_specs=P(None, BATCH_AXIS, panel_fspec),
                )(hist_src, gw, hw, cw)

        def measure(row_leaf_cur, leaf_id, g_, h_, c_, site):
            """One leaf histogram, both formulations.

            flat: masked whole-partition scatter-add — the sum over the
            row axis IS the collective; with the feature-sharded output
            constraint each device reduces only its own slice and XLA
            inserts the shard-sized cross-batch reduction.

            fused: shard_map island — each device compacts its local
            ``row_leaf == leaf`` rows and runs the fused Pallas
            gather-histogram over its panel slice; the island returns
            [d, C/f, B, 3] per-device partials and the ``sum(axis=0)``
            OUTSIDE the island hands the partitioner the exact same
            shard-sized cross-batch reduction (never an all-gather of row
            shards — pinned by the HLO census)."""
            if use_fused:
                def hist_island(panel_loc, rl_loc, leaf_loc):
                    part = subset_histogram_fused_local(
                        rl_loc, leaf_loc, panel_loc, fcols_loc, words_per,
                        hist_width, row_tile=cfg.row_tile,
                        interpret=cfg.hist_interpret, site=site)
                    return part[None]

                part = smap(
                    hist_island,
                    in_specs=(P(None, BATCH_AXIS, panel_fspec),
                              P(BATCH_AXIS), P()),
                    out_specs=P(BATCH_AXIS, panel_fspec, None, None),
                )(panel, row_leaf_cur, jnp.asarray(leaf_id, jnp.int32))
                hist = jnp.sum(part, axis=0)
            else:
                hist = subset_histogram_flat(hist_src, g_, h_, c_,
                                             hist_width, site=site)
            if pack_plan is not None:
                hist = unfold_packed_hist(hist, pack_plan, cfg.max_bin)
            return cstr(hist, P(FEATURE_AXIS if shard_hist else None,
                                None, None))

        # ---- root -------------------------------------------------------
        root_g = jnp.sum(gw)
        root_h = jnp.sum(hw)
        root_c = jnp.sum(cw)
        feat_ok_all = jnp.ones((num_logical,), bool)
        row_leaf0 = cstr(jnp.zeros((n,), jnp.int32), P(BATCH_AXIS))
        with jax.named_scope("histogram"):
            hist_root = measure(row_leaf0, jnp.asarray(0, jnp.int32),
                                gw, hw, cw, site="root")
        res_root, root_feat_ok = find(
            expand(hist_root, root_g, root_h, root_c), root_g, root_h,
            root_c, feat_ok_all)
        res_root = _depth_gate(res_root, jnp.asarray(0), cfg.max_depth)

        store_spec = P(None, FEATURE_AXIS if shard_hist else None,
                       None, None)
        hist_store0 = cstr(jnp.zeros((L, fh, cfg.max_bin, 3), dtype)
                           .at[0].set(hist_root), store_spec)
        feat_ok_store0 = jnp.zeros((L, num_logical), bool).at[0].set(
            root_feat_ok)
        root_f32, root_i32 = pool_rows(res_root, 0)
        sgain0 = jnp.full((L,), -jnp.inf, res_root.gain.dtype).at[0].set(
            res_root.gain)
        sf32_0 = jnp.zeros((L, 8), dtype).at[0].set(root_f32)
        si32_0 = jnp.zeros((L, 3), jnp.int32).at[0].set(root_i32)
        if cfg.has_categorical:
            scat0 = jnp.zeros((L,), bool).at[0].set(res_root.is_cat)
            scatb0 = jnp.zeros((L, cfg.max_bin), bool).at[0].set(
                res_root.cat_bins)
            tcat0 = jnp.zeros((L - 1,), bool)
            tcatb0 = jnp.zeros((L - 1, cfg.max_bin), bool)
        else:
            scat0 = jnp.zeros((0,), bool)
            scatb0 = jnp.zeros((0, 0), bool)
            tcat0 = jnp.zeros((0,), bool)
            tcatb0 = jnp.zeros((0, 0), bool)
        tnf0 = jnp.zeros((L - 1, 3), dtype)
        tni0 = jnp.zeros((L - 1, 5), jnp.int32)
        tlf0 = jnp.zeros((L, 2), dtype).at[0, 1].set(root_c)
        tli0 = jnp.concatenate([jnp.full((L, 1), -1, jnp.int32),
                                jnp.zeros((L, 1), jnp.int32)], axis=1)

        def cond(state):
            step = state[0]
            sgain = state[2]
            return (step < L - 1) & (jnp.max(sgain) > 0.0)

        def body(state):
            (i, row_leaf, sgain, sf32, si32, scat, scatb, hist_store,
             feat_ok, tnf, tni, tlf, tli, tcat, tcatb) = state
            l = jnp.argmax(sgain).astype(jnp.int32)
            new_leaf = i + 1
            node = i
            pair_lr = jnp.stack([l, new_leaf])

            irow = lax.dynamic_index_in_dim(si32, l, axis=0, keepdims=False)
            frow = lax.dynamic_index_in_dim(sf32, l, axis=0, keepdims=False)
            feat, thr = irow[0], irow[1]
            dleft = irow[2].astype(bool)

            # --- routing: ONE elementwise pass over the row partition
            #     (DataPartition::Split without the window machinery —
            #     every row's bin is shard-local, so no collective) -------
            col_idx = feat if meta.col is None else meta.col[feat]
            binf = lax.dynamic_index_in_dim(
                bins, col_idx, axis=1, keepdims=False).astype(jnp.int32)
            cat_args = ((scat[l], scatb[l]) if cfg.has_categorical else ())
            with jax.named_scope("partition"):
                goes_left = route_goes_left(
                    binf, meta, feat, thr, dleft,
                    has_categorical=cfg.has_categorical,
                    is_cat_l=cat_args[0] if cfg.has_categorical else None,
                    cat_row=cat_args[1] if cfg.has_categorical else None,
                    max_bin=cfg.max_bin)
                in_l = row_leaf == l
                row_leaf = cstr(jnp.where(
                    in_l, jnp.where(goes_left, l, new_leaf), row_leaf),
                    P(BATCH_AXIS))

            # --- record the node (same writes as the serial body) --------
            prow = lax.dynamic_index_in_dim(tli, l, axis=0, keepdims=False)
            parent_node = prow[0]
            child_depth = prow[1] + 1
            pn_safe = jnp.where(parent_node >= 0, parent_node, node)
            side = jnp.where(tni[pn_safe, 3] == ~l, 3, 4)
            tni = tni.at[pn_safe, side].set(node, mode="promise_in_bounds")
            tni = tni.at[node].set(
                jnp.stack([feat, thr, irow[2], ~l, ~new_leaf]),
                mode="promise_in_bounds")
            parent_g = frow[0] + frow[3]
            parent_h = frow[1] + frow[4]
            tnf = tnf.at[node].set(
                jnp.stack([sgain[l],
                           leaf_output(parent_g, parent_h,
                                       cfg.lambda_l1, cfg.lambda_l2),
                           tlf[l, 1]]),
                mode="promise_in_bounds")
            tlf = tlf.at[pair_lr].set(
                jnp.stack([jnp.stack([frow[6], frow[2]]),
                           jnp.stack([frow[7], frow[5]])]),
                unique_indices=True, mode="promise_in_bounds")
            tli = tli.at[pair_lr].set(
                jnp.broadcast_to(jnp.stack([node, child_depth]), (2, 2)),
                unique_indices=True, mode="promise_in_bounds")
            if cfg.has_categorical:
                tcat = tcat.at[node].set(cat_args[0],
                                         mode="promise_in_bounds")
                tcatb = tcatb.at[node].set(cat_args[1],
                                           mode="promise_in_bounds")

            # --- smaller-child histogram + parent subtraction ------------
            small_left = frow[2] <= frow[5]
            small_id = jnp.where(small_left, l, new_leaf)
            with jax.named_scope("histogram"):
                if use_fused:
                    hist_small = measure(row_leaf, small_id, gw, hw, cw,
                                         site="split")
                else:
                    mask = (row_leaf == small_id).astype(dtype)
                    hist_small = measure(row_leaf, small_id, gw * mask,
                                         hw * mask, cw * mask, site="split")
            hist_parent = lax.dynamic_index_in_dim(hist_store, l, axis=0,
                                                   keepdims=False)
            hist_large = hist_parent - hist_small
            hist2 = jnp.stack([hist_small, hist_large])
            pair_sl = jnp.where(small_left, pair_lr, pair_lr[::-1])
            hist_store = cstr(hist_store.at[pair_sl].set(
                hist2, unique_indices=True, mode="promise_in_bounds"),
                store_spec)

            fok_parent = lax.dynamic_index_in_dim(feat_ok, l, axis=0,
                                                  keepdims=False)
            lr3 = jnp.stack([lax.slice(frow, (0,), (3,)),
                             lax.slice(frow, (3,), (6,))])
            sl3 = jnp.where(small_left, lr3, lr3[::-1])
            res2, fok2 = jax.vmap(find, in_axes=(0, 0, 0, 0, None))(
                expand(hist2, sl3[:, 0], sl3[:, 1], sl3[:, 2]),
                sl3[:, 0], sl3[:, 1], sl3[:, 2], fok_parent)
            res2 = _depth_gate(res2, child_depth, cfg.max_depth)
            feat_ok = feat_ok.at[pair_sl].set(fok2 & fok_parent[None, :],
                                              unique_indices=True)
            rows_f32, rows_i32 = pool_rows(res2, 1)
            sgain = sgain.at[pair_sl].set(
                res2.gain, unique_indices=True, mode="promise_in_bounds")
            sf32 = sf32.at[pair_sl].set(
                rows_f32, unique_indices=True, mode="promise_in_bounds")
            si32 = si32.at[pair_sl].set(
                rows_i32, unique_indices=True, mode="promise_in_bounds")
            if cfg.has_categorical:
                scat = scat.at[pair_sl].set(
                    res2.is_cat, unique_indices=True,
                    mode="promise_in_bounds")
                scatb = scatb.at[pair_sl].set(
                    res2.cat_bins, unique_indices=True,
                    mode="promise_in_bounds")
            return (i + 1, row_leaf, sgain, sf32, si32, scat, scatb,
                    hist_store, feat_ok, tnf, tni, tlf, tli, tcat, tcatb)

        state = (jnp.asarray(0, jnp.int32), row_leaf0, sgain0, sf32_0,
                 si32_0, scat0, scatb0, hist_store0, feat_ok_store0,
                 tnf0, tni0, tlf0, tli0, tcat0, tcatb0)
        state = lax.while_loop(cond, body, state)
        (step, row_leaf, _, _, _, _, _, _, _,
         tnf, tni, tlf, tli, tcat, tcatb) = state
        tree = unpack_tree(step + 1, tni, tnf, tlf, tli, tcat, tcatb, cfg)
        return tree, row_leaf

    if pack_plan is None:
        def grow_tree(bins, gw, hw, cw, meta, feat_valid):
            return grow_impl(bins, bins, gw, hw, cw, meta, feat_valid)
        return jax.jit(scoped_program_name(grow_tree))

    def grow_tree_packed(bins, hist_bins, gw, hw, cw, meta, feat_valid):
        return grow_impl(bins, hist_bins, gw, hw, cw, meta, feat_valid)
    return jax.jit(scoped_program_name(grow_tree_packed))
