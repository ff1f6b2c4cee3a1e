"""Distributed tree-learner strategies over a jax device mesh.

Re-designs the reference's parallel tree learners
(``src/treelearner/*parallel*_tree_learner.cpp``) as shard_map programs:

* :class:`DataParallelStrategy` — rows sharded; local child histograms are
  ``lax.psum``-reduced over ICI, after which every device owns the global
  histograms and finds the identical best split.  This replaces the
  ReduceScatter + feature-ownership plan + best-split Allreduce of
  ``data_parallel_tree_learner.cpp:50-243`` (on TPU the full-histogram psum
  rides ICI; ownership bookkeeping buys nothing).
* :class:`FeatureParallelStrategy` — every device holds all rows (exactly the
  reference's feature-parallel contract, feature_parallel_tree_learner.cpp),
  histograms/scan run only on the device's feature slice, and the winning
  split is agreed with a gain-argmax sync (``SyncUpGlobalBestSplit``,
  parallel_tree_learner.h:184-207 → pmax + broadcast-from-winner).
* :class:`VotingStrategy` — data-parallel with PV-tree communication
  compression (voting_parallel_tree_learner.cpp): each shard votes its local
  top-k features, the global top-2k are selected from the gathered votes, and
  only those features' histograms are psum-reduced.

All strategies plug into ``make_grower`` and are wrapped in ``shard_map`` by
:func:`make_distributed_grower`.

Since the GSPMD rewrite (``parallel/gspmd.py``, docs/DISTRIBUTED.md) this
module is the FORCED A/B PARTNER (``parallel_impl=shardmap``), not the
default: the NamedSharding path lets the XLA partitioner insert and
overlap the same collectives this file issues by hand.  ``auto`` still
resolves here for multi-process training and for the voting learner
(PV-tree's vote compression is call-site collective machinery by nature).
:class:`DataParallelStrategy` also runs inside the GSPMD program: on a
mesh of row shards alone its ``gspmd_hist=fused`` island is the serial
grower with this strategy's psums (``parallel/gspmd.py``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..grower import (FeatureMeta, GrowerConfig, SerialStrategy, TreeArrays,
                      expand_bundle_hist, make_expand_maps, make_grower,
                      scoped_program_name)
from ..obs.collectives import note_collective
from ..ops.split import SplitResult, best_split, per_feature_best_gain


def _broadcast_from_winner(res: SplitResult, axis_name: str) -> SplitResult:
    """Gain-argmax sync across an axis (SyncUpGlobalBestSplit analogue):
    lowest-ranked shard with the maximal gain wins; its SplitResult is
    broadcast with a psum of masked fields."""
    # one accounting entry for the whole sync (its psums cover every
    # SplitResult field; pmax/pmin ride along at scalar cost)
    note_collective("psum", res, axis_name, site="best_split_sync")
    n_shards = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    gmax = lax.pmax(jnp.where(res.found, res.gain, -jnp.inf), axis_name)
    any_found = lax.pmax(res.found.astype(jnp.int32), axis_name) > 0
    winner = res.found & (res.gain == gmax)
    win_rank = lax.pmin(jnp.where(winner, rank, n_shards), axis_name)
    pick = (rank == win_rank) & any_found

    def bc(v):
        masked = jnp.where(pick, v, jnp.zeros_like(v))
        summed = lax.psum(masked.astype(jnp.float32)
                          if v.dtype == jnp.bool_ else masked, axis_name)
        return summed.astype(v.dtype) if v.dtype != jnp.bool_ \
            else summed > 0.5

    out = SplitResult(*[bc(v) for v in res])
    neg_inf = jnp.asarray(-jnp.inf, res.gain.dtype)
    return out._replace(
        found=any_found,
        gain=jnp.where(any_found, out.gain, neg_inf),
        feature=jnp.where(any_found, out.feature, -1))


class DataParallelStrategy(SerialStrategy):
    """Rows sharded over ``axis_name``; histograms psum-reduced.

    The smaller-child histogram measured by each shard over its local rows is
    psum-reduced (the ReduceScatter + ownership plan of
    ``data_parallel_tree_learner.cpp:148-163`` collapsed to one collective);
    the parent subtraction then happens on the already-global histograms, so
    the larger child is never communicated — exactly the reference's
    guarantee (``:246-252``)."""

    def __init__(self, cfg: GrowerConfig, axis_name: str = "data"):
        super().__init__(cfg)
        self.axis = axis_name

    def reduce_hist(self, hist):
        note_collective("psum", hist, self.axis, site="reduce_hist")
        # the one cross-chip step of a split, named for the trace
        with jax.named_scope("hist_reduce"):
            return lax.psum(hist, self.axis)

    def reduce_scalar(self, x):
        note_collective("psum", x, self.axis, site="reduce_scalar")
        return lax.psum(x, self.axis)

    def row_shards(self):
        return lax.axis_size(self.axis)


class FeatureParallelStrategy(SerialStrategy):
    """All rows on every device; features sliced per shard.

    The physical column count must be padded to a multiple of the shard
    count (pad features are masked via feat_valid=False / absent from the
    bundle maps).  With EFB bundles the shard owns a window of physical
    columns and expands only the logical features living in that window
    (``make_expand_maps`` with a column window); without bundles the
    logical metadata is sliced directly.
    """

    def __init__(self, cfg: GrowerConfig, axis_name: str = "feature",
                 num_shards: int = 1):
        super().__init__(cfg)
        self.axis = axis_name
        self.num_shards = num_shards

    def setup(self, bins, meta: FeatureMeta, feat_valid, num_cols: int):
        n, f = bins.shape
        fl = f // self.num_shards
        ax = lax.axis_index(self.axis)
        start = ax * fl
        bins_local = lax.dynamic_slice(bins, (0, start), (n, fl))
        if meta.col is not None:
            # bundled: logical meta stays global; expansion maps are local
            maps = make_expand_maps(meta, self.cfg.max_bin, fl,
                                    col_start=start)
            return (meta, feat_valid, bins_local, None, None, start, maps)
        meta_local = FeatureMeta(
            num_bin=lax.dynamic_slice(meta.num_bin, (start,), (fl,)),
            missing_type=lax.dynamic_slice(meta.missing_type, (start,), (fl,)),
            default_bin=lax.dynamic_slice(meta.default_bin, (start,), (fl,)),
            is_categorical=lax.dynamic_slice(
                meta.is_categorical, (start,), (fl,)))
        fv_local = lax.dynamic_slice(feat_valid, (start,), (fl,))
        return (meta, feat_valid, bins_local, meta_local, fv_local, start,
                None)

    def hist_bins(self, ctx, bins):
        return ctx[2]

    def expand(self, ctx, hist, pg, ph, pc):
        # the local physical histograms into the (global) logical feature
        # space; features outside this shard's window are zeroed and
        # masked, so the global numbering needs no feature_base shift
        return expand_bundle_hist(hist, pg, ph, pc, ctx[6])

    def find(self, ctx, hist_child, pg, ph, pc, feat_ok):
        meta, feat_valid, _, meta_local, fv_local, start, maps = ctx
        if maps is not None:
            res, ok = best_split(hist_child, pg, ph, pc, meta.num_bin,
                                 meta.missing_type, meta.default_bin,
                                 feat_valid & maps[4] & feat_ok,
                                 self.cfg.split_config(),
                                 is_cat=meta.is_categorical,
                                 with_feat_ok=True)
            ok_global = ok & maps[4]
        else:
            fok_local = lax.dynamic_slice(feat_ok, (start,),
                                          (fv_local.shape[0],))
            # feature_base shifts to global numbering before the argmax sync
            res, ok = best_split(hist_child, pg, ph, pc, meta_local.num_bin,
                                 meta_local.missing_type,
                                 meta_local.default_bin,
                                 fv_local & fok_local,
                                 self.cfg.split_config(),
                                 feature_base=start,
                                 is_cat=meta_local.is_categorical,
                                 with_feat_ok=True)
            ok_global = lax.dynamic_update_slice(
                jnp.zeros_like(feat_ok), ok, (start,))
        # every shard owns a disjoint feature window: OR across shards
        # rebuilds the full is_splittable vector identically everywhere
        ok_i32 = ok_global.astype(jnp.int32)
        note_collective("psum", ok_i32, self.axis, site="feat_ok_sync")
        ok_global = lax.psum(ok_i32, self.axis) > 0
        return _broadcast_from_winner(res, self.axis), ok_global


class DataFeatureStrategy(FeatureParallelStrategy):
    """2-D hybrid: rows sharded over the ``data`` mesh axis, the split
    scan sharded over the ``feature`` axis.

    The composition the reference leaves to its template parameter
    (``data_parallel_tree_learner.cpp:255-256`` instantiates
    DataParallel<GPUTreeLearner> etc. but never ships a data x feature
    product): each (d, f) device histograms ITS row shard over ITS
    column slice; a psum over ``data`` makes the slice's histograms
    global, and the feature-axis argmax sync of the parent class agrees
    on the winning split.  Row routing happens on the data shard,
    replicated across the feature axis."""

    def __init__(self, cfg: GrowerConfig, data_axis: str = "data",
                 feat_axis: str = "feature", num_feat_shards: int = 1):
        super().__init__(cfg, feat_axis, num_feat_shards)
        self.data_axis = data_axis

    def reduce_hist(self, hist):
        note_collective("psum", hist, self.data_axis, site="reduce_hist")
        return lax.psum(hist, self.data_axis)

    def reduce_scalar(self, x):
        note_collective("psum", x, self.data_axis, site="reduce_scalar")
        return lax.psum(x, self.data_axis)

    def row_shards(self):
        return lax.axis_size(self.data_axis)


class VotingStrategy(SerialStrategy):
    """Data-parallel with top-k vote compression (PV-tree).

    ``hist`` returns the LOCAL histograms; ``find`` votes local top-k
    features, selects the global top-2k from the gathered votes, psums only
    the selected slices, and finds the best split on the reduced set.
    """

    def __init__(self, cfg: GrowerConfig, axis_name: str = "data",
                 top_k: int = 20, num_shards: int = 1):
        super().__init__(cfg)
        self.axis = axis_name
        self.top_k = top_k
        # the LOCAL vote scan sees ~1/S of every leaf's rows, so the data /
        # hessian gates must shrink with the shard count or features stop
        # voting long before the leaf is globally unsplittable
        # (voting_parallel_tree_learner.cpp:54-56 divides both by
        # num_machines; integer division for the count, float for the
        # hessian).  The GLOBAL find on the psum-reduced histograms keeps
        # the unscaled config.
        self.local_scfg = cfg.split_config()._replace(
            min_data_in_leaf=cfg.min_data_in_leaf // num_shards,
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf / num_shards)

    def reduce_scalar(self, x):
        note_collective("psum", x, self.axis, site="reduce_scalar")
        return lax.psum(x, self.axis)

    def row_shards(self):
        return lax.axis_size(self.axis)

    # reduce_hist stays identity: histograms remain LOCAL and only the
    # voted feature slices are psum-reduced inside ``find`` (PV-tree's
    # communication compression); the parent-minus-smaller subtraction in
    # the grower is therefore performed in each shard's local space.

    def expand(self, ctx, hist, pg, ph, pc):
        # EFB: expand the LOCAL physical histograms with LOCAL parent
        # sums (every row lands in exactly one bin of physical column 0,
        # so its bin sums are the local leaf totals), not the global
        # ``pg``/``ph``/``pc``.  Expansion is linear in the histogram
        # given additive parents, so the psum of locally-expanded slices
        # in ``find`` equals the expansion of the psum-reduced histogram.
        pl = hist[..., 0, :, :].sum(axis=-2)             # [..., 3] local parent
        return expand_bundle_hist(hist, pl[..., 0], pl[..., 1], pl[..., 2],
                                  ctx[2])

    def find(self, ctx, hist_child, pg, ph, pc, feat_ok):
        # the voting scan runs on a SLICED feature subset, so the serial
        # strategy's full-width fused ctx does not apply (best_split
        # derives the masks inline on the fused path)
        meta, feat_valid, _, _ = ctx
        feat_valid = feat_valid & feat_ok
        scfg = self.cfg.split_config()
        f = hist_child.shape[0]
        k = min(self.top_k, f)
        # local votes from local histograms with LOCAL parent sums (PV-tree
        # votes are defined on each worker's own leaf statistics,
        # voting_parallel_tree_learner.cpp:255-330); the per-feature bin sums
        # [F, 1] broadcast through the candidate arithmetic
        pg_loc = hist_child[:, :, 0].sum(axis=1, keepdims=True)
        ph_loc = hist_child[:, :, 1].sum(axis=1, keepdims=True)
        pc_loc = hist_child[:, :, 2].sum(axis=1, keepdims=True)
        local_gain = per_feature_best_gain(
            hist_child, pg_loc, ph_loc, pc_loc, meta.num_bin,
            meta.missing_type, meta.default_bin, feat_valid, self.local_scfg,
            is_cat=meta.is_categorical)
        _, local_top = lax.top_k(local_gain, k)
        votes_local = jnp.stack([local_gain[local_top],
                                 local_top.astype(local_gain.dtype)], axis=-1)
        note_collective("all_gather", votes_local, self.axis, site="votes")
        gathered = lax.all_gather(votes_local, self.axis)    # [S, k, 2]
        votes = gathered.reshape(-1, 2)
        # global top-2k by voted gain (GlobalVoting :165-195); duplicate
        # feature ids are harmless (redundant reduced slices)
        _, top_idx = lax.top_k(votes[:, 0], min(2 * k, votes.shape[0]))
        sel = votes[top_idx, 1].astype(jnp.int32)        # [2k]
        # reduce only the selected features' histograms (CopyLocalHistogram)
        hist_voted = hist_child[sel]
        note_collective("psum", hist_voted, self.axis, site="voted_hist")
        hist_sel = lax.psum(hist_voted, self.axis)       # [2k, B, 3]
        res, sel_ok = best_split(hist_sel, pg, ph, pc, meta.num_bin[sel],
                                 meta.missing_type[sel],
                                 meta.default_bin[sel],
                                 feat_valid[sel], scfg,
                                 is_cat=meta.is_categorical[sel],
                                 with_feat_ok=True)
        res = res._replace(feature=jnp.where(res.found, sel[jnp.clip(
            res.feature, 0, sel.shape[0] - 1)], -1))
        # is_splittable only from the GLOBALLY-reduced scan of the voted
        # features; features this round never examined globally stay
        # splittable.  (Local gains use per-shard counts, so deriving the
        # flag from them would freeze subtrees whose per-shard row counts
        # fall under min_data_in_leaf even though the leaf is globally
        # splittable.)  sel is identical on every shard, so the state
        # stays shard-consistent without a collective.
        ok = jnp.ones_like(feat_ok).at[sel].set(sel_ok)
        return res, ok


def make_distributed_grower(cfg: GrowerConfig, mesh: Mesh,
                            tree_learner: str = "data",
                            top_k: int = 20, bundled: bool = False,
                            pack_plan=None, name: Optional[str] = None):
    """shard_map-wrapped grow function for a 1-D mesh.

    Returns ``fn(bins, gw, hw, cw, meta, feat_valid) -> (TreeArrays, row_leaf)``
    operating on global (host-level) arrays.  Rows (data/voting) or the
    feature scan (feature) are sharded over the mesh axis.  ``bundled``
    states whether the FeatureMeta carries EFB col/offset arrays (their
    specs must match the pytree).  ``pack_plan`` (data/packing.py) adds a
    second positional arg — the nibble-packed histogram matrix, sharded
    like ``bins`` (data/voting only; the feature learner's column
    slicing is incompatible with shared bytes and boosting gates it off).
    ``name`` names the jitted program (``scoped_program_name``): the GSPMD
    grower's data-parallel island is this program under ``grow_tree``.
    """
    axis = mesh.axis_names[0]
    n_shards = mesh.devices.size
    if tree_learner == "data":
        strategy = DataParallelStrategy(cfg, axis)
        in_row = P(axis)
        row_out = P(axis)
    elif tree_learner == "voting":
        strategy = VotingStrategy(cfg, axis, top_k, num_shards=n_shards)
        in_row = P(axis)
        row_out = P(axis)
    elif tree_learner == "feature":
        strategy = FeatureParallelStrategy(cfg, axis, n_shards)
        in_row = P()
        row_out = P()
    elif tree_learner == "data_feature":
        if len(mesh.axis_names) != 2:
            raise ValueError("data_feature needs a 2-D (data x feature) mesh")
        da, fa = mesh.axis_names
        strategy = DataFeatureStrategy(cfg, da, fa,
                                       int(mesh.shape[fa]))
        in_row = P(da)
        row_out = P(da)
    else:
        raise ValueError(f"unknown tree_learner {tree_learner}")

    if pack_plan is not None and tree_learner in ("feature", "data_feature"):
        raise ValueError("bin packing is incompatible with the "
                         "feature-parallel column slicing")
    grow = make_grower(cfg, strategy, pack_plan=pack_plan)
    if tree_learner in ("data", "voting"):
        bins_spec = P(axis, None)
    elif tree_learner == "data_feature":
        bins_spec = P(mesh.axis_names[0], None)   # rows sharded, cols whole
    else:
        bins_spec = P()
    meta_spec = (FeatureMeta(P(), P(), P(), P(), P(), P()) if bundled
                 else FeatureMeta(P(), P(), P(), P()))
    tree_spec = TreeArrays(*([P()] * len(TreeArrays._fields)))
    hist_spec = (bins_spec,) if pack_plan is not None else ()

    fn = shard_map(grow, mesh=mesh,
                   in_specs=(bins_spec, *hist_spec, in_row, in_row, in_row,
                             meta_spec, P()),
                   out_specs=(tree_spec, row_out),
                   check_vma=False)
    if name is None:
        return jax.jit(fn)

    def program(*args):
        return fn(*args)
    program.__name__ = name
    return jax.jit(scoped_program_name(program))
