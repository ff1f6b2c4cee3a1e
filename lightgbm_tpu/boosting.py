"""Boosting drivers: GBDT, DART, GOSS, RF.

The reference's ``Boosting`` hierarchy (``src/boosting/``, factory
``boosting.cpp:29-76``) becomes Python classes driving the jitted tree grower:

* :class:`GBDT` — ``gbdt.cpp:67-581``: boost-from-average init tree, gradient
  computation, bagging, per-class tree training, shrinkage, score updates,
  rollback, model (de)serialization in the reference text format;
* :class:`DART` — ``dart.hpp:86-194`` drop/normalize arithmetic;
* :class:`GOSS` — ``goss.hpp:86-137`` gradient-based one-side sampling
  (vectorized: exact top-k threshold + Bernoulli keep of the rest);
* :class:`RF`   — ``rf.hpp:18-213`` bagged random forest with averaged output.

Training scores live on device; the O(N) train-score update uses the grower's
``row_leaf`` partition (the reference's ``ScoreUpdater`` + ``DataPartition``
trick), valid scores use jitted binned traversal.
"""
from __future__ import annotations

import copy
import io
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .data.dataset import TrainingData
from .grower import (FeatureMeta, GrowerConfig, StreamedGrower, layout_width,
                     make_grower)
from .metrics import Metric, create_metric, default_metric_for_objective
from .obs import collectives as obs_collectives
from .obs import devprof as obs_devprof
from .obs import flight as obs_flight
from .obs import memory as obs_memory
from .obs import metrics as obs_metrics
from .obs import model_quality as obs_model_quality
from .obs import trace as obs_trace
from .obs.counters import counters as obs_counters
from .ops.histogram import on_tpu
from .objectives import Objective, create_objective, parse_objective_string
from .predictor import (Predictor, predict_binned_leaf, tree_scores_binned,
                        trees_scores_binned)
from .tree import Tree
from .utils import faults as faults_mod
from .utils import log
from .utils.random import make_rng, sample_k
from .utils.timer import PhaseTimers


class NonFiniteError(RuntimeError):
    """A gradient/hessian/leaf value went non-finite and the configured
    ``nonfinite_policy`` could not (or was asked not to) recover."""


# the most leaves whose values a row picks by selects (below); above it, a
# gather.  On a v5e at 10,500,000 rows (scripts/probe_score_update.py; ms
# a call, select / gather, and the select's compile): 31 leaves 0.32 /
# 0.25, 0.8 s; 255 leaves 0.88 / 86.4, 4.4 s; 1023 leaves 3.80 / 56.1,
# 10.6 s; 4095 leaves 15.7 / 75.1, 41.5 s and 226 MB of temporaries.  The
# select saves a tree 52 ms at 1023 leaves and 59 at 4095, so its extra
# compile is repaid in 200 trees at 1023 and in 700 at 4095.
SELECT_MAX_LEAVES = 1023


def leaf_value_of_rows(leaf_values, row_leaf):
    """``leaf_values[row_leaf]``, bit for bit (NaN, infinities and -0.0
    included), for every ``row_leaf`` in ``[0, L)``.

    Up to :data:`SELECT_MAX_LEAVES` leaves, a binary tree of selects on the
    leaf id's bits: the leaves padded with the last one to a power of two,
    each level halves them by one bit test.  That is L - 1 selects and
    log2 L tests a row, elementwise, so ONE loop fusion over the rows,
    with no lookup by N indices (on the v5e a gather of one element an
    index pays 6-9 ns: 86 ms a tree at 10.5M rows).  The form is chosen
    by the leaf count, the one thing that makes the select dearer, and
    counted once a trace as ``score_update_dispatch{impl, leaves}``."""
    L = leaf_values.shape[0]
    if L > SELECT_MAX_LEAVES:
        obs_counters.inc("score_update_dispatch", impl="gather", leaves=L)
        return leaf_values[row_leaf]
    obs_counters.inc("score_update_dispatch", impl="select", leaves=L)
    vals = [leaf_values[min(i, L - 1)]
            for i in range(1 << max(1, (L - 1).bit_length()))]
    bit = 1
    while len(vals) > 1:
        odd = (row_leaf & bit) != 0
        vals = [jnp.where(odd, hi, lo) for lo, hi in zip(vals[::2], vals[1::2])]
        bit <<= 1
    return vals[0]


@jax.jit
def _update_score(scores_k, leaf_values, row_leaf, lr):
    """Add ``lr`` times each row's leaf value to its score, the rows placed
    by the grower's ``row_leaf``, under the ``score_update`` scope (entered
    inside the traced function, so it is in the HLO whatever the cache
    holds)."""
    with jax.named_scope("score_update"):
        return scores_k + lr * leaf_value_of_rows(leaf_values, row_leaf)


@jax.jit
def _route_update_score(scores_k, bins, split_feature, threshold_bin,
                        default_left, left_child, right_child, feat_info,
                        is_cat, cat_bins, leaf_values, lr):
    """Route rows through a fresh device-side tree and add its (shrunk) leaf
    values to their scores, as one program under the ``score_update`` scope
    (entered inside the traced function, so it is in the HLO whatever the
    cache holds)."""
    with jax.named_scope("score_update"):
        row_leaf = predict_binned_leaf(
            bins, split_feature, threshold_bin, default_left, left_child,
            right_child, feat_info, is_cat, cat_bins)
        return scores_k + lr * leaf_value_of_rows(leaf_values, row_leaf)


class _ValidSet:
    def __init__(self, data: TrainingData, name: str, num_class: int,
                 metrics: List[Metric]):
        self.data = data
        self.name = name
        self.bins = jnp.asarray(data.binned)
        self.metrics = metrics
        n = data.num_data
        self.scores = jnp.zeros((num_class, n), jnp.float32)
        if data.metadata.init_score is not None:
            init = np.asarray(data.metadata.init_score, np.float32)
            self.scores = self.scores + init.reshape(num_class, n)


class GBDT:
    """Gradient Boosting Decision Tree driver (gbdt.cpp)."""

    average_output = False
    sub_model_name = "tree"
    allow_boost_from_average = True
    # DART reads/mutates prior trees every iteration and RF feeds host
    # gradients; both stay on the synchronous path
    pipeline_supported = True
    # nonfinite_policy=rollback discards a poisoned iteration via the
    # rollback arithmetic; DART's drop/normalize bookkeeping cannot be
    # partially unwound, so it escalates to raise instead
    rollback_safe = True

    def __init__(self, config: Config, train_set: Optional[TrainingData] = None,
                 objective: Optional[Objective] = None):
        self.config = config
        self.train_set = train_set
        self.objective = objective
        # tree-materialization pipeline state (see train_one_iter): grown
        # trees wait in _pending as device TreeArrays and drain into _models
        # a few iterations late through ONE batched transfer each
        self._pending: List[dict] = []
        self._pipeline = False
        self._pipeline_depth = 3
        self._stopped_no_split = False
        self._iter_had_split = False
        # non-finite guard bookkeeping (docs/ROBUSTNESS.md): one structured
        # event per tripped iteration; a second trip at the SAME iteration
        # under rollback means the non-finite source is persistent
        self._nf_policy = config.nonfinite_policy
        self._nf_event_iter: Optional[int] = None
        self._nf_rolled_iter: Optional[int] = None
        self._score_stash = None   # (iter, scores, [valid scores]) refs
        # serving caches, both invalidated together whenever the stored
        # trees change other than by appending (rollback, merge, DART
        # normalize, leaf edits): the native C++ predictor and the SoA
        # microbatch engine (lightgbm_tpu.inference / docs/SERVING.md)
        self._native_pred = None
        self._pred_engine = None
        self._pred_engine_ntrees = -1
        # training-set bin distribution for the serving drift monitor
        # (obs/model_quality.py): computed lazily at save when the plane
        # is armed, or parsed back from a loaded model file
        self.feature_distribution = None
        self.models: List[Tree] = []
        self.timers = PhaseTimers()   # TIMETAG analogue (gbdt.cpp:22-64)
        self.iter_ = 0
        self._last_iter_leaves = 0
        self._hbm_gauged = False   # allocator peaks recorded after tree 1
        self.num_init_iteration = 0
        self.boost_from_average_ = False
        self.best_iteration = -1
        self.eval_history: Dict[str, Dict[str, List[float]]] = {}
        self.valid_sets: List[_ValidSet] = []
        self.train_metrics: List[Metric] = []
        self.num_class = objective.num_tree_per_iteration if objective else 1
        self.label_idx = 0
        self.feature_names: List[str] = (train_set.feature_names if train_set
                                         else [])
        self.max_feature_idx = (train_set.num_total_features - 1 if train_set
                                else 0)
        if train_set is not None:
            self._setup_device(train_set)

    # ------------------------------------------------- pipelined tree pulling
    #
    # ``models`` drains pending device-side trees on every read, so every
    # consumer (save/predict/importance/rollback/bindings) always sees the
    # complete, ordered list; only the training hot loop uses ``_models`` /
    # ``_pending`` directly.

    @property
    def models(self) -> List[Tree]:
        if self._pending:
            self._drain_pending()
        return self._models

    @models.setter
    def models(self, value) -> None:
        if getattr(self, "_pending", None):
            self._drain_pending()
        self._models = list(value)

    def _drain_pending(self, keep_iters: int = 0) -> None:
        """Materialize pending trees (FIFO) until at most ``keep_iters``
        iteration groups remain.  Each materialization is one batched
        ``jax.device_get`` whose transfer was started asynchronously at
        dispatch time, so by the time a record is ``keep_iters`` iterations
        old the bytes are normally already on host."""
        keep = keep_iters * self.num_class
        if self._stopped_no_split:
            keep = 0            # everything still pending must be reverted
        while self._pending and len(self._pending) > keep:
            rec = self._pending.pop(0)
            it = int(rec["iter"])
            # the non-finite flags ride the SAME batched device_get the
            # drain already does — no extra host<->device synchronization
            with self.timers.phase("tree.wait", iteration=it):
                host, nf_ok, gh_ok = jax.device_get(
                    (rec["arrays"], rec["nf_ok"], rec["gh_ok"]))
            if not bool(nf_ok):
                self._nonfinite_at_drain(it, bool(gh_ok))
            with self.timers.phase("tree.host", iteration=it):
                tree = Tree.from_arrays(host, self.train_set.used_features,
                                        self.train_set.bin_mappers,
                                        self._num_bin_host)
                tree.shrink(rec["lr"])
            if self._stopped_no_split:
                # trained past a (lately discovered) no-split iteration:
                # discard, undoing any score contribution it made
                self._revert_tree_scores(rec["k"], tree)
                continue
            self._models.append(tree)
            # split audit (obs/model_quality.py): fold the freshly
            # materialized host arrays — data this drain fetched anyway,
            # so the armed plane adds zero device syncs (pinned)
            obs_model_quality.get_tracker().observe_tree(
                it, len(self._models) - 1, tree)
            if tree.num_leaves > 1:
                self._iter_had_split = True
            if rec["k"] == self.num_class - 1:
                if not self._iter_had_split:
                    # the reference stops at the first iteration whose trees
                    # cannot split (gbdt.cpp:541-556); reproduce its exact
                    # final state — drop this iteration's trees and rewind
                    log.warning("Stopped training because there are no more "
                                "leaves that meet the split requirements")
                    for _ in range(self.num_class):
                        self._models.pop()
                    self._stopped_no_split = True
                    self.iter_ = rec["iter"]
                    keep = 0    # later pending trees are all discarded
                self._iter_had_split = False

    def _revert_tree_scores(self, k: int, tree: Tree) -> None:
        """Subtract a discarded tree's contribution (rollback_one_iter's
        arithmetic) from train and valid scores."""
        if tree.num_leaves <= 1:
            return
        tree.shrink(-1.0)
        self.scores = self.scores.at[k].add(self._train_tree_score(tree))
        for vs in self.valid_sets:
            vs.scores = vs.scores.at[k].add(tree_scores_binned(
                vs.bins, tree, self.used_feature_index, self.feat_info,
                self.train_set.bin_mappers))

    # ------------------------------------------------------------------ setup

    def _setup_device(self, train: TrainingData) -> None:
        with obs_trace.phase("setup.device"):
            self._setup_device_inner(train)

    def _setup_device_inner(self, train: TrainingData) -> None:
        cfg = self.config
        # host-side for now; _setup_grower owns device placement (multi-
        # process mode shards this globally instead of uploading it whole)
        self.bins = train.binned
        fm = train.feature_meta()
        bundled = "col" in fm
        self.meta = FeatureMeta(
            num_bin=jnp.asarray(fm["num_bin"]),
            missing_type=jnp.asarray(fm["missing_type"]),
            default_bin=jnp.asarray(fm["default_bin"]),
            is_categorical=jnp.asarray(fm["is_categorical"]),
            col=jnp.asarray(fm["col"]) if bundled else None,
            offset=jnp.asarray(fm["offset"]) if bundled else None)
        e = len(fm["num_bin"])
        col = fm["col"] if bundled else np.arange(e, dtype=np.int32)
        off = fm["offset"] if bundled else np.full(e, -1, np.int32)
        self.feat_info = jnp.stack(
            [jnp.asarray(fm["num_bin"]), jnp.asarray(fm["missing_type"]),
             jnp.asarray(fm["default_bin"]), jnp.asarray(col),
             jnp.asarray(off)], axis=1)
        self.used_feature_index = {f: i for i, f in enumerate(train.used_features)}
        self._num_bin_host = np.asarray(fm["num_bin"])
        self.num_data = train.num_data
        n = self.num_data

        with obs_trace.phase("setup.grower"):
            self._setup_grower(cfg, train, fm)
        # rollback must act BEFORE the next iteration trains on poisoned
        # scores, so it forces synchronous tree materialization; the cheap
        # default (raise) keeps the pipeline and detects at drain time
        self._pipeline = (cfg.pipeline_trees and self.pipeline_supported
                          and not self._multiproc
                          and cfg.nonfinite_policy != "rollback")
        if (cfg.pipeline_trees and self.pipeline_supported
                and not self._multiproc and not self._pipeline):
            log.info("nonfinite_policy=rollback forces synchronous tree "
                     "materialization (pipeline_trees disabled)")

        with obs_trace.phase("objective.init"):
            self.objective.init(train.metadata, n)
        self.num_class = self.objective.num_tree_per_iteration
        objective = self.objective

        # scopes are entered INSIDE the traced functions: one around the
        # call of a jitted function is baked into the HLO or not by cache
        # luck.  ``objective`` and ``score_update`` name the loop's own
        # device programs in a capture, beside the grower's scopes.
        def get_gradients(scores):
            with jax.named_scope("objective"):
                return objective.get_gradients(scores)

        self._grad_fn = (jax.jit(get_gradients)
                         if self._row_shards(1) is None
                         else self._sharded_grad_fn())
        self.scores = jnp.zeros((self.num_class, n), jnp.float32,
                                device=self._row_shards(2))
        self._has_init_score = train.metadata.init_score is not None
        if self._has_init_score:
            init = np.asarray(train.metadata.init_score, np.float32)
            self.scores = self.scores + init.reshape(self.num_class, n)
        self._feat_valid_base = np.ones(len(fm["is_categorical"]), dtype=bool)
        self._bag_weight = self._row_ones()
        self._bag_cnt = self._row_ones()
        self._subset_state = None  # (bins[M,F], idx[M], w[M], cnt[M], hist)
        self._bag_rng = make_rng(cfg.bagging_seed)
        self._feat_rng = make_rng(cfg.feature_fraction_seed)

        metric_names = cfg.metric or [default_metric_for_objective(cfg.objective)]
        self.metric_names = metric_names
        self.train_metrics = self._make_metrics(train)
        self._update_score = _update_score

        # device-memory observability (obs/memory.py): owner tags for the
        # live-array census (weakly held — never keeps this booster alive)
        # and the pre-compile HBM pre-flight.  Runs BEFORE the first grow
        # call compiles anything, so a shape that cannot fit fails here in
        # milliseconds instead of minutes into a capture window.
        obs_memory.register_residents(self._memory_residents)
        # live metrics source (obs/metrics.py): phase-timer families +
        # iteration gauge for the /metrics scrape (weakly held, like the
        # census providers)
        obs_metrics.register_source(self._metrics_samples)
        self._memory_preflight(cfg, train)

    def _row_shards(self, ndim: int):
        """Where a per-row array of ``ndim`` axes (rows last) lives: the
        GSPMD mesh's row shards, evenly, where one process holds the mesh
        and the rows split evenly over its ``batch`` axis; None (the
        default device) elsewhere.  Scores, gradients, the objective's
        per-row arrays, the bagging vectors and the grower's row -> leaf
        map all live there, so no array of N rows is whole on one device."""
        if self._gspmd_mesh is None or self._multiproc or self._row_pad:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .parallel.mesh import BATCH_AXIS
        return NamedSharding(self._gspmd_mesh,
                             P(*([None] * (ndim - 1)), BATCH_AXIS))

    def _row_ones(self) -> jnp.ndarray:
        return jnp.ones((self.num_data,), jnp.float32,
                        device=self._row_shards(1))

    def _sharded_grad_fn(self):
        """The gradient program with the objective's per-row arrays
        (``Objective.row_arrays``) on the row shards, handed to it as
        ARGUMENTS: jit bakes an array a function closes over into the
        program as a constant, which every device then holds whole."""
        placed = {k: jax.device_put(v, self._row_shards(v.ndim))
                  for k, v in self.objective.row_arrays().items()}
        # the objective keeps the placed arrays: the whole copies die
        objective = self.objective = self.objective.with_row_arrays(placed)

        def get_gradients(scores, arrays):
            with jax.named_scope("objective"):
                return objective.with_row_arrays(arrays).get_gradients(scores)

        grads = jax.jit(get_gradients)
        return lambda scores: grads(scores, placed)

    def _metrics_samples(self) -> list:
        """Live ``/metrics`` samples of this booster: per-phase
        steady-state means (first, compile-inclusive firing excluded — the
        obs/report.py compile⚠ rule applied to the live view) plus the
        iteration gauge.  Pure host-side dict reads; snapshot via ``list``
        so a concurrent scrape never races the training thread's inserts."""
        out = [("train_iterations", {}, float(self.iter_), "gauge")]
        # phase_seconds / phase_calls come from the process-wide registry
        # (obs/trace.phase counts them there, once)
        for name, mean in self.timers.steady_means().items():
            out.append(("phase_steady_ms", {"phase": name},
                        float(mean) * 1e3, "gauge"))
        return out

    def _memory_residents(self) -> Dict[str, list]:
        """Owner-tagged persistent device arrays for the live census
        (obs/memory.live_census): binned matrix, packed histogram copy,
        scores (+ the rollback stash), bagging vectors, subset gather
        buffers, valid-set arrays, pending pipelined trees."""
        res: Dict[str, list] = {
            # streamed: the binned matrix lives on HOST; its in-flight
            # device blocks are transient and tracked by the stream
            # counters, not the resident census
            "binned": ([] if self._stream_store is not None
                       else [self.bins]),
            "scores": [self.scores],
            "bagging": [self._bag_weight, self._bag_cnt],
        }
        if self._hist_bins is not None:
            res["packed"] = [self._hist_bins]
        if self.objective is not None:
            # labels + the objective's derived per-row device vectors
            # (binary: label sign/weight; ranking: query maps, gains, ...)
            res["objective"] = [v for v in vars(self.objective).values()
                                if hasattr(v, "nbytes")
                                and hasattr(v, "dtype")]
        stash = getattr(self, "_score_stash", None)
        if stash is not None:
            res["scores"] = res["scores"] + [stash[1]] + list(stash[2])
        if self._subset_state is not None:
            res["subset_gather"] = [a for a in self._subset_state
                                    if a is not None]
        if self.valid_sets:
            res["valid"] = [a for vs in self.valid_sets
                            for a in (vs.bins, vs.scores)]
        if self._pending:
            res["pending_trees"] = [a for rec in self._pending
                                    for a in jax.tree.leaves(rec["arrays"])]
        return res

    def _memory_preflight(self, cfg: Config, train: TrainingData) -> None:
        """Predict the training's peak device bytes from the constructed
        shapes and compare against the device capacity / ``hbm_budget``
        (obs/memory.preflight) before the grower compiles."""
        plan = self._pack_plan
        gplan = self._gspmd_plan
        stream = self._stream_store
        ncols = (stream.num_cols if stream is not None
                 else int(np.shape(self.bins)[1]))
        bin_bytes = (stream.dtype.itemsize if stream is not None
                     else self.bins.dtype.itemsize)
        pred = obs_memory.predict_hbm(
            rows=self.num_data,
            features=ncols,
            bins=self.grower_cfg.max_bin,
            leaves=self.grower_cfg.num_leaves,
            num_class=self.num_class,
            bin_bytes=int(bin_bytes),
            stream_chunk_rows=(stream.chunk_rows
                               if stream is not None else 0),
            packed_cols=(plan.num_storage_cols if plan is not None else 0),
            valid_rows=sum(vs.data.num_data for vs in self.valid_sets),
            bucket_min_log2=self.grower_cfg.bucket_min_log2,
            # GSPMD: the pre-flight judges the PER-DEVICE peak the planner
            # already sized the mesh for (docs/DISTRIBUTED.md)
            data_shards=(gplan.data if gplan is not None else 1),
            feature_shards=(gplan.feature if gplan is not None else 1),
            block_shard_bins=(gplan.block_shard_bins
                              if gplan is not None else False),
            gspmd_fused=(gplan is not None
                         and self.grower_cfg.hist_method == "fused"))
        self.memory_prediction = pred
        obs_memory.preflight(
            pred, hbm_budget=cfg.hbm_budget,
            context=f"{self.num_data} rows x {ncols} cols, "
                    f"{self.grower_cfg.num_leaves} leaves, "
                    f"{self.grower_cfg.max_bin} bins"
                    + (f", streamed in {stream.chunk_rows}-row blocks"
                       if stream is not None else ""))

    def _grower_config(self, cfg: Config, train: TrainingData, fm,
                       hist_method: str) -> GrowerConfig:
        return GrowerConfig(
            num_leaves=cfg.num_leaves,
            max_depth=cfg.max_depth,
            min_data_in_leaf=cfg.min_data_in_leaf,
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
            lambda_l1=cfg.lambda_l1,
            lambda_l2=cfg.lambda_l2,
            min_gain_to_split=cfg.min_gain_to_split,
            max_bin=layout_width(train.max_num_bin()),
            hist_method=hist_method,
            row_tile=cfg.pallas_row_tile,
            bucket_min_log2=cfg.pallas_bucket_min_log2,
            has_categorical=bool(np.asarray(fm["is_categorical"]).any()),
            has_missing=bool((np.asarray(fm["missing_type"]) != 0).any()),
            max_cat_threshold=cfg.max_cat_threshold,
            max_cat_group=cfg.max_cat_group,
            cat_smooth_ratio=cfg.cat_smooth_ratio,
            min_cat_smooth=cfg.min_cat_smooth,
            max_cat_smooth=cfg.max_cat_smooth,
            # off-TPU a Pallas kernel can only run interpreted; on a TPU
            # backend it compiles or raises
            hist_interpret=not on_tpu(),
            split_find=cfg.split_find)

    def _setup_grower(self, cfg: Config, train: TrainingData, fm) -> None:
        """Select the tree learner (CreateTreeLearner analogue):
        serial on one device; data/feature/voting over the device mesh.

        Multi-process (multi-host) mode: each process holds its OWN row
        partition (the reference's pre-partitioned parallel learning,
        ``docs/Parallel-Learning-Guide.md``); the binned matrix becomes one
        global jax.Array row-sharded across all processes' devices, and
        per-tree gradient vectors are assembled the same way."""
        self._row_pad = 0
        self._feat_pad = 0
        self._multiproc = False
        self._local_bins_cache = None
        self._pack_plan = None
        self._hist_bins = None
        self._gspmd_mesh = None
        self._gspmd_plan = None
        self._mesh_layout_due = False
        self._stream_store = None   # HostBlockStore when data_stream
        self._streamer = None       # resolved to chunked (data/stream.py)
        self._placement = None      # PlacementPlan the pre-flight walked
        n_devices = len(jax.devices())
        use_dist = cfg.tree_learner != "serial" and (
            cfg.mesh_devices != 1 and n_devices > 1)
        from .parallel.sync import process_count
        if process_count() > 1 and not use_dist:
            log.fatal("num_machines > 1 requires tree_learner=data, voting "
                      "(per-process row partitions) or feature (full data "
                      "on every process) over >1 devices; a serial learner "
                      "would silently train per-partition models")
        # distributed implementation (docs/DISTRIBUTED.md): gspmd writes
        # the grow program over global NamedSharding arrays and the XLA
        # partitioner inserts the collectives; shardmap is the historical
        # explicit-psum choreography, kept as the forced A/B partner.
        # Every downgrade from an explicit request is loud (the rung-
        # honesty discipline: labels must name what runs).
        impl = cfg.parallel_impl
        if impl == "gspmd" and process_count() > 1 \
                and cfg.tree_learner == "feature":
            # feature-parallel multi-host replicates the FULL dataset on
            # every process (the reference contract); the multi-process
            # gspmd placement assembles per-process ROW partitions — the
            # two data contracts are incompatible, so the replication
            # layout keeps the shard_map learner
            log.warning("parallel_impl=gspmd is unavailable for "
                        "multi-process tree_learner=feature (the "
                        "full-data-everywhere replication contract); "
                        "falling back to shard_map")
            obs_counters.event(
                "layout_downgrade", stage="boosting",
                requested="parallel_impl=gspmd", resolved="shardmap",
                reason="multi-process feature-parallel replicates the "
                       "full dataset")
            impl = "shardmap"
        if impl == "gspmd" and cfg.tree_learner == "voting":
            log.warning("parallel_impl=gspmd is unavailable for "
                        "tree_learner=voting (PV-tree vote compression IS "
                        "call-site collective machinery); falling back to "
                        "shard_map")
            obs_counters.event(
                "layout_downgrade", stage="boosting",
                requested="parallel_impl=gspmd", resolved="shardmap",
                reason="voting learner needs explicit vote collectives")
            impl = "shardmap"
        if impl == "auto":
            # gspmd is the default single- AND multi-process: the compiler
            # owns the data plane either way, and the elastic stack
            # (supervisor shrink -> plan_mesh -> elastic_resume) composes
            # with both.  Only the layouts whose data contracts gspmd
            # cannot express keep the shard_map learners.
            impl = ("shardmap" if (cfg.tree_learner == "voting"
                                   or (process_count() > 1
                                       and cfg.tree_learner == "feature"))
                    else "gspmd")
        self._parallel_impl = impl if use_dist else "serial"
        # nibble-pack <=16-bin column pairs for the histogram path
        # (dense_nbits_bin.hpp analogue, data/packing.py).  Multi-process
        # global arrays and the feature-parallel column slicing keep the
        # 1:1 layout (a packed byte would straddle shard ownership).
        if (cfg.enable_bin_packing and process_count() == 1
                and not (use_dist and cfg.tree_learner
                         in ("feature", "data_feature"))):
            from .data.packing import build_pack_plan, pack_columns
            col_bins = (train.layout.col_num_bin
                        if train.layout is not None
                        and train.layout.has_bundles
                        else [train.bin_mappers[i].num_bin
                              for i in train.used_features])
            self._pack_plan = build_pack_plan(col_bins)
            if self._pack_plan is not None:
                self._hist_bins = pack_columns(np.asarray(train.binned),
                                               self._pack_plan)
                log.info("Bin packing: %d of %d columns nibble-packed "
                         "into %d bytes/row (histogram path)",
                         self._pack_plan.num_packed,
                         self._pack_plan.num_phys_cols,
                         self._pack_plan.num_storage_cols)
        # the histogram method, chosen here and nowhere else: the pack plan
        # above fixes the histogram's width, the last thing the choice
        # needs.  grower_cfg.hist_method (which bench labels and A/B
        # artifacts read) names the kernel that runs
        from .data.packing import PACK_JOINT_BINS
        from .grower import resolve_hist_method
        max_bin = layout_width(train.max_num_bin())
        hist_method, reason = resolve_hist_method(
            cfg.use_pallas, cfg.cpu_hist_method, train.binned.dtype,
            jnp.float32, (max(PACK_JOINT_BINS, max_bin)
                          if self._pack_plan is not None else max_bin))
        if reason is not None:
            log.warning("hist_method=fused unavailable (%s); using the "
                        "%s reference path", reason, hist_method)
            obs_counters.event("layout_downgrade", stage="boosting",
                               requested="fused", resolved=hist_method,
                               reason=reason)
        self.grower_cfg = self._grower_config(cfg, train, fm, hist_method)
        # the bagged-subset optimization (gbdt.cpp:323-382 is_use_subset_)
        # gathers rows into a compact matrix — serial learner only for now
        self._can_subset = not use_dist
        if not use_dist:
            if cfg.tree_learner != "serial":
                log.warning("tree_learner=%s requested but only one device is "
                            "in use (devices=%d, mesh_devices=%d); falling "
                            "back to serial", cfg.tree_learner, n_devices,
                            cfg.mesh_devices)
                obs_counters.event(
                    "layout_downgrade", stage="boosting",
                    requested=f"tree_learner={cfg.tree_learner}",
                    resolved="serial",
                    reason="only one device is in use")
            placement = self._resolve_data_placement(cfg, n_devices)
            self._placement = placement
            if placement is not None and placement.mode == "chunked":
                self._setup_streamed(cfg, train, placement)
                return
            if placement is not None and placement.mode == "sharded":
                # the capacity walk escalated PAST streaming: even the
                # double-buffered block pipeline's footprint exceeds one
                # device, but the mesh the planner sized fits — hand the
                # shape to the gspmd learner instead of OOMing serially
                log.warning("training data exceeds single-device capacity "
                            "even streamed; sharding over the %dx%d mesh "
                            "the placement planner sized",
                            placement.mesh.data, placement.mesh.feature)
                obs_counters.event(
                    "layout_downgrade", stage="boosting",
                    requested="tree_learner=serial", resolved="gspmd",
                    reason="data exceeds one device even as streamed "
                           "blocks")
                self._parallel_impl = "gspmd"
                self._can_subset = False
                self._setup_gspmd(cfg, train, n_devices)
                return
            self.bins = jnp.asarray(self.bins)
            if self._hist_bins is not None:
                self._hist_bins = jnp.asarray(self._hist_bins)
            self.grow = jax.jit(make_grower(self.grower_cfg,
                                            pack_plan=self._pack_plan))
            return
        if self._parallel_impl == "gspmd":
            self._setup_gspmd(cfg, train, n_devices)
            return
        from .parallel.learner import make_distributed_grower
        from .parallel.mesh import (make_2d_mesh, make_mesh, pad_features,
                                    pad_rows)
        axis = "feature" if cfg.tree_learner == "feature" else "data"
        if cfg.tree_learner == "data_feature":
            # near-square factorization of the device count into
            # data x feature shards (the 2-D hybrid learner); clamp to
            # the available devices like make_mesh's 1-D truncation
            nd = min(cfg.mesh_devices or n_devices, n_devices)
            dr = max(d for d in range(1, int(nd ** 0.5) + 1) if nd % d == 0)
            mesh = make_2d_mesh(dr, nd // dr)
            if jax.process_count() > 1:
                log.fatal("tree_learner=data_feature is single-process for "
                          "now; use data/voting/feature across machines")
        else:
            mesh = make_mesh(cfg.mesh_devices or 0, axis)
        shards = int(mesh.devices.size)
        n = self.num_data
        self._multiproc = jax.process_count() > 1
        self._multiproc_replicated = False
        if self._multiproc and cfg.tree_learner == "feature":
            # feature-parallel multi-host: EVERY machine holds the full data
            # (the reference's feature-parallel contract,
            # docs/Parallel-Learning-Guide.md) — arrays are replicated over
            # the global mesh and each device scans its own column slice
            self._multiproc_replicated = True
        elif self._multiproc:
            from jax.experimental import multihost_utils
            from jax.sharding import NamedSharding, PartitionSpec as P
            # every process contributes its local partition; per-device row
            # count must agree globally, so pad to the global max
            local_devs = jax.local_device_count()
            counts = np.asarray(multihost_utils.process_allgather(
                np.asarray([n]))).reshape(-1)
            per_dev = int(-(-int(counts.max()) // local_devs))
            self._row_pad = per_dev * local_devs - n
            self._global_rows = per_dev * shards
            binned = np.asarray(train.binned)
            if self._row_pad:
                binned = np.pad(binned, ((0, self._row_pad), (0, 0)))
            self._row_sharding = NamedSharding(mesh, P(axis))
            self.bins = jax.make_array_from_process_local_data(
                NamedSharding(mesh, P(axis, None)), binned,
                (self._global_rows, binned.shape[1]))
            log.info("Multi-process training: %d processes, %d local rows, "
                     "%d global (padded) rows", jax.process_count(), n,
                     self._global_rows)
        elif cfg.tree_learner in ("data", "voting", "data_feature"):
            # on the 2-D mesh rows shard over the "data" axis only
            self._row_pad = pad_rows(n, int(mesh.shape.get("data", shards)))
            self.bins = (jnp.pad(self.bins, ((0, self._row_pad), (0, 0)))
                         if self._row_pad else jnp.asarray(self.bins))
            if self._hist_bins is not None:
                hb = self._hist_bins
                self._hist_bins = (
                    jnp.pad(hb, ((0, self._row_pad), (0, 0)))
                    if self._row_pad else jnp.asarray(hb))
        if cfg.tree_learner in ("feature", "data_feature"):
            bundled = self.meta.col is not None
            ncols = int(np.shape(self.bins)[1])
            col_pad = pad_features(ncols,
                                   int(mesh.shape.get("feature", shards)))
            # pad PHYSICAL columns; bundled logical meta stays intact
            # (no logical feature maps to a pad column)
            binned = np.asarray(self.bins)
            if col_pad:
                binned = np.pad(binned, ((0, 0), (0, col_pad)))
            if not bundled:
                self._feat_pad = col_pad
                if col_pad:
                    pad1 = lambda a, v: np.pad(np.asarray(a),
                                               (0, self._feat_pad),
                                               constant_values=v)
                    self.meta = FeatureMeta(
                        num_bin=pad1(self.meta.num_bin, 1),
                        missing_type=pad1(self.meta.missing_type, 0),
                        default_bin=pad1(self.meta.default_bin, 0),
                        is_categorical=pad1(self.meta.is_categorical, False))
            if self._multiproc_replicated:
                from jax.sharding import NamedSharding, PartitionSpec as P
                from .parallel.sync import allgather_object
                import zlib
                # the replication CONTRACT must hold: every process feeds the
                # same full matrix (a user migrating from tree_learner=data
                # may still be feeding per-process partitions — reject that
                # loudly instead of training on silently inconsistent data)
                sig = (binned.shape,
                       zlib.crc32(np.ascontiguousarray(binned)))
                sigs = allgather_object(sig)
                if any(s != sig for s in sigs):
                    log.fatal("feature-parallel multi-process training "
                              "requires the FULL identical dataset on every "
                              "process (got differing data signatures %s); "
                              "per-process row partitions need "
                              "tree_learner=data or voting", sigs)
                # identical full data on every process -> one replicated
                # global array; per-row vectors ride the same sharding
                repl = NamedSharding(mesh, P())
                self.bins = jax.make_array_from_process_local_data(
                    repl, binned, binned.shape)
                self._row_sharding = repl
                self._global_rows = n
                log.info("Multi-process feature-parallel: %d processes, "
                         "full data replicated (%d rows)",
                         jax.process_count(), n)
            else:
                self.bins = jnp.asarray(binned)
        if self._multiproc:
            # replicated inputs go in as host arrays (jit replicates them);
            # device-committed single-process arrays would be rejected
            self.meta = FeatureMeta(*[None if f is None else np.asarray(f)
                                      for f in self.meta])
        log.info("Using %s-parallel tree learner over %d devices",
                 cfg.tree_learner, shards)
        self.grow = make_distributed_grower(self.grower_cfg, mesh,
                                            cfg.tree_learner, cfg.top_k,
                                            bundled=self.meta.col is not None,
                                            pack_plan=self._pack_plan)

    def _resolve_data_placement(self, cfg: Config, n_devices: int):
        """Training-data placement pre-flight for the serial learner
        (``parallel/mesh.resolve_placement``): walk resident -> streamed
        -> sharded against the device capacity / ``hbm_budget`` BEFORE
        anything compiles.  Returns the :class:`PlacementPlan` (every
        decision also lands as one ``placement_decision`` obs event), or
        None when the walk does not apply."""
        from .parallel import mesh as mesh_mod
        if cfg.boosting_type in ("dart", "goss"):
            # dart's drop/rescale and goss's top-k subsetting assume the
            # resident row layout; config.py rejects an EXPLICIT chunked
            # pin, and auto never volunteers one — an over-budget shape
            # fails in the preflight with the component breakdown instead
            return None
        capacity = (int(cfg.hbm_budget) if cfg.hbm_budget > 0
                    else obs_memory.device_capacity())
        ncols = int(np.shape(self.bins)[1])
        try:
            return mesh_mod.resolve_placement(
                rows=self.num_data, features=ncols,
                bins=self.grower_cfg.max_bin,
                leaves=self.grower_cfg.num_leaves,
                num_class=self.num_class,
                bin_bytes=int(np.asarray(self.bins).dtype.itemsize),
                packed_cols=(self._pack_plan.num_storage_cols
                             if self._pack_plan is not None else 0),
                valid_rows=sum(vs.data.num_data
                               for vs in self.valid_sets),
                capacity=capacity, data_stream=cfg.data_stream,
                stream_chunk_rows=cfg.stream_chunk_rows,
                n_devices=n_devices, prefer="data", procs=1,
                local_devices=jax.local_device_count())
        except mesh_mod.MeshPlanError:
            # the walk refused before _memory_preflight could run: land
            # the legacy hbm_preflight verdict too (obs/report.py reads
            # that event), then let the richer refusal propagate
            pred = obs_memory.predict_hbm(
                rows=self.num_data, features=ncols,
                bins=self.grower_cfg.max_bin,
                leaves=self.grower_cfg.num_leaves,
                num_class=self.num_class,
                bin_bytes=int(np.asarray(self.bins).dtype.itemsize),
                packed_cols=(self._pack_plan.num_storage_cols
                             if self._pack_plan is not None else 0),
                valid_rows=sum(vs.data.num_data
                               for vs in self.valid_sets))
            try:
                obs_memory.preflight(
                    pred, hbm_budget=cfg.hbm_budget,
                    context=f"{self.num_data} rows x {ncols} cols, "
                            f"placement walk refused")
            except RuntimeError:
                pass
            raise

    def _setup_streamed(self, cfg: Config, train: TrainingData,
                        placement) -> None:
        """``data_stream=chunked``: the quantized binned rows stay
        HOST-side and flow through the device as double-buffered
        static-shape blocks (data/stream.py), grown by the host-driven
        :class:`~.grower.StreamedGrower`.  Trees are byte-identical to
        the resident path under order-insensitive (integer) weights —
        the block accumulation runs in fixed block order."""
        from .data.stream import BlockStreamer
        if self._pack_plan is not None:
            log.warning("nibble bin packing is ignored under "
                        "data_stream=chunked (the packed histogram copy "
                        "is a second resident copy of exactly the matrix "
                        "streaming exists to keep off-device); streaming "
                        "the raw 1:1 bin layout")
            obs_counters.event(
                "layout_downgrade", stage="boosting",
                requested="enable_bin_packing=true", resolved="unpacked",
                reason="streamed blocks keep the raw 1:1 bin layout")
            self._pack_plan = None
            self._hist_bins = None
        # not a second choice of kernel: the streamed grower has ONE
        # histogram form (subset_histogram_flat over a block) and the
        # placement that leads here is decided after the method is; the
        # label follows what runs
        if self.grower_cfg.hist_method != "segment":
            log.warning("hist_method=%s is unavailable under "
                        "data_stream=chunked (per-block partial "
                        "histograms run the masked whole-block "
                        "segment-sum); falling back to segment",
                        self.grower_cfg.hist_method)
            obs_counters.event(
                "layout_downgrade", stage="boosting",
                requested=f"hist_method={self.grower_cfg.hist_method}",
                resolved="segment",
                reason="streamed blocks use the masked segment-sum")
            self.grower_cfg = self.grower_cfg._replace(
                hist_method="segment")
        # the bagged-subset gather materializes ANOTHER row matrix on
        # device — bagging under streaming keeps the weight-mask form
        self._can_subset = False
        store = train.to_blocks(placement.chunk_rows)
        self._stream_store = store
        self._streamer = BlockStreamer(store)
        # the grow-call contract passes self.bins positionally; under
        # streaming that slot carries the pipeline, not a device array
        self.bins = self._streamer
        self.grow = StreamedGrower(self.grower_cfg)
        log.info("Using streamed serial tree learner: %d blocks of %d "
                 "rows, double-buffered (%s)", store.num_blocks,
                 store.chunk_rows, placement.reason)

    def _setup_gspmd(self, cfg: Config, train: TrainingData,
                     n_devices: int) -> None:
        """GSPMD learner setup (docs/DISTRIBUTED.md): size the (batch,
        feature) mesh — explicitly (``mesh_shape=DxF``) or through the
        memory-driven planner (``mesh_shape=auto``:
        ``parallel/mesh.plan_mesh`` evaluates the ``predict_hbm`` model
        per candidate shape against the per-device capacity /
        ``hbm_budget``, so a dataset that does not fit one chip's HBM
        trains anyway and an impossible shape fails in milliseconds) —
        place the global arrays, and build the NamedSharding grower.
        XLA owns the data-plane collectives from here;
        ``parallel/sync.py``'s host ladder keeps the control plane."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .grower import fused_gate_reason
        from .parallel import gspmd as gspmd_mod
        from .parallel import mesh as mesh_mod
        # histogram formulation under gspmd (``gspmd_hist``): flat (the
        # masked whole-partition scatter-add — pure XLA, any layout) or
        # fused (the fused Pallas kernel per row shard inside a shard_map
        # island; on a mesh of row shards alone the island is the whole
        # grow loop, parallel/gspmd.py).  ``auto`` takes fused where the
        # one-device resolution took the fused kernel (the chip), one
        # process holds the mesh and the plan below shards rows alone:
        # where PR 38's A/B was made (42M x 28 over four chips: the
        # v5e compiler refuses the flat form's workspace; PERF.md); flat
        # elsewhere.  Behind the same fused_gate_reason and a condition
        # on the mesh known below.
        procs = jax.process_count()
        gspmd_hist = cfg.gspmd_hist
        auto = gspmd_hist == "auto"
        if auto:
            gspmd_hist = ("fused" if self.grower_cfg.hist_method == "fused"
                          and procs == 1 else "flat")
        if gspmd_hist == "fused" and procs > 1:
            log.warning("gspmd_hist=fused is single-process for now (the "
                        "hybrid's shard_map island has no multi-host "
                        "numbers); using the flat scatter-add histogram")
            obs_counters.event(
                "layout_downgrade", stage="boosting",
                requested="gspmd_hist=fused", resolved="flat",
                reason="multi-process training")
            gspmd_hist = "flat"
        hist_width = (max(256, self.grower_cfg.max_bin)
                      if self._pack_plan is not None
                      else self.grower_cfg.max_bin)
        sc_cols = (self._pack_plan.num_storage_cols
                   if self._pack_plan is not None
                   else int(np.shape(self.bins)[1]))
        hist_mat = (self._hist_bins if self._pack_plan is not None
                    else self.bins)
        hist_bins_dtype = np.asarray(hist_mat).dtype
        if gspmd_hist == "fused":
            # shape-independent gate (the shape-dependent half runs after
            # the mesh plan below): downgrade loudly BEFORE labels are
            # read, per the rung-honesty discipline
            reason = fused_gate_reason(hist_bins_dtype, jnp.float32,
                                       hist_width)
            if reason is not None:
                log.warning("gspmd_hist=fused unavailable (%s); using the "
                            "flat scatter-add histogram", reason)
                obs_counters.event(
                    "layout_downgrade", stage="boosting",
                    requested="gspmd_hist=fused", resolved="flat",
                    reason=reason)
                gspmd_hist = "flat"
        nd = min(cfg.mesh_devices or n_devices, n_devices)
        local_devs = jax.local_device_count()
        if procs > 1 and nd != n_devices:
            # a partial mesh cannot hold every process's row partition:
            # some rank's devices would sit outside the mesh and its data
            # would have nowhere to live
            log.warning("mesh_devices=%d ignored across %d processes; the "
                        "gspmd mesh must span all %d devices",
                        cfg.mesh_devices, procs, n_devices)
            obs_counters.event(
                "layout_downgrade", stage="boosting",
                requested=f"mesh_devices={cfg.mesh_devices}",
                resolved=f"mesh_devices={n_devices}",
                reason="multi-process gspmd mesh must span all devices")
            nd = n_devices
        prefer = {"data": "data", "feature": "feature",
                  "data_feature": "square"}.get(cfg.tree_learner, "data")
        explicit = mesh_mod.parse_mesh_shape(cfg.mesh_shape, nd, prefer)
        if explicit is not None and procs > 1:
            refusal = mesh_mod.mesh_shape_fits_processes(
                explicit[0], explicit[1], procs, local_devs)
            if refusal is not None:
                raise mesh_mod.MeshPlanError(
                    f"mesh_shape={cfg.mesh_shape} cannot serve "
                    f"{procs}-process training: {refusal}")
        ncols = int(np.shape(self.bins)[1])
        n = self.num_data
        rows_global = n
        valid_rows = sum(vs.data.num_data for vs in self.valid_sets)
        if procs > 1:
            # the planner (and predict_hbm behind it) must see the GLOBAL
            # shape: every process contributes its own row partition
            from jax.experimental import multihost_utils
            counts = np.asarray(multihost_utils.process_allgather(
                np.asarray([n, valid_rows]))).reshape(-1, 2)
            rows_global = int(counts[:, 0].sum())
            valid_rows = int(counts[:, 1].sum())
            self._proc_row_counts = counts[:, 0].astype(np.int64)
        capacity = (int(cfg.hbm_budget) if cfg.hbm_budget > 0
                    else obs_memory.device_capacity())
        plan_kwargs = dict(
            rows=rows_global, features=ncols,
            bins=self.grower_cfg.max_bin,
            leaves=self.grower_cfg.num_leaves, num_class=self.num_class,
            bin_bytes=int(np.asarray(self.bins).dtype.itemsize),
            packed_cols=(self._pack_plan.num_storage_cols
                         if self._pack_plan is not None else 0),
            valid_rows=valid_rows,
            gspmd_fused=(gspmd_hist == "fused"))
        if explicit is not None:
            d, f = explicit
            from .obs.memory import predict_hbm
            block = str(cfg.shard_axes).strip().lower().replace(" ", "") \
                in ("batch,feature", "feature,batch")
            pred = predict_hbm(data_shards=d, feature_shards=f,
                               block_shard_bins=block, **plan_kwargs)
            plan = mesh_mod.MeshPlan(
                d, f, block, int(pred["peak_bytes"]), capacity,
                dict(sorted({**pred["residents"],
                             **pred["transients"]}.items(),
                            key=lambda kv: -kv[1])[:4]),
                f"explicit mesh_shape={cfg.mesh_shape}")
        else:
            # MeshPlanError propagates: the structured pre-flight error
            # (nothing fits) must surface before anything compiles
            plan = mesh_mod.plan_mesh(nd, capacity=capacity,
                                      prefer=prefer, procs=procs,
                                      local_devices=local_devs,
                                      **plan_kwargs)
        sa = str(cfg.shard_axes).strip().lower().replace(" ", "")
        if sa == "batch":
            plan = plan._replace(block_shard_bins=False)
        elif sa in ("batch,feature", "feature,batch"):
            plan = plan._replace(block_shard_bins=True)
        if auto and (plan.feature > 1 or plan.block_shard_bins):
            gspmd_hist = "flat"
        if gspmd_hist == "fused":
            # shape-dependent half of the fused gate, now that the mesh
            # extents are known: each device's column slice must be exact
            # (shard_map even-split)
            reason = None
            if sc_cols % plan.feature != 0:
                reason = (f"{sc_cols} histogram columns do not split "
                          f"evenly over {plan.feature} feature shards")
            if reason is not None:
                log.warning("gspmd_hist=fused unavailable (%s); using the "
                            "flat scatter-add histogram", reason)
                obs_counters.event(
                    "layout_downgrade", stage="boosting",
                    requested="gspmd_hist=fused", resolved="flat",
                    reason=reason)
                gspmd_hist = "flat"
        # the gspmd builder keys off hist_method: "fused" = hybrid island,
        # anything else = flat (recorded as method=segment by dispatch).
        # Off-TPU the island runs the kernel's interpret mode
        # (hist_interpret, set with the config) — same program shape,
        # Pallas emulated — so the hybrid is CPU-testable.
        self.grower_cfg = self.grower_cfg._replace(
            hist_method="fused" if gspmd_hist == "fused" else "segment")
        obs_counters.event(
            "mesh_plan", data=plan.data, feature=plan.feature,
            block_shard_bins=plan.block_shard_bins,
            per_device_bytes=plan.per_device_bytes,
            capacity_bytes=plan.capacity, reason=plan.reason)
        obs_counters.gauge("mesh_feature_shards", plan.feature)
        mesh = mesh_mod.make_named_mesh(plan.data, plan.feature)
        bins_spec = P(mesh_mod.BATCH_AXIS,
                      mesh_mod.FEATURE_AXIS if plan.block_shard_bins
                      else None)
        if procs > 1:
            # each process holds its OWN row partition (the reference's
            # pre-partitioned parallel learning): its rows go onto its
            # own batch-axis block of the global NamedSharding array.
            # Per-SHARD row count must agree globally (static shapes), so
            # every partition pads to the global max.
            shards_per_proc = plan.data // procs    # planner guarantees >=1
            per_shard = int(-(-int(self._proc_row_counts.max())
                              // shards_per_proc))
            self._row_pad = per_shard * shards_per_proc - n
            self._global_rows = per_shard * plan.data
            binned = np.asarray(self.bins)
            if self._row_pad:
                binned = np.pad(binned, ((0, self._row_pad), (0, 0)))
            self._multiproc = True
            self._multiproc_replicated = False
            self.bins = jax.make_array_from_process_local_data(
                NamedSharding(mesh, bins_spec), binned,
                (self._global_rows, ncols))
            # replicated grower inputs go in as host arrays (jit
            # replicates them); device-committed single-process arrays
            # would be rejected — the shard_map multiproc precedent
            self.meta = FeatureMeta(*[None if f is None else np.asarray(f)
                                      for f in self.meta])
            log.info("Multi-process GSPMD: %d processes, %d local rows, "
                     "%d global (padded) rows", procs, n,
                     self._global_rows)
        else:
            self._row_pad = mesh_mod.pad_rows(n, plan.data)
            binned = np.asarray(self.bins)
            if self._row_pad:
                binned = np.pad(binned, ((0, self._row_pad), (0, 0)))
            self.bins = jax.device_put(binned,
                                       NamedSharding(mesh, bins_spec))
        if self._hist_bins is not None:
            hb = np.asarray(self._hist_bins)
            if self._row_pad:
                hb = np.pad(hb, ((0, self._row_pad), (0, 0)))
            self._hist_bins = jax.device_put(
                hb, NamedSharding(mesh, P(mesh_mod.BATCH_AXIS, None)))
        self._gspmd_mesh = mesh
        self._gspmd_plan = plan
        self._mesh_layout_due = True
        self._gspmd_row_sharding = NamedSharding(
            mesh, P(mesh_mod.BATCH_AXIS))
        if self._multiproc:
            self._row_sharding = self._gspmd_row_sharding
        log.info("Using GSPMD %s learner over a %dx%d (batch, feature) "
                 "mesh (%s)", cfg.tree_learner, plan.data, plan.feature,
                 plan.reason)
        self.grow = gspmd_mod.make_gspmd_grower(
            self.grower_cfg, mesh, bundled=self.meta.col is not None,
            pack_plan=self._pack_plan, block_shard=plan.block_shard_bins)

    def _record_mesh_layout(self, grow_args) -> None:
        """Once, after the GSPMD grower's first call: one ``mesh_layout``
        event (the mesh, the rows a shard holds, the histogram form, the
        compiled program's collectives by kind) and the counter
        ``grow_loop_collective_bytes{op}``, the payload of the collectives
        inside the grow loop's body: what crosses chips at every split
        (``utils/jaxpr_audit.hlo_loop_census``).  The lowering and the
        compile hit jit's own cache: nothing compiles a second time."""
        from .obs.collectives import hlo_census
        from .utils.jaxpr_audit import hlo_loop_census
        self._mesh_layout_due = False
        compiled = self.grow.lower(*grow_args).compile()
        census = hlo_census(compiled, label="grow")
        loop = hlo_loop_census(compiled.as_text())
        for op, rec in loop.items():
            obs_counters.inc("grow_loop_collective_bytes",
                             value=rec["bytes"], op=op)
        plan = self._gspmd_plan
        obs_counters.event(
            "mesh_layout", data=plan.data, feature=plan.feature,
            rows_per_shard=int(self.bins.shape[0]) // plan.data,
            hist_form=self.grower_cfg.hist_method,
            collectives={op: dict(rec) for op, rec in census.items()},
            loop_collectives={op: dict(rec) for op, rec in loop.items()})

    def grow_hlo_census(self, label: str = "grow") -> Dict[str, Dict[str, int]]:
        """Compiled-HLO collective census of the CURRENT grower
        executable (``obs/collectives.hlo_census``): lowers ``self.grow``
        at the exact training shapes/shardings — with the jit cache and
        the persistent compilation cache this reuses the training's own
        executable — and returns ``{op: {count, bytes, max_bytes}}``.
        This is the honest accounting under GSPMD, where the compiler
        (not a call site) decides which collectives run; bench.py's mesh
        rung and tests/test_gspmd.py's audit both read it."""
        from .obs.collectives import hlo_census
        feat_mask = np.ones(len(self._feat_valid_base), dtype=bool)
        if self._feat_pad:
            feat_mask = np.concatenate(
                [feat_mask, np.zeros(self._feat_pad, dtype=bool)])
        if not self._multiproc:
            feat_mask = jnp.asarray(feat_mask)
        if self._streamer is not None:
            # streamed grower: sum the census over its jit pieces (the
            # zero-added-collectives pin — single-device streaming must
            # not smuggle communication into the program)
            return self.grow.hlo_census(self._streamer, self.meta,
                                        feat_mask, label=label)
        zero = self._dist_row_vec(jnp.zeros((self.num_data,), jnp.float32))
        hist_arg = ((self._hist_bins,)
                    if self._pack_plan is not None else ())
        compiled = self.grow.lower(self.bins, *hist_arg, zero, zero, zero,
                                   self.meta, feat_mask).compile()
        return hlo_census(compiled, label=label)

    def _make_metrics(self, data: TrainingData) -> List[Metric]:
        out = []
        for name in self.metric_names:
            m = create_metric(name, self.config)
            if m is not None:
                m.init(data.metadata, data.num_data)
                out.append(m)
        return out

    def add_valid_set(self, data: TrainingData, name: str) -> None:
        vs = _ValidSet(data, name, self.num_class, self._make_metrics(data))
        # replay existing model onto the new valid set (continued training)
        for i, tree in enumerate(self.models):
            k = i % self.num_class
            vs.scores = vs.scores.at[k].add(
                tree_scores_binned(vs.bins, tree, self.used_feature_index,
                                   self.feat_info,
                                   self.train_set.bin_mappers))
        self.valid_sets.append(vs)

    # --------------------------------------------------------------- training

    def _boost_from_average(self) -> None:
        """gbdt.cpp:407-480: constant init tree from the label average.

        Multi-process: the average is computed from globally summed
        (numerator, denominator) stats before the objective's transform —
        GlobalSyncUpByMean — so every rank starts from the same score."""
        num, den = self.objective.average_stats()
        if self._multiproc:
            from .parallel.sync import allgather_object
            parts = allgather_object((num, den))
            num = sum(p[0] for p in parts)
            den = sum(p[1] for p in parts)
        init = self.objective.init_from_average(num / max(den, 1e-300))
        tree = Tree(1)
        tree.leaf_value[0] = init
        self.models.append(tree)
        self.scores = self.scores + init
        for vs in self.valid_sets:
            vs.scores = vs.scores + init
        self.boost_from_average_ = True
        log.info("Start training from score %f", init)

    def _bagging(self, it: int, grad, hess) -> None:
        """Row bagging (gbdt.cpp:323-382).

        fraction <= 0.5 (the reference's ``is_use_subset_`` regime): exact
        ``fraction * N`` rows sampled without replacement are GATHERED into a
        compact device matrix and the tree grows on that — per-tree cost is
        O(bagged rows), not O(N).  Larger fractions keep the cheaper 0/1
        weight-mask form (Bernoulli, vectorized)."""
        cfg = self.config
        if cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0:
            if it % cfg.bagging_freq == 0:
                n = self.num_data
                if self._can_subset and cfg.bagging_fraction <= 0.5:
                    m = max(1, int(n * cfg.bagging_fraction))
                    idx = sample_k(self._bag_rng, n, m)
                    self._set_subset(idx, np.ones(m, np.float32))
                else:
                    self._subset_state = None
                    mask = (self._bag_rng.random(n)
                            < cfg.bagging_fraction).astype(np.float32)
                    self._bag_weight = jax.device_put(
                        mask, self._row_shards(1))
                    self._bag_cnt = self._bag_weight
                self._bagging_on = True
        elif getattr(self, "_bagging_on", False):
            # bagging turned off mid-training (reset_parameter callback,
            # ResetBaggingConfig analogue): drop the stale subset/mask so
            # trees see the full data again
            self._bagging_on = False
            self._subset_state = None
            self._bag_weight = self._row_ones()
            self._bag_cnt = self._bag_weight

    def _set_subset(self, idx: np.ndarray, w: np.ndarray) -> None:
        """Gather rows ``idx`` (weights ``w``) into the compact subset matrix.

        Padded to a power-of-two bucket so re-bagging recompiles the grower at
        most log2 times; padding rows point at row 0 with weight 0 (they flow
        through the partition but contribute nothing to any histogram,
        count, or output)."""
        m = len(idx)
        m_pad = max(1 << max(int(m - 1).bit_length(), 0), 1024)
        pad = m_pad - m
        idx_p = np.concatenate([idx.astype(np.int32),
                                np.zeros(pad, np.int32)])
        w_p = np.concatenate([w.astype(np.float32), np.zeros(pad, np.float32)])
        idx_d = jnp.asarray(idx_p)
        self._subset_state = (jnp.take(self.bins, idx_d, axis=0),
                              idx_d,
                              jnp.asarray(w_p),
                              jnp.asarray((w_p > 0).astype(np.float32)),
                              (jnp.take(self._hist_bins, idx_d, axis=0)
                               if self._hist_bins is not None else None))
        self._bag_weight = jnp.ones((self.num_data,), jnp.float32)
        self._bag_cnt = self._bag_weight

    def _feature_sample(self) -> np.ndarray:
        frac = self.config.feature_fraction
        mask = self._feat_valid_base.copy()
        if frac < 1.0:
            f = len(mask)
            k = max(1, int(f * frac))
            chosen = self._feat_rng.choice(f, size=k, replace=False)
            sub = np.zeros(f, dtype=bool)
            sub[chosen] = True
            mask &= sub
        return mask

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration; returns True if training should stop
        (gbdt.cpp:465-581 TrainOneIter).  Each iteration is one telemetry
        span; the per-phase spans inside come from ``self.timers``."""
        fl = obs_flight.get_flight()
        dp = obs_devprof.get_devprof()
        t0 = time.perf_counter() if fl.enabled else 0.0
        with obs_trace.phase("iteration", index=int(self.iter_)), \
                dp.iteration(int(self.iter_)):
            stop = self._train_one_iter_inner(grad, hess)
        if not self._hbm_gauged:
            # once, after the first tree: the grower's temporaries are now
            # reserved, which predict_hbm (gauge hbm_predicted_peak_bytes)
            # does not count — the two gauges beside it record the gap
            self._hbm_gauged = True
            obs_memory.gauge_hbm_peaks()
        # per-iteration device-memory gauge (no-op singleton when memory
        # observability is off; armed it is a host-side read — it rides
        # the fetches the loop already does, adding no syncs of its own)
        obs_memory.get_memory().sample(site="iteration")
        if fl.enabled:
            # flight-recorder progress record: everything here is a
            # host-side registry read — no device fetch, no collective
            dt = time.perf_counter() - t0
            rec: Dict[str, object] = {"seconds": round(dt, 6)}
            if dt > 0:
                rec["trees_per_sec"] = round(self.num_class / dt, 4)
            leaves = self._last_iter_leaves
            if leaves and dt > 0:
                rec["ms_per_leaf"] = round(dt * 1e3 / leaves, 4)
            kernel = obs_counters.observed_kernel()
            if kernel:
                rec["kernel"] = kernel
            peak = obs_memory.get_memory().measured_peak()
            if peak:
                rec["hbm_peak_bytes"] = int(peak)
            coll = obs_collectives.totals()
            if coll["calls"]:
                rec["collective_bytes"] = coll["bytes"]
            # the just-captured devprof window's idle-gap fraction rides
            # the progress record (parsed before this record is built, so
            # the supervisor's straggler verdict can cite it)
            gap = dp.pop_idle_gap() if dp.enabled else None
            if gap is not None:
                rec["idle_gap_fraction"] = gap
            # streamed pipeline: this iteration's blocking transfer waits
            # over its wall clock — the overlap evidence the bench rung
            # and the stream_stall events summarize
            if self._streamer is not None and dt > 0:
                wait = self._streamer.take_wait_ms()
                rec["stream_wait_ms"] = round(wait, 3)
                rec["stream_stall_fraction"] = round(
                    min(1.0, wait / (dt * 1e3)), 4)
            # per-metric eval values (model-quality plane): the engine
            # evaluates AFTER update, so the freshest stashed values are
            # the previous iteration's — stamped as such
            evals = obs_model_quality.get_tracker().eval_fields()
            if evals:
                rec["eval"] = evals
            fl.progress(int(self.iter_), **rec)
        return stop

    def _train_one_iter_inner(self, grad: Optional[np.ndarray] = None,
                              hess: Optional[np.ndarray] = None) -> bool:
        # leaves this iteration actually split (known on the synchronous
        # path only — pipelined trees drain later); the flight recorder's
        # ms/leaf field rides it
        self._last_iter_leaves = 0
        it = int(self.iter_)     # ties this iteration's spans together
        if (self.iter_ == 0 and self.num_init_iteration == 0
                and self.allow_boost_from_average
                and self.objective is not None
                and self.objective.boost_from_average
                and not self._has_init_score
                and self.num_class == 1
                and self.config.boost_from_average
                and not self.boost_from_average_):
            self._boost_from_average()

        # score arrays are immutable jax values, so holding the
        # iteration-start REFERENCES is a zero-copy undo point: rollback of
        # this (or the just-finished) iteration restores them bit-exactly,
        # which arithmetic subtraction cannot do in f32 ((a+b)-b is off by
        # an ulp for ~half of all inputs) — the invariant
        # nonfinite_policy=rollback and tests/test_robustness.py depend on
        if self.rollback_safe:
            self._score_stash = (self.iter_, self.scores,
                                 [vs.scores for vs in self.valid_sets])

        # pipelined mode never blocks in the loop: every phase is an async
        # dispatch and freshly grown trees drain to host a few iterations
        # late (one batched transfer each).  Synchronous mode blocks each
        # phase on its outputs so async dispatch does not misattribute
        # device time to the next phase.
        # custom gradients stay synchronous: the caller computed them from
        # the CURRENT prediction state, so a lately-discovered no-split
        # rewind must never invalidate iterations their fobj already saw
        pipeline = self._pipeline and grad is None and hess is None
        if not pipeline and self._pending:
            self._drain_pending()           # never interleave modes
            if self._stopped_no_split:
                self._stopped_no_split = False
                return True
        with self.timers.phase("boosting", iteration=it):
            if grad is None or hess is None:
                g, h = self._grad_fn(self.scores)
            else:
                g = jnp.asarray(grad, jnp.float32).reshape(self.num_class, -1)
                h = jnp.asarray(hess, jnp.float32).reshape(self.num_class, -1)
            fi = faults_mod.get_faults()
            if fi.enabled:
                if fi.fire("nan_grad", int(self.iter_)):
                    g = g.at[0, 0].set(jnp.nan)
                if fi.fire("inf_hess", int(self.iter_)):
                    h = h.at[0, 0].set(jnp.inf)
            # device-side finiteness flag, fetched later alongside values
            # the loop already pulls (num_leaves / the drain batch) — the
            # guard adds no host<->device synchronization of its own
            gh_ok = jnp.isfinite(g).all() & jnp.isfinite(h).all()
            if self._nf_policy == "clamp":
                g = jnp.where(jnp.isfinite(g), g, 0.0)
                h = jnp.where(jnp.isfinite(h), h, 1.0)
            if not pipeline:
                jax.block_until_ready((g, h))
        with self.timers.phase("bagging", iteration=it):
            g, h, cnt = self._sample(self.iter_, g, h)
            if not pipeline:
                jax.block_until_ready((g, h, cnt))

        lr = self._shrinkage_rate()
        any_split = False
        for k in range(self.num_class):
            # re-sampled PER TREE like the reference's BeforeTrain
            # (serial_tree_learner.cpp:234-260), not once per iteration
            feat_mask = np.asarray(self._feature_sample())
            if self._feat_pad:
                feat_mask = np.concatenate(
                    [feat_mask, np.zeros(self._feat_pad, dtype=bool)])
            if not self._multiproc:   # multiproc: host arrays auto-replicate
                feat_mask = jnp.asarray(feat_mask)
            with self.timers.phase("tree", iteration=it):
                if self._subset_state is not None:
                    # compact bagged matrix: tree cost is O(bagged rows)
                    sbins, sidx, sw, scnt, shist = self._subset_state
                    hist_arg = (shist,) if self._pack_plan is not None else ()
                    arrays, row_leaf = self.grow(sbins, *hist_arg,
                                                 g[k][sidx] * sw,
                                                 h[k][sidx] * sw, scnt,
                                                 self.meta, feat_mask)
                else:
                    hist_arg = ((self._hist_bins,)
                                if self._pack_plan is not None else ())
                    grow_args = (
                        self.bins, *hist_arg,
                        self._dist_row_vec(g[k] * self._bag_weight),
                        self._dist_row_vec(h[k] * self._bag_weight),
                        self._dist_row_vec(cnt), self.meta, feat_mask)
                    arrays, row_leaf = self.grow(*grow_args)
                    if self._mesh_layout_due:
                        self._record_mesh_layout(grow_args)
                    del grow_args       # the weighted rows die with the call
                    row_leaf = self._local_rows(row_leaf)
                nf_ok = gh_ok & jnp.isfinite(arrays.leaf_value).all()
                if pipeline:
                    # start the host copy NOW; the batched device_get a few
                    # iterations later finds the bytes already landed
                    jax.tree.map(
                        lambda a: getattr(a, "copy_to_host_async",
                                          lambda: None)(), arrays)
                else:
                    # the wait for the grower apart from the host's tree
                    # building: the phase's self time is then the dispatch
                    with self.timers.phase("tree.wait", iteration=it):
                        if self._multiproc:
                            # tree arrays are replicated — pull to host once
                            # so the local scoring/predict paths see
                            # process-local data
                            arrays = jax.tree.map(np.asarray, arrays)
                            num_leaves = int(arrays.num_leaves)
                            nf_ok_h = bool(np.asarray(nf_ok))
                            gh_ok_h = bool(np.asarray(gh_ok))
                        else:
                            # ONE fetch for the split count AND the guard
                            # flags (the sync the loop was already paying)
                            num_leaves, nf_ok_h, gh_ok_h = jax.device_get(
                                (arrays.num_leaves, nf_ok, gh_ok))
                            num_leaves = int(num_leaves)
                    if not bool(nf_ok_h) \
                            and self._handle_nonfinite(k, bool(gh_ok_h)):
                        return False    # iteration rolled back; retry next
                    self._last_iter_leaves += max(0, num_leaves - 1)
                    with self.timers.phase("tree.host", iteration=it):
                        tree = Tree.from_arrays(
                            arrays, self.train_set.used_features,
                            self.train_set.bin_mappers, self._num_bin_host)
                        tree.shrink(lr)
                        self._models.append(tree)
                        # split audit over the arrays this sync path
                        # already fetched — zero added device traffic
                        # (pinned)
                        obs_model_quality.get_tracker().observe_tree(
                            it, len(self._models) - 1, tree)
            # pipelined: the split/no-split outcome is unknown on host, but
            # a no-split tree's leaf_value is all zeros so the score update
            # is a provable no-op — dispatch it unconditionally
            if pipeline or num_leaves > 1:
                any_split = True
                with self.timers.phase("score", iteration=it):
                    lr_dev = jnp.asarray(lr, jnp.float32)
                    if self._subset_state is not None:
                        # out-of-bag rows need scores too (UpdateScoreOutOfBag,
                        # gbdt.cpp:452-463): route ALL rows through the fresh
                        # device-side tree — no host round-trip
                        self.scores = self.scores.at[k].set(
                            self._routed_score(self.scores[k], self.bins,
                                               arrays, lr_dev))
                    else:
                        self.scores = self.scores.at[k].set(
                            self._update_score(self.scores[k],
                                               arrays.leaf_value, row_leaf,
                                               lr_dev))
                    # valid sets are scored from the DEVICE-side TreeArrays —
                    # no host tree conversion or per-tree jit re-entry in the
                    # hot loop (weak-spot fix: tree_scores_binned stays for
                    # replay/rollback/DART paths only)
                    for vs in self.valid_sets:
                        vs.scores = vs.scores.at[k].set(
                            self._routed_score(vs.scores[k], vs.bins, arrays,
                                               lr_dev))
                    if not pipeline:
                        jax.block_until_ready(self.scores)
            if pipeline:
                self._pending.append(
                    {"iter": self.iter_, "k": k, "arrays": arrays, "lr": lr,
                     "nf_ok": nf_ok, "gh_ok": gh_ok})
        self._after_iter()
        self.iter_ += 1
        if pipeline:
            with self.timers.phase("tree", iteration=it):
                self._drain_pending(keep_iters=self._pipeline_depth)
            if self._stopped_no_split:
                # one-shot, like the sync path: a later call retries (a
                # reset_parameter / rollback may have re-enabled splitting)
                self._stopped_no_split = False
                return True
            return False
        if not any_split:
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            # remove the useless trees of this iteration
            for _ in range(self.num_class):
                self._models.pop()
            self.iter_ -= 1
            return True
        return False

    def _routed_score(self, scores_k, bins, arrays, lr):
        """Scores of rows the grower did not place (out-of-bag, held-out):
        routed through the fresh device-side tree and updated in one
        program, under the ``score_update`` scope."""
        return _route_update_score(
            scores_k, bins, arrays.split_feature, arrays.threshold_bin,
            arrays.default_left, arrays.left_child, arrays.right_child,
            self.feat_info, arrays.is_cat, arrays.cat_bins,
            arrays.leaf_value, lr)

    def _sample(self, it, g, h):
        """Row sampling hook: bagging for GBDT, overridden by GOSS/RF."""
        self._bagging(it, g, h)
        return g, h, self._bag_cnt

    # ---- local-rows <-> global-mesh-rows adapters (multi-process) ----------

    def _dist_row_vec(self, x) -> jnp.ndarray:
        """Local per-row vector [n_local] -> the grower's row input: padded
        in-process, or assembled into a global row-sharded jax.Array when
        each process holds its own partition (device-to-device: the local
        slices are placed on their local devices, never via host)."""
        if not self._multiproc:
            x = jnp.pad(x, (0, self._row_pad)) if self._row_pad else x
            if self._gspmd_mesh is not None:
                # commit to the mesh's batch sharding so the grower's
                # input shardings stay stable across iterations (no
                # reshard-driven recompiles)
                return jax.device_put(x, self._gspmd_row_sharding)
            return x
        xl = jnp.pad(jnp.asarray(x, jnp.float32), (0, self._row_pad)) \
            if self._row_pad else jnp.asarray(x, jnp.float32)
        imap = self._row_sharding.addressable_devices_indices_map(
            (self._global_rows,))
        # works for both shardings: row-sharded slices are rebased to this
        # process's block; replicated slices are the full range on every
        # device (start 0) — either way, device-to-device placement only
        start0 = min(s[0].start or 0 for s in imap.values())
        shards = [jax.device_put(
            xl[(s[0].start or 0) - start0:
               (s[0].stop if s[0].stop is not None else self._global_rows)
               - start0], d)
                  for d, s in imap.items()]
        return jax.make_array_from_single_device_arrays(
            (self._global_rows,), self._row_sharding, shards)

    def _local_rows(self, row_leaf) -> jnp.ndarray:
        """The grower's row-sharded output -> this process's local rows."""
        if not self._multiproc:
            if self._row_shards(1) is not None:
                return row_leaf      # on the shards the scores live on
            if self._gspmd_mesh is not None:
                # rows the shards do not divide: the scores are whole on
                # one device, so the map is read out and put there
                return jnp.asarray(np.asarray(row_leaf)[:self.num_data])
            return row_leaf[:self.num_data] if self._row_pad else row_leaf
        if self._multiproc_replicated:   # fully addressable: read directly
            return jnp.asarray(np.asarray(row_leaf)[:self.num_data])
        parts = sorted(row_leaf.addressable_shards,
                       key=lambda s: s.index[0].start or 0)
        # a (batch, feature) mesh replicates the row map along feature:
        # keep one shard per row window, not one per device
        seen = set()
        uniq = []
        for p in parts:
            st = p.index[0].start or 0
            if st not in seen:
                seen.add(st)
                uniq.append(p)
        local = np.concatenate([np.asarray(p.data) for p in uniq])
        return jnp.asarray(local[:self.num_data])

    def _shrinkage_rate(self) -> float:
        return self.config.learning_rate

    def _after_iter(self) -> None:
        pass

    def _train_tree_score(self, tree: Tree) -> jnp.ndarray:
        """Per-row contribution of a tree on this process's train bins."""
        if self._multiproc or self._stream_store is not None:
            # global sharded bins are unusable in a local jit; streamed
            # bins are a host pipeline.  Either way the (rare: rollback /
            # revert) whole-matrix traversal uploads a cached copy.
            if self._local_bins_cache is None:   # cached: DART/rollback reuse
                self._local_bins_cache = jnp.asarray(self.train_set.binned)
            return tree_scores_binned(self._local_bins_cache, tree,
                                      self.used_feature_index, self.feat_info,
                                      self.train_set.bin_mappers)
        s = tree_scores_binned(self.bins, tree, self.used_feature_index,
                               self.feat_info, self.train_set.bin_mappers)
        return s[:self.num_data] if self._row_pad else s

    def _pop_tree_and_revert(self, k: int) -> None:
        """Pop the last stored tree (class ``k``) and subtract its score
        contributions from train and valid scores — the unit step of
        ``rollback_one_iter``, also reused by the non-finite guard's
        partial same-iteration unwind."""
        tree = self.models.pop()
        if tree.num_leaves > 1:
            tree.shrink(-1.0)
            self.scores = self.scores.at[k].add(self._train_tree_score(tree))
            for vs in self.valid_sets:
                vs.scores = vs.scores.at[k].add(tree_scores_binned(
                    vs.bins, tree, self.used_feature_index, self.feat_info,
                    self.train_set.bin_mappers))

    def _stash_usable(self, expect_iter: int) -> bool:
        stash = getattr(self, "_score_stash", None)
        return (self.rollback_safe and stash is not None
                and stash[0] == expect_iter
                and len(stash[2]) == len(self.valid_sets))

    def _restore_score_stash(self) -> None:
        _, self.scores, vscores = self._score_stash
        for vs, s in zip(self.valid_sets, vscores):
            vs.scores = s
        self._score_stash = None

    def rollback_one_iter(self) -> None:
        """gbdt.cpp:583-600.

        Rolling back the most recent iteration restores train/valid scores
        from the iteration-start stash — bit-exact.  Older rollbacks (the
        stash only covers one step) fall back to the reference's
        subtract-the-contribution arithmetic, exact up to f32 rounding."""
        if self.iter_ <= 0:
            return
        self._drop_serving_caches()  # model length alone can't detect this
        if self._stash_usable(self.iter_ - 1):
            for _ in range(self.num_class):
                self.models.pop()
            self._restore_score_stash()
        else:
            self._score_stash = None
            for k in reversed(range(self.num_class)):
                self._pop_tree_and_revert(k)
        self.iter_ -= 1

    # ----------------------------------------------------- non-finite guard

    def _nf_event(self, it: int, stage: str, detected: str) -> None:
        """One structured obs event per tripped iteration (the multiclass
        loop and the per-tree drain records must not multiply it)."""
        if self._nf_event_iter == it:
            return
        self._nf_event_iter = it
        obs_counters.inc("nonfinite_trips", policy=self._nf_policy)
        obs_counters.event("nonfinite", stage=stage, iteration=it,
                           policy=self._nf_policy, detected=detected)
        log.warning("Non-finite %s detected at iteration %d "
                    "(nonfinite_policy=%s)", stage, it, self._nf_policy)

    def _handle_nonfinite(self, k: int, gh_ok: bool) -> bool:
        """Synchronous-path guard trip for class ``k`` of this iteration
        (BEFORE the tree is stored or any score update ran).  Returns True
        when the iteration was rolled back and must be retried."""
        it = int(self.iter_)
        stage = "leaf_value" if gh_ok else "grad/hess"
        self._nf_event(it, stage, detected="iteration")
        if self._nf_policy == "clamp":
            # grad/hess were sanitized on device; a non-finite LEAF with
            # finite inputs means the tree math itself diverged — no safe
            # clamp exists for that
            if gh_ok:
                raise NonFiniteError(
                    f"non-finite leaf values at iteration {it} (tree {k}) "
                    "with finite gradients; clamping cannot recover")
            return False
        if self._nf_policy == "rollback" and self.rollback_safe:
            if self._nf_rolled_iter == it:
                raise NonFiniteError(
                    f"non-finite {stage} persisted at iteration {it} after "
                    "rollback — the source is not transient; fix the "
                    "objective/data or use nonfinite_policy=clamp")
            self._nf_rolled_iter = it
            self._drop_serving_caches()
            # unwind this iteration's already-stored earlier classes:
            # restore the iteration-start score references (bit-exact) and
            # drop their trees; arithmetic revert is the fallback
            if self._stash_usable(it):
                for _ in range(k):
                    self.models.pop()
                self._restore_score_stash()
            else:
                for kk in reversed(range(k)):
                    self._pop_tree_and_revert(kk)
            log.warning("Rolled back iteration %d (%d earlier class "
                        "tree(s) unwound); retrying", it, k)
            return True
        hint = ("rollback is unavailable for this boosting type; use "
                "nonfinite_policy=clamp"
                if self._nf_policy == "rollback" else
                "set nonfinite_policy=rollback or clamp to recover")
        raise NonFiniteError(
            f"non-finite {stage} detected at iteration {it} (tree {k}); "
            f"{hint}, or fix the objective/data producing it")

    def _nonfinite_at_drain(self, it: int, gh_ok: bool) -> None:
        """Pipelined-path guard trip, detected at the (late) drain of
        iteration ``it``'s trees.  Under clamp the device values were
        already sanitized — this is visibility only; otherwise raise."""
        stage = "leaf_value" if gh_ok else "grad/hess"
        self._nf_event(it, stage, detected="drain")
        if self._nf_policy != "clamp":
            raise NonFiniteError(
                f"non-finite {stage} detected at iteration {it} (pipelined "
                "tree drain); set nonfinite_policy=rollback for prompt "
                "per-iteration recovery or clamp to sanitize")

    # ------------------------------------------------------------ checkpoint

    def data_fingerprint(self) -> int:
        """Identity of THIS process's dataset partition (shape + dtype + a
        strided sample of the binned matrix).  Rides every checkpoint — and
        the multi-process manifest — so a resume over different data (a
        re-partitioned shard, changed binning) is a structured error
        instead of silent divergence."""
        from . import checkpoint as checkpoint_mod
        ts = self.train_set
        return checkpoint_mod.data_fingerprint(
            None if ts is None else ts.binned,
            0 if ts is None else ts.num_data)

    def checkpoint_state(self) -> dict:
        """Bit-exact resumable training state (lightgbm_tpu.checkpoint):
        everything ``train_one_iter`` reads that is not derivable from the
        config + dataset — device score matrices, RNG streams, the live
        bagging subset/mask, and iteration bookkeeping."""
        self._drain_pending()
        st = {
            "data_fingerprint": self.data_fingerprint(),
            "kind": self.sub_model_name,
            "models": list(self._models),
            "iter_": self.iter_,
            "num_init_iteration": self.num_init_iteration,
            "boost_from_average_": self.boost_from_average_,
            "best_iteration": self.best_iteration,
            "scores": np.asarray(self.scores),
            "valid_scores": [np.asarray(vs.scores) for vs in self.valid_sets],
            "bag_rng": self._bag_rng.bit_generator.state,
            "feat_rng": self._feat_rng.bit_generator.state,
            "bagging_on": getattr(self, "_bagging_on", False),
            "bag_weight": np.asarray(self._bag_weight),
            "bag_cnt": np.asarray(self._bag_cnt),
            "subset": (None if self._subset_state is None else
                       {"idx": np.asarray(self._subset_state[1]),
                        "w": np.asarray(self._subset_state[2])}),
            "learning_rate": self.config.learning_rate,
        }
        return st

    def load_checkpoint_state(self, st: dict) -> None:
        """Inverse of :meth:`checkpoint_state`; requires a booster built
        on the same dataset/params (the checkpoint carries training state,
        not the binned data — the fingerprint check enforces exactly
        that)."""
        fp = st.get("data_fingerprint")
        if fp is not None and int(fp) != self.data_fingerprint():
            from .checkpoint import CheckpointError
            raise CheckpointError(
                "checkpoint dataset-partition fingerprint does not match "
                "the training data this booster holds — resuming would "
                "silently diverge (did the row shard or binning change?)")
        self._pending = []
        self._models = list(st["models"])
        self.iter_ = int(st["iter_"])
        self.num_init_iteration = int(st["num_init_iteration"])
        self.boost_from_average_ = bool(st["boost_from_average_"])
        self.best_iteration = st["best_iteration"]
        self.scores = jnp.asarray(st["scores"])
        for vs, s in zip(self.valid_sets, st["valid_scores"]):
            vs.scores = jnp.asarray(s)
        self._bag_rng = make_rng(0)
        self._bag_rng.bit_generator.state = st["bag_rng"]
        self._feat_rng = make_rng(0)
        self._feat_rng.bit_generator.state = st["feat_rng"]
        self._bagging_on = bool(st["bagging_on"])
        self._bag_weight = jnp.asarray(st["bag_weight"])
        self._bag_cnt = jnp.asarray(st["bag_cnt"])
        if st["subset"] is not None and self._stream_store is not None:
            log.fatal("checkpoint carries a bagged-subset gather state but "
                      "this booster streams its binned data "
                      "(data_stream=chunked keeps no device row matrix to "
                      "gather from); resume with data_stream=resident")
        if st["subset"] is not None:
            idx_d = jnp.asarray(st["subset"]["idx"])
            w_p = np.asarray(st["subset"]["w"])
            self._subset_state = (
                jnp.take(self.bins, idx_d, axis=0), idx_d, jnp.asarray(w_p),
                jnp.asarray((w_p > 0).astype(np.float32)),
                (jnp.take(self._hist_bins, idx_d, axis=0)
                 if self._hist_bins is not None else None))
        else:
            self._subset_state = None
        self.config.learning_rate = float(st["learning_rate"])
        self._stopped_no_split = False
        self._iter_had_split = False
        self._score_stash = None
        self._drop_serving_caches()

    # ------------------------------------------------------------------- eval

    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        return self._eval("training", self.train_metrics, self.scores)

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        for vs in self.valid_sets:
            out.extend(self._eval(vs.name, vs.metrics, vs.scores))
        return out

    def _eval(self, name, metrics, scores) -> List[Tuple[str, str, float, bool]]:
        """``scores`` is the device array: its fetch is the evaluation's
        first cost (84 MB at 10.5M rows), so it lies inside the phase."""
        it = max(int(self.iter_) - 1, 0)    # the tree just built
        with self.timers.phase("metric", iteration=it, data=name):
            with self.timers.phase("metric.fetch", iteration=it):
                host = self._eval_scores(scores)
            return self._eval_inner(name, metrics, host, it)

    def _eval_scores(self, scores) -> np.ndarray:
        return np.asarray(scores, np.float64)

    def _eval_inner(self, name, metrics, scores,
                    it) -> List[Tuple[str, str, float, bool]]:
        results = []
        mq = obs_model_quality.get_tracker()
        for m in metrics:
            with self.timers.phase("metric." + m.name, iteration=it):
                vals = m.eval(scores, self.objective)
            for mn, v in zip(m.names(), vals):
                results.append((name, mn, float(v), m.is_higher_better))
                # stash for the NEXT progress record (the engine loop
                # evaluates after update, so the flight stream carries
                # each iteration's evals one record late)
                mq.note_eval(name, mn, float(v))
        return results

    # ---------------------------------------------------------------- predict

    def _drop_serving_caches(self) -> None:
        """Invalidate every derived serving artifact.  Appending trees is
        detected by length (the cheap common case during training); any
        other mutation of the stored trees must call this."""
        self._native_pred = None
        self._pred_engine = None
        self._pred_engine_ntrees = -1

    def predict_engine(self, prewarm: bool = False, buckets=None,
                       build: bool = True, backend: str = "auto",
                       traversal: str = None):
        """The cached SoA serving engine for the current model
        (lightgbm_tpu.inference.PredictEngine; docs/SERVING.md).  Built at
        most once per model state: the flatten + threshold tables are
        reused across every subsequent predict/serving call, and appended
        trees (continued training) rebuild automatically.  ``build=False``
        only returns an engine that is already fresh."""
        fresh = (self._pred_engine is not None
                 and self._pred_engine_ntrees == len(self.models))
        if not fresh:
            if not build:
                return None
            from .inference import PredictEngine
            kw = {} if buckets is None else {"buckets": buckets}
            if traversal is None:
                traversal = getattr(self.config, "serving_traversal", "auto")
            self._pred_engine = PredictEngine(
                self.models, self.num_class, prewarm=prewarm,
                backend=backend, model_str=self.save_model_to_string(),
                traversal=traversal, **kw)
            self._pred_engine_ntrees = len(self.models)
        elif prewarm and not self._pred_engine._warmed:
            self._pred_engine.prewarm()
        return self._pred_engine

    def predictor(self, num_iteration: int = -1, raw_score: bool = False,
                  pred_early_stop: bool = False,
                  pred_early_stop_freq: Optional[int] = None,
                  pred_early_stop_margin: Optional[float] = None) -> Predictor:
        return Predictor(self.models, self.num_class, self.objective,
                         engine=self.predict_engine(build=False),
                         average_output=self.average_output,
                         num_iteration=(num_iteration + (1 if (
                             self.boost_from_average_ and num_iteration > 0)
                             else 0)) if num_iteration > 0 else -1,
                         early_stop=pred_early_stop,
                         early_stop_freq=(
                             pred_early_stop_freq if pred_early_stop_freq
                             is not None else self.config.pred_early_stop_freq),
                         early_stop_margin=(
                             pred_early_stop_margin if pred_early_stop_margin
                             is not None
                             else self.config.pred_early_stop_margin))

    def predict(self, X, num_iteration: int = -1, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                pred_early_stop: bool = False,
                pred_early_stop_freq: Optional[int] = None,
                pred_early_stop_margin: Optional[float] = None):
        if pred_contrib:
            # TreeSHAP path attribution — routed around the native
            # short-circuit (the C++ predictor is margin-only here)
            p = self.predictor(num_iteration)
            return p.predict_contrib(X, num_features=self.max_feature_idx + 1)
        if not pred_leaf and not pred_early_stop:
            out = self._native_predict(X, num_iteration, raw_score)
            if out is not None:
                return out
        p = self.predictor(num_iteration, raw_score, pred_early_stop,
                           pred_early_stop_freq, pred_early_stop_margin)
        if pred_leaf:
            return p.predict_leaf_index(X)
        return p.predict(X, raw_score=raw_score)

    def _native_predict(self, X, num_iteration: int, raw_score: bool):
        """OpenMP serving path (predictor.hpp analogue) for batch predict —
        the numpy per-tree walk stays as the fallback/oracle.  Returns None
        when the native library is unavailable or the objective's output
        transform is not implemented natively."""
        from . import native
        obj = self.objective.name if self.objective is not None else ""
        native_transforms = ("regression", "regression_l1", "huber", "fair",
                             "poisson", "binary", "multiclass",
                             "multiclassova", "xentropy", "xentlambda",
                             "lambdarank", "")
        if not native.available() or (not raw_score
                                      and obj not in native_transforms):
            return None
        try:
            if (getattr(self, "_native_pred", None) is None
                    or self._native_pred_ntrees != len(self.models)):
                self._native_pred = native.NativePredictor(
                    model_str=self.save_model_to_string())
                self._native_pred_ntrees = len(self.models)
            ni = num_iteration
            if ni is not None and ni > 0 and self.boost_from_average_:
                ni += 1     # the init tree counts as one stored iteration
            out = self._native_pred.predict(
                np.atleast_2d(np.asarray(X, np.float64)),
                num_iteration=ni if ni and ni > 0 else -1,
                raw_score=raw_score)
            return out
        except Exception as e:     # fall back to the python walk
            log.debug("native predict unavailable (%s); using python path", e)
            return None

    def current_iteration(self) -> int:
        return self.iter_ + self.num_init_iteration

    def merge_from(self, other: "GBDT") -> None:
        """GBDT::MergeFrom (gbdt.h:47-66): the other model's trees are
        PREPENDED (they become init iterations) and this model's follow.
        num_init_iteration grows by the merged count so current_iteration
        keeps matching total trees / num_class — the observable the
        reference gets by deriving iteration counts from models_.size().
        Like the reference, training scores are not recomputed — merge is
        a model-combination operation for predict/save."""
        merged = [copy.deepcopy(t) for t in other.models]
        self.num_init_iteration += len(merged) // max(self.num_class, 1)
        self.models = merged + self.models
        self._drop_serving_caches()

    # ------------------------------------------------------------- model file

    def feature_importance(self, importance_type: str = "split",
                           num_iteration: int = -1) -> np.ndarray:
        """Split/gain importance (gbdt.cpp FeatureImportance), vectorized:
        one concatenation over the kept trees' split arrays + one masked
        bincount instead of the historical trees x splits Python loop
        (reference-parity pinned in tests/test_metrics.py)."""
        n_feat = self.max_feature_idx + 1
        trees = self.models
        if num_iteration > 0:
            cut = (num_iteration + (1 if self.boost_from_average_ else 0)) \
                * self.num_class
            trees = trees[:cut]
        split_trees = [t for t in trees if t.num_leaves > 1]
        if not split_trees:
            return np.zeros(n_feat, dtype=np.float64)
        feats = np.concatenate([t.split_feature[:t.num_leaves - 1]
                                for t in split_trees])
        gains = np.concatenate([t.split_gain[:t.num_leaves - 1]
                                for t in split_trees])
        mask = gains > 0
        weights = gains[mask] if importance_type == "gain" else None
        return np.bincount(feats[mask], weights=weights,
                           minlength=n_feat).astype(np.float64)

    def save_model_to_string(self, num_iteration: int = -1) -> str:
        """gbdt.cpp:948-997 SaveModelToString — reference text format."""
        buf = io.StringIO()
        buf.write(self.sub_model_name + "\n")
        buf.write(f"num_class={self.num_class}\n")
        buf.write(f"num_tree_per_iteration={self.num_class}\n")
        buf.write(f"label_index={self.label_idx}\n")
        buf.write(f"max_feature_idx={self.max_feature_idx}\n")
        if self.objective is not None:
            buf.write(f"objective={self.objective.to_string()}\n")
        if self.boost_from_average_:
            buf.write("boost_from_average\n")
        if self.average_output:
            buf.write("average_output\n")
        buf.write("feature_names=" + " ".join(self.feature_names) + "\n")
        infos = [m.feature_info_str() for m in self.train_set.bin_mappers] \
            if self.train_set else []
        buf.write("feature_infos=" + " ".join(infos) + "\n")
        buf.write("\n")
        num_used = len(self.models)
        if num_iteration > 0:
            ni = num_iteration + (1 if self.boost_from_average_ else 0)
            num_used = min(ni * self.num_class, num_used)
        for i in range(num_used):
            buf.write(self.models[i].to_string(i))
            buf.write("\n")
        buf.write("\nfeature importances:\n")
        # importances over the KEPT trees only (gbdt.cpp:989
        # FeatureImportance(num_used_model)); saved_feature_importance_type
        # = 1 writes total gain at full precision — the reference's int
        # truncation only applies to split counts, which ARE integers
        gain_mode = self.config.saved_feature_importance_type == 1
        imp = self.feature_importance(
            importance_type="gain" if gain_mode else "split",
            num_iteration=num_iteration)
        order = np.argsort(-imp, kind="mergesort")
        for f in order:
            if imp[f] > 0:
                val = repr(float(imp[f])) if gain_mode else int(imp[f])
                buf.write(f"{self.feature_names[f]}={val}\n")
        dist = self._training_distribution()
        if dist:
            buf.write("\n")
            buf.write(obs_model_quality.format_distribution(dist))
        return buf.getvalue()

    def _training_distribution(self):
        """Training-set bin distribution for the serving drift monitor —
        computed once (host bincounts over the already-binned matrix)
        when the model-quality plane is armed, then cached; loaded
        models carry the parsed section instead."""
        if self.feature_distribution is not None:
            return self.feature_distribution
        if not obs_model_quality.get_tracker().enabled:
            return None
        try:
            self.feature_distribution = \
                obs_model_quality.training_bin_distribution(self.train_set)
        except Exception as e:      # never fail a model save over telemetry
            log.debug("training distribution unavailable (%s)", e)
            self.feature_distribution = {}
        return self.feature_distribution

    def save_model(self, filename: str, num_iteration: int = -1) -> None:
        with open(filename, "w") as f:
            f.write(self.save_model_to_string(num_iteration))

    @staticmethod
    def load_from_string(model_str: str, config: Optional[Config] = None) -> "GBDT":
        """gbdt.cpp:1010+ LoadModelFromString."""
        config = config or Config()
        lines = model_str.splitlines()
        booster = GBDT(config)
        header: Dict[str, str] = {}
        i = 0
        if lines and lines[0].strip() in ("tree", "dart", "goss", "rf"):
            booster.sub_model_name = lines[0].strip()
            i = 1
        while i < len(lines):
            line = lines[i].strip()
            if line.startswith("Tree="):
                break
            if line == "boost_from_average":
                booster.boost_from_average_ = True
            elif line == "average_output":
                booster.average_output = True
            elif "=" in line:
                k, v = line.split("=", 1)
                header[k] = v
            i += 1
        booster.num_class = int(header.get("num_tree_per_iteration",
                                           header.get("num_class", "1")))
        booster.label_idx = int(header.get("label_index", "0"))
        booster.max_feature_idx = int(header.get("max_feature_idx", "0"))
        booster.feature_names = header.get("feature_names", "").split()
        if "objective" in header:
            cfg = config.copy()
            booster.objective = parse_objective_string(header["objective"], cfg)
        # parse tree blocks
        blocks: List[str] = []
        cur: List[str] = []
        for line in lines[i:]:
            s = line.strip()
            if s.startswith("Tree="):
                if cur:
                    blocks.append("\n".join(cur))
                cur = []
            elif s.startswith("feature importances"):
                break
            elif s:
                cur.append(s)
        if cur:
            blocks.append("\n".join(cur))
        for b in blocks:
            booster.models.append(Tree.from_string(b))
        booster.num_init_iteration = len(booster.models) // max(booster.num_class, 1)
        booster.iter_ = 0
        # optional trailing sections (the tree-block loop above stops at
        # "feature importances"): the training bin distribution feeds the
        # serving drift monitor
        dist = obs_model_quality.parse_distribution(lines)
        if dist:
            booster.feature_distribution = dist
        return booster


class DART(GBDT):
    """dart.hpp — Dropouts meet MART.

    Model files still start with "tree" like every reference boosting type
    (no SubModelName override exists in the reference; a DART model file IS
    just its trees, already normalized)."""

    pipeline_supported = False   # reads/shrinks prior trees every iteration
    rollback_safe = False        # drop/normalize bookkeeping cannot be
                                 # partially unwound mid-iteration

    def __init__(self, config, train_set=None, objective=None):
        super().__init__(config, train_set, objective)
        self._drop_rng = make_rng(config.drop_seed)
        self.tree_weight: List[float] = []
        self.sum_weight = 0.0
        self._drop_index: List[int] = []
        self._shrinkage = config.learning_rate

    def _trees_scores(self, trees, bins) -> jnp.ndarray:
        """Batched [T, N] contributions (one vmapped call for all dropped
        trees — the drop/normalize walk is per-iteration hot path)."""
        if bins is self.bins and self._multiproc:
            if self._local_bins_cache is None:
                self._local_bins_cache = jnp.asarray(self.train_set.binned)
            bins = self._local_bins_cache
        out = trees_scores_binned(bins, trees, self.used_feature_index,
                                  self.feat_info, self.train_set.bin_mappers)
        if bins is self.bins and self._row_pad and not self._multiproc:
            out = out[:, :self.num_data]
        return out

    def _select_drop(self) -> None:
        cfg = self.config
        self._drop_index = []
        if self._drop_rng.random() >= cfg.skip_drop:
            drop_rate = cfg.drop_rate
            n_iter = self.iter_
            if cfg.uniform_drop:
                if cfg.max_drop > 0 and n_iter > 0:
                    drop_rate = min(drop_rate, cfg.max_drop / n_iter)
                self._drop_index = [i for i in range(n_iter)
                                    if self._drop_rng.random() < drop_rate]
            else:
                if self.sum_weight > 0:
                    inv_avg = len(self.tree_weight) / self.sum_weight
                    if cfg.max_drop > 0:
                        drop_rate = min(drop_rate,
                                        cfg.max_drop * inv_avg / self.sum_weight)
                    self._drop_index = [
                        i for i in range(n_iter)
                        if self._drop_rng.random()
                        < drop_rate * self.tree_weight[i] * inv_avg]
        k = len(self._drop_index)
        if not cfg.xgboost_dart_mode:
            self._shrinkage = cfg.learning_rate / (1.0 + k)
        else:
            self._shrinkage = (cfg.learning_rate if k == 0
                               else cfg.learning_rate / (cfg.learning_rate + k))

    def _model_index(self, it: int, k: int) -> int:
        off = 1 if self.boost_from_average_ else 0
        return off + it * self.num_class + k

    def train_one_iter(self, grad=None, hess=None) -> bool:
        # drop trees BEFORE computing gradients (dart.hpp DroppingTrees);
        # dropped contributions (at original weight w) are cached so the
        # Shrinkage(-1)/Shrinkage(1/(k+1))/Shrinkage(-k) dance of the reference
        # reduces to: train -= w ; later train += F*w, valid -= (1-F)*w, with
        # F = k/(k+1) (or k/(lr+k) in xgboost mode).
        if (self.iter_ == 0 and self.objective is not None
                and self.allow_boost_from_average
                and self.objective.boost_from_average and not self._has_init_score
                and self.num_class == 1 and self.config.boost_from_average
                and not self.boost_from_average_):
            self._boost_from_average()
        self._select_drop()
        self._drop_train_contrib = {}
        pairs = [(i, k) for i in self._drop_index
                 for k in range(self.num_class)]
        if pairs:
            contribs = self._trees_scores(
                [self.models[self._model_index(i, k)] for i, k in pairs],
                self.bins)
            for t, (i, k) in enumerate(pairs):
                self._drop_train_contrib[(i, k)] = contribs[t]
                self.scores = self.scores.at[k].add(-contribs[t])
        finished = super().train_one_iter(grad, hess)
        if not finished:
            self.tree_weight.append(self._shrinkage)
            self.sum_weight += self._shrinkage
            self._normalize()
        else:
            for (i, k), contrib in self._drop_train_contrib.items():
                self.scores = self.scores.at[k].add(contrib)
        return finished

    def _shrinkage_rate(self) -> float:
        return self._shrinkage

    def checkpoint_state(self) -> dict:
        st = super().checkpoint_state()
        st["dart"] = {"drop_rng": self._drop_rng.bit_generator.state,
                      "tree_weight": list(self.tree_weight),
                      "sum_weight": self.sum_weight,
                      "shrinkage": self._shrinkage}
        return st

    def load_checkpoint_state(self, st: dict) -> None:
        super().load_checkpoint_state(st)
        d = st.get("dart") or {}
        if "drop_rng" in d:
            self._drop_rng = make_rng(0)
            self._drop_rng.bit_generator.state = d["drop_rng"]
        self.tree_weight = list(d.get("tree_weight", []))
        self.sum_weight = float(d.get("sum_weight", 0.0))
        self._shrinkage = float(d.get("shrinkage", self.config.learning_rate))

    def _normalize(self) -> None:
        """dart.hpp:141-180 (see train_one_iter comment for the algebra)."""
        cfg = self.config
        k = float(len(self._drop_index))
        if k == 0:
            return
        factor = (k / (k + 1.0) if not cfg.xgboost_dart_mode
                  else k / (k + cfg.learning_rate))
        pairs = [(i, c) for i in self._drop_index
                 for c in range(self.num_class)]
        dropped = [self.models[self._model_index(i, c)] for i, c in pairs]
        self._drop_serving_caches()  # in-place shrink stales both caches
        # one batched traversal per valid set for ALL dropped trees
        valid_contribs = [self._trees_scores(dropped, vs.bins)
                          for vs in self.valid_sets]
        for t, (i, c) in enumerate(pairs):
            dropped[t].shrink(factor)
            self.scores = self.scores.at[c].add(
                self._drop_train_contrib[(i, c)] * factor)
            for vs, contrib in zip(self.valid_sets, valid_contribs):
                vs.scores = vs.scores.at[c].add(contrib[t] * (factor - 1.0))
        for i in self._drop_index:
            if not cfg.uniform_drop and i < len(self.tree_weight):
                denom = (k + 1.0 if not cfg.xgboost_dart_mode
                         else k + cfg.learning_rate)
                self.sum_weight -= self.tree_weight[i] / denom
                self.tree_weight[i] *= factor


class GOSS(GBDT):
    """goss.hpp — Gradient-based One-Side Sampling.

    Stays pipeline-eligible: ``_sample`` pulls the gradient magnitudes to
    host each post-warmup iteration (the top-k threshold is a host
    decision, like the reference's), but that sync never forces TREE
    materialization — the per-tree batched-transfer saving applies in
    full."""

    def _sample(self, it, g, h):
        cfg = self.config
        n = self.num_data
        if it < int(1.0 / max(cfg.learning_rate, 1e-10)):
            ones = jnp.ones((n,), jnp.float32)
            self._bag_weight = ones
            self._subset_state = None
            return g, h, ones
        s = np.asarray(jnp.sum(jnp.abs(g * h), axis=0))
        top_k = max(1, int(n * cfg.top_rate))
        other_k = max(1, int(n * cfg.other_rate))
        thr = np.partition(s, n - top_k)[n - top_k]
        is_top = s >= thr
        n_top = int(is_top.sum())
        rest = n - n_top
        keep_prob = min(1.0, other_k / max(rest, 1))
        keep_other = (~is_top) & (self._bag_rng.random(n) < keep_prob)
        multiply = (n - top_k) / other_k
        if self._can_subset and cfg.top_rate + cfg.other_rate <= 0.5:
            # goss.hpp:120-130 subset regime: gather kept rows, grow compact
            idx = np.flatnonzero(is_top | keep_other)
            w = np.where(is_top[idx], 1.0, multiply).astype(np.float32)
            self._set_subset(idx.astype(np.int32), w)
            return g, h, self._bag_cnt
        self._subset_state = None
        w = np.where(is_top, 1.0, np.where(keep_other, multiply, 0.0)) \
            .astype(np.float32)
        cnt = (w > 0).astype(np.float32)
        self._bag_weight = jnp.asarray(w)
        return g, h, jnp.asarray(cnt)


class RF(GBDT):
    """rf.hpp — bagged random forest: no shrinkage, averaged output,
    gradients always computed from the zero score, no boost-from-average."""
    average_output = True
    allow_boost_from_average = False
    pipeline_supported = False   # feeds host-side gradients every iteration

    def __init__(self, config, train_set=None, objective=None):
        super().__init__(config, train_set, objective)
        if train_set is not None:
            zero = jnp.zeros_like(self.scores)
            self._g0, self._h0 = self._grad_fn(zero)

    def train_one_iter(self, grad=None, hess=None) -> bool:
        if grad is None or hess is None:
            grad, hess = self._g0, self._h0
            return super().train_one_iter(np.asarray(grad), np.asarray(hess))
        return super().train_one_iter(grad, hess)

    def _shrinkage_rate(self) -> float:
        return 1.0

    def _eval_scores(self, scores):
        return super()._eval_scores(scores) / max(self.iter_, 1)


def create_boosting(config: Config, train_set: Optional[TrainingData] = None,
                    objective: Optional[Objective] = None) -> GBDT:
    """Factory (boosting.cpp:29-76)."""
    t = config.boosting_type
    if t in ("gbdt", "gbrt"):
        cls = GBDT
    elif t == "dart":
        cls = DART
    elif t == "goss":
        cls = GOSS
    elif t in ("rf", "random_forest"):
        cls = RF
    else:
        log.fatal("Unknown boosting type %s", t)
    return cls(config, train_set, objective)
