"""Parameter system.

Re-creates the reference's config surface (``include/LightGBM/config.h``):
the ~90-entry alias table (``config.h:353-483``), defaults, unknown-parameter
rejection, and the cross-field conflict checks (``src/io/config.cpp:188-240``)
— as one flat typed dataclass instead of the C++ struct hierarchy
``OverallConfig{IOConfig, BoostingConfig{TreeConfig}, ...}``.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Sequence, Union

from .utils import log

# Alias -> canonical name (reference config.h:353-483, KeyAliasTransform).
PARAM_ALIASES: Dict[str, str] = {
    "config": "config_file",
    "nthread": "num_threads",
    "num_thread": "num_threads",
    "random_seed": "seed",
    "boosting": "boosting_type",
    "boost": "boosting_type",
    "application": "objective",
    "app": "objective",
    "train_data": "data",
    "train": "data",
    "model_output": "output_model",
    "model_out": "output_model",
    "model_input": "input_model",
    "model_in": "input_model",
    "bin_packing": "enable_bin_packing",
    "predict_result": "output_result",
    "prediction_result": "output_result",
    "valid": "valid_data",
    "test_data": "valid_data",
    "test": "valid_data",
    "is_sparse": "is_enable_sparse",
    "enable_sparse": "is_enable_sparse",
    "pre_partition": "is_pre_partition",
    "training_metric": "is_training_metric",
    "train_metric": "is_training_metric",
    "ndcg_at": "ndcg_eval_at",
    "eval_at": "ndcg_eval_at",
    "min_data_per_leaf": "min_data_in_leaf",
    "min_data": "min_data_in_leaf",
    "min_child_samples": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf",
    "min_hessian": "min_sum_hessian_in_leaf",
    "min_child_weight": "min_sum_hessian_in_leaf",
    "num_leaf": "num_leaves",
    "sub_feature": "feature_fraction",
    "colsample_bytree": "feature_fraction",
    "num_iteration": "num_iterations",
    "num_tree": "num_iterations",
    "num_round": "num_iterations",
    "num_trees": "num_iterations",
    "num_rounds": "num_iterations",
    "num_boost_round": "num_iterations",
    "sub_row": "bagging_fraction",
    "subsample": "bagging_fraction",
    "subsample_freq": "bagging_freq",
    "shrinkage_rate": "learning_rate",
    "tree": "tree_learner",
    "num_machine": "num_machines",
    "local_port": "local_listen_port",
    "two_round_loading": "use_two_round_loading",
    "two_round": "use_two_round_loading",
    "mlist": "machine_list_file",
    "is_save_binary": "is_save_binary_file",
    "save_binary": "is_save_binary_file",
    "early_stopping_rounds": "early_stopping_round",
    "early_stopping": "early_stopping_round",
    "verbosity": "verbose",
    "header": "has_header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column",
    "query": "group_column",
    "query_column": "group_column",
    "ignore_feature": "ignore_column",
    "blacklist": "ignore_column",
    "categorical_feature": "categorical_column",
    "cat_column": "categorical_column",
    "cat_feature": "categorical_column",
    "predict_raw_score": "is_predict_raw_score",
    "predict_leaf_index": "is_predict_leaf_index",
    "raw_score": "is_predict_raw_score",
    "leaf_index": "is_predict_leaf_index",
    "min_split_gain": "min_gain_to_split",
    "topk": "top_k",
    "reg_alpha": "lambda_l1",
    "reg_lambda": "lambda_l2",
    "num_classes": "num_class",
    "unbalanced_sets": "is_unbalance",
    "bagging_fraction_seed": "bagging_seed",
}


@dataclasses.dataclass
class Config:
    """Flat parameter set with reference defaults (config.h:94-295)."""

    # task / infra
    task: str = "train"
    device: str = "tpu"            # reference: cpu|gpu; here: tpu|cpu (cpu = same XLA path on host)
    seed: int = 0
    num_threads: int = 0
    verbose: int = 1

    # objective / boosting
    objective: str = "regression"
    boosting_type: str = "gbdt"
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_class: int = 1
    tree_learner: str = "serial"  # serial|feature|data|voting|data_feature

    # tree
    num_leaves: int = 31
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    feature_fraction: float = 1.0
    feature_fraction_seed: int = 2
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    top_rate: float = 0.2          # GOSS
    other_rate: float = 0.1        # GOSS
    top_k: int = 20                # voting parallel
    histogram_pool_size: float = -1.0

    # categorical handling (feature_histogram.hpp:113-223)
    max_cat_group: int = 64
    max_cat_threshold: int = 256
    cat_smooth_ratio: float = 0.01
    min_cat_smooth: float = 5.0
    max_cat_smooth: float = 100.0

    # IO / binning
    max_bin: int = 255
    min_data_in_bin: int = 5
    bin_construct_sample_cnt: int = 200000
    data_random_seed: int = 1
    use_missing: bool = True
    zero_as_missing: bool = False
    enable_bundle: bool = True
    enable_bin_packing: bool = True  # nibble-pack <=16-bin column pairs
    is_enable_sparse: bool = True
    sparse_threshold: float = 0.8
    max_conflict_rate: float = 0.0
    is_pre_partition: bool = False
    use_two_round_loading: bool = False
    is_save_binary_file: bool = False
    has_header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""
    categorical_column: str = ""

    # objectives' knobs
    sigmoid: float = 1.0
    huber_delta: float = 1.0
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    gaussian_eta: float = 1.0
    scale_pos_weight: float = 1.0
    is_unbalance: bool = False
    boost_from_average: bool = True
    max_position: int = 20
    label_gain: Optional[List[float]] = None

    # DART
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    xgboost_dart_mode: bool = False
    uniform_drop: bool = False
    drop_seed: int = 4

    # metric / eval
    metric: List[str] = dataclasses.field(default_factory=list)
    metric_freq: int = 1
    is_training_metric: bool = False
    ndcg_eval_at: List[int] = dataclasses.field(default_factory=lambda: [1, 2, 3, 4, 5])
    early_stopping_round: int = 0
    output_freq: int = 1

    # prediction
    num_iteration_predict: int = -1
    is_predict_raw_score: bool = False
    is_predict_leaf_index: bool = False
    pred_early_stop: bool = False
    pred_early_stop_freq: int = 10
    pred_early_stop_margin: float = 10.0

    # model io
    output_model: str = "LightGBM_model.txt"
    input_model: str = ""
    output_result: str = "LightGBM_predict_result.txt"
    snapshot_freq: int = -1
    snapshot_keep: int = -1        # retain only the K most-recent snapshot
                                   # checkpoints, pruned after each write
                                   # (-1 = keep all)
    snapshot_resume: bool = False  # resume training from the latest VALID
                                   # snapshot checkpoint of output_model
                                   # (torn tails fall back to the previous
                                   # good snapshot; docs/ROBUSTNESS.md)
    profile_dir: str = ""          # write a jax.profiler trace of training here
    device_profile: bool = False   # device-time attribution (obs/devprof.py,
                                   # docs/OBSERVABILITY.md "Device-time
                                   # attribution"): arm programmatic
                                   # jax.profiler windows over profile_iters
                                   # steady-state boosting iterations
                                   # (first firing/compile excluded), parse
                                   # the trace artifacts, and embed a
                                   # schema-versioned device_profile block
                                   # (per-phase device ms, top ops,
                                   # host/device overlap + idle-gap per
                                   # iteration) in the telemetry trace and
                                   # bench JSON.  Implies telemetry=true;
                                   # incompatible with profile_dir (both
                                   # own the one jax profiler session)
    profile_iters: int = 2         # steady-state iterations device_profile
                                   # captures (>= 1); each window is one
                                   # profiler start/stop around one
                                   # boosting iteration
    trace_path: str = ""           # write a Chrome-trace span file (.json or
                                   # .jsonl) of training here (lightgbm_tpu.obs
                                   # telemetry; implies telemetry=true; render
                                   # with `python -m lightgbm_tpu.obs <path>`)
    telemetry: bool = False        # enable the telemetry counters/spans (docs/OBSERVABILITY.md) without writing a trace file
    metrics_port: int = 0          # live metrics export (docs/OBSERVABILITY.md
                                   # "Live telemetry"): > 0 serves the
                                   # Prometheus text view of the telemetry
                                   # registry on GET /metrics from a
                                   # standalone exporter thread while
                                   # training runs.  Rank R of a
                                   # multi-process group binds
                                   # metrics_port + R; the supervisor binds
                                   # metrics_port itself and hands workers
                                   # metrics_port + 1.  Host-side reads
                                   # only — zero added collectives or
                                   # device syncs; 0 = off
    obs_stream_path: str = ""      # per-rank flight recorder
                                   # (obs/flight.py): write a bounded,
                                   # rotated JSONL event stream to
                                   # <path>.rank_R — one iteration-stamped
                                   # progress record per boosting
                                   # iteration (trees/s, observed kernel,
                                   # HBM peak, collective bytes) plus
                                   # every structured obs event as it
                                   # happens.  The supervisor tails all
                                   # ranks' streams for straggler
                                   # detection; "" = off
    straggler_factor: float = 4.0  # supervisor straggler verdict: a rank whose flight-stream progress rate falls this factor behind the group median raises a structured rank_straggler event (requires obs_stream_path; must be > 1)
    model_quality: str = "auto"    # model-quality observability plane
                                   # (obs/model_quality.py, docs/
                                   # OBSERVABILITY.md "Model quality"):
                                   # per-split audit records into the
                                   # flight stream, per-feature gain /
                                   # split-count metrics gauges, and eval
                                   # values on progress records.  auto =
                                   # armed whenever telemetry is armed;
                                   # on | off force it.  Pure host-side
                                   # folds over arrays the trainer already
                                   # fetched — zero added device syncs or
                                   # collectives (pinned)
    convert_model: str = "gbdt_prediction.cpp"  # convert_model task (cli.py) output path
    convert_model_language: str = ""
    saved_feature_importance_type: int = 0  # importance type written to the
                                   # "feature importances:" model-file
                                   # section: 0 = split counts (reference
                                   # default), 1 = total gain (written at
                                   # full float precision, not truncated
                                   # to int)

    # robustness (docs/ROBUSTNESS.md)
    nonfinite_policy: str = "raise"  # guard on non-finite grad/hess/leaf
                                     # values: raise | rollback | clamp.
                                     # raise fails naming the iteration;
                                     # rollback discards the poisoned
                                     # iteration (forces synchronous tree
                                     # materialization); clamp sanitizes
                                     # grad->0 / hess->1 on device.  Every
                                     # trip emits a structured `nonfinite`
                                     # obs event.
    hbm_budget: float = 0.0          # device-memory pre-flight budget in
                                     # BYTES (obs/memory.predict_hbm vs
                                     # docs/MEMORY.md): 0 warns only when
                                     # the predicted peak exceeds the
                                     # detected device capacity; > 0
                                     # raises BEFORE the grower compiles
                                     # when the predicted peak exceeds it
    data_stream: str = "auto"        # training-data placement: resident
                                     # keeps the binned matrix on device
                                     # (the classic path); chunked streams
                                     # host-side row blocks through a
                                     # double-buffered device_put pipeline
                                     # (data/stream.py + the streamed
                                     # grower) so N_rows is no longer
                                     # bounded by HBM; auto lets the
                                     # pre-flight planner walk resident ->
                                     # streamed -> sharded against
                                     # hbm_budget (parallel/mesh.
                                     # resolve_placement) before any
                                     # compile
    stream_chunk_rows: int = 0       # rows per streamed block when
                                     # data_stream resolves to chunked; 0
                                     # picks a default (262144 rows capped
                                     # at ceil(rows/2) so even small
                                     # datasets exercise >= 2 blocks).
                                     # All blocks pad to this one static
                                     # shape, so the chunk loop adds zero
                                     # recompiles
    fault_inject: str = ""           # deterministic fault-injection spec,
                                     # e.g. nan_grad@3,torn_checkpoint@4,
                                     # collective_fail_once (utils/faults.py;
                                     # also via LGBM_TPU_FAULT_INJECT env)
    heartbeat_interval: float = 0.0  # per-rank liveness heartbeats
                                     # (docs/ROBUSTNESS.md "Self-healing
                                     # training"): > 0 stamps iteration +
                                     # wall-time into
                                     # <output_model>.heartbeat.rank_R at
                                     # each iteration boundary, at most
                                     # once per this many seconds — pure
                                     # host-side file writes, zero added
                                     # collectives or device syncs.  The
                                     # supervisor reads the stamps for
                                     # hang detection; 0 = off
    hang_timeout: float = 0.0        # supervisor hang detection: a rank
                                     # whose heartbeat is older than this
                                     # many seconds is declared hung and
                                     # the group is restarted from the
                                     # last committed checkpoint.  Raised
                                     # automatically to exceed the
                                     # collective ladder's worst case so
                                     # an in-band CollectiveError gets a
                                     # chance to surface first; 0 = the
                                     # supervisor default (300 s)
    restart_limit: int = 3           # supervisor restart budget: give up
                                     # (restart_budget_exhausted) after
                                     # this many group restarts WITHOUT
                                     # forward progress — a restart after
                                     # a newer committed checkpoint
                                     # resets the budget
    restart_backoff: float = 1.0     # seconds before the first group
                                     # relaunch; doubles per restart
                                     # while no forward progress is made
    preempt_signal: str = ""         # preemption safety: signals that
                                     # request a coordinated checkpoint at
                                     # the next iteration boundary and a
                                     # clean training exit — "sigterm",
                                     # "sigint", or "sigterm,sigint"
                                     # ("" = off).  Multi-process ranks
                                     # agree on the request through the
                                     # hardened collective ladder (one
                                     # small allgather per iteration while
                                     # armed); snapshots land at
                                     # output_model like snapshot_freq ones
                                     # and resume with snapshot_resume.
    elastic_resume: bool = False     # elastic groups: accept a committed
                                     # snapshot set written by a DIFFERENT
                                     # process count (any W -> this job's
                                     # W'): each rank reassembles its new
                                     # row partition from the old shards
                                     # at global row boundaries and the
                                     # group re-verifies the manifest's
                                     # global dataset fingerprint.  Also
                                     # arms the supervisor's degraded-world
                                     # relaunch.  Default false: strict
                                     # topology matching (a mismatch stays
                                     # fatal)
    elastic_min_ranks: int = 1       # floor for the supervisor's
                                     # degraded-world relaunch: the group
                                     # is never shrunk below this many
                                     # ranks (budget exhaustion applies
                                     # instead)
    world_shrink_after: int = 2      # consecutive STARTUP failures (a rank
                                     # dying before its first heartbeat of
                                     # an incarnation) after which the
                                     # supervisor declares the rank's host
                                     # lost and relaunches the group one
                                     # rank smaller through the elastic
                                     # resume path (requires
                                     # elastic_resume=true)

    # serving (docs/SERVING.md): the high-QPS batched prediction engine
    latency_budget_ms: float = 2.0   # serving microbatcher coalescing
                                     # window: a dispatched request waits
                                     # at most this long for companions
                                     # before its microbatch runs (0 =
                                     # dispatch immediately, no
                                     # coalescing)
    serving_buckets: str = "1,8,64,512,4096"  # ascending microbatch row
                                     # ladder; every request batch is
                                     # padded up to the next bucket so the
                                     # predict executable set stays
                                     # bounded and pre-warmed
                                     # (predict_jit_entries gauge)
    model_watch: str = ""            # hot model swap: checkpoint prefix
                                     # (a trainer's output_model) whose
                                     # committed snapshots/manifests the
                                     # server watches; a newly committed
                                     # iteration is loaded, pre-warmed off
                                     # the serving path, and swapped in
                                     # atomically between microbatches
                                     # ("" = no watching)
    model_watch_interval: float = 1.0  # seconds between model_watch polls
    drift_threshold: float = 0.2     # serving feature-drift alarm level:
                                     # a feature whose PSI (population
                                     # stability index) between the
                                     # training-set bin distribution and
                                     # the current serving window exceeds
                                     # this fires one `feature_drift`
                                     # structured event per window and
                                     # moves the lgbm_tpu_feature_drift
                                     # gauge; <= 0 disables the event
                                     # (gauges still export)
    drift_window_rows: int = 4096    # serving rows accumulated per drift
                                     # comparison window before the PSI is
                                     # recomputed and the histograms reset
                                     # (must be > 0)
    serving_traversal: str = "auto"  # serving-engine tree traversal:
                                     # auto | xla | packed.  ``packed``
                                     # folds each node's fields into one
                                     # i32 word pair and walks a fixed
                                     # max-depth fori ladder (one fused
                                     # gather per step instead of eight) —
                                     # bit-identical raw margins; ``auto``
                                     # picks packed on XLA:CPU where the
                                     # scalar gather lowering makes it
                                     # ~1.6x, and the classic while-loop
                                     # traversal elsewhere

    # distributed (reference NetworkConfig -> JAX mesh knobs)
    num_machines: int = 1
    local_listen_port: int = 12400
    time_out: int = 120
    machine_list_file: str = ""
    # TPU additions: how many mesh devices to use per axis; 0 = all available
    mesh_devices: int = 0
    parallel_impl: str = "auto"    # distributed learner implementation
                                   # (docs/DISTRIBUTED.md): auto | gspmd |
                                   # shardmap.  ``gspmd`` writes the grow
                                   # program over global arrays with
                                   # NamedSharding annotations and lets the
                                   # XLA partitioner insert the collectives
                                   # (the histogram reduce-scatter included);
                                   # ``shardmap`` is the historical explicit
                                   # psum/all_gather choreography, kept as
                                   # the forced A/B partner.  ``auto``
                                   # resolves gspmd single- AND multi-
                                   # process; shardmap only for voting and
                                   # multi-process feature-parallel (whose
                                   # data contracts gspmd cannot express)
    mesh_shape: str = "auto"       # GSPMD (batch, feature) mesh extents:
                                   # auto (the memory-driven planner,
                                   # parallel/mesh.plan_mesh, sizes the mesh
                                   # from predicted per-device HBM) | data
                                   # (all devices on the batch axis) |
                                   # feature | DxF (e.g. 2x4)
    shard_axes: str = "auto"       # which mesh axes shard the BINNED
                                   # matrix under gspmd: auto (planner:
                                   # replicate over feature unless memory
                                   # pressure forces block sharding) |
                                   # batch | batch,feature (row x column
                                   # block sharding)
    gspmd_hist: str = "auto"       # histogram formulation inside the
                                   # gspmd program: flat (masked whole-
                                   # partition scatter-add — pure XLA,
                                   # any layout) | fused (the fused
                                   # Pallas kernel per row shard inside
                                   # a shard_map island; on a mesh of
                                   # row shards alone the island holds
                                   # the serial grower, one histogram
                                   # psum a split; unfusable layouts
                                   # downgrade loudly to flat) | auto
                                   # (fused where the one-device method
                                   # is the fused kernel, one process
                                   # holds the mesh and it shards rows
                                   # alone; flat elsewhere.  PR 38: the
                                   # v5e compiler refuses flat at 42M x
                                   # 28 on four chips, where fused runs;
                                   # below that size the rule is
                                   # unmeasured on a chip)
    collective_timeout: float = 120.0  # seconds one host-object collective
                                       # attempt may block before it is
                                       # failed and retried (parallel/sync.py)
    collective_retries: int = 2        # bounded retries with exponential
                                       # backoff per host-object collective
                                       # before the error surfaces

    # the reference's gpu_* keys: accepted so that its config files load,
    # and ignored (there is no OpenCL platform or double-precision switch
    # here)
    gpu_platform_id: int = -1      # accepted and ignored (reference key)
    gpu_device_id: int = -1        # accepted and ignored (reference key)
    gpu_use_dp: bool = False       # accepted and ignored (reference key)

    # compute backend knobs (TPU analogue of gpu_* params)
    use_pallas: bool = True        # the fused Pallas histogram kernel on
                                   # TPU; false forces the XLA einsum
                                   # reference there
    cpu_hist_method: str = "segment"   # off-TPU histogram: segment | einsum
                                       # | fused (the kernel interpreted:
                                       # how tests reach it without a chip)
    pallas_row_tile: int = 512     # kernel grid: rows per block
    pallas_bucket_min_log2: int = 6    # smallest pow2 gather bucket (64
                                       # rows: deep-tree tail splits pay
                                       # O(leaf) work, not kilobucket
                                       # padding; sub-512 buckets shrink
                                       # the Pallas row tile to match)
    split_find: str = "fused"      # best-split scan formulation: fused
                                   # (gain scan fused onto the hot
                                   # histogram — per-direction reductions,
                                   # loop-invariant masks hoisted, no
                                   # packed candidate arrays) | chain (the
                                   # historical packed-argmax form, kept as
                                   # the forced A/B baseline).  Trees are
                                   # bit-identical either way (pinned)

    pipeline_trees: bool = True    # pipeline tree materialization: keep
    # freshly grown trees on device and pull them to host a few iterations
    # late (one batched async transfer per tree) so the training loop never
    # blocks on device->host latency.  Matters enormously when the
    # accelerator sits behind a high-latency link; synchronous fallback
    # happens automatically for DART/RF, multi-process meshes, and
    # custom-gradient training.  The final model is always bit-identical to
    # the synchronous path; the one observable difference is that a mid-run
    # "no more leaves" stop is DETECTED up to a few iterations late, so
    # per-iteration callbacks may see evals for iterations that are then
    # rewound (tests/test_pipeline.py pins the rewind to the exact
    # synchronous final state).

    # file-task fields (CLI)
    data: str = ""
    valid_data: List[str] = dataclasses.field(default_factory=list)
    config_file: str = ""

    def copy(self) -> "Config":
        return dataclasses.replace(self)


_FIELD_TYPES = {f.name: f.type for f in dataclasses.fields(Config)}
_LIST_FIELDS = {"metric", "ndcg_eval_at", "valid_data", "label_gain"}
_BOOL_TRUE = {"true", "1", "yes", "on", "+"}
_BOOL_FALSE = {"false", "0", "no", "off", "-"}


def _parse_value(name: str, value: Any) -> Any:
    """Coerce a raw (possibly string) value to the field's declared type."""
    ftype = str(_FIELD_TYPES[name])
    if name in _LIST_FIELDS:
        if value is None:
            return None
        if isinstance(value, str):
            parts = [p for p in value.replace(",", " ").split() if p]
        elif isinstance(value, (list, tuple, set, frozenset)):
            # sets arrive from user code like metric={'l2', 'auc'}
            # (python-guide simple_example.py); order them for
            # deterministic eval-log column order
            parts = (sorted(value, key=str)
                     if isinstance(value, (set, frozenset)) else list(value))
        else:
            parts = [value]
        if name == "ndcg_eval_at":
            ks = sorted(int(p) for p in parts)   # ascending, like the
            for k in ks:                         # reference (config.cpp:341)
                if k <= 0:
                    log.fatal("eval_at positions must be positive; got %d", k)
            return ks
        if name == "label_gain":
            return [float(p) for p in parts]
        return [str(p) for p in parts]
    if "bool" in ftype:
        if isinstance(value, bool):
            return value
        s = str(value).strip().lower()
        if s in _BOOL_TRUE:
            return True
        if s in _BOOL_FALSE:
            return False
        raise ValueError(f"cannot parse bool parameter {name}={value!r}")
    if "int" in ftype:
        return int(float(value)) if isinstance(value, str) else int(value)
    if "float" in ftype:
        return float(value)
    return str(value)


def canonicalize_params(params: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Alias-resolve a raw param dict; reject unknown keys (config.h:478-481).

    Explicit canonical keys win over aliased ones, mirroring the reference
    (aliases only fill in missing canonical entries).
    """
    params = dict(params or {})
    out: Dict[str, Any] = {}
    aliased: Dict[str, Any] = {}
    for key, value in params.items():
        k = key.strip().lower()
        if k in PARAM_ALIASES:
            aliased[PARAM_ALIASES[k]] = value
        elif k in _FIELD_TYPES:
            out[k] = value
        elif k in ("objective_seed",):
            continue  # tolerated no-ops
        else:
            raise ValueError(f"Unknown parameter: {key}")
    for k, v in aliased.items():
        out.setdefault(k, v)
    return out


def config_from_params(params: Optional[Dict[str, Any]] = None,
                       base: Optional[Config] = None) -> Config:
    cfg = (base.copy() if base is not None else Config())
    for k, v in canonicalize_params(params).items():
        setattr(cfg, k, _parse_value(k, v))
    check_param_conflicts(cfg)
    return cfg


def check_param_conflicts(cfg: Config) -> None:
    """Cross-field checks, following src/io/config.cpp:188-240."""
    if cfg.num_class <= 0:
        log.fatal("num_class must be positive")
    is_multiclass = cfg.objective in ("multiclass", "multiclassova", "softmax",
                                      "multiclass_ova", "ova", "ovr")
    if is_multiclass and cfg.num_class <= 1:
        log.fatal("Number of classes should be specified and greater than 1 for multiclass training")
    if not is_multiclass and cfg.num_class != 1:
        log.fatal("Number of classes must be 1 for non-multiclass training")
    if cfg.tree_learner not in ("serial", "feature", "data", "voting",
                                "data_feature"):
        log.fatal("Unknown tree learner type %s", cfg.tree_learner)
    if cfg.boosting_type not in ("gbdt", "gbrt", "dart", "goss", "rf", "random_forest"):
        log.fatal("Unknown boosting type %s", cfg.boosting_type)
    if cfg.boosting_type in ("rf", "random_forest"):
        if not (cfg.bagging_freq > 0 and 0.0 < cfg.bagging_fraction < 1.0):
            log.fatal("Random forest needs bagging (bagging_freq > 0 and 0 < bagging_fraction < 1)")
    if cfg.max_bin > 65535:
        log.fatal("max_bin too large (must fit uint16)")
    # parallel <-> learner coupling (config.cpp:212-225): a serial learner
    # forces single-machine; multiple machines with serial would otherwise
    # hang waiting for a network that no strategy uses
    if cfg.tree_learner == "serial" and cfg.num_machines > 1:
        log.warning("tree_learner=serial forces num_machines=1 "
                    "(config.cpp:222-225 semantics)")
        cfg.num_machines = 1
    if cfg.parallel_impl not in ("auto", "gspmd", "shardmap"):
        log.fatal("parallel_impl must be auto, gspmd, or shardmap; got %r",
                  cfg.parallel_impl)
    # mesh_shape syntax is validated here (the real device count is only
    # known at learner setup, where extents are checked against it)
    ms = str(cfg.mesh_shape or "auto").strip().lower()
    if ms not in ("auto", "data", "feature"):
        parts = ms.replace("*", "x").split("x")
        if len(parts) != 2 or not all(p.strip().isdigit() for p in parts) \
                or any(int(p) < 1 for p in parts):
            log.fatal("mesh_shape must be auto, data, feature, or DxF "
                      "(e.g. 2x4); got %r", cfg.mesh_shape)
    sa = str(cfg.shard_axes or "auto").strip().lower().replace(" ", "")
    if sa not in ("auto", "batch", "batch,feature", "feature,batch"):
        log.fatal("shard_axes must be auto, batch, or batch,feature; "
                  "got %r", cfg.shard_axes)
    # the 2-D hybrid shards data x feature over ONE process's mesh; fail at
    # parse time like the other conflicts instead of a late runtime fatal
    if cfg.tree_learner == "data_feature" and cfg.num_machines > 1:
        log.fatal("tree_learner=data_feature is single-process (it shards "
                  "data x feature over one process's device mesh); use "
                  "data, voting, or feature across machines")
    # Pallas grid knobs: catch bad values here with the real cause instead
    # of an opaque Mosaic layout error at trace/compile time
    if cfg.pallas_row_tile <= 0 or cfg.pallas_row_tile % 128 != 0:
        log.fatal("pallas_row_tile must be a positive multiple of 128 "
                  "(the TPU lane width); got %d", cfg.pallas_row_tile)
    if cfg.pallas_bucket_min_log2 < 0 or cfg.pallas_bucket_min_log2 > 26:
        log.fatal("pallas_bucket_min_log2 must be in [0, 26]; got %d",
                  cfg.pallas_bucket_min_log2)
    if cfg.gspmd_hist not in ("auto", "fused", "flat"):
        log.fatal("gspmd_hist must be auto, fused, or flat; got %r",
                  cfg.gspmd_hist)
    if cfg.split_find not in ("fused", "chain"):
        log.fatal("split_find must be fused or chain; got %r",
                  cfg.split_find)
    if cfg.serving_traversal not in ("auto", "xla", "packed"):
        log.fatal("serving_traversal must be auto, xla, or packed; got %r",
                  cfg.serving_traversal)
    if cfg.model_quality not in ("auto", "on", "off"):
        log.fatal("model_quality must be auto, on, or off; got %r",
                  cfg.model_quality)
    if cfg.drift_window_rows <= 0:
        log.fatal("drift_window_rows must be > 0 serving rows per PSI "
                  "window; got %d", cfg.drift_window_rows)
    if cfg.saved_feature_importance_type not in (0, 1):
        log.fatal("saved_feature_importance_type must be 0 (split) or "
                  "1 (gain); got %d", cfg.saved_feature_importance_type)
    if cfg.nonfinite_policy not in ("raise", "rollback", "clamp"):
        log.fatal("nonfinite_policy must be raise, rollback, or clamp; "
                  "got %r", cfg.nonfinite_policy)
    if cfg.fault_inject:
        # fail at parse time with the real cause, not at the injection point
        from .utils.faults import parse_spec
        try:
            entries = parse_spec(cfg.fault_inject)
        except ValueError as e:
            log.fatal("%s", e)
        else:
            world = max(1, cfg.num_machines)
            for e in entries:
                # a rank qualifier naming a rank the job does not run
                # would silently inject nothing — reject it here.  Skipped
                # under an elastic relaunch (LGBM_TPU_WORLD set): the spec
                # was written for the LAUNCH topology, and a shrunk world
                # legitimately no longer runs the evicted rank
                if e.rank is not None and e.rank >= world \
                        and "LGBM_TPU_WORLD" not in os.environ:
                    log.fatal("fault_inject: rank=%d targets a rank this "
                              "job does not run (num_machines=%d)",
                              e.rank, world)
    if cfg.preempt_signal:
        for tok in str(cfg.preempt_signal).replace(",", " ").split():
            if tok.strip().lower() not in ("sigterm", "sigint", "term",
                                           "int"):
                log.fatal("preempt_signal must name sigterm and/or sigint "
                          "(comma-separated); got %r", cfg.preempt_signal)
    if cfg.hbm_budget < 0:
        log.fatal("hbm_budget must be >= 0 bytes (0 = warn-only pre-flight "
                  "against the detected device capacity); got %r",
                  cfg.hbm_budget)
    if cfg.data_stream not in ("auto", "resident", "chunked"):
        log.fatal("data_stream must be auto, resident, or chunked; got %r",
                  cfg.data_stream)
    if cfg.stream_chunk_rows < 0:
        log.fatal("stream_chunk_rows must be >= 0 rows (0 = auto block "
                  "size); got %r", cfg.stream_chunk_rows)
    if cfg.data_stream == "chunked" \
            and cfg.boosting_type in ("dart", "goss"):
        log.fatal("data_stream=chunked is incompatible with "
                  "boosting_type=%s: dart's drop/rescale and goss's top-k "
                  "sampling assume the resident row layout; use "
                  "data_stream=resident or boosting_type=gbdt",
                  cfg.boosting_type)
    if cfg.collective_timeout <= 0:
        log.fatal("collective_timeout must be positive; got %r",
                  cfg.collective_timeout)
    if cfg.collective_retries < 0:
        log.fatal("collective_retries must be >= 0; got %d",
                  cfg.collective_retries)
    if cfg.heartbeat_interval < 0:
        log.fatal("heartbeat_interval must be >= 0 seconds (0 = off); "
                  "got %r", cfg.heartbeat_interval)
    if cfg.hang_timeout < 0:
        log.fatal("hang_timeout must be >= 0 seconds (0 = the supervisor "
                  "default); got %r", cfg.hang_timeout)
    if cfg.hang_timeout and cfg.heartbeat_interval \
            and cfg.hang_timeout <= cfg.heartbeat_interval:
        log.fatal("hang_timeout (%g s) must exceed heartbeat_interval "
                  "(%g s): every rank would look hung between two stamps",
                  cfg.hang_timeout, cfg.heartbeat_interval)
    if cfg.metrics_port < 0 or cfg.metrics_port > 65535:
        log.fatal("metrics_port must be in [0, 65535] (0 = off); got %d",
                  cfg.metrics_port)
    if cfg.profile_iters < 1:
        log.fatal("profile_iters must be >= 1 (steady-state iterations "
                  "the device_profile plane captures); got %d",
                  cfg.profile_iters)
    if cfg.device_profile and cfg.profile_dir:
        log.fatal("device_profile cannot be combined with profile_dir: "
                  "both arm the one process-wide jax profiler session; "
                  "use device_profile for attributed per-phase accounting "
                  "or profile_dir for a raw whole-run XProf trace")
    if cfg.straggler_factor <= 1:
        log.fatal("straggler_factor must be > 1 (a rank is a straggler "
                  "when its progress rate falls that factor behind the "
                  "group median); got %r", cfg.straggler_factor)
    if cfg.latency_budget_ms < 0:
        log.fatal("latency_budget_ms must be >= 0 (0 = dispatch "
                  "immediately); got %r", cfg.latency_budget_ms)
    if cfg.model_watch_interval <= 0:
        log.fatal("model_watch_interval must be positive seconds; got %r",
                  cfg.model_watch_interval)
    try:
        parse_serving_buckets(cfg.serving_buckets)
    except ValueError as e:
        log.fatal("%s", e)
    if cfg.restart_limit < 0:
        log.fatal("restart_limit must be >= 0; got %d", cfg.restart_limit)
    if cfg.restart_backoff < 0:
        log.fatal("restart_backoff must be >= 0 seconds; got %r",
                  cfg.restart_backoff)
    if cfg.elastic_min_ranks < 1:
        log.fatal("elastic_min_ranks must be >= 1; got %d",
                  cfg.elastic_min_ranks)
    if cfg.world_shrink_after < 1:
        log.fatal("world_shrink_after must be >= 1 consecutive startup "
                  "failures; got %d", cfg.world_shrink_after)
def parse_serving_buckets(spec) -> tuple:
    """``serving_buckets`` ("1,8,64,512,4096") -> ascending int tuple;
    raises ValueError on empty/non-positive/non-ascending specs so config
    parsing fails with the real cause (docs/SERVING.md)."""
    if isinstance(spec, (tuple, list)):
        vals = [int(v) for v in spec]
    else:
        vals = [int(v) for v in str(spec).replace(",", " ").split()]
    if not vals:
        raise ValueError("serving_buckets must name at least one batch size")
    if any(v <= 0 for v in vals):
        raise ValueError(f"serving_buckets must be positive; got {vals}")
    if sorted(vals) != vals or len(set(vals)) != len(vals):
        raise ValueError(
            f"serving_buckets must be strictly ascending; got {vals}")
    return tuple(vals)


def parse_config_file(path: str) -> Dict[str, str]:
    """key=value config file, '#' comments (application.cpp:48-104)."""
    params: Dict[str, str] = {}
    with open(path, "r") as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line or "=" not in line:
                continue
            k, v = line.split("=", 1)
            params[k.strip()] = v.strip()
    return params
