"""Binned dataset container — the TPU-native analogue of ``Dataset``.

The reference (``include/LightGBM/dataset.h:280-570``, ``src/io/dataset.cpp``)
stores features as per-group virtual ``Bin`` columns (dense / sparse /
4-bit).  On TPU we keep one dense row-major matrix of bin indices
(uint8 when every feature has <= 256 bins, else uint16) that is uploaded
once to HBM — the layout the reference itself uses for its GPU learner
(``GPU-Performance.md`` recipe: ``sparse_threshold=1`` densifies everything).

Construction = sample rows (``bin_construct_sample_cnt``), fit a
:class:`~lightgbm_tpu.data.binning.BinMapper` per feature, then vectorized
``value_to_bin`` over every column.  Valid datasets are aligned to their
training dataset's bin mappers (reference ``create_valid`` convention).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from ..config import Config
from ..obs import trace as obs_trace
from ..obs.counters import counters as obs_counters
from ..utils import log
from ..utils.random import make_rng, sample_k
from .binning import (BIN_TYPE_CATEGORICAL, BIN_TYPE_NUMERICAL, BinMapper,
                      MISSING_NAN, MISSING_NONE, MISSING_ZERO)
from .bundling import BundleLayout, build_bundled_column, find_bundles
from .metadata import Metadata
from . import sparse as sparse_mod


def jax_process_index() -> int:
    import jax
    return jax.process_index()


class TrainingData:
    """Fully constructed binned dataset (host side)."""

    def __init__(self):
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.bin_mappers: List[BinMapper] = []
        self.used_features: List[int] = []         # original feature index per LOGICAL column
        self.binned: Optional[np.ndarray] = None   # [N, F_physical] uint8/uint16
        self.layout: Optional[BundleLayout] = None  # EFB layout (None: 1:1)
        self.metadata: Metadata = Metadata()
        self.feature_names: List[str] = []
        self.reference: Optional["TrainingData"] = None

    # -- feature meta arrays consumed by the jitted grower --------------------

    @property
    def num_used_features(self) -> int:
        return len(self.used_features)

    def feature_meta(self) -> Dict[str, np.ndarray]:
        """Per-LOGICAL-feature meta (+ bundle decode maps when EFB is on)."""
        mappers = [self.bin_mappers[i] for i in self.used_features]
        out = {
            "num_bin": np.asarray([m.num_bin for m in mappers], dtype=np.int32),
            "missing_type": np.asarray([m.missing_type for m in mappers], dtype=np.int32),
            "default_bin": np.asarray([m.default_bin for m in mappers], dtype=np.int32),
            "is_categorical": np.asarray(
                [m.bin_type == BIN_TYPE_CATEGORICAL for m in mappers], dtype=bool),
        }
        if self.layout is not None and self.layout.has_bundles:
            out["col"] = np.asarray(self.layout.sub_col, dtype=np.int32)
            out["offset"] = np.asarray(self.layout.sub_offset, dtype=np.int32)
        return out

    def to_blocks(self, chunk_rows: int):
        """Block-resident variant of this dataset for streamed training
        (``data_stream=chunked``): the binned matrix cut into
        static-shape host row blocks a :class:`~.stream.BlockStreamer`
        pipelines through the device (data/stream.py).  The matrix
        itself stays host-side — full blocks are views, only the padded
        tail is copied."""
        from .stream import make_block_store
        if self.binned is None:
            log.fatal("Cannot build streamed blocks: dataset has no "
                      "binned matrix")
        return make_block_store(self.binned, chunk_rows)

    def max_num_bin(self) -> int:
        """Histogram width: max bins over PHYSICAL columns."""
        if self.layout is not None and self.layout.has_bundles:
            return self.layout.max_col_bins()
        if not self.used_features:
            return 1
        return max(self.bin_mappers[i].num_bin for i in self.used_features)


def construct(data: np.ndarray,
              config: Config,
              label: Optional[np.ndarray] = None,
              weight: Optional[np.ndarray] = None,
              group: Optional[np.ndarray] = None,
              init_score: Optional[np.ndarray] = None,
              feature_names: Optional[Sequence[str]] = None,
              categorical_features: Optional[Sequence[int]] = None,
              reference: Optional[TrainingData] = None) -> TrainingData:
    """Build a TrainingData from a raw feature matrix.

    Follows ``DatasetLoader::CostructFromSampleData`` (dataset_loader.cpp:482+):
    sample up to ``bin_construct_sample_cnt`` rows, fit per-feature bin mappers
    (in one shot — no two-round streaming needed since the matrix is already
    in memory), then bin every column.
    """
    data = np.asarray(data)
    if data.ndim != 2:
        log.fatal("Training data must be 2-dimensional")
    num_data, num_features = data.shape
    ds = TrainingData()
    ds.num_data = num_data
    ds.num_total_features = num_features
    ds.feature_names = (list(feature_names) if feature_names
                        else [f"Column_{i}" for i in range(num_features)])
    cat_set = set(int(c) for c in (categorical_features or []))

    if reference is not None:
        # valid set aligned to training bin mappers (basic.py reference semantics)
        ds.reference = reference
        ds.bin_mappers = reference.bin_mappers
        ds.used_features = reference.used_features
        ds.feature_names = reference.feature_names
        ds.layout = reference.layout
        if num_features != reference.num_total_features:
            log.fatal("Validation data has %d features, training data has %d",
                      num_features, reference.num_total_features)
    else:
        sample_cnt = min(config.bin_construct_sample_cnt, num_data)
        if sample_cnt < num_data:
            rng = make_rng(config.data_random_seed)
            sample_idx = sample_k(rng, num_data, sample_cnt)
            sample = np.asarray(data[sample_idx], dtype=np.float64)
        else:
            sample = np.asarray(data, dtype=np.float64)
        _fit_from_sample(ds, sample, config, cat_set)

    # bin all columns (native OpenMP binner when available)
    dtype = np.uint8 if ds.max_num_bin() <= 256 else np.uint16
    ncols = (ds.layout.num_columns
             if ds.layout is not None and ds.layout.has_bundles
             else len(ds.used_features))
    binned = np.empty((num_data, ncols), dtype=dtype)
    _bin_rows(ds, np.asarray(data), binned)
    ds.binned = binned

    _set_metadata(ds, num_data, label, weight, group, init_score)
    return ds


def _columns_T(data: np.ndarray, cols, chunk_rows: int = 4096) -> np.ndarray:
    """Contiguous ``[len(cols), N]`` float64 transpose of ``data[:, cols]``.

    Reading a single column of a row-major matrix pulls one cache line per
    element (64 bytes for 8 useful) — per-column loops over wide matrices
    were the second-largest construction cost after bin fitting.  Copying
    row chunks keeps every read sequential and the working set in cache.
    """
    cols = np.asarray(cols, dtype=np.intp)
    n = data.shape[0]
    out = np.empty((len(cols), n), dtype=np.float64)
    for r0 in range(0, n, chunk_rows):
        r1 = min(n, r0 + chunk_rows)
        out[:, r0:r1] = data[r0:r1, cols].T
    return out


# features per block in the construction loops: at 64 float64 columns the
# per-block transpose working set is ~2 MB (in L2/L3), and 64 uint8 output
# columns span exactly one cache line per row on write-back
_COL_BLOCK = 64


def _fit_from_sample(ds: TrainingData, sample: np.ndarray, config: Config,
                     cat_set) -> None:
    """Fit per-feature BinMappers from the sampled rows, filter trivial
    features, and decide the EFB bundle layout (FindBin + FindGroups)."""
    num_features = ds.num_total_features
    num_data = ds.num_data
    # distributed FindBin (dataset_loader.cpp:737-816): with each process
    # holding its own row partition, process p fits mappers only for
    # features j = p (mod P) from ITS sample, then the mapper sets are
    # allgathered so every process bins with the identical mappers
    from ..parallel.sync import allgather_object, process_count
    n_proc = process_count()
    my_features = [j for j in range(num_features)
                   if n_proc == 1 or j % n_proc == jax_process_index()]
    fitted = {}
    min_split_data = _filter_cnt(config, len(sample), num_data)
    for b0 in range(0, len(my_features), _COL_BLOCK):
        chunk = my_features[b0:b0 + _COL_BLOCK]
        cols_t = _columns_T(sample, chunk)
        for k, j in enumerate(chunk):
            col = cols_t[k]
            # sparse convention: pass non-zero values; zeros implied by total count
            nz = col[(col != 0) | np.isnan(col)]
            bin_type = (BIN_TYPE_CATEGORICAL if j in cat_set
                        else BIN_TYPE_NUMERICAL)
            fitted[j] = BinMapper.fit(nz, total_sample_cnt=len(col),
                                      max_bin=config.max_bin,
                                      min_data_in_bin=config.min_data_in_bin,
                                      min_split_data=min_split_data,
                                      bin_type=bin_type,
                                      use_missing=config.use_missing,
                                      zero_as_missing=config.zero_as_missing)
    if n_proc > 1:
        for part in allgather_object(fitted):
            fitted.update(part)
    ds.bin_mappers = [fitted[j] for j in range(num_features)]
    ds.used_features = [j for j, m in enumerate(ds.bin_mappers)
                        if not m.is_trivial]
    if not ds.used_features:
        log.fatal("Cannot construct Dataset: all features are trivial (constant)")

    # EFB: greedily bundle mutually-exclusive sparse features
    # (FindGroups/FastFeatureBundling, dataset.cpp:66-210).  All tree
    # learners consume bundles: serial/data expand physical histograms
    # globally, feature-parallel expands its column window, voting
    # expands locally before casting votes (parallel/learner.py)
    if config.enable_bundle and len(ds.used_features) > 1:
        if n_proc > 1 and jax_process_index() != 0:
            bundles = None     # rank 0 decides, everyone else receives
        else:
            with obs_trace.phase("dataset.find_bundles"):
                bs = sample[:min(len(sample), 20000)]
                nonzero = np.zeros((bs.shape[0], len(ds.used_features)),
                                   dtype=bool)
                for b0 in range(0, len(ds.used_features), _COL_BLOCK):
                    chunk = ds.used_features[b0:b0 + _COL_BLOCK]
                    cols_t = _columns_T(bs, chunk)
                    for k, _ in enumerate(chunk):
                        nonzero[:, b0 + k] = ((cols_t[k] != 0)
                                              | np.isnan(cols_t[k]))
                bundles_local = find_bundles(
                    nonzero,
                    [ds.bin_mappers[j].num_bin for j in ds.used_features],
                    config.max_conflict_rate)
                bundles = [[ds.used_features[k] for k in b]
                           for b in bundles_local]
        if n_proc > 1:
            # the bundle plan must be identical everywhere; rank 0's
            # local sample decides (the mapper set is already global)
            from ..parallel.sync import broadcast_object
            bundles = broadcast_object(bundles)
        layout = BundleLayout(bundles, ds.bin_mappers, ds.used_features)
        if layout.has_bundles:
            ds.layout = layout
            ds.used_features = layout.sub_features
            log.info("EFB bundled %d features into %d columns",
                     len(layout.sub_features), layout.num_columns)
            obs_counters.inc("efb_layout", logical=len(layout.sub_features),
                             physical=layout.num_columns,
                             max_slots=layout.max_col_bins())


def _bin_rows(ds: TrainingData, data: np.ndarray, out: np.ndarray) -> None:
    """Bin a block of raw rows into ``out`` (same row count) using the
    fitted mappers/layout — shared by the in-memory path and each chunk of
    the streamed two-round path."""
    n = data.shape[0]
    dtype = out.dtype
    col_buf = np.empty(n, dtype=dtype)
    if ds.layout is not None and ds.layout.has_bundles:
        lay = ds.layout
        # block by SOURCE-feature count, not bundle count: one bundle can
        # hold many features on sparse data, and the whole point of the
        # blocking is a bounded transpose working set
        blocks, cur, cur_src = [], [], set()
        for col, bundle in enumerate(lay.bundles):
            if cur and len(cur_src) + len(bundle) > _COL_BLOCK:
                blocks.append(cur)
                cur, cur_src = [], set()
            cur.append((col, bundle))
            cur_src.update(bundle)
        if cur:
            blocks.append(cur)
        for block in blocks:
            src = sorted({j for _, b in block for j in b})
            cols_t = _columns_T(data, src)
            lookup = {j: cols_t[k] for k, j in enumerate(src)}
            for col, bundle in block:
                if len(bundle) == 1:
                    ds.bin_mappers[bundle[0]].bin_into(
                        lookup[bundle[0]], col_buf)
                    out[:, col] = col_buf
                else:
                    offsets = [lay.sub_offset[k]
                               for k in range(len(lay.sub_col))
                               if lay.sub_col[k] == col]
                    out[:, col] = build_bundled_column(
                        lookup, bundle, ds.bin_mappers, offsets, dtype,
                        col_buf)
    else:
        for b0 in range(0, len(ds.used_features), _COL_BLOCK):
            chunk = ds.used_features[b0:b0 + _COL_BLOCK]
            cols_t = _columns_T(data, chunk)
            for k, _j in enumerate(chunk):
                ds.bin_mappers[_j].bin_into(cols_t[k], col_buf)
                out[:, b0 + k] = col_buf


def _set_metadata(ds: TrainingData, num_data: int, label, weight, group,
                  init_score) -> None:
    ds.metadata = Metadata(num_data)
    if label is not None:
        ds.metadata.set_label(label)
    else:
        ds.metadata.set_label(np.zeros(num_data, dtype=np.float32))
    ds.metadata.set_weight(weight)
    ds.metadata.set_query(group)
    ds.metadata.set_init_score(init_score)


def construct_streamed(path: str,
                       config: Config,
                       label: Optional[np.ndarray] = None,
                       weight: Optional[np.ndarray] = None,
                       group: Optional[np.ndarray] = None,
                       init_score: Optional[np.ndarray] = None,
                       feature_names: Optional[Sequence[str]] = None,
                       categorical_features: Optional[Sequence[int]] = None,
                       label_idx: int = 0,
                       chunk_rows: int = 200_000) -> TrainingData:
    """Two-round streamed construction from a text file
    (``use_two_round_loading``; dataset_loader.cpp:181-207, 265+).

    Round 1 streams the file once to pull the sampled rows (indices chosen
    exactly like the in-memory path, so mappers are bit-identical) and all
    labels; round 2 streams again, binning each chunk straight into the
    preallocated uint8/16 matrix.  Peak memory is the binned matrix plus one
    raw chunk — the full float64 feature matrix never exists."""
    from .parser import count_data_rows, iter_parsed_chunks

    num_data, num_features = count_data_rows(path, config.has_header,
                                             label_idx)
    ds = TrainingData()
    ds.num_data = num_data
    ds.num_total_features = num_features
    ds.feature_names = (list(feature_names) if feature_names
                        else [f"Column_{i}" for i in range(num_features)])
    cat_set = set(int(c) for c in (categorical_features or []))

    sample_cnt = min(config.bin_construct_sample_cnt, num_data)
    rng = make_rng(config.data_random_seed)
    sample_idx = (sample_k(rng, num_data, sample_cnt)
                  if sample_cnt < num_data
                  else np.arange(num_data))

    # ---- round 1: sampled rows + labels ------------------------------------
    sample = np.empty((len(sample_idx), num_features), dtype=np.float64)
    labels = np.empty(num_data, dtype=np.float32)
    row0 = 0
    for feats, labs in iter_parsed_chunks(path, config.has_header, label_idx,
                                          chunk_rows, ncol=num_features):
        row1 = row0 + len(labs)
        labels[row0:row1] = labs
        lo = np.searchsorted(sample_idx, row0)
        hi = np.searchsorted(sample_idx, row1)
        if hi > lo:
            sample[lo:hi] = feats[sample_idx[lo:hi] - row0]
        row0 = row1
    if row0 != num_data:
        log.fatal("Streamed loading row mismatch: counted %d, parsed %d",
                  num_data, row0)
    _fit_from_sample(ds, sample, config, cat_set)
    del sample

    # ---- round 2: bin chunks straight into the final matrix ----------------
    dtype = np.uint8 if ds.max_num_bin() <= 256 else np.uint16
    ncols = (ds.layout.num_columns
             if ds.layout is not None and ds.layout.has_bundles
             else len(ds.used_features))
    binned = np.empty((num_data, ncols), dtype=dtype)
    row0 = 0
    for feats, _ in iter_parsed_chunks(path, config.has_header, label_idx,
                                       chunk_rows, ncol=num_features):
        _bin_rows(ds, feats, binned[row0:row0 + len(feats)])
        row0 += len(feats)
    ds.binned = binned

    _set_metadata(ds, num_data, labels if label is None else label,
                  weight, group, init_score)
    return ds


def construct_csr(csr,
                  config: Config,
                  label: Optional[np.ndarray] = None,
                  weight: Optional[np.ndarray] = None,
                  group: Optional[np.ndarray] = None,
                  init_score: Optional[np.ndarray] = None,
                  feature_names: Optional[Sequence[str]] = None,
                  categorical_features: Optional[Sequence[int]] = None,
                  reference: Optional[TrainingData] = None) -> TrainingData:
    """Construction from a host :class:`~.sparse.CsrMatrix` in time and
    memory proportional to its STORED ENTRIES (``scipy.sparse`` input and
    the C-ABI sparse ingest; the reference's sparse push,
    ``LGBM_DatasetCreateFromCSR``).

    Round 1 densifies ONLY the sampled rows — CSR rows are O(nnz) random
    access, so unlike the text-file path no full pass is needed; round 2
    (:func:`_bin_stored`) fills every physical column with what an absent
    entry bins to and writes the stored entries' bins over it, one
    budget-bounded run of rows at a time.  No ``[nrow, ncol]`` buffer of
    any width exists, and beside the source and the binned matrix only
    one run's entries.  Sample indices and ordering match the in-memory
    path exactly, and a row where two columns of a bundle are both set
    keeps the later one as ``build_bundled_column`` does, so the fitted
    mappers, the binned matrix — and therefore the trained model — are
    bit-identical to densify-then-construct."""
    num_data, num_features = csr.shape
    ds = TrainingData()
    ds.num_data = num_data
    ds.num_total_features = num_features
    ds.feature_names = (list(feature_names) if feature_names
                        else [f"Column_{i}" for i in range(num_features)])
    cat_set = set(int(c) for c in (categorical_features or []))

    if reference is not None:
        ds.reference = reference
        ds.bin_mappers = reference.bin_mappers
        ds.used_features = reference.used_features
        ds.feature_names = reference.feature_names
        ds.layout = reference.layout
        if num_features != reference.num_total_features:
            log.fatal("Validation data has %d features, training data has %d",
                      num_features, reference.num_total_features)
    else:
        sample_cnt = min(config.bin_construct_sample_cnt, num_data)
        if sample_cnt < num_data:
            rng = make_rng(config.data_random_seed)
            sample_idx = sample_k(rng, num_data, sample_cnt)
        else:
            sample_idx = np.arange(num_data)
        sample = csr.rows(sample_idx)
        _fit_from_sample(ds, sample, config, cat_set)
        del sample

    dtype = np.uint8 if ds.max_num_bin() <= 256 else np.uint16
    with obs_trace.phase("dataset.bin_sparse"):
        ds.binned = _bin_stored(ds, csr, dtype)

    _set_metadata(ds, num_data, label, weight, group, init_score)
    return ds


def _bin_stored(ds: TrainingData, csr, dtype) -> np.ndarray:
    """Bin a :class:`~.sparse.CsrMatrix` from its stored entries alone,
    into the ``[nrow, physical columns]`` matrix: a budget-bounded run of
    rows at a time (:meth:`~.sparse.CsrMatrix.iter_by_column`), each
    physical column of the run starts as the bin an absent entry (0.0)
    has, then each source column's stored values are binned and written
    at their rows.  Beside the source and the result only one run's
    entries and columns exist.

    In a bundle the source columns go in bundle order, so where two are
    non-default in one row the later one stays
    (:func:`~.bundling.build_bundled_column`'s rule, the reference's
    push order)."""
    mappers = ds.bin_mappers
    lay = ds.layout if ds.layout is not None and ds.layout.has_bundles \
        else None
    bundles = lay.bundles if lay else [[j] for j in ds.used_features]
    # every column's first slot, a list a bundle (the layout's is flat)
    ends = np.cumsum([len(b) for b in bundles])
    offsets = [lay.sub_offset[e - len(b):e] if lay else [-1]
               for b, e in zip(bundles, ends)]
    zero_bin = {j: mappers[j].value_to_bin_scalar(0.0)
                for b in bundles for j in b}
    itemsize = np.dtype(dtype).itemsize
    binned = np.empty((csr.nrow, len(bundles)), dtype=dtype)
    for r0, r1, colptr, rows, values in csr.iter_by_column(
            sparse_mod.CSR_CHUNK_BUDGET_BYTES
            // max(1, len(bundles) * itemsize)):
        n = r1 - r0
        # built a column a line, turned row-major a run at a time: a
        # column of a row-major matrix is a strided write of n bytes
        lines = np.empty((len(bundles), n), dtype=dtype)

        def stored(j):
            """(rows, values, bins) of source column ``j`` in the run."""
            lo, hi = int(colptr[j]), int(colptr[j + 1])
            bins = np.empty(hi - lo, dtype=dtype)
            mappers[j].bin_into(values[lo:hi], bins)
            return rows[lo:hi], values[lo:hi], bins

        for line, bundle, offs in zip(lines, bundles, offsets):
            if len(bundle) == 1:
                line[:] = zero_bin[bundle[0]]
                at, _, bins = stored(bundle[0])
                line[at] = bins
                continue
            line[:] = 0                            # slot 0: all default
            for j, off in zip(bundle, offs):
                db = mappers[j].default_bin
                at, vals, bins = stored(j)
                if zero_bin[j] != db:
                    # an absent entry is not in this column's default bin
                    # (a categorical column): every row of it is written,
                    # so it is walked whole, as one column of n values
                    whole = np.zeros(n, dtype=np.float64)
                    whole[at] = vals
                    at, bins = np.arange(n), np.empty(n, dtype=dtype)
                    mappers[j].bin_into(whole, bins)
                keep = bins != db
                at, b = at[keep], bins[keep].astype(np.int32)
                line[at] = (off + b - (b > db)).astype(dtype)
        binned[r0:r1] = lines.T
    return binned


def _filter_cnt(config: Config, sample_cnt: int, num_data: int) -> int:
    """min_split_data for the trivial-feature pre-filter, scaled to the
    sample size (dataset_loader.cpp:495-496 semantics)."""
    return int(config.min_data_in_leaf * sample_cnt / max(num_data, 1))
