"""Public ``Dataset`` / ``Booster`` API.

Mirrors ``python-package/lightgbm/basic.py`` (Dataset :548-1210,
Booster :1213-1854) but binds directly to the in-process TPU engine instead of
ctypes into a C library: lazy construction, reference-aligned validation
datasets, pandas passthrough, model save/load, training loop primitives.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from . import data as data_mod
from .boosting import GBDT, create_boosting
from .config import Config, canonicalize_params, config_from_params
from .data.dataset import TrainingData, construct
from .data.parser import load_text_file, read_header_names
from .objectives import create_objective
from .obs import trace as obs_trace
from .utils import log


def _to_matrix(data) -> np.ndarray:
    if hasattr(data, "values"):         # pandas DataFrame / Series
        data = data.values
    arr = np.asarray(data)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr


def _data_from_pandas(data, pandas_categorical):
    """Convert a DataFrame's ``category`` columns to their integer codes
    (reference basic.py:225-263 _data_from_pandas).  On the train dataset
    ``pandas_categorical`` is None and the category levels are recorded;
    on valid/predict data the recorded levels re-align the codes so the
    same string maps to the same code everywhere.

    Returns (float_matrix, cat_col_names, pandas_categorical)."""
    cat_cols = [c for c in data.columns
                if str(data[c].dtype) == "category"]
    if pandas_categorical is None:
        pandas_categorical = [list(data[c].cat.categories) for c in cat_cols]
    else:
        if len(cat_cols) != len(pandas_categorical):
            raise ValueError("train and valid dataset categorical_feature "
                             "do not match.")
        data = data.copy()
        for col, cats in zip(cat_cols, pandas_categorical):
            if list(data[col].cat.categories) != list(cats):
                data[col] = data[col].cat.set_categories(cats)
    if cat_cols:
        data = data.copy()
        for c in cat_cols:
            # code -1 means NaN or a level outside the train categories —
            # route it through the missing-value path, not as a phantom
            # category (reference _data_from_pandas replace({-1: nan}))
            codes = data[c].cat.codes.to_numpy().astype(np.float64)
            codes[codes == -1] = np.nan
            data[c] = codes
    return (np.asarray(data.values, dtype=np.float64), cat_cols,
            pandas_categorical)


def _load_pandas_categorical(model_str: str):
    """Last-line ``pandas_categorical:<json>`` of a model file
    (reference basic.py:277-289)."""
    import json
    last = model_str.rstrip().rsplit("\n", 1)[-1]
    if last.startswith("pandas_categorical:"):
        return json.loads(last[len("pandas_categorical:"):])
    return None


class Dataset:
    """Lazily-constructed training dataset over a numpy array, a pandas frame, a text file or a ``scipy.sparse`` matrix (basic.py:548+ semantics).

    A ``scipy.sparse`` matrix of any format is read as CSR over its own
    buffers and binned from its stored entries (``data/sparse.py``,
    ``data/dataset.construct_csr``): no dense matrix is made, for training
    data and for a ``reference=`` validation set alike."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = False, silent: bool = False):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._constructed: Optional[TrainingData] = None
        self.raw: Optional[np.ndarray] = None
        self.pandas_categorical: Optional[List[List]] = None

    # -- lazy construction --------------------------------------------------

    def _distributed_row_selection(self, cfg: Config,
                                   n_rows: int) -> Optional[np.ndarray]:
        """Row→machine assignment when several processes train
        data/voting-parallel from the SAME data file without
        pre-partitioning (dataset_loader.cpp LoadTextDataToMemory:563-607):
        a shared-seed random draw per row — per QUERY when query data
        exists — keeps exactly the rows assigned to this rank, so the
        union over ranks is a disjoint cover of the file.  Caller
        established the dist-rows predicate and the distributed runtime."""
        import jax
        if jax.process_count() <= 1:
            return None
        from .utils.random import make_rng
        nm = jax.process_count()
        rank = jax.process_index()
        rng = make_rng(cfg.data_random_seed)
        if self.group is not None:
            counts = np.asarray(self.group, dtype=np.int64)
            assign = rng.integers(0, nm, size=len(counts))
            row_q = np.repeat(np.arange(len(counts)), counts)
            sel = np.flatnonzero(assign[row_q] == rank)
            self.group = counts[assign == rank]
        else:
            assign = rng.integers(0, nm, size=n_rows)
            sel = np.flatnonzero(assign == rank)
        log.info("Distributed loading: rank %d keeps %d of %d rows",
                 rank, len(sel), n_rows)
        return sel

    def construct(self, config: Optional[Config] = None) -> "Dataset":
        cfg = config or config_from_params(self.params)
        # shared-file row distribution applies to the TRAIN file only —
        # validation data (reference set) stays whole on every rank, like
        # the reference's LoadFromFileAlignWithOtherDataset
        dist_intent = (cfg.num_machines > 1 and not cfg.is_pre_partition
                       and cfg.tree_learner in ("data", "voting")
                       and self.reference is None)
        dist_rows = dist_intent and isinstance(self.data, (str, os.PathLike))
        if self._constructed is not None:
            if (dist_intent and getattr(self, "_loaded_from_file", False)
                    and not getattr(self, "_dist_sharded", False)):
                # constructed earlier without the distribution params
                # (e.g. num_data() before train()): training data-parallel
                # on full per-rank replicas would double-count every row —
                # rebuild from the file with the real config
                if not isinstance(self.data, (str, os.PathLike)):
                    log.fatal(
                        "Dataset was constructed without distributed row "
                        "partitioning and the raw file reference was "
                        "freed; pass the num_machines/tree_learner params "
                        "to the Dataset or construct it inside train()")
                log.warning("Reconstructing dataset with distributed row "
                            "partitioning (it was first constructed "
                            "without the parallel params)")
                self._constructed = None
                if getattr(self, "_label_from_file", False):
                    self.label = None   # reload file labels at full length;
                                        # a user-supplied label is kept and
                                        # sharded by [sel] like weight
            else:
                return self
        # the one place binning happens: load, bin and pack, as one span
        with obs_trace.phase("dataset.construct"):
            return self._build(cfg, config, dist_rows)

    def _build(self, cfg: Config, config: Optional[Config],
               dist_rows: bool) -> "Dataset":
        if dist_rows:
            # bring the distributed runtime up BEFORE any jax backend
            # touch, so an early construct() (num_data, save_binary, ...)
            # shards exactly like the one inside train() — idempotent
            from .parallel.mesh import init_distributed_from_config
            init_distributed_from_config(cfg)
            if cfg.use_two_round_loading:
                log.warning("use_two_round_loading falls back to in-memory "
                            "loading when rows are distributed across "
                            "machines (set pre_partition=true to stream "
                            "per-machine files)")
        elif isinstance(self.data, (str, os.PathLike)) \
                and self.reference is None:
            # CheckCanLoadFromBin (dataset_loader.cpp:980-1018): prefer an
            # existing "<data>.bin" cache; accept the data file itself
            # being a binary cache
            path = str(self.data)
            for candidate in (path + ".bin", path):
                if self._is_binary_cache(candidate):
                    log.info("Loading dataset from binary cache %s",
                             candidate)
                    self._constructed = \
                        self._load_binary_training_data(candidate)
                    # user-supplied fields override the cached metadata
                    # (reference binary load + set_field flow)
                    if self.label is not None:
                        self.set_label(self.label)
                    else:
                        self.label = self._constructed.metadata.label
                    if self.weight is not None:
                        self.set_weight(self.weight)
                    if self.group is not None:
                        self.set_group(self.group)
                    if self.init_score is not None:
                        self.set_init_score(self.init_score)
                    self._loaded_from_file = True
                    self._dist_sharded = False
                    return self
        if (isinstance(self.data, (str, os.PathLike))
                and cfg.use_two_round_loading and self.reference is None
                and not dist_rows):
            # two-round streamed loading (dataset_loader.cpp:181-207): the
            # raw float matrix never materializes — sample pass, then a
            # chunked bin-as-you-read pass into the final uint8/16 matrix
            path = str(self.data)
            meta_probe = data_mod.Metadata(0)
            meta_probe.load_side_files(path)
            names = (list(self.feature_name)
                     if isinstance(self.feature_name, (list, tuple))
                     else (read_header_names(path, 0) if cfg.has_header
                           else None))
            cat_idx: List[int] = []
            if isinstance(self.categorical_feature, (list, tuple)):
                for c in self.categorical_feature:
                    if isinstance(c, str) and names and c in names:
                        cat_idx.append(names.index(c))
                    elif not isinstance(c, str):
                        cat_idx.append(int(c))
            self._constructed = data_mod.construct_streamed(
                path, cfg,
                label=(None if self.label is None
                       else np.asarray(self.label, np.float32).ravel()),
                weight=meta_probe.weight if self.weight is None
                else np.asarray(self.weight),
                group=(np.diff(meta_probe.query_boundaries)
                       if self.group is None
                       and meta_probe.query_boundaries is not None
                       else self.group),
                init_score=meta_probe.init_score if self.init_score is None
                else np.asarray(self.init_score),
                feature_names=names, categorical_features=cat_idx)
            self.label = self._constructed.metadata.label
            self.raw = None
            self._loaded_from_file = True
            self._dist_sharded = False
            if cfg.is_save_binary_file:
                self._save_binary_cache()
            if self.free_raw_data:
                self.data = None
            return self
        sparse = data_mod.sparse.from_scipy(self.data)
        if sparse is not None:      # scipy.sparse of any format, as CSR
            self.data = sparse
        if isinstance(self.data, data_mod.CsrMatrix):
            # sparse ingest (scipy.sparse, the C ABI): binned from the
            # stored entries — no dense matrix of any width materializes
            # (data/sparse.py, dataset.construct_csr)
            names = (list(self.feature_name)
                     if isinstance(self.feature_name, (list, tuple))
                     else None)
            cat_idx: List[int] = []
            if isinstance(self.categorical_feature, (list, tuple)):
                for c in self.categorical_feature:
                    if isinstance(c, str) and names and c in names:
                        cat_idx.append(names.index(c))
                    elif not isinstance(c, str):
                        cat_idx.append(int(c))
            ref = self.reference.construct(config)._constructed \
                if self.reference is not None else None
            self._constructed = data_mod.construct_csr(
                self.data, cfg,
                label=(None if self.label is None
                       else np.asarray(self.label, np.float32).ravel()),
                weight=(None if self.weight is None
                        else np.asarray(self.weight)),
                group=None if self.group is None else np.asarray(self.group),
                init_score=(None if self.init_score is None
                            else np.asarray(self.init_score)),
                feature_names=names, categorical_features=cat_idx,
                reference=ref)
            self.raw = None
            self._loaded_from_file = False
            self._dist_sharded = False
            if self.free_raw_data:
                self.data = None
            return self
        pd_cat_cols: List = []   # pandas category-dtype columns, by name
        if isinstance(self.data, (str, os.PathLike)):
            path = str(self.data)
            feats, labels, names = load_text_file(
                path, has_header=cfg.has_header, label_idx=0)
            if self.label is None:
                self.label = labels
                self._label_from_file = True
            mat = feats
            if names and self.feature_name == "auto":
                self.feature_name = names
            # side files: .weight / .query / .init
            meta_probe = data_mod.Metadata(len(labels))
            meta_probe.load_side_files(path)
            if self.weight is None and meta_probe.weight is not None:
                self.weight = meta_probe.weight
            if self.group is None and meta_probe.query_boundaries is not None:
                self.group = np.diff(meta_probe.query_boundaries)
            if self.init_score is None and meta_probe.init_score is not None:
                self.init_score = meta_probe.init_score
            sel = self._distributed_row_selection(cfg, len(mat)) \
                if dist_rows else None
            self._loaded_from_file = True
            self._dist_sharded = sel is not None
            self._want_binary_save = (cfg.is_save_binary_file
                                      and sel is None)
            if sel is not None:   # this rank's shard of the shared file
                n_full = len(mat)
                mat = mat[sel]
                if self.label is not None:
                    self.label = np.asarray(self.label)[sel]
                if self.weight is not None:
                    self.weight = np.asarray(self.weight)[sel]
                if self.init_score is not None:
                    init = np.asarray(self.init_score)
                    k = max(int(getattr(cfg, "num_class", 1) or 1), 1)
                    if k > 1 and init.size == k * n_full:
                        # flattened [num_class, N] layout: select the
                        # shard's rows within every class block
                        init = init.reshape(k, n_full)[:, sel].ravel()
                    else:
                        init = init[sel]
                    self.init_score = init
                # self.group was already partitioned by query unit
        elif hasattr(self.data, "columns") and hasattr(self.data, "dtypes"):
            # pandas: category-dtype columns become their codes, with the
            # train dataset's category levels re-aligning valid data
            # (reference _data_from_pandas)
            ref_pc = (self.reference.pandas_categorical
                      if self.reference is not None
                      else self.pandas_categorical)
            mat, pd_cat_cols, self.pandas_categorical = \
                _data_from_pandas(self.data, ref_pc)
        else:
            mat = _to_matrix(self.data)

        cat_idx: List[int] = []
        names: Optional[List[str]] = None
        if isinstance(self.feature_name, (list, tuple)):
            names = list(self.feature_name)
        if hasattr(self.data, "columns"):   # pandas
            cols = [str(c) for c in self.data.columns]
            if names is None:
                names = cols
            explicit = (list(self.categorical_feature)
                        if self.categorical_feature not in ("auto", None)
                        else [])
            # category-dtype columns are categorical features regardless
            # of the explicit list (reference basic.py:241-247)
            for c in explicit + [str(c) for c in pd_cat_cols]:
                idx = cols.index(c) if isinstance(c, str) else int(c)
                if idx not in cat_idx:
                    cat_idx.append(idx)
        elif isinstance(self.categorical_feature, (list, tuple)):
            for c in self.categorical_feature:
                if isinstance(c, str) and names and c in names:
                    cat_idx.append(names.index(c))
                elif not isinstance(c, str):
                    cat_idx.append(int(c))

        ref = self.reference.construct(config)._constructed \
            if self.reference is not None else None
        label = np.asarray(self.label, dtype=np.float32).ravel() \
            if self.label is not None else None
        self._constructed = construct(
            mat, cfg, label=label,
            weight=None if self.weight is None else np.asarray(self.weight),
            group=None if self.group is None else np.asarray(self.group),
            init_score=None if self.init_score is None
            else np.asarray(self.init_score),
            feature_names=names, categorical_features=cat_idx, reference=ref)
        self.raw = mat if not self.free_raw_data else None
        if getattr(self, "_want_binary_save", False):
            self._want_binary_save = False
            self._save_binary_cache()
        if self.free_raw_data:
            self.data = None
        return self

    def _save_binary_cache(self) -> None:
        """is_save_binary_file: write the "<data>.bin" cache next to the
        text file (dataset_loader.cpp SaveBinaryFile flow)."""
        bin_path = str(self.data) + ".bin"
        self.save_binary(bin_path)
        log.info("Saved binary dataset cache to %s", bin_path)

    @property
    def constructed(self) -> TrainingData:
        if self._constructed is None:
            self.construct()
        return self._constructed

    # -- reference-like helpers --------------------------------------------

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params)

    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._constructed is not None:
            self._constructed.metadata.set_label(np.asarray(label))
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._constructed is not None:
            self._constructed.metadata.set_weight(
                None if weight is None else np.asarray(weight))
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._constructed is not None:
            self._constructed.metadata.set_query(
                None if group is None else np.asarray(group))
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._constructed is not None:
            self._constructed.metadata.set_init_score(
                None if init_score is None else np.asarray(init_score))
        return self

    def get_label(self):
        return (np.asarray(self.constructed.metadata.label)
                if self.constructed.metadata.label is not None else None)

    def get_weight(self):
        return self.constructed.metadata.weight

    def get_group(self):
        qb = self.constructed.metadata.query_boundaries
        return None if qb is None else np.diff(qb)

    def get_init_score(self):
        return self.constructed.metadata.init_score

    def set_field(self, field_name: str, data) -> "Dataset":
        """Generic metadata setter (reference Dataset.set_field)."""
        setters = {"label": self.set_label, "weight": self.set_weight,
                   "group": self.set_group, "query": self.set_group,
                   "init_score": self.set_init_score}
        if field_name not in setters:
            raise ValueError(f"Unknown field {field_name!r}")
        return setters[field_name](data)

    def get_field(self, field_name: str):
        getters = {"label": self.get_label, "weight": self.get_weight,
                   "group": self.get_group, "query": self.get_group,
                   "init_score": self.get_init_score}
        if field_name not in getters:
            raise ValueError(f"Unknown field {field_name!r}")
        return getters[field_name]()

    def set_feature_name(self, feature_name) -> "Dataset":
        if feature_name == "auto":     # reference sentinel: keep as-is
            return self
        self.feature_name = list(feature_name)
        if self._constructed is not None:
            self._constructed.feature_names = list(feature_name)
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        if self._constructed is not None and \
                categorical_feature != self.categorical_feature:
            log.warning("categorical_feature change after construction "
                        "requires reconstructing the Dataset")
            self._constructed = None
        self.categorical_feature = categorical_feature
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        if self._constructed is not None and reference is not self.reference:
            self._constructed = None   # rebin against the new reference
        self.reference = reference
        return self

    def get_ref_chain(self, ref_limit: int = 100):
        """Set of datasets reachable through reference links."""
        chain, cur = [], self
        while cur is not None and len(chain) < ref_limit:
            chain.append(cur)
            cur = cur.reference
        return set(chain)

    def ensure_raw(self) -> Optional[np.ndarray]:
        """Raw feature matrix for the consumers that need one (cv, subset,
        continued training).  When the dataset was constructed without
        materializing it — binary-cache load or streamed loading — the
        matrix is recovered by re-parsing the original text file, provided
        that file still exists, is not itself a cache, and agrees with the
        constructed row count (guards against stale caches)."""
        if self.raw is not None:
            return self.raw
        if isinstance(self.data, data_mod.CsrMatrix):
            # chunk-assembled full densify — only the consumers that
            # genuinely need the whole matrix pay for it
            self.raw = np.asarray(self.data)
            return self.raw
        if isinstance(self.data, (str, os.PathLike)) \
                and not self._is_binary_cache(str(self.data)):
            cfg = config_from_params(self.params)
            try:
                feats, _, _ = load_text_file(str(self.data),
                                             has_header=cfg.has_header)
            except Exception as e:
                log.warning("Could not recover raw data from %s: %s",
                            self.data, e)
                return None
            if self._constructed is not None \
                    and len(feats) != self._constructed.num_data:
                log.warning("Raw file %s has %d rows but the constructed "
                            "dataset has %d — refusing the mismatch",
                            self.data, len(feats),
                            self._constructed.num_data)
                return None
            self.raw = feats
            return self.raw
        return None

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row-subset Dataset sharing this dataset's bin mappers
        (reference Dataset.subset; requires raw data retained in memory)."""
        self.construct()
        raw = self.ensure_raw()
        if raw is None:
            log.fatal("Cannot subset: raw data not in memory (construct "
                      "with free_raw_data=False from an in-memory matrix)")
        idx = np.asarray(used_indices, dtype=np.int64)
        label = self.get_label()
        w = self.get_weight()
        init = self.get_init_score()
        group = self.get_group()
        sub_group = None
        if group is not None:
            # per-row query ids -> counts of SELECTED rows per query, empty
            # queries dropped (row subset of grouped data keeps group
            # structure like the reference's index-based subset)
            qid = np.repeat(np.arange(len(group)), group.astype(np.int64))
            counts = np.bincount(qid[idx], minlength=len(group))
            sub_group = counts[counts > 0]
        return Dataset(raw[idx],
                       label=None if label is None else label[idx],
                       weight=None if w is None else np.asarray(w)[idx],
                       group=sub_group,
                       init_score=None if init is None
                       else np.asarray(init)[idx],
                       reference=self,
                       params=dict(params or self.params))

    def num_data(self) -> int:
        return self.constructed.num_data

    def num_feature(self) -> int:
        return self.constructed.num_total_features

    # token identifying our binary dataset cache files — the analogue of
    # Dataset::binary_file_token checked by CheckCanLoadFromBin.  The
    # payload is npz + JSON, loaded with allow_pickle=False: a cache file
    # is DATA, never executable (unlike pickle).
    BINARY_TOKEN = b"lightgbm_tpu.dataset.v2\n"

    def save_binary(self, filename: str, compress: bool = True) -> "Dataset":
        """Binary dataset cache (Dataset::SaveBinaryFile analogue).

        ``compress=False`` skips zlib (the reference's binary file is also
        raw) — random bin indices barely compress and the deflate pass
        dominates save time on large matrices."""
        import io
        import json
        c = self.constructed
        mappers = [{
            "num_bin": int(m.num_bin), "bin_type": int(m.bin_type),
            "missing_type": int(m.missing_type),
            "is_trivial": bool(m.is_trivial),
            "bin_upper_bound": (None if m.bin_upper_bound is None
                                else [float(x) for x in m.bin_upper_bound]),
            "categorical_2_bin": (None if m.categorical_2_bin is None
                                  else {str(k): int(v) for k, v
                                        in m.categorical_2_bin.items()}),
            "bin_2_categorical": (None if m.bin_2_categorical is None
                                  else [int(x) for x in m.bin_2_categorical]),
            "min_val": float(m.min_val), "max_val": float(m.max_val),
            "default_bin": int(m.default_bin),
        } for m in c.bin_mappers]
        meta = {
            "mappers": mappers,
            "feature_names": list(c.feature_names or []),
            "num_total_features": int(c.num_total_features),
            "used_features": [int(x) for x in c.used_features],
            "bundles": (None if c.layout is None
                        else [[int(j) for j in b] for b in c.layout.bundles]),
        }
        arrays = {"binned": np.asarray(c.binned),
                  "meta_json": np.frombuffer(
                      json.dumps(meta).encode(), dtype=np.uint8).copy()}
        for key, val in (("label", c.metadata.label),
                         ("weight", c.metadata.weight),
                         ("query_boundaries", c.metadata.query_boundaries),
                         ("init_score", c.metadata.init_score)):
            if val is not None:
                arrays[key] = np.asarray(val)
        buf = io.BytesIO()
        (np.savez_compressed if compress else np.savez)(buf, **arrays)
        with open(filename, "wb") as f:
            f.write(Dataset.BINARY_TOKEN)
            f.write(buf.getvalue())
        return self

    @staticmethod
    def _is_binary_cache(filename: str) -> bool:
        try:
            with open(filename, "rb") as f:
                return f.read(len(Dataset.BINARY_TOKEN)) == \
                    Dataset.BINARY_TOKEN
        except OSError:
            return False

    @staticmethod
    def _load_binary_training_data(filename: str) -> TrainingData:
        import io
        import json
        from .data.binning import BinMapper
        from .data.bundling import BundleLayout
        with open(filename, "rb") as f:
            head = f.read(len(Dataset.BINARY_TOKEN))
            if head != Dataset.BINARY_TOKEN:
                raise ValueError(f"{filename} is not a lightgbm_tpu binary "
                                 "dataset cache")
            npz = np.load(io.BytesIO(f.read()), allow_pickle=False)
        meta = json.loads(bytes(npz["meta_json"]).decode())
        td = TrainingData()
        td.binned = npz["binned"]
        td.used_features = list(meta["used_features"])
        td.feature_names = meta["feature_names"]
        td.num_total_features = meta["num_total_features"]
        td.num_data = len(td.binned)
        td.bin_mappers = []
        for d in meta["mappers"]:
            m = BinMapper()
            m.num_bin = d["num_bin"]
            m.bin_type = d["bin_type"]
            m.missing_type = d["missing_type"]
            m.is_trivial = d["is_trivial"]
            m.bin_upper_bound = (None if d["bin_upper_bound"] is None else
                                 np.asarray(d["bin_upper_bound"], np.float64))
            m.categorical_2_bin = (None if d["categorical_2_bin"] is None
                                   else {int(k): v for k, v
                                         in d["categorical_2_bin"].items()})
            m.bin_2_categorical = d["bin_2_categorical"]
            m.min_val = d["min_val"]
            m.max_val = d["max_val"]
            m.default_bin = d["default_bin"]
            td.bin_mappers.append(m)
        if meta.get("bundles") is not None:
            td.layout = BundleLayout(meta["bundles"], td.bin_mappers,
                                     td.used_features)
        td.metadata = data_mod.Metadata(td.num_data)
        td.metadata.set_label(npz["label"] if "label" in npz else None)
        td.metadata.set_weight(npz["weight"] if "weight" in npz else None)
        td.metadata.query_boundaries = (npz["query_boundaries"]
                                        if "query_boundaries" in npz else None)
        td.metadata.set_init_score(npz["init_score"]
                                   if "init_score" in npz else None)
        return td

    @staticmethod
    def load_binary(filename: str) -> "Dataset":
        ds = Dataset(None)
        ds._constructed = Dataset._load_binary_training_data(filename)
        return ds


class Booster:
    """Training/prediction handle; ``predict`` takes a numpy array, a pandas frame, a text file or a ``scipy.sparse`` matrix (basic.py:1213+ semantics)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent: bool = False):
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._train_dataset = train_set
        self.pandas_categorical: Optional[List[List]] = None
        if train_set is not None:
            cfg = config_from_params(self.params)
            log.set_verbosity(cfg.verbose)
            train_set.construct(cfg)
            self.pandas_categorical = train_set.pandas_categorical
            objective = create_objective(cfg)
            self.inner: GBDT = create_boosting(cfg, train_set.constructed,
                                               objective)
        elif model_file is not None:
            with open(model_file) as f:
                content = f.read()
            self.inner = GBDT.load_from_string(
                content, config_from_params(self.params))
            self.pandas_categorical = _load_pandas_categorical(content)
        elif model_str is not None:
            self.inner = GBDT.load_from_string(
                model_str, config_from_params(self.params))
            self.pandas_categorical = _load_pandas_categorical(model_str)
        else:
            raise ValueError("Booster needs train_set, model_file or model_str")

    # -- training loop primitives ------------------------------------------

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct(self.inner.config)
        self.inner.add_valid_set(data.constructed, name)
        self._valid_datasets = getattr(self, "_valid_datasets", [])
        self._valid_datasets.append(data)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; custom objective fobj(preds, train_data) ->
        (grad, hess) like the reference."""
        if fobj is None:
            return self.inner.train_one_iter()
        scores = np.asarray(self.inner.scores, np.float64)
        preds = scores.reshape(-1) if scores.shape[0] > 1 else scores[0]
        grad, hess = fobj(preds, self._train_dataset)
        return self.inner.train_one_iter(np.asarray(grad), np.asarray(hess))

    def rollback_one_iter(self) -> "Booster":
        self.inner.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        return self.inner.current_iteration()

    def attr(self, key: str):
        """Free-form model attribute (reference Booster.attr)."""
        return getattr(self, "_attr", {}).get(key)

    def set_attr(self, **kwargs) -> "Booster":
        store = getattr(self, "_attr", {})
        for k, v in kwargs.items():
            if v is None:
                store.pop(k, None)
            else:
                store[k] = str(v)
        self._attr = store
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        self._train_data_name = name
        return self

    def free_dataset(self) -> "Booster":
        """Release the training/validation data (binned matrices, scores,
        bag subsets) — predict/save/dump still work; further training and
        eval do not (reference Booster.free_dataset contract)."""
        self._train_dataset = None
        self._valid_datasets = []
        inner = self.inner
        inner.train_set = None
        inner.valid_sets = []
        inner.bins = None
        inner.scores = None
        inner._subset_state = None
        inner._local_bins_cache = None
        inner._stream_store = None
        inner._streamer = None
        return self

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """Raw leaf output; tree_id indexes the stored model list directly,
        INCLUDING the boost-from-average init tree when present — the
        reference pushes that init tree into models_ too
        (gbdt.cpp:467-483), so the numbering matches."""
        return float(self.inner.models[tree_id].leaf_value[leaf_id])

    def set_leaf_output(self, tree_id: int, leaf_id: int,
                        value: float) -> "Booster":
        """LGBM_BoosterSetLeafValue analogue: overwrite one leaf's raw
        output (same tree numbering as get_leaf_output)."""
        self.inner.models[tree_id].leaf_value[leaf_id] = float(value)
        self.inner._drop_serving_caches()   # serving caches now stale
        return self

    def merge(self, other: "Booster") -> "Booster":
        """LGBM_BoosterMerge: prepend other's trees to this model
        (reference GBDT::MergeFrom ordering)."""
        self.inner.merge_from(other.inner)
        return self

    def eval(self, data: Dataset, name: str, feval=None):
        """Evaluate the current model on an arbitrary dataset
        (reference Booster.eval)."""
        datasets = getattr(self, "_valid_datasets", [])
        for i, vs in enumerate(self.inner.valid_sets):
            if i < len(datasets) and datasets[i] is data:
                break
        else:
            self.add_valid(data, name)   # not attached: score from scratch
            vs = self.inner.valid_sets[-1]
        res = [(name, m, v, h) for (_, m, v, h)
               in self.inner._eval(vs.name, vs.metrics, vs.scores)]
        return self._add_feval(res, name, feval, vs.scores, data)

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        canon = canonicalize_params(params)
        for k, v in canon.items():
            setattr(self.inner.config, k, type(getattr(self.inner.config, k))(v)
                    if not isinstance(getattr(self.inner.config, k), list) else v)
        self.params.update(canon)   # keep the param record in sync (reference
        return self                 # Booster.reset_parameter does the same)

    # -- evaluation ---------------------------------------------------------

    def eval_train(self, feval=None):
        res = self.inner.eval_train()
        return self._add_feval(res, "training", feval,
                               self.inner.scores, self._train_dataset)

    def eval_valid(self, feval=None):
        res = self.inner.eval_valid()
        if feval is not None:
            datasets = getattr(self, "_valid_datasets", [])
            for i, vs in enumerate(self.inner.valid_sets):
                ds = datasets[i] if i < len(datasets) else None
                res = self._add_feval(res, vs.name, feval, vs.scores, ds)
        return res

    def _add_feval(self, res, name, feval, scores, dataset):
        if feval is not None:
            scores = np.asarray(scores, np.float64)
            preds = scores.reshape(-1) if scores.shape[0] > 1 else scores[0]
            out = feval(preds, dataset)
            if isinstance(out, tuple):
                out = [out]
            for metric, value, is_higher_better in out:
                res = list(res) + [(name, metric, value, is_higher_better)]
        return res

    # -- prediction / io ----------------------------------------------------

    def predict(self, data, num_iteration: int = -1, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                pred_early_stop: bool = False,
                pred_parameter: Optional[Dict[str, Any]] = None, **kwargs):
        if isinstance(data, (str, os.PathLike)):
            feats, _, _ = load_text_file(str(data),
                                         has_header=self.inner.config.has_header)
            data = feats
        elif hasattr(data, "columns") and hasattr(data, "dtypes"):
            data = _data_from_pandas(data, self.pandas_categorical)[0]
        else:
            sparse = (data if isinstance(data, data_mod.CsrMatrix)
                      else data_mod.sparse.from_scipy(data))
            if sparse is not None and len(sparse):
                # one budget-bounded dense chunk at a time; per-row output
                # width is fixed, so the chunks' outputs concatenate
                args = dict(num_iteration=num_iteration, raw_score=raw_score,
                            pred_leaf=pred_leaf, pred_contrib=pred_contrib,
                            pred_early_stop=pred_early_stop,
                            pred_parameter=pred_parameter, **kwargs)
                return np.concatenate(
                    [self.predict(block, **args)
                     for _, block in sparse.iter_dense_chunks()], axis=0)
            data = _to_matrix(data)
        if num_iteration is None or num_iteration <= 0:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        # reference basic.py predict accepts per-call prediction params
        # (pred_parameter dict); merge with the keyword forms
        pp = canonicalize_params(pred_parameter or {})
        pred_early_stop = bool(pp.get("pred_early_stop", pred_early_stop))
        pred_leaf = bool(pp.get("is_predict_leaf_index", pred_leaf))
        pred_contrib = bool(pp.get("is_predict_contrib", pred_contrib))
        raw_score = bool(pp.get("is_predict_raw_score", raw_score))
        es_freq = pp.get("pred_early_stop_freq")
        es_margin = pp.get("pred_early_stop_margin")
        return self.inner.predict(
            data, num_iteration=num_iteration, raw_score=raw_score,
            pred_leaf=pred_leaf, pred_contrib=pred_contrib,
            pred_early_stop=pred_early_stop,
            pred_early_stop_freq=None if es_freq is None else int(es_freq),
            pred_early_stop_margin=(None if es_margin is None
                                    else float(es_margin)))

    def predict_engine(self, prewarm: bool = True, buckets=None):
        """Build (or return the cached) SoA serving engine for this model
        — the flatten + device threshold tables + pre-warmed microbatch
        executables of docs/SERVING.md.  Called once at model
        load/finalize by the serving loop; subsequent ``predict`` calls
        reuse it through the cached :class:`Predictor` engine."""
        return self.inner.predict_engine(prewarm=prewarm, buckets=buckets)

    def save_model(self, filename: str, num_iteration: int = -1) -> "Booster":
        if num_iteration is None or num_iteration <= 0:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        self.inner.save_model(filename, num_iteration)
        if self.pandas_categorical:
            # trailing mapping line, ignored by model parsers (reference
            # _save_pandas_categorical)
            import json
            with open(filename, "a") as f:
                f.write("\npandas_categorical:"
                        + json.dumps(self.pandas_categorical) + "\n")
        return self

    def model_to_string(self, num_iteration: int = -1) -> str:
        s = self.inner.save_model_to_string(num_iteration)
        if self.pandas_categorical:
            import json
            s += ("\npandas_categorical:"
                  + json.dumps(self.pandas_categorical) + "\n")
        return s

    def dump_model(self, num_iteration: int = -1) -> Dict:
        """JSON model dump (gbdt.cpp DumpModel)."""
        inner = self.inner
        trees = inner.models
        if num_iteration > 0:
            cut = (num_iteration + (1 if inner.boost_from_average_ else 0)) \
                * inner.num_class
            trees = trees[:cut]
        return {
            "name": "tree",
            "version": "v2",
            "num_class": inner.num_class,
            "num_tree_per_iteration": inner.num_class,
            "label_index": inner.label_idx,
            "max_feature_idx": inner.max_feature_idx,
            "objective": inner.objective.to_string() if inner.objective else "",
            "average_output": inner.average_output,
            "feature_names": inner.feature_names,
            "tree_info": [t.to_json(i) for i, t in enumerate(trees)],
        }

    def feature_importance(self, importance_type: str = "split",
                           iteration: int = -1) -> np.ndarray:
        return self.inner.feature_importance(importance_type, iteration)

    def feature_name(self) -> List[str]:
        return list(self.inner.feature_names)

    def num_trees(self) -> int:
        return len(self.inner.models)

    def num_feature(self) -> int:
        return self.inner.max_feature_idx + 1

    # pickle support: serialize via model string
    def __getstate__(self):
        state = {"params": self.params,
                 "best_iteration": self.best_iteration,
                 "best_score": self.best_score,
                 "model_str": self.inner.save_model_to_string(-1)}
        return state

    def __setstate__(self, state):
        self.params = state["params"]
        self.best_iteration = state["best_iteration"]
        self.best_score = state["best_score"]
        self._train_dataset = None
        self.inner = GBDT.load_from_string(
            state["model_str"], config_from_params(self.params))
