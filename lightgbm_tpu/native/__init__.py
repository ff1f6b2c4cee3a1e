"""ctypes bindings for the native host runtime (gbt_native.cpp).

The reference's data layer and serving path are C++ (parser.hpp, bin.cpp,
predictor.hpp); this package provides the same split for the TPU framework:
text parsing, value->bin quantization and model prediction run in an
OpenMP-parallel shared library, while training compute stays on TPU.

The library builds on demand with g++ (cached next to the source, keyed
on a hash of the sources + the Python ABI so a copied or stale binary is
rebuilt, never loaded); when no toolchain is available every entry point
degrades to the pure-python implementations, so the native layer is an
accelerator, not a dependency.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sysconfig
import threading
from typing import List, Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "gbt_native.cpp")
_SRC_TRAIN = os.path.join(_DIR, "gbt_capi_train.cpp")
_LIB_PATH = os.path.join(_DIR, "_gbt_native.so")
_KEY_PATH = _LIB_PATH + ".key"     # sidecar: _source_key() of the build

_lock = threading.Lock()
_lib = None
_load_failed = False
_has_train_api = False


def _source_key() -> str:
    """What a binary must have been built from to be loadable: both
    sources and the Python ABI the training shim links against."""
    h = hashlib.sha256((sysconfig.get_config_var("SOABI") or "").encode())
    for src in (_SRC, _SRC_TRAIN):
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _build_is_current() -> bool:
    try:
        with open(_KEY_PATH) as f:
            recorded = f.read().strip()
    except OSError:
        return False
    return os.path.exists(_LIB_PATH) and recorded == _source_key()


def _build() -> bool:
    """Compile to a temporary name, drop the old key, then move binary
    and key into place — at no point does a key sit beside a binary it
    does not describe, whatever a concurrent or interrupted build does."""
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    try:
        if not _compile(tmp):
            return False
        if os.path.exists(_KEY_PATH):
            os.remove(_KEY_PATH)
        os.replace(tmp, _LIB_PATH)
        with open(tmp, "w") as f:
            f.write(_source_key())
        os.replace(tmp, _KEY_PATH)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return True


def _compile(out_path: str) -> bool:
    import sys
    base = ["g++", "-O3", "-shared", "-fPIC", "-o", out_path]
    # preferred: serving runtime + the CPython-embedding training ABI,
    # linked against libpython so standalone C callers (and hosts whose
    # python binary does not re-export libpython symbols) resolve Py_*
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    pylib = f"python{sys.version_info.major}.{sys.version_info.minor}"
    link = ([f"-L{libdir}", f"-l{pylib}", f"-Wl,-rpath,{libdir}"]
            if libdir else [])
    with_train = base + ["-std=c++14", "-fopenmp", _SRC, _SRC_TRAIN,
                         "-I" + sysconfig.get_paths()["include"]] + link
    # fallbacks: unlinked shim (static-python hosts), no training shim
    # (no Python headers), then no OpenMP
    attempts = [with_train,
                [c for c in with_train if c not in link],
                [c for c in with_train if c != "-fopenmp"],
                base + ["-std=c++11", "-fopenmp", _SRC],
                base + ["-std=c++11", _SRC]]
    for cmd in attempts:
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=300)
        except (OSError, subprocess.TimeoutExpired):
            return False
        if proc.returncode == 0:
            return True
    return False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c_ll, c_i, c_p = ctypes.c_longlong, ctypes.c_int, ctypes.c_void_p
    c_d_p = ctypes.POINTER(ctypes.c_double)
    c_f_p = ctypes.POINTER(ctypes.c_float)
    c_i_p = ctypes.POINTER(ctypes.c_int)
    c_ll_p = ctypes.POINTER(ctypes.c_longlong)

    lib.GBTN_ParseFile.restype = c_p
    lib.GBTN_ParseFile.argtypes = [ctypes.c_char_p, c_i, c_i]
    lib.GBTN_ParsedRows.restype = c_ll
    lib.GBTN_ParsedRows.argtypes = [c_p]
    lib.GBTN_ParsedCols.restype = c_ll
    lib.GBTN_ParsedCols.argtypes = [c_p]
    lib.GBTN_ParsedError.restype = ctypes.c_char_p
    lib.GBTN_ParsedError.argtypes = [c_p]
    lib.GBTN_ParsedCopy.restype = None
    lib.GBTN_ParsedCopy.argtypes = [c_p, c_d_p, c_f_p]
    lib.GBTN_ParsedFree.restype = None
    lib.GBTN_ParsedFree.argtypes = [c_p]

    lib.GBTN_BinColumn.restype = None
    lib.GBTN_BinColumn.argtypes = [c_d_p, c_ll, c_d_p, c_i, c_i, c_i, c_p]
    lib.GBTN_GreedyFindBin.restype = c_i
    lib.GBTN_GreedyFindBin.argtypes = [c_d_p, c_ll_p, c_i, c_i, c_ll, c_i,
                                       c_d_p]
    lib.GBTN_BinColumnCategorical.restype = None
    lib.GBTN_BinColumnCategorical.argtypes = [c_d_p, c_ll, c_ll_p, c_i_p,
                                              c_i, c_i, c_i, c_p]

    lib.GBTN_LoadModelString.restype = c_p
    lib.GBTN_LoadModelString.argtypes = [ctypes.c_char_p]
    lib.GBTN_LoadModelFile.restype = c_p
    lib.GBTN_LoadModelFile.argtypes = [ctypes.c_char_p]
    lib.GBTN_ModelError.restype = ctypes.c_char_p
    lib.GBTN_ModelError.argtypes = [c_p]
    lib.GBTN_ModelNumClass.restype = c_i
    lib.GBTN_ModelNumClass.argtypes = [c_p]
    lib.GBTN_ModelNumTrees.restype = c_i
    lib.GBTN_ModelNumTrees.argtypes = [c_p]
    lib.GBTN_ModelNumFeatures.restype = c_i
    lib.GBTN_ModelNumFeatures.argtypes = [c_p]
    lib.GBTN_Predict.restype = None
    lib.GBTN_Predict.argtypes = [c_p, c_d_p, c_ll, c_i, c_i, c_i, c_d_p]
    lib.GBTN_PredictLeaf.restype = None
    lib.GBTN_PredictLeaf.argtypes = [c_p, c_d_p, c_ll, c_i, c_i, c_i_p]
    lib.GBTN_FreeModel.restype = None
    lib.GBTN_FreeModel.argtypes = [c_p]
    lib.GBTN_OpenMPThreads.restype = c_i
    lib.GBTN_OpenMPThreads.argtypes = []

    # training ABI (absent when built without Python headers)
    global _has_train_api
    try:
        lib.GBTN_GetLastError.restype = ctypes.c_char_p
        lib.GBTN_GetLastError.argtypes = []
        lib.GBTN_DatasetCreateFromMat.restype = c_i
        lib.GBTN_DatasetCreateFromMat.argtypes = [
            c_d_p, c_ll, c_i, ctypes.c_char_p, c_f_p, c_p,
            ctypes.POINTER(c_p)]
        lib.GBTN_DatasetFree.restype = c_i
        lib.GBTN_DatasetFree.argtypes = [c_p]
        lib.GBTN_BoosterCreate.restype = c_i
        lib.GBTN_BoosterCreate.argtypes = [c_p, ctypes.c_char_p,
                                           ctypes.POINTER(c_p)]
        lib.GBTN_BoosterUpdateOneIter.restype = c_i
        lib.GBTN_BoosterUpdateOneIter.argtypes = [c_p, c_i_p]
        lib.GBTN_BoosterSaveModel.restype = c_i
        lib.GBTN_BoosterSaveModel.argtypes = [c_p, c_i, ctypes.c_char_p]
        lib.GBTN_BoosterPredictForMat.restype = c_i
        lib.GBTN_BoosterPredictForMat.argtypes = [c_p, c_d_p, c_ll, c_i,
                                                  c_d_p]
        lib.GBTN_BoosterGetNumClass.restype = c_i
        lib.GBTN_BoosterGetNumClass.argtypes = [c_p, c_i_p]
        lib.GBTN_BoosterFree.restype = c_i
        lib.GBTN_BoosterFree.argtypes = [c_p]

        c_c_p = ctypes.c_char_p
        c_cpp = ctypes.POINTER(c_c_p)       # char** (string arrays)
        c_pp = ctypes.POINTER(c_p)
        c_vpp = ctypes.POINTER(c_p)         # const void** out
        lib.GBTN_DatasetCreateFromFile.restype = c_i
        lib.GBTN_DatasetCreateFromFile.argtypes = [c_c_p, c_c_p, c_p, c_pp]
        lib.GBTN_DatasetCreateFromCSR.restype = c_i
        lib.GBTN_DatasetCreateFromCSR.argtypes = [
            c_i_p, c_ll, c_i_p, c_d_p, c_ll, c_ll, c_c_p, c_p, c_pp]
        lib.GBTN_DatasetCreateFromCSC.restype = c_i
        lib.GBTN_DatasetCreateFromCSC.argtypes = [
            c_i_p, c_ll, c_i_p, c_d_p, c_ll, c_ll, c_c_p, c_p, c_pp]
        lib.GBTN_DatasetCreateEmpty.restype = c_i
        lib.GBTN_DatasetCreateEmpty.argtypes = [c_ll, c_i, c_c_p, c_p, c_pp]
        lib.GBTN_DatasetPushRows.restype = c_i
        lib.GBTN_DatasetPushRows.argtypes = [c_p, c_d_p, c_ll, c_i, c_ll]
        lib.GBTN_DatasetPushRowsByCSR.restype = c_i
        lib.GBTN_DatasetPushRowsByCSR.argtypes = [
            c_p, c_i_p, c_ll, c_i_p, c_d_p, c_ll, c_ll, c_ll]
        lib.GBTN_DatasetSetField.restype = c_i
        lib.GBTN_DatasetSetField.argtypes = [c_p, c_c_p, c_p, c_ll, c_i]
        lib.GBTN_DatasetGetField.restype = c_i
        lib.GBTN_DatasetGetField.argtypes = [c_p, c_c_p, c_ll_p, c_vpp,
                                             c_i_p]
        lib.GBTN_DatasetGetNumData.restype = c_i
        lib.GBTN_DatasetGetNumData.argtypes = [c_p, c_ll_p]
        lib.GBTN_DatasetGetNumFeature.restype = c_i
        lib.GBTN_DatasetGetNumFeature.argtypes = [c_p, c_i_p]
        lib.GBTN_DatasetSetFeatureNames.restype = c_i
        lib.GBTN_DatasetSetFeatureNames.argtypes = [c_p, c_cpp, c_i]
        lib.GBTN_DatasetGetFeatureNames.restype = c_i
        lib.GBTN_DatasetGetFeatureNames.argtypes = [c_p, c_cpp, c_i, c_i_p]
        lib.GBTN_DatasetSaveBinary.restype = c_i
        lib.GBTN_DatasetSaveBinary.argtypes = [c_p, c_c_p]
        lib.GBTN_DatasetLoadBinary.restype = c_i
        lib.GBTN_DatasetLoadBinary.argtypes = [c_c_p, c_pp]
        lib.GBTN_DatasetGetSubset.restype = c_i
        lib.GBTN_DatasetGetSubset.argtypes = [c_p, c_i_p, c_ll, c_c_p, c_pp]

        lib.GBTN_BoosterCreateFromModelfile.restype = c_i
        lib.GBTN_BoosterCreateFromModelfile.argtypes = [c_c_p, c_i_p, c_pp]
        lib.GBTN_BoosterLoadModelFromString.restype = c_i
        lib.GBTN_BoosterLoadModelFromString.argtypes = [c_c_p, c_i_p, c_pp]
        lib.GBTN_BoosterMerge.restype = c_i
        lib.GBTN_BoosterMerge.argtypes = [c_p, c_p]
        lib.GBTN_BoosterAddValidData.restype = c_i
        lib.GBTN_BoosterAddValidData.argtypes = [c_p, c_p, c_c_p]
        lib.GBTN_BoosterResetTrainingData.restype = c_i
        lib.GBTN_BoosterResetTrainingData.argtypes = [c_p, c_p]
        lib.GBTN_BoosterResetParameter.restype = c_i
        lib.GBTN_BoosterResetParameter.argtypes = [c_p, c_c_p]
        lib.GBTN_BoosterUpdateOneIterCustom.restype = c_i
        lib.GBTN_BoosterUpdateOneIterCustom.argtypes = [c_p, c_f_p, c_f_p,
                                                        c_ll, c_i_p]
        lib.GBTN_BoosterRollbackOneIter.restype = c_i
        lib.GBTN_BoosterRollbackOneIter.argtypes = [c_p]
        lib.GBTN_BoosterGetCurrentIteration.restype = c_i
        lib.GBTN_BoosterGetCurrentIteration.argtypes = [c_p, c_i_p]
        lib.GBTN_BoosterGetNumFeature.restype = c_i
        lib.GBTN_BoosterGetNumFeature.argtypes = [c_p, c_i_p]
        lib.GBTN_BoosterGetFeatureNames.restype = c_i
        lib.GBTN_BoosterGetFeatureNames.argtypes = [c_p, c_cpp, c_i, c_i_p]
        lib.GBTN_BoosterGetEvalCounts.restype = c_i
        lib.GBTN_BoosterGetEvalCounts.argtypes = [c_p, c_i_p]
        lib.GBTN_BoosterGetEvalNames.restype = c_i
        lib.GBTN_BoosterGetEvalNames.argtypes = [c_p, c_cpp, c_i, c_i_p]
        lib.GBTN_BoosterGetEval.restype = c_i
        lib.GBTN_BoosterGetEval.argtypes = [c_p, c_i, c_i_p, c_d_p]
        lib.GBTN_BoosterGetNumPredict.restype = c_i
        lib.GBTN_BoosterGetNumPredict.argtypes = [c_p, c_i, c_ll_p]
        lib.GBTN_BoosterGetPredict.restype = c_i
        lib.GBTN_BoosterGetPredict.argtypes = [c_p, c_i, c_ll_p, c_d_p]
        lib.GBTN_BoosterGetLeafValue.restype = c_i
        lib.GBTN_BoosterGetLeafValue.argtypes = [c_p, c_i, c_i,
                                                 ctypes.POINTER(
                                                     ctypes.c_double)]
        lib.GBTN_BoosterSetLeafValue.restype = c_i
        lib.GBTN_BoosterSetLeafValue.argtypes = [c_p, c_i, c_i,
                                                 ctypes.c_double]
        lib.GBTN_BoosterSaveModelToString.restype = c_i
        lib.GBTN_BoosterSaveModelToString.argtypes = [c_p, c_i, c_ll,
                                                      c_ll_p, c_c_p]
        lib.GBTN_BoosterDumpModel.restype = c_i
        lib.GBTN_BoosterDumpModel.argtypes = [c_p, c_i, c_ll, c_ll_p, c_c_p]
        lib.GBTN_BoosterCalcNumPredict.restype = c_i
        lib.GBTN_BoosterCalcNumPredict.argtypes = [c_p, c_ll, c_i, c_i,
                                                   c_ll_p]
        lib.GBTN_BoosterPredict.restype = c_i
        lib.GBTN_BoosterPredict.argtypes = [c_p, c_d_p, c_ll, c_i, c_i, c_i,
                                            c_ll, c_ll_p, c_d_p]
        lib.GBTN_BoosterPredictForCSR.restype = c_i
        lib.GBTN_BoosterPredictForCSR.argtypes = [
            c_p, c_i_p, c_ll, c_i_p, c_d_p, c_ll, c_ll, c_i, c_i, c_ll,
            c_ll_p, c_d_p]
        lib.GBTN_BoosterPredictForCSC.restype = c_i
        lib.GBTN_BoosterPredictForCSC.argtypes = [
            c_p, c_i_p, c_ll, c_i_p, c_d_p, c_ll, c_ll, c_i, c_i, c_ll,
            c_ll_p, c_d_p]
        lib.GBTN_BoosterPredictForFile.restype = c_i
        lib.GBTN_BoosterPredictForFile.argtypes = [c_p, c_c_p, c_i, c_c_p,
                                                   c_i, c_i]
        _has_train_api = True
    except AttributeError:
        _has_train_api = False
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if os.environ.get("LGBM_TPU_NO_NATIVE"):
            _load_failed = True
            return None
        try:
            if not _build_is_current() and not _build():
                _load_failed = True
                return None
            _lib = _bind(ctypes.CDLL(_LIB_PATH))
        except OSError:
            _load_failed = True
            return None
    return _lib


def available() -> bool:
    return get_lib() is not None


def train_api_available() -> bool:
    """True when the training C ABI (gbt_capi_train.cpp) was built in."""
    return get_lib() is not None and _has_train_api


# ---------------------------------------------------------------- wrappers

def parse_file(path: str, has_header: bool, label_idx: int
               ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Native text parse -> (features [N, F] f64, labels [N] f32)."""
    lib = get_lib()
    if lib is None:
        return None
    h = lib.GBTN_ParseFile(path.encode(), int(has_header), int(label_idx))
    try:
        err = lib.GBTN_ParsedError(h)
        if err:
            raise ValueError(f"native parser: {err.decode()}")
        n, f = lib.GBTN_ParsedRows(h), lib.GBTN_ParsedCols(h)
        feats = np.empty((n, f), dtype=np.float64)
        labels = np.empty((n,), dtype=np.float32)
        lib.GBTN_ParsedCopy(
            h, feats.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        return feats, labels
    finally:
        lib.GBTN_ParsedFree(h)


def greedy_find_bin(distinct: np.ndarray, counts: np.ndarray, max_bin: int,
                    total_cnt: int, min_data_in_bin: int):
    """Native greedy bin-boundary search; None when the library is absent
    (caller falls back to the pure-Python loop in data/binning.py)."""
    lib = get_lib()
    if lib is None:
        return None
    distinct = np.ascontiguousarray(distinct, dtype=np.float64)
    counts = np.ascontiguousarray(counts, dtype=np.int64)
    out = np.empty(max(int(max_bin), 1), dtype=np.float64)
    n = lib.GBTN_GreedyFindBin(
        distinct.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        len(distinct), int(max_bin), int(total_cnt), int(min_data_in_bin),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    return out[:n].tolist()


def bin_column(values: np.ndarray, bounds: np.ndarray, n_search: int,
               nan_bin: int, out: np.ndarray) -> bool:
    """Native numerical value->bin into preallocated uint8/uint16 ``out``."""
    lib = get_lib()
    if lib is None:
        return False
    values = np.ascontiguousarray(values, dtype=np.float64)
    bounds = np.ascontiguousarray(bounds, dtype=np.float64)
    bits = 8 if out.dtype == np.uint8 else 16
    lib.GBTN_BinColumn(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), len(values),
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        int(n_search), int(nan_bin), bits, out.ctypes.data_as(ctypes.c_void_p))
    return True


def bin_column_categorical(values: np.ndarray, cat_to_bin: dict,
                           overflow_bin: int, out: np.ndarray) -> bool:
    lib = get_lib()
    if lib is None:
        return False
    values = np.ascontiguousarray(values, dtype=np.float64)
    cats = np.asarray(sorted(cat_to_bin), dtype=np.int64)
    bins = np.asarray([cat_to_bin[c] for c in cats], dtype=np.int32)
    bits = 8 if out.dtype == np.uint8 else 16
    lib.GBTN_BinColumnCategorical(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), len(values),
        cats.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        bins.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        len(cats), int(overflow_bin), bits,
        out.ctypes.data_as(ctypes.c_void_p))
    return True


class NativePredictor:
    """Native model predictor (serving path; predictor.hpp analogue)."""

    def __init__(self, model_str: Optional[str] = None,
                 model_file: Optional[str] = None):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        if model_file is not None:
            self._h = lib.GBTN_LoadModelFile(model_file.encode())
        else:
            self._h = lib.GBTN_LoadModelString(model_str.encode())
        err = lib.GBTN_ModelError(self._h)
        if err:
            msg = err.decode()
            lib.GBTN_FreeModel(self._h)
            self._h = None
            raise ValueError(f"native model load: {msg}")
        self.num_class = lib.GBTN_ModelNumClass(self._h)
        self.num_trees = lib.GBTN_ModelNumTrees(self._h)
        self.num_features = lib.GBTN_ModelNumFeatures(self._h)

    def _prepare(self, X: np.ndarray) -> np.ndarray:
        """Contiguous f64 matrix padded/validated to the model's feature
        count (sparse prediction files may have fewer trailing columns)."""
        X = np.ascontiguousarray(np.atleast_2d(X), dtype=np.float64)
        f = X.shape[1]
        if f < self.num_features:
            X = np.pad(X, ((0, 0), (0, self.num_features - f)))
        elif f > self.num_features:
            X = np.ascontiguousarray(X[:, :self.num_features])
        return X

    def predict(self, X: np.ndarray, num_iteration: int = -1,
                raw_score: bool = False) -> np.ndarray:
        X = self._prepare(X)
        n, f = X.shape
        k = max(self.num_class, 1)
        out = np.empty((n, k), dtype=np.float64)
        self._lib.GBTN_Predict(
            self._h, X.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            n, f, int(num_iteration), int(raw_score),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        return out[:, 0] if k == 1 else out

    def predict_leaf(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        X = self._prepare(X)
        n, f = X.shape
        k = max(self.num_class, 1)
        iters = self.num_trees // k if k else 0
        if num_iteration > 0:
            iters = min(num_iteration, iters)
        total = iters * k
        out = np.empty((n, total), dtype=np.int32)
        self._lib.GBTN_PredictLeaf(
            self._h, X.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            n, f, int(num_iteration),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int)))
        return out

    def __del__(self):
        if getattr(self, "_h", None) is not None:
            self._lib.GBTN_FreeModel(self._h)
