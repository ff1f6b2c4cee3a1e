"""Training C ABI round trip (c_api.h:37-719 training-surface analogue):
ctypes -> GBTN_DatasetCreateFromMat -> GBTN_BoosterCreate ->
UpdateOneIter xN -> SaveModel / PredictForMat, cross-checked against the
python engine driving the same data."""
import ctypes

import numpy as np
import pytest

from lightgbm_tpu.native import get_lib, train_api_available

pytestmark = pytest.mark.skipif(not train_api_available(),
                                reason="native training ABI unavailable")

PARAMS = ("objective=binary num_leaves=15 min_data_in_leaf=20 "
          "learning_rate=0.2 verbose=-1")


def _problem(n=1500, f=8, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    w = rng.randn(f)
    y = ((X @ w + 0.5 * rng.randn(n)) > 0).astype(np.float32)
    return np.ascontiguousarray(X, dtype=np.float64), y


def test_capi_train_roundtrip(tmp_path):
    lib = get_lib()
    X, y = _problem()
    n, f = X.shape

    ds = ctypes.c_void_p()
    rc = lib.GBTN_DatasetCreateFromMat(
        X.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), n, f,
        PARAMS.encode(), y.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        None, ctypes.byref(ds))
    assert rc == 0, lib.GBTN_GetLastError().decode()

    bst = ctypes.c_void_p()
    rc = lib.GBTN_BoosterCreate(ds, PARAMS.encode(), ctypes.byref(bst))
    assert rc == 0, lib.GBTN_GetLastError().decode()

    finished = ctypes.c_int(0)
    for _ in range(10):
        rc = lib.GBTN_BoosterUpdateOneIter(bst, ctypes.byref(finished))
        assert rc == 0, lib.GBTN_GetLastError().decode()
    assert finished.value == 0

    k = ctypes.c_int(0)
    assert lib.GBTN_BoosterGetNumClass(bst, ctypes.byref(k)) == 0
    assert k.value == 1

    out = np.empty((n, 1), dtype=np.float64)
    rc = lib.GBTN_BoosterPredictForMat(
        bst, X.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), n, f,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    assert rc == 0, lib.GBTN_GetLastError().decode()

    model_path = str(tmp_path / "capi_model.txt")
    rc = lib.GBTN_BoosterSaveModel(bst, -1, model_path.encode())
    assert rc == 0, lib.GBTN_GetLastError().decode()
    lib.GBTN_BoosterFree(bst)
    lib.GBTN_DatasetFree(ds)

    # the saved model must reproduce the C-ABI predictions through the
    # python engine AND match training the same data via the python API
    import lightgbm_tpu as lgb
    loaded = lgb.Booster(model_file=model_path)
    np.testing.assert_allclose(loaded.predict(X), out[:, 0],
                               rtol=1e-6, atol=1e-9)

    py_params = dict(objective="binary", num_leaves=15, min_data_in_leaf=20,
                     learning_rate=0.2, verbose=-1)
    py_bst = lgb.train(py_params, lgb.Dataset(X, label=y),
                       num_boost_round=10)
    np.testing.assert_allclose(py_bst.predict(X), out[:, 0],
                               rtol=1e-6, atol=1e-9)
    # training through the ABI actually fit the data
    auc_pos = out[y > 0, 0].mean()
    auc_neg = out[y == 0, 0].mean()
    assert auc_pos > auc_neg + 0.2


def test_capi_error_reporting():
    lib = get_lib()
    bst = ctypes.c_void_p()
    rc = lib.GBTN_BoosterCreate(None, b"objective=binary",
                                ctypes.byref(bst))
    assert rc != 0
    assert len(lib.GBTN_GetLastError()) > 0


def _dp(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


def _fp(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _ip(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int))


def _ok(rc):
    assert rc == 0, get_lib().GBTN_GetLastError().decode()


def _to_csr(X):
    mask = X != 0.0
    indptr = np.zeros(len(X) + 1, dtype=np.int32)
    indptr[1:] = np.cumsum(mask.sum(axis=1))
    indices = np.ascontiguousarray(np.nonzero(mask)[1].astype(np.int32))
    data = np.ascontiguousarray(X[mask], dtype=np.float64)
    return indptr, indices, data


def _train_via_abi(ds, n_iter=8, params=PARAMS):
    lib = get_lib()
    bst = ctypes.c_void_p()
    _ok(lib.GBTN_BoosterCreate(ds, params.encode(), ctypes.byref(bst)))
    fin = ctypes.c_int(0)
    for _ in range(n_iter):
        _ok(lib.GBTN_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    return bst


def test_capi_dataset_csr_csc_push_match_dense(tmp_path):
    """CSR, CSC and PushRows construction must produce the same model as
    the dense-matrix path (LGBM_DatasetCreateFromCSR/CSC/PushRows)."""
    lib = get_lib()
    X, y = _problem(900, 6)
    X[np.abs(X) < 0.4] = 0.0          # make it actually sparse
    n, f = X.shape

    def model_of(ds):
        bst = _train_via_abi(ds, 6)
        need = ctypes.c_longlong(0)
        _ok(lib.GBTN_BoosterSaveModelToString(bst, -1, 0,
                                              ctypes.byref(need), None))
        buf = ctypes.create_string_buffer(need.value)
        _ok(lib.GBTN_BoosterSaveModelToString(bst, -1, need.value,
                                              ctypes.byref(need), buf))
        lib.GBTN_BoosterFree(bst)
        return buf.value.decode()

    label_args = (y.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),)

    ds_dense = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetCreateFromMat(_dp(X), n, f, PARAMS.encode(),
                                      *label_args, None,
                                      ctypes.byref(ds_dense)))
    ref_model = model_of(ds_dense)

    # CSR —
    indptr, indices, data = _to_csr(X)
    ds_csr = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetCreateFromCSR(
        _ip(indptr), len(indptr), _ip(indices), _dp(data), len(data), f,
        PARAMS.encode(), None, ctypes.byref(ds_csr)))
    _ok(lib.GBTN_DatasetSetField(ds_csr, b"label",
                                 y.ctypes.data_as(ctypes.c_void_p), n, 0))
    assert model_of(ds_csr) == ref_model

    # CSC —
    Xc = np.asfortranarray(X)
    mask = Xc != 0.0
    colptr = np.zeros(f + 1, dtype=np.int32)
    colptr[1:] = np.cumsum(mask.sum(axis=0))
    rows = np.ascontiguousarray(
        np.nonzero(mask.T)[1].astype(np.int32))
    vals = np.ascontiguousarray(Xc.T[mask.T], dtype=np.float64)
    ds_csc = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetCreateFromCSC(
        _ip(colptr), len(colptr), _ip(rows), _dp(vals), len(vals), n,
        PARAMS.encode(), None, ctypes.byref(ds_csc)))
    _ok(lib.GBTN_DatasetSetField(ds_csc, b"label",
                                 y.ctypes.data_as(ctypes.c_void_p), n, 0))
    assert model_of(ds_csc) == ref_model

    # streaming PushRows in two blocks —
    ds_push = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetCreateEmpty(n, f, PARAMS.encode(), None,
                                    ctypes.byref(ds_push)))
    cut = n // 3
    a = np.ascontiguousarray(X[:cut])
    b = np.ascontiguousarray(X[cut:])
    _ok(lib.GBTN_DatasetPushRows(ds_push, _dp(a), cut, f, 0))
    bp, bi, bd = _to_csr(b)
    _ok(lib.GBTN_DatasetPushRowsByCSR(ds_push, _ip(bp), len(bp), _ip(bi),
                                      _dp(bd), len(bd), f, cut))
    _ok(lib.GBTN_DatasetSetField(ds_push, b"label",
                                 y.ctypes.data_as(ctypes.c_void_p), n, 0))
    assert model_of(ds_push) == ref_model

    for ds in (ds_dense, ds_csr, ds_csc, ds_push):
        lib.GBTN_DatasetFree(ds)


def test_capi_dataset_introspection(tmp_path):
    lib = get_lib()
    X, y = _problem(400, 5)
    n, f = X.shape
    ds = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetCreateFromMat(_dp(X), n, f, PARAMS.encode(),
                                      _fp(y), None, ctypes.byref(ds)))

    nd = ctypes.c_longlong(0)
    nf = ctypes.c_int(0)
    _ok(lib.GBTN_DatasetGetNumData(ds, ctypes.byref(nd)))
    _ok(lib.GBTN_DatasetGetNumFeature(ds, ctypes.byref(nf)))
    assert (nd.value, nf.value) == (n, f)

    # field round trip: weights in, weights out through the C pointer
    w = (np.arange(n) % 3 + 1).astype(np.float32)
    _ok(lib.GBTN_DatasetSetField(ds, b"weight",
                                 w.ctypes.data_as(ctypes.c_void_p), n, 0))
    out_len = ctypes.c_longlong(0)
    out_ptr = ctypes.c_void_p()
    out_type = ctypes.c_int(-1)
    _ok(lib.GBTN_DatasetGetField(ds, b"weight", ctypes.byref(out_len),
                                 ctypes.byref(out_ptr),
                                 ctypes.byref(out_type)))
    assert out_len.value == n and out_type.value == 0
    got = np.ctypeslib.as_array(
        ctypes.cast(out_ptr, ctypes.POINTER(ctypes.c_float)), (n,))
    np.testing.assert_array_equal(got, w)

    # feature names round trip
    names = [f"feat_{i}".encode() for i in range(f)]
    arr = (ctypes.c_char_p * f)(*names)
    _ok(lib.GBTN_DatasetSetFeatureNames(ds, arr, f))
    bufs = [ctypes.create_string_buffer(64) for _ in range(f)]
    out_arr = (ctypes.c_char_p * f)(
        *[ctypes.cast(b, ctypes.c_char_p) for b in bufs])
    out_n = ctypes.c_int(0)
    _ok(lib.GBTN_DatasetGetFeatureNames(ds, out_arr, 64,
                                        ctypes.byref(out_n)))
    assert out_n.value == f
    assert [bufs[i].value for i in range(f)] == names

    # binary save/load: the reloaded dataset trains to the same model
    bin_path = str(tmp_path / "ds.bin").encode()
    _ok(lib.GBTN_DatasetSaveBinary(ds, bin_path))
    ds2 = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetLoadBinary(bin_path, ctypes.byref(ds2)))
    b1, b2 = _train_via_abi(ds, 4), _train_via_abi(ds2, 4)
    need = ctypes.c_longlong(0)
    _ok(lib.GBTN_BoosterSaveModelToString(b1, -1, 0, ctypes.byref(need),
                                          None))
    m1 = ctypes.create_string_buffer(need.value)
    _ok(lib.GBTN_BoosterSaveModelToString(b1, -1, need.value,
                                          ctypes.byref(need), m1))
    m2 = ctypes.create_string_buffer(need.value)
    _ok(lib.GBTN_BoosterSaveModelToString(b2, -1, need.value,
                                          ctypes.byref(need), m2))
    assert m1.value == m2.value

    # row subset: 200-row subset constructs and reports its shape
    idx = np.arange(0, 400, 2, dtype=np.int32)
    sub = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetGetSubset(ds, _ip(idx), len(idx), b"",
                                  ctypes.byref(sub)))
    _ok(lib.GBTN_DatasetGetNumData(sub, ctypes.byref(nd)))
    assert nd.value == len(idx)
    for h in (b1, b2):
        lib.GBTN_BoosterFree(h)
    for h in (ds, ds2, sub):
        lib.GBTN_DatasetFree(h)


def test_capi_booster_lifecycle(tmp_path):
    """Model file/string load, eval introspection, custom-gradient update,
    rollback, leaf get/set, merge, GetPredict, predict types, file
    predict — the rest of the LGBM_Booster* surface."""
    lib = get_lib()
    X, y = _problem(800, 6, seed=9)
    n, f = X.shape
    ds = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetCreateFromMat(_dp(X), n, f, PARAMS.encode(),
                                      _fp(y), None, ctypes.byref(ds)))
    # valid set aligned to the train bins
    Xv, yv = _problem(300, 6, seed=10)
    dv = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetCreateFromMat(_dp(Xv), len(Xv), f, PARAMS.encode(),
                                      _fp(yv), ds, ctypes.byref(dv)))

    bst = ctypes.c_void_p()
    _ok(lib.GBTN_BoosterCreate(ds, (PARAMS + " metric=binary_logloss,auc")
                               .encode(), ctypes.byref(bst)))
    _ok(lib.GBTN_BoosterAddValidData(bst, dv, b"valid_0"))

    fin = ctypes.c_int(0)
    for _ in range(6):
        _ok(lib.GBTN_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    it = ctypes.c_int(0)
    _ok(lib.GBTN_BoosterGetCurrentIteration(bst, ctypes.byref(it)))
    assert it.value == 6
    _ok(lib.GBTN_BoosterRollbackOneIter(bst))
    _ok(lib.GBTN_BoosterGetCurrentIteration(bst, ctypes.byref(it)))
    assert it.value == 5

    nf = ctypes.c_int(0)
    _ok(lib.GBTN_BoosterGetNumFeature(bst, ctypes.byref(nf)))
    assert nf.value == f

    # eval introspection: counts, names, values for train and valid
    cnt = ctypes.c_int(0)
    _ok(lib.GBTN_BoosterGetEvalCounts(bst, ctypes.byref(cnt)))
    assert cnt.value == 2
    bufs = [ctypes.create_string_buffer(32) for _ in range(cnt.value)]
    name_arr = (ctypes.c_char_p * cnt.value)(
        *[ctypes.cast(b, ctypes.c_char_p) for b in bufs])
    out_n = ctypes.c_int(0)
    _ok(lib.GBTN_BoosterGetEvalNames(bst, name_arr, 32,
                                     ctypes.byref(out_n)))
    names = sorted(bufs[i].value.decode() for i in range(out_n.value))
    assert names == ["auc", "binary_logloss"]
    # too-small name buffers must be a reported error, never a silent
    # truncation ("binary_logloss" needs 15 bytes)
    rc = lib.GBTN_BoosterGetEvalNames(bst, name_arr, 4, ctypes.byref(out_n))
    assert rc != 0 and b"buffer too small" in lib.GBTN_GetLastError()
    vals = np.zeros(cnt.value, dtype=np.float64)
    out_len = ctypes.c_int(0)
    for idx in (0, 1):
        _ok(lib.GBTN_BoosterGetEval(bst, idx, ctypes.byref(out_len),
                                    _dp(vals)))
        assert out_len.value == cnt.value
        assert np.all(np.isfinite(vals))

    # inner predictions for train/valid: objective-converted (sigmoid),
    # matching a fresh predict on the same rows (reference GetPredictAt)
    npred = ctypes.c_longlong(0)
    _ok(lib.GBTN_BoosterGetNumPredict(bst, 1, ctypes.byref(npred)))
    assert npred.value == len(Xv)
    scores = np.zeros(npred.value, dtype=np.float64)
    _ok(lib.GBTN_BoosterGetPredict(bst, 1, ctypes.byref(npred),
                                   _dp(scores)))
    assert np.std(scores) > 0
    assert scores.min() >= 0.0 and scores.max() <= 1.0
    fresh = np.zeros(len(Xv), dtype=np.float64)
    cnt_v = ctypes.c_longlong(0)
    _ok(lib.GBTN_BoosterPredict(bst, _dp(Xv), len(Xv), f, 0, -1, len(Xv),
                                ctypes.byref(cnt_v), _dp(fresh)))
    np.testing.assert_allclose(scores, fresh, rtol=1e-6, atol=1e-9)

    # leaf surgery round trip
    leaf = ctypes.c_double(0.0)
    _ok(lib.GBTN_BoosterGetLeafValue(bst, 1, 0, ctypes.byref(leaf)))
    _ok(lib.GBTN_BoosterSetLeafValue(bst, 1, 0, leaf.value + 0.125))
    back = ctypes.c_double(0.0)
    _ok(lib.GBTN_BoosterGetLeafValue(bst, 1, 0, ctypes.byref(back)))
    assert back.value == leaf.value + 0.125
    _ok(lib.GBTN_BoosterSetLeafValue(bst, 1, 0, leaf.value))

    # predict types: raw vs transformed vs leaf indices
    need = ctypes.c_longlong(0)
    _ok(lib.GBTN_BoosterCalcNumPredict(bst, n, 2, -1, ctypes.byref(need)))
    leaves = np.zeros(need.value, dtype=np.float64)
    out_cnt = ctypes.c_longlong(0)
    _ok(lib.GBTN_BoosterPredict(bst, _dp(X), n, f, 2, -1, need.value,
                                ctypes.byref(out_cnt), _dp(leaves)))
    assert out_cnt.value == need.value
    assert leaves.min() >= 0 and leaves.max() > 0
    raw = np.zeros(n, dtype=np.float64)
    _ok(lib.GBTN_BoosterPredict(bst, _dp(X), n, f, 1, -1, n,
                                ctypes.byref(out_cnt), _dp(raw)))
    prob = np.zeros(n, dtype=np.float64)
    _ok(lib.GBTN_BoosterPredict(bst, _dp(X), n, f, 0, -1, n,
                                ctypes.byref(out_cnt), _dp(prob)))
    np.testing.assert_allclose(prob, 1.0 / (1.0 + np.exp(-raw)), rtol=1e-6)

    # CSR / CSC predict parity with dense
    indptr, indices, data = _to_csr(X)
    prob_csr = np.zeros(n, dtype=np.float64)
    _ok(lib.GBTN_BoosterPredictForCSR(
        bst, _ip(indptr), len(indptr), _ip(indices), _dp(data), len(data),
        f, 0, -1, n, ctypes.byref(out_cnt), _dp(prob_csr)))
    np.testing.assert_allclose(prob_csr, prob, rtol=1e-12)
    maskc = X != 0.0
    colptr = np.zeros(f + 1, dtype=np.int32)
    colptr[1:] = np.cumsum(maskc.sum(axis=0))
    crow = np.ascontiguousarray(np.nonzero(maskc.T)[1].astype(np.int32))
    cval = np.ascontiguousarray(X.T[maskc.T], dtype=np.float64)
    prob_csc = np.zeros(n, dtype=np.float64)
    _ok(lib.GBTN_BoosterPredictForCSC(
        bst, _ip(colptr), len(colptr), _ip(crow), _dp(cval), len(cval),
        n, 0, -1, n, ctypes.byref(out_cnt), _dp(prob_csc)))
    np.testing.assert_allclose(prob_csc, prob, rtol=1e-12)

    # custom-gradient update == plain update on binary logloss
    need = ctypes.c_longlong(0)
    _ok(lib.GBTN_BoosterSaveModelToString(bst, -1, 0, ctypes.byref(need),
                                          None))
    snap = ctypes.create_string_buffer(need.value)
    _ok(lib.GBTN_BoosterSaveModelToString(bst, -1, need.value,
                                          ctypes.byref(need), snap))
    p = 1.0 / (1.0 + np.exp(-raw))
    grad = (p - y).astype(np.float32)
    hess = (p * (1 - p)).astype(np.float32)
    _ok(lib.GBTN_BoosterUpdateOneIterCustom(bst, _fp(grad), _fp(hess), n,
                                            ctypes.byref(fin)))
    _ok(lib.GBTN_BoosterGetCurrentIteration(bst, ctypes.byref(it)))
    assert it.value == 6

    # model-string load round trip + merge
    loaded = ctypes.c_void_p()
    iters = ctypes.c_int(0)
    _ok(lib.GBTN_BoosterLoadModelFromString(snap, ctypes.byref(iters),
                                            ctypes.byref(loaded)))
    assert iters.value == 5
    model_path = str(tmp_path / "m.txt").encode()
    _ok(lib.GBTN_BoosterSaveModel(bst, -1, model_path))
    from_file = ctypes.c_void_p()
    _ok(lib.GBTN_BoosterCreateFromModelfile(model_path, ctypes.byref(iters),
                                            ctypes.byref(from_file)))
    assert iters.value == 6
    _ok(lib.GBTN_BoosterMerge(from_file, loaded))
    # merged model: 6 own + 5 merged trees, and the iteration count keeps
    # matching total trees (the reference derives it from models_.size())
    nt_merged = ctypes.c_int(0)
    _ok(lib.GBTN_BoosterGetCurrentIteration(from_file,
                                            ctypes.byref(nt_merged)))
    assert nt_merged.value == 11
    need2 = ctypes.c_longlong(0)
    _ok(lib.GBTN_BoosterDumpModel(from_file, -1, 0, ctypes.byref(need2),
                                  None))
    js2 = ctypes.create_string_buffer(need2.value)
    _ok(lib.GBTN_BoosterDumpModel(from_file, -1, need2.value,
                                  ctypes.byref(need2), js2))
    import json as _json
    assert len(_json.loads(js2.value.decode())["tree_info"]) == 11

    # JSON dump parses and matches the tree count
    _ok(lib.GBTN_BoosterDumpModel(bst, -1, 0, ctypes.byref(need), None))
    js = ctypes.create_string_buffer(need.value)
    _ok(lib.GBTN_BoosterDumpModel(bst, -1, need.value, ctypes.byref(need),
                                  js))
    import json
    dump = json.loads(js.value.decode())
    assert dump["num_class"] == 1 and len(dump["tree_info"]) >= 6

    # reset parameter: smoke (train continues under the new lr)
    _ok(lib.GBTN_BoosterResetParameter(bst, b"learning_rate=0.05"))
    _ok(lib.GBTN_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    # file predict: written predictions match in-memory predict
    data_path = tmp_path / "pred_in.tsv"
    np.savetxt(data_path, np.column_stack([np.zeros(50), X[:50]]),
               delimiter="\t")
    result_path = tmp_path / "pred_out.tsv"
    _ok(lib.GBTN_BoosterPredictForFile(bst, str(data_path).encode(), 0,
                                       str(result_path).encode(), 0, -1))
    file_pred = np.loadtxt(result_path)
    mem = np.zeros(50, dtype=np.float64)
    _ok(lib.GBTN_BoosterPredict(bst, _dp(np.ascontiguousarray(X[:50])), 50,
                                f, 0, -1, 50, ctypes.byref(out_cnt),
                                _dp(mem)))
    np.testing.assert_allclose(file_pred, mem, rtol=1e-9)

    for h in (bst, loaded, from_file):
        lib.GBTN_BoosterFree(h)
    for h in (ds, dv):
        lib.GBTN_DatasetFree(h)


STANDALONE_C = r"""
#include <stdio.h>
#include <stdlib.h>

/* the GBTN training ABI, as an external C consumer declares it */
extern const char* GBTN_GetLastError(void);
extern int GBTN_DatasetCreateFromMat(const double*, long long, int,
                                     const char*, const float*, void*,
                                     void**);
extern int GBTN_DatasetFree(void*);
extern int GBTN_BoosterCreate(void*, const char*, void**);
extern int GBTN_BoosterUpdateOneIter(void*, int*);
extern int GBTN_BoosterPredict(void*, const double*, long long, int, int,
                               int, long long, long long*, double*);
extern int GBTN_BoosterSaveModel(void*, int, const char*);
extern int GBTN_BoosterFree(void*);

#define N 400
#define F 4
#define CHECK(call) if ((call) != 0) { \
    fprintf(stderr, "FAIL %s: %s\n", #call, GBTN_GetLastError()); return 1; }

int main(int argc, char** argv) {
  static double X[N * F];
  static float y[N];
  unsigned s = 12345;
  for (int i = 0; i < N; ++i) {
    double acc = 0.0;
    for (int j = 0; j < F; ++j) {
      s = s * 1103515245u + 12345u;           /* deterministic LCG data */
      X[i * F + j] = ((double)(s % 2000) - 1000.0) / 250.0;
      acc += (j % 2 ? 1.0 : -1.0) * X[i * F + j];
    }
    y[i] = acc > 0.0 ? 1.0f : 0.0f;
  }
  const char* params = "objective=binary num_leaves=7 min_data_in_leaf=10 "
                       "learning_rate=0.2 verbose=-1";
  void* ds = NULL;
  void* bst = NULL;
  int finished = 0;
  CHECK(GBTN_DatasetCreateFromMat(X, N, F, params, y, NULL, &ds));
  CHECK(GBTN_BoosterCreate(ds, params, &bst));
  for (int it = 0; it < 4; ++it)
    CHECK(GBTN_BoosterUpdateOneIter(bst, &finished));
  static double out[N];
  long long out_len = 0;
  CHECK(GBTN_BoosterPredict(bst, X, N, F, 0, -1, N, &out_len, out));
  CHECK(GBTN_BoosterSaveModel(bst, -1, argv[1]));
  double pos = 0.0, neg = 0.0;
  int npos = 0, nneg = 0;
  for (int i = 0; i < N; ++i) {
    if (y[i] > 0.5f) { pos += out[i]; ++npos; } else { neg += out[i]; ++nneg; }
  }
  if (pos / npos <= neg / nneg + 0.1) {
    fprintf(stderr, "FAIL model did not fit: pos %f neg %f\n",
            pos / npos, neg / nneg);
    return 1;
  }
  GBTN_BoosterFree(bst);
  GBTN_DatasetFree(ds);
  printf("STANDALONE_OK %lld\n", out_len);
  return 0;
}
"""


def test_capi_standalone_c_program(tmp_path):
    """A plain C program (no Python in the process until the shim
    bootstraps it) linked against the native library must be able to
    train, predict and save through the ABI — the claim that external
    bindings can train without a host interpreter."""
    import os
    import shutil
    import subprocess
    import sys
    if shutil.which("gcc") is None:
        pytest.skip("no C toolchain")
    import lightgbm_tpu.native as native_pkg
    native_dir = os.path.dirname(os.path.abspath(native_pkg.__file__))
    so = os.path.join(native_dir, "_gbt_native.so")
    src = tmp_path / "standalone.c"
    src.write_text(STANDALONE_C)
    exe = tmp_path / "standalone"
    subprocess.run(["gcc", "-o", str(exe), str(src), so,
                    f"-Wl,-rpath,{native_dir}"], check=True,
                   capture_output=True, text=True)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    model_path = tmp_path / "standalone_model.txt"
    r = subprocess.run([str(exe), str(model_path)], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "STANDALONE_OK" in r.stdout

    # the model written by the C process loads in the python package
    import lightgbm_tpu as lgb
    loaded = lgb.Booster(model_file=str(model_path))
    assert loaded.num_trees() >= 4


def test_capi_reset_training_data():
    """ResetTrainingData must continue boosting FROM the existing model:
    the first post-reset tree fits the residual of the old trees on the
    new data, not the base objective (reference GBDT::ResetTrainingData
    recomputes train scores from the model)."""
    lib = get_lib()
    X, y = _problem(500, 6, seed=3)
    n, f = X.shape
    ds = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetCreateFromMat(_dp(X), n, f, PARAMS.encode(),
                                      _fp(y), None, ctypes.byref(ds)))
    bst = _train_via_abi(ds, 3)
    # a valid set attached BEFORE the reset must survive it (the reference
    # only swaps the train data)
    Xv, yv = _problem(200, 6, seed=8)
    dv = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetCreateFromMat(_dp(Xv), len(Xv), f, PARAMS.encode(),
                                      _fp(yv), ds, ctypes.byref(dv)))
    _ok(lib.GBTN_BoosterAddValidData(bst, dv, b"valid_0"))
    X2, y2 = _problem(500, 6, seed=4)
    ds2 = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetCreateFromMat(_dp(X2), n, f, PARAMS.encode(),
                                      _fp(y2), ds, ctypes.byref(ds2)))
    _ok(lib.GBTN_BoosterResetTrainingData(bst, ds2))
    fin = ctypes.c_int(0)
    _ok(lib.GBTN_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    it = ctypes.c_int(0)
    _ok(lib.GBTN_BoosterGetCurrentIteration(bst, ctypes.byref(it)))
    assert it.value == 4
    ev = np.zeros(1, dtype=np.float64)
    ev_len = ctypes.c_int(0)
    _ok(lib.GBTN_BoosterGetEval(bst, 1, ctypes.byref(ev_len), _dp(ev)))
    assert ev_len.value == 1 and np.isfinite(ev[0])

    # oracle: python continued training on the same sequence (X2 binned
    # against X's mappers via the reference chain, like ds2 above)
    import lightgbm_tpu as lgb
    py_params = dict(objective="binary", num_leaves=15, min_data_in_leaf=20,
                     learning_rate=0.2, verbose=-1)
    d1 = lgb.Dataset(X, label=y)
    first = lgb.train(py_params, d1, num_boost_round=3)
    cont = lgb.train(py_params, lgb.Dataset(X2, label=y2, reference=d1),
                     num_boost_round=1, init_model=first)
    out_cnt = ctypes.c_longlong(0)
    abi_pred = np.zeros(n, dtype=np.float64)
    _ok(lib.GBTN_BoosterPredict(bst, _dp(X2), n, f, 0, -1, n,
                                ctypes.byref(out_cnt), _dp(abi_pred)))
    np.testing.assert_allclose(abi_pred, cont.predict(X2), rtol=1e-6,
                               atol=1e-9)
    lib.GBTN_BoosterFree(bst)
    for h in (ds, ds2, dv):
        lib.GBTN_DatasetFree(h)


def test_capi_get_predict_rf_raw():
    """GetPredict must NOT objective-convert average_output (RF) models —
    reference GBDT::GetPredictAt returns their raw scores untouched."""
    lib = get_lib()
    X, y = _problem(500, 6, seed=6)
    n, f = X.shape
    params = ("objective=binary boosting=rf bagging_freq=1 "
              "bagging_fraction=0.7 num_leaves=15 min_data_in_leaf=20 "
              "verbose=-1")
    ds = ctypes.c_void_p()
    _ok(lib.GBTN_DatasetCreateFromMat(_dp(X), n, f, params.encode(),
                                      _fp(y), None, ctypes.byref(ds)))
    bst = _train_via_abi(ds, 6, params=params)
    npred = ctypes.c_longlong(0)
    _ok(lib.GBTN_BoosterGetNumPredict(bst, 0, ctypes.byref(npred)))
    scores = np.zeros(npred.value, dtype=np.float64)
    _ok(lib.GBTN_BoosterGetPredict(bst, 0, ctypes.byref(npred),
                                   _dp(scores)))
    # raw tree sums: spread far outside (0, 1); a sigmoid regression would
    # squash them back inside
    assert scores.min() < -0.5 or scores.max() > 1.5, \
        (scores.min(), scores.max())
    lib.GBTN_BoosterFree(bst)
    lib.GBTN_DatasetFree(ds)
