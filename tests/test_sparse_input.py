"""``scipy.sparse`` input on the normal path: ``lgb.Dataset(csr_matrix)``
through ``lgb.train`` and ``Booster.predict``, binned from the STORED ENTRIES
(``data/dataset.construct_csr``), against the same matrix dense.

The data is the ``expo`` cell's kind at a size the CPU holds: 20,000 rows x
550 one-hot columns in 4 fields.  Two fields have 270 categories, more than
the 255 a bundle holds, so their rarest columns spill into a bundle they
share; exclusive feature bundling decides that on a sample of 2,000 rows in
which those columns never meet, and on the full data they do.  There the
later column of the bundle stays (``build_bundled_column``'s rule), and that
is part of what has to come out byte-equal.
"""
import os
import sys
import tracemalloc

import numpy as np
import pytest
import scipy.sparse

import lightgbm_tpu as lgb
from lightgbm_tpu.config import config_from_params
from lightgbm_tpu.data import dataset as dataset_mod
from lightgbm_tpu.data import sparse
from lightgbm_tpu.data.binning import BinMapper
from lightgbm_tpu.data.bundling import BundleLayout, build_bundled_column
from lightgbm_tpu.data.dataset import TrainingData, construct, construct_csr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.harness import data_sparse  # noqa: E402

DRAW = {"field_sizes": [4, 270, 6, 270], "zipf_exponent": 0.5, "noise": 1.0,
        "positive_rate": 0.2}
PARAMS = dict(objective="binary", num_leaves=15, min_data_in_leaf=1,
              min_sum_hessian_in_leaf=1.0, bin_construct_sample_cnt=2000,
              verbose=-1)


@pytest.fixture(scope="module")
def problem():
    X, y = data_sparse.make_problem(20000, 550, 0, 34, DRAW)
    dense = lgb.Dataset(X.toarray(), label=y, params=PARAMS)
    bst = lgb.train(PARAMS, dense, num_boost_round=5)
    return X, y, dense.constructed, bst


def as_capi(X):
    """What ``LGBM_DatasetCreateFromCSR`` hands over: int32 indices, float64
    values, copied."""
    return sparse.CsrMatrix(X.indptr, X.indices, X.data.astype(np.float64),
                            X.shape[1])


KINDS = {"csr": lambda X: X, "csc": lambda X: X.tocsc(),
         "coo": lambda X: X.tocoo(), "csr_array": scipy.sparse.csr_array,
         "capi": as_capi}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_trees_from_sparse_are_byte_equal_to_dense(problem, kind):
    X, y, built_dense, bst_dense = problem
    d = lgb.Dataset(KINDS[kind](X), label=y, params=PARAMS)
    bst = lgb.train(PARAMS, d, num_boost_round=5)
    built = d.constructed
    assert built.layout is not None and built.layout.has_bundles
    assert built.layout.bundles == built_dense.layout.bundles
    assert np.array_equal(built.binned, built_dense.binned)
    assert bst.model_to_string() == bst_dense.model_to_string()
    # the sample bundled columns of different fields that do meet on all rows
    met = [(X[:, b] != 0).sum(1).max() for b in built.layout.bundles]
    assert max(met) > 1


def test_scipy_buffers_are_kept_not_widened(problem):
    X = problem[0]
    csr = sparse.from_scipy(X)
    assert csr.indices.dtype == np.int32 and csr.data.dtype == np.float32
    assert np.shares_memory(csr.indices, X.indices)
    assert np.shares_memory(csr.data, X.data)
    assert sparse.from_scipy(X.toarray()) is None
    # duplicates add up, on a copy: the caller's matrix stays as it was
    twice = scipy.sparse.csr_matrix(
        (np.ones(4, np.float32), np.array([0, 0, 1, 1]), np.array([0, 2, 4])),
        shape=(2, 3))
    got = sparse.from_scipy(twice)
    assert np.asarray(got).tolist() == [[2, 0, 0], [0, 2, 0]]
    assert twice.nnz == 4


def test_sparse_validation_set_and_predict(problem):
    X, y, _, bst_dense = problem
    Xv, yv = X[:3000], y[:3000]
    evals = {}
    for kind, (train, valid) in {"sparse": (X, Xv), "dense": (
            X.toarray(), Xv.toarray())}.items():
        dtrain = lgb.Dataset(train, label=y, params=PARAMS)
        dvalid = lgb.Dataset(valid, label=yv, reference=dtrain)
        evals[kind] = {}
        lgb.train(dict(PARAMS, metric="binary_logloss"), dtrain,
                  num_boost_round=3, valid_sets=[dvalid], valid_names=["v"],
                  evals_result=evals[kind], verbose_eval=False)
    assert evals["sparse"] == evals["dense"]
    want = bst_dense.predict(X.toarray())
    for kind in ("csr", "csc", "coo"):
        assert np.array_equal(bst_dense.predict(KINDS[kind](X)), want)
    assert np.array_equal(bst_dense.predict(Xv, pred_leaf=True),
                          bst_dense.predict(Xv.toarray(), pred_leaf=True))


@pytest.mark.parametrize("budget,runs", [(None, 1), (1 << 17, 20)])
def test_construct_csr_is_bounded_by_the_stored_entries(monkeypatch, budget,
                                                        runs):
    """No buffer of ``rows x columns`` cells, of any width: on 20,000 x 550
    (4 stored entries a row) construction peaks under one BYTE a cell, and
    neither the dense chunks nor the full densify are touched.  Under a
    small chunk budget the entries go by in many runs of rows, each grouped
    and binned and let go, and the same matrix comes out."""
    X, y = data_sparse.make_problem(20000, 550, 0, 34, DRAW)
    csr = sparse.from_scipy(X)
    cfg = config_from_params(dict(PARAMS, bin_construct_sample_cnt=500))
    if budget:
        monkeypatch.setattr(sparse, "CSR_CHUNK_BUDGET_BYTES", budget)
    seen = [r1 - r0 for r0, r1, *_ in csr.iter_by_column(10 ** 9)]
    assert sum(seen) == 20000 and len(seen) == runs

    def boom(*a, **kw):
        raise AssertionError("sparse construction densified rows")
    monkeypatch.setattr(sparse.CsrMatrix, "iter_dense_chunks", boom)
    monkeypatch.setattr(sparse.CsrMatrix, "__array__", boom)
    monkeypatch.setattr(dataset_mod, "_bin_rows", boom)
    tracemalloc.start()
    built = construct_csr(csr, cfg, label=y)
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    assert peak < X.shape[0] * X.shape[1], peak
    if budget:
        # the sample's fit, the binned matrix and ONE run's temporaries
        assert peak < 500 * 550 * 8 * 3 + built.binned.nbytes + 8 * budget
    monkeypatch.undo()
    assert np.array_equal(built.binned,
                          construct(X.toarray(), cfg, label=y).binned)


def hand_made(columns, bundles):
    """A TrainingData with mappers fitted to the given dense columns and the
    given bundles, no sampling and no search."""
    ds = TrainingData()
    ds.num_data, ds.num_total_features = columns.shape
    ds.bin_mappers = [BinMapper.fit(c[c != 0], len(c), 255, 1, 0)
                      for c in columns.T]
    ds.layout = BundleLayout(bundles, ds.bin_mappers, None)
    ds.used_features = ds.layout.sub_features
    return ds


def test_the_later_column_of_a_bundle_wins_a_conflict():
    #   row:       0  1  2  3  4  5
    X = np.array([[1, 0, 0, 2, 0, 1],      # column 0: bins 0 / 1 / 2
                  [0, 1, 0, 1, 1, 0],      # column 1
                  [0, 0, 0, 3, 0, 3],      # column 2
                  [5, 5, 0, 0, 0, 0]], np.float64).T   # column 3, alone
    ds = hand_made(X, [[0, 1, 2], [3]])
    assert [m.num_bin for m in ds.bin_mappers] == [3, 2, 2, 2]
    assert ds.layout.sub_offset == [1, 3, 4, -1]
    binned = dataset_mod._bin_stored(
        ds, sparse.from_scipy(scipy.sparse.csr_matrix(X)), np.uint8)
    # row 3 holds all three columns, row 5 columns 0 and 2: the last stays
    assert binned[:, 0].tolist() == [1, 3, 0, 4, 3, 4]
    assert binned[:, 1].tolist() == [1, 1, 0, 0, 0, 0]
    want = build_bundled_column(X, [0, 1, 2], ds.bin_mappers, [1, 3, 4],
                                np.uint8)
    assert binned[:, 0].tolist() == want.tolist()
    # pushed in the other order the other column stays
    flipped = dataset_mod._bin_stored(
        hand_made(X, [[2, 1, 0], [3]]),
        sparse.from_scipy(scipy.sparse.csr_matrix(X)), np.uint8)
    assert flipped[:, 0].tolist() == [3, 2, 0, 4, 2, 3]


def test_a_column_whose_zero_is_not_its_default_bin_is_walked_whole():
    """A categorical column's default bin is 0 whatever bin the category 0
    has: rows that store nothing there are written too, as the dense path
    writes them."""
    rng = np.random.RandomState(3)
    n = 400
    cat = np.where(rng.rand(n) < 0.2, rng.randint(1, 4, n), 0).astype(float)
    one = np.where((cat == 0) & (rng.rand(n) < 0.05), 1.0, 0.0)
    X = np.stack([one, cat], axis=1)
    cfg = config_from_params(dict(PARAMS, max_conflict_rate=0.9))
    sp = construct_csr(sparse.from_scipy(scipy.sparse.csr_matrix(X)), cfg,
                       categorical_features=[1])
    de = construct(X, cfg, categorical_features=[1])
    assert sp.layout is not None and sp.layout.bundles == [[0, 1]]
    m = sp.bin_mappers[1]
    assert m.value_to_bin_scalar(0.0) != m.default_bin
    assert np.array_equal(sp.binned, de.binned)
