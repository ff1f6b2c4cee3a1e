"""Model-file interoperability with the reference LightGBM CLI.

The reference binary (built on demand from /root/reference by the session
fixture ``ref_bin`` in conftest.py) is the oracle: models we save must load
in `lightgbm task=predict` and produce the same predictions — including
categorical bitset thresholds (the reference's own cpp_test discipline,
tests/cpp_test/test.py) — and models the reference trains must load and
predict identically here."""
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.data.parser import load_text_file

# bench.py lives at the repo root (not a package): make its synthetic
# Higgs-like generator importable for the parity tests that reuse it
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import make_data  # noqa: E402

CAT_DATA = "/root/reference/tests/data/categorical.data"


def _ref_predict(ref_bin: str, model_path: str, data_path: str,
                 tmp_path) -> np.ndarray:
    out = str(tmp_path / "ref_preds.txt")
    conf = str(tmp_path / "pred.conf")
    with open(conf, "w") as f:
        f.write(f"task=predict\ndata={data_path}\n"
                f"input_model={model_path}\noutput_result={out}\n")
    subprocess.run([ref_bin, f"config={conf}"], check=True,
                   capture_output=True, timeout=120)
    return np.loadtxt(out)


@pytest.mark.skipif(not os.path.exists(CAT_DATA),
                    reason="reference categorical.data missing")
def test_categorical_model_predict_parity(ref_bin, tmp_path):
    X, y, _ = load_text_file(CAT_DATA, label_idx=0)
    cat_cols = [0, 1, 2, 4, 5, 6]
    params = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 20,
              "verbose": -1}
    ds = lgb.Dataset(X, label=y, categorical_feature=cat_cols)
    bst = lgb.train(params, ds, num_boost_round=10)
    assert any(t.num_cat > 0 for t in bst.inner.models), \
        "expected categorical splits in the model"
    model_path = str(tmp_path / "model.txt")
    bst.save_model(model_path)
    ref = _ref_predict(ref_bin, model_path, CAT_DATA, tmp_path)
    ours = bst.predict(X)
    np.testing.assert_allclose(ours, ref, rtol=1e-5, atol=1e-6)


def test_numerical_model_predict_parity(ref_bin, tmp_path):
    train_path = "/root/reference/examples/binary_classification/binary.train"
    if not os.path.exists(train_path):
        pytest.skip("reference example data missing")
    X, y, _ = load_text_file(train_path, label_idx=0)
    params = {"objective": "binary", "num_leaves": 31, "verbose": -1}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10)
    model_path = str(tmp_path / "model.txt")
    bst.save_model(model_path)
    ref = _ref_predict(ref_bin, model_path, train_path, tmp_path)
    ours = bst.predict(X)
    np.testing.assert_allclose(ours, ref, rtol=1e-5, atol=1e-6)


def test_load_reference_trained_model(ref_bin, tmp_path):
    """Models trained BY the reference CLI must load and predict identically
    in our framework (the reverse direction)."""
    train_path = "/root/reference/examples/binary_classification/binary.train"
    if not os.path.exists(train_path):
        pytest.skip("reference example data missing")
    model_path = str(tmp_path / "ref_model.txt")
    conf = str(tmp_path / "train.conf")
    with open(conf, "w") as f:
        f.write(f"task=train\nobjective=binary\ndata={train_path}\n"
                f"num_trees=10\nnum_leaves=31\noutput_model={model_path}\n"
                f"verbosity=-1\n")
    subprocess.run([ref_bin, f"config={conf}"], check=True,
                   capture_output=True, timeout=300)
    X, y, _ = load_text_file(train_path, label_idx=0)
    bst = lgb.Booster(model_file=model_path)
    ours = bst.predict(X)
    ref = _ref_predict(ref_bin, model_path, train_path, tmp_path)
    np.testing.assert_allclose(ours, ref, rtol=1e-5, atol=1e-6)


def test_training_quality_parity_bench_config(ref_bin, tmp_path):
    """Head-to-head TRAINING quality at the headline bench config
    (GPU-Performance.md:101-117: 255 leaves, 255 bins, min_data=1,
    min_hessian=100, lr=0.1): our trainer and the reference CLI on the
    same Higgs-like data must land within the reference's own GPU-vs-CPU
    AUC envelope (4e-4; measured delta here is ~1e-8)."""
    X, y = make_data(60_000, 28)
    Xtr, ytr, Xva, yva = X[:50_000], y[:50_000], X[50_000:], y[50_000:]
    train_path = tmp_path / "hq_train.tsv"
    np.savetxt(train_path, np.column_stack([ytr, Xtr]), delimiter="\t",
               fmt="%.8g")

    def auc(yv, p):
        order = np.argsort(p)
        r = np.empty(len(p))
        r[order] = np.arange(1, len(p) + 1)
        pos = yv > 0
        return (r[pos].sum() - pos.sum() * (pos.sum() + 1) / 2) \
            / (pos.sum() * (~pos).sum())

    params = dict(objective="binary", num_leaves=255, max_bin=255,
                  min_data_in_leaf=1, min_sum_hessian_in_leaf=100,
                  learning_rate=0.1, verbose=-1)
    bst = lgb.train(params, lgb.Dataset(Xtr, label=ytr),
                    num_boost_round=15)
    ours_auc = auc(yva, np.asarray(bst.predict(Xva)))

    model_path = tmp_path / "hq_ref_model.txt"
    conf = tmp_path / "hq.conf"
    conf.write_text(
        f"task=train\nobjective=binary\ndata={train_path}\n"
        "num_trees=15\nnum_leaves=255\nmax_bin=255\nmin_data_in_leaf=1\n"
        "min_sum_hessian_in_leaf=100\nlearning_rate=0.1\n"
        f"output_model={model_path}\nverbosity=-1\n")
    subprocess.run([ref_bin, f"config={conf}"], check=True,
                   capture_output=True, timeout=600)
    ref = lgb.Booster(model_file=str(model_path))
    ref_auc = auc(yva, np.asarray(ref.predict(Xva)))

    assert ours_auc > 0.85, ours_auc          # both actually learned
    assert abs(ours_auc - ref_auc) < 4e-4, (ours_auc, ref_auc)


def test_dart_goss_rf_model_interop(ref_bin, tmp_path):
    """DART / GOSS / RF model files are plain tree ensembles in the
    reference text format — each must predict identically through the
    reference CLI (gbdt.cpp:948+ serialization is boosting-type
    agnostic; DART trees are saved already normalized)."""
    train_path = "/root/reference/examples/binary_classification/binary.train"
    if not os.path.exists(train_path):
        pytest.skip("reference example data missing")
    X, y, _ = load_text_file(train_path, label_idx=0)
    for btype, extra in (("dart", {"drop_rate": 0.3}),
                         ("goss", {}),
                         ("rf", {"bagging_freq": 1,
                                 "bagging_fraction": 0.7})):
        params = {"objective": "binary", "num_leaves": 15,
                  "boosting": btype, "verbose": -1, **extra}
        bst = lgb.train(params, lgb.Dataset(X, label=y),
                        num_boost_round=8)
        model_path = str(tmp_path / f"{btype}.txt")
        bst.save_model(model_path)
        ref = _ref_predict(ref_bin, model_path, train_path, tmp_path)
        ours = np.asarray(bst.predict(X))
        np.testing.assert_allclose(ours, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=btype)


def test_unbalance_scale_pos_weight_training_parity(ref_bin, tmp_path):
    """is_unbalance / scale_pos_weight label-weighting must reproduce the
    reference's training (binary_objective.hpp:55-86): same data, same
    config on both sides — predictions agree to fp noise."""
    train_path = "/root/reference/examples/binary_classification/binary.train"
    if not os.path.exists(train_path):
        pytest.skip("reference example data missing")
    X, y, _ = load_text_file(train_path, label_idx=0)
    for extra in ({"is_unbalance": "true"}, {"scale_pos_weight": "3.0"}):
        params = {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 20, "verbose": -1,
                  **{k: (v == "true" if v in ("true", "false") else float(v))
                     for k, v in extra.items()}}
        ours = lgb.train(params, lgb.Dataset(X, label=y),
                         num_boost_round=10)
        model_path = tmp_path / "ub_model.txt"
        conf = tmp_path / "ub.conf"
        conf.write_text("\n".join(
            [f"task=train", "objective=binary", f"data={train_path}",
             "num_trees=10", "num_leaves=15", "min_data_in_leaf=20",
             f"output_model={model_path}", "verbosity=-1"]
            + [f"{k}={v}" for k, v in extra.items()]) + "\n")
        subprocess.run([ref_bin, f"config={conf}"], check=True,
                       capture_output=True, timeout=300)
        ref = lgb.Booster(model_file=str(model_path))
        np.testing.assert_allclose(np.asarray(ours.predict(X)),
                                   np.asarray(ref.predict(X)),
                                   rtol=1e-4, atol=1e-5, err_msg=str(extra))


def test_multiclass_training_parity(ref_bin, tmp_path):
    """Multiclass softmax training on the reference's own example data:
    tree-for-tree agreement with the reference CLI (max pred diff ~1e-6)."""
    train_path = ("/root/reference/examples/multiclass_classification/"
                  "multiclass.train")
    if not os.path.exists(train_path):
        pytest.skip("reference example data missing")
    X, y, _ = load_text_file(train_path, label_idx=0)
    params = {"objective": "multiclass", "num_class": 5, "num_leaves": 15,
              "min_data_in_leaf": 20, "verbose": -1}
    ours = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    model_path = tmp_path / "mc_ref.txt"
    conf = tmp_path / "mc.conf"
    conf.write_text(
        f"task=train\nobjective=multiclass\nnum_class=5\ndata={train_path}\n"
        "num_trees=8\nnum_leaves=15\nmin_data_in_leaf=20\n"
        f"output_model={model_path}\nverbosity=-1\n")
    subprocess.run([ref_bin, f"config={conf}"], check=True,
                   capture_output=True, timeout=300)
    ref = lgb.Booster(model_file=str(model_path))
    np.testing.assert_allclose(np.asarray(ours.predict(X)),
                               np.asarray(ref.predict(X)),
                               rtol=1e-4, atol=1e-5)


def test_lambdarank_quality_parity(ref_bin, tmp_path):
    """Lambdarank NDCG@5 on the reference's rank example must land within
    the published CPU-vs-GPU envelope of the reference itself (~1e-2 —
    tree-level equality is not expected: at iteration 0 all scores tie
    and the reference's std::sort permutes the ranking arbitrarily)."""
    train_path = "/root/reference/examples/lambdarank/rank.train"
    test_path = "/root/reference/examples/lambdarank/rank.test"
    if not os.path.exists(train_path):
        pytest.skip("reference example data missing")
    from lightgbm_tpu.data.metadata import Metadata
    Xt, yt, _ = load_text_file(test_path, label_idx=0)
    meta = Metadata(len(yt))
    meta.load_side_files(test_path)
    qb = np.asarray(meta.query_boundaries)

    def ndcg_at(scores, k=5):
        tot, cnt = 0.0, 0
        for q in range(len(qb) - 1):
            s, e = qb[q], qb[q + 1]
            y, p = yt[s:e], scores[s:e]
            if y.max() <= 0:
                continue
            top = np.argsort(-p)[:k]
            dcg = ((2 ** y[top] - 1)
                   / np.log2(np.arange(len(top)) + 2)).sum()
            ideal = np.sort(y)[::-1][:k]
            idcg = ((2 ** ideal - 1)
                    / np.log2(np.arange(len(ideal)) + 2)).sum()
            tot += dcg / idcg
            cnt += 1
        return tot / cnt

    params = {"objective": "lambdarank", "num_leaves": 31, "verbose": -1,
              "metric": "ndcg", "learning_rate": 0.1, "min_data_in_leaf": 1}
    ours = lgb.train(params, lgb.Dataset(train_path), num_boost_round=50)
    ours_ndcg = ndcg_at(np.asarray(ours.predict(Xt)))

    model_path = tmp_path / "lr_ref.txt"
    conf = tmp_path / "lr.conf"
    conf.write_text(
        f"task=train\nobjective=lambdarank\ndata={train_path}\n"
        "num_trees=50\nnum_leaves=31\nlearning_rate=0.1\n"
        f"min_data_in_leaf=1\noutput_model={model_path}\nverbosity=-1\n")
    subprocess.run([ref_bin, f"config={conf}"], check=True,
                   capture_output=True, timeout=600)
    ref = lgb.Booster(model_file=str(model_path))
    ref_ndcg = ndcg_at(np.asarray(ref.predict(Xt)))

    assert ours_ndcg > 0.60, ours_ndcg
    assert ours_ndcg > ref_ndcg - 0.01, (ours_ndcg, ref_ndcg)


def test_objective_sweep_training_parity(ref_bin, tmp_path):
    """Every remaining objective trains tree-for-tree like the reference
    CLI on the reference's own example data (max pred diff ~3e-6 across
    the sweep, measured) — including the weighted case: binary.train has
    a .weight side file that BOTH sides auto-load."""
    reg = "/root/reference/examples/regression/regression.train"
    binc = "/root/reference/examples/binary_classification/binary.train"
    if not (os.path.exists(reg) and os.path.exists(binc)):
        pytest.skip("reference example data missing")
    cases = [(reg, "regression", {}), (reg, "regression_l1", {}),
             (reg, "huber", {}), (reg, "fair", {}),
             (reg, "poisson", {}),
             (reg, "poisson", {"poisson_max_delta_step": 0.3}),
             (binc, "binary", {}), (binc, "binary", {"sigmoid": 2.0}),
             (binc, "xentropy", {}), (binc, "xentlambda", {})]
    for data_path, obj, extra in cases:
        ours = lgb.train({"objective": obj, "num_leaves": 15,
                          "min_data_in_leaf": 20, "verbose": -1, **extra},
                         lgb.Dataset(data_path), num_boost_round=6)
        model_path = tmp_path / "sweep_ref.txt"
        conf = tmp_path / "sweep.conf"
        conf.write_text(
            f"task=train\nobjective={obj}\ndata={data_path}\nnum_trees=6\n"
            "num_leaves=15\nmin_data_in_leaf=20\n"
            + "".join(f"{k}={v}\n" for k, v in extra.items())
            + f"output_model={model_path}\nverbosity=-1\n")
        subprocess.run([ref_bin, f"config={conf}"], check=True,
                       capture_output=True, timeout=300)
        ref = lgb.Booster(model_file=str(model_path))
        X, _, _ = load_text_file(data_path, label_idx=0)
        np.testing.assert_allclose(
            np.asarray(ours.predict(X)), np.asarray(ref.predict(X)),
            rtol=1e-4, atol=1e-4, err_msg=obj)


def test_wide_and_sparse_regime_training_parity(ref_bin, tmp_path):
    """The wide (Epsilon-like many-feature) and sparse one-hot (EFB)
    regimes train tree-for-tree like the reference — including identical
    bundling decisions on the mutually-exclusive one-hot blocks
    (measured max pred diff ~6e-7 for both)."""
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 20, "verbose": -1}
    # enable_bundle defaults True on both sides, so the sparse one-hot
    # blocks exercise EFB without extra params
    cases = [("wide", make_data(3000, 400)),
             ("sparse", make_data(15000, 100, sparsity=0.9))]
    for tag, (X, y) in cases:
        data_path = tmp_path / f"{tag}.tsv"
        np.savetxt(data_path, np.column_stack([y, X]), delimiter="\t",
                   fmt="%.7g")
        ours = lgb.train(params, lgb.Dataset(str(data_path)),
                         num_boost_round=6)
        model_path = tmp_path / f"{tag}_ref.txt"
        conf = tmp_path / f"{tag}.conf"
        conf.write_text(
            f"task=train\nobjective=binary\ndata={data_path}\nnum_trees=6\n"
            "num_leaves=15\nmin_data_in_leaf=20\n"
            f"output_model={model_path}\nverbosity=-1\n")
        subprocess.run([ref_bin, f"config={conf}"], check=True,
                       capture_output=True, timeout=600)
        ref = lgb.Booster(model_file=str(model_path))
        Xr, _, _ = load_text_file(str(data_path), label_idx=0)
        np.testing.assert_allclose(np.asarray(ours.predict(Xr)),
                                   np.asarray(ref.predict(Xr)),
                                   rtol=1e-4, atol=1e-5, err_msg=tag)


def test_regularized_training_parity(ref_bin, tmp_path):
    """lambda_l1/l2 + max_depth + min_gain training must match the
    reference tree-for-tree (measured ~1e-7).  This is the regression
    guard for the reference's feature-pruning heuristic
    (serial_tree_learner.cpp:406-417): a feature with no positive-gain
    candidate on a parent leaf is skipped for the whole subtree — with
    strong L2 regularization that pruning decides real splits."""
    train_path = "/root/reference/examples/binary_classification/binary.train"
    if not os.path.exists(train_path):
        pytest.skip("reference example data missing")
    X, _, _ = load_text_file(train_path, label_idx=0)
    extra = {"lambda_l1": 0.5, "lambda_l2": 10.0, "max_depth": 5,
             "min_gain_to_split": 0.1}
    params = {"objective": "binary", "num_leaves": 31, "verbose": -1,
              **extra}
    ours = lgb.train(params, lgb.Dataset(train_path), num_boost_round=8)
    model_path = tmp_path / "reg_ref.txt"
    conf = tmp_path / "reg.conf"
    conf.write_text(
        f"task=train\nobjective=binary\ndata={train_path}\nnum_trees=8\n"
        "num_leaves=31\n"
        + "".join(f"{k}={v}\n" for k, v in extra.items())
        + f"output_model={model_path}\nverbosity=-1\n")
    subprocess.run([ref_bin, f"config={conf}"], check=True,
                   capture_output=True, timeout=300)
    ref = lgb.Booster(model_file=str(model_path))
    np.testing.assert_allclose(np.asarray(ours.predict(X)),
                               np.asarray(ref.predict(X)),
                               rtol=1e-4, atol=1e-5)


def test_categorical_training_quality_parity(ref_bin, tmp_path):
    """Categorical training quality matches the reference (tree equality
    is tie-order-dependent: the reference's unstable std::sort over the
    smoothed category ratios permutes zero-count-bin ties arbitrarily,
    feature_histogram.hpp:127-131)."""
    data_path = "/root/reference/tests/data/categorical.data"
    if not os.path.exists(data_path):
        pytest.skip("reference categorical.data missing")
    X, y, _ = load_text_file(data_path, label_idx=0)
    cats = [0, 1, 2, 4, 5, 6]
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 20, "verbose": -1}
    ours = lgb.train(params, lgb.Dataset(X, label=y,
                                         categorical_feature=cats),
                     num_boost_round=30)
    model_path = tmp_path / "cat_ref.txt"
    conf = tmp_path / "cat.conf"
    conf.write_text(
        f"task=train\nobjective=binary\ndata={data_path}\nnum_trees=30\n"
        "num_leaves=15\nmin_data_in_leaf=20\n"
        "categorical_feature=0,1,2,4,5,6\n"
        f"output_model={model_path}\nverbosity=-1\n")
    subprocess.run([ref_bin, f"config={conf}"], check=True,
                   capture_output=True, timeout=300)
    ref = lgb.Booster(model_file=str(model_path))

    def logloss(yv, p):
        p = np.clip(p, 1e-15, 1 - 1e-15)
        return float(-np.mean(yv * np.log(p) + (1 - yv) * np.log(1 - p)))

    lo = logloss(y, np.asarray(ours.predict(X)))
    lr = logloss(y, np.asarray(ref.predict(X)))
    assert lo < 0.35, lo
    assert abs(lo - lr) < 5e-3, (lo, lr)


def test_missing_modes_training_parity(ref_bin, tmp_path):
    """NaN-bearing data trains tree-for-tree like the reference in all
    three missing modes (default NaN handling, zero_as_missing,
    use_missing=false) — measured max pred diff ~8e-6."""
    rng = np.random.RandomState(4)
    n = 4000
    X = rng.randn(n, 6)
    X[rng.rand(n, 6) < 0.12] = np.nan
    X[:, 5] = np.where(rng.rand(n) < 0.5, 0.0, rng.randn(n))
    y = ((np.nan_to_num(X[:, 0]) + X[:, 5]
          + 0.3 * rng.randn(n)) > 0.4).astype(float)
    data_path = tmp_path / "nan.tsv"
    np.savetxt(data_path, np.column_stack([y, X]), delimiter="\t",
               fmt="%.7g")
    Xr, _, _ = load_text_file(str(data_path), label_idx=0)
    for extra in ({}, {"zero_as_missing": "true"},
                  {"use_missing": "false"}):
        params = {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 20, "verbose": -1, **extra}
        ours = lgb.train(params, lgb.Dataset(str(data_path)),
                         num_boost_round=8)
        model_path = tmp_path / "n_ref.txt"
        conf = tmp_path / "n.conf"
        conf.write_text(
            f"task=train\nobjective=binary\ndata={data_path}\nnum_trees=8\n"
            "num_leaves=15\nmin_data_in_leaf=20\n"
            + "".join(f"{k}={v}\n" for k, v in extra.items())
            + f"output_model={model_path}\nverbosity=-1\n")
        subprocess.run([ref_bin, f"config={conf}"], check=True,
                       capture_output=True, timeout=300)
        ref = lgb.Booster(model_file=str(model_path))
        np.testing.assert_allclose(np.asarray(ours.predict(Xr)),
                                   np.asarray(ref.predict(Xr)),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=str(extra))


def test_continued_training_and_ova_parity(ref_bin, tmp_path):
    """(a) Continued training ACROSS implementations: stage 1 trained by
    the reference CLI, stage 2 trained by us via init_model, must equal
    the reference training both stages (~8e-8).  (b) multiclassova
    trains tree-for-tree (~1e-6)."""
    btrain = "/root/reference/examples/binary_classification/binary.train"
    mtrain = ("/root/reference/examples/multiclass_classification/"
              "multiclass.train")
    if not (os.path.exists(btrain) and os.path.exists(mtrain)):
        pytest.skip("reference example data missing")
    X, _, _ = load_text_file(btrain, label_idx=0)
    c1 = tmp_path / "c1_ref.txt"
    c2 = tmp_path / "c2_ref.txt"
    (tmp_path / "c1.conf").write_text(
        f"task=train\nobjective=binary\ndata={btrain}\nnum_trees=5\n"
        f"num_leaves=15\noutput_model={c1}\nverbosity=-1\n")
    subprocess.run([ref_bin, f"config={tmp_path / 'c1.conf'}"], check=True,
                   capture_output=True, timeout=300)
    (tmp_path / "c2.conf").write_text(
        f"task=train\nobjective=binary\ndata={btrain}\nnum_trees=5\n"
        f"num_leaves=15\ninput_model={c1}\noutput_model={c2}\n"
        "verbosity=-1\n")
    subprocess.run([ref_bin, f"config={tmp_path / 'c2.conf'}"], check=True,
                   capture_output=True, timeout=300)
    ours = lgb.train({"objective": "binary", "num_leaves": 15,
                      "verbose": -1},
                     lgb.Dataset(btrain, free_raw_data=False),
                     num_boost_round=5, init_model=str(c1))
    ref2 = lgb.Booster(model_file=str(c2))
    np.testing.assert_allclose(np.asarray(ours.predict(X)),
                               np.asarray(ref2.predict(X)),
                               rtol=1e-4, atol=1e-5)

    Xm, ym, _ = load_text_file(mtrain, label_idx=0)
    params = {"objective": "multiclassova", "num_class": 5,
              "num_leaves": 15, "min_data_in_leaf": 20, "verbose": -1}
    ours = lgb.train(params, lgb.Dataset(Xm, label=ym), num_boost_round=5)
    mo = tmp_path / "mo_ref.txt"
    (tmp_path / "mo.conf").write_text(
        f"task=train\nobjective=multiclassova\nnum_class=5\ndata={mtrain}\n"
        "num_trees=5\nnum_leaves=15\nmin_data_in_leaf=20\n"
        f"output_model={mo}\nverbosity=-1\n")
    subprocess.run([ref_bin, f"config={tmp_path / 'mo.conf'}"], check=True,
                   capture_output=True, timeout=300)
    ref = lgb.Booster(model_file=str(mo))
    np.testing.assert_allclose(np.asarray(ours.predict(Xm)),
                               np.asarray(ref.predict(Xm)),
                               rtol=1e-4, atol=1e-5)


def test_metric_values_match_reference_log(ref_bin, tmp_path):
    """Training-log metric VALUES match the reference CLI digit-for-digit
    (weighted binary_logloss and weighted AUC on both the training and
    validation sets — binary.train carries a .weight side file)."""
    tp = "/root/reference/examples/binary_classification/binary.train"
    vp = "/root/reference/examples/binary_classification/binary.test"
    if not os.path.exists(tp):
        pytest.skip("reference example data missing")
    conf = tmp_path / "m.conf"
    conf.write_text(
        f"task=train\nobjective=binary\ndata={tp}\nvalid_data={vp}\n"
        "num_trees=5\nnum_leaves=15\nmetric=binary_logloss,auc\n"
        "is_training_metric=true\nmetric_freq=1\n"
        f"output_model={tmp_path / 'm_ref.txt'}\n")
    r = subprocess.run([ref_bin, f"config={conf}"], check=True,
                       capture_output=True, text=True, timeout=300)
    ref_vals = {}
    for line in r.stdout.splitlines():
        mobj = __import__("re").match(
            r".*Iteration:5, (\S+) (\S+) : ([\d.]+)", line)
        if mobj:
            ref_vals[(mobj.group(1), mobj.group(2))] = float(mobj.group(3))
    assert len(ref_vals) == 4, r.stdout

    evals = {}
    d = lgb.Dataset(tp)
    lgb.train({"objective": "binary", "num_leaves": 15,
               "metric": ["binary_logloss", "auc"], "verbose": -1},
              d, num_boost_round=5,
              valid_sets=[d, d.create_valid(vp)],
              valid_names=["training", "valid_1"],
              callbacks=[lgb.record_evaluation(evals)])
    for (name, metric), rv in ref_vals.items():
        ours = evals[name][metric][-1]
        assert abs(ours - rv) < 1e-5, (name, metric, ours, rv)


def _rank_metric_vs_reference(ref_bin, tmp_path, metric, conf_key):
    """Train a 50-tree lambdarank model with the reference CLI, then
    compare OUR metric computed on that model's own scores against the
    reference's printed iteration-50 eval, digit for digit."""
    import re
    from lightgbm_tpu.data.metadata import Metadata
    from lightgbm_tpu.metrics import create_metric
    from lightgbm_tpu.config import config_from_params

    tp = "/root/reference/examples/lambdarank/rank.train"
    vp = "/root/reference/examples/lambdarank/rank.test"
    if not os.path.exists(tp):
        pytest.skip("reference example data missing")
    conf = tmp_path / f"{metric}.conf"
    model_path = tmp_path / f"{metric}_ref.txt"
    conf.write_text(
        f"task=train\nobjective=lambdarank\ndata={tp}\nvalid_data={vp}\n"
        f"num_trees=50\nnum_leaves=31\nmetric={metric}\n{conf_key}=1,3,5\n"
        f"metric_freq=50\noutput_model={model_path}\n")
    r = subprocess.run([ref_bin, f"config={conf}"], check=True,
                       capture_output=True, text=True, timeout=600)
    ref_vals = {}
    for line in r.stdout.splitlines():
        mo = re.match(rf".*Iteration:50, valid_1 ({metric}@\d) : ([\d.]+)",
                      line)
        if mo:
            ref_vals[mo.group(1)] = float(mo.group(2))
    assert len(ref_vals) == 3, r.stdout

    Xv, yv, _ = load_text_file(vp, label_idx=0)
    meta = Metadata(len(yv))
    meta.load_side_files(vp)
    meta.set_label(np.asarray(yv, np.float32))
    ref = lgb.Booster(model_file=str(model_path))
    scores = np.asarray(ref.predict(Xv, raw_score=True))[None, :]
    cfg = config_from_params({"metric": metric, "ndcg_eval_at": [1, 3, 5],
                              "verbose": -1})
    m = create_metric(metric, cfg)
    m.init(meta, len(yv))
    ours = dict(zip(m.names(), [float(v) for v in m.eval(scores, None)]))
    for k, rv in ref_vals.items():
        assert abs(ours[k] - rv) < 1e-5, (k, ours[k], rv)
    return scores


def test_ndcg_metric_values_match_reference(ref_bin, tmp_path):
    """NDCG on the reference model's OWN scores matches its printed eval
    digit-for-digit (tie-free full model; on coarse models with tied
    scores the reference's unstable std::sort breaks ties arbitrarily,
    dcg_calculator.cpp:93-95, where ours is stable)."""
    scores = _rank_metric_vs_reference(ref_bin, tmp_path, "ndcg", "ndcg_at")
    assert len(np.unique(scores)) == scores.size   # tie-free premise


def test_map_metric_values_match_reference(ref_bin, tmp_path):
    """MAP on the reference model's own scores matches its printed eval
    exactly — including normalization by min(whole-query positives, k)
    and the 1.0 credit only for queries with NO positives
    (map_metric.hpp CalMapAtK)."""
    _rank_metric_vs_reference(ref_bin, tmp_path, "map", "eval_at")


def test_xentlambda_metric_value_parity(ref_bin, tmp_path):
    """xentlambda metric matches the reference in BOTH wirings: with the
    matching xentlambda objective, and the mismatched-objective path
    where the reference feeds the objective's ConvertOutput straight in
    as hhat (xentropy_metric.hpp:206-219)."""
    tp = "/root/reference/examples/binary_classification/binary.train"
    if not os.path.exists(tp):
        pytest.skip("reference example data missing")
    import re
    for obj in ("xentlambda", "xentropy"):
        conf = tmp_path / "xl.conf"
        conf.write_text(
            f"task=train\nobjective={obj}\ndata={tp}\nnum_trees=5\n"
            "num_leaves=15\nmetric=xentlambda\nis_training_metric=true\n"
            f"metric_freq=5\noutput_model={tmp_path / 'xl_ref.txt'}\n")
        r = subprocess.run([ref_bin, f"config={conf}"], check=True,
                           capture_output=True, text=True, timeout=300)
        mo = [re.match(r".*Iteration:5, training xentlambda : ([\d.]+)", l)
              for l in r.stdout.splitlines()]
        ref_val = next(float(m.group(1)) for m in mo if m)

        evals = {}
        d = lgb.Dataset(tp)
        lgb.train({"objective": obj, "num_leaves": 15,
                   "metric": "xentlambda", "verbose": -1},
                  d, num_boost_round=5, valid_sets=[d],
                  valid_names=["training"],
                  callbacks=[lgb.record_evaluation(evals)])
        ours = evals["training"]["xentlambda"][-1]
        assert abs(ours - ref_val) < 1e-5, (obj, ours, ref_val)


def test_sort_partition_training_parity(ref_bin, tmp_path):
    """The partition's transport (one sort of the window, on the
    window table of ``grower._bucket_sizes``) is bit-neutral all the way to
    the reference: a model trained through it predicts within the oracle
    envelope of the reference CLI's."""
    data_path = "/root/reference/examples/binary_classification/binary.train"
    if not os.path.exists(data_path):
        pytest.skip("reference example data missing")
    ours = lgb.train({"objective": "binary", "num_leaves": 15,
                      "min_data_in_leaf": 20, "verbose": -1,
                      "enable_bin_packing": False},
                     lgb.Dataset(data_path), num_boost_round=6)
    model_path = tmp_path / "knobs_ref.txt"
    conf = tmp_path / "knobs.conf"
    conf.write_text(
        f"task=train\nobjective=binary\ndata={data_path}\nnum_trees=6\n"
        "num_leaves=15\nmin_data_in_leaf=20\n"
        f"output_model={model_path}\nverbosity=-1\n")
    subprocess.run([ref_bin, f"config={conf}"], check=True,
                   capture_output=True, timeout=300)
    ref = lgb.Booster(model_file=str(model_path))
    X, _, _ = load_text_file(data_path, label_idx=0)
    np.testing.assert_allclose(
        np.asarray(ours.predict(X)), np.asarray(ref.predict(X)),
        rtol=1e-4, atol=1e-4)
