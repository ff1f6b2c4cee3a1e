"""Distributed tree learners on the virtual 8-device CPU mesh.

The "fake backend" discipline (SURVEY §4): CPU devices stand in for TPU
chips; every learner must agree with the serial learner on the data it
produces (the reference validates its parallel learners the same way —
identical SPMD decisions on every machine)."""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _train_auc(X, y, Xt, yt, extra_params):
    params = {"objective": "binary", "metric": "auc", "verbose": -1,
              "num_leaves": 15, "min_data_in_leaf": 50}
    params.update(extra_params)
    ev = {}
    train = lgb.Dataset(X, label=y)
    valid = train.create_valid(Xt, label=yt)
    bst = lgb.train(params, train, num_boost_round=10, valid_sets=[valid],
                    evals_result=ev, verbose_eval=False)
    return ev["valid_0"]["auc"][-1], bst


@pytest.fixture(scope="module")
def data(binary_example):
    return binary_example


def test_devices_available():
    import jax
    assert len(jax.devices()) >= 8


def _tiny_problem(n=2500, f=10, seed=5):
    rng = np.random.RandomState(seed)
    w = rng.randn(f)
    X = rng.randn(n, f)
    y = (X @ w + 0.5 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _tiny_train(extra, X, y):
    params = {"objective": "binary", "verbose": -1, "num_leaves": 7,
              "min_data_in_leaf": 20}
    params.update(extra)
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4,
                     verbose_eval=False)


@pytest.mark.mesh8
def test_gspmd_data_parallel_fast_tier():
    """Tier-1's 8-logical-device job (conftest mesh8 opt-in): a quick
    GSPMD data-parallel training must reproduce the serial trees and
    actually run the NamedSharding path (not a silent serial
    fallback)."""
    X, y = _tiny_problem()
    bs = _tiny_train({"tree_learner": "serial"}, X, y)
    bg = _tiny_train({"tree_learner": "data"}, X, y)
    assert bg.inner._parallel_impl == "gspmd"
    assert bg.inner._gspmd_plan is not None
    assert bg.inner._gspmd_plan.data > 1
    for t_s, t_g in zip(bs.inner.models, bg.inner.models):
        np.testing.assert_array_equal(t_s.split_feature, t_g.split_feature)
        np.testing.assert_array_equal(t_s.threshold_bin, t_g.threshold_bin)


@pytest.mark.mesh8
def test_gspmd_vs_shardmap_ab_fast_tier():
    """The forced A/B partner stays reachable: parallel_impl=shardmap on
    the same data/learner trains the same trees through the explicit
    psum choreography, so the pair is comparable by construction."""
    X, y = _tiny_problem(seed=11)
    bg = _tiny_train({"tree_learner": "data"}, X, y)
    bm = _tiny_train({"tree_learner": "data",
                      "parallel_impl": "shardmap"}, X, y)
    assert bg.inner._parallel_impl == "gspmd"
    assert bm.inner._parallel_impl == "shardmap"
    assert bm.inner._gspmd_plan is None
    for t_g, t_m in zip(bg.inner.models, bm.inner.models):
        np.testing.assert_array_equal(t_g.split_feature, t_m.split_feature)
        np.testing.assert_array_equal(t_g.threshold_bin, t_m.threshold_bin)


@pytest.mark.mesh8
def test_gspmd_voting_downgrades_to_shardmap_loudly():
    """PV-tree vote compression IS call-site collective machinery; a
    forced gspmd request on the voting learner resolves to shard_map
    with a structured layout_downgrade event (the rung-honesty rule)."""
    from lightgbm_tpu.obs.counters import counters as obs_counters
    X, y = _tiny_problem(seed=13)
    obs_counters.reset()
    bv = _tiny_train({"tree_learner": "voting",
                      "parallel_impl": "gspmd"}, X, y)
    assert bv.inner._parallel_impl == "shardmap"
    events = [e for e in obs_counters.events("layout_downgrade")
              if e.get("requested") == "parallel_impl=gspmd"]
    assert events and events[0]["resolved"] == "shardmap"


def test_data_parallel_matches_serial(data):
    X, y, Xt, yt = data
    auc_serial, bst_s = _train_auc(X, y, Xt, yt, {"tree_learner": "serial"})
    auc_data, bst_d = _train_auc(X, y, Xt, yt, {"tree_learner": "data"})
    # psum-reduced histograms equal global histograms up to f32 summation
    # order; tree structure may tie-break differently in rare cases
    assert auc_data == pytest.approx(auc_serial, abs=5e-3)
    # strong check: identical split structure for the first tree
    t_s, t_d = bst_s.inner.models[0], bst_d.inner.models[0]
    np.testing.assert_array_equal(t_s.split_feature, t_d.split_feature)
    np.testing.assert_array_equal(t_s.threshold_bin, t_d.threshold_bin)


def test_feature_parallel_matches_serial(data):
    X, y, Xt, yt = data
    auc_serial, bst_s = _train_auc(X, y, Xt, yt, {"tree_learner": "serial"})
    auc_feat, bst_f = _train_auc(X, y, Xt, yt, {"tree_learner": "feature"})
    assert auc_feat == pytest.approx(auc_serial, abs=5e-3)
    t_s, t_f = bst_s.inner.models[0], bst_f.inner.models[0]
    np.testing.assert_array_equal(t_s.split_feature, t_f.split_feature)
    np.testing.assert_array_equal(t_s.threshold_bin, t_f.threshold_bin)


def test_voting_parallel_quality(data):
    X, y, Xt, yt = data
    auc_serial, _ = _train_auc(X, y, Xt, yt, {"tree_learner": "serial"})
    auc_vote, _ = _train_auc(X, y, Xt, yt, {"tree_learner": "voting",
                                            "top_k": 10})
    # voting is an approximation (communication compression) — quality must
    # stay close but not bit-identical (measured delta with scaled local
    # constraints: 1.2e-3)
    assert auc_vote == pytest.approx(auc_serial, abs=5e-3)


def test_data_feature_2d_matches_serial(data):
    """The 2-D hybrid learner (rows x feature-scan over a 2x4 mesh,
    DataFeatureStrategy) must reproduce the serial tree exactly: the
    data-axis psum makes each column slice's histograms global and the
    feature-axis argmax sync picks the identical split."""
    X, y, Xt, yt = data
    auc_serial, bst_s = _train_auc(X, y, Xt, yt, {"tree_learner": "serial"})
    auc_2d, bst_2 = _train_auc(X, y, Xt, yt,
                               {"tree_learner": "data_feature"})
    assert auc_2d == pytest.approx(auc_serial, abs=5e-3)
    t_s, t_2 = bst_s.inner.models[0], bst_2.inner.models[0]
    np.testing.assert_array_equal(t_s.split_feature, t_2.split_feature)
    np.testing.assert_array_equal(t_s.threshold_bin, t_2.threshold_bin)


def test_data_feature_2d_with_bundles():
    """EFB bundles through the 2-D learner: the column-window expand maps
    must compose with the data-axis histogram psum."""
    X, y, Xt, yt = _bundled_problem()
    auc_serial, bst_s = _train_auc(X, y, Xt, yt, {"tree_learner": "serial"})
    auc_2d, bst_2 = _train_auc(X, y, Xt, yt,
                               {"tree_learner": "data_feature"})
    assert bst_2.inner.train_set.layout is not None, "expected EFB bundles"
    assert auc_2d == pytest.approx(auc_serial, abs=5e-3)
    t_s, t_2 = bst_s.inner.models[0], bst_2.inner.models[0]
    np.testing.assert_array_equal(t_s.split_feature, t_2.split_feature)
    np.testing.assert_array_equal(t_s.threshold_bin, t_2.threshold_bin)


def test_voting_local_constraint_scaling(data):
    """The LOCAL vote scan must divide min_data_in_leaf /
    min_sum_hessian_in_leaf by the shard count
    (voting_parallel_tree_learner.cpp:54-56): with 8 shards each holding
    ~1/8 of every leaf's rows, an unscaled gate stops features from voting
    on leaves that are globally splittable — here min_data_in_leaf=320 vs
    875 local rows at the root freezes the whole tree after one level
    (a leaf of ~440 local rows cannot produce two ≥320-row children), so
    unscaled code grows ≤3 leaves and this test fails."""
    X, y, Xt, yt = data
    extra = {"min_data_in_leaf": 320, "num_leaves": 12}
    auc_serial, bst_s = _train_auc(X, y, Xt, yt,
                                   {"tree_learner": "serial", **extra})
    auc_vote, bst_v = _train_auc(X, y, Xt, yt,
                                 {"tree_learner": "voting", "top_k": 10,
                                  **extra})
    leaves_s = bst_s.inner.models[0].num_leaves
    leaves_v = bst_v.inner.models[0].num_leaves
    assert leaves_s > 6, "problem setup: serial must actually grow"
    # voting may stop a vote-starved leaf slightly early, never collapse
    assert leaves_v >= leaves_s - 2
    assert auc_vote == pytest.approx(auc_serial, abs=6e-3)


def _bundled_problem(n=3000, groups=3, cats=6, dense=2, n_valid=1000, seed=7):
    """One-hot blocks that EFB bundles + dense columns; valid split drawn
    from the same label weights."""
    rng = np.random.RandomState(seed)
    total = n + n_valid
    cols = []
    logits = np.zeros(total)
    for g in range(groups):
        which = rng.randint(0, cats, size=total)
        block = np.zeros((total, cats))
        block[np.arange(total), which] = rng.rand(total) + 0.5
        logits += rng.randn(cats)[which]
        cols.append(block)
    Xd = rng.randn(total, dense)
    logits += Xd @ rng.randn(dense)
    X = np.column_stack(cols + [Xd])
    y = (logits + 0.3 * rng.randn(total) > 0).astype(np.float64)
    return X[:n], y[:n], X[n:], y[n:]


@pytest.mark.parametrize("learner", ["feature", "data", "voting"])
def test_parallel_learners_with_bundles(learner):
    """EFB bundles flow through every distributed strategy (the round-1
    regression: bundled FeatureMeta crashed feature/voting learners)."""
    X, y, Xt, yt = _bundled_problem()
    auc_serial, bst_s = _train_auc(X, y, Xt, yt, {"tree_learner": "serial"})
    extra = {"tree_learner": learner}
    if learner == "voting":
        extra["top_k"] = 8
    auc_p, bst_p = _train_auc(X, y, Xt, yt, extra)
    assert bst_p.inner.train_set.layout is not None, "expected EFB bundles"
    tol = 2e-2 if learner == "voting" else 5e-3
    assert auc_p == pytest.approx(auc_serial, abs=tol)
    if learner == "feature":
        t_s, t_p = bst_s.inner.models[0], bst_p.inner.models[0]
        np.testing.assert_array_equal(t_s.split_feature, t_p.split_feature)
        np.testing.assert_array_equal(t_s.threshold_bin, t_p.threshold_bin)


def test_multiclass_data_parallel():
    rng = np.random.RandomState(3)
    n, k = 2000, 3
    centers = rng.randn(k, 6) * 3
    labels = rng.randint(0, k, n)
    X = centers[labels] + rng.randn(n, 6)
    params = {"objective": "multiclass", "num_class": 3, "verbose": -1,
              "num_leaves": 7, "tree_learner": "data"}
    bst = lgb.train(params, lgb.Dataset(X, label=labels.astype(np.float64)),
                    num_boost_round=10, verbose_eval=False)
    pred = bst.predict(X)
    assert float(np.mean(pred.argmax(axis=1) == labels)) > 0.85


def test_data_parallel_sort_partition_matches_serial(data):
    """The sort partition composes with the data-parallel mesh: every
    shard sorts its own window and the psum'd histograms reproduce the
    serial tree exactly."""
    X, y, Xt, yt = data
    auc_serial, bst_s = _train_auc(X, y, Xt, yt, {"tree_learner": "serial"})
    auc_os, bst_o = _train_auc(
        X, y, Xt, yt, {"tree_learner": "data",
                       "enable_bin_packing": False})
    assert auc_os == pytest.approx(auc_serial, abs=5e-3)
    t_s, t_o = bst_s.inner.models[0], bst_o.inner.models[0]
    np.testing.assert_array_equal(t_s.split_feature, t_o.split_feature)
    np.testing.assert_array_equal(t_s.threshold_bin, t_o.threshold_bin)
