"""Error-surface tests: bad configurations and bad data must fail
LOUDLY with the reference's messages, never train silently wrong
(config.cpp:188-240 conflict checks + the Python-layer guards).
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import config_from_params


@pytest.mark.parametrize("params,msg", [
    ({"nonsense_key": 1}, "Unknown parameter"),
    ({"objective": "made_up_loss"}, "Unknown objective"),
    ({"num_class": 0}, "num_class"),
    ({"objective": "multiclass"}, "greater than 1"),
    ({"objective": "binary", "num_class": 3}, "must be 1"),
    ({"tree_learner": "quantum"}, "tree learner"),
    ({"boosting": "adaboost"}, "boosting type"),
    ({"boosting": "rf"}, "bagging"),
    ({"max_bin": 100000}, "max_bin"),
    ({"pallas_row_tile": 100}, "multiple of 128"),
    # keys that selected a path that is gone (PR 29): rejected like any
    # other unknown key, not accepted and ignored
    ({"gather_words": "maybe"}, "Unknown parameter: gather_words"),
    ({"gather_panel": "on"}, "Unknown parameter: gather_panel"),
    ({"ordered_bins": "on"}, "Unknown parameter: ordered_bins"),
    ({"pallas_fused": "off"}, "Unknown parameter: pallas_fused"),
    ({"hist_dtype": "float32"}, "Unknown parameter: hist_dtype"),
    ({"partition_impl": "sort"}, "Unknown parameter: partition_impl"),
    ({"bucket_scheme": "pow15"}, "Unknown parameter: bucket_scheme"),
    ({"gspmd_hist": "scatter"}, "gspmd_hist"),
    ({"metric": "made_up_metric", "objective": "binary"}, "metric"),
])
def test_bad_params_rejected(params, msg):
    rng = np.random.RandomState(0)
    X = rng.randn(200, 4)
    y = (X[:, 0] > 0).astype(np.float64)
    with pytest.raises((RuntimeError, ValueError)) as ei:
        base = {"verbose": -1}
        base.update(params)
        lgb.train(base, lgb.Dataset(X, label=y), num_boost_round=1,
                  verbose_eval=False)
    assert msg.lower() in str(ei.value).lower()


def test_valid_set_feature_count_mismatch():
    rng = np.random.RandomState(0)
    X = rng.randn(200, 4)
    y = (X[:, 0] > 0).astype(np.float64)
    train = lgb.Dataset(X, label=y)
    valid = train.create_valid(rng.randn(50, 6), label=np.zeros(50))
    with pytest.raises(RuntimeError, match="features"):
        lgb.train({"objective": "binary", "verbose": -1}, train,
                  num_boost_round=1, valid_sets=[valid],
                  verbose_eval=False)


def test_label_length_mismatch():
    rng = np.random.RandomState(0)
    with pytest.raises((RuntimeError, ValueError)):
        ds = lgb.Dataset(rng.randn(100, 3), label=np.zeros(50))
        lgb.train({"objective": "regression", "verbose": -1}, ds,
                  num_boost_round=1, verbose_eval=False)


def test_lambdarank_requires_group():
    rng = np.random.RandomState(0)
    X = rng.randn(100, 3)
    y = rng.randint(0, 3, 100).astype(np.float64)
    with pytest.raises(RuntimeError, match="[Qq]uery|[Gg]roup"):
        lgb.train({"objective": "lambdarank", "verbose": -1},
                  lgb.Dataset(X, label=y), num_boost_round=1,
                  verbose_eval=False)


def test_serial_with_num_machines_warns_and_forces_single():
    cfg = config_from_params({"tree_learner": "serial", "num_machines": 4})
    assert cfg.num_machines == 1


def test_all_constant_features_rejected():
    with pytest.raises(RuntimeError, match="trivial"):
        ds = lgb.Dataset(np.ones((100, 3)), label=np.zeros(100))
        lgb.train({"objective": "regression", "verbose": -1}, ds,
                  num_boost_round=1, verbose_eval=False)


def test_data_feature_multi_machine_rejected_at_parse_time():
    # the 2-D hybrid learner is single-process; the conflict surfaces with
    # the other parse-time checks (config.cpp:188-240 analogue), not as a
    # late runtime fatal in boosting
    with pytest.raises(RuntimeError, match="data_feature.*single-process"):
        config_from_params({"tree_learner": "data_feature",
                            "num_machines": 2})
