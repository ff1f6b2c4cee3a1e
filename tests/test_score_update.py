"""The score update's pick of each row's leaf value
(``boosting.leaf_value_of_rows``): a binary tree of selects on the leaf id's
bits up to ``boosting.SELECT_MAX_LEAVES`` leaves, a gather above.

- The pick is ``leaf_values[row_leaf]`` bit for bit, NaN (with payload and
  sign), infinities and -0.0 included, on either side of the cut.
- The jitted ``_update_score`` holds no gather at 255 leaves and one past
  the cut, and says which it took in ``score_update_dispatch`` once a trace.
- On four virtual devices with the rows sharded, the compiled update holds
  no collective and keeps the rows' sharding.
- Trained in either form, the models and the final scores are the same,
  through ``_update_score`` and through ``_route_update_score`` (bagged
  subsets, held-out sets, several classes).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import boosting
from lightgbm_tpu.obs.counters import counters
from lightgbm_tpu.utils.jaxpr_audit import hlo_collective_census

CUT = boosting.SELECT_MAX_LEAVES
N = 4096


def leaf_values(L, rng):
    """Standard normal values led by the ones a select or a gather could
    alter: quiet NaNs with a payload and with the sign set, both
    infinities, -0.0."""
    lv = rng.standard_normal(L).astype(np.float32)
    special = np.array([0x7FC00001, 0xFFC00000, 0x7F800000, 0xFF800000,
                        0x80000000], np.uint32).view(np.float32)
    k = min(L, len(special))
    lv[:k] = special[:k]
    return lv


def row_leaf(L, rng, n=N):
    """Every leaf at least once, then uniform leaf ids."""
    ids = np.concatenate([np.arange(L), rng.integers(0, L, n)])
    return ids.astype(np.int32)


def update_args(L, n=N, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(n).astype(np.float32), leaf_values(L, rng),
            rng.integers(0, L, n).astype(np.int32), np.float32(0.1))


def has_gather(text):
    """A gather operation in lowered (StableHLO) or compiled (HLO) text;
    names and source locations aside."""
    return re.search(r"stablehlo\.gather|\bgather\(", text) is not None


def bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("L", [2, 31, 255, CUT, CUT + 1])
def test_pick_is_the_gather_bit_for_bit(L):
    rng = np.random.default_rng(L)
    lv, rl = leaf_values(L, rng), row_leaf(L, rng)
    got = jax.jit(boosting.leaf_value_of_rows)(lv, rl)
    assert got.shape == rl.shape and got.dtype == jnp.float32
    np.testing.assert_array_equal(bits(got), bits(lv[rl]))


@pytest.mark.parametrize("L,gather", [(255, False), (CUT, False),
                                      (CUT + 1, True)])
def test_update_holds_a_gather_only_past_the_cut(L, gather):
    text = boosting._update_score.lower(*update_args(L)).as_text()
    assert has_gather(text) == gather


def test_dispatch_is_counted_once_a_trace():
    args = update_args(255)
    key = "impl=select,leaves=255"
    boosting._update_score.clear_cache()
    before = counters.get("score_update_dispatch").get(key, 0)
    boosting._update_score(*args)
    boosting._update_score(*args)
    assert counters.get("score_update_dispatch")[key] == before + 1
    L = CUT + 1
    boosting._update_score(*update_args(L))
    assert counters.get("score_update_dispatch")[
        f"impl=gather,leaves={L}"] >= 1


def test_row_sharded_update_stays_on_its_shards():
    """The four-chip cell's placement: scores and ``row_leaf`` in four row
    shards, the leaf values whole on each device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.parallel.mesh import BATCH_AXIS
    devices = jax.devices()[:4]
    assert len(devices) == 4
    mesh = Mesh(np.array(devices), (BATCH_AXIS,))
    rows, whole = NamedSharding(mesh, P(BATCH_AXIS)), NamedSharding(mesh, P())
    scores, lv, rl, lr = update_args(255)
    placed = (jax.device_put(scores, rows), jax.device_put(lv, whole),
              jax.device_put(rl, rows), lr)
    compiled = boosting._update_score.lower(*placed).compile()
    text = compiled.as_text()
    assert hlo_collective_census(text) == {}
    assert not has_gather(text)
    out = compiled(*placed)
    assert out.sharding.is_equivalent_to(rows, 1)
    assert {s.data.shape[0] for s in out.addressable_shards} == {N // 4}
    whole_rows = boosting._update_score(scores, lv, rl, lr)
    np.testing.assert_array_equal(bits(out), bits(whole_rows))


def _problem(n=3000, f=8, classes=2, seed=39):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    z = X @ rng.standard_normal(f) + 0.5 * rng.standard_normal(n)
    if classes == 2:
        return X, (z > 0).astype(np.float32)
    return X, np.digitize(z, np.quantile(z, [1 / 3, 2 / 3])).astype(
        np.float32)


def _train(params, X, y, cut, monkeypatch):
    monkeypatch.setattr(boosting, "SELECT_MAX_LEAVES", cut)
    boosting._update_score.clear_cache()
    boosting._route_update_score.clear_cache()
    before = counters.get("score_update_dispatch")
    train = lgb.Dataset(X[:2400], label=y[:2400])
    valid = lgb.Dataset(X[2400:], label=y[2400:], reference=train)
    bst = lgb.train(dict(params, num_leaves=31, min_data_in_leaf=5,
                         verbose=-1), train, num_boost_round=4,
                    valid_sets=[valid], verbose_eval=False)
    impls = {k.split(",")[0]
             for k, v in counters.get("score_update_dispatch").items()
             if v > before.get(k, 0)}
    g = bst.inner
    return (bst.model_to_string(), bits(g.scores),
            [bits(vs.scores) for vs in g.valid_sets], impls,
            g._subset_state is not None)


@pytest.mark.parametrize("case", ["binary", "multiclass", "bagged"])
def test_trees_and_scores_are_the_gathers(case, monkeypatch):
    """Four trees at 31 leaves grown with the pick by selects and with
    the gather (the cut patched to 0): the same model text, and the same
    train and held-out scores bit for bit."""
    params = {"binary": dict(objective="binary"),
              "multiclass": dict(objective="multiclass", num_class=3),
              "bagged": dict(objective="binary", bagging_fraction=0.5,
                             bagging_freq=1, bagging_seed=7)}[case]
    X, y = _problem(classes=3 if case == "multiclass" else 2)
    try:
        select = _train(params, X, y, CUT, monkeypatch)
        gather = _train(params, X, y, 0, monkeypatch)
    finally:
        monkeypatch.undo()
        boosting._update_score.clear_cache()
        boosting._route_update_score.clear_cache()
    assert select[3] == {"impl=select"} and gather[3] == {"impl=gather"}
    assert select[0] == gather[0]
    np.testing.assert_array_equal(select[1], gather[1])
    for a, b in zip(select[2], gather[2]):
        np.testing.assert_array_equal(a, b)
    # the bagged trees grew on a subset: every row's score was routed
    assert select[4] == gather[4] == (case == "bagged")
