"""``LambdarankNDCG`` over length buckets (PR 31): against the benchmark's
plain float64 reference (``benchmarks/harness/reference_rank.py``, which
imports nothing of the program), against the padded-to-longest form it
replaced (kept here as a few lines), and what it says it built.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.harness import reference_rank  # noqa: E402
from lightgbm_tpu import objectives  # noqa: E402
from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.data.metadata import Metadata  # noqa: E402
from lightgbm_tpu.obs.counters import counters  # noqa: E402

# float32 against float64: the discounts are a float32 table, and the gap
# of two neighbouring discounts far down a long query is a small difference
# of large numbers; a row sum runs over up to 400 pairs
TOLERANCE = 2e-5

# 1 and 2; 17 and 25, one past a table size each; 400, far longer than the
# rest; then a run of ordinary ones
LENGTHS = [1, 2, 17, 5, 400, 33, 16, 25, 1, 64, 7, 3]


def problem(seed, lengths=LENGTHS, extra=40):
    rng = np.random.default_rng(seed)
    sizes = np.array(list(lengths) + list(rng.integers(1, 60, extra)))
    n = int(sizes.sum())
    label = rng.integers(0, 5, n).astype(np.float32)
    # one decimal: most documents of a query tie with some other
    score = np.round(rng.standard_normal(n), 1).astype(np.float32)
    score[:3] = 0.0                 # the queries of 1 and 2: all tied
    return sizes, label, score


def built(sizes, label, weight=None, **params):
    md = Metadata()
    md.label, md.weight = label, weight
    md.query_boundaries = np.concatenate([[0], np.cumsum(sizes)]) \
        .astype(np.int32)
    obj = objectives.LambdarankNDCG(Config(**params))
    obj.init(md, len(label))
    return obj


def gradients(obj, score):
    g, h = jax.jit(obj.get_gradients)(jnp.asarray(score)[None])
    return np.asarray(g[0], np.float64), np.asarray(h[0], np.float64)


def gap(got, want):
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    return float(np.max(np.abs(got - want) / scale))


def padded_to_longest(sizes, label, score, sigma=1.0, max_position=20):
    """The form the buckets replaced: every query padded to the longest,
    sorted, all pairs of the sorted block, scattered back."""
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    n, D = len(label), int(sizes.max())
    gains = np.asarray(reference_rank.default_label_gain())
    disc = reference_rank.discounts(D)
    g, h = np.zeros(n + 1, np.float32), np.zeros(n + 1, np.float32)
    for q, L in enumerate(sizes):
        rows = np.full(D, n)
        rows[:L] = np.arange(bounds[q], bounds[q + 1])
        valid = rows < n
        s = np.where(valid, np.append(score, 0)[rows], -np.inf) \
            .astype(np.float32)
        order = np.argsort(-s, kind="stable")
        ss, sy = s[order], np.where(valid, np.append(label, 0)[rows], -1)[order]
        inv = reference_rank.inverse_max_dcg(label[rows[:L]], gains,
                                             max_position)
        pair = (sy[:, None] > sy[None, :]) & (sy[None, :] >= 0)
        with np.errstate(invalid="ignore", over="ignore"):
            ds = ss[:, None] - ss[None, :]
            delta = ((gains[sy.astype(int)][:, None] - gains[sy.astype(int)])
                     * np.abs(disc[:, None] - disc[None, :]) * inv)
            if ss[0] != ss[L - 1]:
                delta = delta / (0.01 + np.abs(ds))
            p = 2.0 / (1.0 + np.exp(2.0 * sigma * ds))
            lam = np.where(pair, -delta * p, 0.0).astype(np.float32)
            hes = np.where(pair, 2 * delta * p * (2 - p), 0.0) \
                .astype(np.float32)
        np.add.at(g, rows[order], lam.sum(1) - lam.sum(0))
        np.add.at(h, rows[order], hes.sum(1) + hes.sum(0))
    return g[:-1].astype(np.float64), h[:-1].astype(np.float64)


@pytest.mark.parametrize("seed", [3, 31, 2 ** 31 + 31])
def test_gradients_agree_with_the_plain_reference(seed):
    sizes, label, score = problem(seed)
    g, h = gradients(built(sizes, label), score)
    ref = reference_rank.Gradients(sizes, {})
    want_g, want_h = ref(score.astype(np.float64), label.astype(np.float64))
    assert gap(g, want_g) < TOLERANCE and gap(h, want_h) < TOLERANCE
    assert np.all(h >= 0) and g[0] == 0 == h[0]      # a query of one


@pytest.mark.parametrize("params", [
    {"sigmoid": 2.0}, {"max_position": 3},
    {"label_gain": [0.0, 1.0, 2.0, 4.0, 5.0]}])
def test_parameters_reach_the_gradients_as_they_reach_the_reference(params):
    sizes, label, score = problem(5)
    g, h = gradients(built(sizes, label, **params), score)
    ref = reference_rank.Gradients(sizes, params)
    want_g, want_h = ref(score.astype(np.float64), label.astype(np.float64))
    assert gap(g, want_g) < TOLERANCE and gap(h, want_h) < TOLERANCE
    plain = gradients(built(sizes, label), score)
    assert gap(g, plain[0]) > 1e-3          # and the parameter did something


@pytest.mark.parametrize("seed", [3, 31])
def test_buckets_equal_the_padded_form_they_replaced(seed):
    sizes, label, score = problem(seed)
    g, h = gradients(built(sizes, label), score)
    old_g, old_h = padded_to_longest(sizes, label, score)
    assert gap(g, old_g) < TOLERANCE and gap(h, old_h) < TOLERANCE


def test_all_scores_tied_is_the_first_iteration():
    sizes, label, _ = problem(7)
    score = np.zeros(len(label), np.float32)
    g, h = gradients(built(sizes, label), score)
    ref = reference_rank.Gradients(sizes, {})
    want_g, want_h = ref(score.astype(np.float64), label.astype(np.float64))
    assert gap(g, want_g) < TOLERANCE and gap(h, want_h) < TOLERANCE
    assert ref.rank_moves == [0]


def test_weights_scale_gradients_and_hessians():
    sizes, label, score = problem(9)
    w = np.random.default_rng(9).uniform(0.5, 2.0, len(label)) \
        .astype(np.float32)
    g, h = gradients(built(sizes, label), score)
    gw, hw = gradients(built(sizes, label, weight=w), score)
    np.testing.assert_allclose(gw, g * w, rtol=1e-6)
    np.testing.assert_allclose(hw, h * w, rtol=1e-6)


@pytest.mark.parametrize("seed", [3, 11])
def test_the_way_back_is_a_bijection_onto_the_rows(seed):
    sizes, label, _ = problem(seed)
    obj = built(sizes, label)
    slot_of_row, idx = np.asarray(obj._slot_of_row), np.asarray(obj._idx)
    n = len(label)
    assert len(np.unique(slot_of_row)) == n
    assert np.array_equal(idx[slot_of_row], np.arange(n))
    assert np.sum(idx < n) == n               # every other slot is padding
    # a bucket holds its queries whole, each inside its padded length
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    lens = np.asarray(obj._len)
    for off, qoff, Q, D, C in obj._buckets:
        rows = idx[off:off + Q * D].reshape(Q, D)
        assert Q % C == 0 and C * D * D <= max(objectives._PAIR_BLOCK, D * D)
        for r in range(Q):
            real = rows[r][rows[r] < n]
            assert len(real) == lens[qoff + r] <= D
            if len(real):
                q = np.searchsorted(bounds, real[0], side="right") - 1
                assert np.array_equal(
                    real, np.arange(bounds[q], bounds[q + 1]))


@pytest.mark.parametrize("longest,want", [
    (1, [1]), (2, [1, 2]), (16, [1, 2, 4, 8, 16]),
    (17, [1, 2, 4, 8, 16, 24]), (25, [1, 2, 4, 8, 16, 24, 32]),
    (1251, [1, 2, 4, 8, 16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512,
            768, 1024, 1536])])
def test_length_table(longest, want):
    assert objectives._length_table(longest) == want


def test_max_dcg_of_init_is_the_references():
    sizes, label, _ = problem(13)
    obj = built(sizes, label, max_position=5)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    gains = np.asarray(reference_rank.default_label_gain())
    inv = np.asarray(obj._inv_max_dcg)
    lens = np.asarray(obj._len)
    got = sorted(zip(lens[lens > 0].tolist(), inv[lens > 0].tolist()))
    ref = sorted((int(bounds[q + 1] - bounds[q]), reference_rank.inverse_max_dcg(
        label[bounds[q]:bounds[q + 1]], gains, 5)) for q in range(len(sizes)))
    assert [a for a, _ in got] == [a for a, _ in ref]
    np.testing.assert_allclose(sorted(b for _, b in got),
                               sorted(b for _, b in ref), rtol=1e-6)


def test_dispatch_counter_says_what_was_built():
    sizes, label, score = problem(3)
    obj = built(sizes, label)
    before = dict(counters.get("objective_dispatch"))
    gradients(obj, score)
    new = [k for k, v in counters.get("objective_dispatch").items()
           if v != before.get(k, 0)]
    assert len(new) == 1
    tags = dict(kv.split("=", 1) for kv in new[0].split(","))
    assert tags["impl"] == "buckets"
    assert int(tags["buckets"]) == len(obj._buckets)
    assert int(tags["slots"]) == len(np.asarray(obj._idx))
    pairs = reference_rank.pair_slots(sizes)
    assert int(tags["pair_slots"]) == sum(
        Q * D * D for _, _, Q, D, _ in obj._buckets) >= pairs
    # the padded-to-longest form would have built this many
    assert int(tags["pair_slots"]) < len(sizes) * int(sizes.max()) ** 2 / 10


def test_inner_scopes_are_in_the_program():
    sizes, label, score = problem(3)
    obj = built(sizes, label)

    def get_gradients(s):
        with jax.named_scope("objective"):
            return obj.get_gradients(s)
    text = jax.jit(get_gradients).lower(jnp.asarray(score)[None]) \
        .as_text(debug_info=True)
    for scope in ("rank_sort", "rank_pairs", "rank_write"):
        assert f"objective/{scope}" in text, scope


def test_objective_init_is_a_phase_span():
    import lightgbm_tpu as lgb
    sizes, label, _ = problem(3)
    X = np.random.default_rng(0).standard_normal((len(label), 4)) \
        .astype(np.float32)
    key = "phase=objective.init"
    calls = counters.get("phase_calls").get(key, 0)
    lgb.train({"objective": "lambdarank", "num_leaves": 4, "verbose": -1,
               "min_data_in_leaf": 1, "metric": "None"},
              lgb.Dataset(X, label=label, group=sizes), num_boost_round=1)
    assert counters.get("phase_calls").get(key, 0) == calls + 1
    assert counters.get("phase_seconds")[key] > 0
