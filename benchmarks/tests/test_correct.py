"""``correct`` has to come out false when it should.

Each test skips the harness's look for a chip and drives the rest of a run
(``run.run_cell``) at a small size on the CPU, once sound and once with the
timed path broken underneath: a step that leaves its state unchanged, half
of the batch left out, an answer altered where it is produced.  The last
test puts the reference at the lower precision in the program's place (the
control) and sees it fail too.

    python3 -m pytest benchmarks/tests -q
"""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from small import NO_CHIP, small_cell  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import check, compare  # noqa: E402


def drive(cell, tmp_path, seed=2 ** 31 + 17):
    return bench_run.run_cell(cell, seed, 1.0, False, NO_CHIP,
                              trace_dir=str(tmp_path / "trace"))


def failed(result):
    return sorted(k for k, v in result["compared"].items()
                  if not (v["limit"] is not None and v["value"] <= v["limit"]))


def test_sound_run_is_correct(tmp_path):
    res = drive(small_cell(), tmp_path)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_sound_run_with_evaluation_is_correct(tmp_path):
    res = drive(small_cell(traffic="train-eval"), tmp_path)
    assert res["correct"], res["compared"]
    assert "metric_gap" in res["compared"]


def test_state_left_unchanged_is_not_correct(tmp_path, monkeypatch):
    """The score update returns the scores it was given."""
    from lightgbm_tpu import boosting
    real_init = boosting.GBDT.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        self._update_score = lambda scores, leaf_values, row_leaf, lr: scores
    monkeypatch.setattr(boosting.GBDT, "__init__", init)
    res = drive(small_cell(), tmp_path)
    assert not res["correct"]
    assert "score_gap" in failed(res) and "leaf_gap_median" in failed(res)


def test_half_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    """The grower sees every second row only; leaf outputs are ratios, so
    the mean is taken over the rest."""
    import jax.numpy as jnp
    from lightgbm_tpu import boosting
    real = boosting.GBDT._sample

    def sample(self, it, g, h):
        g, h, cnt = real(self, it, g, h)
        keep = (jnp.arange(cnt.shape[0]) % 2 == 0).astype(cnt.dtype)
        return g * keep, h * keep, cnt * keep
    monkeypatch.setattr(boosting.GBDT, "_sample", sample)
    res = drive(small_cell(), tmp_path)
    assert not res["correct"]
    assert "count_mismatch" in failed(res)


def test_altered_answer_is_not_correct(tmp_path, monkeypatch):
    """One leaf's output is altered where the host tree is produced."""
    from lightgbm_tpu import tree as tree_mod
    real = tree_mod.Tree.from_arrays

    def from_arrays(*a, **kw):
        t = real(*a, **kw)
        if t.num_leaves > 1:
            t.leaf_value[0] *= 1.05
        return t
    monkeypatch.setattr(tree_mod.Tree, "from_arrays",
                        staticmethod(from_arrays))
    res = drive(small_cell(), tmp_path)
    assert not res["correct"]
    assert "score_gap" in failed(res)
    assert res["read_not_compared"]["leaf_gap"] > 0.04


@pytest.mark.parametrize("seed", [2 ** 31 + 101, 2 ** 31 + 102, 7])
def test_control_is_not_correct(tmp_path, monkeypatch, seed):
    """bfloat16 gradients in the program's place fail a limit that the
    program, on the same trees, passes."""
    seen = {}
    real = check.check_training

    def with_control(*a, **kw):
        numbers, control, secs = real(
            *a, **dict(kw, control_precision="bfloat16"))
        seen["control"] = control
        return numbers, control, secs
    monkeypatch.setattr(check, "check_training", with_control)
    cell = small_cell()
    res = drive(cell, tmp_path, seed)
    assert res["correct"], res["compared"]
    limits = cell["traffic"]["limits"]
    rows, ok = compare.verdict(
        {k: v for k, v in seen["control"].items() if k in limits}, limits)
    assert not ok, rows
    assert [r[0] for r in rows if not r[3]] == ["leaf_gap_median"]
