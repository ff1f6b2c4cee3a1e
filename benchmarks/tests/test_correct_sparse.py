"""``correct`` has to come out false when it should, in a sparse cell.

``tests/test_correct.py`` for ``expo.train-sparse``: the rest of a run
(``run.run_cell``) at a small size on the CPU, once sound and once with each
of the two faults of ``control_sparse.FAULTS`` planted in the program
underneath: the earlier column of a bundle winning a conflict, and a bundle
column decoded one slot off.  The last test puts the sparse reference at the
lower precision in the program's place (the control) and sees it fail too.

    python3 -m pytest benchmarks/tests -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from small import NO_CHIP, small_cell  # noqa: E402
from benchmarks import control_sparse  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import check_sparse, compare  # noqa: E402


def small_sparse_cell(rows=60000):
    """All 700 columns of the 8 fields on 60,000 rows: bundling still sees
    20,000 of them, so the spilled columns of the two large fields meet on
    the rest, a few dozen times."""
    cell = small_cell("expo", "train-sparse", rows=rows)
    cell["name"] = "expo.train-sparse"
    return cell


def drive(cell, tmp_path, seed=2 ** 31 + 34):
    return bench_run.run_cell(cell, seed, 1.0, False, NO_CHIP,
                              trace_dir=str(tmp_path / "trace"))


def failed(result):
    return sorted(k for k, v in result["compared"].items()
                  if not (v["limit"] is not None and v["value"] <= v["limit"]))


def test_sound_sparse_run_is_correct(tmp_path):
    cell = small_sparse_cell()
    res = drive(cell, tmp_path)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["compared"]) == set(cell["traffic"]["limits"])


@pytest.mark.parametrize("fault,limit", [
    ("earlier_wins", "bundle_conflict_gap"),
    ("decode_off_by_one", "count_mismatch")])
def test_planted_fault_is_not_correct(tmp_path, monkeypatch, fault, limit):
    control_sparse.FAULTS[fault](monkeypatch.setattr)
    res = drive(small_sparse_cell(), tmp_path)
    assert not res["correct"]
    assert limit in failed(res), res["compared"]


@pytest.mark.parametrize("seed", [2 ** 31 + 134, 34])
def test_sparse_control_is_not_correct(tmp_path, monkeypatch, seed):
    """bfloat16 gradients in the program's place fail a limit that the
    program, on the same trees, passes."""
    seen = {}
    real = check_sparse.check_training

    def with_control(*a, **kw):
        numbers, control, secs = real(
            *a, **dict(kw, control_precision="bfloat16"))
        seen["control"] = control
        return numbers, control, secs
    monkeypatch.setattr(check_sparse, "check_training", with_control)
    cell = small_sparse_cell()
    res = drive(cell, tmp_path, seed)
    assert res["correct"], res["compared"]
    limits = cell["traffic"]["limits"]
    rows, ok = compare.verdict(
        {k: v for k, v in seen["control"].items() if k in limits}, limits)
    assert not ok, rows
    assert [r[0] for r in rows if not r[3]] == ["leaf_gap_median"]
