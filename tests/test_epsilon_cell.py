"""The ``epsilon`` cell at a size the CPU holds: the benchmark's own run
(``benchmarks/run.run_cell``: the program through ``lgb.train``, then the
plain float64 reference following its first trees) on 4,000 of the 400,000
rows, all 2000 columns, 31 leaves.  Sound runs are ``correct`` with every
compared number under its limit; the reference at bfloat16 in the program's
place (the control) is not, by ``leaf_gap_median`` alone.  The Higgs cells'
twins are ``benchmarks/tests/test_correct.py``.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tests"))
sys.path.insert(0, ROOT)
from small import NO_CHIP, small_cell  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import check, compare  # noqa: E402


def drive(cell, tmp_path, seed):
    return bench_run.run_cell(cell, seed, 1.0, False, NO_CHIP,
                              trace_dir=str(tmp_path / "trace"))


def test_epsilon_is_a_cell_of_the_benchmark():
    from benchmarks.harness import cells
    cell = cells.cell("epsilon.train")
    cfg = cell["config"]
    assert (cfg["rows"], cfg["valid_rows"], cfg["columns"]) == (
        400000, 100000, 2000)
    assert cfg["reduced"] == ["num_trees"]
    assert cfg["params"] == cells.load_json("configs", "higgs.json")["params"]
    assert cell["chips"] == 1 and cell["traffic"]["driver"] == "train"
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"hist_pool_ms_per_tree", "hist_col_tiles", "hist_roofline",
            "tree_mfu", "split_find_ms_per_tree"} <= reported


@pytest.mark.parametrize("seed", [2 ** 31 + 27, 27])
def test_small_epsilon_cell_is_correct_and_its_control_is_not(
        tmp_path, monkeypatch, seed):
    seen = {}
    real = check.check_training

    def with_control(*a, **kw):
        numbers, control, secs = real(
            *a, **dict(kw, control_precision="bfloat16"))
        seen["control"] = control
        return numbers, control, secs
    monkeypatch.setattr(check, "check_training", with_control)
    cell = small_cell("epsilon", "train", rows=4000)
    assert cell["config"]["columns"] == 2000
    res = drive(cell, tmp_path, seed)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    limits = cell["traffic"]["limits"]
    assert set(res["compared"]) == set(limits)
    for name, row in res["compared"].items():
        assert row["value"] <= row["limit"], (name, row)
    rows, ok = compare.verdict(
        {k: v for k, v in seen["control"].items() if k in limits}, limits)
    assert not ok, rows
    assert [r[0] for r in rows if not r[3]] == ["leaf_gap_median"]
