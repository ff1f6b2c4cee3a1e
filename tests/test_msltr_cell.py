"""The ``msltr`` cell at a size the CPU holds: the benchmark's own run
(``benchmarks/run.run_cell``: the program through ``lgb.train`` on a
``Dataset`` with query groups, then the plain float64 reference following
its first trees with its own LambdaRank gradients) on a few thousand of the
2,270,296 rows, all 137 columns, 31 leaves.  Sound runs are ``correct`` with
every compared number under its limit; the reference at bfloat16 in the
program's place (the control) is not.  After ``tests/test_epsilon_cell.py``.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tests"))
sys.path.insert(0, ROOT)
from small import NO_CHIP, small_cell  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import (cells, check, compare, data_rank,  # noqa: E402
                                metrics, reference, reference_rank)
from benchmarks.layer_metrics import _program_counters  # noqa: E402

CELL = "msltr.train-rank"
NEW_METRICS = ("objective_roofline", "objective_pad_ratio")


def small_rank_cell(rows=6000):
    cell = small_cell("msltr", "train-rank", rows=rows)
    cell["config"]["draw"].update(queries=rows // 60, longest_query=400)
    # 31 leaves on a few thousand rows: the hessians of so few queries sum
    # to far less than the published 100
    cell["config"]["params"].update(min_sum_hessian_in_leaf=1.0)
    return cell


def test_msltr_is_a_cell_of_the_benchmark():
    cell = cells.cell(CELL)
    cfg = cell["config"]
    assert (cfg["rows"], cfg["valid_rows"], cfg["columns"]) == (2270296, 0, 137)
    assert cfg["reduced"] == ["num_trees"]
    higgs = cells.load_json("configs", "higgs.json")["params"]
    ranking = {"objective": "lambdarank", "sigmoid": 1.0, "max_position": 20,
               "label_gain": [float(2 ** i - 1) for i in range(31)]}
    assert cfg["params"] == {**higgs, **ranking}
    assert {"queries", "longest_query", "label_quantiles"} <= set(cfg["draw"])
    assert {"rows", "queries", "labels", "ties", "seed"} <= set(cfg["assumed"])
    assert cell["chips"] == 1 and cell["traffic"]["driver"] == "train_rank"
    # train.json's four names; leaf_gap_median set from this cell's own
    # readings (PERF.md section 2): 11 times the largest sound one, a
    # quarter of the smallest control
    assert cell["traffic"]["limits"] == dict(
        cells.load_json("traffic", "train.json")["limits"],
        leaf_gap_median=2e-5)
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"objective_ms_per_tree", "objective_roofline",
            "objective_pad_ratio", "hist_col_tiles", "hist_roofline",
            "partition_window_sizes", "tree_mfu", "compiles_in_window",
            "device_idle_share"} <= reported
    assert {m["name"] for m in cell["end_to_end"]} == {"trees_per_s",
                                                       "setup_s"}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_entry_and_file_agree(name):
    entry = next(m for m in cells.benchmark()["per_layer"]
                 if m["name"] == name)
    spec = cells.load_json("layer_metrics", name + ".json")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["moves"]) == (
        "boosting loop (GBDT.train_one_iter)", "trees_per_s")


def test_the_draw_is_the_published_shape():
    cfg = cells.cell(CELL)["config"]
    draw = cfg["draw"]
    rng = np.random.Generator(np.random.PCG64([cfg["draw_seed"], 1]))
    sizes = data_rank.query_lengths(cfg["rows"], draw["queries"],
                                    draw["longest_query"],
                                    draw["length_sigma"], rng)
    assert sizes.sum() == 2270296 and len(sizes) == 18919
    assert (sizes.min(), sizes.max()) == (1, 1251)
    assert 90 <= np.median(sizes) <= 110
    assert reference_rank.pair_slots(sizes) == 412838434    # configs/msltr.json


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 5])
def test_seed_shuffles_queries_whole(seed):
    draw = dict(cells.cell(CELL)["config"]["draw"], queries=50,
                longest_query=300)
    X0, y0, s0 = data_rank.make_queries(3000, 6, 31, draw)
    X, y, s = data_rank.make_problem(3000, 6, seed, 31, draw)
    assert s.sum() == 3000 and sorted(s) == sorted(s0)
    assert not np.array_equal(s, s0)
    assert np.bincount(y.astype(int), minlength=5).tolist() == \
        np.bincount(y0.astype(int), minlength=5).tolist()
    # a query's documents stay together: the multiset of (row sum, label)
    # of every shuffled query is that of one drawn query of its length
    def keys(X, y, s):
        b = np.concatenate([[0], np.cumsum(s)])
        return sorted(tuple(sorted(zip(X[b[q]:b[q + 1]].sum(1).tolist(),
                                       y[b[q]:b[q + 1]].tolist())))
                      for q in range(len(s)))
    assert keys(X, y, s) == keys(X0, y0, s0)


def test_a_pass_ranks_on_the_scores_it_is_given():
    """A chain's k-th pass ranks on ``ranked_on[k]`` and does its arithmetic
    on its own scores; the control's chain counts its passes apart."""
    sizes = np.array([4, 3])
    y = np.array([2.0, 0, 1, 0, 1, 0, 2])
    own = np.array([0.30, 0.10, 0.20, 0.0, 0.5, 0.4, 0.1])
    other = own.copy()
    other[[1, 2]] = own[[2, 1]]              # two documents change places
    alone = reference_rank.Gradients(sizes, {})
    led = reference_rank.Gradients(sizes, {}, [own, other])
    a = alone(own, y)
    assert np.array_equal(led(own, y)[0], a[0])                  # pass 0
    assert np.array_equal(led(own, y, "bfloat16")[0],
                          reference.round_to(a[0], "bfloat16"))  # its own 0
    b = led(own, y)                                              # pass 1
    assert not np.allclose(b[0][:4], a[0][:4])
    assert np.array_equal(b[0][4:], a[0][4:])    # the other query: as it was
    assert led.rank_moves == [0, 0, 2] and alone.rank_moves == [0]


def test_objective_work_is_counted_from_the_data_alone():
    w = reference_rank.objective_work(np.array([1, 2, 10]))
    assert w == {"pairs": 105, "ops": 105 * reference_rank.PAIR_OPS,
                 "bytes": 13 * reference_rank.ROW_BYTES}


@pytest.mark.parametrize("tags,pairs,want", [
    ({"buckets=16,impl=buckets,pair_slots=668497921,slots=3036897": 1},
     412838434, 668497921 / 412838434),
    ({"impl=padded": 1}, 412838434, None),      # a program that does not tag
    (None, 412838434, None),                    # the parent: no such counter
    ({"buckets=2,impl=buckets,pair_slots=50,slots=9": 3}, None, None)])
def test_pad_ratio_reads_the_dispatch_counter(monkeypatch, tags, pairs, want):
    monkeypatch.setattr(
        _program_counters, "counter",
        lambda name: tags if name == "objective_dispatch" else None)
    got = metrics.read_metric("objective_pad_ratio", {"query_pairs": pairs})
    assert got == want


def test_objective_roofline_reads_scope_and_work():
    work = {"objective": reference_rank.objective_work(np.array([100] * 50))}
    ctx = {"work": work, "trace": {"scope_ms": {"objective": 2.0}},
           "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e11}}
    ops = 50 * 100 * 100 * reference_rank.PAIR_OPS
    assert metrics.read_metric("objective_roofline", ctx) == pytest.approx(
        100.0 * (ops / 1e12) / 2e-3)
    assert metrics.read_metric("objective_roofline",
                               dict(ctx, trace=None)) is None
    assert metrics.read_metric("objective_roofline",
                               dict(ctx, work={})) is None


@pytest.mark.parametrize("seed", [2 ** 31 + 31, 31])
def test_small_msltr_cell_is_correct_and_its_control_is_not(
        tmp_path, monkeypatch, seed):
    seen = {}
    real = check.check_training

    def with_control(*a, **kw):
        numbers, control, secs = real(
            *a, **dict(kw, control_precision="bfloat16"))
        seen["control"] = control
        return numbers, control, secs
    monkeypatch.setattr(check, "check_training", with_control)
    cell = small_rank_cell()
    assert cell["config"]["columns"] == 137
    res = bench_run.run_cell(cell, seed, 1.0, False, NO_CHIP,
                             trace_dir=str(tmp_path / "trace"))
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
    # the binary reference is back in its place
    assert reference.gradients.__module__.endswith("harness.reference")
    # and the run said what the objective built, for objective_pad_ratio
    ratio = metrics.read_metric(
        "objective_pad_ratio",
        {"query_pairs": 6000 * 60})      # any count: the tag is what is read
    assert ratio is not None and ratio > 0
