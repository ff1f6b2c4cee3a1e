"""The second pass over a capture (``harness/sub_scopes.py``): nested tokens
both read, the first pass's numbers stay what they were, self time is
counted once, the dense branch's calls are its sorts, the copies are the
unscoped ones of the grower's program, and a program without the tokens
reads None for every metric and raises nothing."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import (cells, metrics, program_spans,  # noqa: E402
                                sub_scopes, trace)
from small import NO_CHIP, small_cell  # noqa: E402

ALL_CELLS = ["higgs.train", "higgs-63bin.train", "epsilon.train",
             "msltr.train-rank", "expo.train-sparse"]
# metric -> (token, layer, the cells that list it)
NEW_METRICS = {
    "partition_route_ms_per_tree": ("part_route", "partition", ALL_CELLS),
    "partition_window_read_ms_per_tree": ("part_read", "partition",
                                          ALL_CELLS),
    "partition_window_sort_ms_per_tree": ("part_sort", "partition",
                                          ALL_CELLS),
    "partition_dense_ms_per_tree": ("part_dense", "partition", ALL_CELLS),
    "partition_dense_calls_per_tree": ("part_dense", "partition", ALL_CELLS),
    "bundle_decode_ms_per_tree": ("bundle_decode", "partition",
                                  ["expo.train-sparse"]),
    "hist_root_ms_per_tree": ("hist_root", "histogram kernel", ALL_CELLS),
    "fused_panel_ms_per_tree": ("fused_panel", "grower (make_grower)",
                                ALL_CELLS),
    "node_tables_ms_per_tree": ("node_tables", "grower (make_grower)",
                                ALL_CELLS),
    "grower_copy_ms_per_tree": (None, "grower (make_grower)", ALL_CELLS),
}
G = "jit(grow_tree_s2)/"
BODY = G + "while/body/"
BRANCH = BODY + "partition/cond/branch_3_fun/"


def host(name, ts, dur):
    return {"plane": "/host:CPU", "line": "python3", "name": name,
            "meta": "", "ts": 100.0 * ts, "dur": 100.0 * dur}


def op(name, ts, dur, meta=""):
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name,
            "meta": meta, "ts": 100.0 * ts, "dur": 100.0 * dur}


def by_hand():
    """One tree of 1,000 units (a unit is 100 ns) in a window of 1,200."""
    return [
        host("bench:window", 0, 1200),
        {"plane": "/device:TPU:0", "line": "XLA Modules",
         "name": "jit_grow_tree_s2(7)", "meta": "", "ts": 0.0,
         "dur": 100000.0},
        # before the window: ignored though it is named
        op("fusion.1", -50, 40, G + "fused_panel/transpose"),
        # the panel, and a copy of the panel's own: fused_panel, not copies
        op("fusion.2", 0, 40, G + "fused_panel/concatenate"),
        op("copy.3", 40, 10, G + "fused_panel/transpose"),
        # the root's histogram: inside ``histogram``
        op("histogram.4", 50, 100, G + "histogram/hist_root/pallas_call"),
        # the loop: 700 long, 45 its own
        op("while.5", 150, 700, G + "while"),
        op("fusion.6", 150, 20, BODY + "node_tables/argmax"),
        # routing, with the bundle's decode inside it: both read
        op("fusion.7", 170, 60, BODY + "partition/part_route/bundle_decode/"
                                       "select_n"),
        op("fusion.8", 230, 20, BODY + "partition/part_route/or"),
        # the switch: 300 long, 12 its own (the residue of ``partition``);
        # a window branch and, later, the dense one
        op("conditional.9", 250, 300, BODY + "partition/cond"),
        op("fusion.10", 250, 100, BRANCH + "part_read/gather"),
        op("sort.11", 350, 40, BRANCH + "part_sort/sort"),
        op("sort.12", 400, 120, BRANCH + "part_dense/sort"),
        op("fusion.13", 520, 20, BRANCH + "part_dense/select_n"),
        # an operation without a name stack inside the switch: the first
        # pass gives it to ``partition`` by its parent, this pass to no part
        op("reduce-window.14", 540, 5, ""),
        # a per-split histogram: under ``histogram``, not ``hist_root``
        op("histogram.15", 560, 200, BODY + "histogram/pallas_call"),
        # carried arrays moved, under no scope: the copies
        op("copy-start.16", 770, 5, BODY + "copy"),
        op("copy-done.17", 780, 25, BODY + "copy"),
        op("slice-done.18", 810, 10, ""),
        # an unscoped fusion: the rest of ``other``
        op("fusion.19", 825, 15, BODY + "add"),
        # a copy under ``partition``, and one in another program: neither
        op("copy.20", 545, 3, BRANCH + "part_sort/dynamic_update_slice"),
        op("copy.21", 1100, 50, "jit(_update_score)/copy"),
    ]


FIRST = ["partition", "histogram", "hist_pool", "split_find", "row_leaf",
         "objective", "score_update", "bundle_expand"]
COUNTS = ["copy", "copy-start", "copy-done", "slice-start", "slice-done"]


def reduced(events=None):
    sub, first, counts = sub_scopes.tokens_wanted()
    return sub_scopes.reduce_sub_scopes(
        by_hand() if events is None else events, sub, first, counts)


def units(ns):
    return ns / 100.0


def test_tokens_come_from_the_metric_files_by_listing():
    sub, first, counts = sub_scopes.tokens_wanted()
    assert set(sub) == {t for t, _, _ in NEW_METRICS.values() if t} \
        | set(sub_scopes.PRINTED_ONLY)
    assert set(first) == set(FIRST)
    assert counts == COUNTS
    # no token of this pass is handed to the first one
    assert not set(sub) & set(metrics.scopes_wanted(
        [m["name"] for m in cells.benchmark()["per_layer"]]))


def test_nested_tokens_both_read_and_leftmost_wins_does_not_apply():
    r = reduced()
    got = {t: units(v) for t, v in r["ns"].items()}
    assert got == {"part_route": 80, "bundle_decode": 60, "part_read": 100,
                   "part_sort": 43, "part_dense": 140, "hist_root": 100,
                   "fused_panel": 50, "node_tables": 20}
    # the first pass reads the outer names as it did: ``partition`` is
    # the four parts, the switch's own 12 and the unnamed operation in it
    first = trace.reduce_trace(by_hand(), FIRST)
    assert first["scope_ms"]["partition"] * 1e4 == pytest.approx(
        80 + 100 + 43 + 140 + units(sum(
            r["rest_ns"]["partition"].values())))
    assert first["scope_ms"]["histogram"] * 1e4 == pytest.approx(300)
    assert {k: units(v) for k, v in r["rest_ns"]["partition"].items()} \
        == {"conditional.9": 300 - 100 - 40 - 120 - 20 - 5 - 3,
            "reduce-window.14": 5}


def test_self_time_is_counted_once_and_the_sums_hold():
    r = reduced()
    first = trace.reduce_trace(by_hand(), FIRST)
    other = first["program_other_ms"] * 1e4
    # other = the panel 50, the tables 20, the copies 40, the loop's own
    # 45 and the unscoped fusion 15
    assert other == pytest.approx(50 + 20 + 40 + 45 + 15)
    assert units(r["copies_ns"]) == 40
    assert {k: units(v) for k, v in r["rest_ns"]["other"].items()} \
        == {"while.5": 45, "fusion.19": 15}
    # every unit of the program is in exactly one place
    parts = sum(units(v) for t, v in r["ns"].items()
                if t != "bundle_decode")
    rest = sum(units(v) for tab in r["rest_ns"].values()
               for v in tab.values())
    assert parts + units(r["copies_ns"]) + rest == pytest.approx(
        first["program_ms"] * 1e4)


def test_the_dense_branch_is_counted_by_its_sorts():
    r = reduced()
    assert r["sorts"]["part_dense"] == 1
    assert r["sorts"]["part_sort"] == 1
    assert r["sorts"]["part_route"] == 0
    twice = by_hand() + [op("sort.12", 900, 50, BRANCH + "part_dense/sort")]
    assert reduced(twice)["sorts"]["part_dense"] == 2


def test_copies_are_the_unscoped_ones_of_the_program():
    r = reduced()
    assert units(r["copies_ns"]) == 5 + 25 + 10
    # a program that names nothing at the top level (the parent, whose
    # panel is unscoped): its copies cannot be told from the panel's
    bare = [dict(e, meta=e["meta"].replace("fused_panel/", "")
                 .replace("node_tables/", "")) for e in by_hand()]
    r = reduced(bare)
    assert r["copies_ns"] is None
    assert "part_read" in r["ns"] and "fused_panel" not in r["ns"]


def test_outside_the_window_is_ignored():
    inside = reduced()
    shifted = [dict(e, ts=e["ts"] + 10 ** 9) if e["name"] != "bench:window"
               else e for e in by_hand()]
    r = reduced(shifted)
    assert r is None or not r["ns"]
    assert units(inside["ns"]["fused_panel"]) == 50     # not 90


def _ctx(result, iterations=2):
    """A traced run's context in which the second pass has been made."""
    return {"iterations": iterations, "trace": {"scope_ms": {}},
            "program": "grow_tree", sub_scopes.CTX_KEY: result}


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_each_new_metric_is_listed_and_reads(name):
    """Entry, file and reader agree; fed the by-hand capture every reader
    gives its number; fed the parent's (no token) None, without raising."""
    token, layer, listed = NEW_METRICS[name]
    entry = next(m for m in cells.benchmark()["per_layer"]
                 if m["name"] == name)
    spec = cells.load_json("layer_metrics", name + ".json")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    assert entry["layer"] == layer and entry["workloads"] == listed
    assert entry["source"] == "device_trace" and entry["better"] == "lower"
    assert spec.get("sub_scope") == token and "scope" not in spec

    expected = {"partition_route_ms_per_tree": 80,
                "partition_window_read_ms_per_tree": 100,
                "partition_window_sort_ms_per_tree": 43,
                "partition_dense_ms_per_tree": 140,
                "bundle_decode_ms_per_tree": 60,
                "hist_root_ms_per_tree": 100,
                "fused_panel_ms_per_tree": 50,
                "node_tables_ms_per_tree": 20,
                "grower_copy_ms_per_tree": 40}
    got = metrics.read_metric(name, _ctx(reduced()))
    if name == "partition_dense_calls_per_tree":
        assert got == 0.5                       # one sort, two iterations
    else:
        assert got == pytest.approx(expected[name] * 100 / 1e6 / 2)

    parent = [dict(e, meta=e["meta"].replace("grow_tree_s2", "grow_tree"))
              for e in by_hand()]
    for t in ("part_route/", "bundle_decode/", "part_read/", "part_sort/",
              "part_dense/", "hist_root/", "fused_panel/", "node_tables/"):
        parent = [dict(e, meta=e["meta"].replace(t, "")) for e in parent]
    r = reduced(parent)
    assert r["ns"] == {} and r["copies_ns"] is None
    assert metrics.read_metric(name, _ctx(r)) is None
    assert metrics.read_metric(name, _ctx(None)) is None
    assert metrics.read_metric(name, {"iterations": 2, "trace": None}) \
        is None


def test_a_real_capture_of_the_parents_program_reads_none():
    """``recorded_trace.json`` is a cut of a chip run of PR 24: the program
    names ``partition`` and ``histogram`` and none of this pass's tokens."""
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        events = json.load(f)
    r = reduced(events)
    assert r["ns"] == {} and r["copies_ns"] is None
    assert r["rest_ns"]["partition"] and r["rest_ns"]["histogram"]
    for name in NEW_METRICS:
        assert metrics.read_metric(name, _ctx(r)) is None


def test_recorded_capture_reduces_to_the_same_numbers_every_time():
    """A cut of a real chip run's capture (higgs.train, TPU v5 lite, PR 36,
    seed 2147486501, the window's first tree: from the program's start to
    the end of its first split, which takes the dense branch, then one
    later split that takes a window branch, the 344 ms between them closed
    up and the enclosing ``while`` shortened by as much; 460 events, each
    ``meta`` cut to its last 130 characters, where the name stack is)."""
    with open(os.path.join(HERE, "recorded_sub_scopes.json")) as f:
        events = json.load(f)
    r = reduced(events)
    again = reduced(list(reversed(events)))
    assert r["ns"] == again["ns"] and r["sorts"] == again["sorts"]
    assert r["copies_ns"] == again["copies_ns"]
    ms = {t: round(v / 1e6, 3) for t, v in r["ns"].items()}
    assert ms == {"fused_panel": 44.869, "hist_root": 50.275,
                  "node_tables": 0.036, "part_dense": 13.47,
                  "part_route": 0.487, "part_read": 7.514,
                  "part_sort": 0.932}
    assert r["sorts"]["part_dense"] == 1 and r["sorts"]["part_sort"] == 1
    assert round(r["copies_ns"] / 1e6, 3) == 0.742
    # the outer names lose nothing to the inner ones: the first pass reads
    # ``partition`` as the four parts and what stands under none of them,
    # the root's histogram is inside ``histogram``, and the three parts of
    # ``other`` do not exceed it
    first = trace.reduce_trace(events, FIRST)
    parts = sum(r["ns"][t] for t in ("part_route", "part_read", "part_sort",
                                     "part_dense"))
    rest = sum(r["rest_ns"]["partition"].values())
    assert (parts + rest) / 1e6 == pytest.approx(
        first["scope_ms"]["partition"])
    assert rest / 1e6 == pytest.approx(0.002785078)
    assert ms["hist_root"] < first["scope_ms"]["histogram"]
    assert ms["fused_panel"] + ms["node_tables"] + 0.742 \
        < first["program_other_ms"]
    # the compiler names the Pallas call by the innermost scope
    assert any(e["name"].startswith("hist_root.") for e in events)


def test_the_line_says_every_token_and_the_residues(capfd):
    sub_scopes.say_line(reduced(), 2, 1.25)
    err = capfd.readouterr().err
    assert "bench: sub-scopes: " in err
    assert "part_dense=0.007(sorts 1)" in err
    assert "part_sort=0.002(sorts 1)" in err
    assert "bundle_decode=0.003" in err and "copies=0.002" in err
    assert "events in 1.2 s" in err or "events in 1.3 s" in err
    assert "partition under no token of this pass 0.001: " \
           "conditional.9=0.001" in err
    assert "other under no token of this pass 0.003: while.5=0.002 " \
           "fusion.19=0.001" in err


def test_run_cell_returns_with_the_new_readers(tmp_path, monkeypatch, capfd):
    """One traced run of the small cell on the CPU: the capture has no
    device plane, the second pass finds its own way to it, reads what the
    CPU's operations name or nothing, and the run returns."""
    root = tmp_path / "trace"
    monkeypatch.setattr(program_spans, "TRACE_ROOT", str(root))
    monkeypatch.setattr(program_spans, "_loaded", {})
    res = bench_run.run_cell(small_cell(), 2 ** 31 + 36, 1.0, True, NO_CHIP,
                             trace_dir=str(root / "higgs.train"))
    assert res["correct"], res["compared"]
    assert capfd.readouterr().err.count("bench: sub-scopes: ") == 1, \
        "the pass is made once a run, whichever metric asks first"
    for name in NEW_METRICS:
        assert name not in res["metrics"] \
            or res["metrics"][name]["value"] >= 0
