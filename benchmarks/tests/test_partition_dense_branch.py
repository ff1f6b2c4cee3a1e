"""``partition_dense_branch`` (PR 35) read from recorded counter snapshots:
the program whose partition ends in a dense branch (tagged ``read=dense``),
and its parent, whose every branch is a window (``read=column``).
``recorded_counters_dense.json`` says how both were taken.

    python3 -m pytest benchmarks/tests -q
"""
import json
import os

import pytest

from benchmarks.harness import cells, metrics
from benchmarks.layer_metrics import _program_counters

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "partition_dense_branch"


@pytest.fixture
def recorded(monkeypatch):
    with open(os.path.join(HERE, "recorded_counters_dense.json")) as f:
        snapshots = json.load(f)

    def use(which):
        table = snapshots[which] if which else {}
        monkeypatch.setattr(_program_counters, "counter",
                            lambda name: table.get(name) or None)
        return table
    return use


def test_entry_file_and_reader_agree():
    entry = next(m for m in cells.benchmark()["per_layer"]
                 if m["name"] == NAME)
    spec = cells.load_json("layer_metrics", NAME + ".json")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    assert (entry["layer"], entry["moves"], entry["source"],
            entry["better"]) == ("partition", "trees_per_s",
                                 "program_counter", "higher")
    assert entry["workloads"] == [w["name"]
                                  for w in cells.benchmark()["workloads"]]
    # appended: the metrics the benchmark had stand before it, unmoved
    assert cells.benchmark()["per_layer"][-1]["name"] == NAME


@pytest.mark.parametrize("which,dense,sizes", [
    ("dense", 1, 7),        # windows 64 .. 2048 and the dense branch's n
    ("windows", None, 12),  # the parent: 64 .. 32768, no branch tagged dense
    (None, None, None),     # a program with no such counter
])
def test_reads_the_dense_tag(recorded, which, dense, sizes):
    recorded(which)
    assert metrics.read_metric(NAME, {}) == dense
    # its neighbour on the same counter goes on counting distinct sizes,
    # and falls with the shorter table
    assert metrics.read_metric("partition_window_sizes", {}) == sizes
    assert metrics.read_metric("hist_block_fetch", {}) == (
        1 if which else None)


def test_a_retraced_grower_still_reads_one_branch(recorded, monkeypatch):
    """A second trace of the grower doubles every count and adds no key:
    the metric counts branches the program was built with, not traces."""
    once = recorded("dense")["partition_route_dispatch"]
    twice = {k: 2 * v for k, v in once.items()}
    monkeypatch.setattr(_program_counters, "counter", lambda name: twice)
    assert metrics.read_metric(NAME, {}) == 1


def test_a_window_tagged_with_n_is_not_the_dense_branch(monkeypatch):
    """Only the ``read`` tag decides: a window whose size happens to be
    the row count is a window."""
    table = {"read=column,size=64": 1, "read=column,size=30000": 1}
    monkeypatch.setattr(_program_counters, "counter", lambda name: table)
    assert metrics.read_metric(NAME, {}) is None
