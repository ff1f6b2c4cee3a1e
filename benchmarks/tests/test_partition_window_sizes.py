"""``partition_window_sizes`` (PR 30) read from recorded counter snapshots:
the program that tags each partition branch with its window's ``size``, and
its parent, which does not.  ``recorded_counters.json`` says how both were
taken.

    python3 -m pytest benchmarks/tests -q
"""
import json
import os

import pytest

from benchmarks.harness import cells, metrics
from benchmarks.layer_metrics import _program_counters

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "partition_window_sizes"


@pytest.fixture
def recorded(monkeypatch):
    with open(os.path.join(HERE, "recorded_counters.json")) as f:
        snapshots = json.load(f)

    def use(which):
        table = snapshots[which] if which else {}
        monkeypatch.setattr(_program_counters, "counter",
                            lambda name: table.get(name) or None)
    return use


def test_entry_file_and_reader_agree():
    entry = next(m for m in cells.benchmark()["per_layer"]
                 if m["name"] == NAME)
    spec = cells.load_json("layer_metrics", NAME + ".json")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "partition", "trees_per_s", "program_counter")
    assert entry["workloads"] == [w["name"]
                                  for w in cells.benchmark()["workloads"]]


@pytest.mark.parametrize("which,want", [
    ("tagged", 12),     # 64 .. 32768 with the half-steps 12288 and 24576
    ("untagged", None),  # the parent counts its branches under no size
    (None, None),       # a program with no such counter
])
def test_reads_the_distinct_sizes(recorded, which, want):
    recorded(which)
    assert metrics.read_metric(NAME, {}) == want
    # the neighbours that read the same registry are not disturbed
    assert metrics.read_metric("hist_col_tiles", {}) == (1 if which else None)
    assert metrics.read_metric("hist_block_fetch", {}) == (
        1 if which else None)


def test_a_retraced_grower_counts_each_size_once(recorded, monkeypatch):
    """A second trace of the grower doubles every count and adds no size."""
    recorded("tagged")
    once = _program_counters.counter("partition_route_dispatch")
    twice = {k: 2 * v for k, v in once.items()}
    monkeypatch.setattr(_program_counters, "counter", lambda name: twice)
    assert metrics.read_metric(NAME, {}) == 12
