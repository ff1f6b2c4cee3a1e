"""The reduction from a trace to numbers, on a recorded trace cut from a
real chip run (higgs.train-eval, TPU v5 lite, PR 24): the same per-scope
milliseconds, busy union and idle gaps every time, and the arithmetic of
self time and of the union on events built by hand."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
from benchmarks.harness import trace  # noqa: E402

SCOPES = ["histogram", "split_find", "partition"]


def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def close(a, b):
    return abs(a - b) <= 1e-9 * max(abs(a), abs(b), 1e-12)


def test_recorded_trace_reduces_to_the_same_numbers_every_time():
    first = trace.reduce_trace(recorded(), SCOPES)
    again = trace.reduce_trace(list(reversed(recorded())), SCOPES)
    assert first == again
    assert close(first["window_s"], 1.375439754)
    assert close(first["busy_s"], 1.3642355846999998)
    assert close(first["scope_ms"]["histogram"], 658.8415000159999)
    assert close(first["scope_ms"]["partition"], 657.4670947640001)
    assert close(first["scope_ms"]["split_find"], 0.00408414)
    assert close(first["program_other_ms"], 46.09516656200001)
    assert first["device_ops"][0][0] == "histogram/histogram.12"
    assert [g[0] for g in first["idle_gaps"]] == ["bench:eval",
                                                  "bench:update"]
    # nothing is counted twice: scopes and the rest add up to the program
    assert close(sum(first["scope_ms"].values()) + first["program_other_ms"],
                 first["program_ms"])
    assert first["busy_s"] <= first["window_s"]


def op(name, ts, dur, meta=""):
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name,
            "meta": meta, "ts": 100.0 * ts, "dur": 100.0 * dur}


def test_self_time_and_union_by_hand():
    events = [
        {"plane": "/host:CPU", "line": "python3", "name": "bench:window",
         "meta": "", "ts": 0.0, "dur": 100000.0},
        {"plane": "/host:CPU", "line": "python3", "name": "bench:eval",
         "meta": "", "ts": 70000.0, "dur": 30000.0},
        # a while of 60 us holding a partition fusion of 20 and a
        # histogram call of 30: 10 us are the loop's own
        op("while.1", 0, 600, "jit(grow_tree)/while"),
        op("fusion.7", 50, 200, "jit(grow_tree)/while/body/partition/gather"),
        op("histogram.3", 300, 300, "jit(grow_tree)/while/body/histogram/x"),
        op("copy.9", 650, 50, "jit(_update_score)/copy"),
    ]
    r = trace.reduce_trace(events, SCOPES)
    assert r["scope_ms"]["partition"] == 20000 / 1e6
    assert r["scope_ms"]["histogram"] == 30000 / 1e6
    assert r["program_other_ms"] == 10000 / 1e6
    assert r["busy_s"] == 65000 / 1e9           # [0, 600] and [650, 700]
    assert r["window_s"] == 100000 / 1e9
    gaps = dict(r["idle_gaps"])
    assert gaps["bench:eval"] == 30000 / 1e9    # [700, 1000]
    assert gaps[trace.UNSPANNED] == 5000 / 1e9  # [600, 650]


def test_a_trace_with_no_device_operation_reads_nothing():
    assert trace.reduce_trace([], SCOPES) is None
