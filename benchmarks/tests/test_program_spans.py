"""The program's ``lgb:`` spans read out of a capture (``harness/
program_spans.py``), and the six per-layer metrics that PR 25 added.

Self time and the idle gaps by span are checked on events built by hand; the
readers are driven through ``small.py``'s cell on the CPU, where the counters
read numbers and a capture without a device plane reads nothing.

    python3 -m pytest benchmarks/tests -q
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from small import NO_CHIP, small_cell  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import cells, metrics, program_spans  # noqa: E402

NEW_METRICS = ("objective_ms_per_tree", "score_update_ms_per_tree",
               "idle_in_iteration_ms_per_iter", "setup_bin_s",
               "setup_init_s", "compile_s")


def host(name, a, b):
    return {"plane": "/host:CPU", "line": "python3", "name": name,
            "meta": name, "ts": float(a), "dur": float(b - a)}


def device(name, a, b, line="XLA Ops"):
    return {"plane": "/device:TPU:0", "line": line, "name": name,
            "meta": "", "ts": float(a), "dur": float(b - a)}


def by_hand():
    """Two iterations in a window of 100 us (times in ns).  The first has
    its phases; the second has none, so all of it is its own."""
    return [
        host("bench:window", 0, 100_000),
        host("lgb:train", -5_000, 200_000),
        host("lgb:iteration", 10_000, 50_000),
        host("lgb:boosting", 11_000, 15_000),
        host("lgb:tree", 16_000, 45_000),
        host("lgb:tree.wait", 18_000, 43_000),
        host("lgb:tree.host", 43_500, 44_500),
        host("lgb:score", 46_000, 49_000),
        host("lgb:iteration", 60_000, 95_000),
        host("bench:update", 9_000, 51_000),        # not the program's
        device("fusion.1", 12_000, 14_000),
        # a while holding its body's operations: busy once, not twice
        device("while.2", 17_000, 42_000),
        device("fusion.3", 17_500, 30_000),
        device("histogram.4", 30_200, 41_000),
        device("fusion.5", 47_000, 48_500),
        device("copy.6", 48_900, 49_000),           # a 400 ns gap before it
        device("while.2", 61_000, 94_000),
        device("jit_get_gradients(1)", 12_000, 14_000, "XLA Modules"),
        device("jit_grow_tree(2)", 17_000, 42_000, "XLA Modules"),
        device("jit__update_score(3)", 47_000, 48_500, "XLA Modules"),
        device("jit_grow_tree(2)", 61_000, 94_000, "XLA Modules"),
        device("jit_grow_tree(2)", 150_000, 160_000, "XLA Modules"),
    ]


def test_self_time_by_hand():
    spans = program_spans.spans_of(by_hand())
    assert [s["name"] for s in spans][:3] == ["train", "iteration",
                                              "boosting"]
    self_ns = {}
    for s in spans:
        self_ns.setdefault(s["name"], []).append(s["self_ns"])
    # 40 us less boosting 4, tree 29, score 3; the second has no child
    assert self_ns["iteration"] == [4_000, 35_000]
    assert self_ns["tree"] == [29_000 - 25_000 - 1_000]
    assert self_ns["tree.wait"] == [25_000]
    assert self_ns["train"] == [205_000 - 40_000 - 35_000]
    parents = {s["name"]: s["parent"] for s in spans}
    assert parents["tree.wait"] == "tree" and parents["tree"] == "iteration"
    assert parents["iteration"] == "train" and parents["train"] is None


def test_idle_gaps_by_span_by_hand():
    r = program_spans.reduce_spans(by_hand())
    # busy: [12,14] [17,42] [47,48.5] [48.9,49] [61,94] us; every gap cut
    # at the spans' edges, each piece to the narrowest span over it:
    #   [0,12]     train 10, iteration 1, boosting 1
    #   [14,17]    boosting 1, iteration 1, tree 1
    #   [42,47]    tree.wait 1, tree .5, tree.host 1, tree .5,
    #              iteration 1, score 1
    #   [48.5,48.9] under 1 us: no gap
    #   [49,61]    iteration 1, train 10, iteration 1
    #   [94,100]   iteration 1, train 5
    assert r["idle_ns"] == {
        "train": 25_000, "iteration": 6_000, "boosting": 2_000,
        "tree": 2_000, "tree.wait": 1_000, "tree.host": 1_000,
        "score": 1_000}
    assert r["idle_in_iteration_ns"] == 13_000
    assert r["iterations"] == 2 and r["window_ns"] == 100_000
    busy = 2_000 + 25_000 + 1_500 + 100 + 33_000
    assert sum(r["idle_ns"].values()) == 100_000 - busy - 400
    # each program's time in the window, by the module line
    assert r["program_ns"] == {"jit_get_gradients": 2_000,
                               "jit_grow_tree": 58_000,
                               "jit__update_score": 1_500}
    assert r["span_self_ns"]["iteration"] == 39_000


def test_a_program_without_the_spans_reads_empty_tables():
    """The parent of the PR that brought the spans: the same capture with
    no ``lgb:`` event gives no iteration and all idle time to no span."""
    events = [e for e in by_hand() if not e["name"].startswith("lgb:")]
    r = program_spans.reduce_spans(events)
    assert r["iterations"] == 0 and r["idle_in_iteration_ns"] == 0
    assert set(r["idle_ns"]) == {program_spans.NO_SPAN}
    assert program_spans.reduce_spans(
        [e for e in events if e["line"] != "XLA Ops"]) is None
    assert program_spans.reduce_spans([]) is None


def test_tables_are_said_on_standard_error(capsys):
    program_spans.say_tables(program_spans.reduce_spans(by_hand()))
    err = capsys.readouterr().err
    assert "program idle gaps: train=0.025 iteration=0.006" in err
    assert "tree.wait=0.001" in err
    assert "device programs: jit_grow_tree=0.029" in err


def test_recorded_capture_reduces_to_the_same_numbers_every_time():
    """A cut of a real chip run's capture (higgs.train, TPU v5 lite, PR 25:
    the window's third iteration with 3 ms either side, 48 events; the
    trees are pipelined, so the callback's wait for the grower lies outside
    the iteration and ``tree.wait`` is the fetch of an older tree)."""
    with open(os.path.join(HERE, "recorded_program_spans.json")) as f:
        events = json.load(f)
    first = program_spans.reduce_spans(events)
    again = program_spans.reduce_spans(list(reversed(events)))
    assert first["idle_ns"] == again["idle_ns"]
    assert first["iterations"] == again["iterations"] == 1
    assert first["window_ns"] == 17583100.0
    idle = {k: round(v) for k, v in first["idle_ns"].items()}
    # the chip waits while the host dispatches: before the gradient
    # program (boosting), before the grower (tree), and in the callback
    assert idle == {"(no lgb span)": 1766560, "iteration": 333550,
                    "boosting": 1073089, "bagging": 8030, "tree": 2141673}
    assert round(first["idle_in_iteration_ns"]) == 3556341
    assert round(first["program_ns"]["jit_get_gradients"]) == 317747
    assert round(first["span_self_ns"]["tree"]) == 5315490
    parents = {s["name"]: s["parent"]
               for s in program_spans.spans_of(events)}
    for child, parent in (("boosting", "iteration"), ("tree", "iteration"),
                          ("tree.wait", "tree"), ("tree.host", "tree"),
                          ("score", "iteration")):
        assert parents[child] == parent, (child, parents)
    # every piece of idle time is put down once: what is left over is the
    # gaps under a microsecond
    ops = [e for e in events if program_spans.is_device(e, "XLA Ops")]
    busy = sum(b - a for a, b in program_spans.busy_intervals(
        ops, 0.0, first["window_ns"]))
    left = first["window_ns"] - busy - sum(first["idle_ns"].values())
    assert 0 <= left < 1000.0


def test_newest_capture_is_this_processes(tmp_path):
    old = tmp_path / "a" / "plugins" / "profile" / "1"
    new = tmp_path / "b" / "plugins" / "profile" / "2"
    for d in (old, new):
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(b"")
    os.utime(old / "vm.xplane.pb", (1000, 1000))
    os.utime(new / "vm.xplane.pb", (2000, 2000))
    root = str(tmp_path)
    assert program_spans.newest_capture(root, since=0) \
        == str(new / "vm.xplane.pb")
    assert program_spans.newest_capture(root, since=1500) \
        == str(new / "vm.xplane.pb")
    # a capture an earlier process left is not this run's
    assert program_spans.newest_capture(root, since=3000) is None
    assert program_spans.newest_capture(root) is None
    assert abs(program_spans.process_start() - os.stat(
        f"/proc/{os.getpid()}").st_ctime) < 5 * 60


@pytest.fixture
def traced_small_run(tmp_path, monkeypatch):
    """One traced run of the small cell on the CPU, the helper pointed at
    the run's own trace directory."""
    root = tmp_path / "trace"
    monkeypatch.setattr(program_spans, "TRACE_ROOT", str(root))
    monkeypatch.setattr(program_spans, "_loaded", {})
    return bench_run.run_cell(small_cell(), 2 ** 31 + 25, 1.0, True, NO_CHIP,
                              trace_dir=str(root / "higgs.train"))


def test_new_readers_through_the_small_cell(traced_small_run, capfd):
    res = traced_small_run
    assert res["correct"], res["compared"]
    got = res["metrics"]
    # the counters read numbers wherever the program runs
    for name in ("setup_bin_s", "setup_init_s", "compile_s"):
        assert got[name]["value"] > 0 and got[name]["unit"] == "s", got
    # upload and grower build lie inside booster set-up; a run compiles
    assert got["setup_init_s"]["value"] < 60
    # the CPU's capture has no device plane: the trace readers find nothing
    # to read there and the line leaves them out, without raising
    assert program_spans.load() is None
    for name in ("objective_ms_per_tree", "score_update_ms_per_tree",
                 "idle_in_iteration_ms_per_iter"):
        assert name not in got or got[name]["value"] >= 0
    # but the capture holds the program's spans, on the window's clock
    events = program_spans.read_capture(program_spans.newest_capture())
    spans = program_spans.spans_of(events)
    window = next(e for e in events if e["name"] == "bench:window")
    inside = [s for s in spans if s["name"] == "iteration"
              and s["ts"] >= window["ts"]
              and s["end"] <= window["ts"] + window["dur"]]
    assert len(inside) == res["attempted"] >= 1
    assert {"tree", "tree.wait", "tree.host", "boosting", "score"} <= {
        s["name"] for s in spans}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_is_listed_and_reads(name, monkeypatch):
    """Entry, file and reader agree; fed the by-hand capture and the
    program's registry, every reader gives a number; with nothing to read
    it gives None and does not raise."""
    entry = next(m for m in cells.benchmark()["per_layer"]
                 if m["name"] == name)
    spec = cells.load_json("layer_metrics", name + ".json")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    assert entry["workloads"] == ["higgs.train", "higgs-63bin.train"]

    from lightgbm_tpu.obs.counters import counters
    monkeypatch.setattr(program_spans, "_loaded",
                        {"result": program_spans.reduce_spans(by_hand())})
    counters.reset()
    counters.inc("phase_seconds", 5.25, phase="dataset.construct")
    counters.inc("phase_seconds", 10.5, phase="setup.device")
    counters.inc("compile_seconds", 7.0, fun="get_gradients",
                 stage="backend")
    counters.inc("compile_seconds", 1.5, fun="grow_tree", stage="trace")
    ctx = {"iterations": 2,
           "trace": {"scope_ms": {"objective": 0.5, "score_update": 0.25}}}
    expected = {"objective_ms_per_tree": 0.25,
                "score_update_ms_per_tree": 0.125,
                "idle_in_iteration_ms_per_iter": 13_000 / 1e6 / 2,
                "setup_bin_s": 5.25, "setup_init_s": 10.5, "compile_s": 8.5}
    assert metrics.read_metric(name, ctx) == pytest.approx(expected[name])

    counters.reset()
    monkeypatch.setattr(program_spans, "_loaded", {"result": None})
    empty = {"iterations": 2, "trace": {"scope_ms": {}}}
    assert metrics.read_metric(name, empty) is None
    assert metrics.read_metric(name, {"iterations": 2, "trace": None}) \
        is None
