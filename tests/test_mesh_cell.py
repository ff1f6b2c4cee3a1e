"""The ``higgs-42m-data`` cell at a size the CPU holds: ``tree_learner=data``
over a 4 x 1 mesh of virtual devices (rows over four devices, columns whole)
through ``lgb.train`` and the GSPMD grower.

- The benchmark's own run (``benchmarks/run.run_cell`` with the four-chip
  driver) on 20,000 x 28 rows at 63 bins and 31 leaves: ``correct``, every
  compared number under the cell's limits, in both histogram forms; the
  trees are the serial learner's.
- Placement: every per-row array of the booster is row-sharded over the
  four devices in equal shards (PR 21's device-0 imbalance).
- The compiled grow program: no all-gather, no sort of more than a shard's
  rows, and the loop body's one reduction is one leaf's histogram table,
  which the program's ``mesh_layout`` event and counter say too.
- ``gspmd_hist=auto`` takes the form the one-device resolution took.
"""
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tests"))
sys.path.insert(0, ROOT)
from small import NO_CHIP, small_cell  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import cells, data  # noqa: E402

import jax.numpy as jnp  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.obs import memory as obs_memory  # noqa: E402
from lightgbm_tpu.obs.counters import counters  # noqa: E402
from lightgbm_tpu.parallel.mesh import plan_mesh  # noqa: E402
from lightgbm_tpu.utils.jaxpr_audit import (hlo_collective_census,  # noqa
                                            hlo_loop_census)

N, F, B = 20000, 28, 63
SEED = 2 ** 31 + 38


def mesh_cell(form):
    cell = small_cell("higgs-42m-data", "train-mesh", rows=N)
    cell.update(name="higgs-42m-data.train", chips=4)
    cell["config"]["params"].update(max_bin=B, gspmd_hist=form)
    return cell


def params(**extra):
    p = dict(cells.load_json("configs", "higgs-42m-data.json")["params"],
             num_leaves=31, max_bin=B, min_sum_hessian_in_leaf=10)
    p.update(extra)
    return p


@pytest.fixture(scope="module")
def problem():
    X, y, _, _ = data.make_problem(N, 0, F, SEED, 42)
    return X, y


@pytest.fixture(scope="module")
def booster(problem):
    X, y = problem
    counters.reset()
    bst = lgb.train(params(gspmd_hist="fused"), lgb.Dataset(X, label=y),
                    num_boost_round=3, verbose_eval=False)
    return bst, counters.events("mesh_layout"), counters.get(
        "grow_loop_collective_bytes")


def test_the_cell_is_in_the_benchmark():
    cell = cells.cell("higgs-42m-data.train")
    cfg, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 4 and traffic["driver"] == "train_mesh"
    assert (cfg["rows"], cfg["valid_rows"], cfg["columns"]) == (
        42000000, 0, 28)
    higgs = cells.load_json("configs", "higgs.json")
    assert cfg["params"] == dict(higgs["params"], tree_learner="data",
                                 mesh_shape="4x1")
    assert cfg["reduced"] == ["num_trees"]
    assert traffic["limits"] == cells.load_json("traffic",
                                                "train.json")["limits"]
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"collective_ms_per_tree", "hist_reduce_bytes_per_split",
            "hbm_device_spread", "tree_mfu", "peak_hbm_share"} <= reported


@pytest.mark.parametrize("form", ["fused", "flat"])
def test_data_parallel_run_is_correct(form, tmp_path):
    """The cell's own run on four devices: the reference
    (``harness/reference.py``, float64) follows the first three trees on
    every row; split columns and thresholds, leaf counts, leaf outputs and
    the scores the window left all within ``train.json``'s limits."""
    res = bench_run.run_cell(mesh_cell(form), SEED, 1.0, False, NO_CHIP,
                             trace_dir=str(tmp_path / "trace"))
    assert res["correct"], res["compared"]
    for name, v in res["compared"].items():
        assert v["value"] <= v["limit"], (name, v)
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_half_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    """The cell's run with the grower shown every second row only (the
    planted fault of ``benchmarks/control.py``): ``count_mismatch`` fails."""
    from lightgbm_tpu import boosting
    real = boosting.GBDT._sample

    def sample(self, it, g, h):
        g, h, cnt = real(self, it, g, h)
        keep = (jnp.arange(cnt.shape[0]) % 2 == 0).astype(cnt.dtype)
        return g * keep, h * keep, cnt * keep
    monkeypatch.setattr(boosting.GBDT, "_sample", sample)
    res = bench_run.run_cell(mesh_cell("fused"), SEED, 1.0, False, NO_CHIP,
                             trace_dir=str(tmp_path / "trace"))
    assert not res["correct"]
    assert res["compared"]["count_mismatch"]["value"] > 0, res["compared"]


def test_control_is_not_correct(tmp_path, monkeypatch):
    """The reference at bfloat16 in the program's place, on the trees the
    four devices grew, fails a limit of the cell that the program passes."""
    from benchmarks.harness import check, compare
    seen = {}
    real = check.check_training

    def with_control(*a, **kw):
        numbers, control, secs = real(
            *a, **dict(kw, control_precision="bfloat16"))
        seen["control"] = control
        return numbers, control, secs
    monkeypatch.setattr(check, "check_training", with_control)
    cell = mesh_cell("fused")
    res = bench_run.run_cell(cell, SEED, 1.0, False, NO_CHIP,
                             trace_dir=str(tmp_path / "trace"))
    assert res["correct"], res["compared"]
    limits = cell["traffic"]["limits"]
    rows, ok = compare.verdict(
        {k: v for k, v in seen["control"].items() if k in limits}, limits)
    assert not ok, rows
    assert [r[0] for r in rows if not r[3]] == ["leaf_gap_median"]


def test_the_trees_are_the_serial_learners(problem, booster):
    X, y = problem
    serial = lgb.train(params(tree_learner="serial"),
                       lgb.Dataset(X, label=y), num_boost_round=3,
                       verbose_eval=False)
    bst = booster[0]
    for a, b in zip(serial.inner.models, bst.inner.models):
        k = a.num_leaves
        assert b.num_leaves == k
        np.testing.assert_array_equal(a.split_feature[:k - 1],
                                      b.split_feature[:k - 1])
        np.testing.assert_array_equal(a.threshold[:k - 1],
                                      b.threshold[:k - 1])
        np.testing.assert_array_equal(a.leaf_count[:k], b.leaf_count[:k])
        # float32 sums in another order (the serial learner's XLA rung on
        # the CPU; each shard's fused kernel, then the psum): outputs of
        # about 0.05 agree to a few 1e-6
        np.testing.assert_allclose(a.leaf_value[:k], b.leaf_value[:k],
                                   rtol=0, atol=1e-5)
    np.testing.assert_allclose(bst.predict(X[:2000]),
                               serial.predict(X[:2000]), atol=1e-5)


def _even(arr, devices, axis=-1):
    shards = arr.addressable_shards
    assert {s.device for s in shards} == set(devices), arr.sharding
    assert {s.data.shape[axis] for s in shards} == {arr.shape[axis] // 4}, \
        arr.sharding


@pytest.mark.parametrize("name", ["bins", "scores", "gradients", "hessians",
                                  "bag_weight", "bag_cnt", "labels",
                                  "label_sign", "label_weight", "row_leaf"])
def test_per_row_state_is_even(booster, name):
    """Every array of N rows lives in four equal shards, one a device:
    none is whole on device 0."""
    g = booster[0].inner
    devices = list(g._gspmd_mesh.devices.flat)
    assert len(devices) == 4
    if name in ("gradients", "hessians"):
        arr = g._grad_fn(g.scores)[name == "hessians"]
    elif name == "row_leaf":
        z = g._dist_row_vec(g._bag_cnt)
        arr = g.grow(g.bins, z, z, z, g.meta,
                     jnp.ones((F,), bool))[1]
    elif name == "bins":
        return _even(g.bins, devices, axis=0)
    elif name == "scores":
        arr = g.scores
    elif name in ("bag_weight", "bag_cnt"):
        arr = getattr(g, "_" + name)
    else:
        arr = getattr(g.objective, {"labels": "labels",
                                    "label_sign": "_label_sign",
                                    "label_weight": "_label_weight"}[name])
    _even(arr, devices)


def test_grow_program_moves_one_table_a_split(booster):
    """The compiled 4 x 1 grow program: no all-gather of anything, no sort
    over more rows than a shard holds, and inside the loop body ONE
    all-reduce of a leaf's [F, B, 3] f32 table, which the program's own
    record of the census (event and counter) says."""
    bst, events, loop_bytes = booster
    g = bst.inner
    z = g._dist_row_vec(g._bag_cnt)
    text = g.grow.lower(g.bins, z, z, z, g.meta,
                        jnp.ones((F,), bool)).compile().as_text()
    census = hlo_collective_census(text)
    assert "all-gather" not in census and "all-to-all" not in census
    sorted_rows = [int(m) for m in re.findall(
        r"= \(?[a-z0-9]+\[(\d+)\][^=]*? sort\(", text)]
    assert sorted_rows and max(sorted_rows) <= N // 4, sorted_rows
    table = F * B * 3 * 4
    assert hlo_loop_census(text) == {
        "all-reduce": {"count": 1, "bytes": table, "max_bytes": table}}
    assert loop_bytes == {"op=all-reduce": table}
    (ev,) = events
    assert (ev["data"], ev["feature"], ev["rows_per_shard"],
            ev["hist_form"]) == (4, 1, N // 4, "fused")
    assert ev["loop_collectives"]["all-reduce"]["bytes"] == table
    assert ev["collectives"] == census


@pytest.mark.parametrize("one_device,mesh,want", [
    ("segment", "4x1", "segment"), ("fused", "4x1", "fused"),
    ("fused", "2x2", "segment")])
def test_gspmd_hist_auto_follows_the_one_device_method(problem, one_device,
                                                       mesh, want):
    """``auto`` takes the fused form where the one-device resolution took
    the fused kernel (on the chip; here ``cpu_hist_method`` stands in) and
    the mesh shards rows alone, the flat scatter-add elsewhere."""
    X, y = problem
    bst = lgb.train(params(cpu_hist_method=one_device, num_leaves=7,
                           mesh_shape=mesh),
                    lgb.Dataset(X[:4000], label=y[:4000]),
                    num_boost_round=1, verbose_eval=False)
    assert bst.inner._parallel_impl == "gspmd"
    assert bst.inner.grower_cfg.hist_method == want


def test_hlo_loop_census_reads_the_grow_loop_alone():
    text = """HloModule m

%branch.1 (p: f32[4]) -> f32[4] {
  %ar.2 = f32[4]{0} all-reduce(f32[4]{0} %p), to_apply=%add
}

%body.5 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %c = f32[4]{0} conditional(%i, %x), branch_computations={%branch.1, %empty}
  %ag = s32[8]{0} all-gather(s32[2]{0} %y), dimensions={0}
}

%small.7 (p: s32[]) -> s32[] {
  %x = s32[] add(%p, %p)
}

ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %root = f32[16]{0} all-reduce(f32[16]{0} %a), to_apply=%add
  %w = (s32[], f32[4]) while(%t), condition=%cond.4, body=%body.5
  %w2 = s32[] while(%u), condition=%cond.6, body=%small.7
}
"""
    assert hlo_loop_census(text) == {
        "all-reduce": {"count": 1, "bytes": 16, "max_bytes": 16},
        "all-gather": {"count": 1, "bytes": 32, "max_bytes": 32}}
    assert hlo_collective_census(text)["all-reduce"]["count"] == 2
    assert hlo_loop_census("ENTRY %m () -> f32[] {\n}\n") == {}


def test_predict_hbm_holds_the_4x1_plan():
    """The cell's plan at 42,000,000 x 28: the fused form's fullest device
    is the serial grower's at 10.5M rows, its two lane-padded panels most
    of it (the v5e compile here: 462,006,272 B of arguments, 10,761,206,272
    of temporaries, 116,052,992 of code; PERF.md, PR 38), and the planner
    keeps the rows whole over four row shards."""
    kw = dict(rows=42_000_000, features=28, bins=255, leaves=255)
    pred = obs_memory.predict_hbm(data_shards=4, gspmd_fused=True, **kw)
    compiled = 462_006_272 + 10_761_206_272 + 116_052_992
    assert 0.95 <= pred["peak_bytes"] / compiled <= 1.1, pred["peak_bytes"]
    assert pred["transients"]["staging"] == 2 * (10_500_000 + 1) * 128 * 4
    plan = plan_mesh(4, capacity=16_900_000_000, prefer="data",
                     gspmd_fused=True, **kw)
    assert (plan.data, plan.feature) == (4, 1)


def test_collective_reader_reads_the_opcode(monkeypatch):
    """``collective_ms_per_tree`` finds a collective by its HLO opcode in
    the operation's metadata (the v5e names the loop's all-reduce
    ``psum.50``) or by its short name, clips it to the window, and reads a
    tree's time on one device."""
    from benchmarks.harness import program_spans, xplane
    mod = cells.load_module("layer_metrics", "collective_ms_per_tree.py")
    dev = "/device:TPU:0"
    events = [
        {"plane": "/host:CPU", "line": "t", "name": "bench:window",
         "meta": "", "ts": 100.0, "dur": 1000.0},
        {"plane": dev, "line": "XLA Ops", "name": "psum.50", "ts": 200.0,
         "dur": 30.0, "meta": "%psum.50 = f32[28,255,3]{1,0,2} "
                             "all-reduce(%pad_maximum_fusion.9)"},
        {"plane": dev, "line": "XLA Ops", "name": "all-reduce-done.2",
         "meta": "", "ts": 1090.0, "dur": 40.0},      # half in the window
        {"plane": dev, "line": "XLA Ops", "name": "get-tuple-element.4",
         "meta": "%gte = f32[3] get-tuple-element(%all-reduce.38)",
         "ts": 300.0, "dur": 50.0},
        {"plane": dev, "line": "XLA Ops", "name": "fusion.1", "meta": "",
         "ts": 400.0, "dur": 70.0},
    ]
    monkeypatch.setattr(xplane, "read_events", lambda path, keep: events)
    monkeypatch.setattr(program_spans, "newest_capture", lambda: "x.pb")
    ctx = {"trace": {"scope_ms": {}}, "iterations": 2, "devices": 4}
    assert mod.read(ctx) == pytest.approx((30 + 10) / 1e6 / 2 / 4)
    assert mod.read({"trace": None, "iterations": 2}) is None


@pytest.mark.parametrize("peaks,want", [([100, 90, 95, 100], 10.0),
                                        ([0, 0, 0, 0], None), (None, None)])
def test_hbm_device_spread_reader(peaks, want):
    mod = cells.load_module("layer_metrics", "hbm_device_spread.py")
    ctx = {} if peaks is None else {"device_peaks": peaks}
    assert mod.read(ctx) == want


def test_hist_reduce_bytes_reader_sums_the_loop_reductions(monkeypatch):
    from benchmarks.layer_metrics import _program_counters
    mod = cells.load_module("layer_metrics",
                            "hist_reduce_bytes_per_split.py")
    got = {"op=all-reduce": 85680, "op=all-gather": 64,
           "op=reduce-scatter": 16}
    monkeypatch.setattr(_program_counters, "counter",
                        lambda name: got if name ==
                        "grow_loop_collective_bytes" else None)
    assert mod.read({}) == 85696.0
    monkeypatch.setattr(_program_counters, "counter", lambda name: None)
    assert mod.read({}) is None


@pytest.mark.parametrize("bagged", [False, True])
def test_leaf_rows_are_counted_as_integers(bagged, monkeypatch):
    """Past 2**24 rows the split scan's float32 counts round (the driver's
    run of the cell, PR 38: 8 leaves off by a row at 42M rows).  With the
    threshold taken to 0, the data-parallel grower counts each leaf's
    in-bag rows again as integers: they are the rows its ``row_leaf``
    holds, with and without a bag."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu import grower
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig
    from lightgbm_tpu.parallel.learner import make_distributed_grower
    monkeypatch.setattr(grower, "_F32_EXACT_ROWS", 0)
    n, f, b = 4096, 4, 15
    rng = np.random.default_rng(38)
    bins = rng.integers(0, b, size=(n, f), dtype=np.uint8)
    g = (rng.standard_normal(n) + 0.3 * bins[:, 0]).astype(np.float32)
    c = ((rng.random(n) < 0.7) if bagged else np.ones(n)).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    fn = make_distributed_grower(
        GrowerConfig(num_leaves=15, max_bin=b, min_data_in_leaf=1,
                     min_sum_hessian_in_leaf=0.0, hist_method="segment"),
        mesh, "data")
    rows = NamedSharding(mesh, P("data"))
    meta = FeatureMeta(jnp.full((f,), b, jnp.int32), jnp.zeros((f,), jnp.int32),
                       jnp.zeros((f,), jnp.int32), jnp.zeros((f,), bool))
    tree, row_leaf = fn(jax.device_put(bins, NamedSharding(mesh, P("data",
                                                                   None))),
                        jax.device_put(g * c, rows), jax.device_put(c, rows),
                        jax.device_put(c, rows), meta, jnp.ones((f,), bool))
    k = int(tree.num_leaves)
    assert k > 8
    want = np.bincount(np.asarray(row_leaf), weights=c, minlength=k)[:k]
    np.testing.assert_array_equal(np.asarray(tree.leaf_count)[:k], want)
