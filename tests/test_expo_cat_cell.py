"""The ``expo-cat`` cell at a size the CPU holds: the benchmark's own run
(``benchmarks/run.run_cell``: the program through ``lgb.train`` on a
``Dataset`` of 8 integer-coded columns with ``categorical_feature`` on all of
them, then the categorical plain reference following its first trees on the
raw codes) on 60,000 of the 10,000,000 rows, the 8 published cardinalities,
31 leaves: sound, with a category moved to the other side of a split, and
with the control.  Beside them: the configuration's figures, the draw, the
reference's scan against a loop transcription of the published rules, the
map check, the work the shares are counted at, the new metrics' readers and
the ``cat_scan`` scope in the grow program.  After
``tests/test_expo_cell.py``.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tests"))
sys.path.insert(0, ROOT)
from small import NO_CHIP, small_cell  # noqa: E402
from benchmarks import control_cat  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import (cells, check_cat, compare,  # noqa: E402
                                data_sparse, metrics, reference_cat, work)
from benchmarks.layer_metrics import _program_counters  # noqa: E402

CELL = "expo-cat.train-cat"
NEW_METRICS = {
    "cat_scan_ms_per_tree": ("split find (ops/split.py)", "trees_per_s"),
    "hist_width": ("histogram kernel", "trees_per_s")}
SIZES = [12, 31, 7, 24, 22, 297, 297, 10]


def small_cat_cell(rows=60000):
    cell = small_cell("expo-cat", "train-cat", rows=rows)
    cell["name"] = CELL
    return cell


def drive(cell, tmp_path, seed=2 ** 31 + 34):
    return bench_run.run_cell(cell, seed, 1.0, False, NO_CHIP,
                              trace_dir=str(tmp_path / "trace"))


def failed(result):
    return sorted(k for k, v in result["compared"].items()
                  if not (v["limit"] is not None and v["value"] <= v["limit"]))


def test_expo_cat_is_a_cell_of_the_benchmark():
    cell = cells.cell(CELL)
    cfg = cell["config"]
    assert (cfg["rows"], cfg["valid_rows"], cfg["columns"]) == (10000000, 0,
                                                                8)
    assert cfg["categorical_columns"] == list(range(8))
    assert cfg["reduced"] == ["num_trees"] and cfg["bin_bytes"] == 2
    expo = cells.load_json("configs", "expo.json")
    assert cfg["params"] == {**expo["params"], "max_cat_threshold": 256,
                             "max_cat_group": 64, "cat_smooth_ratio": 0.01,
                             "min_cat_smooth": 5, "max_cat_smooth": 100}
    assert (cfg["draw"], cfg["draw_seed"]) == (expo["draw"],
                                               expo["draw_seed"])
    assert cfg["draw"]["field_sizes"] == SIZES
    assert cfg["published"]["auc"] == 0.776217
    assert {"rows", "categories", "kept_bins", "rare_categories",
            "seed"} <= set(cfg["assumed"])
    assert len(cfg["source"]) <= 200
    assert cell["chips"] == 1 and cell["traffic"]["driver"] == "train_cat"
    # train.json's limits with the map check in bound_faults' place, and
    # the category sets' gains against the published scan's best
    want = dict(cells.load_json("traffic", "train.json")["limits"],
                cat_map_faults=0, cat_split_faults=5)
    del want["bound_faults"]
    assert cell["traffic"]["limits"] == want
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) | {"hist_roofline", "partition_roofline",
                               "tree_mfu", "split_find_ms_per_tree",
                               "hist_kernel_ms_per_tree",
                               "partition_ms_per_tree", "hist_col_tiles",
                               "compiles_in_window"} <= reported
    # the listed metrics of the layers this cell runs, as expo.train-sparse
    # was appended to them; not the bundles', the collectives' or the
    # ranking objective's
    sparse = {m["name"] for m in cells.cell("expo.train-sparse")["per_layer"]}
    assert sparse - reported == {
        "bundle_expand_ms_per_tree", "bundle_columns", "setup_bundle_s",
        "setup_bin_sparse_s", "bundle_decode_ms_per_tree"}
    assert reported - sparse == set(NEW_METRICS)
    assert {m["name"] for m in cell["end_to_end"]} == {"trees_per_s",
                                                       "setup_s"}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_entry_and_file_agree(name):
    entry = next(m for m in cells.benchmark()["per_layer"]
                 if m["name"] == name)
    spec = cells.load_json("layer_metrics", name + ".json")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["moves"]) == NEW_METRICS[name]


def test_the_draw_is_expos_rows_as_codes():
    """The codes are expo's one-hot columns less each field's first: the
    same rows in the same order, with the same labels, at any seed; the two
    297-category fields keep more than 256 bins even on 60,000 rows."""
    from benchmarks.drivers import train_cat
    cfg = cells.cell(CELL)["config"]
    seed = 2 ** 31 + 5
    X, y = train_cat.make_problem(30000, seed, cfg["draw_seed"], cfg["draw"])
    assert X.dtype == np.float32 and X.shape == (30000, 8)
    csr, ys = data_sparse.make_problem(30000, 700, seed, cfg["draw_seed"],
                                       cfg["draw"])
    starts = np.concatenate([[0], np.cumsum(SIZES)[:-1]])
    assert np.array_equal(csr.indices.reshape(30000, 8) - starts, X)
    assert np.array_equal(y, ys)
    assert (X >= 0).all() and (X < np.asarray(SIZES)).all()

    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import config_from_params
    X, y = train_cat.make_problem(60000, seed, cfg["draw_seed"], cfg["draw"])
    ds = lgb.Dataset(X, label=y, categorical_feature=list(range(8)))
    ds.construct(config_from_params(dict(cfg["params"])))
    kept = [m.num_bin for m in ds.constructed.bin_mappers]
    assert kept[:5] + kept[7:] == SIZES[:5] + SIZES[7:]
    assert 256 < kept[5] < 297 and 256 < kept[6] < 297
    assert ds.constructed.binned.dtype == np.uint16


def test_sound_categorical_run_is_correct(tmp_path):
    cell = small_cat_cell()
    res = drive(cell, tmp_path)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["compared"]) == set(cell["traffic"]["limits"])
    assert res["compared"]["count_mismatch"]["value"] == 0
    assert res["read_not_compared"]["split_gap"] < 0.05


def test_a_category_moved_is_not_correct(tmp_path, monkeypatch):
    control_cat.FAULTS["category_moved"](monkeypatch.setattr)
    res = drive(small_cat_cell(), tmp_path)
    assert not res["correct"]
    assert "count_mismatch" in failed(res), res["compared"]


@pytest.mark.parametrize("fault", ["no_group_limit", "no_smoothing"])
def test_a_scan_fault_is_not_correct(tmp_path, monkeypatch, fault):
    """The program's categorical scan run with the ``max_cat_group``
    accounting dropped, or the ratios unsmoothed: at many nodes its sets
    are not the published rules' best, either way, and
    ``cat_split_faults`` says so.  With the accounting dropped every other
    compared number holds: the sets are routed, counted and scored as the
    program made them."""
    control_cat.FAULTS[fault](monkeypatch.setattr)
    cell = small_cat_cell()
    res = drive(cell, tmp_path)
    assert not res["correct"]
    assert "cat_split_faults" in failed(res), res["compared"]
    assert res["read_not_compared"]["split_gap"] > \
        5 * reference_cat.SET_GAP_TOLERANCE
    if fault == "no_group_limit":
        assert failed(res) == ["cat_split_faults"], res["compared"]


def test_categorical_control_is_not_correct(tmp_path, monkeypatch):
    """bfloat16 gradients in the program's place fail a limit that the
    program, on the same trees, passes."""
    seen = {}
    real = check_cat.check_training

    def with_control(*a, **kw):
        numbers, control, secs = real(
            *a, **dict(kw, control_precision="bfloat16"))
        seen["control"] = control
        return numbers, control, secs
    monkeypatch.setattr(check_cat, "check_training", with_control)
    cell = small_cat_cell()
    res = drive(cell, tmp_path, 2 ** 31 + 134)
    assert res["correct"], res["compared"]
    limits = cell["traffic"]["limits"]
    rows, ok = compare.verdict(
        {k: v for k, v in seen["control"].items() if k in limits}, limits)
    assert not ok, rows
    fails = [r[0] for r in rows if not r[3]]
    assert "leaf_gap_median" in fails and len(fails) < len(rows), rows


def loop_scan(g, h, c, used_bin, n_bins, full, params):
    """The published scan, one histogram, one position at a time: the
    plain transcription ``reference_cat.scan`` is checked against."""
    l1, l2 = params["lambda_l1"], params["lambda_l2"]
    min_data, min_hess = params["min_data_in_leaf"], \
        params["min_sum_hessian_in_leaf"]
    max_thr, max_group = params["max_cat_threshold"], params["max_cat_group"]

    def leaf_gain(s, hh):
        r = max(abs(s) - l1, 0.0)
        return r * r / (hh + l2)
    G, H, C = sum(g), sum(h), sum(c)
    tot_h = H + 2e-15
    parent = leaf_gain(G, tot_h)
    smooth_h = min(params["max_cat_smooth"],
                   max(params["cat_smooth_ratio"] * C / n_bins,
                       params["min_cat_smooth"]))
    smooth_g = smooth_h * G / (H if H != 0 else 1.0)
    sorted_idx = sorted(range(used_bin),
                        key=lambda i: (g[i] + smooth_g) / (h[i] + smooth_h))
    dirs = [1] if full and 2 * max_thr >= n_bins else [1, -1]
    best, best_dir, best_pos = -np.inf, 0, 0
    for d in dirs:
        at = 0 if d == 1 else used_bin - 1
        rest, per_group = max_group, max(1.0, np.floor(C / max_group))
        group, lg, lh, lc = 0.0, 0.0, 1e-15, 0.0
        for i in range(min(used_bin, max_thr)):
            t = sorted_idx[at]
            at += d
            lg, lh, lc = lg + g[t], lh + h[t], lc + c[t]
            group += c[t]
            if lc < min_data or lh < min_hess:
                continue
            rc, rh = C - lc, tot_h - lh
            if rc < min_data or rh < min_hess:
                break
            if group < per_group:
                continue
            group = 0.0
            rest -= 1
            if rest > 0:
                per_group = max(1.0, np.floor(rc / rest))
            gain = leaf_gain(lg, lh) + leaf_gain(G - lg, rh)
            if gain <= parent + params["min_gain_to_split"]:
                continue
            if gain - parent > best:
                best, best_dir, best_pos = gain - parent, d, i
    return best, best_dir, best_pos


@pytest.mark.parametrize("seed", range(6))
def test_reference_scan_matches_the_loop_transcription(seed):
    """Random histograms with tied ratios, empty bins, full and
    zero-as-missing columns, few groups and a short threshold: the
    vectorized scan picks the loop's gain, direction and position."""
    rng = np.random.RandomState(seed)
    k, b = 40, 37
    c = rng.randint(0, 6, (k, b)).astype(np.float64)
    c[rng.rand(k, b) < 0.15] = 0
    h = c * rng.choice([0.25, 0.5], (k, b))
    g = rng.randint(-3, 4, (k, b)) * h            # many equal ratios
    n_bins = rng.randint(3, b + 1, k)
    full = rng.rand(k) < 0.5
    used_bin = n_bins - 1 + full
    for row, nb in enumerate(n_bins):
        g[row, nb:] = h[row, nb:] = c[row, nb:] = 0
    params = {"lambda_l1": 0.0, "lambda_l2": [0.0, 1.0][seed % 2],
              "min_data_in_leaf": 2, "min_sum_hessian_in_leaf": 0.5,
              "min_gain_to_split": 0.0, "max_cat_threshold": [8, 32][seed % 2],
              "max_cat_group": [3, 64][seed // 3], "cat_smooth_ratio": 0.01,
              "min_cat_smooth": [0.5, 5.0][seed // 3], "max_cat_smooth": 100.0}
    best, d, pos, _ = reference_cat.scan(g, h, c, used_bin, n_bins, full,
                                         params)
    taken = 0
    for row in range(k):
        want = loop_scan(g[row], h[row], c[row], int(used_bin[row]),
                         int(n_bins[row]), bool(full[row]), params)
        assert np.isfinite(best[row]) == np.isfinite(want[0]), row
        if np.isfinite(want[0]):
            taken += 1
            assert best[row] == pytest.approx(want[0], rel=1e-12), row
            assert (d[row], pos[row]) == want[1:], row
    assert taken > k // 2


def test_set_gaps_read_and_count_either_way():
    """``split_gap`` reads the widest gap either way, against the best or
    the median node's, and 1 where the reference would not split;
    ``cat_split_faults`` counts the nodes past the tolerance."""
    best = np.array([10.0, 4.0, 2.0, 4.0, -np.inf])
    given = np.array([10.0, 4.4, 1.9, 4.002, -np.inf])
    assert reference_cat.split_gap(best[:4], given[:4]) == pytest.approx(0.1)
    assert reference_cat.split_gap(best, given) == 1.0
    assert reference_cat.cat_split_faults(best[:4], given[:4]) == 2
    assert reference_cat.cat_split_faults(best, given) == 3


@pytest.mark.parametrize("kept,width", [
    (7, 7), (255, 255), (256, 256), (257, 288), (278, 288), (279, 288),
    (288, 288), (289, 320), (512, 512), (600, 608)])
def test_wide_layouts_share_a_width(kept, width):
    """Up to 256 bins the grower is built at the data's width, as it always
    was; past 256 at a multiple of 32, so the 278 and the 279 kept bins of
    two samplings of ``expo-cat`` build one grow program."""
    from lightgbm_tpu.grower import layout_width
    assert layout_width(kept) == width


def test_reference_routes_by_the_raw_codes():
    """Left where a row's code is in the node's set; a code no set holds,
    unseen by the map or not, goes right."""
    codes = np.array([[0, 1, 2, 3, 4, 5, 9]], np.int64)
    tree = {"num_leaves": 3, "split_feature": np.array([0, 0]),
            "left_child": np.array([1, ~0]), "right_child": np.array([~2, ~1]),
            "cat_codes": [np.array([1, 2, 5]), np.array([2])]}
    assert reference_cat.route(codes, tree).tolist() == [2, 1, 0, 2, 2, 1, 2]


@pytest.mark.parametrize("maps,faults", [
    ([([0, 1, 2, 3], 4)], 0),
    ([([0, 1, 2, 3, 1], 5)], 1),              # a category in two bins
    ([([0, 1, 2, 3], 5)], 1),                 # a bin with no category
    ([([0, 1, 2, 3, 7], 5)], 1),              # ... none the data holds
    ([([0, 1], 2)], 2),                       # too few bins, too little kept
])
def test_map_faults_are_counted(maps, faults):
    """Four categories of 40, 30, 20 and 10 rows."""
    codes = np.repeat(np.arange(4), [40, 30, 20, 10])[None, :]
    assert reference_cat.map_faults(codes, maps, 255, 100) == faults


def test_coverage_allows_for_the_sample():
    """A map whose kept categories cover 98.95 % of all rows is sound from a
    sample of 200,000 of 10,000,000 rows and short from all of them."""
    tol = reference_cat.cover_tolerance(200000, 10 ** 7)
    assert 1e-3 < tol < 1.2e-3
    assert reference_cat.cover_tolerance(60000, 60000) == 0.0
    codes = np.repeat(np.arange(3), [9000, 895, 105])[None, :]
    maps = [([0, 1], 2)]
    assert reference_cat.map_faults(codes, maps, 2, 9000) == 0
    assert reference_cat.map_faults(codes, maps, 2, 10000) == 1


def test_work_is_counted_at_the_kept_bins():
    """``drivers/train_cat.py`` hands ``work.tree_work`` 8 two-byte
    columns and a table of the 8 columns' real kept bins, not 8 x 512 or
    8 x the widest."""
    kept = [12, 31, 7, 24, 22, 279, 278, 10]
    shape = {"rows": 100, "columns": 8, "bins": -(-sum(kept) // 8),
             "bin_bytes": 2}
    w = work.tree_work(shape, [~0], [~1], [100], [60, 40])
    table = 8 * 83 * work.HIST_ENTRY_BYTES
    assert w["histogram"]["bytes"] == 140 * (16 + 8) + 2 * table + 2 * table
    assert w["partition"]["bytes"] == 100 * (8 + 2)


@pytest.mark.parametrize("tags,want", [
    ({"col_tiles=1,fetch=block,hi=24,interpret=False,method=fused,"
      "site=root,width=279": 1,
      "col_tiles=1,fetch=rows,hi=24,interpret=False,method=fused,"
      "site=split,width=279": 254}, 279),
    ({"interpret=False,method=einsum,site=root": 1}, None),  # the fall
    ({"col_tiles=1,fetch=block,interpret=False,method=fused,site=root": 1},
     None),                                      # a program without the tag
    (None, None)])
def test_hist_width_reads_the_program_counter(monkeypatch, tags, want):
    monkeypatch.setattr(_program_counters, "counter",
                        lambda n: tags if n == "hist_dispatch" else None)
    assert metrics.read_metric("hist_width", {}) == want


def test_cat_scan_ms_reads_its_own_pass():
    """The token is read by a pass of its own (the second pass's tokens,
    which every traced cell pays for, stay as they were), nested or not."""
    from benchmarks.harness import sub_scopes, trace
    assert "cat_scan" not in sub_scopes.tokens_wanted()[0]
    ctx = {"iterations": 4, "trace": {"scope_ms": {}},
           "cat_scan_pass": {"ns": {"cat_scan": 2e8}}}
    assert metrics.read_metric("cat_scan_ms_per_tree", ctx) == 50.0
    ctx["cat_scan_pass"] = {"ns": {}}
    assert metrics.read_metric("cat_scan_ms_per_tree", ctx) is None
    assert metrics.read_metric("cat_scan_ms_per_tree", {}) is None

    def op(name, ts, dur, meta=""):
        return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name,
                "meta": meta, "ts": ts, "dur": dur}
    events = [
        {"plane": "/host:CPU", "line": "python", "name": "bench:window",
         "meta": "", "ts": 0.0, "dur": 1000.0},
        {"plane": "/device:TPU:0", "line": "XLA Modules",
         "name": "jit_grow_tree_s3(1)", "meta": "", "ts": 0.0,
         "dur": 1000.0},
        op("fusion.1", 10.0, 30.0, "split_find/cat_scan/sort"),
        op("fusion.2", 50.0, 20.0, "split_find/argmax"),
        op("fusion.3", 80.0, 10.0, "partition/part_route/x")]
    got = sub_scopes.reduce_sub_scopes(events, ["cat_scan"], ["split_find"],
                                       [])
    assert got["ns"] == {"cat_scan": 30.0}
    assert trace.reduce_trace(events, ["split_find"])["scope_ms"][
        "split_find"] == pytest.approx(50.0 / 1e6)


def _grow_jaxpr(categorical, b=40):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower
    n, f = 2048, 3
    cfg = GrowerConfig(num_leaves=7, min_data_in_leaf=1, max_bin=b,
                       hist_method="segment", has_missing=False,
                       has_categorical=categorical)
    meta = FeatureMeta(num_bin=jnp.full((f,), b, jnp.int32),
                       missing_type=jnp.zeros((f,), jnp.int32),
                       default_bin=jnp.zeros((f,), jnp.int32),
                       is_categorical=jnp.full((f,), categorical))
    rng = np.random.RandomState(0)
    return jax.make_jaxpr(make_grower(cfg))(
        jnp.asarray(rng.randint(0, b, (n, f)).astype(np.uint8)),
        jnp.asarray(rng.randn(n).astype(np.float32)),
        jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32), meta,
        jnp.ones((f,), bool))


def _stacks(jaxpr, outer=""):
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        yield stack, eqn.primitive.name
        for val in eqn.params.values():
            for v in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    yield from _stacks(sub, stack)


def test_cat_scan_sits_inside_split_find():
    """The categorical scan's sort, prefix sums and group-accounting loop
    stand under ``split_find/cat_scan`` (a trace reads the inner token in
    its second pass); a grower with no categorical column has no such
    scope."""
    stacks = list(_stacks(_grow_jaxpr(True).jaxpr))
    cat = [(s, p) for s, p in stacks if "cat_scan" in s]
    assert cat
    assert all("split_find" in s.split("cat_scan")[0] for s, _ in cat)
    prims = {p for _, p in cat}
    assert {"sort", "cumsum", "scan"} <= prims, prims
    plain = list(_stacks(_grow_jaxpr(False).jaxpr))
    assert not any("cat_scan" in s for s, _ in plain)


def test_categorical_past_256_bins_trains_on_the_fused_kernel():
    """A 297-category column through ``lgb.train`` with the fused kernel
    asked for: uint16 bins, the kernel built at the data's width rounded up
    to a multiple of 32 with a 24-row hi one-hot (interpreted here), no
    ``layout_downgrade``, and categorical splits in the trees."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.counters import counters
    counters.reset()
    rng = np.random.RandomState(3)
    n = 6000
    # even categories: 99 % of the rows take about 294 of them
    cat = rng.randint(0, 297, n).astype(np.float64)
    y = (np.sin(cat) + 0.3 * rng.randn(n) > 0).astype(np.float64)
    ds = lgb.Dataset(np.stack([cat, rng.randn(n)], 1), label=y,
                     categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 4, "verbose": -1,
                     "min_data_in_leaf": 5, "max_bin": 255,
                     "cpu_hist_method": "fused"},
                    ds, num_boost_round=2, verbose_eval=False)
    kept = ds.constructed.bin_mappers[0].num_bin
    width = bst.inner.grower_cfg.max_bin
    assert 256 < kept <= 297
    assert width == -(-kept // 32) * 32      # grower.layout_width
    assert bst.inner.grower_cfg.hist_method == "fused"
    assert not counters.events("layout_downgrade")
    assert set(counters.get("hist_dispatch")) == {
        f"col_tiles=1,fetch={fetch},hi=24,interpret=True,method=fused,"
        f"site={s},width={width}" for s, fetch in (("root", "block"),
                                                   ("split", "rows"))}
    assert any(t.num_cat for t in bst.inner.models)
