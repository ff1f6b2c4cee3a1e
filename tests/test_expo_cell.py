"""The ``expo`` cell at a size the CPU holds: the benchmark's own run
(``benchmarks/run.run_cell``: the program through ``lgb.train`` on a
``Dataset`` over a ``scipy.sparse.csr_matrix``, then the sparse plain
reference following its first trees on the stored entries) on 60,000 of the
10,000,000 rows, all 700 columns, 31 leaves: sound, with each planted fault
and with the control (``benchmarks/tests/test_correct_sparse.py``, whose tests
run here too).  Beside them: the configuration's figures, the draw, the
sparse reference against the dense one, the work the shares are counted at,
the new metrics' readers, and the two device scopes in the grow program.
After ``tests/test_msltr_cell.py``.
"""
import os
import sys

import numpy as np
import pytest
import scipy.sparse

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tests"))
sys.path.insert(0, ROOT)
from test_correct_sparse import (  # noqa: E402,F401
    test_planted_fault_is_not_correct, test_sound_sparse_run_is_correct,
    test_sparse_control_is_not_correct)
from benchmarks.harness import (cells, data_sparse, metrics,  # noqa: E402
                                reference, reference_sparse, work)
from benchmarks.layer_metrics import _program_counters  # noqa: E402

CELL = "expo.train-sparse"
NEW_METRICS = {
    "bundle_expand_ms_per_tree": ("split find (ops/split.py)", "trees_per_s"),
    "bundle_columns": ("data (Dataset.construct)", "trees_per_s"),
    "setup_bundle_s": ("data (Dataset.construct)", "setup_s"),
    "setup_bin_sparse_s": ("data (Dataset.construct)", "setup_s")}


def test_expo_is_a_cell_of_the_benchmark():
    cell = cells.cell(CELL)
    cfg = cell["config"]
    assert (cfg["rows"], cfg["valid_rows"], cfg["columns"]) == (10000000, 0,
                                                                700)
    assert (cfg["fields"], cfg["stored_per_row"]) == (8, 8)
    assert cfg["reduced"] == ["num_trees"]
    higgs = cells.load_json("configs", "higgs.json")["params"]
    assert cfg["params"] == {**higgs, "enable_bundle": True,
                             "max_conflict_rate": 0.0}
    sizes = cfg["draw"]["field_sizes"]
    assert sizes == [12, 31, 7, 24, 22, 297, 297, 10] and sum(sizes) == 700
    assert {"rows", "fields", "categories", "labels", "seed"} <= set(
        cfg["assumed"])
    assert cfg["published"]["auc"] == 0.776217
    assert cell["chips"] == 1 and cell["traffic"]["driver"] == "train_sparse"
    assert set(cell["traffic"]["limits"]) == {
        "bound_faults", "count_mismatch", "bundle_conflict_gap",
        "leaf_gap_median", "score_gap"}
    for exact in ("bound_faults", "count_mismatch", "bundle_conflict_gap"):
        assert cell["traffic"]["limits"][exact] == 0
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) | {"hist_roofline", "partition_roofline",
                               "tree_mfu", "compiles_in_window",
                               "device_idle_share"} <= reported
    # and the listed metrics of the layers this cell runs too, as
    # msltr.train-rank was appended to them
    rank = {m["name"] for m in cells.cell("msltr.train-rank")["per_layer"]}
    assert rank - reported == {"objective_roofline", "objective_pad_ratio"}
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


def test_the_draw_is_one_category_a_field():
    cfg = cells.cell(CELL)["config"]
    sizes = cfg["draw"]["field_sizes"]
    X, y = data_sparse.make_problem(30000, 700, 2 ** 31 + 5,
                                    cfg["draw_seed"], cfg["draw"])
    assert isinstance(X, scipy.sparse.csr_matrix) and X.dtype == np.float32
    assert X.shape == (30000, 700) and X.nnz == 8 * 30000
    assert X.has_canonical_format and (X.data == 1).all()
    # one column of every field in every row
    starts = np.concatenate([[0], np.cumsum(sizes)])
    field_of = np.searchsorted(starts, X.indices, side="right") - 1
    assert (field_of.reshape(30000, 8) == np.arange(8)).all()
    assert abs(y.mean() - cfg["draw"]["positive_rate"]) < 1e-3
    # heavy-tailed inside a field: the largest column of 297 holds about a
    # sixth of the rows (1 / H_297 = 0.159)
    origin = X.indices.reshape(30000, 8)[:, 5]
    assert 0.14 < np.bincount(origin).max() / 30000 < 0.18
    # one draw: the same seed gives the same rows in the same order, another
    # seed the same rows in another order
    again = data_sparse.make_problem(30000, 700, 2 ** 31 + 5,
                                     cfg["draw_seed"], cfg["draw"])
    assert (again[0] != X).nnz == 0 and np.array_equal(again[1], y)
    other, yo = data_sparse.make_problem(30000, 700, 7, cfg["draw_seed"],
                                         cfg["draw"])
    assert (other != X).nnz > 0

    def rows_sorted(m, labels):
        keyed = np.column_stack([m.indices.reshape(30000, 8), labels])
        return keyed[np.lexsort(keyed.T)]
    assert np.array_equal(rows_sorted(other, yo), rows_sorted(X, y))


def small_problem(rows=4000, seed=3):
    """Columns of a few bins, sparse, the last one negative (its zeros are
    in its LAST bin), to go into bundles whose columns meet."""
    rng = np.random.RandomState(seed)
    dense = np.where(rng.rand(rows, 6) < 0.3,
                     rng.randint(1, 4, (rows, 6)), 0).astype(np.float64)
    dense[:, 5] *= -1
    bounds = [np.array([0.5, 1.5, 2.5, np.inf])] * 5 \
        + [np.array([-2.5, -1.5, -0.5, np.inf])]
    y = (dense[:, 0] + dense[:, 3] - dense[:, 4]
         + rng.randn(rows) > 1).astype(np.float64)
    return dense, scipy.sparse.csr_matrix(dense), y, bounds


def overwrite(dense, bundles):
    """What the later column of a bundle leaves of the earlier ones."""
    out = dense.copy()
    for bundle in bundles:
        taken = np.zeros(len(dense), bool)
        for j in bundle[::-1]:
            out[taken, j] = 0
            taken |= dense[:, j] != 0
    return out


def test_sparse_reference_follows_as_the_dense_one_on_what_remains():
    dense, X, y, bounds = small_problem()
    bundles = [[0, 2, 1], [5, 3], [4]]
    params = {"min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1.0,
              "learning_rate": 0.1}
    tree = {"num_leaves": 4,
            "split_feature": np.array([0, 3, 5]),
            "threshold": np.array([0.5, 1.5, -0.5]),   # the last: 0 goes right
            "left_child": np.array([1, ~0, ~2]),
            "right_child": np.array([2, ~1, ~3]),
            "leaf_value": np.array([0.1, -0.2, 0.3, 0.05])}
    cols = reference_sparse.Columns(X, bounds, bundles)
    left = overwrite(dense, bundles)
    assert cols.overwritten.tolist() == [
        int(((dense[:, j] != 0) & (left[:, j] == 0)).sum()) for j in range(6)]
    assert cols.overwritten.sum() > 100
    # every overwritten entry with the column and the bin that stay in its
    # row, and the slot a bundle's column has to hold there
    met = cols.conflicts
    assert len(met["row"]) == cols.overwritten.sum()
    stays = np.array([[j for j in bundles[k] if left[r, j] != 0][-1]
                      for r, k in zip(met["row"], met["bundle"])])
    assert np.array_equal(met["column"], stays)
    offsets = [[1, 4, 7], [1, 4], [-1]]
    want = reference_sparse.conflict_slots(cols, bundles, offsets)
    value = left[met["row"], stays]
    first = np.array([1, 7, 4, 4, -1, 1])[stays]
    # column 5, the negative one, comes first in its bundle and never
    # stays; of the others value v is bin v, 0 is bin 0, and bin v is the
    # column's slot v - 1
    assert 5 not in stays and set(met["bundle"]) == {0, 1}
    assert np.array_equal(want, first + value.astype(int) - 1)
    assert reference_sparse.bundle_faults(bundles, bounds, 255, offsets) == 0
    assert reference_sparse.bundle_faults(          # two columns on slot 4
        bundles, bounds, 255, [[1, 4, 6], [1, 4], [-1]]) == 1
    assert np.array_equal(reference_sparse.route(cols, tree),
                          reference.route(left.T, tree))
    ours = reference_sparse.Follower(cols, y, params)
    theirs = reference.Follower(left.T, y, bounds, params)
    for _ in range(2):
        a, b = ours.step(tree), theirs.step(tree)
        assert np.array_equal(a["leaf_count"], b["leaf_count"])
        assert np.allclose(a["leaf_value"], b["leaf_value"], rtol=1e-12)
        assert np.array_equal(np.isfinite(a["gains"]),
                              np.isfinite(b["gains"]))
        fin = np.isfinite(a["gains"])
        assert np.allclose(a["gains"][fin], b["gains"][fin], rtol=1e-9)
    rows = np.arange(0, 4000, 7)
    assert np.allclose(
        reference_sparse.score_by_trees(cols.take(rows), [tree, tree]),
        reference.score_by_trees(left.T[:, rows], [tree, tree]))


@pytest.mark.parametrize("bundles,faults", [
    ([[0, 1], [2]], 0),
    ([[0, 1], [1, 2]], 1),                     # a column in two bundles
    ([[0, 1, 7]], 1),                          # a column the data lacks
    ([list(range(90))], 1),                    # 1 + 90 * 3 slots > 256
    ([list(range(85))], 0)])                   # 1 + 85 * 3 = 256 fit
def test_bundles_that_cannot_be_are_counted(bundles, faults):
    bounds = [np.array([0.5, 1.5, 2.5, np.inf])] * 90
    if max(map(max, bundles)) < 10:
        bounds = bounds[:3]
    assert reference_sparse.bundle_faults(bundles, bounds, 255) == faults


def test_work_is_counted_on_the_stored_entries():
    """The driver hands ``work.tree_work`` 8 one-byte entries a row and a
    table of the 700 columns' 1,400 real bins, not 700 columns of 256."""
    shape = {"rows": 100, "columns": 8, "bins": 1400 // 8, "bin_bytes": 1}
    w = work.tree_work(shape, [~0], [~1], [100], [60, 40])
    assert w["histogram"]["rows"] == 140
    table = 1400 * work.HIST_ENTRY_BYTES
    assert w["histogram"]["bytes"] == 140 * (8 + 8) + 2 * table + 2 * table
    assert w["histogram"]["ops"] == 140 * 8 * 2 + 1400 * 3
    wide = work.tree_work(dict(shape, columns=700, bins=256), [~0], [~1],
                          [100], [60, 40])
    assert wide["histogram"]["bytes"] > 100 * w["histogram"]["bytes"]


@pytest.mark.parametrize("name,counter,tags,want", [
    ("bundle_columns", "efb_layout",
     {"logical=700,max_slots=256,physical=10": 1}, 10),
    ("bundle_columns", "efb_layout", None, None),         # the parent
    ("setup_bundle_s", "phase_seconds",
     {"phase=dataset.find_bundles": 0.75, "phase=dataset.construct": 31.0},
     0.75),
    ("setup_bin_sparse_s", "phase_seconds",
     {"phase=dataset.bin_sparse": 17.5}, 17.5),
    ("setup_bin_sparse_s", "phase_seconds",
     {"phase=dataset.construct": 5.0}, None)])            # a dense data set
def test_new_metrics_read_the_program_counters(monkeypatch, name, counter,
                                               tags, want):
    monkeypatch.setattr(_program_counters, "counter",
                        lambda n: tags if n == counter else None)
    assert metrics.read_metric(name, {}) == want


def test_bundle_expand_ms_reads_its_scope():
    ctx = {"iterations": 3, "trace": {"scope_ms": {"bundle_expand": 1500.0}}}
    assert metrics.read_metric("bundle_expand_ms_per_tree", ctx) == 500.0
    assert metrics.read_metric("bundle_expand_ms_per_tree",
                               dict(ctx, trace=None)) is None
    assert "bundle_expand" in metrics.scopes_wanted(
        ["split_find_ms_per_tree", "bundle_expand_ms_per_tree"])


def scoped_eqns(jaxpr, outer=""):
    """(scope stack, equation) of every equation of a jaxpr and of the
    jaxprs inside it (a loop's body, a switch's branches), each under its
    equation's."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        yield stack, eqn
        for val in eqn.params.values():
            for v in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    yield from scoped_eqns(sub, stack)


def scope_stacks(jaxpr):
    return (stack for stack, _ in scoped_eqns(jaxpr))


def _bundled_grow(bundled=True):
    """The grow program's jaxpr over 3 physical columns of 16 slots holding
    12 one-hot logical ones (or, unbundled, the 3 columns themselves), and
    the counter key of its expansion."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower
    n, e, fp, b = 4096, 12, 3, 16
    cfg = GrowerConfig(num_leaves=7, min_data_in_leaf=1, max_bin=b,
                       hist_method="segment", has_missing=False)
    if not bundled:
        e = fp
    meta = FeatureMeta(
        num_bin=jnp.full((e,), 2 if bundled else 5, jnp.int32),
        missing_type=jnp.zeros((e,), jnp.int32),
        default_bin=jnp.zeros((e,), jnp.int32),
        is_categorical=jnp.zeros((e,), bool),
        col=(jnp.repeat(jnp.arange(fp, dtype=jnp.int32), e // fp)
             if bundled else None),
        offset=(jnp.tile(jnp.arange(1, 1 + e // fp, dtype=jnp.int32), fp)
                if bundled else None))
    rng = np.random.RandomState(0)
    args = (jnp.asarray(rng.randint(0, 5, (n, fp)).astype(np.uint8)),
            jnp.asarray(rng.randn(n).astype(np.float32)),
            jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32),
            meta, jnp.ones((e,), bool))
    key = f"impl=slots,logical={e},physical={fp},slots={fp * b}"
    return jax.make_jaxpr(make_grower(cfg))(*args), key, fp * b


def test_bundle_scopes_in_the_grow_program():
    """``bundle_expand`` opens BESIDE ``split_find``, never inside it (a
    trace charges an operation to the leftmost scope of its name, so nested
    it would read nothing), at the root and on the children;
    ``bundle_decode`` sits inside ``partition`` and stays charged to it."""
    from lightgbm_tpu.obs.counters import counters
    before = dict(counters.get("bundle_expand_dispatch"))
    jaxpr, key, _ = _bundled_grow()
    assert counters.get("bundle_expand_dispatch")[key] \
        == before.get(key, 0) + 2                 # the root, the children
    stacks = set(scope_stacks(jaxpr.jaxpr))
    expand = [s for s in stacks if "bundle_expand" in s]
    decode = [s for s in stacks if "bundle_decode" in s]
    assert expand and decode
    assert {s.startswith("//bundle_expand") for s in expand} == {
        True, False}, "the root's expansion, and the children's in the loop"
    for s in expand:
        assert "split_find" not in s.split("bundle_expand")[0], s
    for s in decode:
        assert "partition" in s.split("bundle_decode")[0], s
    assert any("split_find" in s and "bundle_" not in s for s in stacks)


def test_bundle_expand_moves_the_slots():
    """The expansion moves the F_physical * B measured slots (the counter
    names the form and the count: ``impl=slots``, ``slots``): no gather under
    ``bundle_expand`` reads more indices than there are slots, where the
    gather it replaced read E_logical * B.  A grow program on columns that
    are not bundled has no ``bundle_expand`` at all."""
    jaxpr, key, slots = _bundled_grow()
    assert "impl=slots" in key and f"slots={slots}" in key
    eqns = [(s, q) for s, q in scoped_eqns(jaxpr.jaxpr)
            if "bundle_expand" in s]
    assert any(q.primitive.name == "scatter" for _, q in eqns)
    for s, q in eqns:
        if q.primitive.name == "gather":
            n_idx = int(np.prod(q.invars[1].aval.shape[:-1]))
            assert n_idx <= slots, (s, q.invars[1].aval)
    plain, _, _ = _bundled_grow(bundled=False)
    assert not any("bundle_" in s for s in scope_stacks(plain.jaxpr))
