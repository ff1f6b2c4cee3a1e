"""The grower's ``named_scope``s are names and nothing else.

A device trace reads the grow program by the scope tokens in its
operations' names: the benchmark's first pass (``harness/trace.py``) by the
top-level ones, its second (``harness/sub_scopes.py``) by the nested ones.
Pinned here, on the CPU:

* every token stands in the lowered program's operation names, under its
  parent, on the fused rung (interpret mode) and on the XLA rung;
* the scopes change NOTHING but names: with ``jax.named_scope`` patched to
  a null context the lowered module, debug information stripped, is
  text-equal;
* the jitted programs carry ``SCOPE_REVISION`` in their name (jax leaves
  names out of the persistent compile cache's key: a program that differs
  from a cached one in its scopes alone would load the cached one's names),
  and ``grow_tree`` stays in it, which the benchmark finds the program by.

The data set is small enough that the partition builds window branches AND
the dense branch (3,000 rows: windows of 64, 128, 256 slots, n / 8 = 375).
"""
import contextlib
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from lightgbm_tpu.grower import (SCOPE_REVISION, FeatureMeta, GrowerConfig,
                                 _partition_sizes, make_grower)

N, F, B, L = 3000, 5, 16, 8
E, FP = 12, 3           # the bundled data set: logical and physical columns
PROGRAM = rf"jit\(grow_tree_s{SCOPE_REVISION}\)"
BODY = PROGRAM + r"/while/body"
BRANCH = BODY + r"/partition/cond/branch_\d+_fun"

# token -> (the first-pass scope it stands in, or None at the top level;
#           a pattern for the name of one operation that must carry it)
UNDER = {
    "part_route": ("partition", BODY + r"/partition/part_route/"),
    "part_read": ("partition", BRANCH + r"/part_read/"),
    "part_sort": ("partition", BRANCH + r"/part_sort/sort"),
    "part_dense": ("partition", BRANCH + r"/part_dense/sort"),
    "hist_root": ("histogram", PROGRAM + r"/histogram/hist_root/"),
    "fused_panel": (None, PROGRAM + r"/fused_panel/"),
    "node_tables": (None, BODY + r"/node_tables/"),
}
# the scopes the benchmark's FIRST pass reads: a token opened inside one of
# them would be charged to it there, and one meant to stand beside them
# would leave ``grower_other_ms_per_tree``
FIRST_PASS = ("partition", "histogram", "hist_pool", "split_find",
              "bundle_expand", "row_leaf")
RUNGS = {"fused": dict(hist_method="fused", hist_interpret=True),
         "xla": dict(hist_method="segment")}


def _cfg(rung):
    return GrowerConfig(num_leaves=L, min_data_in_leaf=1, max_bin=B,
                        **RUNGS[rung])


def _args():
    meta = FeatureMeta(num_bin=jnp.full((F,), B, jnp.int32),
                       missing_type=jnp.zeros((F,), jnp.int32),
                       default_bin=jnp.zeros((F,), jnp.int32),
                       is_categorical=jnp.zeros((F,), bool))
    rng = np.random.RandomState(0)
    one = jnp.ones((N,), jnp.float32)
    return (jnp.asarray(rng.randint(0, B, (N, F)).astype(np.uint8)),
            jnp.asarray(rng.randn(N).astype(np.float32)), one, one, meta,
            jnp.ones((F,), bool))


def _lowered(rung):
    return jax.jit(make_grower(_cfg(rung))).lower(*_args())


def _op_names(lowered):
    """The name stacks of the lowered operations (the text also locates
    source files, and outlined functions by the tail of a stack)."""
    return set(re.findall(r'loc\("(jit\([^"]+)"',
                          lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def op_names():
    cfg = _cfg("xla")
    sizes = _partition_sizes(cfg, N)
    assert len(sizes) > 1 and sizes[-1] < N, "windows AND the dense branch"
    return {rung: _op_names(_lowered(rung)) for rung in RUNGS}


@pytest.mark.parametrize("rung", list(RUNGS))
@pytest.mark.parametrize("token", list(UNDER))
def test_token_stands_under_its_parent(op_names, token, rung):
    names = [n for n in op_names[rung] if re.search(rf"/{token}(/|$)", n)]
    assert names, f"no operation of the {rung} rung is named {token}"
    parent, pattern = UNDER[token]
    assert any(re.match(pattern, n) for n in names), names[:5]
    for n in names:
        before = n.split(f"/{token}")[0].split("/")
        outer = [s for s in FIRST_PASS if s in before]
        assert outer == ([parent] if parent else []), n


def test_route_holds_the_bundle_decode():
    """On a bundled data set the routing's decode stays where PR 34 put
    it, now one level further in: ``partition/part_route/bundle_decode``."""
    meta = FeatureMeta(
        num_bin=jnp.full((E,), 2, jnp.int32),
        missing_type=jnp.zeros((E,), jnp.int32),
        default_bin=jnp.zeros((E,), jnp.int32),
        is_categorical=jnp.zeros((E,), bool),
        col=jnp.repeat(jnp.arange(FP, dtype=jnp.int32), E // FP),
        offset=jnp.tile(jnp.arange(1, 1 + E // FP, dtype=jnp.int32), FP))
    one = jnp.ones((N,), jnp.float32)
    cfg = GrowerConfig(num_leaves=L, min_data_in_leaf=1, max_bin=B,
                       hist_method="segment", has_missing=False)
    names = _op_names(jax.jit(make_grower(cfg)).lower(
        jnp.zeros((N, FP), jnp.uint8), one, one, one, meta,
        jnp.ones((E,), bool)))
    decode = [n for n in names if "bundle_decode" in n]
    assert decode
    assert all(re.match(BODY + r"/partition/part_route/bundle_decode/", n)
               for n in decode), decode[:5]


@pytest.mark.parametrize("rung", list(RUNGS))
def test_scopes_are_names_only(monkeypatch, rung):
    """The program with every scope taken out is the same program."""
    scoped = _lowered(rung).as_text(debug_info=False)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lowered(rung)
    assert not any(t in n for n in _op_names(bare) for t in UNDER)
    assert bare.as_text(debug_info=False) == scoped


@pytest.mark.parametrize("kind, kwargs", [
    ("grow_tree", {}),
    ("grow_tree_limited", {"step_limit": True}),
    ("grow_tree_packed", {"pack_plan": "a plan"}),
])
def test_program_name_holds_the_revision(kind, kwargs):
    """``pack_plan`` is read only when the program is traced, so any
    object that is not None names the packed entry."""
    fn = make_grower(_cfg("xla"), **kwargs)
    assert fn.__name__ == f"{kind}_s{SCOPE_REVISION}"
    assert "grow_tree" in fn.__name__
    assert SCOPE_REVISION >= 2, "1 was the programs before the revision"


def test_named_program_is_what_jax_compiles(op_names):
    """The name reaches the module (``jit_grow_tree_s3``: in the cache's
    key and in the trace's ``XLA Modules`` line) and every operation."""
    assert f"module @jit_grow_tree_s{SCOPE_REVISION} " in \
        _lowered("xla").as_text(debug_info=False)
    assert all(re.match(PROGRAM, n) for n in op_names["xla"])


def test_gspmd_grower_names_program_and_panel_alike(op_names):
    from lightgbm_tpu.parallel.gspmd import make_gspmd_grower
    from lightgbm_tpu.parallel.mesh import make_named_mesh
    n = 4096                # whole row tiles on each of the 8 devices
    cfg = GrowerConfig(num_leaves=L, min_data_in_leaf=1, max_bin=B,
                       hist_method="fused", hist_interpret=True,
                       has_missing=False)
    grow = make_gspmd_grower(cfg, make_named_mesh(8, 1))
    assert grow.__name__ == f"grow_tree_s{SCOPE_REVISION}"
    bins, g, h, c, meta, ok = _args()
    pad = lambda a: jnp.resize(a, (n,) + a.shape[1:])       # noqa: E731
    # the 8 x 1 mesh runs the serial grower inside ONE shard_map island,
    # whose lowering outlines its body: the compiled operations carry the
    # whole name stack
    names = set(re.findall(r'op_name="([^"]+)"', grow.lower(
        pad(bins), pad(g), pad(h), pad(c), meta, ok).compile().as_text()))
    panel = re.compile(PROGRAM + r"(/shard_map)?/fused_panel/")
    assert any(panel.match(x) for x in names)
    assert any(panel.match(x) for x in op_names["fused"])
