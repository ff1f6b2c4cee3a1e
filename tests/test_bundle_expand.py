"""The bundle expansion (``grower.expand_bundle_hist``) pinned bit for bit to
the form it replaced: ONE gather of E_logical * B indices over the measured
``[F_physical, B, 3]`` histogram, kept here as the oracle (it is no longer
package code).  The slot map moves the F_physical * B measured slots into a
zero-filled logical table and sets each bundled feature's default bin from the
same ``cumsum`` arithmetic, so every layout must give the oracle's array
exactly: one-hot bundles, default bins above 0 (the slot shift), unbundled
columns beside bundles, slots past a column's bins, the feature-parallel
column windows at their edges, and the grower's two children.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.data.bundling import BundleLayout, find_bundles
from lightgbm_tpu.grower import (FeatureMeta, StreamedGrower,
                                 expand_bundle_hist, make_expand_maps)
from test_bundling import _one_hot_problem


def gather_maps(meta, num_bins, col_start=None, col_count=None):
    """The parent's ``make_expand_maps``: a source slot per logical (e, b)."""
    b = jnp.arange(num_bins, dtype=jnp.int32)[None, :]
    off = meta.offset[:, None]
    nb = meta.num_bin[:, None]
    db = meta.default_bin[:, None]
    c = meta.col[:, None]
    if col_start is not None:
        in_win = (c >= col_start) & (c < col_start + col_count)
        c = c - col_start
        flat_max = col_count * num_bins - 1
    slot = off + b - (b > db).astype(jnp.int32)
    src = jnp.where(off < 0, c * num_bins + b,
                    c * num_bins + jnp.clip(slot, 0, num_bins - 1))
    valid = b < nb
    recon = (off >= 0) & (b == db) & valid
    lo = jnp.maximum((c * num_bins + off)[:, 0], 1)
    hi = jnp.maximum((c * num_bins + off + nb - 2)[:, 0], 1)
    if col_start is not None:
        valid = valid & in_win
        recon = recon & in_win
        src = jnp.clip(src, 0, flat_max)
        lo = jnp.clip(lo, 1, flat_max)
        hi = jnp.clip(hi, 1, flat_max)
    return src, valid, recon, lo, hi


def gather_expand(hist, pg, ph, pc, maps):
    """The parent's ``expand_bundle_hist``: ``flat[src]`` over E * B."""
    src, valid, recon, lo, hi = maps
    flat = hist.reshape(-1, hist.shape[-1])
    out = jnp.where(valid[:, :, None], flat[src], 0.0)
    cs = jnp.cumsum(flat, axis=0)
    range_sum = cs[hi] - cs[lo - 1]
    parent = jnp.stack([jnp.asarray(pg, flat.dtype),
                        jnp.asarray(ph, flat.dtype),
                        jnp.asarray(pc, flat.dtype)])
    recon_val = parent[None, :] - range_sum
    return jnp.where(recon[:, :, None], recon_val[:, None, :], out)


class _Mapper:
    def __init__(self, num_bin, default_bin):
        self.num_bin = num_bin
        self.default_bin = default_bin


def _meta(bundles, num_bin, default_bin, align=16):
    """FeatureMeta of a ``BundleLayout`` over stub mappers, the kernel's
    width B (the widest column rounded up to ``align``: slots past the
    columns' bins are measured too) and the physical column count."""
    mappers = [_Mapper(n, d) for n, d in zip(num_bin, default_bin)]
    lay = BundleLayout(bundles, mappers, list(range(len(mappers))))
    sub = lay.sub_features
    meta = FeatureMeta(
        num_bin=jnp.asarray([num_bin[j] for j in sub], jnp.int32),
        missing_type=jnp.zeros((len(sub),), jnp.int32),
        default_bin=jnp.asarray([default_bin[j] for j in sub], jnp.int32),
        is_categorical=jnp.zeros((len(sub),), bool),
        col=jnp.asarray(lay.sub_col, jnp.int32),
        offset=jnp.asarray(lay.sub_offset, jnp.int32))
    return meta, -(-lay.max_col_bins() // align) * align, lay.num_columns


def _one_hot():
    """expo's form: one-hot columns of 2 bins, default bin 0, bundled by
    ``find_bundles``; the two dense columns stay single (16 bins, default
    bin 3)."""
    X, _ = _one_hot_problem(n=2000, groups=4, cats=9, dense=2)
    f = X.shape[1]
    nb = [2] * (f - 2) + [16, 16]
    db = [0] * (f - 2) + [3, 3]
    bundles = find_bundles(X != 0, nb, max_conflict_rate=0.0)
    assert any(len(b) > 1 for b in bundles)
    assert any(len(b) == 1 for b in bundles)
    return _meta(bundles, nb, db)


def _shifted():
    """Bundled features whose default bin is above 0: the slots below it
    keep their bin, the slots above it are one lower (``b > db``)."""
    rng = np.random.RandomState(3)
    nb = list(rng.randint(3, 10, size=12))
    db = [int(rng.randint(1, n)) for n in nb]
    return _meta([[0, 1, 2], [3], [4, 5, 6, 7], [8, 9], [10], [11]], nb, db)


def _mixed():
    """Unbundled columns between bundles, default bins 0 and above, one
    feature of a single bin (it owns no slot), and an unbundled column as
    wide as the kernel (B = 30, no slot to spare)."""
    nb = [5, 1, 7, 4, 30, 3, 16, 2, 2]
    db = [0, 0, 6, 2, 12, 1, 0, 0, 1]
    return _meta([[4], [0, 1, 2], [6], [3, 5, 7, 8]], nb, db, align=1)


def _dataset():
    """The maps of a data set the package itself bundled (``Dataset`` with
    ``enable_bundle``): real mappers, real default bins."""
    import lightgbm_tpu as lgb
    X, y = _one_hot_problem(n=3000, groups=3, cats=6, dense=2, seed=4)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 15, "verbose": -1,
                                         "enable_bundle": True,
                                         "max_conflict_rate": 0.0})
    built = ds.construct().constructed
    fm = built.feature_meta()
    assert "col" in fm
    meta = FeatureMeta(**{k: jnp.asarray(v) for k, v in fm.items()})
    return meta, int(built.max_num_bin()), int(built.binned.shape[1])


LAYOUTS = {"one_hot": _one_hot, "shifted": _shifted, "mixed": _mixed,
           "dataset": _dataset}


def _hist(rng, *shape):
    """Every slot measured (past a column's bins too), with values whose
    sums round: the default bins' arithmetic must be the oracle's own."""
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 7.3)


def _parent(rng, k=None):
    shape = () if k is None else (k,)
    return [jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 50)
            for _ in range(3)]


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_expand_is_the_gather_bit_for_bit(layout):
    meta, b, fp = LAYOUTS[layout]()
    rng = np.random.RandomState(0)
    hist = _hist(rng, fp, b, 3)
    pg, ph, pc = _parent(rng)
    want = gather_expand(hist, pg, ph, pc, gather_maps(meta, b))
    got = expand_bundle_hist(hist, pg, ph, pc, make_expand_maps(meta, b, fp))
    assert got.shape == want.shape == (meta.num_bin.shape[0], b, 3)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # the map built from a TRACED meta, as the jitted growers build it
    traced = jax.jit(lambda m, h: expand_bundle_hist(
        h, pg, ph, pc, make_expand_maps(m, b, fp)))(meta, hist)
    assert np.array_equal(np.asarray(traced), np.asarray(want))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_slot_map_is_one_to_one(layout):
    """Each physical slot feeds at most one logical (e, b), no two slots the
    same one, and the slots that feed nobody point past the table, each to
    a place of its own: the move's ``unique_indices`` is true."""
    meta, b, fp = LAYOUTS[layout]()
    dest, recon_dest, _, _, win = make_expand_maps(meta, b, fp)
    table = meta.num_bin.shape[0] * b
    idx = np.concatenate([np.asarray(dest), np.asarray(recon_dest)])
    assert len(np.unique(idx)) == len(idx)
    fed = idx[idx < table]
    assert len(fed) == int(np.asarray(gather_maps(meta, b)[1]).sum())
    assert win is None


def _windows():
    meta, b, fp = _mixed()
    return [(meta, b, fp, start, count)
            for start, count in ((0, 1), (0, 2), (1, 2), (fp - 2, 2),
                                 (fp - 1, 1), (0, fp))]


@pytest.mark.parametrize("case", range(6))
def test_windowed_expand_is_the_gather(case):
    """The feature-parallel shard's maps (``col_start`` / a window of
    physical columns), at both edges: features outside the window read 0
    and are masked."""
    meta, b, fp, start, count = _windows()[case]
    rng = np.random.RandomState(case)
    hist = _hist(rng, count, b, 3)
    pg, ph, pc = _parent(rng)
    want = gather_expand(hist, pg, ph, pc,
                         gather_maps(meta, b, start, count))
    maps = jax.jit(lambda m, s: make_expand_maps(m, b, count, col_start=s))(
        meta, jnp.asarray(start, jnp.int32))
    got = expand_bundle_hist(hist, pg, ph, pc, maps)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    c = np.asarray(meta.col)
    assert np.array_equal(np.asarray(maps[4]),
                          (c >= start) & (c < start + count))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_children_expand_is_the_gather(layout):
    """A split's two children: as ONE batch (what the growers hand over) and
    under ``jax.vmap`` (what a caller may do), both the oracle vmapped."""
    meta, b, fp = LAYOUTS[layout]()
    rng = np.random.RandomState(1)
    hist2 = _hist(rng, 2, fp, b, 3)
    pg, ph, pc = _parent(rng, 2)
    want = jax.vmap(gather_expand, in_axes=(0, 0, 0, 0, None))(
        hist2, pg, ph, pc, gather_maps(meta, b))
    maps = make_expand_maps(meta, b, fp)
    batched = expand_bundle_hist(hist2, pg, ph, pc, maps)
    vmapped = jax.vmap(expand_bundle_hist, in_axes=(0, 0, 0, 0, None))(
        hist2, pg, ph, pc, maps)
    assert np.array_equal(np.asarray(batched), np.asarray(want))
    assert np.array_equal(np.asarray(vmapped), np.asarray(want))


def test_streamed_bundled_train_matches_resident():
    """The streamed grower expands its children as one batch too: on a
    bundled data set its trees are the resident grower's."""
    import lightgbm_tpu as lgb
    X, y = _one_hot_problem(n=3000, groups=3, cats=6, dense=2, seed=5)
    base = {"objective": "binary", "verbose": -1, "num_leaves": 15,
            "min_data_in_leaf": 5, "enable_bundle": True,
            "max_conflict_rate": 0.0}
    res = lgb.train(dict(base), lgb.Dataset(X, label=y), num_boost_round=4,
                    verbose_eval=False)
    streamed = lgb.train(dict(base, data_stream="chunked",
                              stream_chunk_rows=1000),
                         lgb.Dataset(X, label=y), num_boost_round=4,
                         verbose_eval=False)
    assert isinstance(streamed.inner.grow, StreamedGrower)
    assert streamed.inner.meta.col is not None
    np.testing.assert_allclose(streamed.predict(X), res.predict(X),
                               atol=1e-5)
