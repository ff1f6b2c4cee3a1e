"""High-QPS inference artifact: ensemble SoA node arrays + bucketed
microbatch executables.

``Predictor.predict`` (the training-side oracle) walks a Python list of
:class:`~lightgbm_tpu.tree.Tree` objects per call — per-tree host
traversal, no caching, no latency story.  This module is the dedicated
serving path the ROADMAP names ("Booster: An Accelerator for Gradient
Boosting Decision Trees" is the layout reference):

* :class:`SoABundle` — the whole ensemble flattened ONCE into contiguous
  ``[T, P]`` structure-of-arrays node tables (feature, threshold rank,
  left/right child, default direction, missing type, categorical mask
  reference), with both axes pow2-bucketed exactly like
  ``trees_scores_binned`` so the jit signature set stays bounded.  Leaf
  values stay host-side ``float64`` shaped ``[iterations, K, P+1]``
  (multiclass is a leaf-value channel axis) so the margin accumulation
  reproduces ``Predictor.predict_raw`` bit for bit.
* **On-device raw-feature binning**: per-column *threshold tables* are
  derived from the ensemble (the sorted unique split thresholds of each
  used column — a model-defined :class:`~lightgbm_tpu.data.binning.BinMapper`)
  and uploaded once; a microbatch executable bins a raw ``[B, F]`` batch
  with one vmapped ``searchsorted`` and traverses every tree in the same
  kernel.  Node thresholds become integer *ranks* into the same tables, so
  the routing comparison is exact integer ``bin <= rank``.
* **Bit-exactness discipline**: the f32 threshold tables are rounded
  toward ``-inf`` from the f64 model thresholds, which makes
  ``v <= t_f64`` and ``v <= floor32(t)`` equivalent for every
  f32-representable ``v`` — serving traffic (f32 feature payloads) routes
  identically to the f64 host oracle.  Inputs that genuinely need f64
  (``float64`` values that do not round-trip through f32) are binned on
  host against the f64 tables instead and traversed by the binned-input
  twin executable: same integer routing, still bit-identical.
* **Microbatch executables**: module-level jitted kernels take every
  model array as an *argument* (nothing is baked in as a constant), so a
  hot-swapped model with the same bucket shape reuses the compiled
  executable — zero recompiles across a swap.  Batch shapes are padded up
  a pow2-ish ladder (default 1/8/64/512/4096; ``serving_buckets`` param).
  Nothing is donated: no output has an input's shape and dtype, so a
  donation could never be used (the v5e said so on every compile).
  :func:`jit_entries` exposes the compiled-signature count as the
  ``predict_jit_entries`` gauge (the ``grower_jit_entries`` discipline).

Every dispatch lands a ``predict_dispatch`` counter (batch bucket +
executable identity) and the bin/traverse/margin phases run under obs
spans via :class:`~lightgbm_tpu.utils.timer.PhaseTimers`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .config import parse_serving_buckets
from .obs import memory as obs_memory
from .obs.counters import counters as obs_counters
from .tree import Tree
from .utils import log
from .utils.timer import PhaseTimers

MISSING_NONE, MISSING_ZERO, MISSING_NAN = 0, 1, 2

# default microbatch ladder (rows); the `serving_buckets` param overrides
DEFAULT_BUCKETS: Tuple[int, ...] = (1, 8, 64, 512, 4096)

# kZeroAsMissingValueRange (tree.py ZERO_RANGE), floored to f32 so the
# on-device |v| <= range check matches the host f64 one for f32 inputs
ZERO_RANGE = 1e-20


def _floor_to_f32(a: np.ndarray) -> np.ndarray:
    """Round f64 values toward -inf onto the f32 grid.  For any
    f32-representable ``v``: ``v <= a``  ⟺  ``v <= _floor_to_f32(a)`` —
    the identity the on-device binning's exactness rests on."""
    f = np.asarray(a, np.float64).astype(np.float32)
    over = f.astype(np.float64) > np.asarray(a, np.float64)
    if over.any():
        f[over] = np.nextafter(f[over], np.float32(-np.inf))
    return f


_ZERO_RANGE_F32 = float(_floor_to_f32(np.array([ZERO_RANGE]))[0])


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# --------------------------------------------------------------- SoA bundle


@dataclasses.dataclass
class SoABundle:
    """The ensemble flattened once: contiguous ``[Tp, P]`` node arrays on
    device, leaf values + f64 threshold tables on host."""

    num_trees: int                     # real tree count (rest is padding)
    num_class: int
    tp: int                            # pow2 tree bucket
    p: int                             # pow2 node bucket (max num_leaves-1)
    cols: np.ndarray                   # compact column -> original feature
    thr64: List[np.ndarray]            # per compact column: sorted unique f64
    leaf_value: np.ndarray             # [Tp, P+1] f64 (host margin tables)
    # device arrays
    thr_table: jnp.ndarray             # [Fc, B] f32, +inf padded, floor32
    feat: jnp.ndarray                  # [Tp, P] i32 compact column index
    thr: jnp.ndarray                   # [Tp, P] i32 threshold rank
    default_left: jnp.ndarray          # [Tp, P] bool
    miss: jnp.ndarray                  # [Tp, P] i32 missing type
    left: jnp.ndarray                  # [Tp, P] i32 (leaves encoded ~leaf)
    right: jnp.ndarray                 # [Tp, P] i32
    is_cat: jnp.ndarray                # [Tp, P] bool
    cat_ref: jnp.ndarray               # [Tp, P] i32 row of cat_mask
    cat_mask: jnp.ndarray              # [C, W] bool over raw category values
    # packed-node-word traversal (serving_traversal=packed): each node's
    # routing fields folded into TWO i32 words so a traversal step costs
    # one fused node gather + one data gather instead of eight scalar-
    # lowered gathers (the measured ~1.6x XLA:CPU headroom of PR 8).
    # None when the ensemble is not packable (categorical nodes, or field
    # widths past the word budget) — the classic traversal always exists.
    node_w0: Optional[jnp.ndarray] = None  # [Tp, P] i32: feat | thr<<12
    #                                        | default_left<<28 | miss<<29
    node_w1: Optional[jnp.ndarray] = None  # [Tp, P] i32: left | right<<16
    #                                        (int16 two's complement halves)
    max_depth: int = 0                 # fori ladder length (packed path)

    @property
    def num_cols(self) -> int:
        return len(self.cols)

    @property
    def num_bins(self) -> int:
        return int(self.thr_table.shape[1])

    def exec_id(self) -> str:
        """Executable identity tag: everything but the batch bucket that
        keys the compiled signature."""
        return (f"t{self.tp}p{self.p}f{self.num_cols}b{self.num_bins}"
                f"c{self.cat_mask.shape[0]}w{self.cat_mask.shape[1]}")

    @staticmethod
    def build(trees: Sequence[Tree], num_class: int = 1) -> "SoABundle":
        num_trees = len(trees)
        tp = _pow2_at_least(max(num_trees, 1))
        p = _pow2_at_least(max(max((t.num_leaves - 1 for t in trees),
                                   default=1), 1))
        # pass 1: used columns + per-column threshold tables + cat widths
        used: Dict[int, List[float]] = {}
        cat_bits = 1
        cat_rows = 0
        for t in trees:
            for i in range(max(t.num_leaves - 1, 0)):
                f = int(t.split_feature[i])
                vals = used.setdefault(f, [])
                if t.is_categorical(i):
                    cat_rows += 1
                    cat_bits = max(cat_bits, 32 * len(t.cat_bitset(i)))
                else:
                    vals.append(float(t.threshold[i]))
        cols = np.asarray(sorted(used), dtype=np.int32)
        col_of = {int(f): i for i, f in enumerate(cols)}
        thr64 = [np.unique(np.asarray(used[int(f)], np.float64))
                 for f in cols]
        nb = max((len(u) for u in thr64), default=0) or 1
        fc = max(len(cols), 1)
        table = np.full((fc, nb), np.inf, np.float32)
        for i, u in enumerate(thr64):
            table[i, :len(u)] = _floor_to_f32(u)
        # pass 2: node arrays (padding trees are 0-leaf stumps: children -1
        # terminate traversal at leaf 0, whose padded leaf value is 0)
        feat = np.zeros((tp, p), np.int32)
        thr = np.zeros((tp, p), np.int32)
        dl = np.zeros((tp, p), bool)
        miss = np.zeros((tp, p), np.int32)
        lc = np.full((tp, p), -1, np.int32)
        rc = np.full((tp, p), -1, np.int32)
        ic = np.zeros((tp, p), bool)
        cref = np.zeros((tp, p), np.int32)
        cmask = np.zeros((max(cat_rows, 1), cat_bits), bool)
        lv = np.zeros((tp, p + 1), np.float64)
        ci = 0
        for ti, t in enumerate(trees):
            nl = t.num_leaves
            if nl >= 1 and len(t.leaf_value):
                lv[ti, :nl] = t.leaf_value[:nl]
            nn = nl - 1
            if nn <= 0:
                continue
            fcomp = np.asarray([col_of[int(f)] for f in t.split_feature[:nn]],
                               np.int32)
            feat[ti, :nn] = fcomp
            dl[ti, :nn] = (t.decision_type[:nn]
                           & 2) > 0                      # K_DEFAULT_LEFT_MASK
            miss[ti, :nn] = (t.decision_type[:nn].astype(np.int32) >> 2) & 3
            lc[ti, :nn] = t.left_child[:nn]
            rc[ti, :nn] = t.right_child[:nn]
            for i in range(nn):
                if t.is_categorical(i):
                    ic[ti, i] = True
                    cmask[ci] = t.cat_value_mask(i, cat_bits)
                    cref[ti, i] = ci
                    ci += 1
                else:
                    u = thr64[fcomp[i]]
                    thr[ti, i] = int(np.searchsorted(
                        u, float(t.threshold[i])))
        # packed-node-word twin: build whenever the ensemble fits the word
        # budget (numerical-only, <=4096 used columns, <=65535 threshold
        # ranks, <=32767 nodes/leaves).  Routing fields are folded into two
        # i32 words; children are int16 two's complement halves of w1, so
        # ``(w1 << 16) >> 16`` / ``w1 >> 16`` sign-extend them back exactly.
        w0 = w1 = None
        depth = 0
        packable = (not ic.any() and fc <= 4096 and int(thr.max(initial=0))
                    <= 0xffff and p <= 32767 and nb < (1 << 24))
        if packable:
            w0 = (feat.astype(np.int64) | (thr.astype(np.int64) << 12)
                  | (dl.astype(np.int64) << 28)
                  | (miss.astype(np.int64) << 29)).astype(np.int32)
            w1 = ((lc.astype(np.int64) & 0xffff)
                  | ((rc.astype(np.int64) & 0xffff) << 16)).astype(np.int32)
            depth = max((t.max_depth() for t in trees if t.num_leaves > 1),
                        default=0)
        return SoABundle(
            num_trees=num_trees, num_class=max(num_class, 1), tp=tp, p=p,
            cols=cols, thr64=thr64, leaf_value=lv,
            thr_table=jnp.asarray(table), feat=jnp.asarray(feat),
            thr=jnp.asarray(thr), default_left=jnp.asarray(dl),
            miss=jnp.asarray(miss), left=jnp.asarray(lc),
            right=jnp.asarray(rc), is_cat=jnp.asarray(ic),
            cat_ref=jnp.asarray(cref), cat_mask=jnp.asarray(cmask),
            node_w0=jnp.asarray(w0) if w0 is not None else None,
            node_w1=jnp.asarray(w1) if w1 is not None else None,
            max_depth=int(depth))

    def device_args(self) -> tuple:
        return (self.feat, self.thr, self.default_left, self.miss,
                self.left, self.right, self.is_cat, self.cat_ref,
                self.cat_mask)

    def host_nodes(self) -> Dict[str, np.ndarray]:
        """Host copies of the routing arrays (fetched once, cached) —
        the contribution path replays per-node decisions as cheap host
        integer compares over device-binned rows."""
        cached = getattr(self, "_host_nodes", None)
        if cached is None:
            cached = {name: np.asarray(arr) for name, arr in zip(
                ("feat", "thr", "dl", "miss", "lc", "rc", "ic", "cref",
                 "cmask"), self.device_args())}
            self._host_nodes = cached
        return cached

    def go_matrix(self, t: int, num_nodes: int, bins: np.ndarray,
                  cats: np.ndarray, nanm: np.ndarray,
                  zerom: np.ndarray) -> np.ndarray:
        """go-left per (internal node, row) of tree ``t`` from binned
        rows — integer-for-integer the ``_traverse`` routing decision,
        evaluated for every node instead of only the visited ones (the
        TreeSHAP recursion needs the hot child at each node)."""
        h = self.host_nodes()
        n = bins.shape[0]
        go = np.zeros((num_nodes, n), bool)
        w = h["cmask"].shape[1]
        for i in range(num_nodes):
            f = int(h["feat"][t, i])
            b = bins[:, f]
            is_nan = nanm[:, f]
            mt = int(h["miss"][t, i])
            nan_missing = is_nan if mt == MISSING_NAN \
                else np.zeros(n, bool)
            missing = nan_missing | (zerom[:, f] if mt == MISSING_ZERO
                                     else False)
            gl = np.where(missing, bool(h["dl"][t, i]),
                          b <= int(h["thr"][t, i]))
            if h["ic"][t, i]:
                c = cats[:, f]
                cm = h["cmask"][int(h["cref"][t, i]),
                                np.clip(c, 0, w - 1)]
                gl = (~nan_missing) & (c >= 0) & (c < w) & cm
            go[i] = gl
        return go

    # -------------------------------------------------- host-side binning

    def bin_host(self, xc: np.ndarray):
        """Exact f64 binning for inputs that do not round-trip through f32
        (same integer ranks as the device tables — the binned-input twin
        executable routes identically)."""
        nanm = np.isnan(xc)
        xz = np.where(nanm, 0.0, xc)
        zerom = np.abs(xz) <= ZERO_RANGE
        bins = np.zeros(xc.shape, np.int32)
        for i, u in enumerate(self.thr64):
            if len(u):
                bins[:, i] = np.searchsorted(u, xz[:, i], side="left")
        with np.errstate(invalid="ignore"):
            cats = np.clip(np.trunc(xz), np.iinfo(np.int32).min,
                           np.iinfo(np.int32).max).astype(np.int32)
        return bins, cats, nanm, zerom


# --------------------------------------------------- microbatch executables
#
# Module-level jitted kernels: every model array is an ARGUMENT, so two
# engines with the same bucket shapes (e.g. pre- and post-hot-swap models)
# share one compiled executable.  The raw-input kernel fuses device
# binning with traversal; the binned-input twin serves host-binned f64
# batches.


def _traverse(bins, cats, nanm, zerom, feat, thr, dl, miss, lc, rc, ic,
              cat_ref, cat_mask):
    """Vectorized decision-tree descent over pre-binned features.
    ``NumericalDecisionInner`` / ``CategoricalDecision`` semantics
    (tree.h:257-313), on integer threshold ranks -> leaf index [Tp, B]."""
    n = bins.shape[0]
    num_nodes = feat.shape[1]
    w = cat_mask.shape[1]

    def one_tree(feat_t, thr_t, dl_t, miss_t, lc_t, rc_t, ic_t, cref_t):
        def cond(state):
            node, _ = state
            return jnp.any(node >= 0)

        def body(state):
            node, leaf = state
            nd = jnp.clip(node, 0, num_nodes - 1)
            f = feat_t[nd]
            b = jnp.take_along_axis(bins, f[:, None], axis=1)[:, 0]
            c = jnp.take_along_axis(cats, f[:, None], axis=1)[:, 0]
            is_nan = jnp.take_along_axis(nanm, f[:, None], axis=1)[:, 0]
            is_zero = jnp.take_along_axis(zerom, f[:, None], axis=1)[:, 0]
            mt = miss_t[nd]
            nan_missing = (mt == MISSING_NAN) & is_nan
            missing = nan_missing | ((mt == MISSING_ZERO) & is_zero)
            go = jnp.where(missing, dl_t[nd], b <= thr_t[nd])
            cm = cat_mask[cref_t[nd], jnp.clip(c, 0, w - 1)]
            go_cat = (~nan_missing) & (c >= 0) & (c < w) & cm
            go = jnp.where(ic_t[nd], go_cat, go)
            nxt = jnp.where(go, lc_t[nd], rc_t[nd])
            active = node >= 0
            return (jnp.where(active, nxt, node),
                    jnp.where(active & (nxt < 0), ~nxt, leaf))

        _, leaf = lax.while_loop(
            cond, body, (jnp.zeros((n,), jnp.int32),
                         jnp.zeros((n,), jnp.int32)))
        return leaf

    # named_scope twin of the host predict_traverse span: bakes the
    # serving-traversal identity into the lowered HLO so the device-time
    # attributor (obs/devprof.py) can account traversal kernels by scope
    with jax.named_scope("traverse"):
        return jax.vmap(one_tree)(feat, thr, dl, miss, lc, rc, ic, cat_ref)


def _leaves_from_raw_impl(x, thr_table, *node_args):
    """x [B, Fc] f32 -> leaf [Tp, B]: on-device binning (one vmapped
    searchsorted against the resident threshold tables) fused with the
    traversal."""
    nanm = jnp.isnan(x)
    xz = jnp.where(nanm, jnp.float32(0), x)
    zerom = jnp.abs(xz) <= jnp.float32(_ZERO_RANGE_F32)
    bins = jax.vmap(lambda t, v: jnp.searchsorted(t, v, side="left"),
                    in_axes=(0, 1), out_axes=1)(thr_table, xz)
    bins = bins.astype(jnp.int32)
    cats = xz.astype(jnp.int32)
    return _traverse(bins, cats, nanm, zerom, *node_args)


def _leaves_from_binned_impl(bins, cats, nanm, zerom, *node_args):
    return _traverse(bins, cats, nanm, zerom, *node_args)


# ------------------------------------------- packed-node-word traversal
#
# serving_traversal=packed: the whole per-node routing record rides in two
# i32 words and the per-row feature payload in one (bin rank | nan bit |
# zero bit), so each traversal step is ONE node-word gather pair + ONE
# data-word gather — XLA:CPU lowers each separate gather scalar-by-scalar,
# which made the classic 8-gather step the serving bottleneck (PR 8's
# measured ~1.6x offline headroom).  The depth ladder is a ``fori_loop``
# (no per-step ``any(node >= 0)`` reduction): every row reaches its leaf
# within the bundle's max_depth, finished rows self-loop via the
# ``active`` select.  Routing decisions are integer-for-integer identical
# to ``_traverse``, so leaf indices — and therefore raw margins — are
# bit-identical (pinned in tests/test_serving.py).


def _traverse_packed(dat, w0s, w1s, depth):
    n = dat.shape[0]
    num_nodes = w0s.shape[1]

    def one_tree(w0_t, w1_t):
        def step(_, state):
            node, leaf = state
            nd = jnp.clip(node, 0, num_nodes - 1)
            w0 = w0_t[nd]
            w1 = w1_t[nd]
            f = w0 & 0xfff
            thr = (w0 >> 12) & 0xffff
            dl = (w0 >> 28) & 1
            mt = (w0 >> 29) & 3
            dw = jnp.take_along_axis(dat, f[:, None], axis=1)[:, 0]
            b = dw & 0xffffff
            missing = (((mt == MISSING_NAN) & ((dw >> 24) & 1 == 1))
                       | ((mt == MISSING_ZERO) & ((dw >> 25) & 1 == 1)))
            go = jnp.where(missing, dl == 1, b <= thr)
            nxt = jnp.where(go, (w1 << 16) >> 16, w1 >> 16)
            active = node >= 0
            return (jnp.where(active, nxt, node),
                    jnp.where(active & (nxt < 0), ~nxt, leaf))

        return lax.fori_loop(0, depth, step,
                             (jnp.zeros((n,), jnp.int32),
                              jnp.zeros((n,), jnp.int32)))[1]

    with jax.named_scope("traverse"):   # devprof scope twin (see _traverse)
        return jax.vmap(one_tree)(w0s, w1s)


def _pack_data_words(bins, nanm, zerom):
    return (bins.astype(jnp.int32)
            | (nanm.astype(jnp.int32) << 24)
            | (zerom.astype(jnp.int32) << 25))


def _leaves_from_raw_packed_impl(x, thr_table, w0s, w1s, depth):
    nanm = jnp.isnan(x)
    xz = jnp.where(nanm, jnp.float32(0), x)
    zerom = jnp.abs(xz) <= jnp.float32(_ZERO_RANGE_F32)
    bins = jax.vmap(lambda t, v: jnp.searchsorted(t, v, side="left"),
                    in_axes=(0, 1), out_axes=1)(thr_table, xz)
    return _traverse_packed(_pack_data_words(bins, nanm, zerom),
                            w0s, w1s, depth)


def _leaves_from_binned_packed_impl(bins, cats, nanm, zerom, w0s, w1s,
                                    depth):
    del cats     # packed bundles are numerical-only by construction
    return _traverse_packed(_pack_data_words(bins, nanm, zerom),
                            w0s, w1s, depth)


# ------------------------------------------------- auxiliary device kernels
#
# Model-quality plane (obs/model_quality.py): the binning stage of the
# raw-input traversal factored out standalone.  ``_bin_arrays`` hands the
# device-binned rows to the host TreeSHAP recursion
# (``pred_contrib=True``); ``_bin_hist`` folds one microbatch into
# per-feature threshold-rank histograms with a single scatter-add — the
# serving drift monitor's window accumulator.  Deliberately NOT counted
# by :func:`jit_entries`: that gauge pins the serving *traversal*
# executable set, which these do not touch.


def _bin_arrays_impl(x, thr_table):
    nanm = jnp.isnan(x)
    xz = jnp.where(nanm, jnp.float32(0), x)
    zerom = jnp.abs(xz) <= jnp.float32(_ZERO_RANGE_F32)
    bins = jax.vmap(lambda t, v: jnp.searchsorted(t, v, side="left"),
                    in_axes=(0, 1), out_axes=1)(thr_table, xz)
    return bins.astype(jnp.int32), xz.astype(jnp.int32), nanm, zerom


def _bin_hist_impl(x, thr_table, valid):
    nanm = jnp.isnan(x)
    xz = jnp.where(nanm, jnp.float32(0), x)
    bins = jax.vmap(lambda t, v: jnp.searchsorted(t, v, side="left"),
                    in_axes=(0, 1), out_axes=1)(thr_table, xz)
    bins = bins.astype(jnp.int32)
    nb1 = thr_table.shape[1] + 1
    vi = valid.astype(jnp.int32)
    return jax.vmap(
        lambda b: jnp.zeros((nb1,), jnp.int32).at[b].add(vi),
        in_axes=1)(bins)                                    # [Fc, NB+1]


@functools.lru_cache(maxsize=None)
def _aux_jitted():
    return jax.jit(_bin_arrays_impl), jax.jit(_bin_hist_impl)


@functools.lru_cache(maxsize=None)
def _jitted():
    return (jax.jit(_leaves_from_raw_impl),
            jax.jit(_leaves_from_binned_impl))


@functools.lru_cache(maxsize=None)
def _jitted_packed():
    """Packed-node-word twins (serving_traversal=packed).  ``depth`` is a
    traced scalar, so one executable pair serves every same-shape model —
    the hot-swap zero-recompile contract is unchanged."""
    return (jax.jit(_leaves_from_raw_packed_impl),
            jax.jit(_leaves_from_binned_packed_impl))


def jit_entries() -> int:
    """Compiled-signature count across both microbatch kernels — the
    ``predict_jit_entries`` gauge (``grower_jit_entries`` discipline): a
    mixed-size request replay over a warmed ladder must not move it.
    (Wrapping via ``_jitted`` is free — only executions compile.)"""
    return sum(int(fn._cache_size()) for fn in _jitted() + _jitted_packed())


# ----------------------------------------------------------------- engine


class PredictEngine:
    """The serving-side prediction engine: one SoA flatten at build, then
    bucketed microbatch executables with cached device-resident threshold
    tables.  ``raw_scores`` is bit-identical to
    ``Predictor.predict_raw_trees`` (pinned in tests/test_serving.py).

    ``backend`` picks the traversal that serves margin requests — the
    repo's ``auto`` ladder discipline:

    * ``xla`` — the SoA microbatch executables (this module).  Always
      built (it is the leaf-index path and the hot-swap-ready artifact)
      and the default wherever an accelerator backs jax.
    * ``native`` — the OpenMP C++ predictor (``lightgbm_tpu.native``),
      selected by ``auto`` on a bare-CPU backend when the library is
      available: a single host core walks trees ~4x faster through C++
      than through XLA:CPU's gather lowering (bench `serving` rung
      measures both).  Raw margins are bit-identical either way.
    """

    def __init__(self, trees: Sequence[Tree], num_class: int = 1,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 prewarm: bool = False, backend: str = "auto",
                 model_str: Optional[str] = None,
                 traversal: str = "auto"):
        self.bundle = SoABundle.build(list(trees), num_class)
        self.buckets = parse_serving_buckets(buckets)
        self.num_class = max(num_class, 1)
        self.timers = PhaseTimers()
        self._warmed = False
        if backend not in ("auto", "xla", "native"):
            raise ValueError(f"predict engine backend must be auto, xla, or "
                             f"native; got {backend!r}")
        self._native = None
        self.backend = self._resolve_backend(backend, model_str)
        if traversal not in ("auto", "xla", "packed"):
            raise ValueError(f"predict engine traversal must be auto, xla, "
                             f"or packed; got {traversal!r}")
        self.traversal = self._resolve_traversal(traversal)
        # serving drift monitor (obs/model_quality.DriftMonitor); attached
        # by the ModelServer when the model carries a training
        # distribution — every microbatch's binned rows fold into it
        self.drift = None
        if prewarm:
            self.prewarm()

    def _resolve_traversal(self, want: str) -> str:
        """serving_traversal ladder: ``packed`` walks two folded node
        words down a fixed max-depth fori ladder — the XLA:CPU headroom
        variant (the classic 8-gather step lowers scalar-by-scalar
        there).  ``auto`` picks it on a bare-CPU backend whenever the
        bundle packed; an explicit ``packed`` request on an unpackable
        ensemble degrades LOUDLY to xla (never silently mislabeled)."""
        packable = self.bundle.node_w0 is not None
        if want == "xla":
            return "xla"
        if want == "packed":
            if not packable:
                log.warning("serving_traversal=packed unavailable "
                            "(categorical nodes or field widths past the "
                            "node-word budget); using the xla traversal")
                obs_counters.event(
                    "layout_downgrade", stage="serving",
                    requested="serving_traversal=packed", resolved="xla",
                    reason="bundle not packable (categorical nodes or "
                           "field width)")
                return "xla"
            return "packed"
        backend_cpu = jax.default_backend() == "cpu"
        return "packed" if (packable and backend_cpu) else "xla"

    def _resolve_backend(self, want: str, model_str: Optional[str]) -> str:
        if want == "xla":
            return "xla"
        native_ok = False
        if model_str is not None:
            from . import native
            backend_cpu = jax.default_backend() == "cpu"
            if native.available() and (want == "native" or backend_cpu):
                try:
                    self._native = native.NativePredictor(model_str=model_str)
                    native_ok = True
                except Exception as e:   # fall back to the jitted path
                    log.debug("serving native backend unavailable (%s); "
                              "using xla", e)
        if want == "native" and not native_ok:
            raise ValueError("predict engine backend=native needs the "
                             "native library and a model_str")
        return "native" if native_ok else "xla"

    # ------------------------------------------------------------- shapes

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def _bucket_rows(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_bucket

    def memory_prediction(self) -> Dict[str, int]:
        """The serving term of the ``predict_hbm`` fit model for THIS
        bundle + ladder (obs/memory.py), used by the pre-flight."""
        b = self.bundle
        return obs_memory.predict_hbm(
            rows=0, features=0, bins=0, leaves=1,
            serving_trees=b.tp, serving_nodes=b.p, serving_cols=b.num_cols,
            serving_bins=b.num_bins, serving_buckets=self.buckets)

    def preflight(self, hbm_budget: float = 0.0) -> Dict:
        """Warn (or raise under an explicit ``hbm_budget``) BEFORE the
        first executable compiles when the node arrays + per-bucket batch
        buffers oversubscribe the device."""
        return obs_memory.preflight(self.memory_prediction(),
                                    hbm_budget=hbm_budget, context="serving")

    # -------------------------------------------------------------- warmup

    def prewarm(self, hbm_budget: float = 0.0) -> "PredictEngine":
        """Compile every ladder bucket now so the first request never pays
        a compile; a hot-swapped same-shape model reuses these
        executables."""
        self.preflight(hbm_budget)
        for b in self.buckets:
            x = jnp.zeros((b, max(self.bundle.num_cols, 1)), jnp.float32)
            jax.block_until_ready(self._raw_fn()(x, *self._raw_args()))
        obs_counters.gauge("predict_jit_entries", jit_entries())
        self._warmed = True
        return self

    # ------------------------------------------------- traversal plumbing

    def _raw_fn(self):
        return (_jitted_packed()[0] if self.traversal == "packed"
                else _jitted()[0])

    def _binned_fn(self):
        return (_jitted_packed()[1] if self.traversal == "packed"
                else _jitted()[1])

    def _raw_args(self) -> tuple:
        """Model-side arguments of the raw-input executable (after the
        batch buffer)."""
        b = self.bundle
        if self.traversal == "packed":
            return (b.thr_table, b.node_w0, b.node_w1,
                    jnp.asarray(b.max_depth, jnp.int32))
        return (b.thr_table,) + b.device_args()

    def _binned_args(self) -> tuple:
        b = self.bundle
        if self.traversal == "packed":
            return (b.node_w0, b.node_w1, jnp.asarray(b.max_depth, jnp.int32))
        return b.device_args()

    # ------------------------------------------------------------ leaves

    def _run_bucket(self, xc: np.ndarray, f32_safe: bool) -> np.ndarray:
        """One microbatch: pad rows up the ladder, dispatch the raw-input
        executable (f32-safe input) or the host-binned twin, return leaf
        [T, n]."""
        n = xc.shape[0]
        nb = self._bucket_rows(n)
        bundle = self.bundle
        path = "raw" if f32_safe else "binned"
        with self.timers.phase("predict_bin"):
            if f32_safe:
                xp = np.zeros((nb, max(bundle.num_cols, 1)), np.float32)
                xp[:n, :bundle.num_cols] = xc.astype(np.float32)
                dev_in = (jax.device_put(xp),) + self._raw_args()
                fn = self._raw_fn()
                if self.drift is not None:
                    self.drift.add_counts(np.asarray(_aux_jitted()[1](
                        xp, bundle.thr_table, np.arange(nb) < n)), n)
            else:
                bins, cats, nanm, zerom = bundle.bin_host(xc)
                if self.drift is not None:
                    self.drift.add_bins(bins)
                pad = ((0, nb - n), (0, max(bundle.num_cols, 1) - xc.shape[1]))
                dev_in = tuple(jax.device_put(np.pad(a, pad))
                               for a in (bins, cats, nanm, zerom)) \
                    + self._binned_args()
                fn = self._binned_fn()
        with self.timers.phase("predict_traverse"):
            leaves = fn(*dev_in)
            out = np.asarray(leaves)[:bundle.num_trees, :n]
        obs_counters.inc("predict_dispatch", bucket=nb, path=path,
                         traversal=self.traversal, exec=bundle.exec_id())
        obs_counters.gauge("predict_jit_entries", jit_entries())
        return out

    def leaves(self, X: np.ndarray) -> np.ndarray:
        """Leaf index per (tree, row) -> int32 [T, N]; batches above the
        largest ladder bucket run as consecutive max-bucket microbatches."""
        X = np.atleast_2d(np.asarray(X, np.float64))
        bundle = self.bundle
        if len(bundle.cols) and X.shape[1] <= int(bundle.cols[-1]):
            log.fatal("predict engine: input has %d features but the model "
                      "splits on feature %d", X.shape[1],
                      int(bundle.cols[-1]))
        xc = X[:, bundle.cols] if len(bundle.cols) else \
            np.zeros((X.shape[0], 0), np.float64)
        with np.errstate(invalid="ignore"):
            f32_safe = bool(np.all((xc == xc.astype(np.float32)
                                    .astype(np.float64)) | np.isnan(xc)))
        out = np.empty((bundle.num_trees, X.shape[0]), np.int32)
        step = self.max_bucket
        for lo in range(0, X.shape[0], step):
            chunk = xc[lo:lo + step]
            out[:, lo:lo + chunk.shape[0]] = self._run_bucket(chunk, f32_safe)
        return out

    # ---------------------------------------------------------- binned rows

    def binned_arrays(self, X: np.ndarray):
        """Device-binned rows ``(bins, cats, nanm, zerom)`` in compact-
        column rank space, each [N, Fc] — the ``pred_contrib`` traversal
        rides these through the same bucket ladder / f32-safety
        discipline as :meth:`leaves`, so the per-node decisions replayed
        from them route identically to the serving traversal."""
        X = np.atleast_2d(np.asarray(X, np.float64))
        bundle = self.bundle
        fc = max(bundle.num_cols, 1)
        xc = X[:, bundle.cols] if len(bundle.cols) else \
            np.zeros((X.shape[0], 0), np.float64)
        with np.errstate(invalid="ignore"):
            f32_safe = bool(np.all((xc == xc.astype(np.float32)
                                    .astype(np.float64)) | np.isnan(xc)))
        n = X.shape[0]
        bins = np.zeros((n, fc), np.int32)
        cats = np.zeros((n, fc), np.int32)
        nanm = np.zeros((n, fc), bool)
        zerom = np.zeros((n, fc), bool)
        step = self.max_bucket
        for lo in range(0, n, step):
            chunk = xc[lo:lo + step]
            m = chunk.shape[0]
            if f32_safe:
                nb = self._bucket_rows(m)
                xp = np.zeros((nb, fc), np.float32)
                xp[:m, :bundle.num_cols] = chunk.astype(np.float32)
                out = _aux_jitted()[0](xp, bundle.thr_table)
                for dst, arr in zip((bins, cats, nanm, zerom), out):
                    dst[lo:lo + m] = np.asarray(arr)[:m]
            else:
                for dst, arr in zip((bins, cats, nanm, zerom),
                                    bundle.bin_host(chunk)):
                    dst[lo:lo + m, :arr.shape[1]] = arr
        return bins, cats, nanm, zerom

    # ------------------------------------------------------------- scores

    def raw_scores(self, X: np.ndarray,
                   num_trees: int = -1) -> np.ndarray:
        """Raw margin scores [K, N], bit-identical to the per-tree host
        loop on either backend: the xla path gathers leaf indices from
        the microbatch executables and walks the same f64 leaf tables in
        the same iteration-major order; the native path is the C++
        predictor's identical sequential f64 accumulation."""
        bundle = self.bundle
        k = self.num_class
        total = bundle.num_trees if num_trees is None or num_trees < 0 \
            else min(num_trees, bundle.num_trees)
        if self._native is not None:
            with self.timers.phase("predict_traverse"):
                x = np.atleast_2d(np.asarray(X, np.float64))
                if self.drift is not None and len(bundle.cols):
                    # the native traversal never bins — fold the window
                    # histogram from a host bin pass over the compact
                    # columns so drift sees the same rank space
                    self.drift.add_bins(bundle.bin_host(x[:, bundle.cols])[0])
                out = self._native.predict(x, num_iteration=total // k,
                                           raw_score=True)
                out = out[None, :] if out.ndim == 1 \
                    else np.ascontiguousarray(out.T)
            obs_counters.inc("predict_dispatch", bucket=x.shape[0],
                             path="native", exec=bundle.exec_id())
            return out
        leaves = self.leaves(X)
        with self.timers.phase("predict_margin"):
            n = leaves.shape[1]
            out = np.zeros((k, n), np.float64)
            # the leaf-value channel axis: tree t serves class t % K; per
            # class the per-iteration adds run oldest-first, matching
            # Predictor.predict_raw_trees' accumulation order exactly
            for t in range(total):
                out[t % k] += bundle.leaf_value[t][leaves[t]]
        return out
