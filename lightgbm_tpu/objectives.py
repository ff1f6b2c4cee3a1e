"""Objective functions as pure jnp gradient transforms.

The reference's ``ObjectiveFunction`` hierarchy (``src/objective/*.hpp``,
factory ``src/objective/objective_function.cpp:10-36``) becomes a registry of
classes whose ``get_gradients(score) -> (grad, hess)`` are traced into the
boosting step's jit program.  Host-side setup (label statistics, query
boundaries, lookup tables) happens once in ``init``.

Formulas follow the reference exactly:
* regression L2/L1/huber/fair/poisson — ``regression_objective.hpp``
  (incl. the Gaussian hessian approximation for the non-smooth losses,
  ``common.h:486-495``, and 2.0.5's linear-score Poisson variant);
* binary logloss with sigmoid scaling / is_unbalance / scale_pos_weight —
  ``binary_objective.hpp:13-157``;
* multiclass softmax (K trees per iteration, ``h = 2p(1-p)``) and OVA —
  ``multiclass_objective.hpp``;
* cross-entropy + weighted "xentlambda" — ``xentropy_objective.hpp:39-268``;
* LambdaRank with |ΔNDCG|-weighted pairwise lambdas —
  ``rank_objective.hpp:19-245`` (vectorized per-query pairwise tensors instead
  of the reference's per-query loops + sigmoid lookup table).

Score layout is ``[K, N]`` (K = trees per iteration), matching the reference's
flattened ``score[k * num_data + i]``.
"""
from __future__ import annotations

import copy
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .config import Config
from .data.metadata import Metadata
from .obs.counters import counters as obs_counters
from .utils import log

K_MIN_SCORE = -np.inf
_GAUSS_C_MIN = 1.0e-10


class Objective:
    name = "base"
    is_constant_hessian = False
    boost_from_average = False
    need_accurate_prediction = True

    def __init__(self, config: Config):
        self.config = config
        self.num_tree_per_iteration = 1
        self.weights: Optional[jnp.ndarray] = None
        self.labels: Optional[jnp.ndarray] = None
        self.num_data = 0

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.num_data = num_data
        self.labels = jnp.asarray(metadata.label, jnp.float32)
        self.weights = (jnp.asarray(metadata.weight, jnp.float32)
                        if metadata.weight is not None else None)

    def get_gradients(self, score: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    # the per-row arrays get_gradients reads, rows on the last axis: on a
    # mesh of row shards boosting places them there and hands them to the
    # gradient program as arguments (boosting._sharded_grad_fn)
    row_array_names: Tuple[str, ...] = ("labels", "weights")

    def row_arrays(self) -> Dict[str, jnp.ndarray]:
        return {k: getattr(self, k) for k in self.row_array_names
                if getattr(self, k) is not None}

    def with_row_arrays(self, arrays: Dict[str, jnp.ndarray]) -> "Objective":
        """A shallow copy of this objective that reads ``arrays`` (named
        as :meth:`row_arrays` names them) in place of its own."""
        twin = copy.copy(self)
        for k, v in arrays.items():
            setattr(twin, k, v)
        return twin

    def convert_output(self, x):
        return x

    def average_stats(self) -> Tuple[float, float]:
        """(numerator, denominator) whose ratio is the label average that
        boost-from-average transforms.  Expressed as two plain sums so the
        multi-process driver can psum them globally before the transform —
        the reference's GlobalSyncUpByMean discipline."""
        label = np.asarray(self.labels)
        return float(label.sum()), float(len(label))

    def init_from_average(self, avg: float) -> float:
        """Init score from the (globally agreed) label average."""
        return float(avg)

    def to_string(self) -> str:
        return self.name

    def _w(self, g, h):
        if self.weights is None:
            return g, h
        return g * self.weights, h * self.weights


class RegressionL2(Objective):
    """regression_objective.hpp:11-76 (g = s - y, constant hessian)."""
    name = "regression"
    is_constant_hessian = True
    boost_from_average = True

    def get_gradients(self, score):
        g = score[0] - self.labels
        h = jnp.ones_like(g)
        g, h = self._w(g, h)
        return g[None], h[None]


def _gaussian_hessian(score, label, grad, eta, weight):
    """Common::ApproximateHessianWithGaussian (common.h:486-495)."""
    x = jnp.abs(score - label)
    a = 2.0 * jnp.abs(grad) * weight
    c = jnp.maximum((jnp.abs(score) + jnp.abs(label)) * eta, _GAUSS_C_MIN)
    return weight * jnp.exp(-x * x / (2.0 * c * c)) * a / (c * jnp.sqrt(2 * jnp.pi))


class RegressionL1(Objective):
    """regression_objective.hpp:78-156."""
    name = "regression_l1"
    boost_from_average = True

    def get_gradients(self, score):
        s = score[0]
        w = self.weights if self.weights is not None else jnp.ones_like(s)
        g = jnp.where(s > self.labels, 1.0, -1.0) * w
        h = _gaussian_hessian(s, self.labels, g, self.config.gaussian_eta, w)
        return g[None], h[None]


class RegressionHuber(Objective):
    """regression_objective.hpp:158-220 (quadratic inside delta, L1 outside
    with Gaussian-approximated hessian)."""
    name = "huber"
    boost_from_average = True

    def get_gradients(self, score):
        s = score[0]
        delta = self.config.huber_delta
        w = self.weights if self.weights is not None else jnp.ones_like(s)
        diff = s - self.labels
        inside = jnp.abs(diff) <= delta
        g_out = jnp.where(diff >= 0, delta, -delta) * w
        h_out = _gaussian_hessian(s, self.labels, g_out,
                                  self.config.gaussian_eta, w)
        g = jnp.where(inside, diff * w, g_out)
        h = jnp.where(inside, w, h_out)
        return g[None], h[None]


class RegressionFair(Objective):
    """regression_objective.hpp:233-293."""
    name = "fair"
    boost_from_average = True

    def get_gradients(self, score):
        c = self.config.fair_c
        x = score[0] - self.labels
        g = c * x / (jnp.abs(x) + c)
        h = c * c / (jnp.abs(x) + c) ** 2
        g, h = self._w(g, h)
        return g[None], h[None]


class RegressionPoisson(Objective):
    """regression_objective.hpp:298-358 — v2.0.5 linear-score form:
    g = s - y, h = s + max_delta_step."""
    name = "poisson"
    boost_from_average = True

    def get_gradients(self, score):
        s = score[0]
        g = s - self.labels
        h = s + self.config.poisson_max_delta_step
        g, h = self._w(g, h)
        return g[None], h[None]


class BinaryLogloss(Objective):
    """binary_objective.hpp:13-157."""
    name = "binary"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label = np.asarray(metadata.label)
        cnt_pos = int((label > 0).sum())
        cnt_neg = num_data - cnt_pos
        if cnt_pos == 0 or cnt_neg == 0:
            log.warning("Only one class present in label")
        log.info("Number of positive: %d, number of negative: %d", cnt_pos, cnt_neg)
        lw = [1.0, 1.0]
        if self.config.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                lw[0] = cnt_pos / cnt_neg
            else:
                lw[1] = cnt_neg / cnt_pos
        lw[1] *= self.config.scale_pos_weight
        self._label_sign = jnp.where(self.labels > 0, 1.0, -1.0)
        self._label_weight = jnp.where(self.labels > 0, lw[1], lw[0])

    row_array_names = Objective.row_array_names + ("_label_sign",
                                                   "_label_weight")

    def get_gradients(self, score):
        sig = self.config.sigmoid
        ls = self._label_sign
        response = -ls * sig / (1.0 + jnp.exp(ls * sig * score[0]))
        abs_r = jnp.abs(response)
        g = response * self._label_weight
        h = abs_r * (sig - abs_r) * self._label_weight
        g, h = self._w(g, h)
        return g[None], h[None]

    def convert_output(self, x):
        return 1.0 / (1.0 + np.exp(-self.config.sigmoid * np.asarray(x)))

    def to_string(self):
        return f"binary sigmoid:{self.config.sigmoid:g}"


class MulticlassSoftmax(Objective):
    """multiclass_objective.hpp:16-136 — K trees/iteration."""
    name = "multiclass"

    def __init__(self, config):
        super().__init__(config)
        self.num_tree_per_iteration = config.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        li = np.asarray(metadata.label, dtype=np.int32)
        if li.min() < 0 or li.max() >= self.config.num_class:
            log.fatal("Label must be in [0, %d)", self.config.num_class)
        self._onehot = jnp.asarray(
            np.eye(self.config.num_class, dtype=np.float32)[:, li])  # [K, N]

    row_array_names = Objective.row_array_names + ("_onehot",)

    def get_gradients(self, score):
        p = jax.nn.softmax(score, axis=0)          # [K, N]
        g = p - self._onehot
        h = 2.0 * p * (1.0 - p)
        if self.weights is not None:
            g = g * self.weights[None]
            h = h * self.weights[None]
        return g, h

    def convert_output(self, x):
        x = np.asarray(x, dtype=np.float64)
        e = np.exp(x - x.max(axis=0, keepdims=True))
        return e / e.sum(axis=0, keepdims=True)

    def to_string(self):
        return f"multiclass num_class:{self.config.num_class}"


class MulticlassOVA(Objective):
    """multiclass_objective.hpp:139-210 — K independent binary classifiers."""
    name = "multiclassova"

    def __init__(self, config):
        super().__init__(config)
        self.num_tree_per_iteration = config.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        li = np.asarray(metadata.label, dtype=np.int32)
        self._sign = jnp.asarray(
            np.where(np.eye(self.config.num_class)[:, li] > 0, 1.0, -1.0)
            .astype(np.float32))

    row_array_names = Objective.row_array_names + ("_sign",)

    def get_gradients(self, score):
        sig = self.config.sigmoid
        response = -self._sign * sig / (1.0 + jnp.exp(self._sign * sig * score))
        abs_r = jnp.abs(response)
        g = response
        h = abs_r * (sig - abs_r)
        if self.weights is not None:
            g = g * self.weights[None]
            h = h * self.weights[None]
        return g, h

    def convert_output(self, x):
        return 1.0 / (1.0 + np.exp(-self.config.sigmoid * np.asarray(x)))

    def to_string(self):
        return (f"multiclassova num_class:{self.config.num_class} "
                f"sigmoid:{self.config.sigmoid:g}")


class CrossEntropy(Objective):
    """xentropy_objective.hpp:39-137 (labels in [0,1])."""
    name = "xentropy"
    boost_from_average = True

    def get_gradients(self, score):
        z = 1.0 / (1.0 + jnp.exp(-score[0]))
        g = z - self.labels
        h = z * (1.0 - z)
        g, h = self._w(g, h)
        return g[None], h[None]

    def convert_output(self, x):
        return 1.0 / (1.0 + np.exp(-np.asarray(x)))

    def average_stats(self):
        label = np.asarray(self.labels)
        if self.weights is not None:
            w = np.asarray(self.weights)
            return float((label * w).sum()), float(w.sum())
        return float(label.sum()), float(len(label))

    def init_from_average(self, pavg):
        pavg = min(max(float(pavg), 1e-15), 1.0 - 1e-15)
        init = float(np.log(pavg / (1.0 - pavg)))
        log.info("[xentropy]: pavg=%f -> initscore=%f", pavg, init)
        return init


class CrossEntropyLambda(Objective):
    """xentropy_objective.hpp:139-268 ("xentlambda": intensity-weighted)."""
    name = "xentlambda"
    boost_from_average = True

    def get_gradients(self, score):
        s = score[0]
        y = self.labels
        if self.weights is None:
            z = 1.0 / (1.0 + jnp.exp(-s))
            g = z - y
            h = z * (1.0 - z)
        else:
            w = self.weights
            epf = jnp.exp(s)
            hhat = jnp.log1p(epf)
            z = 1.0 - jnp.exp(-w * hhat)
            enf = 1.0 / epf
            g = (1.0 - y / z) * w / (1.0 + enf)
            c = 1.0 / (1.0 - z)
            d = 1.0 + epf
            a = w * epf / (d * d)
            b = (c / (d * d)) * (1.0 + w * epf - c)
            h = a * (1.0 + y * b)
        return g[None], h[None]

    def convert_output(self, x):
        return np.log1p(np.exp(np.asarray(x)))

    def average_stats(self):
        label = np.asarray(self.labels)
        if self.weights is not None:
            w = np.asarray(self.weights)
            return float((label * w).sum()), float(w.sum())
        return float(label.sum()), float(len(label))

    def init_from_average(self, havg):
        init = float(np.log(np.expm1(max(float(havg), 1e-15))))
        log.info("[xentlambda]: havg=%f -> initscore=%f", havg, init)
        return init


def default_label_gain(max_label: int = 31):
    """2^i - 1 label gains (DCGCalculator::DefaultLabelGain)."""
    return [float((1 << i) - 1) for i in range(max_label)]


# Padded query lengths come in half-steps above this size, as the grower's
# window sizes do above 2^13 (grower._bucket_sizes): a query costs its
# padded length squared, so a table of powers of two alone would build
# twice the pairs of the half-stepped one on lengths just past a power.
_HALF_STEP_FROM = 16
_PAIR_BLOCK = 16e6      # pair slots a chunk of queries may build (64 MB)


def _length_table(longest: int):
    """The static, ascending table of padded query lengths covering
    [1, longest]: powers of two and, from ``_HALF_STEP_FROM`` up, the
    half-steps between them, ending at the first that holds ``longest``."""
    sizes, d = [], 1
    while True:
        sizes.append(d)
        if d >= _HALF_STEP_FROM and sizes[-1] < longest:
            sizes.append(3 * d // 2)
        if sizes[-1] >= longest:
            return sizes
        d *= 2


class LambdarankNDCG(Objective):
    """rank_objective.hpp:19-245, over length buckets.

    ``init`` groups the queries by padded length on ``_length_table``; a
    bucket is a ``[Q_b, D_b]`` table of row indices (``num_data`` where a
    slot is padding), processed in chunks of queries that bound the
    ``[C, D_b, D_b]`` pairwise block.  Nothing is sorted: a document's rank
    is the count of its query's documents that score higher, or as high
    and stand earlier (what a stable descending sort gives), and the block
    is summed along one axis only, each pair seen from both its documents.
    Every document sits in exactly one slot, so the way back to row order
    is one gather through a permutation fixed here (sigmoid applied
    directly — no lookup table needed on TPU).
    """
    name = "lambdarank"
    need_accurate_prediction = False

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Lambdarank tasks require query information")
        bounds = np.asarray(metadata.query_boundaries, np.int64)
        self.num_queries = len(bounds) - 1
        sizes = np.diff(bounds)
        label = np.asarray(metadata.label)
        gains = np.asarray(self.config.label_gain or default_label_gain(),
                           dtype=np.float64)
        if int(label.max()) >= len(gains):
            log.fatal("Label %d exceeds label_gain size", int(label.max()))
        ilabel = label.astype(np.int64)

        # truncated max DCG per query (CalMaxDCGAtK at max_position): the
        # documents by query and descending label, the first k of each
        discounts = 1.0 / np.log2(np.arange(int(sizes.max()) + 2) + 2.0)
        qid = np.repeat(np.arange(self.num_queries), sizes)
        by_label = np.lexsort((-ilabel, qid))
        place = np.arange(num_data) - bounds[qid]
        top = place < self.config.max_position
        max_dcg = np.bincount(
            qid[top], gains[ilabel[by_label][top]] * discounts[place[top]],
            minlength=self.num_queries)
        inv_max_dcg = np.divide(1.0, max_dcg, out=np.zeros_like(max_dcg),
                                where=max_dcg > 0)

        table = np.asarray(_length_table(int(sizes.max())))
        bucket_of = np.searchsorted(table, sizes)
        label_pad = np.append(label, 0).astype(np.float32)
        idx, lens, inv, self._buckets = [], [], [], []
        slot_of_row = np.empty(num_data, np.int64)
        slots = qslots = 0
        for b in np.unique(bucket_of):
            D = int(table[b])
            qs = np.flatnonzero(bucket_of == b)
            C = min(len(qs), max(1, int(_PAIR_BLOCK // (D * D))))
            Q = -(-len(qs) // C) * C             # whole chunks of C queries
            col = np.arange(D)
            real = col[None, :] < sizes[qs][:, None]
            rows = np.full((Q, D), num_data, np.int64)
            rows[:len(qs)] = np.where(real, bounds[qs][:, None] + col,
                                      num_data)
            slot_of_row[rows[:len(qs)][real]] = \
                slots + np.flatnonzero(real.ravel())
            idx.append(rows.ravel())
            lens.append(np.pad(sizes[qs], (0, Q - len(qs))))
            inv.append(np.pad(inv_max_dcg[qs], (0, Q - len(qs))))
            self._buckets.append((slots, qslots, Q, D, C))
            slots += Q * D
            qslots += Q
        idx = np.concatenate(idx)
        self._pair_slots = sum(Q * D * D for _, _, Q, D, _ in self._buckets)
        # converted here, on the host: a dtype given to jnp.asarray is one
        # more device program to compile and run, each
        self._idx = jnp.asarray(idx.astype(np.int32))
        slot_label = label_pad[idx]
        self._slot_label = jnp.asarray(slot_label)
        self._slot_gain = jnp.asarray(
            gains[slot_label.astype(np.int64)].astype(np.float32))
        self._len = jnp.asarray(np.concatenate(lens).astype(np.int32))
        self._inv_max_dcg = jnp.asarray(
            np.concatenate(inv).astype(np.float32))
        self._slot_of_row = jnp.asarray(slot_of_row.astype(np.int32))
        self._discount = jnp.asarray(discounts.astype(np.float32))

    def _one_chunk(self, args):
        s, y, gain, n, inv_mdcg = args     # [C, D] x 3, [C], [C]
        sigma = self.config.sigmoid
        D = s.shape[1]
        col = jnp.arange(D)
        valid = col[None, :] < n[:, None]
        both = valid[:, :, None] & valid[:, None, :]
        ds = s[:, :, None] - s[:, None, :]         # s_i - s_j
        with jax.named_scope("rank_sort"):
            ahead = (ds < 0) | ((ds == 0) & (col[None, :] < col[:, None]))
            rank = jnp.sum(ahead & both, axis=2)
            disc = self._discount[rank]
            best = jnp.max(jnp.where(valid, s, -jnp.inf), axis=1)
            worst = jnp.min(jnp.where(valid, s, jnp.inf), axis=1)
            nondegen = (best != worst)[:, None, None]
        with jax.named_scope("rank_pairs"):
            # document i against j, from i's side: i is the pair's high
            # document where its label is the larger, else its low one
            high = y[:, :, None] > y[:, None, :]
            pair = (y[:, :, None] != y[:, None, :]) & both
            sign = jnp.where(high, 1.0, -1.0)
            dcg_gap = sign * (gain[:, :, None] - gain[:, None, :])
            paired_disc = jnp.abs(disc[:, :, None] - disc[:, None, :])
            delta_ndcg = dcg_gap * paired_disc * inv_mdcg[:, None, None]
            delta_ndcg = jnp.where(
                nondegen, delta_ndcg / (0.01 + jnp.abs(ds)), delta_ndcg)
            p = 2.0 / (1.0 + jnp.exp(2.0 * sigma * sign * ds))
            lam = jnp.where(pair, -sign * delta_ndcg * p, 0.0)
            hes = jnp.where(pair, p * (2.0 - p) * 2.0 * delta_ndcg, 0.0)
            return lam.sum(axis=2), hes.sum(axis=2)

    def get_gradients(self, score):
        # trace-time identity evidence (the hist_dispatch discipline): what
        # was built, per compiled call site
        obs_counters.inc("objective_dispatch", impl="buckets",
                         buckets=len(self._buckets),
                         slots=int(self._idx.shape[0]),
                         pair_slots=self._pair_slots)
        with jax.named_scope("rank_sort"):
            s_slot = jnp.append(score[0], 0.0)[self._idx]
        lam, hes = [], []
        for off, qoff, Q, D, C in self._buckets:
            def table(a):
                return a[off:off + Q * D].reshape(Q // C, C, D)

            def per_query(a):
                return a[qoff:qoff + Q].reshape(Q // C, C)
            args = (table(s_slot), table(self._slot_label),
                    table(self._slot_gain), per_query(self._len),
                    per_query(self._inv_max_dcg))
            if Q == C:
                out = self._one_chunk(tuple(a[0] for a in args))
            else:
                out = lax.map(self._one_chunk, args)
            lam.append(out[0].reshape(-1))
            hes.append(out[1].reshape(-1))
        with jax.named_scope("rank_write"):
            g = jnp.concatenate(lam)[self._slot_of_row]
            h = jnp.concatenate(hes)[self._slot_of_row]
        if self.weights is not None:
            g = g * self.weights
            h = h * self.weights
        return g[None], h[None]


_REGISTRY = {
    "regression": RegressionL2,
    "regression_l2": RegressionL2,
    "mean_squared_error": RegressionL2,
    "mse": RegressionL2,
    "l2": RegressionL2,
    "regression_l1": RegressionL1,
    "l1": RegressionL1,
    "mean_absolute_error": RegressionL1,
    "mae": RegressionL1,
    "huber": RegressionHuber,
    "fair": RegressionFair,
    "poisson": RegressionPoisson,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "softmax": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "multiclass_ova": MulticlassOVA,
    "ova": MulticlassOVA,
    "ovr": MulticlassOVA,
    "xentropy": CrossEntropy,
    "cross_entropy": CrossEntropy,
    "xentlambda": CrossEntropyLambda,
    "cross_entropy_lambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
}


def create_objective(config: Config) -> Objective:
    """Factory (objective_function.cpp:10-36)."""
    name = config.objective.lower()
    if name not in _REGISTRY:
        log.fatal("Unknown objective type name: %s", name)
    return _REGISTRY[name](config)


def parse_objective_string(s: str, config: Config) -> Objective:
    """Parse a model-file objective line, e.g. 'binary sigmoid:1'."""
    toks = s.split()
    cfg = config.copy()
    cfg.objective = toks[0]
    for t in toks[1:]:
        if ":" in t:
            k, v = t.split(":", 1)
            if k == "sigmoid":
                cfg.sigmoid = float(v)
            elif k == "num_class":
                cfg.num_class = int(v)
    return create_objective(cfg)
