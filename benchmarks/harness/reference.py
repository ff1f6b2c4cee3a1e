"""The plain reference: leaf-wise histogram boosting for binary logloss,
in numpy and float64, written from the published algorithm (LightGBM,
``feature_histogram.hpp`` split gain and leaf output).  It imports nothing
of the program.

It *follows* trees rather than racing them.  Two sound growers pick
different splits wherever two gains tie to rounding, and from there the
trees differ in everything; so, as for a served model's tokens, the
reference is run over the answers that were given: at every node of a given
tree it builds the node's histogram from the raw rows and its own gradients,
scans every column and bin for its own best split, and reads how far the
given split's gain lies below that best.  Leaf outputs, row counts, the
score and the metrics are its own, computed from its own chain of scores.

``precision`` rounds the gradients and hessians before they are summed:
``float64`` is the reference, ``bfloat16`` the control (the nearest
precision below the float32 the configurations state).
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

K_EPSILON = 1e-15
THREADS = 4       # columns are independent; the window is closed by now


def round_to(a, precision):
    if precision == "float64":
        return a
    if precision == "bfloat16":
        import ml_dtypes
        return a.astype(np.float32).astype(ml_dtypes.bfloat16) \
                .astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def sigmoid(s):
    return 1.0 / (1.0 + np.exp(-s))


def gradients(score, y, precision="float64"):
    """Binary logloss: g = p - y, h = p (1 - p)."""
    p = sigmoid(score)
    return round_to(p - y, precision), round_to(p * (1.0 - p), precision)


def bin_columns(cols, bounds):
    """Bin of every value: the first bin whose upper bound is >= the value.
    ``cols`` is [columns, rows]; ``bounds[f]`` the ascending upper bounds."""
    widest = max(len(b) for b in bounds)
    out = np.empty(cols.shape, np.uint8 if widest <= 256 else np.uint16)

    def one(f):
        out[f] = np.searchsorted(np.asarray(bounds[f], np.float64)[:-1],
                                 cols[f].astype(np.float64), side="left")
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(one, range(len(bounds))))
    return out


def bound_faults(bounds, max_bin):
    """Columns whose bounds are not strictly ascending or number more than
    ``max_bin``: an exact check, 0 in a sound run."""
    bad = 0
    for b in bounds:
        b = np.asarray(b, np.float64)
        if len(b) > max_bin or len(b) < 1 or np.any(np.diff(b) <= 0):
            bad += 1
    return bad


def route(cols, tree):
    """Leaf of every row, by the tree's real thresholds on the raw values
    (left when value <= threshold).  Internal node i is the i-th split, so
    a parent always comes before its children."""
    n = cols.shape[1]
    leaf = np.zeros(n, np.int32)
    pending = {0: np.arange(n, dtype=np.int32)}
    for i in range(len(tree["left_child"])):
        rows = pending.pop(i)
        left = (cols[tree["split_feature"][i]][rows].astype(np.float64)
                <= tree["threshold"][i])
        for child, sel in ((int(tree["left_child"][i]), rows[left]),
                           (int(tree["right_child"][i]), rows[~left])):
            if child < 0:
                leaf[sel] = ~child
            else:
                pending[child] = sel
    return leaf


def leaf_tables(bins, leaf, g, h, n_leaves, n_bins):
    """[leaves, columns, bins] sums of g, of h and row counts."""
    cols = bins.shape[0]
    base = leaf.astype(np.int64) * n_bins
    size = n_leaves * n_bins
    tg = np.empty((cols, n_leaves, n_bins))
    th = np.empty_like(tg)
    tc = np.empty_like(tg)

    def one(f):
        key = base + bins[f]
        tg[f] = np.bincount(key, g, size).reshape(n_leaves, n_bins)
        th[f] = np.bincount(key, h, size).reshape(n_leaves, n_bins)
        tc[f] = np.bincount(key, None, size).reshape(n_leaves, n_bins)
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(one, range(cols)))
    return (tg.transpose(1, 0, 2), th.transpose(1, 0, 2),
            tc.transpose(1, 0, 2))


def node_tables(leaf_t, tree):
    """Histogram of every internal node: the sum of its children's."""
    n = len(tree["left_child"])
    out = np.zeros((n,) + leaf_t.shape[1:])
    for i in range(n - 1, -1, -1):
        for child in (int(tree["left_child"][i]),
                      int(tree["right_child"][i])):
            out[i] += leaf_t[~child] if child < 0 else out[child]
    return out


def split_gains(ng, nh, nc, n_bins_of, params):
    """Gain of every (node, column, threshold bin) over the parent's, -inf
    where the split is not allowed: left holds bins <= threshold."""
    l1 = float(params.get("lambda_l1", 0.0))
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = float(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    min_gain = float(params.get("min_gain_to_split", 0.0))

    def leaf_gain(sg, sh):
        reg = np.maximum(np.abs(sg) - l1, 0.0)
        return reg * reg / (sh + l2)

    G = ng[:, 0, :].sum(-1)[:, None, None]
    H = nh[:, 0, :].sum(-1)[:, None, None] + 2 * K_EPSILON
    C = nc[:, 0, :].sum(-1)[:, None, None]
    lg, lh, lc = (np.cumsum(ng, -1), np.cumsum(nh, -1) + K_EPSILON,
                  np.cumsum(nc, -1))
    rg, rh, rc = G - lg, H - lh, C - lc
    thr = np.arange(ng.shape[-1])[None, None, :]
    ok = ((thr <= np.asarray(n_bins_of)[None, :, None] - 2)
          & (lc >= min_data) & (rc >= min_data)
          & (lh >= min_hess) & (rh >= min_hess))
    parent = leaf_gain(G, H)
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = leaf_gain(lg, lh) + leaf_gain(rg, rh)
    ok &= gain > parent + min_gain
    return np.where(ok, gain - parent, -np.inf)


def leaf_outputs(leaf_g, leaf_h, params):
    l1 = float(params.get("lambda_l1", 0.0))
    l2 = float(params.get("lambda_l2", 0.0))
    reg = np.maximum(np.abs(leaf_g) - l1, 0.0)
    return (-np.sign(leaf_g) * reg / (leaf_h + l2)
            * float(params.get("learning_rate", 0.1)))


def logloss(score, y):
    p = sigmoid(score)
    return float(-np.mean(np.log(np.where(y > 0, p, 1.0 - p))))


def auc(score, y):
    """Rank-sum AUC; rows of equal score count half."""
    values, inverse = np.unique(score, return_inverse=True)
    pos = np.bincount(inverse, y > 0, len(values))
    neg = np.bincount(inverse, y <= 0, len(values))
    neg_below = np.cumsum(neg) - neg
    return float(np.sum(pos * (neg_below + 0.5 * neg))
                 / (pos.sum() * neg.sum()))


METRICS = {"binary_logloss": logloss, "auc": auc}


class Follower:
    """One chain of scores through the given trees, at one precision."""

    def __init__(self, cols, y, bounds, params, precision="float64",
                 valid=None, bins=None):
        self.cols, self.y, self.params = cols, y.astype(np.float64), params
        self.precision = precision
        self.n_bins_of = [len(b) for b in bounds]
        self.n_bins = max(self.n_bins_of)
        self.bins = bin_columns(cols, bounds) if bins is None else bins
        self.score = np.zeros(cols.shape[1])     # binary logloss starts at 0
        self.valid = valid                        # (cols_v, y_v) or None
        if valid is not None:
            self.valid_score = np.zeros(valid[0].shape[1])

    def step(self, tree, metrics=()):
        """Follow one tree.  Returns its own leaf outputs and counts, the
        gains of every candidate at every node, and its metrics after the
        tree; then moves its scores by its own outputs."""
        n_leaves = int(tree["num_leaves"])
        g, h = gradients(self.score, self.y, self.precision)
        leaf = route(self.cols, tree)
        tg, th, tc = leaf_tables(self.bins, leaf, g, h, n_leaves,
                                 self.n_bins)
        gains = split_gains(node_tables(tg, tree), node_tables(th, tree),
                            node_tables(tc, tree), self.n_bins_of,
                            self.params)
        out = {
            "leaf_count": tc[:, 0, :].sum(-1).astype(np.int64),
            "leaf_value": leaf_outputs(tg[:, 0, :].sum(-1),
                                       th[:, 0, :].sum(-1), self.params),
            "gains": gains,
        }
        self.score += out["leaf_value"][leaf]
        res = {}
        if metrics:
            res["training"] = {m: METRICS[m](self.score, self.y)
                               for m in metrics}
            if self.valid is not None:
                cols_v, y_v = self.valid
                self.valid_score += out["leaf_value"][route(cols_v, tree)]
                res["valid"] = {m: METRICS[m](self.valid_score, y_v)
                                for m in metrics}
        out["metrics"] = res
        return out


def threshold_bins(tree, bounds):
    """Bin index of each of the tree's real thresholds in the given bounds;
    -1 where a threshold is no bound."""
    out = np.full(len(tree["threshold"]), -1, np.int64)
    for i, (f, t) in enumerate(zip(tree["split_feature"],
                                   tree["threshold"])):
        b = np.asarray(bounds[int(f)], np.float64)
        j = int(np.searchsorted(b, t, side="left"))
        if j < len(b) and b[j] == t:
            out[i] = j
    return out


def score_by_trees(cols, trees):
    """Sum of the given trees' own leaf values over the given rows."""
    score = np.zeros(cols.shape[1])
    for tree in trees:
        score += np.asarray(tree["leaf_value"])[route(cols, tree)]
    return score
