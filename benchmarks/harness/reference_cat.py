"""The plain reference for categorical columns: leaf-wise histogram boosting
for binary logloss over integer-coded categorical columns, in numpy and
float64, written from the published algorithm (LightGBM v2.0.5: the
categorical ``BinMapper`` of ``bin.cpp`` and ``FindBestThresholdCategorical``
of ``feature_histogram.hpp:104-223``).  It imports nothing of the program.

As ``reference.py`` it *follows* the given trees rather than racing them:

- a row is routed by its RAW category code: left where the code is in the
  node's category set, right otherwise (an unseen code, or one of the rare
  codes that share a column's last bin, is in no set);
- each node's histogram is built from the raw rows through the program's
  category -> bin maps, taken as given (``bin_2_categorical``: bin ``b``
  holds code ``map[b]``; a code the map lacks falls in the last bin), and
  the maps themselves are checked by ``map_faults`` for what does not
  depend on which rows the program sampled;
- at every node it finds its own best categorical split by the published
  rules (``scan``) and computes the given split's gain from the given
  category set; leaf outputs, counts and scores are its own.

The published rules, per node and column (``scan`` and
``tests/test_expo_cat_cell.py``'s loop transcription):

- ``used_bin`` = bins - 1, or all bins where the column is full-categorical
  (its map kept every category, no missing value): the last bin, where the
  rare and unseen codes are, is never scanned otherwise;
- smoothing: ``smooth_h = min(max_cat_smooth, max(cat_smooth_ratio *
  rows / bins, min_cat_smooth))``, ``smooth_g = smooth_h * G / H`` over the
  node's sums; the scanned bins sorted by ``(g + smooth_g) / (h + smooth_h)``
  ascending, ties in bin order;
- two directions, the ascending order (+1) and the descending (-1), each a
  prefix of up to ``min(max_cat_threshold, bins)`` positions below
  ``used_bin``; -1 is skipped for a full-categorical column whose bins are at
  most ``2 * max_cat_threshold``;
- along a direction the left side grows by one bin a position; a position
  whose left side is short of ``min_data_in_leaf`` rows or
  ``min_sum_hessian_in_leaf`` is passed (``continue``), one whose right side
  is short of either ends the direction (``break``);
- ``max_cat_group`` accounting: the rows of the bins added since the last
  candidate must reach ``min_data_per_group``, which starts at
  ``max(1, rows // max_cat_group)``; a candidate that reaches it resets the
  count, takes one of the ``max_cat_group`` groups and, while groups remain,
  sets ``min_data_per_group = max(1, right rows // groups left)``;
- a candidate's gain ``G_l^2 / (H_l + l2) + G_r^2 / (H_r + l2)`` (L1
  soft-thresholded), kept where it exceeds the parent's by more than
  ``min_gain_to_split``; the best is the first strict maximum in the order
  column, direction (+1 then -1), position.

``precision`` rounds the gradients and hessians before they are summed, as
in ``reference.py``: ``float64`` the reference, ``bfloat16`` the control.
"""
import numpy as np

from . import reference

K_EPSILON = reference.K_EPSILON
SAMPLE_COVER = 0.99        # bin.cpp: kept categories cover 99 % of the sample
# the kept set's share of ALL rows differs from its share of the sample the
# program binned on by sampling error: a share p of n rows drawn from N has
# standard error sqrt(p (1 - p) / n) * sqrt(1 - n / N), 2.2e-4 at p = 0.99,
# n = 200,000, N = 10,000,000; five of them, 1.1e-3, leave a sound map
# clear.  So the check is blind to a small shortfall: on expo-cat a category
# at the cut holds about 5.7e-4 of the rows, and a map that stops one or two
# categories early still reads 0; three or more short of 99 % read a fault
COVER_SIGMAS = 5.0


def cover_tolerance(sampled, rows):
    """How far under 99 % of all rows a sound map's kept categories may
    cover, for a sample of ``sampled`` of ``rows`` rows."""
    if sampled >= rows:
        return 0.0
    return COVER_SIGMAS * np.sqrt(SAMPLE_COVER * (1 - SAMPLE_COVER)
                                  / sampled * (1 - sampled / rows))


def map_faults(codes, maps, max_bin, sampled):
    """Faults of the program's category -> bin maps that do not depend on
    which rows it sampled, summed over columns: a category in two bins; a
    bin that holds no category of the data; a map of fewer than
    ``min(categories, max_bin)`` bins; kept categories that cover less than
    99 % of all rows, less ``cover_tolerance``.  ``codes`` [columns, rows]
    integers, ``maps[j]`` = (``bin_2_categorical``, bins)."""
    faults = 0
    rows = codes.shape[1]
    for col, (b2c, n_bins) in zip(codes, maps):
        b2c = np.asarray(b2c, np.int64)
        counts = np.bincount(col)
        present = np.flatnonzero(counts)
        faults += len(b2c) - len(np.unique(b2c))            # in two bins
        # a bin with no category, or none that the data holds
        faults += max(int(n_bins) - len(b2c), 0)
        faults += int(np.sum(~np.isin(b2c, present)))
        faults += int(n_bins) < min(len(present), int(max_bin))
        kept = np.unique(b2c[(b2c >= 0) & (b2c < len(counts))])
        cover = counts[kept].sum() / rows
        faults += cover < SAMPLE_COVER - cover_tolerance(sampled, rows)
    return int(faults)


def bin_lookups(codes, maps):
    """Per column, the bin of every code up to the largest the data holds:
    ``map``'s position, else the last bin."""
    out = []
    for col, (b2c, n_bins) in zip(codes, maps):
        lut = np.full(int(col.max()) + 1, int(n_bins) - 1, np.int32)
        b2c = np.asarray(b2c, np.int64)
        ok = (b2c >= 0) & (b2c < len(lut))
        lut[b2c[ok]] = np.flatnonzero(ok)
        out.append(lut)
    return out


def full_categorical(col, b2c):
    """Every code the column holds has a bin of its own."""
    return bool(np.all(np.isin(np.flatnonzero(np.bincount(col)),
                               np.asarray(b2c))))


def route(codes, tree):
    """Leaf of every row by the raw codes: left where a row's code is in the
    node's category set (``tree["cat_codes"][i]``)."""
    n = codes.shape[1]
    leaf = np.zeros(n, np.int32)
    pending = {0: np.arange(n, dtype=np.int64)}
    for i in range(len(tree["left_child"])):
        rows = pending.pop(i)
        col = codes[int(tree["split_feature"][i])]
        inset = np.zeros(int(col.max()) + 1, bool)
        cats = np.asarray(tree["cat_codes"][i], np.int64)
        inset[cats[cats < len(inset)]] = True
        left = inset[col[rows]]
        for child, sel in ((int(tree["left_child"][i]), rows[left]),
                           (int(tree["right_child"][i]), rows[~left])):
            if child < 0:
                leaf[sel] = ~child
            else:
                pending[child] = sel
    return leaf


def leaf_gain(sg, sh, l1, l2):
    """G(s, h) with L1 soft-thresholding; a side with no hessian at all
    (an empty column's histogram) reads inf or nan, and is never taken."""
    reg = np.maximum(np.abs(sg) - l1, 0.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        return reg * reg / (sh + l2)


def scan(g, h, c, used_bin, n_bins, full, params):
    """Best categorical split of K (node, column) histograms at once.

    ``g``, ``h``, ``c`` [K, B]: the histograms; ``used_bin``, ``n_bins``
    [K] ints; ``full`` [K] bool.  Returns (gain over the parent's [K],
    -inf where no split is allowed; direction [K]: +1 / -1; positions [K];
    the sorted bins [K, B]): the left set is ``order[k, :pos + 1]`` for +1
    and ``order[k, used_bin - 1 - pos:used_bin]`` for -1."""
    l1 = float(params.get("lambda_l1", 0.0))
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = float(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    min_gain = float(params.get("min_gain_to_split", 0.0))
    max_thr = int(params.get("max_cat_threshold", 256))
    max_group = int(params.get("max_cat_group", 64))
    k, b = g.shape
    G, H, C = g.sum(1), h.sum(1), c.sum(1)
    tot_h = H + 2 * K_EPSILON
    parent = leaf_gain(G, tot_h, l1, l2)
    smooth_h = np.minimum(float(params.get("max_cat_smooth", 100.0)),
                          np.maximum(float(params.get("cat_smooth_ratio",
                                                      0.01))
                                     * C / np.maximum(n_bins, 1),
                                     float(params.get("min_cat_smooth",
                                                      5.0))))
    smooth_g = smooth_h * G / np.where(H == 0, 1.0, H)
    scanned = np.arange(b)[None, :] < used_bin[:, None]
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = (g + smooth_g[:, None]) / (h + smooth_h[:, None])
    order = np.argsort(np.where(scanned, ratio, np.inf), axis=1,
                       kind="stable")
    rows = np.arange(k)
    best = np.full(k, -np.inf)
    best_dir = np.zeros(k, np.int64)
    best_pos = np.zeros(k, np.int64)
    steps = min(max_thr, b)
    for direction in (1, -1):
        on = np.ones(k, bool) if direction == 1 \
            else ~(full & (2 * max_thr >= n_bins))
        lg = np.zeros(k)
        lh = np.full(k, K_EPSILON)
        lc = np.zeros(k)
        group = np.zeros(k)
        rest = np.full(k, float(max_group))
        per_group = np.maximum(1.0, np.floor(C / max_group))
        for pos in range(steps):
            on &= pos < used_bin
            if not on.any():
                break
            at = pos if direction == 1 else used_bin - 1 - pos
            t = order[rows, np.clip(at, 0, b - 1)]
            lg = lg + np.where(on, g[rows, t], 0.0)
            lh = lh + np.where(on, h[rows, t], 0.0)
            lc = lc + np.where(on, c[rows, t], 0.0)
            group = group + np.where(on, c[rows, t], 0.0)
            left_ok = (lc >= min_data) & (lh >= min_hess)
            rc, rh = C - lc, tot_h - lh
            right_ok = (rc >= min_data) & (rh >= min_hess)
            on &= ~left_ok | right_ok                  # break
            take = on & left_ok & (group >= per_group)
            group = np.where(take, 0.0, group)
            rest = np.where(take, rest - 1, rest)
            per_group = np.where(take & (rest > 0),
                                 np.maximum(1.0, np.floor(
                                     rc / np.maximum(rest, 1.0))),
                                 per_group)
            gain = leaf_gain(lg, lh, l1, l2) + leaf_gain(G - lg, rh, l1, l2)
            better = (take & (gain > parent + min_gain)
                      & (gain - parent > best))
            best = np.where(better, gain - parent, best)
            best_dir = np.where(better, direction, best_dir)
            best_pos = np.where(better, pos, best_pos)
    return best, best_dir, best_pos, order


def set_gain(g, h, c, left_bins, params):
    """Gain over the parent's of the split that sends ``left_bins`` left, in
    one [B] histogram; -inf where a side is short of rows or hessian."""
    l1 = float(params.get("lambda_l1", 0.0))
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = float(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    G, H, C = g.sum(), h.sum() + 2 * K_EPSILON, c.sum()
    lg, lh, lc = (g[left_bins].sum(), h[left_bins].sum() + K_EPSILON,
                  c[left_bins].sum())
    if min(lc, C - lc) < min_data or min(lh, H - lh) < min_hess:
        return -np.inf
    return float(leaf_gain(lg, lh, l1, l2) + leaf_gain(G - lg, H - lh, l1, l2)
                 - leaf_gain(G, H, l1, l2))


class Follower:
    """One chain of scores through the given trees, at one precision, over
    ``codes`` [columns, rows] integers binned by the given ``maps``."""

    def __init__(self, codes, y, maps, params, precision="float64",
                 bins=None):
        self.codes, self.y = codes, y.astype(np.float64)
        self.maps, self.params, self.precision = maps, params, precision
        self.luts = bin_lookups(codes, maps)
        self.bins = (np.stack([lut[col].astype(np.uint16) for lut, col in
                               zip(self.luts, codes)])
                     if bins is None else bins)
        self.n_bins = np.array([int(nb) for _, nb in maps])
        full = [full_categorical(col, b2c) for col, (b2c, _) in
                zip(codes, maps)]
        self.full = np.array(full)
        self.used_bin = self.n_bins - 1 + self.full
        self.score = np.zeros(codes.shape[1])

    def left_bins(self, feature, cats):
        """The bins of a node's category set, through the column's map."""
        lut = self.luts[feature]
        cats = np.asarray(cats, np.int64)
        return np.unique(lut[cats[(cats >= 0) & (cats < len(lut))]])

    def step(self, tree):
        """Follow one tree: its own leaf outputs and counts, every node's
        own best gain and the given split's gain; then move its scores."""
        n_leaves = int(tree["num_leaves"])
        g, h = reference.gradients(self.score, self.y, self.precision)
        leaf = route(self.codes, tree)
        width = int(self.n_bins.max())
        tg, th, tc = reference.leaf_tables(self.bins, leaf, g, h, n_leaves,
                                           width)
        ng, nh, nc = (reference.node_tables(t, tree) for t in (tg, th, tc))
        nodes, cols = ng.shape[:2]
        k = nodes * cols
        best, best_dir, best_pos, order = scan(
            ng.reshape(k, width), nh.reshape(k, width), nc.reshape(k, width),
            np.tile(self.used_bin, nodes), np.tile(self.n_bins, nodes),
            np.tile(self.full, nodes), self.params)
        best = best.reshape(nodes, cols)
        given = np.array([
            set_gain(ng[i, f], nh[i, f], nc[i, f],
                     self.left_bins(f, tree["cat_codes"][i]), self.params)
            for i, f in enumerate(np.asarray(tree["split_feature"], int))])
        own_feature = best.argmax(1)
        own = []
        for i, f in enumerate(own_feature):
            kk = i * cols + f
            d, p, u = best_dir[kk], best_pos[kk], self.used_bin[f]
            sel = order[kk, :p + 1] if d == 1 else order[kk, u - 1 - p:u]
            own.append((int(f), np.sort(sel)))
        out = {
            "leaf_count": tc[:, 0, :].sum(-1).astype(np.int64),
            "leaf_value": reference.leaf_outputs(
                tg[:, 0, :].sum(-1), th[:, 0, :].sum(-1), self.params),
            "best_gain": best.max(1), "given_gain": given,
            "own_split": own, "node_hist": (ng, nh, nc),
        }
        self.score += out["leaf_value"][leaf]
        return out


def node_gaps(best, chosen):
    """Per node, the reference's best gain less the given split's, against
    that best or the median node's, whichever is larger; 1 where either is
    not finite (the reference would not split there, or not so)."""
    best, chosen = np.asarray(best), np.asarray(chosen)
    finite = best[np.isfinite(best)]
    floor = float(np.median(finite)) if len(finite) else 0.0
    return np.array([(b - c) / max(b, floor)
                     if np.isfinite(b) and np.isfinite(c) else 1.0
                     for b, c in zip(best, chosen)])


def split_gap(best, chosen):
    """The widest gap EITHER WAY between a given category set's gain and the
    reference's best at its node, by ``node_gaps``' measure (read, not
    compared).  A set worth more than the best of the published rules is as
    much a departure from them as one worth less."""
    return float(np.abs(node_gaps(best, chosen)).max(initial=0.0))


# A node's set is off the published scan where its gain lies more than this
# share of the best (or of the median node's) from it, either way.  Sound
# runs on the chip put a set apart at one node at most, by 2.6e-3 at most
# (PERF.md section 2): the program sorts float32 ratios, and two categories
# whose ratios tie within its rounding swap places, so one prefix of its
# order is no prefix of the reference's.  A fault of the scan moves dozens
# of nodes by 1e-3 to 2e-2.
SET_GAP_TOLERANCE = 1e-3


def cat_split_faults(best, chosen):
    """Nodes whose given set is off the published scan's best, either way,
    by more than ``SET_GAP_TOLERANCE`` (compared): dropping the
    ``max_cat_group`` accounting, or the smoothing, finds sets the rules
    never offer, at many nodes."""
    return int(np.sum(np.abs(node_gaps(best, chosen)) > SET_GAP_TOLERANCE))


def score_by_trees(codes, trees):
    """Sum of the given trees' own leaf values over the given rows."""
    score = np.zeros(codes.shape[1])
    for tree in trees:
        score += np.asarray(tree["leaf_value"])[route(codes, tree)]
    return score
