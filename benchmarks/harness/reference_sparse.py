"""The plain reference's sparse sibling: ``reference.Follower`` for a matrix
handed over as ``scipy.sparse``, in numpy float64 on the STORED ENTRIES.  It
imports nothing of the program; split gains, leaf outputs and gradients are
``reference.py``'s own functions, unedited.

A dense ``[columns, rows]`` copy of 700 x 10,000,000 would be 28 GB and a
pass over its cells minutes, so nothing here is of that shape.  A row that
stores nothing in a column has the value 0 there:

- *routing*: a node's rows go by the tree's real threshold on the column's
  value; the rows that store nothing all go where 0 goes, and only the
  column's stored entries are looked at;
- *a column's histogram*: the sums over its stored entries that lie in the
  node, bin by bin, and for the bin that 0 falls in the node's totals less
  those sums.  (The program reconstructs that bin the same way, in float32
  from its own sums; here both terms are float64 sums over the raw rows.)

**Bundles are taken as given, as thresholds are.**  The program packs
mutually exclusive columns into one physical column (exclusive feature
bundling) from a sample of the rows; on all rows two columns of a bundle do
meet, and then the column that comes LATER in the bundle's list stays and
the earlier one reads as 0 in that row (``dataset.cpp``'s push order: a
later ``PushData`` overwrites; the reference program trains on the same
overwritten bins).  Which columns share a bundle is a choice, like a split:
two sound programs may bundle differently.  What follows from the choice is
not: from the given bundle lists this file works out for itself, on every
row, which stored entries are overwritten and by which column
(``Columns.conflicts``), and follows the trees on the values that remain.
At each of those rows the bundle column THE PROGRAM TRAINED ON has to hold
the winner's slot (``conflict_slots``, compared in ``check_sparse.py`` with
the program's binned matrix: a count of the overwritten entries would be the
same whichever of two columns stays, and a count the program keeps of itself
is no evidence of what it wrote).  It also checks that every bundle fits the
width the configuration states and that no two columns share a slot
(``bundle_faults``).

``precision`` rounds the gradients and hessians before they are summed, as
in ``reference.py``: ``float64`` is the reference, ``bfloat16`` the control.
"""
import copy

import numpy as np

from . import reference


def bundle_faults(bundles, bounds, max_bin, offsets=None):
    """Bundles that cannot be: more slots than ``max_bin + 1`` (one for "every
    column at 0", then each column's bins but the one 0 falls in), a column
    in two bundles, one the data does not have, or, where the columns' first
    slots are given (``offsets``, a list a bundle), two columns on one slot
    or a column outside slots 1 to ``max_bin``.  0 in a sound run."""
    bad, seen = 0, set()
    for k, bundle in enumerate(bundles):
        known = [j for j in bundle if 0 <= j < len(bounds)]
        slots = 1 + sum(len(bounds[j]) - 1 for j in known)
        fault = (len(known) != len(bundle) or any(j in seen for j in bundle)
                 or len(set(bundle)) != len(bundle)
                 or (len(bundle) > 1 and slots > max_bin + 1))
        if offsets is not None and len(bundle) > 1 and not fault:
            taken = np.zeros(max_bin + 2, np.int64)
            for j, off in zip(bundle, offsets[k]):
                lo, hi = int(off), int(off) + len(bounds[j]) - 1
                fault |= not 1 <= lo <= hi <= max_bin + 1
                taken[max(lo, 0):max(hi, 0)] += 1
            fault |= bool((taken > 1).any())
        bad += fault
        seen.update(bundle)
    return bad


class Columns:
    """The matrix by column: for each column the rows that store an entry
    outside the bin of 0, ascending, with the value and its bin; what a later
    column of the same bundle overwrites is taken out, counted a column
    (``overwritten``) and kept in ``conflicts``: for every overwritten entry
    its ``row``, the place of its ``bundle`` in the given list, and the
    ``column`` and ``bin`` of the entry that stays in that row."""

    def __init__(self, X, bounds, bundles):
        csc = X.tocsc()
        csc.sort_indices()
        self.n, self.columns = csc.shape
        values = csc.data.astype(np.float64)
        if np.isnan(values).any():
            raise ValueError("the sparse reference follows no missing values")
        col_of = np.repeat(np.arange(self.columns, dtype=np.int32),
                           np.diff(csc.indptr))
        self.n_bins_of = [len(b) for b in bounds]
        bins = np.empty(len(values), np.int32)
        self.zero_bin = np.empty(self.columns, np.int32)
        for j in range(self.columns):
            edges = np.asarray(bounds[j], np.float64)[:-1]
            lo, hi = csc.indptr[j], csc.indptr[j + 1]
            bins[lo:hi] = np.searchsorted(edges, values[lo:hi], side="left")
            self.zero_bin[j] = np.searchsorted(edges, 0.0, side="left")
        live = bins != self.zero_bin[col_of]      # the rest read as 0 anyway
        written = np.bincount(col_of[live], minlength=self.columns)
        lost = {"row": [], "bundle": [], "column": [], "bin": []}
        for k, bundle in enumerate(bundles):
            if len(bundle) < 2:
                continue
            last = np.full(self.n, -1, np.int32)   # the row's last writer
            left = np.zeros(self.n, np.int32)      # and the bin it leaves
            spans = [(csc.indptr[j], csc.indptr[j + 1]) for j in bundle]
            for place, (lo, hi) in enumerate(spans):
                at = csc.indices[lo:hi][live[lo:hi]]
                last[at], left[at] = place, bins[lo:hi][live[lo:hi]]
            for place, (lo, hi) in enumerate(spans):
                at = csc.indices[lo:hi]
                gone = at[live[lo:hi] & (last[at] != place)]
                live[lo:hi] &= last[at] == place
                lost["row"].append(gone)
                lost["bundle"].append(np.full(len(gone), k, np.int64))
                lost["column"].append(np.asarray(bundle)[last[gone]])
                lost["bin"].append(left[gone])
        self.conflicts = {key: np.concatenate(v).astype(np.int64) if v
                          else np.zeros(0, np.int64)
                          for key, v in lost.items()}
        self.overwritten = written - np.bincount(col_of[live],
                                                 minlength=self.columns)
        self._keep(csc.indices[live].astype(np.int32), values[live],
                   bins[live], col_of[live])

    def _keep(self, rows, values, bins, col_of):
        self.rows, self.values, self.bins, self.col_of = (rows, values, bins,
                                                          col_of)
        self.ptr = np.concatenate([[0], np.cumsum(np.bincount(
            col_of, minlength=self.columns))])

    def column(self, j):
        lo, hi = self.ptr[j], self.ptr[j + 1]
        return self.rows[lo:hi], self.values[lo:hi]

    def take(self, rows):
        """The same columns over the given rows alone, renumbered."""
        place = np.full(self.n, -1, np.int32)
        place[rows] = np.arange(len(rows), dtype=np.int32)
        new = place[self.rows]
        keep = new >= 0
        out = copy.copy(self)
        out.n, out.overwritten, out.conflicts = len(rows), None, None
        out._keep(new[keep], self.values[keep], self.bins[keep],
                  self.col_of[keep])
        return out


def conflict_slots(cols, bundles, offsets):
    """For every overwritten entry (``cols.conflicts``), the slot that its
    bundle's column has to hold in its row: the staying column's first slot
    (``offsets``, a list a bundle, given as the bundles are) plus the rank
    of the staying entry's bin among that column's bins outside the bin of
    0."""
    first = np.zeros(cols.columns, np.int64)
    for bundle, offs in zip(bundles, offsets):
        first[list(bundle)] = offs
    stays, b = cols.conflicts["column"], cols.conflicts["bin"]
    return first[stays] + b - (b > cols.zero_bin[stays])


def route(cols, tree):
    """Leaf of every row, by the tree's real thresholds on the raw values
    (left when value <= threshold; a row that stores nothing has 0).
    Internal node i is the i-th split, as in ``reference.route``."""
    leaf = np.zeros(cols.n, np.int32)
    pending = {0: np.arange(cols.n, dtype=np.int32)}
    apart = np.zeros(cols.n, bool)      # rows that part from the zeros' way
    for i in range(len(tree["left_child"])):
        rows = pending.pop(i)
        threshold = tree["threshold"][i]
        at, values = cols.column(int(tree["split_feature"][i]))
        zero_left = 0.0 <= threshold
        other = at[(values <= threshold) != zero_left]
        apart[other] = True
        left = apart[rows] != zero_left
        apart[other] = False
        for child, sel in ((int(tree["left_child"][i]), rows[left]),
                           (int(tree["right_child"][i]), rows[~left])):
            if child < 0:
                leaf[sel] = ~child
            else:
                pending[child] = sel
    return leaf


def leaf_tables(cols, leaf, g, h, n_leaves, n_bins):
    """[leaves, columns, bins] sums of g, of h and row counts: the stored
    entries bin by bin, and in the bin of 0 the leaf's totals less them."""
    key = ((leaf[cols.rows].astype(np.int64) * cols.columns + cols.col_of)
           * n_bins + cols.bins)
    shape = (n_leaves, cols.columns, n_bins)
    size = n_leaves * cols.columns * n_bins
    at_zero = (np.arange(n_leaves)[:, None], np.arange(cols.columns)[None, :],
               cols.zero_bin[None, :])
    tables = []
    for weight in (g, h, None):
        stored = np.bincount(key, None if weight is None
                             else weight[cols.rows], size) \
            .astype(np.float64).reshape(shape)
        total = np.bincount(leaf, weight, n_leaves)
        stored[at_zero] = total[:, None] - stored.sum(-1)
        tables.append(stored)
    return tables


class Follower:
    """One chain of scores through the given trees, at one precision:
    ``reference.Follower`` on :class:`Columns` (training rows only)."""

    def __init__(self, cols, y, params, precision="float64"):
        self.cols, self.y, self.params = cols, y.astype(np.float64), params
        self.precision = precision
        self.n_bins = max(cols.n_bins_of)
        self.score = np.zeros(cols.n)            # binary logloss starts at 0

    def step(self, tree):
        """Follow one tree: its own leaf outputs and counts and the gains of
        every candidate at every node; then move the scores by its own
        outputs."""
        n_leaves = int(tree["num_leaves"])
        g, h = reference.gradients(self.score, self.y, self.precision)
        leaf = route(self.cols, tree)
        tg, th, tc = leaf_tables(self.cols, leaf, g, h, n_leaves, self.n_bins)
        gains = reference.split_gains(
            reference.node_tables(tg, tree), reference.node_tables(th, tree),
            reference.node_tables(tc, tree), self.cols.n_bins_of, self.params)
        out = {
            "leaf_count": np.rint(tc[:, 0, :].sum(-1)).astype(np.int64),
            "leaf_value": reference.leaf_outputs(
                tg[:, 0, :].sum(-1), th[:, 0, :].sum(-1), self.params),
            "gains": gains, "metrics": {},
        }
        self.score += out["leaf_value"][leaf]
        return out


def score_by_trees(cols, trees):
    """Sum of the given trees' own leaf values over the rows of ``cols``."""
    score = np.zeros(cols.n)
    for tree in trees:
        score += np.asarray(tree["leaf_value"])[route(cols, tree)]
    return score
