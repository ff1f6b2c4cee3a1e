"""Synthetic Expo-shaped data from a seed: the one-hot encoding of a few
categorical fields, handed over as a ``scipy.sparse.csr_matrix``.

The reference's Expo job is 11,000,000 flights x 700 one-hot columns made
from 8 categorical fields (month, day of month, day of week, hour of
departure, carrier, origin, destination, distance band).  No file of it is
here, so every one of these is drawn (the configuration's ``draw``, listed
under its ``assumed``):

- every row holds exactly one category of every field, so a row has as many
  stored entries as there are fields, all of them 1.0, and the columns of
  one field are mutually exclusive, which is what exclusive feature bundling
  is for;
- inside a field the categories are Zipf with exponent ``zipf_exponent``
  (rank r has weight r^-s), each field's ranks laid onto its columns by a
  permutation of the draw, so airports and carriers are heavy-tailed and the
  rarest of 297 still holds about 5,400 of 10,000,000 rows;
- a label is 1 where a weight a column, summed over the row's columns, plus
  noise is above the quantile that leaves ``positive_rate`` of the rows
  positive.

Every run makes the *same* draw (``draw_seed``) and ``--seed`` only shuffles
the rows, as in the dense cells (``harness/data.py``).  There the same rows in
another order keep the work the same.  Here they keep the data and NOT quite
the work: sums in float32 round by the order of their terms, a near-tie
between two splits falls the other way, from there the trees are others, and
on one-hot columns, where a split cuts one category off a large remainder, a
tree's row movement swings by a fifth from one tree to the next.  Eleven seeds
read ``trees_per_s`` 0.55 % apart between their quartiles on the chip, two of
them 1.7 and 4.0 % under the median, where the dense cells read 0.1-0.6 %
(PERF.md section 6, PR 34); the bundles, decided on a sample of rows, are
other ones on every seed as well.  That spread is the cell's, and is reported
as it is: a seed that left the training set alone would hide it, and the rate
of the one sequence of trees it measured would move by as much with any
change to a sum's order.
"""
import numpy as np
import scipy.sparse


def make_fields(rows, draw_seed, draw):
    """(cats [rows, fields] int32: the column of each field's category,
    labels [rows] float32) of the one draw."""
    sizes = np.asarray(draw["field_sizes"], np.int64)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    rng = np.random.Generator(np.random.PCG64(int(draw_seed)))
    cats = np.empty((rows, len(sizes)), np.int32)
    signal = np.zeros(rows, np.float32)
    for k, (size, start) in enumerate(zip(sizes, starts)):
        weight = np.arange(1, size + 1, dtype=np.float64) \
            ** -float(draw["zipf_exponent"])
        rank = np.searchsorted(np.cumsum(weight / weight.sum())[:-1],
                               rng.random(rows), side="right")
        col = rng.permutation(size)[rank]
        cats[:, k] = start + col
        signal += rng.standard_normal(size).astype(np.float32)[col]
    signal = signal / signal.std() + float(draw["noise"]) \
        * rng.standard_normal(rows, dtype=np.float32)
    cut = np.quantile(signal, 1.0 - float(draw["positive_rate"]))
    return cats, (signal > cut).astype(np.float32)


def to_csr(cats, columns):
    """One stored 1.0 a field and row; a row's columns ascend."""
    rows, fields = cats.shape
    return scipy.sparse.csr_matrix(
        (np.ones(rows * fields, np.float32), cats.reshape(-1),
         np.arange(0, rows * fields + 1, fields, dtype=np.int32)),
        shape=(rows, columns))


def make_problem(rows, columns, seed, draw_seed, draw):
    """(X csr_matrix [rows, columns] float32, labels): the one draw, its
    rows shuffled by ``seed``."""
    if int(np.sum(draw["field_sizes"])) != columns:
        raise ValueError(f"field sizes {draw['field_sizes']} do not sum to "
                         f"{columns} columns")
    cats, y = make_fields(rows, draw_seed, draw)
    order = np.random.Generator(np.random.PCG64(int(seed))).permutation(rows)
    return to_csr(cats[order], columns), y[order]
