"""Synthetic MS LTR-shaped data from a seed: the dense columns of
``data.make_data`` in query groups, with graded labels.

MSLR-WEB30K's training folds hold 2,270,296 documents in about 18,900
queries: 120 documents a query on average, the shortest 1, the longest
1,251, labels 0 to 4 with half the documents at 0.  No file of it is here,
so every one of these is drawn (the configuration's ``draw``, listed under
its ``assumed``):

- query lengths are a lognormal draw scaled to the mean, cut to
  [1, ``longest_query``] and brought to sum to ``rows`` exactly; the
  longest is put at ``longest_query`` and the shortest at 1;
- a document's relevance is a linear score of its columns, plus an offset
  of its query, plus noise; labels 0 to 4 cut it at fixed quantiles of all
  documents (``label_quantiles``), so a query with a low offset has no
  relevant document at all, as real ones do.

Every run makes the *same* draw (``draw_seed``); ``--seed`` shuffles whole
queries and the documents inside each, so the work stays the same (PERF.md
section 2).  ``seed`` is any whole number that is not negative.
"""
import numpy as np

from . import data


def query_lengths(rows, queries, longest, sigma, rng):
    """``queries`` whole numbers in [1, ``longest``] that sum to ``rows``."""
    if not queries <= rows <= queries * longest:
        raise ValueError(f"{rows} rows do not fit {queries} queries of 1 to "
                         f"{longest}")
    raw = rng.lognormal(0.0, sigma, queries)
    sizes = np.clip((raw * rows / raw.sum()).astype(np.int64), 1, longest)
    free = np.ones(queries, bool)
    if queries > 2 and rows >= longest + queries - 1:
        ends = [np.argmax(raw), np.argmin(raw)]
        sizes[ends], free[ends] = (longest, 1), False
    while True:
        short = rows - int(sizes.sum())
        if short == 0:
            return sizes
        # one document more (or fewer) in as many queries as are missing,
        # the longest and the shortest left as they are
        room = np.flatnonzero(free & ((sizes < longest) if short > 0
                                      else (sizes > 1)))
        if not len(room):
            raise ValueError(f"no room for {short} more rows")
        sizes[rng.permutation(room)[:abs(short)]] += np.sign(short)


def make_queries(rows, columns, draw_seed, draw):
    """(X [rows, columns] float32, labels [rows] float32 in 0..4,
    sizes [queries] int64) of the one draw, query after query."""
    X, _ = data.make_data(rows, columns, draw_seed)
    rng = np.random.Generator(np.random.PCG64([int(draw_seed), 1]))
    sizes = query_lengths(rows, int(draw["queries"]),
                          int(draw["longest_query"]),
                          float(draw["length_sigma"]), rng)
    signal = X @ rng.standard_normal(columns).astype(np.float32)
    relevance = (signal / signal.std()
                 + float(draw["query_offset"]) * np.repeat(
                     rng.standard_normal(len(sizes), dtype=np.float32), sizes)
                 + float(draw["noise"])
                 * rng.standard_normal(rows, dtype=np.float32))
    cuts = np.quantile(relevance, draw["label_quantiles"])
    return X, np.searchsorted(cuts, relevance).astype(np.float32), sizes


def shuffle_queries(X, y, sizes, seed):
    """The same queries in another order, and each one's documents in
    another order inside it."""
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    place = rng.permutation(len(sizes))          # query q goes to place[q]
    order = np.lexsort((rng.random(len(y), dtype=np.float32),
                        np.repeat(place, sizes)))
    return X[order], y[order], sizes[np.argsort(place)]


def make_problem(rows, columns, seed, draw_seed, draw):
    """(X, labels, sizes): the one draw, shuffled by ``seed``."""
    return shuffle_queries(*make_queries(rows, columns, draw_seed, draw),
                           seed)
