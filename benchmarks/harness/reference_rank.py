"""The plain reference's ranking objective: LambdaRank with NDCG, in numpy
and float64, one query after another, written from the published algorithm
(LightGBM, ``rank_objective.hpp``, ``GetGradientsForOneQuery``; ``dcg_calculator.cpp``
for gains, discounts and the maximal DCG).  It imports nothing of the program.

For a query, documents are ranked by score, best first.  Every pair of a
document with the higher label and one with the lower gives

    delta = (gain_high - gain_low) * |discount_high - discount_low| / maxDCG
    delta /= 0.01 + |s_high - s_low|    where the query's best and worst scores differ
    p = 2 / (1 + exp(2 sigma (s_high - s_low)))
    lambda_high -= delta p;  lambda_low += delta p;  both hessians += 2 delta p (2 - p)

with ``gain = label_gain[label]``, ``discount = 1 / log2(2 + rank)`` and
``maxDCG`` the DCG of the labels in descending order, cut at
``max_position``.  Departures, each stated:

- the reference program reads ``p`` from a table of 1,048,576 entries over
  [-50 / sigma / 2, 50 / sigma / 2]; this computes the exponential itself;
- its ``std::sort`` puts documents of equal score in no stated order; here,
  as in the program, they stay in their order in the query (a stable sort);
- a rank is discrete, as a split is: two sound chains of scores that
  differ in the seventh digit put two leaves of nearly equal output in
  opposite orders, and every document of those leaves changes rank.  So,
  as the Follower follows the given trees' splits, the ranking follows the
  given trees' outputs: documents are ranked on ``ranked_on``, the sums of
  the given trees' own leaf values over the reference's routing, rounded
  to float32, the precision the program holds its scores in; every
  gradient is computed in float64 from the reference's own float64 sums
  of its own outputs.  The given outputs are themselves compared
  (``leaf_gap_median``) and so is the program's state against their sum
  (``score_gap``).  ``Gradients.rank_moves`` counts the documents whose
  rank differs between the two chains.

``precision`` rounds the gradients and hessians as ``reference.round_to``
does: ``float64`` is the reference, ``bfloat16`` the control.
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import reference

# what the arithmetic above costs a pair slot, operation by operation:
# s difference 1, label comparison 1, gain gap 1, discount gap and its
# magnitude 2, the two products to delta 2, |ds| + 0.01 and the division 3,
# the exponential's argument, the exponential, 1 + and the division 4,
# lambda (product, sign) 2, hessian (2 - p, three products) 4, the two
# selections 2, a row sum and a column sum each of lambda and hessian 4
PAIR_OPS = 26
THREADS = min(8, os.cpu_count() or 1)    # queries are independent
ROW_BYTES = 16                # score and label read, g and h written


def default_label_gain(size=31):
    return [float((1 << i) - 1) for i in range(size)]


def discounts(n):
    return 1.0 / np.log2(2.0 + np.arange(n))


def inverse_max_dcg(labels, gains, k):
    """1 / DCG of the best ordering cut at ``k``; 0 where that DCG is 0."""
    top = np.sort(labels)[::-1][:k].astype(np.int64)
    best = float(np.sum(gains[top] * discounts(len(top))))
    return 1.0 / best if best > 0 else 0.0


def query_gradients(s, ranked_on, labels, gains, inv_max_dcg, sigma):
    """(lambdas, hessians) of one query's documents, in the query's order.
    ``ranked_on`` are the scores the ranking is read from.  A block of
    [documents that can be a pair's high one, all documents]: a document of
    the query's lowest label is no pair's high one."""
    g, h = np.zeros(len(s)), np.zeros(len(s))
    if inv_max_dcg == 0.0 or len(s) < 2:
        return g, h                          # no relevant document: all 0
    order = np.argsort(-ranked_on, kind="stable")
    ss, sy = s[order], labels[order].astype(np.int64)
    disc = discounts(len(s))
    hi = np.flatnonzero(sy > sy.min())
    ds = ss[hi, None] - ss[None, :]                      # high - low
    pair = sy[hi, None] > sy[None, :]
    delta = ((gains[sy][hi, None] - gains[sy][None, :])
             * np.abs(disc[hi, None] - disc[None, :]) * inv_max_dcg)
    if ranked_on[order[0]] != ranked_on[order[-1]]:
        delta /= 0.01 + np.abs(ds)
    p = 2.0 / (1.0 + np.exp(2.0 * sigma * ds))
    lam = np.where(pair, -delta * p, 0.0)
    hes = np.where(pair, 2.0 * delta * p * (2.0 - p), 0.0)
    gs, hs = -lam.sum(0), hes.sum(0)                     # as the low one
    gs[hi] += lam.sum(1)                                 # as the high one
    hs[hi] += hes.sum(1)
    g[order], h[order] = gs, hs
    return g, h


def pair_slots(sizes):
    """Pair evaluations a pass over all queries makes: the sum of L^2."""
    return int(np.sum(np.asarray(sizes, np.int64) ** 2))


def objective_work(sizes):
    """{"ops", "bytes", "pairs"} of one tree's gradients, from the data's
    query lengths alone: no bucket, chunk or padding."""
    pairs = pair_slots(sizes)
    return {"pairs": pairs, "ops": pairs * PAIR_OPS,
            "bytes": int(np.sum(sizes)) * ROW_BYTES}


class Gradients:
    """``gradients(score, y, precision)`` for ``reference.Follower`` over
    the queries given by ``sizes``, in the data's order.  ``ranked_on[k]``
    are the scores that the k-th gradient pass of a chain ranks on (a chain
    is a precision: the control's Follower makes its own passes); without
    them a pass ranks on its own scores."""

    def __init__(self, sizes, params, ranked_on=None):
        self.bounds = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
        self.sigma = float(params.get("sigmoid", 1.0))
        self.max_position = int(params.get("max_position", 20))
        self.gains = np.asarray(params.get("label_gain")
                                or default_label_gain(), np.float64)
        self.inv_max_dcg = None
        self.ranked_on, self.passes = ranked_on, {}
        self.rank_moves = []            # per call: documents ranked
        #                                 otherwise than by their own score

    def __call__(self, score, y, precision="float64"):
        b = self.bounds
        if self.inv_max_dcg is None:
            self.inv_max_dcg = [
                inverse_max_dcg(y[b[q]:b[q + 1]], self.gains,
                                self.max_position) for q in range(len(b) - 1)]
        k = self.passes[precision] = self.passes.get(precision, -1) + 1
        ranked_on = (score if self.ranked_on is None
                     else self.ranked_on[k]).astype(np.float32)
        g, h = np.empty(len(score)), np.empty(len(score))
        moved = np.zeros(len(b) - 1, np.int64)

        def one(q):
            a, z = b[q], b[q + 1]
            g[a:z], h[a:z] = query_gradients(
                score[a:z], ranked_on[a:z], y[a:z], self.gains,
                self.inv_max_dcg[q], self.sigma)
            moved[q] = np.sum(np.argsort(-score[a:z], kind="stable")
                              != np.argsort(-ranked_on[a:z], kind="stable"))
        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(one, range(len(b) - 1)))
        self.rank_moves.append(int(moved.sum()))
        return (reference.round_to(g, precision),
                reference.round_to(h, precision))
