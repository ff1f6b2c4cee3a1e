"""Synthetic Higgs-shaped data from a seed: the dense draw of
``bench.make_data`` (standard normals, every fourth column folded positive,
30 % zeros in the first columns, labels from a linear score plus noise),
drawn with numpy's ``Generator`` in float32 instead of ``RandomState`` in
float64 — the same distribution in a third of the time, and set-up is most of
what a run costs.  The original stays in ``bench.py`` (PERF.md, section 7).

One draw gives training and held-out rows, so both are labelled by the same
weight vector.  Every run makes the *same* draw (the configuration's
``draw_seed``) and ``--seed`` only shuffles the rows, training and held-out
apart: with a draw of its own each seed grew trees of another shape, and the
same code read 3.4 % apart from seed to seed but 0.02 % apart on one seed (my
chip runs, PR 24).  The same rows in another order keep the work the same.
``seed`` is any whole number that is not negative.
"""
import numpy as np


def make_data(n, f=28, seed=42):
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    X = rng.standard_normal((n, f), dtype=np.float32)
    X[:, ::4] = np.abs(X[:, ::4]) + np.float32(0.1)
    k = max(1, f // 7)
    X[:, :k][rng.random((n, k), dtype=np.float32) < 0.3] = 0.0
    w = (rng.standard_normal(f) * 0.5).astype(np.float32)
    y = ((X @ w + rng.standard_normal(n, dtype=np.float32)) > 0) \
        .astype(np.float32)
    return X, y


def make_problem(rows, valid_rows, columns, seed, draw_seed):
    """(X, y, Xv, yv): of the one draw the first ``rows`` train and the next
    ``valid_rows`` are held out, each shuffled by ``seed``."""
    X, y = make_data(rows + valid_rows, columns, draw_seed)
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    train = rng.permutation(rows)
    held = rows + rng.permutation(valid_rows)
    return X[train], y[train], X[held], y[held]
