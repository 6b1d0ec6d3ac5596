"""Seeded inputs and the AUC arithmetic: the benchmark's own yardstick.

The task is `bench.py`'s synthetic Higgs-shaped one (28 standard-normal
features, the first eight informative, a mildly non-linear margin), copied
here so that no later PR to the program can move it.  Two things differ from
the original, both for set-up time at 10M-40M rows: the draws come from
`numpy.random.Generator` in float32 blocks (the original's
`RandomState.randn` is single-threaded and takes 2.4 s per million rows on
the sandbox CPU), and the margin is scaled by its analytic standard deviation
instead of the sample's, so a block needs nothing from the others.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

TASK_SEED = 20260730  # the informative weights: never varies
N_INFORM = 8
BLOCK_ROWS = 1 << 20


def task_weights():
    return np.random.RandomState(TASK_SEED).randn(N_INFORM)


def _margin_std(w) -> float:
    # Var[w.x] = |w|^2, Var[0.5 x0 x1] = 0.25, Var[0.3 x2^2] = 0.09 * 2; the
    # cross terms are odd moments of independent normals and vanish
    return float(np.sqrt(np.sum(w * w) + 0.25 + 0.18))


def make_higgs_shaped(n_rows: int, seed: int, n_features: int = 28, label_seed: int = None):
    """(X float32 (n, F), y float32 (n,)).  `seed` draws the rows only.

    Each block of BLOCK_ROWS rows has a generator of its own, keyed by
    (seed, block), so the blocks are filled by a few threads and the table
    does not depend on how many (numpy releases the interpreter lock).

    With `label_seed` the features are still `seed`'s and only the labels'
    coin flips are drawn anew: another sample of the same task over the same
    feature rows, for a table whose binned features are cached."""
    w = task_weights().astype(np.float32)
    scale = np.float32(_margin_std(w))
    X = np.empty((n_rows, n_features), np.float32)
    y = np.empty((n_rows,), np.float32)

    def fill(block: int) -> None:
        rng = np.random.Generator(np.random.PCG64([seed, block]))
        lo = block * BLOCK_ROWS
        xb = X[lo:lo + BLOCK_ROWS]
        rng.standard_normal(out=xb, dtype=np.float32)
        margin = xb[:, :N_INFORM] @ w + 0.5 * xb[:, 0] * xb[:, 1] - 0.3 * xb[:, 2] ** 2
        prob = 1.0 / (1.0 + np.exp(-margin / scale))
        coin = rng if label_seed is None else \
            np.random.Generator(np.random.PCG64([label_seed, block, 1]))
        y[lo:lo + BLOCK_ROWS] = coin.random(len(xb), dtype=np.float32) < prob

    blocks = range(-(-n_rows // BLOCK_ROWS))
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, blocks))  # list(): re-raise a worker's exception
    return X, y


def auc(y, score) -> float:
    """Area under the ROC curve by average ranks (ties share a rank)."""
    score = np.asarray(score, np.float64)
    order = np.argsort(score, kind="mergesort")
    s = score[order]
    pos = np.asarray(y)[order] > 0
    edges = np.flatnonzero(np.r_[True, s[1:] != s[:-1], True])
    avg_rank = (edges[:-1] + edges[1:] + 1) / 2.0  # 1-based, per tie group
    ranks = np.repeat(avg_rank, np.diff(edges))
    n_pos = int(pos.sum())
    n_neg = len(s) - n_pos
    if n_pos == 0 or n_neg == 0:
        raise ValueError("AUC needs both classes")
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg))
