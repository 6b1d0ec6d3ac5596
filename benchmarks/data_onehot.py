"""Seeded one-hot tables in the shape of the reference's Expo experiment: the
Data Expo 2009 airline on-time table with its categorical fields one-hot
encoded, 700 indicator columns of which every row sets 8.  Beside data.py,
which draws dense normals; the AUC arithmetic is data.py's.

The schema is assumed (the real table's, from memory; there is no network):
month 12, day of month 31, day of week 7, departure hour 24, carrier 20,
origin 298, destination 298, distance decile 10.  Month, day of week, hour and
decile are uniform, the day of month follows the calendar (31 in 7 months of
12), and carrier, origin and destination are Zipf (hubs), rank = category.

The table comes back as scipy CSR, value 1.0, exactly one entry a field, a
row's indices ascending, and is never a dense array.  The task is fixed: a
logistic margin of per-category weights plus two pairwise terms (origin x
hour, carrier x month), scaled by its analytic standard deviation; weights
from TASK_SEED.  Blocks of BLOCK_ROWS rows have generators of their own, keyed
by (seed, block), so the table does not depend on the number of threads.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse

TASK_SEED = 20261003  # the weights of the margin: never varies
BLOCK_ROWS = 1 << 20
FIELD_NAMES = ("month", "day_of_month", "day_of_week", "dep_hour", "carrier", "origin",
               "dest", "distance_decile")
ZIPF_FIELDS = ("carrier", "origin", "dest")
PAIRS = (("origin", "dep_hour"), ("carrier", "month"))
PAIR_SCALE = 0.5  # a pairwise table's weights against a field's


def field_probs(fields, zipf_exponent: float) -> list:
    """One probability vector a field."""
    if len(fields) != len(FIELD_NAMES):
        raise ValueError(f"the schema has {len(FIELD_NAMES)} fields, got {len(fields)}")
    out = []
    for name, card in zip(FIELD_NAMES, fields):
        if name in ZIPF_FIELDS:
            p = 1.0 / np.arange(1, card + 1, dtype=np.float64) ** zipf_exponent
        elif name == "day_of_month":  # days 29, 30, 31 exist in 11, 11 and 7 months
            p = np.asarray([12.0] * min(card, 28) + [11.0, 11.0, 7.0][:max(card - 28, 0)])
        else:
            p = np.ones(card)
        out.append(p / p.sum())
    return out


def task_weights(fields) -> dict:
    """{"fields": [w_f (card,)], "pairs": [w (card_a, card_b)]} from TASK_SEED."""
    rng = np.random.RandomState(TASK_SEED)
    at = {name: i for i, name in enumerate(FIELD_NAMES)}
    return {"fields": [rng.randn(card) for card in fields],
            "pairs": [PAIR_SCALE * rng.randn(fields[at[a]], fields[at[b]]) for a, b in PAIRS]}


def _margin_std(weights, probs) -> float:
    """Standard deviation of the margin over independent fields (a pairwise
    term's covariance with its fields' own terms is left out: the scale only
    has to be fixed, and near one)."""
    at = {name: i for i, name in enumerate(FIELD_NAMES)}
    var = sum(float(p @ w ** 2 - (p @ w) ** 2) for w, p in zip(weights["fields"], probs))
    for (a, b), w in zip(PAIRS, weights["pairs"]):
        pab = np.outer(probs[at[a]], probs[at[b]])
        var += float((pab * w ** 2).sum() - (pab * w).sum() ** 2)
    return float(np.sqrt(var))


def make_onehot(n_rows: int, seed: int, fields, zipf_exponent: float = 1.0,
                label_seed: int = None):
    """(X scipy CSR float32 (n, sum(fields)), y float32 (n,)).  `seed` draws
    the rows; with `label_seed` the labels' coin flips come from it instead:
    another sample of the task over the same rows (data.py's contract)."""
    fields = [int(c) for c in fields]
    k, width = len(fields), int(sum(fields))
    probs = field_probs(fields, zipf_exponent)
    cdfs = [np.cumsum(p) for p in probs]
    offsets = np.cumsum([0] + fields[:-1]).astype(np.int32)
    weights = task_weights(fields)
    scale = _margin_std(weights, probs)
    at = {name: i for i, name in enumerate(FIELD_NAMES)}
    indices = np.empty((n_rows, k), np.int32)
    y = np.empty((n_rows,), np.float32)

    def fill(block: int) -> None:
        rng = np.random.Generator(np.random.PCG64([seed, block]))
        lo = block * BLOCK_ROWS
        m = min(BLOCK_ROWS, n_rows - lo)
        cats = [np.minimum(np.searchsorted(cdf, rng.random(m), side="right"), card - 1)
                for cdf, card in zip(cdfs, fields)]
        margin = sum(w[c] for w, c in zip(weights["fields"], cats))
        for (a, b), w in zip(PAIRS, weights["pairs"]):
            margin = margin + w[cats[at[a]], cats[at[b]]]
        prob = 1.0 / (1.0 + np.exp(-margin / scale))
        coin = rng if label_seed is None else \
            np.random.Generator(np.random.PCG64([label_seed, block, 1]))
        y[lo:lo + m] = coin.random(m, dtype=np.float32) < prob
        indices[lo:lo + m] = np.stack(cats, axis=1) + offsets

    blocks = range(-(-n_rows // BLOCK_ROWS))
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, blocks))  # list(): re-raise a worker's exception
    X = scipy.sparse.csr_matrix(
        (np.ones(n_rows * k, np.float32), indices.reshape(-1),
         np.arange(0, n_rows * k + 1, k, dtype=np.int64 if n_rows * k >= 2**31 else np.int32)),
        shape=(n_rows, width))
    X.has_sorted_indices = True  # a field's columns lie after the previous field's
    X.has_canonical_format = True
    return X, y
