"""Seeded tables in the shape of UCI Covertype (Blackard and Dean, 1998;
archive.ics.uci.edu/dataset/31/covertype): 30 x 30 m cells of the Roosevelt
National Forest, 10 quantitative columns, 4 one-hot wilderness-area columns
and 40 one-hot soil-type columns (exactly one of each set a row), labelled
with one of 7 cover types.  Beside data.py and data_onehot.py; the AUC
arithmetic is data.py's.

The schema is the published one: the columns' names, units and ranges below
(UCI's covtype.info), and the classes' counts in the 581,012 rows.  How the
values spread inside their ranges is assumed (from memory; there is no
network): elevation about a mean of its wilderness area with a per-soil
offset, aspect uniform, slope and the distances gamma or exponential, the
three hillshades from aspect and slope; the wilderness area and the soil type
Zipf-skewed (exponent 1.0, rank = column).

The labels come from a fixed teacher: for each class the log of a prior,
piecewise-linear terms of elevation and of the distances to roadways, fire
points and water, and a per-class weight of the row's soil and wilderness;
plus Gumbel noise, so that a class is drawn from the softmax of the margins.
Its weights come from TASK_SEED, and its priors are solved once (on a fixed
sample) so that the classes come out at the published shares.  Blocks of
BLOCK_ROWS rows have generators of their own, keyed by (seed, block), so the
table does not depend on the number of threads.
"""

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

TASK_SEED = 20261019  # the teacher's weights: never varies
BLOCK_ROWS = 1 << 20
PUBLISHED_ROWS = 581_012
# (name, smallest, largest) of the 10 quantitative columns, as UCI lists them
NUMERIC = (("elevation", 1859, 3858), ("aspect", 0, 360), ("slope", 0, 66),
           ("horizontal_distance_to_hydrology", 0, 1397),
           ("vertical_distance_to_hydrology", -173, 601),
           ("horizontal_distance_to_roadways", 0, 7117), ("hillshade_9am", 0, 254),
           ("hillshade_noon", 0, 254), ("hillshade_3pm", 0, 254),
           ("horizontal_distance_to_fire_points", 0, 7173))
WILDERNESS, SOIL = 4, 40
COLUMNS = len(NUMERIC) + WILDERNESS + SOIL  # 54
# spruce/fir, lodgepole pine, ponderosa pine, cottonwood/willow, aspen, douglas-fir, krummholz
CLASS_COUNTS = (211_840, 283_301, 35_754, 2_747, 9_493, 17_367, 20_510)
CLASSES = len(CLASS_COUNTS)
CLASS_SHARES = np.asarray(CLASS_COUNTS, np.float64) / PUBLISHED_ROWS
# where each cover type grows best (m), and the mean elevation of each wilderness area
CLASS_ELEVATION = (3130.0, 2920.0, 2390.0, 2220.0, 2790.0, 2420.0, 3360.0)
WILDERNESS_ELEVATION = (3000.0, 3300.0, 2950.0, 2300.0)
CALIBRATION_ROWS = 1 << 17


def _zipf(card: int) -> np.ndarray:
    p = 1.0 / np.arange(1, card + 1, dtype=np.float64)
    return np.cumsum(p / p.sum())


def _features(rng, m: int):
    """(numeric float64 (m, 10), wilderness int (m,), soil int (m,))."""
    w = np.minimum(np.searchsorted(_zipf(WILDERNESS), rng.random(m), side="right"),
                   WILDERNESS - 1)
    s = np.minimum(np.searchsorted(_zipf(SOIL), rng.random(m), side="right"), SOIL - 1)
    soil_off = np.random.RandomState(TASK_SEED + 1).randn(SOIL) * 150.0
    num = np.empty((m, len(NUMERIC)), np.float64)
    num[:, 0] = np.asarray(WILDERNESS_ELEVATION)[w] + soil_off[s] + 250.0 * rng.standard_normal(m)
    aspect = rng.uniform(0.0, 360.0, m)
    slope = rng.gamma(2.5, 5.6, m)
    num[:, 1], num[:, 2] = aspect, slope
    num[:, 3] = rng.exponential(270.0, m)
    num[:, 4] = 46.0 + 58.0 * rng.standard_normal(m)
    num[:, 5] = rng.gamma(2.0, 1175.0, m)
    rad = np.deg2rad(aspect)
    num[:, 6] = 212.0 + 1.4 * slope * np.cos(rad - np.deg2rad(135.0)) + 12.0 * rng.standard_normal(m)
    num[:, 7] = 223.0 - 0.8 * slope + 10.0 * rng.standard_normal(m)
    num[:, 8] = 142.0 + 2.2 * slope * np.cos(rad - np.deg2rad(270.0)) + 20.0 * rng.standard_normal(m)
    num[:, 9] = rng.gamma(1.8, 1100.0, m)
    lo = np.asarray([c[1] for c in NUMERIC], np.float64)
    hi = np.asarray([c[2] for c in NUMERIC], np.float64)
    return np.rint(np.clip(num, lo, hi)), w, s


@functools.lru_cache(maxsize=1)
def teacher() -> dict:
    """The teacher's weights (TASK_SEED) and its log priors, solved on a fixed
    sample so that the mean class probability is each class's published
    share."""
    rng = np.random.RandomState(TASK_SEED)
    t = {"elevation_width": 120.0 + 60.0 * rng.rand(CLASSES),
         "roads": 0.6 * rng.randn(CLASSES), "fire": 0.6 * rng.randn(CLASSES),
         "water": 0.4 * rng.randn(CLASSES),
         "soil": 1.2 * rng.randn(CLASSES, SOIL), "wilderness": 0.8 * rng.randn(CLASSES, WILDERNESS),
         "bias": np.zeros(CLASSES)}
    num, w, s = _features(np.random.Generator(np.random.PCG64([TASK_SEED, 0])), CALIBRATION_ROWS)
    margin = _margin(t, num, w, s)
    for _ in range(200):  # iterative proportional fitting of the intercepts
        z = margin + t["bias"][:, None]
        p = np.exp(z - z.max(axis=0))
        p /= p.sum(axis=0)
        t["bias"] += np.log(CLASS_SHARES / p.mean(axis=1))
    return t


def _margin(t, num, w, s) -> np.ndarray:
    """(K, m) margins, the intercepts left out."""
    elev = num[:, 0]
    centre = np.asarray(CLASS_ELEVATION)[:, None]
    out = -np.abs(elev[None, :] - centre) / t["elevation_width"][:, None]
    for key, col, scale in (("roads", 5, 2000.0), ("fire", 9, 2000.0), ("water", 3, 300.0)):
        out += t[key][:, None] * np.minimum(num[:, col] / scale, 2.0)[None, :]
    return out + t["soil"][:, s] + t["wilderness"][:, w]


def make_covtype(n_rows: int, seed: int, label_seed: int = None):
    """(X float32 (n, 54), y float32 (n,) class index 0-6).  `seed` draws the
    rows; with `label_seed` the labels' noise comes from it instead."""
    t = teacher()
    X = np.zeros((n_rows, COLUMNS), np.float32)
    y = np.empty((n_rows,), np.float32)

    def fill(block: int) -> None:
        rng = np.random.Generator(np.random.PCG64([seed, block]))
        lo = block * BLOCK_ROWS
        m = min(BLOCK_ROWS, n_rows - lo)
        num, w, s = _features(rng, m)
        rows = np.arange(lo, lo + m)
        X[lo:lo + m, :len(NUMERIC)] = num
        X[rows, len(NUMERIC) + w] = 1.0
        X[rows, len(NUMERIC) + WILDERNESS + s] = 1.0
        noise = rng if label_seed is None else \
            np.random.Generator(np.random.PCG64([label_seed, block, 1]))
        z = _margin(t, num, w, s) + t["bias"][:, None] + noise.gumbel(size=(CLASSES, m))
        y[lo:lo + m] = np.argmax(z, axis=0)

    blocks = range(-(-n_rows // BLOCK_ROWS))
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, blocks))  # list(): re-raise a worker's exception
    return X, y
