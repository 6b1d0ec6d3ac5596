"""Rewrite tests/golden/split_search.npz: ``python
tests/golden/make_split_search.py [root]`` (CPU, seconds), with the
``lightgbm_tpu`` of the tree at ``root`` (this one by default) and the cases of
THIS tree's tests/test_ops.py.  ``best_split_per_feature(hist, ...)`` takes an
(F, B, 3) histogram on either side of PR 40, so the file on record was written
with ``root`` a checkout of its parent, 9f34d0a, whose search read
``hist[..., k]`` out of prefix sums along axis 1:
tests/test_ops.py::TestSplitEntries holds both of this tree's entries to it
byte for byte."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 else os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.dirname(HERE)]

import numpy as np  # noqa: E402

import lightgbm_tpu  # noqa: E402
from test_ops import (SPLIT_CASES, SPLIT_FIELDS, SPLIT_MODES,  # noqa: E402
                      split_case_on_the_histogram_entry)

assert os.path.dirname(os.path.dirname(os.path.abspath(lightgbm_tpu.__file__))) == ROOT
np.savez(os.path.join(HERE, "split_search.npz"), **{
    f"{case}-{int(um)}-{int(hc)}-{field}": value
    for case in SPLIT_CASES for um, hc in SPLIT_MODES
    for field, value in zip(SPLIT_FIELDS, split_case_on_the_histogram_entry(case, um, hc))})
