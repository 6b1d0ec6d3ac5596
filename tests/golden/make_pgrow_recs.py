"""Rewrite tests/golden/pgrow_recs.npz from the tree this file sits in:
``python tests/golden/make_pgrow_recs.py`` (CPU, kernels interpreted, about
a minute).  tests/test_pgrow_levels.py::TestRecordsGolden compares against it byte
for byte, so run it on the PARENT of a change that claims to leave the
arithmetic alone, or on a change that moves it on purpose (and say so)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["LIGHTGBM_TPU_PGROW"] = "force"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

import numpy as np  # noqa: E402

from test_pgrow_levels import GOLDEN_CASES, chunk_records  # noqa: E402

np.savez(os.path.join(HERE, "pgrow_recs.npz"),
         **{name: chunk_records(lg, sharded) for name, lg, sharded in GOLDEN_CASES})
