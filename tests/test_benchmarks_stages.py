"""benchmarks/tests/test_stages.py, run with tier-1 (see benchmarks_suite.py)."""

from benchmarks_suite import load

globals().update(load("test_stages"))
