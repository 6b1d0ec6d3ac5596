"""The harness's own tests (``benchmarks/tests``) as members of tier-1.

The driver's command is ``pytest tests/``, and ``benchmarks/`` may only be
changed by a ``benchmark`` PR, so each ``tests/test_benchmarks_<file>.py``
re-exports one file of ``benchmarks/tests`` from here.  Loaded by path under
a name of its own, not imported: ``tests/test_data.py`` and
``benchmarks/tests/test_data.py`` share a basename and neither directory is
a package."""

import importlib.util
import os
import sys

BENCH_TESTS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "benchmarks", "tests")


def _by_path(basename: str):
    name = f"benchmarks_tests_{basename}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH_TESTS, basename + ".py"))
        sys.modules[name] = module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    return sys.modules[name]


def load(basename: str) -> dict:
    """The public names of ``benchmarks/tests/<basename>.py`` (tests, fixtures,
    what they close over), for a thin module's ``globals().update``.  Their
    ``conftest.py`` goes first, as pytest would run it: it puts the repo root
    and ``benchmarks/`` on ``sys.path``, and whatever fixtures it comes to hold
    belong to every file."""
    names = {}
    for part in ("conftest", basename):
        names.update((k, v) for k, v in vars(_by_path(part)).items() if not k.startswith("_"))
    return names
